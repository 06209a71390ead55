"""A serving cell whose reference check holds the share of served tokens
within the margin to a floor, not every token (``serve_app_routed.py`` says
why): ``kinds/serve.py``
unchanged — its window, counters, checks and ``ctx`` — with
``serve_routed_child`` as the process that starts the server. A kind of
its own for the reason ``serve_wide.py`` is one: a PR that adds a
configuration may not edit the files the benchmark has."""
from __future__ import annotations

from unittest import mock

from . import serve_wide

CHILD = "benchmarks.kinds.serve_routed_child"


def run(cell, a, t_process_start: float, log) -> dict:
    with mock.patch.object(serve_wide, "CHILD", CHILD):
        return serve_wide.run(cell, a, t_process_start, log)
