"""A serving cell of a model whose vocabulary ``benchmarks/tokenizer.py``
cannot hold (more than 35,328 ids): ``kinds/serve.py`` unchanged — its
window, counters, checks and ``ctx`` — with ``serve_wide_child`` as the
process that starts the server. A kind of its own only because a PR that
adds a configuration may not edit the files the benchmark has; the
``benchmark`` PR that widens ``OneCharTokenizer`` deletes both files and
``tokenizer_wide.py`` and sets the configuration's ``kind`` to ``serve``
(PERF.md §7)."""
from __future__ import annotations

from unittest import mock

from . import serve

CHILD = "benchmarks.kinds.serve_wide_child"


def run(cell, a, t_process_start: float, log) -> dict:
    spawn = serve.Child

    def child(_module, *args, **kwargs):
        return spawn(CHILD, *args, **kwargs)
    with mock.patch.object(serve, "Child", child):
        return serve.run(cell, a, t_process_start, log)
