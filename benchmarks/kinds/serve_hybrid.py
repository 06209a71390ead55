"""A serving cell of a model with recurrent-state layers
(``benchmarks/models/qwen3_next.py``): ``kinds/serve.py`` unchanged — its
window, counters, checks and ``ctx`` — as ``kinds/serve_routed.py`` runs it
(through ``serve_wide.run``, the child named by ``serve_wide.CHILD``), with
``serve_hybrid_child`` as the process that starts the server (a wide
vocabulary, the reference check by share, as ``serve_routed_child``), and one
guard on each side against a process left running (what lost PRs 35, 42
and 43: ``benchmarks/proc.py`` starts the child in a session of its own,
every ``ray_tpu.core.worker`` leads its own, and ``Child.stop`` waits for
the child alone):

* the run's environment carries a fresh tag (``TAG_ENV``), which the child
  and everything it starts inherit; when ``serve_wide.run`` has returned
  or raised, ``sweep`` walks ``/proc`` for the tag, kills what carries it
  and returns only when nothing does (30 s, then it says what was left);
* the child ends itself, and whatever carries the tag, when this process
  is gone (``serve_hybrid_child.py``): a run killed during set-up can run
  no ``finally``.

A kind of its own for the reason ``serve_wide.py`` is one: a PR that adds
a configuration may not edit the files the benchmark has. Folding both
guards into ``proc.py`` for every kind is a ``benchmark`` PR's (ROADMAP
R12)."""
from __future__ import annotations

import os
import signal
import time
import uuid
from unittest import mock

from . import serve_wide

CHILD = "benchmarks.kinds.serve_hybrid_child"
TAG_ENV = "RTPU_BENCH_RUN_TAG"
SWEEP_S = 30.0


def tagged(tag: str) -> list:
    """[(pid, command line)] of the processes, this one apart, whose
    environment carries ``TAG_ENV=tag``."""
    needle = f"{TAG_ENV}={tag}".encode()
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if needle not in f.read().split(b"\0"):
                    continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out.append((int(pid), f.read().replace(b"\0", b" ").decode()))
        except OSError:
            continue            # gone, or another user's
    return out


def sweep(tag: str, log=print, patience: float = SWEEP_S) -> list:
    """Kill every process that carries the tag until none does; returns
    what was still there at the deadline (and says so on a line)."""
    deadline = time.monotonic() + patience
    while True:
        left = tagged(tag)
        if not left or time.monotonic() > deadline:
            break
        for pid, _ in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        time.sleep(0.1)
    if left:
        log({"phase": "sweep", "left_running": left})
    return left


def run(cell, a, t_process_start: float, log) -> dict:
    tag = uuid.uuid4().hex
    os.environ[TAG_ENV] = tag   # proc.child_env copies this environment
    try:
        with mock.patch.object(serve_wide, "CHILD", CHILD):
            return serve_wide.run(cell, a, t_process_start, log)
    finally:
        sweep(tag, log)
        del os.environ[TAG_ENV]
