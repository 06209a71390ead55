"""``serve_routed_child`` (a wide vocabulary, the reference check by share)
with ``HybridBenchLLMServer`` as the server (beside the share it holds a
snapshot of the recurrent state to the reference's: ``serve_app_hybrid.py``)
behind a watcher: ``proc.Child`` starts this process in a session of its
own, so a ``benchmarks.run`` that is killed — in set-up this process sits
in one call for minutes and reads no stdin — would leave it and its
workers alive. A thread polls ``os.getppid()``; when the parent is gone it
kills every process that carries the run's tag (``serve_hybrid.TAG_ENV``:
the head, the workers) and ends this one with ``os._exit``.

``SERVE_HYBRID_SETUP_DELAY_S`` (read here alone, for
tests/benchmark_harness/test_qwen3next_cell.py) holds the set-up for that
many seconds once ``ray_tpu.init`` has started its workers."""
from __future__ import annotations

import os
import signal
import sys
import threading
import time

import benchmarks.serve_app
import benchmarks.tokenizer
from benchmarks.kinds.serve_child import main
from benchmarks.kinds.serve_hybrid import TAG_ENV, tagged
from benchmarks.serve_app_hybrid import HybridBenchLLMServer
from benchmarks.tokenizer_wide import WideTokenizer

benchmarks.tokenizer.OneCharTokenizer = WideTokenizer
benchmarks.serve_app.BenchLLMServer = HybridBenchLLMServer


def _watch(parent: int) -> None:
    while os.getppid() == parent:
        time.sleep(0.25)
    for pid, _ in tagged(os.environ.get(TAG_ENV, "")):
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    os._exit(1)


def _slow_set_up(seconds: float) -> None:
    build = benchmarks.serve_app.build_app

    def slow(*args, **kwargs):
        time.sleep(seconds)
        return build(*args, **kwargs)
    benchmarks.serve_app.build_app = slow


if __name__ == "__main__":
    threading.Thread(target=_watch, args=(os.getppid(),),
                     daemon=True).start()
    if os.environ.get("SERVE_HYBRID_SETUP_DELAY_S"):
        _slow_set_up(float(os.environ["SERVE_HYBRID_SETUP_DELAY_S"]))
    sys.exit(main())
