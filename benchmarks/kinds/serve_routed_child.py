"""``serve_child`` with ``WideTokenizer`` in ``OneCharTokenizer``'s place
(a vocabulary of 128,256: ``serve_wide_child.py``) and
``RoutedBenchLLMServer`` in ``BenchLLMServer``'s: ``serve_child.main`` and
``serve_app.build_app`` look both names up when they run."""
from __future__ import annotations

import sys

import benchmarks.serve_app
import benchmarks.tokenizer
from benchmarks.kinds.serve_child import main
from benchmarks.serve_app_routed import RoutedBenchLLMServer
from benchmarks.tokenizer_wide import WideTokenizer

benchmarks.tokenizer.OneCharTokenizer = WideTokenizer
benchmarks.serve_app.BenchLLMServer = RoutedBenchLLMServer

if __name__ == "__main__":
    sys.exit(main())
