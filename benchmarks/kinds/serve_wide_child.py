"""``serve_child`` with ``WideTokenizer`` in ``OneCharTokenizer``'s place:
``serve_child.main`` imports the name from ``benchmarks.tokenizer`` when
it runs, so the driver it builds, and everything else it does, is its
own (see ``serve_wide.py`` for why this is a file)."""
from __future__ import annotations

import sys

import benchmarks.tokenizer
from benchmarks.kinds.serve_child import main
from benchmarks.tokenizer_wide import WideTokenizer

benchmarks.tokenizer.OneCharTokenizer = WideTokenizer

if __name__ == "__main__":
    sys.exit(main())
