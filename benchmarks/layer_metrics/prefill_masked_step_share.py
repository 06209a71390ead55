"""Kernels (ops/ragged_paged_attention.py, the per-kv-head body at the
prefill shape): of the live grid steps the prefill dispatches' rows swept,
the share that took the body WITH the live-key predicate — the diagonal
block, a partly live tail block — and not the one without it, which a
block wholly in a tile's prefix takes. Counters ``prefill_key_steps_masked``
/ ``prefill_key_steps`` (llm/paged_engine.py ``_prefill_step``: integer
arithmetic on each row's (pos, n) with the tile rows and keys a step of
the model module's kernel, one layer's sweep a row). None on a program
without the counters. Declared in BENCHMARK.json since PR 33; counters
alone, so over the window."""
from ._engine import per


def read(ctx: dict):
    return per(ctx, "prefill_key_steps_masked", "prefill_key_steps", 100.0)
