"""``prefill_masked_step_share`` (PR 32; declared for the kanana cell since
PR 33) on hand-made counters, and None where the program has no
``prefill_key_steps`` (every earlier commit) or nothing was counted."""
import importlib

import pytest

NAME = "prefill_masked_step_share"
# 61 prefill dispatches of four rows, each row four 32-row tiles of ~25
# live 512-key steps, of which the last one or two carry the predicate
BEFORE = {"prefill_dispatches": 10, "prefill_key_steps": 4_000,
          "prefill_key_steps_masked": 250, "mesh": None}
AFTER = {"prefill_dispatches": 71, "prefill_key_steps": 28_400,
         "prefill_key_steps_masked": 1_714, "mesh": None}


def _read(ctx: dict):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{NAME}").read(ctx)


def test_share_is_masked_steps_over_live_steps():
    ctx = {"stats_before": BEFORE, "stats_after": AFTER}
    assert _read(ctx) == pytest.approx(100.0 * 1_464 / 24_400, rel=1e-12)


@pytest.mark.parametrize("before,after", [
    # the parent's counters: dispatches, no key steps
    ({"prefill_dispatches": 10}, {"prefill_dispatches": 71}),
    # one of the two alone is nothing to read either
    ({k: v for k, v in BEFORE.items() if k != "prefill_key_steps_masked"},
     {k: v for k, v in AFTER.items() if k != "prefill_key_steps_masked"}),
    (None, None),                   # no snapshot at all
    (BEFORE, BEFORE),               # no prefill dispatch in the window
], ids=["parent", "one_counter", "no_snapshot", "nothing_counted"])
def test_share_is_none_without_the_counters(before, after):
    assert _read({"stats_before": before, "stats_after": after}) is None
