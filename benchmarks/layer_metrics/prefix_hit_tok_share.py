"""Engine scheduler: share of the prompt tokens sent in the window whose
prefill the prefix cache skipped (counter ``prefix_tokens_saved`` over the
client's records); None where the counter is missing."""
from ._engine import deltas


def read(ctx: dict):
    saved = deltas(ctx).get("prefix_tokens_saved")
    sent = sum(r.prompt_tokens for r in ctx.get("records", []))
    return 100.0 * saved / sent if sent and saved is not None else None
