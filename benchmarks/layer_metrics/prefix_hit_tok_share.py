"""Engine scheduler: share of the prompt tokens sent in the window whose
prefill the prefix cache skipped."""
from ._common import delta


def read(ctx: dict):
    saved = delta(ctx, "prefix_tokens_saved")
    sent = sum(r.prompt_tokens for r in ctx.get("records", []))
    return 100.0 * saved / sent if sent and saved is not None else None
