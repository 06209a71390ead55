"""Tokens of the steps between the sync that opens the window and the first
sync at or after ``--seconds``, over the host seconds between those two
syncs (time spent tracing, in a traced run, taken out)."""


def read(ctx: dict):
    m = ctx["train"]
    return m["tokens"] / m["window_s"]
