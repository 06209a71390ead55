"""The ``breakdown`` of a traced run: the ten device operations that took
most time, and the longest idle gaps, each named by what the host was
doing: what the client saw (requests in flight, how many still waited for a
first token), then the annotation the traced process was inside — the
engine's phase on the stepping thread (``rtpu.engine.*`` / ``rtpu.loop.*``),
the training loop's ``train_step`` / ``report`` — then the programs on
either side.

A gap's name is cut to 64 characters where it is recorded, so it is built
short: ``<annotation>.<client>.<program before>-to-<program after>``, as in
``rtpu.engine.admit.wait17of24.decode_w1-to-prefill_r4`` — the stepping
thread was admitting requests, 17 of the 24 requests in flight still waited
for their first chunk (``stream24``: none did; ``idle_client``: none in
flight), between a ``jit_rtpu_decode_w1`` and a ``jit_rtpu_prefill_r4``."""
from __future__ import annotations

import re


def _host_state(ctx: dict, start_wall: float, end_wall: float) -> str:
    """What the benchmark knows of the host during [start, end] (wall
    clock): for a serving cell, how many requests were in flight and
    whether any still waited for its first token."""
    records = ctx.get("all_records")
    if records is None:
        return ""
    off = ctx["wall_offset"]
    mid = (start_wall + end_wall) / 2 - off
    live = [r for r in records if r.sent and r.sent <= mid < (r.done or 1e18)]
    if not live:
        return "idle_client"
    waiting = sum(1 for r in live if not r.first or r.first > mid)
    return f"wait{waiting}of{len(live)}" if waiting else f"stream{len(live)}"


def _short(program: str) -> str:
    """``jit_rtpu_decode_w8`` -> ``decode_w8``."""
    return re.sub(r"^jit_(rtpu_)?", "", program)


def breakdown(ctx: dict) -> dict:
    trace = ctx["trace"]
    ops = [[name, seconds] for name, seconds, *_ in trace["ops"][:10]]
    gaps = []
    offset = trace.get("clock_offset_ns")
    for g in trace["idle_gaps"]:
        state = ""
        if offset is not None:
            t0 = (g["start_ns"] + offset) / 1e9
            state = _host_state(ctx, t0, t0 + g["seconds"])
        state = ".".join(filter(None, [*g.get("host", []), state]))
        name = (f"{state or 'host_unknown'}.{_short(g['after']) or 'start'}"
                f"-to-{_short(g['before']) or 'end'}")
        gaps.append([name[:64], g["seconds"]])
    return {"device_ops": ops, "idle_gaps": gaps}
