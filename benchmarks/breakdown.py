"""The ``breakdown`` of a traced run: the ten device operations that took
most time, and the longest idle gaps, each named by what the host was doing
as far as the benchmark's own spans can tell (spans inside the engine do
not exist yet)."""
from __future__ import annotations


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
        return "no_request_in_flight"
    waiting = sum(1 for r in live if not r.first or r.first > mid)
    return (f"first_chunk_wait.{waiting}of{len(live)}" if waiting
            else f"streaming.{len(live)}_in_flight")


def breakdown(ctx: dict) -> dict:
    trace = ctx["trace"]
    ops = [[name, seconds] for name, seconds, *_ in trace["ops"][:10]]
    gaps = []
    offset = trace.get("clock_offset_ns")
    for g in trace["idle_gaps"]:
        # host annotations inside the traced process, else what the
        # client saw at that instant, then the programs on either side
        state = ".".join(g.get("host", []))
        if not state and offset is not None:
            t0 = (g["start_ns"] + offset) / 1e9
            state = _host_state(ctx, t0, t0 + g["seconds"])
        name = (f"{state or 'host_unknown'}.after.{g['after'] or 'start'}"
                f".before.{g['before'] or 'end'}")
        gaps.append([name[:120], g["seconds"]])
    return {"device_ops": ops, "idle_gaps": gaps}
