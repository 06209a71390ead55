"""Open loop: independent users. Requests go out on a schedule drawn from
the seed, whether or not earlier ones have finished, from ``warmup_s``
before the window opens to its end."""
from __future__ import annotations

import asyncio

from ..client import Window, sleep_until
from ..sampling import arrivals, lengths


async def run(traffic: dict, rng, vocab: int, client, window: Window):
    lead = float(traffic["warmup_s"])
    span = lead + window.seconds
    due = window.start - lead + arrivals(
        traffic["arrivals"], float(traffic["rate_rps"]), span, rng)
    n = len(due)
    p_len = lengths(traffic["prompt_tokens"], n, rng)
    o_len = lengths(traffic["output_tokens"], n, rng)
    shared = rng.integers(0, vocab, int(traffic.get(
        "shared_prefix_tokens", 0))).tolist()
    # bodies are made before the first request is due: the send path then
    # only sleeps and writes
    bodies = [client.body(
        shared + rng.integers(0, vocab, int(p_len[i])).tolist(), o_len[i])
        for i in range(n)]
    tasks = []
    for i in range(n):
        await sleep_until(due[i])
        tasks.append(asyncio.ensure_future(client.send(
            bodies[i], float(due[i]), int(o_len[i]),
            len(shared) + int(p_len[i]))))
    await asyncio.gather(*tasks)
