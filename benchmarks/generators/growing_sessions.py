"""Closed loop of multi-turn sessions whose context GROWS: a session's
first turn is a context of ``first_turn_tokens``; every later turn resends
the whole history — every earlier prompt and answer — plus a new message of
``message_tokens``, waiting for every answer, so that a slow system
receives less load. A session ends when its next prompt and answer would
pass ``max_context_tokens``, and a fresh conversation takes its place.

The history's answer parts are seeded random ids of each answer's length,
not the served text: ``benchmarks/client.py`` keeps a response's token
count and not its text, and a seed's prompts are then the same whatever
the program serves. Records are tagged ``session``, ``conv`` and ``turn``.

Lengths are drawn stratified (``closed_sessions.py``): a round of
conversations takes one first-turn length from each equal slice of its
distribution, the slices rotating by one from round to round; and every
``GROUP`` consecutive turns of ONE conversation take their messages and
their answers from ``GROUP`` equal slices each, in a seeded order — so a
conversation grows at nearly the same rate whatever the seed, and how many
conversations end inside a window (each fresh one a cold context: what
moved ``out_tok_s`` by +-5% from seed to seed, PERF.md §6, PR 47) barely
moves with it."""
from __future__ import annotations

import asyncio
import time

import numpy as np

from ..client import Window, sleep_until
from ..sampling import lengths

ROUNDS, TURNS = 8, 64       # conversations a session may start; turns of one
GROUP = 8                   # turns that share one stratified draw
SPARE = 2                   # positions the engine keeps free of a prompt


async def run(traffic: dict, rng, vocab: int, client, window: Window):
    n_sess = int(traffic["sessions"])
    lead = float(traffic["warmup_s"])
    stagger = float(traffic.get("stagger_s", 0.0))
    limit = int(traffic["max_context_tokens"]) - SPARE
    first = [int(x) for r in range(ROUNDS)
             for x in lengths(traffic["first_turn_tokens"], n_sess, rng,
                              order=(np.arange(n_sess) + r) % n_sess)]
    groups = len(first) * TURNS // GROUP
    m_len, o_len = (
        np.concatenate([lengths(traffic[key], GROUP, rng)
                        for _ in range(groups)])
        for key in ("message_tokens", "output_tokens"))
    next_conv = iter(range(len(first)))
    # a generator of its own for each conversation: what it holds does
    # not depend on which session reaches it first
    conv_rng = rng.spawn(len(first))

    async def session(s: int):
        await sleep_until(window.start - lead + s * stagger)
        while time.perf_counter() < window.end:
            d = next(next_conv)
            draw = lambda n: conv_rng[d].integers(     # noqa: E731
                0, vocab, int(n)).tolist()
            history = draw(first[d])
            for turn in range(TURNS):
                i = d * TURNS + turn
                prompt = history + (draw(m_len[i]) if turn else [])
                want = int(o_len[i])
                if len(prompt) + want > limit \
                        or time.perf_counter() >= window.end:
                    break
                await client.send(client.body(prompt, want),
                                  time.perf_counter(), want, len(prompt),
                                  session=s, conv=d, turn=turn)
                history = prompt + draw(want)

    await asyncio.gather(*(session(s) for s in range(n_sess)))
