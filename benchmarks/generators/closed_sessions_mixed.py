"""Closed loop of two classes of session in ONE queue, each session
waiting for every answer before its next request, so that a slow system
receives less load:

* ``long``: as ``closed_sessions.py`` — a session holds one document and
  asks ``questions_per_session`` questions about it in turn, each the
  document plus a new suffix, then takes the next document;
* ``short``: every request a new prompt (no document, nothing shared).

Each record is tagged with its class (``cls``), the long ones with
``doc`` and ``question`` too, so that a reader can tell what a short
request pays behind the long ones. Lengths are drawn stratified a class,
as ``closed_sessions.py`` draws its documents: round by round, so every
seed puts nearly the same work into the window.

Long sessions start ``stagger_s`` apart from ``warmup_s`` before the
window; short session ``j`` of ``m`` starts among them, after
``(j + 0.5) / m`` of the long sessions have."""
from __future__ import annotations

import asyncio
import time

import numpy as np

from ..client import Window, sleep_until
from ..sampling import lengths


async def run(traffic: dict, rng, vocab: int, client, window: Window):
    long_, short = traffic["long"], traffic["short"]
    n_long, n_short = int(long_["sessions"]), int(short["sessions"])
    n_q = int(long_["questions_per_session"])
    lead = float(traffic["warmup_s"])
    stagger = float(traffic.get("stagger_s", 0.0))
    think = float(traffic.get("think_time_s", 0.0))
    rng_long, rng_short = rng.spawn(2)

    # lengths for the whole run, a ROUND at a time (a round = one document
    # a long session, one prompt a short one), handed out in the order
    # sessions ask for them: a round takes one value from each equal slice
    # of its distribution — a document's slice rotating by one from round
    # to round, a question's, an answer's and a short prompt's in a seeded
    # order — so the ~1.2 rounds of long asks a window holds cover every
    # distribution evenly whatever the seed. (What still moves `out_tok_s`
    # by ~4% from seed to seed is which documents' cold prefill falls
    # inside the window, not the lengths: PERF.md §6, PR 40.)
    rounds = 64

    def by_round(dist, a_round, n_rounds, rng, rotate=False):
        return np.concatenate([
            lengths(dist, a_round, rng,
                    order=(np.arange(a_round) + r) % a_round if rotate
                    else None) for r in range(n_rounds)])
    doc_len = by_round(long_["document_tokens"], n_long, rounds, rng_long,
                       rotate=True).tolist()
    q_len = by_round(long_["question_tokens"], n_long * n_q, rounds, rng_long)
    o_len = by_round(long_["output_tokens"], n_long * n_q, rounds, rng_long)
    next_doc = iter(range(len(doc_len)))
    # a generator of its own for each document: what a document holds does
    # not depend on which session reaches it first
    doc_rng = rng_long.spawn(len(doc_len))

    short_rounds = 512
    p_len = by_round(short["prompt_tokens"], n_short, short_rounds,
                     rng_short, rotate=True).tolist()
    so_len = by_round(short["output_tokens"], n_short, short_rounds,
                      rng_short)
    next_short = iter(range(len(p_len)))
    short_seed = rng_short.integers(0, 2 ** 31)

    async def pause():
        if think:
            await asyncio.sleep(think)

    async def long_session(s: int):
        await sleep_until(window.start - lead + s * stagger)
        while time.perf_counter() < window.end:
            d = next(next_doc)
            doc = doc_rng[d].integers(0, vocab, doc_len[d]).tolist()
            for q in range(n_q):
                if time.perf_counter() >= window.end:
                    return
                i = d * n_q + q
                prompt = doc + doc_rng[d].integers(
                    0, vocab, int(q_len[i])).tolist()
                await client.send(client.body(prompt, o_len[i]),
                                  time.perf_counter(), int(o_len[i]),
                                  len(prompt), cls="long", session=s, doc=d,
                                  question=q)
                await pause()

    async def short_session(j: int):
        await sleep_until(window.start - lead
                          + (j + 0.5) / n_short * n_long * stagger)
        while time.perf_counter() < window.end:
            i = next(next_short)
            prompt = np.random.default_rng([short_seed, i]).integers(
                0, vocab, p_len[i]).tolist()
            await client.send(client.body(prompt, so_len[i]),
                              time.perf_counter(), int(so_len[i]),
                              len(prompt), cls="short", session=n_long + j)
            await pause()

    await asyncio.gather(*(long_session(s) for s in range(n_long)),
                         *(short_session(j) for j in range(n_short)))
