"""Closed loop of sessions: each of ``sessions`` callers holds one document
and asks ``questions_per_session`` questions about it in turn, each question
the document plus a new suffix, waiting for every answer; then takes the
next document. A slow system therefore receives less load."""
from __future__ import annotations

import asyncio
import time

import numpy as np

from ..client import Window, sleep_until
from ..sampling import lengths


async def run(traffic: dict, rng, vocab: int, client, window: Window):
    n_sess = int(traffic["sessions"])
    n_q = int(traffic["questions_per_session"])
    lead = float(traffic["warmup_s"])
    think = float(traffic.get("think_time_s", 0.0))
    # document lengths for the whole run, handed out in the order sessions
    # ask for them: each round of n_sess documents takes one from each of
    # n_sess slices of the distribution, the slices rotating by one from
    # round to round. The seed moves a length only inside its slice, so
    # every seed puts nearly the same work into the window; a window holds
    # few documents, and a seeded order of slices moved the tokens it
    # delivers by 10% from seed to seed
    rounds = 64
    doc_len = [int(x) for r in range(rounds)
               for x in lengths(traffic["document_tokens"], n_sess, rng,
                                order=(np.arange(n_sess) + r) % n_sess)]
    q_len = lengths(traffic["question_tokens"], len(doc_len) * n_q, rng)
    o_len = lengths(traffic["output_tokens"], len(doc_len) * n_q, rng)
    next_doc = iter(range(len(doc_len)))
    # a generator of its own for each document: what a document holds does
    # not depend on which session reaches it first
    doc_rng = rng.spawn(len(doc_len))

    async def session(s: int):
        await sleep_until(window.start - lead
                          + s * float(traffic.get("stagger_s", 0.0)))
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
                                  len(prompt), session=s, doc=d, question=q)
                if think:
                    await asyncio.sleep(think)

    await asyncio.gather(*(session(s) for s in range(n_sess)))
