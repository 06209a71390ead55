"""``RoutedBenchLLMServer`` for a model with recurrent-state layers
(Qwen3-Next's gated delta rule): the reference check by share, and beside
it a check of THE STATE ITSELF — the mechanism the configuration adds,
which the share cannot see.

Why (my chip runs, PR 47; PERF.md §6): the share of served tokens within
the margin is dominated by the router's flips (top-10 of 512 in bf16
against float32), so the reference with its recurrent state kept in bf16
reads 0.953 there, inside the correct program's 0.935-0.969: a program
that held its states or its snapshots in less than the float32 the
configuration's file states would pass.

What is compared: after the share's prompts (the long document asked
twice, so its first ask has left a snapshot a dispatch short of its end),
the document's head is asked ONCE MORE, cut one token behind a boundary
``at`` — a multiple of 1,024 tokens, so that the reference runs in whole
blocks. That ask resumes from the first ask's snapshot, prefills on to
``at`` and files its prompt's end snapshot there: a state that has been
through every way a state travels in the program (fresh, chained from row
to row of a dispatch, carried from dispatch to dispatch in the slot, filed
as a snapshot, resumed from it, filed again). The snapshot is read from
the pool under its page's hash and held, GDN layer by GDN layer, against
the state the plain reference's token-by-token scan leaves behind the same
``at`` tokens: ``gap`` = ``|S - S_ref| / |S_ref|`` (Frobenius, all of a
layer's heads), ``conv_gap`` the same for the convolution's tail. Three
limits (every reading: PERF.md §6):

* ``state_gap_limit`` on the FIRST GDN layer's gap: nothing routed lies
  ahead of it, so its inputs differ from the reference's by bf16 rounding
  alone, and its gap is the same from seed to seed — 0.0060-0.0064 over
  twelve seeds (an error in a bf16 key reaches the whole state through the
  rule's erasure, token after token) — where the reference with its state
  rounded to bf16 after every token (the spec's ``control``) reads
  0.0080-0.0129 against the same program.
* ``state_gap_limit_gross`` on every layer's gap and ``conv_gap``: behind
  an expert layer the inputs carry the router's flips (0.015-0.04 after
  one, 0.07-0.13 after six), so a deeper state says only that it is this
  sequence's state, computed in the type the file states (0.28 against
  the reference at 3 mantissa bits; an unrelated state lies sqrt 2 away).
* ``bf16_exact_limit`` on the share of the snapshots' entries that bf16
  holds exactly (2-3 in 100,000 of a float32 state, all of one that was
  filed through bf16): ONE rounding moves the gap by 0.0002, less than
  the limit's room, so storage is told by the bits, not by the gap.
"""
from __future__ import annotations

import time

from benchmarks.serve_app import CONTEXT_SHARE
from benchmarks.serve_app_routed import RoutedBenchLLMServer

BLOCK = 1024


class HybridBenchLLMServer(RoutedBenchLLMServer):
    def reference_check(self, spec: dict, context_tokens: int) -> dict:
        b = self._bench
        if spec.get("control"):
            b["model"] = dict(b["model"], reference_control=spec["control"])
        out = super().reference_check(spec, context_tokens)
        if "share" in out:          # else refused ahead of any comparison
            t0 = time.perf_counter()
            out["state"] = state = self.state_check(spec, context_tokens)
            out["state_s"] = time.perf_counter() - t0
            if not state["ok"]:
                out["ok"] = False
                out.setdefault("why", state["why"])
        return out

    def state_check(self, spec: dict, context_tokens: int) -> dict:
        import jax
        import numpy as np

        from benchmarks.spec import resolve
        b, eng = self._bench, self.engine
        page = self.engine_cfg.page_size
        # the document of the share's ``context`` group: its first draw
        rng = np.random.default_rng([b["seed"], 0xC0FFEE])
        doc = rng.integers(0, b["model"]["vocab_size"], int(
            CONTEXT_SHARE * (context_tokens - spec["context_suffix_tokens"]
                             - spec["new_tokens"]))).tolist()
        at = next(n for n in ((len(doc) - 1) // unit * unit
                              for unit in (BLOCK, page)) if n)
        before = dict(eng.stats)
        self.completions({"prompt": doc[:at + 1], "max_tokens": 1,
                          "temperature": 0.0})
        out = {"at": at, "resumed_tokens": eng.stats["state_hit_tokens"]
               - before["state_hit_tokens"]}
        if not out["resumed_tokens"]:
            return dict(out, ok=False,
                        why="the state check's ask resumed from no snapshot")
        pool = eng.cache.state.space
        h = eng.cache.hash_chain(doc[:at])[-1]
        with self._steplock:        # a dispatch donates engine.caches
            sid = pool.hash_to_page.get(h)
            if sid is None:
                return dict(out, ok=False, why=f"no snapshot at {at} tokens")
            got = [(np.asarray(c["snap_S"][sid]),
                    np.asarray(c["snap_conv"][sid].astype("float32")))
                   for c in eng.caches if "snap_S" in c]
        ref = resolve(b["reference"])(b["model"])
        want = jax.jit(ref.states)(eng.params, np.asarray(doc[:at], np.int32))

        def gap(x, y):
            x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
            return float(np.linalg.norm(x - y) / np.linalg.norm(y))
        bits = np.concatenate([s.view(np.uint32).ravel() for s, _ in got])
        out.update(
            gap=[gap(s, ws) for (s, _), (ws, _) in zip(got, want)],
            conv_gap=[gap(t, wt) for (_, t), (_, wt) in zip(got, want)],
            bf16_exact_share=float((bits & 0xFFFF == 0).mean()),
            limit=spec["state_gap_limit"],
            gross_limit=spec["state_gap_limit_gross"],
            bf16_exact_limit=spec["bf16_exact_limit"])
        out["ok"] = bool(
            np.isfinite(out["gap"] + out["conv_gap"]).all()
            and out["gap"][0] <= out["limit"]
            and max(out["gap"] + out["conv_gap"]) <= out["gross_limit"]
            and out["bf16_exact_share"] <= out["bf16_exact_limit"])
        if not out["ok"]:
            out["why"] = "a recurrent state's snapshot is not the reference's"
        return out
