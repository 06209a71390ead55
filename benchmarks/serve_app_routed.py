"""``BenchLLMServer`` for a model whose routing is a discontinuous function
of its activations with weights too large to shrug off (kanana-2-30b-a3b:
the six selected of 128 experts carry 2.448 / 6 = 0.41 each, where OLMoE's
eight softmax weights are ~0.04 each): the reference check holds the SHARE
of served tokens that are the reference's argmax or within ``logit_margin``
of it to a floor, where ``BenchLLMServer`` holds every token to the margin.

Why (my chip runs, PR 31; PERF.md §6): the program runs in bf16 and the
plain reference in float32. Their router scores differ by ~1e-3, and the
last expert selected lies within that of the first one left out for ~7% of
(token, layer) pairs: with seven expert layers 40% of tokens swap an expert
of weight 0.41 somewhere, and their logits then move by up to 3. On a
correct program 57-64% of served tokens were the reference's argmax and
the worst gap of a run was 1.0-3.3 (OLMoE: 0.025); with the cache or the
absorbed query rounded below bf16 (float8_e4m3) the worst gap is 3.1-3.6:
no limit on the WORST token tells the two apart, and one wide enough for
the first passes anything. The share does: 0.68-0.72 of 288 tokens within
0.1 on a correct program over four seeds, 0.34-0.44 with the cache below
bf16 and 0.43 with the absorbed query below bf16. ``min_share`` lies
between. Conditioning on the reference's own routing margin (judging a
token only where its sixth and seventh experts lie far apart) was tried
and does not separate them better: flips of the tokens it attends reach a
token too.

Everything else is ``BenchLLMServer.reference_check``: the same prompts,
the same two groups (the second ask of the long document a prefix-cache
hit decoded over a full-length latent cache), the same teacher-forced
reference and the same per-token quantity.
"""
from __future__ import annotations

import time

from benchmarks.serve_app import CONTEXT_SHARE, BenchLLMServer


class RoutedBenchLLMServer(BenchLLMServer):
    def reference_check(self, spec: dict, context_tokens: int) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.spec import resolve
        b = self._bench
        rng = np.random.default_rng([b["seed"], 0xC0FFEE])
        vocab = b["model"]["vocab_size"]
        n_new = spec["new_tokens"]
        draw = lambda n: rng.integers(0, vocab, int(n)).tolist()  # noqa: E731
        lo, hi = spec["prompt_tokens"]
        suffix = spec["context_suffix_tokens"]
        doc = draw(CONTEXT_SHARE * (context_tokens - suffix - n_new))
        groups = {"short": [draw(n) for n in
                            np.linspace(lo, hi, spec["samples"])],
                  "context": [doc + draw(suffix) for _ in range(2)]}
        t0 = time.perf_counter()
        served, saved = {}, {}
        for name, prompts in groups.items():
            before = self.engine.stats["prefix_tokens_saved"]
            served[name] = [self.completions(
                {"prompt": p, "max_tokens": n_new, "temperature": 0.0}
            )["choices"][0]["token_ids"] for p in prompts]
            saved[name] = self.engine.stats["prefix_tokens_saved"] - before
        t1 = time.perf_counter()
        ref = resolve(b["reference"])(b["model"])

        @jax.jit
        def gaps(params, tokens, first, picked):
            rows = ref.head(params, jax.lax.dynamic_slice_in_dim(
                ref.hidden(params, tokens), first, n_new))
            chosen = jnp.take_along_axis(rows, picked[:, None], -1)[:, 0]
            return rows.max(-1) - chosen

        out = {"margin": spec["logit_margin"],
               "min_share": spec["min_share"], "tokens": 0,
               "argmax_matches": 0, "within_margin": 0,
               "worst_logit_gap": {}, "share_within_margin": {},
               "context_prompt_tokens": len(groups["context"][0]),
               "context_prefix_tokens_saved": saved["context"]}
        for name, prompts in groups.items():
            # one width to a group: two compiles in all
            width = -(-(max(map(len, prompts)) + n_new) // 128) * 128
            group = []
            for p, toks in zip(prompts, served[name]):
                if len(toks) != n_new:
                    return {"ok": False, "why": f"served {len(toks)} tokens, "
                            f"asked {n_new}"}
                seq = np.zeros((width,), np.int32)
                seq[:len(p) + n_new] = p + toks
                gap = np.asarray(gaps(self.engine.params, seq, len(p) - 1,
                                      np.asarray(toks, np.int32)))
                if not np.isfinite(gap).all():
                    return {"ok": False,
                            "why": "non-finite reference logits"}
                group.append(gap)
            group = np.concatenate(group)
            within = int((group <= spec["logit_margin"]).sum())
            out["argmax_matches"] += int((group == 0).sum())
            out["within_margin"] += within
            out["tokens"] += len(group)
            out["worst_logit_gap"][name] = float(group.max())
            out["share_within_margin"][name] = within / len(group)
        cached = (not self.engine_cfg.enable_prefix_caching
                  or saved["context"] > 0)
        out["share"] = out["within_margin"] / out["tokens"]
        out["ok"] = cached and out["share"] >= spec["min_share"]
        if not cached:
            out["why"] = "the repeated document was not a prefix-cache hit"
        out.update(serve_s=t1 - t0, reference_s=time.perf_counter() - t1)
        return out
