"""Plain reference of OLMoE-1B-7B's decoder block (Hugging Face
``modeling_olmoe.py``): straightforward ``jax.numpy`` in float32 at the
highest matmul precision, no kernels, no cache, no batching, no grouping of
tokens by expert. Written from the published equations and independent of
``ray_tpu/models/llama.py``; it reads only that module's parameter names.

    x   = x + Wo . Attn(RoPE(nq(Wq n1(x))), RoPE(nk(Wk n1(x))), Wv n1(x))
    out = x + MoE(n2(x))
    n(x) = x / sqrt(mean(x^2) + eps) * g

``nq`` and ``nk`` are RMSNorms with a learned weight over the WHOLE projected
vector (all heads together), before the reshape into heads and before RoPE.
RoPE rotates the two halves of a head; attention is causal softmax at
``head_dim ** -0.5``, one KV head per query head group (OLMoE: MHA).

    p      = softmax(n2(x) Wr)           over all experts, float32
    e, p_e = the num_experts_per_tok largest p and their experts
             (divided by their sum only where norm_topk_prob: OLMoE, false)
    MoE    = sum_e p_e * Wdown[e] (silu(Wgate[e] h) * (Wup[e] h))

Every token is put through EVERY expert in turn and keeps the result only
where it selected that expert (a weight of p_e, else 0): 64 / 8 times the
work, and no way to get the routing wrong. Expert weights are upcast one
expert at a time. Departure from the description: none. Norm gains are
applied in float32 here, where the program rounds the normalised
activations to bf16 first.
"""
from __future__ import annotations

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


class OlmoeDecoder:
    def __init__(self, model: dict):
        self.m = model
        self.hd = model.get("head_dim") or (
            model["hidden_size"] // model["num_attention_heads"])

    # -- building blocks ---------------------------------------------------

    def _norm(self, x, g):
        import jax.numpy as jnp
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + self.m["rms_norm_eps"]) * g

    def _rope(self, x, pos):
        """x [S, H, D], pos [S]."""
        import jax.numpy as jnp
        half = self.hd // 2
        inv = float(self.m["rope_theta"]) ** (
            -jnp.arange(half, dtype=jnp.float32) * 2.0 / self.hd)
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _attend(self, q, k, v):
        """q [S, H, D], k and v [S, KVH, D] -> [S, H * D], causal, one KV
        head with its group of query heads at a time."""
        import jax
        import jax.numpy as jnp
        s, nh, hd = q.shape
        nkv = k.shape[1]
        pos = jnp.arange(s)
        causal = pos[None, :] <= pos[:, None]

        def group(qkv):
            qg, kg, vg = qkv                    # [G, S, D], [S, D], [S, D]
            scores = jnp.einsum("gqd,kd->gqk", qg, kg) / jnp.sqrt(float(hd))
            scores = jnp.where(causal[None], scores, -jnp.inf)
            return jnp.einsum("gqk,kd->gqd",
                              jax.nn.softmax(scores, axis=-1), vg)
        out = jax.lax.map(group, (
            q.reshape(s, nkv, nh // nkv, hd).transpose(1, 2, 0, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        return out.transpose(2, 0, 1, 3).reshape(s, nh * hd)

    def routing(self, h, w_router):
        """h [S, hidden] -> (weights [S, k], experts [S, k])."""
        import jax
        import jax.numpy as jnp
        probs = jax.nn.softmax(h @ w_router.astype(jnp.float32), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, self.m["num_experts_per_tok"])
        if self.m.get("norm_topk_prob"):
            top_p = top_p / top_p.sum(-1, keepdims=True)
        return top_p, top_e

    def _moe(self, h, p, layer):
        import jax
        import jax.numpy as jnp
        n_exp = self.m["num_experts"]
        top_p, top_e = self.routing(h, p["w_router"])
        # [S, E]: p_e where the token selected expert e, else 0
        weight = (jax.nn.one_hot(top_e, n_exp, dtype=jnp.float32)
                  * top_p[..., None]).sum(1)

        def expert(acc, e):
            wg, wu, wd = (p[n][layer, e].astype(jnp.float32)
                          for n in _EXPERT_WEIGHTS)
            y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return acc + weight[:, e, None] * y, None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(n_exp))
        return out

    def _layer(self, x, p, layer):
        import jax.numpy as jnp
        m, hd = self.m, self.hd
        nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
        s = x.shape[0]
        f32 = lambda w: w.astype(jnp.float32)           # noqa: E731
        pos = jnp.arange(s)
        h = self._norm(x, f32(p["attn_norm"]))
        q = self._norm(h @ f32(p["wq"]), f32(p["q_norm"]))
        k = self._norm(h @ f32(p["wk"]), f32(p["k_norm"]))
        q = self._rope(q.reshape(s, nh, hd), pos)
        k = self._rope(k.reshape(s, nkv, hd), pos)
        v = (h @ f32(p["wv"])).reshape(s, nkv, hd)
        x = x + self._attend(q, k, v) @ f32(p["wo"])
        return x + self._moe(self._norm(x, f32(p["mlp_norm"])), p, layer)

    # -- forward and loss --------------------------------------------------

    def hidden(self, params: dict, tokens):
        """tokens [S] int32 -> the final norm's output [S, hidden]."""
        import jax
        import jax.numpy as jnp
        stacks = params["layers"]

        def layer(x, i):
            # the experts' stacks are indexed [layer, expert] where they
            # are used, one expert's float32 copy at a time
            p = {n: a if n in _EXPERT_WEIGHTS else a[i]
                 for n, a in stacks.items()}
            return self._layer(x, p, i), None
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            x, _ = jax.lax.scan(jax.checkpoint(layer), x,
                                jnp.arange(stacks["attn_norm"].shape[0]))
            return self._norm(x, params["final_norm"].astype(jnp.float32))

    def head(self, params: dict, x):
        """[..., hidden] -> logits [..., V] float32."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            return x @ params["lm_head"].astype(jnp.float32)

    def logits(self, params: dict, tokens):
        """tokens [S] int32 -> logits [S, V] float32 of one sequence."""
        return self.head(params, self.hidden(params, tokens))

    def loss(self, params: dict, tokens):
        """Mean next-token negative log-likelihood over tokens [B, S+1]."""
        import jax
        import jax.numpy as jnp

        def one(seq):
            logp = jax.nn.log_softmax(self.logits(params, seq[:-1]), -1)
            return -jnp.take_along_axis(logp, seq[1:, None], -1).mean()
        return jnp.mean(jax.lax.map(one, tokens))
