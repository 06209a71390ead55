"""Plain reference of the DeepSeek-V3 decoder block as kanana-2-30b-a3b
publishes it (Hugging Face ``modeling_deepseek_v3.py``, ``q_lora_rank``
null): straightforward ``jax.numpy`` in float32 at the highest matmul
precision, no kernels, no cache, no batching, no grouping of tokens by
expert. Written from the published equations and independent of
``ray_tpu/models/mla_moe.py``'s attention algebra; it reads only that
module's parameter names (``w_uk`` [H, nope, rank] and ``w_uv``
[H, rank, v] are Wkvb's two halves, per head).

    n(x) = x / sqrt(mean(x^2) + eps) * g
    q = Wq n1(x) -> [H, nope + rope];  a = Wkva n1(x) -> [rank + rope]
    c = n_kv(a[:rank]);  k_rope = RoPE(a[rank:])       one rope key, all heads
    k_h = (W_UK_h c) ‖ k_rope  [nope + rope];  v_h = W_UV_h c  [v]
    s_h = (q_nope_h ‖ RoPE(q_rope_h)) . k_h * (nope + rope) ** -0.5
    x = x + Wo concat_h(causal softmax(s_h) v_h)

The EXPANDED form: every head's keys (192 wide) and values (128 wide) are
built from the latents, where the program attends in the latent space with
W_UK folded into the query and W_UV applied after (the absorbed form) over
a cache of ``c ‖ k_rope``. RoPE rotates the published interleaved pairs
``(x[2i], x[2i+1])`` at ``theta ** (-2i / rope)`` and leaves them in place.

    layers < first_k_dense_replace:  x = x + SwiGLU(n2(x))
    others: z = n2(x);  s = sigmoid(z Wr)               float32
            E = the num_experts_per_tok largest (s + b)  (n_group 1)
            w_e = routed_scaling_factor * s_e / (sum_E s + 1e-20)
            x = x + sum_E w_e SwiGLU_e(z) + SwiGLU_shared(z)

Every token is put through EVERY routed expert in turn and keeps the result
only where it selected that expert: 128 / 6 times the work, and no way to
get the routing wrong. Attention runs one head at a time in blocks of
queries, so a 16k-token sequence needs [block, S] scores and never
[H, S, S]. Norm gains are applied in float32 here, where the program rounds
the normalised activations to bf16 first.
"""
from __future__ import annotations

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


class DeepseekV3Decoder:
    def __init__(self, model: dict):
        self.m = model

    # -- building blocks ---------------------------------------------------

    def _norm(self, x, g):
        import jax.numpy as jnp
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + self.m["rms_norm_eps"]) * g

    def _rope(self, x, pos):
        """x [S, rope], pos [S]: pairs (x[2i], x[2i+1]), rotated in place."""
        import jax.numpy as jnp
        half = x.shape[-1] // 2
        inv = float(self.m["rope_theta"]) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a, b = x[:, 0::2], x[:, 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)

    def _attention(self, h, p):
        """h [S, hidden] (normed) -> [S, H * v]: one head at a time, its
        queries in blocks."""
        import jax
        import jax.numpy as jnp
        m = self.m
        s = h.shape[0]
        nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
        rank, qk = m["kv_lora_rank"], nope + rope
        f32 = lambda w: w.astype(jnp.float32)           # noqa: E731
        pos = jnp.arange(s)
        a = h @ f32(p["wkv_a"])
        c = self._norm(a[:, :rank], f32(p["kv_norm"]))
        k_rope = self._rope(a[:, rank:], pos)
        block = next(b for b in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                     if s % b == 0)

        def head(i):
            q = h @ f32(jax.lax.dynamic_slice_in_dim(p["wq"], i * qk, qk, 1))
            q = jnp.concatenate([q[:, :nope], self._rope(q[:, nope:], pos)],
                                axis=-1)
            k = jnp.concatenate([c @ f32(p["w_uk"][i]).T, k_rope], axis=-1)
            v = c @ f32(p["w_uv"][i])

            def rows(j):
                q_pos = j * block + jnp.arange(block)
                scores = jax.lax.dynamic_slice_in_dim(
                    q, j * block, block) @ k.T / jnp.sqrt(float(qk))
                scores = jnp.where(pos[None, :] <= q_pos[:, None], scores,
                                   -jnp.inf)
                return jax.nn.softmax(scores, axis=-1) @ v
            return jax.lax.map(rows, jnp.arange(s // block)).reshape(s, -1)
        out = jax.lax.map(head, jnp.arange(m["num_attention_heads"]))
        return out.transpose(1, 0, 2).reshape(s, -1)

    @staticmethod
    def _swiglu(z, gate, up, down):
        import jax
        return (jax.nn.silu(z @ gate) * (z @ up)) @ down

    def routing(self, z, w_router, bias):
        """z [S, hidden] -> (weights [S, k], experts [S, k])."""
        import jax
        import jax.numpy as jnp
        scores = jax.nn.sigmoid(z @ w_router.astype(jnp.float32))
        _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                 self.m["num_experts_per_tok"])
        top_s = jnp.take_along_axis(scores, top_e, axis=-1)
        weights = self.m["routed_scaling_factor"] * top_s / (
            top_s.sum(-1, keepdims=True) + 1e-20)
        return weights, top_e

    def _moe(self, z, p, stacks, layer):
        import jax
        import jax.numpy as jnp
        n_exp = self.m["n_routed_experts"]
        weights, top_e = self.routing(z, p["w_router"], p["router_bias"])
        # [S, E]: w_e where the token selected expert e, else 0
        weight = (jax.nn.one_hot(top_e, n_exp, dtype=jnp.float32)
                  * weights[..., None]).sum(1)

        def expert(acc, e):
            wg, wu, wd = (stacks[n][layer, e].astype(jnp.float32)
                          for n in _EXPERT_WEIGHTS)
            return acc + weight[:, e, None] * self._swiglu(z, wg, wu, wd), None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(z), jnp.arange(n_exp))
        f32 = lambda w: w.astype(jnp.float32)           # noqa: E731
        return out + self._swiglu(z, f32(p["ws_gate"]), f32(p["ws_up"]),
                                  f32(p["ws_down"]))

    def _attn_half(self, x, p):
        import jax.numpy as jnp
        f32 = lambda w: w.astype(jnp.float32)           # noqa: E731
        x = x + self._attention(self._norm(x, f32(p["attn_norm"])),
                                p) @ f32(p["wo"])
        return x, self._norm(x, f32(p["mlp_norm"]))

    # -- forward -----------------------------------------------------------

    def hidden(self, params: dict, tokens):
        """tokens [S] int32 -> the final norm's output [S, hidden]."""
        import jax
        import jax.numpy as jnp
        dense, moe = params["dense_layers"], params["layers"]

        def dense_layer(x, i):
            p = {n: a[i] for n, a in dense.items()}
            x, z = self._attn_half(x, p)
            return x + self._swiglu(z, *(p[n].astype(jnp.float32)
                                         for n in _EXPERT_WEIGHTS)), None

        def moe_layer(x, i):
            # the experts' stacks are indexed [layer, expert] where they
            # are used, one expert's float32 copy at a time
            p = {n: a[i] for n, a in moe.items() if n not in _EXPERT_WEIGHTS}
            x, z = self._attn_half(x, p)
            return x + self._moe(z, p, moe, i), None
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            x, _ = jax.lax.scan(dense_layer, x,
                                jnp.arange(dense["attn_norm"].shape[0]))
            x, _ = jax.lax.scan(moe_layer, x,
                                jnp.arange(moe["attn_norm"].shape[0]))
            return self._norm(x, params["final_norm"].astype(jnp.float32))

    def head(self, params: dict, x):
        """[..., hidden] -> logits [..., V] float32, the vocabulary in
        slices (all of a 128k-row head in float32 is a gigabyte)."""
        import jax
        import jax.numpy as jnp
        w = params["lm_head"]
        v = w.shape[1]
        width = next(c for c in range(min(v, 16384), 0, -1) if v % c == 0)
        with jax.default_matmul_precision("highest"):
            parts = jax.lax.map(
                lambda i: x @ jax.lax.dynamic_slice_in_dim(
                    w, i * width, width, 1).astype(jnp.float32),
                jnp.arange(v // width))
        return jnp.moveaxis(parts, 0, -2).reshape(x.shape[:-1] + (v,))

    def logits(self, params: dict, tokens):
        """tokens [S] int32 -> logits [S, V] float32 of one sequence."""
        return self.head(params, self.hidden(params, tokens))
