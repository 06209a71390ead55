"""Plain reference of the dense pre-norm decoder (Mistral-7B-v0.3's block):
straightforward ``jax.numpy`` in float32 at the highest matmul precision,
no kernels, no cache, no batching, weights upcast one layer at a time.
Written from the published description and independent of
``ray_tpu/models/llama.py``; it reads only that module's parameter names.

    h   = x + Wo . Attn(RoPE(Wq n1(x)), RoPE(Wk n1(x)), Wv n1(x))
    out = h + Wdown . (silu(Wgate n2(h)) * (Wup n2(h)))
    n(x) = x / sqrt(mean(x^2) + eps) * g

RoPE rotates the two halves of a head (the Hugging Face layout, which the
published checkpoints use); query head h attends KV head h // (H / KVH).
Departure from the description: none. Norm gains are applied in float32
here, where the program rounds the normalised activations to bf16 first.
"""
from __future__ import annotations


class DenseDecoder:
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
        inv = self.m["rope_theta"] ** (
            -jnp.arange(half, dtype=jnp.float32) * 2.0 / self.hd)
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _attend(self, q, k, v):
        """q [S, H, D], k and v [S, KVH, D] -> [S, H * D], causal. One KV
        head with its group of query heads at a time: the scores of all
        heads at once are 2 GB in float32 at 4k tokens."""
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
        # query head h attends KV head h // (H / KVH)
        out = jax.lax.map(group, (
            q.reshape(s, nkv, nh // nkv, hd).transpose(1, 2, 0, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        return out.transpose(2, 0, 1, 3).reshape(s, nh * hd)

    def _layer(self, x, p):
        import jax
        import jax.numpy as jnp
        m, hd = self.m, self.hd
        nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
        s = x.shape[0]
        f32 = lambda w: w.astype(jnp.float32)           # noqa: E731
        pos = jnp.arange(s)
        h = self._norm(x, f32(p["attn_norm"]))
        q = self._rope((h @ f32(p["wq"])).reshape(s, nh, hd), pos)
        k = self._rope((h @ f32(p["wk"])).reshape(s, nkv, hd), pos)
        v = (h @ f32(p["wv"])).reshape(s, nkv, hd)
        x = x + self._attend(q, k, v) @ f32(p["wo"])
        h = self._norm(x, f32(p["mlp_norm"]))
        gate = jax.nn.silu(h @ f32(p["w_gate"]))
        return x + (gate * (h @ f32(p["w_up"]))) @ f32(p["w_down"])

    # -- forward and loss --------------------------------------------------

    def hidden(self, params: dict, tokens):
        """tokens [S] int32 -> the final norm's output [S, hidden]."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            # checkpointed so that a gradient through this keeps one
            # layer's float32 weights at a time; the arithmetic is the same
            layer = jax.checkpoint(self._layer)
            x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None),
                                x, params["layers"])
            return self._norm(x, params["final_norm"].astype(jnp.float32))

    def head(self, params: dict, x):
        """[..., hidden] -> logits [..., V] float32 (a caller that needs
        few rows slices ``hidden``'s output first)."""
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
