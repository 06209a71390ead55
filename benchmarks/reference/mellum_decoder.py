"""Plain reference of Mellum2-12B-A2.5B's decoder (``model_type: mellum``,
written from the keys of its published ``config.json``): straightforward
``jax.numpy`` in float32 at the highest matmul precision, no kernels, no
cache, no batching, no grouping of tokens by expert. Independent of
``ray_tpu/models/llama.py``; it reads only that module's parameter names.

For hidden ``x`` at position ``i`` of a layer of kind ``layer_types[l]``:

    h = x + Wo . Attn(RoPE_kind(Wq n1(x)), RoPE_kind(Wk n1(x)), Wv n1(x))
    y = h + sum_{e in top8(p)} (p_e / sum_top8 p) . Wd_e (silu(Wg_e n2(h)) * Wu_e n2(h))
    p = softmax_64(Wr n2(h))    in float32
    n(x) = x / sqrt(mean(x^2) + eps) * g

Heads are ``head_dim`` wide (128, where hidden / heads is 72), 8 query
heads a KV head, scale ``head_dim ** -0.5``. Key ``j`` is seen iff
``j <= i`` and, in a ``sliding_attention`` layer, ``i - j <
sliding_window``: the mask is built from the positions. RoPE rotates the
two halves of a head. ``rope_parameters`` gives each kind its own: a sliding
layer the plain frequencies ``theta ** (-2k / head_dim)``; a full layer
YaRN's — a frequency that turns more than ``beta_fast`` times over
``original_max_position_embeddings`` keeps its value, one that turns fewer
than ``beta_slow`` times is divided by ``factor``, a linear ramp over the
indices between — with cos and sin multiplied by ``attention_factor``.

Every token is put through EVERY expert in turn and keeps the result only
where it selected that expert (a weight of p_e / sum, else 0).

Departures from the source, all listed under ``assumed`` in the
configuration's file: no norm on q and k (the config has no key for one);
the MTP head its description mentions is left out (no key); ``layer_types``
alone decides a layer's kind (``max_window_layers`` 0 and
``use_sliding_window`` say nothing more); ``intermediate_size`` is used by
no layer (``mlp_layer_types`` is all ``sparse``). Norm gains are applied in
float32 here, where the program rounds the normalised activations to bf16
first.
"""
from __future__ import annotations

import math

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


class MellumDecoder:
    def __init__(self, model: dict, **control):
        """``control``: what the reference check's controls change —
        ``window=False`` computes the sliding layers without their window,
        ``yarn_factor=False`` the full layers without ``attention_factor``,
        ``round_to="float8_e4m3fn"`` rounds every weight and every
        normed activation that enters a projection to that type (3
        mantissa bits: the nearest precision below the bf16 the
        configuration states). The first and the last must FAIL the check
        against the program; PERF.md §6 has the readings."""
        self.m = model
        self.hd = model["head_dim"]
        self.window = control.get("window", True)
        self.yarn_factor = control.get("yarn_factor", True)
        self.round_to = control.get("round_to")

    def _r(self, x):
        """float32 ``x``, through ``round_to`` where a control sets it."""
        import jax.numpy as jnp
        x = x.astype(jnp.float32)
        return x if self.round_to is None else x.astype(
            jnp.dtype(self.round_to)).astype(jnp.float32)

    # -- building blocks ---------------------------------------------------

    def _norm(self, x, g):
        import jax.numpy as jnp
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + self.m["rms_norm_eps"]) * g

    def inv_freq(self, kind: str):
        """([head_dim / 2] inverse frequencies, factor on cos and sin) of
        ``rope_parameters[kind]``."""
        import numpy as np

        from ..models.mellum import rope_parameters
        r = rope_parameters(self.m)[kind]
        theta, d = float(r["rope_theta"]), self.hd
        plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        if r["rope_type"] == "default":
            return plain, 1.0
        if r["rope_type"] != "yarn":
            raise ValueError(f"rope_type {r['rope_type']!r}")
        orig = r["original_max_position_embeddings"]

        def index_of(turns):
            # the index whose frequency turns ``turns`` times over orig
            return d * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))
        low = max(math.floor(index_of(r["beta_fast"])), 0)
        high = min(math.ceil(index_of(r["beta_slow"])), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        factor = r.get("attention_factor") or (
            0.1 * math.log(r["factor"]) + 1.0)
        return (plain / r["factor"] * ramp + plain * (1.0 - ramp),
                factor if self.yarn_factor else 1.0)

    def _rope(self, x, pos, kind: str):
        """x [S, H, D], pos [S]."""
        import jax.numpy as jnp
        half = self.hd // 2
        inv, factor = self.inv_freq(kind)
        ang = pos[:, None].astype(jnp.float32) * jnp.asarray(
            inv, jnp.float32)[None, :]
        cos = (jnp.cos(ang) * factor)[:, None, :]
        sin = (jnp.sin(ang) * factor)[:, None, :]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def _attend(self, q, k, v, kind: str):
        """q [S, H, D], k and v [S, KVH, D] -> [S, H * D]: one KV head
        with its group of query heads at a time, the queries in blocks,
        the mask from the positions."""
        import jax
        import jax.numpy as jnp
        s, nh, hd = q.shape
        nkv = k.shape[1]
        pos = jnp.arange(s)
        sliding = kind == "sliding_attention" and self.window
        block = next(b for b in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                     if s % b == 0)

        def group(qkv):
            qg, kg, vg = qkv                    # [G, S, D], [S, D], [S, D]

            def rows(j):
                i = (j * block + jnp.arange(block))[:, None]
                seen = pos[None, :] <= i
                if sliding:
                    seen &= i - pos[None, :] < self.m["sliding_window"]
                scores = jnp.einsum(
                    "gqd,kd->gqk", jax.lax.dynamic_slice_in_dim(
                        qg, j * block, block, 1), kg) / jnp.sqrt(float(hd))
                scores = jnp.where(seen[None], scores, -jnp.inf)
                return jnp.einsum("gqk,kd->gqd",
                                  jax.nn.softmax(scores, axis=-1), vg)
            out = jax.lax.map(rows, jnp.arange(s // block))  # [S/b,G,b,D]
            return out.transpose(1, 0, 2, 3).reshape(-1, s, hd)
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
        if self.m["norm_topk_prob"]:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        return top_p, top_e

    def _moe(self, h, p, layer):
        import jax
        import jax.numpy as jnp
        n_exp = self.m["num_experts"]
        top_p, top_e = self.routing(h, p["w_router"])
        # [S, E]: the weight where the token selected expert e, else 0
        weight = (jax.nn.one_hot(top_e, n_exp, dtype=jnp.float32)
                  * top_p[..., None]).sum(1)

        def expert(acc, e):
            wg, wu, wd = (self._r(p[n][layer, e]) for n in _EXPERT_WEIGHTS)
            y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return acc + weight[:, e, None] * y, None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(n_exp))
        return out

    def _layer(self, x, p, layer, kind: str):
        import jax.numpy as jnp
        m, hd = self.m, self.hd
        nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
        s = x.shape[0]
        f32 = lambda w: w.astype(jnp.float32)           # noqa: E731
        pos = jnp.arange(s)
        h = self._r(self._norm(x, f32(p["attn_norm"])))
        q = self._rope((h @ self._r(p["wq"])).reshape(s, nh, hd), pos, kind)
        k = self._rope((h @ self._r(p["wk"])).reshape(s, nkv, hd), pos, kind)
        v = (h @ self._r(p["wv"])).reshape(s, nkv, hd)
        x = x + self._r(self._attend(q, k, v, kind)) @ self._r(p["wo"])
        return x + self._moe(
            self._r(self._norm(x, f32(p["mlp_norm"]))), p, layer)

    # -- forward -------------------------------------------------------------

    def hidden(self, params: dict, tokens):
        """tokens [S] int32 -> the final norm's output [S, hidden]."""
        import jax
        import jax.numpy as jnp
        stacks = params["layers"]
        kinds = self.m["layer_types"][:self.m["num_hidden_layers"]]
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            # the layers in turn, a Python loop: a layer's kind is a
            # string of the config, not a number a scan could carry
            for i, kind in enumerate(kinds):
                p = {n: a if n in _EXPERT_WEIGHTS else a[i]
                     for n, a in stacks.items()}
                x = jax.checkpoint(
                    lambda x, p, i=i, kind=kind: self._layer(x, p, i, kind)
                )(x, p)
            return self._norm(x, params["final_norm"].astype(jnp.float32))

    def head(self, params: dict, x):
        """[..., hidden] -> logits [..., V] float32."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            return self._r(x) @ self._r(params["lm_head"])

    def logits(self, params: dict, tokens):
        """tokens [S] int32 -> logits [S, V] float32 of one sequence."""
        return self.head(params, self.hidden(params, tokens))
