"""Plain reference of Qwen3-Next's decoder (``model_type: qwen3_next``,
written from the keys of its published ``config.json`` and the layer
equations of transformers' ``modeling_qwen3_next.py``): straightforward
``jax.numpy`` in float32 at the highest matmul precision, the recurrence
as a ``lax.scan`` over single tokens, no kernels, no cache, no batching,
no grouping of tokens by expert. Independent of
``ray_tpu/models/qwen3_next.py``; it reads only that module's parameter
names and the layout of its projections' rows.

For hidden ``x`` of a layer ``i`` (``n(x; w) = x / sqrt(mean(x^2) + eps) *
(1 + w)``, the gain zero-centred):

    full layer, (i + 1) % full_attention_interval == 0:
        [q | gate] = Wq n(x) per head (2 x head_dim); k = Wk n(x); v = Wv n(x)
        q = n(q; q_norm), k = n(k; k_norm) per head; RoPE (rope_theta) on
        the first partial_rotary_factor x head_dim dims, by halves
        h = x + Wo ((softmax(q k^T / sqrt(head_dim)) v) * sigmoid(gate))
    GDN layer:
        [q k v z] = W_qkvz n(x), grouped by key head: (q 128 | k 128 |
        v 2 x 128 | z 2 x 128); [b a] = W_ba n(x), (b 2 | a 2) a key head
        [q | k | v] through a causal depthwise convolution of
        linear_conv_kernel_dim (no bias), then SiLU
        q, k L2-normalised (eps 1e-6), q / sqrt(128); a key head serves
        linear_num_value_heads / linear_num_key_heads value heads
        beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias)
        per value head, S = 0 at the sequence's start, token by token:
            S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T
            o_t = S^T q_t
        h = x + W_out (w * o / sqrt(mean(o^2) + eps) * silu(z))  (w as is)
    every layer:
        p = softmax(Wr n(h)) over all ``experts_routed``; E = top
        num_experts_per_tok, p renormalised over E
        y = h + sum_{e in E, e held} p_e SwiGLU_e(n(h))
              + sigmoid(w_sg n(h)) SwiGLU_shared(n(h))

Every token is put through EVERY held expert in turn and keeps the result
where it selected that expert. ``experts_held`` = [lo, hi) of the
``experts_routed`` (512): this chip's share of a layer's experts; what the
absent experts would add is left out, as in the program. The vocabulary is
the configuration's slice. Long sequences go through the per-token parts
in blocks of tokens, so that the float32 temporaries fit beside a full
chip.

Departures from ``modeling_qwen3_next.py``, each listed under ``assumed``
in the configuration's file: multi-token prediction is left out; the
state and its snapshots are float32 (transformers keeps the recurrent
state in the activations' type unless asked otherwise); the router's
weights and softmax are float32; ``a_log`` / ``dt_bias`` are drawn as
ray_tpu/models/qwen3_next.py ``init`` says. Norm gains are applied in
float32 here, where the program rounds the normalised activations to
bf16 first.

``states`` gives each GDN layer's state and convolution tail behind a
sequence's last token: what the program's snapshot at that boundary must
hold (``benchmarks/serve_app_hybrid.py`` compares them).

Two controls, given to the constructor (``model["reference_control"]``,
which the check fills from its spec): ``round_to="float8_e4m3fn"`` fails
the check's share of served tokens (0.60-0.68 against the program's
0.91-0.99) and ``state_dtype="bfloat16"`` fails its comparison of the
state — and NOT the share (0.953: a state in bf16 moves the logits by
less than the router's flips do). PERF.md §6 has the readings.
"""
from __future__ import annotations

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
_BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


class Qwen3NextDecoder:
    def __init__(self, model: dict, **control):
        """``control`` (over ``model["reference_control"]``):
        ``state_dtype="bfloat16"`` keeps the recurrent state in that type
        (rounded after every token), ``round_to="float8_e4m3fn"`` rounds
        every weight and every normed activation that enters a projection
        to that type (3 mantissa bits: the nearest precision below the
        bf16 the configuration states)."""
        control = {**(model.get("reference_control") or {}), **control}
        self.m = model
        self.hd = model["head_dim"]
        self.rot = int(model["partial_rotary_factor"] * self.hd)
        self.nk, self.nv = (model["linear_num_key_heads"],
                            model["linear_num_value_heads"])
        self.dk, self.dv = (model["linear_key_head_dim"],
                            model["linear_value_head_dim"])
        self.held = tuple(model.get("experts_held")
                          or (0, model["num_experts"]))
        self.routed = model.get("experts_routed") or model["num_experts"]
        self.round_to = control.get("round_to")
        self.state_dtype = control.get("state_dtype")

    def full(self, layer: int) -> bool:
        return (layer + 1) % self.m["full_attention_interval"] == 0

    def _r(self, x):
        """float32 ``x``, through ``round_to`` where a control sets it."""
        import jax.numpy as jnp
        x = x.astype(jnp.float32)
        return x if self.round_to is None else x.astype(
            jnp.dtype(self.round_to)).astype(jnp.float32)

    # -- building blocks ---------------------------------------------------

    def _rms(self, x):
        import jax.numpy as jnp
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + self.m["rms_norm_eps"])

    def _norm(self, x, w):
        """Zero-centred gain."""
        import jax.numpy as jnp
        return self._rms(x) * (1.0 + w.astype(jnp.float32))

    @staticmethod
    def _in_blocks(fn, *xs, largest: int = _BLOCKS[0]):
        """``fn`` over blocks of the leading (token) axis, results put
        back together."""
        import jax
        s = xs[0].shape[0]
        b = next(b for b in _BLOCKS if b <= largest and s % b == 0)
        out = jax.lax.map(lambda a: fn(*a), tuple(
            x.reshape((s // b, b) + x.shape[1:]) for x in xs))
        return jax.tree.map(lambda y: y.reshape((s,) + y.shape[2:]), out)

    def _rope(self, x, pos):
        """x [S, H, D]: the first ``rot`` dims rotated by halves."""
        import jax.numpy as jnp
        rot = self.rot
        inv = float(self.m["rope_theta"]) ** (
            -jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
        ang = pos[:, None].astype(jnp.float32) * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        a, b = x[..., :rot // 2], x[..., rot // 2:rot]
        return jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], -1)

    def _full_layer(self, x, p):
        """Keys and values of every token first; then the queries a block
        at a time — projected, normed, rotated, attended (one KV head with
        its group of query heads at a time), gated and projected out — so
        that no [S, heads x 2 x head_dim] array is ever whole."""
        import jax
        import jax.numpy as jnp
        m, hd = self.m, self.hd
        nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
        s = x.shape[0]
        wq, wk, wv, wo = (self._r(p[n]) for n in ("wq", "wk", "wv", "wo"))
        h = self._r(self._norm(x, p["attn_norm"]))
        pos = jnp.arange(s)
        k, v = self._in_blocks(lambda hb: (hb @ wk, hb @ wv), h)
        k = self._rope(self._norm(k.reshape(s, nkv, hd), p["k_norm"]), pos)
        kt, vt = k.transpose(1, 0, 2), v.reshape(s, nkv, hd).transpose(
            1, 0, 2)                                    # [KVH, S, D]

        def queries(hb, at):                # [b, hidden], [b]
            b = hb.shape[0]
            qg = (hb @ wq).reshape(b, nh, 2 * hd)
            q = self._rope(self._norm(qg[..., :hd], p["q_norm"]), at)
            seen = pos[None, :] <= at[:, None]          # [b, S]

            def group(a):
                qh, kh, vh = a              # [b, G, D], [S, D], [S, D]
                scores = jnp.einsum("qgd,kd->gqk", qh, kh) / jnp.sqrt(
                    float(hd))
                scores = jnp.where(seen[None], scores, -jnp.inf)
                return jnp.einsum("gqk,kd->qgd",
                                  jax.nn.softmax(scores, axis=-1), vh)
            attn = jax.lax.map(group, (
                q.reshape(b, nkv, nh // nkv, hd).transpose(1, 0, 2, 3),
                kt, vt))                                # [KVH, b, G, D]
            attn = attn.transpose(1, 0, 2, 3).reshape(b, nh, hd)
            gated = self._r(attn * jax.nn.sigmoid(qg[..., hd:]))
            return gated.reshape(b, -1) @ wo
        return x + self._in_blocks(queries, h, pos, largest=256)

    def _gdn_layer(self, x, p):
        import jax
        import jax.numpy as jnp
        nk, nv, dk, dv = self.nk, self.nv, self.dk, self.dv
        r = nv // nk
        width = self.m["linear_conv_kernel_dim"]
        f32 = jnp.float32
        w_qkvz, w_ba, w_out = (self._r(p[n]) for n in (
            "w_qkvz", "w_ba", "w_out"))
        conv_w = p["conv_w"].astype(f32)                    # [W, ch]
        a_rate = jnp.exp(p["a_log"].astype(f32))
        h = self._r(self._norm(x, p["attn_norm"]))
        s = x.shape[0]
        b = next(b for b in _BLOCKS if s % b == 0)

        def l2(t):
            return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        def token(state, xs):
            qt, kt, vt, gt, bt = xs            # [nv, dk] x 2, [nv, dv], [nv]
            state = state * jnp.exp(gt)[:, None, None]
            u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, state))
            state = state + kt[:, :, None] * u[:, None, :]
            if self.state_dtype is not None:
                # an explicit rounding: the compiler drops a convert there
                # and back (xla_allow_excess_precision), and did — the
                # first control read the correct program's numbers
                info = jnp.finfo(jnp.dtype(self.state_dtype))
                state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
            return state, jnp.einsum("hk,hkv->hv", qt, state)

        def block(carry, hb):
            state, tail = carry                 # [nv, dk, dv], [W - 1, ch]
            mixed = (hb @ w_qkvz).reshape(b, nk, 2 * dk + 2 * r * dv)
            ba = (hb @ w_ba).reshape(b, nk, 2 * r)
            qkv = jnp.concatenate([
                mixed[..., :dk].reshape(b, -1),
                mixed[..., dk:2 * dk].reshape(b, -1),
                mixed[..., 2 * dk:2 * dk + r * dv].reshape(b, -1)], -1)
            z = mixed[..., 2 * dk + r * dv:].reshape(b, nv, dv)
            ext = jnp.concatenate([tail, qkv], 0)
            conv = jax.nn.silu(sum(ext[j:j + b] * conv_w[j]
                                   for j in range(width)))
            q = l2(conv[:, :nk * dk].reshape(b, nk, dk)) / jnp.sqrt(float(dk))
            k = l2(conv[:, nk * dk:2 * nk * dk].reshape(b, nk, dk))
            v = conv[:, 2 * nk * dk:].reshape(b, nv, dv)
            beta = jax.nn.sigmoid(ba[..., :r].reshape(b, nv))
            g = -a_rate * jax.nn.softplus(
                ba[..., r:].reshape(b, nv) + p["dt_bias"].astype(f32))
            state, o = jax.lax.scan(token, state, (
                jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, g,
                beta))
            y = p["gdn_norm"].astype(f32) * self._rms(o) * jax.nn.silu(z)
            return (state, ext[b:]), self._r(y).reshape(b, -1) @ w_out
        init = (jnp.zeros((nv, dk, dv), f32),
                jnp.zeros((width - 1, conv_w.shape[1]), f32))
        end, out = jax.lax.scan(block, init, h.reshape(s // b, b, -1))
        return x + out.reshape(s, -1), end

    def routing(self, z, w_router):
        """z [S, hidden] -> (weights [S, k], experts [S, k])."""
        import jax
        import jax.numpy as jnp
        probs = jax.nn.softmax(z @ w_router.astype(jnp.float32), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, self.m["num_experts_per_tok"])
        return top_p / top_p.sum(-1, keepdims=True), top_e

    def moe(self, x, p, layer, held=None, shared=True):
        """The layer's second half without its residual, over the experts
        ``held`` = [lo, hi) (default: the configuration's share), whose
        weights are rows lo - own_lo .. of ``p``'s stacks; ``shared``:
        with the shared expert."""
        import jax
        import jax.numpy as jnp
        lo, hi = held or self.held
        z = self._r(self._norm(x, p["mlp_norm"]))
        top_p, top_e = self.routing(z, p["w_router"])
        weight = (jax.nn.one_hot(top_e, self.routed, dtype=jnp.float32)
                  * top_p[..., None]).sum(1)                 # [S, routed]

        def expert(acc, e):
            wg, wu, wd = (self._r(p[n][layer, e - self.held[0]])
                          for n in _EXPERT_WEIGHTS)
            y = (jax.nn.silu(z @ wg) * (z @ wu)) @ wd
            return acc + jnp.take(weight, e, axis=1)[:, None] * y, None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(lo, hi))
        if shared:
            sg, su, sd = (self._r(p[n]) for n in (
                "ws_gate", "ws_up", "ws_down"))
            out = out + jax.nn.sigmoid(z @ self._r(p["w_sg"])) * (
                (jax.nn.silu(z @ sg) * (z @ su)) @ sd)
        return out

    # -- forward -------------------------------------------------------------

    def layer_params(self, params: dict, layer: int) -> dict:
        kind = "full_layers" if self.full(layer) else "gdn_layers"
        own = sum(self.full(i) == self.full(layer) for i in range(layer))
        p = {n: a[own] for n, a in params[kind].items()}
        p.update({n: a if n in _EXPERT_WEIGHTS else a[layer]
                  for n, a in params["moe"].items()})
        return p

    def _layers(self, params: dict, tokens):
        """tokens [S] int32 -> (the last layer's output [S, hidden], each
        GDN layer's (state [nv, dk, dv], convolution tail [W - 1, ch])
        behind token S - 1)."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            x, ends = params["embed"][tokens].astype(jnp.float32), []
            for i in range(self.m["num_hidden_layers"]):
                p = self.layer_params(params, i)
                if self.full(i):
                    x = self._full_layer(x, p)
                else:
                    x, end = self._gdn_layer(x, p)
                    ends.append(end)
                x = x + self._in_blocks(
                    lambda xb, p=p, i=i: self.moe(xb, p, i), x)
                # one layer at a time: left free, the compiler keeps
                # ~0.5 GB a layer of a 16k-token sequence alive to the end
                x = jax.lax.optimization_barrier(x)
            return x, ends

    def hidden(self, params: dict, tokens):
        """tokens [S] int32 -> the final norm's output [S, hidden]."""
        import jax
        with jax.default_matmul_precision("highest"):
            return self._norm(self._layers(params, tokens)[0],
                              params["final_norm"])

    def states(self, params: dict, tokens):
        """tokens [S] int32 -> [(state, convolution tail)] a GDN layer,
        float32, as a sequence of exactly these tokens leaves them."""
        return self._layers(params, tokens)[1]

    def head(self, params: dict, x):
        """[..., hidden] -> logits [..., V] float32."""
        import jax
        with jax.default_matmul_precision("highest"):
            return self._r(x) @ self._r(params["lm_head"])

    def logits(self, params: dict, tokens):
        """tokens [S] int32 -> logits [S, V] float32 of one sequence."""
        return self.head(params, self.hidden(params, tokens))
