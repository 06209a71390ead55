"""Plain reference of Ling-3.0-flash's decoder (``model_type:
bailing_hybrid``), written from the keys of its published ``config.json``
and the layer equations of Kimi Linear (arXiv:2510.26692; the public
``flash-linear-attention`` KDA layer), DeepSeek-V2's latent attention
(arXiv:2405.04434) and the group-limited ``noaux_tc`` router as the
Ling-2.0 code (``BailingMoeV2``) publishes it: straightforward
``jax.numpy`` in float32 at the highest matmul precision, the recurrence as
a ``lax.scan`` over single tokens, latent attention in the EXPANDED form
(keys and values a head), no kernels, no cache, no batching, no grouping
of tokens by expert. Independent of ``ray_tpu/models/ling_hybrid.py``; it
reads only that module's parameter names.

For hidden ``x`` of layer ``i`` (``n(x; w) = x / sqrt(mean(x^2) + eps) *
w``):

    KDA layer, (i + 1) % layer_group_size != 0; H = num_attention_heads
    heads of head_dim keys and values (num_kv_heads_for_linear_attn 0):
        q = Wq n(x), k = Wk n(x), v = Wv n(x)
        q | k | v through a causal depthwise convolution of
        short_conv_kernel_size (no bias), then SiLU (linear_silu)
        q, k L2-normalised a head (eps 1e-6), q / sqrt(head_dim); no RoPE
        beta = sigmoid(Wb n(x))  [H];  a = Wf n(x)  [H, head_dim]
        g = kda_lower_bound * sigmoid(exp(A_log) (a + dt_bias))
        per head, S = 0 at the sequence's start, token by token:
            S <- Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t)
            S <- S + k_t u^T;  o_t = S^T q_t
        h = x + Wo (w_o * o / sqrt(mean(o^2) + eps) * sigmoid(Wg n(x)))
    MLA layer (q_lora_rank null):
        q = Wq n(x) -> [H, nope + rope];  a = Wkva n(x) -> [rank + rope]
        c = n(a[:rank]; kv_norm);  k_r = RoPE(a[rank:])    one rope key
        k_h = (W_UK_h c) ‖ k_r;  v_h = W_UV_h c
        s_h = (q_nope_h ‖ RoPE(q_rope_h)) . k_h * (nope + rope) ** -0.5
        o_h = causal softmax(s_h) v_h * sigmoid(w_h . n(x))   (head_wise)
        h = x + Wo concat_h(o_h)
        RoPE rotates the published interleaved pairs (x[2i], x[2i+1]) at
        rope_theta ** (-2i / rope) and leaves them in place
    layers < the dense prefix:  y = h + SwiGLU(n(h); intermediate_size)
    others: z = n(h);  s = sigmoid(Wr z);  s' = s + b
        n_group groups of consecutive experts; a group's score = the sum
        of its two largest s'; the topk_group best groups stay; E = the
        num_experts_per_tok largest s' among their experts
        w_e = routed_scaling_factor * s_e / (sum_E s + 1e-20)
        y = h + sum_{e in E, e held} w_e SwiGLU_e(z) + SwiGLU_shared(z)

Every token is put through EVERY held expert in turn and keeps the result
where it selected that expert. ``experts_held`` = [lo, hi) of the
``experts_routed`` (512): this chip's share of a layer's experts; what the
absent experts would add is left out, as in the program. The vocabulary is
the configuration's slice. Long sequences go through the per-token parts
in blocks of tokens, attention a head and a block of queries at a time,
so that the float32 temporaries fit beside a full chip.

Departures, each listed under ``assumed`` in the configuration's file: the
form of the decay (the config gives its bound, not its expression); the
gates' input and place; ``use_qk_norm`` taken as met by the norms the two
mixers have by construction; multi-token prediction left out; the state
and its snapshots float32; the router's weights and scores float32. Norm
gains are applied in float32 here, where the program rounds the normalised
activations to bf16 first.

``states`` gives each KDA layer's state and convolution tail behind a
sequence's last token: what the program's snapshot at that boundary must
hold (``benchmarks/serve_app_hybrid.py`` compares them).

Four controls, given to the constructor (``model["reference_control"]``,
which the check fills from its spec), each of which a correct program must
FAIL against: ``state_dtype="bfloat16"`` (the recurrent state rounded after
every token), ``decay="head_mean"`` (a token's decay averaged over a
head's channels: KDA computed as a gated delta rule with one decay a
head), ``groups=False`` (the group selection skipped: the top-k of all
experts) and ``round_to="float8_e4m3fn"`` (every weight and every normed
activation that enters a projection at 3 mantissa bits: the nearest
precision below the bf16 the configuration states). PERF.md §6 has the
readings.
"""
from __future__ import annotations

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
_BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


class LingHybridDecoder:
    def __init__(self, model: dict, **control):
        control = {**(model.get("reference_control") or {}), **control}
        self.m = model
        self.heads = model["num_attention_heads"]
        self.dk = self.dv = model["head_dim"]
        self.held = tuple(model.get("experts_held")
                          or (0, model["num_experts"]))
        self.routed = model.get("experts_routed") or model["num_experts"]
        self.n_dense = min(model["first_k_dense_replace"], model.get(
            "dense_layers_kept", model["first_k_dense_replace"]))
        self.round_to = control.get("round_to")
        self.state_dtype = control.get("state_dtype")
        self.decay = control.get("decay", "channel")
        self.groups = control.get("groups", True)

    def latent(self, layer: int) -> bool:
        return (layer + 1) % self.m["layer_group_size"] == 0

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
        import jax.numpy as jnp
        return self._rms(x) * w.astype(jnp.float32)

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

    @staticmethod
    def _swiglu(z, gate, up, down):
        import jax
        return (jax.nn.silu(z @ gate) * (z @ up)) @ down

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

    def _mla_layer(self, x, p):
        """One head at a time, its queries in blocks: [block, S] scores
        and never [H, S, S]."""
        import jax
        import jax.numpy as jnp
        m = self.m
        s = x.shape[0]
        nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
        rank, qk = m["kv_lora_rank"], nope + rope
        h = self._r(self._norm(x, p["attn_norm"]))
        pos = jnp.arange(s)
        a = h @ self._r(p["wkv_a"])
        c = self._r(self._norm(a[:, :rank], p["kv_norm"]))
        k_rope = self._rope(a[:, rank:], pos)
        gate = jax.nn.sigmoid(h @ self._r(p["w_hgate"]))        # [S, H]
        block = next(b for b in _BLOCKS[1:] if s % b == 0)

        def head(i):
            q = h @ self._r(jax.lax.dynamic_slice_in_dim(
                p["wq"], i * qk, qk, 1))
            q = jnp.concatenate([q[:, :nope], self._rope(q[:, nope:], pos)],
                                axis=-1)
            k = jnp.concatenate([c @ self._r(p["w_uk"][i]).T, k_rope],
                                axis=-1)
            v = c @ self._r(p["w_uv"][i])

            def rows(j):
                q_pos = j * block + jnp.arange(block)
                scores = jax.lax.dynamic_slice_in_dim(
                    q, j * block, block) @ k.T / jnp.sqrt(float(qk))
                scores = jnp.where(pos[None, :] <= q_pos[:, None], scores,
                                   -jnp.inf)
                return jax.nn.softmax(scores, axis=-1) @ v
            o = jax.lax.map(rows, jnp.arange(s // block)).reshape(s, -1)
            return o * jnp.take(gate, i, axis=1)[:, None]
        out = jax.lax.map(head, jnp.arange(self.heads))         # [H, S, v]
        out = self._r(out.transpose(1, 0, 2).reshape(s, -1))
        return x + self._in_blocks(lambda ob: ob @ self._r(p["wo"]), out)

    def _kda_layer(self, x, p):
        import jax
        import jax.numpy as jnp
        nh, dk, dv = self.heads, self.dk, self.dv
        width = self.m["short_conv_kernel_size"]
        bound = float(self.m["kda_lower_bound"])
        f32 = jnp.float32
        wq, wk, wv, w_f, w_g, w_b, wo = (self._r(p[n]) for n in (
            "wq", "wk", "wv", "w_f", "w_g", "w_b", "wo"))
        conv_w = p["conv_w"].astype(f32)                    # [W, ch]
        rate = jnp.exp(p["a_log"].astype(f32))              # [H]
        bias = p["dt_bias"].astype(f32)                     # [H, dk]
        h = self._r(self._norm(x, p["attn_norm"]))
        s = x.shape[0]
        b = next(b for b in _BLOCKS if s % b == 0)

        def l2(t):
            return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        def token(state, xs):
            qt, kt, vt, gt, bt = xs        # [H, dk] x 2, [H, dv], [H, dk], [H]
            state = state * jnp.exp(gt)[:, :, None]
            u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, state))
            state = state + kt[:, :, None] * u[:, None, :]
            if self.state_dtype is not None:
                # an explicit rounding: the compiler drops a convert there
                # and back (xla_allow_excess_precision)
                info = jnp.finfo(jnp.dtype(self.state_dtype))
                state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
            return state, jnp.einsum("hk,hkv->hv", qt, state)

        def block(carry, hb):
            state, tail = carry                 # [H, dk, dv], [W - 1, ch]
            ext = jnp.concatenate([tail, jnp.concatenate(
                [hb @ wq, hb @ wk, hb @ wv], -1)], 0)
            conv = jax.nn.silu(sum(ext[j:j + b] * conv_w[j]
                                   for j in range(width)))
            q = l2(conv[:, :nh * dk].reshape(b, nh, dk)) / jnp.sqrt(float(dk))
            k = l2(conv[:, nh * dk:2 * nh * dk].reshape(b, nh, dk))
            v = conv[:, 2 * nh * dk:].reshape(b, nh, dv)
            beta = jax.nn.sigmoid(hb @ w_b)                     # [b, H]
            g = bound * jax.nn.sigmoid(rate[:, None] * (
                (hb @ w_f).reshape(b, nh, dk) + bias))
            if self.decay == "head_mean":
                g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
            state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
            y = p["o_norm"].astype(f32) * self._rms(o) * jax.nn.sigmoid(
                (hb @ w_g).reshape(b, nh, dv))
            return (state, ext[b:]), self._r(y).reshape(b, -1) @ wo
        init = (jnp.zeros((nh, dk, dv), f32),
                jnp.zeros((width - 1, conv_w.shape[1]), f32))
        end, out = jax.lax.scan(block, init, h.reshape(s // b, b, -1))
        return x + out.reshape(s, -1), end

    def routing(self, z, w_router, bias):
        """z [S, hidden] -> (weights [S, k], experts [S, k])."""
        import jax
        import jax.numpy as jnp
        m = self.m
        scores = jax.nn.sigmoid(z @ w_router.astype(jnp.float32))
        choice = scores + bias.astype(jnp.float32)
        if self.groups and m["n_group"] > 1:
            grouped = choice.reshape(z.shape[0], m["n_group"], -1)
            best = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)  # [S, G]
            # a group's rank among the groups, 0 the best
            rank = jnp.argsort(jnp.argsort(-best, axis=-1), axis=-1)
            grouped = jnp.where((rank < m["topk_group"])[..., None],
                                grouped, -jnp.inf)
            choice = grouped.reshape(choice.shape)
        _, top_e = jax.lax.top_k(choice, m["num_experts_per_tok"])
        top_s = jnp.take_along_axis(scores, top_e, axis=-1)
        weights = m["routed_scaling_factor"] * top_s / (
            top_s.sum(-1, keepdims=True) + 1e-20)
        return weights, top_e

    def moe(self, x, p, layer, held=None, shared=True):
        """An expert layer's second half without its residual, over the
        experts ``held`` = [lo, hi) (default: the configuration's share),
        whose weights are rows lo - own_lo .. of ``p``'s stacks (``layer``
        counts among the expert layers); ``shared``: with the shared
        expert."""
        import jax
        import jax.numpy as jnp
        lo, hi = held or self.held
        z = self._r(self._norm(x, p["mlp_norm"]))
        top_w, top_e = self.routing(z, p["w_router"], p["router_bias"])
        weight = (jax.nn.one_hot(top_e, self.routed, dtype=jnp.float32)
                  * top_w[..., None]).sum(1)                 # [S, routed]

        def expert(acc, e):
            wg, wu, wd = (self._r(p[n][layer, e - self.held[0]])
                          for n in _EXPERT_WEIGHTS)
            return acc + jnp.take(weight, e, axis=1)[:, None] * self._swiglu(
                z, wg, wu, wd), None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(lo, hi))
        if shared:
            out = out + self._swiglu(z, *(self._r(p[n]) for n in (
                "ws_gate", "ws_up", "ws_down")))
        return out

    def dense(self, x, p):
        z = self._r(self._norm(x, p["mlp_norm"]))
        return self._swiglu(z, *(self._r(p[n]) for n in _EXPERT_WEIGHTS))

    # -- forward -------------------------------------------------------------

    def layer_params(self, params: dict, layer: int) -> dict:
        kind = "mla_layers" if self.latent(layer) else "kda_layers"
        own = sum(self.latent(i) == self.latent(layer) for i in range(layer))
        p = {n: a[own] for n, a in params[kind].items()}
        if layer < self.n_dense:
            p.update({n: a[layer] for n, a in params["dense_mlp"].items()})
        else:
            li = layer - self.n_dense
            p.update({n: a if n in _EXPERT_WEIGHTS else a[li]
                      for n, a in params["moe"].items()})
        return p

    def _layers(self, params: dict, tokens):
        """tokens [S] int32 -> (the last layer's output [S, hidden], each
        KDA layer's (state [H, dk, dv], convolution tail [W - 1, ch])
        behind token S - 1)."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            x, ends = params["embed"][tokens].astype(jnp.float32), []
            for i in range(self.m["num_hidden_layers"]):
                p = self.layer_params(params, i)
                if self.latent(i):
                    x = self._mla_layer(x, p)
                else:
                    x, end = self._kda_layer(x, p)
                    ends.append(end)
                if i < self.n_dense:
                    x = x + self._in_blocks(
                        lambda xb, p=p: self.dense(xb, p), x)
                else:
                    x = x + self._in_blocks(
                        lambda xb, p=p, li=i - self.n_dense:
                        self.moe(xb, p, li), x)
                # one layer at a time: left free, the compiler keeps a
                # layer's temporaries of a 16k-token sequence alive
                x = jax.lax.optimization_barrier(x)
            return x, ends

    def hidden(self, params: dict, tokens):
        """tokens [S] int32 -> the final norm's output [S, hidden]."""
        import jax
        with jax.default_matmul_precision("highest"):
            return self._norm(self._layers(params, tokens)[0],
                              params["final_norm"])

    def states(self, params: dict, tokens):
        """tokens [S] int32 -> [(state, convolution tail)] a KDA layer,
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
