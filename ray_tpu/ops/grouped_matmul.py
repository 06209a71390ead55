"""Grouped matmul for dropless mixture-of-experts (Pallas, TPU): rows that
are grouped by expert, each group multiplied by its own expert's weights.

The layout is what makes the kernel plain. `group_layout` gives every
expert a run of whole ROW TILES (``tile_rows`` rows; a group's last tile is
padded), so a tile belongs to exactly one expert and the kernel is an
ordinary tiled matmul whose weight block is picked per tile from a
scalar-prefetched ``tile_expert`` table — no group boundary ever falls
inside a tile, so there is no in-tile masking (the megablox kernel's
compact layout needs it):

* grid ``(row tile,)``; a weight block is the WHOLE expert, ``[K, N]``.
  The experts lie row-major, so a block is one contiguous run of ``K x
  N`` values whatever N is, the row tiles are swept once and the ``[tm,
  N]`` output tile is written once, lane-dense. A column slice ``[K,
  tn]`` is pieces a row's length apart and one more sweep of the row
  tiles, re-reading ``x``, a slice: inside OLMoE's decode program the
  fused call took 911 us as column halves and 737 whole (PERF.md section
  6, PR 54, has the sweep over all five served widths, blocks by rows of
  K included: the whole expert is fastest or tied everywhere).
  `_vmem_scope` says what the pipeline's two buffers of blocks need and
  refuses an expert they cannot hold;
* consecutive row tiles of one expert name the same weight block, which
  Pallas does not fetch again, so HBM traffic is the weights of the
  experts hit plus the (small) activations. At serving shapes that is
  the whole cost — 64 experts x 3 x [2048, 1024] are 805 MB a layer
  against a few MB of rows — so tiles are small (16-32 rows) and the
  padding rows they bring cost nothing that matters;
* the table is as long as the worst case (every group wasting a tile),
  and the tiles past the last live one are DEAD: their block indices are
  clamped to the last live tile's (nothing is fetched or written) and
  `pl.when` skips their body, so a dead step costs its launch alone;
* `grouped_swiglu` fuses the gate and up projections and the activation
  (``silu(x Wg) * (x Wu)``): both weight blocks stream in one sweep and
  the ``[rows, F]`` intermediates never touch HBM.

Off the TPU (and for every backward pass) the same layout runs through
``jax.lax.ragged_dot`` with the padded group sizes: that is the numerical
contract, the CPU fallback and the ``custom_vjp`` backward (a training
step's expert gradients are ragged_dot's own transposes; a Pallas
backward is not written yet — PERF.md §7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The compiler's default VMEM scope of a kernel, which a call whose buffers
# fit it leaves unstated, and the most a call may state. A stated scope is
# taken from what the program's other operations keep in VMEM around the
# call (kanana's prefill keeps a layer's expert rows there, 111.5 MiB of
# the chip's 128: they fit beside 16 MiB and not beside 32), so a call
# states what its buffers need and no more. 32 MiB holds two buffers of
# OLMoE's fused expert, 2 x [2048, 1024] bf16 = 8 MiB each, the largest
# served. `_VMEM_SPARE` is counted in for the compiler's own temporaries.
_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_SCOPE = 32 * 2 ** 20
_VMEM_SPARE = 2 ** 20

# the largest row tile: a group of this many rows reads its expert's weight
# block for a full tile of work (what the serving engine sizes a routed
# model's prefill dispatch by)
MAX_TILE_ROWS = 128


def tile_rows(assignments: int, experts: int) -> int:
    """Rows per tile: the power of two at or above twice the mean group
    (most groups then fit one tile, and the padding stays under the
    weights' cost), from 16 (a bf16 vreg's sublanes) to MAX_TILE_ROWS."""
    mean = max(1, assignments // max(experts, 1))
    return min(MAX_TILE_ROWS, max(16, 1 << (2 * mean - 1).bit_length()))


def num_tiles(assignments: int, experts: int, tm: int) -> int:
    """Static bound on the row tiles a layout can need: every non-empty
    group may waste up to ``tm - 1`` rows."""
    return max(1, (assignments + min(assignments, experts) * (tm - 1)) // tm)


def group_layout(expert_of, owned, experts: int, tm: int):
    """The tile-aligned layout of ``A`` assignments over ``experts`` groups.

    expert_of [A] int32 (values outside [0, experts) must have ``owned``
    false); owned [A] bool or None. Returns ``(row_of [A], padded_sizes
    [E], tile_expert [n_tiles], n_live [1])``: the padded row each
    assignment lands in (``n_tiles * tm``, out of range, when not owned),
    each group's size rounded up to whole tiles, the expert of each row
    tile, and how many tiles are live. Stable: assignments keep their
    order inside a group. No sort: the ranks are a cumulative sum of the
    one-hot assignment matrix, which at A x E of 512 x 64 is nothing."""
    a = expert_of.shape[0]
    n_tiles = num_tiles(a, experts, tm)
    safe = jnp.clip(expert_of, 0, experts - 1)
    onehot = expert_of[:, None] == jnp.arange(experts, dtype=jnp.int32)
    if owned is not None:
        onehot = onehot & owned[:, None]
    onehot = onehot.astype(jnp.int32)
    counts = onehot.sum(0)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0), safe[:, None],
                               1)[:, 0] - 1
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    row_of = (ends - padded)[safe] + rank
    if owned is not None:
        row_of = jnp.where(owned, row_of, n_tiles * tm)
    n_live = ends[-1] // tm
    first_row = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    tile_expert = (ends[None, :] <= first_row[:, None]).sum(1)
    # dead tiles name the last live tile's expert: no weight block moves
    last = tile_expert[jnp.maximum(n_live - 1, 0)]
    tile_expert = jnp.where(first_row < ends[-1], tile_expert, last)
    return (row_of.astype(jnp.int32), padded.astype(jnp.int32),
            jnp.minimum(tile_expert, experts - 1).astype(jnp.int32),
            jnp.reshape(n_live, (1,)).astype(jnp.int32))


def _vmem_scope(tm: int, k: int, n: int, operands: int,
                itemsize: int) -> int | None:
    """The VMEM scope a call states: None where its buffers fit the
    compiler's default, else what they need. The buffers are two (the
    pipeline's) of the ``operands`` whole-expert blocks ``[K, N]``, of the
    row tile ``[tm, K]`` and of the output tile ``[tm, N]``, and the
    float32 products ``[tm, N]`` an operand."""
    need = (2 * (operands * k * n + tm * k + tm * n) * itemsize
            + operands * tm * n * 4 + _VMEM_SPARE)
    if need > _VMEM_SCOPE:
        raise ValueError(
            f"grouped matmul: {operands} expert block(s) [{k}, {n}] of "
            f"{itemsize}-byte values under {tm}-row tiles need {need} "
            f"bytes of VMEM, over the {_VMEM_SCOPE} a call may state: an "
            "expert this large needs blocks by rows of K, which this "
            "kernel does not have")
    return None if need <= _VMEM_DEFAULT else need


def _gmm_kernel(te_ref, live_ref, layer_ref, x_ref, *refs):
    """One row tile: x [tm, K] times each operand's expert [K, N] in
    float32; ``refs`` are the weight blocks (two: gate and up, fused with
    the activation) and the output tile."""
    from jax.experimental import pallas as pl

    *w_refs, o_ref = refs

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        x = x_ref[...]
        parts = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
                 for w in w_refs]
        out = jax.nn.silu(parts[0]) * parts[1] if len(parts) == 2 \
            else parts[0]
        o_ref[...] = out.astype(o_ref.dtype)


# Jitted for the reason ops/ragged_paged_attention._ragged_call is: the
# serving paths unroll their layers in Python, and one shared function
# lowers the kernel body once per shape, not once per layer (the layer is
# a prefetched scalar, so ten layers share one lowering).
@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _gmm_call(x, weights, tile_expert, n_live, layer, *, tm: int,
              interpret: bool):
    """x [M, K] in the tile-aligned layout; weights: one [L, E, K, N]
    stack (x @ W[layer, e]) or two (silu(x @ Wg[layer, e]) * (x @
    Wu[layer, e])); layer [1] int32. Returns [M, N]; the rows of dead
    tiles are never written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = weights[0].shape[-1]
    scope = _vmem_scope(tm, k, n, len(weights), weights[0].dtype.itemsize)

    def live(i, live_ref):
        return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0))

    w_spec = pl.BlockSpec((None, None, k, n),
                          lambda i, te, lv, ly: (ly[0], te[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m // tm,),
        in_specs=[pl.BlockSpec((tm, k),
                               lambda i, te, lv, ly: (live(i, lv), 0)),
                  *[w_spec] * len(weights)],
        out_specs=pl.BlockSpec((tm, n),
                               lambda i, te, lv, ly: (live(i, lv), 0)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=scope),
        name="grouped_swiglu" if len(weights) == 2 else "grouped_matmul",
    )(tile_expert, n_live, layer, x, *weights)


def grouped_ffn_reference(x, w_gate, w_up, w_down, padded_sizes,
                          layer: int | None = None):
    """The expert SwiGLU on the tile-aligned layout through
    ``jax.lax.ragged_dot``: rows past the groups' total come out zero."""
    if layer is not None:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=padded_sizes)
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def _ffn_kernels(x, w_gate, w_up, w_down, tile_expert, n_live, tm, layer,
                 interpret):
    if layer is None:           # one layer's weights: a stack of one
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
    call = functools.partial(
        _gmm_call, tile_expert=tile_expert, n_live=n_live,
        layer=jnp.full((1,), layer or 0, jnp.int32), tm=tm,
        interpret=interpret)
    return call(call(x, (w_gate, w_up)), (w_down,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def grouped_ffn(x, w_gate, w_up, w_down, padded_sizes, tile_expert, n_live,
                tm: int, layer: int | None, interpret: bool):
    """Every expert's SwiGLU over its own rows: x [M, D] in `group_layout`'s
    order, weights [E, D, F] / [E, D, F] / [E, F, D] — or, with ``layer``
    (a Python int), the layers' stacks [L, E, ...], of which the kernels
    read that layer's blocks in place: a sliced stack cannot be a Pallas
    operand without XLA copying it first, 805 MB a layer at OLMoE's widths.
    Returns [M, D]; only the rows of live tiles mean anything.
    ``padded_sizes`` is what the backward's ragged_dot groups by."""
    return _ffn_kernels(x, w_gate, w_up, w_down, tile_expert, n_live, tm,
                        layer, interpret)


def _ffn_fwd(x, w_gate, w_up, w_down, padded_sizes, tile_expert, n_live,
             tm, layer, interpret):
    out = _ffn_kernels(x, w_gate, w_up, w_down, tile_expert, n_live, tm,
                       layer, interpret)
    return out, (x, w_gate, w_up, w_down, padded_sizes)


def _ffn_bwd(tm, layer, interpret, res, g):
    x, w_gate, w_up, w_down, padded_sizes = res
    _, vjp = jax.vjp(functools.partial(grouped_ffn_reference,
                                       padded_sizes=padded_sizes,
                                       layer=layer),
                     x, w_gate, w_up, w_down)
    return (*vjp(g), None, None, None)


grouped_ffn.defvjp(_ffn_fwd, _ffn_bwd)
