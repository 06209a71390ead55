"""Logical-axis sharding rules → concrete NamedShardings.

The reference never does sharding math itself — it passes tensor/pipeline
degrees to external engines (vLLM: llm/_internal/serve/configs/
server_models.py:391-415) and wraps torch FSDP for sharded-DP
(train/torch/train_loop_utils.py `prepare_model`). Here sharding is
first-class: model code names its dimensions with *logical* axes and this
module maps them onto mesh axes, in the style of T5X/Flax partitioning rules.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import get_mesh, BATCH_AXES

# Default logical→mesh axis rules for transformer/CNN families.
# Each entry: (logical_axis, mesh axis or tuple of mesh axes or None).
# First rule whose mesh axes all exist in the mesh (and are unused so far in
# the same spec) wins.
LOGICAL_AXIS_RULES: tuple[tuple[str, object], ...] = (
    ("batch", ("dp", "fsdp")),
    ("sequence", "sp"),
    ("embed", "fsdp"),          # FSDP shards params along embed/feature dims
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("q_seq", "sp"),
    ("kv_seq", None),
    ("head_dim", None),
    # Vocab rows shard over (tp, fsdp): Megatron-style vocab-parallel
    # embedding. The gather from a row-sharded table partitions cleanly
    # (clamp+mask+psum over tp·fsdp) and its output inherits the *index*
    # sharding (batch over dp·fsdp) — no feature-dim→batch-dim reshard. The
    # old rule (embed dim over fsdp) made every embedding lookup flip a
    # feature-sharded gather output to batch-sharded, which XLA can only do
    # by involuntary full rematerialization (replicate + repartition), fwd
    # and bwd. On lm_head ("embed", "vocab") the embed dim claims fsdp
    # first, so logits stay tp-sharded exactly as before.
    ("vocab", ("tp", "fsdp")),
    ("expert", "ep"),
    ("stage", "pp"),
    ("channel", None),
    ("norm", None),
)


def logical_spec(logical_axes: Sequence[Optional[str]],
                 mesh=None,
                 rules=LOGICAL_AXIS_RULES) -> P:
    """Map a tuple of logical axis names (None = replicated) to a PartitionSpec.

    Mesh axes present in the mesh with size 1 are kept (harmless); mesh axes
    absent from the mesh are dropped. A mesh axis is used at most once per
    spec (XLA requirement) — later logical axes lose the contested axis.
    """
    mesh = mesh or get_mesh()
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    rule_map = dict(rules)
    used: set[str] = set()
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
            continue
        if ax not in rule_map:
            raise ValueError(f"no sharding rule for logical axis {ax!r}")
        target = rule_map[ax]
        if target is None:
            out.append(None)
            continue
        cand = (target,) if isinstance(target, str) else tuple(target)
        cand = tuple(a for a in cand if a in mesh_axes and a not in used)
        used.update(cand)
        if not cand:
            out.append(None)
        elif len(cand) == 1:
            out.append(cand[0])
        else:
            out.append(cand)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(logical_axes: Sequence[Optional[str]], mesh=None,
                   rules=LOGICAL_AXIS_RULES) -> NamedSharding:
    mesh = mesh or get_mesh()
    if mesh is None:
        raise RuntimeError("no mesh: call inside parallel.use_mesh(...)")
    return NamedSharding(mesh, logical_spec(logical_axes, mesh, rules))


def logical_sharding(tree_of_axes, mesh=None, rules=LOGICAL_AXIS_RULES):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: named_sharding(axes, mesh, rules),
        tree_of_axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def shard_pytree(tree, tree_of_axes, mesh=None, rules=LOGICAL_AXIS_RULES):
    """device_put a pytree according to its logical axes."""
    shardings = logical_sharding(tree_of_axes, mesh, rules)
    return jax.device_put(tree, shardings)


def constrain(x, logical_axes: Sequence[Optional[str]], mesh=None,
              rules=LOGICAL_AXIS_RULES):
    """`lax.with_sharding_constraint` by logical axes; no-op without a mesh.

    Model code calls this at layer boundaries so XLA propagates the intended
    layout; safe to leave in for single-device / CPU tests.
    """
    mesh = mesh or get_mesh()
    if mesh is None or len(mesh.devices.flat) == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_spec(logical_axes, mesh, rules)))


def shard_kernel(fn, in_axes: Sequence[Sequence[Optional[str]]],
                 out_axes: Sequence[Optional[str]]):
    """Wrap a Pallas kernel call in `jax.shard_map` over the current mesh,
    its operands and result laid out by logical axes.

    Mosaic kernels cannot be partitioned automatically: a jitted program
    whose kernel operands carry NamedShardings fails to compile for real
    chips ("wrap the call in a shard_map"). Under shard_map each device
    runs the kernel on its own shard — heads and kv_heads on tp, batch on
    dp/fsdp — and an operand laid out otherwise is moved by XLA outside
    the kernel. Returns `fn` unchanged with no mesh, on one device, or
    when tracing already happens inside a shard_map (pipeline stages,
    ring attention), where the kernel sees per-shard operands as it is.
    """
    mesh = get_mesh()
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return fn
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(logical_spec(a, mesh) for a in in_axes),
        out_specs=logical_spec(out_axes, mesh), check_vma=False)


def batch_spec(mesh=None) -> P:
    """PartitionSpec for a [batch, ...] array: batch over dp+fsdp."""
    mesh = mesh or get_mesh()
    axes = tuple(a for a in BATCH_AXES
                 if mesh is not None and a in mesh.axis_names)
    return P(axes if axes else None)
