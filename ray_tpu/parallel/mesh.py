"""Device-mesh construction and TPU topology discovery.

Replaces two reference components with one TPU-native abstraction:

* the accelerator manager's TPU topology discovery
  (python/ray/_private/accelerators/tpu.py:110 TPUAcceleratorManager — chip
  counts, pod/slice env introspection), and
* the process-group bootstrap that Train performs per worker
  (python/ray/train/torch/config.py:115 `dist.init_process_group`).

On TPU there is no user-space comm library to initialise: a
`jax.sharding.Mesh` laid out over the slice's ICI torus *is* the communicator.
Axis conventions (used by models/, train/, serve/):

  dp    data parallel              (gradient psum over ICI/DCN)
  fsdp  fully-sharded data parallel (params/optimizer sharded, all-gathered)
  tp    tensor parallel            (Megatron-style layer sharding)
  sp    sequence/context parallel  (ring attention / Ulysses, parallel.ring)
  ep    expert parallel            (MoE expert sharding)
  pp    pipeline parallel          (multi-slice MPMD stages)
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Optional, Sequence

import numpy as np

# Canonical mesh-axis order. ICI-dominant axes (tp, sp) go last so that
# mesh_utils places them on the innermost (fastest, most tightly coupled)
# physical axes of the torus; dp/pp ride DCN across slices.
AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")

# Batch-like logical dimensions shard over every data-ish axis.
BATCH_AXES = ("dp", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape: axis name -> size; at most one -1 (inferred).

    MeshSpec(dp=-1, tp=4) on 32 devices resolves dp=8. By default
    (keep_unit_axes=True) ALL six axes appear in the mesh, size-1 ones
    included — so sharding rules can target any axis unconditionally. With
    keep_unit_axes=False only axes of size > 1 are kept.
    """

    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    keep_unit_axes: bool = True

    def resolved(self, n_devices: int) -> dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        inferred = [a for a, s in sizes.items() if s == -1]
        if len(inferred) > 1:
            raise ValueError(f"at most one axis may be -1, got {inferred}")
        known = math.prod(s for s in sizes.values() if s != -1)
        if inferred:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}")
            sizes[inferred[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {known} devices, have {n_devices}")
        return sizes


def build_mesh(spec: MeshSpec | dict | None = None,
               devices: Optional[Sequence] = None,
               axis_names: Optional[Sequence[str]] = None):
    """Build a `jax.sharding.Mesh` from a MeshSpec over `devices`.

    Uses `jax.experimental.mesh_utils.create_device_mesh` on real TPU so axis
    ordering respects ICI topology (nearest-neighbour axes innermost); plain
    reshape on CPU/virtual devices.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if isinstance(spec, dict):
        spec = MeshSpec(**spec)
    if spec is None:
        spec = MeshSpec(dp=-1)
    sizes = spec.resolved(len(devices))
    if axis_names is None:
        axis_names = [a for a in AXIS_ORDER
                      if spec.keep_unit_axes or sizes[a] > 1]
        if not axis_names:
            axis_names = ["dp"]
    shape = tuple(sizes[a] for a in axis_names)

    if devices[0].platform == "tpu":
        # raises when the shape does not map onto the ICI topology: a
        # naive device order would run, slower, and nothing would say so
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, tuple(axis_names))


# ---------------------------------------------------------------------------
# Current-mesh context (the analog of torch.distributed's implicit default
# process group; everything in models/train resolves shardings against this).
# ---------------------------------------------------------------------------

_local = threading.local()


def get_mesh():
    """Current mesh set by `use_mesh`, or None."""
    return getattr(_local, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh):
    """Set the current mesh for this thread (nestable)."""
    prev = getattr(_local, "mesh", None)
    _local.mesh = mesh
    try:
        yield mesh
    finally:
        _local.mesh = prev


# ---------------------------------------------------------------------------
# TPU topology discovery (TPUAcceleratorManager parity, tpu.py:110)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TpuTopology:
    """What the scheduler needs to know about the attached TPU.

    `slice_granularity` is the key scheduling fact the reference encodes as
    TPU-pod head resources: ICI failure domains are whole slices, so placement
    groups gang-reserve slices (SURVEY.md §7 'elastic slice recovery').
    """

    generation: str          # "v4", "v5e", "v5p", "v6e", "cpu"
    num_devices: int         # addressable chips from this process
    num_slices: int
    devices_per_slice: int
    chips_per_host: int
    peak_flops_bf16: float   # per chip, for MFU accounting

    @property
    def total_peak_flops(self) -> float:
        return self.peak_flops_bf16 * self.num_devices


# THE peaks table: jax `device_kind` -> (generation, peak bf16 FLOP/s per
# chip), from Google Cloud's TPU documentation (v5e: 197 TFLOP/s). A TPU
# that is not listed is an error, never a default: an MFU against the
# wrong peak is a wrong number. The "cpu" row is nominal and keeps MFU
# math defined in CPU tests.
DEVICE_PEAKS = {
    "TPU v4": ("v4", 275e12),
    "TPU v5 lite": ("v5e", 197e12),
    "TPU v5e": ("v5e", 197e12),
    "TPU v5": ("v5p", 459e12),
    "TPU v5p": ("v5p", 459e12),
    "TPU v6 lite": ("v6e", 918e12),
    "TPU v6e": ("v6e", 918e12),
    "cpu": ("cpu", 1e11),
}


def device_peak(device) -> tuple[str, float]:
    """(generation, peak bf16 FLOP/s) of a jax device, from DEVICE_PEAKS.
    Any non-TPU platform reads the nominal "cpu" row; an unlisted TPU
    kind raises."""
    if device.platform != "tpu":
        return DEVICE_PEAKS["cpu"]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device_kind "
            f"{device.device_kind!r}; add it to parallel.mesh.DEVICE_PEAKS "
            f"with its source") from None


def tpu_topology(devices: Optional[Sequence] = None) -> TpuTopology:
    """Discover topology from `jax.devices()` attributes.

    Unlike the reference (GCE metadata + GKE env probing, tpu.py:213-320),
    JAX's PJRT device objects expose coords/slice_index directly — no cloud
    metadata round-trips.
    """
    import jax
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    gen, peak = device_peak(devices[0])
    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    num_slices = max(1, len(slice_ids))
    hosts = {getattr(d, "process_index", 0) for d in devices}
    return TpuTopology(
        generation=gen,
        num_devices=len(devices),
        num_slices=num_slices,
        devices_per_slice=len(devices) // num_slices,
        chips_per_host=max(1, len(devices) // max(1, len(hosts))),
        peak_flops_bf16=peak,
    )
