"""TPU slice identity as a scheduling primitive.

Reference parity: TPUAcceleratorManager (reference:
python/ray/_private/accelerators/tpu.py:110 — pod name/worker-id become
`TPU-{pod_type}-head` resources so gangs co-schedule onto one pod; :213-320
probes GCE metadata / GKE env for that identity). Here slice identity is a
node LABEL and placement groups carry a `same_label` constraint — the
scheduler picks one slice value for the whole gang (core/runtime.py
`_try_reserve_pg_locked`), which is both simpler and stronger than resource
name encoding: any gang shape can demand "all inside one ICI domain".

Labels are discovered from the TPU VM runtime environment variables (set on
every GCE TPU VM / GKE TPU pod), never by importing jax — agent startup
must not touch the accelerator.
"""
from __future__ import annotations

import os
from typing import Optional

SLICE_LABEL = "rtpu.tpu.slice"            # pod/slice name (ICI domain id)
WORKER_ID_LABEL = "rtpu.tpu.worker_id"    # host index within the slice
GENERATION_LABEL = "rtpu.tpu.generation"  # "v4" | "v5e" | "v5p" | "v6e"
TOPOLOGY_LABEL = "rtpu.tpu.topology"      # e.g. "v5litepod-16"


def discover_tpu_labels(env=None) -> dict[str, str]:
    """Slice-identity labels from TPU VM env vars (reference analog:
    tpu.py:213 get_current_pod_name / :246 get_current_node_tpu_worker_id,
    which fall back to these same envs on GKE)."""
    env = os.environ if env is None else env
    labels: dict[str, str] = {}
    name = env.get("TPU_NAME") or env.get("TPU_POD_NAME")
    if name:
        labels[SLICE_LABEL] = name
    worker_id = env.get("TPU_WORKER_ID")
    if worker_id:
        labels[WORKER_ID_LABEL] = worker_id
    acc = env.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5litepod-16"
    if acc:
        labels[TOPOLOGY_LABEL] = acc
        labels[GENERATION_LABEL] = accelerator_generation(acc)
    return labels


def require_granted_tpu(resources: Optional[dict]) -> None:
    """Raise when a task or actor that was granted a "TPU" resource runs
    where JAX sees no TPU: it would otherwise compute on the CPU and
    nothing would say so. Called by the worker before it runs the task or
    builds the actor. ``JAX_PLATFORMS=cpu`` set explicitly (CPU tests,
    rehearsals on virtual devices) is the one way to run such work
    without a chip. Initialises the JAX backend — the grant is what
    entitles this process to the chip."""
    if not (resources or {}).get("TPU", 0) or \
            os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"granted a TPU resource but JAX runs on {dev.platform!r} "
            f"({dev.device_kind}): no TPU is attached to this process; "
            f"set JAX_PLATFORMS=cpu to run it on the CPU on purpose")


def accelerator_generation(accelerator_type: str) -> str:
    """"v5litepod-16" -> "v5e", "v4-8" -> "v4" (reference tpu.py:58-76
    keeps the same family table)."""
    head = accelerator_type.split("-")[0].lower()
    return {"v5litepod": "v5e", "v5p": "v5p", "v6e": "v6e",
            "v4": "v4", "v3": "v3", "v2": "v2"}.get(head, head)


def slice_chips(accelerator_type: str) -> int:
    """Chip count of a slice. The numeric suffix counts TENSORCORES on
    v2-v4/v5p (2 per chip) but CHIPS on v5e/v6e — the same quirk the
    reference hard-codes (tpu.py:15-58 chips-per-host/accelerator tables).
    "v4-8" -> 4 chips; "v5litepod-8" -> 8 chips."""
    n = int(accelerator_type.rsplit("-", 1)[1])
    if accelerator_generation(accelerator_type) in ("v2", "v3", "v4", "v5p"):
        return max(1, n // 2)
    return n


def slice_hosts(accelerator_type: str, chips_per_host: int = 4) -> int:
    """Worker-VM (host) count of a slice."""
    return max(1, slice_chips(accelerator_type) // chips_per_host)


def slice_placement_group(num_hosts: int,
                          chips_per_host: float = 4,
                          *,
                          generation: Optional[str] = None,
                          extra_bundle_resources: Optional[dict] = None,
                          name: str = ""):
    """Reserve a whole slice's worth of hosts inside ONE ICI domain.

    One {TPU: chips_per_host} bundle per host, STRICT_SPREAD (one host
    each), all pinned to a single value of SLICE_LABEL. `generation`
    additionally restricts every bundle to nodes of that TPU family.
    """
    from .placement_group import placement_group
    bundle = {"TPU": float(chips_per_host),
              **(extra_bundle_resources or {})}
    selectors = None
    if generation is not None:
        selectors = [{GENERATION_LABEL: generation}] * num_hosts
    return placement_group(
        [dict(bundle) for _ in range(num_hosts)],
        strategy="STRICT_SPREAD",
        name=name,
        same_label=SLICE_LABEL,
        bundle_label_selectors=selectors)
