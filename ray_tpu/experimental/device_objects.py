"""Device objects: values whose payload stays on the accelerator.

Reference parity: "Ray Direct Transport" / GPU objects
(_private/gpu_object_manager.py:41 GPUObjectManager,
@ray.method(tensor_transport=...)) — ObjectRefs whose tensor payload
stays in device memory and moves via collective transports instead of
plasma.

TPU-first reduction: each worker process owns a device-object registry;
``DeviceObject.wrap(x)`` records the jax.Array there and what travels
through the object store is a tiny stub (owner wid + key + aval). A
consumer in the SAME process gets the original array back with zero
copies or transfers; a consumer elsewhere fetches the host representation
from the owner over the control plane and re-places it on its own device.
On a multi-host pod the cross-process path is where an ICI/DCN collective
transport slots in (jax.experimental transfer — the single-chip image has
no second device to exercise it, so a host copy is the fallback the way
the reference falls back to object-store copies for non-NCCL-able pairs).

    @ray_tpu.remote
    class Producer:
        def make(self):
            return DeviceObject.wrap(jnp.ones((1024, 1024)))

    obj = ray_tpu.get(p.make.remote())   # a stub — no device transfer yet
    x = obj.to_device()                  # local hit or owner fetch
"""
from __future__ import annotations

import threading
import uuid
from typing import Any, Optional

_registry: dict[str, Any] = {}
_lock = threading.Lock()
_stats = {"wrapped": 0, "local_hits": 0, "remote_fetches": 0,
          "released": 0}
_MAX_ENTRIES = 256


def _my_wid() -> str:
    from ..core import runtime as rt_mod
    rt = rt_mod.get_runtime_if_exists()
    wid = getattr(rt, "wid", None)
    return wid if wid is not None else "driver"


def device_object_stats() -> dict:
    with _lock:
        return dict(_stats, registered=len(_registry))


class DeviceObject:
    """Pickles as (owner, key, aval); the array never rides the pickle."""

    def __init__(self, owner: str, key: str, shape, dtype):
        self.owner = owner
        self.key = key
        self.shape = shape
        self.dtype = dtype

    # -- producer ------------------------------------------------------- #

    @classmethod
    def wrap(cls, array) -> "DeviceObject":
        key = uuid.uuid4().hex
        with _lock:
            if len(_registry) >= _MAX_ENTRIES:
                raise RuntimeError(
                    f"device-object registry full ({_MAX_ENTRIES}); "
                    f"release() finished objects")
            _registry[key] = array
            _stats["wrapped"] += 1
        return cls(_my_wid(), key, tuple(array.shape), str(array.dtype))

    # -- consumer ------------------------------------------------------- #

    def to_device(self, timeout_s: float = 60.0):
        """The array: zero-copy when this process owns it, owner fetch +
        device_put otherwise."""
        with _lock:
            arr = _registry.get(self.key)
        if arr is not None:
            with _lock:
                _stats["local_hits"] += 1
            return arr
        host = self._fetch_host(timeout_s)
        import jax
        arr = jax.device_put(host)
        with _lock:
            _stats["remote_fetches"] += 1
        return arr

    def _fetch_host(self, timeout_s: float):
        import time as _time

        from ..core import runtime as rt_mod
        from ..core.ids import ObjectID
        from ..core.object_store import GetTimeoutError
        rt = rt_mod.get_runtime_if_exists()
        if rt is None:
            raise RuntimeError("ray_tpu.init() first")
        reply = ObjectID.from_random()
        rb = reply.binary()
        deadline = _time.monotonic() + timeout_s
        if hasattr(rt, "_rpc"):      # worker / driver client
            rt.send({"t": "device_fetch", "owner": self.owner,
                     "key": self.key, "reply_oid": rb})
            # the payload may come back over the conn (own-store nodes)
            # or through the shared store — poll both
            while True:
                got = rt._rpc_replies.pop(rb, None)
                if got is not None:
                    status, payload = got
                    break
                try:
                    status, payload = rt.store.get(reply, timeout_ms=200)
                    rt.store.delete(reply)
                    break
                except GetTimeoutError:
                    if _time.monotonic() > deadline:
                        # a late conn-delivered payload must be dropped,
                        # not parked forever (worker._rpc does the same)
                        rt._rpc_abandoned.add(rb)
                        raise TimeoutError(
                            f"device object fetch from {self.owner} "
                            f"timed out") from None
        else:                        # head driver
            rt.device_fetch(self.owner, self.key, rb, requester="driver")
            while True:
                try:
                    status, payload = rt.store.get(reply, timeout_ms=200)
                    rt.store.delete(reply)
                    break
                except GetTimeoutError:
                    if _time.monotonic() > deadline:
                        raise TimeoutError(
                            f"device object fetch from {self.owner} "
                            f"timed out") from None
        if status == "err":
            raise RuntimeError(payload)
        return payload

    def release(self) -> bool:
        """Drop the owner-side registration (owner process only)."""
        with _lock:
            hit = _registry.pop(self.key, None)
            if hit is not None:
                _stats["released"] += 1
            return hit is not None

    def __repr__(self):
        return (f"DeviceObject(owner={self.owner}, shape={self.shape}, "
                f"dtype={self.dtype})")


def _fetch_payload(key: str):
    """Owner-side: the (status, host-array) payload for a device_fetch
    (delivery is the runtime's job — store or conn, per requester)."""
    import numpy as np
    with _lock:
        arr = _registry.get(key)
    if arr is None:
        return ("err", f"device object {key!r} not registered "
                       f"(released or evicted)")
    return ("ok", np.asarray(arr))
