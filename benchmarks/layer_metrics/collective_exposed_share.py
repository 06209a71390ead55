"""Mesh (parallel/sharding.py, parallel/mesh.py): time a collective runs
while no compute runs on that device, over the traced slice, worst chip."""
from ._common import trace


def read(ctx: dict):
    t = trace(ctx)
    if t is None or not t["window_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
