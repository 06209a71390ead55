"""Mesh: bytes moved because a committed buffer left its pinned sharding
(counter). Anything but 0 also makes the run incorrect."""
from ._common import delta


def read(ctx: dict):
    return delta(ctx, "mesh_reshard_bytes")
