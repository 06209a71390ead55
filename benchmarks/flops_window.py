"""Operations and bytes of attention in a decoder whose layers are of two
kinds (``layer_types``: ``sliding_attention`` layers see the last
``sliding_window`` keys, ``full_attention`` layers every key), computed
from the configuration file's published keys. Kept with the benchmark,
beside ``flops.py``, whose ``kv_bytes_per_token`` takes every layer to
hold every token and is wrong for such a configuration: a token costs the
full layers' bytes for as long as its sequence lives, and the sliding
layers' only while a window holds it.
"""
from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def layers_of(m: dict, kind: str) -> int:
    """Layers of ``kind`` among the ``num_hidden_layers`` that are run
    (a depth cut keeps the list whole and runs its head)."""
    return m["layer_types"][:m["num_hidden_layers"]].count(kind)


def kv_bytes_per_token_layer(m: dict, dtype_bytes: int = 2) -> int:
    """Keys and values of one token in one layer (2,048 B at 4 KV heads
    of 128 in bf16): what attention reads of a key, and what a pool stores
    of it."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * dtype_bytes


def held_bytes_per_token(m: dict, kind: str, dtype_bytes: int = 2) -> int:
    """Cache bytes a token HOLDS in the layers of ``kind``: in the full
    layers for good (``page_size`` x this is the engine's ``page_nbytes``),
    in the sliding ones while it is within a window
    (``window_page_nbytes``)."""
    return layers_of(m, kind) * kv_bytes_per_token_layer(m, dtype_bytes)


def attn_pair_flops(m: dict) -> int:
    """FLOPs of one (query, key) pair in one layer, all heads: QK^T and
    PV, 2 FLOPs each, over ``head_dim`` (16,384 at 32 heads of 128)."""
    return 4 * m["num_attention_heads"] * m["head_dim"]


def pairs(pos: int, n: int, window: int | None = None) -> int:
    """(query, key) pairs that ``n`` queries at positions ``pos`` ..
    ``pos + n - 1`` score in one layer: query q sees its q + 1 keys, or in
    a sliding layer the last ``window`` of them."""
    if window is None:
        return n * pos + n * (n + 1) // 2
    ramp = min(max(window - 1 - pos, 0), n)     # queries short of a window
    return ramp * pos + ramp * (ramp + 1) // 2 + (n - ramp) * window


def live_pages(length: int, page_size: int, window: int | None = None) -> int:
    """Pages one decode step streams of a sequence whose current token is
    at position ``length``: those of keys 0 .. length, or in a sliding
    layer of the last ``window`` of them."""
    first = 0 if window is None else max(length + 1 - window, 0)
    return length // page_size - first // page_size + 1
