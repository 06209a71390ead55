"""Operations and bytes the algorithm needs, computed from shapes, and the
table of peaks. Kept with the benchmark so that no program change moves a
utilization or a roofline share.

Counts follow the published convention for model FLOPs: matrix
multiplications of the weights that act on every token (the embedding
table is a lookup and counts nothing), causal attention at half the
square, backward = 2 x forward, recomputation not counted.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``. A device that
    is not in the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to benchmarks/peaks.json with its source")
    return table[device_kind]


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer that multiply every token."""
    d, hd = m["hidden_size"], head_dim(m)
    q = d * m["num_attention_heads"] * hd
    kv = 2 * d * m["num_key_value_heads"] * hd
    o = m["num_attention_heads"] * hd * d
    mlp = 3 * d * m["intermediate_size"]
    return q + kv + o + mlp


def matmul_params(m: dict) -> int:
    """Layers plus the output head; the embedding lookup is not a matmul."""
    return (m["num_hidden_layers"] * layer_matmul_params(m)
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    """Every stored parameter (for memory, not for FLOPs)."""
    d = m["hidden_size"]
    return (matmul_params(m) + m["vocab_size"] * d
            + m["num_hidden_layers"] * 2 * d + d)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """Keys and values one cached token holds across the layers."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * head_dim(m) * dtype_bytes)


def attention_fwd_flops(m: dict, batch: int, seq: int,
                        causal: bool = True) -> float:
    """QK^T and PV of one layer's forward: 2 matmuls x 2 FLOPs x
    B*H*S*S*D, halved when causal."""
    full = 4.0 * batch * m["num_attention_heads"] * seq * seq * head_dim(m)
    return full / 2 if causal else full


def attention_bwd_flops(m: dict, batch: int, seq: int,
                        causal: bool = True) -> float:
    """Flash backward: dV, dP, dQ, dK are four matmuls the size of the
    forward's two, plus the recomputed QK^T: 2.5 x forward."""
    return 2.5 * attention_fwd_flops(m, batch, seq, causal)


def attention_io_bytes(m: dict, batch: int, seq: int, dtype_bytes: int = 2,
                       backward: bool = False) -> float:
    """Least HBM traffic of one layer's attention call: q, k, v read and o
    written once (forward); q, k, v, o, do read and dq, dk, dv written
    (backward)."""
    hd = head_dim(m)
    q = batch * seq * m["num_attention_heads"] * hd * dtype_bytes
    kv = batch * seq * m["num_key_value_heads"] * hd * dtype_bytes
    if backward:
        return 4.0 * q + 4.0 * kv       # q,o,do,dq + k,v,dk,dv
    return 2.0 * q + 2.0 * kv


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward model FLOPs per trained token at sequence
    length ``seq``: 6 per matmul weight plus causal attention (forward 1x,
    backward 2x of the forward's matmuls; no recomputation)."""
    attn_fwd_per_tok = (m["num_hidden_layers"]
                        * attention_fwd_flops(m, 1, seq) / seq)
    return 6.0 * matmul_params(m) + 3.0 * attn_fwd_per_tok


def roofline_min_s(flops: float, bytes_: float, peak: dict) -> tuple:
    """(least seconds, which bound): the larger of operations over peak
    FLOP/s and bytes over peak bytes/s."""
    t_c = flops / peak["bf16_flops"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
