"""Operations and bytes that the measured work NEEDS, from shapes alone.

These are the numerators of every utilisation and roofline share the
benchmark reports. They count what the algorithm requires, never what
an implementation happens to recompute or pad, so a share cannot pass
100% unless the traced time leaves out part of the work.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """Published peaks of ``device_kind`` from ``peaks.json``; a kind
    that is not in the table raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        known = [k for k in table if not k.startswith("_")]
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {known})")
    return table[device_kind]


def train_flops_per_token(n_params: int, layers: int, seq: int,
                          hidden: int) -> float:
    """Forward + backward operations one trained token requires:
    ``6 N`` for the weight matmuls (N counts every parameter; the word
    table is counted once, as the tied decoder matmul) plus
    ``12 L S H`` for attention's two S x S products (``4 S H`` a layer
    forward, three times that with the backward). Recomputation does
    not count."""
    return 6.0 * n_params + 12.0 * layers * seq * hidden


def flash_attention_train(batch: int, heads: int, seq: int, head_dim: int,
                          layers: int, itemsize: int = 2) -> dict:
    """One training step's attention, all layers, bidirectional:
    forward QK^T and PV (4 B H S^2 Dh), backward dV, dP, dK, dQ
    (8 B H S^2 Dh). The backward kernels' recomputation of P is not
    needed work and is left out. Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv
    (the log-sum-exp rows are 1/Dh of a tensor and are left out)."""
    per = float(batch) * heads * seq * seq * head_dim
    tensor = float(batch) * heads * seq * head_dim * itemsize
    return {"flops": 12.0 * per * layers, "bytes": 12.0 * tensor * layers}


def paged_decode_bytes(live_token_steps: float, layers: int, heads: int,
                       head_dim: int, itemsize: int) -> float:
    """Bytes of keys and values that decoding must read:
    ``live_token_steps`` is the sum, over every token step of every
    decoding slot, of the tokens in that slot's cache at that step.
    Nominal bytes (no tile padding, no bucket padding)."""
    return float(live_token_steps) * layers * 2 * heads * head_dim * itemsize


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time the chip could take and which bound binds."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
