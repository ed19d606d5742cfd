"""Work counts from what the traffic requires, never from the program's
padded or bucketed shapes, and the chip peaks they are held against.

A decode token at context ``ctx`` (it attends ``ctx`` positions, itself
included) needs the matmuls of every layer and of the output head, and
attention over its live context.
"""
from __future__ import annotations

import json
import os
from typing import Iterable, Tuple

BYTES = 2                                   # bfloat16 activations and KV
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of ``device_kind``;
    a kind that is not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def layer_matmul_params(s) -> int:
    d, hd = s.d_model, s.head_dim
    attn = d * (s.heads + 2 * s.kv_heads) * hd + s.heads * hd * d
    mlp = 3 * d * s.d_ff                        # SwiGLU: gate, up, down
    return attn + mlp


def attn_flops(s, q_pos: Iterable[int]) -> float:
    """QK^T and PV over all layers for queries attending ``ctx`` positions
    each (``q_pos`` yields the contexts)."""
    return 4.0 * s.layers * s.heads * s.head_dim * float(sum(q_pos))


def decode_flops(s, ctx: int) -> float:
    return 2.0 * (s.layers * layer_matmul_params(s) + s.d_model * s.vocab) \
        + attn_flops(s, [ctx])


def decode_attn(s, ctx: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the paged-decode kernel needs for one token at
    context ``ctx``: its live K and V, and its q and o."""
    kv = 2 * ctx * s.kv_heads * s.head_dim * BYTES
    qo = 2 * s.heads * s.head_dim * BYTES
    return attn_flops(s, [ctx]), float(s.layers * (kv + qo))


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
