"""Pallas TPU flash-decode: single-query attention over a KV cache, split
across the cache length so the memory-bound cache read parallelizes over
grid cells; per-split (m, l, acc) partials are merged by a cheap log-sum-exp
combine in the ops wrapper.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, acc_ref, ml_ref, *,
                   ls: int, scale: float):
    s_idx = pl.program_id(2)
    q = q_ref[0, 0].reshape(1, -1).astype(jnp.float32)        # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                       # (ls, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (1, ls)
    length = len_ref[0, 0]
    pos = s_idx * ls + jax.lax.broadcasted_iota(jnp.int32, (1, ls), 1)
    s = jnp.where(pos < length, s, NEG_INF)
    m = jnp.max(s)
    p = jnp.exp(s - m)
    l = jnp.sum(p)
    v = v_ref[0, 0].astype(jnp.float32)                       # (ls, hd)
    acc = jax.lax.dot(p, v, preferred_element_type=jnp.float32)  # (1, hd)
    acc_ref[0, 0, 0] = acc[0]
    # lanes [0:64) carry m, lanes [64:128) carry l
    ml_ref[0, 0, 0] = jnp.concatenate(
        [jnp.full((64,), m, jnp.float32), jnp.full((64,), l, jnp.float32)])


def decode_attention_bhd(q, k, v, lengths, *, n_splits: int = 8,
                         interpret: bool = False):
    """q: (B,H,hd); k,v: (B,KV,L,hd); lengths: (B,) -> (B,H,hd)."""
    B, H, hd = q.shape
    KV, L = k.shape[1], k.shape[2]
    G = H // KV
    while L % n_splits:
        n_splits //= 2
    n_splits = max(n_splits, 1)
    ls = L // n_splits
    kernel = functools.partial(_decode_kernel, ls=ls,
                               scale=1.0 / math.sqrt(hd))
    grid = (B, H, n_splits)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))
    acc, ml = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, 1, ls, hd), lambda b, h, s: (b, h // G, s, 0)),
            pl.BlockSpec((1, 1, ls, hd), lambda b, h, s: (b, h // G, s, 0)),
            pl.BlockSpec((1, 1), lambda b, h, s: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, 1, 128), lambda b, h, s: (b, h, s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n_splits, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, n_splits, 128), jnp.float32),
        ],
        compiler_params=cparams,
        interpret=interpret,
    )(q, k, v, lengths.reshape(B, 1).astype(jnp.int32))

    m = ml[..., 0]                                            # (B,H,ns)
    l = ml[..., 64]
    m_g = jnp.max(m, axis=-1, keepdims=True)
    w = jnp.exp(m - m_g)
    l_g = jnp.sum(l * w, axis=-1)
    out = jnp.sum(acc * w[..., None], axis=2) / jnp.maximum(
        l_g[..., None], 1e-30)
    return out.astype(q.dtype)
