"""Pallas TPU flash attention: blocked online-softmax with causal/local
block masking and GQA via index-map head folding.

Layout: q (B,H,S,hd), k/v (B,KV,S,hd).  Grid (B, H, nq, nk) with the kv
dimension "arbitrary" (sequential) so the (m, l, acc) VMEM scratch carries
across kv blocks.  Block sizes default to (512, 512) — MXU-aligned, and the
working set  q(512,hd) + k/v(512,hd) + p(512,512)  fits VMEM at hd<=256.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, bq: int, bk: int, s_valid: int,
                  scale: float):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * bq
    k_start = j * bk
    needed = k_start < s_valid
    if causal:
        needed &= k_start <= q_start + bq - 1
    if window:
        needed &= k_start + bk > q_start - window

    @pl.when(needed)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = ki < s_valid
        if causal:
            ok &= ki <= qi
        if window:
            ok &= ki > qi - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:, :1]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 512, block_kv: int = 512,
                         interpret: bool = False):
    """q: (B,H,S,hd), k/v: (B,KV,S,hd) -> (B,H,S,hd)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_kv, S)
    pad_q = (-S) % bq
    pad_k = (-S) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (S + pad_q) // bq
    nk = (S + pad_k) // bk

    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               bq=bq, bk=bk, s_valid=S,
                               scale=1.0 / math.sqrt(hd))
    grid = (B, H, nq, nk)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S + pad_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=cparams,
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S]
