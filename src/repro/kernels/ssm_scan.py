"""Pallas TPU chunked selective-scan kernel (Mamba / RG-LRU style diagonal
recurrence  h_t = a_t * h_{t-1} + b_t).

Layout ``(B, S, N, D)``: the channel axis D is the 128-wide lane axis and
the small state axis N sits on sublanes, so a ``(1, chunk, N, bD)`` block
carries no lane padding (with N on lanes a state of 16 would pad to 128
and multiply the block's VMEM by eight).  Grid (B, n_channel_blocks,
n_chunks) with the chunk dimension sequential: the carry h lives in VMEM
scratch across chunks, and within a chunk a loop walks the steps one at a
time, each an elementwise ``(N, bD)`` update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(a_ref, b_ref, h0_ref, hs_ref, hT_ref, h_scr, *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    def step(t, h):                                      # h: (N, bD)
        h = a_ref[0, t].astype(jnp.float32) * h + \
            b_ref[0, t].astype(jnp.float32)
        hs_ref[0, t] = h.astype(hs_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])

    @pl.when(c == pl.num_programs(2) - 1)
    def _emit():
        hT_ref[0] = h_scr[...].astype(hT_ref.dtype)


def ssm_scan_blocked(a_bar, b_bar, h0, *, chunk: int = 32,
                     block_d: int = 512, interpret: bool = False):
    """a_bar,b_bar: (B,S,N,D) fp32; h0: (B,N,D).  Returns (h_seq, h_final).

    VMEM: a, b and h_seq blocks of ``chunk * N * bD * 4`` bytes each, double
    buffered — 6 MiB at chunk 32, N 16, bD 512."""
    B, S, N, D = a_bar.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        a_bar = jnp.pad(a_bar, ((0, 0), (0, pad), (0, 0), (0, 0)),
                        constant_values=1.0)
        b_bar = jnp.pad(b_bar, ((0, 0), (0, pad), (0, 0), (0, 0)))
    bD = min(block_d, D)
    nc = (S + pad) // chunk
    grid = (B, D // bD, nc)
    kernel = functools.partial(_scan_kernel, chunk=chunk)
    hs, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, N, bD), lambda b, d, c: (b, c, 0, d)),
            pl.BlockSpec((1, chunk, N, bD), lambda b, d, c: (b, c, 0, d)),
            pl.BlockSpec((1, N, bD), lambda b, d, c: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, N, bD), lambda b, d, c: (b, c, 0, d)),
            pl.BlockSpec((1, N, bD), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S + pad, N, D), jnp.float32),
            jax.ShapeDtypeStruct((B, N, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, bD), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_bar, b_bar, h0)
    return hs[:, :S], hT
