"""Pallas TPU kernel for the paper's phase-2 hot spot: Cartesian pairwise
link scoring  score[i,j] = c_i^T W e_j + w_c.c_i + w_e.e_j + b  over the
compacted claim/evidence buffers (Listing 2's mapPartitions body).

Grid (n_claim_blocks, n_evid_blocks) with the evidence dimension sequential:
the per-claim-block projection  CW = C_blk @ W  is computed once per claim
block (at j == 0) into VMEM scratch and reused across evidence blocks — the
kernel-level analogue of the paper's "load the model once per partition".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pair_kernel(c_ref, e_ref, w_ref, wc_ref, we_ref, b_ref, o_ref, cw_scr,
                 *, bn: int, bm: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _project():
        c = c_ref[...].astype(jnp.float32)                    # (bn, d)
        cw_scr[...] = jax.lax.dot(c, w_ref[...].astype(jnp.float32),
                                  preferred_element_type=jnp.float32)

    c = c_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)                        # (bm, d)
    bil = jax.lax.dot_general(cw_scr[...], e, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (bn, bm)
    lin_c = jax.lax.dot(c, wc_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)   # (bn, 1)
    lin_e = jax.lax.dot(e, we_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)   # (bm, 1)
    o_ref[...] = bil + lin_c + lin_e.T + b_ref[0, 0]


def pair_score_blocked(claims, evidence, W, w_c, w_e, bias, *,
                       block_n: int = 128, block_m: int = 128,
                       interpret: bool = False):
    """claims: (N,d)  evidence: (M,d)  W: (d,d)  w_c/w_e: (d,)  -> (N,M)."""
    N, d = claims.shape
    M = evidence.shape[0]
    bn = min(block_n, N)
    bm = min(block_m, M)
    pad_n = (-N) % bn
    pad_m = (-M) % bm
    if pad_n:
        claims = jnp.pad(claims, ((0, pad_n), (0, 0)))
    if pad_m:
        evidence = jnp.pad(evidence, ((0, pad_m), (0, 0)))
    grid = ((N + pad_n) // bn, (M + pad_m) // bm)
    kernel = functools.partial(_pair_kernel, bn=bn, bm=bm)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
            pl.BlockSpec((d, d), lambda i, j: (0, 0)),
            pl.BlockSpec((d, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((d, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N + pad_n, M + pad_m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        compiler_params=cparams,
        interpret=interpret,
    )(claims, evidence, W, w_c.reshape(d, 1), w_e.reshape(d, 1),
      jnp.asarray(bias, jnp.float32).reshape(1, 1))
    return out[:N, :M]
