"""jit'd public wrappers around the Pallas kernels.

Call sites pass ``interpret=use_interpret()``: the kernels lower to Mosaic
on a TPU backend and run in the Pallas interpreter on any other (the CPU
test suite validates them there against ref.py / the pure-jnp model
paths).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.paged_attention import (paged_decode_attention_bkgd,
                                           paged_extend_attention_bkgd,
                                           paged_kv_write_bkgd)
from repro.kernels.pair_score import pair_score_blocked
from repro.kernels.ssm_scan import ssm_scan_blocked


def use_interpret() -> bool:
    """True unless the default backend is a TPU: Pallas TPU kernels only
    compile for a TPU, and everywhere else they run interpreted."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False):
    """q: (B,S,H,hd), k/v: (B,S,KV,hd) -> (B,S,H,hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               interpret=interpret)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("n_splits", "interpret"))
def decode_attention(q, k, v, lengths, *, n_splits: int = 8,
                     interpret: bool = False):
    """q: (B,H,hd); k/v: (B,L,KV,hd) caches; lengths: (B,) -> (B,H,hd)."""
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    return decode_attention_bhd(q, kt, vt, lengths, n_splits=n_splits,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           layer=None, *, interpret: bool = False):
    """q: (B,H,hd); k_pool/v_pool: (num_blocks, KV, bs, hd) shared pools,
    or (R, num_blocks, KV, bs, hd) stacked over layers with ``layer`` the
    one to read; block_tables: (B, nb); lengths: (B,) -> (B,H,hd).

    The kernel gathers K/V through the block table inside the grid (scalar
    prefetch resolves the layer and the physical pool rows), so no dense
    per-sequence cache, and no per-layer slice of a stack, is ever
    materialized."""
    B, H, hd = q.shape
    if k_pool.ndim == 4:        # a per-layer pool: layer 0 of a stack of one
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    KV = k_pool.shape[2]
    G = H // KV
    out = paged_decode_attention_bkgd(q.reshape(B, KV, G, hd),
                                      k_pool, v_pool, block_tables, lengths,
                                      layer, interpret=interpret)
    return out.reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(k_pool, v_pool, layer, phys, off, k_rows, v_rows, *,
                   interpret: bool = False):
    """``pool[layer, phys[b], :, off[b]] = rows[b]`` for both pools, in
    place.  k_pool/v_pool: (R, num_blocks, KV, bs, hd); layer: scalar;
    phys/off: (B,); k_rows/v_rows: (B, KV, hd) -> (k_pool, v_pool).

    Each real block may be named by at most one row of a call; rows that
    name the null block 0 leave junk there."""
    return paged_kv_write_bkgd(k_pool, v_pool,
                               jnp.asarray(layer, jnp.int32).reshape(1),
                               phys, off, k_rows, v_rows,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_extend_attention(q, k_pool, v_pool, block_tables, pos0, *,
                           interpret: bool = False):
    """q: (B,S,H,hd) suffix queries at absolute positions ``pos0 + s``;
    k_pool/v_pool: (num_blocks, KV, bs, hd) shared pools (suffix K/V
    already scattered in); block_tables: (B, nb); pos0: (B,)
    -> (B,S,H,hd).

    The paged-prefill/extend sibling of :func:`paged_decode_attention`:
    online softmax over the prefix blocks + in-flight suffix, block
    tables scalar-prefetched, masked like the dense oracle
    (key p visible to query s iff p <= pos0 + s)."""
    B, S, H, hd = q.shape
    KV = k_pool.shape[1]
    G = H // KV
    qk = q.reshape(B, S, KV, G, hd).transpose(0, 2, 1, 3, 4)
    out = paged_extend_attention_bkgd(qk.reshape(B, KV, S * G, hd),
                                      k_pool, v_pool, block_tables, pos0,
                                      G=G, interpret=interpret)
    out = out.reshape(B, KV, S, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def pair_score(link_params, claims, evidence, *, block_n: int = 128,
               block_m: int = 128, interpret: bool = False):
    """Blocked bilinear pair scoring; same contract as
    svm.link_score_matrix (full-rank W form)."""
    d = claims.shape[-1]
    return pair_score_blocked(claims, evidence, link_params["W"],
                              link_params["w"][:d], link_params["w"][d:],
                              link_params["bias"], block_n=block_n,
                              block_m=block_m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def ssm_scan(xc, dt, Bc, Cc, A, D, h0=None, *, chunk: int = 32,
             block_d: int = 512, interpret: bool = False):
    """Same contract as models.ssm.selective_scan (returns (y, h_final)).

    The recurrence runs in the kernel's lane-dense ``(B, S, N, D)`` layout
    (channels on the 128-wide lane axis, the small state on sublanes)."""
    Bsz, S, di = xc.shape
    a_bar = jnp.exp(dt[:, :, None, :] * A.T[None, None])      # (B,S,N,D)
    b_bar = (dt * xc)[:, :, None, :] * Bc[..., None]
    if h0 is None:
        h0 = jnp.zeros((Bsz, di, A.shape[-1]), jnp.float32)
    h_seq, h_fin = ssm_scan_blocked(a_bar, b_bar, h0.transpose(0, 2, 1),
                                    chunk=chunk, block_d=min(block_d, di),
                                    interpret=interpret)
    y = jnp.einsum("bsnd,bsn->bsd", h_seq, Cc) + xc * D[None, None]
    return y, h_fin.transpose(0, 2, 1)
