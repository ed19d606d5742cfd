"""Pure-jnp oracles for every Pallas kernel (the ground truth the kernels
are validated against in tests, shape/dtype-swept)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B,S,H,hd)  k,v: (B,S,KV,hd).  Masked full attention, fp32 math."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    qi = jnp.arange(S)[:, None]
    si = jnp.arange(S)[None, :]
    ok = jnp.ones((S, S), bool)
    if causal:
        ok &= si <= qi
    if window:
        ok &= si > qi - window
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths):
    """q: (B,H,hd) single query; k,v: (B,L,KV,hd); lengths: (B,) valid prefix.
    Returns (B,H,hd)."""
    B, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    L = k.shape[1]
    ok = jnp.arange(L)[None, :] < lengths[:, None]
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


def _pool_gather(pool, block_tables):
    """(num_blocks, KV, bs, hd) pool -> (B, nb*bs, KV, hd) virtual caches."""
    B, nb = block_tables.shape
    _, KV, bs, hd = pool.shape
    return pool[block_tables].swapaxes(2, 3).reshape(B, nb * bs, KV, hd)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths):
    """Single-query decode attention through a block table.

    q: (B,H,hd); k_pool/v_pool: (num_blocks, KV, bs, hd) — the shared
    device pool; block_tables: (B, nb) int32 physical block ids backing
    each sequence's virtual positions (padded with the null block);
    lengths: (B,) valid prefix length.  Returns (B,H,hd).

    The gather ``pool[bt]`` materializes each sequence's virtual cache
    ``(B, nb*bs, KV, hd)`` and then this is exactly
    :func:`decode_attention_ref` — which is what makes it both the
    XLA fallback inside the model and the oracle for the Pallas kernel.
    """
    return decode_attention_ref(q, _pool_gather(k_pool, block_tables),
                                _pool_gather(v_pool, block_tables), lengths)


def paged_extend_attention_ref(q, k_pool, v_pool, block_tables, pos0):
    """Suffix-extend attention through a block table.

    q: (B,S,H,hd) queries at absolute positions ``pos0 + s``; pools and
    tables as in :func:`paged_decode_attention_ref`; pos0: (B,) absolute
    position of each row's first query.  Key at virtual position p is
    visible to query s iff ``p <= pos0 + s`` — causal over absolute
    positions, exactly the dense extend mask.  Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k_pool.shape[1]
    G = H // KV
    k = _pool_gather(k_pool, block_tables)
    v = _pool_gather(v_pool, block_tables)
    L = k.shape[1]
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    positions = pos0[:, None] + jnp.arange(S)[None, :]
    ok = jnp.arange(L)[None, None, :] <= positions[:, :, None]
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def pair_score_ref(claims, evidence, W, w_c, w_e, bias):
    """The paper's phase-2 Cartesian scoring: (N,d) x (M,d) -> (N,M)."""
    bil = (claims.astype(jnp.float32) @ W.astype(jnp.float32)) @ evidence.astype(jnp.float32).T
    lin = (claims.astype(jnp.float32) @ w_c)[:, None] + (evidence.astype(jnp.float32) @ w_e)[None, :]
    return bil + lin + bias


def ssm_scan_ref(a_bar, b_bar, h0):
    """Diagonal SSM recurrence h_t = a_t * h_{t-1} + b_t.
    a_bar, b_bar: (B,S,D,N) fp32; h0: (B,D,N).  Returns (h_seq, h_final)."""
    def step(h, ab):
        a, b = ab
        h = a * h + b
        return h, h
    hT, hs = jax.lax.scan(step, h0, (a_bar.transpose(1, 0, 2, 3),
                                     b_bar.transpose(1, 0, 2, 3)))
    return hs.transpose(1, 0, 2, 3), hT
