"""Attention: full / sliding-window (chunked, sub-quadratic) / decode, with
GQA-MQA, optional dual-base RoPE (gemma3), qk-norm, MLA (DeepSeek), and
cross-attention (enc-dec).  Pure-jnp reference paths; perf-critical paths can
be routed through Pallas kernels (cfg.use_kernels) which target TPU and are
validated in interpret mode against these same functions.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sharding import shard
from repro.models.layers import apply_rope, dense_init, ones_init, rms_norm

NEG_INF = -2.0e38
FLASH_MIN_SEQ = 1024          # switch to chunked online-softmax attention


# ----------------------------------------------------------------------
def init_attn(key, cfg, *, cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.p_dtype
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), ("embed", "heads"), dt),
        "wk": dense_init(ks[1], (d, KV * hd), ("embed", "kv_heads"), dt),
        "wv": dense_init(ks[2], (d, KV * hd), ("embed", "kv_heads"), dt),
        "wo": dense_init(ks[3], (H * hd, d), ("heads", "embed"), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones_init((hd,), (None,), dt)
        p["k_norm"] = ones_init((hd,), (None,), dt)
    return p


def _project_qkv(params, xq, xkv, cfg, positions_q, positions_kv, rope_base):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # pin head sharding immediately so qk-norm/rope (fp32 element-wise) stay
    # local to each head shard instead of tempting GSPMD into reshards
    q = shard((xq @ params["wq"]).reshape(B, Sq, H, hd),
              "batch", "seq", "heads", None)
    k = shard((xkv @ params["wk"]).reshape(B, Skv, KV, hd),
              "batch", "seq", "kv_heads", None)
    v = shard((xkv @ params["wv"]).reshape(B, Skv, KV, hd),
              "batch", "seq", "kv_heads", None)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if rope_base:
        q = apply_rope(q, positions_q, rope_base)
        k = apply_rope(k, positions_kv, rope_base)
    return q, k, v


def mha(q, k, v, mask, softcap: float = 0.0):
    """q: (B,Sq,H,hd)  k,v: (B,Skv,KV,hd)  mask: broadcastable (B,1,Sq,Skv)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is not None:
        scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def causal_mask(Sq: int, Skv: int, offset: int = 0):
    """mask[q, s] = s <= q + offset (offset = Skv - Sq for suffix queries)."""
    qi = jnp.arange(Sq)[:, None]
    si = jnp.arange(Skv)[None, :]
    return si <= qi + offset


# ----------------------------------------------------------------------
# Chunked flash-style attention in pure jnp: online softmax over kv chunks,
# EXACT block skipping for causal/window patterns (a python loop over query
# chunks gives each q-chunk a static kv range, so HLO FLOPs match the true
# sub-quadratic cost — no masked-waste).  This is both the XLA path used by
# the dry-run at long sequence and the oracle for kernels/flash_attention.
def flash_attention_jnp(q, k, v, *, causal: bool = True, window: int = 0,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        softcap: float = 0.0, kv_offset: int = 0,
                        q_offset_dynamic=None, kv_valid=None):
    """q: (B,Sq,H,hd)  k,v: (B,Skv,KV,hd) -> (B,Sq,H,hd).  fp32 accumulation.

    kv_offset: STATIC position of kv[0] relative to q[0] (e.g. -window for a
      halo-prefixed kv) — keeps the causal/window block ranges static/exact.
    q_offset_dynamic: traced scalar added to q positions in MASKS only (used
      by the gathered-KV ring path where ranges must stay full).
    kv_valid: optional traced bool (Skv,) ANDed into the mask (halo validity).
    """
    from repro.core import flags
    B, S, H, hd = q.shape
    Skv_in = k.shape[1]
    KV = k.shape[2]
    hd_v = v.shape[-1]                                 # may differ (MLA)
    G = H // KV
    if flags.COST_MODE:
        # kernel-realistic block granularity, python-unrolled kv loop
        q_chunk = kv_chunk = (window if window else 2048)
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, Skv_in)
    pad_q = (-S) % q_chunk
    pad_k = (-Skv_in) % kv_chunk
    Sq, Sk = S + pad_q, Skv_in + pad_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if kv_valid is not None:
            kv_valid = jnp.pad(kv_valid, (0, pad_k))
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    kc = k.reshape(B, nk, kv_chunk, KV, hd)
    vc = v.reshape(B, nk, kv_chunk, KV, hd_v)
    scale = 1.0 / math.sqrt(hd)
    dynamic_ranges = q_offset_dynamic is not None

    def one_q_chunk(qi_idx: int, q_i, q_off):
        """q_i: (B,C,KV,G,hd); returns (B,C,KV,G,hd)."""
        C = q_chunk
        q_pos = qi_idx * C + jnp.arange(C)
        if q_off is not None:
            q_pos = q_pos + q_off
        # static kv chunk range for this q chunk (exact block skipping);
        # with a dynamic q offset the range must stay full
        if causal and not dynamic_ranges:
            hi = min(nk, ((qi_idx + 1) * C - 1 - kv_offset) // kv_chunk + 1)
        else:
            hi = nk
        lo = 0
        if window and not dynamic_ranges:
            lo = max(0, (qi_idx * C - window - kv_offset) // kv_chunk)

        def kv_step(carry, j):
            m, l, acc = carry
            k_j = jax.lax.dynamic_index_in_dim(kc, j, axis=1, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vc, j, axis=1, keepdims=False)
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_j).astype(jnp.float32)
            s = s * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            kv_pos = j * kv_chunk + jnp.arange(kv_chunk) + kv_offset
            ok = kv_pos[None, :] < Skv_in + kv_offset
            if causal:
                ok = ok & (kv_pos[None, :] <= q_pos[:, None])
            if window:
                ok = ok & (kv_pos[None, :] > q_pos[:, None] - window)
            if kv_valid is not None:
                vmask = jax.lax.dynamic_index_in_dim(
                    kv_valid.reshape(nk, kv_chunk), j, axis=0, keepdims=False)
                ok = ok & vmask[None, :]
            s = jnp.where(ok[None, None, None], s, NEG_INF)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + jnp.sum(p, axis=-1)
            acc2 = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(q_i.dtype), v_j).astype(jnp.float32)
            return (m2, l2, acc2), None

        m0 = jnp.full((B, KV, G, C), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, C), jnp.float32)
        a0 = jnp.zeros((B, KV, G, C, hd_v), jnp.float32)
        if flags.COST_MODE:
            carry = (m0, l0, a0)
            for j in range(lo, hi):
                carry, _ = kv_step(carry, jnp.asarray(j))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(lo, hi))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)            # (B,C,KV,G,hd)

    qg = q.reshape(B, nq, q_chunk, KV, G, hd)
    outs = []
    for i in range(nq):
        fn = jax.checkpoint(functools.partial(one_q_chunk, i))
        outs.append(fn(qg[:, i], q_offset_dynamic))
    out = jnp.concatenate(outs, axis=1)[:, :S]
    return out.reshape(B, S, H, hd_v).astype(q.dtype)


# ----------------------------------------------------------------------
# Context-parallel (sequence-sharded) attention for prefill/scoring under the
# paper's broadcast placement: weights replicated, the sequence split over
# the `model` axis (shard_map).  Local-window layers exchange only a
# window-sized halo (collective_permute); global layers all-gather K/V and
# flash over the gathered cache.  This is the TPU-native form of the paper's
# "ship the model once, split the instances" — see EXPERIMENTS.md §Perf.
def seqshard_attn_forward(params, x, cfg, *, kind: str, mesh, batch_axes):
    from jax.sharding import PartitionSpec as P

    B, S, _ = x.shape
    n = mesh.shape["model"]
    S_loc = S // n
    rope_base = cfg.rope_local_base if kind == "local" else cfg.rope_base
    W = cfg.window
    b_ax = batch_axes if batch_axes else None

    def local_fn(p, xl):
        # xl: (B_loc, S_loc, d).  shard() constraints must no-op inside the
        # manual-sharding region:
        from repro.core.sharding import use_sharding
        with use_sharding(None):
            return _local_body(p, xl)

    def _local_body(p, xl):
        r = jax.lax.axis_index("model")
        off = r * S_loc
        pos = off + jnp.arange(S_loc)[None, :]
        q, k, v = _project_qkv(p, xl, xl, cfg, pos, pos, rope_base)
        if kind == "local" and W and W <= S_loc:
            # halo: previous rank's last W keys/values (rank 0 gets zeros)
            perm = [(i, i + 1) for i in range(n - 1)]
            k_h = jax.lax.ppermute(k[:, -W:], "model", perm)
            v_h = jax.lax.ppermute(v[:, -W:], "model", perm)
            kk = jnp.concatenate([k_h, k], axis=1)
            vv = jnp.concatenate([v_h, v], axis=1)
            kv_ok = (off - W + jnp.arange(W + S_loc)) >= 0
            out = flash_attention_jnp(q, kk, vv, causal=True, window=W,
                                      softcap=cfg.attn_softcap, kv_offset=-W,
                                      kv_valid=kv_ok)
        else:
            kk = jax.lax.all_gather(k, "model", axis=1, tiled=True)
            vv = jax.lax.all_gather(v, "model", axis=1, tiled=True)
            out = flash_attention_jnp(q, kk, vv, causal=True,
                                      softcap=cfg.attn_softcap,
                                      q_offset_dynamic=off)
        out = out.reshape(xl.shape[0], S_loc, -1) @ p["wo"]
        return out, k, v

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), P(b_ax, "model", None)),
                       out_specs=(P(b_ax, "model", None),
                                  P(b_ax, "model", None, None),
                                  P(b_ax, "model", None, None)),
                       check_vma=False)
    return fn(params, x)


# ----------------------------------------------------------------------
# Full-sequence forward (train / prefill).
def attn_forward(params, x, cfg, *, kind: str, positions=None, encoder_kv=None,
                 qkv=None):
    """kind: "causal" | "local" | "global" | "bidir" | "cross"."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    rope_base = 0.0 if kind in ("bidir", "cross") else (
        cfg.rope_local_base if kind == "local" else cfg.rope_base)

    if kind == "cross":
        xkv = encoder_kv
        pos_kv = jnp.arange(xkv.shape[1])[None, :]
        q, k, v = _project_qkv(params, x, xkv, cfg, positions, pos_kv, 0.0)
        if S >= FLASH_MIN_SEQ or xkv.shape[1] >= FLASH_MIN_SEQ:
            out = flash_attention_jnp(q, k, v, causal=False,
                                      softcap=cfg.attn_softcap)
        else:
            out = mha(q, k, v, None, cfg.attn_softcap)
        return out.reshape(B, S, -1) @ params["wo"]

    q, k, v = qkv if qkv is not None else _project_qkv(
        params, x, x, cfg, positions, positions, rope_base)

    window = cfg.window if kind == "local" else 0
    if cfg.use_kernels and kind in ("causal", "global", "local") and S >= 128:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window,
                                   interpret=kops.use_interpret())
    elif S >= FLASH_MIN_SEQ:
        out = flash_attention_jnp(q, k, v, causal=kind != "bidir",
                                  window=window, softcap=cfg.attn_softcap)
    elif kind == "local" and cfg.window and S > cfg.window:
        out = _local_attention(q, k, v, cfg.window, cfg.attn_softcap)
    else:
        mask = None
        if kind in ("causal", "global"):
            mask = causal_mask(S, S)[None, None]
        elif kind == "local":
            m = causal_mask(S, S)
            if cfg.window:
                si = jnp.arange(S)
                m = m & (si[None, :] > si[:, None] - cfg.window)
            mask = m[None, None]
        out = mha(q, k, v, mask, cfg.attn_softcap)
    out = shard(out.reshape(B, S, -1), "batch", "seq", "heads")
    return shard(out @ params["wo"], "batch", "seq", None)


def _local_attention(q, k, v, window: int, softcap: float):
    """Chunked sliding-window attention: O(S * 2W) compute.

    Token t attends to s in (t - window, t].  Chunk size C == window; each
    query chunk attends to (previous chunk ++ own chunk) with a banded mask.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    C = window
    pad = (-S) % C
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, padw) for t in (q, k, v))
        S2 = S + pad
    else:
        S2 = S
    nc = S2 // C
    qc = q.reshape(B, nc, C, H, hd)
    kc = k.reshape(B, nc, C, KV, hd)
    vc = v.reshape(B, nc, C, KV, hd)
    kprev = jnp.pad(kc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vprev = jnp.pad(vc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    kk = jnp.concatenate([kprev, kc], axis=2)            # (B,nc,2C,KV,hd)
    vv = jnp.concatenate([vprev, vc], axis=2)
    G = H // KV
    qg = qc.reshape(B, nc, C, KV, G, hd)
    scores = jnp.einsum("bnqkgh,bnskh->bnkgqs", qg, kk).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    # positions within the 2C strip: query i (0..C-1) sits at absolute C + i.
    qi = jnp.arange(C)[:, None] + C
    si = jnp.arange(2 * C)[None, :]
    band = (si <= qi) & (si > qi - window)
    # first chunk has no previous chunk: mask strip [0, C) there.
    first = (jnp.arange(nc) == 0)[:, None, None]
    band = band[None] & ~(first & (si < C)[None])
    scores = jnp.where(band[None, :, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnkgqs,bnskh->bnqkgh", probs, vv)
    out = out.reshape(B, S2, H, hd)
    return out[:, :S]


# ----------------------------------------------------------------------
# Decode with caches.
def init_kv_cache(cfg, batch: int, max_len: int, *, ring: bool = False):
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    L = min(max_len, cfg.window) if ring and cfg.window else max_len
    c = {
        "k": jnp.zeros((batch, L, KV, hd), cfg.act_dtype),
        "v": jnp.zeros((batch, L, KV, hd), cfg.act_dtype),
    }
    if ring:
        c["pos"] = jnp.full((batch, L), -1, jnp.int32)
    return c


def cache_axes(cache):
    """Logical axes for cache pytrees (for sharding specs)."""
    def ax(path_leaf):
        arr = path_leaf
        if arr.ndim == 4:
            return ("batch", None, "kv_heads", None)
        if arr.ndim == 3:
            return ("batch", None, None)
        return ("batch", None)
    return jax.tree_util.tree_map(ax, cache)


def batched_cache_update(cache_arr, new_row, slot):
    """cache_arr: (B, L, ...); new_row: (B, ...); slot: (B,).

    Per-batch dynamic_update_slice (vmapped) instead of a gather/scatter —
    GSPMD keeps the update local to each batch shard, where a fancy-indexed
    scatter forces a cache all-gather (measured: 2 GB/layer at decode_32k).
    """
    def upd(c, row, s):
        return jax.lax.dynamic_update_slice_in_dim(c, row[None], s, axis=0)
    return jax.vmap(upd)(cache_arr, new_row, slot)


def attn_decode(params, x, cache, pos, cfg, *, kind: str):
    """x: (B,1,d).  pos: (B,) current absolute position.  Returns (out, cache)."""
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rope_base = cfg.rope_local_base if kind == "local" else cfg.rope_base
    q, k, v = _project_qkv(params, x, x, cfg, pos[:, None], pos[:, None], rope_base)

    ring = kind == "local" and cfg.window and cache["k"].shape[1] <= cfg.window
    L = cache["k"].shape[1]
    slot = (pos % L) if ring else pos                    # (B,)
    cache = dict(cache)
    with jax.named_scope("kv_write"):
        cache["k"] = batched_cache_update(cache["k"], k[:, 0], slot)
        cache["v"] = batched_cache_update(cache["v"], v[:, 0], slot)
        if ring:
            cache["pos"] = batched_cache_update(cache["pos"], pos, slot)
    if ring:
        valid = (cache["pos"] >= 0) & (cache["pos"] > (pos[:, None] - cfg.window)) \
            & (cache["pos"] <= pos[:, None])
    else:
        valid = jnp.arange(L)[None, :] <= pos[:, None]
    mask = valid[:, None, None, :]                        # (B,1,1,L)
    out = mha(q, cache["k"], cache["v"], mask, cfg.attn_softcap)
    out = out.reshape(B, 1, -1) @ params["wo"]
    return out, cache


def attn_extend(params, x, cache, pos0, cfg, *, kind: str):
    """Dense-cache analogue of :func:`paged_attn_extend`: append ``S``
    tokens at absolute positions ``pos0 + j`` (per row) and attend
    causally over absolute positions.  The speculative verify step runs
    this over gather-hoisted virtual caches — one batched extend scores a
    whole draft window.  KV writes use ``mode="drop"`` so a frozen slot's
    window hanging past the cache edge writes nothing (a clamped write
    would corrupt the last live row)."""
    B, S, _ = x.shape
    rope_base = cfg.rope_local_base if kind == "local" else cfg.rope_base
    positions = pos0[:, None] + jnp.arange(S)[None, :]       # (B, S)
    q, k, v = _project_qkv(params, x, x, cfg, positions, positions,
                           rope_base)
    L = cache["k"].shape[1]
    bidx = jnp.arange(B)[:, None]
    cache = dict(cache)
    with jax.named_scope("kv_write"):
        cache["k"] = cache["k"].at[bidx, positions].set(
            k.astype(cache["k"].dtype), mode="drop")
        cache["v"] = cache["v"].at[bidx, positions].set(
            v.astype(cache["v"].dtype), mode="drop")
    valid = jnp.arange(L)[None, None, :] <= positions[:, :, None]
    out = mha(q, cache["k"], cache["v"], valid[:, None], cfg.attn_softcap)
    out = out.reshape(B, S, -1) @ params["wo"]
    return out, cache


@jax.named_scope("kv_write")
def prefill_into_cache(params_unused, k, v, cache, cfg, *, kind: str):
    """Write full-seq K/V (B,S,KV,hd) into a fresh cache."""
    S = k.shape[1]
    L = cache["k"].shape[1]
    if "pos" in cache:                                    # ring: keep last L
        take = min(S, L)
        idx = (jnp.arange(L) + (S - take)) % L if S >= L else jnp.arange(L)
        ks = k[:, -take:]
        vs = v[:, -take:]
        pos = jnp.arange(S - take, S)
        slots = pos % L
        cache = dict(cache)
        cache["k"] = cache["k"].at[:, slots].set(ks)
        cache["v"] = cache["v"].at[:, slots].set(vs)
        cache["pos"] = cache["pos"].at[:, slots].set(pos[None, :])
        return cache
    cache = dict(cache)
    cache["k"] = cache["k"].at[:, :S].set(k)
    cache["v"] = cache["v"].at[:, :S].set(v)
    return cache


# ----------------------------------------------------------------------
# Paged KV cache: K/V live in one shared block pool per layer instead of a
# dense (B, max_len) stripe per slot; each sequence names its blocks in a
# block table (serving/kvpool.py owns the host-side allocator).  Physical
# block 0 is the reserved null block: table padding points at it and
# masked/pad writes are redirected into it, so a stale entry can corrupt
# nothing.  Gather-through-the-table + masked mha is the exact jnp path
# (and the parity oracle); ``cfg.use_kernels`` routes decode through the
# Pallas paged kernels, which resolve the layer and the pool rows via
# scalar-prefetched block tables, write and read the layer-stacked pools in
# place, and never materialize a dense per-sequence cache.

def init_paged_kv_cache(cfg, num_blocks: int, block_size: int):
    """Per-layer block pool ``(num_blocks + 1, KV, block_size, hd)``:
    ``num_blocks`` usable + 1 reserved null row.  A block's last two axes
    are (positions, head_dim), the tiling the Pallas kernels DMA."""
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "kp": jnp.zeros((num_blocks + 1, KV, block_size, hd), cfg.act_dtype),
        "vp": jnp.zeros((num_blocks + 1, KV, block_size, hd), cfg.act_dtype),
    }


def is_paged_cache(cache) -> bool:
    return isinstance(cache, dict) and "kp" in cache


def pool_rows(pool, bt):
    """Pool ``(..., N, KV, bs, hd)`` gathered through a block table
    ``bt (n, w)`` into dense-layout rows ``(..., n, w*bs, KV, hd)``."""
    *lead, _, KV, bs, hd = pool.shape
    n, w = bt.shape
    rows = jnp.take(pool, bt, axis=len(lead))        # (..., n, w, KV, bs, hd)
    return rows.swapaxes(-2, -3).reshape(*lead, n, w * bs, KV, hd)


def pool_write(pool, phys, off, rows):
    """``pool (N, KV, bs, hd)`` with ``rows (B, S, KV, hd)`` written at
    block ``phys[b, s]``, offset ``off[b, s]``."""
    # phys/off sit on either side of the KV slice, so the indexed rows come
    # out as (B, S, KV, hd) — the layout of ``rows``
    return pool.at[phys, :, off].set(rows.astype(pool.dtype))


def _pool_slots(vpos, bt, bs: int):
    """Physical block and offset of each virtual position ``vpos (B, S)``
    through the block table ``bt (B, nb)``.  Positions beyond the table
    (prompt pads past ``nb*bs``) redirect to the null block."""
    nb = bt.shape[1]
    vblock = vpos // bs
    phys = jnp.take_along_axis(bt, jnp.minimum(vblock, nb - 1), axis=1)
    return jnp.where(vblock < nb, phys, 0), vpos % bs


@jax.named_scope("kv_write")
def _paged_scatter(cache, k, v, vpos, bt):
    """Write per-position K/V rows into the pool through the block table.

    k/v: (B, S, KV, hd); vpos: (B, S) virtual positions; bt: (B, nb)."""
    phys, off = _pool_slots(vpos, bt, cache["kp"].shape[-2])
    cache = dict(cache)
    cache["kp"] = pool_write(cache["kp"], phys, off, k)
    cache["vp"] = pool_write(cache["vp"], phys, off, v)
    return cache


def _paged_gather(cache, bt):
    """(B, nb*bs, KV, hd) virtual caches, materialized via the table."""
    return pool_rows(cache["kp"], bt), pool_rows(cache["vp"], bt)


def paged_attn_decode(params, x, cache, pos, bt, cfg, *, kind: str,
                      layer=None):
    """Single decode step over a paged cache.

    x: (B,1,d); pos: (B,) absolute write position; bt: (B, nb) block
    table.  Same math as :func:`attn_decode` on a dense cache holding the
    same tokens — validity is ``index <= pos`` either way.

    On the kernel path (``cfg.use_kernels``) the cache holds the pools
    stacked over the group's layers, ``(R, N, KV, bs, hd)``, as the layer
    loop carries them, and ``layer`` names this layer: the new row goes in
    through the aliased write kernel and the decode kernel reads the layer
    from the stack, so neither slices nor copies a pool.  Each live row
    writes a block no other row of the step writes (the engine allocates
    and copies-on-write ahead of the loop); idle rows write the null
    block."""
    B = x.shape[0]
    rope_base = cfg.rope_local_base if kind == "local" else cfg.rope_base
    q, k, v = _project_qkv(params, x, x, cfg, pos[:, None], pos[:, None],
                           rope_base)
    if cfg.use_kernels:
        from repro.kernels import ops as kops
        interpret = kops.use_interpret()
        phys, off = _pool_slots(pos[:, None], bt, cache["kp"].shape[-2])
        with jax.named_scope("kv_write"):
            kp, vp = kops.paged_kv_write(cache["kp"], cache["vp"], layer,
                                         phys[:, 0], off[:, 0], k[:, 0],
                                         v[:, 0], interpret=interpret)
        cache = {"kp": kp, "vp": vp}
        out = kops.paged_decode_attention(q[:, 0], kp, vp, bt, pos + 1,
                                          layer, interpret=interpret)
        out = out[:, None]
    else:
        cache = _paged_scatter(cache, k, v, pos[:, None], bt)
        kg, vg = _paged_gather(cache, bt)
        L = kg.shape[1]
        valid = jnp.arange(L)[None, :] <= pos[:, None]
        out = mha(q, kg, vg, valid[:, None, None, :], cfg.attn_softcap)
    out = out.reshape(B, 1, -1) @ params["wo"]
    return out, cache


def paged_attn_extend(params, x, cache, pos0, bt, cfg, *, kind: str):
    """Prefill a suffix into a paged cache: S tokens starting at absolute
    position ``pos0`` (per row), attending to the cached prefix blocks
    *and* causally within the suffix.  This is the paged admit path — a
    prefix-cache hit makes ``pos0 > 0`` and only the un-cached suffix is
    computed.  x: (B,S,d); pos0: (B,); bt: (B, nb)."""
    B, S, _ = x.shape
    rope_base = cfg.rope_local_base if kind == "local" else cfg.rope_base
    positions = pos0[:, None] + jnp.arange(S)[None, :]       # (B, S)
    q, k, v = _project_qkv(params, x, x, cfg, positions, positions,
                           rope_base)
    cache = _paged_scatter(cache, k, v, positions, bt)
    if cfg.use_kernels:
        # Pallas sibling of the decode kernel: online softmax over prefix
        # blocks + the just-scattered suffix, block tables scalar-prefetched
        # — no dense per-sequence materialization
        from repro.kernels import ops as kops
        out = kops.paged_extend_attention(q, cache["kp"], cache["vp"], bt,
                                          pos0,
                                          interpret=kops.use_interpret())
    else:
        kg, vg = _paged_gather(cache, bt)
        L = kg.shape[1]
        # causal over absolute positions: cache index l holds virtual pos l
        valid = jnp.arange(L)[None, None, :] <= positions[:, :, None]
        out = mha(q, kg, vg, valid[:, None], cfg.attn_softcap)
    out = out.reshape(B, S, -1) @ params["wo"]
    return out, cache


# ----------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV; absorbed decode.
def init_mla(key, cfg):
    d, H = cfg.d_model, cfg.n_heads
    r, rh, nh, vh = cfg.kv_lora_rank, cfg.rope_head_dim, cfg.nope_head_dim, cfg.v_head_dim
    dt = cfg.p_dtype
    ks = jax.random.split(key, 8)
    return {
        "wq": dense_init(ks[0], (d, H * (nh + rh)), ("embed", "heads"), dt),
        "w_dkv": dense_init(ks[1], (d, r), ("embed", None), dt),
        "w_krope": dense_init(ks[2], (d, rh), ("embed", None), dt),
        "kv_norm": ones_init((r,), (None,), dt),
        "w_uk": dense_init(ks[3], (r, H * nh), (None, "heads"), dt),
        "w_uv": dense_init(ks[4], (r, H * vh), (None, "heads"), dt),
        "wo": dense_init(ks[5], (H * vh, d), ("heads", "embed"), dt),
    }


def _mla_q(params, x, cfg, positions):
    B, S, _ = x.shape
    H, rh, nh = cfg.n_heads, cfg.rope_head_dim, cfg.nope_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, nh + rh)
    q_nope, q_rope = q[..., :nh], q[..., nh:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_base)
    return q_nope, q_rope


def mla_forward(params, x, cfg, positions=None):
    B, S, _ = x.shape
    H, rh, nh, vh = cfg.n_heads, cfg.rope_head_dim, cfg.nope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _mla_q(params, x, cfg, positions)
    ckv = rms_norm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)
    krope = apply_rope((x @ params["w_krope"])[:, :, None, :], positions,
                       cfg.rope_base)                     # (B,S,1,rh)
    k_nope = (ckv @ params["w_uk"]).reshape(B, S, H, nh)
    v = (ckv @ params["w_uv"]).reshape(B, S, H, vh)
    # assemble per-head q/k of width nh+rh; flash/mha scale 1/sqrt(nh+rh)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)        # (B,S,H,nh+rh)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(krope, (B, S, H, rh))], axis=-1)
    if S >= FLASH_MIN_SEQ:
        out = flash_attention_jnp(q, k, v, causal=True)
    else:
        out = mha(q, k, v, causal_mask(S, S)[None, None])
    out = out.reshape(B, S, H * vh)
    return out @ params["wo"], (ckv, krope[:, :, 0, :])


def init_mla_cache(cfg, batch: int, max_len: int):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.act_dtype),
        "krope": jnp.zeros((batch, max_len, cfg.rope_head_dim), cfg.act_dtype),
    }


def mla_decode(params, x, cache, pos, cfg):
    """Absorbed decode: scores and context in the compressed (r)-space."""
    B = x.shape[0]
    H, rh, nh, vh = cfg.n_heads, cfg.rope_head_dim, cfg.nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(params, x, cfg, pos[:, None])  # (B,1,H,·)
    ckv_t = rms_norm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)  # (B,1,r)
    krope_t = apply_rope((x @ params["w_krope"])[:, :, None, :], pos[:, None],
                         cfg.rope_base)[:, 0, 0]           # (B,rh)
    cache = dict(cache)
    cache["ckv"] = batched_cache_update(cache["ckv"], ckv_t[:, 0], pos)
    cache["krope"] = batched_cache_update(cache["krope"], krope_t, pos)
    # absorb: q_eff[h] = q_nope[h] @ w_uk[:, h]^T  -> (B,H,r)
    w_uk = params["w_uk"].reshape(r, H, nh)
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    L = cache["ckv"].shape[1]
    scale = 1.0 / math.sqrt(nh + rh)
    s = (jnp.einsum("bhr,bsr->bhs", q_eff, cache["ckv"])
         + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0], cache["krope"])).astype(jnp.float32) * scale
    valid = jnp.arange(L)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    ctx_c = jnp.einsum("bhs,bsr->bhr", p, cache["ckv"])    # (B,H,r)
    w_uv = params["w_uv"].reshape(r, H, vh)
    out = jnp.einsum("bhr,rhd->bhd", ctx_c, w_uv).reshape(B, 1, H * vh)
    return out @ params["wo"], cache
