"""Unified decoder LM over heterogeneous ScanGroups.

Layers are stacked per (group, pattern-position) and iterated with
``jax.lax.scan`` so compiled HLO size (and compile time) is independent of
depth; remat policy wraps the scan body.  Supports:

  kinds A/L/G (attention: full / sliding-window / dual-rope-global),
  M (attention+MoE; MLA attention if cfg.kv_lora_rank), D (dense layer in a
  MoE model), S (Mamba-1), R (RG-LRU recurrent block).

Three modes share one code path: ``full`` (train / scoring), ``prefill``
(full pass that also fills caches), ``decode`` (single-token step with
caches).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sharding import Param, shard, split_params
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (dense_init, embed, init_embedding, init_mlp,
                                 apply_mlp, layer_norm, mask_padded_logits,
                                 ones_init, rms_norm, unembed, zeros_init)

ATTN_KINDS = ("A", "L", "G", "M", "D")


# ----------------------------------------------------------------------
# norms
def init_norm(cfg):
    if cfg.norm == "layernorm":
        return {"w": ones_init((cfg.d_model,), (None,), cfg.p_dtype),
                "b": zeros_init((cfg.d_model,), (None,), cfg.p_dtype)}
    w = jnp.zeros if cfg.rms_plus_one else jnp.ones
    return {"w": Param(w((cfg.d_model,), cfg.p_dtype), (None,))}


def apply_norm(p, x, cfg):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps, plus_one=cfg.rms_plus_one)


# ----------------------------------------------------------------------
# per-layer init
def init_layer(key, cfg, kind: str):
    ks = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg)}
    if kind == "S":
        p["mixer"] = ssm_mod.init_ssm(ks[0], cfg)
        return p
    if kind == "R":
        p["mixer"] = rglru_mod.init_rglru(ks[0], cfg)
    elif kind == "M" and cfg.kv_lora_rank:
        p["mixer"] = attn.init_mla(ks[0], cfg)
    else:
        p["mixer"] = attn.init_attn(ks[0], cfg)
    p["ln2"] = init_norm(cfg)
    if kind == "M":
        p["ffn"] = moe_mod.init_moe(ks[1], cfg)
    elif kind == "D":
        p["ffn"] = init_mlp(ks[1], cfg, d_ff=cfg.dense_d_ff or cfg.d_ff)
    else:
        p["ffn"] = init_mlp(ks[1], cfg)
    return p


def init_layer_cache(cfg, kind: str, batch: int, max_len: int):
    if kind == "S":
        return ssm_mod.init_ssm_state(cfg, batch)
    if kind == "R":
        return rglru_mod.init_rglru_state(cfg, batch)
    if kind == "M" and cfg.kv_lora_rank:
        return attn.init_mla_cache(cfg, batch, max_len)
    ring = kind == "L" and cfg.window and cfg.window < max_len
    return attn.init_kv_cache(cfg, batch, max_len, ring=bool(ring))


def paged_supported(cfg, max_len: int) -> bool:
    """Can this arch serve from a paged KV block pool?

    Attention layers with a standard (non-ring) KV cache page naturally:
    the cache is position-addressed, so positions can live in scattered
    physical blocks.  SSM ("S") / RG-LRU ("R") carry *recurrent state*,
    not a position-addressed cache — nothing to page; MLA ("M" with
    ``kv_lora_rank``) uses its own compressed cache format; a ring cache
    ("L" with ``window < max_len``) aliases positions modulo the window.
    Those families keep the dense path.
    """
    for g in cfg.groups:
        for kind in g.pattern:
            if kind in ("S", "R"):
                return False
            if kind == "M" and cfg.kv_lora_rank:
                return False
            if kind == "L" and cfg.window and cfg.window < max_len:
                return False
    return True


def init_paged_caches(cfg, num_blocks: int, block_size: int):
    """Block-pool caches: one shared ``(num_blocks+1, KV, bs, hd)`` K/V
    pool per layer (row 0 reserved as the null block) instead of a dense
    per-slot stripe, stacked over each group's repeats like
    :func:`init_caches`.  The admit scan slices a layer's pool from the
    stack as ``xs``; the kernel decode loop carries the stacks whole
    (:func:`run_backbone`)."""
    if not paged_supported(cfg, max_len=1 << 30):
        raise ValueError(f"{cfg.name}: family holds non-pageable state "
                         f"(SSM/RG-LRU/MLA/ring) — use the dense cache")
    caches = []
    for g in cfg.groups:
        pos_caches = []
        for kind in g.pattern:
            c = attn.init_paged_kv_cache(cfg, num_blocks, block_size)
            c = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (g.repeats,) + a.shape), c)
            pos_caches.append(c)
        caches.append(pos_caches)
    return caches


# ----------------------------------------------------------------------
# per-layer apply
def apply_layer(p, x, cfg, kind: str, mode: str, cache, pos, bt=None,
                layer=None):
    """Returns (x, aux, new_cache).  ``bt`` is the (B, nb) block table
    when ``cache`` is paged (decode/extend modes); ``layer`` is the layer's
    index in the stacked pools of ``cache`` on the kernel decode path.

    The mixer half (``ln1`` through its residual add) runs under the
    named scope ``attention`` (``recurrent`` for S/R layers), the FFN half
    (``ln2`` through its residual add) under ``mlp``: each op's metadata
    carries the scope, so a profiler trace can split a layer's device time
    without the scope costing anything at run time."""
    mixer = "recurrent" if kind in ("S", "R") else "attention"
    with jax.named_scope(mixer):
        x, cache = _apply_mixer(p, x, cfg, kind, mode, cache, pos, bt,
                                layer)
    if kind == "S":
        return x, jnp.zeros((), jnp.float32), cache
    with jax.named_scope("mlp"):
        return _apply_ffn(p, x, cfg, kind) + (cache,)


def _apply_mixer(p, x, cfg, kind: str, mode: str, cache, pos, bt, layer):
    """``x + mixer(ln1(x))`` and the layer's new cache."""
    h = apply_norm(p["ln1"], x, cfg)

    if kind == "S":
        if mode == "decode":
            mix, cache = ssm_mod.ssm_decode(p["mixer"], h, cache, cfg)
        else:
            mix, new_state = ssm_mod.ssm_forward(
                p["mixer"], h, cfg, state=None)
            cache = new_state if mode == "prefill" else cache
        return x + mix, cache

    if kind == "R":
        if mode == "decode":
            mix, cache = rglru_mod.rglru_decode(p["mixer"], h, cache, cfg)
        else:
            mix, new_state = rglru_mod.rglru_forward(p["mixer"], h, cfg, state=None)
            cache = new_state if mode == "prefill" else cache
    elif kind == "M" and cfg.kv_lora_rank:
        if mode == "decode":
            mix, cache = attn.mla_decode(p["mixer"], h, cache, pos, cfg)
        else:
            mix, (ckv, krope) = attn.mla_forward(p["mixer"], h, cfg)
            if mode == "prefill":
                S = ckv.shape[1]
                cache = dict(cache)
                cache["ckv"] = cache["ckv"].at[:, :S].set(ckv.astype(cache["ckv"].dtype))
                cache["krope"] = cache["krope"].at[:, :S].set(krope.astype(cache["krope"].dtype))
    else:
        akind = {"A": "causal", "G": "global", "L": "local",
                 "M": "causal", "D": "causal"}[kind]
        from repro.core.sharding import current_ctx
        ctx = current_ctx()
        S = h.shape[1]
        use_seqshard = (ctx is not None and ctx.policy == "seqtp"
                        and mode != "decode" and S >= attn.FLASH_MIN_SEQ
                        and S % ctx.mesh.shape.get("model", 1) == 0)
        if mode == "decode" and attn.is_paged_cache(cache):
            mix, cache = attn.paged_attn_decode(p["mixer"], h, cache, pos,
                                                bt, cfg, kind=akind,
                                                layer=layer)
        elif mode == "extend" and attn.is_paged_cache(cache):
            # paged suffix prefill: S tokens appended at absolute position
            # `pos` (per row), attending through the block table
            mix, cache = attn.paged_attn_extend(p["mixer"], h, cache, pos,
                                                bt, cfg, kind=akind)
        elif mode == "extend":
            # dense-cache extend: the speculative verify window
            mix, cache = attn.attn_extend(p["mixer"], h, cache, pos, cfg,
                                          kind=akind)
        elif mode == "decode":
            mix, cache = attn.attn_decode(p["mixer"], h, cache, pos, cfg, kind=akind)
        elif use_seqshard:
            mix, k, v = attn.seqshard_attn_forward(
                p["mixer"], h, cfg, kind=akind, mesh=ctx.mesh,
                batch_axes=ctx.rules.get("batch"))
            if mode == "prefill":
                cache = attn.prefill_into_cache(None, k, v, cache, cfg,
                                                kind=akind)
        elif mode == "prefill":
            B, S, _ = h.shape
            positions = jnp.arange(S)[None, :]
            rope_base = cfg.rope_local_base if akind == "local" else cfg.rope_base
            q, k, v = attn._project_qkv(p["mixer"], h, h, cfg,
                                        positions, positions, rope_base)
            cache = attn.prefill_into_cache(None, k, v, cache, cfg, kind=akind)
            mix = attn.attn_forward(p["mixer"], h, cfg, kind=akind, qkv=(q, k, v))
        else:
            mix = attn.attn_forward(p["mixer"], h, cfg, kind=akind)
    return x + mix, cache


def _apply_ffn(p, x, cfg, kind: str):
    """``(x + ffn(ln2(x)), aux)``: aux is the MoE router loss, else 0."""
    aux = jnp.zeros((), jnp.float32)
    h2 = apply_norm(p["ln2"], x, cfg)
    if kind == "M":
        f, aux = moe_mod.apply_moe(p["ffn"], h2, cfg)
    else:
        f = apply_mlp(p["ffn"], h2, cfg)
    return x + f, aux


# ----------------------------------------------------------------------
# parameter trees
def _stack_params(trees):
    def stack(*leaves):
        if isinstance(leaves[0], Param):
            return Param(jnp.stack([l.value for l in leaves]),
                         ("layers",) + leaves[0].axes)
        return jnp.stack(leaves)
    return jax.tree_util.tree_map(stack, *trees,
                                  is_leaf=lambda l: isinstance(l, Param))


def init_group_params(key, cfg, group):
    """list over pattern positions; each a Param tree stacked over repeats."""
    out = []
    for pidx, kind in enumerate(group.pattern):
        reps = [init_layer(jax.random.fold_in(key, pidx * 4096 + r), cfg, kind)
                for r in range(group.repeats)]
        out.append(_stack_params(reps) if group.repeats > 1 else
                   jax.tree_util.tree_map(
                       lambda p: Param(p.value[None], ("layers",) + p.axes),
                       reps[0], is_leaf=lambda l: isinstance(l, Param)))
    return out


def init_params(key, cfg):
    ks = jax.random.split(key, 2 + len(cfg.groups))
    p = {"embedding": init_embedding(ks[0], cfg),
         "final_norm": init_norm(cfg),
         "groups": [init_group_params(ks[2 + i], cfg, g)
                    for i, g in enumerate(cfg.groups)]}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.padded_vocab),
                                  ("embed", "vocab"), cfg.p_dtype)
    return p


def init_caches(cfg, batch: int, max_len: int):
    caches = []
    for g in cfg.groups:
        pos_caches = []
        for kind in g.pattern:
            c = init_layer_cache(cfg, kind, batch, max_len)
            c = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (g.repeats,) + a.shape), c)
            pos_caches.append(c)
        caches.append(pos_caches)
    return caches


# ----------------------------------------------------------------------
# backbone runner
def _remat_wrap(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _carries_pools(cfg, mode: str, caches) -> bool:
    """Kernel decode over paged pools: the layer loop carries the stacked
    pools and the kernels write and read them in place."""
    return (mode == "decode" and cfg.use_kernels and caches is not None
            and all(attn.is_paged_cache(c) for gc in caches for c in gc))


def _run_group_carrying_pools(gp, x, aux, cfg, g, pools, pos, bt):
    """One group's layers in decode over its stacked paged pools
    ``(R, N+1, KV, bs, hd)``, one stack per pattern position.

    Only the parameters are scanned in per layer; the pools ride in the
    carry whole, and each layer's write kernel (aliased) and decode kernel
    take the layer index.  So no per-layer pool slice is taken or stacked
    back, and the compiler keeps every pool in place from loop entry to
    exit.  Always a scan without remat: the unrolled form serves the
    dry-run's cost pass, which runs dense caches, and decode keeps nothing
    for a backward pass."""
    def body(carry, layer_ps):
        r, xx, aux, pools = carry
        pools = list(pools)
        for pi, kind in enumerate(g.pattern):
            xx, a, pools[pi] = apply_layer(layer_ps[pi], xx, cfg, kind,
                                           "decode", pools[pi], pos, bt,
                                           layer=r)
            aux = aux + a
        return (r + 1, xx, aux, pools), None

    (_, x, aux, pools), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.int32), x, aux, list(pools)), gp)
    return x, aux, pools


def run_backbone(params, x, cfg, mode: str, caches=None, pos=None, bt=None):
    """x: (B,S,d) embedded input.  Returns (x, aux, new_caches).
    ``bt``: (B, nb) block table for paged caches (loop-invariant).

    Kernel decode over paged pools carries the stacked pools through the
    layer loop (:func:`_run_group_carrying_pools`); every other mode and
    cache scans each layer's cache in as ``xs`` and stacks it back as
    ``ys``."""
    aux0 = jnp.zeros((), jnp.float32)
    new_caches = []
    carry_pools = _carries_pools(cfg, mode, caches)
    for gi, g in enumerate(cfg.groups):
        gp = params["groups"][gi]
        if carry_pools:
            x, aux0, pools = _run_group_carrying_pools(
                gp, x, aux0, cfg, g, caches[gi], pos, bt)
            new_caches.append(pools)
            continue
        gc = caches[gi] if caches is not None else [None] * len(g.pattern)

        def body(carry, per_rep, _pattern=g.pattern):
            xx, aux = carry
            layer_ps, layer_cs = per_rep
            ncs = []
            for pi, kind in enumerate(_pattern):
                cc = layer_cs[pi] if layer_cs is not None else None
                xx, a, nc = apply_layer(layer_ps[pi], xx, cfg, kind, mode,
                                        cc, pos, bt)
                aux = aux + a
                ncs.append(nc)
            return (xx, aux), (tuple(ncs) if layer_cs is not None else None)

        body = _remat_wrap(body, cfg)
        xs_cache = tuple(gc) if caches is not None else None
        if cfg.scan_layers:
            (x, aux0), ys = jax.lax.scan(body, (x, aux0), (gp, xs_cache))
        else:
            # unrolled (dry-run cost pass; also useful for debugging)
            ys_list = []
            for r in range(g.repeats):
                take = lambda t: jax.tree_util.tree_map(lambda a: a[r], t)
                (x, aux0), y = body((x, aux0), (take(gp),
                                                take(xs_cache) if xs_cache is not None else None))
                ys_list.append(y)
            ys = (jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ys_list)
                  if ys_list and ys_list[0] is not None else None)
        new_caches.append(list(ys) if ys is not None else None)
    return x, aux0, new_caches


# ----------------------------------------------------------------------
# public entry points.  The token embedding runs under the named scope
# ``embed``; the final norm, LM head and sampling under ``head``.
@jax.named_scope("embed")
def _embed(params, cfg, tokens=None, embeds=None):
    if embeds is None:
        x = embed(params["embedding"], tokens, cfg)
    else:
        x = embeds.astype(cfg.act_dtype)
    return shard(x, "batch", "seq", "embed")


@jax.named_scope("head")
def _final(params, x, cfg):
    """Final norm and LM head: logits."""
    return _head(params, apply_norm(params["final_norm"], x, cfg), cfg)


def forward(params, cfg, tokens=None, embeds=None):
    """Full-sequence causal LM forward.  Returns (logits, aux)."""
    x = _embed(params, cfg, tokens, embeds)
    x, aux, _ = run_backbone(params, x, cfg, "full")
    return _final(params, x, cfg), aux


def prefill(params, cfg, tokens, caches, embeds=None, last_index=None):
    """Fill caches with a full pass; returns (logits at `last_index`
    (default: final position), caches)."""
    x = _embed(params, cfg, tokens, embeds)
    x, aux, caches = run_backbone(params, x, cfg, "prefill", caches,
                                  pos=None)
    if last_index is None:
        x = x[:, -1:]
    else:
        li = jnp.asarray(last_index)
        if li.ndim == 0:
            x = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        else:
            # per-row last positions: bucketed batch prefill pads prompts to
            # a shared length, so each row's true final token sits at its
            # own index
            x = jnp.take_along_axis(x, li.astype(jnp.int32)[:, None, None],
                                    axis=1)
    return _final(params, x, cfg), caches


def decode_step(params, cfg, tokens, caches, pos, bt=None):
    """tokens: (B,1) int32; pos: (B,) absolute position being written;
    ``bt``: (B, nb) block table when ``caches`` are paged."""
    x = _embed(params, cfg, tokens)
    x, aux, caches = run_backbone(params, x, cfg, "decode", caches, pos=pos,
                                  bt=bt)
    return _final(params, x, cfg), caches


def extend_paged(params, cfg, tokens, caches, pos0, bt, last_index):
    """Paged admit pass: append ``tokens (B,S)`` to sequences whose first
    ``pos0 (B,)`` positions are already cached in the block pool (a
    prefix-cache hit), writing suffix K/V through the block table ``bt``
    and returning logits at per-row ``last_index`` (into the suffix) plus
    the updated pool caches.  With ``pos0 == 0`` this is a full paged
    prefill."""
    x = _embed(params, cfg, tokens)
    x, aux, caches = run_backbone(params, x, cfg, "extend", caches,
                                  pos=pos0, bt=bt)
    li = jnp.asarray(last_index).astype(jnp.int32)
    x = jnp.take_along_axis(x, li[:, None, None], axis=1)
    return _final(params, x, cfg), caches


@jax.named_scope("head")
def sample_tokens(logits, temperature: float = 0.0, rng=None):
    """In-jit sampling.  logits: (B, V) -> (B,) int32.

    ``temperature`` is a *static* policy: 0.0 compiles to greedy argmax (the
    parity-tested default), anything else to categorical sampling at that
    temperature (``rng`` required)."""
    if temperature and temperature > 0.0:
        if rng is None:
            raise ValueError("temperature sampling needs an rng key")
        return jax.random.categorical(
            rng, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def decode_fused(params, cfg, tokens, caches, pos, *, temperature: float = 0.0,
                 rng=None, bt=None):
    """One decode step that never ships logits to the host: embed -> backbone
    -> head -> sample, returning only the (B,) sampled token ids (instead of
    the (B, vocab) logits) plus the updated caches."""
    logits, caches = decode_step(params, cfg, tokens, caches, pos, bt=bt)
    return sample_tokens(logits[:, 0], temperature, rng), caches


# ----------------------------------------------------------------------
# Paged virtual caches.  The fused K-step loop over a paged pool used to
# resolve the block table on EVERY decode step (a scatter + full gather
# per layer per step, all inside the jit).  Hoisting the indirection out
# of the loop — materialize each sequence's blocks once as a dense-layout
# "virtual" cache, run the unchanged dense loop body on it, scatter back
# only the rows the loop can have written — removes all per-step table
# resolution at bitwise-identical math: the gather is an exact copy, and
# rows the two layouts zero-fill differently are masked out of the
# softmax either way (exp(NEG_INF - m) == 0.0 exactly).  Bonus: the
# virtual width is the engine's *bucketed live-sequence width* (nb * bs
# for the widest table in use), not max_len, so attention reads shrink
# with the actual context — which is how paged decode gets to beat dense.

def gather_paged_virtual(caches, bt):
    """Materialize per-slot dense caches from the block pools.

    ``bt (B, nb)`` may be narrower than the full table (width-bucketed by
    the engine); the result leaves are ``{"k","v"} (R, B, nb*bs, KV, hd)``
    — exactly the layout :func:`init_caches` builds, so every dense
    decode path runs on them unchanged."""
    out = []
    for gc in caches:
        out.append([{"k": attn.pool_rows(c["kp"], bt),
                     "v": attn.pool_rows(c["vp"], bt)} for c in gc])
    return out


def refresh_paged_virtual(virt, caches, bt_rows, slot_idx):
    """Surgically re-gather ``len(slot_idx)`` slots of a resident virtual
    cache from the block pools, leaving every other slot's rows untouched.

    The admit path uses this instead of a full regather: freshly admitted
    slots' pool rows were just written by the admit prefill, while the
    *other* slots' resident rows may be ahead of the pool (lazy
    writeback) and must NOT be re-read from it.  ``bt_rows (n, vw)`` is
    each admitted slot's table cut to the resident width; duplicate
    ``slot_idx`` entries (batch padding) write identical values."""
    out = []
    for gv, gc in zip(virt, caches):
        row = []
        for cv, c in zip(gv, gc):
            row.append({
                "k": cv["k"].at[:, slot_idx].set(
                    attn.pool_rows(c["kp"], bt_rows).astype(cv["k"].dtype)),
                "v": cv["v"].at[:, slot_idx].set(
                    attn.pool_rows(c["vp"], bt_rows).astype(cv["v"].dtype)),
            })
        out.append(row)
    return out


def scatter_paged_back(caches, virt, bt, start, width: int, stop=None):
    """Write rows ``[start, start + width)`` of the virtual caches back
    into the block pools — the only rows a loop starting at ``start`` can
    have written.  Rows past a sequence's table redirect to the null
    block (so a frozen slot's junk writes and a finished slot's nulled
    table persist nothing real); rows past the virtual width clamp on
    read but are likewise null-redirected.  ``stop (B,)`` additionally
    null-redirects rows ``>= stop[s]`` — the lazy-writeback flush uses it
    to clamp each slot to its own written count, so one slot's pending
    width can't push another slot's junk tail into a still-shared
    (not-yet-COWed) block."""
    B, nb = bt.shape
    bs = caches[0][0]["kp"].shape[-2]
    L = virt[0][0]["k"].shape[2]
    rows = start[:, None] + jnp.arange(width)[None, :]           # (B, W)
    take = jnp.minimum(rows, L - 1)[None, :, :, None, None]
    vblock = rows // bs
    phys = jnp.take_along_axis(bt, jnp.minimum(vblock, nb - 1), axis=1)
    phys = jnp.where(vblock < nb, phys, 0)
    if stop is not None:
        phys = jnp.where(rows < stop[:, None], phys, 0)
    off = rows % bs
    # one pool_write per stacked layer: pools (R, N, KV, bs, hd), rows
    # (R, B, W, KV, hd)
    write = jax.vmap(lambda p, r: attn.pool_write(p, phys, off, r))
    out = []
    for gc, gv in zip(caches, virt):
        row_out = []
        for c, cv in zip(gc, gv):
            kr = jnp.take_along_axis(cv["k"], take, axis=2)
            vr = jnp.take_along_axis(cv["v"], take, axis=2)
            row_out.append({"kp": write(c["kp"], kr),
                            "vp": write(c["vp"], vr)})
        out.append(row_out)
    return out


def decode_loop(params, cfg, caches, pos, last, active, remaining, rng, *,
                k: int, max_len: int, temperature: float = 0.0, bt=None):
    """K fused decode steps with one host sync at the end.

    All loop state lives on device: ``pos`` (B,) next write position,
    ``last`` (B,) last sampled token, ``active`` (B,) bool slot liveness,
    ``remaining`` (B,) decode-token budget.  Per-slot stop is honored
    *exactly* via masking — an exhausted slot's pos/last/budget freeze and
    its tokens stop being emitted, while the batch keeps stepping (batch
    elements never interact inside a step, so frozen slots cannot perturb
    live ones).  Returns ``(out (B,k) int32, emitted (B,) int32, caches,
    pos, last, active, remaining, rng)``; ``out[s, :emitted[s]]`` are slot
    s's real tokens (liveness is monotone within the loop, so they form a
    prefix).

    With ``bt`` (paged caches) the jnp path runs gather-hoisted: virtual
    dense caches once per K steps, the identical dense body inside, one
    bounded scatter-back at the end.  ``cfg.use_kernels`` runs every step
    on the pools themselves, in place: the layer loop carries the stacked
    pools, the write kernel puts each step's K/V row into its block and
    the decode kernel reads the layer's blocks (:func:`run_backbone`), so
    neither a dense copy nor a per-layer pool slice is materialized.
    """
    if bt is not None and not cfg.use_kernels:
        start = pos
        out, emitted, virt, pos, last, active, remaining, rng = decode_loop(
            params, cfg, gather_paged_virtual(caches, bt), pos, last,
            active, remaining, rng, k=k, max_len=max_len,
            temperature=temperature)
        caches = scatter_paged_back(caches, virt, bt, start, k)
        return out, emitted, caches, pos, last, active, remaining, rng

    def body(i, carry):
        caches, pos, last, active, remaining, rng, out, emitted = carry
        rng, sub = jax.random.split(rng)
        nxt, caches = decode_fused(params, cfg, last[:, None], caches, pos,
                                   temperature=temperature, rng=sub, bt=bt)
        nxt = jnp.where(active, nxt, last)
        out = jax.lax.dynamic_update_index_in_dim(out, nxt, i, 1)
        emitted = emitted + active.astype(jnp.int32)
        live = active.astype(jnp.int32)
        pos = pos + live
        remaining = remaining - live
        active = active & (remaining > 0) & (pos < max_len - 1)
        # a slot that just went inactive feeds token 0 from here on, exactly
        # like the reference loop's zero-fill for empty slots — keeps the
        # batch composition identical for archs where rows couple (MoE)
        last = jnp.where(active, nxt, jnp.zeros_like(nxt))
        return caches, pos, last, active, remaining, rng, out, emitted

    out0 = jnp.zeros((pos.shape[0], k), jnp.int32)
    em0 = jnp.zeros((pos.shape[0],), jnp.int32)
    caches, pos, last, active, remaining, rng, out, emitted = jax.lax.fori_loop(
        0, k, body, (caches, pos, last, active, remaining, rng, out0, em0))
    return out, emitted, caches, pos, last, active, remaining, rng


# ----------------------------------------------------------------------
# Speculative multi-token decode (paged engines, greedy only).
def ngram_draft(hist, pos, last, d: int):
    """Bigram n-gram draft: find the most recent earlier occurrence of
    the (previous token, last token) bigram in the on-device history and
    propose the ``d`` tokens that followed it; with no match, repeat the
    last token.  One masked scan plus one gather over ``hist`` — free
    next to a backbone pass, and surprisingly effective on repetitive
    output (which greedy LM decode produces in abundance)."""
    B, L = hist.shape
    prev = jnp.take_along_axis(hist, jnp.maximum(pos - 1, 0)[:, None],
                               axis=1)[:, 0]
    i = jnp.arange(1, L)
    ok = (hist[:, :-1] == prev[:, None]) & (hist[:, 1:] == last[:, None]) \
        & (i[None, :] < pos[:, None])
    m = jnp.max(jnp.where(ok, i[None, :], -1), axis=1)
    cont = jnp.where(m >= 0, m + 1, pos)
    idx = jnp.minimum(cont[:, None] + jnp.arange(d)[None, :], pos[:, None])
    return jnp.take_along_axis(hist, idx, axis=1)


def verify_extend(params, cfg, tokens, caches, pos0):
    """Speculative verify: one batched dense-cache extend of the (B, d+1)
    window ``[last] ++ draft`` at absolute positions ``pos0 + j``,
    returning greedy argmax targets at EVERY window position plus the
    updated caches.  Position j's logits are computed from exactly the
    tokens a non-speculative loop would have in cache when sampling the
    token for position ``pos0 + j + 1`` — provided tokens[0..j] all match
    what that loop would have emitted, which is precisely the accepted
    prefix the caller keeps."""
    x = _embed(params, cfg, tokens)
    x, _, caches = run_backbone(params, x, cfg, "extend", caches,
                                pos=pos0, bt=None)
    return sample_tokens(_final(params, x, cfg)), caches


def spec_decode_loop(params, cfg, caches, hist, pos, last, active, remaining,
                     rng, *, k: int, d: int, max_len: int, bt,
                     draft_fn=None, virt=None):
    """K speculative verify iterations over a paged cache, one host sync.

    Each iteration drafts ``d`` tokens (``draft_fn(hist, pos, last, d)``,
    default :func:`ngram_draft`), verifies ``[last] ++ draft`` in ONE
    batched extend over the gather-hoisted virtual caches, and emits the
    accepted draft prefix plus the first correction — between 1 and d+1
    tokens per backbone pass.  Token-exact vs the non-speculative loop:
    every emitted token is the greedy argmax of a context consisting
    entirely of previously-emitted tokens (acceptance stops at the first
    draft/target mismatch, so no unverified token ever conditions an
    emitted one).  Greedy only — the engine enforces temperature == 0.

    ``hist (B, max_len)`` is the device token history (``hist[p]`` = the
    token at position p for every p <= pos); paged admits seed it and
    this loop maintains it.  Returns ``(out (B, k*(d+1)), emitted (B,),
    stats (2,) int32 [extra tokens accepted, drafts proposed], caches,
    virt, hist, pos, last, active, remaining, rng)``.

    ``virt`` may carry a still-valid virtual cache from a previous sync
    (the engine keeps it device-resident and invalidates on admit/fork/
    width change); ``None`` gathers a fresh one from the pool.  With
    ``caches=None`` (requires ``virt``) the pool scatter-back is skipped
    entirely — the engine's lazy-writeback mode, where the pool is made
    authoritative only when something needs to read it.
    """
    if draft_fn is None:
        draft_fn = ngram_draft
    start = pos
    if virt is None:
        virt = gather_paged_virtual(caches, bt)
    B = pos.shape[0]
    W = k * (d + 1)

    def body(i, carry):
        (virt, hist, pos, last, active, remaining, out, emitted,
         acc, prop) = carry
        draft = draft_fn(hist, pos, last, d)                    # (B, d)
        window = jnp.concatenate([last[:, None], draft], axis=1)
        targets, virt = verify_extend(params, cfg, window, virt, pos)
        match = (draft == targets[:, :d]).astype(jnp.int32)
        a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)         # (B,)
        cap = jnp.minimum(remaining, jnp.maximum(max_len - 1 - pos, 0))
        e = jnp.where(active, jnp.minimum(a + 1, cap), 0).astype(jnp.int32)
        # write the whole d+1 window at column `emitted`: entries past the
        # accepted count are junk that the next iteration's window (which
        # starts exactly at the new `emitted`) overwrites; a frozen slot's
        # writes land in [emitted, emitted+d+1) which never reaches W
        # because inactivity at iteration j implies emitted <= (d+1)(j+1)
        out = jax.vmap(
            lambda o, t, s: jax.lax.dynamic_update_slice_in_dim(o, t, s, 0)
        )(out, targets, emitted)
        # history rows pos+1 .. pos+d+1 get the verified targets; rows
        # beyond the accepted count are junk above the new pos — never
        # read (the draft clips reads at pos) and overwritten by the next
        # iteration before pos reaches them.  mode="drop" so a window
        # hanging past max_len can't clamp-corrupt a live row.
        hidx = pos[:, None] + 1 + jnp.arange(d + 1)[None, :]
        hist = hist.at[jnp.arange(B)[:, None], hidx].set(targets,
                                                         mode="drop")
        acc = acc + jnp.sum(jnp.where(active, e - 1, 0))
        prop = prop + jnp.sum(jnp.where(active, d, 0))
        emitted = emitted + e
        pos = pos + e
        remaining = remaining - e
        active = active & (remaining > 0) & (pos < max_len - 1)
        last_new = jnp.take_along_axis(
            targets, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
        last = jnp.where(active, last_new, jnp.zeros_like(last))
        return (virt, hist, pos, last, active, remaining, out, emitted,
                acc, prop)

    out0 = jnp.zeros((B, W), jnp.int32)
    em0 = jnp.zeros((B,), jnp.int32)
    z = jnp.zeros((), jnp.int32)
    (virt, hist, pos, last, active, remaining, out, emitted, acc, prop) = \
        jax.lax.fori_loop(0, k, body, (virt, hist, pos, last, active,
                                       remaining, out0, em0, z, z))
    # the last verify's speculative rows reach start + emitted + d, so the
    # scatter-back window is d+1 wider than the emission bound
    if caches is not None:
        L = virt[0][0]["k"].shape[2]
        caches = scatter_paged_back(caches, virt, bt, start,
                                    min(W + d + 1, L))
    return (out, emitted, jnp.stack([acc, prop]), caches, virt, hist, pos,
            last, active, remaining, rng)


def _head(params, x, cfg):
    if cfg.tie_embeddings:
        logits = unembed(params["embedding"], x, cfg)
    else:
        logits = x @ params["lm_head"]
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
        logits = mask_padded_logits(logits, cfg)
    return shard(logits, "batch", "seq", "vocab")


# ----------------------------------------------------------------------
# loss
def lm_loss(params, cfg, tokens, targets=None, embeds=None):
    """Next-token cross-entropy (mean over tokens) + router aux."""
    logits, aux = forward(params, cfg, tokens=tokens, embeds=embeds)
    if targets is None:
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = jnp.ones_like(nll)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux, (loss, aux)
