"""LM serving engine with continuous batching.

This is the paper's two-phase pipeline read onto LM serving (see
docs/DESIGN.md, "Two-phase pipeline -> serving"):
prefill is the per-instance *map* (each request independent), the batcher is
the *aggregation* (requests meet in a shared decode batch), and the decode
step is the parallel post-aggregation map.  Weights are placed once
(broadcast/tp policy) and reused across micro-batches — the mapPartitions
amortization.

Static shapes throughout: a fixed number of decode slots; prefill pads to
power-of-two buckets (pad-tolerant families only) to bound recompilation.

Two hot paths (``ServeConfig.fused``):

* **fused** (default): a decode iteration never leaves the device — the
  jitted step embeds, runs the backbone, and *samples in-jit* (greedy or
  temperature), returning only ``(slots,)`` token ids; caches / pos /
  last-token / liveness / budget are donated device buffers updated in
  place; a ``lax.fori_loop`` runs ``sync_every`` (K) steps per host sync
  with per-slot stop honored exactly via masking; admits run as bucketed
  batch prefill fused with a donated slot insert.
* **reference**: the original per-token loop (one host round trip and a
  ``(slots, vocab)`` logits transfer per token, full cache re-materialized
  per step and per admit).  It is the parity oracle
  (``tests/test_serving_fused.py``) and the "before" side of
  ``BENCH_serving.json``.

With ``ServeConfig.paged`` the fused loop additionally runs against a
**paged KV cache** (``serving/kvpool.py``): K/V live in a shared
per-layer block pool addressed through per-slot block tables, blocks are
allocated as decode advances (not reserved at ``max_len``), shared
system/task prompts are prefilled once via a content-hashed prefix cache,
and forks share blocks copy-on-write.  Token-exact vs the dense fused
path (``tests/test_serving_paged.py``); capacity numbers in
``BENCH_serving.json`` under ``"paged"``.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.metrics import MetricsRegistry
from repro.cluster.tracing import (NULL_SPAN, current_recorder,
                                   current_tracer)
from repro.models import api, transformer as tfm
from repro.serving.kvpool import (NULL_BLOCK, BlockAllocator, PoolExhausted,
                                  hash_token_blocks_memo, pack_block_arrays,
                                  padded_table, unpack_block_arrays)


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512              # cache length per slot
    slots: int = 4                  # decode batch size (continuous batching)
    fused: bool = True              # on-device K-step loop + in-jit sampling
    sync_every: int = 8             # K: decode steps per host sync (fused)
    temperature: float = 0.0        # 0.0 -> greedy argmax (in-jit either way)
    seed: int = 0                   # sampling rng seed (temperature > 0)
    # Pad prompts up to power-of-two buckets so several queued requests
    # prefill in one call.  Auto-gated: recurrent archs (SSM/RG-LRU) would
    # absorb pads into their state, MoE capacity couples batch rows, and
    # ring (windowed) caches could evict real K/V — those families keep the
    # exact-length path (same-length prompts still batch there).
    prefill_bucketing: bool = True
    min_bucket: int = 8             # smallest prefill bucket (pad-tolerant)
    # Paged KV cache (serving/kvpool.py): K/V live in a shared block pool
    # instead of one dense max_len stripe per slot, so per-replica session
    # capacity is bounded by *tokens in flight*, not slots x max_len.
    # Families holding non-pageable state (SSM/RG-LRU/MLA/ring windows)
    # silently keep the dense path (engine.paged reports the outcome).
    paged: bool = False
    block_size: int = 16            # tokens per KV block
    # usable pool blocks; 0 -> slots * (max_len / block_size), i.e. the
    # same token capacity the dense layout reserves.  Capacity gains come
    # from raising `slots` while holding kv_blocks * block_size fixed.
    kv_blocks: int = 0
    prefix_cache: bool = True       # content-hashed full-block prompt reuse
    # Speculative multi-token decode (paged + greedy only): an in-loop
    # n-gram draft proposes `spec_draft` tokens per fused step, verify is
    # one batched paged extend over the whole decode batch, and the
    # accepted prefix plus one corrected token is emitted — 1..spec_draft+1
    # tokens per backbone pass, token-exact vs the non-speculative loop.
    # MoE families silently fall back to non-speculative paged decode
    # (expert capacity couples the verify window's batch rows).
    speculative: bool = False
    spec_draft: int = 3             # drafted tokens per verify window
    # KV lifecycle (paged only): under block-pool pressure, preempt the
    # lowest-priority active session — serialize its blocks off-device,
    # free them, and re-admit later with the swapped prefix restored
    # block-exact — instead of completing it early as a
    # `kv_pool_exhausted` victim.  Turns 4x pool oversubscription into
    # routine operation; token streams are unchanged by construction
    # (the restored pool rows are the bytes that were swapped out).
    kv_swap: bool = False
    swap_tier: str = "host"         # "host" (in-request bytes) | "artifact"

    def __post_init__(self):
        if self.fused and self.sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got "
                             f"{self.sync_every}: a 0-step fused loop would "
                             f"spin without ever finishing a request")
        if not self.fused and self.temperature:
            raise ValueError("the reference (fused=False) path decodes "
                             "greedy-only; temperature sampling requires "
                             "the fused engine")
        if self.paged:
            if not self.fused:
                raise ValueError("paged=True requires the fused engine; "
                                 "the per-token reference loop is dense-"
                                 "only (it is the parity oracle)")
            if self.block_size < 1 or self.max_len % self.block_size:
                raise ValueError(
                    f"block_size ({self.block_size}) must divide max_len "
                    f"({self.max_len}): equal virtual cache length is what "
                    f"makes the paged path token-exact vs the dense oracle")
        if self.speculative:
            if not self.paged:
                raise ValueError("speculative=True requires paged=True: "
                                 "the draft/verify loop runs as a batched "
                                 "extend over the paged block pool")
            if self.temperature:
                raise ValueError("speculative decode is greedy-only: the "
                                 "accepted-prefix emission is token-exact "
                                 "only under argmax (temperature == 0)")
            if self.spec_draft < 1:
                raise ValueError(f"spec_draft must be >= 1, got "
                                 f"{self.spec_draft}")
        if self.kv_swap and not self.paged:
            raise ValueError("kv_swap=True requires paged=True: swap "
                             "serializes KV *blocks*; the dense layout "
                             "has no block granularity to preempt at")
        if self.swap_tier not in ("host", "artifact"):
            raise ValueError(f"swap_tier must be 'host' or 'artifact', "
                             f"got {self.swap_tier!r}")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new: int                    # decoded-token budget (prefill token free)
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str = ""         # "max_new" | "max_len" once done
    submit_t: float = 0.0
    first_token_t: float = 0.0
    done_t: float = 0.0
    # streaming: called at every host sync with the tokens that sync
    # produced — on_tokens(req, new_tokens, done).  One call per K-step
    # sync on the fused/paged paths, per token on the reference path.
    on_tokens: Optional[Callable[["Request", List[int], bool], None]] = None
    # tracing: the engine-side request span (submit -> finish) and the
    # context engine batch spans parent on; under a cluster the context
    # arrives with the work item, standalone submits root their own
    trace_span: Any = None
    trace_ctx: Any = None
    # paged engines compute the chained prefix-cache block hashes at
    # submit() time (memoized across identical prompts) so the sha256
    # chain never runs on the admit critical path
    block_hashes: Optional[List[bytes]] = None
    # KV-swap preemption order: lower preempts first; ties break toward
    # the newest request (least decode work lost).  0 is the default
    # class — the future per-tenant priority plumbing lands here.
    priority: int = 0
    # set while the request is swapped out: the serialized KV state a
    # re-admit restores instead of re-prefilling (see SessionSnapshot)
    kv_snapshot: Optional["SessionSnapshot"] = None
    # resilience: absolute time.monotonic() deadline — once passed the
    # engine finishes the session (queued or mid-decode) with
    # finish_reason="deadline" and frees its KV instead of decoding
    # tokens nobody will read; ``cancel_cb()`` is polled each host sync
    # and True finishes it with finish_reason="cancelled" the same way
    deadline_s: Optional[float] = None
    cancel_cb: Optional[Callable[[], bool]] = None

    @property
    def decoded(self) -> int:
        """Tokens produced by decode steps (excludes the prefill sample)."""
        return max(len(self.out_tokens) - 1, 0)


@dataclasses.dataclass
class SessionSnapshot:
    """Everything a preempted session needs to resume block-exact.

    The device side is ``n_blocks`` pool rows covering positions
    ``[0, pos)`` — serialized via :func:`pack_block_arrays` and carried
    either inline (``data``, host swap tier) or as a content-addressed
    ``digest`` in the ArtifactStore (``swap_tier="artifact"``).  The host
    side is the three scalars the fused loop needs: the next write
    position, the remaining decode budget, and the last emitted token
    (the next step's input).  ``Request.out_tokens`` stays on the request
    itself, so emission resumes mid-stream with nothing re-emitted.
    """
    pos: int
    rem: int
    last_tok: int
    n_blocks: int
    data: Optional[bytes] = None
    digest: Optional[str] = None


def _insert_slot(big, small, slot: int):
    """Write a batch-1 cache pytree into slot `slot` of the engine cache.
    Cache leaves have batch at axis 1: (repeats, B, ...)."""
    return jax.tree_util.tree_map(
        lambda b, s: b.at[:, slot:slot + 1].set(s.astype(b.dtype)), big, small)


def pad_tolerant(cfg, max_len: int) -> bool:
    """Can this arch prefill right-padded prompts exactly?

    False for SSM ("S") / RG-LRU ("R") — the recurrent state would absorb
    pad tokens; for MoE ("M") — expert capacity couples batch rows, so pads
    can displace real tokens; and for windowed attention ("L") with a ring
    cache — writing pads into the ring can evict real K/V.  Plain causal /
    global attention is exactly invariant to right-padding (pads sit
    *after* every real token, decode masks positions beyond ``pos``, and
    each pad cache entry is overwritten before it ever becomes visible).
    """
    for g in cfg.groups:
        for kind in g.pattern:
            if kind in ("S", "R", "M"):
                return False
            if kind == "L" and cfg.window and cfg.window < max_len:
                return False
    return True


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


class _PromptTooLong(ValueError):
    """A prompt no allocation could ever satisfy (needs more blocks than
    the whole pool): rejected per-request, never raised out of step()."""


class EngineFns:
    """Jitted engine functions shareable by N engine replicas with identical
    cfg/scfg — one XLA compile for the whole pool instead of one per replica.

    Fused-path functions donate the engine's device state (caches, pos,
    last-token, liveness, budget) so XLA updates the KV caches in place
    instead of copying the full pytree every step/admit; callers must treat
    the passed-in state as consumed and adopt the returned buffers.
    """

    def __init__(self, cfg, scfg: ServeConfig):
        self.cfg, self.scfg = cfg, scfg
        self.pad_ok = pad_tolerant(cfg, scfg.max_len)
        self.paged_ok = tfm.paged_supported(cfg, scfg.max_len)
        # MoE expert capacity couples batch rows: admitting several prompts
        # (or pad-duplicated rows) in one prefill would let rows displace
        # each other's expert slots and diverge from the reference path's
        # batch-1 admits — so MoE admits stay batch-1
        self.row_coupled = any(k == "M" for g in cfg.groups
                               for k in g.pattern)
        def decode(params, tokens, caches, pos):
            return tfm.decode_step(params, cfg, tokens, caches, pos)

        self.decode = jax.jit(decode)
        # jit-cache builds are locked: the bundle is shared across thread
        # replicas, and a duplicated build means a duplicated multi-second
        # XLA compile — the exact cost this class exists to amortize
        self._build_lock = threading.Lock()
        # (plen,) -> jitted exact-length batch-1 prefill (reference path)
        self.prefill_cache: Dict[int, Callable] = {}
        # (bucket, n) -> jitted fused prefill+sample+insert (fused path)
        self._admit_cache: Dict[Tuple[int, int], Callable] = {}
        k, max_len, temp = scfg.sync_every, scfg.max_len, scfg.temperature

        def loop_fn(params, caches, pos, last, active, remaining, rng):
            return tfm.decode_loop(params, cfg, caches, pos, last, active,
                                   remaining, rng, k=k, max_len=max_len,
                                   temperature=temp)

        # donate caches/pos/last/active/remaining/rng: the K-step loop
        # aliases every state buffer instead of materializing a copy
        self.decode_loop = jax.jit(loop_fn, donate_argnums=(1, 2, 3, 4, 5, 6))

        def paged_loop_fn(params, bt, caches, pos, last, active, remaining,
                          rng):
            # per-step pool path: the Pallas decode kernel reads the block
            # pool directly, so there is no virtual cache to keep resident
            out, em, caches, pos, last, active, remaining, rng = \
                tfm.decode_loop(params, cfg, caches, pos, last, active,
                                remaining, rng, k=k, max_len=max_len,
                                temperature=temp, bt=bt)
            # pack tokens + emitted counts into one array so the host sync
            # is a single device fetch (liveness/positions/budget are
            # host-derivable from the emitted counts)
            packed = jnp.concatenate([out, em[:, None]], axis=1)
            return packed, bt, caches, pos, last, active, remaining, rng

        def paged_virt_loop_fn(params, virt, pos, last, active, remaining,
                               rng):
            # resident-virtual path with lazy writeback: the engine
            # gathered `virt` from the pool once (gather_virt) and keeps
            # it device-resident; a steady-state sync is EXACTLY the
            # dense loop on it — no pool, no block table, no scatter —
            # and the pool is brought current only when something needs
            # to read it (flush_fn at admit/fork/victim boundaries)
            out, em, virt, pos, last, active, remaining, rng = \
                tfm.decode_loop(params, cfg, virt, pos, last, active,
                                remaining, rng, k=k, max_len=max_len,
                                temperature=temp)
            packed = jnp.concatenate([out, em[:, None]], axis=1)
            return packed, virt, pos, last, active, remaining, rng

        # the virtual caches are donated AND passed through as an output:
        # they stay device-resident across syncs and jit re-specializes
        # per bucketed *width*, so decode attention spans the widest live
        # sequence's whole-wave budget instead of nb_max blocks.  On the
        # kernel path the block table rides the same donate-and-return
        # contract instead (the Pallas kernel reads the pool directly).
        if cfg.use_kernels:
            self.paged_decode_loop = jax.jit(
                paged_loop_fn, donate_argnums=(1, 2, 3, 4, 5, 6, 7))
        else:
            self.paged_decode_loop = jax.jit(
                paged_virt_loop_fn, donate_argnums=(1, 2, 3, 4, 5, 6))
        self.gather_virt = jax.jit(tfm.gather_paged_virtual)
        # (width,) -> jitted lazy-writeback flush: scatter rows
        # [start, stop) of the virtual caches into the pool, per-slot
        # clamped; width-bucketed so compiles stay bounded
        self._flush_cache: Dict[int, Callable] = {}

        # speculative decode rides the paged path only: greedy-only
        # (ServeConfig enforces temperature == 0) and never on row-coupled
        # (MoE) families, whose verify windows would cross-talk through
        # expert capacity
        self.spec = scfg.speculative and self.paged_ok \
            and not self.row_coupled

        def spec_loop_fn(params, virt, hist, pos, last, active, remaining,
                         rng):
            # lazy writeback: caches=None skips the in-loop pool scatter;
            # the engine flushes the resident virtual caches on demand
            (out, em, stats, _, virt, hist, pos, last, active, remaining,
             rng) = tfm.spec_decode_loop(
                 params, cfg, None, hist, pos, last, active, remaining,
                 rng, k=k, d=scfg.spec_draft, max_len=max_len, bt=None,
                 virt=virt)
            # stats ride as two extra broadcast columns so the host sync
            # stays a single device fetch even under speculation
            st = jnp.broadcast_to(stats[None, :], (out.shape[0], 2))
            packed = jnp.concatenate([out, em[:, None], st], axis=1)
            return (packed, virt, hist, pos, last, active, remaining, rng)

        if self.spec:
            self.spec_decode_loop = jax.jit(
                spec_loop_fn, donate_argnums=(1, 2, 3, 4, 5, 6, 7))
        # (bucket, n) -> jitted paged suffix-extend + sample + slot insert
        self._paged_admit_cache: Dict[Tuple[int, int], Callable] = {}

        def cow(caches, src, dst):
            """Copy-on-write: ``pool[dst[i]] = pool[src[i]]`` for every
            layer's K/V pool (donated).  Pad pairs are (0, 0) — a
            null-block self-copy; callers pad pair counts to powers of
            two so jit's shape specialization stays bounded."""
            return jax.tree_util.tree_map(
                lambda c: c.at[:, dst].set(c[:, src]), caches)

        self.cow = jax.jit(cow, donate_argnums=(0,))

        def kv_export(caches, ids):
            """Gather pool rows ``ids`` from every layer's K/V pool (the
            swap-out / migration serialization read).  NOT donated — the
            pool stays live; ``ids`` is padded to a power of two with the
            null block and the junk pad rows are sliced off host-side."""
            return jax.tree_util.tree_map(lambda c: c[:, ids], caches)

        self.kv_export = jax.jit(kv_export)

        def kv_import(caches, ids, rows):
            """Scatter serialized rows back into pool blocks ``ids`` (the
            swap-in / migration adopt write; donated).  Pad ids are the
            null block, which absorbs the pad rows' junk by design."""
            return jax.tree_util.tree_map(
                lambda c, r: c.at[:, ids].set(r.astype(c.dtype)),
                caches, rows)

        self.kv_import = jax.jit(kv_import, donate_argnums=(0,))

    def flush_fn(self, width: int) -> Callable:
        """Jitted lazy-writeback flush: write rows ``[start[s], stop[s])``
        of the resident virtual caches into the block pool (donated),
        null-redirecting each slot's junk tail past ``stop[s]``.  One
        compile per power-of-two pending width."""
        with self._build_lock:
            fn = self._flush_cache.get(width)
            if fn is None:
                def flush(caches, virt, bt, start, stop):
                    return tfm.scatter_paged_back(caches, virt, bt, start,
                                                  width, stop=stop)
                fn = jax.jit(flush, donate_argnums=(0,))
                self._flush_cache[width] = fn
        return fn

    def bucket(self, plen: int) -> int:
        """Prefill compile bucket for a prompt of length ``plen``."""
        if not (self.scfg.prefill_bucketing and self.pad_ok):
            return plen                       # exact-length path
        return min(max(_next_pow2(plen), self.scfg.min_bucket),
                   self.scfg.max_len)

    def admit_fn(self, bucket: int, n: int) -> Callable:
        """Jitted bucketed batch prefill: prefill ``n`` prompts padded to
        ``bucket`` in one call, sample their first tokens in-jit, and insert
        caches + per-slot state via donated ``dynamic_update_slice``."""
        key = (bucket, n)
        with self._build_lock:
            return self._admit_cache.get(key) or self._build_admit_fn(key)

    def _build_admit_fn(self, key: Tuple[int, int]) -> Callable:
        bucket, n = key
        cfg, scfg = self.cfg, self.scfg

        def dense_admit(params, tokens, last_idx, slot_idx, budget,
                        caches, pos, last, active, remaining, rng):
            """tokens (n,bucket) · last_idx/slot_idx/budget (n,) ·
            engine state donated; returns (first_tokens (n,), state...)."""
            small = api.init_caches(cfg, n, scfg.max_len)
            rng, sub = jax.random.split(rng)
            logits, small = tfm.prefill(params, cfg, tokens, small,
                                        last_index=last_idx)
            toks = tfm.sample_tokens(logits[:, 0], scfg.temperature, sub)
            for j in range(n):            # static unroll over admits
                s = slot_idx[j]
                caches = jax.tree_util.tree_map(
                    lambda b, sm: jax.lax.dynamic_update_slice_in_dim(
                        b, sm[:, j:j + 1].astype(b.dtype), s, axis=1),
                    caches, small)
                act_j = (budget[j] > 0) & (last_idx[j] + 1 < scfg.max_len - 1)
                pos = jax.lax.dynamic_update_index_in_dim(
                    pos, last_idx[j] + 1, s, 0)
                # an immediately-exhausted admit parks the slot on token 0,
                # the reference loop's zero-fill for empty slots
                last = jax.lax.dynamic_update_index_in_dim(
                    last, jnp.where(act_j, toks[j], 0), s, 0)
                remaining = jax.lax.dynamic_update_index_in_dim(
                    remaining, budget[j], s, 0)
                active = jax.lax.dynamic_update_index_in_dim(
                    active, act_j, s, 0)
            return toks, caches, pos, last, active, remaining, rng

        self._admit_cache[key] = jax.jit(
            dense_admit, donate_argnums=(5, 6, 7, 8, 9, 10))
        return self._admit_cache[key]

    def paged_admit_fn(self, bucket: int, n: int) -> Callable:
        """Jitted paged admit: extend ``n`` sequences by their (padded)
        suffix tokens through their block tables, sample first tokens
        in-jit, and update the donated slot state."""
        key = (bucket, n)
        with self._build_lock:
            return self._paged_admit_cache.get(key) or \
                self._build_paged_admit_fn(key)

    def _build_paged_admit_fn(self, key: Tuple[int, int]) -> Callable:
        bucket, n = key
        cfg, scfg = self.cfg, self.scfg
        spec = self.spec

        def paged_admit(params, tokens, meta, bt, virt,
                        caches, hist, pos, last, active, remaining, rng):
            """tokens (n,bucket) suffix ids · meta (4,n) = [pos0
            cached-prefix length; last_idx suffix-local last index;
            slot_idx; budget] packed into one upload · bt (n, nb_max)
            block tables · engine state donated.  ``hist`` is the
            speculative draft's (slots, max_len) token history and
            ``virt`` the resident virtual caches — either may be None.
            The admitted slots' virtual rows are re-gathered in here
            (one dispatch, no extra uploads) so a steady-state admit
            never flushes or fully regathers the resident view."""
            pos0, last_idx, slot_idx, budget = (meta[j] for j in range(4))
            rng, sub = jax.random.split(rng)
            logits, caches = tfm.extend_paged(params, cfg, tokens, caches,
                                              pos0, bt, last_index=last_idx)
            toks = tfm.sample_tokens(logits[:, 0], scfg.temperature, sub)
            if virt is not None:
                vw = virt[0][0]["k"].shape[2] // scfg.block_size
                virt = tfm.refresh_paged_virtual(virt, caches,
                                                 bt[:, :vw], slot_idx)
            if spec:
                # seed the draft history with the suffix tokens at their
                # absolute positions.  Bucket pads land above the row's
                # position and are overwritten before any draft can read
                # them; positions below pos0 (a prefix-cache hit) keep the
                # slot's stale contents, which can only cost draft
                # acceptance, never correctness.
                idxs = pos0[:, None] + jnp.arange(bucket)[None, :]
                hist = hist.at[slot_idx[:, None], idxs].set(tokens,
                                                            mode="drop")
            for j in range(n):            # static unroll over admits
                s = slot_idx[j]
                nxt = pos0[j] + last_idx[j] + 1     # next write position
                act_j = (budget[j] > 0) & (nxt < scfg.max_len - 1)
                pos = jax.lax.dynamic_update_index_in_dim(pos, nxt, s, 0)
                last = jax.lax.dynamic_update_index_in_dim(
                    last, jnp.where(act_j, toks[j], 0), s, 0)
                remaining = jax.lax.dynamic_update_index_in_dim(
                    remaining, budget[j], s, 0)
                active = jax.lax.dynamic_update_index_in_dim(
                    active, act_j, s, 0)
                if spec:
                    hist = hist.at[s, nxt].set(toks[j], mode="drop")
            return toks, virt, caches, hist, pos, last, active, remaining, \
                rng

        # hist/virt may arrive as None (non-speculative engines; no
        # resident view yet) — an empty pytree, so donating it is a no-op
        # and jit re-traces once per presence combination
        jitted = jax.jit(paged_admit,
                         donate_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
        self._paged_admit_cache[key] = jitted
        return self._paged_admit_cache[key]


    def prefill_fn(self, plen: int) -> Callable:
        """Exact-length batch-1 prefill (reference path, pre-PR shape)."""
        with self._build_lock:
            if plen not in self.prefill_cache:
                cfg, scfg = self.cfg, self.scfg

                def prefill_exact(params, tokens):
                    caches = api.init_caches(cfg, 1, scfg.max_len)
                    return tfm.prefill(params, cfg, tokens, caches)

                self.prefill_cache[plen] = jax.jit(prefill_exact)
            return self.prefill_cache[plen]


def make_engine_fns(cfg, scfg: ServeConfig) -> EngineFns:
    """Shared-jit bundle for an engine pool (see :class:`EngineFns`)."""
    return EngineFns(cfg, scfg)


class Engine:
    def __init__(self, params, cfg, scfg: ServeConfig,
                 metrics: Optional[MetricsRegistry] = None,
                 shared_fns: Optional[EngineFns] = None):
        self.params, self.cfg, self.scfg = params, cfg, scfg
        if cfg.family == "encdec":
            raise NotImplementedError("Engine serves decoder-LM families")
        self.fns = shared_fns if shared_fns is not None \
            else make_engine_fns(cfg, scfg)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # paged KV: only families whose whole cache is position-addressed
        # attention K/V can page; the rest (SSM/RG-LRU/MLA/ring) keep the
        # dense path — observable via `engine.paged` and the counter
        self.paged = scfg.paged and self.fns.paged_ok
        if scfg.paged and not self.fns.paged_ok:
            self.metrics.counter("engine.paged_fallback_dense").inc()
        if self.paged:
            bs = scfg.block_size
            self.nb_max = scfg.max_len // bs
            n_blocks = scfg.kv_blocks or scfg.slots * self.nb_max
            self.caches = tfm.init_paged_caches(cfg, n_blocks, bs)
            self.alloc = BlockAllocator(n_blocks, bs)
            self.alloc.on_evict = lambda bid: current_recorder().record(
                "kv_evict", block=bid)
            self._seq_of_slot: List[Optional[int]] = [None] * scfg.slots
            self._bt = np.zeros((scfg.slots, self.nb_max), np.int32)
            self._pos_h = np.zeros((scfg.slots,), np.int64)
            self._rem_h = np.zeros((scfg.slots,), np.int64)
            self._act_h = np.zeros((scfg.slots,), bool)
            # device-resident block table (donated through the decode loop
            # and passed back): host mutations set the dirty flag and the
            # next sync re-uploads, sliced to the bucketed width that
            # covers the longest live sequence
            self._bt_dev = None
            self._bt_width = 0
            self._bt_dirty = True
            # device-resident virtual caches (gather-hoisted dense view of
            # the live slots' blocks): reused across syncs; None forces a
            # regather — set on admit/fork/victim and on width change.
            # Writeback to the pool is LAZY: _wb_h[s] is the first
            # position not yet flushed; _flush_virt() makes the pool
            # authoritative before anything reads it
            self._virt = None
            self._virt_width = 0
            self._wb_h = np.zeros((scfg.slots,), np.int64)
            # swap_tier="artifact": lazily-built content-addressed store
            # for swapped block payloads (host tier carries bytes inline)
            self._swap_store = None
            self.metrics.gauge("engine.kv_blocks_total").set(n_blocks)
            self._kv_gauges()
        else:
            self.caches = api.init_caches(cfg, scfg.slots, scfg.max_len)
        # speculative decode: paged + greedy + row-decoupled only (the
        # fns bundle holds the gate); fall back silently but observably
        self.speculative = self.paged and self.fns.spec
        if scfg.speculative and not self.speculative:
            self.metrics.counter("engine.spec_fallback").inc()
        if self.speculative:
            # device token history feeding the n-gram draft: row s holds
            # the tokens of slot s's sequence at their absolute positions
            self._hist = jnp.zeros((scfg.slots, scfg.max_len), jnp.int32)
        self.active: List[Optional[Request]] = [None] * scfg.slots
        self.queue: Deque[Request] = deque()
        self.finished: List[Request] = []
        if scfg.fused:
            # device-resident loop state (donated through every fused call)
            self._pos = jnp.zeros((scfg.slots,), jnp.int32)
            self._last = jnp.zeros((scfg.slots,), jnp.int32)
            self._active = jnp.zeros((scfg.slots,), bool)
            self._remaining = jnp.zeros((scfg.slots,), jnp.int32)
            self._rng = jax.random.PRNGKey(scfg.seed)
        else:
            self.pos = np.zeros((scfg.slots,), np.int32)
        # monotonic request ids: never reused, regardless of how many
        # requests are queued/active/finished at submit time
        self._rids = itertools.count(1000)
        # flipped by the first submit carrying a deadline or cancel_cb;
        # keeps the per-step resilience sweep off the hot path otherwise
        self._watch_early = False

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int,
               on_tokens: Optional[Callable] = None,
               trace_ctx: Any = None, priority: int = 0,
               deadline_s: Optional[float] = None,
               cancel_cb: Optional[Callable[[], bool]] = None) -> Request:
        with current_tracer().span("engine.submit", parent=trace_ctx,
                                   step=True):
            req = Request(rid=next(self._rids),
                          prompt=np.asarray(prompt, np.int32), max_new=max_new,
                          submit_t=time.perf_counter(), on_tokens=on_tokens,
                          priority=priority, deadline_s=deadline_s,
                          cancel_cb=cancel_cb)
            if deadline_s is not None or cancel_cb is not None:
                self._watch_early = True
            if self.paged and self.scfg.prefix_cache:
                # sha256 prefix-chain hashing runs here — off the admit/step
                # critical path, and memoized across identical prompts
                req.block_hashes = hash_token_blocks_memo(
                    req.prompt, self.scfg.block_size)
            # with a cluster context this parents into the request's trace;
            # standalone (trace_ctx None) it roots one, subject to sampling
            sp = current_tracer().span("engine.request", parent=trace_ctx,
                                       rid=req.rid, prompt_len=len(req.prompt),
                                       max_new=max_new)
            if sp.recording:
                req.trace_span = sp
                req.trace_ctx = sp.ctx
            self.queue.append(req)
            return req

    def _emit(self, req: Request, toks: List[int], done: bool):
        """Per-sync streaming callback; a throwing consumer must not take
        the engine (and every other slot's request) down with it."""
        if req.on_tokens is None:
            return
        try:
            req.on_tokens(req, list(toks), done)
        except Exception:
            self.metrics.counter("engine.stream_errors").inc()

    def _kv_gauges(self):
        self.metrics.gauge("engine.kv_blocks_free").set(
            self.alloc.free_blocks)
        self.metrics.gauge("engine.kv_blocks_cached").set(
            self.alloc.cached_blocks)

    def _close_span(self, req: Request):
        if req.trace_span is not None:
            req.trace_span.tag(finish=req.finish_reason,
                               decoded=req.decoded)
            req.trace_span.end()
            req.trace_span = None

    def _finish(self, slot: int, reason: str):
        req = self.active[slot]
        req.done = True
        req.finish_reason = reason
        req.done_t = time.perf_counter()
        self._close_span(req)
        self.finished.append(req)
        self.active[slot] = None
        if self.paged:
            # release the sequence's blocks (cached prefix blocks survive
            # via the prefix cache's own reference) and null the table row
            # so the still-running device loop can write nothing real
            sid = self._seq_of_slot[slot]
            if sid is not None:
                self.alloc.free_seq(sid)
                self._seq_of_slot[slot] = None
                self._bt[slot] = NULL_BLOCK
                # the freed blocks can be re-allocated to another slot in
                # this very sync — a stale device copy of this row would
                # let the frozen slot's masked writes land in the new
                # owner's blocks
                self._bt_dirty = True
                # drop the dead slot's pending writeback: its blocks are
                # freed, and a later flush must not inflate its width for
                # rows nobody can read (the nulled table row would drop
                # them anyway)
                self._wb_h[slot] = self._pos_h[slot]
            self._kv_gauges()
        self.metrics.counter("engine.requests").inc()
        self.metrics.counter("engine.tokens").inc(req.decoded)
        if reason == "max_len":
            self.metrics.counter("engine.truncated").inc()
        self.metrics.histogram("engine.ttft_s").observe(
            req.first_token_t - req.submit_t)
        self.metrics.histogram("engine.latency_s").observe(
            req.done_t - req.submit_t)

    # ------------------------------------------------------------------
    # fused path
    def _admit_fused(self):
        free = [s for s in range(self.scfg.slots) if self.active[s] is None]
        while free and self.queue:
            asp = current_tracer().span(
                "engine.admit", parent=self.queue[0].trace_ctx, step=True)
            # longest same-bucket *prefix* of the queue (strict FIFO), up to
            # the number of free slots, prefilled as one padded batch
            bucket = self.fns.bucket(len(self.queue[0].prompt))
            batch = [self.queue.popleft()]
            # MoE rows couple through expert capacity: batch/pad admits
            # would diverge from the reference path's batch-1 prefill
            max_admit = 1 if self.fns.row_coupled else len(free)
            while self.queue and len(batch) < max_admit and \
                    self.fns.bucket(len(self.queue[0].prompt)) == bucket:
                batch.append(self.queue.popleft())
            n = len(batch)
            slots_idx, free = free[:n], free[n:]
            # pad the batch dimension up to a power of two so admit
            # compiles are bounded by |buckets| x log2(slots), not by every
            # batch size the queue happens to produce.  Pad rows duplicate
            # row 0 *and its slot* and come first, so the real rows' writes
            # (last in the unrolled insert) always win.
            n_pad = _next_pow2(n) if n > 1 else 1
            rows = [batch[0]] * (n_pad - n) + batch
            row_slots = np.asarray([slots_idx[0]] * (n_pad - n) + slots_idx,
                                   np.int32)
            tokens = np.zeros((n_pad, bucket), np.int32)
            last_idx = np.zeros((n_pad,), np.int32)
            budget = np.zeros((n_pad,), np.int32)
            for j, req in enumerate(rows):
                plen = len(req.prompt)
                tokens[j, :plen] = req.prompt
                last_idx[j] = plen - 1
                budget[j] = max(req.max_new, 0)
            rids = [r.rid for r in batch]
            if asp.recording:
                asp.tag(bucket=bucket, n=n, n_pad=n_pad, rids=rids)
            current_recorder().record("admit", rids=rids, bucket=bucket, n=n)
            _qh = self.metrics.histogram("engine.queue_wait_s")
            _now = time.perf_counter()
            for r in batch:
                _qh.observe(_now - r.submit_t)
            # the prefill span brackets the jitted call *plus* the host
            # sync that realizes its tokens — tracing never reaches
            # inside jit, it measures the host-visible stage
            psp = current_tracer().span("engine.prefill", parent=asp,
                                        step=True, bucket=bucket, n_pad=n_pad)
            toks, self.caches, self._pos, self._last, self._active, \
                self._remaining, self._rng = \
                self.fns.admit_fn(bucket, n_pad)(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(last_idx),
                    jnp.asarray(row_slots), jnp.asarray(budget),
                    self.caches, self._pos, self._last,
                    self._active, self._remaining, self._rng)
            toks_h = np.asarray(toks)[n_pad - n:]
            psp.end()
            now = time.perf_counter()
            for j, req in enumerate(batch):
                req.out_tokens.append(int(toks_h[j]))
                req.first_token_t = now
                self.active[slots_idx[j]] = req
                if req.max_new <= 0:
                    self._finish(slots_idx[j], "max_new")
                elif len(req.prompt) >= self.scfg.max_len - 1:
                    self._finish(slots_idx[j], "max_len")
                self._emit(req, req.out_tokens[-1:], req.done)
            asp.end()
            self.metrics.counter("engine.prefill_batches").inc()

    def _batch_ctx(self):
        """Trace parent for a decode-sync span: the first traced active
        request (one span serves the whole shared batch)."""
        return next((r.trace_ctx for r in self.active
                     if r is not None and r.trace_ctx is not None), None)

    def _decode_sync_span(self):
        dsp = current_tracer().span("engine.decode_sync",
                                    parent=self._batch_ctx(), step=True)
        if dsp.recording:
            dsp.tag(k=self.scfg.sync_every,
                    n_active=sum(r is not None for r in self.active))
        return dsp

    def _step_fused(self) -> bool:
        self._admit_fused()
        if not any(r is not None for r in self.active):
            return False
        dsp = self._decode_sync_span()
        out, emitted, self.caches, self._pos, self._last, self._active, \
            self._remaining, self._rng = self.fns.decode_loop(
                self.params, self.caches, self._pos, self._last,
                self._active, self._remaining, self._rng)
        # one host sync per K decode steps (sampling happened in-jit)
        hsp = current_tracer().span("engine.host_sync", parent=dsp,
                                    step=True)
        out_h = np.asarray(out)
        em_h = np.asarray(emitted)
        act_h = np.asarray(self._active)
        rem_h = np.asarray(self._remaining)
        hsp.end()
        esp = current_tracer().span("engine.stream_emit", parent=dsp,
                                    step=True) \
            if any(r is not None and r.on_tokens is not None
                   for r in self.active) else NULL_SPAN
        for s, req in enumerate(self.active):
            if req is None:
                continue
            new = [int(t) for t in out_h[s, :em_h[s]]]
            req.out_tokens.extend(new)
            if not act_h[s]:
                self._finish(s, "max_new" if rem_h[s] <= 0 else "max_len")
            self._emit(req, new, req.done)
        esp.end()
        dsp.end()
        self.metrics.counter("engine.steps").inc()
        return True

    # ------------------------------------------------------------------
    # paged path: same fused K-step loop, but K/V live in a shared block
    # pool addressed through per-slot block tables (serving/kvpool.py).
    # Admits prefill only the suffix a prefix-cache hit leaves uncovered;
    # block allocation / COW / freeing are host decisions executed on
    # device between syncs.
    def _prep_paged(self, req: Request):
        """Plan one admit without side effects: prefix hits, suffix shape,
        and the block headroom it would need.  None == cannot admit now."""
        bs = self.scfg.block_size
        plen = len(req.prompt)
        if not self.scfg.prefix_cache:
            hashes: List[bytes] = []
        elif req.block_hashes is not None:      # hashed at submit()
            hashes = req.block_hashes
        else:                                   # forked/hand-built request
            hashes = hash_token_blocks_memo(req.prompt, bs)
        # reuse covers at most plen-1 tokens: the last prompt token must be
        # recomputed so the admit has logits to sample the first output
        reusable = hashes[:max(plen - 1, 0) // bs]
        hits = self.alloc.prefix_lookup(reusable)
        n_cached_tok = len(hits) * bs
        need = -(-plen // bs) - len(hits) + 1      # +1 decode-ahead block
        if need > self.alloc.num_blocks:
            # would defer forever: the whole pool cannot hold this prompt
            raise _PromptTooLong(
                f"prompt of {plen} tokens needs {need} KV blocks but the "
                f"pool has only {self.alloc.num_blocks}: raise kv_blocks "
                f"or shorten the prompt")
        if need > self.alloc.available_excluding(hits):
            return None
        return (hashes, hits, n_cached_tok, plen - n_cached_tok)

    def _reject_oversized(self, req: Request, detail: str):
        """Fail just the unservable request — never the batch it queued
        with.  It completes empty with an explicit finish reason instead
        of raising out of ``step()`` (where a replica loop would spill
        the whole in-flight batch and re-route the poison request into
        the next replica)."""
        req.done = True
        req.finish_reason = "rejected_prompt_too_long"
        req.done_t = req.first_token_t = time.perf_counter()
        self._close_span(req)
        self.finished.append(req)
        self.metrics.counter("engine.rejected_too_long").inc()
        self._emit(req, [], True)

    def _admit_paged(self):
        free = [s for s in range(self.scfg.slots) if self.active[s] is None]
        while free and self.queue:
            # opened before the batch is formed, so the span covers the
            # prefix lookups, block allocation and table building too
            with current_tracer().span("engine.admit",
                                       parent=self.queue[0].trace_ctx,
                                       step=True) as asp:
                if not self._admit_paged_batch(free, asp):
                    break

    def _admit_paged_batch(self, free: List[int], asp) -> bool:
        """Admit one batch from the queue head into ``free`` (taken in
        place); False when the head has to wait for pool headroom."""
        scfg = self.scfg
        # NO flush here, by construction: admission only reads
        # *published* prefix blocks (immutable once published — decode
        # writes COW first) and only binds *free* blocks, while every
        # lazily-pending virtual row targets a live slot's private
        # block (fork/victim flush before sharing or freeing, and
        # _finish resets a dead slot's watermark) — so the pool is
        # authoritative for everything an admit can touch
        if self.queue[0].kv_snapshot is not None:
            # a preempted session resumes by block import, never by
            # re-prefill; deferring it keeps FIFO (nothing behind it
            # may overtake the resume)
            if self._try_restore(free):
                return True
            self.metrics.counter("engine.admit_deferred_kv").inc()
            return False
        try:
            prep = self._prep_paged(self.queue[0])
        except _PromptTooLong as e:
            self._reject_oversized(self.queue.popleft(), str(e))
            return True
        if prep is None:
            # pool pressure: leave the queue intact — admission
            # headroom gating upstream keeps this rare
            self.metrics.counter("engine.admit_deferred_kv").inc()
            return False
        bucket = self.fns.bucket(prep[3])
        max_admit = 1 if self.fns.row_coupled else len(free)
        # pop-and-commit one request at a time so each headroom probe
        # sees the blocks its batch-mates already claimed
        rows = []
        while prep is not None and len(rows) < max_admit and \
                self.fns.bucket(prep[3]) == bucket:
            req = self.queue.popleft()
            hashes, hits, n_cached_tok, suffix_len = prep
            plen = len(req.prompt)
            slot = free[len(rows)]
            sid = self.alloc.new_seq()
            self.alloc.append_shared(sid, hits)
            self.alloc.extend_to(sid, plen)
            self._seq_of_slot[slot] = sid
            self._bt[slot] = padded_table(self.alloc.table(sid),
                                          self.nb_max)
            self._bt_dirty = True
            self._pos_h[slot] = plen
            self._wb_h[slot] = plen   # nothing pending: admit writes pool
            self._rem_h[slot] = max(req.max_new, 0)
            self._act_h[slot] = req.max_new > 0 and \
                plen < scfg.max_len - 1
            self.metrics.counter("engine.prefix_hit_blocks").inc(
                len(hits))
            # denominator of the hit rate: count the blocks actually
            # *looked up* (reuse is capped at plen-1 tokens), not the
            # prompt's full-block count — else a block-aligned prompt
            # could never reach hit_rate 1.0
            self.metrics.counter("engine.prefix_lookup_blocks").inc(
                max(plen - 1, 0) // self.scfg.block_size)
            self.metrics.counter("engine.prefill_tokens_saved").inc(
                n_cached_tok)
            rows.append((req, slot, sid, hashes, n_cached_tok,
                         suffix_len))
            try:
                # a snapshot-carrying head never joins a prefill
                # batch — the outer loop restores it via block import
                prep = self._prep_paged(self.queue[0]) \
                    if self.queue and \
                    self.queue[0].kv_snapshot is None else None
            except _PromptTooLong:
                # oversized next prompt: stop batching here; the head
                # of the next admit loop rejects it individually,
                # after this batch's extend has run
                prep = None
        n = len(rows)
        del free[:n]
        # pad the batch dim to a power of two (same compile-bounding
        # trick as the dense admit); pad rows duplicate row 0 and its
        # slot/table — identical values to identical addresses
        n_pad = _next_pow2(n) if n > 1 else 1
        full = [rows[0]] * (n_pad - n) + rows
        tokens = np.zeros((n_pad, bucket), np.int32)
        pos0 = np.zeros((n_pad,), np.int32)
        last_idx = np.zeros((n_pad,), np.int32)
        slot_arr = np.zeros((n_pad,), np.int32)
        budget = np.zeros((n_pad,), np.int32)
        bt = np.zeros((n_pad, self.nb_max), np.int32)
        for j, (req, slot, sid, hashes, n_cached_tok, suffix_len) in \
                enumerate(full):
            tokens[j, :suffix_len] = req.prompt[n_cached_tok:]
            pos0[j] = n_cached_tok
            last_idx[j] = suffix_len - 1
            slot_arr[j] = slot
            budget[j] = max(req.max_new, 0)
            bt[j] = self._bt[slot]
        hit_toks = sum(r[4] for r in rows)
        rids = [r[0].rid for r in rows]
        if asp.recording:
            asp.tag(bucket=bucket, n=n, n_pad=n_pad, rids=rids,
                    prefix_hit_tokens=hit_toks,
                    kv_blocks_free=self.alloc.free_blocks)
        current_recorder().record("admit", rids=rids, bucket=bucket, n=n,
                                  prefix_hit_tokens=hit_toks)
        _qh = self.metrics.histogram("engine.queue_wait_s")
        _now = time.perf_counter()
        for r in rows:
            _qh.observe(_now - r[0].submit_t)
        psp = current_tracer().span("engine.prefill", parent=asp,
                                    step=True, bucket=bucket, n_pad=n_pad)
        # one packed (4, n_pad) upload for the per-row int vectors —
        # host->device dispatches dominate the admit wall here.  The jit
        # also re-gathers the admitted slots' rows of the resident view
        # in the same call (other slots' lazily-pending rows must NOT be
        # re-read from the pool); a prompt wider than the resident view
        # is fine — decode's width check (need > width) forces a flush +
        # full regather before any truncated row could be read.
        meta = np.stack([pos0, last_idx, slot_arr, budget])
        toks, self._virt, self.caches, hist, self._pos, \
            self._last, self._active, self._remaining, self._rng = \
            self.fns.paged_admit_fn(bucket, n_pad)(
                self.params, jnp.asarray(tokens),
                jnp.asarray(meta), jnp.asarray(bt), self._virt,
                self.caches,
                self._hist if self.speculative else None,
                self._pos, self._last, self._active,
                self._remaining, self._rng)
        if self.speculative:
            self._hist = hist
        toks_h = np.asarray(toks)[n_pad - n:]
        psp.end()
        now = time.perf_counter()
        for j, (req, slot, sid, hashes, n_cached_tok, suffix_len) in \
                enumerate(rows):
            plen = len(req.prompt)
            if self.speculative and n_cached_tok:
                # a prefix-cache hit skips the admit extend for the
                # cached tokens, so the in-jit history seeding never
                # sees them — backfill host-side (admits are rare;
                # this keeps the n-gram draft sighted over the whole
                # context instead of just the uncached suffix)
                self._hist = self._hist.at[slot, :n_cached_tok].set(
                    jnp.asarray(req.prompt[:n_cached_tok], jnp.int32))
            if scfg.prefix_cache:
                # every *full* prompt block is now written and
                # immutable (decode writes start at plen) — publish it
                n_full = plen // scfg.block_size
                self.alloc.prefix_insert(hashes[:n_full],
                                         self.alloc.table(sid)[:n_full])
            req.out_tokens.append(int(toks_h[j]))
            req.first_token_t = now
            self.active[slot] = req
            if req.max_new <= 0:
                self._finish(slot, "max_new")
            elif plen >= scfg.max_len - 1:
                self._finish(slot, "max_len")
            self._emit(req, req.out_tokens[-1:], req.done)
        self.metrics.counter("engine.prefill_batches").inc()
        self._kv_gauges()
        return True

    def _flush_virt(self):
        """Lazy-writeback flush: scatter every virtual-cache row decoded
        since the last flush into the block pool, making the pool
        authoritative again.  Steady-state syncs skip the per-sync
        scatter entirely; this runs only when something needs to read the
        pool — an admit's regather, a fork, a pool-exhausted victim, or
        an explicit :meth:`flush_kv`.  Each slot is clamped to its own
        written range (``stop``), and finished slots' rows null-redirect
        through their nulled table rows."""
        if self._virt is None:
            self._wb_h[:] = self._pos_h
            return
        pend = int(np.max(self._pos_h - self._wb_h))
        if pend <= 0:
            return
        # the device table must be current for the flushed rows: _finish
        # nulls dead rows and appends bind fresh blocks, both set dirty
        if self._bt_dirty or self._bt_width != self._virt_width:
            self._bt_dev = jnp.asarray(self._bt[:, :self._virt_width])
            self._bt_width = self._virt_width
            self._bt_dirty = False
        width = _next_pow2(pend) if pend > 1 else 1
        self.caches = self.fns.flush_fn(width)(
            self.caches, self._virt, self._bt_dev,
            jnp.asarray(self._wb_h.astype(np.int32)),
            jnp.asarray(self._pos_h.astype(np.int32)))
        self._wb_h[:] = self._pos_h

    def flush_kv(self):
        """Make the block pool authoritative for every live sequence (the
        resident virtual caches are flushed; a no-op on dense or kernel
        paths).  Anything that reads KV content from ``engine.caches``
        directly — tests, future block swap/migration — must call this
        first."""
        if self.paged:
            self._flush_virt()

    def _exhaust_victim(self, slot: int):
        """PoolExhausted mid-decode: complete this slot's request with
        ``finish_reason="kv_pool_exhausted"`` and free its blocks (the
        single-victim contract, like ``rejected_prompt_too_long``) instead
        of raising out of ``step()`` and poisoning its batch-mates — the
        freed blocks can satisfy later slots in this very sync."""
        req = self.active[slot]
        self.metrics.counter("engine.kv_pool_exhausted").inc()
        current_recorder().record("kv_pool_exhausted", rid=req.rid,
                                  slot=slot, pos=int(self._pos_h[slot]))
        self._active = self._active.at[slot].set(False)
        self._last = self._last.at[slot].set(0)
        self._act_h[slot] = False
        # flush BEFORE the free: the other slots' pending rows must reach
        # the pool while every table row still maps to its true owner
        self._flush_virt()
        self._virt = None
        self._finish(slot, "kv_pool_exhausted")
        self._emit(req, [], True)

    # ------------------------------------------------------------------
    # resilience: deadline expiry + cancellation.  Both terminate a
    # session early at the next step boundary — "within one sync" — with
    # the single-victim contract of _exhaust_victim: the rest of the
    # batch keeps decoding, the victim's KV frees immediately.
    @staticmethod
    def _early_reason(req: Request, now: float) -> Optional[str]:
        if req.cancel_cb is not None:
            try:
                if req.cancel_cb():
                    return "cancelled"
            except Exception:           # noqa: BLE001 - poller's bug
                pass                    # a broken poller must not kill step()
        if req.deadline_s is not None and now > req.deadline_s:
            return "deadline"
        return None

    def _finish_early(self, slot: int, reason: str):
        """End an *active* slot mid-decode with ``reason``; on the paged
        path this frees its blocks inside the current sync (flush first,
        exactly like :meth:`_exhaust_victim`, so surviving slots' pending
        rows reach the pool while the table still maps every owner)."""
        req = self.active[slot]
        if self.scfg.fused:
            self._active = self._active.at[slot].set(False)
            self._last = self._last.at[slot].set(0)
        if self.paged:
            self._act_h[slot] = False
            self._flush_virt()
            self._virt = None
        self._finish(slot, reason)
        self._emit(req, [], True)

    def _sweep_expired(self):
        """Per-step resilience sweep: complete queued work that is already
        pointless (expired in queue / cancelled before admit) without it
        ever taking a slot, then end active sessions whose deadline passed
        or whose submitter cancelled."""
        now = time.monotonic()
        if self.queue:
            keep: Deque[Request] = deque()
            for req in self.queue:
                reason = self._early_reason(req, now)
                if reason is None:
                    keep.append(req)
                    continue
                req.done = True
                req.finish_reason = reason
                req.done_t = req.first_token_t = time.perf_counter()
                self._close_span(req)
                self.finished.append(req)
                self.metrics.counter(
                    "engine.cancelled" if reason == "cancelled"
                    else "engine.deadline_expired").inc()
                current_recorder().record(
                    reason if reason == "cancelled" else "deadline_expired",
                    rid=req.rid, where="engine_queue")
                self._emit(req, [], True)
            self.queue = keep
        for s, req in enumerate(self.active):
            if req is None:
                continue
            reason = self._early_reason(req, now)
            if reason is None:
                continue
            self.metrics.counter(
                "engine.cancelled" if reason == "cancelled"
                else "engine.deadline_expired").inc()
            current_recorder().record(
                reason if reason == "cancelled" else "deadline_expired",
                rid=req.rid, where="mid_decode",
                decoded=req.decoded)
            self._finish_early(s, reason)

    # ------------------------------------------------------------------
    # KV lifecycle: preemption + host/artifact swap (ServeConfig.kv_swap)
    # and warm migration export/import (cluster drain path).  Both ride
    # the same serialization primitives: pin the blocks, flush the
    # resident view so the pool is authoritative, gather the rows in one
    # jitted call, and pack them with kvpool.pack_block_arrays.
    def _swap_payload_store(self):
        if self._swap_store is None:
            # deferred import: artifacts -> backends -> engine is a cycle
            # at module scope
            from repro.cluster.artifacts import ArtifactStore
            self._swap_store = ArtifactStore()
        return self._swap_store

    def _gather_block_rows(self, blocks: List[int]) -> bytes:
        """Serialize pool rows ``blocks`` (caller flushed + pinned)."""
        ids = np.asarray(blocks, np.int32)
        n_pad = _next_pow2(len(ids)) if len(ids) > 1 else 1
        ids_p = np.full((n_pad,), NULL_BLOCK, np.int32)
        ids_p[:len(ids)] = ids
        rows = self.fns.kv_export(self.caches, jnp.asarray(ids_p))
        arrays = [np.asarray(leaf)[:, :len(ids)]
                  for leaf in jax.tree_util.tree_leaves(rows)]
        return pack_block_arrays(arrays)

    def _scatter_block_rows(self, blocks: List[int], arrays) -> None:
        """Write serialized rows (one array per cache leaf, block axis 1)
        into pool blocks ``blocks``."""
        ids = np.asarray(blocks, np.int32)
        n_pad = _next_pow2(len(ids)) if len(ids) > 1 else 1
        ids_p = np.full((n_pad,), NULL_BLOCK, np.int32)
        ids_p[:len(ids)] = ids
        padded = []
        for a in arrays:
            if n_pad > a.shape[1]:
                fill = np.zeros(a.shape[:1] + (n_pad - a.shape[1],)
                                + a.shape[2:], a.dtype)
                a = np.concatenate([a, fill], axis=1)
            padded.append(jnp.asarray(a))
        rows = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.caches), padded)
        self.caches = self.fns.kv_import(self.caches, jnp.asarray(ids_p),
                                         rows)

    def _wave_hi(self, s: int, adv: int, d: int) -> int:
        """Highest position (exclusive) slot ``s`` can write this sync."""
        scfg = self.scfg
        lo = int(self._pos_h[s])
        hi = min(lo + min(adv, int(self._rem_h[s])), scfg.max_len)
        if d:
            # the last verify window scatters up to d+1 rows past the
            # final emitted position
            hi = min(min(lo + min(adv, int(self._rem_h[s])),
                         scfg.max_len - 1) + d + 1, scfg.max_len)
        return hi

    def _swap_demand(self, s: int, adv: int, d: int) -> int:
        """Blocks slot ``s`` will claim this sync: fresh allocations plus
        COW copies of shared blocks in its write range."""
        bs = self.scfg.block_size
        sid = self._seq_of_slot[s]
        lo = int(self._pos_h[s])
        hi = self._wave_hi(s, adv, d)
        table = self.alloc.table(sid)
        fresh = max(-(-hi // bs) - len(table), 0)
        shared = sum(1 for j in range(lo // bs, min(-(-hi // bs),
                                                    len(table)))
                     if self.alloc.refcount(table[j]) > 1)
        return fresh + shared

    def _swap_out(self, slot: int):
        """Preempt slot ``slot``: serialize its blocks off-device, free
        them, and requeue the request at the queue FRONT carrying a
        :class:`SessionSnapshot` — it resumes ahead of never-admitted
        requests as soon as headroom returns.  The flush runs while the
        victim's table is untouched, so the export reads exactly the rows
        decode wrote."""
        req = self.active[slot]
        sid = self._seq_of_slot[slot]
        self._flush_virt()
        pos = int(self._pos_h[slot])
        table = self.alloc.table(sid)
        blocks = table[:-(-pos // self.scfg.block_size)] if pos else []
        snap = SessionSnapshot(
            pos=pos, rem=int(self._rem_h[slot]),
            last_tok=req.out_tokens[-1] if req.out_tokens else 0,
            n_blocks=len(blocks))
        if blocks:
            self.alloc.pin(blocks)
            try:
                data = self._gather_block_rows(blocks)
            finally:
                self.alloc.unpin(blocks)
            if self.scfg.swap_tier == "artifact":
                snap.digest = self._swap_payload_store().put_bytes(data)
            else:
                snap.data = data
        req.kv_snapshot = snap
        self.queue.appendleft(req)
        self.active[slot] = None
        self.alloc.free_seq(sid)
        self._seq_of_slot[slot] = None
        self._bt[slot] = NULL_BLOCK
        self._bt_dirty = True
        self._wb_h[slot] = self._pos_h[slot]
        self._act_h[slot] = False
        self._active = self._active.at[slot].set(False)
        self._last = self._last.at[slot].set(0)
        # the freed blocks can be rebound this very sync — regather so no
        # stale resident row aliases the new owner's content
        self._virt = None
        self.metrics.counter("engine.kv_swap_out").inc()
        self.metrics.counter("engine.kv_swapped_blocks").inc(len(blocks))
        current_recorder().record("kv_swap_out", rid=req.rid, slot=slot,
                                  pos=pos, blocks=len(blocks))
        self._kv_gauges()

    def _preempt_for_headroom(self, adv: int, d: int):
        """Swap-out preflight: while this wave's worst-case block demand
        exceeds the pool, preempt the lowest-``(priority, -rid)`` active
        session (lowest priority class first; ties toward the newest
        request, which has the least decode work to lose).  Runs BEFORE
        cow_targets/extend_to mutate any table, so exports always see
        consistent tables and no COW pair can reference a freed block.  A
        lone survivor is never preempted — if it still cannot fit, the
        existing ``_exhaust_victim`` fallback applies."""
        while True:
            live = [(r.priority, -r.rid, s)
                    for s, r in enumerate(self.active) if r is not None]
            if len(live) <= 1:
                return
            demand = sum(self._swap_demand(s, adv, d) for _, _, s in live)
            if demand <= self.alloc.available_blocks:
                return
            live.sort()
            self._swap_out(live[0][2])

    def _try_restore(self, free: List[int]) -> bool:
        """Queue head is a swapped-out session: re-admit it by importing
        its serialized blocks instead of prefilling.  True = handled
        (restored into a slot, or finished as unrestorable); False =
        deferred on pool pressure with the queue left intact — FIFO
        holds, so the preempted session resumes before anything behind
        it."""
        req = self.queue[0]
        snap = req.kv_snapshot
        need = snap.n_blocks + 1            # +1 decode-ahead block
        if need > self.alloc.num_blocks:
            # no future state of this pool can restore it: complete
            # explicitly (the single-victim contract)
            self.queue.popleft()
            req.kv_snapshot = None
            self.metrics.counter("engine.kv_pool_exhausted").inc()
            current_recorder().record("kv_pool_exhausted", rid=req.rid,
                                      pos=snap.pos, at="restore")
            req.done = True
            req.finish_reason = "kv_pool_exhausted"
            req.done_t = time.perf_counter()
            self._close_span(req)
            self.finished.append(req)
            self._emit(req, [], True)
            return True
        if need > self.alloc.available_blocks:
            return False
        slot = free.pop(0)
        self.queue.popleft()
        # survivors may hold lazily-pending decode rows that exist only in
        # the resident view; the restore invalidates that view below, so
        # flush them into the pool first or they would be silently dropped
        self._flush_virt()
        data = snap.data if snap.data is not None \
            else self._swap_payload_store().read_bytes(snap.digest)
        sid = self.alloc.new_seq()
        self.alloc.extend_to(sid, snap.pos)
        table = self.alloc.table(sid)
        if snap.n_blocks:
            self._scatter_block_rows(table, unpack_block_arrays(data))
        self._seq_of_slot[slot] = sid
        self._bt[slot] = padded_table(table, self.nb_max)
        self._bt_dirty = True
        # the pool now holds the restored rows; regather before decoding
        self._virt = None
        pos = snap.pos
        self._pos_h[slot] = pos
        self._wb_h[slot] = pos
        self._rem_h[slot] = snap.rem
        alive = snap.rem > 0 and pos < self.scfg.max_len - 1
        self._act_h[slot] = alive
        self._pos = self._pos.at[slot].set(pos)
        self._last = self._last.at[slot].set(snap.last_tok if alive else 0)
        self._remaining = self._remaining.at[slot].set(max(snap.rem, 0))
        self._active = self._active.at[slot].set(alive)
        if self.speculative:
            # rebuild the draft history at absolute positions: prompt,
            # then every token emitted so far (hist[pos] == last_tok)
            toks = np.concatenate(
                [req.prompt, np.asarray(req.out_tokens, np.int32)]
            )[:self.scfg.max_len]
            self._hist = self._hist.at[slot, :len(toks)].set(
                jnp.asarray(toks, jnp.int32))
        self.active[slot] = req
        req.kv_snapshot = None
        self.metrics.counter("engine.kv_swap_in").inc()
        current_recorder().record("kv_swap_in", rid=req.rid, slot=slot,
                                  pos=pos, blocks=snap.n_blocks)
        if not alive:
            self._finish(slot, "max_new" if snap.rem <= 0 else "max_len")
        self._kv_gauges()
        return True

    # ------------------------------------------------------------------
    # warm migration: drain-time hand-off of the prefix cache's published
    # blocks to a session's new rendezvous home (cluster/router.py ships
    # the frame; cluster/replica.py calls these between batches)
    def export_kv_state(self) -> Optional[dict]:
        """Serialize the prefix cache — ``(chained hash, block rows)`` in
        LRU order — as one picklable frame, or None when there is nothing
        to ship (dense engine / empty cache).  Published blocks are
        immutable (decode COWs before writing), so the export needs no
        quiesce beyond a flush; pins keep eviction away mid-gather."""
        if not self.paged:
            return None
        items = self.alloc.prefix_items()
        if not items:
            return None
        self.flush_kv()
        blocks = [b for _, b in items]
        self.alloc.pin(blocks)
        try:
            data = self._gather_block_rows(blocks)
        finally:
            self.alloc.unpin(blocks)
        self.metrics.counter("engine.kv_export_blocks").inc(len(blocks))
        current_recorder().record("kv_export", blocks=len(blocks))
        return {"kind": "kv_blocks", "block_size": self.scfg.block_size,
                "hashes": [h for h, _ in items], "data": data}

    def import_kv_state(self, state) -> int:
        """Adopt a migrated replica's prefix blocks: every unseen hash
        binds a *free* block (never evicting — adopted entries arrive
        evictable, so admission headroom never shrinks) and the shipped
        rows are scattered in with one jitted call.  Idempotent: already
        cached hashes are skipped, so at-least-once delivery is safe.
        Returns the number of adopted blocks."""
        if not self.paged or not isinstance(state, dict) \
                or state.get("kind") != "kv_blocks" \
                or state.get("block_size") != self.scfg.block_size:
            return 0
        arrays = unpack_block_arrays(state["data"])
        ids: List[int] = []
        cols: List[int] = []
        for i, h in enumerate(state["hashes"]):
            b = self.alloc.import_cached(h)
            if b is None:
                continue
            ids.append(b)
            cols.append(i)
        if not ids:
            return 0
        sel = np.asarray(cols, np.intp)
        self._scatter_block_rows(ids, [a[:, sel] for a in arrays])
        self.metrics.counter("engine.kv_import_blocks").inc(len(ids))
        current_recorder().record("kv_import", blocks=len(ids))
        self._kv_gauges()
        return len(ids)

    def _step_paged(self) -> bool:
        self._admit_paged()
        if not any(r is not None for r in self.active):
            return False
        scfg = self.scfg
        d = scfg.spec_draft if self.speculative else 0
        adv = scfg.sync_every * (d + 1)   # max emissions in one sync
        dsp = self._decode_sync_span()
        # allocate-ahead, COW and the block-table upload (or the resident
        # view's regather) before the loop is dispatched
        ksp = current_tracer().span("engine.kv_prep", parent=dsp, step=True)
        if scfg.kv_swap:
            # swap preflight: make room by preempting whole sessions
            # BEFORE any table mutates below, so swap-outs export
            # consistent tables and never strand a COW pair
            self._preempt_for_headroom(adv, d)
        # host pre-work: every active slot needs writable private blocks
        # covering every position this loop can write — allocate ahead,
        # COW any block shared with the prefix cache or a fork.  Under
        # speculation the last verify window scatters up to d+1 rows past
        # the final emitted position, so cover (but never allocate past
        # max_len) those too.
        cow_src: List[int] = []
        cow_dst: List[int] = []
        max_hi = 1
        for s, req in enumerate(self.active):
            if req is None:
                continue
            sid = self._seq_of_slot[s]
            lo = int(self._pos_h[s])
            hi = self._wave_hi(s, adv, d)
            pairs = self.alloc.cow_targets(sid, lo, hi)
            try:
                fresh = self.alloc.extend_to(sid, hi)
            except PoolExhausted:
                # the victim's COW pairs are dropped: its sequence is
                # freed, so mirroring them on device could race the very
                # allocations its freed blocks now satisfy
                self._exhaust_victim(s)
                continue
            cow_src += [p[0] for p in pairs]
            cow_dst += [p[1] for p in pairs]
            if pairs or fresh:
                self._bt[s] = padded_table(self.alloc.table(sid),
                                           self.nb_max)
                self._bt_dirty = True
            max_hi = max(max_hi, hi)
        if not any(r is not None for r in self.active):
            ksp.end()
            dsp.end()
            return True
        if cow_src:
            pad = (_next_pow2(len(cow_src)) if len(cow_src) > 1 else 1) \
                - len(cow_src)
            src = jnp.asarray([0] * pad + cow_src, jnp.int32)
            dst = jnp.asarray([0] * pad + cow_dst, jnp.int32)
            self.caches = self.fns.cow(self.caches, src, dst)
            self.metrics.counter("engine.kv_cow_copies").inc(len(cow_src))
            dsp.tag(cow_copies=len(cow_src))
            current_recorder().record("cow", n=len(cow_src))
        # resident virtual caches with lazy writeback: a steady-state sync
        # is ONE jit call (the dense loop on the resident view) — no pool
        # scatter, no block-table upload, no gather.  The width bucket
        # covers every position this WAVE can ever write (pos + remaining
        # budget), so the view stays width-stable across block-boundary
        # crossings — and across admits too, since the admit jit
        # refreshes its own slots' rows in place; a regather
        # (invalidation or width growth) flushes pending rows first so
        # the pool it reads is authoritative.  The kernel path instead re-cuts the device
        # table to the tighter per-sync bound (the Pallas kernel re-reads
        # the pool every step; width only sets how many blocks the grid
        # walks).
        use_virt = self.speculative or not self.cfg.use_kernels
        if use_virt:
            need = 1
            for s, req in enumerate(self.active):
                if req is None:
                    continue
                fin = min(int(self._pos_h[s]) + int(self._rem_h[s]) + d + 1,
                          scfg.max_len)
                need = max(need, -(-fin // scfg.block_size))
            nbw = 1
            while nbw < need:
                nbw *= 2
            nbw = min(nbw, self.nb_max)
            if self._virt is not None and self._virt_width > nbw:
                # a wider resident cache is still valid (extra columns are
                # all >= pos, junk-tolerant) — keep it rather than regather
                nbw = self._virt_width
            if self._virt is None or self._virt_width != nbw:
                self._flush_virt()
                if self._bt_dirty or nbw != self._bt_width:
                    self._bt_dev = jnp.asarray(self._bt[:, :nbw])
                    self._bt_width = nbw
                    self._bt_dirty = False
                self._virt = self.fns.gather_virt(self.caches,
                                                  self._bt_dev)
                self._virt_width = nbw
                self._wb_h[:] = self._pos_h
        else:
            need = -(-max_hi // scfg.block_size)
            nbw = 1
            while nbw < need:
                nbw *= 2
            nbw = min(nbw, self.nb_max)
            if self._bt_dirty or nbw != self._bt_width:
                self._bt_dev = jnp.asarray(self._bt[:, :nbw])
                self._bt_width = nbw
                self._bt_dirty = False
        ksp.end()
        if self.speculative:
            ssp = current_tracer().span("engine.spec_decode", parent=dsp,
                                        step=True, draft_len=d)
            packed, self._virt, self._hist, self._pos, self._last, \
                self._active, self._remaining, self._rng = \
                self.fns.spec_decode_loop(
                    self.params, self._virt, self._hist, self._pos,
                    self._last, self._active, self._remaining, self._rng)
        elif use_virt:
            packed, self._virt, self._pos, self._last, self._active, \
                self._remaining, self._rng = self.fns.paged_decode_loop(
                    self.params, self._virt, self._pos, self._last,
                    self._active, self._remaining, self._rng)
        else:
            packed, self._bt_dev, self.caches, self._pos, self._last, \
                self._active, self._remaining, self._rng = \
                self.fns.paged_decode_loop(
                    self.params, self._bt_dev, self.caches, self._pos,
                    self._last, self._active, self._remaining, self._rng)
        hsp = current_tracer().span("engine.host_sync", parent=dsp,
                                    step=True)
        # ONE device fetch: [tokens | emitted]; liveness, positions and
        # budgets advance host-side by exactly the emitted counts
        packed_h = np.asarray(packed)
        if self.speculative:
            out_h, em_h = packed_h[:, :-3], packed_h[:, -3]
        else:
            out_h, em_h = packed_h[:, :-1], packed_h[:, -1]
        self._pos_h += em_h.astype(np.int64)
        self._rem_h -= em_h.astype(np.int64)
        self._act_h &= (self._rem_h > 0) & \
            (self._pos_h < scfg.max_len - 1)
        if self.speculative:
            acc, prop = int(packed_h[0, -2]), int(packed_h[0, -1])
            self.metrics.counter("engine.spec_proposed").inc(prop)
            self.metrics.counter("engine.spec_accepted").inc(acc)
            ssp.tag(proposed=prop, accepted=acc)
            ssp.end()
        hsp.end()
        esp = current_tracer().span("engine.stream_emit", parent=dsp,
                                    step=True) \
            if any(r is not None and r.on_tokens is not None
                   for r in self.active) else NULL_SPAN
        for s, req in enumerate(self.active):
            if req is None:
                continue
            new = [int(t) for t in out_h[s, :em_h[s]]]
            req.out_tokens.extend(new)
            if not self._act_h[s]:
                self._finish(s, "max_new" if self._rem_h[s] <= 0
                             else "max_len")
            self._emit(req, new, req.done)
        esp.end()
        dsp.end()
        self.metrics.counter("engine.steps").inc()
        return True

    def fork(self, parent: Request, max_new: int,
             on_tokens: Optional[Callable] = None) -> Request:
        """Branch an *active* request into a new session that shares all
        of its KV blocks copy-on-write (parallel sampling / n-best).  The
        child continues from the parent's current position; its blocks
        stay shared until either side writes (then `cow_targets` splits
        exactly the written block).  Paged engines only; needs a free
        slot."""
        if not self.paged:
            raise RuntimeError("fork requires a paged engine "
                               "(ServeConfig.paged=True on a supported "
                               "family)")
        try:
            pslot = next(s for s, r in enumerate(self.active)
                         if r is parent)
        except StopIteration:
            raise ValueError(f"request {parent.rid} is not active "
                             f"(finished or still queued)") from None
        try:
            slot = next(s for s, r in enumerate(self.active) if r is None)
        except StopIteration:
            raise RuntimeError("no free slot to fork into") from None
        child = Request(rid=next(self._rids), prompt=parent.prompt.copy(),
                        max_new=max_new,
                        out_tokens=list(parent.out_tokens),
                        submit_t=time.perf_counter(), on_tokens=on_tokens)
        child.first_token_t = child.submit_t
        # the child's first regather reads the parent's rows from the
        # pool — flush the parent's pending writeback before sharing
        self._flush_virt()
        sid = self.alloc.fork(self._seq_of_slot[pslot])
        self._seq_of_slot[slot] = sid
        self._bt[slot] = padded_table(self.alloc.table(sid), self.nb_max)
        self._bt_dirty = True
        # the child slot's resident virtual row is whatever its previous
        # occupant left behind — regather before the next sync
        self._virt = None
        self._pos_h[slot] = self._pos_h[pslot]
        self._rem_h[slot] = max(max_new, 0)
        pos = int(self._pos_h[pslot])
        last_tok = parent.out_tokens[-1] if parent.out_tokens else 0
        alive = max_new > 0 and pos < self.scfg.max_len - 1
        self._act_h[slot] = alive
        self._pos = self._pos.at[slot].set(pos)
        self._last = self._last.at[slot].set(last_tok if alive else 0)
        self._remaining = self._remaining.at[slot].set(max(max_new, 0))
        self._active = self._active.at[slot].set(alive)
        if self.speculative:
            self._hist = self._hist.at[slot].set(self._hist[pslot])
        self.active[slot] = child
        self.metrics.counter("engine.forks").inc()
        if not alive:
            self._finish(slot, "max_new" if max_new <= 0 else "max_len")
        self._kv_gauges()
        return child

    # ------------------------------------------------------------------
    # reference path: the pre-PR per-token loop (parity oracle / "before"
    # benchmark side); one host round trip + (slots, vocab) logits transfer
    # per token, full cache copy per step and per admit.
    def _admit_reference(self):
        for slot in range(self.scfg.slots):
            if self.active[slot] is None and self.queue:
                req = self.queue.popleft()
                plen = len(req.prompt)
                logits, small = self.fns.prefill_fn(plen)(
                    self.params, jnp.asarray(req.prompt[None]))
                self.caches = _insert_slot(self.caches, small, slot)
                tok = int(jnp.argmax(logits[0, -1]))
                req.out_tokens.append(tok)
                req.first_token_t = time.perf_counter()
                self.active[slot] = req
                self.pos[slot] = plen                 # next write position
                if req.max_new <= 0:
                    self._finish(slot, "max_new")
                elif plen >= self.scfg.max_len - 1:
                    self._finish(slot, "max_len")
                self._emit(req, req.out_tokens[-1:], req.done)

    def _step_reference(self) -> bool:
        self._admit_reference()
        if not any(r is not None for r in self.active):
            return False
        toks = np.zeros((self.scfg.slots, 1), np.int32)
        for s, req in enumerate(self.active):
            if req is not None:
                toks[s, 0] = req.out_tokens[-1]
        logits, self.caches = self.fns.decode(self.params, jnp.asarray(toks),
                                              self.caches,
                                              jnp.asarray(self.pos))
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for s, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[s] += 1
            req.out_tokens.append(int(nxt[s]))
            if req.decoded >= req.max_new:
                self._finish(s, "max_new")
            elif self.pos[s] >= self.scfg.max_len - 1:
                self._finish(s, "max_len")
            self._emit(req, req.out_tokens[-1:], req.done)
        self.metrics.counter("engine.steps").inc()
        return True

    # ------------------------------------------------------------------
    def step(self):
        """One engine iteration: admit, then decode — a single step on the
        reference path, ``sync_every`` fused steps (one host sync) on the
        fused and paged paths."""
        with current_tracer().span("engine.step", step=True):
            if self._watch_early:
                self._sweep_expired()
            if self.paged:
                return self._step_paged()
            if self.scfg.fused:
                return self._step_fused()
            return self._step_reference()

    def run_until_drained(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or any(r is not None for r in self.active)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.finished
