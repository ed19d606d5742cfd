"""Paged KV-cache engine: token-exact parity with the dense fused oracle,
prefix-cache reuse, copy-on-write forks, and eviction under pool pressure.

The paged path (block-pool caches, block-table decode, suffix-only admits
behind a content-hashed prefix cache) must be observationally invisible:
greedy token streams match the dense fused engine request-for-request,
including mid-K-loop completion + slot refill and max_len truncation.
"""
import jax
import numpy as np
import pytest

from tests._hyp_compat import given, settings, st

from repro.configs import get_config
from repro.configs.base import ScanGroup, reduced
from repro.models import api, transformer as tfm
from repro.serving import BlockAllocator, Engine, PoolExhausted, ServeConfig
from repro.serving.kvpool import hash_token_blocks

# transformer families whose whole cache is position-addressed attention
# K/V — the pageable set (GQA incl. internlm2, MHA, MoE-with-plain-attn)
PAGED_FAMILIES = ["internlm2-1.8b",     # GQA 2:1 (reduced)
                  "gemma-7b",           # MHA, tied embeddings
                  "qwen3-moe-30b-a3b"]  # MoE (batch-1 admits), qk-norm


def _model(arch, seed=0):
    cfg = reduced(get_config(arch))
    params, _ = api.init(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _drain(params, cfg, scfg, prompts, max_new):
    eng = Engine(params, cfg, scfg)
    reqs = [eng.submit(p, max_new=max_new) for p in prompts]
    eng.run_until_drained()
    return eng, reqs


# ----------------------------------------------------------------------
# parity vs the dense fused oracle
@pytest.mark.parametrize("arch", PAGED_FAMILIES)
def test_paged_matches_dense_with_refill(arch):
    """5 requests through 2 slots: slots complete mid-K-loop and refill
    from the queue; K does not divide max_new; block_size smaller than
    most prompts so sequences span several blocks."""
    cfg, params = _model(arch)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 9, 7, 12, 6)]
    _, dense = _drain(params, cfg,
                      ServeConfig(max_len=64, slots=2, fused=True,
                                  sync_every=4),
                      prompts, max_new=6)
    peng, paged = _drain(params, cfg,
                         ServeConfig(max_len=64, slots=2, fused=True,
                                     sync_every=4, paged=True, block_size=8),
                         prompts, max_new=6)
    assert peng.paged
    for i, (a, b) in enumerate(zip(dense, paged)):
        assert a.out_tokens == b.out_tokens, (arch, i)
        assert a.finish_reason == b.finish_reason == "max_new"
    # every request's blocks were released at finish
    assert peng.alloc.free_blocks + peng.alloc.cached_blocks == \
        peng.alloc.num_blocks


def test_paged_truncation_parity():
    """max_len truncation fires at the same token on both paths even when
    it lands mid-K-loop, and the paged slot frees its blocks."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab, size=n).astype(np.int32)
               for n in (4, 9)]
    _, dense = _drain(params, cfg,
                      ServeConfig(max_len=32, slots=2, fused=True,
                                  sync_every=8),
                      prompts, max_new=100)
    _, paged = _drain(params, cfg,
                      ServeConfig(max_len=32, slots=2, fused=True,
                                  sync_every=8, paged=True, block_size=8),
                      prompts, max_new=100)
    for a, b in zip(dense, paged):
        assert a.out_tokens == b.out_tokens
        assert a.finish_reason == b.finish_reason == "max_len"


def test_paged_kernel_path_matches_reference_path():
    """cfg.use_kernels routes paged decode through the Pallas kernel
    (interpret mode on CPU); tokens must match the jnp gather path."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab, size=n).astype(np.int32)
               for n in (6, 11)]
    scfg = ServeConfig(max_len=32, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8)
    _, ref = _drain(params, cfg, scfg, prompts, max_new=5)
    _, ker = _drain(params, cfg.replace(use_kernels=True), scfg,
                    prompts, max_new=5)
    for a, b in zip(ref, ker):
        assert a.out_tokens == b.out_tokens


def _block_writers(bt, pos, active, remaining, k, max_len, bs):
    """Every physical block the K-step loop writes, mapped to the slots
    that write it: live slots at each position they decode, frozen and
    idle slots at their frozen position, as the device loop does."""
    writers = {}
    nb = bt.shape[1]
    pos, active, remaining = pos.copy(), active.copy(), remaining.copy()
    for _ in range(k):
        for s in range(len(pos)):
            vb = pos[s] // bs
            phys = int(bt[s, vb]) if vb < nb else 0
            writers.setdefault(phys, set()).add(s)
        pos = pos + active
        remaining = remaining - active
        active = active & (remaining > 0) & (pos < max_len - 1)
    return writers


@pytest.mark.parametrize("groups", [None, (ScanGroup(("A", "A"), 2),)],
                         ids=["one_layer", "two_positions_two_repeats"])
def test_paged_kernel_path_refills_slots_and_reuses_blocks(groups):
    """More requests than slots on the kernel path, over a pool too small
    to keep them all: slots refill, freed blocks are written again, and
    the tokens equal the jnp path's — also where the decode loop carries a
    stack of several layers for each of several pattern positions.  Before
    every decode loop, no real block is written by two slots: the aliased
    write kernel is exact only for blocks one row of a step names."""
    cfg = reduced(get_config("internlm2-1.8b"))
    if groups is not None:
        cfg = cfg.replace(groups=groups,
                          n_layers=sum(g.n_layers for g in groups))
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, cfg.vocab, size=n).astype(np.int32)
               for n in (9, 12, 5, 14, 7)]
    scfg = ServeConfig(max_len=32, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8, kv_blocks=7)
    _, ref = _drain(params, cfg, scfg, prompts, max_new=6)

    eng = Engine(params, cfg.replace(use_kernels=True), scfg)
    loop = eng.fns.paged_decode_loop
    shared, owners = [], {}

    def checked_loop(params, bt, caches, pos, last, active, remaining, rng):
        bt_h = np.asarray(bt)
        writers = _block_writers(bt_h, np.asarray(pos), np.asarray(active),
                                 np.asarray(remaining), scfg.sync_every,
                                 scfg.max_len, scfg.block_size)
        shared.extend(b for b, slots in writers.items()
                      if b != 0 and len(slots) > 1)
        for s, req in enumerate(eng.active):
            if req is not None:
                for b in set(bt_h[s].tolist()) - {0}:
                    owners.setdefault(b, set()).add(id(req))
        return loop(params, bt, caches, pos, last, active, remaining, rng)

    eng.fns.paged_decode_loop = checked_loop
    ker = [eng.submit(p, max_new=6) for p in prompts]
    eng.run_until_drained()
    assert not shared
    # some block served one request, was freed, and served another
    assert any(len(reqs) > 1 for reqs in owners.values())
    for a, b in zip(ref, ker):
        assert a.out_tokens == b.out_tokens
        assert b.finish_reason == "max_new"


def test_unpageable_family_falls_back_dense():
    """SSM state is not position-addressed: paged=True degrades to the
    dense fused path (observable, not silent) and still serves."""
    cfg, params = _model("falcon-mamba-7b")
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab, size=6).astype(np.int32)]
    scfg = ServeConfig(max_len=32, slots=2, fused=True, paged=True,
                       block_size=8)
    eng, reqs = _drain(params, cfg, scfg, prompts, max_new=4)
    assert not eng.paged
    assert eng.metrics.counter("engine.paged_fallback_dense").value == 1
    assert all(r.done for r in reqs)


# ----------------------------------------------------------------------
# prefix cache
def test_prefix_cache_hits_and_accounting():
    """Second request with a shared 2-block prefix reuses the cached
    blocks (counters record hits and prefill tokens saved) and emits
    exactly the tokens a cold engine would."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(5)
    common = rng.randint(0, cfg.vocab, size=16).astype(np.int32)
    p1 = np.concatenate([common,
                         rng.randint(0, cfg.vocab, 4).astype(np.int32)])
    p2 = np.concatenate([common,
                         rng.randint(0, cfg.vocab, 3).astype(np.int32)])
    scfg = ServeConfig(max_len=64, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8)
    eng = Engine(params, cfg, scfg)
    r1 = eng.submit(p1, max_new=5)
    eng.run_until_drained()
    assert eng.metrics.counter("engine.prefix_hit_blocks").value == 0
    r2 = eng.submit(p2, max_new=5)
    eng.run_until_drained()
    assert eng.metrics.counter("engine.prefix_hit_blocks").value == 2
    assert eng.metrics.counter("engine.prefill_tokens_saved").value == 16
    # miss accounting: lookups counted in blocks, hits a subset
    assert eng.metrics.counter("engine.prefix_lookup_blocks").value == 4
    # parity with a cold dense engine for both requests
    _, dense = _drain(params, cfg,
                      ServeConfig(max_len=64, slots=2, fused=True,
                                  sync_every=4), [p1, p2], max_new=5)
    assert r1.out_tokens == dense[0].out_tokens
    assert r2.out_tokens == dense[1].out_tokens


def test_prefix_cache_survives_request_free():
    """Finishing a request keeps its full prompt blocks alive through the
    cache's own reference; an identical later prompt hits all of them."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, cfg.vocab, size=17).astype(np.int32)  # 2 full
    scfg = ServeConfig(max_len=64, slots=2, fused=True, paged=True,
                       block_size=8)
    eng = Engine(params, cfg, scfg)
    r1 = eng.submit(prompt.copy(), max_new=4)
    eng.run_until_drained()
    assert eng.alloc.cached_blocks == 2
    r2 = eng.submit(prompt.copy(), max_new=4)
    eng.run_until_drained()
    assert eng.metrics.counter("engine.prefix_hit_blocks").value == 2
    assert r1.out_tokens == r2.out_tokens


def test_eviction_under_pressure():
    """A pool too small to cache everything evicts LRU prefix blocks to
    satisfy new admits instead of refusing them; token streams stay exact
    vs dense throughout."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab, size=12).astype(np.int32)
               for _ in range(6)]
    # 2 slots x max_len=32/bs=8 dense-equivalent would be 8 blocks; give
    # the pool barely more than one sequence's worth so cached prefixes
    # must be evicted as new prompts arrive
    scfg = ServeConfig(max_len=32, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8, kv_blocks=7)
    eng, paged = _drain(params, cfg, scfg, prompts, max_new=4)
    assert eng.alloc.evictions > 0
    _, dense = _drain(params, cfg,
                      ServeConfig(max_len=32, slots=2, fused=True,
                                  sync_every=4), prompts, max_new=4)
    for a, b in zip(dense, paged):
        assert a.out_tokens == b.out_tokens


def test_admits_defer_under_pool_pressure():
    """When the pool cannot hold another prompt even after eviction, the
    admit waits in the queue (deferral counter) until blocks free up —
    nothing is dropped and nothing corrupts."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, cfg.vocab, size=12).astype(np.int32)
               for _ in range(3)]
    scfg = ServeConfig(max_len=32, slots=3, fused=True, sync_every=4,
                       paged=True, block_size=8, kv_blocks=4,
                       prefix_cache=False)
    eng, reqs = _drain(params, cfg, scfg, prompts, max_new=4)
    assert all(r.done for r in reqs)
    assert eng.metrics.counter("engine.admit_deferred_kv").value > 0


# ----------------------------------------------------------------------
# copy-on-write forks
def test_fork_greedy_identical_and_cow_isolated():
    """A greedy fork shares the parent's blocks and must continue with
    exactly the parent's stream — COW splits only the written block, and
    the parent's subsequent tokens match an unforked run (shared history
    uncorrupted)."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, cfg.vocab, size=10).astype(np.int32)
    scfg = ServeConfig(max_len=64, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8)
    solo_eng, (solo,) = _drain(params, cfg, scfg, [prompt.copy()],
                               max_new=12)
    eng = Engine(params, cfg, scfg)
    parent = eng.submit(prompt.copy(), max_new=12)
    eng.step()                          # admit + one K-step sync
    child = eng.fork(parent, max_new=parent.max_new - parent.decoded)
    eng.run_until_drained()
    assert eng.alloc.cow_copies > 0
    assert parent.out_tokens == solo.out_tokens
    assert child.out_tokens == solo.out_tokens[:len(child.out_tokens)]


def test_fork_temperature_diverges():
    """With temperature sampling the forked branch explores its own
    continuation while sharing the prompt KV copy-on-write."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(10)
    prompt = rng.randint(0, cfg.vocab, size=9).astype(np.int32)
    scfg = ServeConfig(max_len=64, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8, temperature=1.0, seed=3)
    eng = Engine(params, cfg, scfg)
    parent = eng.submit(prompt, max_new=16)
    eng.step()
    fork_at = len(parent.out_tokens)
    child = eng.fork(parent, max_new=parent.max_new - parent.decoded)
    eng.run_until_drained()
    assert parent.out_tokens[:fork_at] == child.out_tokens[:fork_at]
    assert parent.out_tokens != child.out_tokens


def test_fork_requires_paged_and_active():
    cfg, params = _model("internlm2-1.8b")
    dense = Engine(params, cfg, ServeConfig(max_len=32, slots=2))
    req = dense.submit(np.arange(4, dtype=np.int32), max_new=2)
    with pytest.raises(RuntimeError, match="paged"):
        dense.fork(req, max_new=2)
    peng = Engine(params, cfg, ServeConfig(max_len=32, slots=2, paged=True,
                                           block_size=8))
    queued = peng.submit(np.arange(4, dtype=np.int32), max_new=2)
    with pytest.raises(ValueError, match="not active"):
        peng.fork(queued, max_new=2)


# ----------------------------------------------------------------------
# allocator unit behavior (host-side, no jax)
def test_allocator_refcounts_and_free():
    al = BlockAllocator(num_blocks=8, block_size=4)
    s1 = al.new_seq()
    fresh = al.extend_to(s1, 10)          # 3 blocks
    assert len(fresh) == 3 and al.free_blocks == 5
    s2 = al.fork(s1)
    assert all(al.refcount(b) == 2 for b in al.table(s1))
    al.free_seq(s1)
    assert all(al.refcount(b) == 1 for b in al.table(s2))
    al.free_seq(s2)
    assert al.free_blocks == 8


def test_allocator_cow_splits_only_written_range():
    al = BlockAllocator(num_blocks=8, block_size=4)
    s1 = al.new_seq()
    al.extend_to(s1, 12)                  # blocks for positions 0..11
    s2 = al.fork(s1)
    copies = al.cow_targets(s2, 9, 11)    # write range inside block 2
    assert len(copies) == 1
    assert al.table(s2)[:2] == al.table(s1)[:2]       # still shared
    assert al.table(s2)[2] != al.table(s1)[2]         # split
    assert al.refcount(al.table(s1)[2]) == 1
    assert al.cow_targets(s2, 9, 11) == []            # now private


def test_allocator_null_block_never_allocated():
    al = BlockAllocator(num_blocks=4, block_size=4)
    s = al.new_seq()
    al.extend_to(s, 16)
    assert 0 not in al.table(s)


def test_allocator_exhaustion_and_eviction():
    al = BlockAllocator(num_blocks=4, block_size=4)
    s1 = al.new_seq()
    al.extend_to(s1, 8)                   # 2 blocks live
    hashes = hash_token_blocks(list(range(8)), 4)
    al.prefix_insert(hashes, al.table(s1))
    al.free_seq(s1)                       # cache-only now: evictable
    assert al.free_blocks == 2 and al.evictable_blocks == 2
    s2 = al.new_seq()
    al.extend_to(s2, 16)                  # needs all 4 -> evicts 2
    assert al.evictions == 2
    with pytest.raises(PoolExhausted):
        al.extend_to(al.new_seq(), 4)


def test_oversized_prompt_rejected_individually():
    """A prompt the whole pool cannot hold completes empty with an
    explicit finish reason — it must not raise out of step() (killing its
    batch-mates) and must not wedge the queue behind it."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(11)
    ok_prompt = rng.randint(0, cfg.vocab, size=8).astype(np.int32)
    big_prompt = rng.randint(0, cfg.vocab, size=30).astype(np.int32)
    scfg = ServeConfig(max_len=32, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8, kv_blocks=4,
                       prefix_cache=False)
    eng = Engine(params, cfg, scfg)
    a = eng.submit(ok_prompt, max_new=3)
    b = eng.submit(big_prompt, max_new=3)       # needs 4+1 blocks > 4
    c = eng.submit(ok_prompt.copy(), max_new=3)
    eng.run_until_drained()
    assert a.done and a.finish_reason == "max_new"
    assert b.done and b.finish_reason == "rejected_prompt_too_long"
    assert b.out_tokens == []
    assert c.done and c.out_tokens == a.out_tokens
    assert eng.metrics.counter("engine.rejected_too_long").value == 1


def test_pool_exhausted_mid_decode_completes_victim():
    """When decode growth exhausts the pool with nothing evictable, the
    engine sacrifices the slot it could not extend: the victim completes
    with finish_reason="kv_pool_exhausted" (its emitted prefix intact and
    token-exact), its blocks return to the pool, and the surviving slot
    decodes on to a token-exact finish — nothing raises out of step()."""
    cfg, params = _model("internlm2-1.8b")
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, cfg.vocab, size=8).astype(np.int32)
               for _ in range(2)]
    # each sequence wants 32 positions = 4 blocks; 2 x 4 > 5 available,
    # so one slot must be sacrificed mid-decode
    scfg = ServeConfig(max_len=32, slots=2, fused=True, sync_every=4,
                       paged=True, block_size=8, kv_blocks=5,
                       prefix_cache=False)
    eng, reqs = _drain(params, cfg, scfg, prompts, max_new=24)
    assert all(r.done for r in reqs)
    reasons = [r.finish_reason for r in reqs]
    assert reasons.count("kv_pool_exhausted") == 1
    assert eng.metrics.counter("engine.kv_pool_exhausted").value == 1
    # every block returned to the pool when the requests finished
    assert eng.alloc.free_blocks == 5
    # both streams are exact prefixes of the dense oracle's: the victim
    # up to its eviction, the survivor to completion
    _, dense = _drain(params, cfg,
                      ServeConfig(max_len=32, slots=2, fused=True,
                                  sync_every=4), prompts, max_new=24)
    for a, b in zip(dense, reqs):
        assert b.out_tokens == a.out_tokens[:len(b.out_tokens)]
        if b.finish_reason != "kv_pool_exhausted":
            assert b.out_tokens == a.out_tokens
            assert b.finish_reason == a.finish_reason


def test_available_excluding_pinned_hits():
    """The admit headroom probe must not double-count its own prefix hits
    as evictable: taking the hits pins them, shrinking the eviction
    pool."""
    al = BlockAllocator(num_blocks=3, block_size=4)
    s = al.new_seq()
    al.extend_to(s, 12)
    hashes = hash_token_blocks(list(range(12)), 4)
    al.prefix_insert(hashes, al.table(s))
    al.free_seq(s)                         # all 3 blocks cache-only
    hits = al.prefix_lookup(hashes[:2])
    assert al.available_blocks == 3
    assert al.available_excluding(hits) == 1


def test_hash_token_blocks_chains_prefixes():
    bs = 4
    a = hash_token_blocks([1, 2, 3, 4, 5, 6, 7, 8, 9], bs)
    b = hash_token_blocks([1, 2, 3, 4, 5, 6, 7, 8, 42], bs)
    c = hash_token_blocks([9, 2, 3, 4, 5, 6, 7, 8], bs)
    assert len(a) == 2 and a[:2] == b[:2]     # full blocks identical
    assert c[0] != a[0] and c[1] != a[1]      # divergence chains forward


# ----------------------------------------------------------------------
# config validation
def test_serve_config_paged_validation():
    with pytest.raises(ValueError, match="fused"):
        ServeConfig(paged=True, fused=False)
    with pytest.raises(ValueError, match="block_size"):
        ServeConfig(paged=True, max_len=100, block_size=16)


def test_paged_supported_gate():
    assert tfm.paged_supported(reduced(get_config("internlm2-1.8b")), 64)
    assert tfm.paged_supported(reduced(get_config("qwen3-moe-30b-a3b")), 64)
    assert not tfm.paged_supported(reduced(get_config("falcon-mamba-7b")), 64)
    assert not tfm.paged_supported(
        reduced(get_config("recurrentgemma-2b")), 64)
    assert not tfm.paged_supported(
        reduced(get_config("deepseek-v2-lite-16b")), 64)
    assert not tfm.paged_supported(reduced(get_config("gemma3-4b")), 64)


# ----------------------------------------------------------------------
# KV lifecycle properties (PR 8): the swap serialization frame and the
# preempt/swap/restore decode path, over randomized shapes and loads.

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_pack_unpack_bit_exact_property(seed):
    """serialize -> ship -> deserialize is bit-exact for arbitrary leaf
    counts, block counts, dtypes, and head geometries."""
    from repro.serving import pack_block_arrays, unpack_block_arrays

    rng = np.random.RandomState(seed % (2**31 - 1) or 1)
    n_leaves = int(rng.randint(1, 5))
    n_blocks = int(rng.randint(1, 7))
    arrays = []
    for _ in range(n_leaves):
        dt = np.dtype(["<f4", "<i4", "<f2", "<u1"][rng.randint(0, 4)])
        shape = (int(rng.randint(1, 3)), n_blocks, int(rng.randint(2, 9)),
                 int(rng.randint(1, 4)), int(rng.randint(2, 9)))
        if dt.kind == "f":
            a = rng.randn(*shape).astype(dt)
        else:
            a = rng.randint(0, 255, size=shape).astype(dt)
        arrays.append(a)
    out = unpack_block_arrays(pack_block_arrays(arrays))
    assert len(out) == n_leaves
    for a, b in zip(arrays, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


_SWAP_MODEL = {}


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_swap_restore_decode_token_exact_property(seed):
    """Whatever prompt lengths / budgets / request counts the seed draws,
    a tight swapping pool's restored block tables produce token-exact
    decode versus an ample-pool oracle, nothing finishes as a
    ``kv_pool_exhausted`` victim, and every swap-out is restored."""
    from repro.cluster.backends import shared_engine_fns

    if "m" not in _SWAP_MODEL:
        _SWAP_MODEL["m"] = _model("internlm2-1.8b")
    cfg, params = _SWAP_MODEL["m"]
    rng = np.random.RandomState(seed % (2**31 - 1) or 1)
    n_req = int(rng.randint(4, 7))
    prompts = [rng.randint(0, cfg.vocab,
                           size=int(rng.randint(4, 12))).astype(np.int32)
               for _ in range(n_req)]
    max_new = int(rng.randint(6, 14))
    ample = ServeConfig(max_len=48, slots=2, sync_every=4, paged=True,
                        block_size=8, kv_blocks=64, prefix_cache=False)
    tight = ServeConfig(max_len=48, slots=4, sync_every=4, paged=True,
                        block_size=8, kv_blocks=9, prefix_cache=True,
                        kv_swap=True)

    def drain(scfg):
        eng = Engine(params, cfg, scfg,
                     shared_fns=shared_engine_fns(cfg, scfg))
        reqs = [eng.submit(p.copy(), max_new=max_new) for p in prompts]
        eng.run_until_drained()
        return eng, reqs

    _, oracle = drain(ample)
    eng, got = drain(tight)
    for i, (a, b) in enumerate(zip(oracle, got)):
        assert b.finish_reason == "max_new", (i, b.finish_reason)
        assert a.out_tokens == b.out_tokens, \
            (i, a.out_tokens, b.out_tokens)
    snap = eng.metrics.snapshot()
    assert snap.get("engine.kv_pool_exhausted", 0) == 0
    assert snap.get("engine.kv_swap_in", 0) == \
        snap.get("engine.kv_swap_out", 0)
    assert eng.alloc.free_blocks + eng.alloc.cached_blocks == \
        eng.alloc.num_blocks
