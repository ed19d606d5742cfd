"""Compile the main path's Pallas kernels for a TPU v5e at published widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) v5e chip, which raises whatever the chip's compiler would raise —
misaligned block shapes, too much VMEM — errors the CPU interpreter never
sees.  The topology is described inside a fixture (never while a module is
imported), and the persistent compilation cache is off around these
compiles: an entry written here could not be read back without a chip.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ScanGroup
from repro.kernels import ops
from repro.models import api, transformer as tfm

INTERNLM2 = get_config("internlm2-1.8b")
FALCON_MAMBA = get_config("falcon-mamba-7b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# internlm2-1.8b serving widths: 8 slots, 16-token blocks, 1024-token tables
B, BS, NB = 8, 16, 64
H, KV, HD = INTERNLM2.n_heads, INTERNLM2.n_kv_heads, INTERNLM2.head_dim
POOL = ((B * NB + 1, KV, BS, HD), jnp.bfloat16)
# two layers' pools stacked, as the decode loop carries them
STACK = ((2,) + POOL[0], jnp.bfloat16)


def test_paged_decode_compiles(one_chip):
    _compile(functools.partial(ops.paged_decode_attention, interpret=False),
             one_chip, ((B, H, HD), jnp.bfloat16), POOL, POOL,
             ((B, NB), jnp.int32), ((B,), jnp.int32))


def test_paged_decode_on_stacked_pools_compiles(one_chip):
    _compile(functools.partial(ops.paged_decode_attention, interpret=False),
             one_chip, ((B, H, HD), jnp.bfloat16), STACK, STACK,
             ((B, NB), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))


def test_paged_kv_write_compiles(one_chip):
    compiled = _compile(functools.partial(ops.paged_kv_write,
                                          interpret=False),
                        one_chip, STACK, STACK, ((), jnp.int32),
                        ((B,), jnp.int32), ((B,), jnp.int32),
                        ((B, KV, HD), jnp.bfloat16),
                        ((B, KV, HD), jnp.bfloat16))
    # the pools are updated in place: both outputs alias their inputs
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# ops that only name a buffer: they move no pool data
_NAMING_OPS = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


def test_paged_decode_loop_keeps_the_pool_in_place(one_chip, monkeypatch):
    """The K-step paged decode loop on the kernel path, internlm2 widths at
    two layers and 64 slots: no op but a Pallas kernel outputs a buffer of
    a layer's or the stack's pool shape (no layout copy, slice, update
    slice, scatter or fusion of a pool), and the temps hold less than one
    layer's K pool.  The pool is large enough that the compiler keeps it
    in HBM rather than staging it through on-chip memory."""
    # kernels compile for the described chip, not the CPU's interpreter
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    layers, slots, blocks = 2, 64, 1024
    cfg = INTERNLM2.replace(n_layers=layers,
                            groups=(ScanGroup(("A",), layers),),
                            use_kernels=True)
    params, _ = api.abstract_params(cfg)
    caches = jax.eval_shape(lambda: tfm.init_paged_caches(cfg, blocks, BS))

    def loop(params, bt, caches, pos, last, active, remaining, rng):
        return tfm.decode_loop(params, cfg, caches, pos, last, active,
                               remaining, rng, k=8, max_len=NB * BS, bt=bt)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    row = lambda dtype: jax.ShapeDtypeStruct((slots,), dtype,
                                             sharding=one_chip)
    compiled = jax.jit(loop, donate_argnums=(2,)).lower(
        on_chip(params),
        jax.ShapeDtypeStruct((slots, NB), jnp.int32, sharding=one_chip),
        on_chip(caches), row(jnp.int32), row(jnp.int32), row(jnp.bool_),
        row(jnp.int32), on_chip(jax.random.PRNGKey(0))).compile()
    # a layer's pool (N, KV, bs, hd), alone or with a leading layer axis
    pool = re.compile(r"bf16\[(\d+,)?%d,%d,%d,%d\]" % ((blocks + 1, KV, BS,
                                                         HD)))
    movers = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(", line)
        if not m or not pool.search(m.group(2)):
            continue
        name, _, opcode = m.groups()
        if opcode in _NAMING_OPS or 'custom_call_target="tpu_custom_call"' \
                in line:
            continue
        movers.append(f"{name} ({opcode})")
    assert not movers, movers
    layer_pool_bytes = (blocks + 1) * KV * BS * HD * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool_bytes


@pytest.mark.parametrize("S", [32, 256])
def test_paged_extend_compiles(one_chip, S):
    _compile(functools.partial(ops.paged_extend_attention, interpret=False),
             one_chip, ((B, S, H, HD), jnp.bfloat16), POOL, POOL,
             ((B, NB), jnp.int32), ((B,), jnp.int32))


def test_flash_attention_compiles(one_chip):
    S = 2048
    _compile(functools.partial(ops.flash_attention, causal=True,
                               interpret=False),
             one_chip, ((1, S, H, HD), jnp.bfloat16),
             ((1, S, KV, HD), jnp.bfloat16), ((1, S, KV, HD), jnp.bfloat16))


def test_ssm_scan_compiles(one_chip):
    S, di, N = 2048, FALCON_MAMBA.d_inner, FALCON_MAMBA.ssm_state
    f32 = jnp.float32
    _compile(functools.partial(ops.ssm_scan, interpret=False), one_chip,
             ((1, S, di), f32), ((1, S, di), f32), ((1, S, N), f32),
             ((1, S, N), f32), ((di, N), f32), ((di,), f32))


def test_pair_score_compiles(one_chip):
    d, n, m = 1024, 1024, 512          # PipelineConfig().feat_dim
    f32 = jnp.float32
    link = {"W": jax.ShapeDtypeStruct((d, d), f32, sharding=one_chip),
            "w": jax.ShapeDtypeStruct((2 * d,), f32, sharding=one_chip),
            "bias": jax.ShapeDtypeStruct((), f32, sharding=one_chip)}
    claims = jax.ShapeDtypeStruct((n, d), f32, sharding=one_chip)
    evid = jax.ShapeDtypeStruct((m, d), f32, sharding=one_chip)
    compiled = jax.jit(functools.partial(ops.pair_score, interpret=False)
                       ).lower(link, claims, evid).compile()
    assert "tpu_custom_call" in compiled.as_text()
