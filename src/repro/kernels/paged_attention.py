"""Pallas TPU paged attention: decode (one query per sequence) and extend
(a suffix of queries per sequence) over a block-pooled KV cache, gathered
*inside* the kernel through a per-sequence block table, and the decode
step's in-place K/V write.

K/V live in block pools, ``(num_blocks, KV, block_size, hd)`` for one
layer, stacked over layers as ``(R, num_blocks, KV, block_size, hd)``, and
each sequence names its blocks in ``block_tables (B, nb)``.  A block's last
two axes are ``(block_size, hd)``, which is the tiling the TPU compiler
accepts for a per-block DMA.  The block table and the valid lengths (and,
for the decode kernels, the layer) ride in as *scalar prefetch* operands,
so the grid's last (sequential) dimension walks a sequence's blocks and the
BlockSpec ``index_map`` resolves the physical pool row **before** the
kernel body runs — the DMA engine fetches exactly the blocks the sequence
owns, never a dense ``max_len`` stripe.  Per-block ``(m, l, acc)`` partials
accumulate across the sequential grid dimension in VMEM scratch (the
standard online-softmax pattern), and blocks past the sequence's length are
skipped with ``@pl.when``.

The decode kernels take the stacked pools and index the layer themselves,
so a layer loop can carry the pools and update them in place: the write
kernel rewrites each touched block through ``input_output_aliases``, and
no op outside the two kernels touches a pool-shaped buffer.

The oracles are ``ref.paged_decode_attention_ref`` and
``ref.paged_extend_attention_ref``; the write's is ``pool.at[...].set``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _paged_decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, bs: int,
                         scale: float):
    """Grid (B, nb); the block dimension is sequential per sequence.

    One step fetches a whole pool block, every kv head of it, and runs the
    online-softmax update for each head's query group.

    q_ref: (1, KV, G, hd); k_ref/v_ref: (1, 1, KV, bs, hd) — the pool
    block named by bt[b, j] in layer ``layer_ref[0]``; o_ref: (1, KV, G,
    hd); m/l: (KV, G, 128) (column 0 used), acc: (KV, G, hd) — VMEM
    scratch carried across j.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    length = len_ref[b]
    n_kv = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs < length)
    def _block():
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)               # (G, hd)
            k = k_ref[0, 0, h].astype(jnp.float32)            # (bs, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(pos < length, s * scale, NEG_INF)   # (G, bs)
            m_prev = m_ref[h][:, :1]                          # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = jnp.broadcast_to(
                l_ref[h][:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
                l_ref.shape[1:])
            v = v_ref[0, 0, h].astype(jnp.float32)            # (bs, hd)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_bkgd(q, k_pool, v_pool, block_tables, lengths,
                                layer, *, interpret: bool = False):
    """q: (B, KV, G, hd); k_pool/v_pool: (R, num_blocks, KV, bs, hd)
    stacked over layers; block_tables: (B, nb) int32; lengths: (B,) int32;
    layer: (1,) int32, the layer of the stack to attend over
    -> (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    bs = k_pool.shape[3]
    nb = block_tables.shape[1]
    kernel = functools.partial(_paged_decode_kernel, bs=bs,
                               scale=1.0 / math.sqrt(hd))
    kv_spec = pl.BlockSpec((1, 1, KV, bs, hd),
                           lambda b, j, bt, ln, ly: (ly[0], bt[b, j], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, lengths, layer
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd),
                         lambda b, j, bt, ln, ly: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, j, bt, ln, ly: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 128), jnp.float32),   # running max (col 0)
            pltpu.VMEM((KV, G, 128), jnp.float32),   # running sum (col 0)
            pltpu.VMEM((KV, G, hd), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      layer.astype(jnp.int32), q, k_pool, v_pool)


def _paged_kv_write_kernel(layer_ref, phys_ref, off_ref, k_row_ref,
                           v_row_ref, k_blk_ref, v_blk_ref, k_out_ref,
                           v_out_ref):
    """Grid (B,): step b rewrites pool block (layer, phys[b]) of both pools
    with position ``off[b]`` replaced by row b.

    k_row_ref/v_row_ref: (1, KV, hd); k_blk_ref/v_blk_ref and the aliased
    k_out_ref/v_out_ref: (1, 1, KV, bs, hd).  A single row is narrower than
    the (bs, hd) tile a DMA may address, so the whole block is read,
    selected into and written back."""
    b = pl.program_id(0)
    hit = jax.lax.broadcasted_iota(jnp.int32, k_blk_ref.shape[2:], 1) \
        == off_ref[b]                                         # (KV, bs, hd)
    for row_ref, blk_ref, out_ref in ((k_row_ref, k_blk_ref, k_out_ref),
                                      (v_row_ref, v_blk_ref, v_out_ref)):
        row = row_ref[0].astype(jnp.float32)[:, None, :]      # (KV, 1, hd)
        blk = blk_ref[0, 0].astype(jnp.float32)
        out_ref[0, 0] = jnp.where(hit, row, blk).astype(out_ref.dtype)


def paged_kv_write_bkgd(k_pool, v_pool, layer, phys, off, k_rows, v_rows, *,
                        interpret: bool = False):
    """k_pool/v_pool: (R, num_blocks, KV, bs, hd), updated in place;
    layer: (1,) int32; phys/off: (B,) int32 block and position of each new
    row; k_rows/v_rows: (B, KV, hd) -> (k_pool, v_pool).

    The grid is sequential, and a step's block is fetched while the step
    before it runs: two rows that name one block in the same call can lose
    one of their writes.  Callers give each real block to at most one
    row per call; rows that name the null block write junk there, as the
    null block is meant to absorb."""
    B = phys.shape[0]
    _, _, KV, bs, hd = k_pool.shape
    row_spec = pl.BlockSpec((1, KV, hd), lambda b, ly, ph, of: (b, 0, 0))
    blk_spec = pl.BlockSpec((1, 1, KV, bs, hd),
                            lambda b, ly, ph, of: (ly[0], ph[b], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # layer, phys, off
        grid=(B,),
        in_specs=[row_spec, row_spec, blk_spec, blk_spec],
        out_specs=[blk_spec, blk_spec],
    )
    return pl.pallas_call(
        _paged_kv_write_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        # operands count the scalar-prefetch ones: the pools are 5 and 6
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer.astype(jnp.int32), phys.astype(jnp.int32),
      off.astype(jnp.int32), k_rows.astype(k_pool.dtype),
      v_rows.astype(v_pool.dtype), k_pool, v_pool)


def _paged_extend_kernel(bt_ref, pos0_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, bs: int, S: int, G: int,
                         scale: float):
    """Grid (B, KV, nb); the last dimension is sequential per (b, h).

    The extend sibling of :func:`_paged_decode_kernel`: ``S`` suffix
    queries per sequence (absolute positions ``pos0[b] + s``) run online
    softmax over the prefix blocks *and* the in-flight suffix (already
    scattered into the pool), masked causally over absolute positions —
    key position p is visible to query s iff ``p <= pos0[b] + s``, the
    dense oracle's mask.  Rows are the S*G (query, group-head) pairs of
    one kv head, query-major, carried across j.

    q_ref: (1, 1, S*G, hd); k_ref/v_ref: (1, 1, bs, hd) — head h of pool
    block bt[b, j]; o_ref: (1, 1, S*G, hd).
    """
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    p0 = pos0_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs < p0 + S)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)                   # (S*G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bs, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        key_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (S * G, bs), 1)
        q_pos = p0 + jax.lax.broadcasted_iota(
            jnp.int32, (S * G, bs), 0) // G
        s = jnp.where(key_pos <= q_pos, s, NEG_INF)           # (S*G, bs)
        m_prev = m_ref[:, :1]                                 # (S*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        v = v_ref[0, 0].astype(jnp.float32)                   # (bs, hd)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def paged_extend_attention_bkgd(q, k_pool, v_pool, block_tables, pos0, *,
                                G: int, interpret: bool = False):
    """q: (B, KV, S*G, hd) suffix queries, rows query-major (row r is
    query r // G of group head r % G); k_pool/v_pool: (num_blocks, KV, bs,
    hd); block_tables: (B, nb) int32; pos0: (B,) int32 absolute position
    of each row's first query -> (B, KV, S*G, hd).  Suffix K/V must already
    be scattered into the pool (the kernel reads them back through the
    table like any prefix block — one code path, no separate in-flight
    operand)."""
    B, KV, SG, hd = q.shape
    S = SG // G
    bs = k_pool.shape[2]
    nb = block_tables.shape[1]
    kernel = functools.partial(_paged_extend_kernel, bs=bs, S=S, G=G,
                               scale=1.0 / math.sqrt(hd))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_tables, pos0
        grid=(B, KV, nb),
        in_specs=[
            pl.BlockSpec((1, 1, SG, hd),
                         lambda b, h, j, bt, p0: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda b, h, j, bt, p0: (bt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda b, h, j, bt, p0: (bt[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, SG, hd),
                               lambda b, h, j, bt, p0: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((SG, 128), jnp.float32),      # running max (col 0)
            pltpu.VMEM((SG, 128), jnp.float32),      # running sum (col 0)
            pltpu.VMEM((SG, hd), jnp.float32),       # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, SG, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos0.astype(jnp.int32),
      q, k_pool, v_pool)
