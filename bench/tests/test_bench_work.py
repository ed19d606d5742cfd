"""Work counts of the ``dense_gqa`` block (bench/blocks/dense_gqa.py) and
peaks (bench/work.py, bench/peaks.json) on hand-computed cases, and the
readers that turn them into roofline shares."""
from __future__ import annotations

import importlib.util
import os
import types

import pytest

from bench import work as W
from bench.blocks import dense_gqa as G

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 2 layers, d 8, 2 q heads over 1 kv head of 4, d_ff 16, vocab 10
S = G.Spec(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
           vocab=10, eps=1e-5, rope_theta=1e4)
PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m", os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_layer_matmul_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8; swiglu 3 x 8x16
    assert G.layer_matmul_params(S) == 64 + 32 + 32 + 64 + 3 * 128


def test_decode_flops_by_hand():
    mm = 2 * (2 * 576 + 8 * 10)                 # layers + head, 2 per MAC
    # attention: 4 x layers x heads x head_dim x context
    assert G.decode_flops(S, 5, {}) == mm + 4 * 2 * 2 * 4 * 5
    assert G.decode_flops(S, 6, {}) - G.decode_flops(S, 5, {}) == \
        4 * 2 * 2 * 4


def test_kernel_bytes_by_hand():
    f, b = G.decode_attn(S, 10, {})
    # per layer: K and V of 10 positions x 1 kv head x 4 x 2 B, q and o
    assert b == 2 * (2 * 10 * 1 * 4 * 2 + 2 * 2 * 4 * 2)
    assert f == 4 * 2 * 2 * 4 * 10


def test_least_time_is_the_larger_bound():
    assert W.least_seconds(1000, 10, PEAK) == 10.0       # compute-bound
    assert W.least_seconds(10, 1000, PEAK) == 100.0      # memory-bound


def test_peaks_by_device_kind_and_unknown_kind_is_an_error():
    p = W.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        W.peaks("cpu")


def fake_ctx(decode_ctx=(), kernel_s=0.0, module_s=0.0):
    return types.SimpleNamespace(
        block=G, spec=S, counters={}, peak=PEAK, trace=object(),
        decode_ctx=list(decode_ctx),
        kernel_seconds=lambda pat: kernel_s,
        module_seconds=lambda pat: module_s)


def test_roofline_share_counts_required_work_not_padding():
    # a token decoded at context 7 from a block table 16 blocks wide: the
    # client records 7 and the share counts 7 positions, whatever the
    # kernel read
    dec = reader("paged_decode_roofline")
    least = W.least_seconds(*G.decode_attn(S, 7, {}), PEAK)
    assert dec(fake_ctx(decode_ctx=[7], kernel_s=2 * least)) == \
        pytest.approx(50.0)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    for name in ("paged_decode_roofline", "mfu.decode_step"):
        assert reader(name)(fake_ctx(kernel_s=1.0, module_s=1.0)) is None
    # work but no kernel events: no share, never 0
    assert reader("paged_decode_roofline")(fake_ctx(decode_ctx=[3])) is None
    assert reader("mfu.decode_step")(fake_ctx(decode_ctx=[3])) is None
    none = types.SimpleNamespace(trace=None, decode_ctx=[1])
    assert reader("device_idle.online")(none) is None


def test_mfu_reader_by_hand():
    f = G.decode_flops(S, 4, {}) + G.decode_flops(S, 5, {})
    got = reader("mfu.decode_step")(fake_ctx(decode_ctx=[4, 5],
                                             module_s=f / 100.0 * 2))
    assert got == pytest.approx(50.0)
