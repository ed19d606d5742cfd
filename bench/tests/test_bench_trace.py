"""The trace reduction (bench/trace.py) on hand-built event lists and on a
small trace recorded here."""
from __future__ import annotations

import pytest

from bench import trace as T


def ev(name, start, dur, **stats):
    return T.Event(name, float(start), float(dur), tuple(stats.items()))


def test_union_merges_overlaps_and_clips_to_window():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 90, 30)]
    assert T.merged(evs, 0, 100) == [(0, 15), (30, 35), (90, 100)]
    assert T.busy_ns(evs, 0, 100) == 15 + 5 + 10
    # an event nested in another adds nothing
    assert T.busy_ns([ev("m", 0, 50), ev("op", 10, 5)], 0, 100) == 50


@pytest.mark.parametrize("events,lo,hi,want", [
    ([], 0, 100, 1.0),                                  # nothing ran
    ([ev("a", 0, 100)], 0, 100, 0.0),                   # always busy
    ([ev("a", 0, 40), ev("b", 20, 40)], 0, 100, 0.4),   # overlap counted once
    ([ev("a", -50, 100)], 0, 100, 0.5),                 # clipped at the start
])
def test_idle_share(events, lo, hi, want):
    assert T.idle_share(events, lo, hi) == pytest.approx(want)


def test_empty_window_has_no_idle_share():
    assert T.idle_share([ev("a", 0, 10)], 5, 5) is None
    assert T.busy_ns([], 0, 0) == 0


def test_matching_is_by_op_name_not_by_operand():
    evs = [ev("%paged_decode_attention.3 = bf16[8] custom-call(s32[4] %a)",
              0, 10),
           ev("%fusion.1 = bf16[8] fusion(bf16[8] %paged_decode_attention.3)",
              10, 5),
           ev("%paged_extend_attention = bf16[8] custom-call()", 20, 10),
           ev("jit_paged_loop_fn(1234)", 0, 40)]
    dec = T.matching(evs, r"paged_decode_attention(\.\d+)?$")
    assert [e.op for e in dec] == ["paged_decode_attention.3"]
    assert T.summed_ns(dec, 0, 100) == 10
    assert T.summed_ns(T.matching(evs, r"paged_"), 5, 25) == 5 + 5
    assert [e.op for e in T.matching(evs, r"jit_paged_loop_fn\(")] == \
        ["jit_paged_loop_fn(1234)"]


def test_top_ops_sums_by_name_in_seconds():
    evs = [ev("%x = f32[2] add()", 0, 2e9), ev("y", 0, 1e9),
           ev("%x = f32[2] add()", 3e9, 1e9)]
    assert T.top_ops(evs, 0, 10e9) == [("x", 3.0), ("y", 1.0)]
    assert T.top_ops(evs, 0, 10e9, n=1) == [("x", 3.0)]


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    dev = [ev("op", 0, 10), ev("op", 40, 10), ev("op", 90, 10)]
    host = [ev("decode_loop", 0, 100), ev("prefill", 55, 30),
            ev("$python frame", 10, 30), ev(T.WINDOW_SPAN, 0, 100)]
    gaps = dict(T.idle_gaps(dev, host, 0, 100))
    # 10..40 lies in decode_loop only (the Python frame is ignored);
    # 50..90 has its middle (70) in prefill
    assert gaps == {"decode_loop": pytest.approx(30e-9),
                    "prefill": pytest.approx(40e-9)}
    assert dict(T.idle_gaps([], [], 0, 10)) == {"host": pytest.approx(1e-8)}


def test_window_is_the_bench_span_else_the_device_extent():
    tr = T.Trace({"/device:TPU:0": {T.OPS_LINE: [ev("a", 5, 10)]}},
                 [ev(T.WINDOW_SPAN, 2, 50), ev("decode_loop", 3, 4)])
    assert tr.window() == (2, 52)
    tr.host = []
    assert tr.window() == (5, 15)
    assert T.Trace({}, []).window() == (0.0, 0.0)


def test_load_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("decode_loop"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    names = {e.name for e in tr.host}
    assert {T.WINDOW_SPAN, "decode_loop"} <= names
    lo, hi = tr.window()
    span = next(e for e in tr.host if e.name == "decode_loop")
    assert lo <= span.start_ns and span.end_ns <= hi
    # the CPU backend has no device plane: no busy time, no ops to name
    assert all(not p.startswith("/device:TPU") for p in tr.devices)


def test_load_without_a_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        T.load(str(tmp_path))


def test_trace_context_reads_busy_idle_kernels_and_programs():
    """What the per-layer readers get from a trace: overlapping ops count
    once in the busy time, a kernel and a program by their names."""
    from bench.run import TraceCtx
    dev = {T.OPS_LINE: [ev("%a = f32[] add()", 0, 40),
                        ev("%b = f32[] mul()", 30, 20),
                        ev("%paged_decode_attention.1 = bf16[] custom-call()",
                           80, 10)],
           T.MODULES_LINE: [ev("jit_paged_loop_fn(1)", 0, 60)]}
    tr = T.Trace({"/device:TPU:0": dev}, [ev(T.WINDOW_SPAN, 0, 100)])
    ctx = TraceCtx(tr)
    assert ctx.window_s == pytest.approx(100e-9)
    assert ctx.busy_s() == pytest.approx(60e-9)
    assert ctx.idle_pct() == pytest.approx(40.0)
    assert ctx.kernel_seconds(r"paged_decode_attention(\.\d+)?$") == \
        pytest.approx(10e-9)
    assert ctx.module_seconds(r"jit_paged_loop_fn\(") == pytest.approx(60e-9)
    assert ctx.breakdown()["idle_gaps"] == [["host", pytest.approx(40e-9)]]
