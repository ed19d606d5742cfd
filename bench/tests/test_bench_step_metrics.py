"""The readers of the program's step spans (``admit_share.online``,
``decode_sync_host_ms.online``) on hand-built event lists."""
from __future__ import annotations

import pytest

from bench import trace as T
from bench.registry import Registry
from bench.run import TraceCtx

NO_TRACE = TraceCtx(None)


def ev(name, start, dur):
    return T.Event(name, float(start), float(dur))


def reader(name):
    return Registry().reader(name)


def ctx(host, window=(0, 1000)):
    """What a reader is given for a trace of these host spans, whose
    ``bench_window`` spans ``window``."""
    lo, hi = window
    tr = T.Trace({}, [ev(T.WINDOW_SPAN, lo, hi - lo)] + list(host))
    return TraceCtx(tr)


# ----------------------------------------------------------------------
def test_admit_share_is_the_union_of_admit_spans_over_the_window():
    host = [ev("engine.step", 0, 500), ev("engine.admit", 100, 200),
            ev("engine.admit", 250, 100),             # overlaps the first
            ev("engine.prefill", 120, 150),
            ev("engine.admit", 900, 300)]             # cut at the window
    assert reader("admit_share.online")(ctx(host)) == pytest.approx(
        100.0 * (250 + 100) / 1000)


def test_admit_share_reads_zero_with_steps_and_no_admit():
    assert reader("admit_share.online")(
        ctx([ev("engine.step", 0, 100)])) == 0.0


def test_admit_share_is_none_without_step_spans():
    read = reader("admit_share.online")
    assert read(ctx([ev("prefill", 0, 100)])) is None
    assert read(NO_TRACE) is None


def test_decode_sync_host_time_leaves_out_the_wait_on_the_device():
    host = [ev("engine.decode_sync", 100, 40e6),
            ev("engine.kv_prep", 100, 2e6),
            ev("engine.host_sync", 100 + 3e6, 35e6),
            ev("engine.decode_sync", 50e6, 20e6),
            ev("engine.host_sync", 52e6, 16e6),
            # outside the window: not read
            ev("engine.decode_sync", 900e6, 200e6),
            ev("engine.host_sync", 901e6, 1e6)]
    got = reader("decode_sync_host_ms.online")(ctx(host, window=(0, 1e9)))
    assert got == pytest.approx(((40 - 35) + (20 - 16)) / 2)


def test_decode_sync_host_time_is_none_without_syncs():
    read = reader("decode_sync_host_ms.online")
    assert read(ctx([ev("engine.step", 0, 100)])) is None
    assert read(NO_TRACE) is None
