"""The engine's step spans in the profiler trace, the named device
programs, and the named scopes inside the model step.

A step span (``Tracer.span(..., step=True)``) enters the ``jax.profiler``
trace while it runs, on the device ops' clock, whether or not a tracer is
installed; with the profiler off it is the shared ``NULL_SPAN``.  Every
jitted engine program has a name of its own, so a trace's ``XLA Modules``
line says what ran, and the model step's ops carry the ``embed``,
``attention``, ``kv_write``, ``mlp`` and ``head`` scopes in their
metadata."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace as T
from repro.cluster import tracing
from repro.cluster.tracing import NULL_SPAN, Tracer
from repro.configs import get_config
from repro.configs.base import reduced
from repro.models import api
from repro.serving import Engine, ServeConfig
from repro.serving.engine import EngineFns

STEP_SPANS = ("engine.step", "engine.admit", "engine.prefill",
              "engine.decode_sync", "engine.kv_prep", "engine.host_sync",
              "engine.stream_emit", "engine.submit")


def _model(use_kernels=False):
    cfg = dataclasses.replace(reduced(get_config("internlm2-1.8b")),
                              use_kernels=use_kernels)
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _paged(**kw):
    return ServeConfig(max_len=64, slots=2, sync_every=4, paged=True,
                       block_size=8, **kw)


def _serve(eng, prompts, max_new=5):
    for p in prompts:
        eng.submit(p, max_new=max_new, on_tokens=lambda *a: None)
    eng.run_until_drained()


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A tiny paged engine, compiled first, then stepped under the
    profiler with no tracer installed: the loaded trace."""
    cfg, params = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 11, 7)]
    warm = Engine(params, cfg, _paged())
    _serve(warm, prompts)
    eng = Engine(params, cfg, _paged(), shared_fns=warm.fns)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    assert tracing.current_tracer() is tracing.NULL_TRACER
    tracing.start_profiling(log_dir)
    try:
        _serve(eng, prompts)
    finally:
        tracing.stop_profiling()
    return T.load(log_dir)


def _inside(child, parents):
    return any(p.start_ns <= child.start_ns and child.end_ns <= p.end_ns
               for p in parents)


def test_step_spans_reach_the_profiler_trace(profiled):
    names = {e.name for e in profiled.host}
    assert set(STEP_SPANS) <= names, set(STEP_SPANS) - names


@pytest.mark.parametrize("child,parent", [
    ("engine.admit", "engine.step"),
    ("engine.prefill", "engine.admit"),
    ("engine.decode_sync", "engine.step"),
    ("engine.kv_prep", "engine.decode_sync"),
    ("engine.host_sync", "engine.decode_sync"),
    ("engine.stream_emit", "engine.decode_sync"),
])
def test_step_spans_nest_in_their_parents(profiled, child, parent):
    parents = [e for e in profiled.host if e.name == parent]
    children = [e for e in profiled.host if e.name == child]
    assert children and all(_inside(c, parents) for c in children)


def test_submit_span_lies_outside_the_steps(profiled):
    steps = [e for e in profiled.host if e.name == "engine.step"]
    subs = [e for e in profiled.host if e.name == "engine.submit"]
    assert len(subs) == 3 and not any(_inside(s, steps) for s in subs)


def test_profiler_off_step_span_is_the_null_span():
    assert not tracing._PROFILING
    tr = tracing.current_tracer()
    assert tr.span("engine.step", step=True) is NULL_SPAN
    assert tr.span("engine.admit", parent=None, step=True,
                   bucket=8) is NULL_SPAN
    assert tr.spans() == []


def test_step_spans_record_only_under_a_sampled_parent():
    """A step span never roots a trace of its own; under a sampled
    request it records as that request's child."""
    tr = Tracer(enabled=True, sample_rate=1.0)
    assert tr.span("engine.step", step=True) is NULL_SPAN
    with tr.span("engine.request") as req:
        with tr.span("engine.decode_sync", parent=req, step=True) as d:
            assert d.recording
    assert [s["name"] for s in tr.spans()] == ["engine.decode_sync",
                                               "engine.request"]
    assert tr.spans()[0]["parent"] == tr.spans()[1]["span"]


def test_profiling_step_span_without_tracer_does_not_record(tmp_path):
    tracing.start_profiling(str(tmp_path))
    try:
        sp = tracing.current_tracer().span("engine.step", step=True)
        assert sp is not NULL_SPAN and not sp.recording and sp.ctx is None
        child = tracing.current_tracer().span("engine.admit", parent=sp,
                                              step=True)
        child.end()
        child.end()                      # a second end is a no-op
        sp.end()
        # a span that is not a step span stays out of the profiler
        assert tracing.current_tracer().span("engine.request") is NULL_SPAN
    finally:
        tracing.stop_profiling()
    names = {e.name for e in T.load(str(tmp_path)).host}
    assert {"engine.step", "engine.admit"} <= names
    assert "engine.request" not in names


# ----------------------------------------------------------------------
def _programs(fns):
    """Every jitted program of an ``EngineFns`` bundle, built."""
    progs = {"decode": fns.decode, "decode_loop": fns.decode_loop,
             "paged_decode_loop": fns.paged_decode_loop,
             "gather_virt": fns.gather_virt, "cow": fns.cow,
             "kv_export": fns.kv_export, "kv_import": fns.kv_import,
             "admit_fn": fns.admit_fn(8, 1),
             "paged_admit_fn": fns.paged_admit_fn(8, 1),
             "prefill_fn": fns.prefill_fn(5), "flush_fn": fns.flush_fn(1)}
    if fns.spec:
        progs["spec_decode_loop"] = fns.spec_decode_loop
    return {k: getattr(f, "__wrapped__", f).__name__
            for k, f in progs.items()}


def test_engine_programs_have_distinct_names():
    cfg, _ = _model(use_kernels=True)
    kernel = _programs(EngineFns(cfg, _paged()))
    assert kernel["paged_decode_loop"] == "paged_loop_fn"
    cfg_j, _ = _model()
    spec = _programs(EngineFns(cfg_j, _paged(speculative=True)))
    assert spec["paged_decode_loop"] == "paged_virt_loop_fn"
    for names in (kernel, spec):
        assert len(set(names.values())) == len(names), names
        assert not {"fn", "<lambda>"} & set(names.values()), names
    assert kernel["paged_admit_fn"] == "paged_admit"
    assert kernel["admit_fn"] == "dense_admit"
    assert kernel["prefill_fn"] == "prefill_exact"


def _scopes(text):
    """The named scopes on the op locations of lowered (debug) text."""
    return {part for name in re.findall(r'loc\("([^"]*)"', text)
            for part in name.split("/")[:-1]}


@pytest.fixture(scope="module")
def kernel_engine():
    cfg, params = _model(use_kernels=True)
    return Engine(params, cfg, _paged())


@pytest.mark.parametrize("scope", ["embed", "attention", "kv_write", "mlp",
                                   "head"])
def test_lowered_decode_loop_carries_the_scopes(kernel_engine, scope):
    e = kernel_engine
    bt = jnp.asarray(e._bt[:, :1])
    text = e.fns.paged_decode_loop.lower(
        e.params, bt, e.caches, e._pos, e._last, e._active, e._remaining,
        e._rng).as_text(debug_info=True)
    assert "module @jit_paged_loop_fn" in text
    assert scope in _scopes(text)


@pytest.mark.parametrize("scope", ["embed", "attention", "kv_write", "mlp",
                                   "head"])
def test_lowered_paged_admit_carries_the_scopes(kernel_engine, scope):
    e = kernel_engine
    n, bucket = 1, 8
    text = e.fns.paged_admit_fn(bucket, n).lower(
        e.params, jnp.zeros((n, bucket), jnp.int32),
        jnp.zeros((4, n), jnp.int32), jnp.zeros((n, e.nb_max), jnp.int32),
        None, e.caches, None, e._pos, e._last, e._active, e._remaining,
        e._rng).as_text(debug_info=True)
    assert "module @jit_paged_admit" in text
    assert scope in _scopes(text)
