"""The harness as a whole: BENCHMARK.json against the contract's limits,
discovery by name, the refusal to run without a TPU, and tiny runs of every
cell on the CPU, with the look for a chip stood in."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import CELLS, ROOT, run_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_to_the_contract():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    cells = [w["name"] for w in b["workloads"]]
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["name"] in {w["config"] for w in b["workloads"]}
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(cells)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m["workloads"]) <= set(cells) if "workloads" in m \
            else True
    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        # each cell the metric is read in reports the metric it moves
        for cell in m.get("workloads", cells):
            mv = e2e[m["moves"]]
            assert cell in mv.get("workloads", cells)
    for cell in cells:
        rep = [m for m in b["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert len(rep) >= 2
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])
    assert len(json.dumps(b)) < 64 * 1024


def test_discovery_finds_new_files_by_name(tmp_path):
    """A later change adds a configuration, a mix and a metric as new files
    and new entries; no file that is there is edited."""
    from bench.registry import Registry
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in tmp_path.rglob("*") if x.is_file())}
    b = bench_json()
    conf = json.load(open(tmp_path / "bench/configs/internlm2-1.8b.json"))
    conf["name"] = "new-model"
    (tmp_path / "bench/configs/new-model.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"arrivals": {"kind": "poisson", "rate_per_s": 1.0},
         "prompt": {"dist": "uniform", "min": 8, "max": 9},
         "output": {"dist": "uniform", "min": 2, "max": 3}}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["configs"].append({"name": "new-model", "source": "x",
                         "file": "bench/configs/new-model.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "new-cell", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "new_metric", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "kernels", "moves": "setup_s",
                           "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    reg = Registry(str(tmp_path), str(tmp_path / "bench"))
    cell = reg.workload("new-cell")
    assert reg.config(cell["config"])["name"] == "new-model"
    assert reg.traffic(cell["traffic"])["prompt"]["max"] == 9
    names = [m["name"] for m in reg.metrics_of("new-cell", "per_layer")]
    assert names == ["new_metric"]
    assert reg.reader("new_metric")(None) == 42.0
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data, p


@pytest.mark.parametrize("fault", ["none", "token_altered"])
def test_a_new_block_enters_as_new_files_and_its_cell_runs(tmp_path, fault,
                                                           monkeypatch):
    """A second architecture block (LayerNorm, GELU MLP, tied head), its
    configuration, mix and cell go into a copy of the benchmark as new files
    and entries only, and a run of the cell on the CPU is correct against
    that block's reference; with a token altered where it is sampled, it is
    not.  No file that was there changes."""
    from bench.registry import Registry
    from bench_tiny import MIX, TINY, run_tiny, tiny_config
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in tmp_path.rglob("*") if x.is_file())}
    shutil.copy(os.path.join(ROOT, "bench", "tests", "extra_blocks",
                             "layernorm_gelu_tied.py"),
                tmp_path / "bench/blocks/layernorm_gelu_tied.py")
    conf = tiny_config("internlm2-1.8b", dict(
        TINY, norm="layernorm", mlp="gelu_mlp", tie_word_embeddings=True))
    conf.update(name="tiny-lgt", block="layernorm_gelu_tied")
    (tmp_path / "bench/configs/tiny-lgt.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/tiny-mix.json").write_text(json.dumps(MIX))
    b = bench_json()
    b["configs"].append({"name": "tiny-lgt", "source": "x",
                         "file": "bench/configs/tiny-lgt.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "tiny-lgt-chat", "config": "tiny-lgt",
                           "traffic": "tiny-mix", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    reg = Registry(str(tmp_path), str(tmp_path / "bench"))
    assert reg.block(reg.config("tiny-lgt")).__file__ == str(
        tmp_path / "bench/blocks/layernorm_gelu_tied.py")
    if fault == "token_altered":
        from test_bench_faults import FAULTS
        FAULTS[fault](monkeypatch)
    res = run_tiny(reg, "tiny-lgt-chat", seed=2**31 + 5)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s"}
    gap = res["checks"]["max_gap"]
    if fault == "none":
        assert res["correct"] is True and gap["value"] < 1e-3
    else:
        assert res["correct"] is False and gap["value"] > gap["limit"]
    for p, data in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == data, p


def test_readers_get_only_what_the_engine_counted_while_traced(
        tiny_registry, monkeypatch):
    """``ctx.counters`` holds what the window's engine counted over the
    traced steps alone: a count made in a step outside the trace, before it
    or after it, does not reach it."""
    from bench import run, serving_adapter as sa, work
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    inside, steps, seen, last = [False], {"in": 0, "out": 0}, [], []
    start, stop, step = sa.start_trace, sa.stop_trace, sa.Server.step

    def traced_start(log_dir):
        start(log_dir)
        inside[0] = True

    def traced_stop():
        inside[0] = False
        stop()

    def counted_step(self):
        where = "in" if inside[0] else "out"
        self.metrics.counter("test." + where).inc()
        steps[where] += 1
        last[:] = [self]
        step(self)

    class Ctx(run.TraceCtx):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen.append(self.counters)

    monkeypatch.setattr(sa, "start_trace", traced_start)
    monkeypatch.setattr(sa, "stop_trace", traced_stop)
    monkeypatch.setattr(sa.Server, "step", counted_step)
    monkeypatch.setattr(run, "TraceCtx", Ctx)
    res = run_tiny(tiny_registry, CELLS[0], trace=1)
    assert res["correct"] is True
    (counts,) = seen
    window = last[0].metrics
    assert window.counter("test.out").value > 0     # steps outside the trace
    assert steps["in"] > 0 and counts["test.in"] == steps["in"]
    assert counts["test.out"] == 0
    assert 0 < counts["engine.steps"] < window.counter("engine.steps").value
    assert not any(k.endswith((".mean", ".p95")) for k in counts)


def test_run_without_a_tpu_exits_non_zero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "internlm2-chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_in_a_directory_with_only_the_benchmark_fails(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "internlm2-chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_of_every_cell_is_correct(tiny_registry, cell):
    res = run_tiny(tiny_registry, cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in tiny_registry.metrics_of(cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_tiny_run_is_correct_and_reads_only_its_metrics(
        tiny_registry, cell, monkeypatch):
    from bench import work
    # no peak table holds the CPU; a stand-in lets the readers run
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    res = run_tiny(tiny_registry, cell, trace=1)
    assert res["correct"] is True and res["failed"] == 0
    want = {m["name"] for m in tiny_registry.metrics_of(cell, "per_layer")}
    assert set(res["metrics"]) <= want
    assert res["device"]["window_s"] > 0
    assert all(len(v) <= 10 for v in res["breakdown"].values())
    assert list(res)[-1] == "checks"
