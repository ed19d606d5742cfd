"""Helpers of the benchmark's CPU tests: a registry of tiny cells, built
from the real configuration files with their widths cut, in a temporary
copy of the benchmark's layout."""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "dtype": "float32"}
MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 4.0},
       "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                  "min": 8, "max": 24},
       "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                  "min": 4, "max": 12},
       "block": 8}


def tiny_config(name: str, model: dict) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        conf = json.load(f)
    conf["model"].update(model)
    conf["deployment"].update(slots=4, kv_pool_tokens=1024)
    return conf


def build_tiny_registry(root: str) -> str:
    """A copy of ``bench/`` and ``BENCHMARK.json`` under ``root`` whose
    cells run tiny widths on the CPU; returns the copy's bench directory."""
    bench = os.path.join(root, "bench")
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        with open(os.path.join(root, c["file"]), "w") as f:
            json.dump(tiny_config(c["name"], TINY), f)
    for w in spec["workloads"]:
        with open(os.path.join(bench, "traffic", w["traffic"] + ".json"),
                  "w") as f:
            json.dump(MIX, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return bench


@pytest.fixture(scope="module")
def tiny_registry(tmp_path_factory):
    from bench.registry import Registry
    root = str(tmp_path_factory.mktemp("bench_tiny"))
    return Registry(root, build_tiny_registry(root))


CPU_DEVICE = {"platform": "cpu", "kind": "cpu (test stand-in)", "count": 1}


def run_tiny(reg, cell, seed=4, trace=0):
    from bench import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       "2", "--trace", str(trace)], reg=reg,
                      device=CPU_DEVICE)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
