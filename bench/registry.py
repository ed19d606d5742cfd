"""Discovery by name: the harness finds every configuration, architecture
block, traffic mix and per-layer metric from the names in
``BENCHMARK.json`` and in the configuration files.

* a configuration is the JSON file its entry names under ``file``;
* a block ``<name>``, which a configuration file names under ``block``, is
  ``bench/blocks/<name>.py``: the module that holds everything the
  benchmark knows of that architecture (its interface is documented in
  ``bench/blocks/__init__.py``).  There is no default block;
* a traffic mix ``<name>`` is ``bench/traffic/<name>.json``;
* a per-layer metric ``<name>`` is ``bench/metrics/<name>.py``, a module
  with ``read(ctx)`` that returns a number, or None where it finds nothing
  to read.

So a later change adds an architecture, a configuration, a mix or a metric
as new files and new entries, and edits no file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Callable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str, path: str) -> ModuleType:
    """The module at ``path``, under ``name`` in ``sys.modules`` (which a
    dataclass needs while its module runs)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Registry:
    def __init__(self, root: str = ROOT, bench_dir: str = HERE):
        self.root, self.dir = root, bench_dir
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def block(self, conf: dict) -> ModuleType:
        """The block module the configuration ``conf`` names."""
        name = conf.get("block")
        if not name:
            raise ValueError(f"{conf['name']}: the configuration names no "
                             f"block")
        path = os.path.join(self.dir, "blocks", name + ".py")
        if not os.path.isfile(path):
            raise ValueError(f"{conf['name']}: no module for block {name!r} "
                             f"(looked for {path})")
        return _load("bench_block_" + name.replace(".", "_"), path)

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def metrics_of(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.dir, "metrics", metric + ".py")
        return _load("bench_metric_" + metric.replace(".", "_"), path).read
