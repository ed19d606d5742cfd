"""The only module of the benchmark that imports the program under test.

It builds the serving engine (``repro.serving.Engine``) on its paged KV
pool with the Pallas kernels, as a deployment on one chip would run it, and
gives the harness a small interface: submit, step, the engine's queue-wait
samples, and the program's profiler hooks.

What it sets, and what it leaves to the program:

* ``ServeConfig(paged=True)`` and ``ArchConfig(use_kernels=True)``, each only
  while the program still has the field, so a program that makes one path
  the only path needs no edit here.
* ``slots`` and ``max_len`` from the configuration and the mix, and
  ``kv_blocks`` as the configuration's pool token budget over the program's
  own block size.
* Everything else at the program's defaults: ``block_size``, ``sync_every``,
  ``prefill_bucketing``, ``min_bucket``, ``prefix_cache``.  A change of a
  default is measured on its new value.

The architecture is built from the configuration file's numbers through
its block's ``program_fields`` and ``program_params``
(``bench/blocks/``), not from the program's own preset, so the program runs
as the configuration states.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Callable, Dict, List

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.cluster import tracing as _tracing  # noqa: E402
from repro.cluster.metrics import MetricsRegistry, is_gauge_key  # noqa: E402
from repro.configs.base import ArchConfig, ScanGroup  # noqa: E402
from repro.serving import Engine, ServeConfig  # noqa: E402


def _fields(cls) -> set:
    return {f.name for f in dataclasses.fields(cls)}


def block_size() -> int:
    """The program's default KV block size."""
    return ServeConfig().block_size


def arch(fields: dict) -> ArchConfig:
    """The program's architecture config from a block's ``program_fields``,
    whose ``groups`` are ``(pattern, repeats)`` pairs."""
    kw = dict(fields, groups=tuple(ScanGroup(tuple(p), int(r))
                                   for p, r in fields["groups"]))
    if "use_kernels" in _fields(ArchConfig):
        kw["use_kernels"] = True
    return ArchConfig(**kw)


TokenCallback = Callable[[int, List[int], bool], None]


class Server:
    """One engine on the paged path.  ``fresh()`` starts a new engine that
    shares the compiled programs of this one (an empty pool, new counters):
    set-up warms a first engine, and the measured window runs a fresh one."""

    def __init__(self, params, cfg: ArchConfig, slots: int, max_len: int,
                 pool_tokens: int, shared=None):
        self.cfg = cfg
        bs = block_size()
        max_len = -(-max_len // bs) * bs
        kw = dict(max_len=max_len, slots=slots,
                  kv_blocks=pool_tokens // bs)
        if "paged" in _fields(ServeConfig):
            kw["paged"] = True
        self.scfg = ServeConfig(**kw)
        self.metrics = MetricsRegistry()
        self.engine = Engine(params, cfg, self.scfg, metrics=self.metrics,
                             shared_fns=shared)
        if not getattr(self.engine, "paged", True):
            raise RuntimeError(f"{cfg.name}: the engine fell back from the "
                               f"paged KV pool")
        self._rid: Dict[int, object] = {}

    def fresh(self) -> "Server":
        params, fns = self.engine.params, self.engine.fns
        self.engine = None                  # free this pool first
        return Server(params, self.cfg, self.scfg.slots, self.scfg.max_len,
                      self.scfg.kv_blocks * block_size(), shared=fns)

    @property
    def pool_blocks(self) -> int:
        return self.scfg.kv_blocks

    @property
    def max_len(self) -> int:
        return self.scfg.max_len

    def bucket(self, plen: int) -> int:
        """The program's prefill bucket for a prompt of ``plen`` tokens."""
        return self.engine.fns.bucket(plen)

    def submit(self, idx: int, prompt: np.ndarray, n_out: int,
               on_tokens: TokenCallback) -> None:
        """Queue a request for ``n_out`` served tokens (the first sampled
        by prefill).  ``on_tokens(idx, tokens, done)`` is called at each
        host sync that delivers some of them."""
        def cb(req, toks, done):
            on_tokens(idx, toks, done)
        req = self.engine.submit(prompt, max_new=n_out - 1, on_tokens=cb)
        self._rid[idx] = req

    def result(self, idx: int):
        """(tokens served, finish reason) of a submitted request."""
        r = self._rid[idx]
        return list(r.out_tokens), r.finish_reason

    def queued(self) -> int:
        return len(self.engine.queue)

    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(r is not None for r in e.active)

    def step(self) -> None:
        """One engine iteration: admit what fits, then one decode sync.
        Returns once the sync's tokens are on the host."""
        self.engine.step()

    def queue_wait_p95_s(self):
        """95th percentile of the engine's own submit-to-admit samples,
        and how many were observed.  The program keeps them in a bounded
        reservoir, so the percentile is exact while the count is under its
        cap (4,096)."""
        h = self.metrics.histogram("engine.queue_wait_s")
        return h.percentile(95), h.count

    def counters(self) -> dict:
        """The engine's monotone counts as a flat dict of numbers: its
        counters by name and each histogram's ``<name>.count`` and bucket
        counts, without levels (gauges, means, percentiles), as the
        program's ``is_gauge_key`` tells them apart.  The difference of two
        snapshots is what the engine counted between them."""
        return {k: v for k, v in self.metrics.snapshot().items()
                if not is_gauge_key(k)}

    def close(self) -> None:
        self.engine = None


def start_trace(log_dir: str) -> None:
    """Start the profiler through the program's hook, which also arms its
    step spans (``engine.step``, ``engine.admit``, ``engine.prefill``,
    ``engine.decode_sync`` and the rest) in the same trace."""
    _tracing.start_profiling(log_dir)


def stop_trace() -> None:
    _tracing.stop_profiling()
