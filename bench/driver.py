"""The client side of a run: warm-up of every shape the cell's traffic
uses, then the measured window, open loop.

One thread drives both the arrivals and the engine.  Each loop turn hands
the requests that have come due to the engine and then runs one engine
step (an admit and one host sync of decoding); a step returns once its
tokens are on the host.  A request is timed from when it was due, so a
long step delays the arrivals behind it, and that delay counts in their
latency; how late the hand-off ran is reported apart.

Two limits hold requests on the client side, as a front end would:

* at most ``HANDOFF`` requests wait in the engine's own queue, the largest
  prefill batch the warm-up compiles;
* a request is handed over only while the pool could hold every request in
  flight at its full length, so that no request is cut short for want of
  KV blocks.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import traffic as traffic_mod

HANDOFF = 8


@dataclasses.dataclass
class Record:
    due: float = 0.0            # absolute perf_counter time it was due
    handed: float = 0.0
    first: float = 0.0          # first token at the client
    last: float = 0.0
    n: int = 0                  # tokens delivered
    done: bool = False


def blocks_for(plen: int, n_out: int, bs: int) -> int:
    return -(-(plen + n_out) // bs) + 1


class CompileCounter:
    """Counts JAX traces and compiles (also persistent-cache loads) while
    ``armed``; nothing should trace or compile inside the window."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENTS[1]:
            self.compile_s += duration
        if self.armed and event in self.EVENTS:
            self.count += 1


# ----------------------------------------------------------------------
def warm_shapes(server, mix: dict) -> Tuple[List[int], List[int]]:
    """The prefill buckets and the decode block-table widths (in blocks)
    that this mix's requests can reach."""
    bs = server.scfg.block_size
    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    buckets = sorted({server.bucket(n) for n in range(lo, hi + 1)})
    nb_max = server.max_len // bs
    widths = {_width(p, bs, nb_max) for p in range(lo + 1, server.max_len + 1)}
    return buckets, sorted(widths)


def _width(pos: int, bs: int, nb_max: int) -> int:
    """The power-of-two block-table width (in blocks, at most ``nb_max``)
    that holds ``pos`` positions."""
    need = -(-pos // bs)
    w = 1
    while w < need:
        w *= 2
    return min(w, nb_max)


def warm_up(server, mix: dict, rng: np.random.Generator, vocab: int) -> int:
    """Run every (bucket, batch) prefill program and every decode width
    the mix can reach once, through the engine itself.  Returns the number
    of warm-up requests served."""
    bs = server.scfg.block_size
    nb_max = server.max_len // bs
    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    buckets, widths = warm_shapes(server, mix)
    jobs: List[List[Tuple[int, int]]] = []       # batches of (plen, n_out)
    for b in buckets:
        plen = min(max(b, lo), hi)
        while server.bucket(plen) != b:          # a length in this bucket
            plen -= 1
        n = 1
        while n <= HANDOFF:
            jobs.append([(plen, 2)] * n)
            n *= 2
    for w in widths:
        fits = [p for p in range(lo, hi + 1) if _width(p + 1, bs, nb_max) == w]
        if fits:
            jobs.append([(fits[0], 2)])
        else:                        # decode from the longest prompt to w
            pos = next(p for p in range(hi + 1, server.max_len)
                       if _width(p, bs, nb_max) == w)
            jobs.append([(hi, pos - hi + 2)])
    served = 0
    for batch in jobs:
        for i, (plen, n_out) in enumerate(batch):
            toks = rng.integers(0, vocab, size=plen, dtype=np.int32)
            server.submit(-1 - served - i, toks, n_out, lambda *a: None)
        served += len(batch)
        while server.busy():
            server.step()
    return served


# ----------------------------------------------------------------------
class Client:
    def __init__(self, server, reqs: List[traffic_mod.Request],
                 seconds: float):
        self.server, self.reqs, self.seconds = server, reqs, seconds
        self.rec: Dict[int, Record] = {}
        self.t0 = self.end = self.stop = 0.0
        self.lateness: List[float] = []
        # KV blocks reserved for the requests in flight, at their full length
        self.outstanding_blocks = self.peak_blocks = 0
        # work done in traced steps: the context of each decoded token
        self.tracing = self.traced = False
        self.decode_ctx: List[int] = []

    def _on_tokens(self, idx: int, toks: List[int], done: bool) -> None:
        t = time.perf_counter()
        r = self.rec[idx]
        req = self.reqs[idx]
        if toks:
            if r.n == 0:
                r.first = t
            r.last = t
            if self.tracing:
                plen = len(req.prompt)
                self.decode_ctx.extend(plen + j for j in
                                       range(max(r.n, 1), r.n + len(toks)))
            r.n += len(toks)
        if done and not r.done:
            r.done = True
            self.outstanding_blocks -= blocks_for(
                len(req.prompt), req.n_out, self.server.scfg.block_size)

    def run(self, drain_s: float, counter: CompileCounter,
            trace: Optional[Tuple[float, float, Callable, Callable]] = None
            ) -> None:
        """The window, then the drain.  ``trace`` is (start offset, length,
        start_fn, stop_fn): the profiler runs over whole engine steps from
        the first step after the offset until the length has passed."""
        srv = self.server
        bs = srv.scfg.block_size
        pool = srv.pool_blocks
        todo = deque(range(len(self.reqs)))
        held: deque = deque()
        self.t0 = time.perf_counter()
        self.end = self.t0 + self.seconds
        counter.armed = True
        t_start = t_stop = None
        if trace:
            t_start = self.t0 + trace[0]
        while True:
            now = time.perf_counter()
            while todo and self.t0 + self.reqs[todo[0]].due_s <= now and \
                    self.t0 + self.reqs[todo[0]].due_s < self.end:
                i = todo.popleft()
                self.rec[i] = Record(due=self.t0 + self.reqs[i].due_s)
                held.append(i)
            if now >= self.end:
                todo.clear()
                counter.armed = False
            while held and srv.queued() < HANDOFF:
                req = self.reqs[held[0]]
                need = blocks_for(len(req.prompt), req.n_out, bs)
                if self.outstanding_blocks + need > pool and \
                        self.outstanding_blocks:
                    break
                i = held.popleft()
                self.outstanding_blocks += need
                self.peak_blocks = max(self.peak_blocks,
                                       self.outstanding_blocks)
                r = self.rec[i]
                r.handed = time.perf_counter()
                self.lateness.append(r.handed - r.due)
                srv.submit(i, req.prompt, req.n_out, self._on_tokens)
            if trace and t_start is not None and now >= t_start:
                trace[2]()
                self.tracing = self.traced = True
                t_start = None
                t_stop = time.perf_counter() + trace[1]
            if srv.busy():
                srv.step()
            elif not todo and not held:
                break
            else:
                nxt = self.t0 + self.reqs[todo[0]].due_s if todo else now
                time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.002))
            if self.tracing and time.perf_counter() >= t_stop:
                trace[3]()
                self.tracing = False
            if time.perf_counter() > self.end + drain_s:
                break
        self.stop = time.perf_counter()
        counter.armed = False
        if self.tracing:
            trace[3]()
            self.tracing = False

    # ------------------------------------------------------------------
    def attempted(self) -> List[int]:
        return sorted(self.rec)

    def finished_ok(self, idx: int) -> bool:
        r = self.rec[idx]
        return r.done and r.n == self.reqs[idx].n_out

    def ttft_s(self) -> List[float]:
        """Every request scheduled in the window; one that never delivered
        a token counts as waiting until the run stopped following it."""
        return [(r.first if r.n else self.stop) - r.due
                for r in self.rec.values()]

    def tpot_s(self) -> List[float]:
        """Requests that delivered two tokens or more; one cut short counts
        over the tokens it delivered."""
        return [(r.last - r.first) / (r.n - 1) for r in self.rec.values()
                if r.n >= 2]
