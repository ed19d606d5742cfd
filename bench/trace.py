"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read: the device's busy time (the union of its op intervals), the
idle share, the device time of events that match a name, and the idle gaps
labelled by the host span open in them.

A trace is read with ``jax.profiler.ProfileData`` into plain ``Event``
lists, so every function below also runs on hand-built lists in the tests.
Planes named ``/device:...`` are devices; on a TPU their ``XLA Ops`` line
holds one event per executed op (a Pallas kernel is one op) and their ``XLA
Modules`` line one event per executed program.  Host planes hold the
``TraceAnnotation`` spans, among them the harness's ``bench_window``, which
sets the traced window, and the program's step spans (``engine.step``,
``engine.admit``, ``engine.prefill``, ``engine.decode_sync`` and the rest).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op(self) -> str:
        """The HLO instruction's name: an op event's name is the
        instruction's text, ``%name = shape op(operands...)``."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    # device plane name -> line name -> events
    devices: Dict[str, Dict[str, List[Event]]]
    host: List[Event]                  # every event of the host planes

    def window(self) -> Tuple[float, float]:
        """The ``bench_window`` span, or the extent of all device events
        where there is none."""
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if spans:
            w = max(spans, key=lambda e: e.dur_ns)
            return w.start_ns, w.end_ns
        evs = [e for d in self.devices.values() for es in d.values()
               for e in es]
        if not evs:
            return 0.0, 0.0
        return min(e.start_ns for e in evs), max(e.end_ns for e in evs)

    def line(self, name: str) -> Dict[str, List[Event]]:
        """Per device plane, the events of one line."""
        return {d: lines.get(name, []) for d, lines in self.devices.items()}


def _stats(ev) -> Tuple[Tuple[str, str], ...]:
    out = []
    try:
        for k, v in ev.stats:
            out.append((str(k), str(v)))
    except (TypeError, ValueError):
        pass
    return tuple(out)


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        if not (is_dev or plane.name.startswith("/host:")):
            continue
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                         _stats(e) if is_dev else ())
                   for e in line.events]
            if is_dev:
                lines[line.name] = evs
            else:
                host.extend(evs)
        if is_dev:
            devices[plane.name] = lines
    return Trace(devices, host)


# ----------------------------------------------------------------------
def merged(events: Iterable[Event], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """The union of the events' intervals, clipped to ``[lo, hi]``, as
    sorted disjoint intervals."""
    iv = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                if e.end_ns > lo and e.start_ns < hi)
    out: List[List[float]] = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(t - s for s, t in merged(events, lo, hi))


def idle_share(events: Sequence[Event], lo: float, hi: float
               ) -> Optional[float]:
    """1 - busy / window; None for an empty window."""
    if hi <= lo:
        return None
    return 1.0 - busy_ns(events, lo, hi) / (hi - lo)


def matching(events: Iterable[Event], pattern: str) -> List[Event]:
    """The events whose op name (see ``Event.op``) matches ``pattern`` from
    its start; an operand of the same name further on does not match."""
    rx = re.compile(pattern)
    return [e for e in events if rx.match(e.op)]


def summed_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Summed durations of the events, each clipped to ``[lo, hi]``."""
    return sum(max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
               for e in events)


def top_ops(events: Iterable[Event], lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` op names that took the most device seconds (an op nested in
    another, as a loop's body in the loop, counts in both)."""
    tot: Dict[str, float] = defaultdict(float)
    for e in events:
        d = max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
        if d:
            tot[e.op] += d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in best]


def idle_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float, n: int = 10) -> List[Tuple[str, float]]:
    """Device idle time inside ``[lo, hi]``, summed by the innermost host
    span open at the middle of each gap (``host`` where none is); the ``n``
    largest labels, in seconds."""
    busy = merged(events, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    # the Python tracer's events start with "$"; the named spans do not
    spans = [h for h in host
             if h.name != WINDOW_SPAN and not h.name.startswith("$")]
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [h for h in spans if h.start_ns <= mid < h.end_ns]
        label = min(open_, key=lambda h: h.dur_ns).name if open_ else "host"
        tot[label] += e - s
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in best]
