"""The one traffic generator: a mix is a JSON file of parameters under
``bench/traffic/``, and this module turns it and a seed into the requests of
one run.

Every seed serves the same work.  Sizes and inter-arrival gaps are drawn as
stratified quantiles of the mix's distributions, in blocks of ``block``
requests, and the seed only shuffles them within each block and draws the
prompt token ids.  So two seeds offer the same set of sizes and arrivals in
another order, and any prefix of whole blocks holds the same work.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    idx: int
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # (plen,) int32 token ids
    n_out: int            # tokens served, the first one sampled by prefill


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a length distribution, as whole
    numbers clipped to ``[min, max]``.  ``spec["dist"]`` is ``lognormal``
    (``median``, ``sigma``) or ``uniform``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _shuffled_blocks(rng, values: np.ndarray, n: int) -> np.ndarray:
    """``n`` values: whole copies of ``values``, each shuffled apart."""
    reps = -(-n // len(values))
    return np.concatenate([rng.permutation(values) for _ in range(reps)])[:n]


def max_total(mix: dict) -> int:
    """Longest sequence a request of this mix can reach."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The requests of one run, in due order.

    ``poisson`` arrivals at ``rate_per_s`` put ``round(rate * seconds)``
    requests in the window, their gaps the stratified quantiles of an
    exponential scaled to end inside it."""
    rng = np.random.default_rng(seed)
    block = int(mix.get("block", 32))
    arr = mix["arrivals"]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    n = max(int(round(arr["rate_per_s"] * seconds)), 1)
    u = (np.arange(block) + 0.5) / block
    gaps = _shuffled_blocks(rng, -np.log1p(-u), n)
    due = np.cumsum(gaps)
    due *= seconds * (1 - 0.5 / n) / due[-1]
    plens = _shuffled_blocks(rng, quantiles(mix["prompt"], block), n)
    nouts = _shuffled_blocks(rng, quantiles(mix["output"], block), n)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, size=int(plens[i]), dtype=np.int32)
        out.append(Request(i, float(due[i]), toks, int(nouts[i])))
    return out
