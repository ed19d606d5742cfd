"""A run whose timed path is broken underneath must come out not correct.

The look for a chip is stood in and the rest of a run is driven at tiny
widths on the CPU, with the program broken in one of the ways a served
model can be:

* a token altered where it is produced: the program's in-jit sampling
  serves the token after the best;
* a step that returns its state unchanged: the KV pool is handed back
  without the rows a prefill or decode step wrote, so later tokens attend
  to what was there before.
"""
from __future__ import annotations

import pytest

from bench_tiny import CELLS, run_tiny


def _token_after_the_best(monkeypatch):
    from repro.models import transformer as tfm
    good = tfm.sample_tokens

    def altered(logits, temperature=0.0, rng=None):
        return (good(logits, temperature, rng) + 1) % logits.shape[-1]

    monkeypatch.setattr(tfm, "sample_tokens", altered)


def _pool_left_unchanged(monkeypatch):
    from repro.models import attention

    monkeypatch.setattr(attention, "pool_write",
                        lambda pool, phys, off, rows: pool)


FAULTS = {"token_altered": _token_after_the_best,
          "state_unchanged": _pool_left_unchanged}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_run_with_a_broken_timed_path_is_not_correct(
        tiny_registry, cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run_tiny(tiny_registry, cell, seed=9)
    assert res["correct"] is False
    gap = res["checks"]["max_gap"]
    assert gap["value"] > gap["limit"]
