"""The client's tails and the ``failed`` check: a request that stalls can
neither leave the tails smaller nor leave the run correct."""
from __future__ import annotations

import types

import numpy as np
import pytest

from bench_tiny import TINY, tiny_config
from bench import check, driver
from bench import reference as R
from bench import traffic as TR
from bench.blocks import dense_gqa as G


def client_with(records, stop):
    reqs = [TR.Request(i, 0.0, np.zeros(4, np.int32), 4)
            for i in range(len(records))]
    c = driver.Client(types.SimpleNamespace(), reqs, 1.0)
    c.rec = dict(enumerate(records))
    c.stop = stop
    return c


def test_a_request_with_no_token_counts_in_ttft_until_the_run_stopped():
    done = driver.Record(due=1.0, first=1.5, last=2.1, n=4, done=True)
    stalled = driver.Record(due=2.0)
    c = client_with([done, stalled], stop=130.0)
    assert c.ttft_s() == [pytest.approx(0.5), pytest.approx(128.0)]
    assert c.tpot_s() == [pytest.approx(0.2)]
    assert [c.finished_ok(i) for i in c.attempted()] == [True, False]


def test_a_request_cut_short_counts_in_tpot_over_its_tokens():
    cut = driver.Record(due=0.0, first=1.0, last=2.0, n=3, done=True)
    c = client_with([cut], stop=5.0)
    assert c.tpot_s() == [pytest.approx(0.5)]
    assert not c.finished_ok(0)


@pytest.mark.parametrize("failed,correct", [(0, True), (1, False)])
def test_a_failed_request_makes_the_run_not_correct(failed, correct):
    s = G.spec(tiny_config("internlm2-1.8b", TINY))
    w = G.make_weights(s, 3)
    prompt = np.arange(1, 9, dtype=np.int32)
    lg = R.logits_at(G.logits, w, s, prompt[None], [[7]])
    best = [int(np.asarray(lg)[0, 0].argmax())]
    v = check.compare(G, w, s, [prompt], [best], 0.3, failed)
    assert v["checks"]["max_gap"]["value"] == 0.0
    assert v["checks"]["failed"] == {"value": failed, "limit": 0}
    assert v["correct"] is correct
