"""MLaaS service front: batching, deadlines, and the launch drivers."""
import subprocess
import sys
import os
import time

import numpy as np
import pytest

from repro.core.partitioner import fit_cost_model
from repro.core.service import MLaaSService


def test_service_batches_and_completes():
    calls = []

    def step(payloads):
        calls.append(len(payloads))
        return [p * 2 for p in payloads]

    svc = MLaaSService(step, capacity=4).start()
    reqs = [svc.submit(i, timeout_s=2.0) for i in range(10)]
    for r in reqs:
        assert r.done.wait(5.0)
    svc.stop()
    assert [r.result for r in reqs] == [2 * i for i in range(10)]
    assert svc.stats["requests"] == 10
    assert max(calls) <= 4


def test_idle_service_does_not_busy_poll():
    """An idle service blocks on its inbox (capped waits) instead of
    spinning at poll_s: over ~0.3s idle it must wake only a handful of
    times (the old 2ms poll woke ~150x), yet a late submit still completes
    promptly and stop() returns without waiting out the cap."""
    svc = MLaaSService(lambda ps: ps, capacity=4).start()
    time.sleep(0.3)
    wakeups_idle = svc.metrics.counter("service.loop_wakeups").value
    assert wakeups_idle <= 25, \
        f"idle loop woke {wakeups_idle}x in 0.3s — still busy-polling"
    r = svc.submit("late", timeout_s=2.0)
    assert r.done.wait(3.0) and r.result == "late"
    t0 = time.monotonic()
    svc.stop()
    assert time.monotonic() - t0 < MLaaSService.IDLE_WAIT_CAP_S + 1.0


def test_service_flushes_on_deadline_slack():
    def slow_step(payloads):
        time.sleep(0.05)
        return payloads

    model = fit_cost_model([1, 4], [0.05, 0.05])   # flat cost
    svc = MLaaSService(slow_step, capacity=64, cost_model=model).start()
    r = svc.submit("only-one", timeout_s=0.5)
    assert r.done.wait(3.0), "lone request must flush before its deadline"
    svc.stop()
    assert not r.missed_deadline
    # capacity 64 never filled: the deadline policy fired
    assert svc.mean_batch() <= 2


def _run(mod, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    return subprocess.run([sys.executable, "-m", mod, *args], env=env,
                          capture_output=True, text=True, timeout=1200)


@pytest.mark.slow
def test_launch_train_driver_resumes(tmp_path):
    d = str(tmp_path / "run")
    r1 = _run("repro.launch.train", "--steps", "6", "--batch", "2",
              "--seq", "32", "--ckpt-every", "3", "--ckpt-dir", d)
    assert r1.returncode == 0, r1.stdout + r1.stderr
    r2 = _run("repro.launch.train", "--steps", "8", "--batch", "2",
              "--seq", "32", "--ckpt-every", "3", "--ckpt-dir", d)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed from checkpoint" in r2.stdout


@pytest.mark.slow
def test_launch_serve_driver():
    r = _run("repro.launch.serve", "--reduced", "--requests", "3",
             "--max-new", "4", "--slots", "2", "--max-len", "64")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tok/s=" in r.stdout
