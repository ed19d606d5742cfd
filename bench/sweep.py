#!/usr/bin/env python3
"""Find the knee of an online cell: one set-up, then one window at each
offered rate, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 4,6,8,10

For each rate it prints the requests scheduled and finished, the time to
first token (p50, p95), the p95 time per output token, the mean time to
first token of the first and of the last third of the arrivals, and how
many requests still waited for their first token when the window closed.
The knee is the highest rate at which the backlog does not grow over the
window: the last third of the arrivals waits, on average, no more than
1.5 times as long as the first third, plus 0.1 s.  A cell runs at a rate
fixed in its traffic file: 0.8 x the knee, written there by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--dump-dir", default="",
                    help="also write each window's requests to "
                         "<dir>/rate_<rate>.json")
    args = ap.parse_args(argv)
    from bench.registry import Registry
    reg = Registry()
    cell = reg.workload(args.workload)
    run.setup_jax_cache()
    run.device_info(int(cell["chips"]))
    sess = run.Session(reg, cell, args.seed)
    steady = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        sess.mix = dict(sess.mix, arrivals={"kind": "poisson",
                                            "rate_per_s": rate})
        c = sess.window(args.seed + k, args.seconds)
        if args.dump_dir:
            run.dump_requests(c, os.path.join(args.dump_dir,
                                              f"rate_{rate}.json"))
        recs = sorted(c.rec.values(), key=lambda r: r.due)
        third = max(len(recs) // 3, 1)
        waits = [(r.first if r.n else c.stop) - r.due for r in recs]
        waiting = sum(1 for r in recs if not r.n or r.first > c.end)
        row = {"rate_per_s": rate, "scheduled": len(recs),
               "finished": sum(1 for i in c.rec if c.finished_ok(i)),
               "ttft_p50_ms": 1e3 * run.pct(waits, 50),
               "ttft_p95_ms": 1e3 * run.pct(waits, 95),
               "tpot_p95_ms": 1e3 * run.pct(c.tpot_s(), 95),
               "ttft_first_third_ms": 1e3 * sum(waits[:third]) / third,
               "ttft_last_third_ms": 1e3 * sum(waits[-third:]) / third,
               "waiting_at_close": waiting,
               "pool_peak_share": c.peak_blocks / sess.server.pool_blocks}
        print(json.dumps(row), flush=True)
        if row["ttft_last_third_ms"] <= 1.5 * row["ttft_first_third_ms"] \
                + 100.0:
            steady.append(rate)
    knee = max(steady) if steady else None
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
