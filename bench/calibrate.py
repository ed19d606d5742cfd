#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, on the chip, at the
cell's own size and load: for each seed, the program's widest gap
(``max_gap``, as a run computes it) and the float8 control's, read at
every position of the same sampled prompts and served tokens.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 10

One set-up serves every seed: each seed gets its own weights and traffic
on the same compiled programs.  The limit lies above the largest program
reading and below the smallest control reading (PERF.md).
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import check
    from bench.registry import Registry
    reg = Registry()
    cell = reg.workload(args.workload)
    run.setup_jax_cache()
    run.device_info(int(cell["chips"]))
    seeds = [int(s) for s in args.seeds.split(",")]
    sess = run.Session(reg, cell, seeds[0])
    for seed in seeds:
        client = sess.window(seed, args.seconds, new_weights=True)
        prompts, outputs, failed = sess.served(client, seed)
        del client
        sess.free()
        weights = sess.block.make_weights(sess.spec, seed)
        v = check.compare(sess.block, weights, sess.spec, prompts, outputs,
                          sess.conf["check"]["max_gap"], failed, control=True)
        del weights
        print(json.dumps({"seed": seed, "failed": failed,
                          "program_max_gap": v["checks"]["max_gap"]["value"],
                          "control_max_gap": v["control_max_gap"],
                          "tokens": v["served_tokens_compared"],
                          "at_best": v["at_reference_best"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
