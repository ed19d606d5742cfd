#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; both are found by name.  The run:

1. fails, printing no result, unless JAX finds a TPU with as many chips as
   the cell asks for;
2. set-up: makes the weights from the seed on the device, builds the
   serving engine through ``serving_adapter`` and runs every prefill and
   decode program the mix can reach once (warm-up), then starts a fresh
   engine on the compiled programs;
3. the window: offers the mix's requests open loop for ``--seconds``, and
   follows every request scheduled in it to its end;
4. checks what the timed path served against the float32 reference
   (``check.py``), after the program's state is freed;
5. prints one JSON line: with ``--trace 0`` the cell's end-to-end metrics,
   with ``--trace 1`` its per-layer metrics, read from a profiler trace of a
   few seconds inside the window.

JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``,
so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DRAIN_S = 120.0          # how long requests of the window may take after it
TRACE_AT = 0.4           # the trace starts this share into the window
TRACE_S = 3.0            # and lasts about this long (whole engine steps)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def setup_jax_cache() -> str:
    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info(chips: int) -> dict:
    """The device as JAX reports it; exits unless it is a TPU with at least
    ``chips`` chips."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{d.platform} device(s) ({d.device_kind}). No result.")
        sys.exit(3)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def pct(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


class TraceCtx:
    """What a per-layer metric's reader is given (see ``bench/metrics``):
    the trace (None where the window was not traced), the cell's block
    module and its spec (work counts: ``block.decode_flops(spec, c,
    counters)``), the chip's peaks, the context of each token decoded while
    the trace ran, the engine's queue-wait p95 and count, every request's
    time to first token, and what the engine counted while the trace ran
    (``Server.counters()`` at the trace's stop less at its start), so that
    the counts cover the same steps as ``decode_ctx``."""

    def __init__(self, tr, *, block=None, spec=None, peak=None,
                 decode_ctx=(), queue_wait=(0.0, 0), ttft_s=(),
                 counters=None):
        from bench import trace as T
        self.block, self.spec, self.peak, self.trace = block, spec, peak, tr
        self.decode_ctx = list(decode_ctx)
        self.queue_wait = queue_wait
        self.ttft_s = list(ttft_s)      # every request of the window
        self.counters = dict(counters or {})
        self._T = T
        if tr is not None:
            self.lo, self.hi = tr.window()
            self.window_s = (self.hi - self.lo) / 1e9
            self.ops = {d: es for d, es in tr.line(T.OPS_LINE).items() if es}
            self.modules = tr.line(T.MODULES_LINE)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips that ran ops."""
        T = self._T
        if not self.ops:
            return 0.0
        return sum(T.busy_ns(es, self.lo, self.hi)
                   for es in self.ops.values()) / len(self.ops) / 1e9

    def idle_pct(self):
        """Idle share of the window in %, averaged over the chips."""
        shares = [self._T.idle_share(es, self.lo, self.hi)
                  for es in self.ops.values()]
        if not shares or shares[0] is None:
            return None
        return 100.0 * sum(shares) / len(shares)

    def module_seconds(self, pattern: str) -> float:
        T = self._T
        return sum(T.summed_ns(T.matching(es, pattern), self.lo, self.hi)
                   for es in self.modules.values()) / 1e9

    def kernel_seconds(self, pattern: str) -> float:
        T = self._T
        return sum(T.summed_ns(T.matching(es, pattern), self.lo, self.hi)
                   for es in self.ops.values()) / 1e9

    def breakdown(self) -> dict:
        T = self._T
        ops = [e for es in self.ops.values() for e in es]
        return {"device_ops": [list(x) for x in
                               T.top_ops(ops, self.lo, self.hi)],
                "idle_gaps": [list(x) for x in
                              T.idle_gaps(ops, self.trace.host, self.lo,
                                          self.hi)]}


def dump_trace(tr, path: str) -> None:
    """A summary of a trace for reading by hand: per device line, the
    events that took most time, with their stats."""
    from collections import defaultdict
    out = {"host_names": sorted({e.name for e in tr.host
                                 if not e.name.startswith("$")})[:200]}
    for d, lines in tr.devices.items():
        for ln, evs in lines.items():
            tot = defaultdict(float)
            ex = {}
            for e in evs:
                tot[e.name] += e.dur_ns
                ex.setdefault(e.name, [list(s) for s in e.stats][:12])
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:40]
            out[f"{d} | {ln}"] = [{"name": k, "ns": v, "n": sum(
                1 for e in evs if e.name == k), "stats": ex[k]}
                for k, v in top]
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


def dump_requests(client, path: str) -> None:
    """Each request of the window, for reading by hand: its prompt and
    output lengths, and when it was due, handed to the engine, and when
    its first and last tokens reached the client."""
    t0 = client.t0
    rows = []
    for i in client.attempted():
        r, req = client.rec[i], client.reqs[i]
        rows.append({"i": i, "plen": len(req.prompt), "n_out": req.n_out,
                     "due": r.due - t0, "handed": r.handed - t0,
                     "first": r.first - t0 if r.n else None,
                     "last": r.last - t0 if r.n else None, "n": r.n})
    with open(path, "w") as f:
        json.dump({"window_s": client.seconds, "stop": client.stop - t0,
                   "requests": rows}, f)


def traced_counts(before=None, after=None) -> dict:
    """What the engine counted between two ``Server.counters()``
    snapshots; empty unless both were taken."""
    if before is None or after is None:
        return {}
    return {k: v - before.get(k, 0) for k, v in after.items()}


class Session:
    """One process's set-up for a cell: weights from the seed, the engine
    warmed on every shape the mix reaches.  ``window()`` runs the measured
    window on a fresh engine over the compiled programs, on the same
    weights or on another seed's."""

    def __init__(self, reg, cell: dict, seed: int):
        import numpy as np
        from bench import driver, traffic
        from bench import serving_adapter as sa
        self.conf = reg.config(cell["config"])
        self.mix = reg.traffic(cell["traffic"])
        self.block = reg.block(self.conf)
        self.spec = self.block.spec(self.conf)
        self.vocab = self.block.vocab(self.spec)
        self.cfg = sa.arch(self.block.program_fields(self.spec,
                                                     self.conf["name"]))
        self.max_len = traffic.max_total(self.mix)
        self.counter = driver.CompileCounter()
        self.server = self.fns = None
        t0 = time.perf_counter()
        self._engine(seed)
        t1 = time.perf_counter()
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
        n = driver.warm_up(self.server, self.mix, rng, self.vocab)
        self.fns = self.server.engine.fns
        log(f"set-up: imports and device {t0 - T_START:.3f} s, weights and "
            f"engine {t1 - t0:.3f} s, warm-up {time.perf_counter() - t1:.3f}"
            f" s ({n} requests; {self.counter.compile_s:.3f} s of it "
            f"compiling or loading from the cache)")

    def _engine(self, seed: Optional[int]) -> None:
        """A fresh engine over the compiled programs: on this seed's
        weights, or (``seed`` None) on the weights it has."""
        from bench import serving_adapter as sa
        if seed is None:
            self.server = self.server.fresh()
            return
        self.free()
        weights = self.block.make_weights(self.spec, seed)
        deploy = self.conf["deployment"]
        params = self.block.program_params(weights, self.cfg.padded_vocab)
        self.server = sa.Server(params, self.cfg, slots=deploy["slots"],
                                max_len=self.max_len,
                                pool_tokens=deploy["kv_pool_tokens"],
                                shared=self.fns)

    def window(self, seed: int, seconds: float, trace=None,
               new_weights: bool = False):
        """The measured window on a fresh engine; returns the client.
        ``new_weights`` serves this seed's weights, made anew."""
        from bench import driver, traffic
        self._engine(seed if new_weights else None)
        reqs = traffic.generate(self.mix, seed, seconds, self.vocab)
        client = driver.Client(self.server, reqs, seconds)
        client.run(DRAIN_S, self.counter, trace)
        return client

    def served(self, client, seed: int):
        """The sampled requests' prompts and served tokens, and how many
        requests of the window failed."""
        from bench import check
        ok = [i for i in client.attempted() if client.finished_ok(i)]
        picked = check.sample(ok, {i: client.rec[i].n for i in ok}, seed)
        return ([client.reqs[i].prompt for i in picked],
                [self.server.result(i)[0] for i in picked],
                len(client.attempted()) - len(ok))

    def free(self) -> None:
        """Drop the engine and its weights, so the reference has the chip."""
        if self.server is not None:
            self.server.close()
        self.server = None
        gc.collect()


def main(argv=None, reg=None, device=None) -> int:
    """``reg`` and ``device`` are for the tests: another registry, and a
    device record that stands in for the look for a chip."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default="",
                    help="also write a summary of the trace to this path")
    ap.add_argument("--dump-requests", default="",
                    help="also write each request's sizes and times (seconds "
                         "from the window's start) to this path")
    args = ap.parse_args(argv)

    from bench.registry import Registry
    reg = reg or Registry()
    cell = reg.workload(args.workload)
    if device is None:
        setup_jax_cache()
        device = device_info(int(cell["chips"]))

    import jax
    from bench import check, work
    from bench import serving_adapter as sa
    from bench import trace as T

    sess = Session(reg, cell, args.seed)
    trace_dir, holder, counts, trace = None, [], [], None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")

        def start():
            counts.append(sess.server.counters())
            sa.start_trace(trace_dir)
            holder.append(jax.profiler.TraceAnnotation(T.WINDOW_SPAN))
            holder[-1].__enter__()

        def stop():
            holder[-1].__exit__(None, None, None)
            sa.stop_trace()
            counts.append(sess.server.counters())

        trace = (TRACE_AT * args.seconds,
                 min(TRACE_S, 0.3 * args.seconds), start, stop)

    client = sess.window(args.seed, args.seconds, trace)
    setup_s = client.t0 - T_START
    mem = jax.devices()[0].memory_stats() or {}
    mem_peak = int(mem.get("peak_bytes_in_use", 0))

    attempted = client.attempted()
    failed = sum(1 for i in attempted if not client.finished_ok(i))
    if args.dump_requests:
        dump_requests(client, args.dump_requests)
    late = client.lateness
    pool, bs = sess.server.pool_blocks, sess.server.scfg.block_size
    kv_b = sess.block.kv_bytes_per_token(sess.spec)
    log(f"KV pool: {pool} blocks of {bs} tokens, {kv_b} B a token, "
        f"{pool * bs * kv_b / 1e9:.3f} GB; at most {client.peak_blocks} "
        f"blocks ({100.0 * client.peak_blocks / pool:.1f}%) reserved for the "
        f"requests in flight at their full length")
    log(f"window {args.seconds} s: {len(attempted)} requests scheduled, "
        f"{failed} failed; generator lateness p50 {pct(late, 50)} s p95 "
        f"{pct(late, 95)} s max {max(late) if late else None} s")
    log(f"traces or compiles inside the window: {sess.counter.count}")
    qw = sess.server.queue_wait_p95_s()
    log(f"setup_s {setup_s:.3f}; engine queue wait p95 {qw[0]:.4f} s over "
        f"{qw[1]} admits")
    ttft = client.ttft_s()
    log(f"time to first token over {len(ttft)} requests: p50 "
        f"{pct(ttft, 50)} s p90 {pct(ttft, 90)} s p95 {pct(ttft, 95)} s; "
        f"time per output token p95 {pct(client.tpot_s(), 95)} s")

    result = {"correct": False, "attempted": len(attempted),
              "failed": failed, "metrics": {},
              "device": dict(device, memory_peak_bytes=mem_peak)}
    if args.trace:
        tr = T.load(trace_dir) if client.traced else None
        if args.dump_trace and tr is not None:
            dump_trace(tr, args.dump_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = TraceCtx(tr, block=sess.block, spec=sess.spec,
                       peak=work.peaks(device["kind"]),
                       decode_ctx=client.decode_ctx, queue_wait=qw,
                       ttft_s=ttft, counters=traced_counts(*counts))
        if tr is not None:
            result["device"]["busy_s"] = ctx.busy_s()
            result["device"]["window_s"] = ctx.window_s
            result["breakdown"] = ctx.breakdown()
        for m in reg.metrics_of(args.workload, "per_layer"):
            v = reg.reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    else:
        ttft95, tpot = pct(ttft, 95), pct(client.tpot_s(), 95)
        values = {"setup_s": setup_s,
                  "ttft_p95_ms": None if ttft95 is None else 1e3 * ttft95,
                  "tpot_p95_ms": None if tpot is None else 1e3 * tpot}
        for m in reg.metrics_of(args.workload, "end_to_end"):
            if values.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}

    # correctness: sample, free the program's state, rerun the reference
    prompts, outputs, failed = sess.served(client, args.seed)
    del client
    sess.free()
    weights = sess.block.make_weights(sess.spec, args.seed)
    verdict = check.compare(sess.block, weights, sess.spec, prompts,
                            outputs, sess.conf["check"]["max_gap"], failed)
    result["correct"] = verdict["correct"]
    result["checks"] = verdict["checks"]
    log(f"compared {verdict['served_tokens_compared']} served tokens of "
        f"{len(prompts)} requests; {verdict['at_reference_best']} are the "
        f"reference's best")
    for k, v in verdict["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
