"""Production serving driver: continuous-batching engine(s) + the MLaaS
request path.  With ``--replicas N`` (N > 1) requests travel through the
cluster layer — a Router fanning out over N engine replicas with admission
control and unified metrics.  ``--transport`` picks replica placement:

  * ``thread``  — replicas share this process and its JAX runtime; weights
    are zero-copy but device FLOPs do not scale.
  * ``process`` — each replica is a spawned worker process with an RPC
    inbox, rebuilt from a serializable spec (arch + seed or
    ``--weights-dir``); independent JAX runtimes, so compute scales.
  * ``socket``  — the same spec-rebuilt worker behind a framed TCP
    connection with a versioned reconnect handshake: here the workers are
    spawned locally and dial back over loopback, but the identical worker
    (``python -m repro.cluster.worker_main``) can run on any host that
    reaches this process — heartbeat-timeout crash detection and
    artifact-store weight fetch included.

The model is served at its published widths with seeded random weights;
``--reduced`` selects the tiny same-family preset the CPU tests use.  With
thread replicas on an accelerator host each replica serves from a device
of its own, and asking for more replicas than devices fails at start.

    PYTHONPATH=src python -m repro.launch.serve --requests 8 --max-new 16
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced \
        --replicas 2 --router-policy least_loaded --requests 8 \
        --transport socket

The process exits non-zero when any request ends without completing.
"""
from __future__ import annotations

import argparse
import json
import threading
import time
import urllib.request

import jax
import numpy as np

from repro.cluster import (AdmissionConfig, AdmissionController,
                           BrownoutController, EngineBackend,
                           MetricsRegistry, POLICIES, ReplicaConfig, Router,
                           SLOEngine, SLOObjective, StatsServer, TRANSPORTS,
                           TelemetrySampler, TimeSeriesStore, Tracer,
                           current_tracer, engine_spec, prometheus_text,
                           render_watch, set_tracer, to_chrome_trace)
from repro.cluster.tracing import start_profiling, stop_profiling
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import reduced as reduce_cfg
from repro.launch.compile_cache import setup_compile_cache
from repro.models import api
from repro.serving import Engine, ServeConfig, make_engine_fns


def _start_telemetry(args, snapshot_fn, registry, router=None):
    """Build the stats stack — ring-buffer TimeSeriesStore, SLO burn-rate
    engine, background sampler, HTTP stats endpoint, optional terminal
    watcher — and return a ``finalize()`` that takes one last sample,
    dumps the routes (``--stats-dump``), and tears everything down."""
    from repro.cluster.tracing import current_recorder

    store = TimeSeriesStore()
    slo = SLOEngine([SLOObjective(kind="any")], registry,
                    recorder=current_recorder())
    if router is not None:
        router.slo = slo            # brownout reads slo.pressure()
    sampler = TelemetrySampler(snapshot_fn, store, registry=registry,
                               tracer=current_tracer(), slo=slo,
                               period_s=args.stats_period)
    sampler.start()
    server = None
    port = args.stats_port
    if port is None and args.stats_dump:
        port = 0
    if port is not None:
        server = StatsServer(snapshot_fn, store, slo=slo,
                             host=args.stats_host, port=port).start()
        print(f"[stats] /metrics /timeseries.json /slo.json /dash "
              f"on {server.url}")
    stop_watch = threading.Event()
    wt = None
    if args.watch:
        def _watch_loop():
            while not stop_watch.wait(1.0):
                print("\x1b[2J\x1b[H" + render_watch(store, slo.status()))
        wt = threading.Thread(target=_watch_loop, daemon=True,
                              name="stats-watch")
        wt.start()

    def finalize():
        stop_watch.set()
        if wt is not None:
            wt.join(timeout=2.0)
        sampler.stop()
        sampler.tick()              # one last sample so dumps see the end
        if args.watch:
            print(render_watch(store, slo.status()))
        if args.stats_dump and server is not None:
            routes = (("metrics", "txt", "/metrics"),
                      ("timeseries", "json", "/timeseries.json"),
                      ("slo", "json", "/slo.json"),
                      ("dash", "html", "/dash"))
            for name, ext, route in routes:
                with urllib.request.urlopen(server.url + route,
                                            timeout=10.0) as resp:
                    body = resp.read()
                with open(f"{args.stats_dump}.{name}.{ext}", "wb") as f:
                    f.write(body)
            print(f"[stats] dumped {len(routes)} routes -> "
                  f"{args.stats_dump}.*")
        if server is not None:
            server.stop()

    return finalize


def replica_devices(n: int):
    """The device each of ``n`` in-process replicas serves from: a chip of
    its own on an accelerator host, ``None`` (the default device, shared)
    on the CPU backend."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        return [None] * n
    if n > len(devs):
        raise ValueError(f"--replicas {n} exceeds the {len(devs)} "
                         f"{devs[0].platform} devices of this host: each "
                         f"replica needs a device of its own")
    return devs[:n]


def _incomplete(finish_reasons, outs):
    """Requests that ended without completing: a cluster result that is not
    a token list, or an engine error / ``rejected_*`` finish."""
    bad = [f"request {i}: finish_reason={r!r}"
           for i, r in enumerate(finish_reasons)
           if r == "error" or r.startswith("rejected_")]
    bad += [f"request {i}: {type(o).__name__} {o!r}"[:200]
            for i, o in enumerate(outs) if not isinstance(o, list)]
    return bad


def main(argv=None):
    """Serve seeded requests; returns ``{"cfg", "params", "prompts",
    "outputs", "wall_s", "replicas"}`` (``outputs[i]``: the tokens served
    for ``prompts[i]``; ``replicas``: per thread replica, its device and
    how many requests it finished)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    choices=[a for a in ARCH_IDS if a != "whisper-base"])
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family preset "
                         "(configs.base.reduced, for CPU runs) instead of "
                         "the published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=4,
                    help="shortest seeded prompt, in tokens")
    ap.add_argument("--max-prompt", type=int, default=15,
                    help="longest seeded prompt, in tokens")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the cluster router")
    ap.add_argument("--router-policy", default="round_robin",
                    choices=list(POLICIES))
    ap.add_argument("--max-queue", type=int, default=4096,
                    help="admission control: global queued-cost bound")
    ap.add_argument("--transport", default="thread", choices=list(TRANSPORTS),
                    help="replica placement: host threads, worker processes "
                         "with RPC inboxes, or socket workers over framed "
                         "TCP (remote-host capable)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    help="per-token reference decode loop instead of the "
                         "fused on-device K-step loop")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="K: fused decode steps per host sync")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="in-jit sampling temperature (0 = greedy argmax)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: per-layer block pool + block "
                         "tables + content-hashed prefix cache instead of "
                         "one dense max_len stripe per slot (families with "
                         "non-pageable state keep the dense path)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="usable pool blocks (paged); 0 = dense-equivalent "
                         "slots * max_len/block_size")
    ap.add_argument("--kv-swap", action="store_true",
                    help="KV lifecycle swap (paged): under pool pressure "
                         "preempt whole lowest-priority sessions to the "
                         "swap tier and restore them block-exact at "
                         "re-admit instead of completing them early as "
                         "kv_pool_exhausted victims")
    ap.add_argument("--swap-tier", default="host",
                    choices=("host", "artifact"),
                    help="where swapped KV blocks live: host memory "
                         "(inline bytes) or the content-addressed "
                         "artifact store")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative multi-token decode on the paged path: "
                         "an n-gram draft proposes spec-draft tokens per "
                         "step and one batched paged extend verifies them "
                         "(greedy only; requires --paged)")
    ap.add_argument("--spec-draft", type=int, default=3,
                    help="draft tokens proposed per speculative step")
    ap.add_argument("--request-timeout", type=float, default=600.0,
                    help="per-request deadline budget in seconds; the "
                         "budget rides the wire to workers, which drop "
                         "expired queue work and finish expired sessions "
                         "mid-decode (finish_reason='deadline')")
    ap.add_argument("--brownout", action="store_true",
                    help="graded overload controller: under queue/KV "
                         "pressure, degrade service (disable speculation, "
                         "halve max_new, tighten admission) instead of "
                         "only shedding at the front door")
    ap.add_argument("--kv-headroom", type=float, default=0.0,
                    help="admission: shed when the cluster's free KV-block "
                         "fraction drops below this (0 disables)")
    ap.add_argument("--weights-dir", default=None,
                    help="checkpoint dir for process workers to load "
                         "weights from (default: deterministic init at "
                         "seed 0 inside each worker, matching the "
                         "thread/single-replica paths)")
    ap.add_argument("--trace", action="store_true",
                    help="record per-request spans through router, "
                         "transport, replica, and engine stages")
    ap.add_argument("--trace-sample-rate", type=float, default=1.0,
                    help="fraction of requests that root a trace "
                         "(workers always follow a sampled parent)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the collected spans as Chrome trace-event "
                         "JSON (load in Perfetto / chrome://tracing); "
                         "implies --trace")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write the final metrics snapshot in Prometheus "
                         "text exposition format")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the run "
                         "into DIR (TensorBoard/Perfetto loadable); the "
                         "engine's step spans (engine.step, engine.admit, "
                         "engine.decode_sync, ...) land in it too")
    ap.add_argument("--stats-port", type=int, default=None, metavar="PORT",
                    help="serve live stats over HTTP: /metrics (Prometheus), "
                         "/timeseries.json, /slo.json, /dash (HTML "
                         "dashboard); 0 picks an ephemeral port")
    ap.add_argument("--stats-host", default="127.0.0.1",
                    help="stats bind address (loopback unless you mean it)")
    ap.add_argument("--stats-dump", default=None, metavar="PREFIX",
                    help="at end of run, fetch every stats route over HTTP "
                         "and write PREFIX.metrics.txt / .timeseries.json / "
                         ".slo.json / .dash.html; implies --stats-port 0")
    ap.add_argument("--watch", action="store_true",
                    help="render a terminal stats screen every second "
                         "while the run is in flight")
    ap.add_argument("--stats-period", type=float, default=0.25,
                    help="telemetry sampling cadence in seconds")
    args = ap.parse_args(argv)

    if args.trace_out:
        args.trace = True
    if args.trace:
        set_tracer(Tracer(enabled=True,
                          sample_rate=args.trace_sample_rate,
                          replica="parent"))
    if args.profile_dir:
        start_profiling(args.profile_dir)

    cache_dir = setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    # remote workers init/load their own weights; don't pay for a parent copy
    need_params = args.replicas <= 1 or \
        args.transport not in ("process", "socket")
    params = api.init(jax.random.PRNGKey(0), cfg)[0] if need_params else None
    scfg = ServeConfig(max_len=args.max_len, slots=args.slots,
                       fused=args.fused, sync_every=args.sync_every,
                       temperature=args.temperature, paged=args.paged,
                       block_size=args.block_size, kv_blocks=args.kv_blocks,
                       speculative=args.speculative,
                       spec_draft=args.spec_draft, kv_swap=args.kv_swap,
                       swap_tier=args.swap_tier)
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(0, cfg.vocab,
                           size=rng.randint(args.min_prompt,
                                            args.max_prompt + 1)
                           ).astype(np.int32)
               for _ in range(args.requests)]

    snap = None
    stats_on = (args.stats_port is not None or args.stats_dump is not None
                or args.watch)
    finalize_stats = None
    if args.replicas <= 1:
        metrics = MetricsRegistry() if (args.prom_out or stats_on) else None
        eng = Engine(params, cfg, scfg, metrics=metrics)
        if stats_on:
            finalize_stats = _start_telemetry(args, metrics.snapshot,
                                              metrics)
        reqs = [eng.submit(p, max_new=args.max_new) for p in prompts]
        t0 = time.perf_counter()
        eng.run_until_drained()
        wall = time.perf_counter() - t0
        outs = [r.out_tokens for r in reqs]
        finish = [r.finish_reason for r in reqs]
        replicas = [{"device": str(jax.devices()[0]),
                     "served": len(eng.finished)}]
        toks = sum(len(r.out_tokens) for r in reqs)
        lats = [r.done_t - r.submit_t for r in reqs]
        if finalize_stats is not None:
            finalize_stats()
        if metrics is not None:
            snap = metrics.snapshot()
    else:
        metrics = MetricsRegistry()
        router = Router(policy=args.router_policy, metrics=metrics,
                        admission=AdmissionController(
                            AdmissionConfig(
                                max_queue_cost=args.max_queue,
                                min_kv_headroom_frac=args.kv_headroom),
                            metrics),
                        brownout=BrownoutController() if args.brownout
                        else None)
        rcfg = ReplicaConfig(max_batch=args.slots)
        engines = []
        if args.transport in ("process", "socket"):
            spec = engine_spec(arch=args.arch, max_len=args.max_len,
                               slots=args.slots, reduce=args.reduced, seed=0,
                               weights_path=args.weights_dir,
                               fused=args.fused, sync_every=args.sync_every,
                               temperature=args.temperature,
                               paged=args.paged, block_size=args.block_size,
                               kv_blocks=args.kv_blocks,
                               speculative=args.speculative,
                               spec_draft=args.spec_draft,
                               kv_swap=args.kv_swap,
                               swap_tier=args.swap_tier)
            for _ in range(args.replicas):
                router.add_replica(spec=spec, cfg=rcfg,
                                   transport=args.transport)
        else:
            shared_fns = make_engine_fns(cfg, scfg)
            for dev in replica_devices(args.replicas):
                if dev is None:
                    eng = Engine(params, cfg, scfg, metrics=metrics,
                                 shared_fns=shared_fns)
                else:
                    # the replica's weights and device state live on its
                    # own chip; jitted calls follow the committed weights
                    with jax.default_device(dev):
                        eng = Engine(jax.device_put(params, dev), cfg, scfg,
                                     metrics=metrics, shared_fns=shared_fns)
                engines.append((dev, eng))
                router.add_replica(EngineBackend(eng), rcfg)
        if stats_on:
            finalize_stats = _start_telemetry(args, router.cluster_snapshot,
                                              metrics, router=router)
        t0 = time.perf_counter()
        creqs = [router.submit((p, args.max_new), cost=args.max_new,
                               session_key=str(i),
                               timeout_s=args.request_timeout)
                 for i, p in enumerate(prompts)]
        outs = [router.wait(r, timeout=args.request_timeout)
                for r in creqs]
        wall = time.perf_counter() - t0
        if finalize_stats is not None:
            finalize_stats()
        router.stop()
        finish = [""] * len(outs)
        replicas = [{"device": str(dev if dev is not None
                                   else jax.devices()[0]),
                     "served": len(eng.finished)} for dev, eng in engines]
        toks = sum(len(o) for o in outs if isinstance(o, list))
        lats = [r.finished_s - r.submitted_s for r in creqs]
        snap = metrics.snapshot()
        print(f"[cluster] replicas={args.replicas} "
              f"transport={args.transport} "
              f"policy={args.router_policy} "
              f"completed={snap['router.completed']:.0f} "
              f"shed={snap.get('admission.shed_queue_full', 0):.0f}")

    dev = jax.devices()[0] if need_params else None
    where = f"{dev.platform}:{dev.device_kind}" if dev is not None \
        else f"{args.transport} workers"
    print(f"[serve] arch={args.arch} reduced={args.reduced} on={where} "
          f"reqs={len(prompts)} tokens={toks} "
          f"tok/s={toks / wall:.1f} p50={np.median(lats):.2f}s "
          f"p99={np.percentile(lats, 99):.2f}s compile_cache={cache_dir}")

    if args.profile_dir:
        stop_profiling()
        print(f"[profile] jax trace written under {args.profile_dir}")
    if args.trace_out:
        spans = current_tracer().spans()
        with open(args.trace_out, "w") as f:
            json.dump(to_chrome_trace(spans), f)
        print(f"[trace] {len(spans)} spans -> {args.trace_out}")
    if args.prom_out:
        with open(args.prom_out, "w") as f:
            f.write(prometheus_text(snap or {}))
        print(f"[metrics] prometheus exposition -> {args.prom_out}")
    bad = _incomplete(finish, outs)
    if bad:
        raise SystemExit(f"[serve] {len(bad)} of {len(prompts)} requests "
                         f"did not complete: " + "; ".join(bad))
    return {"cfg": cfg, "params": params, "prompts": prompts,
            "outputs": outs, "wall_s": wall, "replicas": replicas}


if __name__ == "__main__":
    main()
