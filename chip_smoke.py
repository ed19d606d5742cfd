#!/usr/bin/env python3
"""Smoke test of the serving main path on a TPU, at internlm2-1.8b's
published widths (24 layers, d_model 2048, 16 query / 8 kv heads of 128,
d_ff 8192, vocab 92544; bf16, random weights from a seed).

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip, in order:
  (a) device — the default backend must be a TPU; there is no CPU
      fallback.
  (b) dense fused serve — ``repro.launch.serve.main`` at published width,
      greedy, 8 seeded prompts of 32-256 tokens, 32 new tokens each.
  (c) paged serve — the same prompts through ``Engine`` with
      ``use_kernels=True`` and ``ServeConfig(paged=True)``: the Pallas paged
      decode/extend kernels run compiled (the lowered decode loop must hold
      a ``tpu_custom_call``).
  Both serves are checked teacher-forced against the float32 reference
  forward (``repro.models.reference``).

Four chips, instead:
  * the paper's two-phase pipeline (``core.pipeline.make_batch_step``) over
    a 4-device data mesh, against the single-shard step on the same rows;
  * ``Router`` over four internlm2-1.8b replicas, one per chip, through
    ``serve.main --replicas 4``, teacher-forced like (b).

Any failure exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.models.reference import teacher_forced_margins  # noqa: E402

# A served greedy token passes when its reference logit is within
# MARGIN_TOL reference standard deviations (over the vocabulary, at that
# position) of the reference maximum.  bf16 weights and activations move
# logits by a few hundredths of a deviation, so a near-tie can flip the
# argmax by about that much; a wrong token sits about four deviations
# below the maximum of a 92544-way draw.
MARGIN_TOL = 0.25
SEED = 0                             # prompts, weights and pipeline rows


@dataclasses.dataclass(frozen=True)
class Sizes:
    requests: int = 8
    min_prompt: int = 32
    max_prompt: int = 256
    max_new: int = 32
    slots: int = 8
    max_len: int = 1024
    block_size: int = 16
    reduced: bool = False            # the CPU preset (tests only)

    def serve_argv(self, seed: int):
        argv = ["--requests", str(self.requests),
                "--min-prompt", str(self.min_prompt),
                "--max-prompt", str(self.max_prompt),
                "--max-new", str(self.max_new), "--slots", str(self.slots),
                "--max-len", str(self.max_len), "--seed", str(seed)]
        return argv + (["--reduced"] if self.reduced else [])


class CompileClock:
    """Seconds JAX spends lowering and compiling (summed over threads),
    read from its monitoring events.  Tracing is left out: a nested jit's
    trace is also inside its caller's, so its events overlap."""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def phase_device():
    """(a) The default backend must be a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{d.platform!r} ({d.device_kind}); there is no "
                         f"CPU fallback")
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _check_complete(label, outputs, max_new):
    short = [i for i, o in enumerate(outputs)
             if not isinstance(o, list) or len(o) != max_new + 1]
    if short:
        raise SystemExit(f"[{label}] requests {short} did not finish with "
                         f"{max_new} new tokens")


def _report(label, n_tokens, wall_s, compile_s):
    kind = jax.devices()[0].device_kind
    print(f"[{label}] {n_tokens} tokens in {wall_s:.3f} s wall, "
          f"{compile_s:.3f} s of it lowering+compiling; "
          f"{n_tokens / wall_s:.1f} tok/s on {kind}")


def phase_dense(sizes: Sizes, seed: int, clock: CompileClock, extra=(),
                label="dense"):
    """(b) Dense fused serve through the normal entry point."""
    c0, t0 = clock.seconds, time.perf_counter()
    res = serve.main(sizes.serve_argv(seed) + list(extra))
    wall = time.perf_counter() - t0
    _check_complete(label, res["outputs"], sizes.max_new)
    _report(label, sum(len(o) for o in res["outputs"]), wall,
            clock.seconds - c0)
    return res


def phase_paged(cfg, params, prompts, sizes: Sizes, clock: CompileClock):
    """(c) Paged serve with the Pallas paged kernels, cold and then warm (a
    fresh engine, empty prefix cache, the cold one's compiled functions);
    both must serve the same tokens.  Returns the outputs."""
    from repro.serving import Engine, ServeConfig
    kcfg = cfg.replace(use_kernels=True)
    scfg = ServeConfig(max_len=sizes.max_len, slots=sizes.slots,
                       paged=True, block_size=sizes.block_size)
    outputs, fns = None, None
    for run in ("cold", "warm"):
        c0, t0 = clock.seconds, time.perf_counter()
        eng = Engine(params, kcfg, scfg, shared_fns=fns)
        if not eng.paged:
            raise SystemExit(f"[paged] {cfg.name} fell back to the dense "
                             f"cache")
        reqs = [eng.submit(p, max_new=sizes.max_new) for p in prompts]
        eng.run_until_drained()
        wall = time.perf_counter() - t0
        outs = [r.out_tokens for r in reqs]
        _check_complete("paged", outs, sizes.max_new)
        _report(f"paged/{run}", sum(len(o) for o in outs), wall,
                clock.seconds - c0)
        if outputs is not None and outs != outputs:
            raise SystemExit("[paged] the warm run served other tokens "
                             "than the cold run")
        outputs, fns = outs, eng.fns
    has_kernel = paged_loop_has_kernel(eng)
    print(f"[paged] decode-loop HLO holds tpu_custom_call: {has_kernel}")
    if jax.default_backend() == "tpu" and not has_kernel:
        raise SystemExit("[paged] the paged decode loop lowered without "
                         "a compiled Pallas kernel")
    return outputs


def paged_loop_has_kernel(eng) -> bool:
    """Lower the engine's paged decode loop at its state's shapes and look
    for the Mosaic custom call a compiled Pallas kernel lowers to."""
    import jax.numpy as jnp
    text = eng.fns.paged_decode_loop.lower(
        eng.params, jnp.asarray(eng._bt), eng.caches, eng._pos, eng._last,
        eng._active, eng._remaining, eng._rng).as_text()
    return "tpu_custom_call" in text


def check_outputs(label, params, cfg, prompts, outputs,
                  tol: float = MARGIN_TOL) -> float:
    """Teacher-forced check of served tokens against the float32
    reference; raises SystemExit past ``tol``.  Returns the worst margin."""
    margins = teacher_forced_margins(params, cfg, prompts, outputs)
    worst = float(max(m.max() for m in margins))
    n_pos = sum(len(m) for m in margins)
    n_top = int(sum((m == 0).sum() for m in margins))
    print(f"[{label}] teacher-forced vs float32 reference: worst margin "
          f"{worst:.4f} std (tolerance {tol}); {n_top}/{n_pos} tokens are "
          f"the reference argmax")
    if worst > tol:
        raise SystemExit(f"[{label}] teacher-forced check failed: a served "
                         f"token is {worst:.4f} reference std below the "
                         f"reference maximum (tolerance {tol})")
    return worst


def token_agreement(a, b) -> float:
    """Share of positions where two servings emitted the same token."""
    same = sum(int(x == y) for oa, ob in zip(a, b) for x, y in zip(oa, ob))
    return same / max(sum(len(o) for o in a), 1)


def phase_pipeline(seed: int, rows_per_shard: int = 256):
    """The two-phase pipeline over a data mesh of every device, against
    the single-shard step on the same seeded rows."""
    import jax.numpy as jnp
    from repro.core.pipeline import (PipelineConfig, extract_links,
                                     init_models, make_batch_step)
    from repro.launch.mesh import make_mesh
    n = len(jax.devices())
    rows = n * rows_per_shard
    # per-shard capacities equal to the rows a shard holds: nothing can be
    # dropped, so the sharded and single-shard steps see the same rows
    pcfg = PipelineConfig(claim_capacity=rows_per_shard,
                          evid_capacity=rows_per_shard, use_pair_kernel=True)
    single = dataclasses.replace(pcfg, claim_capacity=rows,
                                 evid_capacity=rows)
    models, _ = init_models(jax.random.PRNGKey(seed), pcfg)
    # random dual weights: centering them puts the decision boundary inside
    # the data, so phase 1 passes some rows and filters the rest
    for m in ("claim", "evidence"):
        models[m]["alpha"] = models[m]["alpha"] - models[m]["alpha"].mean()
    X = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (rows, pcfg.feat_dim), jnp.float32)
    keys = jnp.arange(rows, dtype=jnp.int32) // 16      # 16-row documents
    mesh = make_mesh((n,), ("data",))
    with jax.default_matmul_precision("highest"):
        out_m = make_batch_step(pcfg, mesh)(models, X, keys)
        out_1 = make_batch_step(single)(models, X, keys)
    out_m, out_1 = (jax.tree_util.tree_map(np.asarray, o)
                    for o in (out_m, out_1))
    links_m = {(c, e): s for c, e, s in extract_links(out_m)}
    links_1 = {(c, e): s for c, e, s in extract_links(out_1)}
    drops = int(out_m.n_dropped)
    diff = max((abs(links_m[k] - links_1[k]) for k in links_1
                if k in links_m), default=0.0)
    scale = max((abs(s) for s in links_1.values()), default=1.0)
    n_claims = int((out_1.claim_index >= 0).sum())
    print(f"[pipeline] {n}-device data mesh vs single shard over {rows} "
          f"rows ({n_claims} claims): "
          f"{len(links_m)} vs {len(links_1)} links, n_dropped={drops}, "
          f"max score diff {diff:.3g} (scores up to {scale:.3g})")
    if not links_1 or set(links_m) != set(links_1) or drops \
            or diff > 1e-4 * scale:
        raise SystemExit("[pipeline] the sharded step disagrees with the "
                         "single-shard step")


def phase_replicas(sizes: Sizes, seed: int, clock: CompileClock):
    """Router over one internlm2-1.8b replica per device."""
    n = len(jax.devices())
    try:
        serve.replica_devices(n + 1)
    except ValueError as e:
        print(f"[replicas] {n + 1} replicas refused at start: {e}")
    else:
        raise SystemExit(f"[replicas] {n + 1} replicas on {n} devices "
                         f"were not refused")
    res = phase_dense(sizes, seed, clock, label="replicas",
                      extra=["--replicas", str(n), "--transport", "thread"])
    devices = [r["device"] for r in res["replicas"]]
    print(f"[replicas] served per replica: "
          + ", ".join(f"{r['device']}={r['served']}"
                      for r in res["replicas"]))
    if len(set(devices)) != n or any(r["served"] == 0
                                     for r in res["replicas"]):
        raise SystemExit("[replicas] every replica must serve requests on "
                         "a device of its own")
    check_outputs("replicas", res["params"], res["cfg"], res["prompts"],
                  res["outputs"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip phases (data-mesh pipeline, "
                         "one replica per chip) and nothing else")
    args = ap.parse_args(argv)

    device = phase_device()
    cache_dir = setup_compile_cache()
    print(f"[cache] persistent compile cache: {cache_dir}")
    clock = CompileClock()
    sizes = Sizes()
    if args.four_chips:
        if device["count"] != 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{device['count']}")
        phase_pipeline(SEED)
        phase_replicas(sizes, SEED, clock)
    else:
        res = phase_dense(sizes, SEED, clock)
        cfg, params, prompts = res["cfg"], res["params"], res["prompts"]
        paged = phase_paged(cfg, params, prompts, sizes, clock)
        check_outputs("dense", params, cfg, prompts, res["outputs"])
        check_outputs("paged", params, cfg, prompts, paged)
        print(f"[agree] dense and paged emitted the same token at "
              f"{token_agreement(res['outputs'], paged):.3f} of positions")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
