"""Dry-run core: lower + compile every (arch x shape) cell on a production
mesh, extract memory/cost analysis and the collective schedule, and emit the
roofline terms.  No device buffers are ever allocated (ShapeDtypeStruct in,
AOT-compiled artifact out).

Import order note: this module must be imported AFTER the process has set
XLA_FLAGS (dryrun.py does that in its first two lines).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ArchConfig, SHAPE_BY_NAME, ScanGroup, ShapeCase
from repro.core import flags
from repro.core.sharding import ShardingCtx, _rules, use_sharding
from repro.launch import steps as steps_mod
from repro.models import api
from repro.optim import adamw_init

# TPU v5e constants (per chip)
HW = dict(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)

LONG_CONTEXT_ARCHS = ("falcon-mamba-7b", "recurrentgemma-2b", "gemma3-4b")

# gradient-accumulation steps for train_4k so activations fit 16 GB HBM
# (memory_analysis-driven; see EXPERIMENTS.md §Dry-run)
TRAIN_ACCUM = {
    "starcoder2-3b": 4, "gemma3-4b": 4, "internlm2-1.8b": 2, "gemma-7b": 4,
    "whisper-base": 1, "internvl2-1b": 2, "recurrentgemma-2b": 4,
    "deepseek-v2-lite-16b": 8, "qwen3-moe-30b-a3b": 16, "falcon-mamba-7b": 8,
}


def cell_applicable(arch: str, shape_name: str) -> Tuple[bool, str]:
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False, ("pure full-attention KV at 500k tokens is quadratic-"
                       "prefill / unbounded-cache; run only for SSM/hybrid/"
                       "mostly-local archs (DESIGN.md §5)")
    return True, ""


# ----------------------------------------------------------------------
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([0-9,]+)\]<=\[")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}


def _buf_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> List[dict]:
    """Per-device wire-byte estimates for every collective in the compiled
    module.  Result shapes in partitioned HLO are per-shard."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        buf = _buf_bytes(type_str)
        g = 1
        mi = _GROUPS_IOTA_RE.search(line)
        if mi:
            g = int(mi.group(1).split(",")[-1])
        else:
            ml = _GROUPS_LIST_RE.search(line)
            if ml:
                g = len(ml.group(1).split(","))
        if op == "all-reduce":
            wire = 2 * buf * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            wire = buf * (g - 1)                  # result is the shard
        elif op == "all-gather":
            wire = buf * (g - 1) / max(g, 1)      # result is gathered buf
        elif op == "all-to-all":
            wire = buf * (g - 1) / max(g, 1)
        else:                                      # collective-permute
            wire = buf
        out.append(dict(op=op, buf_bytes=buf, group=g, wire_bytes=wire))
    return out


# ----------------------------------------------------------------------
def model_param_counts(cfg: ArchConfig) -> Dict[str, float]:
    params_abs, axes = api.abstract_params(cfg)
    leaves = jax.tree_util.tree_leaves(params_abs)
    ax_leaves = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda t: isinstance(t, tuple) and all(
            a is None or isinstance(a, str) for a in t))
    total = sum(int(np.prod(l.shape)) for l in leaves)
    expert = sum(int(np.prod(l.shape)) for l, a in zip(leaves, ax_leaves)
                 if "experts" in a)
    embed = 0
    for l in leaves:
        if l.shape and cfg.vocab in l.shape:
            embed += int(np.prod(l.shape))
    active = total - expert
    if cfg.n_experts:
        active += expert * cfg.top_k / cfg.n_experts
    return dict(total=total, active=active, experts=expert, embed=embed)


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    policy: str
    ok: bool
    skipped: bool = False
    reason: str = ""
    lower_s: float = 0.0
    compile_s: float = 0.0
    flops_dev: float = 0.0
    bytes_dev: float = 0.0
    coll_wire_bytes_dev: float = 0.0
    n_collectives: int = 0
    coll_by_op: Dict[str, float] = dataclasses.field(default_factory=dict)
    arg_bytes_dev: int = 0
    out_bytes_dev: int = 0
    temp_bytes_dev: int = 0
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    dominant: str = ""
    model_flops_dev: float = 0.0
    useful_ratio: float = 0.0
    params_total: float = 0.0
    params_active: float = 0.0
    error: str = ""

    def to_json(self):
        return dataclasses.asdict(self)


# ----------------------------------------------------------------------
def build_cell(cfg: ArchConfig, sc: ShapeCase, mesh: Mesh, policy: str,
               accum_steps: int = 1):
    """Returns (fn, args, in_shardings, out_shardings, donate, act_rules)."""
    n_data = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                          if a in ("pod", "data")]))
    rules = dict(_rules(policy, mesh.axis_names))
    if sc.global_batch < n_data:
        rules["batch"] = None                      # don't shard tiny batch
    ctx = ShardingCtx(mesh, policy, rules)

    params_abs, axes = api.abstract_params(cfg)
    param_sh = steps_mod.shardings_like(axes, ctx)
    repl = NamedSharding(mesh, P())

    def bsh(nd):
        data_axes = rules.get("batch")
        return NamedSharding(mesh, P(data_axes, *([None] * (nd - 1))))

    batch_abs = api.input_specs(cfg, "train" if sc.kind != "decode" else "decode",
                                sc.global_batch, sc.seq_len)
    batch_sh = {k: bsh(len(v.shape)) for k, v in batch_abs.items()}

    if sc.kind == "train":
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        opt_sh = steps_mod.opt_shardings(param_sh)
        step = steps_mod.make_train_step(cfg, accum_steps=accum_steps)
        metric_sh = {k: repl for k in ("loss", "ce", "aux", "grad_norm", "lr")}
        return (step, (params_abs, opt_abs, batch_abs),
                (param_sh, opt_sh, batch_sh),
                (param_sh, opt_sh, metric_sh), (0, 1), rules)

    max_len = sc.seq_len
    caches_abs = jax.eval_shape(
        lambda: api.init_caches(cfg, sc.global_batch, max_len,
                                enc_len=sc.seq_len))
    # caches are seq-sharded over `model` for BOTH prefill (written) and
    # decode (read): one layout end-to-end, no reshard between phases
    cache_sh = steps_mod.cache_specs(cfg, mesh, max_len, sc.global_batch,
                                     policy, shard_seq=True)
    logits_sh = NamedSharding(mesh, P(rules.get("batch"), None,
                                      rules.get("vocab")))
    if sc.kind == "prefill":
        step = steps_mod.make_prefill_step(cfg, max_len)
    else:
        step = steps_mod.make_decode_step(cfg)
    return (step, (params_abs, batch_abs, caches_abs),
            (param_sh, batch_sh, cache_sh),
            (logits_sh, cache_sh), (2,), rules)


def depth_samples(cfg: ArchConfig):
    """Depth-reduced configs for the cost pass.

    XLA's cost_analysis counts scan bodies once, so costs are extracted from
    UNROLLED depth-1/depth-2 variants (full shapes) and extrapolated:
      cost(full) = cost(base) + sum_g (R_g - 1) * (cost(sample_g) - cost(base)).
    Exact because per-layer cost within a group is shape-identical.
    """
    if cfg.family == "encdec":
        base = cfg.replace(enc_layers=1, dec_layers=1, n_layers=2,
                           scan_layers=False, groups=())
        samples = []
        if cfg.enc_layers > 1:
            samples.append((cfg.replace(enc_layers=2, dec_layers=1, n_layers=3,
                                        scan_layers=False, groups=()),
                            cfg.enc_layers - 1))
        if cfg.dec_layers > 1:
            samples.append((cfg.replace(enc_layers=1, dec_layers=2, n_layers=3,
                                        scan_layers=False, groups=()),
                            cfg.dec_layers - 1))
        return base, samples

    def with_repeats(reps):
        gs = tuple(ScanGroup(g.pattern, r) for g, r in zip(cfg.groups, reps))
        return cfg.replace(groups=gs, n_layers=sum(g.n_layers for g in gs),
                           scan_layers=False)

    ones = [1] * len(cfg.groups)
    base = with_repeats(ones)
    samples = []
    for gi, g in enumerate(cfg.groups):
        if g.repeats > 1:
            reps = list(ones)
            reps[gi] = 2
            samples.append((with_repeats(reps), g.repeats - 1))
    return base, samples


def _compile_cell(cfg, sc, mesh, policy, accum_steps: int = 1):
    fn, args, in_sh, out_sh, donate, rules = build_cell(
        cfg, sc, mesh, policy, accum_steps=accum_steps)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    with use_sharding(mesh, policy, rules=rules):
        lowered = jitted.lower(*args)
    return lowered.compile()


def _extract_cost(compiled) -> Dict[str, Any]:
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    by_op: Dict[str, float] = {}
    for c in colls:
        by_op[c["op"]] = by_op.get(c["op"], 0.0) + c["wire_bytes"]
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)),
                wire=float(sum(c["wire_bytes"] for c in colls)),
                ncoll=float(len(colls)), by_op=by_op)


def cost_pass(cfg: ArchConfig, sc: ShapeCase, mesh: Mesh, policy: str):
    """Corrected per-device cost via unrolled depth minis + extrapolation."""
    base_cfg, samples = depth_samples(cfg)
    flags.COST_MODE = True
    try:
        base = _extract_cost(_compile_cell(base_cfg, sc, mesh, policy))
        total = dict(base)
        total["by_op"] = dict(base["by_op"])
        for cfg_s, extra in samples:
            s = _extract_cost(_compile_cell(cfg_s, sc, mesh, policy))
            for k in ("flops", "bytes", "wire", "ncoll"):
                total[k] += extra * max(s[k] - base[k], 0.0)
            for op in set(s["by_op"]) | set(base["by_op"]):
                delta = s["by_op"].get(op, 0.0) - base["by_op"].get(op, 0.0)
                total["by_op"][op] = (total["by_op"].get(op, 0.0)
                                      + extra * max(delta, 0.0))
    finally:
        flags.COST_MODE = False
    return total


def run_cell(arch: str, shape_name: str, mesh: Mesh, policy: Optional[str] = None,
             cfg_override=None, skip_memory_pass: bool = False,
             skip_cost_pass: bool = False) -> CellResult:
    sc = SHAPE_BY_NAME[shape_name]
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    ok, reason = cell_applicable(arch, shape_name)
    policy = policy or ("fsdp_tp" if sc.kind == "train" else "tp")
    res = CellResult(arch=arch, shape=shape_name, mesh=mesh_name,
                     policy=policy, ok=False)
    if not ok:
        res.skipped = True
        res.reason = reason
        res.ok = True
        return res

    cfg = get_config(arch)
    cfg = cfg.replace(remat="full" if sc.kind == "train" else "none")
    if cfg_override:
        cfg = cfg.replace(**cfg_override)
    counts = model_param_counts(cfg)
    res.params_total, res.params_active = counts["total"], counts["active"]

    try:
        # ---- cost pass: unrolled depth minis, extrapolated
        if not skip_cost_pass:
            t0 = time.perf_counter()
            cost = cost_pass(cfg, sc, mesh, policy)
            res.lower_s = time.perf_counter() - t0
            res.flops_dev = cost["flops"]
            res.bytes_dev = cost["bytes"]
            res.coll_wire_bytes_dev = cost["wire"]
            res.n_collectives = int(cost["ncoll"])
            res.coll_by_op = cost["by_op"]

        # ---- memory/compile pass: production (scanned) config; train cells
        # use gradient accumulation to fit HBM (cost is accum-invariant)
        if not skip_memory_pass:
            accum = TRAIN_ACCUM.get(arch, 1) if sc.kind == "train" else 1
            t0 = time.perf_counter()
            compiled = _compile_cell(cfg, sc, mesh, policy, accum_steps=accum)
            res.compile_s = time.perf_counter() - t0
            res.policy = policy + (f"+accum{accum}" if accum > 1 else "")
            ma = compiled.memory_analysis()
            if ma is not None:
                res.arg_bytes_dev = int(ma.argument_size_in_bytes)
                res.out_bytes_dev = int(ma.output_size_in_bytes)
                res.temp_bytes_dev = int(ma.temp_size_in_bytes)

        # ---- roofline terms (per chip, seconds)
        res.t_compute = res.flops_dev / HW["peak_flops"]
        res.t_memory = res.bytes_dev / HW["hbm_bw"]
        res.t_collective = res.coll_wire_bytes_dev / HW["ici_bw"]
        res.dominant = max(
            [("compute", res.t_compute), ("memory", res.t_memory),
             ("collective", res.t_collective)], key=lambda kv: kv[1])[0]

        # ---- useful-FLOPs ratio
        n_chips = mesh.devices.size
        tokens = sc.global_batch * (sc.seq_len if sc.kind != "decode" else 1)
        mult = 6 if sc.kind == "train" else 2
        res.model_flops_dev = mult * counts["active"] * tokens / n_chips
        res.useful_ratio = (res.model_flops_dev / res.flops_dev
                            if res.flops_dev else 0.0)
        res.ok = True
    except Exception as e:  # noqa: BLE001 — report per-cell failures
        res.error = f"{type(e).__name__}: {e}"[:2000]
        res.ok = False
    return res


def save_result(res: CellResult, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    base_policy = res.policy.split("+")[0]
    name = f"{res.arch}__{res.shape}__{res.mesh}__{base_policy}.json"
    path = os.path.join(out_dir, name)
    d = res.to_json()
    # memory-only re-runs (skip_cost) merge into existing cost numbers
    if res.ok and not res.skipped and res.flops_dev == 0 and os.path.exists(path):
        old = json.load(open(path))
        for k in ("flops_dev", "bytes_dev", "coll_wire_bytes_dev",
                  "n_collectives", "coll_by_op", "t_compute", "t_memory",
                  "t_collective", "dominant", "model_flops_dev",
                  "useful_ratio", "lower_s"):
            d[k] = old.get(k, d[k])
    with open(path, "w") as f:
        json.dump(d, f, indent=1)
