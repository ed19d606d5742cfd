"""Logical-axis sharding: the TPU translation of the paper's "broadcast
variable" (§3.1) and of its Conclusion's "give each node a portion of the
trained model".

Every parameter is initialized together with a tuple of *logical* axis names
(``"embed"``, ``"ff"``, ``"heads"``, ``"experts"``, ...).  A
:class:`ShardingPolicy` maps logical names to physical mesh axes:

  * ``broadcast`` — the paper-faithful placement: weights fully replicated on
    every chip (Spark broadcast variable), data sharded over all data axes.
  * ``tp``        — tensor-parallel serving: ff/heads/vocab/experts split over
    the ``model`` axis, replicated over ``data`` (beyond-paper).
  * ``fsdp_tp``   — training placement: tp + parameter/optimizer state sharded
    over the ``data`` (and ``pod``) axes, ZeRO-3 style (beyond-paper).

Models call :func:`shard` on activations at strategic points; between those
constraints GSPMD propagates shardings and inserts collectives.
"""
from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class Param:
    """A parameter leaf paired with its logical axes (init-time only).

    Registered as a pytree node with ``axes`` as static aux data, so Param
    trees flow through eval_shape / tree_map / jit with only the array value
    as a traced leaf.
    """
    __slots__ = ("value", "axes")

    def __init__(self, value, axes: Tuple[Optional[str], ...]):
        self.value = value
        self.axes = tuple(axes)

    def __repr__(self):
        return f"Param({getattr(self.value, 'shape', self.value)}, {self.axes})"


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.axes),
    lambda axes, ch: Param(ch[0], axes),
)


def param_leaf(x) -> bool:
    return isinstance(x, Param)


def split_params(tree):
    """Split a tree of :class:`Param` into (values, logical_axes) trees."""
    values = jax.tree_util.tree_map(lambda p: p.value, tree, is_leaf=param_leaf)
    axes = jax.tree_util.tree_map(lambda p: p.axes, tree, is_leaf=param_leaf)
    return values, axes


# ----------------------------------------------------------------------
# Policies: logical axis -> mesh axis (or tuple of mesh axes).

_BATCH_AXES_1POD = ("data",)
_BATCH_AXES_2POD = ("pod", "data")


def _rules(policy: str, mesh_axes: Tuple[str, ...]):
    data_axes = tuple(a for a in ("pod", "data") if a in mesh_axes)
    model = "model" if "model" in mesh_axes else None
    if policy == "broadcast":          # paper-faithful: full replication,
        # instances data-parallel over EVERY chip (the Spark worker pool)
        return {"batch": data_axes + ((model,) if model else ())}
    if policy == "tp":                 # serving: shard the model, replicate over data
        return {
            "batch": data_axes,
            "ff": model, "heads": model, "vocab": model,
            "experts": model, "inner": model, "lru": model,
            # kv heads replicated: they rarely divide the model axis and the
            # K/V activations are small; q heads carry the TP split
        }
    if policy == "fsdp_tp":            # training: tp + ZeRO-3 over data axes
        return {
            "batch": data_axes,
            "ff": model, "heads": model, "vocab": model,
            "experts": model, "inner": model, "lru": model,
            "embed": data_axes,        # fully-sharded params/opt state
        }
    if policy == "seqtp":              # context-parallel serving: weights
        # replicated (paper's broadcast), the SEQUENCE dim takes the model
        # axis — per-layer activation all-reduces disappear; only attention
        # exchanges K/V (beyond-paper; see EXPERIMENTS.md §Perf)
        return {"batch": data_axes, "seq": model}
    raise ValueError(f"unknown policy {policy!r}")


class ShardingCtx(NamedTuple):
    mesh: Mesh
    policy: str
    rules: dict

    def spec_for(self, logical_axes: Tuple[Optional[str], ...]) -> P:
        parts, used = [], set()
        for ax in logical_axes:
            m = self.rules.get(ax)
            if m is None:
                parts.append(None)
                continue
            ms = (m,) if isinstance(m, str) else tuple(m)
            ms = tuple(a for a in ms if a not in used)
            used.update(ms)
            parts.append(ms[0] if len(ms) == 1 else (ms if ms else None))
            if not ms:
                parts[-1] = None
        return P(*parts)

    def sharding_for(self, logical_axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes))


_local = threading.local()


def current_ctx() -> Optional[ShardingCtx]:
    return getattr(_local, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], policy: str = "broadcast", rules=None):
    prev = current_ctx()
    if mesh is None:
        _local.ctx = None
    else:
        _local.ctx = ShardingCtx(
            mesh, policy,
            rules if rules is not None else _rules(policy, mesh.axis_names))
    try:
        yield _local.ctx
    finally:
        _local.ctx = prev


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Constrain an activation's sharding; no-op outside a sharding context."""
    ctx = current_ctx()
    if ctx is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"axes {logical_axes} vs rank {x.ndim}")
    return jax.lax.with_sharding_constraint(x, ctx.sharding_for(logical_axes))


def param_shardings(axes_tree, ctx: Optional[ShardingCtx] = None):
    """Tree of NamedShardings for a logical-axes tree (init/checkpoint use)."""
    ctx = ctx or current_ctx()
    if ctx is None:
        return None
    is_axes = lambda t: isinstance(t, tuple) and all(a is None or isinstance(a, str) for a in t)
    return jax.tree_util.tree_map(lambda ax: ctx.sharding_for(ax), axes_tree, is_leaf=is_axes)


def batch_spec(ctx: Optional[ShardingCtx], extra_dims: int = 1) -> P:
    """PartitionSpec for (batch, ...) activations/inputs."""
    if ctx is None:
        return P()
    m = ctx.rules.get("batch") or ()
    first = m if len(m) > 1 else (m[0] if m else None)
    return P(first, *([None] * extra_dims))
