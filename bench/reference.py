"""The plain reference: seeded weights and a float32 forward pass for the
dense decoder the benchmark runs, written from the published model
description and independent of the program under test.

The block is internlm2's (arXiv:2403.17297): RMSNorm, rotate-half RoPE,
grouped-query attention with no biases, SwiGLU MLP, untied output head.  A
configuration file that asks for another block is refused.

The weights are made here, from the seed, in the dtype they are served in,
in one jitted call on the device.  The benchmark hands them to the program
through ``serving_adapter``; the reference makes them again from the seed
after the program has been freed, so it takes nothing the program made.

The forward pass runs one layer at a time inside ``lax.scan``, casting that
layer's weights to float32, under ``default_matmul_precision("highest")``
so that a TPU does not run float32 matmuls in bfloat16 passes.  The control
(``fp8=True``) is the same pass with every matmul operand rounded to
float8 e4m3 under a per-tensor (weights) or per-row (activations) scale:
the precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Spec:
    """The shape of one configuration, read from its file under
    ``bench/configs/``."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    rope_theta: float
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, conf: dict) -> "Spec":
        m = conf["model"]
        block = (m["mlp"], m["norm"], m["bias"], m["tie_word_embeddings"])
        if block != ("swiglu", "rmsnorm", False, False):
            raise ValueError(f"{conf['name']}: the reference has no block "
                             f"with (mlp, norm, bias, tied) = {block}")
        return cls(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m["head_dim"], d_ff=m["intermediate_size"],
                   vocab=m["vocab_size"], eps=m["norm_eps"],
                   rope_theta=m["rope_theta"], dtype=m["dtype"])


def _layer_shapes(s: Spec):
    d, hd = s.d_model, s.head_dim
    return {"wq": (d, s.heads * hd), "wk": (d, s.kv_heads * hd),
            "wv": (d, s.kv_heads * hd), "wo": (s.heads * hd, d),
            "ln1_w": (d,), "ln2_w": (d,), "w_gate": (d, s.d_ff),
            "w_up": (d, s.d_ff), "w_down": (s.d_ff, d)}


def _init(key, s: Spec):
    dt = jnp.dtype(s.dtype)
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(keys), shape, F32)
                ).astype(dt)

    layers = {}
    for name, shape in _layer_shapes(s).items():
        full = (s.layers,) + shape
        if len(shape) == 1:
            layers[name] = normal(full, 0.1, 1.0)        # norm scales
        else:
            layers[name] = normal(full, 1.0 / math.sqrt(shape[0]))
    return {"embed": normal((s.vocab, s.d_model), 1.0 / math.sqrt(s.d_model)),
            "final_w": normal((s.d_model,), 0.1, 1.0), "layers": layers,
            "head": normal((s.d_model, s.vocab), 1.0 / math.sqrt(s.d_model))}


@functools.lru_cache(maxsize=None)
def _init_jit(s: Spec):
    return jax.jit(functools.partial(_init, s=s))


def make_weights(s: Spec, seed: int):
    """Every weight of the model, made from ``seed`` in one jitted call, in
    the dtype it is served in."""
    return _init_jit(s)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number: its low 32 bits make
    the key and the rest are folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def param_count(s: Spec) -> int:
    n = sum(math.prod((s.layers,) + sh) for sh in _layer_shapes(s).values())
    return n + 2 * s.vocab * s.d_model + s.d_model


# ----------------------------------------------------------------------
# forward
def _q8(x, axis):
    """Round to float8 e4m3 under a scale that maps the amax over ``axis``
    to the format's largest value; back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, None)
    return x @ w


def _norm(x, w, s: Spec):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + s.eps) * w


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1; x (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, s: Spec, fp8: bool):
    B, S, _ = x.shape
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    p = {k: v.astype(F32) for k, v in p.items()}
    h = _norm(x, p["ln1_w"], s)
    q = _rope(_mm(h, p["wq"], fp8).reshape(B, S, H, hd), s.rope_theta)
    k = _rope(_mm(h, p["wk"], fp8).reshape(B, S, KV, hd), s.rope_theta)
    v = _mm(h, p["wv"], fp8).reshape(B, S, KV, hd)
    q = q.reshape(B, S, KV, H // KV, hd)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
    if fp8:
        pr = _q8(pr, -1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", pr, v).reshape(B, S, H * hd)
    x = x + _mm(o, p["wo"], fp8)
    h = _norm(x, p["ln2_w"], s)
    f = jax.nn.silu(_mm(h, p["w_gate"], fp8)) * _mm(h, p["w_up"], fp8)
    return x + _mm(f, p["w_down"], fp8)


def _logits_at(w, tokens, at, *, s: Spec, fp8: bool):
    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda c, p: (_layer(c, p, s, fp8), None), x,
                        w["layers"])
    x = jnp.take_along_axis(x, at[..., None], axis=1)          # (B, T, d)
    x = _norm(x, w["final_w"].astype(F32), s)
    return _mm(x, w["head"].astype(F32), fp8)


_logits_at_jit = jax.jit(_logits_at, static_argnames=("s", "fp8"))


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def _gaps(w, tokens, at, served, *, s: Spec, fp8: bool):
    """Per position: how far the reference logit of ``served`` lies below
    the reference's best, and (with ``fp8``) the same for the token the
    float8 control puts first."""
    ref = _logits_at(w, tokens, at, s=s, fp8=False)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    if not fp8:
        return gap, jnp.zeros_like(gap)
    ctl = _logits_at(w, tokens, at, s=s, fp8=True).argmax(-1)
    return gap, best - jnp.take_along_axis(ref, ctl[..., None], -1)[..., 0]


def logits_at(w, s: Spec, tokens, at, fp8: bool = False):
    """float32 logits ``(B, T, vocab)`` of ``tokens (B, S)`` at positions
    ``at (B, T)`` (the logits there predict the token after)."""
    with jax.default_matmul_precision("highest"):
        return _logits_at_jit(w, jnp.asarray(tokens, jnp.int32),
                              jnp.asarray(at, jnp.int32), s=s, fp8=fp8)


def served_gaps(w, s: Spec, prompts, outputs, control: bool = False,
                rows_per_call: int = 4, pad_multiple: int = 128):
    """Teacher-forced gaps of served greedy tokens against the reference.

    ``outputs[i]`` are the tokens served after ``prompts[i]`` (the first
    sampled from the prefill).  The reference runs once over ``prompt ++
    outputs[:-1]``; at the position that predicts served token t it reads
    ``max_v ref[v] - ref[t]``: 0 where the served token is the reference's
    best.  Returns per request an array of the program's gaps and, with
    ``control``, one of the float8 control's.  Requests are run a few at a
    time, padded to a multiple of ``pad_multiple``, so that it fits."""
    gaps, ctl = [], []
    order = sorted(range(len(prompts)),
                   key=lambda i: len(prompts[i]) + len(outputs[i]))
    res = {}
    for c in range(0, len(order), rows_per_call):
        idx = order[c:c + rows_per_call]
        seqs = [np.concatenate([np.asarray(prompts[i], np.int32),
                                np.asarray(outputs[i][:-1], np.int32)])
                for i in idx]
        L = -(-max(len(q) for q in seqs) // pad_multiple) * pad_multiple
        T = max(len(outputs[i]) for i in idx)
        tokens = np.zeros((len(idx), L), np.int32)
        at = np.zeros((len(idx), T), np.int32)
        served = np.zeros((len(idx), T), np.int32)
        for r, (i, q) in enumerate(zip(idx, seqs)):
            n = len(outputs[i])
            tokens[r, :len(q)] = q
            at[r] = len(prompts[i]) - 1 + np.minimum(np.arange(T), n - 1)
            served[r, :n] = outputs[i]
            served[r, n:] = outputs[i][-1]
        with jax.default_matmul_precision("highest"):
            g, cg = _gaps(w, jnp.asarray(tokens), jnp.asarray(at),
                          jnp.asarray(served), s=s, fp8=control)
        g, cg = np.asarray(g), np.asarray(cg)
        for r, i in enumerate(idx):
            n = len(outputs[i])
            res[i] = (g[r, :n], cg[r, :n])
    for i in range(len(prompts)):
        gaps.append(res[i][0])
        ctl.append(res[i][1])
    return (gaps, ctl) if control else gaps
