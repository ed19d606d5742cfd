"""The plain reference's shared library: what every block's reference
(``bench/blocks/<block>.py``) reuses, written from the published model
descriptions and independent of the program under test.

* ``seed_key``: the PRNG key a block makes its weights from.
* ``q8`` and ``mm``: the float8 control.  With ``fp8`` every matmul
  operand is rounded to float8 e4m3 under a per-tensor (weights) or per-row
  (activations) scale: the precision below the bfloat16 the configurations
  state.
* ``rms_norm`` and ``rope`` (rotate-half).
* ``logits_at`` and ``served_gaps``: a block's logits function run
  teacher-forced over the served tokens, a few requests at a time, under
  ``default_matmul_precision("highest")`` so that a TPU does not run float32
  matmuls in bfloat16 passes, and the gap of each served token.

A block's logits function is ``logits(w, tokens, at, *, s, fp8)``: float32
logits ``(B, T, vocab)`` of ``tokens (B, S)`` at positions ``at (B, T)``,
one layer at a time, from weights ``w`` of spec ``s``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number: its low 32 bits make
    the key and the rest are folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def q8(x, axis):
    """Round to float8 e4m3 under a scale that maps the amax over ``axis``
    to the format's largest value; back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x, w, fp8: bool):
    if fp8:
        x, w = q8(x, -1), q8(w, None)
    return x @ w


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1; x (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("logits", "s", "fp8"))
def _logits_at(w, tokens, at, *, logits, s, fp8: bool):
    return logits(w, tokens, at, s=s, fp8=fp8)


@functools.partial(jax.jit, static_argnames=("logits", "s", "fp8"))
def _gaps(w, tokens, at, served, *, logits, s, fp8: bool):
    """Per position: how far the reference logit of ``served`` lies below
    the reference's best, and (with ``fp8``) the same for the token the
    float8 control puts first."""
    ref = logits(w, tokens, at, s=s, fp8=False)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    if not fp8:
        return gap, jnp.zeros_like(gap)
    ctl = logits(w, tokens, at, s=s, fp8=True).argmax(-1)
    return gap, best - jnp.take_along_axis(ref, ctl[..., None], -1)[..., 0]


def logits_at(logits, w, s, tokens, at, fp8: bool = False):
    """float32 logits ``(B, T, vocab)`` of ``tokens (B, S)`` at positions
    ``at (B, T)`` (the logits there predict the token after), by the block
    logits function ``logits``."""
    with jax.default_matmul_precision("highest"):
        return _logits_at(w, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(at, jnp.int32), logits=logits, s=s,
                          fp8=fp8)


def served_gaps(logits, w, s, prompts, outputs, control: bool = False,
                rows_per_call: int = 4, pad_multiple: int = 128):
    """Teacher-forced gaps of served greedy tokens against the reference
    that the block logits function ``logits`` computes.

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
                          jnp.asarray(served), logits=logits, s=s,
                          fp8=control)
        g, cg = np.asarray(g), np.asarray(cg)
        for r, i in enumerate(idx):
            n = len(outputs[i])
            res[i] = (g[r, :n], cg[r, :n])
    for i in range(len(prompts)):
        gaps.append(res[i][0])
        ctl.append(res[i][1])
    return (gaps, ctl) if control else gaps
