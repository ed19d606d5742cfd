"""Plain float32 reference forward for dense decoder LMs of the internlm2
kind (full causal GQA attention, RoPE, SwiGLU, RMSNorm), and the
teacher-forced check of served tokens against it.

It shares nothing with the serving path but the parameter tree: no
kernels, no KV cache, no bucketing, no sharding constraints — one layer at
a time in straightforward ``jax.numpy``, every weight cast to float32
inside the layer loop (so a 2B model's float32 copy never exists whole),
under ``jax.default_matmul_precision("highest")`` so a TPU does not run the
float32 matmuls in bfloat16 passes.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def check_supported(cfg) -> None:
    """Raise unless ``cfg`` is a plain dense decoder this reference
    implements exactly."""
    kinds = {k for g in cfg.groups for k in g.pattern}
    unsupported = [name for name, bad in (
        ("layer kinds other than A", kinds != {"A"}),
        ("mlp other than swiglu", cfg.mlp != "swiglu"),
        ("norm other than rmsnorm", cfg.norm != "rmsnorm"
         or cfg.rms_plus_one),
        ("qk-norm", cfg.qk_norm),
        ("soft-capping", bool(cfg.attn_softcap or cfg.logit_softcap)),
        ("embedding scale", cfg.emb_scale)) if bad]
    if unsupported:
        raise NotImplementedError(
            f"{cfg.name}: the reference forward has no {', '.join(unsupported)}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, base):
    """x (B, S, heads, hd); rotate-half RoPE at absolute positions pos."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=F32) * 2.0 / x.shape[-1])
    ang = pos[..., None].astype(F32) * freqs                # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, x, p):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = _rms(x, p["ln1"]["w"], cfg.norm_eps)
    a = p["mixer"]
    q = _rope((h @ a["wq"]).reshape(B, S, H, hd), pos, cfg.rope_base)
    k = _rope((h @ a["wk"]).reshape(B, S, KV, hd), pos, cfg.rope_base)
    v = (h @ a["wv"]).reshape(B, S, KV, hd)
    q = q.reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(B, S, H * hd) @ a["wo"]
    h = _rms(x, p["ln2"]["w"], cfg.norm_eps)
    f = p["ffn"]
    return x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]


def _logits_at(params, tokens, at, *, cfg):
    x = params["embedding"]["table"][tokens].astype(F32)
    def body(c, layers):
        for p in layers:                    # one pattern period
            c = _layer(cfg, c, p)
        return c, None

    for gp in params["groups"]:
        x, _ = jax.lax.scan(body, x, tuple(gp))
    x = jnp.take_along_axis(x, at[..., None], axis=1)           # (B, T, d)
    x = _rms(x, params["final_norm"]["w"].astype(F32), cfg.norm_eps)
    head = (params["embedding"]["table"].T if cfg.tie_embeddings
            else params["lm_head"])
    return (x @ head.astype(F32))[..., :cfg.vocab]


_logits_at_jit = jax.jit(_logits_at, static_argnames=("cfg",))


def reference_logits(params, cfg, tokens, at):
    """float32 logits ``(B, T, vocab)`` of ``tokens (B, S)`` at sequence
    positions ``at (B, T)`` (the logits there predict the token after)."""
    check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        return _logits_at_jit(params, jnp.asarray(tokens, jnp.int32),
                              jnp.asarray(at, jnp.int32), cfg=cfg)


def teacher_forced_margins(params, cfg, prompts: Sequence[np.ndarray],
                           outputs: Sequence[Sequence[int]],
                           pad_multiple: int = 64) -> List[np.ndarray]:
    """Score served greedy tokens against the reference, teacher-forced.

    ``outputs[i]`` are the tokens served after ``prompts[i]`` (the first
    one sampled from the prefill).  The reference runs once over each
    ``prompt ++ outputs[:-1]``; at the position that predicts served token
    t it gives ``(max_v ref[v] - ref[token_t]) / std_v ref[v]`` — 0 where
    the served token is the reference's argmax, and a random token sits
    about four standard deviations below the maximum of a vocabulary-wide
    draw.  Returns one margin array per request."""
    n = len(prompts)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outputs)]
    L = max(len(s) for s in seqs)
    L = -(-L // pad_multiple) * pad_multiple
    T = max(len(o) for o in outputs)
    tokens = np.zeros((n, L), np.int32)
    at = np.zeros((n, T), np.int32)
    for i, (p, o, s) in enumerate(zip(prompts, outputs, seqs)):
        tokens[i, :len(s)] = s
        at[i] = len(p) - 1 + np.minimum(np.arange(T), len(o) - 1)
    logits = np.asarray(reference_logits(params, cfg, tokens, at))
    margins = []
    for i, o in enumerate(outputs):
        lg = logits[i, :len(o)]
        chosen = lg[np.arange(len(o)), np.asarray(o)]
        margins.append((lg.max(-1) - chosen) / lg.std(-1))
    return margins
