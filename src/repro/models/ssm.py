"""Mamba-1 SSM block (falcon-mamba-7b): in_proj -> causal depthwise conv ->
selective scan -> gate -> out_proj.

The selective scan runs chunked: a ``lax.scan`` over sequence chunks with an
``associative_scan`` inside each chunk, so peak memory is
O(B * chunk * d_inner * state) instead of O(B * S * d_inner * state).
A Pallas kernel (kernels/ssm_scan.py) implements the same chunked schedule for
TPU; this module is the jnp reference path used by dry-run and smoke tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sharding import Param, shard
from repro.models.layers import dense_init, zeros_init, ones_init

SCAN_CHUNK = 64


def init_ssm(key, cfg):
    d, di, N, dtr, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.conv_k
    dt = cfg.p_dtype
    ks = jax.random.split(key, 7)
    a_init = jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1)))
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di), ("embed", "inner"), dt),
        "conv_w": dense_init(ks[1], (K, di), (None, "inner"), dt, scale=0.5),
        "conv_b": zeros_init((di,), ("inner",), dt),
        "x_proj": dense_init(ks[2], (di, dtr + 2 * N), ("inner", None), dt),
        "dt_proj": dense_init(ks[3], (dtr, di), (None, "inner"), dt),
        "dt_bias": zeros_init((di,), ("inner",), dt),
        "A_log": Param(a_init, ("inner", None)),
        "D": ones_init((di,), ("inner",), dt),
        "out_proj": dense_init(ks[4], (di, d), ("inner", "embed"), dt),
    }


def _conv1d_causal(x, w, b):
    """x: (B,S,di), depthwise causal conv, kernel (K,di)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :] for i in range(K))
    return out + b[None, None, :]


def _ssm_params(params, xc, cfg):
    """Per-token dt, B, C from the conv output xc (B,S,di)."""
    N, dtr = cfg.ssm_state, cfg.dt_rank
    proj = xc @ params["x_proj"]                       # (B,S,dtr+2N)
    dt_in, Bc, Cc = jnp.split(proj, [dtr, dtr + N], axis=-1)
    dt = jax.nn.softplus(dt_in @ params["dt_proj"] + params["dt_bias"])  # (B,S,di)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))  # (di,N)
    return dt.astype(jnp.float32), Bc.astype(jnp.float32), Cc.astype(jnp.float32), A


def selective_scan(xc, dt, Bc, Cc, A, D, h0=None, chunk: int = SCAN_CHUNK):
    """xc: (B,S,di)  dt: (B,S,di)  Bc,Cc: (B,S,N)  A: (di,N)  D: (di,)

    Returns (y (B,S,di), h_final (B,di,N)).
    """
    from repro.core import flags
    Bsz, S, di = xc.shape
    if flags.COST_MODE:
        chunk = max(chunk, S // 32)
    N = Bc.shape[-1]
    xf = xc.astype(jnp.float32)
    a_bar = jnp.exp(dt[..., None] * A[None, None])                   # (B,S,di,N)
    b_bar = (dt * xf)[..., None] * Bc[:, :, None, :]                  # (B,S,di,N)

    pad = (-S) % chunk
    if pad:
        a_bar = jnp.pad(a_bar, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0)
        b_bar = jnp.pad(b_bar, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (S + pad) // chunk
    a_c = a_bar.reshape(Bsz, nc, chunk, di, N).transpose(1, 0, 2, 3, 4)
    b_c = b_bar.reshape(Bsz, nc, chunk, di, N).transpose(1, 0, 2, 3, 4)

    if h0 is None:
        h0 = jnp.zeros((Bsz, di, N), jnp.float32)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    def chunk_step(h, ab):
        a, b = ab                                                     # (B,chunk,di,N)
        acc_a, acc_b = jax.lax.associative_scan(combine, (a, b), axis=1)
        h_all = acc_a * h[:, None] + acc_b                            # (B,chunk,di,N)
        return h_all[:, -1], h_all

    from repro.core import flags
    if flags.COST_MODE:
        h, hs = h0, []
        for i in range(nc):
            h, h_all = chunk_step(h, (a_c[i], b_c[i]))
            hs.append(h_all)
        h_fin, h_seq = h, jnp.stack(hs)
    else:
        h_fin, h_seq = jax.lax.scan(chunk_step, h0, (a_c, b_c))
    h_seq = h_seq.transpose(1, 0, 2, 3, 4).reshape(Bsz, nc * chunk, di, N)[:, :S]
    y = jnp.einsum("bsdn,bsn->bsd", h_seq, Cc) + xf * D[None, None].astype(jnp.float32)
    return y, h_fin


def ssm_forward(params, x, cfg, state=None):
    """x: (B,S,d) -> (out, new_state).  state = {"conv": (B,K-1,di), "h": (B,di,N)}"""
    B, S, d = x.shape
    di, K = cfg.d_inner, cfg.conv_k
    xz = x @ params["in_proj"]
    xs, z = jnp.split(xz, 2, axis=-1)
    xs = shard(xs, "batch", "seq", "inner")
    if state is not None:
        xs_ext = jnp.concatenate([state["conv"].astype(xs.dtype), xs], axis=1)
        conv_full = _conv1d_causal(xs_ext, params["conv_w"], params["conv_b"])
        xc = conv_full[:, K - 1:]
    else:
        xc = _conv1d_causal(xs, params["conv_w"], params["conv_b"])
    xc = jax.nn.silu(xc)
    dt, Bc, Cc, A = _ssm_params(params, xc, cfg)
    h0 = state["h"] if state is not None else None
    if cfg.use_kernels and S >= 128:
        from repro.kernels import ops as kops
        y, h_fin = kops.ssm_scan(xc.astype(jnp.float32), dt, Bc, Cc, A,
                                 params["D"].astype(jnp.float32),
                                 h0=h0, interpret=kops.use_interpret())
    else:
        y, h_fin = selective_scan(xc, dt, Bc, Cc, A, params["D"], h0=h0)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = shard(y @ params["out_proj"], "batch", "seq", None)
    new_state = {
        "conv": xs[:, -(K - 1):].astype(jnp.float32) if S >= K - 1 else
                jnp.concatenate([state["conv"], xs], 1)[:, -(K - 1):] if state is not None
                else jnp.pad(xs, ((0, 0), (K - 1 - S, 0), (0, 0))).astype(jnp.float32),
        "h": h_fin,
    }
    return out, new_state


def init_ssm_state(cfg, batch: int):
    return {
        "conv": jnp.zeros((batch, cfg.conv_k - 1, cfg.d_inner), jnp.float32),
        "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }


def ssm_decode(params, x, state, cfg):
    """Single-token step.  x: (B,1,d)."""
    B = x.shape[0]
    di, K, N = cfg.d_inner, cfg.conv_k, cfg.ssm_state
    xz = x @ params["in_proj"]
    xs, z = jnp.split(xz, 2, axis=-1)                   # (B,1,di)
    conv_in = jnp.concatenate([state["conv"].astype(xs.dtype), xs], axis=1)  # (B,K,di)
    xc = jnp.einsum("bkd,kd->bd", conv_in, params["conv_w"]) + params["conv_b"]
    xc = jax.nn.silu(xc)[:, None]                       # (B,1,di)
    dt, Bc, Cc, A = _ssm_params(params, xc, cfg)
    a_bar = jnp.exp(dt[:, 0, :, None] * A[None])        # (B,di,N)
    b_bar = (dt[:, 0] * xc[:, 0].astype(jnp.float32))[..., None] * Bc[:, 0, None, :]
    h = a_bar * state["h"] + b_bar
    y = jnp.einsum("bdn,bn->bd", h, Cc[:, 0]) + xc[:, 0].astype(jnp.float32) * params["D"].astype(jnp.float32)
    y = y[:, None].astype(x.dtype) * jax.nn.silu(z)
    out = y @ params["out_proj"]
    return out, {"conv": conv_in[:, 1:].astype(jnp.float32), "h": h}
