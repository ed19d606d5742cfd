"""internlm2's block (arXiv:2403.17297): RMSNorm, rotate-half RoPE,
grouped-query attention with no biases, SwiGLU MLP, untied output head.  A
configuration that asks for another block is refused.

The weights are made from the seed, in the dtype they are served in, in one
jitted call on the device.  The benchmark hands them to the program through
``serving_adapter``; the reference makes them again from the seed after the
program has been freed, so it takes nothing the program made.

The reference runs one layer at a time inside ``lax.scan``, casting that
layer's weights to float32 (see ``bench/reference.py`` for the precision
and the float8 control).

Work counts are what the traffic requires, never the program's padded or
bucketed shapes: a token decoded at context ``ctx`` (it attends ``ctx``
positions, itself included) needs the matmuls of every layer and of the
output head, and attention over its live context.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp

from bench import reference as R

F32 = jnp.float32
BYTES = 2                                   # bfloat16 activations and KV


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


def spec(conf: dict) -> Spec:
    m = conf["model"]
    block = (m["mlp"], m["norm"], m["bias"], m["tie_word_embeddings"])
    if block != ("swiglu", "rmsnorm", False, False):
        raise ValueError(f"{conf['name']}: dense_gqa has no block with "
                         f"(mlp, norm, bias, tied) = {block}")
    return read_spec(conf)


def read_spec(conf: dict) -> Spec:
    """The shape the configuration file states, whatever its block."""
    m = conf["model"]
    return Spec(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"],
                head_dim=m["head_dim"], d_ff=m["intermediate_size"],
                vocab=m["vocab_size"], eps=m["norm_eps"],
                rope_theta=m["rope_theta"], dtype=m["dtype"])


def vocab(s: Spec) -> int:
    return s.vocab


def kv_bytes_per_token(s: Spec) -> int:
    return s.layers * 2 * s.kv_heads * s.head_dim * BYTES


# ----------------------------------------------------------------------
# weights
def _layer_shapes(s: Spec):
    d, hd = s.d_model, s.head_dim
    return {"wq": (d, s.heads * hd), "wk": (d, s.kv_heads * hd),
            "wv": (d, s.kv_heads * hd), "wo": (s.heads * hd, d),
            "ln1_w": (d,), "ln2_w": (d,), "w_gate": (d, s.d_ff),
            "w_up": (d, s.d_ff), "w_down": (s.d_ff, d)}


TOP = ("embed", "final_w", "head")


def init_weights(key, s: Spec, layer_shapes: dict, top: Iterable[str]):
    """Weights drawn from ``key`` in the served dtype: each of
    ``layer_shapes`` stacked over the layers, then each of ``top`` (among
    ``embed``, ``final_w``, ``final_b`` and ``head``).  A matrix has std
    1/sqrt(its input width); a vector named ``*_w`` is a norm scale about 1,
    any other a bias about 0."""
    dt = jnp.dtype(s.dtype)
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std, mean=0.0):
        return (mean + std * jax.random.normal(next(keys), shape, F32)
                ).astype(dt)

    def vector(name, shape):
        return normal(shape, 0.1, 1.0) if name.endswith("_w") \
            else normal(shape, 0.1)

    layers = {}
    for name, shape in layer_shapes.items():
        full = (s.layers,) + shape
        if len(shape) == 1:
            layers[name] = vector(name, full)
        else:
            layers[name] = normal(full, 1.0 / math.sqrt(shape[0]))
    d, std = s.d_model, 1.0 / math.sqrt(s.d_model)
    w = {"layers": layers}
    for name in top:
        if name == "embed":
            w[name] = normal((s.vocab, d), std)
        elif name == "head":
            w[name] = normal((d, s.vocab), std)
        else:
            w[name] = vector(name, (d,))
    return w


@functools.lru_cache(maxsize=None)
def init_jit(s: Spec, layer_shapes, top: Tuple[str, ...]):
    """``init_weights`` jitted once for a shape; ``layer_shapes(s)`` gives
    the layer's weight shapes."""
    return jax.jit(functools.partial(init_weights, s=s,
                                     layer_shapes=layer_shapes(s), top=top))


def make_weights(s: Spec, seed: int):
    """Every weight of the model, made from ``seed`` in one jitted call, in
    the dtype it is served in."""
    return init_jit(s, _layer_shapes, TOP)(R.seed_key(seed))


def param_count(s: Spec) -> int:
    n = sum(math.prod((s.layers,) + sh) for sh in _layer_shapes(s).values())
    return n + 2 * s.vocab * s.d_model + s.d_model


# ----------------------------------------------------------------------
# the program's side
def program_fields(s: Spec, name: str) -> dict:
    return dict(name=name, family="dense", n_layers=s.layers,
                d_model=s.d_model, n_heads=s.heads, n_kv_heads=s.kv_heads,
                head_dim=s.head_dim, d_ff=s.d_ff, vocab=s.vocab,
                groups=((("A",), s.layers),), rope_base=s.rope_theta,
                mlp="swiglu", norm="rmsnorm", norm_eps=s.eps,
                tie_embeddings=False, dtype=s.dtype, param_dtype=s.dtype)


def program_params(w, padded_vocab: int):
    L = w["layers"]
    layer = {"ln1": {"w": L["ln1_w"]}, "ln2": {"w": L["ln2_w"]},
             "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")},
             "mixer": {k: L[k] for k in ("wq", "wk", "wv", "wo")}}
    table, head = w["embed"], w["head"]
    pad = padded_vocab - table.shape[0]
    if pad:
        table = jnp.pad(table, ((0, pad), (0, 0)))
        head = jnp.pad(head, ((0, 0), (0, pad)))
    return {"embedding": {"table": table}, "groups": [[layer]],
            "final_norm": {"w": w["final_w"]}, "lm_head": head}


# ----------------------------------------------------------------------
# the reference
def attention(h, p, s, fp8: bool):
    """Causal grouped-query attention with rotate-half RoPE and no biases,
    from the normed input ``h (B, S, d)``, through the output projection."""
    B, S, _ = h.shape
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    q = R.rope(R.mm(h, p["wq"], fp8).reshape(B, S, H, hd), s.rope_theta)
    k = R.rope(R.mm(h, p["wk"], fp8).reshape(B, S, KV, hd), s.rope_theta)
    v = R.mm(h, p["wv"], fp8).reshape(B, S, KV, hd)
    q = q.reshape(B, S, KV, H // KV, hd)
    if fp8:
        q, k, v = R.q8(q, -1), R.q8(k, -1), R.q8(v, -1)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
    if fp8:
        pr = R.q8(pr, -1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", pr, v).reshape(B, S, H * hd)
    return R.mm(o, p["wo"], fp8)


def _layer(x, p, s: Spec, fp8: bool):
    p = {k: v.astype(F32) for k, v in p.items()}
    x = x + attention(R.rms_norm(x, p["ln1_w"], s.eps), p, s, fp8)
    h = R.rms_norm(x, p["ln2_w"], s.eps)
    f = jax.nn.silu(R.mm(h, p["w_gate"], fp8)) * R.mm(h, p["w_up"], fp8)
    return x + R.mm(f, p["w_down"], fp8)


def logits(w, tokens, at, *, s: Spec, fp8: bool):
    """float32 logits at positions ``at`` (see ``bench/reference.py``)."""
    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda c, p: (_layer(c, p, s, fp8), None), x,
                        w["layers"])
    x = jnp.take_along_axis(x, at[..., None], axis=1)          # (B, T, d)
    x = R.rms_norm(x, w["final_w"].astype(F32), s.eps)
    return R.mm(x, w["head"].astype(F32), fp8)


def served_gaps(w, s: Spec, prompts, outputs, control: bool = False):
    return R.served_gaps(logits, w, s, prompts, outputs, control)


# ----------------------------------------------------------------------
# work
def layer_matmul_params(s) -> int:
    d, hd = s.d_model, s.head_dim
    attn = d * (s.heads + 2 * s.kv_heads) * hd + s.heads * hd * d
    mlp = 3 * d * s.d_ff                        # SwiGLU: gate, up, down
    return attn + mlp


def attn_flops(s, q_pos: Iterable[int]) -> float:
    """QK^T and PV over all layers for queries attending ``ctx`` positions
    each (``q_pos`` yields the contexts)."""
    return 4.0 * s.layers * s.heads * s.head_dim * float(sum(q_pos))


def decode_flops(s: Spec, ctx: int, counters) -> float:
    return 2.0 * (s.layers * layer_matmul_params(s) + s.d_model * s.vocab) \
        + attn_flops(s, [ctx])


def decode_attn(s, ctx: int, counters) -> Tuple[float, float]:
    """(FLOPs, bytes) the paged-decode kernel needs for one token at
    context ``ctx``: its live K and V, and its q and o."""
    kv = 2 * ctx * s.kv_heads * s.head_dim * BYTES
    qo = 2 * s.heads * s.head_dim * BYTES
    return attn_flops(s, [ctx]), float(s.layers * (kv + qo))
