"""A second block, kept as test data: LayerNorm with a bias, a GELU (tanh)
MLP with biases, and an output head tied to the token embedding, as in
starcoder2 (arXiv:2402.19173), over ``dense_gqa``'s grouped-query attention
without biases (the program has no field for starcoder2's attention
biases).  The harness tests copy it into a copy of the benchmark as
``bench/blocks/layernorm_gelu_tied.py``, a new file, and run a cell of it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.blocks import dense_gqa as G

F32 = jnp.float32
Spec = G.Spec
TOP = ("embed", "final_w", "final_b")


def spec(conf: dict) -> Spec:
    m = conf["model"]
    block = (m["mlp"], m["norm"], m["bias"], m["tie_word_embeddings"])
    if block != ("gelu_mlp", "layernorm", False, True):
        raise ValueError(f"{conf['name']}: layernorm_gelu_tied has no block "
                         f"with (mlp, norm, bias, tied) = {block}")
    return G.read_spec(conf)


vocab = G.vocab
kv_bytes_per_token = G.kv_bytes_per_token


def _layer_shapes(s: Spec):
    d, hd, f = s.d_model, s.head_dim, s.d_ff
    return {"wq": (d, s.heads * hd), "wk": (d, s.kv_heads * hd),
            "wv": (d, s.kv_heads * hd), "wo": (s.heads * hd, d),
            "ln1_w": (d,), "ln1_b": (d,), "ln2_w": (d,), "ln2_b": (d,),
            "w_up": (d, f), "b_up": (f,), "w_down": (f, d), "b_down": (d,)}


def make_weights(s: Spec, seed: int):
    return G.init_jit(s, _layer_shapes, TOP)(R.seed_key(seed))


def program_fields(s: Spec, name: str) -> dict:
    return dict(G.program_fields(s, name), mlp="gelu_mlp", norm="layernorm",
                tie_embeddings=True)


def program_params(w, padded_vocab: int):
    L = w["layers"]
    layer = {"ln1": {"w": L["ln1_w"], "b": L["ln1_b"]},
             "ln2": {"w": L["ln2_w"], "b": L["ln2_b"]},
             "ffn": {k: L[k] for k in ("w_up", "b_up", "w_down", "b_down")},
             "mixer": {k: L[k] for k in ("wq", "wk", "wv", "wo")}}
    table = jnp.pad(w["embed"], ((0, padded_vocab - w["embed"].shape[0]),
                                 (0, 0)))
    return {"embedding": {"table": table}, "groups": [[layer]],
            "final_norm": {"w": w["final_w"], "b": w["final_b"]}}


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _layer(x, p, s: Spec, fp8: bool):
    p = {k: v.astype(F32) for k, v in p.items()}
    h = _layer_norm(x, p["ln1_w"], p["ln1_b"], s.eps)
    x = x + G.attention(h, p, s, fp8)
    h = _layer_norm(x, p["ln2_w"], p["ln2_b"], s.eps)
    f = _gelu_tanh(R.mm(h, p["w_up"], fp8) + p["b_up"])
    return x + R.mm(f, p["w_down"], fp8) + p["b_down"]


def logits(w, tokens, at, *, s: Spec, fp8: bool):
    table = w["embed"].astype(F32)
    x = table[tokens]
    x, _ = jax.lax.scan(lambda c, p: (_layer(c, p, s, fp8), None), x,
                        w["layers"])
    x = jnp.take_along_axis(x, at[..., None], axis=1)
    x = _layer_norm(x, w["final_w"].astype(F32), w["final_b"].astype(F32),
                    s.eps)
    return R.mm(x, table.T, fp8)                 # the tied head


def served_gaps(w, s: Spec, prompts, outputs, control: bool = False):
    return R.served_gaps(logits, w, s, prompts, outputs, control)


def decode_flops(s: Spec, ctx: int, counters) -> float:
    d, hd = s.d_model, s.head_dim
    attn = d * (s.heads + 2 * s.kv_heads) * hd + s.heads * hd * d
    mlp = 2 * d * s.d_ff                         # up, down
    return 2.0 * (s.layers * (attn + mlp) + d * s.vocab) \
        + G.attn_flops(s, [ctx])


decode_attn = G.decode_attn
