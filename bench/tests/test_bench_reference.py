"""The float32 reference of the ``dense_gqa`` block (bench/blocks/) and
its shared library (bench/reference.py) against the program's own forward
pass at reduced widths, at two head groupings, and its weights, control and
refusals."""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import TINY, tiny_config
from bench import reference as R
from bench.blocks import dense_gqa as G
from extra_blocks import layernorm_gelu_tied as LGT

# 2 and 4 query heads to a kv head (internlm2-1.8b has 2)
CASES = [("gqa2", TINY), ("gqa4", dict(TINY, num_key_value_heads=1))]
# and a block of other equations (LayerNorm, GELU MLP, tied head)
LGT_MODEL = dict(TINY, norm="layernorm", mlp="gelu_mlp",
                 tie_word_embeddings=True)
BLOCKS = [(n, G, m) for n, m in CASES] + [("layernorm_gelu_tied", LGT,
                                           LGT_MODEL)]


def spec_of(name, model):
    return G.spec(tiny_config("internlm2-1.8b", model))


@pytest.mark.parametrize("name,block,model", BLOCKS,
                         ids=[c[0] for c in BLOCKS])
def test_reference_matches_the_program_prefill_logits(name, block, model):
    from bench import serving_adapter as sa
    from repro.models import transformer as tfm
    s = block.spec(tiny_config("internlm2-1.8b", model))
    w = block.make_weights(s, 3)
    cfg = sa.arch(block.program_fields(s, "internlm2-1.8b")).replace(
        use_kernels=False)
    params = block.program_params(w, cfg.padded_vocab)
    toks = np.random.default_rng(0).integers(0, s.vocab, (2, 24),
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = tfm.forward(params, cfg, tokens=jnp.asarray(toks))
    at = np.broadcast_to(np.arange(24), (2, 24))
    ref = R.logits_at(block.logits, w, s, toks, at)
    prog = np.asarray(prog)[..., :s.vocab]
    assert np.abs(prog - np.asarray(ref)).max() < 1e-4 * np.abs(prog).max()


@pytest.mark.parametrize("name,model", CASES, ids=[c[0] for c in CASES])
def test_served_gaps_are_zero_for_the_reference_greedy_tokens(name, model):
    s = spec_of(name, model)
    w = G.make_weights(s, 5)
    prompt = np.arange(1, 13, dtype=np.int32)
    toks = list(prompt)
    out = []
    for _ in range(6):          # greedy decoding by the reference itself
        lg = R.logits_at(G.logits, w, s, np.asarray([toks]),
                         [[len(toks) - 1]])
        out.append(int(np.asarray(lg)[0, 0].argmax()))
        toks.append(out[-1])
    gaps, ctl = G.served_gaps(w, s, [prompt], [out], control=True)
    assert gaps[0].shape == (6,) and np.all(gaps[0] == 0)
    assert np.all(ctl[0] >= 0)
    # an altered token lies below the best
    bad = [(t + 1) % s.vocab for t in out]
    assert G.served_gaps(w, s, [prompt], [bad])[0].min() > 0


def test_weights_are_made_from_the_seed_and_a_large_seed_is_whole():
    s = spec_of(*CASES[0])
    a, b = G.make_weights(s, 2**31 + 17), G.make_weights(s, 2**31 + 17)
    c = G.make_weights(s, 17)
    for k in ("wq", "w_down"):
        np.testing.assert_array_equal(a["layers"][k], b["layers"][k])
    assert not np.array_equal(a["layers"]["wq"], c["layers"]["wq"])
    assert a["layers"]["wq"].shape == (2, 64, 64)
    n = sum(x.size for x in jax.tree_util.tree_leaves(a))
    assert n == G.param_count(s)


# sha256 over every leaf of the tiny model's weights, in key order; pinned
# so that moving the code that makes them cannot change them
WEIGHT_DIGESTS = {
    ("float32", 3):
        "72f84717c661f591caa40476d93cb22c43e6ac88be728ed1f5242ce75ea45ae5",
    ("bfloat16", 2**31 + 17):
        "8c506725a42ca862305f1326b4d7d0301dc0a246895bb76fc8bd5c603848d23c"}


def weights_digest(w) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype,seed", sorted(WEIGHT_DIGESTS))
def test_weights_are_pinned_by_digest(dtype, seed):
    w = G.make_weights(spec_of("gqa2", dict(TINY, dtype=dtype)), seed)
    assert weights_digest(w) == WEIGHT_DIGESTS[dtype, seed]


def test_fp8_rounding_keeps_scale_and_loses_precision():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 256)) * 1e-3,
                    jnp.float32)
    q = R.q8(x, -1)
    rel = float(jnp.abs(q - x).max() / jnp.abs(x).max())
    assert 1e-3 < rel < 0.1


REFUSED = [("norm", "layernorm"), ("mlp", "gelu_tanh"), ("bias", True),
           ("tie_word_embeddings", True), ("block", "no_such_block"),
           ("block", None)]


@pytest.mark.parametrize("key,value", REFUSED,
                         ids=[f"{k}={v}" for k, v in REFUSED])
def test_a_configuration_with_another_block_is_refused(key, value):
    """``dense_gqa.spec`` refuses a block it does not compute, and the
    registry a configuration that names no block or a block with no
    module."""
    from bench.registry import Registry
    conf = tiny_config("internlm2-1.8b", TINY)
    if key == "block":
        conf.pop("block")
        if value is not None:
            conf["block"] = value
        with pytest.raises(ValueError, match=value or "names no block"):
            Registry().block(conf)
    else:
        conf["model"][key] = value
        with pytest.raises(ValueError, match="dense_gqa"):
            G.spec(conf)
