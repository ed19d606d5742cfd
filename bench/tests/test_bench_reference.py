"""The float32 reference (bench/reference.py) against the program's own
forward pass at reduced widths, at two head groupings, and its weights and
control."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import TINY, tiny_config
from bench import reference as R

# 2 and 4 query heads to a kv head (internlm2-1.8b has 2)
CASES = [("gqa2", TINY), ("gqa4", dict(TINY, num_key_value_heads=1))]


def spec_of(name, model):
    return R.Spec.from_config(tiny_config("internlm2-1.8b", model))


@pytest.mark.parametrize("name,model", CASES, ids=[c[0] for c in CASES])
def test_reference_matches_the_program_prefill_logits(name, model):
    from bench import serving_adapter as sa
    from repro.models import transformer as tfm
    s = spec_of(name, model)
    w = R.make_weights(s, 3)
    cfg = sa.arch(s, "internlm2-1.8b").replace(use_kernels=False)
    params = sa.program_params(w, cfg)
    toks = np.random.default_rng(0).integers(0, s.vocab, (2, 24),
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = tfm.forward(params, cfg, tokens=jnp.asarray(toks))
    at = np.broadcast_to(np.arange(24), (2, 24))
    ref = R.logits_at(w, s, toks, at)
    prog = np.asarray(prog)[..., :s.vocab]
    assert np.abs(prog - np.asarray(ref)).max() < 1e-4 * np.abs(prog).max()


@pytest.mark.parametrize("name,model", CASES, ids=[c[0] for c in CASES])
def test_served_gaps_are_zero_for_the_reference_greedy_tokens(name, model):
    s = spec_of(name, model)
    w = R.make_weights(s, 5)
    prompt = np.arange(1, 13, dtype=np.int32)
    toks = list(prompt)
    out = []
    for _ in range(6):          # greedy decoding by the reference itself
        lg = R.logits_at(w, s, np.asarray([toks]), [[len(toks) - 1]])
        out.append(int(np.asarray(lg)[0, 0].argmax()))
        toks.append(out[-1])
    gaps, ctl = R.served_gaps(w, s, [prompt], [out], control=True)
    assert gaps[0].shape == (6,) and np.all(gaps[0] == 0)
    assert np.all(ctl[0] >= 0)
    # an altered token lies below the best
    bad = [(t + 1) % s.vocab for t in out]
    assert R.served_gaps(w, s, [prompt], [bad])[0].min() > 0


def test_weights_are_made_from_the_seed_and_a_large_seed_is_whole():
    s = spec_of(*CASES[0])
    a, b = R.make_weights(s, 2**31 + 17), R.make_weights(s, 2**31 + 17)
    c = R.make_weights(s, 17)
    for k in ("wq", "w_down"):
        np.testing.assert_array_equal(a["layers"][k], b["layers"][k])
    assert not np.array_equal(a["layers"]["wq"], c["layers"]["wq"])
    assert a["layers"]["wq"].shape == (2, 64, 64)
    n = sum(x.size for x in jax.tree_util.tree_leaves(a))
    assert n == R.param_count(s)


def test_fp8_rounding_keeps_scale_and_loses_precision():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 256)) * 1e-3,
                    jnp.float32)
    q = R._q8(x, -1)
    rel = float(jnp.abs(q - x).max() / jnp.abs(x).max())
    assert 1e-3 < rel < 0.1


@pytest.mark.parametrize("key,value", [("norm", "layernorm"),
                                       ("mlp", "gelu_tanh"), ("bias", True),
                                       ("tie_word_embeddings", True)])
def test_a_configuration_with_another_block_is_refused(key, value):
    with pytest.raises(ValueError):
        spec_of("x", dict(TINY, **{key: value}))
