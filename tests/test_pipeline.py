"""The paper's two-phase pipeline: correctness vs brute force + properties."""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core.filtering import compact_by_score
from repro.core.pipeline import (PipelineConfig, batch_step_local,
                                 extract_links, init_models, make_batch_step)
from repro.data.text import corpus_arrays, margot_models, synthetic_corpus
from repro.models import svm as svm_mod


PCFG = PipelineConfig(feat_dim=256, claim_capacity=96, evid_capacity=192)


def brute_force_links(models, X, keys, pcfg):
    """Reference semantics: the paper's filter + per-doc Cartesian join."""
    kw = dict(gamma=pcfg.svm_gamma, coef0=pcfg.svm_coef0, degree=pcfg.svm_degree)
    c_sc = np.asarray(svm_mod.svm_score(models["claim"], X, **kw))
    e_sc = np.asarray(svm_mod.svm_score(models["evidence"], X, **kw))
    links = set()
    for i in np.nonzero(c_sc > pcfg.threshold)[0]:
        for j in np.nonzero(e_sc > pcfg.threshold)[0]:
            if keys[i] != keys[j]:
                continue
            s = float(svm_mod.link_score_matrix(
                models["link"], X[i:i + 1], X[j:j + 1])[0, 0])
            if s > 0:
                links.add((int(i), int(j)))
    return links


@pytest.fixture(scope="module")
def corpus():
    docs = synthetic_corpus(3, 40, seed=2)
    X, keys, sents = corpus_arrays(docs, dim=PCFG.feat_dim)
    models, _ = margot_models(PCFG)
    return models, jnp.asarray(X), jnp.asarray(keys)


def test_batch_matches_brute_force(corpus):
    models, X, keys = corpus
    step = make_batch_step(PCFG)
    out = step(models, X, keys)
    assert int(out.n_dropped) == 0, "capacity must cover this corpus"
    got = {(c, e) for c, e, s in extract_links(out)}
    want = brute_force_links(models, np.asarray(X), np.asarray(keys), PCFG)
    assert got == want


def test_permutation_invariance(corpus):
    """Shuffling input rows must not change the link set (modulo row ids)."""
    models, X, keys = corpus
    step = make_batch_step(PCFG)
    perm = np.random.RandomState(0).permutation(X.shape[0])
    out1 = step(models, X, keys)
    out2 = step(models, X[perm], keys[perm])
    links1 = {(int(perm[c]) if False else c, e)
              for c, e, _ in extract_links(out1)}
    # map shuffled indices back to original rows
    links2 = {(int(perm[c]), int(perm[e])) for c, e, _ in extract_links(out2)}
    assert {(c, e) for c, e in links1} == links2


def test_capacity_overflow_counted():
    pcfg = PipelineConfig(feat_dim=64, claim_capacity=2, evid_capacity=2)
    models, _ = margot_models(pcfg)
    docs = synthetic_corpus(2, 50, seed=3)
    X, keys, _ = corpus_arrays(docs, dim=64)
    out = make_batch_step(pcfg)(models, jnp.asarray(X), jnp.asarray(keys))
    assert int(out.n_dropped) > 0      # tiny capacity must overflow and say so


# ----------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.integers(1, 16), st.integers(0, 2 ** 31 - 1))
def test_compaction_properties(n, cap, seed):
    """compact_by_score: all kept rows positive, sorted-desc, exact count."""
    rng = np.random.RandomState(seed)
    scores = jnp.asarray(rng.randn(n).astype(np.float32))
    feats = jnp.asarray(rng.randn(n, 4).astype(np.float32))
    keys = jnp.asarray(rng.randint(0, 5, size=n).astype(np.int32))
    out = compact_by_score(feats, scores, keys, cap)
    n_pos = int((np.asarray(scores) > 0).sum())
    kept = int(out.valid.sum())
    assert kept == min(n_pos, cap)
    assert int(out.n_dropped) == max(n_pos - cap, 0)
    s = np.asarray(out.scores)[np.asarray(out.valid)]
    assert np.all(s > 0)
    assert np.all(np.diff(s) <= 1e-6)          # descending
    # kept rows are the TOP-scoring positives
    if kept:
        thresh = np.sort(np.asarray(scores))[::-1][kept - 1]
        assert s.min() >= thresh - 1e-6


# ----------------------------------------------------------------------
SHARDED_CHECK = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.pipeline import PipelineConfig, make_batch_step, extract_links
from repro.data.text import synthetic_corpus, corpus_arrays, margot_models

pcfg = PipelineConfig(feat_dim=256, claim_capacity=16, evid_capacity=32)
models, _ = margot_models(pcfg)
docs = synthetic_corpus(4, 32, seed=5)
X, keys, _ = corpus_arrays(docs, dim=256)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
step_sharded = make_batch_step(pcfg, mesh=mesh)
out_s = step_sharded(models, jnp.asarray(X), jnp.asarray(keys))
links_s = {(c, e) for c, e, _ in extract_links(out_s)}

# oracle: same per-shard capacities applied shard-locally
n = X.shape[0] // 8
links_r = set()
from repro.core.pipeline import batch_step_local
import repro.core.filtering as F
from repro.models import svm as svm_mod
kw = dict(gamma=pcfg.svm_gamma, coef0=pcfg.svm_coef0, degree=pcfg.svm_degree)
claims_all, evids = [], []
for s in range(8):
    Xs, ks = jnp.asarray(X[s*n:(s+1)*n]), jnp.asarray(keys[s*n:(s+1)*n])
    c_sc = svm_mod.svm_score(models["claim"], Xs, **kw)
    e_sc = svm_mod.svm_score(models["evidence"], Xs, **kw)
    c = F.compact_by_score(Xs, c_sc, ks, pcfg.claim_capacity)
    e = F.compact_by_score(Xs, e_sc, ks, pcfg.evid_capacity)
    claims_all.append((c, s*n))
    evids.append((e, s*n))
for c, coff in claims_all:
    for ci in range(pcfg.claim_capacity):
        if not bool(c.valid[ci]):
            continue
        for e, eoff in evids:
            for ei in range(pcfg.evid_capacity):
                if not bool(e.valid[ei]):
                    continue
                if int(c.keys[ci]) != int(e.keys[ei]):
                    continue
                s_ = float(svm_mod.link_score_matrix(
                    models["link"], c.feats[ci:ci+1], e.feats[ei:ei+1])[0, 0])
                if s_ > 0:
                    links_r.add((int(c.index[ci]) + coff,
                                 int(e.index[ei]) + eoff))
assert links_s == links_r, (sorted(links_s)[:5], sorted(links_r)[:5])
print("SHARDED-OK", len(links_s))
"""


def test_sharded_pipeline_equivalence():
    """shard_map(8 devices) == shard-local oracle, in a subprocess (needs its
    own XLA_FLAGS before jax init)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SHARDED_CHECK], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED-OK" in r.stdout
