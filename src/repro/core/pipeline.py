"""TwoPhasePipeline — the paper's contribution as a composable JAX module.

Phase 1 (map):   every instance is scored independently by the broadcast
                 models (claim + evidence detectors).          [Listing 1]
Filter:          static-shape compaction of positives (per shard), which is
                 what bounds the phase-2 shuffle.              [§3.1 / §3.2]
Phase 2 (join+map): compacted claims are all-gathered over the data axis
                 (the shuffle), evidence stays local, and every shard scores
                 its (C_total × E_local) pair block — the "parallel step
                 after the aggregation" the paper prescribes.  [Listing 2]

Distribution is ``shard_map`` over the mesh's data axis; the weights enter
replicated (paper's broadcast variable) or tensor-sharded (policy "tp",
the beyond-paper placement from the paper's own Conclusion).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.filtering import Compacted, compact_by_score
from repro.core import joins
from repro.models import svm as svm_mod


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    feat_dim: int = 1024
    claim_capacity: int = 64        # per shard
    evid_capacity: int = 128        # per shard
    threshold: float = 0.0
    svm_gamma: float = 0.1
    svm_coef0: float = 1.0
    svm_degree: int = 2
    link_rank: int = 0              # 0 -> full bilinear
    use_pair_kernel: bool = False   # route phase 2 through kernels/pair_score


class PipelineOut(NamedTuple):
    link_scores: jax.Array   # (C_total, E) pair scores
    pair_valid: jax.Array    # (C_total, E) bool
    claim_index: jax.Array   # (C_total,) original row ids (-1 invalid)
    evid_index: jax.Array    # (E,)
    claim_keys: jax.Array    # (C_total,)
    evid_keys: jax.Array     # (E,)
    n_dropped: jax.Array     # () compaction overflow count


def init_models(key, pcfg: PipelineConfig, n_sv: int = 1024):
    """Claim/evidence SVMs + link model (the paper's three classifiers)."""
    from repro.core.sharding import split_params
    k1, k2, k3 = jax.random.split(key, 3)
    tree = {
        "claim": svm_mod.init_svm(k1, n_sv, pcfg.feat_dim),
        "evidence": svm_mod.init_svm(k2, n_sv, pcfg.feat_dim),
        "link": svm_mod.init_link(k3, pcfg.feat_dim, rank=pcfg.link_rank),
    }
    return split_params(tree)


# ----------------------------------------------------------------------
def _phase1_local(models, X, keys, pcfg: PipelineConfig):
    kw = dict(gamma=pcfg.svm_gamma, coef0=pcfg.svm_coef0, degree=pcfg.svm_degree)
    c_sc = svm_mod.svm_score(models["claim"], X, **kw)
    e_sc = svm_mod.svm_score(models["evidence"], X, **kw)
    claims = compact_by_score(X, c_sc, keys, pcfg.claim_capacity, pcfg.threshold)
    evid = compact_by_score(X, e_sc, keys, pcfg.evid_capacity, pcfg.threshold)
    return claims, evid


def _phase2_local(models, claims: Compacted, evid: Compacted,
                  pcfg: PipelineConfig):
    if pcfg.use_pair_kernel:
        from repro.kernels import ops as kops
        scores = kops.pair_score(models["link"], claims.feats, evid.feats,
                                 interpret=kops.use_interpret())
    else:
        scores = svm_mod.link_score_matrix(models["link"], claims.feats,
                                           evid.feats)
    mask = joins.pair_mask_batch(claims, evid)
    return scores, mask


def batch_step_local(models, X, keys, pcfg: PipelineConfig) -> PipelineOut:
    """Single-shard reference (also the shard-local body)."""
    claims, evid = _phase1_local(models, X, keys, pcfg)
    scores, mask = _phase2_local(models, claims, evid, pcfg)
    return PipelineOut(scores, mask, claims.index, evid.index,
                       claims.keys, evid.keys,
                       claims.n_dropped + evid.n_dropped)


def make_batch_step(pcfg: PipelineConfig, mesh: Optional[Mesh] = None,
                    data_axis: str = "data"):
    """Returns jitted ``step(models, X, keys) -> PipelineOut``.

    With a mesh: X/keys sharded over `data_axis`; claims all-gathered
    (the shuffle); output pair block is (C_total, E_local) per shard.
    """
    if mesh is None:
        @jax.jit
        def step(models, X, keys):
            # offset local indices trivially (single shard)
            return batch_step_local(models, X, keys, pcfg)
        return step

    nshards = mesh.shape[data_axis]

    def body(models, X, keys):
        claims, evid = _phase1_local(models, X, keys, pcfg)
        # global row ids: offset by shard start
        idx = jax.lax.axis_index(data_axis)
        offset = idx * X.shape[0]
        claims = claims._replace(index=jnp.where(claims.valid,
                                                 claims.index + offset, -1))
        evid = evid._replace(index=jnp.where(evid.valid,
                                             evid.index + offset, -1))
        # THE SHUFFLE: gather only the compacted claims (paper §3.1)
        gather = lambda a: jax.lax.all_gather(a, data_axis, tiled=True)
        claims_all = Compacted(*(gather(l) for l in claims[:5]),
                               n_dropped=jax.lax.psum(claims.n_dropped, data_axis))
        scores, mask = _phase2_local(models, claims_all, evid, pcfg)
        n_drop = claims_all.n_dropped + jax.lax.psum(evid.n_dropped, data_axis)
        return PipelineOut(scores, mask, claims_all.index, evid.index,
                           claims_all.keys, evid.keys, n_drop)

    dspec = P(data_axis)
    out_specs = PipelineOut(
        link_scores=P(None, data_axis), pair_valid=P(None, data_axis),
        claim_index=P(), evid_index=P(data_axis),
        claim_keys=P(), evid_keys=P(data_axis), n_dropped=P())
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), dspec, dspec),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


# ----------------------------------------------------------------------
def extract_links(out: PipelineOut, threshold: float = 0.0):
    """Host-side: positive, valid (claim_row, evidence_row, score) triples."""
    import numpy as np
    sc = np.asarray(out.link_scores)
    ok = np.asarray(out.pair_valid) & (sc > threshold)
    ci, ei = np.nonzero(ok)
    return [(int(out.claim_index[c]), int(out.evid_index[e]), float(sc[c, e]))
            for c, e in zip(ci, ei)]
