"""The comparison that decides ``correct``.

Once the window has closed and every request in it has been followed to its
end, a sample drawn from the seed of the requests it finished, the one with
the most served tokens among them, is run through the float32 reference
(the block's ``served_gaps``), teacher-forced on the served tokens.  Compared:

* ``max_gap``: the widest gap, over every served token of the sample, by
  which the reference's logit of the served token lies below the
  reference's best logit at that position.  Its limit is the
  configuration's ``check.max_gap``, set from chip readings of the program
  and of the float8 control (see PERF.md).
* ``failed``: requests scheduled in the window that did not finish with
  the number of tokens they asked for: refused, cut short, or still
  unfinished when the run stopped following them.  Limit 0, so a stall
  cannot leave the tails smaller.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

SAMPLE = 8


def sample(finished: Sequence[int], n_served: Dict[int, int], seed: int,
           k: int = SAMPLE) -> List[int]:
    """``k`` request indices drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (n_served[i], -i))
    rest = [i for i in finished if i != longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return sorted([longest] + [rest[j] for j in pick])


def compare(block, weights, spec, prompts, outputs, limit: float,
            failed: int, control: bool = False) -> dict:
    """The numbers compared, each beside its limit, and the verdict;
    ``block`` is the configuration's block module."""
    res = block.served_gaps(weights, spec, prompts, outputs, control=control)
    gaps, ctl = res if control else (res, None)
    worst = float(max(g.max() for g in gaps)) if gaps else float("nan")
    out = {
        "checks": {
            "max_gap": {"value": worst, "limit": limit},
            "failed": {"value": failed, "limit": 0},
        },
        "served_tokens_compared": int(sum(len(g) for g in gaps)),
        "at_reference_best": int(sum(int((g == 0).sum()) for g in gaps)),
    }
    out["correct"] = bool(gaps) and worst <= limit and failed == 0
    if control:
        out["control_max_gap"] = float(max(g.max() for g in ctl))
    return out
