"""The control of ``correct``: the reference computed in float8 (the
precision below the bfloat16 the configurations state) in the program's
place must fail the limit each configuration's file states, while the
reference's own greedy tokens pass it.  Run here at a size a test can
hold; PERF.md gives the readings at the cells' own sizes on the chip."""
from __future__ import annotations

import numpy as np

from bench_tiny import tiny_config
from bench import check
from bench import reference as R
from bench.blocks import dense_gqa as G

SMALL = {"num_hidden_layers": 8, "hidden_size": 512,
         "intermediate_size": 1024, "num_attention_heads": 8,
         "num_key_value_heads": 4, "head_dim": 64, "vocab_size": 4096,
         "dtype": "float32"}
PROMPT, OUT = 64, 48


def greedy(w, s, prompt, n):
    """The reference's own greedy tokens (one compiled length)."""
    toks = np.zeros((1, PROMPT + OUT), np.int32)
    toks[0, :len(prompt)] = prompt
    out = []
    for k in range(len(prompt), len(prompt) + n):
        lg = R.logits_at(G.logits, w, s, toks, [[k - 1]])
        out.append(int(np.asarray(lg)[0, 0].argmax()))
        toks[0, k] = out[-1]
    return out


def test_the_float8_control_fails_the_limit():
    conf = tiny_config("internlm2-1.8b", SMALL)
    limit = conf["check"]["max_gap"]
    s = G.spec(conf)
    w = G.make_weights(s, 21)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, s.vocab, PROMPT, dtype=np.int32)
               for _ in range(4)]
    outputs = [greedy(w, s, p, OUT) for p in prompts]
    ok = check.compare(G, w, s, prompts, outputs, limit, 0, control=True)
    assert ok["correct"] is True
    # the control in the program's place: at each position of the same
    # prompts and tokens it serves the token float8 puts first
    served = []
    for p, o in zip(prompts, outputs):
        seq = np.concatenate([p, np.asarray(o[:-1], np.int32)])[None]
        at = (len(p) - 1 + np.arange(len(o)))[None]
        served.append(np.asarray(R.logits_at(G.logits, w, s, seq, at,
                                             fp8=True))
                      [0].argmax(-1).tolist())
    bad = check.compare(G, w, s, prompts, served, limit, 0)
    assert bad["correct"] is False
    assert ok["control_max_gap"] > limit
