"""The comparison that decides ``correct``: served greedy tokens against
the configuration's plain reference.

After the window, a sample drawn from the seed of the requests the window
finished (the one with the longest output always among them) is run
through the reference once, each prompt followed by its served tokens.
At each served position the reading is the gap by which the served
token's reference logit lies below the reference's best logit.  The
number compared is the mean gap over the sample's served tokens: the
widest gap does not separate the program from its control (PERF.md,
section 4), because a bfloat16 program already picks the second of two
near-tied logits now and then.  The control reads, at the same
positions, the gap of the token that the reference computed in a lower
precision puts first.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Dict, List, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reference(name: str):
    path = os.path.join(HERE, "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(finished: Sequence[dict], seed: int, n: int) -> List[dict]:
    """``n`` finished requests drawn from ``seed``: the longest output
    first, the rest uniformly from the others."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i]["output"]), i))
    rest = order[1:]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [finished[order[0]]] + [finished[rest[int(i)]] for i in pick]


def sequences(reqs: Sequence[dict], length: int):
    """Tokens (S, length) of prompt + served tokens but the last, and the
    (row, position, served token) of every served token."""
    toks = np.zeros((len(reqs), length), np.int32)
    rows, pos, served = [], [], []
    for i, r in enumerate(reqs):
        p, o = np.asarray(r["prompt"]), np.asarray(r["output"])
        seq = np.concatenate([p, o[:-1]])
        toks[i, :len(seq)] = seq
        rows += [i] * len(o)
        pos += list(range(len(p) - 1, len(p) - 1 + len(o)))
        served += list(o)
    return toks, np.array(rows), np.array(pos), np.array(served, np.int32)


def summary(g) -> Dict[str, float]:
    """Widest, mean and 99th-percentile gap, the share of positions whose
    token is not the reference's best, and the count."""
    g = np.asarray(g, np.float64)
    if not len(g):
        return {"n": 0}
    return {"n": int(len(g)), "max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99)),
            "share_off_best": float(np.mean(g > 0))}


def gaps(ref, cfg: dict, seed: int, reqs: Sequence[dict], length: int,
         quants: Sequence[str] = ()) -> Dict[str, np.ndarray]:
    """Per served position: ``served`` — reference best minus reference
    logit of the served token; for each of ``quants``, ``<quant>`` —
    reference best minus reference logit of the token the reference
    computed in that precision puts first."""
    toks, rows, pos, served = sequences(reqs, length)
    h = ref.final_hidden(cfg, seed, toks)
    h_rows = h[rows, pos]
    del h
    best, at, _ = ref.logit_readings(cfg, seed, h_rows, served)
    out = {"served": best - at}
    for q in quants:
        hq = ref.final_hidden(cfg, seed, toks, quant=q)
        _, _, first = ref.logit_readings(cfg, seed, hq[rows, pos], served,
                                         quant=q)
        del hq
        _, at_q, _ = ref.logit_readings(cfg, seed, h_rows, first)
        out[q] = best - at_q
    return out
