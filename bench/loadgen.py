"""Closed-loop request sets, drawn from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

    {"kind": "closed_loop", "clients": 16, "requests": 64, "scale": 0.15,
     "prompt": {"median": 1020, "sigma": 0.5, "min": 1, "max": 512},
     "output": {"median": 129, "sigma": 0.99, "min": 1, "max": 256},
     "pairing_seed": 0}

``median`` and ``sigma`` are the source's lognormal; ``scale`` (default 1)
multiplies every length, so a cut to scale keeps the source's shape.
Lengths are the scaled lognormal's quantiles at ``(i + 0.5) / requests``,
rounded, clipped to ``[min, max]``, paired prompt-to-output and put in
submission order by permutations drawn from ``pairing_seed``.  So every seed serves the same
(prompt length, output budget) sequence, the same work and the same
program shapes: the seed draws only the token ids, uniform over the
vocabulary.

Closed loop: ``clients`` slots, every request queued at the stream's
start and admitted FIFO as a slot frees, zero think time.  Request k is
*sent* at the stream's start for k < clients, and otherwise when the
(k - clients + 1)-th request of the stream finishes
(:func:`send_times`).
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class RequestSpec:
    prompt: np.ndarray         # (P,) int32 token ids
    max_new: int


def load_traffic(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def stratified_lengths(spec: dict, n: int, scale: float = 1.0) -> List[int]:
    """``n`` lognormal quantiles (median ``scale * spec['median']``, log-sd
    ``spec['sigma']``) clipped to ``[min, max]``, ascending."""
    z = NormalDist()
    med = scale * spec["median"]
    out = []
    for i in range(n):
        v = med * math.exp(spec["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def length_pairs(traffic: dict) -> List[tuple]:
    """The seed-independent (prompt length, output budget) pairs, in
    submission order."""
    n, scale = traffic["requests"], traffic.get("scale", 1.0)
    prompts = stratified_lengths(traffic["prompt"], n, scale)
    outputs = stratified_lengths(traffic["output"], n, scale)
    rng = np.random.default_rng(traffic["pairing_seed"])
    pair, order = rng.permutation(n), rng.permutation(n)
    return [(prompts[int(i)], outputs[int(pair[i])]) for i in order]


def longest_sequence(traffic: dict) -> int:
    """The most positions one request of the traffic fills: its prompt
    and its output budget."""
    return max(p + o for p, o in length_pairs(traffic))


def request_set(traffic: dict, seed: int, vocab: int) -> List[RequestSpec]:
    """The stream's requests in submission order, drawn from ``seed``."""
    if traffic.get("kind") != "closed_loop":
        raise ValueError(f"unknown traffic kind {traffic.get('kind')!r}")
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x5EED])
    out = []
    for p_len, max_new in length_pairs(traffic):
        prompt = rng.integers(0, vocab, size=p_len, dtype=np.int64)
        out.append(RequestSpec(prompt.astype(np.int32), max_new))
    return out


def send_times(t_start: float, finished_at: Sequence[float],
               clients: int) -> List[float]:
    """When each request of one closed-loop stream was sent.

    ``finished_at`` is in submission order.  With FIFO admission into
    ``clients`` slots, request k (k >= clients) takes the slot freed by the
    (k - clients + 1)-th finish of the stream, so it is sent at that
    finish; several finishes in one round send as many requests."""
    done = sorted(finished_at)
    return [t_start if k < clients else done[k - clients]
            for k in range(len(finished_at))]
