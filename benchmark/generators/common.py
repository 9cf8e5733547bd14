"""Shared pieces of the traffic generators.

The layout of a mix over time is a REPLAYED TRACE: sizes and gaps are drawn
independently (no stratifying, no balancing: bursts and clumps of long
requests stay in) from the traffic file's own `shape_seed`, so every run of a
cell replays one realisation of the stated process. The run's --seed only
swaps sizes between neighbours (local_shuffle) and draws what the tokens and
the weights are. Why not a new realisation for every --seed: a 50 s window
holds some 50 requests here, and on the chip (PERF.md, PR 23) one seed
repeated to 0.1-2 % while seeds that reordered the same sizes and gaps
differed by 3-15 %, several times what a bound may be."""

from __future__ import annotations

import math

import numpy as np


def drawn_lengths(spec: dict, n: int, rng: np.random.Generator, divisor: int = 1) -> list:
    """n independent lengths from the stated distribution, clipped."""
    if n <= 0:
        return []
    lo = max(1, int(spec["min"]) // divisor)
    hi = max(lo, int(spec["max"]) // divisor)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu = math.log(spec["median"] / divisor)
    draws = np.exp(mu + float(spec["sigma"]) * rng.standard_normal(n))
    return [int(max(lo, min(hi, round(x)))) for x in draws]


def drawn_gaps(arrival: dict, n: int, span: float, rng: np.random.Generator) -> list:
    """n independent inter-arrival gaps of the arrival process, rescaled to
    sum to `span`. For `poisson` that is exactly a Poisson process given its
    count in the span (exponential gaps over their sum are the spacings of
    uniform arrival times); the count is fixed so that every window offers
    rate x span requests."""
    if n <= 0:
        return []
    proc = arrival.get("process", "poisson")
    if proc == "poisson":
        gaps = rng.exponential(1.0, n)
    elif proc == "gamma":  # bursts: shape k = 1 / cv^2, mean 1
        k = 1.0 / float(arrival["cv"]) ** 2
        gaps = rng.gamma(k, 1.0 / k, n)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return (gaps * (span / gaps.sum())).tolist()


def permuted(values: list, rng: np.random.Generator) -> list:
    idx = rng.permutation(len(values))
    return [values[i] for i in idx]


def local_shuffle(values: list, rng: np.random.Generator, block: int = 4) -> list:
    """`values` with each run of `block` consecutive entries shuffled among
    themselves: another order for every seed, and the same profile over time."""
    out = []
    for i in range(0, len(values), block):
        out += permuted(values[i:i + block], rng)
    return out


def random_ids(rng: np.random.Generator, n: int, vocab: int) -> list:
    # id 0 is the tokenizer's unknown word; never offered
    return rng.integers(1, vocab, size=n).tolist()
