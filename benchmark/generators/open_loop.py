"""Open-loop traffic: independent single requests on a replayed schedule.

Parameters (traffic file): rate_rps, arrival {process, cv}, prompt_tokens and
output_tokens {dist, median, sigma, min, max}, lead_in_s, shape_seed. Prompts share
nothing. Returns chains of one turn each (see loadgen.play)."""

from __future__ import annotations

import numpy as np

from .common import drawn_gaps, drawn_lengths, local_shuffle, random_ids


def generate(params: dict, seed: int, seconds: float, vocab: int, divisor: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    shape = np.random.default_rng(int(params.get("shape_seed", 0)))
    rate = float(params["rate_rps"])
    # the lead-in and the window are drawn apart, each with rate x span
    # requests, from the traffic file's shape_seed: the window replays the
    # same arrival times and the same sizes for every --seed, which swaps
    # sizes between neighbours and draws the tokens
    chains, t0 = [], 0.0
    for span in (float(params["lead_in_s"]), float(seconds)):
        n = max(1, round(rate * span))
        gaps = drawn_gaps(params["arrival"], n, span, shape)
        plens = local_shuffle(drawn_lengths(params["prompt_tokens"], n, shape, divisor), rng)
        olens = local_shuffle(drawn_lengths(params["output_tokens"], n, shape, divisor), rng)
        # the first request is due at the start of the lead-in: set-up ends there
        t = t0
        for gap, pl, ol in zip(gaps, plens, olens):
            chains.append({
                "due_s": t, "prefix_ids": [],
                "turns": [{"user_ids": random_ids(rng, pl, vocab),
                           "max_tokens": max(2, ol), "think_s": 0.0}],
            })
            t += gap
        t0 += span
    return {"chains": chains}
