"""Questions on shared documents: a few long documents are put in front of
the model once each (their openers, in the lead-in, build the documents'
pages) and are then asked about again and again by independent single
requests on a replayed open-loop schedule, so a question's prompt is a whole
document, cached but for its last page, plus the question.

Parameters (traffic file): documents, document_tokens {dist, median, sigma,
min, max}, opener_gap_s, rate_rps, arrival {process, cv}, questions_from_s,
question_tokens and output_tokens {dist, ...}, lead_in_s, shape_seed. Every
size, gap and choice of document is drawn from `shape_seed`; the run's --seed
swaps sizes between neighbours and draws the tokens (generators/common.py).
Returns chains of one turn each (see loadgen.play): a document's opener has the
document as its `prefix_ids`, 16 user tokens and 2 tokens asked, due
`opener_gap_s` after the one before from the lead-in's start; a question has a
document, drawn uniformly, as its `prefix_ids`."""

from __future__ import annotations

import numpy as np

from .common import drawn_gaps, drawn_lengths, local_shuffle, random_ids

OPENER_USER_TOKENS = 16
OPENER_MAX_TOKENS = 2


def generate(params: dict, seed: int, seconds: float, vocab: int, divisor: int = 1) -> dict:
    rng = np.random.default_rng(seed)
    shape = np.random.default_rng(int(params.get("shape_seed", 0)))
    n_docs = int(params["documents"])
    docs = [random_ids(rng, n, vocab)
            for n in drawn_lengths(params["document_tokens"], n_docs, shape, divisor)]
    chains = [{"due_s": i * float(params["opener_gap_s"]), "prefix_ids": doc,
               "turns": [{"user_ids": random_ids(rng, OPENER_USER_TOKENS, vocab),
                          "max_tokens": OPENER_MAX_TOKENS, "think_s": 0.0}]}
              for i, doc in enumerate(docs)]
    # the questions of the lead-in's end and of the window are drawn apart,
    # each with rate x span requests: the window replays the same arrival
    # times, sizes and documents for every --seed
    rate, lead = float(params["rate_rps"]), float(params["lead_in_s"])
    t0 = float(params["questions_from_s"])
    for span in (lead - t0, float(seconds)):
        n = max(1, round(rate * span))
        gaps = drawn_gaps(params["arrival"], n, span, shape)
        which = shape.integers(0, n_docs, size=n).tolist()
        qlens = local_shuffle(drawn_lengths(params["question_tokens"], n, shape, divisor), rng)
        olens = local_shuffle(drawn_lengths(params["output_tokens"], n, shape, divisor), rng)
        t = t0
        for gap, d, ql, ol in zip(gaps, which, qlens, olens):
            chains.append({
                "due_s": t, "prefix_ids": docs[d],
                "turns": [{"user_ids": random_ids(rng, ql, vocab),
                           "max_tokens": max(2, ol), "think_s": 0.0}],
            })
            t += gap
        t0 += span
    return {"chains": chains}
