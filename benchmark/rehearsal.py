"""The sizes of a --rehearse run, for run.py and serve.py alike:
rehearse.json, with the configuration's own optional `rehearse` group
(`model`, `server_flags`, `correct_tolerance`, `correct_routing_margin`) laid
over it key by key, and the result laid over the configuration's `model` and
`server_flags`. A configuration without the group rehearses at rehearse.json's
sizes alone."""

from __future__ import annotations

import json
import os


def rehearsal_sizes(cfg: dict, here: str) -> dict:
    """{"model", "server_flags", "length_divisor", "correct_tolerance",
    "correct_routing_margin"} of a rehearsal of configuration file `cfg`. The
    ratio of query to KV heads is kept where the configuration has both and
    its group names no `n_kv_heads` of its own."""
    with open(os.path.join(here, "rehearse.json")) as f:
        base = json.load(f)
    own = cfg.get("rehearse") or {}
    sizes = {**base["model"], **own.get("model", {})}
    model = {**cfg["model"], **sizes}
    if "n_kv_heads" not in own.get("model", {}) and \
            {"n_heads", "n_kv_heads"} <= set(cfg["model"]):
        ratio = cfg["model"]["n_heads"] // cfg["model"]["n_kv_heads"]
        model["n_kv_heads"] = max(1, model["n_heads"] // ratio)
    return {"model": model,
            "server_flags": {**cfg["server_flags"], **base["server_flags"],
                             **own.get("server_flags", {})},
            "length_divisor": int(base["length_divisor"]),
            "correct_tolerance": float(own.get("correct_tolerance", base["correct_tolerance"])),
            # the margin is a reading at the rehearsal's own sizes: a
            # configuration's full-size one says nothing about it (None: not
            # stated, which serve.py refuses where the check follows picks)
            "correct_routing_margin": own.get("correct_routing_margin")}
