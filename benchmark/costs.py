"""Bytes a step has to move, from shapes alone: the yardstick's own
arithmetic, kept with the benchmark so that no later PR can change it.

A decode step of a dense decoder reads every weight once (the embedding table
is gathered, not streamed: only the rows of the batch are read) and the K and
V of every live token of every sequence in the batch, bounded by the sliding
window."""

from __future__ import annotations

BYTES = {"bf16": 2, "int8": 1, "fp8": 1}


def weight_stream_bytes(model: dict, dtype: str = "bf16") -> int:
    e, f, l = model["dim"], model["ffn_dim"], model["n_layers"]
    hd = e // model["n_heads"]
    attn = e * model["n_heads"] * hd * 2 + e * model["n_kv_heads"] * hd * 2
    mlp = 3 * e * f
    head = e * model["vocab_size"]
    return (l * (attn + mlp) + head) * BYTES[dtype]


def kv_bytes_per_token(model: dict, dtype: str = "bf16") -> int:
    hd = model["dim"] // model["n_heads"]
    return model["n_layers"] * model["n_kv_heads"] * hd * 2 * BYTES[dtype]


def decode_step_bytes(model: dict, context_lens: list, dtype: str = "bf16") -> int:
    """One decode step over a batch whose sequences hold `context_lens`
    tokens each: all weights once + the visible KV of every sequence."""
    win = model.get("sliding_window") or 0
    seen = sum(min(c, win) if win else c for c in context_lens)
    return weight_stream_bytes(model, dtype) + seen * kv_bytes_per_token(model, dtype)


def load_peaks(path: str, device_kind: str) -> dict:
    import json

    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in {path}: "
                       "add it with its source, there is no default")
    return table[device_kind]
