"""Bytes a step has to move, from shapes alone: the yardstick's own
arithmetic, kept with the benchmark so that no later PR can change it.

A decode step reads every weight it uses once (the embedding table is
gathered, not streamed: only the rows of the batch are read; norm weights are
left out) and the cache of every live token of every sequence in the batch,
bounded by the sliding window. Everything is reckoned from the
configuration's `model` group (the program's ModelConfig field names) by
architecture: attention by `attn_type`, feed-forward by layer kind."""

from __future__ import annotations

BYTES = {"bf16": 2, "int8": 1, "fp8": 1}


def attn_params(model: dict) -> int:
    """One layer's attention projections. `gqa`: wq, wk, wv, wo at the head
    size (`head_dim_override` where set). `mla`: wq (or wq_lat + wq_up when
    the query is compressed), wkv_a, wkv_b, wo."""
    e, h = model["dim"], model["n_heads"]
    if model.get("attn_type", "gqa") == "mla":
        dc, dr = model["kv_lora_rank"], model["qk_rope_head_dim"]
        dn, dv, qr = model["qk_nope_head_dim"], model["v_head_dim"], model.get("q_lora_rank") or 0
        wq = e * qr + qr * h * (dn + dr) if qr else e * h * (dn + dr)
        return wq + e * (dc + dr) + dc * h * (dn + dv) + h * dv * e
    hd = model.get("head_dim_override") or e // h
    return e * h * hd * 2 + e * model["n_kv_heads"] * hd * 2


def expert_layer_params(model: dict, experts_hit: int) -> int:
    """One expert layer's feed-forward: the router, the shared experts (one
    fused FFN) and `experts_hit` routed experts."""
    e, f = model["dim"], model["moe_ffn_dim"]
    shared = model.get("shared_expert_ffn_dim") or (model.get("n_shared_experts") or 0) * f
    return e * model["n_experts"] + 3 * e * shared + experts_hit * 3 * e * f


def weight_stream_bytes(model: dict, dtype: str = "bf16", experts_hit: float | None = None) -> int | float:
    """Weights one step reads. `experts_hit` is how many routed experts of an
    expert layer the step's rows reach between them; the default is
    `n_experts_active`, the least a step can read (every row agrees), so a
    share reckoned without a counter is understated and never overstated. A
    reader that has the measured number passes it (a mean over layers and
    steps, so not a whole number); a path that computes every expert streams
    `n_experts`."""
    l, n_exp = model["n_layers"], model.get("n_experts") or 0
    dense = (model.get("n_dense_layers") or 0) if n_exp else l
    total = l * attn_params(model) + dense * 3 * model["dim"] * model["ffn_dim"]
    if n_exp:
        hit = model["n_experts_active"] if experts_hit is None else experts_hit
        total += (l - dense) * expert_layer_params(model, min(max(hit, 0), n_exp))
    head = model["dim"] * model["vocab_size"]
    return (total + head) * BYTES[dtype]


def kv_bytes_per_token(model: dict, dtype: str = "bf16") -> int:
    """Cache one token holds over all layers: K and V at the head size, or
    the latent and the decoupled rotary key of latent attention."""
    if model.get("attn_type", "gqa") == "mla":
        return model["n_layers"] * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * BYTES[dtype]
    hd = model.get("head_dim_override") or model["dim"] // model["n_heads"]
    return model["n_layers"] * model["n_kv_heads"] * hd * 2 * BYTES[dtype]


def decode_step_bytes(model: dict, context_lens: list, dtype: str = "bf16",
                      experts_hit: int | None = None) -> int:
    """One decode step over a batch whose sequences hold `context_lens`
    tokens each: the weights once + the visible cache of every sequence."""
    win = model.get("sliding_window") or 0
    seen = sum(min(c, win) if win else c for c in context_lens)
    return (weight_stream_bytes(model, dtype, experts_hit)
            + seen * kv_bytes_per_token(model, dtype))


def load_peaks(path: str, device_kind: str) -> dict:
    import json

    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in {path}: "
                       "add it with its source, there is no default")
    return table[device_kind]
