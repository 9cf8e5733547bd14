"""Parameters and bytes of a decoder-hybrid-decoder (Phi-4-mini-flash:
Mamba-1 mixers and window attention in turn, one full attention layer whose
keys and values every cross attention layer reads again, gated memory units;
dynamo_tpu/models/sambay.py), from the configuration's `model` group alone;
kept with the benchmark like `costs.py` and `costs_ssm.py`, which it leaves
untouched (the one reckons every layer as attention, the other counts
attention layers by Jamba's period).

Every byte count is a floor: what the step or the kernel cannot avoid moving,
so that a share of the peak bandwidth cannot read over 100 %. Norm weights,
biases and the small float32 vectors (D, the lambdas, the sub-norm) are left
out of the step's stream as `costs.py` leaves norms out.
"""

from __future__ import annotations

BF16, F32 = 2, 4
KINDS = ("mamba", "window", "full", "gmu", "cross")


def layer_kinds(model: dict) -> list:
    """One word a layer, as `ModelConfig.layer_kinds` derives them."""
    n = int(model["n_layers"])
    half = n // 2
    return [("mamba" if l <= half else "gmu") if l % 2 == 0 else
            "window" if l < half else "full" if l == half + 1 else "cross"
            for l in range(n)]


def count(model: dict, kind: str) -> int:
    return layer_kinds(model).count(kind)


def head_dim(model: dict) -> int:
    return int(model["dim"]) // int(model["n_heads"])


def d_inner(model: dict) -> int:
    return int(model.get("mamba_expand", 2)) * int(model["dim"])


def mixer_matrix_params(model: dict) -> int:
    """A Mamba-1 mixer's matrices: in_proj (to 2 d), the convolution, x_proj
    (to dt_rank + 2 N), dt_proj, out_proj."""
    e, d = int(model["dim"]), d_inner(model)
    n, r, k = int(model["mamba_d_state"]), int(model["mamba_dt_rank"]), int(model.get("mamba_d_conv", 4))
    return e * 2 * d + k * d + d * (r + 2 * n) + r * d + d * e


def mixer_params(model: dict) -> int:
    """And its vectors: the convolution's and dt_proj's biases, A_log, D."""
    d, n = d_inner(model), int(model["mamba_d_state"])
    return mixer_matrix_params(model) + d + d + n * d + d


def attention_matrix_params(model: dict) -> int:
    """Wqkv and out_proj of a differential attention layer."""
    e, hd = int(model["dim"]), head_dim(model)
    h, hk = int(model["n_heads"]), int(model["n_kv_heads"])
    return e * (h + 2 * hk) * hd + h * hd * e


def cross_matrix_params(model: dict) -> int:
    """The query and the output projection of a cross attention layer."""
    e, hd, h = int(model["dim"]), head_dim(model), int(model["n_heads"])
    return 2 * e * h * hd


def diff_vector_params(model: dict, cross: bool) -> int:
    """Biases of the projections, the four lambda vectors, the sub-norm."""
    e, hd = int(model["dim"]), head_dim(model)
    h, hk = int(model["n_heads"]), int(model["n_kv_heads"])
    return (h if cross else h + 2 * hk) * hd + e + 4 * hd + 2 * hd


def gmu_params(model: dict) -> int:
    return 2 * int(model["dim"]) * d_inner(model)


def mlp_params(model: dict) -> int:
    """fc1 (to gate and up) and fc2, no bias."""
    return 3 * int(model["dim"]) * int(model["ffn_dim"])


def param_count(model: dict) -> int:
    """Every parameter: the mixers by kind, each layer's MLP and two
    LayerNorms (weight and bias), the final norm, the tied embedding once."""
    e, l = int(model["dim"]), int(model["n_layers"])
    attn = count(model, "window") + count(model, "full")
    return (count(model, "mamba") * mixer_params(model)
            + attn * (attention_matrix_params(model) + diff_vector_params(model, False))
            + count(model, "cross") * (cross_matrix_params(model) + diff_vector_params(model, True))
            + count(model, "gmu") * gmu_params(model)
            + l * (mlp_params(model) + 4 * e) + 2 * e
            + int(model["vocab_size"]) * e)


def weight_stream_bytes(model: dict) -> int:
    """Weights a decode step reads once: every mixer's matrices, the MLPs,
    and the tied embedding once as the head."""
    attn = count(model, "window") + count(model, "full")
    return BF16 * (count(model, "mamba") * mixer_matrix_params(model)
                   + attn * attention_matrix_params(model)
                   + count(model, "cross") * cross_matrix_params(model)
                   + count(model, "gmu") * gmu_params(model)
                   + int(model["n_layers"]) * mlp_params(model)
                   + int(model["vocab_size"]) * int(model["dim"]))


def state_layer_bytes(model: dict) -> int:
    """One sequence's S in one Mamba layer: [d_state, d] float32."""
    return int(model["mamba_d_state"]) * d_inner(model) * F32


def conv_layer_bytes(model: dict) -> int:
    return (int(model.get("mamba_d_conv", 4)) - 1) * d_inner(model) * BF16


def state_slot_bytes(model: dict) -> int:
    return count(model, "mamba") * (state_layer_bytes(model) + conv_layer_bytes(model))


def kv_token_layer_bytes(model: dict) -> int:
    """K and V of one token in one attention layer's cache (window or
    full): n_kv_heads heads of head_dim each, a pair stored as one head."""
    return 2 * int(model["n_kv_heads"]) * head_dim(model) * BF16


def full_reads(model: dict) -> int:
    """Times a decode step reads the full layer's live KV: the layer itself
    and every cross attention layer."""
    return count(model, "full") + count(model, "cross")


def full_kv_step_bytes(model: dict, live_tokens: float) -> float:
    """The full layer's live keys and values, as often as a step reads them."""
    return full_reads(model) * live_tokens * kv_token_layer_bytes(model)


def window_kv_step_bytes(model: dict, rows: float, context: float) -> float:
    """The window layers' live keys and values: min(context, window) tokens
    a row in each."""
    live = min(float(context), float(model["sliding_window"]))
    return count(model, "window") * rows * live * kv_token_layer_bytes(model)


def decode_step_bytes(model: dict, rows: float, context: float) -> float:
    """One decode step over `rows` sequences of `context` tokens each: the
    weights once, each row's S read and written and its convolution inputs
    read in every Mamba layer, the windows, the full layer's live KV as
    often as it is read."""
    per_row = count(model, "mamba") * (2 * state_layer_bytes(model) + conv_layer_bytes(model))
    return (weight_stream_bytes(model) + rows * per_row
            + window_kv_step_bytes(model, rows, context)
            + full_kv_step_bytes(model, rows * context))


def decode_call_bytes(model: dict, pages: float, rows: float, page_size: int) -> float:
    """Floor of one decode-kernel call on one attention layer's cache
    (window or full: the same shapes): `pages` live pages (summed over the
    rows) of keys and values, each row's padded queries in (2 x n_heads of
    2 x head_dim) and output out, bf16."""
    h, hd = int(model["n_heads"]), head_dim(model)
    return (pages * page_size * kv_token_layer_bytes(model)
            + rows * 2 * (h * 2 * hd) * BF16)
