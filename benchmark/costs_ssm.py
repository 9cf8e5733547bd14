"""Parameters and bytes of a hybrid state-space model (Jamba: Mamba-1 mixers
with an attention layer every `attn_layer_period`, a dense MLP in every
layer, tied embedding), from shapes alone; kept with the benchmark like
`costs.py`, which it leaves untouched and which reckons every layer as
attention.

Every byte count is a floor: what the step or the kernel cannot avoid moving,
so that a share of the peak bandwidth cannot read over 100 %. Norm weights and
the mixers' vectors (biases, D) are left out as `costs.py` leaves norms out.

The selective scan is not bound by these bytes: a token of a layer is
N x d = 81,920 multiply-add triples and as many `exp`s (the decays
exp(dt (x) A) are not shared between tokens) against ~62 KB of operands, so on
a prefill chunk the vector and transcendental units set its time and its
share of the HBM roofline is small by nature. A decode row (one token) moves
its whole state, 2 x 327,680 bytes a layer, for the same 81,920 triples: that
one is bound by the state's traffic, which is why the state pool is the
mechanism the decode-heavy cell measures.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def d_inner(model: dict) -> int:
    return int(model.get("mamba_expand", 2)) * int(model["dim"])


def attn_layers(model: dict) -> int:
    """Layers that are attention: l % period == offset."""
    p, o = int(model["attn_layer_period"]), int(model["attn_layer_offset"])
    return sum(l % p == o for l in range(int(model["n_layers"])))


def mamba_layers(model: dict) -> int:
    return int(model["n_layers"]) - attn_layers(model)


def mixer_params(model: dict) -> int:
    """One Mamba-1 mixer with Jamba's inner norms: in_proj (to 2 d), the
    depthwise convolution and its bias, x_proj (to dt_rank + 2 N), the three
    norms, dt_proj and its bias, A_log, D, out_proj."""
    e, d = int(model["dim"]), d_inner(model)
    n, r, k = int(model["mamba_d_state"]), int(model["mamba_dt_rank"]), int(model.get("mamba_d_conv", 4))
    return (e * 2 * d + k * d + d + d * (r + 2 * n) + r + 2 * n
            + r * d + d + d * n + d + d * e)


def mixer_matrix_params(model: dict) -> int:
    """What a step streams of a mixer in bf16: its four matrices and the
    convolution (the vectors and A_log, float32 and small, are left out)."""
    e, d = int(model["dim"]), d_inner(model)
    n, r, k = int(model["mamba_d_state"]), int(model["mamba_dt_rank"]), int(model.get("mamba_d_conv", 4))
    return e * 2 * d + k * d + d * (r + 2 * n) + r * d + d * e


def attention_params(model: dict) -> int:
    e, h = int(model["dim"]), int(model["n_heads"])
    hd = e // h
    return 2 * e * h * hd + 2 * e * int(model["n_kv_heads"]) * hd


def mlp_params(model: dict) -> int:
    return 3 * int(model["dim"]) * int(model["ffn_dim"])


def param_count(model: dict) -> int:
    """Every parameter, the two norms of each layer and the final one
    included; the tied embedding once."""
    e, l = int(model["dim"]), int(model["n_layers"])
    return (mamba_layers(model) * mixer_params(model)
            + attn_layers(model) * attention_params(model)
            + l * (mlp_params(model) + 2 * e) + e
            + int(model["vocab_size"]) * e)


def state_layer_bytes(model: dict) -> int:
    """One sequence's S in one layer: [d_state, d] float32."""
    return int(model["mamba_d_state"]) * d_inner(model) * F32


def conv_layer_bytes(model: dict) -> int:
    """Its last d_conv - 1 convolution inputs in one layer, bf16."""
    return (int(model.get("mamba_d_conv", 4)) - 1) * d_inner(model) * BF16


def state_slot_bytes(model: dict) -> int:
    """What a sequence holds beside its pages, whatever its length."""
    return mamba_layers(model) * (state_layer_bytes(model) + conv_layer_bytes(model))


def kv_bytes_per_token(model: dict) -> int:
    """K and V of the attention layers alone."""
    hd = int(model["dim"]) // int(model["n_heads"])
    return attn_layers(model) * int(model["n_kv_heads"]) * hd * 2 * BF16


def weight_stream_bytes(model: dict) -> int:
    """Weights a decode step reads once: the mixers' matrices, the attention
    projections, the MLPs, and the tied embedding once as the head."""
    return BF16 * (mamba_layers(model) * mixer_matrix_params(model)
                   + attn_layers(model) * attention_params(model)
                   + int(model["n_layers"]) * mlp_params(model)
                   + int(model["vocab_size"]) * int(model["dim"]))


def decode_step_bytes(model: dict, rows: float, live_tokens: float) -> float:
    """One decode step over `rows` sequences that hold `live_tokens` tokens
    of KV between them: the weights once, each row's S read and written and
    its convolution inputs read in every Mamba layer, the live KV."""
    per_row = mamba_layers(model) * (2 * state_layer_bytes(model) + conv_layer_bytes(model))
    return weight_stream_bytes(model) + rows * per_row + live_tokens * kv_bytes_per_token(model)


def ssm_update_call_bytes(model: dict, rows: float) -> float:
    """One call of `ssm_update` (one layer of one decode step): each row's S
    in and out, its operands as the kernel takes them (c, dt in and y out:
    float32 [d]; B, C: float32 [N]), and A [N, d] float32 once."""
    d, n = d_inner(model), int(model["mamba_d_state"])
    return rows * (2 * state_layer_bytes(model) + 3 * d * F32 + 2 * n * F32) + n * d * F32


def ssm_scan_call_bytes(model: dict, tokens: float, row_segments: float,
                        chunk_segments: float) -> float:
    """One call of `ssm_scan` (one layer of one flat step): each token's
    operands (as ssm_update's), the state of every decode-row segment in and
    out, of every chunk segment out (a chunk that starts its sequence reads
    none: the floor), and A once."""
    d, n = d_inner(model), int(model["mamba_d_state"])
    s = state_layer_bytes(model)
    return (tokens * (3 * d * F32 + 2 * n * F32) + row_segments * 2 * s
            + chunk_segments * s + n * d * F32)
