"""Parameters, bytes and operations of a model with Kimi-delta-attention
layers (Ling-3.0: KDA mixers with a gated latent-attention layer closing every
`kda_layer_period`, leading dense MLPs, then routed experts of which the chip
holds a share, one shared expert, untied head), from shapes alone; kept with
the benchmark like `costs.py`, which it leaves untouched.

Every byte count is a floor: what the step or the kernel cannot avoid moving,
so that a share of the peak bandwidth cannot read over 100 %. Norm weights and
the mixers' vectors (A_log, dt_bias, the head norm) are left out as `costs.py`
leaves norms out.

A decode row moves its whole state: 32 heads x [128, 128] float32 = 2 MiB a
layer in and as much out, for 32 x 128 x 128 x ~6 operations: `kda_update` is
bound by the state's traffic. The chunk kernel (`kda_chunk`: the walk over
blocks of 64 tokens, a head's state resident in VMEM) does four small matmuls
a block a head, 7.3 MFLOP against 196 KiB of operands and state: 37 operations a byte,
under the chip's 240, so its roofline too is its bytes.
"""

from __future__ import annotations

BF16, F32 = 2, 4
BLOCK = 64  # tokens of one block of the chunk kernel (ops/kda.py)
VEC_ROWS = 8  # rows of a head's operand tile in kda_update


def _i(model: dict, key: str) -> int:
    return int(model[key])


def mla_layers(model: dict) -> int:
    """Layers that are latent attention: (l + 1) % period == 0."""
    return _i(model, "n_layers") // _i(model, "kda_layer_period")


def kda_layers(model: dict) -> int:
    return _i(model, "n_layers") - mla_layers(model)


def moe_layers(model: dict) -> int:
    return _i(model, "n_layers") - int(model.get("n_dense_layers") or 0)


def kda_width(model: dict) -> int:
    """H x d_k (= H x d_v): the width of q, k, v and of the decay."""
    return _i(model, "n_heads") * _i(model, "kda_head_dim")


def kda_mixer_params(model: dict) -> int:
    """The matrices of one KDA mixer: q, k, v and the decay [E, H d], the
    output [H d, E], beta and the gate [E, H], the three convolutions."""
    e, w, h = _i(model, "dim"), kda_width(model), _i(model, "n_heads")
    return 4 * e * w + w * e + 2 * e * h + int(model.get("kda_conv", 4)) * 3 * w


def mla_mixer_params(model: dict) -> int:
    """One gated MLA mixer without query compression."""
    e, h = _i(model, "dim"), _i(model, "n_heads")
    dn, dr = _i(model, "qk_nope_head_dim"), _i(model, "qk_rope_head_dim")
    dv, dc = _i(model, "v_head_dim"), _i(model, "kv_lora_rank")
    return e * h * (dn + dr) + e * (dc + dr) + dc * h * (dn + dv) + e * h + h * dv * e


def expert_params(model: dict) -> int:
    return 3 * _i(model, "dim") * _i(model, "moe_ffn_dim")


def experts_held(model: dict) -> int:
    return int(model.get("n_experts_held") or model["n_experts"])


def expert_layer_params(model: dict, experts: float) -> float:
    """One expert layer with `experts` routed experts read: the router, the
    shared expert, the experts."""
    shared = int(model.get("n_shared_experts") or 0)
    return (_i(model, "dim") * _i(model, "n_experts")
            + (shared + experts) * expert_params(model))


def param_count(model: dict) -> int:
    """Every matrix this chip holds (norms and the gate's vectors left out)."""
    e = _i(model, "dim")
    return int(kda_layers(model) * kda_mixer_params(model)
               + mla_layers(model) * mla_mixer_params(model)
               + int(model.get("n_dense_layers") or 0) * 3 * e * _i(model, "ffn_dim")
               + moe_layers(model) * expert_layer_params(model, experts_held(model))
               + 2 * _i(model, "vocab_size") * e)


def state_layer_bytes(model: dict) -> int:
    """One sequence's S in one KDA layer: [H, d_k, d_v] float32."""
    return kda_width(model) * _i(model, "kda_head_dim") * F32


def conv_layer_bytes(model: dict) -> int:
    """Its last kda_conv - 1 inputs of the three convolutions, bf16."""
    return (int(model.get("kda_conv", 4)) - 1) * 3 * kda_width(model) * BF16


def state_slot_bytes(model: dict) -> int:
    return kda_layers(model) * (state_layer_bytes(model) + conv_layer_bytes(model))


def latent_bytes_per_token(model: dict) -> int:
    """The latent and the rotary key of the MLA layers alone, as shaped (the
    pool keeps them in whole 128-lane rows: a floor leaves the padding out)."""
    return mla_layers(model) * (_i(model, "kv_lora_rank") + _i(model, "qk_rope_head_dim")) * BF16


def weight_stream_bytes(model: dict, experts_hit: float) -> float:
    """Weights a decode step reads once: every mixer, the dense MLPs, of each
    expert layer the router, the shared expert and the `experts_hit` held
    experts its rows reached, and the head."""
    e = _i(model, "dim")
    return BF16 * (kda_layers(model) * kda_mixer_params(model)
                   + mla_layers(model) * mla_mixer_params(model)
                   + int(model.get("n_dense_layers") or 0) * 3 * e * _i(model, "ffn_dim")
                   + moe_layers(model) * expert_layer_params(model, experts_hit)
                   + _i(model, "vocab_size") * e)


def decode_step_bytes(model: dict, rows: float, live_tokens: float,
                      experts_hit: float) -> float:
    """One decode step over `rows` sequences that hold `live_tokens` cached
    tokens between them: the weights once, each row's S read and written and
    its convolution inputs read in every KDA layer, the live latents."""
    per_row = kda_layers(model) * (2 * state_layer_bytes(model) + conv_layer_bytes(model))
    return (weight_stream_bytes(model, experts_hit) + rows * per_row
            + live_tokens * latent_bytes_per_token(model))


def kda_update_call_bytes(model: dict, rows: float) -> float:
    """One call of `kda_update` (one KDA layer of one decode step): each
    row's S in and out, its operand tile in (VEC_ROWS float32 rows of d a
    head: alpha, k, q, v, beta) and o out (float32 [H, d_v])."""
    w = kda_width(model)
    return rows * (2 * state_layer_bytes(model) + VEC_ROWS * w * F32 + w * F32)


def kda_chunk_call_bytes(model: dict, tokens: float, segments: float = 1.0) -> float:
    """One call of `kda_chunk` (one KDA layer of one prefill chunk of `tokens`
    real tokens): a block a head reads U, Wk, K^T and Q (each [64, d] float32),
    Aq [64, 64] and the block's decay [8, d], and writes o [64, d]; a segment
    reads and writes a head's state once."""
    h, d = _i(model, "n_heads"), _i(model, "kda_head_dim")
    block = (5 * BLOCK * d + BLOCK * BLOCK + VEC_ROWS * d) * F32
    return h * (tokens / BLOCK * block + segments * 2 * d * d * F32)


def kda_chunk_call_flops(model: dict, tokens: float) -> float:
    """Its operations: Wk S, Q S and K^T W ([64, d] x [d, d] each) and Aq W
    ([64, 64] x [64, d]) a block a head."""
    h, d = _i(model, "n_heads"), _i(model, "kda_head_dim")
    return h * tokens / BLOCK * (3 * 2 * BLOCK * d * d + 2 * BLOCK * BLOCK * d)
