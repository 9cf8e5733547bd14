"""Weight-only quantization for the serving engine.

Decode is HBM-bandwidth-bound: every step streams the full weight set. Per-
channel symmetric int8 halves that traffic vs bf16 — the dequant (int8 →
bf16 multiply by a per-output-channel scale) fuses into the matmul's
operand load under XLA, so the MXU still sees bf16 operands while HBM moves
half the bytes. The reference reaches quantized serving through its engines
(vLLM/TRT-LLM fp8/int8 checkpoints); this is the native TPU path.

Convention: a quantized weight is the dict {"q": int8 [..., in, out],
"s": f32 [..., 1, out]} (scale broadcasting over the contraction dim).
`mm(x, w)` is the single matmul entry point the model uses — it accepts
either a plain array or a quantized dict, so one forward serves both.
The embedding table quantizes per ROW (scale [V, 1], "dt" dtype sentinel)
because the row is both the gather unit and the tied lm_head's output
channel.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp

# weights worth quantizing: the per-step streamed bulk. The vocab matrix is
# included — at 3B scale the tied embed/lm_head is ~12% of decode traffic
# (128k x 3k bf16 = 0.79 GB read every step for logits) and per-channel
# int8 keeps argmax/top-k sampling stable. Norms stay f32.
DEFAULT_QUANT_NAMES = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down",
    "w_fc1", "w_fc2",  # a fused gate-and-up MLP (models/sambay.py)
    "embed", "lm_head",
)


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def mm(x: jax.Array, w: Any) -> jax.Array:
    """x @ w for plain or quantized weights (dequant fused by XLA)."""
    if is_quantized(w):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _quantize_impl(w: jax.Array, mode: str, axis: int) -> Dict[str, jax.Array]:
    # One fused kernel: the fp32 intermediates never materialize in HBM
    # (eager op-by-op would allocate a full fp32 copy per op — 2x the bf16
    # leaf — which OOMs a 16G chip during whole-model quantization).
    amax = jnp.max(jnp.abs(w).astype(jnp.float32), axis=axis, keepdims=True)
    if mode == "fp8":
        scale = jnp.maximum(amax, 1e-8) / 448.0  # e4m3 finite max
        q = (w.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    else:
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
            jnp.int8
        )
    return {"q": q, "s": scale}


# donating variant: XLA reuses the source buffer for the output — the
# caller's array is DELETED on accelerator backends, so this is only safe
# on arrays the caller owns exclusively (quantize_params(donate=True))
_quantize_donating = jax.jit(
    _quantize_impl, static_argnames=("mode", "axis"), donate_argnums=(0,)
)
_quantize_keeping = jax.jit(_quantize_impl, static_argnames=("mode", "axis"))


def _quantize(w: Any, mode: str, axis: int, donate: bool) -> Dict[str, jax.Array]:
    if not isinstance(w, jax.Array):
        # host array: the device copy made by asarray is ours to donate
        return _quantize_donating(jnp.asarray(w), mode, axis)
    fn = _quantize_donating if donate else _quantize_keeping
    return fn(w, mode, axis)


def quantize_weight(
    w: jax.Array, mode: str = "int8", donate: bool = False
) -> Dict[str, jax.Array]:
    """Per-output-channel symmetric quantization. w [..., in, out] → q/s
    dict. Modes: int8 (127-step, robust everywhere) and fp8 (e4m3 — keeps
    more dynamic range per channel; v5p+ has native fp8 matmul paths).
    donate=True deletes the source array (memory headroom during whole-
    model quantization) — only pass it for arrays nobody else holds."""
    return _quantize(w, mode, -2, donate)


def quantize_embed(
    w: jax.Array, mode: str = "int8", donate: bool = False
) -> Dict[str, jax.Array]:
    """Quantize the [V, E] embedding table with per-row scales. The "dt"
    zero-size leaf records the table's pre-quantization dtype so
    embed_lookup can keep the activation dtype the model was built with."""
    dt = w.dtype
    out = _quantize(w, mode, -1, donate)
    out["dt"] = jnp.zeros((0,), dt)
    return out


def dequantize_weight(w: Dict[str, jax.Array], dtype=jnp.bfloat16) -> jax.Array:
    return (w["q"].astype(jnp.float32) * w["s"]).astype(dtype)


def embed_lookup(embed: Any, tokens: jax.Array) -> jax.Array:
    """Token-embedding gather for plain or row-quantized tables."""
    if is_quantized(embed):
        dt = embed["dt"].dtype if "dt" in embed else jnp.bfloat16
        return embed["q"][tokens].astype(dt) * embed["s"][tokens].astype(dt)
    return embed[tokens]


def tied_logits(h: jax.Array, embed: Any) -> jax.Array:
    """h @ embed.T for plain or row-quantized tables (tied lm_head)."""
    if is_quantized(embed):
        return (h @ embed["q"].T.astype(h.dtype)) * embed["s"][:, 0].astype(h.dtype)
    return h @ embed.T


# -- KV-cache quantization --------------------------------------------------
# A quantized KV pool is the dict {"q": int8 [L, NP, PS, Hk, D],
# "s": f32 [L, NP, PS, Hk]} — one symmetric scale per cached (token, head)
# vector, reduced over the head dim. 132 bytes per vector vs 256 bf16, so
# decode's per-step KV stream nearly halves. The token-major pool layout
# (models/llama.py make_kv_pool) leaves the scales naturally aligned with
# "q" minus the vector dim — kv_quantize/kv_dequantize apply verbatim,
# and Pallas blocks one page of scales as a legal (None, PS, Hk) tile
# (minor dims (PS, Hk) = full array dims). The pool rides through jit /
# lax.scan / donation as a pytree; attention folds the scales into the
# softmax scores (K) and probabilities (V) instead of dequantizing whole
# pages. Reference analog: the KV block manager's fp8 KV layouts
# (lib/kvbm-kernels/cuda/tensor_kernels.cu) — engine-owned quantized cache.


@jax.jit
def kv_quantize(x: jax.Array) -> Dict[str, jax.Array]:
    """Quantize KV vectors over the last (head) dim: [..., D] → {"q":
    int8 [..., D], "s": f32 [...]}. Used for pool writes and onboarding."""
    amax = jnp.max(jnp.abs(x).astype(jnp.float32), axis=-1)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127
    ).astype(jnp.int8)
    return {"q": q, "s": s}


def kv_dequantize(d: Dict[str, jax.Array], dtype=jnp.bfloat16) -> jax.Array:
    """{"q","s"} → dense [..., D] in `dtype` (transfer/offload boundary —
    host tiers and the disagg wire format stay bf16 so heterogeneous
    workers interoperate; onboarding re-quantizes)."""
    return (d["q"].astype(jnp.float32) * d["s"][..., None]).astype(dtype)


def kv_pool_quantize(pool: jax.Array) -> Dict[str, jax.Array]:
    """Quantize a dense token-major KV pool [..., NP, PS, Hk, D] into the
    pool convention. With the token-major layout the scales align with
    "q" minus the vector dim, so this IS kv_quantize — kept as a named
    entry point so pool-building callers don't depend on that
    coincidence."""
    return kv_quantize(pool)


def kv_pool_dequantize(pool: Dict[str, jax.Array], dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of kv_pool_quantize: pool-convention dict → dense
    [..., NP, PS, Hk, D]."""
    return kv_dequantize(pool, dtype)


def quantize_params(
    params: Dict[str, Any], names: Iterable[str] = DEFAULT_QUANT_NAMES,
    mode: str = "int8", donate: bool = False,
) -> Dict[str, Any]:
    """Quantize the named layer weights of a llama param tree in place-ish
    (returns a new tree; unquantized leaves pass through). donate=True
    frees each source leaf as it converts — pass it only when the caller
    owns `params` exclusively (e.g. a tree it just random-initialized)."""
    names = set(names)
    out = dict(params)
    layers = dict(params["layers"])
    for name in list(layers):
        if name in names:
            layers[name] = quantize_weight(layers[name], mode, donate=donate)
    out["layers"] = layers
    if "embed" in names and not is_quantized(out["embed"]):
        out["embed"] = quantize_embed(out["embed"], mode, donate=donate)
    if "lm_head" in names and out.get("lm_head") is not None:
        if not is_quantized(out["lm_head"]):
            out["lm_head"] = quantize_weight(out["lm_head"], mode, donate=donate)
    return out
