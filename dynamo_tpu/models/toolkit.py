"""Shared transformer toolkit: norms, RoPE (+ llama3/yarn scaling),
paged attention (jnp reference path), the token-major KV pool and its
scatter writer. Every model family (llama/qwen dense, Gemma-2, DeepSeek
MLA, MoE) composes these; family modules add only what differs.

Split out of models/llama.py (r5) so new architectures extend a family
module instead of growing one god-module. TPU-first notes live with each
function (pool layout rationale on make_kv_pool, scatter form on
_write_kv).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models.config import ModelConfig

Params = Dict[str, Any]


class SideCacheOps(NamedTuple):
    """What the module of a model with a cache beside its KV pages answers
    the runner, as its `SIDE` (engine/model_runner.py `_side_ops`;
    docs/FAMILIES.md). The step programs take the pool as `state=` and
    return it last, donated; `rows` and `segs` build what they take as
    `slots=` and `seg_slots=`, in the format the model's forward reads, from
    the operands the engine's side cache hands a step (`Runner` steps'
    `side=`; None: the scratch unit). A model that keeps several kinds
    (models/sambay.py: a state slot and window pages) names them joined by
    "+", in the order of engine/side_cache.KINDS' parts: its `units` and
    unit bytes are tuples, one entry a kind, its pool whatever its forward
    reads, and a sequence's operand the tuple of what it holds in each."""

    kind: str  # Runner.side_kind (engine/side_cache.KINDS; "a+b": both)
    make_pool: Callable  # (config, units, page_size, dtype) -> the pool:
    #   zeros, unit 0 scratch
    unit_bytes: Callable  # (config, page_size, dtype) -> bytes of one unit
    rows: Callable  # (sides, B, max_pages) -> `slots` at bucket B: a row a
    #   sequence (None: a pad row), pad rows behind them
    segs: Callable  # (sides, lens, seg_cap, t_bucket, max_pages) ->
    #   `seg_slots` of the ragged step: a segment a sequence (decode rows
    #   first, then the chunks, as _prep_ragged lays them) of lens[i] tokens
    forward: Callable  # models/llama.forward's operands and `state=`,
    #   `slots=` -> (logits, k_pool, v_pool, *picks, pool); picks (the chosen
    #   experts, the listed ones) where the model routes


def make_kv_pool(
    config: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
    kv_quantize: Optional[str] = None,
):
    """Pool layout [L, NP, PS, Hk, D] — token-major. Chosen for the TPU
    memory system, measured on v5e:
    - a page is one CONTIGUOUS PS*Hk*D slab, so the Pallas kernels DMA it
      in a single transfer (the head-major layout needed Hk strided
      chunks per page), with a legal (PS, Hk, D) → minor (Hk=8, D=128)
      tile;
    - the decode KV append is a scatter whose index dim is the LEADING
      axis of a [L, NP*PS, Hk, D] view with contiguous [Hk, D] rows —
      the only scatter form XLA:TPU lowers to a fast in-place update
      (~6x faster than head-major scatters in the decode loop);
    - every pool representation (dense, int8 "q", int8 "s") has the page
      axis at 1, so page indexing tree_maps uniformly.

    kv_quantize="int8" returns dict pools {"q": int8 [L, NP, PS, Hk, D],
    "s": f32 [L, NP, PS, Hk]} (models/quant.py KV convention — the scale
    tree aligns with "q" minus the vector dim, no transposes anywhere).

    MLA models cache ONE latent vector per token ([..., 1, d_c + d_rh] —
    the whole point of the architecture: V3's cache is 57x smaller than
    its full-head equivalent). The "k" pool holds the latent; the "v"
    pool shrinks to a 1-wide placeholder so every page-indexed code path
    (transfer, tiering, disagg export) keeps its uniform k/v shape
    contract without meaningful memory. A model with an indexer
    (DeepSeek-V3.2) caches its index keys there instead of the stub."""
    if config.is_mla:
        # (a model with KDA layers keeps latents of its MLA layers alone)
        lat = (config.kv_layers, num_pages, page_size, 1, config.mla_pool_dim)
        stub = (config.kv_layers, num_pages, page_size, 1, 1)
        if config.has_indexer:
            # the indexer's keys take the second array: one index_head_dim
            # vector a token beside its latent, under the same page table
            # and the same page ids, so whatever shares, copies, exports or
            # offloads a page by its id carries both (models/mla.py)
            if kv_quantize is not None:
                raise ValueError(
                    "a model with an indexer keeps its latent pages and its "
                    "index keys unquantized (engine/runner_api.py "
                    "refusal)")
            stub = stub[:-1] + (config.index_head_dim,)
        if kv_quantize == "int8":
            # int8 latent cache: one f32 scale per (token) latent vector —
            # halves V3's already-57x-smaller cache again. The Pallas MLA
            # kernels don't carry int8 yet, so the model falls back to
            # the jnp gather path for quantized MLA (models/mla.py).
            return (
                {"q": jnp.zeros(lat, jnp.int8),
                 "s": jnp.zeros(lat[:-1], jnp.float32)},
                {"q": jnp.zeros(stub, jnp.int8),
                 "s": jnp.zeros(stub[:-1], jnp.float32)},
            )
        if kv_quantize is not None:
            raise ValueError(f"unknown kv_quantize mode {kv_quantize!r}")
        return jnp.zeros(lat, dtype=dtype), jnp.zeros(stub, dtype=dtype)
    # a hybrid model's pool holds its attention layers alone (kv_layers)
    shape = (config.kv_layers, num_pages, page_size, config.n_kv_heads, config.head_dim)
    if config.is_sambay:
        # the one full layer's pool, a pair of KV heads as one head twice
        # as wide, whole 8-row tiles of them (`ModelConfig.pool_heads`; that
        # module also makes the window pool)
        if kv_quantize is not None:
            raise ValueError(
                "a decoder-hybrid-decoder's caches are not quantized")
        shape = shape[:3] + (config.pool_heads, 2 * config.head_dim)
        return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)
    if config.has_window_pool:
        # the global layers' pool, values narrower than keys (the window
        # layers' is models/mimo.make_window_pool)
        if kv_quantize is not None:
            raise ValueError("a window-pool model's caches are not quantized")
        return (jnp.zeros(shape[:-1] + (config.key_pool_dim,), dtype=dtype),
                jnp.zeros(shape[:-1] + (config.value_dim,), dtype=dtype))
    if kv_quantize == "int8":
        mk = lambda: {
            "q": jnp.zeros(shape, jnp.int8),
            "s": jnp.zeros(shape[:-1], jnp.float32),
        }
        return mk(), mk()
    if kv_quantize is not None:
        raise ValueError(f"unknown kv_quantize mode {kv_quantize!r}")
    return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)


# --------------------------------------------------------------------------
# building blocks
# --------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             zero_centered: bool = False) -> jax.Array:
    """zero_centered (Gemma): weights store w with output = normed*(1+w)."""
    xf = x.astype(jnp.float32)
    normed = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = weight + 1.0 if zero_centered else weight
    return (normed * w).astype(x.dtype)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    """LayerNorm with bias, in float32 (models/mla.py's index keys,
    models/sambay.py's every norm)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _yarn_mscale(scale: float, mscale: float) -> float:
    import math

    if scale <= 1.0 or mscale == 0.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(config: Optional[ModelConfig], hd: int, theta: float):
    """[hd//2] f32 inverse frequencies with the config's long-context
    scaling applied (HF rope_scaling semantics):
    - "llama3": wavelengths past orig_max/low_freq_factor interpolate by
      1/factor; short ones keep base; a smooth band blends between.
    - "yarn": NTK-by-parts — per-dim blend of interpolated (1/factor)
      and base frequencies with a ramp between the beta_fast/beta_slow
      correction dims (DeepSeek V2/V3 long-context recipe).
    Computed in numpy (static per compile — positions vary, these don't).
    """
    import math

    half = hd // 2
    base = theta ** -(np.arange(0, half, dtype=np.float64) / half)
    if config is None or config.rope_scaling == "none":
        return jnp.asarray(base, jnp.float32)
    c = config
    if c.rope_scaling == "linear":
        # uniform position interpolation (Gemma-3 global layers: factor 8)
        return jnp.asarray(base / c.rope_factor, jnp.float32)
    if c.rope_scaling == "llama3":
        orig = c.rope_orig_max_seq or c.max_seq_len
        wavelen = 2.0 * math.pi / base
        low_wl = orig / c.rope_low_freq_factor
        high_wl = orig / c.rope_high_freq_factor
        smooth = (orig / wavelen - c.rope_low_freq_factor) / max(
            c.rope_high_freq_factor - c.rope_low_freq_factor, 1e-9
        )
        smooth = np.clip(smooth, 0.0, 1.0)
        blended = (1 - smooth) * base / c.rope_factor + smooth * base
        out = np.where(
            wavelen < high_wl, base,
            np.where(wavelen > low_wl, base / c.rope_factor, blended),
        )
        return jnp.asarray(out, jnp.float32)
    if c.rope_scaling == "yarn":
        orig = c.rope_orig_max_seq or c.max_seq_len

        def corr_dim(n_rot: float) -> float:
            return (hd * math.log(orig / (n_rot * 2 * math.pi))) / (
                2 * math.log(theta)
            )

        low = max(math.floor(corr_dim(c.rope_beta_fast)), 0)
        high = min(math.ceil(corr_dim(c.rope_beta_slow)), hd - 1)
        ramp = np.clip(
            (np.arange(half, dtype=np.float64) - low) / max(high - low, 1),
            0.0, 1.0,
        )
        extrap_mask = 1.0 - ramp  # 1 → keep base (high-freq dims)
        out = (base / c.rope_factor) * (1 - extrap_mask) + base * extrap_mask
        return jnp.asarray(out, jnp.float32)
    raise ValueError(f"unknown rope_scaling {c.rope_scaling!r}")


def rope(x: jax.Array, positions: jax.Array, theta: float,
         config: Optional[ModelConfig] = None,
         inv_freq: Optional[jax.Array] = None) -> jax.Array:
    """HF-Llama half-rotation RoPE. x: [..., S, n_heads, head_dim],
    positions: [..., S]. `config` applies its rope_scaling (llama3/yarn
    frequency remap + yarn's cos/sin magnitude mscale). An explicit
    `inv_freq` overrides the table (dual-rope models select per layer —
    Gemma-3's local/global bases — inside the layer scan)."""
    hd = x.shape[-1]
    half = hd // 2
    if inv_freq is None:
        inv_freq = rope_inv_freq(config, hd, theta)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., S, half]
    m = 1.0
    if config is not None and config.rope_scaling == "yarn":
        m = _yarn_mscale(config.rope_factor, config.rope_mscale)
        if config.rope_mscale_all_dim:
            m = m / _yarn_mscale(config.rope_factor, config.rope_mscale_all_dim)
    cos = (jnp.cos(angles) * m)[..., None, :]  # broadcast over heads
    sin = (jnp.sin(angles) * m)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def attn_score_scale(config: ModelConfig, qk_dim: int) -> float:
    """Softmax scale incl. yarn's mscale^2 correction (DeepSeek modeling:
    softmax_scale = qk_dim^-0.5 * mscale(factor, mscale_all_dim)^2)."""
    scale = qk_dim ** -0.5
    if config.rope_scaling == "yarn" and config.rope_mscale_all_dim:
        m = _yarn_mscale(config.rope_factor, config.rope_mscale_all_dim)
        scale = scale * m * m
    return scale


def paged_attention_jnp(
    q: jax.Array,  # [B, S, Hk, G, Dh] (grouped query heads)
    k_pool_l: jax.Array,  # [NP, PS, Hk, Dh] one layer's key pool
    v_pool_l: jax.Array,
    page_table: jax.Array,  # [B, MP] int32
    q_positions: jax.Array,  # [B, S] absolute positions of the queries
    kv_lens: jax.Array,  # [B] context length (tokens valid in pool)
    return_stats: bool = False,
    scale: Optional[float] = None,  # score scale override (MLA: the
    #   effective qk dim differs from the cached vector's dim)
    softcap: float = 0.0,  # Gemma-2 attention-score soft capping
    window=None,  # sliding window (traced per-layer scalar; None/0 = off)
    sink=None,  # f32 [Hk, G] learned sink logit a query head (gpt_oss,
    #   MiMo-V2): one more column of the softmax that takes mass and gives
    #   no value; None = no such column. Values may be narrower than keys.
):
    """Reference (jnp gather) paged attention with causal masking by
    absolute position. Flat context index c == absolute position c because
    page tables map positions in order. Returns [B, S, Hk, G, Dh]; with
    `return_stats`, also fp32 (m, l) [B, S, Hk, G, 1] online-softmax stats
    (rows with an empty context get l == 0 and out == 0, so merging with
    attention over other context stays exact)."""
    def gather(pool_l, dtype):
        if isinstance(pool_l, dict):  # int8 KV (models/quant.py): dequant
            # rides the gather; XLA fuses the cast+scale into operand load.
            # Multiply in f32 (scales are f32) so this path and the Pallas
            # kernels apply identical scale math, then cast the product.
            g = pool_l["q"][page_table].astype(jnp.float32)
            s = pool_l["s"][page_table][..., None]  # aligned with g
            pool_l = (g * s).astype(dtype)
        else:
            pool_l = pool_l[page_table]
        B, MP, PS, Hk, Dh = pool_l.shape
        return pool_l.reshape(B, MP * PS, Hk, Dh)

    k = gather(k_pool_l, q.dtype)
    v = gather(v_pool_l, q.dtype)
    _, C, Hk, Dh = k.shape

    if scale is None:
        scale = Dh**-0.5
    scores = jnp.einsum("bskgd,bckd->bkgsc", q, k).astype(jnp.float32) * scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    ctx_pos = jnp.arange(C, dtype=jnp.int32)
    valid = (ctx_pos[None, :] < kv_lens[:, None])[:, None, None, None, :]
    causal = ctx_pos[None, None, :] <= q_positions[:, :, None]  # [B,S,C]
    if window is not None:
        # sliding window: only the last `window` positions are visible
        # (window <= 0 disables — the per-layer Gemma-2 pattern rides a
        # scanned scalar, so this stays trace-friendly)
        win = jnp.where(jnp.asarray(window) > 0, jnp.asarray(window), 1 << 30)
        causal = causal & (
            ctx_pos[None, None, :] > q_positions[:, :, None] - win
        )
    mask = valid & causal[:, None, None, :, :]
    scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)  # [B,Hk,G,S,1]
    if sink is not None:
        with jax.named_scope("attn.sink"):
            b = sink.astype(jnp.float32)[None, :, :, None, None]
            m = jnp.maximum(m, b)
    p = jnp.where(mask, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(b - m)
    out = jnp.einsum("bkgsc,bckd->bskgd", (p / jnp.maximum(l, 1e-30)).astype(q.dtype), v)
    if return_stats:
        t = lambda x: x.transpose(0, 3, 1, 2, 4)  # [B,Hk,G,S,1] → [B,S,Hk,G,1]
        return out, t(m), t(l)
    return out


def _write_kv(pool, l_idx, new, page_table, positions):
    """Scatter new KV for layer l_idx into the full stacked token-major
    pool [L, NP, PS, Hk, Dh] — the pool stays a single carried buffer
    across the layer scan, never a per-layer copy. new: [B, S, Hk, Dh];
    positions: [B, S] absolute positions, -1 marks padding (dropped via
    out-of-bounds scatter + mode='drop'). Dict pools (int8 KV,
    models/quant.py) quantize on write — one scale per written
    (token, head) vector.

    The scatter runs on a [L, NP*PS, Hk, Dh] view with ONE flat token
    index per written vector, immediately after the (scalar) layer index:
    the update rows are contiguous [Hk, Dh] slabs addressed by a single
    leading index — the form XLA:TPU keeps in place (measured ~6x faster
    in the decode loop than indices straddling a sliced head axis)."""
    if isinstance(pool, dict):
        L, NP, PS, Hk, Dh = pool["q"].shape
    else:
        L, NP, PS, Hk, Dh = pool.shape
    B, S = positions.shape
    MP = page_table.shape[1]
    valid = positions >= 0
    pos = jnp.maximum(positions, 0)
    page_of_pos = jnp.clip((pos // PS).astype(jnp.int32), 0, MP - 1)
    page_idx = jnp.take_along_axis(page_table, page_of_pos, axis=1)  # [B, S]
    # OOB → dropped; distinct OOB values per padding token keep the index
    # set duplicate-free so unique_indices=True below stays honest
    oob = NP + jnp.arange(B * S, dtype=jnp.int32).reshape(B, S)
    page_idx = jnp.where(valid, page_idx, oob)
    slot = (pos % PS).astype(jnp.int32)
    flat = (page_idx * PS + slot).reshape(-1)  # [B*S] flat token cells
    kw = dict(mode="drop", unique_indices=True)
    if isinstance(pool, dict):
        from dynamo_tpu.models.quant import kv_quantize

        d = kv_quantize(new.reshape(B * S, Hk, Dh))
        return {
            "q": pool["q"].reshape(L, NP * PS, Hk, Dh)
            .at[l_idx, flat].set(d["q"], **kw).reshape(L, NP, PS, Hk, Dh),
            "s": pool["s"].reshape(L, NP * PS, Hk)
            .at[l_idx, flat].set(d["s"], **kw).reshape(L, NP, PS, Hk),
        }
    return (
        pool.reshape(L, NP * PS, Hk, Dh)
        .at[l_idx, flat].set(new.reshape(B * S, Hk, Dh), **kw)
        .reshape(L, NP, PS, Hk, Dh)
    )
