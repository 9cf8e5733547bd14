"""On-device token sampling: greedy / temperature / top-k / top-p.

Runs fused at the end of the jitted decode step (logits never leave the
device except as one sampled token id per sequence).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SamplingParams(NamedTuple):
    """Per-sequence device-resident sampling state (arrays of shape [B])."""

    temperature: jax.Array  # f32; 0 → greedy
    top_k: jax.Array  # i32; 0 → disabled
    top_p: jax.Array  # f32; 1.0 → disabled
    key: jax.Array  # [B, 2] u32 PRNG keys
    rep_penalty: jax.Array  # f32; 1.0 → disabled (HF-style multiplicative)
    freq_penalty: jax.Array  # f32; 0.0 → disabled (count-scaled subtract)
    presence_penalty: jax.Array  # f32; 0.0 → disabled (flat subtract)

    @classmethod
    def make(
        cls, temperature, top_k, top_p, seeds,
        rep_penalty=None, freq_penalty=None, presence_penalty=None,
    ) -> "SamplingParams":
        n = len(temperature)
        return cls(
            temperature=jnp.asarray(temperature, jnp.float32),
            top_k=jnp.asarray(top_k, jnp.int32),
            top_p=jnp.asarray(top_p, jnp.float32),
            key=jax.vmap(lambda s: jax.random.key_data(jax.random.PRNGKey(s)))(
                jnp.asarray(seeds, jnp.uint32)
            ),
            rep_penalty=jnp.asarray(
                [1.0] * n if rep_penalty is None else rep_penalty, jnp.float32
            ),
            freq_penalty=jnp.asarray(
                [0.0] * n if freq_penalty is None else freq_penalty, jnp.float32
            ),
            presence_penalty=jnp.asarray(
                [0.0] * n if presence_penalty is None else presence_penalty,
                jnp.float32,
            ),
        )


def apply_penalties(
    logits: jax.Array,
    counts_all: jax.Array,
    counts_out: jax.Array,
    params: SamplingParams,
) -> jax.Array:
    """Repetition / frequency / presence penalties over raw logits
    (reference sampling mapping, lib/llm/src/protocols/openai/).

    Two count tables [B, V] f32, matching the de-facto split (HF vs
    OpenAI/vLLM semantics):
    - `counts_all` (prompt + generated) drives HF-style repetition: seen
      tokens' positive logits are divided by the penalty, negative
      multiplied — pushes uniformly away from any reuse;
    - `counts_out` (GENERATED ONLY) drives the OpenAI pair: frequency
      subtracts penalty * count, presence subtracts the penalty once for
      any generated token. Prompt content must not pre-penalize the first
      generated token.
    All-default params make this an exact no-op, so one compiled path
    serves penalized and unpenalized batches."""
    seen_all = counts_all > 0.0
    rp = params.rep_penalty[:, None]
    logits = jnp.where(
        seen_all, jnp.where(logits > 0, logits / rp, logits * rp), logits
    )
    logits = logits - params.freq_penalty[:, None] * counts_out
    logits = logits - params.presence_penalty[:, None] * (counts_out > 0.0)
    return logits


# Sampling truncates to the top MAX_CANDIDATES logits first (one lax.top_k,
# no full-vocab sorts — a full 128k sort per sequence costs ~ms on TPU and
# dominated the decode step). Probability mass beyond the top-64 of a
# trained LM is negligible; top_k requests above this cap are clamped.
MAX_CANDIDATES = 64


def _filtered_scaled(logits: jax.Array, params: SamplingParams):
    """Shared filter pipeline: top-K truncate, apply top-k/top-p masks,
    temperature-scale. Returns (idx [B,K] token ids desc, scaled [B,K])."""
    B, V = logits.shape
    K = min(MAX_CANDIDATES, V)
    vals, idx = jax.lax.top_k(logits, K)  # [B, K] descending

    j = jnp.arange(K)
    # top-k filter (0 → disabled, clamped to K candidates)
    k_eff = jnp.where(params.top_k > 0, jnp.minimum(params.top_k, K), K)
    vals = jnp.where(j[None, :] < k_eff[:, None], vals, -jnp.inf)
    # top-p (nucleus): keep token j while cumulative prob before j < top_p
    # (always keeps j=0)
    probs = jax.nn.softmax(vals, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    vals = jnp.where(cum_before < params.top_p[:, None], vals, -jnp.inf)

    scaled = vals / jnp.maximum(params.temperature, 1e-6)[:, None]
    return idx, scaled


def filtered_probs(logits: jax.Array, params: SamplingParams):
    """The EXACT distribution `sample` draws from, as explicit
    probabilities: (idx [B,K] candidate token ids, probs [B,K]). Greedy
    rows (temperature <= 0) come back one-hot on idx[:, 0]. This is what
    speculative decoding's accept/resample math consumes for both the
    draft (q) and target (p) models."""
    idx, scaled = _filtered_scaled(logits, params)
    probs = jax.nn.softmax(scaled, axis=-1)
    greedy = jnp.zeros_like(probs).at[:, 0].set(1.0)
    probs = jnp.where((params.temperature <= 0.0)[:, None], greedy, probs)
    return idx, probs


def top_logprobs(logits: jax.Array, sampled: jax.Array, k: int):
    """Logprob report for the OpenAI `logprobs` surface, computed from the
    RAW model distribution (pre temperature/top-k/top-p — what clients use
    logprobs for: inspecting the model, not the sampler). Returns
    (tok_lp [B], top_ids [B, k], top_lps [B, k]); k=0 → empty top arrays."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    tok_lp = jnp.take_along_axis(lp, sampled[:, None], axis=1)[:, 0]
    if k <= 0:
        B = logits.shape[0]
        return tok_lp, jnp.zeros((B, 0), jnp.int32), jnp.zeros((B, 0), jnp.float32)
    vals, ids = jax.lax.top_k(lp, k)
    return tok_lp, ids.astype(jnp.int32), vals


def sample(
    logits: jax.Array, params: SamplingParams, step: jax.Array, mask=None,
    bias=None,
) -> jax.Array:
    """logits [B, V] f32 → token ids [B] i32. `step` folds the decode step
    index into each sequence's key so repeated calls draw fresh samples.
    `mask` [B, V] bool (guided decoding) bans False tokens outright; the
    caller guarantees every live row keeps at least one allowed token.
    `bias` [B, V] f32 (OpenAI logit_bias) adds to the logits before
    filtering — ±100 effectively forces/bans per the OpenAI contract."""
    with jax.named_scope("sample"):  # HLO metadata only
        if bias is not None:
            logits = logits + bias
        if mask is not None:
            logits = jnp.where(mask, logits, -1e30)
        idx, scaled = _filtered_scaled(logits, params)

        def draw(key_data, row):
            key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
            return jax.random.categorical(jax.random.fold_in(key, step), row)

        choice = jax.vmap(draw)(params.key, scaled).astype(jnp.int32)
        pick = jnp.where(params.temperature <= 0.0, 0, choice)  # idx 0 = argmax
        return jnp.take_along_axis(idx, pick[:, None], axis=1)[:, 0].astype(jnp.int32)
