"""ModelRunner: compiled, sharded prefill/decode step functions.

XLA-first execution model (SURVEY.md §7 "continuous batching under XLA's
static shapes"):
- every step shape is drawn from a fixed bucket set (decode batch buckets,
  prefill chunk buckets) so each shape compiles once and is cached;
- the paged KV pool is carried as two sharded jax.Arrays and **donated** on
  every step — XLA updates it in place, no reallocation;
- params are placed with the ShardingPolicy's megatron-style specs over the
  (data, model, expert, seq) mesh; XLA inserts the per-block all-reduces
  over ICI;
- sampling runs fused at the end of the decode step, so one int32 per
  sequence is the only per-token device→host transfer.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import OrderedDict, deque
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.runner_api import (
    BucketOverflowError,
    MixedOut,
    Runner,
    refusal,
)
from dynamo_tpu.engine.sampling import SamplingParams, sample
from dynamo_tpu.models import jamba, ling, llama, mimo, sambay
from dynamo_tpu.models.config import ModelConfig, mean_over_layers
from dynamo_tpu.models.moe import routing_stats
from dynamo_tpu.parallel.mesh import MeshConfig, ShardingPolicy, make_mesh
from dynamo_tpu.runtime.annotations import (
    DISPATCH,
    READBACK,
    STAGE,
    phase,
    synced,
)

log = logging.getLogger("dynamo_tpu.engine.runner")


# A routed model's step programs (config.is_moe) return one more output
# than a dense model's, always and last: a dict of the router's picks as
# the program laid them out (`decode` [n_steps, L_moe, B, k], `chunks`
# [L_moe, N, S, k], `flat` [L_moe, T, k]; int32 expert ids) and `load`,
# the f32 [5] expert-load counters of its forwards over real tokens
# (models/moe.routing_stats, summed over the forwards). Where the model has
# an indexer, `chosen_decode` [n_steps, L, B, W] and `chosen_chunks`
# [L, N, S, W] beside them: the tokens each query attended to, as bit words
# (models/mla.pack_chosen). A dense model's programs return what they
# always did. See ModelRunner._note_routed.


def _forward(config: ModelConfig, params, tokens, positions, k_pool, v_pool,
             page_table, kv_lens, last_index=None, attn_impl: str = "jnp",
             mesh=None, sp_has_prior: bool = True, lora=None,
             adapter_idx=None, mm_embeds=None, mm_mask=None):
    """The prefill step program of a routed model: llama.forward, with the
    picks of its chunk rows beside the pools. (A dense model's is
    llama.forward itself.)"""
    logits, k_pool, v_pool, sel, listed, *chosen = llama.forward(
        config, params, tokens, positions, k_pool, v_pool, page_table,
        kv_lens, last_index, attn_impl=attn_impl, mesh=mesh,
        sp_has_prior=sp_has_prior, lora=lora, adapter_idx=adapter_idx,
        mm_embeds=mm_embeds, mm_mask=mm_mask, return_routed=True,
        return_listed=True,
    )
    return logits, k_pool, v_pool, {
        "chunks": sel,
        "load": _chunk_load(config, sel, positions >= 0, listed),
        **({"chosen_chunks": chosen[0]} if chosen else {})}


def _side_ops(config: ModelConfig):
    """The record (models/toolkit.SideCacheOps) of the module whose models
    keep a cache beside their KV pages; None for every other model."""
    if config.is_sambay:
        return sambay.SIDE
    if config.is_hybrid:
        return jamba.SIDE
    if config.has_window_pool:
        return mimo.SIDE
    if config.is_kda:
        return ling.SIDE
    return None


def _side_forward(config: ModelConfig, params, tokens, positions, *args,
                  chunk_picks: bool = False, **kw):
    """The forward of a model whose step programs carry a second pool as
    `state` (`_side_ops`): (logits, k_pool, v_pool, *picks, pool). With
    `chunk_picks` (the prefill program) a routed model's picks of its chunk
    rows come as `_forward` hands them out."""
    out = _side_ops(config).forward(config, params, tokens, positions, *args,
                                    **kw)
    if chunk_picks and len(out) > 4:
        logits, k_pool, v_pool, sel, listed, state = out
        return logits, k_pool, v_pool, {
            "chunks": sel,
            "load": _chunk_load(config, sel, positions >= 0, listed)}, state
    return out


def _chunk_load(config: ModelConfig, sel, valid, listed):
    """routing_stats of one [N, S] prefill forward: sel [L_moe, N, S, k]."""
    L, N, S, k = sel.shape
    return routing_stats(sel.reshape(L, N * S, k), valid.reshape(N * S),
                         config, listed)


def _decode_loop(
    config: ModelConfig,
    attn_impl: str,
    mesh,  # for sharded pallas attention on TP meshes (None = single dev)
    n_steps: int,
    n_logprobs: int,  # static; -1 = no logprob outputs, >=0 = top-N report
    params,
    tokens0,  # [B] int32 current token per seq — host-packed OR a device
    # array chained from the previous dispatch's output (pipelining: the
    # caller never has to sync tokens to host between dispatches)
    packed,  # int32 [B + B*MP (+B if lora) + 1]: pos|pt|adapters|step
    hist,  # None (no penalties) or int32 [B, H] token history padded with
    # vocab_size — builds the on-device count table the penalties read
    mask,  # None or bool [B, V] guided-decoding sampling mask for step 0
    # (a constrained dispatch without mask_fn runs n_steps=1 so one mask
    # covers the loop; with mask_fn the per-step masks come from the host)
    bias,  # None or f32 [B, V] additive logit bias (OpenAI logit_bias;
    # constant per request, so it rides full fused loops unlike masks)
    k_pool,
    v_pool,
    sampling: SamplingParams,
    lora=None,  # stacked multi-LoRA tree (models/lora.py)
    mask_fn=None,  # static: host callback (t, prev_tokens) -> bool [B, V]
    # advancing guided DFA states between fused steps (ordered io_callback;
    # FALLBACK for schemas too large for the device table — see `guided`)
    guided=None,  # None or (gtrans [G, V] i32, gmask [G, V] bool,
    # gstate [B] i32, gpend scalar i32): device-resident guided DFA
    # (guided/device_table.py). Per-step state advance and mask gather
    # happen in-XLA inside the scan — zero host round trips, unlike the
    # ordered-io_callback mask_fn path this replaces for bounded schemas.
    # Unguided/dead rows sit in the shared DEAD state (all-True mask).
    # gpend != 0 advances at t=0 too (the ragged tail: tok0 was sampled
    # on device by the ragged step and never folded into gstate).
    state=None,  # a state-space model's state pool (models/jamba.py) or a
    # window-pool model's window pool (models/mimo.py): carried through the
    # steps like the KV pools and returned last; None, and no operand, for
    # every other model
    slots=None,  # with it: int32 [B] each row's state slot; the window
    # pool's: int32 [B, MP] each row's window page table
):
    """n_steps decode iterations fused in one jit: forward → sample → feed
    the sampled token back, entirely on device (lax.scan). Amortizes the
    per-dispatch host sync over n_steps tokens. All per-dispatch dynamic
    ints arrive in ONE packed array — each separate host array would be
    its own host→device transfer. `hist` (penalties) is the
    one exception: it is batch×history sized, so it rides as its own array
    only when a request actually uses penalties.
    Returns (tokens [B, n_steps], last [B], lp, k_pool, v_pool) where lp is
    None or (tok_lp [B, T], top_ids [B, T, K], top_lps [B, T, K]); a routed
    model's adds {"decode", "load"} (see _forward above)."""
    B = sampling.temperature.shape[0]
    routed = config.is_moe
    n_fields = 2 if lora is not None else 1
    MP = (packed.shape[0] - 1 - n_fields * B) // B
    positions0 = packed[:B]
    page_table = packed[B : B + B * MP].reshape(B, MP)
    adapter_idx = packed[B + B * MP : 2 * B + B * MP] if lora is not None else None
    step0 = packed[-1]

    use_pen = hist is not None
    counts0 = out0 = None
    if use_pen:
        # hist = (tokens [B, H] padded with vocab_size, prompt_len [B]);
        # the count of GENERATED tokens only (positions >= prompt_len)
        # feeds the OpenAI frequency/presence pair, the full count feeds
        # HF repetition — see sampling.apply_penalties
        hist_tok, prompt_len = hist
        V = config.vocab_size
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        cols = jnp.arange(hist_tok.shape[1], dtype=jnp.int32)[None, :]
        # pad tokens == V scatter out of bounds and drop
        counts0 = jnp.zeros((B, V), jnp.float32).at[
            rows, hist_tok
        ].add(1.0, mode="drop")
        out_tok = jnp.where(cols >= prompt_len[:, None], hist_tok, V)
        out0 = jnp.zeros((B, V), jnp.float32).at[
            rows, out_tok
        ].add(1.0, mode="drop")

    use_guided = guided is not None
    if use_guided:
        gtrans, gmask, gstate0, gpend = guided

    hybrid = state is not None

    def body(carry, t):
        gs = st = None
        if hybrid:
            carry, st = carry[:-1], carry[-1]
        if use_guided:
            carry, gs = carry[:-1], carry[-1]
        if use_pen:
            tok, kp, vp, cnt, cnt_out = carry
        else:
            (tok, kp, vp), cnt, cnt_out = carry, None, None
        pos = jnp.where(positions0 < 0, -1, positions0 + t)
        kvl = jnp.where(positions0 < 0, 0, positions0 + t + 1)
        if hybrid:
            logits, kp, vp, *sel, st = _side_forward(
                config, params, tok[:, None], pos[:, None], kp, vp,
                page_table, kvl, attn_impl=attn_impl, mesh=mesh, state=st,
                slots=slots,
            )
        else:
            logits, kp, vp, *sel = llama.forward(
                config, params, tok[:, None], pos[:, None], kp, vp,
                page_table, kvl, attn_impl=attn_impl, mesh=mesh, lora=lora,
                adapter_idx=adapter_idx, return_routed=routed,
                return_listed=routed,
            )
        raw = logits[:, 0, :]
        l = raw
        if use_pen:
            from dynamo_tpu.engine.sampling import apply_penalties

            l = apply_penalties(raw, cnt, cnt_out, sampling)
        m = mask
        if use_guided:
            # device-resident guided DFA: advance each row's state by the
            # token it fed this step (t>0, or t==0 under pending), then
            # gather its mask row — all in-XLA, no host round trip. Dead/
            # unguided rows self-loop in DEAD (all-True), matching the
            # host GuidedMaskContext's alive=False semantics exactly.
            adv = (t > 0) | (gpend != 0)
            gs = jnp.where(adv, gtrans[gs, tok], gs)
            m = gmask[gs]
        if mask_fn is not None:
            # guided rows in a multi-step loop: the DFA advances host-side
            # between fused steps (tok = what step t-1 sampled), so the
            # whole constrained batch rides full decode_steps loops instead
            # of collapsing to n_steps=1
            from jax.experimental import io_callback

            m = io_callback(
                mask_fn,
                jax.ShapeDtypeStruct((B, config.vocab_size), jnp.bool_),
                t, tok, ordered=True,
            )
        s = sample(l, sampling, step0 + t, mask=m, bias=bias)
        outs = (s,)
        if n_logprobs >= 0:
            from dynamo_tpu.engine.sampling import top_logprobs

            outs = (s,) + top_logprobs(raw, s, n_logprobs)
        if routed:
            picks = sel[0][:, :, 0]  # [L_moe, B, k]
            outs = outs + tuple(c[:, :, 0] for c in sel[2:]) + (  # [L, B, W]
                picks, routing_stats(picks, positions0 >= 0, config, sel[1]))
        if use_pen:
            r = jnp.arange(B, dtype=jnp.int32)
            cnt = cnt.at[r, s].add(1.0)
            cnt_out = cnt_out.at[r, s].add(1.0)
            nxt = (s, kp, vp, cnt, cnt_out)
        else:
            nxt = (s, kp, vp)
        if use_guided:
            nxt = nxt + (gs,)
        if hybrid:
            nxt = nxt + (st,)
        return nxt, outs

    carry0 = (tokens0, k_pool, v_pool) + ((counts0, out0) if use_pen else ())
    if use_guided:
        carry0 = carry0 + (gstate0,)
    if hybrid:
        carry0 = carry0 + (state,)
    carry, ys = lax.scan(body, carry0, jnp.arange(n_steps, dtype=jnp.int32))
    last, k_pool, v_pool = carry[0], carry[1], carry[2]
    toks = ys[0]
    lp = None
    if n_logprobs >= 0:
        # scan stacks along T as the leading axis; report [B, T, ...]
        lp = (ys[1].T, jnp.swapaxes(ys[2], 0, 1), jnp.swapaxes(ys[3], 0, 1))
    # `last` (== toks[:, -1]) is returned as its own output so a chaining
    # caller can feed it straight into the next dispatch — slicing the
    # token matrix caller-side would be an extra eager device program
    out = (toks.T, last, lp, k_pool, v_pool)  # [B, n_steps], [B]
    if routed:
        out += ({"decode": ys[-2], "load": ys[-1].sum(0),
                 **({"chosen_decode": ys[-3]} if config.has_indexer else {})},)
    if hybrid:
        out += (carry[-1],)
    return out


def _mixed_loop(
    config: ModelConfig,
    attn_impl: str,
    mesh,
    n_steps: int,
    params,
    ptok,  # [N, S] packed prefill chunk tokens (bucket-padded)
    ppos,  # [N, S] positions (-1 padding)
    ppt,  # [N, MP] per-chunk page tables
    pkvl,  # [N] per-chunk kv lens
    plast,  # [N]: last valid index per chunk row
    padapter,  # [N] LoRA slot per chunk's sequence (None w/o LoRA)
    tokens0,
    packed,
    k_pool,
    v_pool,
    sampling: SamplingParams,
    lora=None,
    prows=None,  # routed models only: int32 scalar, the real chunk rows
    # (rows past it replicate row 0 and are left out of the load counters)
):
    """One fused engine iteration under mixed scheduling: the token-
    budgeted prefill chunk set (one ragged segment per batch row) AND
    the n_steps decode loop in a single jit — ONE host sync per
    iteration instead of 1 + n_chunks (the unfused packed MixedPlan
    launches one program per chunk). Every chunk belongs to a
    different sequence (disjoint pages) than the decode batch and its
    packed siblings, so ordering inside the program is free for XLA to
    choose. Returns (toks [B, n_steps], last [B], chunk_logits [N, V],
    k_pool, v_pool); a routed model's adds {"chunks", "decode", "load"}
    (see _forward above)."""
    routed = config.is_moe
    logits, k_pool, v_pool, *sel = llama.forward(
        config, params, ptok, ppos, k_pool, v_pool, ppt, pkvl, plast,
        attn_impl=attn_impl, mesh=mesh, lora=lora, adapter_idx=padapter,
        return_routed=routed, return_listed=routed,
    )
    toks, last, _, k_pool, v_pool, *dec = _decode_loop(
        config, attn_impl, mesh, n_steps, -1, params, tokens0, packed,
        None, None, None, k_pool, v_pool, sampling, lora,
    )
    chunk_logits = logits[:, 0]  # [N, V], one row per packed chunk
    out = (toks, last, chunk_logits, k_pool, v_pool)
    if routed:
        real = jnp.arange(ptok.shape[0], dtype=jnp.int32)[:, None] < prows
        load = _chunk_load(config, sel[0], (ppos >= 0) & real, sel[1])
        out += ({"chunks": sel[0], "decode": dec[0]["decode"],
                 "load": load + dec[0]["load"]},)
    return out


def _ragged_step(
    config: ModelConfig,
    attn_impl: str,
    mesh,
    params,
    tokens,  # [1, T] flat step tokens: decode batch (one each) + chunks
    positions,  # [1, T] per-token absolute positions (-1 padding)
    tok_pt,  # [T, MP] per-token page-table rows (KV writes, jnp fallback)
    tok_kvl,  # [T] per-token context lengths
    seg_pt,  # [SEG, MP] per-segment page-table rows (kernel SMEM operand)
    seg_kvl,  # [SEG] per-segment context lengths
    meta,  # [5, NW] work units (ops.ragged_paged_attention)
    gather_idx,  # [SEG_CAP] flat index of each segment's LAST token
    k_pool,
    v_pool,
    sampling: SamplingParams,  # BASE rows padded to SEG_CAP (per-seq on
    # the verify path; row_seq gathers them out to entry rows in-XLA)
    row_seq,  # int32 [SEG_CAP] base-row index per sampled row — identity
    # on the mixed path; on the verify path it maps each expanded verify
    # entry back to its sequence's base sampling row, so the staged base
    # is CACHEABLE across iterations (per-seq params are stable while
    # the per-entry expansion used to churn a fresh host build +
    # transfer every dispatch — the re-staging tax this removes)
    row_j,  # int32 [SEG_CAP] verify position per row (0 = the row's own
    # seed; j>0 folds the per-position seed (seed*1000003+j) & 0x7FFFFFFF
    # in uint32 — bit-identical to the host expansion it replaces, since
    # PRNGKey(s) for a uint32 seed is key data [0, s])
    step,  # traced scalar int32
    mask,  # bool [SEG_CAP, V] sampling mask, ALWAYS an operand (all-True
    # when no row is guided — constant treedef keeps guided-on and
    # guided-off dispatches in the same compiled variant, dynlint J004)
    bias,  # f32 [SEG_CAP, V] additive logit bias, ALWAYS an operand
    # (all-zero when no row is biased — same constant-treedef rule; lets
    # logit_bias rows ride the verify/mixed dispatch instead of pausing
    # speculation batch-wide)
    state=None,  # a state-space model's state pool, returned last (see
    # _decode_loop); None for every other model
    seg_slots=None,  # int32 [3, SEG_CAP] with it: each segment's state
    # slot, first flat token and tokens (models/jamba.forward); the window
    # pool's: (tok_wpt [T, MP], seg_wpt [SEG, MP]), the window page tables
    # beside tok_pt and seg_pt (models/mimo.forward)
):
    """The ragged mixed step: ONE forward serves the whole decode batch
    (each sequence a q_len=1 segment) and every packed prefill chunk from
    a single flat [T] token axis. Logits come back only at the SEG_CAP
    gathered last-token rows; sampling covers all of them (decode rows
    use their real per-sequence params, the rest ride padding params and
    are discarded host-side). Every shape here is a function of the T
    bucket alone, so the mixed family compiles |T buckets| variants
    instead of the (decode x chunk x pack) triple product.

    Decode steps 1..n-1 of a fused iteration run through the UNCHANGED
    _decode_loop as a second dispatch chained on this one's sampled
    tokens — its variants are the plain decode-bucket set the engine
    already pays for, and sampling row seeds/steps line up exactly with
    the legacy fused path (sample() derives randomness per row from the
    sequence seed and the step counter only). Returns (toks [SEG_CAP],
    seg_logits, k_pool, v_pool); a routed model's adds {"flat", "load"}
    (see _forward above)."""
    routed = config.is_moe
    if state is not None:
        logits, k_pool, v_pool, *sel, state = _side_forward(
            config, params, tokens, positions, k_pool, v_pool, tok_pt,
            tok_kvl, last_index=gather_idx, attn_impl=attn_impl, mesh=mesh,
            ragged=(seg_pt, seg_kvl, meta), state=state, slots=seg_slots,
        )
    else:
        logits, k_pool, v_pool, *sel = llama.forward(
            config, params, tokens, positions, k_pool, v_pool, tok_pt,
            tok_kvl, last_index=gather_idx, attn_impl=attn_impl, mesh=mesh,
            ragged=(seg_pt, seg_kvl, meta), return_routed=routed,
            return_listed=routed,
        )
    seg_logits = logits[0]  # [SEG_CAP, V]
    # in-XLA sampling expansion: gather each row's base (per-seq) params,
    # then fold the verify position into the seed for j>0 rows. Matches
    # the host-side `(seed * 1000003 + j) & 0x7FFFFFFF` fold bit-for-bit:
    # key data for PRNGKey(uint32 s) is [0, s], uint32 wraparound agrees
    # with the arbitrary-precision host value mod 2^31.
    exp = jax.tree_util.tree_map(lambda a: a[row_seq], sampling)
    base_seed = exp.key[:, 1]  # u32 [SEG_CAP]
    eff = (base_seed * jnp.uint32(1000003) + row_j.astype(jnp.uint32)) \
        & jnp.uint32(0x7FFFFFFF)
    key = jnp.where(
        row_j[:, None] > 0,
        jnp.stack([jnp.zeros_like(eff), eff], axis=-1),
        exp.key,
    )
    exp = exp._replace(key=key)
    toks = sample(seg_logits, exp, step, mask=mask, bias=bias)  # [SEG_CAP]
    out = (toks, seg_logits, k_pool, v_pool)
    if routed:
        flat = sel[0][:, 0]  # [L_moe, T, k]
        out += ({"flat": flat, "load": routing_stats(
            flat, positions[0] >= 0, config, sel[1])},)
    if state is not None:
        out += (state,)
    return out


# device n-gram draft ring width: history tokens kept per slot. Smaller
# than the host NGRAM_SCAN_WINDOW (4096) — the match is identical for
# sequences shorter than the window, and the ring's HBM cost is
# SLOTS * W * 4 bytes
DRAFT_RING_WINDOW = 512


def _draft_ring_step(hist, lens, upd_tok, upd_n, k: int, max_match: int = 4):
    """One fused device draft step over ALL slots: append each slot's
    newly committed tokens to its history ring (shifting left on
    overflow), then run the prompt-lookup suffix match and gather k
    continuation tokens per slot — `engine.ngram_draft.propose` compiled
    to dense [SLOTS, W] ops (longest suffix m in [1, max_match] wins,
    most recent occurrence wins, continuation clipped at the history
    end), bit-identical to the host scan whenever the history fits the
    ring. Returns (hist, lens, drafts [SLOTS, k], n_prop [SLOTS]).

    hist [SLOTS, W] i32 (-1 padded), lens [SLOTS] i32, upd_tok
    [SLOTS, D] i32 (-1 padded), upd_n [SLOTS] i32. The whole warm spec
    loop's draft side is this one dispatch: the engine stages only the
    [SLOTS, D] committed-token delta and reads back only the proposals
    (sanitizer label draft_readback)."""
    SLOTS, W = hist.shape
    D = upd_tok.shape[1]
    i32 = jnp.int32
    # -- append with left-shift on overflow --------------------------------
    over = jnp.clip(lens + upd_n - W, 0, None)  # [SLOTS]
    gidx = jnp.arange(W, dtype=i32)[None, :] + over[:, None]
    hp = jnp.concatenate([hist, jnp.full((SLOTS, D), -1, i32)], axis=1)
    hist = jnp.take_along_axis(hp, gidx, axis=1)
    lens = lens - over
    pos = lens[:, None] + jnp.arange(D, dtype=i32)[None, :]
    valid = jnp.arange(D, dtype=i32)[None, :] < upd_n[:, None]
    rows = jnp.broadcast_to(jnp.arange(SLOTS, dtype=i32)[:, None], pos.shape)
    hist = hist.at[rows, jnp.where(valid, pos, W)].set(
        jnp.where(valid, upd_tok, -1), mode="drop"
    )
    lens = lens + upd_n
    # -- suffix match ------------------------------------------------------
    hpad = jnp.concatenate(
        [hist, jnp.full((SLOTS, max_match + k), -1, i32)], axis=1
    )
    s_arr = jnp.arange(W, dtype=i32)[None, :]
    best_s = jnp.full((SLOTS,), -1, i32)
    best_m = jnp.zeros((SLOTS,), i32)
    for m in range(max_match, 0, -1):  # longest suffix wins
        match = jnp.ones((SLOTS, W), bool)
        for i in range(m):
            sfx = jnp.take_along_axis(
                hist, jnp.clip(lens - m + i, 0, W - 1)[:, None], axis=1
            )  # [SLOTS, 1]
            match = match & (hpad[:, i : i + W] == sfx)
        # candidate start s needs the full m-gram AND >= 1 continuation
        # token before the suffix itself: s + m <= len - 1
        match = match & ((s_arr + m) <= (lens[:, None] - 1))
        match = match & (lens[:, None] >= m + 1)
        cand = jnp.where(match, s_arr, -1).max(axis=1)  # most recent
        take = (best_s < 0) & (cand >= 0)
        best_s = jnp.where(take, cand, best_s)
        best_m = jnp.where(take, i32(m), best_m)
    start = best_s + best_m
    idx = start[:, None] + jnp.arange(k, dtype=i32)[None, :]
    drafts = jnp.take_along_axis(hpad, jnp.clip(idx, 0, None), axis=1)
    n_prop = jnp.where(best_s >= 0, jnp.clip(lens - start, 0, k), 0)
    return hist, lens, drafts, n_prop


class _GuidedMaskTrampoline:
    """Identity-stable host callback for `_decode_loop`'s per-step guided
    masks: the jit cache keys static args by hash, so the callback-bearing
    program must trace against ONE object per runner — the per-dispatch
    DFA context (engine GuidedMaskContext: row matchers + state copies) is
    swapped into `ctx` right before each dispatch. Safe with async
    dispatch because the engine materializes every dispatch's sampled
    tokens before it builds the next plan, so at most one context is live
    at a time (asserted)."""

    def __init__(self):
        self.ctx = None

    def __call__(self, t, prev_tokens):
        ctx = self.ctx
        assert ctx is not None, "guided mask callback fired without context"
        return np.asarray(ctx(int(t), np.asarray(prev_tokens)), dtype=bool)


def _named(fn, name: Optional[str] = None):
    """`fn` under a stable `__name__`, so that jax calls its program
    `jit_<name>` in HLO dumps and on the device trace's module line. A
    functools.partial has no name and traces as `jit__unknown`; the
    default is the wrapped function's own name less its leading
    underscores (`_decode_loop` -> `jit_decode_loop`). The name goes on a
    fresh partial, never on the shared module-level function."""
    p = partial(fn)
    p.__name__ = p.__qualname__ = (
        name or getattr(fn, "func", fn).__name__.lstrip("_")
    )
    return p


# -- compile accounting ------------------------------------------------------
# What a thread is compiling for: `family` is the _CompiledFamily whose
# call is on this thread's stack (it counts its own growth), `other` the
# catch-all family of the runner this thread serves (name_step_thread).
_compile_tls = threading.local()
_compile_listener_lock = threading.Lock()
_compile_listener_on = False
# fires once per program XLA is asked for, compiled or read back from the
# persistent cache (jax 0.9.0: pxla._cached_compilation wraps
# compile_or_get_cached in it); an in-memory jit cache hit fires nothing
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile_event(event: str, duration_secs: float, **_kw) -> None:
    """Every program no family saw, once, to the runner its thread
    serves: the eager slice and index programs of the serving path. A
    thread that named no runner (warm-up, tests, the asyncio side) is
    charged to nobody, so N replicas in one process never count one
    program N times."""
    if event != _BACKEND_COMPILE_EVENT:
        return
    family = getattr(_compile_tls, "family", None)
    if family is not None:
        family._asked_xla = True
        return
    other = getattr(_compile_tls, "other", None)
    if other is not None:
        other.variants += 1
        other.calls += 1
        other.compile_s += duration_secs


def _ensure_compile_listener() -> None:
    """One process-wide listener, registered with the first runner."""
    global _compile_listener_on
    with _compile_listener_lock:
        if not _compile_listener_on:
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_event)
            _compile_listener_on = True


class _CompiledFamily:
    """One jitted step-function family. Jits `fn` under the function's own
    name (see _named), counts distinct compiled variants (jit cache
    growth in a call that asked XLA for a program) and the cumulative
    wall seconds of calls that compiled (trace+lower+compile — the
    host-side stall each new bucket costs), and marks each call
    `engine.dispatch` on the profiler's timeline. A cache entry that
    asked XLA for nothing is a call signature, not a variant: the first
    call on a fresh KV pool sees the sharding the pool was allocated
    under, every later one the equivalent sharding a step program
    returned it with, and the second resolves to the program the first
    built. compile_stats() feeds the worker's /metrics gauges and the
    benchmark's `runner.compiles_in_window`. The catch-all family
    `other` has no function: _on_compile_event counts into it."""

    def __init__(self, name: str, fn=None, **jit_kwargs):
        self.name = name
        self._fn = None if fn is None else jax.jit(_named(fn), **jit_kwargs)
        self.variants = 0
        self.compile_s = 0.0
        self.calls = 0
        self._asked_xla = False  # set by _on_compile_event inside a call
        _ensure_compile_listener()

    def _cache_size(self):
        try:
            return self._fn._cache_size()
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        self.calls += 1
        before = self._cache_size()
        outer = getattr(_compile_tls, "family", None)
        _compile_tls.family = self
        self._asked_xla = False
        t0 = time.monotonic()
        try:
            with phase(DISPATCH, family=self.name):
                out = self._fn(*args, **kwargs)
        finally:
            _compile_tls.family = outer
        after = self._cache_size()
        if (self._asked_xla and before is not None and after is not None
                and after > before):
            self.variants += after - before
            self.compile_s += time.monotonic() - t0
        return out

    def stats(self) -> Dict[str, Any]:
        return {
            "variants": self.variants,
            "compile_s": round(self.compile_s, 4),
            "calls": self.calls,
        }


class _RoutedPart:
    """One dispatch's routed output (see _forward) and how its rows map
    to the plan: `picks` the device arrays by layout (None once
    routed_picks() took them), `load` the counters (device until a
    readback brought them), `forwards` the forward passes the dispatch
    ran, `n_dec` the real decode rows, `chunk_lens` the real tokens of
    each real chunk in plan order."""

    __slots__ = ("picks", "load", "forwards", "n_dec", "chunk_lens")

    def __init__(self, routed, forwards, n_dec, chunk_lens):
        self.picks = {k: v for k, v in routed.items() if k != "load"}
        self.load = routed["load"]
        self.forwards = forwards
        self.n_dec = n_dec
        self.chunk_lens = tuple(chunk_lens)


def _beside_picks(picks, chosen, cells: int) -> np.ndarray:
    """What `routed_picks()` hands out for a model with an indexer: the
    picks int32 [L_moe, ..., k] and, below them along the first axis, the
    tokens each query of each of the L layers attended to (`chosen` int32
    [L, ..., W], as models/mla.pack_chosen laid them out on the device):
    [L_moe + L x R, ..., k], a layer's set as R rows of k int32 words in
    token order (token s is bit s % 32 of word s // 32, little-endian;
    R x k x 32 >= cells, zeros behind)."""
    from dynamo_tpu.models.mla import unpack_chosen

    k = picks.shape[-1]
    by = np.packbits(unpack_chosen(chosen, cells), axis=-1, bitorder="little")
    by = np.pad(by, [(0, 0)] * (by.ndim - 1) + [(0, -by.shape[-1] % (4 * k))])
    rows = np.ascontiguousarray(by).view("<i4")  # [L, ..., R * k]
    rows = np.moveaxis(rows.reshape(rows.shape[:-1] + (-1, k)), -2, 1)
    return np.concatenate(
        [np.asarray(picks), rows.reshape((-1,) + rows.shape[2:])], axis=0)


def _device_get_with_loads(parts, x=None):
    """jax.device_get(x) and, in the same call, of the expert-load
    counters of `parts` that no readback has brought yet."""
    late = [p for p in parts if not isinstance(p.load, np.ndarray)]
    if not late:
        return jax.device_get(x)
    out, loads = jax.device_get((x, [p.load for p in late]))
    for p, h in zip(late, loads):
        p.load = np.asarray(h)
    return out


class MoeLoad:
    """An iteration's expert-load counters, as ModelRunner.take_moe_load()
    hands them to the engine. `ready` says every dispatch's counters came
    back with some readback of the iteration; where one did not (a
    prefill chunk that sampled nothing is never read back) `result()`
    fetches it, which the engine puts off until the next iteration has
    synchronised anyway."""

    def __init__(self, parts, n_layers: int):
        self._parts = parts
        self._n_layers = n_layers

    @property
    def ready(self) -> bool:
        return all(isinstance(p.load, np.ndarray) for p in self._parts)

    def result(self) -> Tuple[int, float, float, float, int]:
        """(moe_token_slots, moe_experts_hit, moe_load_max_share,
        moe_held_slots, moe_experts_listed): see
        runtime/flight_recorder.IterationRecord."""
        _device_get_with_loads(self._parts)
        units = sum(p.forwards for p in self._parts) * self._n_layers
        if not units:
            return 0, 0.0, 0.0, 0.0, 0
        tot = np.sum([p.load for p in self._parts], axis=0, dtype=np.float64)
        return (int(round(tot[0])), float(tot[1] / units),
                float(tot[2] / units), float(tot[3] / self._n_layers),
                int(round(tot[4])))


class DecodeHandle:
    """A decode dispatch that nobody has read back (ModelRunner.
    decode_dispatch): its results on the device and what is its own of
    the routed bookkeeping. `last` [rows] is what the next dispatch takes
    as its rows' first tokens without a round trip through the host;
    `rows` the decode bucket both must share for that."""

    __slots__ = ("toks", "last", "lp", "parts", "rows")

    def __init__(self, toks, last, lp, parts, rows: int):
        self.toks, self.last, self.lp = toks, last, lp
        self.parts = parts
        self.rows = rows


class MixedHandle:
    """A fused mixed dispatch that nobody has read back (ModelRunner.
    mixed_dispatch): the decode rows' tokens on the device (`tok0` [B],
    the ragged step's, None where the padded program ran; `rest`
    [B, steps], the steps chained on them or the padded program's all,
    None where one ragged step was everything), the chunks' last-token
    logits, and what MixedOut says of the program."""

    __slots__ = ("tok0", "rest", "chunk_logits", "n_chunks", "ragged",
                 "pages_live")

    def __init__(self, tok0, rest, chunk_logits, n_chunks: int,
                 ragged: bool, pages_live: int = 0):
        self.tok0, self.rest = tok0, rest
        self.chunk_logits, self.n_chunks = chunk_logits, n_chunks
        self.ragged, self.pages_live = ragged, pages_live


# Wire layout version for P→D / cross-worker KV payloads. v2 = token-major
# [L, n, PS, Hk, D]; v1 (implicit, no field) was head-major. Mirrors the
# disk tier's BLOCK_LAYOUT_VERSION: in a mixed-version cluster (rolling
# upgrade) an old-layout peer's bytes sliced under the new axis order import
# transposed KV silently — reject and force recompute instead.
KV_WIRE_LAYOUT_VERSION = 2


class KvWireLayoutMismatch(ValueError):
    pass


def kv_arrays_to_payload(k: np.ndarray, v: np.ndarray, tp: int = 1) -> Dict[str, Any]:
    """KV wire format for P→D transfer and G2 offload: [L, n, PS, Hk, D]
    (token-major, page axis 1 — the pool layout) arrays as raw bytes +
    shape/dtype metadata. Single definition — the engine and host tier
    must not re-implement it.

    Cross-TP layout handshake (ref docs/design-docs/kvbm-design.md:161–237,
    esp. :188–197 — the reference negotiates serialized layout metadata and
    permutes blocks when P and D run different TP degrees): the wire format
    is always DENSE FULL-HEAD pages — export all-gathers the head shards
    over ICI, import scatters into the local pool under whatever sharding
    the importer's mesh uses, with GSPMD inserting the reshard. So a TP=1
    prefill worker and a TP=4 decode worker interoperate without an
    explicit permute protocol; the metadata below (page geometry + exporter
    tp degree) lets the importer VALIDATE compatibility and fall back to
    local recompute instead of adopting mis-shaped bytes."""
    out_extra = {}
    if v.shape != k.shape:
        # MLA pools are asymmetric: k = latent pages, v = 1-wide stub
        out_extra["v_shape"] = list(v.shape)
    return {
        "data": True,
        "k": k.tobytes(),
        "v": v.tobytes(),
        "shape": list(k.shape),
        "dtype": str(k.dtype),
        **out_extra,
        "n_pages": int(k.shape[1]),
        "layout": KV_WIRE_LAYOUT_VERSION,
        # layout handshake metadata: [L, n, PS, Hk, D] geometry, explicit
        "page_size": int(k.shape[2]),
        "kv_heads": int(k.shape[3]),
        "head_dim": int(k.shape[4]),
        "layers": int(k.shape[0]),
        "tp": int(tp),
    }


def layer_group_bounds(num_layers: int, groups: int) -> List[Tuple[int, int]]:
    """Contiguous [lo, hi) layer slabs for the streamed onboard: `groups`
    near-equal groups, the earlier ones taking the remainder so the first
    (blocking) transfer is never the runt."""
    g = max(1, min(int(groups), int(num_layers)))
    base, rem = divmod(int(num_layers), g)
    bounds: List[Tuple[int, int]] = []
    lo = 0
    for i in range(g):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def kv_quant_arrays_to_payload(kq, ks, vq, vs) -> Dict[str, Any]:
    """Native int8+scales KV payload for LOCAL tier promotion (engine →
    runner in one process; arrays stay arrays, no byte serialization).
    Carries the tier codec's per-(token, head) q/s pair in the pool
    stacking [L, n, PS, Hk, D] / [L, n, PS, Hk] so an int8 device pool
    adopts it without a dequantize/requantize round trip. The
    CROSS-WORKER wire stays dense (kv_arrays_to_payload) — heterogeneous
    workers keep interoperating."""
    return {
        "data": True,
        "quant": "int8_ts",
        "kq": kq, "ks": ks, "vq": vq, "vs": vs,
        "shape": list(kq.shape),
        "n_pages": int(kq.shape[1]),
        "layout": KV_WIRE_LAYOUT_VERSION,
        "page_size": int(kq.shape[2]),
        "kv_heads": int(kq.shape[3]),
        "head_dim": int(kq.shape[4]),
        "layers": int(kq.shape[0]),
    }


def kv_payload_incompatible(
    payload: Dict[str, Any],
    page_shape: Tuple[int, int, int, int],
    dtype: Optional[str] = None,
) -> Optional[str]:
    """Reason string when `payload` cannot be imported into a pool whose
    per-page geometry is `page_shape` = (L, PS, Hk, D) and (optionally)
    whose wire dtype name is `dtype`; None when compatible. Wire version,
    page geometry and dtype must match exactly — the exporter's TP degree
    is deliberately NOT checked (the dense full-head wire makes it
    irrelevant; see kv_arrays_to_payload)."""
    if payload.get("layout") != KV_WIRE_LAYOUT_VERSION:
        return f"layout {payload.get('layout')} != {KV_WIRE_LAYOUT_VERSION}"
    L, PS, Hk, D = page_shape
    shape = payload.get("shape") or []
    if len(shape) != 5:
        return f"malformed shape {shape}"
    got = (shape[0], shape[2], shape[3], shape[4])
    if got != (L, PS, Hk, D):
        return f"page geometry {got} != local (L={L}, PS={PS}, Hk={Hk}, D={D})"
    if dtype is not None and payload.get("dtype") != dtype:
        return f"dtype {payload.get('dtype')} != local {dtype}"
    return None


def kv_payload_to_arrays(payload: Dict[str, Any], page_shape=None, dtype=None):
    """Inverse of kv_arrays_to_payload; None if the payload carries no data
    (simulated workers). Raises KvWireLayoutMismatch when the sender used a
    different pool layout version or (when `page_shape`/`dtype` is given) a
    different page geometry or element type — the importer must fail the
    transfer (recompute locally) rather than adopt mis-shaped bytes."""
    if not payload or not payload.get("k"):
        return None
    if payload.get("layout") != KV_WIRE_LAYOUT_VERSION:
        raise KvWireLayoutMismatch(
            f"kv wire layout {payload.get('layout')} != {KV_WIRE_LAYOUT_VERSION}"
        )
    if page_shape is not None:
        bad = kv_payload_incompatible(payload, page_shape, dtype)
        if bad:
            raise KvWireLayoutMismatch(bad)
    import ml_dtypes

    name = payload["dtype"]
    dtype = np.dtype(ml_dtypes.bfloat16) if "bfloat16" in name else np.dtype(name)
    shape = tuple(payload["shape"])
    v_shape = tuple(payload.get("v_shape") or shape)
    k = np.frombuffer(payload["k"], dtype=dtype).reshape(shape)
    v = np.frombuffer(payload["v"], dtype=dtype).reshape(v_shape)
    return k, v


def _next_bucket(buckets: Sequence[int], n: int) -> int:
    for b in buckets:
        if b >= n:
            return b
    raise BucketOverflowError(n, buckets)


def _chunk_rows(chunk_logits: jax.Array, n: int) -> List[jax.Array]:
    """The last-token logits of a mixed dispatch's n real chunks, one
    device row each, split once its tokens are back (the device is idle
    either way). A lone chunk is indexed and several are unstacked: the
    eager programs these paths have always run, and the ones
    benchmark/serve.py's walk meets (one chunk through
    decode_multi_with_prefill, several by iterating the rows)."""
    if n == 1:
        return [chunk_logits[0]]
    return list(chunk_logits)[:n]


class ModelRunner(Runner):
    supports_logit_bias = True  # engine gates biased requests on this
    prefill_enqueues = True  # (its logits are read where they are sampled)
    static_shapes = True
    holds_kv = True
    has_verify_spec = True
    has_draft_ring = True

    def __init__(
        self,
        config: ModelConfig,
        mesh_config: Optional[MeshConfig] = None,
        *,
        num_pages: int = 512,
        page_size: int = 16,
        max_pages_per_seq: int = 128,
        decode_buckets: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
        prefill_buckets: Sequence[int] = (16, 32, 64, 128, 256, 512, 1024),
        ragged_buckets: Sequence[int] = (32, 64, 128, 256, 512, 1024, 2048),
        dtype=jnp.bfloat16,
        seed: int = 0,
        params: Optional[Any] = None,
        devices: Optional[list] = None,
        attn_impl: Optional[str] = None,  # None → pallas on TPU, jnp elsewhere
        draft_config: Optional[ModelConfig] = None,  # enables spec decode
        draft_params: Optional[Any] = None,
        spec_gamma: int = 4,  # draft tokens proposed per verify pass
        lora_slots: int = 0,  # >0 enables multi-LoRA (slot 0 = base)
        lora_rank: int = 8,
        lora_targets=None,  # defaults to models/lora.py DEFAULT_TARGETS
        quantize: Optional[str] = None,  # "int8" → weight-only quant
        kv_quantize: Optional[str] = None,  # "int8" → quantized KV pools
    ):
        self.config = config
        self.vocab_size = config.vocab_size
        self.max_seq_len = config.max_seq_len
        self._sanitizer = None  # set by attach_sanitizer (engine opt-in)
        self.mesh_config = mesh_config or MeshConfig()
        self.mesh = make_mesh(self.mesh_config, devices)
        self.platform = self.mesh.devices.flat[0].platform
        self.policy = ShardingPolicy(self.mesh)
        # pipeline parallelism: layer-stacked params and the KV pool shard
        # their leading [L] axis over `pipe`; step functions run the GPipe
        # schedule (ops/pipeline_parallel.py). v1 composition envelope —
        # the schedule's inner ops are plain jnp, so other mesh axes and
        # the feature planes that thread extra per-layer state are gated
        # off explicitly rather than silently miscomputed.
        self.pp = self.mesh_config.pipe > 1
        if self.pp:
            from dynamo_tpu.ops import pipeline_parallel as _ppmod

            mc = self.mesh_config
            if (mc.model, mc.expert, mc.seq, mc.data) != (1, 1, 1, 1):
                raise NotImplementedError(
                    "pipe>1 composes with no other mesh axis yet "
                    f"(got {mc.shape})"
                )
            if config.n_layers % mc.pipe != 0:
                raise ValueError(
                    f"{config.n_layers} layers not divisible by "
                    f"pipe={mc.pipe} stages"
                )
            if draft_config is not None or lora_slots > 0 or kv_quantize:
                raise NotImplementedError(
                    "speculative decoding / LoRA / int8-KV are not wired "
                    "on the pipeline-parallel path yet"
                )
            _ppmod._check(config)  # dense GQA family only
            self._ppmod = _ppmod
        # mesh spanning several processes (multi-host group,
        # parallel/multihost.py): pool reads must gather to a replicated
        # sharding before device_get — remote shards aren't addressable
        self.multihost = any(
            d.process_index != jax.process_index() for d in self.mesh.devices.flat
        )
        # a model that keeps a cache beside its KV pages (models/jamba.py:
        # a state slot a sequence; models/mimo.py: the window layers' pool,
        # the KV pool holds the global layers alone): its module's record.
        # The step programs take and return that pool (`self.state`), sized
        # by ensure_side_cache (the engine knows how many sequences it runs)
        self._side_mod = _side_ops(config)
        self.side_kind = self._side_mod.kind if self._side_mod else None
        self.state = None  # the pool, once ensured
        self.side_units = 0
        # a model whose last layers only a sampled row's logits need: told
        # `sampled=False`, a prefill chunk is served without them, and the
        # dispatches count the rows they ran on (fill_record)
        self.skips_unsampled = config.has_cross_decoder
        self._sampled_rows = self._skipped_tokens = 0
        mc = self.mesh_config
        kinds = self.side_kind.split("+") if self.side_kind else ()
        if self._side_mod is not None:
            self.has_verify_spec = False  # no rollback of it
            self.side_unit_bytes = self._side_mod.unit_bytes(
                config, page_size, dtype)
        if "state" in kinds:
            if mc.n_devices > 1:
                raise NotImplementedError(
                    "a state-space model is not sharded yet: its state pool "
                    f"and mixers run on one device (mesh {mc.shape})")
            if draft_config is not None or lora_slots > 0:
                self._no_side(
                    "speculative decoding with a draft model (and LoRA)")
            if config.is_kda and kv_quantize:
                self._no_side("a quantized KV cache (--kv-quantize)")
        if "window" in kinds:
            if mc.n_devices > 1:
                raise NotImplementedError(
                    "a window-pool model is not sharded yet: both its caches "
                    f"live on one device (mesh {mc.shape})")
            if draft_config is not None or lora_slots > 0 or kv_quantize:
                self._no_side(
                    "speculative decoding with a draft model, LoRA and a "
                    "quantized KV cache")
        # a model with an indexer (models/mla.py): the pool's second array
        # holds its index keys under the latent pages' own page table, so
        # pages are copied, exported, imported and offloaded as the pair
        # with nothing added here. Refused: what would read or write the
        # pair in a form the selection was never run on. It has no fused
        # mixed program: chunks ride beside the decoding rows as two
        # dispatches (Runner.fuses_mixed)
        self.fuses_mixed = not (config.has_indexer or config.is_kda)
        if config.has_indexer:
            if kv_quantize:
                raise NotImplementedError(refusal(
                    "indexer", config.name,
                    "a quantized KV cache (--kv-quantize)"))
            if self.mesh_config.n_devices > 1:
                raise NotImplementedError(refusal(
                    "indexer", config.name,
                    f"a mesh of several devices ({self.mesh_config.shape})"))
            if draft_config is not None:
                raise NotImplementedError(refusal(
                    "indexer", config.name,
                    "speculative decoding with a draft model"))
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.decode_buckets = tuple(decode_buckets)
        self.prefill_buckets = tuple(prefill_buckets)
        # packed-prefill row-count buckets: the legacy fused mixed program
        # compiles per (decode bucket, chunk bucket, pack bucket) triple
        self.pack_buckets = (1, 2, 4, 8, 16, 32)
        # ragged flat-token mixed path: ONE [T] bucket per compile. The
        # engine inserts mixed_prefill_tokens + max decode batch via
        # ensure_ragged_bucket so the scheduler's budget IS a compile
        # bucket (full mixed iterations never round up).
        self.ragged_buckets = tuple(sorted(ragged_buckets))
        self.ragged_q_block = 8
        # a verify dispatch samples at most seg_cap rows; budgeting verify
        # tokens to RAGGED_MAX_SEGS (minus one slot per decode row / chunk)
        # keeps every verify dispatch inside the gather the compiled
        # program already has — the no-new-compile-families invariant
        # (docs/ragged_attention.md)
        from dynamo_tpu.ops.ragged_paged_attention import RAGGED_MAX_SEGS

        self.spec_seg_budget = RAGGED_MAX_SEGS
        self.dtype = dtype

        t0 = time.monotonic()
        owns_params = params is None
        if params is None:
            params = llama.init_params(config, jax.random.PRNGKey(seed), dtype)
        self.quantize = quantize
        if quantize in ("int8", "fp8"):
            from dynamo_tpu.models.quant import quantize_params

            # donate only self-initialized trees: donation frees each bf16
            # leaf as it converts (halves peak HBM during quantization) but
            # deletes the caller's arrays on accelerator backends
            params = quantize_params(params, mode=quantize, donate=owns_params)
        elif quantize is not None:
            raise ValueError(f"unknown quantize mode {quantize!r}")
        self.params = jax.device_put(params, self.policy.params_sharding(params))
        # the unsharded tree was built on the default device: on a mesh it
        # would sit on chip 0 beside that chip's shards for the rest of
        # construction (6.4 GB at llama-3.2-3b)
        del params
        # padding writes scatter to page index == num_pages, out of bounds,
        # and are dropped (scatter mode="drop" in llama._write_kv)
        self.kv_quantize = kv_quantize
        # transfer-path page movement via the Pallas batched copy kernels
        # (ops/block_copy.py) instead of XLA gather/scatter — opt-in until
        # a hardware A/B lands (same rollout policy as attn_impl).
        # Single-device pools run the plain pallas_call; TP-only meshes run
        # it under shard_map over the head-sharded pool (per-shard page
        # streams, zero collectives — the decode_paged_attention_sharded
        # pattern). Other mesh axes keep the XLA path (GSPMD partitions it).
        import os

        tp_only_mesh = (
            mc.model > 1 and mc.data == mc.expert == mc.seq == mc.pipe == 1
        )
        flag = os.environ.get("DYN_KV_COPY_KERNEL", "").lower()
        self._kv_copy_kernel = (
            flag in ("1", "true", "on", "yes")
            and (self.mesh_config.n_devices == 1 or tp_only_mesh)
        )
        self._kv_copy_sharded = self._kv_copy_kernel and tp_only_mesh
        # non-TPU runs (CPU tests) execute the copy kernels in interpret
        # mode (platform from the mesh's devices, like attn_impl)
        self._kv_copy_interpret = (
            self.mesh.devices.flat[0].platform != "tpu"
        )
        self.k_pool, self.v_pool = self._new_kv_pools(config)
        placed_s = time.monotonic() - t0

        # speculative decoding: the draft model owns parallel KV pools
        # addressed by the SAME page tables (block management, prefix
        # sharing, preemption all come for free; pages onboarded from the
        # host tier lack draft KV, which only costs acceptance rate, never
        # correctness — the verify pass is authoritative)
        self.draft_config = draft_config
        self.spec_gamma = spec_gamma
        if draft_config is not None:
            if draft_params is None:
                draft_params = llama.init_params(
                    draft_config, jax.random.PRNGKey(seed + 1), dtype
                )
            self.draft_params = jax.device_put(
                draft_params, self.policy.params_sharding(draft_params)
            )
            self.draft_k_pool, self.draft_v_pool = self._new_kv_pools(
                draft_config
            )

        # multi-LoRA: stacked adapter factors, one slot per adapter, batched
        # per-sequence adapter indices through every step function
        self.lora = None
        self._adapter_slots: Dict[str, int] = {}
        self.lora_rank = lora_rank
        if lora_slots > 0:
            from dynamo_tpu.models import lora as lora_mod

            self.lora_targets = tuple(lora_targets or lora_mod.DEFAULT_TARGETS)
            tree = lora_mod.init_lora_params(
                config, lora_slots + 1, lora_rank, self.lora_targets, dtype
            )
            self.lora = jax.device_put(tree, self.policy.params_sharding(tree))

        if attn_impl is not None:
            self.attn_impl_reason = "set by caller"
        else:
            platform = self.mesh.devices.flat[0].platform
            # pallas on a real accelerator; TP meshes run the kernel inside
            # shard_map over the model axis (heads are independent). Other
            # parallel axes (data/expert/seq) are not yet covered by the
            # sharded wrappers, so those meshes keep the jnp path (GSPMD
            # partitions it) — reported, never silent (device_report)
            mc = self.mesh_config
            tp_only = mc.data == mc.expert == mc.seq == 1
            if platform == "cpu":
                attn_impl, self.attn_impl_reason = "jnp", "cpu platform"
            elif not tp_only:
                attn_impl = "jnp"
                self.attn_impl_reason = (
                    "data/expert/seq mesh axis > 1: no sharded Pallas wrapper"
                )
            else:
                attn_impl = "pallas"
                self.attn_impl_reason = f"{platform}, model-axis-only mesh"
        self.attn_impl = attn_impl
        # static mesh handle threaded to forward for sharded kernels / ring
        self._fwd_mesh = self.mesh if self.mesh_config.n_devices > 1 else None

        # prefill uses the flash kernel on TPU (S>1), jnp elsewhere; with a
        # seq mesh axis, prefill goes sequence-parallel (ring attention)
        self.sp_enabled = self.mesh_config.seq > 1
        # a decode dispatch can be left in flight and the next one chained
        # on its tokens (decode_dispatch / decode_collect); the PP and SP
        # programs were never run that way
        self.can_run_ahead = not (self.pp or self.sp_enabled)
        # where a dispatch's host tokens go (_stage_decode_rows)
        self._tok_sharding = None if self.pp else self.policy.replicated()
        # per-family compile observability (variant counts + compile
        # seconds); see _CompiledFamily / compile_stats()
        self._families: Dict[str, _CompiledFamily] = {}

        # programs no family calls (eager slices of step results, index
        # math) compiled on the thread that serves this runner; not in
        # _families, whose growth after warm-up the sanitizer calls a leak
        self._other = _CompiledFamily("other")

        def _family(name, fn, **jit_kwargs):
            fam = _CompiledFamily(name, fn, **jit_kwargs)
            self._families[name] = fam
            return fam

        # a routed model's step programs hand out the router's picks and
        # the expert-load counters (see _forward); the pipeline-parallel
        # programs have no such output
        self.routed = bool(self.config.is_moe) and not self.pp
        # the dispatches since the engine last took them (_note_routed);
        # bounded for callers that never do (warm-up walks, benches)
        self._routed_parts: "deque[_RoutedPart]" = deque(maxlen=64)
        # a state-holding model's programs also take (and donate) the
        # state pool, by keyword; no other model's jit hears of it
        skw = ({"donate_argnames": ("state",)}
               if self._side_mod is not None else {})
        if self._side_mod is not None:
            self._jit_forward = _family(
                "forward",
                partial(_side_forward, self.config, chunk_picks=True),
                donate_argnums=(3, 4), static_argnames=("attn_impl", "mesh"),
                **skw,
            )
        else:
            self._jit_forward = _family(
                "forward",
                partial(_forward if self.routed else llama.forward, self.config),
                donate_argnums=(3, 4),  # k_pool, v_pool
                static_argnames=("attn_impl", "mesh", "sp_has_prior"),
            )
        self._jit_sample = jax.jit(sample)
        self._jit_decode_loop = _family(
            "decode_loop",
            partial(_decode_loop, self.config, self.attn_impl, self._fwd_mesh),
            static_argnums=(0, 1),  # n_steps, n_logprobs
            static_argnames=("mask_fn",),  # guided per-step mask callback
            donate_argnums=(8, 9),  # k_pool, v_pool
            **skw,
        )
        # one trampoline per runner: static-arg identity keys the jit
        # cache, so the guided-callback program compiles once per bucket
        self._mask_tramp = _GuidedMaskTrampoline()
        # cached all-True ragged sampling masks per row-cap (the mask is a
        # permanent _ragged_step operand; unconstrained dispatches reuse
        # one device-resident array instead of re-transferring [SEG, V])
        self._true_mask_cache: Dict[int, jax.Array] = {}
        self._zero_bias_cache: Dict[int, jax.Array] = {}
        # cached identity (row_seq, row_j) maps per row cap — the mixed
        # path's no-op for the ragged step's in-XLA sampling expansion
        self._row_map_cache: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        self._row_slices_met: set = set()  # SEG_CAPs (_meet_row_slices)
        # device-resident guided DFA staging (combined transition/mask
        # tables keyed by schema uids) + state scratch; see _stage_guided
        self._guided_dev_cache: "OrderedDict[Any, Tuple[jax.Array, jax.Array]]" = (
            OrderedDict()
        )
        # the engine's guided-fusion gate: per-step masks ride the decode
        # loop's host callback / the ragged step's mask operand, neither
        # of which the PP loop carries
        self.guided_fused = not self.pp
        if self.pp:
            from dynamo_tpu.parallel.mesh import AXIS_PIPE

            self._jit_pp_prefill = jax.jit(
                partial(self._ppmod.pp_forward, self.config),
                donate_argnums=(3, 4),  # k_pool, v_pool
                static_argnames=("mesh", "axis"),
            )
            self._jit_pp_decode = jax.jit(
                partial(
                    self._ppmod.pp_decode_loop, self.config, self.mesh,
                    AXIS_PIPE,
                ),
                static_argnums=(0,),  # n_steps
                donate_argnums=(5, 6),  # k_pool, v_pool
            )
        if not self.pp:
            self._jit_mixed = _family(
                "mixed",
                partial(_mixed_loop, self.config, self.attn_impl,
                        self._fwd_mesh),
                static_argnums=(0,),  # n_steps
                donate_argnums=(10, 11),  # k_pool, v_pool
            )
            self._jit_ragged = _family(
                "ragged",
                partial(_ragged_step, self.config, self.attn_impl,
                        self._fwd_mesh),
                donate_argnums=(9, 10),  # k_pool, v_pool
                **skw,
            )
        # device n-gram draft ring (_draft_ring_step): registered
        # UNCONDITIONALLY so spec-on and spec-off runners expose the same
        # family set (pinned by test_spec_decode); it compiles only when
        # the engine enables device drafting (ensure_draft_ring warms it
        # before the sanitizer's recompile-tripwire freeze)
        self._jit_draft_ring = _family(
            "draft", _draft_ring_step,
            static_argnums=(4, 5),  # k, max_match
            donate_argnums=(0, 1),  # hist, lens
        )
        self._draft_ring = None  # (hist_dev, lens_dev) once ensured
        self._draft_ring_host = None  # (np hist, np lens) mirror
        self._draft_ring_dirty = False  # mirror edited → restage
        self._draft_ring_shape = None  # (slots, window, delta_cap)
        # ragged flat-token mixed dispatch wherever the fused mixed path
        # runs. PP/SP keep the padded [N, S] program; LoRA batches carry
        # per-row adapters the single flat row cannot, and MLA has no
        # ragged attention yet.
        self.ragged_mixed = (
            not self.pp and not self.sp_enabled
            and self.lora is None and not config.is_mla
        )
        # device-resident sampling cache: batches re-send identical sampling
        # params every dispatch; transferring them each time costs one
        # host→device transfer PER ARRAY (see _decode_loop)
        self._sampling_cache: Dict[Any, SamplingParams] = {}
        if draft_config is not None:
            from dynamo_tpu.engine.spec_decode import spec_rounds

            self._jit_spec = jax.jit(
                partial(
                    spec_rounds, self.config, draft_config,
                    self.attn_impl, self.attn_impl, self._fwd_mesh,
                ),
                static_argnums=(0, 1),  # gamma, n_rounds
                donate_argnums=(6, 7, 8, 9),  # both KV pool pairs
            )
            self._jit_draft_forward = jax.jit(
                partial(llama.forward, draft_config),
                donate_argnums=(3, 4),
                static_argnames=("attn_impl",),
            )
        rep = self.device_report()
        log.info(
            "runner ready: %s params+pool placed in %.1fs (mesh %s, %d pages "
            "x %d tokens) on %s %r devices %s; attn_impl=%s (%s), "
            "decode_page_routine=%s, decode_step_pages=%s, "
            "ragged_page_routine=%s, ragged_mixed=%s, "
            "kv_copy_kernel=%s (interpret=%s)",
            config.name, placed_s, self.mesh_config.shape, num_pages,
            page_size, rep["platform"], rep["device_kind"], rep["device_ids"],
            self.attn_impl, self.attn_impl_reason, rep["decode_page_routine"],
            rep["decode_step_pages"], rep["ragged_page_routine"],
            self.ragged_mixed,
            self._kv_copy_kernel, self._kv_copy_interpret,
        )

    def device_report(self) -> Dict[str, Any]:
        """What this runner executes on and which paths the platform
        selected — the `runner ready` log line and the worker's
        GET /debug/device carry it, so serving on the CPU, or on the jnp
        gather where Pallas was expected, is never silent. `kv_shards`
        gives the devices that hold K-pool shards and one shard's shape (a
        TP=4 mesh must show four devices and Hk/4 heads). Both are read
        off the sharding, not the buffers: the step thread donates those
        while the status server calls this. `decode_page_routine` is what
        the Pallas decode kernel does with a page at this worker's shapes
        (ops/paged_attention.py `page_routine`, the decision the kernel's
        wrapper and its walk make: one shard's KV heads, the query heads on
        each, the pool's dtype, a sink, values narrower than keys); a name
        a kind, {"global", "window"}, where window layers keep a pool of
        their own; None where no Pallas decode kernel is on the path (the
        jnp gather). Latent attention's kernel walks the same list over its
        one pool, and `decode_step_pages` is the pages a grid step of it
        brings under this worker's page table (`decode_step`, the walk's
        own decision; None for every other model). `ragged_page_routine` is
        the same of the ragged (mixed-step) kernel, from its own decision
        (ops/ragged_paged_attention.py `ragged_page_routine`); None also
        where the mixed step is not the ragged program."""
        from dynamo_tpu.models.mla import latent_decode_on_kernel
        from dynamo_tpu.ops.paged_attention import decode_step, page_routine
        from dynamo_tpu.ops.ragged_paged_attention import ragged_page_routine

        devs = list(self.mesh.devices.flat)
        k_leaf = jax.tree.leaves(self.k_pool)[0]
        shard_shape = list(k_leaf.sharding.shard_shape(k_leaf.shape))
        decode_routine = ragged_routine = step_pages = None
        c = self.config
        if (self.attn_impl == "pallas" and c.is_mla
                and latent_decode_on_kernel(isinstance(self.k_pool, dict),
                                            self.mesh_config.model > 1)):
            # (one KV head, replicated: the heads a shard is left with are
            # the query's, and the decision does not hang on them)
            decode_routine, step_pages = decode_step(
                (1, c.n_heads), self.k_pool, None, self.max_pages_per_seq,
                False)
        elif self.attn_impl == "pallas" and not c.is_mla:
            shards = k_leaf.shape[3] // shard_shape[3]
            v_width = jax.tree.leaves(self.v_pool)[0].shape[-1]
            # (a pool head, where a pool holds the model's KV heads paired)
            Hk_pool = k_leaf.shape[3]
            kinds = {"global": (Hk_pool, c.sink_global)}
            if "window" in self._by_kind(self.side_units):
                kinds["window"] = (c.n_kv_heads_window or Hk_pool,
                                   c.sink_window)

            quantized = isinstance(self.k_pool, dict)

            def routine_of(ask):
                """`ask(local Hk, G, a sink)` a kind; one name where one."""
                routine = {kind: ask(Hk // shards, c.n_heads // Hk, sinked)
                           for kind, (Hk, sinked) in kinds.items()}
                return routine["global"] if len(routine) == 1 else routine

            decode_routine = routine_of(lambda Hk, G, sinked: page_routine(
                Hk, G, k_leaf.dtype, quantized, sinked,
                k_leaf.shape[-1] == v_width))
            if self.ragged_mixed:
                ragged_routine = routine_of(
                    lambda Hk, G, _: ragged_page_routine(Hk, G, quantized))
        memory = {}
        for d in devs:
            if d.process_index != jax.process_index():
                continue  # another host's chip: not addressable from here
            st = d.memory_stats()  # None on the CPU backend
            if st:
                memory[str(d.id)] = {
                    k: int(st[k])
                    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                    if k in st
                }
        # (a side pool under its kind's keys; 0 under a kind it has not)
        units = {"state": 0, "window": 0, **self._by_kind(self.side_units)}
        unit_bytes = {"state": 0, "window": 0,
                      **self._by_kind(self.side_unit_bytes)}
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_ids": [d.id for d in devs],
            "mesh": list(self.mesh_config.shape),
            "attn_impl": self.attn_impl,
            "attn_impl_reason": self.attn_impl_reason,
            "decode_page_routine": decode_routine,
            "decode_step_pages": step_pages,
            "ragged_page_routine": ragged_routine,
            "ragged_mixed": self.ragged_mixed,
            "kv_copy_kernel": self._kv_copy_kernel,
            "kv_copy_interpret": self._kv_copy_interpret,
            "kv_shards": {
                "devices": sorted(d.id for d in k_leaf.sharding.device_set),
                "shard_shape": shard_shape,
            },
            "kv_pool_bytes": self.kv_pool_bytes(),
            "state_slots": units["state"],
            "state_pool_bytes": units["state"] * unit_bytes["state"],
            "window_pages": units["window"],
            "window_pool_bytes": units["window"] * unit_bytes["window"],
            "memory": memory,
        }

    # -- steps -------------------------------------------------------------
    def prefill(
        self,
        tokens: List[int],
        start_pos: int,
        page_table_row: List[int],
        prior_len: int,
        adapter: int = 0,
        mm: Optional[Dict[str, Any]] = None,  # {"embeds": [n,E], "offsets": [n]}
        side=None,  # a model with a side cache: what the sequence holds
        #   there (None: scratch). A state slot: a chunk at start_pos 0
        #   starts from zeros, a later one from what the slot holds. A
        #   window page table: a list.
        sampled: bool = True,  # False: nobody reads this chunk's logits (it
        #   does not end its prompt); a runner that `skips_unsampled`
        #   leaves out what only they need and returns zeros
    ) -> jax.Array:
        """Run one prefill chunk for a single sequence. `tokens` are the
        uncomputed prompt tokens starting at absolute position `start_pos`;
        `prior_len` is the context length already in the pool (prefix-cache
        hits + earlier chunks). `mm` injects multimodal embeddings at
        chunk-local offsets. Returns last-token logits [V] (device)."""
        with phase(STAGE):
            tok, pos, pt, kv_lens, n = self._prep_prefill(tokens, start_pos, page_table_row, prior_len)
            if not self.pp:
                mm_embeds, mm_mask = self._mm_arrays(mm, tok.shape[1])
        if self.pp:
            if mm is not None:
                raise NotImplementedError(
                    "multimodal prefill is not wired on the PP path yet"
                )
            logits, self.k_pool, self.v_pool = self._jit_pp_prefill(
                self.params, tok, pos, self.k_pool, self.v_pool, pt, kv_lens,
                mesh=self.mesh, axis="pipe",
            )
            return logits[0, n - 1]
        if self._side_mod is not None:
            if mm is not None:
                raise NotImplementedError(
                    "multimodal prefill is not wired for a model with a "
                    "state pool or a window pool")
            logits, self.k_pool, self.v_pool, *routed = self._jit_forward(
                self.params, tok, pos, self.k_pool, self.v_pool, pt, kv_lens,
                jnp.int32(n - 1), attn_impl=self.attn_impl,
                mesh=self._fwd_mesh,
                **self._state_kw([side], 1),
                # (traced: one compiled program serves both)
                **({"sampled": jnp.asarray(bool(sampled))}
                   if self.skips_unsampled else {}),
            )
            self._note_routed(self._keep_state(routed), 1, chunk_lens=[n])
            self._note_sampled(0, [n], int(bool(sampled)))
            return logits[0, 0]
        impl = "ring" if self.sp_enabled else self.attn_impl
        logits, self.k_pool, self.v_pool, *routed = self._jit_forward(
            self.params, tok, pos, self.k_pool, self.v_pool, pt, kv_lens,
            jnp.int32(n - 1), attn_impl=impl,
            mesh=self.mesh if impl == "ring" else self._fwd_mesh,
            sp_has_prior=prior_len > 0,
            lora=self.lora,
            adapter_idx=jnp.asarray([adapter], jnp.int32) if self.lora is not None else None,
            mm_embeds=mm_embeds, mm_mask=mm_mask,
        )
        self._note_routed(routed, 1, chunk_lens=[n])
        return logits[0, 0]

    # -- the side cache (a model with a cache beside its KV pages) ----------
    def ensure_side_cache(self, units):
        """Hold a side pool of at least `units` units (unit 0 is scratch)
        and say how many it has: 0 where the model keeps none. A model of
        several kinds (`side_kind` "a+b") takes and gives a tuple, one
        count a kind. Growing allocates a zeroed pool, so it is for
        construction, before any sequence holds a unit."""
        if self._side_mod is None:
            return 0
        want, have = self._by_kind(units), self._by_kind(self.side_units)
        if self.state is None or any(want[k] > have[k] for k in want):
            units = (tuple(int(u) for u in units) if "+" in self.side_kind
                     else int(units))
            self.state = jax.jit(
                partial(self._side_mod.make_pool, self.config, units,
                        self.page_size, self.dtype),
                out_shardings=self.policy.replicated())()
            self.side_units = units
        return self.side_units

    def _by_kind(self, value) -> Dict[str, int]:
        """{kind: its entry} of a fact the door states once a kind
        (`side_units`, `side_unit_bytes`: an int where there is one kind, a
        tuple in `side_kind`'s order where there are several; 0: not yet)."""
        kinds = self.side_kind.split("+") if self.side_kind else []
        entries = value if isinstance(value, tuple) else (value,) * len(kinds)
        return dict(zip(kinds, entries))

    def _state_pool(self):
        """The pool a step hands its program: whoever takes the runner
        sizes it first (the engine does: ensure_side_cache)."""
        if self.state is None:
            raise RuntimeError(
                f"{self.config.name} keeps a second pool beside its KV pages "
                "(a recurrent state a sequence, or its window layers' cache) "
                "and nobody sized it: call ensure_side_cache(units) first")
        return self.state

    def _state_kw(self, side, B: int) -> Dict[str, Any]:
        """_decode_loop's and the prefill program's keywords for the rows'
        `side` operands at bucket B; nothing for a model with one pool."""
        if self._side_mod is None:
            return {}
        return {"state": self._state_pool(),
                "slots": self._side_mod.rows(side or (), B,
                                             self.max_pages_per_seq)}

    def _seg_state_kw(self, side, n_dec: int, chunks, seg_cap: int,
                      t_bucket: int) -> Dict[str, Any]:
        """_ragged_step's keywords: the segments' `side` operands (decode
        rows first, then the chunks, as _prep_ragged lays them)."""
        if self._side_mod is None:
            return {}
        sides = list(side or [None] * n_dec) + [c.get("side") for c in chunks]
        lens = [1] * n_dec + [len(c["tokens"]) for c in chunks]
        return {"state": self._state_pool(),
                "seg_slots": self._side_mod.segs(
                    sides, lens, seg_cap, t_bucket, self.max_pages_per_seq)}

    def _keep_state(self, extra: list) -> list:
        """A state-holding model's step returns its pool last: keep it,
        and hand back what else followed the KV pools."""
        if self._side_mod is not None:
            self.state = extra[-1]
            return extra[:-1]
        return extra

    def _no_side(self, what: str) -> None:
        """Refuse `what`, in its kind's sentence, where the model keeps a
        side cache: its second pool is neither moved, copied nor rolled
        back with the pages."""
        if self._side_mod is not None:
            raise NotImplementedError(
                refusal(self.side_kind, self.config.name, what))

    # -- routed experts ------------------------------------------------------
    def _note_routed(self, routed, forwards: int, n_dec: int = 0,
                     chunk_lens: Sequence[int] = (),
                     chained: bool = False) -> None:
        """Keep a dispatch's routed output (a routed model's step programs
        return it last; `routed` is what followed the fixed outputs, empty
        for a dense model) until the engine takes it. The picks of earlier
        dispatches nobody took are let go, so routed_picks() answers for
        the newest dispatch alone (`chained`: and the one it continues)."""
        if not routed:
            return
        if not chained:
            for p in self._routed_parts:
                p.picks = None
        self._routed_parts.append(
            _RoutedPart(routed[0], forwards, n_dec, chunk_lens))

    # -- the rows only logits need ------------------------------------------
    def _note_sampled(self, decode_rows: int, chunk_lens: Sequence[int] = (),
                      chunk_rows: int = 0) -> None:
        """A dispatch handed its program these real rows to run the
        cross-decoder on: `decode_rows` (a row a step) and, of chunks of
        these lengths, the `chunk_rows` at `last_index` (none of a chunk
        told `sampled=False`); their other tokens went without."""
        if self.skips_unsampled:
            self._sampled_rows += decode_rows + chunk_rows
            self._skipped_tokens += sum(chunk_lens) - chunk_rows

    def fill_record(self, record) -> None:
        """The dispatches since the last record, on this one (a decode
        dispatch the engine ran ahead counts where it was enqueued)."""
        if self.skips_unsampled:
            record.yoco_cross_rows = self._sampled_rows
            record.yoco_skipped_tokens = self._skipped_tokens
            self._sampled_rows = self._skipped_tokens = 0

    def _readback(self, x):
        """jax.device_get of a dispatch's results and, in the same call,
        of the expert-load counters no readback has brought yet (a dense
        model has none: the plain device_get it always was)."""
        if not self._routed_parts:
            out = jax.device_get(x)
        else:
            out = _device_get_with_loads(self._routed_parts, x)
        synced()  # the step clock: nothing serial is enqueued any more
        return out

    def routed_picks(self):
        """The experts every token of the newest dispatch (a ragged step
        and the decode loop chained on it are one) was routed to,
        fetched in one device_get and cut to the plan's real rows:
        (decode, int32 [steps, L_moe, n_dec, k] or None: the decode rows'
        picks step by step; chunks: one int32 [L_moe, n, k] per prefill
        chunk in dispatch order). The engine calls this only
        in an iteration where a request asked (`routed_experts`); the
        arrays otherwise never leave the device."""
        parts = [p for p in self._routed_parts if p.picks is not None]
        with self._allow("token_readback"), phase(READBACK):
            host = self._readback([p.picks for p in parts])
        dec: List[np.ndarray] = []
        chunks: List[np.ndarray] = []
        cells = self.max_pages_per_seq * self.page_size
        for p, h in zip(parts, host):
            p.picks = None
            # an indexer's choices ride below the picks (_beside_picks)
            chosen_c, chosen_d = h.get("chosen_chunks"), h.get("chosen_decode")
            if "flat" in h:  # [L_moe, T, k]: decode rows, then the chunks
                if p.n_dec:
                    dec.append(np.asarray(h["flat"][None, :, : p.n_dec]))
                off = p.n_dec
                for n in p.chunk_lens:
                    chunks.append(np.asarray(h["flat"][:, off : off + n]))
                    off += n
            if "chunks" in h:  # [L_moe, N, S, k]: one padded row a chunk
                for i, n in enumerate(p.chunk_lens):
                    picks = np.asarray(h["chunks"][:, i, :n])
                    if chosen_c is not None:
                        picks = _beside_picks(picks, chosen_c[:, i, :n], cells)
                    chunks.append(picks)
            if "decode" in h:  # [n_steps, L_moe, B, k]
                picks = np.asarray(h["decode"][:, :, : p.n_dec])
                if chosen_d is not None:  # the layers lead in _beside_picks
                    picks = np.moveaxis(_beside_picks(
                        np.moveaxis(picks, 1, 0),
                        np.moveaxis(chosen_d[:, :, : p.n_dec], 1, 0), cells), 0, 1)
                dec.append(picks)
        return (np.concatenate(dec) if dec else None), chunks

    def take_moe_load(self) -> MoeLoad:
        """The expert-load counters of the dispatches since the last call
        (the engine: once an iteration), and forget those dispatches."""
        parts = list(self._routed_parts)
        self._routed_parts.clear()
        return MoeLoad(parts, self.config.n_layers - self.config.n_dense_layers)

    def _mm_arrays(self, mm: Optional[Dict[str, Any]], S: int):
        """(mm_embeds [1,S,E], mm_mask [1,S]) padded to the bucket, or
        (None, None)."""
        if mm is None:
            return None, None
        E = self.config.dim
        embeds = np.zeros((1, S, E), np.float32)
        mask = np.zeros((1, S), bool)
        for row, off in zip(mm["embeds"], mm["offsets"]):
            embeds[0, off] = row
            mask[0, off] = True
        return jnp.asarray(embeds), jnp.asarray(mask)

    def _prep_prefill(self, tokens: List[int], start_pos: int, page_table_row: List[int], prior_len: int):
        """Bucket-pad one prefill chunk into device inputs (shared by the
        target and draft prefill paths)."""
        n = len(tokens)
        S = _next_bucket(self.prefill_buckets, n)
        tok = np.zeros((1, S), np.int32)
        tok[0, :n] = tokens
        pos = np.full((1, S), -1, np.int32)
        pos[0, :n] = np.arange(start_pos, start_pos + n)
        pt = self._pad_page_table([page_table_row])
        kv_lens = np.asarray([prior_len + n], np.int32)
        return jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(pt), jnp.asarray(kv_lens), n

    def attach_sanitizer(self, san) -> None:
        """Adopt the engine's runtime sanitizer: staging / readback sites
        below run inside named allow_transfer scopes so the engine can
        hold `jax.transfer_guard("disallow")` across whole dispatches."""
        self._sanitizer = san

    def layout_table(self):
        """(name, live array, declared NamedSharding) rows for every model
        param and KV pool — the statically-derived layout contract
        (ShardingPolicy over parallel/mesh.py's canonical spec tables)
        zipped with the arrays that must satisfy it. The sanitizer's
        layout guard diffs live `jax.Array.sharding` against these at
        warm-path entry; dynlint's DYN-S rules check the same tables
        statically (docs/static_analysis.md)."""
        rows = []

        def _walk(prefix, tree, shardings):
            leaves = jax.tree_util.tree_leaves_with_path(tree)
            wants = jax.tree_util.tree_leaves(shardings)
            for (path, leaf), want in zip(leaves, wants):
                name = prefix + "/".join(
                    str(getattr(k, "key", k)) for k in path
                )
                rows.append((name.rstrip("/"), leaf, want))

        _walk("params/", self.params,
              self.policy.params_sharding(self.params))
        _walk("k_pool/", self.k_pool,
              self.policy.kv_pool_sharding_tree(self.k_pool))
        _walk("v_pool/", self.v_pool,
              self.policy.kv_pool_sharding_tree(self.v_pool))
        if getattr(self, "draft_params", None) is not None:
            _walk("draft_params/", self.draft_params,
                  self.policy.params_sharding(self.draft_params))
        if getattr(self, "draft_k_pool", None) is not None:
            _walk("draft_k_pool/", self.draft_k_pool,
                  self.policy.kv_pool_sharding_tree(self.draft_k_pool))
            _walk("draft_v_pool/", self.draft_v_pool,
                  self.policy.kv_pool_sharding_tree(self.draft_v_pool))
        if getattr(self, "lora", None) is not None:
            _walk("lora/", self.lora,
                  self.policy.params_sharding(self.lora))
        return rows

    def _allow(self, label: str):
        san = self._sanitizer
        return contextlib.nullcontext() if san is None else san.allow_transfer(label)

    def _adapter_array(self, adapters: Optional[List[int]], B: int):
        if self.lora is None:
            return None
        idx = np.zeros(B, np.int32)
        if adapters:
            idx[: len(adapters)] = adapters
        return jnp.asarray(idx)

    def _stage_decode_rows(self, tokens, positions, page_tables, step,
                           adapters, B: int):
        """The decode rows of a dispatch as _decode_loop takes them, at
        decode bucket B: (tokens [B], packed pos|pt|adapters|step) on the
        device, all per-dispatch dynamic ints in ONE transfer (see
        _decode_loop). `tokens` may be a device array [B] — the ragged
        tail chains on the tokens the ragged step sampled, a dispatch run
        ahead on the `last` of the one before it — and passes through
        untouched: no readback, no eager slice program. Host tokens are
        placed as a step program returns its own (replicated over the
        mesh, committed): the placement is part of the jit's signature,
        so the loop a warm-up compiled from host tokens is the one a
        chained dispatch finds, and not a second program."""
        n = len(positions)
        pt = self._pad_page_table(page_tables, B)
        MP = pt.shape[1]
        packed = np.zeros(
            B * (1 + MP) + (B if self.lora is not None else 0) + 1, np.int32)
        packed[:B] = -1
        packed[:n] = positions
        packed[B : B + B * MP] = pt.ravel()
        if self.lora is not None and adapters:
            packed[B + B * MP : B + B * MP + len(adapters)] = adapters
        packed[-1] = step
        with self._allow("decode_staging"):
            if isinstance(tokens, jax.Array):
                tok = tokens
            else:
                tok_h = np.zeros(B, np.int32)
                tok_h[:n] = tokens
                tok = (jnp.asarray(tok_h) if self._tok_sharding is None
                       else jax.device_put(tok_h, self._tok_sharding))
            return tok, jnp.asarray(packed)

    def _pad_rows(self, rows: np.ndarray, B: int, fill, dtype) -> jax.Array:
        """[n, V] guided masks (fill True) or logit-bias rows (fill 0) at
        B rows on the device; the pad rows stay all-allowed / unbiased."""
        out = np.full((B, self.config.vocab_size), fill, dtype)
        out[: rows.shape[0]] = rows
        with self._allow("decode_staging"):
            return jnp.asarray(out)

    def _guided_kw(self, mask_fn, guided_dev, B: int) -> Dict[str, Any]:
        """_decode_loop's keyword for rows whose guided masks change from
        step to step: the host callback (mask_fn, which wins when both are
        given: the fallback for schemas past the device-table budget) or
        the device-resident DFA plan."""
        if mask_fn is not None:
            mask_fn.B = B  # callback mask rows must match the padded bucket
            self.set_guided_ctx(mask_fn)
            return {"mask_fn": self._mask_tramp}
        if guided_dev is not None:
            return {"guided": self._guided_op(guided_dev, B)}
        return {}

    def decode_multi(
        self,
        n_steps: int,
        tokens: List[int],
        positions: List[int],
        page_tables: List[List[int]],
        sampling,  # SamplingParams or dict of host lists
        step: int,
        adapters: Optional[List[int]] = None,
        masks: Optional[np.ndarray] = None,  # [n, V] bool guided masks
        biases: Optional[np.ndarray] = None,  # [n, V] f32 logit_bias rows
        mask_fn=None,  # GuidedMaskContext: per-step host-advanced masks,
        # letting constrained rows ride full n_steps fused loops (the
        # static `masks` covers step 0 semantics when mask_fn is None)
        guided_dev=None,  # (tables, row_entries, pending): device-resident
        # guided DFA plan — tables a deduped List[DeviceGuidedTable],
        # row_entries[i] None (unguided row) or (table_idx, local_state).
        # Replaces mask_fn's per-step io_callback with an in-XLA
        # advance+gather for bounded schemas (see _decode_loop `guided`)
        n_logprobs: int = -1,
        histories: Optional[List[List[int]]] = None,
        prompt_lens: Optional[List[int]] = None,
        side: Optional[list] = None,  # a model with a side cache: what each
        # row's sequence holds there (None: scratch, the warm-up's dummies)
    ):
        """n_steps fused decode iterations (one host sync total). Page
        tables must already cover positions[i] + n_steps slots. Returns
        sampled tokens [B_bucket, n_steps] (host): decode_dispatch and its
        decode_collect, back to back.

        The sampling extras: `histories` (per-sequence prompt+generated
        token ids) switches on repetition/frequency/presence penalties —
        `prompt_lens[i]` marks where generated output starts in
        histories[i] (frequency/presence are output-only; absent = whole
        history is prompt); with `n_logprobs` >= 0 the return is
        (sampled, (tok_lp [B, T], top_ids [B, T, K], top_lps [B, T, K]))
        host arrays."""
        return self.decode_collect(self.decode_dispatch(
            n_steps, tokens, positions, page_tables, sampling, step,
            adapters=adapters, masks=masks, biases=biases, mask_fn=mask_fn,
            guided_dev=guided_dev, n_logprobs=n_logprobs,
            histories=histories, prompt_lens=prompt_lens, side=side))

    def decode_bucket(self, n: int) -> int:
        return _next_bucket(self.decode_buckets, n)

    def decode_dispatch(self, n_steps, tokens, positions, page_tables,
                        sampling, step, adapters=None, masks=None,
                        biases=None, mask_fn=None, guided_dev=None,
                        n_logprobs=-1, histories=None, prompt_lens=None,
                        side=None, prev: Optional[DecodeHandle] = None,
                        ) -> DecodeHandle:
        """decode_multi's stage and enqueue, without its readback: the
        handle decode_collect reads. With `prev` (a handle of the same
        decode bucket, read back or not) the rows take their first tokens
        from its `last` on the device and `tokens` is not looked at: row i
        continues row i. A row with position -1 is a pad row wherever it
        sits (an empty page table, state slot 0): it writes nothing and its
        tokens mean nothing, which is how a row that ended keeps its place
        open. The pools are donated from one program to the next, so a
        dispatch queued behind another runs after it on the device."""
        if self.pp and (
                n_logprobs >= 0 or histories is not None or biases is not None
                or mask_fn is not None or guided_dev is not None):
            raise NotImplementedError(
                "logprobs/penalties/logit_bias/multi-step guided masks "
                "are not wired on the pipeline-parallel decode path yet"
            )
        with phase(STAGE):
            n = len(positions)
            B = _next_bucket(self.decode_buckets, n)
            if prev is not None:
                if prev.rows != B:
                    raise ValueError(
                        f"a dispatch of {n} rows (bucket {B}) cannot chain "
                        f"on one of bucket {prev.rows}")
                tokens = prev.last
            tok, packed_dev = self._stage_decode_rows(
                tokens, positions, page_tables, step, adapters, B)
            hist = None
            if histories is not None:
                # bucketed so history growth re-compiles per bucket, not per
                # token; pad token == vocab_size scatters drop in _decode_loop
                H = max(8, max((len(h) for h in histories), default=1))
                H = -(-H // 128) * 128
                hist_h = np.full((B, H), self.config.vocab_size, np.int32)
                plen_h = np.zeros(B, np.int32)
                for i, h in enumerate(histories):
                    hist_h[i, : len(h)] = h
                    plen_h[i] = (
                        prompt_lens[i] if prompt_lens is not None else len(h)
                    )
                with self._allow("decode_staging"):
                    hist = (jnp.asarray(hist_h), jnp.asarray(plen_h))
            mask_dev = (None if masks is None
                        else self._pad_rows(masks, B, True, bool))
            bias_dev = (None if biases is None
                        else self._pad_rows(biases, B, 0.0, np.float32))
            mkw = self._guided_kw(mask_fn, guided_dev, B)
            with self._allow("decode_staging"):
                samp = self._device_sampling(sampling, B)
        lp, parts = None, []
        if self.pp:
            toks, last, self.k_pool, self.v_pool = self._jit_pp_decode(
                n_steps, self.params, tok, packed_dev, mask_dev,
                self.k_pool, self.v_pool, samp,
            )
        else:
            toks, last, lp, self.k_pool, self.v_pool, *routed = self._jit_decode_loop(
                n_steps, n_logprobs, self.params, tok, packed_dev, hist,
                mask_dev, bias_dev, self.k_pool, self.v_pool,
                samp, self.lora, **mkw, **self._state_kw(side, B),
            )
            routed = self._keep_state(routed)
            if self.skips_unsampled:  # (pad rows, position -1, are no rows)
                self._note_sampled(n_steps * sum(p >= 0 for p in positions))
            if routed:
                # the handle's own until it is collected: a readback of
                # another dispatch must not wait for these counters
                parts = [_RoutedPart(routed[0], n_steps, n, ())]
        handle = DecodeHandle(toks, last, lp if n_logprobs >= 0 else None,
                              parts, B)
        with self._allow("token_readback"):
            # start the copy to the host behind this program, before
            # another is enqueued: decode_collect then waits for this
            # dispatch's results and not for a place in a transfer queue
            for a in jax.tree_util.tree_leaves(
                    (toks, handle.lp, [p.load for p in parts])):
                a.copy_to_host_async()
        return handle

    def decode_collect(self, handle: DecodeHandle):
        """Read a decode_dispatch back: sampled [B_bucket, n_steps] on the
        host, with a logprob report as decode_multi pairs them. It waits
        for this dispatch and for none queued behind it: the expert-load
        counters it fetches beside the tokens are the handle's own (and
        those of earlier dispatches that sampled nothing), and from here
        on routed_picks() and take_moe_load() answer for this dispatch."""
        if handle.parts:
            for p in self._routed_parts:
                p.picks = None
            self._routed_parts.extend(handle.parts)
            handle.parts = []
        with self._allow("token_readback"), phase(READBACK):
            if handle.lp is not None:
                toks_h, lp_h = self._readback((handle.toks, handle.lp))
                return np.asarray(toks_h), tuple(np.asarray(a) for a in lp_h)
            return np.asarray(self._readback(handle.toks))

    def can_fuse(self, n_decode: int, n_chunks: int, *,
                 constrained: bool) -> bool:
        if (self.pp or self.sp_enabled or self.has_draft
                or not self.fuses_mixed):
            # SP runners prefill with ring attention on the full mesh —
            # the fused program's plain attn_impl would miscompute the
            # chunk's KV there
            return False
        # masks and bias exist only as ragged-step / decode-loop operands:
        # guided or biased decode rows fuse iff the plan rides the ragged
        # flat-token program (never the padded [N, S] one, which would
        # silently drop the constraint)
        return not constrained or self._use_ragged(n_decode, n_chunks)

    def decode_multi_with_prefill(self, n_steps, tokens, positions, page_tables,
                                  sampling, step, chunk_tokens, chunk_start,
                                  chunk_table, chunk_prior, chunk_adapter=0, **kw):
        # Kept for benchmark/serve.py's warm-up walk alone (no program PR may
        # edit it; ROADMAP D10); the engine calls decode_multi_with_prefills.
        chunk = {"tokens": chunk_tokens, "start": chunk_start, "table": chunk_table,
                 "prior": chunk_prior, "adapter": chunk_adapter}
        toks, rows = self.decode_multi_with_prefills(
            n_steps, tokens, positions, page_tables, sampling, step, [chunk], **kw)
        return toks, rows[0]

    def _prep_prefill_packed(self, chunks: List[Dict[str, Any]]):
        """Bucket-pad a packed chunk set into ragged [N, S] device inputs,
        one row per chunk (each row's valid tokens are a contiguous run
        from s=0, which is the layout the prefill attention kernels'
        q_start/q_len metadata requires — a flat concatenation of
        segments would break their causal masking). Rows past the real
        chunk count replicate row 0: the duplicate rewrites identical KV
        bytes to the same pages (harmless) and avoids q_len=0 edge cases
        in the kernels; its logits row is discarded by the caller."""
        N = _next_bucket(self.pack_buckets, len(chunks))
        S = _next_bucket(
            self.prefill_buckets, max(len(c["tokens"]) for c in chunks)
        )
        tok = np.zeros((N, S), np.int32)
        pos = np.full((N, S), -1, np.int32)
        kvl = np.zeros(N, np.int32)
        last = np.zeros(N, np.int32)
        adapters = np.zeros(N, np.int32)
        rows = []
        for i in range(N):
            c = chunks[i] if i < len(chunks) else chunks[0]
            n = len(c["tokens"])
            tok[i, :n] = c["tokens"]
            pos[i, :n] = np.arange(c["start"], c["start"] + n)
            kvl[i] = c["prior"] + n
            last[i] = n - 1
            adapters[i] = c.get("adapter") or 0
            rows.append(c["table"])
        pt = self._pad_page_table(rows, N)
        padapter = jnp.asarray(adapters) if self.lora is not None else None
        return (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(pt),
                jnp.asarray(kvl), jnp.asarray(last), padapter)

    def decode_multi_with_prefills(self, *args, **kw) -> MixedOut:
        """Fused mixed iteration, enqueued and read back in one call
        (mixed_dispatch's signature): MixedOut(sampled [B_bucket, n_steps]
        host, last-token logits [V] device per chunk, which program ran).
        What a multi-host group replays and benchmark/serve.py's walk
        calls; the engine's step loop runs the two halves with its
        delivery between them."""
        return self.mixed_collect(self.mixed_dispatch(*args, **kw))

    def mixed_dispatch(
        self,
        n_steps: int,
        tokens: List[int],
        positions: List[int],
        page_tables: List[List[int]],
        sampling,
        step: int,
        chunks: List[Dict[str, Any]],  # {"tokens", "start", "table",
        #   "prior", "adapter"} per packed chunk (distinct sequences)
        adapters: Optional[List[int]] = None,
        masks: Optional[np.ndarray] = None,  # [n_dec, V] step-0 guided masks
        mask_fn=None,  # GuidedMaskContext for the fused tail steps 1..n-1
        biases: Optional[np.ndarray] = None,  # [n_dec, V] logit-bias rows
        guided_dev=None,  # device guided DFA plan for the fused tail
        side: Optional[list] = None,  # a model with a side cache: what each
        # decode row's sequence holds there; a chunk's rides its dict as "side"
    ) -> MixedHandle:
        """Stage and enqueue a fused mixed iteration: the decode batch's
        n_steps AND the token-budgeted prefill chunk set, one chunk or
        many, in one dispatch (two on the ragged path, chained on the
        device), and return without reading anything back (mixed_collect
        does). The one place that picks the program: the ragged
        flat-token step where the runner has it and the plan fits its
        segments; the padded [N, S] program (_mixed_loop, each chunk a
        row) otherwise, and when an unconstrained plan overflows the
        ragged T buckets. The engine takes the two-dispatch path for the
        feature planes this doesn't carry (can_fuse;
        logprobs/penalties/multimodal chunks)."""
        if self.pp:
            raise NotImplementedError("fused mixed step has no PP path")
        constrained = (masks is not None or mask_fn is not None
                       or biases is not None or guided_dev is not None)
        if self._use_ragged(len(positions), len(chunks)):
            try:
                return self._decode_multi_with_prefills_ragged(
                    n_steps, tokens, positions, page_tables, sampling, step,
                    chunks, masks=masks, mask_fn=mask_fn, biases=biases,
                    guided_dev=guided_dev, side=side,
                )
            except BucketOverflowError as e:
                if constrained or self._side_mod is not None:
                    # (a model with a second pool has no padded program either)
                    # the padded fallback has no mask/bias plane; the
                    # engine sheds chunks and retries rather than dropping
                    # a guided row's constraint or a bias ban
                    raise
                log.warning(
                    "mixed plan (%d tokens) overflows ragged T buckets "
                    "(largest %d); using the padded fallback", e.n, e.largest,
                )
        elif constrained:
            raise NotImplementedError(
                "guided masks / logit bias require the ragged mixed path "
                "(can_fuse gates on it)"
            )
        if self._side_mod is not None:
            raise NotImplementedError(
                "a model with a state pool or a window pool fuses a mixed "
                "plan on the ragged program "
                f"alone, and this plan ({len(positions)} rows + {len(chunks)} "
                "chunks) has more segments than it takes")
        n_dec = len(positions)
        with phase(STAGE):
            chunk_half = self._prep_prefill_packed(chunks)
            B = _next_bucket(self.decode_buckets, n_dec)
            tok_dev, packed_dev = self._stage_decode_rows(
                tokens, positions, page_tables, step, adapters, B)
            samp = self._device_sampling(sampling, B)
        kw = {}
        if self.routed:  # rows past the real chunks replicate row 0
            kw["prows"] = jnp.int32(len(chunks))
        toks, _, chunk_logits, self.k_pool, self.v_pool, *routed = self._jit_mixed(
            n_steps, self.params, *chunk_half, tok_dev, packed_dev,
            self.k_pool, self.v_pool, samp, self.lora, **kw,
        )
        self._note_routed(routed, 1 + n_steps, n_dec,
                          [len(c["tokens"]) for c in chunks])
        return MixedHandle(None, toks, chunk_logits, len(chunks), False)

    def mixed_collect(self, handle: MixedHandle) -> MixedOut:
        """Read a mixed_dispatch back: decode_multi_with_prefills'
        MixedOut."""
        h = handle
        with phase(READBACK):
            tok0, rest = self._readback((h.tok0, h.rest))
            rows = _chunk_rows(h.chunk_logits, h.n_chunks)
        if tok0 is None:
            toks = np.asarray(rest)
        elif rest is None:
            toks = np.asarray(tok0)[:, None]
        else:
            toks = np.concatenate(
                [np.asarray(tok0)[:, None], np.asarray(rest)], axis=1)
        return MixedOut(toks, rows, h.ragged, h.pages_live)

    # -- guided sampling masks --------------------------------------------
    def _true_mask(self, rows: int) -> jax.Array:
        """Device-resident all-True [rows, V] sampling mask. The ragged
        step takes the mask as a PERMANENT operand (constant treedef =
        no variant split between guided and free dispatches), so the
        unconstrained common case must not pay a [rows, V] host→device
        transfer per iteration — one cached array per row cap does."""
        hit = self._true_mask_cache.get(rows)
        if hit is None:
            hit = jnp.ones((rows, self.config.vocab_size), jnp.bool_)
            self._true_mask_cache[rows] = hit
        return hit

    def _seg_mask(self, masks: Optional[np.ndarray], seg_cap: int) -> jax.Array:
        """Pad row-aligned guided masks to the sampled-row cap (pad rows
        all-allowed); None = the cached all-True operand."""
        if masks is None:
            return self._true_mask(seg_cap)
        return self._pad_rows(masks, seg_cap, True, bool)

    def _zero_bias(self, rows: int) -> jax.Array:
        """Device-resident all-zero [rows, V] logit bias — the cached
        no-op counterpart of _true_mask for the ragged step's permanent
        bias operand."""
        hit = self._zero_bias_cache.get(rows)
        if hit is None:
            hit = jnp.zeros((rows, self.config.vocab_size), jnp.float32)
            self._zero_bias_cache[rows] = hit
        return hit

    def _seg_bias(self, biases: Optional[np.ndarray], seg_cap: int) -> jax.Array:
        """Pad row-aligned logit-bias rows to the sampled-row cap (pad
        rows zero); None = the cached all-zero operand."""
        if biases is None:
            return self._zero_bias(seg_cap)
        return self._pad_rows(biases, seg_cap, 0.0, np.float32)

    def _identity_rows(self, seg_cap: int) -> Tuple[jax.Array, jax.Array]:
        """Cached identity (row_seq, row_j) maps: non-verify ragged
        dispatches sample row i with base row i's params and no seed
        fold, so the in-XLA expansion is a no-op gather and both arrays
        stay device-resident across iterations (same rationale as
        _true_mask)."""
        hit = self._row_map_cache.get(seg_cap)
        if hit is None:
            hit = (
                jnp.arange(seg_cap, dtype=jnp.int32),
                jnp.zeros(seg_cap, jnp.int32),
            )
            self._row_map_cache[seg_cap] = hit
        return hit

    def set_guided_ctx(self, ctx) -> None:
        """Install the per-dispatch guided-DFA context the decode loop's
        host callback reads (see _GuidedMaskTrampoline)."""
        self._mask_tramp.ctx = ctx

    def stage_guided_tables(self, tables) -> Tuple[jax.Array, jax.Array, List[int]]:
        """Stage a batch's device guided DFA (combined token-level
        transition + mask tables, guided/device_table.py) and return
        (trans_dev [G, V], mask_dev [G, V], state offsets per table).

        Keyed by the schemas' uids so the combined arrays stay
        device-resident across every dispatch of the same constraint set
        — the whole point of the device path is that NOTHING guided
        moves host→device in the warm loop except the [B] initial-state
        vector. Bounded LRU: admission churn across many distinct
        schema combinations evicts the oldest combination."""
        from dynamo_tpu.guided.device_table import combine_tables

        key = tuple(t.uid for t in tables)
        hit = self._guided_dev_cache.get(key)
        if hit is not None:
            self._guided_dev_cache.move_to_end(key)
            trans_dev, mask_dev, offsets = hit
            return trans_dev, mask_dev, offsets
        trans, mask, offsets = combine_tables(tables)
        with self._allow("decode_staging"):
            trans_dev = jnp.asarray(trans)
            mask_dev = jnp.asarray(mask)
        self._guided_dev_cache[key] = (trans_dev, mask_dev, offsets)
        while len(self._guided_dev_cache) > 32:
            self._guided_dev_cache.popitem(last=False)
        return trans_dev, mask_dev, offsets

    def _guided_op(self, guided_dev, B: int):
        """Materialize a (tables, row_entries, pending) plan into the
        _decode_loop `guided` operand tuple for a B-row bucket: combined
        tables from the staged cache, per-row global initial states (pad
        and unguided rows sit in DEAD), pending as a traced scalar so
        pending-0/1 dispatches share one compiled variant."""
        g_tables, g_rows, g_pend = guided_dev
        trans_dev, gmask_dev, offs = self.stage_guided_tables(g_tables)
        dead = int(trans_dev.shape[0]) - 1
        gs0 = np.full(B, dead, np.int32)
        for i, ent in enumerate(g_rows):
            if ent is not None:
                ti, st = ent
                gs0[i] = offs[ti] + int(st)
        with self._allow("decode_staging"):
            gs0_dev = jnp.asarray(gs0)
            gpend = jnp.int32(1 if g_pend else 0)
        return (trans_dev, gmask_dev, gs0_dev, gpend)

    # -- ragged flat-token mixed path -------------------------------------
    def _use_ragged(self, n_decode: int, n_chunks: int) -> bool:
        from dynamo_tpu.ops.ragged_paged_attention import RAGGED_MAX_SEGS

        return (
            self.ragged_mixed
            and n_decode + n_chunks <= RAGGED_MAX_SEGS
        )

    def ensure_ragged_bucket(self, t: int) -> None:
        """Insert an exact T bucket (rounded up to the q-block). The
        engine wires the scheduler's mixed_prefill_tokens + max decode
        batch here at startup, so the token budget IS the compile bucket
        and a full mixed iteration never rounds up to the next power of
        two."""
        qb = self.ragged_q_block
        t = max(qb, -(-int(t) // qb) * qb)
        if t not in self.ragged_buckets:
            self.ragged_buckets = tuple(sorted(set(self.ragged_buckets) | {t}))

    def _prep_ragged(
        self,
        tokens: List[int],
        positions: List[int],
        page_tables: List[List[int]],
        chunks: List[Dict[str, Any]],
    ):
        """Flatten one mixed plan — the decode batch (q_len=1 segments,
        first) + the packed prefill chunks — into a single [T_bucket]
        token axis with the kernel/model metadata from
        build_ragged_metadata. T is the TRUE token sum (no per-segment
        alignment padding): 1x512 + 3x32 chunks + 4 decode rows cost 612
        tokens, not 4x512 padded rows. Raises BucketOverflowError past
        the largest T bucket (the engine sheds chunks and retries)."""
        from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

        n_dec = len(positions)
        q_lens = [1] * n_dec + [len(c["tokens"]) for c in chunks]
        q_starts = list(positions) + [c["start"] for c in chunks]
        kv_lens = [p + 1 for p in positions] + [
            c["prior"] + len(c["tokens"]) for c in chunks
        ]
        rows = list(page_tables) + [c["table"] for c in chunks]
        t_real = sum(q_lens)
        t_bucket = _next_bucket(self.ragged_buckets, t_real)
        md = build_ragged_metadata(
            q_lens, q_starts, kv_lens, rows, t_bucket,
            q_block=self.ragged_q_block, max_pages=self.max_pages_per_seq,
        )
        seg_cap = md["seg_page_table"].shape[0]
        flat = np.zeros(t_bucket, np.int32)
        flat[:n_dec] = tokens
        off = n_dec
        for c in chunks:
            flat[off : off + len(c["tokens"])] = c["tokens"]
            off += len(c["tokens"])
        gather = np.zeros(seg_cap, np.int32)
        gather[: n_dec + len(chunks)] = md["last_index"]
        return (
            jnp.asarray(flat[None]),
            jnp.asarray(md["tok_positions"])[None],
            jnp.asarray(md["tok_page_table"]),
            jnp.asarray(md["tok_kv_lens"]),
            jnp.asarray(md["seg_page_table"]),
            jnp.asarray(md["seg_kv_lens"]),
            jnp.asarray(md["meta"]),
            jnp.asarray(gather),
            seg_cap,
            self._ragged_pages_live(md),
        )

    def _ragged_pages_live(self, md) -> int:
        """MixedOut.pages_live of a ragged dispatch, from its host
        metadata: the live (work unit, page) pairs one layer's kernel call
        walks (the mean over layers where sliding and global alternate)."""
        from dynamo_tpu.ops.ragged_paged_attention import ragged_live_pairs

        def pairs(window):
            return ragged_live_pairs(md["meta"], md["seg_kv_lens"], window,
                                     self.page_size, self.max_pages_per_seq)

        window = self.config.sliding_window
        return mean_over_layers(self.config, pairs(0),
                                pairs(window) if window else 0)

    def _decode_multi_with_prefills_ragged(
        self,
        n_steps: int,
        tokens: List[int],
        positions: List[int],
        page_tables: List[List[int]],
        sampling,
        step: int,
        chunks: List[Dict[str, Any]],
        masks: Optional[np.ndarray] = None,
        mask_fn=None,
        biases: Optional[np.ndarray] = None,
        guided_dev=None,  # device guided DFA plan (decode_multi): step 0
        # rides the ragged mask operand (`masks`), the fused tail rides
        # the in-XLA advance
        side: Optional[list] = None,
    ) -> MixedHandle:
        """Ragged mixed iteration, two dispatches with T-bucket-only and
        decode-bucket-only compile keys respectively:
        1. _ragged_step: flat forward over [T] (decode step 0 + all
           chunks) + last-token gather + sampling at SEG_CAP rows;
        2. steps 1..n-1 through the UNCHANGED _decode_loop, chained on
           the step-0 tokens (positions/step advanced by one, so row
           seeds and step indices match the legacy fused loop exactly).
        Returns mixed_dispatch's handle: both are enqueued, neither is
        read back."""
        n_dec = len(positions)
        with phase(STAGE):
            (ftok, fpos, tok_pt, tok_kvl, seg_pt, seg_kvl, meta, gather,
             seg_cap, pages_live) = self._prep_ragged(
                 tokens, positions, page_tables, chunks)
            row_seq, row_j = self._identity_rows(seg_cap)
            samp = self._device_sampling(sampling, seg_cap)
            step_dev = jnp.int32(step)
            seg_mask = self._seg_mask(masks, seg_cap)
            seg_bias = self._seg_bias(biases, seg_cap)
            skw = self._seg_state_kw(side, n_dec, chunks, seg_cap,
                                     ftok.shape[1])
        sampled, seg_logits, self.k_pool, self.v_pool, *routed = self._jit_ragged(
            self.params, ftok, fpos, tok_pt, tok_kvl, seg_pt, seg_kvl,
            meta, gather, self.k_pool, self.v_pool,
            samp, row_seq, row_j, step_dev, seg_mask, seg_bias, **skw,
        )
        routed = self._keep_state(routed)
        chunk_lens = [len(c["tokens"]) for c in chunks]
        self._note_routed(routed, 1, n_dec, chunk_lens)
        # (the program gathers one row a segment, `gather`, whether or not
        # the segment's chunk ends its prompt: one row of a fixed shape)
        self._note_sampled(n_dec, chunk_lens, len(chunks))
        B = _next_bucket(self.decode_buckets, n_dec)
        # both slices are eager programs enqueued behind the ragged step,
        # before the host blocks: the device never waits for them
        self._meet_row_slices(sampled)
        tok0 = sampled[:B]  # decode rows lead the segment order
        chunk_logits = seg_logits[n_dec : n_dec + len(chunks)]  # [N, V]
        if n_steps > 1:
            with phase(STAGE):
                # guided rows continue through the fused tail: tok0 was
                # sampled on the device and is not yet folded into the row
                # states, so the host callback advances each DFA copy by it
                # before masking inner step 0, and the device plan runs
                # with pending set
                if guided_dev is not None:
                    guided_dev = (guided_dev[0], guided_dev[1], True)
                mkw = self._guided_kw(mask_fn, guided_dev, B)
                bias_dev = (None if biases is None
                            else self._pad_rows(biases, B, 0.0, np.float32))
                tok0, packed_dev = self._stage_decode_rows(
                    tok0, [p + 1 for p in positions], page_tables, step + 1,
                    None, B)
                samp = self._device_sampling(sampling, B)
            # n_steps is the scheduler's fixed multi-step count, so
            # n_steps-1 adds exactly ONE decode_loop variant alongside the
            # legacy path's n_steps — bounded by design (ragged two-
            # dispatch split, docs/ragged_attention.md)
            rest, _, _, self.k_pool, self.v_pool, *routed = self._jit_decode_loop(  # dynlint: disable=DYN-J004
                n_steps - 1, -1, self.params, tok0, packed_dev,
                None, None, bias_dev, self.k_pool, self.v_pool,
                samp, None, **mkw, **self._state_kw(side, B),
            )
            routed = self._keep_state(routed)
            self._note_routed(routed, n_steps - 1, n_dec=n_dec, chained=True)
            self._note_sampled((n_steps - 1) * n_dec)
        else:
            rest = None
        return MixedHandle(tok0, rest, chunk_logits, len(chunks), True,
                           pages_live)

    def _meet_row_slices(self, sampled: jax.Array) -> None:
        """`sampled[:B]` is one eager program a (SEG_CAP, decode bucket)
        pair. The first dispatch at a T bucket runs it for every decode
        bucket that fits, so that none compiles later, under traffic:
        benchmark/serve.py's walk meets each T bucket but not each pair (a
        T bucket no larger than the decode bucket, 40 rows and a 20-token
        chunk at buckets 64 / 64, it cannot form from whole dummies)."""
        cap = sampled.shape[0]
        if cap in self._row_slices_met:
            return
        self._row_slices_met.add(cap)
        for b in self.decode_buckets:
            if b < cap:
                sampled[:b]

    def verify_spec(
        self,
        tokens: List[int],
        positions: List[int],
        page_tables: List[List[int]],
        drafts: List[List[int]],
        sampling,
        step: int,
        chunks: Sequence[Dict[str, Any]] = (),
        masks: Optional[Dict[int, np.ndarray]] = None,  # row index ->
        # [V] bool guided mask for that row's single verify position
        # (guided rows never draft, so exactly one position each)
        biases: Optional[Dict[int, np.ndarray]] = None,  # row index ->
        # [V] f32 logit-bias row, same draft-less single-position contract
    ) -> MixedOut:
        """One speculative-verify iteration through the SAME _jit_ragged
        program as the mixed path — zero new compile families or
        variants, by construction.

        Each speculating sequence contributes ONE segment of q_len
        len(draft)+1 to the flat [T] axis (its last real token followed
        by the drafted tokens); packed prefill chunks ride behind as
        usual. The gather array is content-only (not a shape), so
        instead of one last-token entry per segment it carries an entry
        for EVERY verify position — the kernel's causal masking already
        gives each flat token its correct prefix logits (chunked prefill
        depends on the same property), and sampling at SEG_CAP rows
        covers them all. Verify position j>0 folds j into the row seed
        so positions draw independent randomness (temperature-0 is
        argmax and unaffected — greedy byte-identity holds).

        KV for the fed draft tokens lands at positions
        computed_len..computed_len+K as a side effect; the engine
        commits a prefix of it simply by advancing computed_len per
        accepted token (stale suffix KV is overwritten or never read —
        kv_len masking), so rollback is free and page/hash lineage only
        ever covers committed tokens.

        Returns (rows, chunk_logits): rows[i] is the np token vector of
        length len(drafts[i])+1 sampled from the TARGET distribution at
        each verify position; chunk_logits are the packed chunks' last-
        token logits, device-resident, same contract as the mixed path.
        Raises BucketOverflowError when the plan exceeds the T bucket or
        the gather capacity (defensive — the scheduler budgets drafted
        tokens against both)."""
        self._no_side("speculative verify (no rollback)")
        from dynamo_tpu.ops.ragged_paged_attention import (
            RAGGED_MAX_SEGS, build_ragged_metadata, ragged_seg_cap,
        )

        with phase(STAGE):
            chunks = list(chunks)
            n_rows = len(positions)
            row_lens = [len(d) + 1 for d in drafts]
            q_lens = row_lens + [len(c["tokens"]) for c in chunks]
            q_starts = list(positions) + [c["start"] for c in chunks]
            kv_lens = [p + ln for p, ln in zip(positions, row_lens)] + [
                c["prior"] + len(c["tokens"]) for c in chunks
            ]
            rows = list(page_tables) + [c["table"] for c in chunks]
            n_seg = len(q_lens)
            t_real = sum(q_lens)
            t_bucket = _next_bucket(self.ragged_buckets, t_real)
            seg_cap = ragged_seg_cap(t_bucket)
            entries = sum(row_lens) + len(chunks)
            if n_seg > RAGGED_MAX_SEGS or entries > seg_cap:
                raise BucketOverflowError(max(n_seg, entries), (seg_cap,))
            md = build_ragged_metadata(
                q_lens, q_starts, kv_lens, rows, t_bucket,
                q_block=self.ragged_q_block, max_pages=self.max_pages_per_seq,
            )
            flat = np.zeros(t_bucket, np.int32)
            off = 0
            for tok, d in zip(tokens, drafts):
                flat[off] = tok
                flat[off + 1 : off + 1 + len(d)] = d
                off += len(d) + 1
            for c in chunks:
                flat[off : off + len(c["tokens"])] = c["tokens"]
                off += len(c["tokens"])
            cu = md["cu_q_lens"]
            gather = np.zeros(seg_cap, np.int32)
            w = 0
            for i in range(n_rows):
                gather[w : w + row_lens[i]] = np.arange(cu[i], cu[i + 1])
                w += row_lens[i]
            chunk_entry0 = w
            for s in range(n_rows, n_seg):
                gather[w] = cu[s + 1] - 1
                w += 1
            # per-entry sampling expansion happens IN-XLA (_ragged_step's
            # row_seq/row_j gather+seed-fold): the staged base is the per-
            # SEQUENCE params — stable across verify iterations, so
            # _device_sampling cache-hits instead of rebuilding + re-staging
            # a fresh per-entry expansion every dispatch. Chunk (and pad)
            # entries point at a padding base row: greedy, seed 0 — exactly
            # the params the host expansion gave them.
            row_seq = np.zeros(seg_cap, np.int32)
            row_j = np.zeros(seg_cap, np.int32)
            w2 = 0
            for i in range(n_rows):
                row_seq[w2 : w2 + row_lens[i]] = i
                row_j[w2 : w2 + row_lens[i]] = np.arange(row_lens[i])
                w2 += row_lens[i]
            # chunk entries (and trailing pad rows) sample with padding
            # params; n_rows < seg_cap whenever chunk entries exist (entries
            # = sum(row_lens) + len(chunks) <= seg_cap and row_lens >= 1)
            row_seq[w2:] = min(n_rows, seg_cap - 1)
            row_masks = None
            if masks:
                # guided rows ride the verify dispatch as draft-less q_len=1
                # segments (per-sequence speculation pause): mask only their
                # verify position, every other entry stays all-allowed
                row_masks = np.ones(
                    (sum(row_lens), self.config.vocab_size), bool
                )
                offs = np.concatenate([[0], np.cumsum(row_lens)])
                for i, m in masks.items():
                    row_masks[offs[i]] = m
            row_biases = None
            if biases:
                row_biases = np.zeros(
                    (sum(row_lens), self.config.vocab_size), np.float32
                )
                offs = np.concatenate([[0], np.cumsum(row_lens)])
                for i, b in biases.items():
                    row_biases[offs[i]] = b
            with self._allow("verify_staging"):
                staged = (
                    jnp.asarray(flat[None]),
                    jnp.asarray(md["tok_positions"])[None],
                    jnp.asarray(md["tok_page_table"]),
                    jnp.asarray(md["tok_kv_lens"]),
                    jnp.asarray(md["seg_page_table"]),
                    jnp.asarray(md["seg_kv_lens"]),
                    jnp.asarray(md["meta"]),
                    jnp.asarray(gather),
                )
                samp = self._device_sampling(sampling, seg_cap)
                row_seq_d = jnp.asarray(row_seq)
                row_j_d = jnp.asarray(row_j)
                step_d = jnp.int32(step)
                seg_mask = self._seg_mask(row_masks, seg_cap)
                seg_bias = self._seg_bias(row_biases, seg_cap)
        sampled, seg_logits, self.k_pool, self.v_pool, *routed = self._jit_ragged(
            self.params, *staged,
            self.k_pool, self.v_pool,
            samp, row_seq_d, row_j_d, step_d, seg_mask, seg_bias,
        )
        # the counters hold for a verify dispatch too; its picks have no
        # reader (a worker that speculates refuses `routed_experts`)
        self._note_routed([{"load": r["load"]} for r in routed], 1)
        with self._allow("token_readback"), phase(READBACK):
            sampled_h = np.asarray(self._readback(sampled))  # one bulk sync
        out: List[np.ndarray] = []
        w = 0
        for ln in row_lens:
            out.append(sampled_h[w : w + ln])
            w += ln
        if chunks:
            # slicing with host ints stages them as dynamic-slice starts;
            # that is dispatch staging, same budget as the operand block
            with self._allow("verify_staging"):
                chunk_logits = seg_logits[
                    chunk_entry0 : chunk_entry0 + len(chunks)
                ]
        else:
            chunk_logits = []  # no slice at all: a zero-length take would
            # still stage its bounds and trip the strict transfer guard
        return MixedOut(out, chunk_logits, True, self._ragged_pages_live(md))

    # -- device n-gram draft ring ------------------------------------------
    def ensure_draft_ring(
        self, slots: int, k: int, window: int = DRAFT_RING_WINDOW,
    ) -> int:
        """Allocate the device draft ring ([slots, window] history + per-
        slot lengths) and WARM the draft jit — compile happens here, at
        engine-enable time, never inside the warm loop (the sanitizer's
        recompile tripwire freezes family variants after warmup).
        Returns the per-iteration delta capacity D: the engine resets a
        slot (host-mirror rewrite + cold restage) when a sequence
        commits more than D tokens between proposals."""
        D = max(16, int(k) + 2)
        shape = (int(slots), int(window), D)
        if self._draft_ring_shape == shape and self._draft_ring is not None:
            return D
        hist = np.full((slots, window), -1, np.int32)
        lens = np.zeros(slots, np.int32)
        self._draft_ring_host = (hist, lens)
        with self._allow("spec_staging"):
            self._draft_ring = (jnp.asarray(hist), jnp.asarray(lens))
            zt = jnp.full((slots, D), -1, jnp.int32)
            zn = jnp.zeros(slots, jnp.int32)
        h, l = self._draft_ring
        h, l, _, _ = self._jit_draft_ring(h, l, zt, zn, int(k))
        self._draft_ring = (h, l)
        self._draft_ring_dirty = False
        self._draft_ring_shape = shape
        return D

    def draft_ring_reset(self, slot: int, tokens: Sequence[int]) -> None:
        """Rewrite one slot's history (admission, slot reuse, or a delta
        too large for the append bucket) in the HOST mirror; the next
        draft_step restages the whole ring — cold-path by construction,
        the warm loop only ever appends deltas."""
        hist, lens = self._draft_ring_host
        W = hist.shape[1]
        tail = list(tokens)[-W:]
        hist[slot] = -1
        hist[slot, : len(tail)] = tail
        lens[slot] = len(tail)
        self._draft_ring_dirty = True

    def draft_step(
        self, updates: Sequence[Tuple[int, Sequence[int]]], k: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One fused device draft step: append each (slot, delta) to the
        ring and propose k continuation tokens per slot (see
        _draft_ring_step). Stages only the [SLOTS, D] delta; the
        proposal readback is the loop's single draft-side host touch
        (sanitizer label draft_readback). Returns (drafts [SLOTS, k],
        n_prop [SLOTS]) host arrays."""
        slots, W, D = self._draft_ring_shape
        hist_h, lens_h = self._draft_ring_host
        upd_tok = np.full((slots, D), -1, np.int32)
        upd_n = np.zeros(slots, np.int32)
        for slot, delta in updates:
            d = list(delta)
            assert len(d) <= D, "draft delta exceeds ring bucket (reset)"
            upd_tok[slot, : len(d)] = d
            upd_n[slot] = len(d)
            # mirror the append so a later reset/restage stays coherent
            n = len(d)
            if lens_h[slot] + n > W:
                over = lens_h[slot] + n - W
                hist_h[slot, : W - over] = hist_h[slot, over:]
                lens_h[slot] -= over
            hist_h[slot, lens_h[slot] : lens_h[slot] + n] = d
            lens_h[slot] += n
        with self._allow("spec_staging"):
            if self._draft_ring_dirty:
                # cold restage after slot resets: the device ring is
                # rebuilt from the mirror (deltas above are already in
                # the mirror, so stage ZERO updates this round)
                self._draft_ring = (jnp.asarray(hist_h), jnp.asarray(lens_h))
                self._draft_ring_dirty = False
                upd_tok[:] = -1
                upd_n[:] = 0
            ut = jnp.asarray(upd_tok)
            un = jnp.asarray(upd_n)
        h, l = self._draft_ring
        h, l, drafts, n_prop = self._jit_draft_ring(h, l, ut, un, int(k))
        self._draft_ring = (h, l)
        with self._allow("draft_readback"):
            d_h, n_h = jax.device_get((drafts, n_prop))
        return np.asarray(d_h), np.asarray(n_h)

    def compile_families(self) -> Dict[str, _CompiledFamily]:
        return self._families

    def compile_stats(self) -> Dict[str, Dict[str, Any]]:
        """Per step-function family, and `other` for every program no
        family saw: compiled-variant count, cumulative compile seconds,
        call count. Ships as worker gauges (worker_common) and is what
        the benchmark subtracts over its window, so a compile after
        warm-up is a count, not a guess."""
        out = {name: fam.stats() for name, fam in self._families.items()}
        out["other"] = self._other.stats()
        return out

    def name_step_thread(self) -> None:
        """The calling thread serves this runner from here on (the
        engine's step thread, once, at the start of its loop): a program
        compiled on it outside any family counts in `other`."""
        _compile_tls.other = self._other

    def _device_sampling(self, sampling, B: int) -> SamplingParams:
        """Device-resident cache of padded sampling params. Batches resend
        identical sampling lists every dispatch; materializing them fresh
        costs several host→device transfers per dispatch.
        SamplingParams instances pass through (assumed already
        on device and bucket-sized by the caller)."""
        if isinstance(sampling, SamplingParams):
            return _pad_sampling(sampling, B)
        n = len(sampling["temperature"])
        rep = list(sampling.get("rep") or [1.0] * n)
        freq = list(sampling.get("freq") or [0.0] * n)
        presence = list(sampling.get("presence") or [0.0] * n)
        key = (
            B,
            tuple(sampling["temperature"]),
            tuple(sampling["top_k"]),
            tuple(sampling["top_p"]),
            tuple(sampling["seeds"]),
            tuple(rep), tuple(freq), tuple(presence),
        )
        hit = self._sampling_cache.get(key)
        if hit is None:
            pad = B - n
            hit = SamplingParams.make(
                temperature=list(sampling["temperature"]) + [0.0] * pad,
                top_k=list(sampling["top_k"]) + [0] * pad,
                top_p=list(sampling["top_p"]) + [1.0] * pad,
                seeds=list(sampling["seeds"]) + [0] * pad,
                rep_penalty=rep + [1.0] * pad,
                freq_penalty=freq + [0.0] * pad,
                presence_penalty=presence + [0.0] * pad,
            )
            if len(self._sampling_cache) >= 512:
                self._sampling_cache.clear()
            self._sampling_cache[key] = hit
        return hit

    def reload_params(self, path: str) -> None:
        """Swap the serving weights from an orbax snapshot IN PLACE (the
        RL weight-update path, reference lib/rl role: policy weights
        refresh between rollouts without restarting the worker). The
        jitted step functions take params as an argument, so the swap is
        just a device_put with the same shardings — no recompilation."""
        from dynamo_tpu.engine.weights import load_orbax

        new = load_orbax(path)
        new = jax.tree.map(jnp.asarray, new)
        if self.quantize in ("int8", "fp8"):
            # the jitted step fns were traced against the QUANTIZED tree
            # (scale leaves, int8 dtypes) — a raw tree would retrace/crash
            from dynamo_tpu.models.quant import quantize_params

            new = quantize_params(new, mode=self.quantize, donate=True)
        self.params = jax.device_put(
            new, self.policy.params_sharding(new)
        )

    @property
    def has_draft(self) -> bool:
        return self.draft_config is not None

    # -- multi-LoRA registry ------------------------------------------------
    def adapter_names(self) -> List[str]:
        return list(self._adapter_slots)

    def register_adapter(self, name: str, factors: Dict[str, Any]) -> int:
        """Install an adapter's factors into the next free slot; returns
        the slot index sequences reference. factors: models/lora.py layout
        ({t}_a [L,in,r], {t}_b [L,r,out], scaling folded into B)."""
        from dynamo_tpu.models import lora as lora_mod

        if self.lora is None:
            raise RuntimeError("runner built without lora_slots")
        if name in self._adapter_slots:
            return self._adapter_slots[name]
        slot = len(self._adapter_slots) + 1  # 0 is the base slot
        n_slots = next(iter(self.lora["layers"].values())).shape[1]
        if slot >= n_slots:
            raise RuntimeError(f"all {n_slots - 1} LoRA slots in use")
        self.lora = lora_mod.set_adapter_slot(self.lora, slot, factors)
        self._adapter_slots[name] = slot
        log.info("registered LoRA adapter %r in slot %d", name, slot)
        return slot

    def adapter_slot(self, name: Optional[str]) -> int:
        if not name:
            return 0
        return self._adapter_slots[name]

    def spec_decode_multi(
        self,
        n_rounds: int,
        tokens: List[int],
        positions: List[int],
        page_tables: List[List[int]],
        sampling,
        step: int,
        gamma: Optional[int] = None,
        adapters: Optional[List[int]] = None,
    ):
        """n_rounds fused speculative rounds (one host sync). Returns
        (tokens [B_bucket, R, gamma+1], counts [B_bucket, R]); row i's
        round r contributes counts[i, r] valid tokens. Page tables must
        cover positions[i] + n_rounds*(gamma+1) slots. `gamma` overrides
        the configured draft length (the engine shrinks it near token
        budgets so the draft pool never gaps)."""
        gamma = self.spec_gamma if gamma is None else gamma
        n = len(tokens)
        B = _next_bucket(self.decode_buckets, n)
        tok = np.zeros(B, np.int32)
        tok[:n] = tokens
        pos = np.full(B, -1, np.int32)
        pos[:n] = positions
        pt = self._pad_page_table(page_tables, B)

        with self._allow("spec_staging"):
            tok_d, pos_d, pt_d = jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(pt)
            samp = self._device_sampling(sampling, B)
            step_d = jnp.int32(step)
            adapt_d = self._adapter_array(adapters, B)
        toks, counts, self.k_pool, self.v_pool, self.draft_k_pool, self.draft_v_pool = (
            self._jit_spec(
                gamma, n_rounds, self.params, self.draft_params,
                tok_d, pos_d,
                self.k_pool, self.v_pool, self.draft_k_pool, self.draft_v_pool,
                pt_d, samp, step_d, self.lora, adapt_d,
            )
        )
        with self._allow("token_readback"), phase(READBACK):
            toks_h, counts_h = jax.device_get((toks, counts))
        return np.asarray(toks_h), np.asarray(counts_h)

    def draft_prefill(
        self,
        tokens: List[int],
        start_pos: int,
        page_table_row: List[int],
        prior_len: int,
        mm: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Prefill the DRAFT model's KV pools for a chunk (same page
        table as the target). Logits are discarded — only the KV matters
        for later proposals. mm is injected only when the draft's hidden
        size matches (otherwise proposals just degrade, never correctness)."""
        tok, pos, pt, kv_lens, n = self._prep_prefill(tokens, start_pos, page_table_row, prior_len)
        mm_embeds = mm_mask = None
        if mm is not None and self.draft_config.dim == self.config.dim:
            mm_embeds, mm_mask = self._mm_arrays(mm, tok.shape[1])
        _, self.draft_k_pool, self.draft_v_pool = self._jit_draft_forward(
            self.draft_params, tok, pos, self.draft_k_pool, self.draft_v_pool,
            pt, kv_lens, jnp.int32(n - 1), attn_impl=self.attn_impl,
            mesh=self._fwd_mesh, mm_embeds=mm_embeds, mm_mask=mm_mask,
        )

    def sample_one(self, logits: jax.Array, sampling, step: int,
                   mask: Optional[np.ndarray] = None,
                   bias: Optional[np.ndarray] = None) -> int:
        out = self._jit_sample(
            logits[None, :], _as_sampling(sampling), jnp.int32(step),
            mask=jnp.asarray(mask[None, :]) if mask is not None else None,
            bias=jnp.asarray(bias[None, :]) if bias is not None else None,
        )
        return int(self._readback(out)[0])

    def sample_one_ex(
        self,
        logits: jax.Array,
        sampling,
        step: int,
        history: Optional[List[int]] = None,
        n_logprobs: int = -1,
        mask: Optional[np.ndarray] = None,
        bias: Optional[np.ndarray] = None,
    ):
        """sample_one with penalties (over `history` token ids) and/or a
        logprob report. Returns (token, lp) where lp is None or
        (tok_lp, top_ids list, top_lps list) for the sampled position."""
        if not hasattr(self, "_jit_sample_one_ex"):
            self._jit_sample_one_ex = jax.jit(
                _named(partial(_sample_one_ex, self.config.vocab_size)),
                static_argnums=(0,),
            )
        hist = None
        if history is not None:
            H = -(-max(1, len(history)) // 128) * 128
            h = np.full(H, self.config.vocab_size, np.int32)
            h[: len(history)] = history
            hist = jnp.asarray(h)
        out = self._jit_sample_one_ex(
            n_logprobs, logits, hist, _as_sampling(sampling), jnp.int32(step),
            jnp.asarray(mask[None, :]) if mask is not None else None,
            jnp.asarray(bias[None, :]) if bias is not None else None,
        )
        out = self._readback(out)
        tok = int(out[0][0])
        if n_logprobs < 0:
            return tok, None
        tok_lp, ids, vals = out[1], out[2], out[3]
        return tok, (
            float(tok_lp[0]),
            [int(i) for i in ids[0]],
            [float(v) for v in vals[0]],
        )

    def _pad_page_table(self, rows: List[List[int]], B: Optional[int] = None) -> np.ndarray:
        B = B or len(rows)
        pt = np.zeros((B, self.max_pages_per_seq), np.int32)
        for i, row in enumerate(rows):
            pt[i, : len(row)] = row
        return pt

    def embed(self, token_lists: List[List[int]]) -> np.ndarray:
        """Batched embedding forward → [n, E] float32 (L2-normalized)."""
        if not hasattr(self, "_jit_encode"):
            self._jit_encode = jax.jit(partial(llama.encode, self.config))
        n = len(token_lists)
        B = _next_bucket(self.decode_buckets, n)
        S = _next_bucket(self.prefill_buckets, max(len(t) for t in token_lists))
        toks = np.zeros((B, S), np.int32)
        lens = np.zeros(B, np.int32)
        for i, t in enumerate(token_lists):
            toks[i, : len(t)] = t
            lens[i] = len(t)
        out = self._jit_encode(self.params, jnp.asarray(toks), jnp.asarray(lens))
        return np.asarray(jax.device_get(out))[:n]

    # -- disagg KV transfer: device-resident path (colocated P/D) ----------
    # Transfer/offload boundary contract: pages always cross it DENSE (the
    # pool dtype, normally bf16) regardless of kv_quantize — host tiers,
    # the disagg wire format and peer workers see one layout, so quantized
    # and unquantized workers interoperate. Export dequantizes, import
    # re-quantizes (per-vector scales are recomputed; error is one extra
    # rounding, bounded by the int8 step).
    def _dense_pages(self, pool, idx):
        # token-major pools: page axis 1 for every representation
        if isinstance(pool, dict):
            from dynamo_tpu.models.quant import kv_pool_dequantize

            sel = jax.tree.map(lambda a: a[:, idx], pool)
            return kv_pool_dequantize(sel, dtype=self.dtype)
        if self._kv_copy_kernel:
            from dynamo_tpu.ops.block_copy import (
                gather_pages,
                gather_pages_sharded,
            )

            if self._kv_copy_sharded:
                return gather_pages_sharded(
                    pool, idx, self.mesh, interpret=self._kv_copy_interpret
                )
            return gather_pages(pool, idx, interpret=self._kv_copy_interpret)
        return pool[:, idx]

    def _store_pages(self, pool, idx, dense):
        if isinstance(pool, dict):
            from dynamo_tpu.models.quant import kv_pool_quantize

            d = kv_pool_quantize(dense)
            return jax.tree.map(lambda a, u: a.at[:, idx].set(u), pool, d)
        if self._kv_copy_kernel:
            from dynamo_tpu.ops.block_copy import (
                scatter_pages,
                scatter_pages_sharded,
            )

            if self._kv_copy_sharded:
                return scatter_pages_sharded(
                    pool, idx, dense.astype(pool.dtype), self.mesh,
                    interpret=self._kv_copy_interpret,
                )
            return scatter_pages(pool, idx, dense.astype(pool.dtype),
                                 interpret=self._kv_copy_interpret)
        return pool.at[:, idx].set(dense)

    def export_pages_device(self, pages: List[int]):
        """Gather whole KV pages into fresh device buffers (no host copy).
        The gather materializes a new array, so the source pool can keep
        being donated by its engine's step loop afterwards."""
        self._no_side("KV export by pages (export_pages_device)")
        idx = jnp.asarray(np.asarray(pages, np.int32))
        return self._dense_pages(self.k_pool, idx), self._dense_pages(self.v_pool, idx)

    def import_pages_device(self, target_pages: List[int], offset: int, k, v) -> None:
        """Scatter device-staged pages into this pool's slots (the TPU
        analog of the reference's NIXL device-to-device transfer; the
        host-staged path below is the DCN fallback)."""
        self._no_side("KV import by pages (import_pages_device)")
        idx = jnp.asarray(np.asarray(target_pages, np.int32))
        n = len(target_pages)
        self.k_pool = self._store_pages(self.k_pool, idx, k[:, offset : offset + n])
        self.v_pool = self._store_pages(self.v_pool, idx, v[:, offset : offset + n])

    def copy_pages(self, src: int, dst: int) -> None:
        """Fork-on-branch CoW: duplicate one page's KV into a fresh slot
        so a branch can diverge without clobbering the sibling's partial
        tail page. One jitted donated program (src/dst are traced
        scalars — a single compile serves every fork); quantized dict
        pools copy raw payload+scales, no dequant round-trip. Draft-model
        pools mirror the page table, so a speculating runner copies those
        too."""
        if not hasattr(self, "_jit_copy_page"):
            def _cp(kp, vp, s, d):
                def one(p):
                    if isinstance(p, dict):
                        return jax.tree.map(
                            lambda a: a.at[:, d].set(a[:, s]), p
                        )
                    return p.at[:, d].set(p[:, s])
                return one(kp), one(vp)
            self._jit_copy_page = jax.jit(
                _named(_cp, "copy_page"), donate_argnums=(0, 1))
        self.k_pool, self.v_pool = self._jit_copy_page(
            self.k_pool, self.v_pool, src, dst
        )
        if getattr(self, "draft_k_pool", None) is not None:
            self.draft_k_pool, self.draft_v_pool = self._jit_copy_page(
                self.draft_k_pool, self.draft_v_pool, src, dst
            )

    # -- disagg KV transfer (host-staged DCN path, SURVEY.md §2.11) ---------
    def export_pages(self, pages: List[int]) -> Dict[str, Any]:
        """Device→host read of whole KV pages for P→D transfer. Layout on
        the wire: [L, n_pages, PS, Hk, D] per pool, raw bytes. On a
        multi-host mesh the gather runs jitted with a replicated output
        sharding (an all-gather over ICI) so every process holds the full
        pages and the host read is local."""
        self._no_side("KV export by pages (export_pages: disaggregation, tier demotion)")
        idx = jnp.asarray(np.asarray(pages, np.int32))
        if self.multihost:
            if not hasattr(self, "_jit_export_repl"):
                from jax.sharding import NamedSharding

                from dynamo_tpu.parallel.mesh import SPEC_REPLICATED

                repl = NamedSharding(self.mesh, SPEC_REPLICATED)
                self._jit_export_repl = jax.jit(
                    lambda kp, vp, i: (
                        self._dense_pages(kp, i), self._dense_pages(vp, i)
                    ),
                    out_shardings=(repl, repl),
                )
            k_d, v_d = self._jit_export_repl(self.k_pool, self.v_pool, idx)
            k = np.asarray(jax.device_get(k_d))
            v = np.asarray(jax.device_get(v_d))
            return kv_arrays_to_payload(k, v, tp=self.mesh_config.model)
        k = np.asarray(jax.device_get(self._dense_pages(self.k_pool, idx)))
        v = np.asarray(jax.device_get(self._dense_pages(self.v_pool, idx)))
        return kv_arrays_to_payload(k, v, tp=self.mesh_config.model)

    @property
    def kv_page_shape(self) -> Tuple[int, int, int, int]:
        """(L, PS, Hk, D) page geometry of this runner's pools — the local
        side of the cross-TP layout handshake. Derived from the ACTUAL
        k-pool shape, so MLA's latent pool (Hk=1, D=d_c+d_rh) advertises
        its real geometry instead of a phantom full-head one."""
        k = self.k_pool["q"] if isinstance(self.k_pool, dict) else self.k_pool
        L, _, PS, Hk, D = k.shape
        return (L, PS, Hk, D)

    @property
    def kv_wire_dtype(self) -> str:
        """Dtype name pages cross the transfer boundary with (the DENSE
        pool dtype — quantized pools dequantize at export)."""
        return str(np.dtype(self.dtype))

    def _store_pages_layers(self, pool, idx, dense, lo: int):
        """Layer-group scatter: write dense [Lg, n, PS, Hk, D] pages into
        pool layers [lo, lo+Lg) at slots idx — the per-group unit of the
        streamed onboard. Quantized pools fold the group on device; the
        block-copy kernel path has a dedicated layer-sliced variant."""
        Lg = int(dense.shape[0])
        if isinstance(pool, dict):
            from dynamo_tpu.models.quant import kv_pool_quantize

            d = kv_pool_quantize(dense)
            return jax.tree.map(
                lambda a, u: a.at[lo : lo + Lg, idx].set(u), pool, d)
        if self._kv_copy_kernel and not self._kv_copy_sharded:
            from dynamo_tpu.ops.block_copy import scatter_pages_layers

            return scatter_pages_layers(
                pool, idx, dense.astype(pool.dtype),
                jnp.asarray([lo], jnp.int32),
                interpret=self._kv_copy_interpret,
            )
        return pool.at[lo : lo + Lg, idx].set(dense.astype(pool.dtype))

    def import_pages(self, target_pages: List[int], offset: int,
                     payload: Dict[str, Any], layer_groups: int = 1) -> None:
        """Host→device write of transferred pages into this pool's page
        slots. `offset` = first payload page to use (earlier pages were
        satisfied by the local prefix cache). Validates the payload's layout
        metadata against the local pool geometry (KvWireLayoutMismatch on
        any divergence); a cross-TP exporter is fine — the dense wire pages
        reshard into this mesh's pool sharding on the scatter below.

        layer_groups > 1 streams the import in contiguous layer slabs
        (FlowKV-style): each group's host staging + device scatter issues
        independently, so the scheduler can dispatch prefill as soon as
        the shallow layers land while deeper groups are still in flight.
        Final pool contents are identical to a whole-sequence import."""
        self._no_side("KV import by pages (import_pages: disaggregation, tier onboarding, remote pulls)")
        if payload.get("quant") == "int8_ts":
            return self._import_pages_quant(
                target_pages, offset, payload, layer_groups)
        arrays = kv_payload_to_arrays(payload, self.kv_page_shape, self.kv_wire_dtype)
        if arrays is None:
            return
        k, v = arrays
        sel = slice(offset, offset + len(target_pages))
        idx = jnp.asarray(np.asarray(target_pages, np.int32))
        if layer_groups <= 1:
            self.k_pool = self._store_pages(self.k_pool, idx, jnp.asarray(k[:, sel]))
            self.v_pool = self._store_pages(self.v_pool, idx, jnp.asarray(v[:, sel]))
            return
        L = self.kv_page_shape[0]
        for lo, hi in layer_group_bounds(L, layer_groups):
            self.k_pool = self._store_pages_layers(
                self.k_pool, idx, jnp.asarray(k[lo:hi, sel]), lo)
            self.v_pool = self._store_pages_layers(
                self.v_pool, idx, jnp.asarray(v[lo:hi, sel]), lo)

    def _import_pages_quant(self, target_pages: List[int], offset: int,
                            payload: Dict[str, Any],
                            layer_groups: int = 1) -> None:
        """Native int8+scales import (kv_quant_arrays_to_payload): tier
        blocks already in the device fold land in quantized pools with NO
        dequantize/requantize round trip — zero extra rounding on the
        promotion path. Dense-pool runners dequantize instead (same
        result as the dense wire, one rounding)."""
        if payload.get("layout") != KV_WIRE_LAYOUT_VERSION:
            raise KvWireLayoutMismatch(
                f"kv wire layout {payload.get('layout')} != {KV_WIRE_LAYOUT_VERSION}"
            )
        kq, ks = np.asarray(payload["kq"]), np.asarray(payload["ks"])
        vq, vs = np.asarray(payload["vq"]), np.asarray(payload["vs"])
        L, PS, Hk, D = self.kv_page_shape
        got = (kq.shape[0],) + tuple(kq.shape[2:])
        if got != (L, PS, Hk, D):
            raise KvWireLayoutMismatch(
                f"quant page geometry {got} != local (L={L}, PS={PS}, "
                f"Hk={Hk}, D={D})"
            )
        if not isinstance(self.k_pool, dict):
            from dynamo_tpu.kvbm.quant import dequantize_block

            dt = np.dtype(self.dtype)
            dense = {
                "data": True,
                "k": dequantize_block({"q": kq, "s": ks}, dt).tobytes(),
                "v": dequantize_block({"q": vq, "s": vs}, dt).tobytes(),
                "shape": list(kq.shape), "dtype": str(dt),
                "v_shape": list(vq.shape),
                "n_pages": int(kq.shape[1]),
                "layout": KV_WIRE_LAYOUT_VERSION,
                "page_size": PS, "kv_heads": Hk, "head_dim": D, "layers": L,
                "tp": 1,
            }
            return self.import_pages(target_pages, offset, dense, layer_groups)
        sel = slice(offset, offset + len(target_pages))
        idx = jnp.asarray(np.asarray(target_pages, np.int32))
        for lo, hi in layer_group_bounds(L, max(1, layer_groups)):
            for name, q, s in (("k_pool", kq, ks), ("v_pool", vq, vs)):
                pool = getattr(self, name)
                setattr(self, name, {
                    "q": pool["q"].at[lo:hi, idx].set(jnp.asarray(q[lo:hi, sel])),
                    "s": pool["s"].at[lo:hi, idx].set(jnp.asarray(s[lo:hi, sel])),
                })

    def pools_deleted(self) -> bool:
        """True when the KV pool buffers were consumed by donation into a
        step that then FAILED — the arrays exist as tracers but their
        device memory is gone, and every later step raises."""
        try:
            return any(
                getattr(a, "is_deleted", lambda: False)()
                for a in jax.tree.leaves(
                    (self.k_pool, self.v_pool, self.state))
            )
        except Exception:
            return True

    def reset_kv_pools(self) -> None:
        """Rebuild zeroed KV pools with the original shapes/sharding (the
        recovery path after pools_deleted()). All cached KV content is
        lost — the caller must also reset its PagePool bookkeeping."""
        self.k_pool, self.v_pool = self._new_kv_pools(self.config)
        if self.draft_config is not None:
            self.draft_k_pool, self.draft_v_pool = self._new_kv_pools(
                self.draft_config
            )
        if self.state is not None:  # a failed step consumed it too
            self.state = None
            self.ensure_side_cache(self.side_units)

    def _new_kv_pools(self, config: ModelConfig):
        """Zeroed (k, v) pools for `config`, allocated DIRECTLY under their
        mesh sharding. Built on the default device and then device_put,
        both full pools sit on chip 0 beside the weights while the shards
        are cut: at llama-3.2-3b / 768 pages that is 5.6 GB + 6.4 GB + the
        shards, which does not fit a 16 GB chip although every shard does
        (the TP=4 worker died here on its first chip run)."""
        args = (config, self.num_pages, self.page_size, self.dtype)
        k_shape, _ = jax.eval_shape(
            partial(llama.make_kv_pool, *args, kv_quantize=self.kv_quantize)
        )
        sh = self.policy.kv_pool_sharding_tree(k_shape)
        # jit of the module-level function with static args: every runner
        # of the same shape reuses one compiled allocator
        return jax.jit(
            llama.make_kv_pool, static_argnums=(0, 1, 2, 3),
            static_argnames=("kv_quantize",), out_shardings=(sh, sh),
        )(*args, kv_quantize=self.kv_quantize)

    # -- memory ------------------------------------------------------------
    def kv_pool_bytes(self) -> int:
        leaves = jax.tree.leaves((self.k_pool, self.v_pool))
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)


def _sample_one_ex(vocab_size: int, n_logprobs: int, logits, hist, sampling,
                   step, mask=None, bias=None):
    """Single-position sampling with optional penalties + logprob report
    (the prefill-first-token path of the decode loop's extras). `hist`
    here is the PROMPT only — nothing has been generated yet, so the
    output-only frequency/presence counts are zero and only repetition
    (prompt+generated semantics) can bite."""
    from dynamo_tpu.engine.sampling import apply_penalties, top_logprobs

    raw = logits[None, :]
    l = raw
    if hist is not None:
        counts = jnp.zeros((1, vocab_size), jnp.float32).at[0, hist].add(
            1.0, mode="drop"
        )
        l = apply_penalties(raw, counts, jnp.zeros_like(counts), sampling)
    s = sample(l, sampling, step, mask=mask, bias=bias)
    if n_logprobs >= 0:
        return (s,) + top_logprobs(raw, s, n_logprobs)
    return (s,)


def _as_sampling(s) -> SamplingParams:
    if isinstance(s, SamplingParams):
        return s
    return SamplingParams.make(
        temperature=s["temperature"], top_k=s["top_k"], top_p=s["top_p"],
        seeds=s["seeds"], rep_penalty=s.get("rep"),
        freq_penalty=s.get("freq"), presence_penalty=s.get("presence"),
    )


def _pad_sampling(s: SamplingParams, B: int) -> SamplingParams:
    n = s.temperature.shape[0]
    if n == B:
        return s
    pad = B - n
    return SamplingParams(
        temperature=jnp.pad(s.temperature, (0, pad)),
        top_k=jnp.pad(s.top_k, (0, pad)),
        top_p=jnp.pad(s.top_p, (0, pad), constant_values=1.0),
        key=jnp.pad(s.key, ((0, pad), (0, 0))),
        rep_penalty=jnp.pad(s.rep_penalty, (0, pad), constant_values=1.0),
        freq_penalty=jnp.pad(s.freq_penalty, (0, pad)),
        presence_penalty=jnp.pad(s.presence_penalty, (0, pad)),
    )
