"""Plain reference of the `mistral4` decoder block (Mistral-Small-4-119B-2603)
as ONE CHIP of an expert-parallel deployment holds it: latent attention with a
compressed query and a position-dependent query scale, and a layer of routed
experts of which this chip holds a share, plus one shared expert. float32
jax.numpy, no cache, no kernels, no batching, one layer at a time from the
served tree, matmuls at `highest` precision.

Written from the published `config.json` (every layer alike:
`first_k_dense_replace` 0) and the DeepSeek-V3 family's published modelling
code, whose layer this is under Mistral's keys, in the non-absorbed form: the
latent is kept nowhere, every head's keys and values are rebuilt from it for
the whole sequence. What it shares with `mla_moe_decoder.py` (RMSNorm, the
yarn table, rotary embedding, SwiGLU, the router's selection scores, the mixing
weights, `need_of`, the head) it imports from that file, which no PR of this
kind edits.

Attention, per layer: x = RMSNorm(h). Queries `RMSNorm(x @ wq_lat) @ wq_up`
(or `x @ wq` without a compressed query), per head a content part
(`qk_nope_head_dim`) and a rotary part (`qk_rope_head_dim`); the whole query of
position p is multiplied by `1 + attn_qscale_beta * ln(1 + floor(p /
attn_qscale_orig))` (`llama_4_scaling_beta` over the yarn
`original_max_position_embeddings`; 1 below it). `x @ wkv_a` splits into the
latent (`kv_lora_rank`), RMS-normed, and ONE rotary key shared by all heads;
`latent @ wkv_b` gives per head the content key and the value. Scores
(q_nope . k_nope + q_rope . k_rope) x (nope + rope)^-0.5 x mscale(factor,
mscale_all_dim)^2, causal softmax, values, `wo`.

Feed-forward: the router in float32 over ALL `n_experts` (`x @ w_router`,
softmax, the `n_experts_active` best, their weights renormalised over all of
the picks, times `moe_routed_scale`). This chip holds the `n_experts_held`
experts from `expert_first` on (`we_gate / we_up / we_down` have that many on
their expert axis): each held expert's SwiGLU is computed for the whole
sequence, one expert at a time, and enters a token's sum with the weight the
token's picks give it, which is 0 where the token did not pick it. **What an
expert held elsewhere would add is left out**, exactly as in the program, and
the shared expert (one SwiGLU of width `n_shared_experts x moe_ffn_dim`, which
every chip computes alike) is added whole. With every expert held this is the
published layer.

Departures from the published description, each to agree with what this
program serves (random weights make either convention a valid model):
  - rotary pairs are (i, i + half), the half-rotation layout, where the
    published checkpoints interleave (`rope_interleave`): the program permutes
    on import (models/mla.py docstring, engine/weights.py);
  - the router's scoring function has no key in the config: softmax over the
    128 logits, the family's published router (`moe_scoring`; `sigmoid` is
    computed as `mla_moe_decoder.selection` computes it);
  - the query scale's formula is read from the key's name and Llama 4's
    published temperature scale (the configuration file's `assumed`);
  - the absent experts' terms are left out (the model-configs guide, section
    4): the next layer reads this chip's partial sum.

Followed mode (`follow_at`): as `mla_moe_decoder.follow_at`; `picks` are ids
over the router's FULL width whatever share is held.

`model` is the configuration file's `model` group (the program's ModelConfig
field names), `params` the served tree: embed [V, E], norm_f [E], lm_head
[E, V], layers.{attn_norm, kv_norm, mlp_norm, q_lat_norm [L, .]; wq_lat, wq_up
(or wq), wkv_a, wkv_b, wo [L, in, out]; w_router [L, E, n]; we_gate, we_up,
we_down [L, held, in, out]; ws_gate, ws_up, ws_down [L, in, out]}.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _sibling("mla_moe_decoder")
_f32, _rms, _rope, _swiglu = _base._f32, _base._rms, _base._rope, _base._swiglu
rope_table, selection, mixing_weights = _base.rope_table, _base.selection, _base.mixing_weights
need_of, _logprobs, QUERY_BLOCK = _base.need_of, _base._logprobs, _base.QUERY_BLOCK


def query_scale(pos, model: dict):
    """[S] float32: 1 + beta ln(1 + floor(pos / orig)); all ones where the
    model has no such scale."""
    beta = float(model.get("attn_qscale_beta") or 0.0)
    if not beta:
        return jnp.ones(pos.shape, jnp.float32)
    orig = float(model["attn_qscale_orig"])
    return 1.0 + beta * jnp.log(1.0 + jnp.floor(pos.astype(jnp.float32) / orig))


def _attention(h, lp, pos, model, inv, m, soft):
    S = h.shape[0]
    H, eps = int(model["n_heads"]), float(model["norm_eps"])
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv, dc = int(model["v_head_dim"]), int(model["kv_lora_rank"])
    x = _rms(h, _f32(lp["attn_norm"]), eps)
    if "wq_lat" in lp:
        q = _rms(x @ _f32(lp["wq_lat"]), _f32(lp["q_lat_norm"]), eps) @ _f32(lp["wq_up"])
    else:
        q = x @ _f32(lp["wq"])
    q = q.reshape(S, H, dn + dr) * query_scale(pos, model)[:, None, None]
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv, m)
    kv = x @ _f32(lp["wkv_a"])
    latent = _rms(kv[:, :dc], _f32(lp["kv_norm"]), eps)
    k_rope = _rope(kv[:, None, dc:], pos, inv, m)  # [S, 1, dr]: one key for all heads
    up = (latent @ _f32(lp["wkv_b"])).reshape(S, H, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    blocks = []
    for s0 in range(0, S, QUERY_BLOCK):
        sl = slice(s0, s0 + QUERY_BLOCK)
        scores = (jnp.einsum("shd,thd->hst", q_nope[sl], k_nope)
                  + jnp.einsum("shd,td->hst", q_rope[sl], k_rope[:, 0])) * soft
        mask = pos[None, :] <= pos[sl, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        blocks.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v))
    attn = jnp.concatenate(blocks, axis=0)
    return h + attn.reshape(S, H * dv) @ _f32(lp["wo"])


def held_range(model: dict):
    """(id of the first held expert, how many are held)."""
    n = int(model["n_experts"])
    return int(model.get("expert_first") or 0), int(model.get("n_experts_held") or n)


def _experts(x, lp, model, picks, follow):
    """The held experts' part of the expert layer for x [S, E] plus the
    shared expert, computed with the experts `picks` [S, k] (ids over the full
    width) where `follow` (a traced flag: one program serves both modes, so
    the reference's own picks handed back give the same bits) and with the
    reference's own otherwise; (output, need [S], the experts used)."""
    scores, choose = selection(x @ _f32(lp["w_router"]), lp.get("router_bias"), model)
    own = jnp.argsort(-choose, axis=-1)[:, : picks.shape[-1]]
    sel = jnp.where(follow, picks, own).astype(own.dtype)
    need = need_of(choose, sel)
    sel = jnp.clip(sel, 0, choose.shape[-1] - 1)
    w = mixing_weights(scores, sel, model)  # renormalised over ALL of a token's picks
    first, held = held_range(model)

    def add(acc, j):
        # one held expert at a time, sliced out of the stack inside the loop:
        # handed to the scan whole, the three stacks were converted to float32
        # before it (3.2 GB at the published widths, beside 10.85 GB of weights)
        gate, up, down = (lp[k][j] for k in ("we_gate", "we_up", "we_down"))
        mine = (w * (sel == first + j)).sum(-1)  # [S]: 0 for a token that did not pick it
        return acc + mine[:, None] * _swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(x), jnp.arange(held, dtype=sel.dtype))
    if "ws_gate" in lp:
        out = out + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out, need, sel


def _layer(h, lp, pos, inv, sizes, m, soft, picks, follow):
    """(the residual stream after the layer, need [S], experts used [S, k])."""
    model = dict(sizes)
    h = _attention(h, lp, pos, model, inv, m, soft)
    x = _rms(h, _f32(lp["mlp_norm"]), float(model["norm_eps"]))
    y, need, sel = _experts(x, lp, model, picks, follow)
    return h + y, need, sel


_step = jax.jit(_layer, static_argnums=(4, 5, 6))


def hidden_states(model: dict, params, tokens: np.ndarray, picks=None):
    """(the residual stream [S, E] after the last layer, need [S, L], the
    experts used [S, L, k]). `picks` int [S, L, k]: the experts to compute
    every position's expert layers with; None: the reference's own."""
    if int(model.get("n_dense_layers") or 0) or "layers_dense" in params:
        raise ValueError("this family has no leading dense layer")
    inv, m, soft = rope_table(model)
    sizes = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (bool, int, float, str))))
    dev = next(iter(params["embed"].devices()))
    tok = jax.device_put(jnp.asarray(tokens, jnp.int32), dev)
    pos = jnp.arange(tok.shape[0], dtype=jnp.int32)
    inv = jax.device_put(jnp.asarray(inv, jnp.float32), dev)
    h = _f32(params["embed"][tok])
    L, k = int(model["n_layers"]), int(model["n_experts_active"])
    follow = picks is not None
    if follow:
        picks = np.asarray(picks)
        if picks.shape != (tok.shape[0], L, k):
            raise ValueError(f"picks {picks.shape}: want {(tok.shape[0], L, k)}")
    else:
        picks = np.zeros((tok.shape[0], L, k), np.int32)
    picks = jax.device_put(jnp.asarray(picks, jnp.int32), dev)
    flag = jax.device_put(jnp.asarray(follow), dev)
    needs, used = [], []
    for l in range(L):
        # one layer at a time, to where the embedding lives: a tree whose layer
        # stack is kept on the host (no room beside the program's own) works
        lp = jax.device_put(jax.tree.map(lambda a: a[l], params["layers"]), dev)
        h, need, sel = _step(h, lp, pos, inv, sizes, m, soft, picks[:, l], flag)
        needs.append(need)
        used.append(sel)
    return h, jnp.stack(needs, axis=1), jnp.stack(used, axis=1)


def follow_at(model: dict, params, tokens: np.ndarray, at: list, picks):
    """(log-softmax of the next-token distribution after each position in
    `at`, for one sequence `tokens` [S]: float32 [len(at), V]; need, float32
    [S, L]), every expert layer of EVERY position computed with the served
    experts `picks` int [S, L, k] (ids over the router's full width) and this
    reference's own unbiased float32 scores as their weights: the held
    experts' part, as everywhere in this file. `need` says how far its own
    selection scores, over all `n_experts`, would have to move for the served
    set to be their top k (`mla_moe_decoder.need_of`). With this reference's
    own picks (`own_picks`) handed back it returns `logprobs_at`'s rows bit
    for bit and a need of 0 everywhere."""
    with jax.default_matmul_precision("highest"):
        h, need, _ = hidden_states(model, params, tokens, picks)
        return _logprobs(model, params, h, at), np.asarray(need)


def own_picks(model: dict, params, tokens: np.ndarray) -> np.ndarray:
    """The experts this reference routes every position to: int32 [S, L, k]."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(hidden_states(model, params, tokens)[2])


def logprobs_at(model: dict, params, tokens: np.ndarray, at: list) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return _logprobs(model, params, hidden_states(model, params, tokens)[0], at)
