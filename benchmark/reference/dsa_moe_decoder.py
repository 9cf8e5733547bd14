"""Plain reference of the `deepseek_v32` decoder block (DeepSeek-V3.2) as ONE
CHIP of an expert-parallel unit holds it: the DeepSeek-V3 block (latent
attention with a compressed query; a dense or a sigmoid-routed, group-limited
expert feed-forward with one shared expert, of whose routed experts this chip
holds a share) with the lightning indexer in every layer and the softmax of
latent attention taken over the indexer's `index_topk` best tokens alone.
float32 jax.numpy, no cache, no kernels, no batching, one layer at a time from
the served tree, matmuls at `highest` precision. It imports nothing from the
program.

Written from the published `config.json` and the published inference code's
equations. What it shares with `mla_moe_decoder.py` (RMSNorm, the yarn table,
the rotary embedding, SwiGLU, the router's selection, `need_of`, the head, the
leading dense layers) and with `mistral4_decoder.py` (the held share of the
experts, the followed picks) it imports from those files, which no PR of this
kind edits.

Attention, per layer, for a sequence of T tokens: x = RMSNorm(h); cq =
RMSNorm(x @ wq_lat); per head q = cq @ wq_up split into a content part
(`qk_nope_head_dim`) and a rotary part (`qk_rope_head_dim`); `x @ wkv_a` splits
into the latent (`kv_lora_rank`), RMS-normed, and ONE rotary key for all heads;
`latent @ wkv_b` gives per head the content key and the value (the
non-absorbed form: nothing is cached). Scores (q_nope . k_nope + q_rope .
k_rope) x (nope + rope)^-0.5 x mscale(factor, mscale_all_dim)^2.

The indexer, between them (`index_n_heads` heads of `index_head_dim`):

    qI[t, j] = (cq[t] @ wi_q)[j]            rotary on each head's first rope dims
    kI[s]    = LayerNorm(x[s] @ wi_k)       one key a token; rotary likewise
    w[t, j]  = (x[t] @ wi_w)[j] x heads^-0.5 x head_dim^-0.5
    I[t, s]  = sum_j w[t, j] relu(qI[t, j] . kI[s])          for s <= t
    S[t]     = the min(index_topk, t + 1) positions s <= t with the largest
               I[t, s], ties towards the lower position

and the softmax of token t runs over s in S[t] only: the full [T, T] index
scores, an explicit top-k (a stable sort), a boolean mask, a masked softmax, a
block of queries at a time. With `index_topk >= T` every causal position is
chosen and this is `mla_moe_decoder`'s attention.

`follow_at` follows the served program's choices, both kinds: the router's
picks as `mistral4_decoder.follow_at` does, and the SELECTION: every layer's
softmax runs over the tokens the program says it attended to, after holding
that set against this reference's own float32 index scores (`selection_need`)
as a pick is held against the selection scores. Without that a served token
that bf16 arithmetic moved across the top-k boundary, one of thousands whose
index scores lie a thousandth apart, now and then carries several per cent of
the softmax, and the comparison reads the boundary and not the arithmetic
(PERF.md section 6, PR 44). The served sets arrive in `picks`, below the
expert layers' rows: see `split_served`. The LayerNorm has a weight
and a bias (`ik_norm`, `ik_norm_b`) and the model's `norm_eps`; the rotary uses
the model's own theta and yarn frequencies; the index scores carry no yarn
scale.

Departures from the published inference code, each to agree with what this
program serves (the configuration file's `assumed` lists them): no Hadamard
rotation and no FP8 on qI / kI (the rotation is orthogonal and changes no dot
product); rotary pairs are (i, i + half), the half-rotation layout, in latent
attention and in the indexer alike (with drawn weights a layout is a column
permutation); the absent experts' terms are left out (the model-configs guide,
section 4); the multi-token-prediction module is no part of the model's logits
and is not here.

`model` is the configuration file's `model` group (the program's ModelConfig
field names), `params` the served tree: embed [V, E], norm_f [E], lm_head
[E, V], layers_dense and layers, each {attn_norm, kv_norm, mlp_norm,
q_lat_norm, ik_norm, ik_norm_b [L, .]; wq_lat, wq_up, wkv_a, wkv_b, wo, wi_q,
wi_k, wi_w [L, in, out]}, the dense ones {w_gate, w_up, w_down}, the expert
ones {w_router [L, E, n], router_bias [L, n]; we_gate, we_up, we_down [L, held,
in, out]; ws_gate, ws_up, ws_down [L, in, out]}.
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
_share = _sibling("mistral4_decoder")
_f32, _rms, _rope, _swiglu = _base._f32, _base._rms, _base._rope, _base._swiglu
rope_table, need_of, _logprobs = _base.rope_table, _base.need_of, _base._logprobs
selection, mixing_weights, held_range = _base.selection, _base.mixing_weights, _share.held_range

QUERY_BLOCK = 256


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _turn_first(x, pos, inv, m):
    """Rotary on the first 2 x len(inv) dims of x [S, H, D]; the rest as is."""
    d = 2 * inv.shape[0]
    return jnp.concatenate([_rope(x[..., :d], pos, inv, m), x[..., d:]], axis=-1)


def index_parts(x, cq, lp, pos, model, inv, m):
    """(qI [S, Hi, Di], w [S, Hi], kI [S, Di]) of one layer, float32."""
    S = x.shape[0]
    hi, di = int(model["index_n_heads"]), int(model["index_head_dim"])
    qi = _turn_first((cq @ _f32(lp["wi_q"])).reshape(S, hi, di), pos, inv, m)
    ki = _layer_norm(x @ _f32(lp["wi_k"]), _f32(lp["ik_norm"]), _f32(lp["ik_norm_b"]),
                     float(model["norm_eps"]))
    ki = _turn_first(ki[:, None, :], pos, inv, m)[:, 0]
    w = (x @ _f32(lp["wi_w"])) * (hi ** -0.5 * di ** -0.5)
    return qi, w, ki


def index_scores(qi, w, ki):
    """I [Sq, T] = sum_j w[t, j] relu(qI[t, j] . kI[s]); no mask."""
    return jnp.einsum("thc,th->tc", jax.nn.relu(jnp.einsum("thd,cd->thc", qi, ki)), w)


def chosen(scores, q_pos, k_pos, topk: int):
    """bool [Sq, T]: for each query the min(topk, q_pos + 1) causal positions
    with the largest score, ties towards the lower position (a stable sort of
    the negated scores; what is not causal sorts last and is masked again)."""
    causal = k_pos[None, :] <= q_pos[:, None]
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1, stable=True)
    top = order[:, : min(int(topk), scores.shape[-1])]
    picked = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], top].set(True)
    return picked & causal


def selection_need(scores, served, q_pos, k_pos, topk: int):
    """How far the index scores [Sq, T] would have to move for each query's
    served set (bool [Sq, T]) to be its top `topk`: the best causal score left
    out minus the weakest taken, in spreads (standard deviations) of the
    query's causal scores, which makes it a number like a pick's need (a
    difference of two sigmoid scores) that the one `correct_routing_margin`
    of benchmark/serve.py can hold beside it; 0 where the set is the top-k
    already (ties either way) and for a query that sees at most `topk` tokens
    and took them all; inf for a set of another size than min(topk, q_pos + 1)
    or with a token the query cannot see. On the chip at the published widths
    (PERF.md section 6, PR 44) the sound bf16 program's selections need
    0.31-0.40, the same weights under int8 0.90, a sliding window in the
    selection's place 9.1; its picks 0.05-0.125 and int8's 0.10-0.11, so on
    this share the selections tell the programs apart and the picks do not."""
    causal = k_pos[None, :] <= q_pos[:, None]
    n = causal.sum(-1)
    sound = ((served & ~causal).sum(-1) == 0) & (served.sum(-1) == jnp.minimum(n, int(topk)))
    weakest = jnp.where(served, scores, jnp.inf).min(-1)
    left_out = jnp.where(causal & ~served, scores, -jnp.inf).max(-1)
    mean = jnp.where(causal, scores, 0.0).sum(-1) / n
    spread = jnp.sqrt(jnp.where(causal, jnp.square(scores - mean[:, None]), 0.0).sum(-1) / n)
    need = jnp.maximum(left_out - weakest, 0.0) / jnp.maximum(spread, 1e-30)
    return jnp.where(sound, jnp.where(n <= int(topk), 0.0, need), jnp.inf)


def _attention(h, lp, pos, model, inv, m, soft, served=None, follow=False):
    """(the residual stream after attention, need [S] of the served selection:
    zeros where nothing is followed or the model selects nothing)."""
    S = h.shape[0]
    H, eps = int(model["n_heads"]), float(model["norm_eps"])
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv, dc = int(model["v_head_dim"]), int(model["kv_lora_rank"])
    topk = int(model.get("index_topk") or 0)
    x = _rms(h, _f32(lp["attn_norm"]), eps)
    cq = _rms(x @ _f32(lp["wq_lat"]), _f32(lp["q_lat_norm"]), eps)
    q = (cq @ _f32(lp["wq_up"])).reshape(S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv, m)
    kv = x @ _f32(lp["wkv_a"])
    latent = _rms(kv[:, :dc], _f32(lp["kv_norm"]), eps)
    k_rope = _rope(kv[:, None, dc:], pos, inv, m)  # [S, 1, dr]: one key for all heads
    up = (latent @ _f32(lp["wkv_b"])).reshape(S, H, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    if topk:
        qi, w, ki = index_parts(x, cq, lp, pos, model, inv, m)
    blocks, needs = [], []
    for s0 in range(0, S, QUERY_BLOCK):
        sl = slice(s0, s0 + QUERY_BLOCK)
        scores = (jnp.einsum("shd,thd->hst", q_nope[sl], k_nope)
                  + jnp.einsum("shd,td->hst", q_rope[sl], k_rope[:, 0])) * soft
        if topk:
            index = index_scores(qi[sl], w[sl], ki)
            mask = chosen(index, pos[sl], pos, topk)
            if served is not None:  # `follow` is traced: one program, both modes
                needs.append(jnp.where(
                    follow, selection_need(index, served[sl], pos[sl], pos, topk), 0.0))
                # a set that is no one's choice (need inf) may be empty: the
                # softmax still has to see something, and `correct` is false
                mask = jnp.where(follow & served[sl].any(-1, keepdims=True), served[sl], mask)
        else:
            mask = pos[None, :] <= pos[sl, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        blocks.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v))
    attn = jnp.concatenate(blocks, axis=0)
    need = jnp.concatenate(needs) if needs else jnp.zeros((S,), jnp.float32)
    return h + attn.reshape(S, H * dv) @ _f32(lp["wo"]), need


def need_under_groups(biased, picks, model):
    """`need_of` for a group-limited router: how far the selection scores
    `biased` [S, n] (the bias added, no group banned yet) would have to move
    for the served set `picks` [S, k] to be what the two-stage selection
    chooses. `mla_moe_decoder.need_of` on the reference's own banned groups
    reads inf for any pick from a group the float32 scores banned, however
    narrowly; a served bf16 program flips a group now and then as it flips an
    expert, so the groups are held to the margin like the experts. Stage one:
    the groups the served picks lie in (more than `topk_groups` of them: inf)
    have to be among the groups kept; the need is the best score of a group
    left out minus the weakest of a group used, HALVED, a group's score being
    the sum of two selection scores (so that it is in the unit an expert's
    need has: the difference of two scores). Stage two: `need_of` among the
    experts of the groups then kept (the used ones, and the best others).
    The larger of the two; 0 where the served set is the reference's own."""
    groups, keep = int(model.get("n_expert_groups") or 0), int(model.get("topk_groups") or 0)
    if not (groups > 1 and 0 < keep < groups):
        return need_of(biased, picks)
    n = biased.shape[-1]
    per = n // groups
    rows = jnp.arange(picks.shape[0])[:, None]
    gscore = jnp.sort(biased.reshape(-1, groups, per), axis=-1)[..., -min(2, per):].sum(-1)
    used = jnp.zeros(gscore.shape, bool).at[rows, jnp.clip(picks, 0, n - 1) // per].set(True)
    kept_ids = jnp.argsort(-jnp.where(used, jnp.inf, gscore), axis=-1)[:, :keep]
    kept = jnp.zeros(gscore.shape, bool).at[rows, kept_ids].set(True)
    weakest_used = jnp.where(used, gscore, jnp.inf).min(-1)
    best_left_out = jnp.where(kept, -jnp.inf, gscore).max(-1)
    need_g = jnp.where(used.sum(-1) <= keep,
                       jnp.maximum(best_left_out - weakest_used, 0.0) / 2, jnp.inf)
    choose = jnp.where(jnp.repeat(kept, per, axis=-1), biased, -jnp.inf)
    return jnp.maximum(need_g, need_of(choose, picks))


def _experts(x, lp, model, picks, follow):
    """The held experts' part of the expert layer for x [S, E] plus the
    shared expert (`mistral4_decoder._experts`, with the need of a
    group-limited router): computed with the experts `picks` [S, k] (ids over
    the full width) where `follow` and with the reference's own otherwise;
    (output, need [S], the experts used)."""
    scores, choose = selection(x @ _f32(lp["w_router"]), lp.get("router_bias"), model)
    biased = scores if lp.get("router_bias") is None else scores + _f32(lp["router_bias"])
    own = jnp.argsort(-choose, axis=-1)[:, : picks.shape[-1]]
    sel = jnp.where(follow, picks, own).astype(own.dtype)
    need = need_under_groups(biased, sel, model)
    sel = jnp.clip(sel, 0, choose.shape[-1] - 1)
    w = mixing_weights(scores, sel, model)  # renormalised over ALL of a token's picks
    first, held = held_range(model)

    def add(acc, j):  # one held expert at a time, sliced out of the stack
        gate, up, down = (lp[k][j] for k in ("we_gate", "we_up", "we_down"))
        mine = (w * (sel == first + j)).sum(-1)  # [S]: 0 for a token that did not pick it
        return acc + mine[:, None] * _swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(x), jnp.arange(held, dtype=sel.dtype))
    if "ws_gate" in lp:
        out = out + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out, need, sel


def _layer(h, lp, pos, inv, sizes, m, soft, served=None, follow_served=False,
           picks=None, follow=False):
    """(the residual stream after the layer, need [S] of the served selection,
    (need [S], experts used [S, k]) of an expert layer or None of a dense
    one). `served` bool [S, S]: the tokens each position attended to, followed
    where `follow_served`; `picks` [S, k] likewise where `follow`."""
    model = dict(sizes)
    h, need_sel = _attention(h, lp, pos, model, inv, m, soft, served, follow_served)
    x = _rms(h, _f32(lp["mlp_norm"]), float(model["norm_eps"]))
    if "w_router" in lp:
        y, need, sel = _experts(x, lp, model, picks, follow)
        return h + y, need_sel, (need, sel)
    return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), need_sel, None


_step = jax.jit(_layer, static_argnums=(4, 5, 6))


def split_served(model: dict, picks, n_moe: int):
    """What the program streams as `routed_experts` for a model with an
    indexer, int [S, n_moe + L x R, k], taken apart: (the experts [S, n_moe,
    k], the served selection bool [L, S, S]). Below the expert layers' rows
    lie, layer by layer, R rows of k int32 words: the tokens the position
    attended to in that layer, token s bit s % 32 of word s // 32
    (little-endian), zeros behind the context. A `picks` of the expert rows
    alone has no selection to follow: (picks, None)."""
    picks = np.asarray(picks)
    S, L = picks.shape[0], int(model["n_layers"])
    if picks.shape[1] == n_moe or not int(model.get("index_topk") or 0):
        return picks, None
    words = np.ascontiguousarray(picks[:, n_moe:].astype("<i4")).reshape(S, L, -1)
    if words.shape[-1] * 32 < S:
        raise ValueError(f"picks {picks.shape}: the selection's rows cover "
                         f"{words.shape[-1] * 32} tokens of {S}")
    n = -(-S // 32)
    bits = np.unpackbits(words[..., :n].copy().view(np.uint8), axis=-1, bitorder="little")
    # a token behind the sequence is no one's choice: the set is emptied, and
    # an empty set's need is inf
    beyond = words[..., n:].any(-1) | bits[..., S:].any(-1)
    served = bits[..., :S].astype(bool) & ~beyond[..., None]
    return picks[:, :n_moe], np.moveaxis(served, 1, 0)


def hidden_states(model: dict, params, tokens: np.ndarray, picks=None):
    """(the residual stream [S, E] after the last layer, need [S, L_moe], the
    experts used [S, L_moe, k], the selections' need [S, L]). `picks` int [S,
    L_moe, k]: the experts to compute every position's expert layers with, and
    below them, where the program streamed it, the selection to attend under
    (`split_served`); None: the reference's own of both."""
    inv, m, soft = rope_table(model)
    sizes = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (bool, int, float, str))))
    dev = next(iter(params["embed"].devices()))
    tok = jax.device_put(jnp.asarray(tokens, jnp.int32), dev)
    pos = jnp.arange(tok.shape[0], dtype=jnp.int32)
    inv = jax.device_put(jnp.asarray(inv, jnp.float32), dev)
    h = _f32(params["embed"][tok])
    n_dense = int(model.get("n_dense_layers") or 0) if "layers_dense" in params else 0
    n_moe, k = int(model["n_layers"]) - n_dense, int(model["n_experts_active"])
    follow, served = picks is not None, None
    if follow:
        picks, served = split_served(model, picks, n_moe)
        if picks.shape != (tok.shape[0], n_moe, k):
            raise ValueError(f"picks {picks.shape}: want {(tok.shape[0], n_moe, k)}")
    else:
        picks = np.zeros((tok.shape[0], n_moe, k), np.int32)
    picks = jax.device_put(jnp.asarray(picks, jnp.int32), dev)
    flag = jax.device_put(jnp.asarray(follow), dev)
    follow_sel = jax.device_put(jnp.asarray(served is not None), dev)
    if int(model.get("index_topk") or 0):  # own picks handed back: the same program
        served = np.zeros((int(model["n_layers"]), 1, 1), bool) if served is None else served
    needs, used, needs_sel = [], [], []
    for l in range(int(model["n_layers"])):
        stack, i = (params["layers_dense"], l) if l < n_dense else (params["layers"], l - n_dense)
        # one layer at a time, to where the embedding lives: a tree whose layer
        # stacks are kept on the host (no room beside the program's own) works
        lp = jax.device_put(jax.tree.map(lambda a: a[i], stack), dev)
        mine = None if served is None else jax.device_put(
            jnp.broadcast_to(jnp.asarray(served[l]), (tok.shape[0],) * 2), dev)
        if "w_router" not in lp:
            h, need_sel, _ = _step(h, lp, pos, inv, sizes, m, soft, mine, follow_sel)
        else:
            h, need_sel, (need, sel) = _step(h, lp, pos, inv, sizes, m, soft, mine, follow_sel,
                                             picks[:, len(needs)], flag)
            needs.append(need)
            used.append(sel)
        needs_sel.append(need_sel)
    return h, jnp.stack(needs, axis=1), jnp.stack(used, axis=1), jnp.stack(needs_sel, axis=1)


def follow_at(model: dict, params, tokens: np.ndarray, at: list, picks):
    """As `mistral4_decoder.follow_at`: the rows of `at` and need [S, L_moe +
    L], every expert layer computed with the served experts and every layer's
    attention under the served selection, both from `picks` int [S, L_moe + L
    x R, k] as the program streams them (`split_served`; ids over the router's
    full width), each held against this reference's own scores: an expert
    layer's need, then a layer's selection's (`selection_need`). A `picks` of
    the expert rows alone follows them and attends to this reference's own
    float32 choice."""
    with jax.default_matmul_precision("highest"):
        h, need, _, need_sel = hidden_states(model, params, tokens, picks)
        need = np.concatenate([np.asarray(need), np.asarray(need_sel)], axis=1)
        return _logprobs(model, params, h, at), need


def own_picks(model: dict, params, tokens: np.ndarray) -> np.ndarray:
    """The experts this reference routes every position to: int32 [S, L_moe, k]."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(hidden_states(model, params, tokens)[2])


def logprobs_at(model: dict, params, tokens: np.ndarray, at: list) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return _logprobs(model, params, hidden_states(model, params, tokens)[0], at)
