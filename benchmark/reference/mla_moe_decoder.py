"""Plain reference of the `deepseek_v3` decoder block (DeepSeek-V2/V3,
Moonlight): multi-head latent attention and a mixture of routed and shared
experts behind leading dense layers. float32 jax.numpy, no cache, no kernels,
no batching, one layer at a time from the served tree, matmuls at `highest`
precision (on a TPU a float32 matmul otherwise runs in bf16 passes).

Written from the published description (DeepSeek-V2 section 2.1, DeepSeek-V3
sections 2.1.1-2.1.2, and the published `modeling_deepseek.py`), in the
non-absorbed form: the latent is kept nowhere, every head's keys and values
are rebuilt from it for the whole sequence.

Attention, per layer: x = RMSNorm(h). Queries `x @ wq` (or, with a compressed
query, `RMSNorm(x @ wq_lat) @ wq_up`), per head split into a content part
(`qk_nope_head_dim`) and a rotary part (`qk_rope_head_dim`). `x @ wkv_a`
splits into the latent (`kv_lora_rank`), which is RMS-normed, and ONE rotary
key shared by all heads. `latent @ wkv_b` gives, per head, the content key
and the value. Rotary embedding on the two rotary parts only; scores
(q_nope . k_nope + q_rope . k_rope) x (nope + rope)^-0.5, causal softmax,
values, `wo`.

Feed-forward: SwiGLU of width `ffn_dim` in the first `n_dense_layers` layers
(tree `layers_dense`); after them the router in float32: scores = sigmoid (or
softmax over all experts) of `x @ w_router`; `router_bias` is added for
SELECTION only; with expert groups the `topk_groups` groups whose two best
biased scores sum highest stay and the rest are banned; the `n_experts_active`
best are taken; their weights are the UNBIASED scores, renormalised to sum 1
when `moe_norm_topk`, times `moe_routed_scale`. Only a token's selected
experts are computed, token by token; the shared experts (one fused SwiGLU of
width `n_shared_experts x moe_ffn_dim`) are added.

Departures from the published code, each to agree with what this program
serves (random weights make either convention a valid model):
  - rotary pairs are (i, i + half), the half-rotation layout, where the
    published checkpoints interleave (i, i + 1): the program permutes on
    import (models/mla.py docstring);
  - `rope_scaling` "yarn" follows the published rule (frequencies blended
    between the `rope_beta_fast` / `rope_beta_slow` correction dimensions, cos
    and sin scaled by mscale(factor, rope_mscale) / mscale(factor,
    rope_mscale_all_dim), scores by mscale(factor, rope_mscale_all_dim)^2);
    other scalings are not in this family and raise;
  - the bias and the group limit apply under either scoring function (the
    publication defines them with sigmoid; no configuration pairs them with
    softmax).

Followed mode (`follow_at`): the expert layers are computed with the experts
the SERVED program picked, each pick first held against this reference's own
float32 selection scores (`need_of`); see `follow_at`.

`model` is the configuration file's `model` group (the program's ModelConfig
field names), `params` the served tree: embed [V, E], norm_f [E], lm_head
[E, V], layers / layers_dense.{attn_norm, kv_norm, mlp_norm [L, .]; wq (or
wq_lat, q_lat_norm, wq_up), wkv_a, wkv_b, wo [L, in, out]}, layers_dense.{w_gate,
w_up, w_down}, layers.{w_router [L, E, n]; router_bias [L, n]; we_gate, we_up,
we_down [L, n, in, out]; ws_gate, ws_up, ws_down [L, in, out]}.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


QUERY_BLOCK = 512
TOKEN_BLOCK = 8  # tokens whose selected experts are gathered at once


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 or m == 0.0 else 0.1 * m * math.log(factor) + 1.0


def rope_table(model: dict):
    """(inverse frequencies [rope/2], scale of cos and sin, softmax scale)."""
    d = int(model["qk_rope_head_dim"])
    theta = float(model["rope_theta"])
    half = d // 2
    inv = theta ** -(np.arange(half, dtype=np.float64) / half)
    qk = int(model["qk_nope_head_dim"]) + d
    kind = model.get("rope_scaling", "none")
    if kind == "none":
        return inv, 1.0, qk ** -0.5
    if kind != "yarn":
        raise ValueError(f"rope_scaling {kind!r} is not part of this family")
    factor = float(model.get("rope_factor", 1.0))
    orig = int(model.get("rope_orig_max_seq") or model["max_seq_len"])

    def correction_dim(rotations: float) -> float:
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(model.get("rope_beta_fast", 32.0)))), 0)
    high = min(math.ceil(correction_dim(float(model.get("rope_beta_slow", 1.0)))), d - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / max(high - low, 1), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)  # high frequencies keep their base
    all_dim = float(model.get("rope_mscale_all_dim", 0.0))
    m = _mscale(factor, float(model.get("rope_mscale", 1.0)))
    soft = qk ** -0.5
    if all_dim:
        m = m / _mscale(factor, all_dim)
        soft = soft * _mscale(factor, all_dim) ** 2
    return inv, m, soft


def _rope(x, pos, inv, m):
    # x [S, H, D]; rotate the (x1, x2) halves by pos * inv
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


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
    q = q.reshape(S, H, dn + dr)
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


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def selection(logits, bias, model):
    """(the unbiased scores [S, n], the selection scores [S, n]: the bias
    added, the experts of a banned group at -inf) from float32 router logits."""
    if model.get("moe_scoring", "softmax") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choose = scores if bias is None else scores + _f32(bias)
    groups, keep = int(model.get("n_expert_groups") or 0), int(model.get("topk_groups") or 0)
    if groups > 1 and 0 < keep < groups:
        per = choose.shape[-1] // groups
        grouped = choose.reshape(-1, groups, per)
        best2 = jnp.sort(grouped, axis=-1)[..., -min(2, per):].sum(-1)
        kept = jnp.argsort(-best2, axis=-1)[:, :keep]
        allowed = jnp.zeros(best2.shape, bool).at[jnp.arange(best2.shape[0])[:, None], kept].set(True)
        choose = jnp.where(jnp.repeat(allowed, per, axis=-1), choose, -jnp.inf)
    return scores, choose


def mixing_weights(scores, sel, model):
    """The weights of experts `sel` [S, k]: their UNBIASED scores,
    renormalised over the set when `moe_norm_topk`, times the routed scale."""
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if model.get("moe_norm_topk", True):
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
    return w * float(model.get("moe_routed_scale", 1.0))


def route(logits, bias, model):
    """(selected experts [S, k], their mixing weights [S, k], the biased
    selection scores [S, n]) from float32 router logits."""
    scores, choose = selection(logits, bias, model)
    sel = jnp.argsort(-choose, axis=-1)[:, : int(model["n_experts_active"])]
    return sel, mixing_weights(scores, sel, model), choose


def need_of(choose, picks):
    """How far the selection scores `choose` [S, n] would have to move for
    the set `picks` [S, k] to be their top k: the best score among the experts
    passed over minus the weakest among the picks, 0 where the picks are the
    top k already, inf for a pick from a banned group, a repeated id or an id
    that is no expert's."""
    n = choose.shape[-1]
    real = ((picks >= 0) & (picks < n)).all(-1)
    p = jnp.clip(picks, 0, n - 1)
    inside = jnp.zeros(choose.shape, bool).at[jnp.arange(p.shape[0])[:, None], p].set(True)
    weakest = jnp.take_along_axis(choose, p, axis=-1).min(-1)
    passed_over = jnp.where(inside, -jnp.inf, choose).max(-1)
    sound = real & (inside.sum(-1) == p.shape[-1]) & ~jnp.isneginf(weakest)
    return jnp.where(sound, jnp.maximum(passed_over - weakest, 0.0), jnp.inf)


def _experts(x, lp, model, picks, follow):
    """The expert layer's output for x [S, E], computed with the experts
    `picks` [S, k] where `follow` (a traced flag: one program serves both
    modes, so the reference's own picks handed back give the same bits) and
    with the reference's own otherwise; (output, need [S], the experts used)."""
    scores, choose = selection(x @ _f32(lp["w_router"]), lp.get("router_bias"), model)
    own = jnp.argsort(-choose, axis=-1)[:, : picks.shape[-1]]
    sel = jnp.where(follow, picks, own).astype(own.dtype)
    need = need_of(choose, sel)
    sel = jnp.clip(sel, 0, choose.shape[-1] - 1)
    w = mixing_weights(scores, sel, model)

    def token(args):
        xt, st, wt = args  # [E], [k], [k]: only this token's experts are read
        y = jax.vmap(lambda g, u, d: _swiglu(xt, g, u, d))(
            lp["we_gate"][st], lp["we_up"][st], lp["we_down"][st])
        return (wt[:, None] * y).sum(0)

    out = jax.lax.map(token, (x, sel, w), batch_size=TOKEN_BLOCK)
    if "ws_gate" in lp:
        out = out + _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out, need, sel


def _layer(h, lp, pos, inv, sizes, m, soft, picks=None, follow=False):
    """(the residual stream after the layer, (need [S], experts used [S, k])
    of an expert layer or None of a dense one)."""
    model = dict(sizes)
    h = _attention(h, lp, pos, model, inv, m, soft)
    x = _rms(h, _f32(lp["mlp_norm"]), float(model["norm_eps"]))
    if "w_router" in lp:
        if picks is None:  # called without: the reference's own (tests/test_moe_routing_trace.py)
            picks = jnp.zeros((h.shape[0], int(model["n_experts_active"])), jnp.int32)
        y, need, sel = _experts(x, lp, model, picks, follow)
        return h + y, (need, sel)
    return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None


_step = jax.jit(_layer, static_argnums=(4, 5, 6))


def hidden_states(model: dict, params, tokens: np.ndarray, picks=None):
    """(the residual stream [S, E] after the last layer, need [S, L_moe], the
    experts used [S, L_moe, k]). `picks` int [S, L_moe, k]: the experts to
    compute every position's expert layers with; None: the reference's own."""
    inv, m, soft = rope_table(model)
    sizes = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (bool, int, float, str))))
    dev = next(iter(params["embed"].devices()))
    tok = jax.device_put(jnp.asarray(tokens, jnp.int32), dev)
    pos = jnp.arange(tok.shape[0], dtype=jnp.int32)
    inv = jax.device_put(jnp.asarray(inv, jnp.float32), dev)
    h = _f32(params["embed"][tok])
    n_dense = int(model.get("n_dense_layers") or 0) if "layers_dense" in params else 0
    routed = "w_router" in params["layers"]
    n_moe = int(model["n_layers"]) - n_dense if routed else 0
    k = int(model.get("n_experts_active") or 0)
    follow = picks is not None
    if follow:
        picks = np.asarray(picks)
        if picks.shape != (tok.shape[0], n_moe, k):
            raise ValueError(f"picks {picks.shape}: want {(tok.shape[0], n_moe, k)}")
    else:
        picks = np.zeros((tok.shape[0], n_moe, k), np.int32)
    picks = jax.device_put(jnp.asarray(picks, jnp.int32), dev)
    flag = jax.device_put(jnp.asarray(follow), dev)
    needs, used = [], []
    for l in range(int(model["n_layers"])):
        stack, i = (params["layers_dense"], l) if l < n_dense else (params["layers"], l - n_dense)
        # one layer at a time, to where the embedding lives: a tree whose layer
        # stacks are kept on the host (no room beside the program's own) works
        lp = jax.device_put(jax.tree.map(lambda a: a[i], stack), dev)
        if "w_router" not in lp:
            h, _ = _step(h, lp, pos, inv, sizes, m, soft)
            continue
        h, (need, sel) = _step(h, lp, pos, inv, sizes, m, soft, picks[:, len(needs)], flag)
        needs.append(need)
        used.append(sel)
    if not needs:
        return h, jnp.zeros((tok.shape[0], 0)), picks
    return h, jnp.stack(needs, axis=1), jnp.stack(used, axis=1)


def _logprobs(model, params, h, at):
    h = _rms(h[jnp.asarray(at)], _f32(params["norm_f"]), float(model["norm_eps"]))
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return np.asarray(jax.nn.log_softmax(h @ _f32(head), axis=-1))


def follow_at(model: dict, params, tokens: np.ndarray, at: list, picks):
    """(log-softmax of the next-token distribution after each position in
    `at`, for one sequence `tokens` [S]: float32 [len(at), V]; need, float32
    [S, L_moe]), every expert layer of EVERY position computed with the served
    experts `picks` int [S, L_moe, k] (ids over the router's full width) and
    this reference's own unbiased float32 scores as their weights. `need` says
    how far its own selection scores would have to move for the served set to
    be its top k (`need_of`). A served bf16 program moves a selection score by
    its own rounding and so picks, now and then, another expert than float32
    arithmetic; what it then computes is right for ITS picks and far from what
    this reference computes for its own, with no program at fault. So the
    comparison follows the served picks, and holds each of them to be one that
    the float32 scores nearly made: the caller holds `need` to a margin the
    configuration states (`correct_routing_margin`). With this reference's own
    picks (`own_picks`) handed back it returns `logprobs_at`'s rows bit for
    bit and a need of 0 everywhere."""
    with jax.default_matmul_precision("highest"):
        h, need, _ = hidden_states(model, params, tokens, picks)
        return _logprobs(model, params, h, at), np.asarray(need)


def own_picks(model: dict, params, tokens: np.ndarray) -> np.ndarray:
    """The experts this reference routes every position to: int32 [S, L_moe, k]."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(hidden_states(model, params, tokens)[2])


def logprobs_at(model: dict, params, tokens: np.ndarray, at: list) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return _logprobs(model, params, hidden_states(model, params, tokens)[0], at)
