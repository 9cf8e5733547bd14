"""Plain reference of the Ling-3.0-flash text decoder as ONE CHIP of an
expert-parallel deployment holds it: Kimi-delta-attention (KDA) layers with a
gated latent-attention (MLA) layer closing every `kda_layer_period`, leading
dense SwiGLU layers, then grouped sigmoid experts of which this chip holds a
share, plus one shared expert. float32 jax.numpy, no cache, no kernels, no
batching, one layer at a time from the served tree, matmuls at `highest`
precision. It imports nothing from the program. What it shares with
`mla_moe_decoder.py` (RMSNorm, rotary embedding, SwiGLU, the head) and with
`dsa_moe_decoder.py` (the held share of a group-limited sigmoid router and
the followed picks: `_experts`) it imports from those files, which no PR of
this kind edits.

Written from the published `config.json` (catalog `Ling-3.0-flash-VL`; the
language model's keys) and the Kimi Linear report (arXiv:2510.26692, the KDA
layer; the public flash-linear-attention gate with a lower bound). Layer l
mixes with MLA iff (l + 1) % kda_layer_period == 0, else KDA; the first
`n_dense_layers` layers have a dense MLP. Pre-norm residuals: h += mixer(
rms(h)); h += mlp(rms(h)).

KDA layer, per token t (H heads, d_k = d_v = `kda_head_dim`):
  q~, k~, v~ = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv)): causal
    depthwise convolutions of `kda_conv` taps a channel (the tree keeps the
    three side by side in `conv` [K, 3 H d_k]), zeros before the sequence;
  q = l2norm_head(q~) d_k^-1/2, k = l2norm_head(k~) (x / sqrt(sum x^2 + 1e-6));
  g = lower sigmoid(exp(A_log_h) (x Wa + dt_bias)) in (lower, 0) a channel
    (`kda_gate_lower` = -5: `kda_safe_gate`), alpha = exp(g);
  beta = sigmoid(x W_beta) a head;
  S' = alpha[:, None] S_{t-1}; S_t = S' + beta k (v - k^T S')^T; o = S_t^T q,
    token by token (`lax.scan`), S_0 = 0, float32 [H, d_k, d_v];
  out = (rms_head(o) * o_norm * sigmoid(x W_g)_h) Wo.
MLA layer: q = x Wq [H, nope + rope]; [c, k_r] = x Wkv_a; c = rms(c); [k_n, v]
  = c Wkv_b; rotary (theta `rope_theta`, no scaling) on q's rope part and on
  the one k_r all heads share; softmax(q . [k_n, k_r] (nope + rope)^-1/2),
  causal, over the whole sequence; out = (attn_h * sigmoid(x W_g)_h) Wo.
Expert block: `dsa_moe_decoder._experts` (sigmoid scores, the bias for
  selection alone, the best `topk_groups` of `n_expert_groups` groups by the
  sum of each group's top 2, the top `n_experts_active` of what is left,
  weights the unbiased scores renormalised over the picks times
  `moe_routed_scale`; the held experts' terms and the shared expert; what an
  expert held elsewhere would add is left out, as in the program).

Departures from the published description, each to agree with what this
program serves (random weights make either convention a valid model):
  - rotary pairs are (i, i + half), the half-rotation layout;
  - `use_qk_norm` is read as the L2 norm of q and k in the KDA layers and the
    RMSNorm of the compressed latent in the MLA layers (the configuration
    file's `assumed`);
  - the SwiGLU clamp (`expert_swiglu_limit_list`) is 0 in every layer held
    and is not computed;
  - no multi-token-prediction module, no vision tower.

Followed mode (`follow_at`): as `mistral4_decoder.follow_at`; `picks` are ids
over the router's FULL width whatever share is held.

`model` is the configuration file's `model` group (the program's ModelConfig
field names), `params` the served tree (models/ling.py's docstring).
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
_dsa = _sibling("dsa_moe_decoder")
_f32, _rms, _rope, _swiglu = _base._f32, _base._rms, _base._rope, _base._swiglu
rope_table, _logprobs, _experts = _base.rope_table, _base._logprobs, _dsa._experts

QUERY_BLOCK = 256


def is_attn_layer(model: dict, l: int) -> bool:
    return (l + 1) % int(model["kda_layer_period"]) == 0


def _conv(a, w):
    """a [S, C], w [K, C]: out[t] = sum_j w[j] a[t - (K - 1) + j], zeros
    before the sequence."""
    K, S = w.shape[0], a.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), a.dtype), a], axis=0)
    return sum(w[j] * padded[j:j + S] for j in range(K))


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_recurrence(q, k, v, g, beta):
    """q, k, g [S, H, dk], v [S, H, dv], beta [S, H] -> o [S, H, dv]: the
    delta rule with a decay a channel, token by token from S = 0."""
    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S  # [H, dk, dv]
        u = v_t - jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, g, beta))
    return o


def _kda(h, norm, kp, model):
    S = h.shape[0]
    H, dk, eps = int(model["n_heads"]), int(model["kda_head_dim"]), float(model["norm_eps"])
    x = _rms(h, _f32(norm), eps)
    a = jnp.concatenate([x @ _f32(kp["wq"]), x @ _f32(kp["wk"]), x @ _f32(kp["wv"])], axis=-1)
    qkv = jax.nn.silu(_conv(a, _f32(kp["conv"])))
    q, k, v = (qkv[:, i * H * dk:(i + 1) * H * dk].reshape(S, H, dk) for i in range(3))
    q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
    scale = jnp.repeat(jnp.exp(_f32(kp["A_log"])), dk)
    g = float(model.get("kda_gate_lower", -5.0)) * jax.nn.sigmoid(
        scale * (x @ _f32(kp["wa"]) + _f32(kp["dt_bias"])))
    beta = jax.nn.sigmoid(x @ _f32(kp["w_beta"]))
    o = kda_recurrence(q, k, v, g.reshape(S, H, dk), beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * _f32(kp["o_norm"])
    o = o * jax.nn.sigmoid(x @ _f32(kp["w_g"]))[:, :, None]
    return h + o.reshape(S, H * dk) @ _f32(kp["wo"])


def _mla(h, norm, mp, pos, model, inv, m, soft):
    S = h.shape[0]
    H, eps = int(model["n_heads"]), float(model["norm_eps"])
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dv, dc = int(model["v_head_dim"]), int(model["kv_lora_rank"])
    x = _rms(h, _f32(norm), eps)
    q = (x @ _f32(mp["wq"])).reshape(S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv, m)
    kv = x @ _f32(mp["wkv_a"])
    latent = _rms(kv[:, :dc], _f32(mp["kv_norm"]), eps)
    k_rope = _rope(kv[:, None, dc:], pos, inv, m)  # [S, 1, dr]: one key for all heads
    up = (latent @ _f32(mp["wkv_b"])).reshape(S, H, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    blocks = []
    for s0 in range(0, S, QUERY_BLOCK):
        sl = slice(s0, s0 + QUERY_BLOCK)
        scores = (jnp.einsum("shd,thd->hst", q_nope[sl], k_nope)
                  + jnp.einsum("shd,td->hst", q_rope[sl], k_rope[:, 0])) * soft
        mask = pos[None, :] <= pos[sl, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        blocks.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v))
    attn = jnp.concatenate(blocks, axis=0) * jax.nn.sigmoid(x @ _f32(mp["w_g"]))[:, :, None]
    return h + attn.reshape(S, H * dv) @ _f32(mp["wo"])


def _layer(h, lp, mp, pos, inv, sizes, m, soft, attn, picks=None, follow=False):
    """(the residual stream after the layer, (need [S], experts used [S, k])
    of an expert layer or None of a dense one). `mp`: the layer's mixer (a
    KDA one's or, `attn`, an MLA one's), `lp` its norms and feed-forward."""
    model = dict(sizes)
    if attn:
        h = _mla(h, lp["attn_norm"], mp, pos, model, inv, m, soft)
    else:
        h = _kda(h, lp["attn_norm"], mp, model)
    x = _rms(h, _f32(lp["mlp_norm"]), float(model["norm_eps"]))
    if "w_router" in lp:
        y, need, sel = _experts(x, lp, model, picks, follow)
        return h + y, (need, sel)
    return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None


_step = jax.jit(_layer, static_argnums=(5, 6, 7, 8))


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
    L = int(model["n_layers"])
    n_dense = int(model.get("n_dense_layers") or 0) if "layers_dense" in params else 0
    n_moe, k = L - n_dense, int(model["n_experts_active"])
    follow = picks is not None
    if follow:
        picks = np.asarray(picks)
        if picks.shape != (tok.shape[0], n_moe, k):
            raise ValueError(f"picks {picks.shape}: want {(tok.shape[0], n_moe, k)}")
    else:
        picks = np.zeros((tok.shape[0], n_moe, k), np.int32)
    picks = jax.device_put(jnp.asarray(picks, jnp.int32), dev)
    flag = jax.device_put(jnp.asarray(follow), dev)
    needs, used, ranks = [], [], {True: 0, False: 0}
    for l in range(L):
        stack, i = (params["layers_dense"], l) if l < n_dense else (params["layers"], l - n_dense)
        attn = is_attn_layer(model, l)
        # one layer at a time, to where the embedding lives
        lp = jax.device_put(jax.tree.map(lambda a: a[i], stack), dev)
        mp = jax.device_put(jax.tree.map(lambda a: a[ranks[attn]],
                                         params["mla" if attn else "kda"]), dev)
        ranks[attn] += 1
        if "w_router" not in lp:
            h, _ = _step(h, lp, mp, pos, inv, sizes, m, soft, attn)
            continue
        h, (need, sel) = _step(h, lp, mp, pos, inv, sizes, m, soft, attn,
                               picks[:, len(needs)], flag)
        needs.append(need)
        used.append(sel)
    return h, jnp.stack(needs, axis=1), jnp.stack(used, axis=1)


def follow_at(model: dict, params, tokens: np.ndarray, at: list, picks):
    """(log-softmax of the next-token distribution after each position in
    `at`, for one sequence `tokens` [S]: float32 [len(at), V]; need, float32
    [S, L_moe]), every expert layer of EVERY position computed with the served
    experts `picks` int [S, L_moe, k] (ids over the router's full width) and
    this reference's own unbiased float32 scores as their weights: the held
    experts' part. `need` says how far its own selection scores would have to
    move for the served set to be what the two-stage selection chooses
    (`dsa_moe_decoder.need_under_groups`). With this reference's own picks
    (`own_picks`) handed back it returns `logprobs_at`'s rows bit for bit and
    a need of 0 everywhere."""
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
