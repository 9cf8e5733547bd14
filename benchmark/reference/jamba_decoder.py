"""Plain reference of Jamba's hybrid decoder (`model_type: jamba` with every
MLP dense): float32 jax.numpy, no cache, no state pool, no kernels, no
batching, one layer at a time from the served bf16 tree, matmuls at `highest`
precision, the state-space recurrence a `lax.scan` over the tokens.

Every layer: x = x + mixer(rms(x, attn_norm)); x = x + SwiGLU(rms(x,
mlp_norm)). The mixer is causal softmax attention iff l % attn_layer_period ==
attn_layer_offset (grouped queries, scale head_dim^-0.5, no rotary or any
other position term, no bias, no window), else a Mamba-1 mixer with Jamba's
three inner norms:

    [a, z] = u W_in                       (each d = mamba_expand x dim wide)
    c_t = silu(b_conv + sum_j w_conv[j] a_{t-K+1+j})   (zeros before token 0)
    [dt', B, C] = c_t W_x                 (split dt_rank, d_state, d_state)
    dt = softplus(rms(dt') W_dt + b_dt);  B = rms(B);  C = rms(C)
    S_t = exp(dt (x) A) S_{t-1} + (dt c_t) (x) B,  A = -exp(A_log),  S_{-1} = 0
    y_t = S_t C + D c_t;   out = (y_t silu(z_t)) W_out

After the last layer rms(x, norm_f), logits x E^T with the embedding E.

Held to transformers' `JambaForCausalLM` (`modeling_jamba.JambaMambaMixer.
slow_forward`, float32, random biases too) in tests/test_jamba.py. Departures
from it: (1) the state is `[d_state, d]` here and `[d, d_state]` there, so
`A_log` is read transposed: the same numbers; (2) `slow_forward` rounds the
state to the activations' type before `S C` (`ssm_state.to(dtype)`), which in
float32 is nothing; (3) weights are whatever tree is served, every matrix
`[in, out]` and the convolution `[K, d]`.

`model` is the configuration file's `model` group (the program's ModelConfig
field names), `params` the served tree: embed [V, E], norm_f [E], layers.
{attn_norm, mlp_norm [L, E]; w_gate, w_up, w_down [L, in, out]}, mamba.{w_in
[Lm, E, 2d], w_conv [Lm, K, d], b_conv [Lm, d], w_x [Lm, d, R + 2N], dt_norm
[Lm, R], b_norm, c_norm [Lm, N], w_dt [Lm, R, d], b_dt [Lm, d], A_log [Lm, N,
d], D [Lm, d], w_out [Lm, d, E]}, attn.{wq, wk, wv, wo [La, in, out]}; Lm and
La count the Mamba and the attention layers in model order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


QUERY_BLOCK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _attention(x, ap, n_heads, n_kv):
    S = x.shape[0]
    D = ap["wq"].shape[-1] // n_heads
    q = (x @ ap["wq"]).reshape(S, n_heads, D)
    k = jnp.repeat((x @ ap["wk"]).reshape(S, n_kv, D), n_heads // n_kv, axis=1)
    v = jnp.repeat((x @ ap["wv"]).reshape(S, n_kv, D), n_heads // n_kv, axis=1)
    pos = jnp.arange(S)
    blocks = []
    for s0 in range(0, S, QUERY_BLOCK):
        scores = jnp.einsum("shd,thd->hst", q[s0:s0 + QUERY_BLOCK], k) * (D ** -0.5)
        mask = pos[None, :] <= pos[s0:s0 + QUERY_BLOCK, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        blocks.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(blocks, axis=0).reshape(S, n_heads * D) @ ap["wo"]


def _mamba(x, mp, eps):
    S = x.shape[0]
    K, d = mp["w_conv"].shape
    N = mp["b_norm"].shape[0]
    R = mp["dt_norm"].shape[0]
    az = x @ mp["w_in"]
    a, z = az[:, :d], az[:, d:]
    ap = jnp.concatenate([jnp.zeros((K - 1, d), jnp.float32), a], axis=0)
    c = mp["b_conv"] + sum(mp["w_conv"][j] * ap[j:j + S] for j in range(K))
    c = jax.nn.silu(c)
    dbc = c @ mp["w_x"]
    dt = jax.nn.softplus(_rms(dbc[:, :R], mp["dt_norm"], eps) @ mp["w_dt"] + mp["b_dt"])
    Bm = _rms(dbc[:, R:R + N], mp["b_norm"], eps)
    Cm = _rms(dbc[:, R + N:], mp["c_norm"], eps)
    A = -jnp.exp(mp["A_log"])  # [N, d]

    def step(state, inp):
        dt_t, c_t, b_t, c_out = inp
        state = jnp.exp(dt_t[None, :] * A) * state + (dt_t * c_t)[None, :] * b_t[:, None]
        return state, jnp.sum(state * c_out[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, d), jnp.float32), (dt, c, Bm, Cm))
    y = y + mp["D"] * c
    return (y * jax.nn.silu(z)) @ mp["w_out"]


def _layer(h, lp, mix, is_attn, n_heads, n_kv, eps):
    lp, mix = _f32(lp), _f32(mix)
    x = _rms(h, lp["attn_norm"], eps)
    h = h + (_attention(x, mix, n_heads, n_kv) if is_attn else _mamba(x, mix, eps))
    x = _rms(h, lp["mlp_norm"], eps)
    return h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def logprobs_at(model: dict, params, tokens: np.ndarray, at: list) -> np.ndarray:
    """log-softmax of the next-token distribution after each position in
    `at`, for one sequence `tokens` [S]: float32 [len(at), V]."""
    with jax.default_matmul_precision("highest"):
        dev = next(iter(params["embed"].devices()))
        tok = jax.device_put(jnp.asarray(tokens, jnp.int32), dev)
        h = params["embed"][tok].astype(jnp.float32)
        period, offset = int(model["attn_layer_period"]), int(model["attn_layer_offset"])
        eps = float(model["norm_eps"])
        step = jax.jit(_layer, static_argnums=(3, 4, 5, 6))
        n_attn = n_mamba = 0
        for l in range(int(model["n_layers"])):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            is_attn = l % period == offset
            if is_attn:
                mix = jax.tree.map(lambda a: a[n_attn], params["attn"])
                n_attn += 1
            else:
                mix = jax.tree.map(lambda a: a[n_mamba], params["mamba"])
                n_mamba += 1
            h = step(h, lp, mix, is_attn, int(model["n_heads"]),
                     int(model["n_kv_heads"]), eps)
        h = _rms(h[jnp.asarray(at)], params["norm_f"].astype(jnp.float32), eps)
        logits = h @ params["embed"].astype(jnp.float32).T
        return np.asarray(jax.nn.log_softmax(logits, axis=-1))
