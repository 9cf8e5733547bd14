"""Plain reference of a dense pre-norm decoder (Phi-3 / Mistral / Llama
layout): float32 jax.numpy, no cache, no kernels, no batching, one layer at a
time from the served bf16 tree, matmuls at `highest` precision (on a TPU a
float32 matmul otherwise runs in bf16 passes).

Follows the published architecture: RMSNorm (weight x normalised, eps inside
the root), rotary embedding in the half-rotation layout over the whole head,
grouped-query attention with a causal mask and a sliding window (a query at
position p sees keys p-W+1..p), SwiGLU feed-forward, untied output head.
Departures: none in the mathematics; weights are whatever tree is served.

`model` is the configuration file's `model` group (the program's ModelConfig
field names), `params` the served tree: embed [V, E], norm_f [E], lm_head
[E, V], layers.{attn_norm, mlp_norm [L, E]; wq, wk, wv, wo, w_gate, w_up,
w_down [L, in, out]}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


QUERY_BLOCK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    # x [S, H, D]; rotate (x1, x2) halves by pos * theta^(-2i/D)
    half = x.shape[-1] // 2
    inv = theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(h, lp, pos, n_heads, n_kv, theta, eps, window):
    f32 = lambda a: a.astype(jnp.float32)
    S, E = h.shape
    D = lp["wq"].shape[-1] // n_heads
    x = _rms(h, f32(lp["attn_norm"]), eps)
    q = _rope((x @ f32(lp["wq"])).reshape(S, n_heads, D), pos, theta)
    k = _rope((x @ f32(lp["wk"])).reshape(S, n_kv, D), pos, theta)
    v = (x @ f32(lp["wv"])).reshape(S, n_kv, D)
    g = n_heads // n_kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    # queries in blocks of QUERY_BLOCK rows: the same sums, and the scores of a
    # long sequence ([heads, S, S] in float32) never exist whole beside a
    # served model and its cache
    blocks = []
    for s0 in range(0, S, QUERY_BLOCK):
        qs, i = q[s0:s0 + QUERY_BLOCK], pos[s0:s0 + QUERY_BLOCK, None]
        scores = jnp.einsum("shd,thd->hst", qs, k) * (D ** -0.5)
        j = pos[None, :]
        mask = j <= i
        if window > 0:
            mask = mask & (j > i - window)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        blocks.append(jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v))
    attn = jnp.concatenate(blocks, axis=0)
    h = h + attn.reshape(S, n_heads * D) @ f32(lp["wo"])
    x = _rms(h, f32(lp["mlp_norm"]), eps)
    gate = jax.nn.silu(x @ f32(lp["w_gate"]))
    return h + (gate * (x @ f32(lp["w_up"]))) @ f32(lp["w_down"])


def logprobs_at(model: dict, params, tokens: np.ndarray, at: list) -> np.ndarray:
    """log-softmax of the next-token distribution after each position in
    `at`, for one sequence `tokens` [S]: float32 [len(at), V]."""
    with jax.default_matmul_precision("highest"):
        dev = next(iter(params["embed"].devices()))
        tok = jax.device_put(jnp.asarray(tokens, jnp.int32), dev)
        pos = jnp.arange(tok.shape[0], dtype=jnp.int32)
        h = params["embed"][tok].astype(jnp.float32)
        # every layer is global-or-sliding by one rule; these configurations
        # slide in every layer (sw_period 1, residue 1: no layer is global)
        period = int(model.get("sw_period", 2))
        residue = int(model.get("sw_global_residue", 1))
        win = int(model.get("sliding_window", 0))
        step = jax.jit(_layer, static_argnums=(3, 4, 5, 6, 7))
        for l in range(int(model["n_layers"])):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            w = 0 if (win and l % period == residue) else win
            h = step(h, lp, pos, int(model["n_heads"]), int(model["n_kv_heads"]),
                     float(model["rope_theta"]), float(model["norm_eps"]), w)
        h = _rms(h[jnp.asarray(at)], params["norm_f"].astype(jnp.float32),
                 float(model["norm_eps"]))
        logits = h @ params["lm_head"].astype(jnp.float32)
        return np.asarray(jax.nn.log_softmax(logits, axis=-1))
