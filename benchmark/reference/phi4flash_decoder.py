"""Plain reference of Phi-4-mini-flash's decoder-hybrid-decoder (`model_type:
phi4flash`, `mb_per_layer` 2; arXiv:2507.06607): float32 jax.numpy, no cache,
no state pool, no window pool, no kernels, no batching, one layer at a time
from the served bf16 tree, matmuls at `highest` precision, the state-space
recurrence a `lax.scan` over the tokens, EVERY layer on EVERY token.

Every layer: x = x + mixer(LN(x)); x = x + fc2(up * silu(gate)), [gate | up] =
fc1(LN(x)); LN a LayerNorm with bias. With n layers (n % 4 == 0), layer l is

    l even, l <= n/2      a Mamba-1 mixer (no inner norms):
        [a, z] = u W_in;  c_t = silu(b_conv + sum_j w_conv[j] a_{t-K+1+j})
        [dt', B, C] = c_t W_x;  dt = softplus(dt' W_dt + b_dt)
        S_t = exp(dt (x) A) S_{t-1} + (dt c_t) (x) B,  A = -exp(A_log)
        m_t = S_t C + D c_t;   out = (m_t silu(z_t)) W_out
    l odd, l < n/2        differential attention under `sliding_window`
    l = n/2 + 1           differential attention, full; its k, v are KEPT
    l even, l >= n/2 + 2  a gated memory unit: (m * silu(u W_in)) W_out with m
                          layer n/2's m_t of the same token
    l odd, l >= n/2 + 3   differential CROSS attention: q = u W_q + b_q on the
                          k, v layer n/2 + 1 kept, full

Differential attention, the heads paired (q [S, H/2, 2, hd], k, v [S, Hk/2,
2, hd], query pair i on KV pair j = i // (H / Hk)), no position term:

    a1 = softmax(q[i,0] k[j,0]^T / sqrt(hd)) [v[j,0] | v[j,1]]
    a2 = softmax(q[i,1] k[j,1]^T / sqrt(hd)) [v[j,0] | v[j,1]]
    o_i = RMSNorm_{2 hd}(a1 - lam a2; subln) (1 - lam0)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 l)

then o W_o + b_o. After the last layer LN(x; norm_f), logits x E^T with the
embedding E. These equations are ISSUE 54's reading of the published
modelling file and of the paper (no copy of either was on the builder's
machine): benchmark/configs/phi-4-mini-flash-reasoning.json `assumed` lists
what the config's keys do not state.

`model` is the configuration file's `model` group (the program's ModelConfig
field names), `params` the served tree (dynamo_tpu/models/sambay.py's
docstring): every matrix `[in, out]`, the convolution `[K, d]`, `lam` `[hd,
4]` with columns lq1 lk1 lq2 lk2; `attn` holds the window layers and then the
full one, `cross`, `gmu` and `mamba` their layers in model order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


QUERY_BLOCK = 512
VOCAB_BLOCK = 32768  # rows of the embedding turned to float32 at a time


def layer_kinds(n_layers: int) -> list:
    half = n_layers // 2
    return [("mamba" if l <= half else "gmu") if l % 2 == 0 else
            "window" if l < half else "full" if l == half + 1 else "cross"
            for l in range(n_layers)]


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _diff_attention(q, k, v, ap, layer, window, eps):
    """q [S, H, hd], k, v [S, Hk, hd] -> [S, H hd]: the paired heads'
    differential attention, causal, under `window` (0: full)."""
    S, H, hd = q.shape
    Hk = k.shape[1]
    q = q.reshape(S, H // 2, 2, hd)
    rep = H // Hk
    k = jnp.repeat(k.reshape(S, Hk // 2, 2, hd), rep, axis=1)  # [S, H/2, 2, hd]
    v = jnp.repeat(v.reshape(S, Hk // 2, 2 * hd), rep, axis=1)  # [v0 | v1]
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))
    lq1, lk1, lq2, lk2 = (ap["lam"][:, i] for i in range(4))
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    pos = jnp.arange(S)
    blocks = []
    for s0 in range(0, S, QUERY_BLOCK):
        qp = pos[s0:s0 + QUERY_BLOCK]
        mask = pos[None, :] <= qp[:, None]
        if window:
            mask = mask & (pos[None, :] > qp[:, None] - window)
        scores = jnp.einsum("spcd,tpcd->pcst", q[s0:s0 + QUERY_BLOCK], k) * (hd ** -0.5)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        a = jnp.einsum("pcst,tpe->spce", jax.nn.softmax(scores, axis=-1), v)
        x = a[:, :, 0] - lam * a[:, :, 1]  # [s, H/2, 2 hd]
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        blocks.append(x * ap["subln"] * (1.0 - lam0))
    o = jnp.concatenate(blocks, axis=0).reshape(S, H * hd)
    return o @ ap["wo"] + ap["bo"]


def _mamba(x, mp):
    """-> (the mixer's output [S, E], the scan's output m [S, d])."""
    S = x.shape[0]
    K, d = mp["w_conv"].shape
    N = mp["A_log"].shape[0]
    R = mp["w_dt"].shape[0]
    az = x @ mp["w_in"]
    a, z = az[:, :d], az[:, d:]
    ap = jnp.concatenate([jnp.zeros((K - 1, d), jnp.float32), a], axis=0)
    c = jax.nn.silu(mp["b_conv"] + sum(mp["w_conv"][j] * ap[j:j + S] for j in range(K)))
    dbc = c @ mp["w_x"]
    dt = jax.nn.softplus(dbc[:, :R] @ mp["w_dt"] + mp["b_dt"])
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(mp["A_log"])  # [N, d]

    def step(state, inp):
        dt_t, c_t, b_t, c_out = inp
        state = jnp.exp(dt_t[None, :] * A) * state + (dt_t * c_t)[None, :] * b_t[:, None]
        return state, jnp.sum(state * c_out[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, d), jnp.float32), (dt, c, Bm, Cm))
    m = y + mp["D"] * c
    return (m * jax.nn.silu(z)) @ mp["w_out"], m


def _layer(h, carried, lp, mix, kind, layer, n_heads, n_kv, window, eps):
    """One layer. `carried`: (m, k, v) as the layers before left them (the
    memory of layer n/2, the full layer's keys and values); returned as this
    layer leaves them."""
    lp, mix = _f32(lp), _f32(mix)
    m, k_kept, v_kept = carried
    x = _ln(h, lp["attn_norm_w"], lp["attn_norm_b"], eps)
    S = x.shape[0]
    if kind == "mamba":
        out, m = _mamba(x, mix)
    elif kind == "gmu":
        out = (m * jax.nn.silu(x @ mix["w_in"])) @ mix["w_out"]
    elif kind == "cross":
        hd = mix["wq"].shape[-1] // n_heads
        q = (x @ mix["wq"] + mix["bq"]).reshape(S, n_heads, hd)
        out = _diff_attention(q, k_kept, v_kept, mix, layer, 0, eps)
    else:
        hd = mix["wqkv"].shape[-1] // (n_heads + 2 * n_kv)
        qkv = x @ mix["wqkv"] + mix["bqkv"]
        q = qkv[:, :n_heads * hd].reshape(S, n_heads, hd)
        k = qkv[:, n_heads * hd:(n_heads + n_kv) * hd].reshape(S, n_kv, hd)
        v = qkv[:, (n_heads + n_kv) * hd:].reshape(S, n_kv, hd)
        out = _diff_attention(q, k, v, mix, layer,
                              window if kind == "window" else 0, eps)
        if kind == "full":
            k_kept, v_kept = k, v
    h = h + out
    x = _ln(h, lp["mlp_norm_w"], lp["mlp_norm_b"], eps)
    gu = x @ lp["w_fc1"]
    F = gu.shape[-1] // 2
    h = h + (gu[:, F:] * jax.nn.silu(gu[:, :F])) @ lp["w_fc2"]
    return h, (m, k_kept, v_kept)


def logprobs_at(model: dict, params, tokens: np.ndarray, at: list) -> np.ndarray:
    """log-softmax of the next-token distribution after each position in
    `at`, for one sequence `tokens` [S]: float32 [len(at), V]."""
    with jax.default_matmul_precision("highest"):
        dev = next(iter(params["embed"].devices()))
        tok = jax.device_put(jnp.asarray(tokens, jnp.int32), dev)
        h = params["embed"][tok].astype(jnp.float32)
        n_heads, n_kv = int(model["n_heads"]), int(model["n_kv_heads"])
        window, eps = int(model["sliding_window"]), float(model["norm_eps"])
        # (the layer's index is traced: one program a kind and a length)
        step = jax.jit(_layer, static_argnums=(4, 6, 7, 8, 9))
        stack_of = {"mamba": "mamba", "window": "attn", "full": "attn",
                    "gmu": "gmu", "cross": "cross"}
        seen = dict.fromkeys(stack_of.values(), 0)
        S = len(tokens)
        d = params["mamba"]["w_out"].shape[1]
        hd = params["attn"]["lam"].shape[1]
        carried = (jnp.zeros((S, d), jnp.float32),
                   jnp.zeros((S, n_kv, hd), jnp.float32),
                   jnp.zeros((S, n_kv, hd), jnp.float32))
        for l, kind in enumerate(layer_kinds(int(model["n_layers"]))):
            stack = stack_of[kind]
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            mix = jax.tree.map(lambda a: a[seen[stack]], params[stack])
            seen[stack] += 1
            h, carried = step(h, carried, lp, mix, kind, l, n_heads, n_kv,
                              window, eps)
            # one layer on the device at a time: a buffer is allocated when
            # its program is enqueued, and the host would enqueue every
            # layer's slices and float32 copies before the first has run
            # (3.5 GB beside a served model's pools, PERF.md PR 54)
            jax.block_until_ready(h)
        nf = _f32(params["norm_f"])
        h = _ln(h[jnp.asarray(at)], nf["w"], nf["b"], eps)
        V = params["embed"].shape[0]
        blocks = []
        for v0 in range(0, V, VOCAB_BLOCK):  # (and one block of the head)
            blocks.append(jax.block_until_ready(
                h @ params["embed"][v0:v0 + VOCAB_BLOCK].astype(jnp.float32).T))
        logits = jnp.concatenate(blocks, axis=-1)
        return np.asarray(jax.nn.log_softmax(logits, axis=-1))
