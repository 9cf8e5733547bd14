"""Phi-4-mini-flash's decoder-hybrid-decoder (`model_type: phi4flash`,
`ModelConfig.mb_per_layer`; arXiv:2507.06607; the equations are
benchmark/reference/phi4flash_decoder.py's).

The self-decoder, layers 0 .. n/2 + 1: Mamba-1 mixers (models/jamba.py's,
without Jamba's three inner norms) on the even layers, differential
attention under `sliding_window` on the odd ones, and as its last layer one
of FULL differential attention. The cross-decoder, layers n/2 + 2 .. n - 1:
gated memory units on the even layers, `W_out (m * silu(W_in x))` with `m`
the scan output (`y + D c`, before its gate) of the self-decoder's last
Mamba layer at the same token, and on the odd layers differential CROSS
attention: a query and an output projection of their own on the keys and
values the full layer cached. Every layer: `h += mixer(LN(h))`, `h +=
fc2(up * silu(gate))` with `[gate | up] = fc1(LN(h))`; LayerNorms with bias,
no position term, the embedding tied as the head (`ModelConfig.layer_kinds`).

Differential attention. Query heads and KV heads are paired; query pair i
reads KV pair j = i // (query pairs a KV pair): `a1 = softmax(q[i,0]
k[j,0]^T s) [v[j,0] | v[j,1]]`, `a2` likewise from the pair's second query
and key, `o_i = RMSNorm(a1 - lam a2; w) (1 - lam0)`, `lam = exp(lq1 . lk1) -
exp(lq2 . lk2) + lam0`, `lam0 = 0.8 - 0.6 exp(-0.3 l)`. The pools hold a KV
pair as ONE head of 2 x head_dim, `[k0 | k1]` and `[v0 | v1]` (the
projection's own layout: a free reshape), and the two softmaxes are ordinary
grouped-query attention on it with the queries zero-padded, `[q0 | 0]` and
`[0 | q1]`, at the scale of ONE head (`head_dim ** -0.5`): the GQA kernels
serve them as they are (ops/paged_attention.py, ops/ragged_paged_attention.py).
Ten pairs take the pools' head axis to 16 (`ModelConfig.pool_heads`): the
heads behind the pairs hold zeros, their queries are zeros and their outputs
are dropped.

What a sequence keeps. The KV pool (`toolkit.make_kv_pool`) holds the full
layer alone, `[1, NP, PS, pool_heads, 2 hd]`: the model's only full-length
cache, read n/4 times a token (once by the layer itself, then by every cross
layer). Beside its pages a sequence has a **state slot** (the Mamba layers'
`S` and convolution inputs: models/jamba.py's pool) AND **window pages**
(the window layers' keys and values for the last `sliding_window` tokens: a
second page table as models/mimo.py's, pages freed as they leave the
window): the step programs take `state = {"state": .., "window": ..}` and
`slots = (the state slots, the window tables)` in the two modules' own
formats, and return the pair last, donated.

The cross-decoder runs on the rows that are sampled. Layers n/2 + 2 .. n - 1
write no cache and no state, so only a row whose logits are read needs
them: every row of a decode step, and of a prefill chunk (alone or as a
segment of the ragged step) the row at `last_index`, `m` and `h` gathered
there. A chunk that does not end its prompt (`sampled` False, traced: one
compiled program) runs no cross-decoder and no head at all.

Parameter tree: embed [V, E]; norm_f {w, b} [E]; layers.{attn_norm_w,
attn_norm_b, mlp_norm_w, mlp_norm_b [L, E]; w_fc1 [L, E, 2 F]; w_fc2 [L, F,
E]} (every layer); mamba.{w_in, w_conv, b_conv, w_x, w_dt, b_dt, A_log, D,
w_out} [Lm, ...]; attn.{wqkv [La, E, (H + 2 Hk) hd], bqkv, wo [La, H hd, E],
bo, lam [La, hd, 4] (columns lq1 lk1 lq2 lk2), subln [La, 2 hd]} (the
window layers, then the full one: model order); cross.{wq [Lc, E, H hd],
bq, wo, bo, lam, subln}; gmu.{w_in [Lg, E, d], w_out [Lg, d, E]}. Every
drawn matrix is `[..., in, out]`; biases, norm weights, A_log, D and b_dt
are fills (benchmark/serve.py draws what init_params draws).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models import jamba, mimo
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import embed_lookup, mm, tied_logits
from dynamo_tpu.models.toolkit import (
    Params,
    SideCacheOps,
    _write_kv,
    layer_norm,
    paged_attention_jnp,
)


# the cross layers' kernel call as a device trace prints it where it runs on
# the sampled rows of a prefill program (in a decode step it is the full
# layer's own call under the decode kernel's own name, eight times a step:
# benchmark/layers/_sambay.py counts a step by them)
CROSS_ROWS_KERNEL = "yoco_cross_attention_rows"


def lambda_init(layer):
    """lam0 of differential attention at model layer `layer` (0-based)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


# --------------------------------------------------------------------------
# init + pools
# --------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    c = config
    kinds = c.layer_kinds
    L, Lm = c.n_layers, kinds.count("mamba")
    La = kinds.count("window") + 1
    Lc, Lg = kinds.count("cross"), kinds.count("gmu")
    E, F, hd = c.dim, c.ffn_dim, c.head_dim
    d, N, R, K = c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
    k = jax.random.split(key, 16)

    def w(kk, fan_in, *shape):
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    zeros = lambda *shape: jnp.zeros(shape, jnp.float32)
    # Mamba-1's published initialisation (models/jamba.init_params)
    steps = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), d))
    b_dt = np.log(np.expm1(steps))

    def diff(ks, n, q_only: bool):
        width = c.n_heads * hd if q_only else (c.n_heads + 2 * c.n_kv_heads) * hd
        name = "wq" if q_only else "wqkv"
        return {
            name: w(ks[0], E, n, E, width),
            "b" + name[1:]: zeros(n, width),
            "wo": w(ks[1], c.n_heads * hd, n, c.n_heads * hd, E),
            "bo": zeros(n, E),
            # the four lambda vectors as columns: drawn at fan-in hd like
            # every other matrix (normal x hd^-0.5)
            "lam": w(ks[2], hd, n, hd, 4).astype(jnp.float32),
            "subln": ones(n, 2 * hd),
        }

    return {
        "embed": w(k[0], E, c.vocab_size, E),
        "norm_f": {"w": ones(E), "b": zeros(E)},
        "layers": {
            "attn_norm_w": ones(L, E), "attn_norm_b": zeros(L, E),
            "mlp_norm_w": ones(L, E), "mlp_norm_b": zeros(L, E),
            "w_fc1": w(k[1], E, L, E, 2 * F),
            "w_fc2": w(k[2], F, L, F, E),
        },
        "mamba": {
            "w_in": w(k[3], E, Lm, E, 2 * d),
            "w_conv": w(k[4], K, Lm, K, d),
            "b_conv": zeros(Lm, d),
            "w_x": w(k[5], d, Lm, d, R + 2 * N),
            "w_dt": w(k[6], R, Lm, R, d),
            "b_dt": jnp.broadcast_to(jnp.asarray(b_dt, jnp.float32), (Lm, d)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (Lm, N, d)),
            "D": ones(Lm, d),
            "w_out": w(k[7], d, Lm, d, E),
        },
        "attn": diff(k[8:11], La, False),
        "cross": diff(k[11:14], Lc, True),
        "gmu": {"w_in": w(k[14], E, Lg, E, d), "w_out": w(k[15], d, Lg, d, E)},
    }


def make_window_pool(config: ModelConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """{"k", "v": [Lw, NPw, PS, Hp, 2 hd]}: zeros; page 0 scratch."""
    c = config
    shape = (c.layer_kinds.count("window"), num_pages, page_size,
             c.pool_heads, 2 * c.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def window_page_bytes(config: ModelConfig, page_size: int, itemsize: int = 2) -> int:
    """Bytes of one window page over all window layers, as the pool holds
    it (`ModelConfig.pool_heads`)."""
    c = config
    return (c.layer_kinds.count("window") * page_size * c.pool_heads
            * 2 * c.head_dim * 2 * itemsize)


# --------------------------------------------------------------------------
# building blocks
# --------------------------------------------------------------------------


def pad_queries(q):
    """q [..., Hp, Gp, 2, hd] (KV pair, query pair on it, the pair's two
    queries) -> [..., Hp, 2 Gp, 2 hd]: `[q0 | 0]` and `[0 | q1]`, the
    queries that read a paired pool head's first and second key."""
    z = jnp.zeros_like(q[..., 0, :])
    q = jnp.stack([jnp.concatenate([q[..., 0, :], z], axis=-1),
                   jnp.concatenate([z, q[..., 1, :]], axis=-1)], axis=-2)
    return q.reshape(q.shape[:-4] + (q.shape[-4], 2 * q.shape[-3], q.shape[-1]))


def diff_combine(a, lam_cols, subln, layer, eps: float):
    """a [..., Hp, 2 Gp, 2 hd], what the two softmaxes of each query pair
    gave on the pair's values -> [..., Hp * Gp * 2 hd]: `RMSNorm(a1 - lam
    a2; subln) (1 - lam0)`, float32 throughout."""
    with jax.named_scope("sambay.diff_combine"):
        lam0 = lambda_init(layer)
        lc = lam_cols.astype(jnp.float32)
        lam = (jnp.exp(jnp.sum(lc[:, 0] * lc[:, 1]))
               - jnp.exp(jnp.sum(lc[:, 2] * lc[:, 3])) + lam0)
        a = a.astype(jnp.float32)
        a = a.reshape(a.shape[:-2] + (a.shape[-2] // 2, 2, a.shape[-1]))
        x = a[..., 0, :] - lam * a[..., 1, :]
        x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        x = x * subln * (1.0 - lam0)
        return x.reshape(x.shape[:-3] + (-1,))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S] (padding = -1)
    k_pool: jax.Array,  # [1, NP, PS, Hp, 2 hd] the full layer's pool
    v_pool: jax.Array,
    page_table: jax.Array,
    kv_lens: jax.Array,
    last_index=None,
    attn_impl: str = "jnp",
    mesh=None,
    ragged=None,  # as models/llama.forward's
    state: Optional[Dict[str, Dict[str, jax.Array]]] = None,  # {"state":
    #   jamba.make_state_pool's, "window": make_window_pool's}
    slots=None,  # (the rows' state slots, their window page tables), each as
    #   models/jamba.forward and models/mimo.forward take theirs. None: the
    #   scratch units.
    sampled=True,  # False (a Python bool, or a traced one: one program): no
    #   row's logits are read, so the cross-decoder and the head are left
    #   out and the logits are zeros
):
    """models/llama.forward for a decoder-hybrid-decoder: the same operands
    and (logits, k_pool, v_pool), then the pair of side pools."""
    c = config
    B, S = tokens.shape
    if mesh is not None and any(mesh.shape.get(a, 1) > 1
                                for a in ("model", "expert", "seq", "pipe")):
        raise NotImplementedError(
            "a decoder-hybrid-decoder is not sharded over a mesh yet")
    if state is None:
        raise ValueError(
            "a decoder-hybrid-decoder's forward needs its state pool and its "
            "window pool")
    if ragged is not None and B != 1:
        raise ValueError("ragged forward takes a single flat [1, T] row")
    if isinstance(k_pool, dict):
        raise NotImplementedError(
            "a decoder-hybrid-decoder's caches are not quantized")
    pallas = attn_impl == "pallas"
    decode = S == 1 and ragged is None
    kinds = c.layer_kinds
    n_pairs = kinds.count("window")  # (Mamba, window) pairs in front
    n_back = kinds.count("cross")  # (GMU, cross) pairs behind
    full_at, mem_at = c.n_layers // 2 + 1, c.n_layers // 2
    E, hd, H = c.dim, c.head_dim, c.n_heads
    Hp, Gp = c.n_kv_heads // 2, c.n_heads // c.n_kv_heads  # KV pairs,
    #   query pairs on each
    HP = c.pool_heads
    F = c.ffn_dim
    T = B * S
    scale = hd ** -0.5

    def to_pool(x, axis: int):
        """Zeros behind the Hp pairs on `axis`, up to the pools' HP heads."""
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, HP - Hp)
        return jnp.pad(x, pad) if HP > Hp else x

    slot_rows, wtabs = slots if slots is not None else (None, None)
    if slot_rows is None:
        slot_rows = jnp.zeros((3, 1) if ragged is not None else (B,), jnp.int32)
        if ragged is not None:  # one segment over the whole axis
            slot_rows = slot_rows.at[2, 0].set(S)
    plan = jamba._plan(positions, slot_rows, ragged is not None)
    if ragged is not None:
        seg_pt, seg_kvl, rmeta = ragged
        tok_wpt, seg_wpt = (wtabs if wtabs is not None else
                            (jnp.zeros_like(page_table), jnp.zeros_like(seg_pt)))
    else:
        seg_pt = seg_kvl = rmeta = seg_wpt = None
        tok_wpt = wtabs if wtabs is not None else jnp.zeros_like(page_table)
    window = jnp.int32(c.sliding_window)
    ssm, wpool = state["state"], state["window"]

    h = embed_lookup(params["embed"], tokens)
    safe_pos = jnp.maximum(positions, 0)
    q_start = safe_pos[:, 0]
    q_len = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)

    # which rows the cross-decoder runs on: every row where no last_index
    # names some (a decode step); else the rows at last_index alone
    flat_pos = positions.reshape(T)
    if last_index is None:
        row_tab = (page_table if (ragged is not None or S == 1)
                   else jnp.repeat(page_table, S, axis=0))
        row_kvl = jnp.where(flat_pos >= 0, flat_pos + 1, 0).astype(jnp.int32)
    elif ragged is not None:
        row_idx, row_tab, row_kvl = last_index.reshape(-1), seg_pt, seg_kvl
    else:
        if B != 1:
            raise NotImplementedError(
                "a decoder-hybrid-decoder takes prefill chunks one at a time "
                "or as segments of the ragged step")
        row_idx = jnp.asarray(last_index, jnp.int32).reshape(1)
        row_tab, row_kvl = page_table, kv_lens

    # each kind's walk of live pages, built once above the layers
    walk_full = walk_win = walk_rows = None
    if pallas and (S == 1 or ragged is not None):
        if ragged is not None:
            from dynamo_tpu.ops.ragged_paged_attention import ragged_walk

            walk_full = ragged_walk((HP, 2 * Gp), k_pool, v_pool, seg_pt,
                                    seg_kvl, rmeta, None, S)
            walk_win = ragged_walk((HP, 2 * Gp), wpool["k"], wpool["v"],
                                   seg_wpt, seg_kvl, rmeta, window, S)
        else:
            from dynamo_tpu.ops.paged_attention import decode_walk

            walk_full = decode_walk((HP, 2 * Gp), k_pool, v_pool, page_table,
                                    kv_lens, None, False)
            walk_win = decode_walk((HP, 2 * Gp), wpool["k"], wpool["v"],
                                   tok_wpt, kv_lens, window, False)
            walk_rows = walk_full  # a decode step's rows are its batch
    if pallas and walk_rows is None:
        from dynamo_tpu.ops.paged_attention import decode_walk

        walk_rows = decode_walk((HP, 2 * Gp), k_pool, v_pool, row_tab,
                                row_kvl, None, False)

    def mlp(h, lp):
        with jax.named_scope("ffn"):
            x = layer_norm(h, lp["mlp_norm_w"], lp["mlp_norm_b"], c.norm_eps)
            gu = mm(x, lp["w_fc1"])
            return h + mm(gu[..., F:] * jax.nn.silu(gu[..., :F]), lp["w_fc2"])

    def layer_of(l):
        return jax.tree.map(lambda a: a[l], params["layers"])

    def mamba_layer(h, ssm, l, m_idx, scan_out: bool = False):
        lp = layer_of(l)
        mp = jax.tree.map(lambda a: a[m_idx], params["mamba"])
        x = layer_norm(h, lp["attn_norm_w"], lp["attn_norm_b"],
                       c.norm_eps).reshape(T, E)
        out, ssm, *m = jamba._mamba_mixer(
            c, mp, x, plan, ssm, m_idx, attn_impl, inner_norms=False,
            scan_out=scan_out)
        return (mlp(h + out.reshape(B, S, E), lp), ssm) + tuple(m)

    def self_attention(h, l, rank, kp, vp, windowed: bool):
        """Differential attention of model layer `l` (`rank` among
        params["attn"]) on its own cache (kp, vp) at the cache's layer
        `at`: the window pool's `rank`, the KV pool's 0."""
        lp = layer_of(l)
        ap = jax.tree.map(lambda a: a[rank], params["attn"])
        at = jnp.asarray(rank if windowed else 0, jnp.int32)
        table = tok_wpt if windowed else page_table
        seg_table = seg_wpt if windowed else seg_pt
        win = window if windowed else None
        walk = walk_win if windowed else walk_full
        names = mimo.WINDOW_KERNELS if windowed else {}
        with jax.named_scope("attn.proj"):
            x = layer_norm(h, lp["attn_norm_w"], lp["attn_norm_b"], c.norm_eps)
            qkv = mm(x, ap["wqkv"]) + ap["bqkv"].astype(x.dtype)
            q = to_pool(pad_queries(
                qkv[..., :H * hd].reshape(B, S, Hp, Gp, 2, hd)), -3)
            k = to_pool(qkv[..., H * hd:(H + c.n_kv_heads) * hd]
                        .reshape(B, S, Hp, 2 * hd), -2)
            v = to_pool(qkv[..., (H + c.n_kv_heads) * hd:]
                        .reshape(B, S, Hp, 2 * hd), -2)
        if ragged is not None:
            kp = _write_kv(kp, at, k.reshape(S, 1, HP, 2 * hd), table,
                           positions.reshape(S, 1))
            vp = _write_kv(vp, at, v.reshape(S, 1, HP, 2 * hd), table,
                           positions.reshape(S, 1))
        else:
            kp = _write_kv(kp, at, k, table, positions)
            vp = _write_kv(vp, at, v, table, positions)
        with jax.named_scope("attn.window" if windowed else "attn.kernel"):
            kw = dict(scale=scale)
            if ragged is not None and pallas:
                from dynamo_tpu.ops.ragged_paged_attention import ragged_paged_attention

                a = ragged_paged_attention(
                    q[0], kp, vp, seg_table, seg_kvl, rmeta, win, at, walk,
                    name=names.get("ragged"), **kw)[None]
            elif ragged is not None:
                a = paged_attention_jnp(
                    q[0][:, None], kp[at], vp[at], table,
                    safe_pos.reshape(S, 1), kv_lens, window=win, **kw)[:, 0][None]
            elif pallas and S == 1:
                from dynamo_tpu.ops.paged_attention import decode_paged_attention

                a = decode_paged_attention(
                    q[:, 0], kp, vp, table, kv_lens, win, at, walk,
                    name=names.get("decode"), **kw)[:, None]
            elif pallas:
                from dynamo_tpu.ops.flash_prefill import prefill_paged_attention

                a = prefill_paged_attention(
                    q, kp, vp, table, q_start, q_len, kv_lens, win, at,
                    name=names.get("prefill"), **kw)
            else:
                a = paged_attention_jnp(q, kp[at], vp[at], table, safe_pos,
                                        kv_lens, window=win, **kw)
        with jax.named_scope("attn.proj"):
            o = diff_combine(a[..., :Hp, :, :], ap["lam"], ap["subln"], l,
                             c.norm_eps)
            h = h + mm(o.astype(h.dtype), ap["wo"]) + ap["bo"].astype(h.dtype)
        return mlp(h, lp), kp, vp

    # -- the self-decoder: every token --------------------------------------
    def front(carry, i):
        h, ssm, wk, wv = carry
        h, ssm = mamba_layer(h, ssm, 2 * i, i)
        h, wk, wv = self_attention(h, 2 * i + 1, i, wk, wv, True)
        return (h, ssm, wk, wv), None

    (h, ssm, wk, wv), _ = lax.scan(
        front, (h, ssm, wpool["k"], wpool["v"]),
        jnp.arange(n_pairs, dtype=jnp.int32))
    h, ssm, m = mamba_layer(h, ssm, mem_at, n_pairs, scan_out=True)
    h, k_pool, v_pool = self_attention(h, full_at, n_pairs, k_pool, v_pool, False)
    state = {"state": ssm, "window": {"k": wk, "v": wv}}

    # -- the cross-decoder: the rows that are sampled ------------------------
    def cross_decoder(h, m):
        """h [R, E], m [R, d] f32 -> logits [R, V] f32: the (GMU, cross
        attention) pairs, the final norm and the head, a row a query of
        context row_kvl under row_tab."""
        def back(h, j):
            l = full_at + 1 + 2 * j
            lp = layer_of(l)
            gp = jax.tree.map(lambda a: a[j], params["gmu"])
            with jax.named_scope("sambay.gmu"):
                x = layer_norm(h, lp["attn_norm_w"], lp["attn_norm_b"], c.norm_eps)
                g = m * jax.nn.silu(mm(x, gp["w_in"]).astype(jnp.float32))
                h = mlp(h + mm(g.astype(h.dtype), gp["w_out"]), lp)
            lp = layer_of(l + 1)
            cp = jax.tree.map(lambda a: a[j], params["cross"])
            with jax.named_scope("yoco.cross_attn"):
                x = layer_norm(h, lp["attn_norm_w"], lp["attn_norm_b"], c.norm_eps)
                q = mm(x, cp["wq"]) + cp["bq"].astype(x.dtype)
                q = to_pool(pad_queries(q.reshape(-1, Hp, Gp, 2, hd)), -3)
                if pallas:
                    from dynamo_tpu.ops.paged_attention import decode_paged_attention

                    a = decode_paged_attention(
                        q, k_pool, v_pool, row_tab, row_kvl, None,
                        jnp.int32(0), walk_rows, scale=scale,
                        name=None if decode else CROSS_ROWS_KERNEL)
                else:
                    a = paged_attention_jnp(
                        q[:, None], k_pool[0], v_pool[0], row_tab,
                        jnp.maximum(row_kvl - 1, 0)[:, None], row_kvl,
                        scale=scale)[:, 0]
                o = diff_combine(a[:, :Hp], cp["lam"], cp["subln"], l + 1,
                                 c.norm_eps)
                h = h + mm(o.astype(h.dtype), cp["wo"]) + cp["bo"].astype(h.dtype)
            return mlp(h, lp), None

        h, _ = lax.scan(back, h, jnp.arange(n_back, dtype=jnp.int32))
        with jax.named_scope("lm_head"):
            h = layer_norm(h, params["norm_f"]["w"], params["norm_f"]["b"],
                           c.norm_eps)
            return tied_logits(h, params["embed"]).astype(jnp.float32)

    h = h.reshape(T, E)
    if last_index is not None:
        h, m = h[row_idx], m[row_idx]
    skip = lambda h, m: jnp.zeros((h.shape[0], c.vocab_size), jnp.float32)
    if isinstance(sampled, bool):
        logits = (cross_decoder if sampled else skip)(h, m)
    else:
        logits = lax.cond(sampled, cross_decoder, skip, h, m)
    if last_index is not None:
        logits = logits[None] if ragged is not None else logits[:, None]
    else:
        logits = logits.reshape(B, S, -1)
    return logits, k_pool, v_pool, state


# --------------------------------------------------------------------------
# what the runner asks of a model with a cache beside its pages: the two
# kinds that exist, composed (engine/side_cache.py)
# --------------------------------------------------------------------------

def _side_make_pool(config: ModelConfig, units, page_size: int, dtype):
    slots, pages = units
    return {"state": jamba.make_state_pool(config, slots, conv_dtype=dtype),
            "window": make_window_pool(config, pages, page_size, dtype)}


def _side_unit_bytes(config: ModelConfig, page_size: int, dtype):
    return (jamba.state_slot_bytes(config, conv_dtype=dtype),
            window_page_bytes(config, page_size, jnp.dtype(dtype).itemsize))


def _split(sides):
    """A sequence's (state slot, window table) pairs -> the two lists; a
    pad row (None) is scratch in both."""
    return ([s[0] if s else None for s in sides],
            [s[1] if s else None for s in sides])


def _side_rows(sides, B: int, max_pages: int):
    slots, tables = _split(sides)
    return (jamba.SIDE.rows(slots, B, max_pages),
            mimo.SIDE.rows(tables, B, max_pages))


def _side_segs(sides, lens, seg_cap: int, t_bucket: int, max_pages: int):
    slots, tables = _split(sides)
    return (jamba.SIDE.segs(slots, lens, seg_cap, t_bucket, max_pages),
            mimo.SIDE.segs(tables, lens, seg_cap, t_bucket, max_pages))


SIDE = SideCacheOps("state+window", _side_make_pool, _side_unit_bytes,
                    _side_rows, _side_segs, forward)
