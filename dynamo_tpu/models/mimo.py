"""MiMo-V2-Flash's decoder: attention layers of two kinds said layer by layer
(`ModelConfig.layer_pattern`: 0 global, 1 under a sliding window), a dense
SwiGLU MLP in the first `n_dense_layers` layers and sigmoid-routed experts
in the rest (`model_type: mimo_v2_flash`; the equations are
benchmark/reference/mimo_v2_decoder.py's).

What differs from models/llama.py, and why this is a forward of its own:
the layers are not alike. A global layer has `n_kv_heads` KV heads and
rotates with `rope_theta`; a window layer has `n_kv_heads_window`, rotates
with `rope_local_theta`, sees the last `sliding_window` tokens and has a
learned sink logit a query head. `wk` / `wv` have another shape in each
kind, so one `lax.scan` over a stack of identical layers cannot carry the
model: the walk is runs of layers of one kind (a run of several is one scan
over its indices, the stacks read in place), as models/jamba.py walks
mixers and attention. In every layer keys are `head_dim` wide and values
`value_dim`, the rotary turns the first `rope_partial_dims` dims of a head
(`toolkit.rope` on that slice) and the values are scaled by
`attn_value_scale` before attention. The feed-forward is `moe._moe_block`
(a held share, the work-list kernel on a decode step) or the dense SwiGLU.

Two caches. The KV pool (`toolkit.make_kv_pool`) holds the global layers
alone, `[Lg, NP, PS, Hk, dk]` keys and `[.., dv]` values; the WINDOW pool
(`make_window_pool`) the window layers, `{"k": [Lw, NPw, PS, Hkw, dk], "v":
[.., dv]}`; a key wider than one 128-lane row takes whole rows in both
(`ModelConfig.key_pool_dim`: 192 -> 256, zeros behind it). A sequence has
a page table in each, indexed by the same logical page; the engine frees a
window page once it lies wholly below what any later query can see and
points its entry at scratch page 0, which no kernel's walk visits
(`ops.paged_attention.live_pages`). Every step
program takes the window pool as `state` and the window tables as `slots`
(the operands a state-holding model's programs already have) and returns
the pool last, donated.

Parameter tree: embed [V, E], norm_f [E], lm_head [E, V]; layers_dense
{attn_norm, mlp_norm, w_gate, w_up, w_down} [n_dense, ...]; layers
{attn_norm, mlp_norm, w_router, router_bias, we_gate, we_up, we_down}
[L_moe, ...] (the expert axis is the held share's); attn_global / attn_window
{wq, wk, wv, wo (+ sink_bias [L, H] where the kind has sinks)} in model
order. Every drawn matrix is `[..., in, out]`; norm weights, `router_bias`
and `sink_bias` are fills (`sink_bias` a ramp over the heads, see
`init_params`).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.moe import EXPERT_STACKS, _moe_block, experts_kernel_stack
from dynamo_tpu.models.quant import embed_lookup, mm
from dynamo_tpu.models.toolkit import (
    Params,
    SideCacheOps,
    _write_kv,
    paged_attention_jnp,
    rms_norm,
    rope,
    rope_inv_freq,
)

GLOBAL, WINDOW = 0, 1
# the window kernels' names as a device trace prints them (benchmark/layers
# finds each kind by name; both hold `attention`, one call a layer a step)
WINDOW_KERNELS = {"decode": "window_attention_decode",
                  "ragged": "window_attention_ragged",
                  "prefill": "window_attention_prefill"}
# the fill of `sink_bias`: a ramp over the query heads. No equation gives a
# sink from the key, and a zero sink is 1/129 of a window's mass, which no
# tolerance would miss; on the top heads of this ramp it is about a third
SINK_RAMP = (-1.0, 4.0)


def kv_heads(c: ModelConfig, kind: int) -> int:
    return c.n_kv_heads_window if kind == WINDOW else c.n_kv_heads


def has_sink(c: ModelConfig, kind: int) -> bool:
    return c.sink_window if kind == WINDOW else c.sink_global


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    c = config
    E, H, dk, dv = c.dim, c.n_heads, c.head_dim, c.value_dim
    nd, Lm = c.n_dense_layers, c.n_layers - c.n_dense_layers
    k = jax.random.split(key, 20)

    def w(kk, fan_in, *shape):
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)

    def attn(kind, ks):
        L = len(c.window_layers if kind == WINDOW else c.global_layers)
        Hk = kv_heads(c, kind)
        p = {
            "wq": w(ks[0], E, L, E, H * dk),
            "wk": w(ks[1], E, L, E, Hk * dk),
            "wv": w(ks[2], E, L, E, Hk * dv),
            "wo": w(ks[3], H * dv, L, H * dv, E),
        }
        if has_sink(c, kind):
            p["sink_bias"] = jnp.broadcast_to(
                jnp.linspace(*SINK_RAMP, H, dtype=jnp.float32), (L, H))
        return p

    params: Params = {
        "embed": w(k[0], E, c.vocab_size, E),
        "norm_f": ones(E),
        "lm_head": w(k[1], E, E, c.vocab_size),
        "attn_global": attn(GLOBAL, k[2:6]),
        "attn_window": attn(WINDOW, k[6:10]),
        "layers": {
            "attn_norm": ones(Lm, E),
            "mlp_norm": ones(Lm, E),
            "w_router": w(k[10], E, Lm, E, c.n_experts),
            "we_gate": w(k[11], E, Lm, c.experts_held, E, c.moe_ffn_dim),
            "we_up": w(k[12], E, Lm, c.experts_held, E, c.moe_ffn_dim),
            "we_down": w(k[13], c.moe_ffn_dim, Lm, c.experts_held, c.moe_ffn_dim, E),
        },
    }
    if c.moe_router_bias:
        params["layers"]["router_bias"] = jnp.zeros((Lm, c.n_experts), jnp.float32)
    if nd:
        params["layers_dense"] = {
            "attn_norm": ones(nd, E),
            "mlp_norm": ones(nd, E),
            "w_gate": w(k[14], E, nd, E, c.ffn_dim),
            "w_up": w(k[15], E, nd, E, c.ffn_dim),
            "w_down": w(k[16], c.ffn_dim, nd, c.ffn_dim, E),
        }
    return params


def make_window_pool(config: ModelConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """{"k": [Lw, NPw, PS, Hkw, dk], "v": [.., dv]}: zeros; page 0 scratch."""
    c = config
    shape = (len(c.window_layers), num_pages, page_size, c.n_kv_heads_window)
    return {"k": jnp.zeros(shape + (c.key_pool_dim,), dtype),
            "v": jnp.zeros(shape + (c.value_dim,), dtype)}


def window_page_bytes(config: ModelConfig, page_size: int, itemsize: int = 2) -> int:
    """Bytes of one window page over all window layers, as the pool holds
    it (keys at `key_pool_dim`)."""
    c = config
    return (len(c.window_layers) * page_size * c.n_kv_heads_window
            * (c.key_pool_dim + c.value_dim) * itemsize)


def window_pages_needed(window: int, page_size: int, tokens: int) -> int:
    """Window pages a run of `tokens` consecutive queries needs at once: the
    pages from the one that holds the first query's lowest visible position
    to the one of the last query, wherever the run starts."""
    return (window + tokens - 2) // page_size + 2


def window_first_live_page(first_query: int, window: int, page_size: int) -> int:
    """The lowest logical page any query at `first_query` or later sees under
    the window: pages below it are dead for good (`live_pages`' rule)."""
    return max(first_query - window + 1, 0) // page_size


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S] (padding = -1)
    k_pool: jax.Array,  # [Lg, NP, PS, Hk, dk] the global layers' pool
    v_pool: jax.Array,  # [Lg, NP, PS, Hk, dv]
    page_table: jax.Array,
    kv_lens: jax.Array,
    last_index=None,
    attn_impl: str = "jnp",
    mesh=None,
    ragged=None,  # as models/llama.forward's
    state: Optional[Dict[str, jax.Array]] = None,  # make_window_pool's
    slots=None,  # the window page tables: int32 [B, MP] beside `page_table`;
    #   on the ragged step (tok_wpt [T, MP], seg_wpt [SEG, MP]). None: every
    #   entry scratch (a warm-up's dummies).
    return_routed: bool = False,
    return_listed: bool = False,
):
    """models/llama.forward for a window-pool model: the same operands and
    (logits, k_pool, v_pool[, picks[, listed]]), then the window pool."""
    c = config
    B, S = tokens.shape
    if mesh is not None and any(mesh.shape.get(a, 1) > 1
                                for a in ("model", "expert", "seq", "pipe")):
        raise NotImplementedError(
            "a window-pool model is not sharded over a mesh yet")
    if state is None:
        raise ValueError("a window-pool model's forward needs its window pool")
    if ragged is not None and B != 1:
        raise ValueError("ragged forward takes a single flat [1, T] row")
    if return_listed and not return_routed:
        raise ValueError("return_listed rides on return_routed")
    H, dk, dv = c.n_heads, c.head_dim, c.value_dim
    pallas = attn_impl == "pallas"
    if ragged is not None:
        seg_pt, seg_kvl, rmeta = ragged
        tok_wpt, seg_wpt = (slots if slots is not None else
                            (jnp.zeros_like(page_table), jnp.zeros_like(seg_pt)))
        tables = {GLOBAL: (page_table, seg_pt), WINDOW: (tok_wpt, seg_wpt)}
    else:
        wpt = slots if slots is not None else jnp.zeros_like(page_table)
        tables = {GLOBAL: (page_table, None), WINDOW: (wpt, None)}
    pools = {GLOBAL: (k_pool, v_pool), WINDOW: (state["k"], state["v"])}
    window_of = {GLOBAL: None, WINDOW: jnp.int32(c.sliding_window)}
    inv_freq = {
        GLOBAL: rope_inv_freq(None, c.rope_partial_dims or dk, c.rope_theta),
        WINDOW: rope_inv_freq(None, c.rope_partial_dims or dk,
                              c.rope_local_theta or c.rope_theta)}

    h = embed_lookup(params["embed"], tokens)
    safe_pos = jnp.maximum(positions, 0)
    real_rows = positions >= 0
    q_start = safe_pos[:, 0]
    q_len = jnp.sum(real_rows.astype(jnp.int32), axis=1)

    # each kind's walk of live pages, built once above the layers (see
    # models/llama.forward): the window layers' stops at the window's edge,
    # so a freed page's table entry is never read
    walks = {GLOBAL: None, WINDOW: None}
    if pallas and (S == 1 or ragged is not None):
        for kind in (GLOBAL, WINDOW):
            Hk = kv_heads(c, kind)
            if ragged is not None:
                from dynamo_tpu.ops.ragged_paged_attention import ragged_walk

                walks[kind] = ragged_walk(
                    (Hk, H // Hk), *pools[kind], tables[kind][1], seg_kvl,
                    rmeta, window_of[kind], S)
            else:
                from dynamo_tpu.ops.paged_attention import decode_walk

                walks[kind] = decode_walk(
                    (Hk, H // Hk), *pools[kind], tables[kind][0], kv_lens,
                    window_of[kind], has_sink(c, kind))

    def partial_rope(x, kind):
        """The rotary on the first `rope_partial_dims` dims of each head."""
        r = c.rope_partial_dims
        if not r or r == dk:
            return rope(x, safe_pos, 0.0, inv_freq=inv_freq[kind])
        return jnp.concatenate(
            [rope(x[..., :r], safe_pos, 0.0, inv_freq=inv_freq[kind]),
             x[..., r:]], axis=-1)

    def attention(h, lp, ap, kind, rank, kp, vp):
        """One attention layer of `kind` on its own pool (kp, vp) at layer
        `rank` of it: the GQA body of models/llama.py's layer with what
        this model adds (two head sizes, the partial rotary, the value
        scale, the sink), on the kernels and the jnp form alike."""
        Hk = kv_heads(c, kind)
        G = H // Hk
        l_idx = jnp.asarray(rank, jnp.int32)
        table, seg_table = tables[kind]
        window = window_of[kind]
        with jax.named_scope("attn.proj"):
            x = rms_norm(h, lp["attn_norm"], c.norm_eps)
            q = partial_rope(mm(x, ap["wq"]).reshape(B, S, H, dk), kind)
            k = partial_rope(mm(x, ap["wk"]).reshape(B, S, Hk, dk), kind)
            v = mm(x, ap["wv"]).reshape(B, S, Hk, dv)
            if c.attn_value_scale:
                v = v * jnp.asarray(c.attn_value_scale, v.dtype)
            if c.key_pool_dim != dk:
                # keys as the pool holds them (`key_pool_dim`): zeros behind
                # each key and each query, which add nothing to q . k
                pad = [(0, 0)] * 3 + [(0, c.key_pool_dim - dk)]
                q, k = jnp.pad(q, pad), jnp.pad(k, pad)
        if ragged is not None:
            kp = _write_kv(kp, l_idx, k.reshape(S, 1, Hk, -1), table,
                           positions.reshape(S, 1))
            vp = _write_kv(vp, l_idx, v.reshape(S, 1, Hk, dv), table,
                           positions.reshape(S, 1))
        else:
            kp = _write_kv(kp, l_idx, k, table, positions)
            vp = _write_kv(vp, l_idx, v, table, positions)
        sink = None
        if has_sink(c, kind):
            sink = ap["sink_bias"].astype(jnp.float32).reshape(Hk, G)

        def kv_slab():
            return kp[rank], vp[rank]

        scope = "attn.window" if kind == WINDOW else "attn.kernel"
        names = WINDOW_KERNELS if kind == WINDOW else {}
        with jax.named_scope(scope):
            qg = q.reshape(B, S, Hk, G, -1)
            kw = dict(scale=dk ** -0.5)
            if ragged is not None and pallas:
                from dynamo_tpu.ops.ragged_paged_attention import ragged_paged_attention

                attn = ragged_paged_attention(
                    qg[0], kp, vp, seg_table, seg_kvl, rmeta, window, l_idx,
                    walks[kind], sink=sink, name=names.get("ragged"), **kw)[None]
            elif ragged is not None:
                attn = paged_attention_jnp(
                    qg[0][:, None], *kv_slab(), table, safe_pos.reshape(S, 1),
                    kv_lens, window=window, sink=sink, **kw)[:, 0][None]
            elif pallas and S == 1:
                from dynamo_tpu.ops.paged_attention import decode_paged_attention

                attn = decode_paged_attention(
                    qg[:, 0], kp, vp, table, kv_lens, window, l_idx,
                    walks[kind], sink=sink, name=names.get("decode"), **kw)[:, None]
            elif pallas:
                from dynamo_tpu.ops.flash_prefill import prefill_paged_attention

                attn = prefill_paged_attention(
                    qg, kp, vp, table, q_start, q_len, kv_lens, window, l_idx,
                    sink=sink, name=names.get("prefill"), **kw)
            else:
                attn = paged_attention_jnp(
                    qg, *kv_slab(), table, safe_pos, kv_lens, window=window,
                    sink=sink, **kw)
        with jax.named_scope("attn.proj"):
            h = h + mm(attn.reshape(B, S, H * dv), ap["wo"])
        return h, kp, vp

    moe_stack = experts_kernel_stack(c, params["layers"], B * S, mesh, attn_impl)

    def ffn(h, lp, i_ffn, routed: bool):
        """The layer's feed-forward: the dense SwiGLU, or the routed experts
        (`i_ffn`: the layer's index among the expert layers)."""
        with jax.named_scope("ffn"):
            x = rms_norm(h, lp["mlp_norm"], c.norm_eps)
            if not routed:
                gate = jax.nn.silu(mm(x, lp["w_gate"]))
                return h + mm(gate * mm(x, lp["w_up"]), lp["w_down"]), None
            stack = None if moe_stack is None else moe_stack + (i_ffn,)
            out, sel, listed = _moe_block(c, lp, x, mesh, real_rows, stack)
            return h + out, (sel, listed)

    def one_layer(carry, l, rank, kind: int, routed: bool):
        """Layer `l` (traced in a run's scan), `rank` its rank among the
        layers of its `kind`; `routed`: experts, not the dense MLP."""
        h, pools = carry
        stack_name = "layers" if routed else "layers_dense"
        i_ffn = l - c.n_dense_layers if routed else l
        skip = EXPERT_STACKS if (routed and moe_stack is not None) else ()
        # (a weight under `quantize` is a dict of data and scales)
        lp = {n: jax.tree.map(lambda x: x[i_ffn], a)
              for n, a in params[stack_name].items() if n not in skip}
        ap = jax.tree.map(
            lambda a: a[rank],
            params["attn_window" if kind == WINDOW else "attn_global"])
        kp, vp = pools[kind]
        h, kp, vp = attention(h, lp, ap, kind, rank, kp, vp)
        h, out = ffn(h, lp, i_ffn, routed)
        return (h, {**pools, kind: (kp, vp)}), out

    # runs of consecutive layers of one kind and one feed-forward: a run of
    # several is one scan over its indices (one compiled body a run)
    runs = []
    for l in range(c.n_layers):
        key = (c.layer_pattern[l], l >= c.n_dense_layers)
        if runs and runs[-1][0] == key:
            runs[-1][2] += 1
        else:
            runs.append([key, l, 1])
    picks, listed = [], []
    n_seen = {GLOBAL: 0, WINDOW: 0}
    carry = (h, pools)
    for (kind, routed), l0, count in runs:
        r0 = n_seen[kind]
        n_seen[kind] += count
        if count == 1:
            carry, out = one_layer(carry, l0, r0, kind, routed)
            out = None if out is None else jax.tree.map(lambda a: a[None], out)
        else:
            carry, out = lax.scan(
                lambda cr, i: one_layer(cr, l0 + i, r0 + i, kind, routed),
                carry, jnp.arange(count, dtype=jnp.int32))
        if out is not None:
            picks.append(out[0])
            listed.append(out[1])
    h, pools = carry

    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["norm_f"], c.norm_eps)
        if last_index is not None:
            if getattr(last_index, "ndim", 0) >= 1:
                idx = last_index.reshape((1, -1, 1) if ragged is not None
                                         else (-1, 1, 1))
                h = jnp.take_along_axis(h, idx, axis=1)
            else:
                h = lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
        logits = mm(h, params["lm_head"]).astype(jnp.float32)
    k_pool, v_pool = pools[GLOBAL]
    out = (logits, k_pool, v_pool)
    if return_routed:
        out += (jnp.concatenate(picks, axis=0),)  # [L_moe, B, S, k]
    if return_listed:
        out += (jnp.concatenate(listed, axis=0),)  # [L_moe]
    return out + ({"k": pools[WINDOW][0], "v": pools[WINDOW][1]},)


# --------------------------------------------------------------------------
# what the runner asks of a model with a cache beside its pages
# --------------------------------------------------------------------------

def _side_unit_bytes(config: ModelConfig, page_size: int, dtype) -> int:
    return window_page_bytes(config, page_size, jnp.dtype(dtype).itemsize)


def _window_tables(sides, n: int, max_pages: int) -> np.ndarray:
    """int32 [n, max_pages]: the window page tables of `sides`, pad rows
    (None, and behind the last) and missing entries at scratch page 0."""
    out = np.zeros((n, max_pages), np.int32)
    for i, row in enumerate(sides):
        if row:
            out[i, : len(row)] = row
    return out


def _side_rows(sides, B: int, max_pages: int) -> jax.Array:
    return jnp.asarray(_window_tables(sides, B, max_pages))


def _side_segs(sides, lens, seg_cap: int, t_bucket: int, max_pages: int):
    """(tok_wpt int32 [t_bucket, max_pages], seg_wpt [seg_cap, max_pages]):
    the window page tables by token and by segment, laid out as
    build_ragged_metadata lays tok_pt and seg_pt."""
    seg_wpt = _window_tables(sides, seg_cap, max_pages)
    tok_wpt = np.zeros((t_bucket, max_pages), np.int32)
    tok_wpt[: sum(lens)] = np.repeat(seg_wpt[: len(lens)], lens, axis=0)
    return jnp.asarray(tok_wpt), jnp.asarray(seg_wpt)


SIDE = SideCacheOps(
    "window", make_window_pool, _side_unit_bytes, _side_rows, _side_segs,
    partial(forward, return_routed=True, return_listed=True))
