"""Multi-head latent attention (DeepSeek V2/V3/R1) — the MLA family's
one divergence from the shared toolkit: attention runs absorbed over a
per-token latent cache instead of full-head K/V pools.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models.quant import mm
from dynamo_tpu.models.toolkit import (
    _write_kv,
    attn_score_scale,
    layer_norm,
    paged_attention_jnp,
    rms_norm,
    rope,
)


def _mla_attention(c, lp, h, k_pool, l_idx, page_table, positions, safe_pos,
                   kv_lens, attn_impl="jnp", mesh=None, q_start=None,
                   q_len=None, ik_pool=None, walk=None):
    """Multi-head latent attention (DeepSeek V2/V3/R1), absorbed form.

    Per token the pool caches one [d_c + d_rh] vector: the RMS-normed KV
    latent c_kv plus the decoupled-RoPE shared key k_R. The W_UK
    up-projection is absorbed into the query (q_abs = q_nope @ W_UK), so
    attention runs DIRECTLY over the latent cache — scores are
    q_abs·c_kv + q_R·k_R, i.e. standard paged attention with Hk=1,
    G=n_heads, Dh=d_c+d_rh and values = the latent slice of the same
    pool; W_UV then lifts the attended latent to per-head values. That
    reuse means every pool mechanism (paging, prefix cache, tiering,
    disagg export) serves MLA unchanged.

    RoPE uses this module's half-rotation convention; HF DeepSeek
    checkpoints interleave — engine/weights.py must permute on import.
    Returns (attn [B, S, H*d_v], k_pool, ik_pool, chosen): `ik_pool` is the
    pool's second array, a 1-wide stub that is handed back untouched unless
    the model has an indexer (DeepSeek-V3.2, `c.has_indexer`). Then it holds
    one index key a token under the latent pages' own page table, this
    step's keys are written to it beside the latents, and wherever a row's
    context is longer than `index_topk` the softmax runs over the
    indexer's best `index_topk` tokens alone (`_selected_attention`); a
    step whose every row is at or below it takes the dense path untouched.
    `chosen` (None without an indexer) is the set each query attended to,
    int32 [B, S, W] bit words over the page table's width (`pack_chosen`):
    what a check that follows the served selection asks the program for.
    `walk`: the Pallas decode kernel's list of the step's live pages
    (ops/mla_attention.py `latent_walk`), which the caller builds once a
    step above its layer scan; None: the kernel builds its own.

    Named scopes, as on the GQA path (models/llama.py): `attn.proj` the
    query and latent projections with their norms, RoPE and the cache
    write; `attn.absorb` W_UK folded into the query; `attn.kernel` the
    attention over the latent cache; `attn.lift` W_UV back to per-head
    values; `attn.qscale` (inside `attn.proj`) the position-dependent
    query scale, where the model has one; with an indexer `attn.index`
    (its projections, the key write, the scores), `attn.select` (the best
    `index_topk`: a decode step's select kernel, a chunk's mask) and
    `attn.gather` (the selected latent rows). The output projection is
    the caller's `attn.proj`."""
    B, S = positions.shape
    H = c.n_heads
    dn, dr, dv, dc = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                      c.v_head_dim, c.kv_lora_rank)

    with jax.named_scope("attn.proj"):
        x = rms_norm(h, lp["attn_norm"], c.norm_eps)
        if c.q_lora_rank:
            q_lat = rms_norm(mm(x, lp["wq_lat"]), lp["q_lat_norm"], c.norm_eps)
            q = mm(q_lat, lp["wq_up"])
        else:
            q = mm(x, lp["wq"])
        q = q.reshape(B, S, H, dn + dr)
        if c.attn_qscale_beta:
            # the query of position p times 1 + beta ln(1 + floor(p / orig))
            # (Mistral-Small-4's `llama_4_scaling_beta`): a scale of the
            # scores that hangs on the query's position, so it goes onto
            # the query, content and rotary part alike, where the static
            # softmax scale goes into the kernel; in f32, then rounded
            # once. Exactly 1 below `orig`.
            with jax.named_scope("attn.qscale"):
                qs = 1.0 + c.attn_qscale_beta * jnp.log1p(jnp.floor(
                    safe_pos.astype(jnp.float32) / c.attn_qscale_orig))
                q = (q.astype(jnp.float32) * qs[..., None, None]).astype(q.dtype)
        q_nope, q_r = q[..., :dn], q[..., dn:]
        q_r = rope(q_r, safe_pos, c.rope_theta, config=c)

        kv = mm(x, lp["wkv_a"])  # [B, S, d_c + d_rh]
        c_kv = rms_norm(kv[..., :dc], lp["kv_norm"], c.norm_eps)
        k_r = rope(kv[..., None, dc:], safe_pos, c.rope_theta, config=c)[..., 0, :]
        lat = jnp.concatenate([c_kv, k_r], axis=-1)[:, :, None, :]  # [B,S,1,D]
        lat = _to_pool_width(lat, k_pool)
        k_pool = _write_kv(k_pool, l_idx, lat, page_table, positions)
    quantized = isinstance(k_pool, dict)  # int8 latent cache
    if c.has_indexer:
        with jax.named_scope("attn.index"):
            qi, wi, ki = _index_parts(c, lp, x, q_lat, safe_pos)
            ik_pool = _write_kv(ik_pool, l_idx, ki[:, :, None, :],
                                page_table, positions)

    wkv_b = lp["wkv_b"].reshape(dc, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
    with jax.named_scope("attn.absorb"):
        q_abs = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)  # [B,S,H,d_c]
    scale = attn_score_scale(c, dn + dr)
    tp = mesh is not None and mesh.shape.get("model", 1) > 1

    def dense():
        with jax.named_scope("attn.kernel"):
            return _latent_attention(
                k_pool, l_idx, q_abs, q_r, page_table, safe_pos, kv_lens,
                quantized=quantized, attn_impl=attn_impl, tp=tp, mesh=mesh,
                q_start=q_start, q_len=q_len, dc=dc, scale=scale, walk=walk)

    chosen = None
    if c.has_indexer:
        # a context of at most index_topk tokens is attended to whole: the
        # step then runs what a model without an indexer runs
        C = page_table.shape[1] * jax.tree.leaves(k_pool)[0].shape[2]
        attn_lat, chosen = lax.cond(
            jnp.max(kv_lens) > c.index_topk,
            lambda: _selected_attention(
                c, k_pool, ik_pool, l_idx, q_abs, q_r, qi, wi, page_table,
                positions, kv_lens, attn_impl=attn_impl, dc=dc, scale=scale),
            lambda: (dense(), all_live_chosen(positions, kv_lens, C)))
    else:
        attn_lat = dense()
    with jax.named_scope("attn.lift"):
        attn = jnp.einsum("bshc,chv->bshv", attn_lat, w_uv)
    return attn.reshape(B, S, H * dv), k_pool, ik_pool, chosen


def _to_pool_width(x, k_pool):
    """x [..., d_c + d_rh] with zeros behind it up to the latent pool's
    width (`ModelConfig.mla_pool_dim`: wider only for a model with an
    indexer), for a latent on its way into the pool and for a query on its
    way to the pool's rows: the zeros add nothing to a score."""
    pad = jax.tree.leaves(k_pool)[0].shape[-1] - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _index_parts(c, lp, x, q_lat, safe_pos):
    """The lightning indexer's three projections of a step's tokens: index
    queries [B, S, Hi, Di] from the normed compressed query, a weight a head
    [B, S, Hi] (f32, times Hi^-0.5 Di^-0.5) and ONE index key a token
    [B, S, Di] from the attention-normed hidden state, the key under a
    LayerNorm. The rotary (the model's own theta and yarn frequencies, this
    module's half-rotation layout) turns the first qk_rope_head_dim dims of
    each query head and of the key."""
    B, S = safe_pos.shape
    hi, di, dr = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim

    def turned(v):  # [B, S, n, Di]
        return jnp.concatenate(
            [rope(v[..., :dr], safe_pos, c.rope_theta, config=c),
             v[..., dr:]], axis=-1)

    qi = turned(mm(q_lat, lp["wi_q"]).reshape(B, S, hi, di))
    ki = layer_norm(mm(x, lp["wi_k"]), lp["ik_norm"], lp["ik_norm_b"],
                     c.norm_eps)
    ki = turned(ki[:, :, None, :])[:, :, 0]
    wi = mm(x, lp["wi_w"]).astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
    return qi, wi, ki


def index_scores(qi, wi, keys):
    """I[b, t, s] = sum_j wi[b, t, j] relu(qi[b, t, j] . keys[b, s]), f32:
    qi [B, S, Hi, Di], wi [B, S, Hi] f32, keys [B, C, Di]. The heads are
    walked in blocks so that the [B, S, block, C] scores before the sum
    stay under half a GiB at a long prefill chunk's sizes."""
    B, S, hi, _ = qi.shape
    C = keys.shape[1]
    hb = hi
    while hb > 1 and B * S * hb * C * 4 > (1 << 29):
        hb //= 2
    while hi % hb:
        hb -= 1

    def block(q_b, w_b):  # [B, S, hb, Di], [B, S, hb]
        s = jnp.einsum("bshd,bcd->bshc", q_b, keys,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("bshc,bsh->bsc", jax.nn.relu(s), w_b)

    if hb == hi:
        return block(qi, wi)
    qb = jnp.moveaxis(qi.reshape(B, S, hi // hb, hb, -1), 2, 0)
    wb = jnp.moveaxis(wi.reshape(B, S, hi // hb, hb), 2, 0)
    out, _ = lax.scan(lambda acc, qw: (acc + block(*qw), None),
                      jnp.zeros((B, S, C), jnp.float32), (qb, wb))
    return out


def select_topk(scores, k, with_mask=False):
    """The positions of the k largest scores of each row of [..., C], ties
    towards the lower position; int32 [..., k], best first (lax.top_k puts
    the lower index first among equals). Dead positions come in as -inf
    and so come out last. `with_mask`: also the same set as bool [..., C],
    from the sort's own k-th value and the last position it took at that
    value (a row with under k live positions has dead ones in it: the
    caller masks them). No serving path sorts any more: this is the
    reference the decode step's select kernel (ops/dsa_select.py) and
    `topk_mask` are held to (tests/test_dsa.py, tests/test_dsa_select.py,
    scripts/tpu_parity.py)."""
    vals, idx = lax.top_k(scores, k)
    idx = idx.astype(jnp.int32)
    if not with_mask:
        return idx
    kth = vals[..., -1:]
    last = jnp.max(jnp.where(vals == kth, idx, -1), axis=-1, keepdims=True)
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return idx, (scores > kth) | ((scores == kth) & (at <= last))


def topk_mask(scores, k):
    """bool [..., C]: the same set as `select_topk`, as a mask and without a
    sort: the k-th largest score of each row by a radix select over the
    scores' order-preserving 32-bit keys (32 passes of compare and count),
    everything above it, and of its equals the lowest positions that fill
    the k. What a long prefill chunk uses, where a sort of [chunk, context]
    costs several times the index scores themselves."""
    b = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    u = jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))  # monotone in x

    def bit(i, t):  # the largest t with count(u >= t) >= k, from the top bit
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum((u >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(n >= k, cand, t)

    t = lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))[..., None]
    above, equal = u > t, u == t
    room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal.astype(jnp.int32), axis=-1) <= room))


def chosen_words(C: int) -> int:
    """The int32 words of one query's chosen set over a context of C cells."""
    return -(-C // 32)


def pack_chosen(mask):
    """bool [..., C] -> int32 [..., W], W = chosen_words(C): cell s is bit
    s // W of word s % W, so that a word is built from 32 contiguous slices
    of the mask and nothing is laid out anew on the device (`unpack_chosen`
    is the host's way back)."""
    C = mask.shape[-1]
    W = chosen_words(C)
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, 32 * W - C)])
    words = jnp.zeros(mask.shape[:-1] + (W,), jnp.uint32)
    for b in range(32):
        words |= m[..., b * W:(b + 1) * W].astype(jnp.uint32) << jnp.uint32(b)
    return lax.bitcast_convert_type(words, jnp.int32)


def unpack_chosen(words, C: int):
    """numpy: int32 [..., W] as `pack_chosen` laid them out -> bool [..., C]."""
    import numpy as np

    w = np.ascontiguousarray(words).view(np.uint32)
    bits = np.empty(w.shape[:-1] + (32, w.shape[-1]), bool)
    for b in range(32):
        bits[..., b, :] = (w >> np.uint32(b)) & np.uint32(1)
    return bits.reshape(w.shape[:-1] + (-1,))[..., :C]


def all_live_chosen(positions, kv_lens, C: int):
    """`pack_chosen` of "every cached token up to the query's own", without
    the mask: positions [B, S] (-1: a pad query, which chose nothing),
    kv_lens [B]. Cell b * W + w is live where it is <= the query's last."""
    W = chosen_words(C)
    last = jnp.minimum(positions, kv_lens[:, None] - 1)[..., None]  # [B, S, 1]
    n = jnp.clip((last - jnp.arange(W, dtype=jnp.int32)) // W + 1, 0, 32)
    n = jnp.where(last < 0, 0, n).astype(jnp.uint32)
    words = jnp.where(n >= 32, jnp.uint32(0xFFFFFFFF),
                      (jnp.uint32(1) << jnp.minimum(n, 31)) - jnp.uint32(1))
    return lax.bitcast_convert_type(words, jnp.int32)


def _selected_attention(c, k_pool, ik_pool, l_idx, q_abs, q_r, qi, wi,
                        page_table, positions, kv_lens, *, attn_impl, dc,
                        scale):
    """Latent attention whose softmax runs over the indexer's choice: for
    each query token t the min(index_topk, t + 1) cached tokens s <= t with
    the largest index score I[t, s].

    A decode step on the chip: the select kernel (ops/dsa_select.py: a
    threshold select and a compaction, no sort) hands over the pool's cells
    of the chosen tokens, live ones first, and the chosen set as words; the
    chosen latent rows are gathered into a buffer laid out as pages of their
    own, `index_topk` slots a row, and the decode kernel the dense path runs
    walks that buffer under an identity page table (one call a layer: it
    reads the selected rows and nothing else of the context). Everything else (a prefill
    chunk; the jnp path): the selection as a mask, and attention over the
    context a block of queries by a block of pages at a time with a running
    softmax, as far as the block's last query sees; a chunk's queries would
    each gather index_topk rows of their own, 2 M row reads a layer at a
    chunk of 1024, where the masked form reads each page once a block.
    Returns ([B, S, H, d_c], the chosen sets as `pack_chosen` words)."""
    B, S = positions.shape
    L, NP, PS, _, Dl = k_pool.shape
    di = ik_pool.shape[-1]
    MP = page_table.shape[1]
    C = MP * PS
    K = min(c.index_topk, C)
    H = q_abs.shape[2]
    ctx_pos = jnp.arange(C, dtype=jnp.int32)
    with jax.named_scope("attn.index"):
        keys = ik_pool[l_idx, page_table].reshape(B, C, di)
        scores = index_scores(qi, wi, keys)  # [B, S, C] f32
        live = ((ctx_pos[None, None, :] <= positions[:, :, None])
                & (ctx_pos[None, None, :] < kv_lens[:, None, None]))
        scores = jnp.where(live, scores, -jnp.inf)
    qf = _to_pool_width(jnp.concatenate([q_abs, q_r], axis=-1), k_pool)
    if S == 1 and attn_impl == "pallas":
        from dynamo_tpu.ops.dsa_select import dsa_select
        from dynamo_tpu.ops.mla_attention import decode_mla_attention

        with jax.named_scope("attn.select"):
            # a select, not a sort: the K best as the pool's cells, live first
            n_live = jnp.clip(jnp.minimum(positions[:, 0] + 1, kv_lens), 0, C)
            cells, words = dsa_select(scores[:, 0], page_table, n_live, k=K)
            chosen = words[:, None]
            n_sel = jnp.minimum(n_live, K)
        kp = -(-K // PS)  # pages a row's buffer takes
        with jax.named_scope("attn.gather"):
            sel = k_pool.reshape(L, NP * PS, Dl)[l_idx, cells]  # [B, K, Dl]
            sel = jnp.pad(sel, ((0, 0), (0, kp * PS - K), (0, 0)))
            sel = sel.reshape(B * kp, PS, 1, Dl)
        own = jnp.arange(B * kp, dtype=jnp.int32).reshape(B, kp)
        with jax.named_scope("attn.kernel"):
            return decode_mla_attention(
                qf[:, 0], sel, own, n_sel, dc=dc, scale=scale)[:, None], chosen

    with jax.named_scope("attn.select"):
        chosen = topk_mask(scores, K) & live  # [B, S, C]
    # blocks: `ppb` pages of keys (about 2048 tokens) by `bq` queries, the
    # [bq, H, keys] float32 scores of a pair near 64 MiB
    ppb = max(d for d in range(1, max(1, 2048 // PS) + 1) if MP % d == 0)
    kb = ppb * PS
    bq = max(d for d in range(1, S + 1)
             if S % d == 0 and (d == 1 or d * H * kb * 4 <= (1 << 26)))
    # the rows' pages once a layer, outside the loops: a read of the carried
    # pool inside them makes XLA copy the pool whole a block (42 MB here
    # against 1.7 GB a block)
    with jax.named_scope("attn.gather"):
        lat_all = k_pool[l_idx, page_table].reshape(B, C, Dl)

    def q_block(args):
        q_b, chosen_b, last = args  # [B, bq, H, Dl], [B, bq, C], scalar

        def k_block(j, state):
            m, l, acc = state
            lat = lax.dynamic_slice_in_dim(lat_all, j * kb, kb, 1)
            ok = lax.dynamic_slice_in_dim(chosen_b, j * kb, kb, 2)[:, :, None]
            with jax.named_scope("attn.kernel"):
                s = jnp.einsum("bqhd,bkd->bqhk", q_b, lat,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(ok, s, -1e30)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m - m_new)
                pv = jnp.einsum("bqhk,bkc->bqhc", p.astype(q_b.dtype),
                                lat[..., :dc],
                                preferred_element_type=jnp.float32)
                return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                        acc * alpha + pv)

        init = (jnp.full((B, bq, H, 1), -1e30, jnp.float32),
                jnp.zeros((B, bq, H, 1), jnp.float32),
                jnp.zeros((B, bq, H, dc), jnp.float32))
        _, l, acc = lax.fori_loop(0, last // kb + 1, k_block, init)
        return (acc / jnp.maximum(l, 1e-30)).astype(q_b.dtype)

    nb = S // bq
    split = lambda a: jnp.moveaxis(a.reshape((B, nb, bq) + a.shape[2:]), 1, 0)
    # the last position a block's queries see (pad rows, at -1, see nothing)
    last = jnp.clip(jnp.max(split(positions), axis=(1, 2)), 0, C - 1)
    out = lax.map(q_block, (split(qf), split(chosen), last))
    with jax.named_scope("attn.select"):
        words = pack_chosen(chosen)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, dc), words


def latent_decode_on_kernel(quantized: bool, tp: bool) -> bool:
    """Whether a decode step under attn_impl "pallas" runs the latent
    kernel (ops/mla_attention.py `decode_mla_attention`): always on a dense
    pool; on int8 latent pages (scales fold into scores and values per
    token) only off a model mesh and opted in by DYN_MLA_INT8_KERNEL, until
    the hardware parity gate proves the (1, PS) scale tile in compiled
    Mosaic (same rollout policy as DYN_KV_COPY_KERNEL). `_latent_attention`
    asks here, and so does `ModelRunner.device_report`."""
    return not quantized or (not tp and os.environ.get(
        "DYN_MLA_INT8_KERNEL", "").lower() in ("1", "true", "on", "yes"))


def _latent_attention(k_pool, l_idx, q_abs, q_r, page_table, safe_pos,
                      kv_lens, *, quantized, attn_impl, tp, mesh, q_start,
                      q_len, dc, scale, walk=None):
    """Attention of the absorbed query over the layer's latent pages, by
    the path the pool's dtype, the platform and the step's shape select.
    Returns the attended latent [B, S, H, d_c]. `walk`: the decode
    kernel's (`_mla_attention`)."""
    S = q_abs.shape[1]

    def query():  # [B, S, H, the pool's width], built where a path wants it
        return _to_pool_width(jnp.concatenate([q_abs, q_r], axis=-1), k_pool)

    # the Pallas paths read the stacked pool in place, at l_idx; the jnp
    # gathers take the layer's slab
    if quantized:
        # int8 latent pages: the decode kernel where it is opted in
        # (`latent_decode_on_kernel`). Default, and all prefill, uses the
        # jnp gather: the value view slices q's leading d_c columns while
        # KEEPING the per-vector scale — elementwise dequant makes
        # column slicing scale-exact.
        if (attn_impl == "pallas" and S == 1
                and latent_decode_on_kernel(quantized, tp)):
            from dynamo_tpu.ops.mla_attention import decode_mla_attention

            qd = query()[:, 0]
            attn_lat = decode_mla_attention(
                qd, k_pool, page_table, kv_lens, l_idx, work=walk, dc=dc,
                scale=scale,
            )[:, None]
        else:
            lat_pool_l = jax.tree.map(lambda a: a[l_idx], k_pool)
            qg = query()[:, :, None, :, :]
            v_view = {"q": lat_pool_l["q"][..., :dc], "s": lat_pool_l["s"]}
            attn_lat = paged_attention_jnp(
                qg, lat_pool_l, v_view, page_table, safe_pos, kv_lens,
                scale=scale,
            )[:, :, 0]
    elif attn_impl == "pallas" and S > 1 and q_start is not None:
        # chunked-prefill hot path: flash MLA over latent pages; on TP
        # meshes the kernel runs per-head-shard under shard_map against
        # the replicated latent pool (zero collectives)
        from dynamo_tpu.ops.mla_attention import (
            prefill_mla_attention,
            prefill_mla_attention_sharded,
        )

        qp = query()  # [B, S, H, Dl]
        if tp:
            attn_lat = prefill_mla_attention_sharded(
                qp, k_pool, page_table, q_start, q_len, kv_lens,
                mesh, layer=l_idx, dc=dc, scale=scale,
            )
        else:
            attn_lat = prefill_mla_attention(
                qp, k_pool, page_table, q_start, q_len, kv_lens, l_idx,
                dc=dc, scale=scale,
            )
    elif attn_impl == "pallas" and S == 1:
        # decode hot path: Pallas streams the rows' live latent pages
        # once, several a grid step — the same DMA feeds both score (full
        # latent) and value (first d_c cols)
        from dynamo_tpu.ops.mla_attention import (
            decode_mla_attention,
            decode_mla_attention_sharded,
        )

        qd = query()[:, 0]  # [B, H, Dl]
        if tp:
            attn_lat = decode_mla_attention_sharded(
                qd, k_pool, page_table, kv_lens, mesh, layer=l_idx,
                work=walk, dc=dc, scale=scale,
            )[:, None]
        else:
            attn_lat = decode_mla_attention(
                qd, k_pool, page_table, kv_lens, l_idx, work=walk, dc=dc,
                scale=scale,
            )[:, None]  # [B, 1, H, d_c]
    else:
        lat_pool_l = k_pool[l_idx]
        qg = query()[:, :, None, :, :]
        attn_lat = paged_attention_jnp(
            qg, lat_pool_l, lat_pool_l[..., :dc], page_table, safe_pos,
            kv_lens, scale=scale,
        )[:, :, 0]  # [B, S, H, d_c]
    return attn_lat
