"""Multi-head latent attention (DeepSeek V2/V3/R1) — the MLA family's
one divergence from the shared toolkit: attention runs absorbed over a
per-token latent cache instead of full-head K/V pools.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.models.quant import mm
from dynamo_tpu.models.toolkit import (
    _write_kv,
    attn_score_scale,
    paged_attention_jnp,
    rms_norm,
    rope,
)


def _mla_attention(c, lp, h, k_pool, l_idx, page_table, positions, safe_pos,
                   kv_lens, attn_impl="jnp", mesh=None, q_start=None,
                   q_len=None):
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
    Returns (attn [B, S, H*d_v], k_pool).

    Named scopes, as on the GQA path (models/llama.py): `attn.proj` the
    query and latent projections with their norms, RoPE and the cache
    write; `attn.absorb` W_UK folded into the query; `attn.kernel` the
    attention over the latent cache; `attn.lift` W_UV back to per-head
    values; `attn.qscale` (inside `attn.proj`) the position-dependent
    query scale, where the model has one. The output projection is the
    caller's `attn.proj`."""
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
        k_pool = _write_kv(k_pool, l_idx, lat, page_table, positions)
    quantized = isinstance(k_pool, dict)  # int8 latent cache

    wkv_b = lp["wkv_b"].reshape(dc, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
    with jax.named_scope("attn.absorb"):
        q_abs = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)  # [B,S,H,d_c]
    scale = attn_score_scale(c, dn + dr)
    tp = mesh is not None and mesh.shape.get("model", 1) > 1
    with jax.named_scope("attn.kernel"):
        attn_lat = _latent_attention(
            k_pool, l_idx, q_abs, q_r, page_table, safe_pos, kv_lens,
            quantized=quantized, attn_impl=attn_impl, tp=tp, mesh=mesh,
            q_start=q_start, q_len=q_len, dc=dc, scale=scale)
    with jax.named_scope("attn.lift"):
        attn = jnp.einsum("bshc,chv->bshv", attn_lat, w_uv)
    return attn.reshape(B, S, H * dv), k_pool


def _latent_attention(k_pool, l_idx, q_abs, q_r, page_table, safe_pos,
                      kv_lens, *, quantized, attn_impl, tp, mesh, q_start,
                      q_len, dc, scale):
    """Attention of the absorbed query over the layer's latent pages, by
    the path the pool's dtype, the platform and the step's shape select.
    Returns the attended latent [B, S, H, d_c]."""
    S = q_abs.shape[1]
    lat_pool_l = jax.tree.map(lambda a: a[l_idx], k_pool)
    if quantized:
        # int8 latent pages. Decode can ride the Pallas kernel (scales
        # fold into scores/values per token) — opt-in via
        # DYN_MLA_INT8_KERNEL until the hardware parity gate proves the
        # (PS,) scale tile in compiled Mosaic (same rollout policy as
        # DYN_KV_COPY_KERNEL). Default, and all prefill, uses the jnp
        # gather: the value view slices q's leading d_c columns while
        # KEEPING the per-vector scale — elementwise dequant makes
        # column slicing scale-exact.
        import os as _os

        use_kernel = (
            attn_impl == "pallas" and S == 1 and not tp
            and _os.environ.get("DYN_MLA_INT8_KERNEL", "").lower()
            in ("1", "true", "on", "yes")
        )
        if use_kernel:
            from dynamo_tpu.ops.mla_attention import decode_mla_attention

            qd = jnp.concatenate([q_abs, q_r], axis=-1)[:, 0]
            attn_lat = decode_mla_attention(
                qd, lat_pool_l, page_table, kv_lens, dc=dc, scale=scale,
            )[:, None]
        else:
            qg = jnp.concatenate([q_abs, q_r], axis=-1)[:, :, None, :, :]
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

        qp = jnp.concatenate([q_abs, q_r], axis=-1)  # [B, S, H, Dl]
        if tp:
            attn_lat = prefill_mla_attention_sharded(
                qp, lat_pool_l, page_table, q_start, q_len, kv_lens,
                mesh, dc=dc, scale=scale,
            )
        else:
            attn_lat = prefill_mla_attention(
                qp, lat_pool_l, page_table, q_start, q_len, kv_lens,
                dc=dc, scale=scale,
            )
    elif attn_impl == "pallas" and S == 1:
        # decode hot path: Pallas streams latent pages once — the same
        # DMA feeds both score (full latent) and value (first d_c cols)
        from dynamo_tpu.ops.mla_attention import (
            decode_mla_attention,
            decode_mla_attention_sharded,
        )

        qd = jnp.concatenate([q_abs, q_r], axis=-1)[:, 0]  # [B, H, Dl]
        if tp:
            attn_lat = decode_mla_attention_sharded(
                qd, lat_pool_l, page_table, kv_lens, mesh, dc=dc, scale=scale,
            )[:, None]
        else:
            attn_lat = decode_mla_attention(
                qd, lat_pool_l, page_table, kv_lens, dc=dc, scale=scale,
            )[:, None]  # [B, 1, H, d_c]
    else:
        qg = jnp.concatenate([q_abs, q_r], axis=-1)[:, :, None, :, :]
        attn_lat = paged_attention_jnp(
            qg, lat_pool_l, lat_pool_l[..., :dc], page_table, safe_pos,
            kv_lens, scale=scale,
        )[:, :, 0]  # [B, S, H, d_c]
    return attn_lat
