"""Llama-family transformer, functional JAX with a paged KV cache.

TPU-first design notes:
- Layer params are **stacked** on a leading [n_layers] axis and the forward
  runs `lax.scan` over layers → one compiled layer body, fast XLA compiles
  even at 80 layers, and scan-carried KV pool updates.
- The KV cache is a global paged pool `[L, Hk, num_pages, page_size, Dh]`;
  sequences own pages via a page table (flat position p lives at
  `page_table[p // page_size], p % page_size`). Gathered attention reads are
  the jnp reference path; the Pallas ragged-paged-attention kernel
  (dynamo_tpu/ops) replaces them on TPU.
- GQA, RoPE (HF half-rotation convention), RMSNorm(fp32), SwiGLU; bf16
  params/activations, fp32 softmax and logits.

Family layout (r5 split): shared blocks in models/toolkit.py, MLA in
models/mla.py, MoE in models/moe.py; this module owns init + the unified
scan-over-stacked-layers forward. The forward stays ONE function across
families on purpose: every family shares the paged-cache plumbing and
the single compiled scan body (per-family forwards would duplicate
both), and family divergence is config-driven branches resolved at
trace time.

The reference framework delegates all of this to vLLM/SGLang/TRT-LLM
(SURVEY.md: "the engine layer is the reference's biggest delegated
dependency"); this module is the native TPU replacement.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.mla import _mla_attention
from dynamo_tpu.models.moe import EXPERT_STACKS, _moe_block, experts_kernel_stack
from dynamo_tpu.models.quant import embed_lookup, mm, tied_logits

# The shared toolkit lives in models/toolkit.py (r5 split); these names
# are re-exported here because this module has always been their home
# (ops/pipeline_parallel, engine, tests import them from models.llama).
from dynamo_tpu.models.toolkit import (  # noqa: F401
    Params,
    _write_kv,
    _yarn_mscale,
    attn_score_scale,
    make_kv_pool,
    paged_attention_jnp,
    rms_norm,
    rope,
    rope_inv_freq,
)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (benchmarks / tests; checkpoint loading in
    engine/weights.py replaces values with the same tree structure).

    MoE models with `n_dense_layers` (DeepSeek first_k_dense_replace) get
    a SECOND stacked tree `layers_dense` for the leading dense-FFN layers
    — the forward runs two scans, one compiled body each."""
    c = config
    if c.is_sambay:  # Phi-4-mini-flash: a decoder-hybrid-decoder
        from dynamo_tpu.models import sambay

        return sambay.init_params(c, key, dtype)
    if c.is_hybrid:  # Jamba: a tree and a forward of its own
        from dynamo_tpu.models import jamba

        return jamba.init_params(c, key, dtype)
    if c.has_window_pool:  # MiMo-V2: layers of two kinds (models/mimo.py)
        from dynamo_tpu.models import mimo

        return mimo.init_params(c, key, dtype)
    if c.is_kda:  # Ling-3.0: KDA layers beside latent attention (models/ling.py)
        from dynamo_tpu.models import ling

        return ling.init_params(c, key, dtype)
    if c.is_moe and c.n_dense_layers:
        moe_part = _init_layer_stack(
            c, key, c.n_layers - c.n_dense_layers, moe=True, dtype=dtype
        )
        dense_part = _init_layer_stack(
            c, jax.random.fold_in(key, 1), c.n_dense_layers, moe=False,
            dtype=dtype,
        )
        params = _init_top(c, key, dtype)
        params["layers"] = moe_part
        params["layers_dense"] = dense_part
        return params
    params = _init_top(c, key, dtype)
    params["layers"] = _init_layer_stack(
        c, key, c.n_layers, moe=c.is_moe, dtype=dtype
    )
    return params


def _init_top(c: ModelConfig, key: jax.Array, dtype) -> Params:
    k = jax.random.split(key, 15)

    def w(kk, fan_in, *shape):
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    params: Params = {
        "embed": w(k[0], c.dim, c.vocab_size, c.dim),
        "norm_f": jnp.full(
            (c.dim,), 0.0 if c.norm_zero_centered else 1.0, jnp.float32
        ),
    }
    if not c.tie_embeddings:
        params["lm_head"] = w(k[9], c.dim, c.dim, c.vocab_size)
    return params


def _init_layer_stack(config: ModelConfig, key: jax.Array, L: int,
                      moe: bool, dtype) -> Dict[str, Any]:
    """One stacked per-layer tree covering L layers (attention + either a
    dense FFN or the MoE block)."""
    c = config
    k = jax.random.split(key, 15)
    hd = c.head_dim

    def norm_init(*shape):
        # zero-centered norms (Gemma) store w with runtime (1 + w)
        fill = 0.0 if c.norm_zero_centered else 1.0
        return jnp.full(shape, fill, dtype=jnp.float32)

    def w(key, fan_in, *shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    if c.is_mla:
        # MLA (DeepSeek V2/V3): KV compressed to a per-token latent +
        # decoupled-RoPE shared key; q optionally compressed too
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        attn_p = {
            "attn_norm": norm_init(L, c.dim),
            "wkv_a": w(k[2], c.dim, L, c.dim, c.kv_lora_rank + dr),
            "kv_norm": norm_init(L, c.kv_lora_rank),
            "wkv_b": w(k[3], c.kv_lora_rank, L, c.kv_lora_rank,
                       c.n_heads * (dn + dv)),
            "wo": w(k[4], c.n_heads * dv, L, c.n_heads * dv, c.dim),
            "mlp_norm": norm_init(L, c.dim),
        }
        if c.q_lora_rank:
            attn_p["wq_lat"] = w(k[1], c.dim, L, c.dim, c.q_lora_rank)
            attn_p["q_lat_norm"] = norm_init(L, c.q_lora_rank)
            attn_p["wq_up"] = w(k[10], c.q_lora_rank, L, c.q_lora_rank,
                                c.n_heads * (dn + dr))
        else:
            attn_p["wq"] = w(k[1], c.dim, L, c.dim, c.n_heads * (dn + dr))
        if c.has_indexer:
            # the lightning indexer (models/mla.py): index queries from
            # the compressed query, one index key a token and a weight a
            # head from the normed hidden state, a LayerNorm on the key
            ki = jax.random.split(jax.random.fold_in(key, 44), 3)
            hi, di = c.index_n_heads, c.index_head_dim
            attn_p["wi_q"] = w(ki[0], c.q_lora_rank, L, c.q_lora_rank, hi * di)
            attn_p["wi_k"] = w(ki[1], c.dim, L, c.dim, di)
            attn_p["wi_w"] = w(ki[2], c.dim, L, c.dim, hi)
            attn_p["ik_norm"] = jnp.ones((L, di), jnp.float32)
            attn_p["ik_norm_b"] = jnp.zeros((L, di), jnp.float32)
    else:
        attn_p = {
            "wq": w(k[1], c.dim, L, c.dim, c.n_heads * hd),
            "wk": w(k[2], c.dim, L, c.dim, c.n_kv_heads * hd),
            "wv": w(k[3], c.dim, L, c.dim, c.n_kv_heads * hd),
            "wo": w(k[4], c.n_heads * hd, L, c.n_heads * hd, c.dim),
        }
        if c.pre_norms:
            attn_p["attn_norm"] = norm_init(L, c.dim)
            attn_p["mlp_norm"] = norm_init(L, c.dim)
    layers = attn_p
    if c.attn_bias:  # Qwen2 family: biases on the q/k/v projections
        layers.update(
            {
                "bq": jnp.zeros((L, c.n_heads * hd), dtype),
                "bk": jnp.zeros((L, c.n_kv_heads * hd), dtype),
                "bv": jnp.zeros((L, c.n_kv_heads * hd), dtype),
            }
        )
    if c.qk_norm:  # Qwen3 family: per-head RMSNorm on q/k before RoPE
        qd, kd = ((c.n_heads * hd, c.n_kv_heads * hd)  # OLMo-2: full width
                  if c.qk_norm_wide else (hd, hd))
        layers.update(
            {"q_norm": norm_init(L, qd), "k_norm": norm_init(L, kd)}
        )
    if c.post_norms:  # Gemma-2 sandwich norms on the residual branches
        layers.update({
            "post_attn_norm": norm_init(L, c.dim),
            "post_mlp_norm": norm_init(L, c.dim),
        })
    if moe:
        # the router (and its bias) at full width; the expert axis of the
        # expert matrices is what this chip holds (all, unless told)
        held = c.experts_held
        layers.update(
            {
                "w_router": w(k[5], c.dim, L, c.dim, c.n_experts),
                "we_gate": w(k[6], c.dim, L, held, c.dim, c.moe_ffn_dim),
                "we_up": w(k[7], c.dim, L, held, c.dim, c.moe_ffn_dim),
                "we_down": w(k[8], c.moe_ffn_dim, L, held, c.moe_ffn_dim, c.dim),
            }
        )
        if c.moe_router_bias:  # DeepSeek-V3 e_score_correction_bias
            layers["router_bias"] = jnp.zeros((L, c.n_experts), jnp.float32)
        if c.n_shared_experts:  # deepseek/qwen2-moe shared experts (fused)
            sf = c.shared_ffn_dim
            layers.update(
                {
                    "ws_gate": w(k[12], c.dim, L, c.dim, sf),
                    "ws_up": w(k[13], c.dim, L, c.dim, sf),
                    "ws_down": w(k[14], sf, L, sf, c.dim),
                }
            )
    else:
        layers.update(
            {
                "w_gate": w(k[5], c.dim, L, c.dim, c.ffn_dim),
                "w_up": w(k[6], c.dim, L, c.dim, c.ffn_dim),
                "w_down": w(k[7], c.ffn_dim, L, c.ffn_dim, c.dim),
            }
        )
    return layers


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S] absolute positions (padding = -1)
    k_pool: jax.Array,  # [L, NP, PS, Hk, Dh] (token-major, make_kv_pool)
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, MP]
    kv_lens: jax.Array,  # [B] context length AFTER this step's tokens
    last_index: Optional[jax.Array] = None,  # scalar (or [B] per-row, for
    #   ragged packed chunks): only compute logits at this position
    attn_impl: str = "jnp",  # "jnp" | "pallas" | "ring" (sequence-parallel)
    mesh=None,  # jax.sharding.Mesh, required for attn_impl="ring"
    sp_has_prior: bool = True,  # ring: False skips the paged prior-context
    #   pass entirely (fresh prefill — the common SP case)
    lora: Optional[Params] = None,  # stacked multi-adapter tree (models/lora.py)
    adapter_idx: Optional[jax.Array] = None,  # [B] slot per sequence (0=base)
    mm_embeds: Optional[jax.Array] = None,  # [B, S, E] multimodal embeddings
    mm_mask: Optional[jax.Array] = None,  # [B, S] True → replace token embed
    ragged: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    # flat-segment mixed forward: (seg_page_table [SEG, MP], seg_kv_lens
    #   [SEG], meta [5, NW]) from ops.ragged_paged_attention
    #   .build_ragged_metadata. tokens/positions come in [1, T]; the
    #   page_table/kv_lens args switch meaning to the builder's PER-TOKEN
    #   arrays ([T, MP] / [T]) so KV writes and the jnp fallback stay
    #   exactly correct for arbitrary segment layouts, while the pallas
    #   branch uses the seg-level arrays (SMEM-sized). last_index holds
    #   FLAT per-segment last-token indices.
    return_routed: bool = False,  # static: a routed model's step programs
    #   set it and get the router's picks as a fourth output
    return_listed: bool = False,  # static, with return_routed: a fifth
    #   output, what the expert kernels' work lists held (see below)
) -> Tuple[jax.Array, ...]:
    """One forward pass (covers prefill chunks S>1 and decode S=1).

    Writes this step's K/V into the pool pages, attends over the full
    context, returns (logits[B, S, V], k_pool, v_pool). Padding tokens
    (position < 0) are dropped from pool writes via scatter mode='drop'.
    With `last_index` (prefill), the vocab projection runs on that single
    position only — logits come back [B, 1, V], skipping S-1 lm_head
    matmuls over a 100k+ vocab.

    With `return_routed` (routed models only) a fourth output follows the
    pools: int32 [L_moe, B, S, k], the experts each token was routed to,
    expert layers in model order (the leading dense layers have none).
    They are the `ys` of the expert-layer scan, so they cost one small
    output and no second pass. With `return_listed` a fifth follows:
    int32 [L_moe], the entries of each expert layer's work list of hit
    experts as its kernel was given it (models/moe.py; 0 in a forward
    that took the dense path). A model with an indexer (`c.has_indexer`)
    adds one more, last: int32 [L, B, S, W], the tokens each query of each
    layer attended to as bit words (models/mla.py `pack_chosen`).
    """
    c = config
    B, S = tokens.shape
    if c.is_sambay:
        raise NotImplementedError(
            "a decoder-hybrid-decoder runs models/sambay.forward, which takes "
            "and returns its state pool and its window pool; this path has "
            "one pool")
    if c.is_hybrid:
        raise NotImplementedError(
            "a model with state-space layers runs models/jamba.forward, which "
            "takes and returns the state pool; this path has no state")
    if c.has_window_pool:
        raise NotImplementedError(
            "a model with window and global layers runs models/mimo.forward, "
            "which takes and returns the window pool; this path has one pool")
    if c.is_kda:
        raise NotImplementedError(
            "a model with KDA layers runs models/ling.forward, which takes "
            "and returns the state pool; this path has no state")
    if return_routed and not c.is_moe:
        raise ValueError("return_routed needs a model with routed experts")
    if return_listed and not return_routed:
        raise ValueError("return_listed rides on return_routed")
    # a forward of few rows on the chip computes its routed experts from
    # the layer-STACKED weights, read in place by a kernel (a slice of the
    # stack in front of a custom call is a copy of it): the three stacks
    # then stay out of what the layer scan slices
    moe_stack = (experts_kernel_stack(c, params["layers"], B * S, mesh,
                                      attn_impl) if c.is_moe else None)
    scan_layers = params["layers"]
    if moe_stack is not None:
        scan_layers = {k: v for k, v in scan_layers.items()
                       if k not in EXPERT_STACKS}
    real_rows = positions >= 0
    if ragged is not None:
        if B != 1:
            raise ValueError("ragged forward takes a single flat [1, T] row")
        if c.is_mla:
            raise NotImplementedError(
                "ragged mixed forward is not supported for MLA models"
            )
        if attn_impl == "ring":
            raise NotImplementedError(
                "ragged mixed forward is incompatible with sequence "
                "parallelism; the runner keeps the padded path for SP/PP"
            )
    hd = c.head_dim
    G = c.n_heads // c.n_kv_heads

    h = embed_lookup(params["embed"], tokens)  # [B, S, E] (gather)
    if c.embed_multiplier:
        # Granite: explicit embedding multiplier
        h = h * jnp.asarray(c.embed_multiplier, h.dtype)
    elif c.embed_scale:
        # Gemma: embeddings scaled by sqrt(dim), with the normalizer
        # rounded through the embedding dtype (HF semantics)
        h = h * jnp.asarray(c.dim**0.5, h.dtype)
    if mm_embeds is not None:
        # multimodal injection: image-placeholder positions take the vision
        # encoder's embeddings instead of the token embedding (prefix-cache
        # correctness relies on the scheduler salting block hashes with the
        # image content — scheduler._chain_seed)
        h = jnp.where(mm_mask[..., None], mm_embeds.astype(h.dtype), h)
    safe_pos = jnp.maximum(positions, 0)
    # prefill-kernel metadata: valid tokens are a contiguous run from s=0
    # (ModelRunner contract), so start/len fully describe the positions
    q_start = safe_pos[:, 0]
    q_len = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)
    if attn_impl == "ring":
        # sequence parallelism: pin activations sharded over the seq mesh
        # axis from the embedding on, so every projection runs on S/n tokens
        from jax.sharding import NamedSharding

        from dynamo_tpu.parallel.mesh import SPEC_SEQ_ACT

        h = lax.with_sharding_constraint(h, NamedSharding(mesh, SPEC_SEQ_ACT))

    # the Pallas decode and ragged kernels walk a list of live pages (a
    # decode row's; a ragged work unit's), which hangs on the lengths,
    # the positions and the window alone: built here, once a step,
    # because XLA leaves it in the layer scan's body (three small fusions
    # a layer). A model whose layers alternate sliding and global gets
    # both lists and each layer picks one.
    walk_sliding = walk_global = None
    # the heads ONE call of the kernel sees: a model-axis shard's
    shards = mesh.shape.get("model", 1) if mesh is not None else 1
    if attn_impl == "pallas" and c.is_mla and S == 1:
        # latent attention's decode kernel walks the same list over its
        # one pool (its chunk kernel and the ragged program do not yet)
        from dynamo_tpu.ops.mla_attention import latent_walk

        walk_global = latent_walk(c.n_heads // shards, k_pool, page_table,
                                  kv_lens)
    elif attn_impl == "pallas" and not c.is_mla and (S == 1 or ragged is not None):
        heads = (c.n_kv_heads // shards, c.n_heads // c.n_kv_heads)
        if ragged is not None:
            from dynamo_tpu.ops.ragged_paged_attention import ragged_walk

            seg_pt, seg_kvl, rmeta = ragged

            def build_walk(window):
                return ragged_walk(heads, k_pool, v_pool, seg_pt, seg_kvl,
                                   rmeta, window, S)
        else:
            from dynamo_tpu.ops.paged_attention import decode_walk

            def build_walk(window):
                return decode_walk(heads, k_pool, v_pool, page_table, kv_lens,
                                   window, False)

        if c.sliding_window > 0:
            walk_sliding = build_walk(jnp.int32(c.sliding_window))
        if c.sliding_window <= 0 or any(
                l % c.sw_period == c.sw_global_residue
                for l in range(c.n_layers)):
            walk_global = build_walk(None)

    def layer_walk(win):
        """This layer's list: the one there is, or by the layer's window."""
        if walk_sliding is None or walk_global is None:
            return walk_global if walk_sliding is None else walk_sliding
        return jax.tree.map(lambda g, s: jnp.where(win > 0, s, g),
                            walk_global, walk_sliding)

    lora_layers = (lora or {}).get("layers", {})
    if lora_layers and c.is_mla:
        # the MLA branch never consults the LoRA factors; failing loudly
        # beats an adapter that appears to load but changes nothing
        raise NotImplementedError("LoRA is not supported for MLA models")

    # Gemma-3 dual rope tables (static per compile; selected per layer
    # inside the scan)
    rope_if_global = rope_if_local = None
    if c.rope_local_theta:
        rope_if_global = rope_inv_freq(c, hd, c.rope_theta)
        rope_if_local = rope_inv_freq(None, hd, c.rope_local_theta)

    def moe_ffn(lp, x, l_idx):
        stack = None
        if moe_stack is not None:  # + this layer's index into the stacks
            stack = moe_stack + (l_idx - c.n_dense_layers,)
        return _moe_block(c, lp, x, mesh, real_rows, stack)

    def routed_ys(routed, chosen=None):
        """What a layer hands the scan, if the caller asked: an expert
        layer its picks (+ what its work list held); a layer with an
        indexer (models/mla.py) also the tokens it attended to."""
        if not return_routed:
            return None
        ys = None
        if routed is not None:
            ys = tuple(routed) if return_listed else routed[0]
        return ys if chosen is None else (ys, chosen)

    def make_layer(use_moe):
        def layer(carry, xs):
            return _layer_body(carry, xs, use_moe)
        return layer

    def _layer_body(carry, xs, use_moe):
        h, k_pool, v_pool = carry
        lp, ll, l_idx = xs

        def lproj(y, x, name):
            """y = x @ W (+ per-sequence LoRA delta x @ A[a] @ B[a])."""
            a = ll.get(name + "_a")
            if a is None:
                return y
            Ag = a[adapter_idx]  # [B, in, r]
            Bg = ll[name + "_b"][adapter_idx]  # [B, r, out]
            z = jnp.einsum("bsi,bir->bsr", x, Ag)
            return y + jnp.einsum("bsr,bro->bso", z, Bg)

        # named scopes mark the parts of a layer in HLO metadata (an HLO
        # dump and xprof then say which part a fusion belongs to); they
        # change no computation
        routed = None  # an expert layer's scan outputs (routed_ys)
        if c.is_mla:
            # _mla_attention names its own parts (attn.proj / absorb /
            # kernel / lift), matching the GQA path below
            attn, k_pool, v_pool, chosen = _mla_attention(
                c, lp, h, k_pool, l_idx, page_table, positions, safe_pos,
                kv_lens, attn_impl=attn_impl, mesh=mesh,
                q_start=q_start, q_len=q_len, ik_pool=v_pool,
                walk=walk_global,
            )
            with jax.named_scope("attn.proj"):
                h = h + mm(attn, lp["wo"])
            with jax.named_scope("ffn"):
                x = rms_norm(h, lp["mlp_norm"], c.norm_eps)
                if use_moe:
                    ffw, *routed = moe_ffn(lp, x, l_idx)
                    h = h + ffw
                else:
                    gate = jax.nn.silu(mm(x, lp["w_gate"]))
                    h = h + mm(gate * mm(x, lp["w_up"]), lp["w_down"])
            return (h, k_pool, v_pool), routed_ys(routed, chosen)

        zc = c.norm_zero_centered
        with jax.named_scope("attn.proj"):
            # OLMo-2 (pre_norms=False): the sublayer reads the raw residual
            x = (rms_norm(h, lp["attn_norm"], c.norm_eps, zero_centered=zc)
                 if c.pre_norms else h)
            q = lproj(mm(x, lp["wq"]), x, "wq")
            k = lproj(mm(x, lp["wk"]), x, "wk")
            v = lproj(mm(x, lp["wv"]), x, "wv")
            if c.attn_bias:  # Qwen2 projection biases
                q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
            if c.qk_norm and c.qk_norm_wide:
                # OLMo-2: RMS statistics over the FULL projection width,
                # before the head reshape (per-head norm is a different op)
                q = rms_norm(q, lp["q_norm"], c.norm_eps, zero_centered=zc)
                k = rms_norm(k, lp["k_norm"], c.norm_eps, zero_centered=zc)
            q = q.reshape(B, S, c.n_heads, hd)
            k = k.reshape(B, S, c.n_kv_heads, hd)
            v = v.reshape(B, S, c.n_kv_heads, hd)
            if c.qk_norm and not c.qk_norm_wide:
                # Qwen3/Gemma-3 per-head RMSNorm before RoPE
                q = rms_norm(q, lp["q_norm"], c.norm_eps, zero_centered=zc)
                k = rms_norm(k, lp["k_norm"], c.norm_eps, zero_centered=zc)
            if c.rope_local_theta:
                # Gemma-3 dual rope: sliding layers rotate with the local
                # base, global layers with rope_theta (+ its scaling). Both
                # tables are static; the per-layer pick is one [hd/2] select
                # riding the scan — still one compiled body.
                is_global = (l_idx % c.sw_period) == c.sw_global_residue
                iv = jnp.where(is_global, rope_if_global, rope_if_local)
                q = rope(q, safe_pos, c.rope_theta, inv_freq=iv)
                k = rope(k, safe_pos, c.rope_theta, inv_freq=iv)
            else:
                q = rope(q, safe_pos, c.rope_theta, config=c)
                k = rope(k, safe_pos, c.rope_theta, config=c)

        # surgical in-place scatter into the carried pools (no pool copy)
        if ragged is not None:
            # per-token page-table rows: view the flat [1, T] step as
            # B=T, S=1 so the same scatter covers mixed segment layouts
            k_pool = _write_kv(
                k_pool, l_idx, k.reshape(S, 1, c.n_kv_heads, hd),
                page_table, positions.reshape(S, 1),
            )
            v_pool = _write_kv(
                v_pool, l_idx, v.reshape(S, 1, c.n_kv_heads, hd),
                page_table, positions.reshape(S, 1),
            )
        else:
            k_pool = _write_kv(k_pool, l_idx, k, page_table, positions)
            v_pool = _write_kv(v_pool, l_idx, v, page_table, positions)

        def kv_slab():
            # one layer's slab of each pool, for the paths that gather
            # pages in jnp. The Pallas kernels take the stacked pools and
            # l_idx instead: a custom call cannot read a view, so a slab
            # in front of one is a copy of it, every layer of every step.
            with jax.named_scope("attn.kv_slab"):
                return (jax.tree.map(lambda a: a[l_idx], k_pool),
                        jax.tree.map(lambda a: a[l_idx], v_pool))

        with jax.named_scope("attn.kernel"):
            qg = q.reshape(B, S, c.n_kv_heads, G, hd)
            tp = mesh is not None and mesh.shape.get("model", 1) > 1
            gemma_attn = (
                c.attn_logit_softcap > 0 or c.sliding_window > 0
                or c.query_pre_attn_scalar > 0 or c.attn_scale > 0
            )
            if gemma_attn and attn_impl == "ring":
                # the ring kernel has no window/softcap operands: falling
                # through to the dense jnp path would silently replace the
                # seq-sharded prefill with a replicated gather (huge slowdown
                # or OOM on exactly the long prompts SP exists for)
                raise NotImplementedError(
                    "sequence-parallel ring attention does not support "
                    "sliding-window/softcap models (Mistral/Gemma); run this "
                    "model without --seq-parallel"
                )
            # Gemma-family extras (softcap / sliding-window / scalar scale)
            # collapse to the kernel/jnp defaults for every other config, so
            # ONE decode dispatch covers all families. window_l rides the
            # scan: Gemma-2 alternates sliding (even) / global (odd) — the
            # kernel takes it as a scalar-prefetch operand so the alternation
            # stays one compiled body.
            win = None
            if gemma_attn and c.sliding_window > 0:
                # global iff l % sw_period == sw_global_residue (Gemma-2:
                # even sliding / odd global; Gemma-3: 5 local : 1 global)
                win = jnp.where(
                    (l_idx % c.sw_period) == c.sw_global_residue,
                    jnp.int32(0), jnp.int32(c.sliding_window),
                )
            g_scale = (
                c.query_pre_attn_scalar ** -0.5
                if c.query_pre_attn_scalar > 0 else None
            )
            if c.attn_scale:  # Granite: the softmax scale given directly
                g_scale = c.attn_scale
            if ragged is not None:
                seg_pt, seg_kvl, rmeta = ragged
                if attn_impl == "pallas":
                    from dynamo_tpu.ops.ragged_paged_attention import (
                        ragged_paged_attention,
                        ragged_paged_attention_sharded,
                    )

                    kwr = dict(scale=g_scale, softcap=c.attn_logit_softcap)
                    if tp:
                        attn = ragged_paged_attention_sharded(
                            qg[0], k_pool, v_pool, seg_pt, seg_kvl, rmeta,
                            mesh, window=win, layer=l_idx,
                            work=layer_walk(win), **kwr,
                        )[None]
                    else:
                        attn = ragged_paged_attention(
                            qg[0], k_pool, v_pool, seg_pt, seg_kvl, rmeta,
                            win, l_idx, layer_walk(win), **kwr,
                        )[None]  # [1, T, Hk, G, hd]
                else:
                    # per-token B=T, S=1 rows of the canonical jnp reference;
                    # gemma extras collapse to the defaults for other configs
                    attn = paged_attention_jnp(
                        qg[0][:, None], *kv_slab(), page_table,
                        safe_pos.reshape(S, 1), kv_lens,
                        scale=g_scale, softcap=c.attn_logit_softcap, window=win,
                    )[:, 0][None]
            elif attn_impl == "pallas" and S == 1:
                from dynamo_tpu.ops.paged_attention import (
                    decode_paged_attention,
                    decode_paged_attention_sharded,
                )

                kwg = dict(scale=g_scale, softcap=c.attn_logit_softcap)
                walk = layer_walk(win)
                if tp:
                    attn = decode_paged_attention_sharded(
                        qg[:, 0], k_pool, v_pool, page_table, kv_lens,
                        mesh, window=win, layer=l_idx, work=walk, **kwg,
                    )[:, None]
                else:
                    attn = decode_paged_attention(
                        qg[:, 0], k_pool, v_pool, page_table, kv_lens,
                        win, l_idx, walk, **kwg,
                    )[:, None]  # [B, 1, Hk, G, hd]
            elif attn_impl == "pallas":
                # flash prefill carries the gemma extras the same way the
                # decode kernel does (softcap/scale static, window as a
                # scalar-prefetch operand) — one dispatch for all families
                from dynamo_tpu.ops.flash_prefill import (
                    prefill_paged_attention,
                    prefill_paged_attention_sharded,
                )

                kwp = dict(scale=g_scale, softcap=c.attn_logit_softcap)
                if tp:
                    attn = prefill_paged_attention_sharded(
                        qg, k_pool, v_pool, page_table, q_start, q_len, kv_lens,
                        mesh, window=win, layer=l_idx, **kwp,
                    )
                else:
                    attn = prefill_paged_attention(
                        qg, k_pool, v_pool, page_table, q_start, q_len, kv_lens,
                        win, l_idx, **kwp,
                    )
            elif gemma_attn:
                # non-pallas gemma runs: jnp path
                attn = paged_attention_jnp(
                    qg, *kv_slab(), page_table, safe_pos, kv_lens,
                    scale=g_scale,
                    softcap=c.attn_logit_softcap,
                    window=win,
                )
            elif attn_impl == "ring":
                # sequence-parallel prefill: ring attention over this chunk's
                # fresh K/V (seq-sharded, ppermute over ICI) merged with paged
                # attention over prior context (prefix-cache hits / earlier
                # chunks, read from the seq-replicated pool) via online-softmax
                # stats — exact full-context softmax, no dense gather of the
                # chunk
                from dynamo_tpu.ops.ring_attention import ring_attention

                kv_sentinel = jnp.where(positions >= 0, positions, jnp.int32(2**30))
                out_r, m_r, l_r = ring_attention(
                    qg, k, v, positions, kv_sentinel, mesh, return_stats=True
                )
                if not sp_has_prior:
                    attn = out_r  # fresh prefill: chunk IS the full context
                else:
                    prior_lens = jnp.maximum(kv_lens - q_len, 0)
                    out_p, m_p, l_p = paged_attention_jnp(
                        qg, *kv_slab(), page_table, safe_pos, prior_lens,
                        return_stats=True,
                    )
                    m_star = jnp.maximum(m_r, m_p)
                    w_r = l_r * jnp.exp(m_r - m_star)
                    w_p = l_p * jnp.exp(m_p - m_star)
                    denom = jnp.maximum(w_r + w_p, 1e-30)
                    attn = (
                        (out_r.astype(jnp.float32) * w_r + out_p.astype(jnp.float32) * w_p)
                        / denom
                    ).astype(h.dtype)
            else:
                attn = paged_attention_jnp(qg, *kv_slab(), page_table, safe_pos, kv_lens)
        with jax.named_scope("attn.proj"):
            attn = attn.reshape(B, S, c.n_heads * hd)
            attn_out = lproj(mm(attn, lp["wo"]), attn, "wo")
            if c.post_norms:  # Gemma-2: norm the branch before the residual
                attn_out = rms_norm(
                    attn_out, lp["post_attn_norm"], c.norm_eps, zero_centered=zc
                )
            if c.residual_multiplier != 1.0:  # Granite branch scaling
                attn_out = attn_out * jnp.asarray(
                    c.residual_multiplier, attn_out.dtype
                )
            h = h + attn_out

        with jax.named_scope("ffn"):
            x = (rms_norm(h, lp["mlp_norm"], c.norm_eps, zero_centered=zc)
                 if c.pre_norms else h)
            rm = c.residual_multiplier
            if use_moe:
                ffw, *routed = moe_ffn(lp, x, l_idx)
            else:
                act = (
                    partial(jax.nn.gelu, approximate=True)
                    if c.act == "gelu_tanh" else jax.nn.silu
                )
                gate = act(lproj(mm(x, lp["w_gate"]), x, "w_gate"))
                up = lproj(mm(x, lp["w_up"]), x, "w_up")
                ffw = lproj(mm(gate * up, lp["w_down"]), gate * up, "w_down")
                if c.post_norms:
                    ffw = rms_norm(
                        ffw, lp["post_mlp_norm"], c.norm_eps, zero_centered=zc
                    )
            if rm != 1.0:  # Granite branch scaling
                ffw = ffw * jnp.asarray(rm, ffw.dtype)
            h = h + ffw
        return (h, k_pool, v_pool), routed_ys(routed)

    dense_stack = params.get("layers_dense")
    if dense_stack is not None:
        # DeepSeek first_k_dense_replace: leading dense-FFN layers run in
        # their own scan (own compiled body), then the MoE layers
        if lora_layers:
            raise NotImplementedError(
                "LoRA is not supported with n_dense_layers models"
            )
        kD = c.n_dense_layers
        (h, k_pool, v_pool), first = lax.scan(
            make_layer(False),
            (h, k_pool, v_pool),
            (dense_stack, {}, jnp.arange(kD, dtype=jnp.int32)),
        )
        (h, k_pool, v_pool), routed = lax.scan(
            make_layer(True),
            (h, k_pool, v_pool),
            (scan_layers, {},
             jnp.arange(kD, c.n_layers, dtype=jnp.int32)),
        )
        if return_routed and c.has_indexer:  # (picks, chosen) a layer
            routed, chosen = routed[0], jnp.concatenate([first[1], routed[1]])
    else:
        (h, k_pool, v_pool), routed = lax.scan(
            make_layer(c.is_moe),
            (h, k_pool, v_pool),
            (scan_layers, lora_layers,
             jnp.arange(c.n_layers, dtype=jnp.int32)),
        )

    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["norm_f"], c.norm_eps,
                     zero_centered=c.norm_zero_centered)
        if last_index is not None:
            if getattr(last_index, "ndim", 0) >= 1 and ragged is not None:
                # flat-segment forward: indices are flat token positions of
                # each segment's last token — gather them all from the one row
                h = jnp.take_along_axis(
                    h, last_index.reshape(1, -1, 1), axis=1
                )  # [1, NSEG, E]
            elif getattr(last_index, "ndim", 0) >= 1:
                # ragged packed prefill: each batch row is a different chunk
                # with its own last valid position
                h = jnp.take_along_axis(
                    h, last_index.reshape(-1, 1, 1), axis=1
                )  # [B, 1, E]
            else:
                h = lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)  # [B, 1, E]
        lm_head = params.get("lm_head")
        if lm_head is None:  # tied embeddings
            logits = tied_logits(h, params["embed"])
        else:
            logits = mm(h, lm_head)
        logits = logits.astype(jnp.float32)
        if c.logits_divider != 1.0:  # Granite
            logits = logits / c.logits_divider
        if c.final_logit_softcap:
            cap = c.final_logit_softcap
            logits = cap * jnp.tanh(logits / cap)
    if return_routed and c.has_indexer:  # after the picks: [L, B, S, W]
        if dense_stack is None:
            routed, chosen = routed
        routed = (tuple(routed) if return_listed else (routed,)) + (chosen,)
        return (logits, k_pool, v_pool) + routed
    if return_listed:
        return (logits, k_pool, v_pool) + routed  # + [L_moe]
    if return_routed:
        return logits, k_pool, v_pool, routed  # [L_moe, B, S, k]
    return logits, k_pool, v_pool


def encode(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, S]
    lengths: jax.Array,  # [B] real lengths (padding masked)
) -> jax.Array:
    """Embedding forward: dense causal self-attention (no KV pool), masked
    mean-pool of the final-norm hidden states, L2-normalized → [B, E].
    Serves /v1/embeddings (reference http/service/openai.rs:2902)."""
    c = config
    if c.is_mla:
        raise ValueError("embedding forward is not supported for MLA models")
    if c.n_dense_layers:
        raise ValueError(
            "embedding forward is not supported for mixed dense/MoE models"
        )
    if (c.post_norms or c.norm_zero_centered or c.embed_scale
            or c.attn_logit_softcap or c.sliding_window
            or c.query_pre_attn_scalar or c.act != "silu"):
        raise ValueError(
            "embedding forward is not supported for Gemma-family configs"
        )
    B, S = tokens.shape
    hd = c.head_dim
    G = c.n_heads // c.n_kv_heads
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)

    h = embed_lookup(params["embed"], tokens)

    def layer(h, xs):
        lp, _ = xs
        x = rms_norm(h, lp["attn_norm"], c.norm_eps)
        q, k, v = mm(x, lp["wq"]), mm(x, lp["wk"]), mm(x, lp["wv"])
        if c.attn_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(B, S, c.n_heads, hd)
        k = k.reshape(B, S, c.n_kv_heads, hd)
        v = v.reshape(B, S, c.n_kv_heads, hd)
        if c.qk_norm:
            q = rms_norm(q, lp["q_norm"], c.norm_eps)
            k = rms_norm(k, lp["k_norm"], c.norm_eps)
        q = rope(q, positions, c.rope_theta, config=c)
        k = rope(k, positions, c.rope_theta, config=c)
        qg = q.reshape(B, S, c.n_kv_heads, G, hd)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * hd**-0.5
        ti = jnp.arange(S)
        mask = (ti[None, :] <= ti[:, None])[None, None, None] & (
            ti[None, :] < lengths[:, None]
        )[:, None, None, None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1).astype(h.dtype)
        attn = jnp.einsum("bkgst,btkd->bskgd", probs, v).reshape(B, S, c.n_heads * hd)
        h = h + mm(attn, lp["wo"])
        x = rms_norm(h, lp["mlp_norm"], c.norm_eps)
        if c.is_moe:
            h = h + _moe_block(c, lp, x)[0]
        else:
            h = h + mm(jax.nn.silu(mm(x, lp["w_gate"])) * mm(x, lp["w_up"]), lp["w_down"])
        return h, None

    h, _ = lax.scan(
        layer, h, (params["layers"], jnp.arange(c.n_layers, dtype=jnp.int32))
    )
    h = rms_norm(h, params["norm_f"], c.norm_eps).astype(jnp.float32)
    valid = (jnp.arange(S)[None, :] < lengths[:, None]).astype(jnp.float32)
    pooled = (h * valid[..., None]).sum(1) / jnp.maximum(valid.sum(1), 1)[:, None]
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)
