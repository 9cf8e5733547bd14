"""Model architecture configs (Llama family first; MoE fields for
DeepSeek/Mixtral-style wide-EP later)."""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional


def mean_over_layers(c, on_global: int, on_sliding: int) -> int:
    """The mean over a model's layers of a count that is `on_global` on a
    layer with global attention and `on_sliding` on one under the sliding
    window (layer l is global iff l % sw_period == sw_global_residue;
    every layer is without a window). For the flight recorder's
    `*_pages_live` counters; `c` is anything with ModelConfig's four
    window fields."""
    if not c.sliding_window:
        return on_global
    pattern = getattr(c, "layer_pattern", ())
    if pattern:  # the layers said one by one (1: under the window)
        n_global = len(pattern) - sum(pattern)
    else:
        n_global = sum(l % c.sw_period == c.sw_global_residue
                       for l in range(c.n_layers))
    return round((on_global * n_global
                  + on_sliding * (c.n_layers - n_global)) / c.n_layers)


@dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    vocab_size: int = 512
    dim: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    ffn_dim: int = 128
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # attention variants (one forward serves the whole family):
    # Qwen2-style q/k/v projection biases
    attn_bias: bool = False
    # Qwen3-style per-head RMSNorm on q and k before RoPE
    qk_norm: bool = False
    # OLMo-2-style qk-norm statistics over the FULL projection width
    # (weight [H*hd], applied before the head reshape) instead of
    # per-head; only meaningful with qk_norm=True
    qk_norm_wide: bool = False
    # Gemma family:
    #   gelu_tanh MLP activation (GeGLU) instead of SiLU
    act: str = "silu"  # "silu" | "gelu_tanh"
    #   embeddings scaled by sqrt(dim) after lookup
    embed_scale: bool = False
    # Granite scalar multipliers (HF GraniteConfig): explicit embedding
    # multiplier (wins over embed_scale's sqrt(dim)), residual-branch
    # multiplier, direct attention softmax scale (wins over
    # query_pre_attn_scalar/head_dim), and a DIVIDER on the final logits
    embed_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    attn_scale: float = 0.0
    logits_divider: float = 1.0
    #   RMSNorm weights are zero-centered: output = normed * (1 + w)
    norm_zero_centered: bool = False
    #   Gemma-2 sandwich norms: post-attention and post-FFW RMSNorms on
    #   the residual branches (in addition to the pre-norms)
    post_norms: bool = False
    #   OLMo-2 drops the pre-norms entirely: the sublayer reads the raw
    #   residual stream and ONLY the post_norms above apply (set
    #   post_norms=True together with pre_norms=False)
    pre_norms: bool = True
    #   attention-score soft capping: s = cap * tanh(s / cap); 0 = off
    attn_logit_softcap: float = 0.0
    #   final-logit soft capping; 0 = off
    final_logit_softcap: float = 0.0
    #   attention scale = query_pre_attn_scalar^-0.5 (0 → head_dim^-0.5)
    query_pre_attn_scalar: float = 0.0
    #   sliding-window attention on alternating layers (Gemma-2 pattern:
    #   even layers sliding, odd global); 0 = all-global
    sliding_window: int = 0
    #   sliding pattern generalization: layer l is GLOBAL when
    #   l % sw_period == sw_global_residue, else sliding. Defaults encode
    #   Gemma-2 (period 2, residue 1: even sliding / odd global);
    #   Gemma-3 is period 6, residue 5 (5 local : 1 global).
    sw_period: int = 2
    sw_global_residue: int = 1
    #   Gemma-3 dual rope: sliding layers use this base frequency while
    #   global layers use rope_theta (+ its rope_scaling); 0 = single rope
    rope_local_theta: float = 0.0
    # explicit head_dim when it differs from dim // n_heads (Qwen3-MoE)
    head_dim_override: int = 0
    # MoE (0 experts = dense)
    n_experts: int = 0
    n_experts_active: int = 0
    moe_ffn_dim: int = 0
    # DeepSeek/Qwen2-MoE-style always-active shared experts, fused into one
    # dense FFN of width shared_ffn_dim (explicit when it isn't simply
    # n_shared_experts * moe_ffn_dim, e.g. Qwen2-MoE's 20480)
    n_shared_experts: int = 0
    shared_expert_ffn_dim: int = 0
    # router scoring: softmax over top-k logits (Mixtral/Qwen) or sigmoid
    # gates renormalized over the top-k (DeepSeek-V3)
    moe_scoring: str = "softmax"
    # HF norm_topk_prob: True renormalizes the selected weights to sum to
    # 1 (softmax-over-selected; Mixtral/Qwen3-MoE). False keeps the
    # softmax-over-ALL-experts probabilities un-renormalized (Qwen2-MoE) —
    # the routed output is deliberately scaled by sum(top-k probs) < 1.
    moe_norm_topk: bool = True
    # EP dispatch capacity per (src,dst) lane as a multiple of the even
    # split. 0.0 (default) = lossless (n_experts/n_experts_active): the EP
    # path then matches the dense path exactly, so the shape-dependent
    # EP/dense selection never changes results. Operators trade memory for
    # drops by setting e.g. 1.5.
    moe_capacity_factor: float = 0.0
    # DeepSeek-V3 router fidelity: e_score_correction_bias param present
    # (aux-loss-free balancing — shifts top-k SELECTION only) and
    # routed_scaling_factor multiplying the final mixing weights
    moe_router_bias: bool = False
    moe_routed_scale: float = 1.0
    # first k layers use a dense FFN instead of MoE (HF
    # first_k_dense_replace; DeepSeek-V3 = 3)
    n_dense_layers: int = 0
    # DeepSeek-V3 group-limited expert routing (HF n_group/topk_group):
    # experts partition into n_expert_groups; selection first keeps the
    # topk_groups best groups (by sum of each group's top-2 scores), then
    # picks top-k experts within them
    n_expert_groups: int = 0
    topk_groups: int = 0
    # This chip's share of the routed experts under expert parallelism
    # (the model-configs guide's section 4): it holds `n_experts_held`
    # of them from id `expert_first` on (0 held = all of them: every
    # preset). `n_experts` stays the router's width: the router and its
    # picks are over all experts, we_gate / we_up / we_down have the held
    # count as their expert axis, and the block computes the held
    # experts' part of the layer (models/moe.py).
    n_experts_held: int = 0
    expert_first: int = 0
    # RoPE long-context scaling (HF rope_scaling):
    #   "llama3" — Llama-3.1+ frequency smoothing (factor, low/high freq)
    #   "yarn"   — DeepSeek/Qwen yarn (factor, betas, mscale): also scales
    #              attention scores by mscale(factor)^2
    rope_scaling: str = "none"
    rope_factor: float = 1.0
    rope_orig_max_seq: int = 0  # original_max_position_embeddings
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    # MLA — multi-head latent attention (DeepSeek V2/V3/R1; reference
    # flagship model family, recipes/deepseek-r1). The KV cache stores one
    # compressed latent + decoupled-RoPE key per token instead of full
    # K/V heads: cache dim = kv_lora_rank + qk_rope_head_dim (e.g. 576 vs
    # 128 heads x 2 x 128 = 32768 for V3 — 57x smaller).
    attn_type: str = "gqa"  # "gqa" | "mla"
    kv_lora_rank: int = 0  # d_c: KV latent dim
    q_lora_rank: int = 0  # query compression rank (0 = direct q proj)
    qk_rope_head_dim: int = 0  # decoupled positional key dim (shared head)
    qk_nope_head_dim: int = 0  # per-head content key dim
    v_head_dim: int = 0
    # position-dependent query scale of latent attention (Mistral-Small-4's
    # `llama_4_scaling_beta`): the query of position p is multiplied by
    # 1 + beta * ln(1 + floor(p / orig)); exactly 1 below `orig`. 0 = off
    attn_qscale_beta: float = 0.0
    attn_qscale_orig: int = 0
    # DeepSeek-V3.2's lightning indexer (models/mla.py): every layer scores
    # the cached tokens for each query with `index_n_heads` small heads of
    # `index_head_dim` against ONE cached index key a token, and latent
    # attention's softmax runs over the `index_topk` best of them alone. The
    # index keys live in the pool's second array, under the latent pages'
    # own page table. 0 = no indexer: every preset but the dsa ones. A
    # context of at most `index_topk` tokens is attended to whole.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    # Mamba-1 selective state-space layers (Jamba; models/jamba.py): layer
    # l is attention iff l % attn_layer_period == attn_layer_offset, and a
    # Mamba mixer of inner width mamba_expand x dim otherwise. 0 states = no
    # such layer: every preset but the jamba ones. A sequence then carries,
    # besides its KV pages, one constant-size recurrent state (a state slot).
    # Such a model's attention has no position term (the state-space layers
    # carry the order of the tokens): the rope_* fields are not read.
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0
    mamba_expand: int = 2
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    # Attention layers of two kinds, said layer by layer (MiMo-V2-Flash's
    # `hybrid_layer_pattern`; models/mimo.py): `layer_pattern[l]` is 0 for
    # a layer of global attention (`n_kv_heads` KV heads, `rope_theta`) and
    # 1 for one under `sliding_window` (`n_kv_heads_window` KV heads,
    # `rope_local_theta`). Empty: every preset but the mimo ones. The two
    # kinds keep caches of their own: the KV pool holds the global layers,
    # a WINDOW pool the others, for the last `sliding_window` tokens of a
    # sequence alone (a second page table a sequence; pages are freed as
    # they leave the window). With it on `gqa`: `v_head_dim` is the value
    # heads' size where it differs from the keys' `head_dim`,
    # `rope_partial_dims` the leading dims of a head the rotary turns (0:
    # all), `attn_value_scale` a scalar on the values (0: none), and
    # `sink_window` / `sink_global` give the kind's layers a learned sink
    # logit a query head: one more column of the softmax's denominator.
    layer_pattern: tuple = ()
    n_kv_heads_window: int = 0
    rope_partial_dims: int = 0
    attn_value_scale: float = 0.0
    sink_window: bool = False
    sink_global: bool = False
    # Kimi-delta-attention layers beside gated latent attention (Ling-3.0;
    # models/ling.py): layer l is latent attention iff (l + 1) %
    # kda_layer_period == 0 and a KDA mixer otherwise: `n_heads` heads whose
    # keys and values are `kda_head_dim` wide, a matrix state [d_k, d_v] a
    # head updated by a per-channel gated delta rule (ops/kda.py), causal
    # convolutions of `kda_conv` taps on q, k and v, the log-decay a channel
    # in (`kda_gate_lower`, 0). 0 = no such layer: every preset but the ling
    # ones. A sequence then carries, besides the latent pages of its MLA
    # layers, one state slot (engine/side_cache.StateSlots). Both kinds of
    # mixer end in a head-wise sigmoid output gate.
    kda_layer_period: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_gate_lower: float = -5.0
    # A decoder-hybrid-decoder (Phi-4-mini-flash-reasoning's `mb_per_layer`;
    # models/sambay.py): the first half of the n_layers (a multiple of 4)
    # alternates Mamba-1 mixers (even layers up to n/2) with differential
    # attention under `sliding_window` (odd layers below n/2); layer n/2 + 1
    # is the one layer of full differential attention, whose keys and values
    # the odd layers from n/2 + 3 on read again with queries of their own
    # (cross attention: no cache of theirs), and the even layers from n/2 + 2
    # on are gated memory units on layer n/2's scan output (no cache, no
    # recurrence): `layer_kinds`. 0 = no such model: every preset but the
    # phi4flash ones; 2 is the only period the walk is written for. A
    # sequence then carries a state slot AND window pages beside the pages
    # of the one full layer; no position term enters the scores; the norms
    # are LayerNorms with bias. Heads are paired (differential attention):
    # the pools hold a pair of KV heads as one head of 2 x head_dim.
    mb_per_layer: int = 0

    def __post_init__(self):
        if self.layer_pattern:
            # JSON hands a list in (benchmark/serve.py: ModelConfig(**model))
            object.__setattr__(self, "layer_pattern",
                               tuple(int(k) for k in self.layer_pattern))
            if (len(self.layer_pattern) != self.n_layers
                    or set(self.layer_pattern) - {0, 1}
                    or self.sliding_window <= 0
                    or self.n_kv_heads_window <= 0
                    or self.n_heads % self.n_kv_heads_window
                    or self.attn_type != "gqa" or self.mamba_d_state
                    or self.rope_partial_dims % 2
                    or self.rope_partial_dims > self.head_dim):
                raise ValueError(
                    "layer_pattern says each of n_layers layers 0 (global) or "
                    "1 (window) and needs sliding_window > 0, "
                    "n_kv_heads_window dividing n_heads, an even "
                    "rope_partial_dims within head_dim, and grouped-query "
                    "attention without state-space layers")
        elif (self.n_kv_heads_window or self.rope_partial_dims
              or self.attn_value_scale or self.sink_window or self.sink_global
              or (self.v_head_dim and self.attn_type == "gqa")):
            raise ValueError(
                "n_kv_heads_window, rope_partial_dims, attn_value_scale, the "
                "sinks and a value head size on gqa are read by the walk over "
                "a layer_pattern alone (models/mimo.py): state the pattern")
        if not self.pre_norms and not self.post_norms:
            # the layer would have NO norms at all — and paths gated only
            # on post_norms/qk_norm would KeyError deep inside lax.scan
            raise ValueError(
                "pre_norms=False requires post_norms=True (OLMo-2 style: "
                "the branch outputs are normed instead of the inputs)"
            )
        if self.n_experts_held or self.expert_first:
            if not (0 <= self.expert_first and 0 < self.n_experts_held
                    and self.expert_first + self.n_experts_held <= self.n_experts):
                raise ValueError(
                    f"held experts [{self.expert_first}, {self.expert_first} + "
                    f"{self.n_experts_held}) do not lie inside the router's "
                    f"{self.n_experts}"
                )
        if self.attn_qscale_beta and (
                self.attn_type != "mla" or self.attn_qscale_orig <= 0):
            raise ValueError(
                "attn_qscale_beta is latent attention's query scale and "
                "needs attn_type='mla' and attn_qscale_orig > 0"
            )
        if self.index_topk or self.index_n_heads or self.index_head_dim:
            if not (self.is_mla and self.q_lora_rank > 0
                    and self.index_topk > 0 and self.index_n_heads > 0
                    and self.index_head_dim >= self.qk_rope_head_dim > 0
                    and self.index_head_dim % 2 == 0):
                raise ValueError(
                    "index_topk, index_n_heads and index_head_dim are latent "
                    "attention's indexer and come together: they need "
                    "attn_type='mla', q_lora_rank > 0 (the index queries are "
                    "projected from the compressed query) and an even "
                    "index_head_dim of at least qk_rope_head_dim (the rotary "
                    "turns a head's first qk_rope_head_dim dims)")
        if self.is_kda:
            if not (self.is_mla and self.kda_layer_period > 1
                    and self.kda_head_dim > 0 and self.kda_conv > 1
                    and self.kda_gate_lower < 0):
                raise ValueError(
                    "KDA layers (models/ling.py) need attn_type='mla' for "
                    "the layer that closes each kda_layer_period > 1, "
                    "kda_head_dim > 0, kda_conv > 1 and kda_gate_lower < 0")
            if (self.is_hybrid or self.has_indexer or self.layer_pattern
                    or self.sliding_window or self.tie_embeddings
                    or self.attn_qscale_beta or self.attn_bias
                    or self.post_norms or not self.pre_norms
                    or self.act != "silu"):
                raise ValueError(
                    "a model with KDA layers is Ling-3.0's: plain pre-norm "
                    "residuals, SwiGLU, an untied head, latent attention "
                    "without indexer, window or query scale")
        elif self.kda_head_dim:
            raise ValueError(
                "kda_head_dim is read by the walk over KDA layers alone "
                "(models/ling.py): state kda_layer_period")
        if self.is_sambay:
            if not (self.mb_per_layer == 2 and self.n_layers % 4 == 0
                    and self.n_layers >= 8 and self.sliding_window > 0
                    and self.mamba_d_state > 0 and self.mamba_dt_rank > 0
                    and self.n_heads % 2 == 0 and self.n_kv_heads % 2 == 0
                    and self.n_heads % self.n_kv_heads == 0):
                raise ValueError(
                    "a decoder-hybrid-decoder (models/sambay.py) needs "
                    "mb_per_layer 2, n_layers a multiple of 4 and at least 8 "
                    "(the fewest with every kind of layer), sliding_window > "
                    "0, mamba_d_state > 0, mamba_dt_rank > 0, and paired "
                    "heads: n_heads and n_kv_heads even, the one dividing "
                    "the other")
            if (self.is_mla or self.is_moe or self.layer_pattern
                    or self.is_kda or self.attn_layer_period
                    or not self.tie_embeddings or self.qk_norm
                    or self.post_norms or not self.pre_norms
                    or self.act != "silu"):
                raise ValueError(
                    "a decoder-hybrid-decoder is Phi-4-mini-flash's: dense "
                    "gated MLPs, tied embedding, differential attention with "
                    "no position term, no experts, latents or layer pattern")
        if self.is_hybrid:
            if not (0 <= self.attn_layer_offset < self.attn_layer_period
                    and self.mamba_dt_rank > 0):
                raise ValueError(
                    "state-space layers need attn_layer_period > "
                    "attn_layer_offset >= 0 and mamba_dt_rank > 0"
                )
            if (self.is_mla or self.is_moe or self.sliding_window
                    or not self.tie_embeddings
                    or self.attn_bias or self.qk_norm or self.post_norms
                    or not self.pre_norms or self.act != "silu"):
                raise ValueError(
                    "a hybrid state-space model (models/jamba.py) is Jamba's: "
                    "dense SwiGLU MLPs, tied embedding, grouped-query "
                    "attention with no position term, window, bias or "
                    "extra norm"
                )

    @property
    def is_hybrid(self) -> bool:
        """State-space layers beside the attention layers (Jamba)."""
        return self.mamba_d_state > 0 and not self.is_sambay

    @property
    def is_sambay(self) -> bool:
        """A decoder-hybrid-decoder (Phi-4-mini-flash; models/sambay.py)."""
        return self.mb_per_layer > 0

    @property
    def has_cross_decoder(self) -> bool:
        """The layers behind the last cache write no cache and no state (a
        decoder-hybrid-decoder's gated memory units and cross attention):
        only a row whose logits are read needs them, and a runner serves a
        prefill chunk nobody samples without them (Runner.skips_unsampled)."""
        return self.is_sambay

    @property
    def layer_kinds(self) -> tuple:
        """A decoder-hybrid-decoder's layers, one word each: "mamba",
        "window", "full" (the layer whose KV is shared), "gmu", "cross"."""
        half = self.n_layers // 2
        return tuple(
            ("mamba" if l <= half else "gmu") if l % 2 == 0 else
            "window" if l < half else "full" if l == half + 1 else "cross"
            for l in range(self.n_layers))

    @property
    def is_kda(self) -> bool:
        """Kimi-delta-attention layers beside the latent attention (Ling)."""
        return self.kda_layer_period > 0

    def is_attn_layer(self, l: int) -> bool:
        if self.is_kda:
            return (l + 1) % self.kda_layer_period == 0
        if self.is_sambay:  # the one layer whose keys and values are kept
            return l == self.n_layers // 2 + 1
        return (not self.is_hybrid
                or l % self.attn_layer_period == self.attn_layer_offset)

    @property
    def attn_layers(self) -> tuple:
        """The layers that hold KV, in model order; an attention layer's
        index into the KV pool is its rank here."""
        return tuple(l for l in range(self.n_layers) if self.is_attn_layer(l))

    @property
    def kv_layers(self) -> int:
        """Layers of the KV pool: a hybrid model's attention layers, a
        window-pool model's global layers, else all."""
        if self.has_window_pool:
            return len(self.global_layers)
        if self.is_hybrid or self.is_kda or self.is_sambay:
            return len(self.attn_layers)
        return self.n_layers

    @property
    def kda_layers(self) -> int:
        return self.n_layers - self.kv_layers if self.is_kda else 0

    @property
    def has_window_pool(self) -> bool:
        """Window and global attention layers with caches of their own."""
        return bool(self.layer_pattern)

    @property
    def global_layers(self) -> tuple:
        """The layers of global attention, in model order; a layer's index
        into the KV pool is its rank here."""
        return tuple(l for l, k in enumerate(self.layer_pattern) if k == 0)

    @property
    def window_layers(self) -> tuple:
        """The layers under the sliding window (rank: the window pool's)."""
        return tuple(l for l, k in enumerate(self.layer_pattern) if k == 1)

    @property
    def key_pool_dim(self) -> int:
        """The width a key takes in the pools of a window-pool model:
        `head_dim`, rounded up to whole 128-lane rows where it spans more
        than one (192 -> 256, zeros behind the key). The device pads such a
        key to whole rows anyway; said in the shape, XLA lays the pool out
        token-major as the kernels read it, where a pool of 192-wide keys
        and thousands of pages gets the PAGE axis minor and is converted at
        every step program's entry and exit (PERF.md section 6, PR 40)."""
        d = self.head_dim
        return d if d <= 128 else -(-d // 128) * 128

    @property
    def pool_heads(self) -> int:
        """Heads of a decoder-hybrid-decoder's pools (models/sambay.py): a
        pair of the model's KV heads as one head of 2 x head_dim, and the
        count rounded up to whole 8-row tiles where it spans more than one
        (10 -> 16, zeros behind the pairs). The device pads a token's (10,
        128) slab to 16 rows anyway, and a pool of 10-row slabs and
        thousands of pages is laid out head-major by XLA and converted,
        whole, at every step program's entry and exit (the v5e compiler,
        PR 54: 18.96 GB of 15.75 for one decode step; `key_pool_dim` is the
        same finding on the lane axis)."""
        pairs = self.n_kv_heads // 2
        return pairs if pairs <= 8 else -(-pairs // 8) * 8

    @property
    def value_dim(self) -> int:
        """A value head's size on gqa (the keys' unless told)."""
        return self.v_head_dim or self.head_dim

    @property
    def mamba_layers(self) -> int:
        if self.is_sambay:
            return self.layer_kinds.count("mamba")
        return self.n_layers - self.kv_layers

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or (self.dim // self.n_heads)

    @property
    def is_mla(self) -> bool:
        return self.attn_type == "mla"

    @property
    def mla_cache_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mla_pool_dim(self) -> int:
        """The width a token takes in the latent pool. A model with an
        indexer states it in whole 128-lane rows where it spans more than
        one (576 -> 640, zeros behind the rotary key), as `key_pool_dim`
        does and for its reason: the device pads a row to that anyway, and
        a pool of 576-wide rows and thousands of pages got the PAGE axis
        minor, converted at the entry and the exit of every step program
        (0.4 s a dispatch at 4096 pages: PERF.md section 6, PR 44). Every
        other model's pool stays `mla_cache_dim` wide, as it was: the cause
        is the width and the page count, not the indexer, so `deepseek-v3`
        at thousands of pages is knowingly left on the slow layout until a
        PR of its own may move `mistral-small-4-119b`'s step programs (the
        rule then: `mla_cache_dim > 128` and not a whole number of rows). A
        model with KDA layers (PR 49) is new to the tree and takes the whole
        rows from the start."""
        d = self.mla_cache_dim
        wide = self.has_indexer or self.is_kda
        return -(-d // 128) * 128 if wide and d > 128 else d

    @property
    def has_indexer(self) -> bool:
        """Latent attention over the indexer's top `index_topk` tokens."""
        return self.index_topk > 0

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this chip holds (all, unless told)."""
        return self.n_experts_held or self.n_experts

    @property
    def holds_share(self) -> bool:
        return self.experts_held < self.n_experts

    @property
    def shared_ffn_dim(self) -> int:
        return self.shared_expert_ffn_dim or self.n_shared_experts * self.moe_ffn_dim

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)


PRESETS: Dict[str, ModelConfig] = {
    # test-size model (CPU-mesh CI)
    "tiny": ModelConfig(),
    "tiny-moe": ModelConfig(
        name="tiny-moe", n_experts=4, n_experts_active=2, moe_ffn_dim=96
    ),
    # test-size second/third architectures (CPU CI for the qwen family)
    "tiny-qwen2": ModelConfig(name="tiny-qwen2", attn_bias=True),
    "tiny-qwen3": ModelConfig(
        name="tiny-qwen3", qk_norm=True, head_dim_override=32,
    ),
    # deepseek-style MoE: shared expert + sigmoid router scoring
    "tiny-moe-shared": ModelConfig(
        name="tiny-moe-shared", n_experts=4, n_experts_active=2,
        moe_ffn_dim=96, n_shared_experts=1, moe_scoring="sigmoid",
    ),
    # Gemma-2 test model (CPU CI for the Gemma family: GeGLU, scaled
    # embeddings, zero-centered sandwich norms, softcaps, sliding window)
    "tiny-gemma2": ModelConfig(
        name="tiny-gemma2", tie_embeddings=True, act="gelu_tanh",
        embed_scale=True, norm_zero_centered=True, post_norms=True,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        query_pre_attn_scalar=16.0, sliding_window=8, rope_theta=10000.0,
    ),
    # Gemma-3 test model (qk-norm, 2:1 local/global window pattern, dual
    # rope bases — the production pattern is 5:1 with period 6)
    "tiny-gemma3": ModelConfig(
        name="tiny-gemma3", n_layers=3, tie_embeddings=True,
        act="gelu_tanh", embed_scale=True, norm_zero_centered=True,
        post_norms=True, qk_norm=True, query_pre_attn_scalar=16.0,
        sliding_window=8, sw_period=3, sw_global_residue=2,
        rope_theta=100000.0, rope_local_theta=10000.0,
    ),
    # MLA test models (CPU CI for the DeepSeek attention family)
    "tiny-mla": ModelConfig(
        name="tiny-mla", attn_type="mla", kv_lora_rank=32,
        qk_rope_head_dim=16, qk_nope_head_dim=32, v_head_dim=32,
    ),
    "tiny-mla-q": ModelConfig(  # with query compression (V3-style q path)
        name="tiny-mla-q", attn_type="mla", kv_lora_rank=32, q_lora_rank=48,
        qk_rope_head_dim=16, qk_nope_head_dim=32, v_head_dim=32,
    ),
    # MLA + wide-EP MoE (the deepseek-style-wideep recipe's dryrun model):
    # full V3 feature set at test size — router selection bias, routed
    # scale, one leading dense layer
    "tiny-mla-moe": ModelConfig(
        name="tiny-mla-moe", n_layers=3, attn_type="mla", kv_lora_rank=32,
        qk_rope_head_dim=16, qk_nope_head_dim=32, v_head_dim=32,
        n_experts=4, n_experts_active=2, moe_ffn_dim=96,
        n_shared_experts=1, moe_scoring="sigmoid",
        moe_router_bias=True, moe_routed_scale=2.5, n_dense_layers=1,
    ),
    # DeepSeek-V3.2's structure at test size (CPU CI): the tiny-mla-moe
    # block with a compressed query, the lightning indexer over it (4 heads
    # of 16, the 8 best tokens attended to), one leading dense layer, 16
    # experts in 4 groups of which 2 stay, the second quarter of them held
    "tiny-dsa": ModelConfig(
        name="tiny-dsa", n_layers=3, attn_type="mla", kv_lora_rank=32,
        q_lora_rank=48, qk_rope_head_dim=16, qk_nope_head_dim=32,
        v_head_dim=32, index_topk=8, index_n_heads=4, index_head_dim=16,
        n_experts=16, n_experts_active=4, moe_ffn_dim=64,
        n_shared_experts=1, moe_scoring="sigmoid", moe_router_bias=True,
        moe_routed_scale=2.5, n_dense_layers=1, n_expert_groups=4,
        topk_groups=2, n_experts_held=4, expert_first=4, norm_eps=1e-6,
        rope_theta=10000.0, rope_scaling="yarn", rope_factor=40.0,
        rope_orig_max_seq=8, rope_mscale=1.0, rope_mscale_all_dim=1.0,
    ),
    # Llama 3.2 1B (fits one v5e chip in bf16 with room for KV)
    "llama-3.2-1b": ModelConfig(
        name="llama-3.2-1b",
        vocab_size=128256,
        dim=2048,
        n_layers=16,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=8192,
        max_seq_len=131072,
        rope_theta=500000.0,
        tie_embeddings=True,
        rope_scaling="llama3", rope_factor=32.0, rope_orig_max_seq=8192,
    ),
    # Llama 3.2 3B — single-chip flagship: head_dim 128 (TPU lane-aligned KV
    # tiles), ~6.4GB bf16, fits one v5e chip with a large KV pool
    "llama-3.2-3b": ModelConfig(
        name="llama-3.2-3b",
        vocab_size=128256,
        dim=3072,
        n_layers=28,
        n_heads=24,
        n_kv_heads=8,
        ffn_dim=8192,
        max_seq_len=131072,
        rope_theta=500000.0,
        tie_embeddings=True,
        rope_scaling="llama3", rope_factor=32.0, rope_orig_max_seq=8192,
    ),
    # Llama 3.1 8B (reference BASELINE config #1 model)
    "llama-3.1-8b": ModelConfig(
        name="llama-3.1-8b",
        vocab_size=128256,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14336,
        max_seq_len=131072,
        rope_scaling="llama3", rope_factor=8.0, rope_orig_max_seq=8192,
    ),
    # Qwen 2.5 7B (second architecture family: attention biases)
    "qwen2.5-7b": ModelConfig(
        name="qwen2.5-7b",
        vocab_size=152064,
        dim=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        ffn_dim=18944,
        max_seq_len=32768,
        rope_theta=1000000.0,
        norm_eps=1e-6,
        attn_bias=True,
    ),
    # Qwen3 8B (qk-norm family)
    "qwen3-8b": ModelConfig(
        name="qwen3-8b",
        vocab_size=151936,
        dim=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=12288,
        max_seq_len=40960,
        rope_theta=1000000.0,
        norm_eps=1e-6,
        qk_norm=True,
        head_dim_override=128,
    ),
    # Qwen3 30B-A3B: wide-EP flagship recipe (128 experts, top-8) — the
    # analog of the reference's wide-EP MoE recipes (recipes/deepseek-r1):
    # EP=8..32 meshes dispatch tokens over ICI via ops/moe_dispatch.py
    "qwen3-30b-a3b": ModelConfig(
        name="qwen3-30b-a3b",
        vocab_size=151936,
        dim=2048,
        n_layers=48,
        n_heads=32,
        n_kv_heads=4,
        ffn_dim=6144,  # unused (all layers MoE)
        max_seq_len=40960,
        rope_theta=1000000.0,
        norm_eps=1e-6,
        qk_norm=True,
        head_dim_override=128,
        n_experts=128,
        n_experts_active=8,
        moe_ffn_dim=768,
    ),
    # DeepSeek-V3/R1 (671B-A37B): the reference's flagship BASELINE model
    # (README.md:78, recipes/deepseek-r1 wide-EP). MLA + 256-expert
    # sigmoid-scored MoE (selection-bias balancing, routed scale 2.5, one
    # shared expert) with the first 3 layers dense (first_k_dense_replace).
    "deepseek-v3": ModelConfig(
        name="deepseek-v3",
        vocab_size=129280,
        dim=7168,
        n_layers=61,
        n_heads=128,
        n_kv_heads=128,
        ffn_dim=18432,
        max_seq_len=163840,
        rope_theta=10000.0,
        norm_eps=1e-6,
        attn_type="mla",
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_rope_head_dim=64,
        qk_nope_head_dim=128,
        v_head_dim=128,
        n_experts=256,
        n_experts_active=8,
        moe_ffn_dim=2048,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        moe_router_bias=True,
        moe_routed_scale=2.5,
        n_dense_layers=3,
        n_expert_groups=8,
        topk_groups=4,
        rope_scaling="yarn",
        rope_factor=40.0,
        rope_orig_max_seq=4096,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=1.0,
        rope_mscale_all_dim=1.0,
    ),
    # DeepSeek-V3.2 (685B-A37B): the V3 block with the lightning indexer in
    # every layer (64 heads of 128 on the compressed query, one 128-wide
    # index key a token) and latent attention over its 2048 best tokens.
    # The multi-token-prediction module (layer 61) is left out
    # (benchmark/configs/deepseek-v3.2.json holds one chip's share)
    "deepseek-v3.2": ModelConfig(
        name="deepseek-v3.2", vocab_size=129280, dim=7168, n_layers=61,
        n_heads=128, n_kv_heads=128, ffn_dim=18432, max_seq_len=163840,
        rope_theta=10000.0, norm_eps=1e-6, attn_type="mla",
        kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64,
        qk_nope_head_dim=128, v_head_dim=128, index_topk=2048,
        index_n_heads=64, index_head_dim=128, n_experts=256,
        n_experts_active=8, moe_ffn_dim=2048, n_shared_experts=1,
        moe_scoring="sigmoid", moe_router_bias=True, moe_routed_scale=2.5,
        n_dense_layers=3, n_expert_groups=8, topk_groups=4,
        rope_scaling="yarn", rope_factor=40.0, rope_orig_max_seq=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0,
    ),
    # Granite 3.1 8B (Llama layout + the four Granite scalar
    # multipliers; logits_scaling divides the final logits)
    "granite-3.1-8b": ModelConfig(
        name="granite-3.1-8b",
        vocab_size=49155,
        dim=4096,
        n_layers=40,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=12800,
        max_seq_len=131072,
        rope_theta=10000000.0,
        norm_eps=1e-5,
        tie_embeddings=True,
        embed_multiplier=12.0,
        residual_multiplier=0.22,
        attn_scale=0.0078125,
        logits_divider=16.0,
    ),
    # OLMo-2 7B (reordered norms: post-only on the branch outputs; wide
    # qk-norm over the full projection width)
    "olmo-2-7b": ModelConfig(
        name="olmo-2-7b",
        vocab_size=100352,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,
        ffn_dim=11008,
        max_seq_len=4096,
        rope_theta=500000.0,
        norm_eps=1e-6,
        pre_norms=False,
        post_norms=True,
        qk_norm=True,
        qk_norm_wide=True,
    ),
    # Phi-3 mini 4k (fused qkv/gate_up checkpoint layout; every-layer
    # sliding window like Mistral)
    "phi-3-mini-4k": ModelConfig(
        name="phi-3-mini-4k",
        vocab_size=32064,
        dim=3072,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,
        ffn_dim=8192,
        max_seq_len=4096,
        rope_theta=10000.0,
        norm_eps=1e-5,
        sliding_window=2047,
        sw_period=1,
        sw_global_residue=1,
    ),
    # latent attention with a compressed query and the position-dependent
    # query scale (toy `orig`, so that it is not 1 in a short test), routed
    # experts of which this chip holds the second quarter: Mistral-Small-4's
    # structure at test size (CPU CI)
    "tiny-mistral4": ModelConfig(
        name="tiny-mistral4", n_layers=2, attn_type="mla", kv_lora_rank=32,
        q_lora_rank=48, qk_rope_head_dim=16, qk_nope_head_dim=16,
        v_head_dim=32, n_experts=16, n_experts_active=4, moe_ffn_dim=64,
        n_shared_experts=1, n_experts_held=4, expert_first=4,
        attn_qscale_beta=0.1, attn_qscale_orig=8, norm_eps=1e-6,
        rope_theta=10000.0, rope_scaling="yarn", rope_factor=128.0,
        rope_orig_max_seq=8, rope_mscale=1.0, rope_mscale_all_dim=1.0,
    ),
    # Mistral-Small-4-119B-2603 (latent attention at rank 256 with a
    # compressed query, 128 routed experts of width 2048, four a token,
    # one shared; all 36 layers alike). 238 GB in bf16: a chip holds a
    # share (n_experts_held / expert_first, benchmark/configs/
    # mistral-small-4-119b.json)
    "mistral-small-4-119b": ModelConfig(
        name="mistral-small-4-119b",
        vocab_size=131072,
        dim=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=32,
        ffn_dim=12288,  # unused (no dense layer)
        max_seq_len=1048576,
        rope_theta=10000.0,
        norm_eps=1e-6,
        attn_type="mla",
        kv_lora_rank=256,
        q_lora_rank=1024,
        qk_rope_head_dim=64,
        qk_nope_head_dim=64,
        v_head_dim=128,
        n_experts=128,
        n_experts_active=4,
        moe_ffn_dim=2048,
        n_shared_experts=1,
        moe_scoring="softmax",
        moe_norm_topk=True,
        moe_routed_scale=1.0,
        rope_scaling="yarn",
        rope_factor=128.0,
        rope_orig_max_seq=8192,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=1.0,
        rope_mscale_all_dim=1.0,
        attn_qscale_beta=0.1,
        attn_qscale_orig=8192,
    ),
    # Jamba's structure at test size (CPU CI): period 4 with the attention
    # layer at 2, one KV head
    "tiny-jamba": ModelConfig(
        name="tiny-jamba", n_layers=8, n_heads=4, n_kv_heads=1,
        tie_embeddings=True, norm_eps=1e-6, mamba_d_state=4,
        mamba_dt_rank=8, attn_layer_period=4, attn_layer_offset=2,
    ),
    # Phi-4-mini-flash's structure at test size (models/sambay.py): the
    # fewest layers with every kind (Mamba 0 2 4, window 1 3, the full layer
    # 5, a gated memory unit 6, cross attention 7); 4 query heads on 2 KV
    # heads of 16 (two query pairs on one KV pair of 32), window 16 (two
    # pages at page size 8)
    "tiny-phi4flash": ModelConfig(
        name="tiny-phi4flash", n_layers=8, n_heads=4, n_kv_heads=2,
        tie_embeddings=True, norm_eps=1e-5, sliding_window=16,
        mamba_d_state=4, mamba_dt_rank=8, mb_per_layer=2,
    ),
    # Phi-4-mini-flash-reasoning as published (3.85 B): 32 layers, 40 query
    # heads on 20 KV heads of 64, window 512, Mamba-1 at the family's
    # defaults (benchmark/configs/phi-4-mini-flash-reasoning.json `assumed`)
    "phi-4-mini-flash-reasoning": ModelConfig(
        name="phi-4-mini-flash-reasoning", vocab_size=200064, dim=2560,
        n_layers=32, n_heads=40, n_kv_heads=20, ffn_dim=10240,
        max_seq_len=262144, norm_eps=1e-5, tie_embeddings=True,
        sliding_window=512, mamba_d_state=16, mamba_d_conv=4,
        mamba_dt_rank=160, mamba_expand=2, mb_per_layer=2,
    ),
    # Ling-3.0's structure at test size (CPU CI; models/ling.py): two periods
    # of three (KDA, KDA, MLA), the first layer dense, 4 heads of 16 in the
    # KDA layers, latent rank 32 without query compression, 16 sigmoid
    # experts in 4 groups of which 2 stay, the second quarter held
    "tiny-ling": ModelConfig(
        name="tiny-ling", n_layers=6, n_heads=4, n_kv_heads=4, attn_type="mla",
        kv_lora_rank=32, qk_rope_head_dim=16, qk_nope_head_dim=32,
        v_head_dim=32, kda_layer_period=3, kda_head_dim=16, norm_eps=1e-6,
        rope_theta=6e6, n_experts=16, n_experts_active=4, moe_ffn_dim=64,
        n_shared_experts=1, moe_scoring="sigmoid", moe_router_bias=True,
        moe_routed_scale=2.5, n_dense_layers=1, n_expert_groups=4,
        topk_groups=2, n_experts_held=4, expert_first=4,
    ),
    # test-size MiMo-V2-Flash structure (models/mimo.py): a dense global
    # layer, four window layers, a global one, a window one; window 16 (two
    # pages at page size 8); 4 query heads on 1 (global) / 2 (window) KV
    # heads, keys 24 and values 16 wide with 8 rotary dims, sinks on the
    # window layers; 8 sigmoid-routed experts, 2 a token, behind the bias
    "tiny-mimo": ModelConfig(
        name="tiny-mimo", n_layers=7, n_heads=4, n_kv_heads=1,
        n_kv_heads_window=2, head_dim_override=24, v_head_dim=16,
        rope_partial_dims=8, attn_value_scale=0.707, sliding_window=16,
        layer_pattern=(0, 1, 1, 1, 1, 0, 1), sink_window=True,
        rope_theta=5e6, rope_local_theta=1e4, n_dense_layers=1,
        n_experts=8, n_experts_active=2, moe_ffn_dim=32,
        moe_scoring="sigmoid", moe_router_bias=True,
    ),
    # MiMo-V2-Flash as published (309 B, 15 B active): 48 layers, global
    # attention at 0, 5, 11, ... (4 KV heads, theta 5e6), the others under a
    # window of 128 (8 KV heads, theta 1e4, a sink logit a head); keys 192
    # and values 128 wide, 64 rotary dims; layer 0 dense, then 256
    # sigmoid-routed experts of width 2048, 8 a token, no shared expert
    "mimo-v2-flash": ModelConfig(
        name="mimo-v2-flash", vocab_size=152576, dim=4096, n_layers=48,
        n_heads=64, n_kv_heads=4, n_kv_heads_window=8, ffn_dim=16384,
        max_seq_len=262144, head_dim_override=192, v_head_dim=128,
        rope_partial_dims=64, attn_value_scale=0.707, sliding_window=128,
        layer_pattern=tuple(0 if l == 0 or l % 6 == 5 else 1
                            for l in range(48)),
        sink_window=True, rope_theta=5e6, rope_local_theta=1e4,
        n_dense_layers=1, n_experts=256, n_experts_active=8,
        moe_ffn_dim=2048, moe_scoring="sigmoid", moe_router_bias=True,
    ),
    # AI21-Jamba2-3B (also published as Jamba Reasoning 3B): 26 Mamba-1
    # mixers and 2 attention layers (7 and 21) of 28, every MLP dense, MQA
    # at 20 heads on 1 KV head of 128, no position term, tied embedding
    "ai21-jamba2-3b": ModelConfig(
        name="ai21-jamba2-3b",
        vocab_size=65536,
        dim=2560,
        n_layers=28,
        n_heads=20,
        n_kv_heads=1,
        ffn_dim=8192,
        max_seq_len=262144,
        norm_eps=1e-6,
        tie_embeddings=True,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_dt_rank=160,
        mamba_expand=2,
        attn_layer_period=14,
        attn_layer_offset=7,
    ),
    # Mistral 7B v0.1 (every-layer sliding window via the period-1
    # schedule: (l % 1) == 1 never holds, so no layer is global)
    "mistral-7b": ModelConfig(
        name="mistral-7b",
        vocab_size=32000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14336,
        max_seq_len=32768,
        rope_theta=10000.0,
        norm_eps=1e-5,
        sliding_window=4096,
        sw_period=1,
        sw_global_residue=1,
    ),
    # Mixtral 8x7B (classic sparse-MoE family; block_sparse_moe layout)
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        ffn_dim=14336,
        max_seq_len=32768,
        rope_theta=1000000.0,
        norm_eps=1e-5,
        n_experts=8,
        n_experts_active=2,
        moe_ffn_dim=14336,
        moe_scoring="softmax",
        moe_norm_topk=True,
    ),
    # Gemma 1 7B (GeGLU + scaled embeddings + zero-centered norms; MHA
    # with head_dim 256 wider than dim/n_heads)
    "gemma-7b": ModelConfig(
        name="gemma-7b",
        vocab_size=256000,
        dim=3072,
        n_layers=28,
        n_heads=16,
        n_kv_heads=16,
        ffn_dim=24576,
        max_seq_len=8192,
        rope_theta=10000.0,
        norm_eps=1e-6,
        tie_embeddings=True,
        act="gelu_tanh",
        embed_scale=True,
        norm_zero_centered=True,
        head_dim_override=256,
    ),
    # Gemma 2 9B (fourth architecture family)
    "gemma-2-9b": ModelConfig(
        name="gemma-2-9b",
        vocab_size=256000,
        dim=3584,
        n_layers=42,
        n_heads=16,
        n_kv_heads=8,
        ffn_dim=14336,
        max_seq_len=8192,
        rope_theta=10000.0,
        norm_eps=1e-6,
        tie_embeddings=True,
        head_dim_override=256,
        act="gelu_tanh",
        embed_scale=True,
        norm_zero_centered=True,
        post_norms=True,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        query_pre_attn_scalar=256.0,
        sliding_window=4096,
    ),
    # Llama 3.1 70B (BASELINE north-star model; TP=8 on v5e)
    "llama-3.1-70b": ModelConfig(
        name="llama-3.1-70b",
        vocab_size=128256,
        dim=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        ffn_dim=28672,
        max_seq_len=131072,
        rope_scaling="llama3", rope_factor=8.0, rope_orig_max_seq=8192,
    ),
}


def get_config(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model config {name!r}; have {sorted(PRESETS)}")
    return PRESETS[name]
