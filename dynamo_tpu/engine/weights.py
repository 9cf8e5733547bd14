"""Checkpoint loading: HF safetensors / orbax → the engine's param tree.

Fills the role of the reference's model-fetch path (lib/llm/src/hub.rs +
per-backend weight loading inside vLLM/TRT-LLM): map a HuggingFace
Llama-family checkpoint directory onto models/llama.py's stacked-layer
pytree, casting to the serving dtype, ready for ShardingPolicy placement.

HF → dynamo_tpu name map (Llama/Mistral/Qwen2/Qwen3/Qwen-MoE/OLMo-2
architectures; Phi-3's fused qkv_proj/gate_up_proj resolve to the split
names below via virtual get_slice row-splits, Mixtral's
block_sparse_moe.experts.N.{w1,w3,w2} map to we_{gate,up,down}, and
Gemma-1/2/3 / DeepSeek-MLA deviations are noted inline):
  model.embed_tokens.weight            → embed                [V, E]
  model.layers.{i}.input_layernorm     → layers/attn_norm[i]
  model.layers.{i}.self_attn.{q,k,v}_proj (transposed) → layers/w{q,k,v}[i]
  model.layers.{i}.self_attn.{q,k,v}_proj.bias → layers/b{q,k,v}[i] (Qwen2)
  model.layers.{i}.self_attn.{q,k}_norm.weight → layers/{q,k}_norm[i] (Qwen3)
  model.layers.{i}.self_attn.o_proj    (transposed)    → layers/wo[i]
  model.layers.{i}.post_attention_layernorm → layers/mlp_norm[i]
  model.layers.{i}.mlp.{gate,up,down}_proj (transposed) → layers/w_{gate,up,down}[i]
  model.layers.{i}.mlp.gate.weight (transposed)        → layers/w_router[i] (MoE)
  model.layers.{i}.mlp.experts.{e}.{gate,up,down}_proj → layers/we_*[i, e]
  model.layers.{i}.mlp.shared_expert.{gate,up,down}_proj → layers/ws_*[i]
    (DeepSeek naming `shared_experts` accepted too)
  model.norm.weight                    → norm_f
  lm_head.weight (transposed)          → lm_head (absent if tied)
"""

from __future__ import annotations

import json
import logging
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

from dynamo_tpu.models.config import ModelConfig

log = logging.getLogger("dynamo_tpu.engine.weights")


def load_hf_checkpoint(
    checkpoint_dir: str, config: ModelConfig, dtype="bfloat16"
) -> Dict[str, Any]:
    """Load a HF Llama safetensors checkpoint into the stacked param tree
    (numpy arrays; the ModelRunner device_puts them with shardings)."""
    import ml_dtypes
    from safetensors import safe_open

    if config.is_sambay:
        raise NotImplementedError(
            f"{config.name}: no checkpoint loader for a decoder-hybrid-decoder "
            "(models/sambay.py) yet: the published config gives the family's "
            "keys and no tensor names, so the tree's leaves cannot be mapped "
            "until a checkpoint's index is in the repository; it serves drawn "
            "weights (--model tiny-phi4flash, "
            "benchmark/configs/phi-4-mini-flash-reasoning.json)")
    if config.is_kda:
        raise NotImplementedError(
            f"{config.name}: no checkpoint loader for a model with KDA layers "
            "(models/ling.py) yet: the published config gives the family's "
            "keys and no tensor names, so the tree's leaves cannot be mapped "
            "until a checkpoint's index is in the repository; it serves drawn "
            "weights (--model tiny-ling, benchmark/configs/ling-3.0-flash-vl.json)")
    np_dtype = np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16" else np.dtype(dtype)
    d = Path(checkpoint_dir)
    files = sorted(d.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {checkpoint_dir}")

    # name -> file handle index
    tensors: Dict[str, Any] = {}
    handles = []
    for f in files:
        h = safe_open(str(f), framework="numpy")
        handles.append(h)
        for name in h.keys():
            # multimodal wrappers (Gemma-3 vision+text) prefix the LM
            # tree with "language_model."; alias the stripped name so the
            # text mapping below serves both checkpoint shapes (the value
            # keeps the REAL key the file must be read with)
            if name.startswith("language_model."):
                tensors[name[len("language_model."):]] = (h, name)
            tensors[name] = (h, name)

    def _raw(name: str) -> np.ndarray:
        if name in tensors:
            h, key = tensors[name]
            return h.get_tensor(key)
        # Phi-3 fuses q/k/v into qkv_proj and gate/up into gate_up_proj
        # (rows [q; k; v] resp. [gate; up] in the HF [out, in] layout).
        # Resolve the split names virtually so one mapping serves both
        # checkpoint shapes.
        parts = name.split(".")
        proj = parts[-2] if len(parts) >= 2 else ""
        if proj in ("q_proj", "k_proj", "v_proj"):
            fused = ".".join(parts[:-2] + ["qkv_proj", parts[-1]])
            if fused in tensors:
                h, key = tensors[fused]
                q = config.n_heads * config.head_dim
                kv = config.n_kv_heads * config.head_dim
                lo = {"q_proj": 0, "k_proj": q, "v_proj": q + kv}[proj]
                # get_slice reads only the needed rows (q is read 3x per
                # layer otherwise — gigabytes of redundant IO at 7B scale)
                return h.get_slice(key)[lo:lo + (q if proj == "q_proj" else kv)]
        if proj in ("gate_proj", "up_proj"):
            fused = ".".join(parts[:-2] + ["gate_up_proj", parts[-1]])
            if fused in tensors:
                h, key = tensors[fused]
                f = config.ffn_dim
                sl = h.get_slice(key)
                return sl[:f] if proj == "gate_proj" else sl[f:2 * f]
        raise KeyError(name)

    def get(name: str, transpose: bool = False) -> np.ndarray:
        arr = _raw(name)
        if transpose:
            arr = arr.T
        return np.ascontiguousarray(arr).astype(np_dtype)

    def get_f32(name: str) -> np.ndarray:
        return _raw(name).astype(np.float32)

    L = config.n_layers
    if config.is_hybrid:
        return _load_jamba(config, get, get_f32)
    if config.has_window_pool:
        return _load_mimo(config, get, get_f32)
    if config.is_mla:
        return _load_mla(config, tensors, get, get_f32, checkpoint_dir)
    first_q = get("model.layers.0.self_attn.q_proj.weight", transpose=True)
    if first_q.shape != (config.dim, config.n_heads * config.head_dim):
        raise ValueError(
            f"checkpoint shape {first_q.shape} does not match config "
            f"{config.name} ({config.dim}, {config.n_heads * config.head_dim})"
        )

    def stack(fmt: str, transpose: bool) -> np.ndarray:
        return np.stack([get(fmt.format(i=i), transpose=transpose) for i in range(L)])

    def stack_f32(fmt: str) -> np.ndarray:
        return np.stack([get_f32(fmt.format(i=i)) for i in range(L)])

    # Gemma-2 renames: post_attention_layernorm is the POST-attn sandwich
    # norm (not the pre-FFW norm llama uses it for); the pre-FFW norm is
    # pre_feedforward_layernorm
    mlp_norm_name = (
        "model.layers.{i}.pre_feedforward_layernorm.weight"
        if config.post_norms
        else "model.layers.{i}.post_attention_layernorm.weight"
    )
    params: Dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "layers": {
            "wq": stack("model.layers.{i}.self_attn.q_proj.weight", True),
            "wk": stack("model.layers.{i}.self_attn.k_proj.weight", True),
            "wv": stack("model.layers.{i}.self_attn.v_proj.weight", True),
            "wo": stack("model.layers.{i}.self_attn.o_proj.weight", True),
        },
        "norm_f": get_f32("model.norm.weight"),
    }
    layers = params["layers"]
    if config.pre_norms:
        layers["attn_norm"] = stack_f32(
            "model.layers.{i}.input_layernorm.weight"
        )
        layers["mlp_norm"] = stack_f32(mlp_norm_name)
    if config.post_norms:
        layers["post_attn_norm"] = stack_f32(
            "model.layers.{i}.post_attention_layernorm.weight"
        )
        layers["post_mlp_norm"] = stack_f32(
            "model.layers.{i}.post_feedforward_layernorm.weight"
        )
    if config.attn_bias:
        layers["bq"] = stack("model.layers.{i}.self_attn.q_proj.bias", False)
        layers["bk"] = stack("model.layers.{i}.self_attn.k_proj.bias", False)
        layers["bv"] = stack("model.layers.{i}.self_attn.v_proj.bias", False)
    if config.qk_norm:
        layers["q_norm"] = stack_f32("model.layers.{i}.self_attn.q_norm.weight")
        layers["k_norm"] = stack_f32("model.layers.{i}.self_attn.k_norm.weight")
    if config.is_moe:
        # two MoE tensor layouts in the wild: qwen/deepseek
        # (mlp.gate + mlp.experts.N.{gate,up,down}_proj) and Mixtral
        # (block_sparse_moe.gate + experts.N.{w1,w3,w2} where w1=gate,
        # w3=up, w2=down)
        mixtral = (
            "model.layers.0.block_sparse_moe.gate.weight" in tensors
        )
        moe_base = "block_sparse_moe" if mixtral else "mlp"
        part_names = (
            {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}
            if mixtral else
            {"gate_proj": "gate_proj", "up_proj": "up_proj",
             "down_proj": "down_proj"}
        )
        layers["w_router"] = stack(
            "model.layers.{i}." + moe_base + ".gate.weight", True
        )

        def stack_experts(part: str) -> np.ndarray:
            p = part_names[part]
            return np.stack(
                [
                    np.stack(
                        [
                            get(
                                f"model.layers.{i}.{moe_base}.experts.{e}.{p}.weight",
                                transpose=True,
                            )
                            for e in held_experts(config)
                        ]
                    )
                    for i in range(L)
                ]
            )

        layers["we_gate"] = stack_experts("gate_proj")
        layers["we_up"] = stack_experts("up_proj")
        layers["we_down"] = stack_experts("down_proj")
        if config.n_shared_experts:
            base = "model.layers.{i}.mlp.shared_expert"
            if f"model.layers.0.mlp.shared_experts.gate_proj.weight" in tensors:
                base = "model.layers.{i}.mlp.shared_experts"  # deepseek naming
            layers["ws_gate"] = stack(base + ".gate_proj.weight", True)
            layers["ws_up"] = stack(base + ".up_proj.weight", True)
            layers["ws_down"] = stack(base + ".down_proj.weight", True)
            if "model.layers.0.mlp.shared_expert_gate.weight" in tensors:
                layers["ws_gatectl"] = stack(
                    "model.layers.{i}.mlp.shared_expert_gate.weight", True
                )
    else:
        layers["w_gate"] = stack("model.layers.{i}.mlp.gate_proj.weight", True)
        layers["w_up"] = stack("model.layers.{i}.mlp.up_proj.weight", True)
        layers["w_down"] = stack("model.layers.{i}.mlp.down_proj.weight", True)
    if "lm_head.weight" in tensors and not config.tie_embeddings:
        params["lm_head"] = get("lm_head.weight", transpose=True)
    log.info("loaded HF checkpoint %s (%d files)", checkpoint_dir, len(files))
    return params


def held_experts(c: ModelConfig) -> range:
    """Ids of the routed experts whose tensors a load reads: the share
    this chip holds (`n_experts_held` from `expert_first` on), or all."""
    return range(c.expert_first, c.expert_first + c.experts_held)


def _rope_deinterleave(d: int) -> np.ndarray:
    """Column permutation converting HF DeepSeek's INTERLEAVED rope layout
    (x0,y0,x1,y1,...) to this module's half-rotation layout (all x then
    all y). The HF modeling file performs this view-transpose at runtime
    on q_pe/k_pe every step; folding it into the weights once at load
    makes the layouts agree with models/llama.py's rope()."""
    return np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])


def _load_mla(config: ModelConfig, tensors, get, get_f32,
              checkpoint_dir: str) -> Dict[str, Any]:
    """DeepSeek V2/V3 MLA checkpoint → the stacked (layers_dense, layers)
    trees. HF names: kv_a_proj_with_mqa / kv_a_layernorm / kv_b_proj,
    q_proj or q_a_proj/q_a_layernorm/q_b_proj, o_proj; MoE layers carry
    mlp.experts.{e}.* + mlp.shared_experts.* + mlp.gate.weight (+
    e_score_correction_bias)."""
    c = config
    L, kD = c.n_layers, c.n_dense_layers
    dn, dr, dv, dc = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                      c.v_head_dim, c.kv_lora_rank)
    rp = _rope_deinterleave(dr)
    read: set = set()  # names this load asked for (a v3.2 load accounts
    #   for every tensor of the checkpoint: _account_for_every_tensor)
    _get, _get_f32 = get, get_f32

    def get(name: str, transpose: bool = False) -> np.ndarray:
        read.add(name)
        return _get(name, transpose)

    def get_f32(name: str) -> np.ndarray:
        read.add(name)
        return _get_f32(name)

    def attn_rows(i: int) -> Dict[str, Any]:
        pre = f"model.layers.{i}."
        wkv_a = get(pre + "self_attn.kv_a_proj_with_mqa.weight", True)
        # de-interleave the k_pe block (last dr output columns)
        wkv_a[:, dc:] = wkv_a[:, dc:][:, rp]
        row = {
            "attn_norm": get_f32(pre + "input_layernorm.weight"),
            "wkv_a": wkv_a,
            "kv_norm": get_f32(pre + "self_attn.kv_a_layernorm.weight"),
            "wkv_b": get(pre + "self_attn.kv_b_proj.weight", True),
            "wo": get(pre + "self_attn.o_proj.weight", True),
            "mlp_norm": get_f32(pre + "post_attention_layernorm.weight"),
        }

        def fix_q(wq: np.ndarray) -> np.ndarray:
            # per head, de-interleave the rope block [dn:dn+dr]
            w3 = wq.reshape(wq.shape[0], c.n_heads, dn + dr)
            w3[:, :, dn:] = w3[:, :, dn:][:, :, rp]
            return w3.reshape(wq.shape)

        if c.q_lora_rank:
            row["wq_lat"] = get(pre + "self_attn.q_a_proj.weight", True)
            row["q_lat_norm"] = get_f32(pre + "self_attn.q_a_layernorm.weight")
            row["wq_up"] = fix_q(get(pre + "self_attn.q_b_proj.weight", True))
        else:
            row["wq"] = fix_q(get(pre + "self_attn.q_proj.weight", True))
        if c.has_indexer:
            # deepseek_v32's lightning indexer. Its rotary is published
            # non-interleaved on a head's first dims, which is this
            # program's half-rotation layout: the columns stay as they are
            ix = pre + "self_attn.indexer."
            row["wi_q"] = get(ix + "wq_b.weight", True)
            row["wi_k"] = get(ix + "wk.weight", True)
            row["wi_w"] = get(ix + "weights_proj.weight", True)
            row["ik_norm"] = get_f32(ix + "k_norm.weight")
            row["ik_norm_b"] = get_f32(ix + "k_norm.bias")
        return row

    def dense_rows(i: int) -> Dict[str, Any]:
        pre = f"model.layers.{i}.mlp."
        return {
            "w_gate": get(pre + "gate_proj.weight", True),
            "w_up": get(pre + "up_proj.weight", True),
            "w_down": get(pre + "down_proj.weight", True),
        }

    def moe_rows(i: int) -> Dict[str, Any]:
        pre = f"model.layers.{i}.mlp."
        row = {
            "w_router": get(pre + "gate.weight", True),
            "we_gate": np.stack([
                get(f"{pre}experts.{e}.gate_proj.weight", True)
                for e in held_experts(c)
            ]),
            "we_up": np.stack([
                get(f"{pre}experts.{e}.up_proj.weight", True)
                for e in held_experts(c)
            ]),
            "we_down": np.stack([
                get(f"{pre}experts.{e}.down_proj.weight", True)
                for e in held_experts(c)
            ]),
        }
        if c.moe_router_bias:
            row["router_bias"] = get_f32(pre + "gate.e_score_correction_bias")
        if c.n_shared_experts:
            row["ws_gate"] = get(pre + "shared_experts.gate_proj.weight", True)
            row["ws_up"] = get(pre + "shared_experts.up_proj.weight", True)
            row["ws_down"] = get(pre + "shared_experts.down_proj.weight", True)
        return row

    def stack_rows(rows: list) -> Dict[str, Any]:
        return {k: np.stack([r[k] for r in rows]) for k in rows[0]}

    moe_layers = [
        {**attn_rows(i), **(moe_rows(i) if c.is_moe else dense_rows(i))}
        for i in range(kD, L)
    ]
    params: Dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "layers": stack_rows(moe_layers),
        "norm_f": get_f32("model.norm.weight"),
    }
    if kD:
        params["layers_dense"] = stack_rows(
            [{**attn_rows(i), **dense_rows(i)} for i in range(kD)]
        )
    if "lm_head.weight" in tensors and not c.tie_embeddings:
        params["lm_head"] = get("lm_head.weight", True)
    if c.has_indexer:
        _account_for_every_tensor(c, tensors, read)
    log.info("loaded DeepSeek MLA checkpoint %s", checkpoint_dir)
    return params


def _account_for_every_tensor(c: ModelConfig, tensors, read: set) -> None:
    """A deepseek_v32 load leaves no tensor of the checkpoint unexplained:
    what it did not read is either a layer past the model's own (layer 61 of
    the published checkpoint, the multi-token-prediction module, which is a
    drafter and changes no logit: skipped, in words), a routed expert this
    chip does not hold, or a tensor this loader has no name for, which it
    refuses (an FP8 checkpoint's scales, a renamed indexer matrix: loading
    around them would serve another model in silence)."""
    import re

    held = set(held_experts(c))
    skipped, unknown = set(), []
    for name in sorted(set(tensors) - read):
        if name.startswith("language_model.") and name[15:] in read:
            continue  # a wrapper's prefixed name, read under its alias
        m = re.match(r"(?:language_model\.)?model\.layers\.(\d+)\.(.*)", name)
        if m and int(m.group(1)) >= c.n_layers:
            skipped.add(int(m.group(1)))
            continue
        e = re.match(r"mlp\.experts\.(\d+)\.", m.group(2)) if m else None
        if e and int(e.group(1)) not in held:
            continue
        if name == "lm_head.weight" and c.tie_embeddings:
            continue
        unknown.append(name)
    if unknown:
        raise ValueError(
            f"the checkpoint holds {len(unknown)} tensors this loader has no "
            f"name for, the first {unknown[0]!r}: a deepseek_v32 layer is "
            "input_layernorm, post_attention_layernorm, self_attn.{q_a_proj, "
            "q_a_layernorm, q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm, "
            "kv_b_proj, o_proj}, self_attn.indexer.{wq_b, wk, k_norm, "
            "weights_proj} and its mlp; nothing is loaded around a tensor "
            "that is not one of them (FP8 scales need a dequantized "
            "checkpoint)")
    for i in sorted(skipped):
        log.info(
            "layer %d of the checkpoint is past the model's %d layers and is "
            "skipped: the multi-token-prediction module is a drafter, it "
            "changes no logit of the model and is not built", i, c.n_layers)


def config_from_hf(checkpoint_dir: str, name: Optional[str] = None) -> ModelConfig:
    """Derive a ModelConfig from a HF config.json (llama / qwen2 / qwen3 /
    qwen2_moe / qwen3_moe model types)."""
    cfg = json.loads((Path(checkpoint_dir) / "config.json").read_text())
    mt = cfg.get("model_type", "llama")
    if mt == "gemma3" and isinstance(cfg.get("text_config"), dict):
        # multimodal wrapper config: the LM (incl. its rope_scaling!)
        # lives under text_config — unwrap BEFORE any field is read.
        # HF serializes NESTED configs as diffs against the class
        # defaults, so a real gemma-3-*-it text_config omits defaulted
        # fields (rope_theta 1e6, sliding_window, query_pre_attn_scalar,
        # ...) — overlay the upstream defaults underneath or those fields
        # silently pick up OUR generic fallbacks (wrong logits).
        defaults: Dict[str, Any] = {}
        try:
            import transformers as _tf

            defaults = _tf.Gemma3TextConfig().to_dict()
        except Exception:
            # loader must work without transformers: pin the defaults our
            # mapping reads (upstream Gemma3TextConfig values)
            defaults = {
                "rope_theta": 1_000_000.0, "rope_local_base_freq": 10_000.0,
                "sliding_window": 4096, "query_pre_attn_scalar": 256.0,
                "head_dim": 256, "rms_norm_eps": 1e-6,
                "max_position_embeddings": 131072,
                "tie_word_embeddings": True,
            }
        cfg = {**defaults, **cfg["text_config"], "model_type": "gemma3_text"}
        mt = "gemma3_text"
    if mt == "jamba":
        return _jamba_config(cfg, name)
    if mt == "mimo_v2_flash":
        return _mimo_config(cfg, name)
    rope_kw = _rope_scaling_from_hf(cfg)
    if mt.startswith("deepseek") or mt == "mistral4":
        # mistral4 (Mistral-Small-4) is the DeepSeek-V3 layer under
        # Mistral's config keys: its rotary settings sit under
        # `rope_parameters` (rope_theta among them), it has no
        # `scoring_func` key (softmax over all experts, the family's
        # published router) and it scales the query by position
        # (`llama_4_scaling_beta`, over the yarn `original_max_position_
        # embeddings`). Its checkpoints interleave the rotary pairs like
        # DeepSeek's (`rope_interleave`), which _load_mla permutes.
        rp = cfg.get("rope_parameters") or {}
        if cfg.get("rope_interleave") is False:
            raise ValueError(
                "rope_interleave false: this loader permutes interleaved "
                "rotary pairs on import and has no path that leaves them"
            )
        qscale_kw = {}
        if rp.get("llama_4_scaling_beta"):
            qscale_kw = dict(
                attn_qscale_beta=float(rp["llama_4_scaling_beta"]),
                attn_qscale_orig=int(rp["original_max_position_embeddings"]),
            )
        index_kw = {}
        if cfg.get("index_topk"):  # deepseek_v32: the lightning indexer
            index_kw = dict(index_topk=int(cfg["index_topk"]),
                            index_n_heads=int(cfg["index_n_heads"]),
                            index_head_dim=int(cfg["index_head_dim"]))
        return ModelConfig(
            **rope_kw,
            **qscale_kw,
            **index_kw,
            n_expert_groups=int(cfg.get("n_group") or 0),
            topk_groups=int(cfg.get("topk_group") or 0),
            name=name or cfg.get("_name_or_path", "deepseek-hf"),
            vocab_size=cfg["vocab_size"],
            dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            ffn_dim=cfg["intermediate_size"],
            max_seq_len=cfg.get("max_position_embeddings", 8192),
            rope_theta=float(cfg.get("rope_theta") or rp.get("rope_theta")
                             or 10000.0),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            attn_type="mla",
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            q_lora_rank=int(cfg.get("q_lora_rank") or 0),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
            n_experts=int(cfg.get("n_routed_experts") or 0),
            n_experts_active=int(cfg.get("num_experts_per_tok") or 0),
            moe_ffn_dim=int(cfg.get("moe_intermediate_size") or 0),
            n_shared_experts=int(cfg.get("n_shared_experts") or 0),
            moe_scoring=(
                "sigmoid" if cfg.get("scoring_func") == "sigmoid" else "softmax"
            ),
            moe_norm_topk=bool(cfg.get("norm_topk_prob", True)),
            # V3's aux-loss-free balancing ships the correction bias
            moe_router_bias=cfg.get("topk_method") == "noaux_tc",
            moe_routed_scale=float(cfg.get("routed_scaling_factor") or 1.0),
            n_dense_layers=int(cfg.get("first_k_dense_replace") or 0),
        )
    n_experts = int(cfg.get("num_experts") or cfg.get("n_routed_experts")
                    or cfg.get("num_local_experts") or 0)  # mixtral naming
    gemma2 = mt == "gemma2"
    gemma3 = mt.startswith("gemma3")
    gemma_kw = {}
    if mt == "granite":
        # Granite: Llama layout + four scalar multipliers (HF
        # GraniteConfig); logits_scaling DIVIDES the final logits
        gemma_kw.update(
            embed_multiplier=float(cfg.get("embedding_multiplier") or 0.0),
            residual_multiplier=float(cfg.get("residual_multiplier") or 1.0),
            # HF's default when the field is omitted is 1.0 — i.e. a
            # softmax scale of ONE, not head_dim**-0.5
            attn_scale=float(cfg.get("attention_multiplier", 1.0) or 1.0),
            logits_divider=float(cfg.get("logits_scaling") or 1.0),
        )
    if mt == "olmo2":
        # OLMo-2 reorders the norms: NO pre-norms — the residual stream
        # feeds attention/MLP raw and post_{attention,feedforward}_
        # layernorm norm the branch OUTPUTS (same tensor names Gemma-2
        # uses for its sandwich); qk-norm runs over the FULL projection
        # width before the head reshape.
        gemma_kw.update(post_norms=True, pre_norms=False, qk_norm_wide=True)
    if mt == "gemma":
        # Gemma-1: the GeGLU/scaled-embed/zero-centered-norm subset of
        # the Gemma-2 flags — no sandwich norms, softcaps, or window
        gemma_kw.update(
            act="gelu_tanh",
            embed_scale=True,
            norm_zero_centered=True,
        )
    if mt in ("mistral", "mixtral", "phi3") and cfg.get("sliding_window"):
        # Mistral-family sliding window applies to EVERY layer (HF
        # masks q-k >= sliding_window on all of them — no alternation).
        # Expressed in the generalized schedule as period 1 with an
        # unreachable global residue: (l % 1) == 1 is never true.
        gemma_kw.update(
            sliding_window=int(cfg["sliding_window"]),
            sw_period=1,
            sw_global_residue=1,
        )
    if gemma2 or gemma3:
        gemma_kw = dict(
            act="gelu_tanh",
            embed_scale=True,
            norm_zero_centered=True,
            post_norms=True,
            attn_logit_softcap=float(cfg.get("attn_logit_softcapping") or 0.0),
            final_logit_softcap=float(
                cfg.get("final_logit_softcapping") or 0.0
            ),
            query_pre_attn_scalar=float(
                cfg.get("query_pre_attn_scalar") or 0.0
            ),
            sliding_window=int(cfg.get("sliding_window") or 0),
        )
    if gemma3:
        # 5 local : 1 global pattern + dual rope bases. Derive the
        # period/residue from layer_types when present and verify it is
        # the canonical periodic pattern — silently mis-phasing the
        # window schedule would corrupt logits with no error.
        layer_types = cfg.get("layer_types")
        period = int(cfg.get("sliding_window_pattern") or 6)
        if layer_types:
            globals_ = [i for i, t in enumerate(layer_types)
                        if t == "full_attention"]
            if globals_:
                period = globals_[0] + 1
            expect = [
                "full_attention" if (i % period) == period - 1
                else "sliding_attention"
                for i in range(len(layer_types))
            ]
            if layer_types != expect:
                raise ValueError(
                    "gemma3 layer_types is not the canonical "
                    f"{period - 1}:1 local/global pattern; refusing to "
                    "mis-phase the sliding schedule"
                )
        gemma_kw.update(
            sw_period=period,
            sw_global_residue=period - 1,
            # HF's default when the field is omitted is 10000.0; falling
            # back to 0.0 would silently disable the dual rope and rotate
            # sliding layers with the 1e6 global base
            rope_local_theta=float(
                cfg.get("rope_local_base_freq", 10000.0) or 10000.0
            ),
        )
    return ModelConfig(
        **rope_kw,
        **gemma_kw,
        name=name or cfg.get("_name_or_path", "hf-model"),
        vocab_size=cfg["vocab_size"],
        dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
        ffn_dim=cfg["intermediate_size"],
        max_seq_len=cfg.get("max_position_embeddings", 8192),
        rope_theta=float(cfg.get("rope_theta", 500000.0)),
        norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        # qwen2 ships biases by default; qwen3 advertises them explicitly
        attn_bias=bool(cfg.get("attention_bias", mt in ("qwen2", "qwen2_moe"))),
        qk_norm=mt in ("qwen3", "qwen3_moe", "olmo2") or gemma3,
        head_dim_override=int(cfg.get("head_dim") or 0),
        n_experts=n_experts,
        n_experts_active=int(cfg.get("num_experts_per_tok") or 0),
        # mixtral has no separate moe_intermediate_size: its experts use
        # the dense intermediate width. The fallback is gated on the
        # MODEL TYPE, not n_experts — a qwen-family MoE config that
        # diff-omits moe_intermediate_size must keep failing loudly on
        # wrong shapes, not silently adopt the dense width
        moe_ffn_dim=int(
            cfg.get("moe_intermediate_size")
            or (cfg.get("intermediate_size") if mt == "mixtral" else 0)
            or 0
        ),
        n_shared_experts=int(
            cfg.get("n_shared_experts")
            or (1 if cfg.get("shared_expert_intermediate_size") else 0)
        ),
        shared_expert_ffn_dim=int(cfg.get("shared_expert_intermediate_size") or 0),
        moe_scoring="sigmoid" if cfg.get("scoring_func") == "sigmoid" else "softmax",
        # Qwen2-MoE ships norm_topk_prob=false: keep softmax-over-all
        # probabilities un-renormalized (HF semantics)
        moe_norm_topk=bool(cfg.get("norm_topk_prob", True)),
    )


def _mimo_config(cfg: Dict[str, Any], name: Optional[str]) -> ModelConfig:
    """`model_type: mimo_v2_flash` (models/mimo.py): every key of the
    published row on a ModelConfig field; what the program does not build is
    refused in words."""
    L = int(cfg["num_hidden_layers"])
    pattern = tuple(int(k) for k in cfg["hybrid_layer_pattern"][:L])
    freq = [int(k) for k in cfg.get("moe_layer_freq", [0] + [1] * (L - 1))[:L]]
    n_dense = freq.index(1) if 1 in freq else L
    if any(k == 0 for k in freq[n_dense:]):
        raise NotImplementedError(
            f"mimo_v2_flash with moe_layer_freq {freq}: dense MLPs after the "
            "first expert layer are not built (the leading layers alone)")
    if cfg.get("n_shared_experts"):
        raise NotImplementedError("mimo_v2_flash with shared experts is not built")
    if (int(cfg.get("n_group") or 1), int(cfg.get("topk_group") or 1)) != (1, 1):
        raise NotImplementedError("mimo_v2_flash with expert groups is not built")
    if cfg.get("attention_bias") or cfg.get("attention_projection_layout") == "fused_qkv":
        raise NotImplementedError(
            "mimo_v2_flash with projection biases or a fused qkv layout is not built")
    for a, b in (("swa_num_attention_heads", "num_attention_heads"),
                 ("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim")):
        if cfg.get(a, cfg[b]) != cfg[b]:
            raise NotImplementedError(
                f"mimo_v2_flash with {a} != {b}: the window layers differ from "
                "the global ones in KV heads, rotary base and sink alone")
    dk = int(cfg["head_dim"])
    return ModelConfig(
        name=name or cfg.get("_name_or_path") or "mimo-v2-flash",
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=L,
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        n_kv_heads_window=cfg["swa_num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"],
        max_seq_len=cfg.get("max_position_embeddings", 262144),
        norm_eps=float(cfg.get("layernorm_epsilon", 1e-5)),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        head_dim_override=dk, v_head_dim=int(cfg["v_head_dim"]),
        rope_partial_dims=int(float(cfg.get("partial_rotary_factor", 1.0)) * dk) // 2 * 2,
        attn_value_scale=float(cfg.get("attention_value_scale") or 0.0),
        sliding_window=int(cfg["sliding_window"]), layer_pattern=pattern,
        sink_window=bool(cfg.get("add_swa_attention_sink_bias")),
        sink_global=bool(cfg.get("add_full_attention_sink_bias")),
        rope_theta=float(cfg["rope_theta"]),
        rope_local_theta=float(cfg.get("swa_rope_theta", cfg["rope_theta"])),
        n_dense_layers=n_dense, n_experts=cfg["n_routed_experts"],
        n_experts_active=cfg["num_experts_per_tok"],
        moe_ffn_dim=cfg["moe_intermediate_size"],
        moe_scoring=cfg.get("scoring_func", "sigmoid"),
        moe_norm_topk=bool(cfg.get("norm_topk_prob", True)),
        moe_router_bias=cfg.get("topk_method") == "noaux_tc",
        moe_routed_scale=float(cfg.get("routed_scaling_factor") or 1.0),
    )


def _load_mimo(c: ModelConfig, get, get_f32) -> Dict[str, Any]:
    """models/mimo.py's tree from a `mimo_v2_flash` state dict, by the names
    the published row settles: the llama layout of its layers, deepseek_v3's
    router (`mlp.gate.weight`, `mlp.gate.e_score_correction_bias`:
    `topk_method: noaux_tc`) and experts, `attention_sink_bias` (the row's
    `add_swa_attention_sink_bias`). A tensor that is not under its name here
    is refused in words: the row gives keys, not a state dict, and no name
    is guessed twice."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import mimo

    def need(read, name, **kw):
        try:
            return read(name, **kw)
        except KeyError:
            raise NotImplementedError(
                f"this mimo_v2_flash checkpoint has no tensor {name!r}: the "
                "published config settles that name and no other is tried; "
                "say which tensor holds it in engine/weights._load_mimo"
            ) from None

    def stack(read, layers, fmt, **kw):
        return np.stack([need(read, fmt.format(l=l), **kw) for l in layers])

    P = "model.layers.{l}."
    out: Dict[str, Any] = {
        "embed": need(get, "model.embed_tokens.weight"),
        "norm_f": need(get_f32, "model.norm.weight"),
        "lm_head": need(get, "lm_head.weight", transpose=True),
    }
    for group, layers, kind in (("attn_global", c.global_layers, mimo.GLOBAL),
                                ("attn_window", c.window_layers, mimo.WINDOW)):
        out[group] = {
            ours: stack(get, layers, P + f"self_attn.{theirs}.weight", transpose=True)
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj"))}
        if mimo.has_sink(c, kind):
            out[group]["sink_bias"] = stack(
                get_f32, layers, P + "self_attn.attention_sink_bias")
    norms = (("attn_norm", "input_layernorm"), ("mlp_norm", "post_attention_layernorm"))
    mlp = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))
    dense, routed = range(c.n_dense_layers), range(c.n_dense_layers, c.n_layers)
    if c.n_dense_layers:
        out["layers_dense"] = {
            **{o: stack(get_f32, dense, P + t + ".weight") for o, t in norms},
            **{"w_" + o: stack(get, dense, P + f"mlp.{t}.weight", transpose=True)
               for o, t in mlp}}
    held = range(c.expert_first, c.expert_first + c.experts_held)
    out["layers"] = {
        **{o: stack(get_f32, routed, P + t + ".weight") for o, t in norms},
        "w_router": stack(get, routed, P + "mlp.gate.weight", transpose=True),
        **{"we_" + o: np.stack([stack(get, routed, P + f"mlp.experts.{e}.{t}.weight",
                                      transpose=True) for e in held], axis=1)
           for o, t in mlp}}
    if c.moe_router_bias:
        out["layers"]["router_bias"] = stack(
            get_f32, routed, P + "mlp.gate.e_score_correction_bias")
    return jax.tree.map(jnp.asarray, out)


def _jamba_config(cfg: Dict[str, Any], name: Optional[str]) -> ModelConfig:
    """`model_type: jamba` with every MLP dense (models/jamba.py)."""
    if int(cfg.get("num_experts") or 1) > 1:
        raise NotImplementedError(
            f"jamba with num_experts = {cfg['num_experts']}: the routed "
            "MLPs of the larger Jamba models are not built; only the dense "
            "configurations (num_experts 1) load")
    if cfg.get("mamba_proj_bias"):
        raise NotImplementedError("jamba with mamba_proj_bias is not built")
    if not cfg.get("mamba_conv_bias", True):
        raise NotImplementedError(
            "jamba without mamba_conv_bias: the loader expects conv1d.bias")
    rank = cfg.get("mamba_dt_rank", "auto")
    if rank == "auto":
        rank = -(-cfg["hidden_size"] // 16)
    return ModelConfig(
        name=name or cfg.get("_name_or_path") or "jamba",
        vocab_size=cfg["vocab_size"],
        dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
        ffn_dim=cfg["intermediate_size"],
        max_seq_len=cfg.get("max_position_embeddings", 262144),
        norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        tie_embeddings=True,  # the forward reads the embedding as the head
        mamba_d_state=int(cfg.get("mamba_d_state", 16)),
        mamba_d_conv=int(cfg.get("mamba_d_conv", 4)),
        mamba_dt_rank=int(rank),
        mamba_expand=int(cfg.get("mamba_expand", 2)),
        attn_layer_period=int(cfg.get("attn_layer_period", 8)),
        attn_layer_offset=int(cfg.get("attn_layer_offset", 4)),
    )


# models/jamba.py's tree <-> `JambaForCausalLM`'s state dict: (our leaf,
# their name under model.layers.{i}., how the array turns). "T": a Linear's
# [out, in] is our [in, out]; "conv": conv1d.weight [d, 1, K] is our [K, d];
# "A": A_log [d, N] is our [N, d].
_JAMBA_EVERY = (("attn_norm", "input_layernorm.weight", ""),
                ("mlp_norm", "pre_ff_layernorm.weight", ""),
                ("w_gate", "feed_forward.gate_proj.weight", "T"),
                ("w_up", "feed_forward.up_proj.weight", "T"),
                ("w_down", "feed_forward.down_proj.weight", "T"))
_JAMBA_MAMBA = (("w_in", "mamba.in_proj.weight", "T"),
                ("w_conv", "mamba.conv1d.weight", "conv"),
                ("b_conv", "mamba.conv1d.bias", ""),
                ("w_x", "mamba.x_proj.weight", "T"),
                ("dt_norm", "mamba.dt_layernorm.weight", ""),
                ("b_norm", "mamba.b_layernorm.weight", ""),
                ("c_norm", "mamba.c_layernorm.weight", ""),
                ("w_dt", "mamba.dt_proj.weight", "T"),
                ("b_dt", "mamba.dt_proj.bias", ""),
                ("A_log", "mamba.A_log", "A"),
                ("D", "mamba.D", ""),
                ("w_out", "mamba.out_proj.weight", "T"))
_JAMBA_ATTN = (("wq", "self_attn.q_proj.weight", "T"),
               ("wk", "self_attn.k_proj.weight", "T"),
               ("wv", "self_attn.v_proj.weight", "T"),
               ("wo", "self_attn.o_proj.weight", "T"))
_JAMBA_F32 = {"attn_norm", "mlp_norm", "b_conv", "dt_norm", "b_norm",
              "c_norm", "b_dt", "A_log", "D"}  # the program's f32 leaves


def _jamba_groups(c: ModelConfig):
    """(subtree, its leaf table, the model layers it stacks in order)."""
    every = list(range(c.n_layers))
    return (("layers", _JAMBA_EVERY, every),
            ("mamba", _JAMBA_MAMBA, [l for l in every if not c.is_attn_layer(l)]),
            ("attn", _JAMBA_ATTN, list(c.attn_layers)))


def _load_jamba(c: ModelConfig, get, get_f32) -> Dict[str, Any]:
    def leaf(ours, name, how):
        if ours in _JAMBA_F32:
            a = get_f32(name)
            return a.T if how == "A" else a
        if how == "conv":  # [d, 1, K] -> [K, d]
            a = get(name)
            return np.ascontiguousarray(a[:, 0, :].T)
        return get(name, transpose=how == "T")

    params: Dict[str, Any] = {
        "embed": get("model.embed_tokens.weight"),
        "norm_f": get_f32("model.final_layernorm.weight"),
    }
    for sub, table, at in _jamba_groups(c):
        params[sub] = {
            ours: np.stack([leaf(ours, f"model.layers.{l}.{theirs}", how)
                            for l in at])
            for ours, theirs, how in table}
    return params


def jamba_to_hf_state(c: ModelConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """models/jamba.py's tree as `JambaForCausalLM`'s state dict (numpy
    float32): _load_jamba's inverse, for the tests that hold the program and
    its reference to transformers."""
    f = lambda a: np.asarray(a, np.float32)
    back = {"": lambda a: a, "T": lambda a: a.T, "A": lambda a: a.T,
            "conv": lambda a: a.T[:, None, :]}
    out = {"model.embed_tokens.weight": f(params["embed"]),
           "lm_head.weight": f(params["embed"]),
           "model.final_layernorm.weight": f(params["norm_f"])}
    for sub, table, at in _jamba_groups(c):
        for ours, theirs, how in table:
            for i, l in enumerate(at):
                out[f"model.layers.{l}.{theirs}"] = np.ascontiguousarray(
                    back[how](f(params[sub][ours][i])))
    return out


def _rope_scaling_from_hf(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """HF rope_scaling dict → ModelConfig rope_* kwargs. Unknown scaling
    types raise — silently ignoring one produces numerically wrong
    long-context attention."""
    # newer configs (mistral4) keep the same keys under `rope_parameters`,
    # beside rope_theta; read where `rope_scaling` is absent
    rs = cfg.get("rope_scaling") or cfg.get("rope_parameters")
    if not rs or not (rs.get("rope_type") or rs.get("type")):
        return {}
    kind = rs.get("rope_type") or rs.get("type") or ""
    if kind == "llama3":
        return {
            "rope_scaling": "llama3",
            "rope_factor": float(rs.get("factor", 8.0)),
            "rope_orig_max_seq": int(
                rs.get("original_max_position_embeddings") or 8192
            ),
            "rope_low_freq_factor": float(rs.get("low_freq_factor", 1.0)),
            "rope_high_freq_factor": float(rs.get("high_freq_factor", 4.0)),
        }
    if kind in ("linear", "default"):
        # uniform position interpolation (Gemma-3 global rope: factor 8);
        # "default" is HF's explicit no-op
        f = float(rs.get("factor", 1.0))
        if f == 1.0 or kind == "default":
            return {}
        return {"rope_scaling": "linear", "rope_factor": f}
    if kind == "yarn":
        return {
            "rope_scaling": "yarn",
            "rope_factor": float(rs.get("factor", 1.0)),
            "rope_orig_max_seq": int(
                rs.get("original_max_position_embeddings") or 4096
            ),
            "rope_beta_fast": float(rs.get("beta_fast", 32.0)),
            "rope_beta_slow": float(rs.get("beta_slow", 1.0)),
            "rope_mscale": float(rs.get("mscale", 1.0)),
            "rope_mscale_all_dim": float(rs.get("mscale_all_dim", 0.0)),
        }
    raise ValueError(
        f"unsupported rope_scaling type {kind!r} (supported: llama3, yarn)"
    )


def save_orbax(params: Dict[str, Any], path: str) -> None:
    """Persist a param tree with orbax (fast-resume staging; the TPU analog
    of the reference's GMS/ModelExpress fast-restart role)."""
    import orbax.checkpoint as ocp

    ckpt = ocp.StandardCheckpointer()
    ckpt.save(Path(path).resolve(), params, force=True)
    ckpt.wait_until_finished()


def load_orbax(path: str) -> Dict[str, Any]:
    import orbax.checkpoint as ocp

    ckpt = ocp.StandardCheckpointer()
    return ckpt.restore(Path(path).resolve())
