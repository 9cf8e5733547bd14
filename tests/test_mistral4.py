"""Mistral-Small-4's layer as one chip of an expert-parallel deployment holds
it (CPU, toy sizes, seeded random weights): latent attention with a compressed
query and the position-dependent query scale, a router over all experts and an
expert layer that holds a share of them. The program against the benchmark's
plain references (`benchmark/reference/mistral4_decoder.py`, and the uncut
`mla_moe_decoder.py` for the whole layer); ModelConfig fields `n_experts_held`,
`expert_first`, `attn_qscale_beta`, `attn_qscale_orig`."""

import asyncio
import dataclasses
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig, get_config
from dynamo_tpu.models.moe import _moe_block, routing_stats
from dynamo_tpu.models.quant import mm
from dynamo_tpu.runtime.context import Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = get_config("tiny-mistral4")  # 16 experts, 4 a token, experts 4..7 held
WHOLE = TOY.with_(n_experts_held=0, expert_first=0)
PAGE = 4


def _reference(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, os.path.join(REPO, "benchmark", "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference("mistral4_decoder")


@pytest.fixture(scope="module")
def whole_ref():
    return _reference("mla_moe_decoder")


def _model(c: ModelConfig) -> dict:
    return dataclasses.asdict(c)


def _share(c: ModelConfig, q: int) -> ModelConfig:
    """The q-th of the shares a stage's chips hold between them."""
    held = c.experts_held
    return c.with_(n_experts_held=held, expert_first=q * held)


# -- (1) through the paged latent pool, against the reference's full forward --


@pytest.mark.parametrize("dtype, tol", [
    (jnp.float32, 2e-4),
    # bf16 weights and activations against the float32 reference at width 64:
    # a logit is a sum of 64 products each rounded to 8 bits of mantissa, and a
    # token whose bf16 router input picks another expert than float32 reads
    # further off; ten seeds read 0.02-0.09 on the worst logprob (the
    # rehearsal's tolerance is 0.25 for the same reason). The tight comparison
    # is the float32 one; this one holds the bf16 path to the same mathematics.
    (jnp.bfloat16, 0.25),
])
def test_prefill_then_decode_matches_the_reference(ref, dtype, tol):
    """A 19-token prefill (a query scale of 1 up to position 7, 1.069 from 8,
    1.110 from 16) and five decode steps through the latent pool, every
    position's log-softmax against the reference's cacheless forward on the
    same tree; the picks the program hands out are the reference's own."""
    c = TOY
    assert c.holds_share and c.q_lora_rank and c.attn_qscale_beta
    p = llama.init_params(c, jax.random.PRNGKey(3), dtype)
    toks = np.random.default_rng(0).integers(1, c.vocab_size, 24)
    n0 = 19
    pt = jnp.arange(1, 9, dtype=jnp.int32)[None, :]
    kp, vp = llama.make_kv_pool(c, 16, PAGE, dtype)
    with jax.default_matmul_precision("highest"):
        out, kp, vp, sel = llama.forward(
            c, p, jnp.asarray(toks[None, :n0]), jnp.arange(n0)[None], kp, vp, pt,
            jnp.asarray([n0]), return_routed=True)
        rows, picks = [np.asarray(out[0])], [np.asarray(sel)[:, 0]]
        for t in range(n0, len(toks)):
            out, kp, vp, sel = llama.forward(
                c, p, jnp.asarray([[toks[t]]]), jnp.asarray([[t]]), kp, vp, pt,
                jnp.asarray([t + 1]), return_routed=True)
            rows.append(np.asarray(out[0]))
            picks.append(np.asarray(sel)[:, 0])
    got = np.asarray(jax.nn.log_softmax(np.concatenate(rows), axis=-1))
    served = np.concatenate(picks, axis=1).transpose(1, 0, 2)  # [S, L, k]
    at = list(range(len(toks)))
    if dtype == jnp.float32:
        want = ref.logprobs_at(_model(c), p, toks, at)
        own = ref.own_picks(_model(c), p, toks)
        assert (np.sort(served, -1) == np.sort(own, -1)).all()
    else:  # the served picks followed, as the benchmark's check does
        want, need = ref.follow_at(_model(c), p, toks, at, served)
        assert need.max() < 0.05
    assert served.max() >= c.expert_first + c.experts_held  # ids over the full width
    assert np.abs(got - want).max() <= tol


def test_query_scale_is_what_moves_the_logits(ref):
    """With the scale switched off the program's logits leave the
    reference's from the first position past `orig`, and not before."""
    c = TOY
    p = llama.init_params(c, jax.random.PRNGKey(3), jnp.float32)
    toks = np.random.default_rng(1).integers(1, c.vocab_size, 20)
    off = c.with_(attn_qscale_beta=0.0)
    pt = jnp.arange(1, 9, dtype=jnp.int32)[None, :]
    kp, vp = llama.make_kv_pool(c, 16, PAGE, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out = llama.forward(off, p, jnp.asarray(toks[None]), jnp.arange(20)[None],
                            kp, vp, pt, jnp.asarray([20]))[0][0]
    got = np.asarray(jax.nn.log_softmax(out, axis=-1))
    want = ref.logprobs_at(_model(c), p, toks, list(range(20)))
    err = np.abs(got - want).max(-1)
    assert err[: c.attn_qscale_orig].max() < 2e-4 < err[c.attn_qscale_orig:].max()
    np.testing.assert_allclose(
        np.asarray(ref.query_scale(jnp.arange(20), _model(c)))[[0, 7, 8, 16]],
        [1.0, 1.0, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(3)], rtol=1e-6)


def test_query_scale_is_refused_off_latent_attention():
    with pytest.raises(ValueError, match="attn_qscale_beta"):
        get_config("tiny").with_(attn_qscale_beta=0.1, attn_qscale_orig=8)


# -- (2) the shares add up ---------------------------------------------------


def _layer_inputs(seed=5, tokens=23):
    p = llama.init_params(WHOLE, jax.random.PRNGKey(seed), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], p["layers"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, tokens, WHOLE.dim), jnp.float32)
    return p, lp, x


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_whole_layer(ref, whole_ref, side):
    """The routed parts that the four chips of a stage compute, each over its
    own quarter of the experts, plus the shared expert counted ONCE, are what
    the uncut reference (`mla_moe_decoder`, every expert) gives for the whole
    layer: nothing is lost or counted twice by cutting the layer."""
    p, lp, x = _layer_inputs()
    model = _model(WHOLE)
    with jax.default_matmul_precision("highest"):
        picks = jnp.zeros((x.shape[1], WHOLE.n_experts_active), jnp.int32)
        want, need, own = whole_ref._experts(x[0], lp, model, picks, False)
        shared = whole_ref._swiglu(x[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        parts, sels = [], []
        for q in range(4):
            c = _share(TOY, q)
            mine = {k: (v[c.expert_first: c.expert_first + 4] if k.startswith("we_") else v)
                    for k, v in lp.items()}
            if side == "program":
                y, sel, _ = _moe_block(c, mine, x)
                y, sel = y[0], sel[0]
            else:
                y, _, sel = ref._experts(x[0], mine, _model(c), picks, False)
            parts.append(np.asarray(y - shared))
            sels.append(np.sort(np.asarray(sel), -1))
    for s in sels:  # every chip routes over all 16 and picks the same
        assert (s == np.sort(np.asarray(own), -1)).all()
    assert all(np.abs(part).max() > 1e-3 for part in parts)  # each share adds something
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- (3) every expert held: the block is what it was -------------------------


def _parent_block(c, lp, x):
    """models/moe._moe_block's one-chip path as the parent commit had it."""
    from dynamo_tpu.ops.moe_dispatch import router_topk

    gate = jax.nn.silu(mm(x, lp["ws_gate"]))
    shared = mm(gate * mm(x, lp["ws_up"]), lp["ws_down"])
    logits = (x @ lp["w_router"]).astype(jnp.float32)
    weights, sel = router_topk(
        logits, c.n_experts_active, c.moe_scoring, c.moe_norm_topk,
        bias=lp.get("router_bias"), routed_scale=c.moe_routed_scale,
        n_groups=c.n_expert_groups, topk_groups=c.topk_groups)
    weights, sel = weights.astype(x.dtype), sel.astype(jnp.int32)

    def one_expert(we_gate, we_up, we_down):
        return mm(jax.nn.silu(mm(x, we_gate)) * mm(x, we_up), we_down)

    out = jax.vmap(one_expert)(lp["we_gate"], lp["we_up"], lp["we_down"])
    sel_out = jnp.take_along_axis(out.transpose(1, 2, 0, 3), sel[..., None], axis=2)
    return jnp.sum(sel_out * weights[..., None], axis=2) + shared, sel


@pytest.mark.parametrize("held", [0, WHOLE.n_experts])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_every_expert_held_is_bit_identical_to_the_parent(held, dtype):
    """`n_experts_held` 0 (every preset) and `n_experts_held` = `n_experts`
    alike: the block's output and picks are the parent's, bit for bit."""
    c = WHOLE.with_(n_experts_held=held)
    assert not c.holds_share and c.experts_held == c.n_experts
    _, lp, x = _layer_inputs()
    lp = jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.float32 and a.ndim > 1 else a, lp)
    x = x.astype(dtype)
    got, sel, _ = jax.jit(lambda lp, x: _moe_block(c, lp, x))(lp, x)
    want, want_sel = jax.jit(lambda lp, x: _parent_block(c, lp, x))(lp, x)
    assert (np.asarray(sel) == np.asarray(want_sel)).all()
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("bad", [dict(n_experts_held=4, expert_first=13),
                                 dict(n_experts_held=17), dict(expert_first=2),
                                 dict(n_experts_held=4, expert_first=-1)])
def test_a_held_range_outside_the_router_is_refused(bad):
    with pytest.raises(ValueError, match="held experts"):
        WHOLE.with_(**bad)


def test_init_builds_the_held_experts_and_the_whole_router():
    shapes = jax.eval_shape(lambda: llama.init_params(TOY, jax.random.PRNGKey(0)))["layers"]
    assert shapes["w_router"].shape == (2, TOY.dim, 16)
    for k in ("we_gate", "we_up", "we_down"):
        assert shapes[k].shape[:2] == (2, 4)
    full = jax.eval_shape(lambda: llama.init_params(WHOLE, jax.random.PRNGKey(0)))["layers"]
    assert full["we_gate"].shape[:2] == (2, 16)


# -- (4) config.json -> ModelConfig, and a load that reads the held range ----

MISTRAL4_JSON = {  # the catalog's keys (architectures.jsonl, Mistral-Small-4-119B-2603)
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 12288, "kv_lora_rank": 256,
    "max_position_embeddings": 1048576, "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 36, "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
    "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_parameters": {"beta_fast": 32, "beta_slow": 1, "factor": 128, "llama_4_scaling_beta": 0.1,
                        "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 8192,
                        "rope_theta": 10000, "rope_type": "yarn", "type": "yarn"},
    "routed_scaling_factor": 1, "sliding_window": None, "tie_word_embeddings": False,
    "topk_group": 1, "v_head_dim": 128, "vocab_size": 131072,
}


def test_mistral4_config_json_maps_onto_the_preset(tmp_path):
    from dynamo_tpu.engine.weights import config_from_hf

    (tmp_path / "config.json").write_text(json.dumps(MISTRAL4_JSON))
    c = config_from_hf(str(tmp_path), name="mistral-small-4-119b")
    want = get_config("mistral-small-4-119b")
    diff = {k: (v, getattr(want, k)) for k, v in dataclasses.asdict(c).items()
            if v != getattr(want, k)}
    # n_group 1 / topk_group 1 is "no groups" either way (the block limits
    # groups only where there are several)
    assert diff == {"n_expert_groups": (1, 0), "topk_groups": (1, 0)}
    assert c.attn_qscale_beta == 0.1 and c.attn_qscale_orig == 8192
    assert c.rope_scaling == "yarn" and c.rope_factor == 128.0 and c.rope_theta == 10000.0
    assert llama.attn_score_scale(c, 128) == pytest.approx(
        128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2)
    # the benchmark's configuration is this model cut to one chip's share
    with open(os.path.join(REPO, "benchmark", "configs", "mistral-small-4-119b.json")) as f:
        cell = ModelConfig(**json.load(f)["model"])
    cut = dict(n_layers=6, vocab_size=32768, n_experts_held=32, expert_first=32, max_seq_len=4096)
    assert cell == want.with_(**cut)


def test_the_published_file_keeps_every_catalog_number():
    with open(os.path.join(REPO, "benchmark", "configs", "mistral-small-4-119b.json")) as f:
        cfg = json.load(f)
    differs = sorted(k for k, v in MISTRAL4_JSON.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: MISTRAL4_JSON[k] for k in cfg["reduced"]}


def test_a_checkpoint_load_reads_only_the_held_experts(tmp_path):
    """A mistral4-shaped checkpoint that HOLDS only experts 2..3 of 8 on disk
    loads under the matching held range (a loader that read another expert's
    tensor would raise), rotary columns de-interleaved, and serves a finite
    forward; without the range the same load fails on expert 0."""
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.weights import _rope_deinterleave, config_from_hf, load_hf_checkpoint

    V, E, L, H, dc, dr, dn, dv, qr, MF, NEXP = 64, 32, 2, 2, 16, 8, 8, 16, 24, 24, 8
    rng = np.random.default_rng(5)

    def w(*shape):
        return rng.standard_normal(shape).astype(np.float32) * 0.05

    t = {"model.embed_tokens.weight": w(V, E), "model.norm.weight": np.ones(E, np.float32),
         "lm_head.weight": w(V, E)}
    for i in range(L):
        pre = f"model.layers.{i}."
        t[pre + "input_layernorm.weight"] = np.ones(E, np.float32)
        t[pre + "post_attention_layernorm.weight"] = np.ones(E, np.float32)
        t[pre + "self_attn.q_a_proj.weight"] = w(qr, E)
        t[pre + "self_attn.q_a_layernorm.weight"] = np.ones(qr, np.float32)
        t[pre + "self_attn.q_b_proj.weight"] = w(H * (dn + dr), qr)
        t[pre + "self_attn.kv_a_proj_with_mqa.weight"] = w(dc + dr, E)
        t[pre + "self_attn.kv_a_layernorm.weight"] = np.ones(dc, np.float32)
        t[pre + "self_attn.kv_b_proj.weight"] = w(H * (dn + dv), dc)
        t[pre + "self_attn.o_proj.weight"] = w(E, H * dv)
        t[pre + "mlp.gate.weight"] = w(NEXP, E)
        for e in (2, 3):
            t[pre + f"mlp.experts.{e}.gate_proj.weight"] = w(MF, E)
            t[pre + f"mlp.experts.{e}.up_proj.weight"] = w(MF, E)
            t[pre + f"mlp.experts.{e}.down_proj.weight"] = w(E, MF)
        for part, shape in (("gate", (MF, E)), ("up", (MF, E)), ("down", (E, MF))):
            t[pre + f"mlp.shared_experts.{part}_proj.weight"] = w(*shape)
    save_file(t, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        **MISTRAL4_JSON, "vocab_size": V, "hidden_size": E, "num_hidden_layers": L,
        "num_attention_heads": H, "num_key_value_heads": H, "kv_lora_rank": dc, "q_lora_rank": qr,
        "qk_rope_head_dim": dr, "qk_nope_head_dim": dn, "v_head_dim": dv,
        "n_routed_experts": NEXP, "num_experts_per_tok": 2, "moe_intermediate_size": MF,
        "max_position_embeddings": 64}))
    whole = config_from_hf(str(tmp_path), name="toy-mistral4")
    assert whole.n_experts == NEXP and whole.q_lora_rank == qr and whole.attn_qscale_orig == 8192
    with pytest.raises(KeyError, match=r"experts\.0\."):
        load_hf_checkpoint(str(tmp_path), whole, dtype="float32")
    c = whole.with_(n_experts_held=2, expert_first=2)
    params = load_hf_checkpoint(str(tmp_path), c, dtype="float32")
    assert params["layers"]["we_gate"].shape == (L, 2, E, MF)
    assert params["layers"]["w_router"].shape == (L, E, NEXP)
    np.testing.assert_array_equal(params["layers"]["we_up"][1, 1],
                                  t["model.layers.1.mlp.experts.3.up_proj.weight"].T)
    perm = _rope_deinterleave(dr)
    raw = t["model.layers.0.self_attn.q_b_proj.weight"].T.reshape(qr, H, dn + dr)
    np.testing.assert_array_equal(
        params["layers"]["wq_up"][0].reshape(qr, H, dn + dr)[:, :, dn:], raw[:, :, dn:][:, :, perm])
    kp, vp = llama.make_kv_pool(c, 8, 4, jnp.float32)
    logits = llama.forward(c, jax.tree.map(jnp.asarray, params), jnp.asarray([[1, 2, 3, 4]]),
                           jnp.asarray([[0, 1, 2, 3]]), kp, vp,
                           jnp.arange(8, dtype=jnp.int32)[None], jnp.asarray([4]))[0]
    assert np.isfinite(np.asarray(logits)).all()


def test_worker_flags_set_the_held_range():
    from dynamo_tpu import worker

    args = worker.parse_args(["--model", "tiny-mistral4", "--experts-held", "8",
                              "--expert-first", "8"])
    assert (args.experts_held, args.expert_first) == (8, 8)
    assert worker.parse_args([]).experts_held == 0


# -- (5) the counters under a share, and (6) the payload ----------------------


def _count(picks, first, held):
    """numpy twin of models/moe.routing_stats over forwards: picks
    [forwards][L, tokens, k] -> (slots, hit, share, held slots)."""
    slots, hit, share, mine, units, L = 0, 0.0, 0.0, 0.0, 0, picks[0].shape[0]
    for f in picks:
        _, T, k = f.shape
        slots += T * k
        for l in range(L):
            load = np.bincount(f[l].ravel(), minlength=first + held)[first: first + held]
            hit += (load > 0).sum()
            share += load.max() / T
            mine += load.sum()
            units += 1
    return slots, hit / units, share / units, mine / L


def test_routing_stats_count_the_held_experts():
    rng = np.random.default_rng(2)
    sel = np.stack([np.stack([rng.permutation(16)[:4] for _ in range(9)]) for _ in range(2)])
    valid = np.array([True] * 7 + [False] * 2)
    got = np.asarray(routing_stats(jnp.asarray(sel, jnp.int32), jnp.asarray(valid), TOY))
    slots, hit, share, mine = _count([sel[:, :7]], 4, 4)
    np.testing.assert_allclose(got, [slots, hit * 2, share * 2, mine * 2, 0], rtol=1e-6)
    whole = np.asarray(routing_stats(jnp.asarray(sel, jnp.int32), jnp.asarray(valid), WHOLE))
    assert whole[3] == whole[0] * 2  # every expert held: every slot, in both layers


@pytest.fixture(scope="module")
def served():
    """The toy through the engine's normal path (float32): a lead that decodes
    while the others prefill in chunks, every request asking for its picks."""
    mp = pytest.MonkeyPatch()
    mp.setenv("DYN_FUSED_MIXED", "1")
    runner = ModelRunner(TOY, num_pages=128, page_size=PAGE, max_pages_per_seq=16,
                         decode_buckets=(1, 2, 4), prefill_buckets=(8, 16), seed=7,
                         dtype=jnp.float32)
    engine = InferenceEngine(runner, max_batch=4, chunk_size=8, mixed_prefill_tokens=8,
                             mixed_prefill_seqs=1)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(1, TOY.vocab_size, n).tolist(), k)
            for n, k in [(6, 24), (7, 12), (19, 6)]]

    async def one(prompt, n_out):
        return [it async for it in engine.generate(
            {"token_ids": prompt, "sampling": {"temperature": 0.0, "routed_experts": True},
             "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}}, Context())]

    async def drive():
        return await asyncio.gather(*(one(p, k) for p, k in reqs))

    try:
        out = asyncio.run(drive())
    finally:
        engine.stop()
        mp.undo()
    return dict(runner=runner, engine=engine, reqs=reqs, items=out,
                records=engine.recorder.snapshot())


def test_counters_under_a_share(served):
    k = TOY.n_experts_active
    recs = [r for r in served["records"] if r.moe_token_slots]
    assert recs
    for r in recs:
        assert r.moe_token_slots == k * (r.decode_seqs * r.decode_steps + r.chunk_tokens)
        assert 0.0 <= r.moe_held_slots <= r.moe_token_slots
        assert 0.0 <= r.moe_experts_hit <= TOY.experts_held  # of the held, not of 16
        assert 0.0 <= r.moe_load_max_share <= 1.0
        assert type(r.moe_held_slots) is float
    total, held = sum(r.moe_token_slots for r in recs), sum(r.moe_held_slots for r in recs)
    assert 0.1 < held / total < 0.45  # a quarter of the experts, a handful of tokens
    assert served["engine"].moe_totals["held_slots_total"] == pytest.approx(held)
    assert served["engine"].moe_totals["token_slots_total"] == total


def test_the_payload_keeps_ids_over_the_full_width(served, ref):
    """`routed_experts` under a share: positions 0 .. n_prompt + n_out - 2
    once each and in order, k ids a layer drawn from all 16 experts, and they
    are the picks the float32 reference makes on the same tokens."""
    p = served["runner"].params
    seen = set()
    for (prompt, n_out), items in zip(served["reqs"], served["items"]):
        toks = [t for it in items for t in it["token_ids"]]
        assert len(toks) == n_out
        ids, at = [], 0
        for it in items:
            r = it.get("routed_experts")
            if r:
                assert r["start"] == at
                ids += r["ids"]
                at += len(r["ids"])
        got = np.asarray(ids)  # [S, L, k]
        assert got.shape == (len(prompt) + n_out - 1, TOY.n_layers, TOY.n_experts_active)
        seq = np.asarray(prompt + toks[:-1], np.int32)
        own = ref.own_picks(_model(TOY), p, seq)
        assert (np.sort(got, -1) == np.sort(own, -1)).mean() > 0.99  # a tie in a million
        seen |= set(got.ravel().tolist())
    assert min(seen) < TOY.expert_first and max(seen) >= TOY.expert_first + TOY.experts_held


async def test_metrics_show_the_held_slots(served):
    from dynamo_tpu.frontend.protocols import ModelCard
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    engine = InferenceEngine(served["runner"], max_batch=4, chunk_size=8)
    rt = DistributedRuntime(discovery=MemDiscovery(realm="mistral4-metrics"),
                            event_transport="inproc")
    try:
        w = await serve_worker(rt, engine, ModelCard(name="m"), digest_period_s=0,
                               publish_kv_events=False, publish_fpm=False)
        prompt, _ = served["reqs"][0]
        items = [it async for it in engine.generate(
            {"token_ids": prompt, "sampling": {"temperature": 0.0},
             "stop": {"max_tokens": 6, "stop_ids": [], "ignore_eos": True}}, Context())]
        assert items[-1]["finish_reason"] == "length"
        for _ in range(100):
            if (engine._rec_late is None and engine._inflight is None
                    and not engine._undelivered
                    and not engine.scheduler.has_work()):
                break
            await asyncio.sleep(0.01)
        engine._publish_fpm("decode", 0.0, 0)  # (the hook refreshes /metrics)
        for _ in range(100):  # the loop's idle pass delivers it
            if not engine._undelivered:
                break
            await asyncio.sleep(0.01)
        lines = rt.metrics.render().decode().splitlines()
        totals = dict(engine.moe_totals)
        await w.stop()
    finally:
        await rt.shutdown(drain_timeout=1)
        engine.stop()

    def value(name):
        got = [float(ln.rsplit(" ", 1)[1]) for ln in lines
               if re.match(rf"dynamo_{name}(\{{| )", ln)]
        assert len(got) == 1, (name, got)
        return got[0]

    assert value("moe_token_slots_total") == totals["token_slots_total"] > 0
    assert value("moe_held_slots_total") == pytest.approx(totals["held_slots_total"])
    assert 0 <= value("moe_held_slots_total") < value("moe_token_slots_total")
    assert value("moe_experts_hit") <= TOY.experts_held
