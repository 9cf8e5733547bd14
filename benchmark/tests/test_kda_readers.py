"""What PR 49 added to the yardstick: costs_kda.py's integers against the
program's own tree and counts done by hand, the four readers on hand-made
contexts, what they return for a program that has no such kernel or counter
(None: the line leaves the metric out, and nothing raises), make_params on
the family's tree, the rehearsal overlay, and the BENCHMARK.json entries."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import costs_kda
import loadgen
import rehearsal
from dynamo_tpu.models import ling, llama
from dynamo_tpu.models.config import PRESETS, ModelConfig

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "ling3-reasoning-steady"
NEW = ["kda.decode_step_ms", "kda.decode_stream_pct",
       "kernels.kda_update_roofline_pct", "kernels.kda_chunk_roofline_pct"]
APPENDED = ["moe.experts_hit_mean", "moe.load_max_share", "moe.held_slot_pct",
            "ssm.state_slots_used_pct"]
with open(os.path.join(BENCH, "configs", "ling-3.0-flash-vl.json")) as _f:
    CFG = json.load(_f)
MODEL = CFG["model"]
with open(os.path.join(BENCH, "configs", "ai21-jamba2-3b.json")) as _f:
    JAMBA = json.load(_f)["model"]


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return _module(os.path.join(BENCH, "layers", name + ".py"),
                   "layer_" + name.replace(".", "_")).read


# -- the arithmetic ---------------------------------------------------------


def test_the_integers_are_the_cut_models():
    assert costs_kda.kda_layers(MODEL) == 15 and costs_kda.mla_layers(MODEL) == 3
    assert costs_kda.moe_layers(MODEL) == 16
    # by hand: four [2560, 4096] projections and the output, two [2560, 32]
    # heads' worth, three convolutions of 4 taps on 4096 channels
    assert costs_kda.kda_mixer_params(MODEL) == 5 * 2560 * 4096 + 2 * 2560 * 32 + 4 * 3 * 4096 == 52_641_792
    assert costs_kda.mla_mixer_params(MODEL) == (
        2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32 + 4096 * 2560) == 31_965_184
    assert costs_kda.expert_params(MODEL) == 3 * 2560 * 768 == 5_898_240
    assert costs_kda.state_layer_bytes(MODEL) == 32 * 128 * 128 * 4 == 2_097_152
    assert costs_kda.conv_layer_bytes(MODEL) == 3 * 3 * 4096 * 2 == 73_728
    assert costs_kda.state_slot_bytes(MODEL) == 15 * 2_170_880 == 32_563_200
    assert costs_kda.latent_bytes_per_token(MODEL) == 3 * 576 * 2
    # the program's tree holds these matrices and its vectors (norms, the
    # gate's fills, the router's bias), and its pool these bytes
    c = ModelConfig(**MODEL)
    shapes = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0), jnp.bfloat16))
    leaves = jax.tree.leaves(shapes)
    vectors = sum(int(np.prod(a.shape)) for a in leaves if a.dtype == jnp.float32)
    assert sum(int(np.prod(a.shape)) for a in leaves) == costs_kda.param_count(MODEL) + vectors
    assert costs_kda.param_count(MODEL) + vectors == 4_215_902_560  # 8.43 GB in bf16
    assert ling.state_slot_bytes(c) == costs_kda.state_slot_bytes(MODEL)


def test_the_new_mechanism_is_over_half_of_a_decode_steps_bytes():
    w = costs_kda.weight_stream_bytes(MODEL, experts_hit=15)
    outside = costs_kda.weight_stream_bytes(MODEL, experts_hit=0)
    assert 2.2e9 < outside < 2.3e9 and w - outside == 2 * 16 * 15 * 5_898_240
    step = costs_kda.decode_step_bytes(MODEL, rows=40, live_tokens=40 * 1100, experts_hit=15)
    state = 40 * 15 * (2 * 2_097_152 + 73_728)
    assert step == w + state + 40 * 1100 * 3456
    kda = state + 15 * costs_kda.kda_mixer_params(MODEL) * 2
    assert 7.5e9 < step < 8.0e9 and 0.5 < kda / step < 0.56
    assert 0.31 < state / step < 0.34  # and the state's share grows with every row


def test_a_kernel_call_moves_states_and_operands():
    s, w = 2_097_152, 4096
    assert costs_kda.kda_update_call_bytes(MODEL, 1) == 2 * s + 8 * w * 4 + w * 4
    assert costs_kda.kda_update_call_bytes(MODEL, 40.5) == pytest.approx(40.5 * (2 * s + 147_456))
    block = (5 * 64 * 128 + 64 * 64 + 8 * 128) * 4  # a head's operands of one block
    assert block == 184_320
    assert costs_kda.kda_chunk_call_bytes(MODEL, 512) == 32 * (8 * block + 2 * 65_536)
    flops = 32 * 8 * (3 * 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128)
    assert costs_kda.kda_chunk_call_flops(MODEL, 512) == flops
    # 37 operations a byte: under the chip's 197e12 / 819e9 = 240, so the bytes bind
    assert 35 < flops / costs_kda.kda_chunk_call_bytes(MODEL, 512) < 38


# -- the readers ------------------------------------------------------------


def _iter(**kw):
    base = {"decode_seqs": 40, "decode_steps": 4, "kv_usage": 0.2, "ragged": False,
            "n_chunks": 0, "chunk_tokens": 0, "state_slots_used": 40, "state_slots_total": 64,
            "moe_experts_hit": 15.0, "kda_update_rows": 160, "kda_chunk_tokens": 0,
            "kda_chunk_segments": 0}
    return {**base, **kw}


def _ctx(iters, trace=None, model=MODEL, captures=None):
    return {"counters": {"iterations": iters, "trace": captures and {"captures": captures}},
            "model": model, "here": BENCH,
            "percentile": loadgen.percentile,
            "ready": {"device": {"kind": "TPU v5 lite"},
                      "engine": {"page_size": 64, "num_pages": 4096}},
            "trace": trace}


def _loop(durations_ms, steps):
    """A decode-loop module: 3 attention calls and 15 updates a step."""
    return {"jit_decode_loop[kda_update]": {
        "durations_ms": durations_ms,
        "kernels": [{"decode_mla_attention": 3 * n, "kda_update": 15 * n,
                     "routed_experts": 16 * n} for n in steps]}}


def test_a_step_is_the_attention_calls_over_the_mla_layers():
    trace = {"modules": _loop([48.0, 52.0, 36.0, 12.5], [4, 4, 3, 1])}
    assert reader("kda.decode_step_ms")(_ctx([_iter()], trace)) == pytest.approx(12.25)
    # the accepted reader divides by all 18 layers and finds no whole step
    assert reader("runner.decode_step_ms")(_ctx([_iter()], trace)) is None
    assert reader("kda.decode_step_ms")(_ctx([_iter()], trace, model=JAMBA)) is None
    assert reader("kda.decode_step_ms")(_ctx([_iter()])) is None  # untraced
    assert reader("kda.decode_step_ms")(_ctx([_iter()], {"modules": {}})) is None


def test_the_stream_share_is_the_steps_bytes_over_its_time():
    trace = {"modules": _loop([48.0], [4])}
    need = costs_kda.decode_step_bytes(MODEL, 40, 0.2 * 4096 * 64, 15.0)
    got = reader("kda.decode_stream_pct")(_ctx([_iter()], trace))
    assert got == pytest.approx(100 * need / 819e9 / 12e-3) and 70 < got < 100
    assert reader("kda.decode_stream_pct")(_ctx([_iter()])) is None
    assert reader("kda.decode_stream_pct")(_ctx([_iter()], trace, model=JAMBA)) is None


def test_the_kernels_shares_join_the_trace_with_the_captured_iterations():
    caps = [{"start_wall": 10.0, "stop_wall": 11.0}]
    its = [_iter(ts=10.5), _iter(ts=20.0, decode_seqs=8, kda_update_rows=32),
           _iter(ts=10.6, decode_seqs=0, decode_steps=0, kda_update_rows=0, n_chunks=1,
                 chunk_tokens=384, kda_chunk_tokens=384, kda_chunk_segments=1)]
    trace = {"kernels": {"kda_update": {"calls": 60, "total_s": 60 * 250e-6},
                         "kda_chunk.1": {"calls": 15, "total_s": 15 * 400e-6}}}
    ctx = _ctx(its, trace, captures=caps)
    up = reader("kernels.kda_update_roofline_pct")(ctx)
    assert up == pytest.approx(100 * costs_kda.kda_update_call_bytes(MODEL, 40) / 819e9 / 250e-6)
    assert 80 < up < 100
    ch = reader("kernels.kda_chunk_roofline_pct")(ctx)
    assert ch == pytest.approx(100 * costs_kda.kda_chunk_call_bytes(MODEL, 384) / 819e9 / 400e-6)
    for name in ("kernels.kda_update_roofline_pct", "kernels.kda_chunk_roofline_pct"):
        assert reader(name)(_ctx(its)) is None  # untraced
        assert reader(name)(_ctx(its, {"kernels": {"ssm_update": {"calls": 1, "total_s": 1.0}}})) is None
        # a program without the counters (the parent of PR 49): nothing to read
        bare = [{k: v for k, v in i.items() if not k.startswith("kda_")} for i in its]
        assert reader(name)(_ctx(bare, trace)) is None


# -- the harness's own files ---------------------------------------------------


def test_make_params_draws_what_the_program_draws_and_keeps_its_fills():
    from serve import drawn_leaves

    c = PRESETS["tiny-ling"]
    shapes = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0), jnp.bfloat16))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    drawn = drawn_leaves(c, jnp.bfloat16)
    for (path, sd), is_drawn in zip(leaves, drawn):
        name = getattr(path[-1], "key", str(path[-1]))
        fills = ("norm", "A_log", "dt_bias", "router_bias")
        assert is_drawn != any(f in name for f in fills), name
        assert not is_drawn or sd.dtype == jnp.bfloat16


def test_the_rehearsal_overlay_is_a_model_the_program_builds():
    reh = rehearsal.rehearsal_sizes(CFG, BENCH)
    c = ModelConfig(**reh["model"])
    assert c.is_kda and c.kv_layers == 2 and c.kda_layers == 4 and c.n_dense_layers == 1
    assert c.holds_share and c.n_expert_groups == 4
    assert reh["correct_routing_margin"] is not None
    assert reh["server_flags"]["mixed-prefill-tokens"] == 16


def test_the_configuration_file_keeps_every_published_number():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(l) for l in f]
    pub = next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")
    assert CFG["source"] == pub["source_url"]
    for k, v in pub["config"].items():
        if k in CFG["reduced"]:
            assert CFG["published"][k] == v and CFG[k] != v
        else:
            assert CFG[k] == v, k
    assert MODEL["n_experts"] == CFG["published"]["num_experts"]
    assert MODEL["n_experts_held"] == CFG["num_experts"] == 32
    assert not any(CFG["expert_swiglu_limit_list"][:MODEL["n_layers"]])
    assert not any(CFG["share_expert_swiglu_limit_list"][:MODEL["n_layers"]])


def test_the_benchmark_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1 and len(cell["why"]) <= 200
    assert bench["configs"][-1]["name"] == cell["config"] == "ling-3.0-flash-vl"
    assert len(bench["configs"][-1]["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL
    assert CELL not in by_name["runner.decode_step_ms"]["workloads"]
    # (its reader reads this cell, 8-10 %: PERF.md section 5; an older by-hand
    # test holds that list to the one dense latent cell, so it is not appended)
    assert CELL not in by_name["kernels.mla_decode_roofline_pct"]["workloads"]
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "open_loop" and traffic["prompt_tokens"]["median"] == 512
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.6,
                                        "min": 128, "max": 2048}


def test_the_mix_replays_the_first_typical_window_at_its_rate():
    """chat-steady.json's rule for `shape_seed`, held by test_arithmetic.py for
    the older steady mixes (a list this PR may not edit): the first of 1, 2, 3,
    ... whose 50 s window is typical at the mix's own rate and lead-in."""
    import run
    from test_arithmetic import _run_seconds, window_stats

    t = run.read_traffic("reasoning-steady-ling3")
    stats = [window_stats(t, _run_seconds(), s) for s in range(1, t["shape_seed"] + 1)]
    assert [s["typical"] for s in stats] == [False] * (t["shape_seed"] - 1) + [True], stats[-1]
    assert stats[-1]["requests"] == round(t["rate_rps"] * _run_seconds())

