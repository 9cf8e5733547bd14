"""What PR 36 added to the yardstick: costs_ssm.py's integers against the
published model, the five readers on hand-made contexts, what they return for
a program that has no such kernel or counter (None: the line leaves the metric
out, and nothing raises), make_params on the hybrid tree (every leaf of rank 1
a fill), the rehearsal overlay, and the BENCHMARK.json entries."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import costs_ssm
import loadgen
import rehearsal
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS, ModelConfig

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "jamba2-reasoning-steady"
NEW = ["ssm.decode_step_ms", "ssm.decode_stream_pct", "ssm.state_slots_used_pct",
       "kernels.ssm_update_roofline_pct", "kernels.ssm_scan_roofline_pct"]
with open(os.path.join(BENCH, "configs", "ai21-jamba2-3b.json")) as _f:
    CFG = json.load(_f)
MODEL = CFG["model"]
with open(os.path.join(BENCH, "configs", "phi-3-mini-4k.json")) as _f:
    PHI3 = json.load(_f)["model"]


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return _module(os.path.join(BENCH, "layers", name + ".py"),
                   "layer_" + name.replace(".", "_")).read


# -- the arithmetic ---------------------------------------------------------


def test_the_integers_are_the_published_models():
    assert costs_ssm.mamba_layers(MODEL) == 26 and costs_ssm.attn_layers(MODEL) == 2
    assert costs_ssm.mixer_params(MODEL) == 41_241_792
    assert costs_ssm.attention_params(MODEL) == 13_762_560
    assert costs_ssm.mlp_params(MODEL) == 62_914_560
    assert costs_ssm.param_count(MODEL) == 3_029_337_472  # 3.029 B, 6.06 GB in bf16
    assert costs_ssm.state_layer_bytes(MODEL) == 16 * 5120 * 4
    assert costs_ssm.state_slot_bytes(MODEL) == 26 * (327_680 + 30_720) == 9_318_400
    assert costs_ssm.kv_bytes_per_token(MODEL) == 1024
    # the program's tree holds exactly these parameters, and its pool these bytes
    shapes = jax.eval_shape(lambda: llama.init_params(
        ModelConfig(**MODEL), jax.random.PRNGKey(0), jnp.bfloat16))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == costs_ssm.param_count(MODEL)
    from dynamo_tpu.models import jamba

    assert jamba.state_slot_bytes(ModelConfig(**MODEL)) == costs_ssm.state_slot_bytes(MODEL)


def test_a_full_batchs_state_and_mixers_are_near_half_of_a_steps_bytes():
    w = costs_ssm.weight_stream_bytes(MODEL)
    assert 6.0e9 < w < 6.06e9  # the matrices of 6.06 GB: vectors and norms left out
    step = costs_ssm.decode_step_bytes(MODEL, rows=64, live_tokens=64 * 700)
    state = 64 * 26 * (2 * 327_680 + 30_720)
    assert step == w + state + 64 * 700 * 1024
    mixers = 26 * costs_ssm.mixer_matrix_params(MODEL) * 2
    assert 0.14 < state / step < 0.16 and 0.43 < (state + mixers) / step < 0.47
    assert costs_ssm.decode_step_bytes(MODEL, 8, 8 * 700) < 6.25e9  # 8 rows: 2 % state


def test_a_kernel_call_moves_states_and_operands():
    s, d, n = 327_680, 5120, 16
    assert costs_ssm.ssm_update_call_bytes(MODEL, 1) == 2 * s + 3 * d * 4 + 2 * n * 4 + n * d * 4
    assert costs_ssm.ssm_update_call_bytes(MODEL, 48.5) == pytest.approx(
        48.5 * (2 * s + 61_568) + s)
    assert costs_ssm.ssm_scan_call_bytes(MODEL, 200, 30, 2) == 200 * 61_568 + 30 * 2 * s + 2 * s + s


# -- the readers ------------------------------------------------------------


def _iter(**kw):
    base = {"decode_seqs": 48, "decode_steps": 4, "kv_usage": 0.25, "ragged": False,
            "n_chunks": 0, "chunk_tokens": 0, "state_slots_used": 48, "state_slots_total": 64,
            "ssm_scan_tokens": 0, "ssm_scan_segments": 0}
    return {**base, **kw}


def _ctx(iters, trace=None, model=MODEL, captures=None):
    return {"counters": {"iterations": iters, "trace": captures and {"captures": captures}},
            "model": model, "here": BENCH,
            "percentile": loadgen.percentile,
            "ready": {"device": {"kind": "TPU v5 lite"},
                      "engine": {"page_size": 64, "num_pages": 2880}},
            "trace": trace}


def _loop(durations_ms, steps):
    """A decode-loop module: 2 attention calls and 26 updates a step."""
    return {"jit_decode_loop[ssm_update]": {
        "durations_ms": durations_ms,
        "kernels": [{"decode_paged_attention": 2 * n, "ssm_update": 26 * n} for n in steps]}}


def test_a_step_is_the_attention_calls_over_the_attention_layers():
    trace = {"modules": _loop([40.0, 44.0, 30.0, 10.5], [4, 4, 3, 1])}
    assert reader("ssm.decode_step_ms")(_ctx([_iter()], trace)) == pytest.approx(10.25)
    # the accepted reader divides by all 28 layers and finds no whole step
    assert reader("runner.decode_step_ms")(_ctx([_iter()], trace)) is None
    assert reader("ssm.decode_step_ms")(_ctx([_iter()], trace, model=PHI3)) is None
    assert reader("ssm.decode_step_ms")(_ctx([_iter()])) is None  # untraced
    assert reader("ssm.decode_step_ms")(_ctx([_iter()], {"modules": {}})) is None


def test_the_stream_share_is_the_steps_bytes_over_bandwidth_over_its_time():
    trace = {"modules": _loop([40.0, 40.0], [4, 4])}
    iters = [_iter(decode_seqs=40, kv_usage=0.2), _iter(decode_seqs=56, kv_usage=0.3),
             _iter(decode_seqs=0, decode_steps=0)]
    need = costs_ssm.decode_step_bytes(MODEL, 48, 0.25 * 2880 * 64)
    got = reader("ssm.decode_stream_pct")(_ctx(iters, trace))
    assert got == pytest.approx(100 * need / 819e9 / 10e-3)
    assert 80 < got < 100
    assert reader("ssm.decode_stream_pct")(_ctx(iters)) is None
    assert reader("ssm.decode_stream_pct")(_ctx([], trace)) is None


def test_slots_in_use_is_the_median_over_decode_iterations():
    iters = [_iter(state_slots_used=32), _iter(state_slots_used=48), _iter(state_slots_used=64),
             _iter(decode_seqs=0, state_slots_used=1)]
    assert reader("ssm.state_slots_used_pct")(_ctx(iters)) == pytest.approx(75.0)
    parent = _iter()
    del parent["state_slots_used"], parent["state_slots_total"]
    assert reader("ssm.state_slots_used_pct")(_ctx([parent])) is None  # no such counter
    assert reader("ssm.state_slots_used_pct")(_ctx([_iter(state_slots_total=0)])) is None


def test_the_update_kernels_share_counts_the_decode_loops_steps():
    # a ragged iteration's first step runs on the scan: three steps of update
    iters = [_iter(decode_seqs=40), _iter(decode_seqs=60, ragged=True, n_chunks=1,
                                          ssm_scan_tokens=160, ssm_scan_segments=61)]
    rows = (40 * 4 + 60 * 3) / 7
    kernels = {"ssm_update": {"calls": 26 * 7, "total_s": 26 * 7 * 60e-6, "median_us": 60.0},
               "ssm_scan": {"calls": 26, "total_s": 26 * 400e-6, "median_us": 400.0}}
    got = reader("kernels.ssm_update_roofline_pct")(_ctx(iters, {"kernels": kernels}))
    assert got == pytest.approx(
        100 * costs_ssm.ssm_update_call_bytes(MODEL, rows) / 819e9 / 60e-6)
    assert 0 < got < 100
    scan = reader("kernels.ssm_scan_roofline_pct")(_ctx(iters, {"kernels": kernels}))
    assert scan == pytest.approx(
        100 * costs_ssm.ssm_scan_call_bytes(MODEL, 160, 60, 1) / 819e9 / 400e-6)
    assert 0 < scan < 100


def test_the_shares_take_their_rows_from_the_captured_seconds():
    """The batch climbs through a window and the trace holds three seconds of
    it: rows, KV and the scan's tokens come from the iterations that began
    inside a capture, and from all of them where none did."""
    caps = [{"start_wall": 100.0, "stop_wall": 101.0, "written_s": 0.2},
            {"start_wall": 102.0, "stop_wall": 103.0, "written_s": 0.2}]
    early = [_iter(ts=90.0 + k, decode_seqs=20, kv_usage=0.1) for k in range(5)]
    late = [_iter(ts=100.5, decode_seqs=50, kv_usage=0.3),
            _iter(ts=102.2, decode_seqs=54, kv_usage=0.3, ragged=True, n_chunks=1,
                  ssm_scan_tokens=150, ssm_scan_segments=55),
            _iter(ts=101.5, decode_seqs=20, kv_usage=0.1, ragged=True, n_chunks=1,
                  ssm_scan_tokens=40, ssm_scan_segments=21)]  # between two captures
    kernels = {"ssm_update": {"calls": 26 * 7, "total_s": 26 * 7 * 60e-6, "median_us": 60.0},
               "ssm_scan": {"calls": 26, "total_s": 26 * 400e-6, "median_us": 400.0}}
    trace = {"modules": _loop([40.0, 40.0], [4, 4]), "kernels": kernels}
    ctx = _ctx(early + late, trace, captures=caps)
    assert reader("ssm.decode_stream_pct")(ctx) == pytest.approx(
        100 * costs_ssm.decode_step_bytes(MODEL, 52, 0.3 * 2880 * 64) / 819e9 / 10e-3)
    assert reader("kernels.ssm_update_roofline_pct")(ctx) == pytest.approx(
        100 * costs_ssm.ssm_update_call_bytes(MODEL, (50 * 4 + 54 * 3) / 7) / 819e9 / 60e-6)
    assert reader("kernels.ssm_scan_roofline_pct")(ctx) == pytest.approx(
        100 * costs_ssm.ssm_scan_call_bytes(MODEL, 150, 54, 1) / 819e9 / 400e-6)
    # no iteration inside a capture (or no capture recorded): the whole window
    for other in (_ctx(early + late, trace, captures=[dict(caps[0], start_wall=200.0, stop_wall=201.0)]),
                  _ctx(early + late, trace)):
        assert reader("ssm.decode_stream_pct")(other) == pytest.approx(
            100 * costs_ssm.decode_step_bytes(MODEL, 20, 0.1 * 2880 * 64) / 819e9 / 10e-3)


def test_the_scan_counts_one_forward_a_chunk_where_nothing_fused():
    iters = [_iter(decode_seqs=0, decode_steps=0, n_chunks=1, ssm_scan_tokens=120, ssm_scan_segments=1),
             _iter(n_chunks=3, ssm_scan_tokens=90, ssm_scan_segments=3)]  # two-dispatch: 3 forwards
    kernels = {"ssm_scan": {"calls": 104, "total_s": 104 * 100e-6, "median_us": 100.0}}
    got = reader("kernels.ssm_scan_roofline_pct")(_ctx(iters, {"kernels": kernels}))
    assert got == pytest.approx(
        100 * costs_ssm.ssm_scan_call_bytes(MODEL, 210 / 4, 0, 1) / 819e9 / 100e-6)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernels_or_the_counters_reads_nothing(name):
    """The parent commit serves no such model; a phi-3 trace has no state
    kernel and its records no slot: None, and no exception."""
    dense = {"decode_seqs": 8, "decode_steps": 4, "kv_usage": 0.2, "ragged": True, "n_chunks": 1}
    trace = {"kernels": {"decode_paged_attention": {"calls": 10, "total_s": 1e-3, "median_us": 100.0}},
             "modules": {"jit_decode_loop[decode_paged_attention]": {
                 "durations_ms": [30.0], "kernels": [{"decode_paged_attention": 128}]}}}
    assert reader(name)(_ctx([dense], trace, model=PHI3)) is None
    assert reader(name)(_ctx([], model=PHI3)) is None
    if name != "ssm.state_slots_used_pct":  # a counter: it needs no trace
        assert reader(name)(_ctx([_iter()], {})) is None


# -- the harness's tree and its rehearsal -----------------------------------


def test_make_params_fills_every_leaf_of_rank_one():
    serve = _module(os.path.join(BENCH, "serve.py"), "bench_serve_ssm")
    c = PRESETS["tiny-jamba"]
    got = serve.make_params(c, 2**31 + 5, jax.devices()[0], jnp.bfloat16)
    a = llama.init_params(c, jax.random.PRNGKey(1), jnp.bfloat16)
    assert jax.tree.structure(got) == jax.tree.structure(a)
    drawn = serve.drawn_leaves(c, jnp.bfloat16)
    n_fill = 0
    for (path, g), x, d in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(a), drawn):
        where = jax.tree_util.keystr(path)
        assert g.shape == x.shape and g.dtype == x.dtype, where
        stacked = g.ndim - (0 if where in ("['embed']", "['norm_f']") else 1)
        if stacked < 2 or where.endswith("['A_log']"):
            assert not d, where  # a vector (and A): the program's own value
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
            n_fill += 1
        else:
            assert d, where
            fan_in = g.shape[-1] if where == "['embed']" else g.shape[-2]
            assert np.asarray(g, np.float32).std() * fan_in ** 0.5 == pytest.approx(1.0, abs=0.2), where
    assert n_fill == 2 + 1 + 7  # two layer norms, the final one, the mixer's seven
    assert got["mamba"]["w_conv"].shape[-2] == c.mamba_d_conv  # drawn at fan-in 4


def test_the_rehearsal_is_a_toy_of_the_same_structure():
    reh = rehearsal.rehearsal_sizes(CFG, BENCH)
    c = ModelConfig(**reh["model"])
    assert c.is_hybrid and c.attn_layers == (2, 6) and c.n_layers == 8
    assert (c.dim, c.n_heads, c.n_kv_heads, c.sliding_window) == (64, 4, 1, 0)
    assert (c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_inner) == (4, 8, 128)
    assert reh["correct_routing_margin"] is None and "correct_routing_margin" not in CFG
    assert reh["correct_tolerance"] == CFG["rehearse"]["correct_tolerance"]


def test_the_configuration_states_the_published_keys_uncut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
    assert CFG["source"] == row["source_url"] and CFG["reduced"] == []
    for k, v in row["config"].items():
        assert CFG[k] == v, k
    c = ModelConfig(**MODEL)
    assert (c.dim, c.n_layers, c.n_heads, c.n_kv_heads, c.ffn_dim, c.vocab_size) == (
        row["hidden_size"], row["layers"], row["num_attention_heads"],
        row["num_key_value_heads"], row["dense_width"], row["vocab_size"])
    assert (c.mamba_d_state, c.mamba_d_conv, c.mamba_dt_rank, c.mamba_expand) == (16, 4, 160, 2)
    assert c.attn_layers == (7, 21) and c.tie_embeddings and c.head_dim == 128


def test_the_benchmark_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["config"] == "ai21-jamba2-3b" and cell["traffic"] == "reasoning-steady-jamba2"
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cfg["reduced"] == [] and len(cfg["why"]) <= 200
    assert os.path.exists(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    per = {m["name"]: m for m in bench["per_layer"]}  # by name: later PRs append
    for name in NEW:
        assert CELL in per[name]["workloads"]
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    # the two readers that count a step over every layer find none in this cell
    assert CELL not in per["runner.decode_step_ms"]["workloads"]
    assert CELL not in per["model.decode_stream_pct"]["workloads"]
    # every accepted metric without a list is read in this cell too
    unlisted = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert len(unlisted) >= 19 and all(os.path.exists(
        os.path.join(BENCH, "layers", n + ".py")) for n in unlisted)
