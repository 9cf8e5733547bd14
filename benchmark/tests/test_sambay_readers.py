"""What PR 54 added to the yardstick: costs_sambay.py's integers against the
published model (3.85 B) and a hand-reckoned decode step, every new reader on
a hand-made context and the re-used ones on THIS model group (what they count
right here, and the two that cannot: they have twins of their own name), what
each returns for a program without the span or the counter (None: the line
leaves the metric out, nothing raises), make_params on the tree, the new mix
by test_arithmetic's rules, the rehearsal overlay and the BENCHMARK.json
entries. The cell end to end is test_rehearsal.py's, which walks
BENCHMARK.json."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import costs_sambay
import costs_ssm
import loadgen
import rehearsal
from dynamo_tpu.models import jamba, llama, sambay
from dynamo_tpu.models.config import ModelConfig

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "phi4flash-reasoning-steady"
NEW = ["sambay.decode_step_ms", "sambay.decode_stream_pct", "yoco.cross_kv_reread_pct",
       "yoco.prefill_skipped_pct", "kernels.sambay_window_decode_roofline_pct",
       "kernels.sambay_full_decode_roofline_pct"]
REUSED = ["kernels.ssm_update_roofline_pct", "kernels.ssm_scan_roofline_pct",
          "kernels.ragged_attn_busy_pct", "ssm.state_slots_used_pct",
          "swa.window_resident_pct", "swa.window_pages_used_pct"]
with open(os.path.join(BENCH, "configs", "phi-4-mini-flash-reasoning.json")) as _f:
    CFG = json.load(_f)
MODEL = CFG["model"]
with open(os.path.join(BENCH, "configs", "ai21-jamba2-3b.json")) as _f:
    JAMBA = json.load(_f)["model"]
PEAK = 819e9  # peaks.json, TPU v5 lite


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
    m = MODEL
    assert [costs_sambay.count(m, k) for k in costs_sambay.KINDS] == [9, 8, 1, 7, 7]
    assert costs_sambay.layer_kinds(m) == list(ModelConfig(**m).layer_kinds)
    assert costs_sambay.mlp_params(m) == 78_643_200
    assert costs_sambay.mixer_matrix_params(m) == 41_144_320
    assert costs_sambay.attention_matrix_params(m) == 13_107_200 + 6_553_600
    assert costs_sambay.cross_matrix_params(m) == 13_107_200
    assert costs_sambay.gmu_params(m) == 26_214_400
    assert costs_sambay.param_count(m) == 3_852_562_944  # 3.85 B, 7.7 GB in bf16
    shapes = jax.eval_shape(lambda: llama.init_params(
        ModelConfig(**m), jax.random.PRNGKey(0), jnp.bfloat16))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == costs_sambay.param_count(m)
    assert costs_sambay.state_slot_bytes(m) == 9 * (327_680 + 30_720) == \
        jamba.state_slot_bytes(ModelConfig(**m))
    # a token of a layer's cache: 20 heads of 64, K and V, bf16: the DATA. The
    # pools hold the 10 pairs in 16 heads (whole tiles), 8192 bytes as stored
    assert costs_sambay.kv_token_layer_bytes(m) == 5120
    assert sambay.window_page_bytes(ModelConfig(**m), 64) == 8 * 64 * 8192
    assert costs_sambay.full_reads(m) == 8


def test_a_decode_step_by_hand():
    """40 rows of 1250 tokens: the weights once, nine states in and out, eight
    windows of 512, the full layer's KV eight times: ISSUE 54's 10.8 GB."""
    m = MODEL
    w = costs_sambay.weight_stream_bytes(m)
    assert w == 2 * (9 * 41_144_320 + 9 * 19_660_800 + 7 * 13_107_200 + 7 * 26_214_400
                     + 32 * 78_643_200 + 200064 * 2560)
    assert 7.70e9 < w < 7.71e9
    state = 40 * 9 * (2 * 327_680 + 30_720)
    windows = 8 * 40 * 512 * 5120
    full = 8 * 40 * 1250 * 5120
    assert costs_sambay.full_kv_step_bytes(m, 40 * 1250) == full == 2_048_000_000
    assert costs_sambay.window_kv_step_bytes(m, 40, 1250) == windows
    assert costs_sambay.window_kv_step_bytes(m, 40, 100) == 8 * 40 * 100 * 5120
    step = costs_sambay.decode_step_bytes(m, 40, 1250)
    assert step == w + state + windows + full
    assert 10.8e9 < step < 10.9e9 and 13.2e-3 < step / PEAK < 13.3e-3
    assert 0.18 < full / step < 0.20
    # a kernel call: the live pages, padded queries in (80 of 128) and out
    assert costs_sambay.decode_call_bytes(m, 800, 40, 64) == 800 * 64 * 5120 + 40 * 2 * 40 * 128 * 2


# -- the readers ------------------------------------------------------------


def _iter(**kw):
    base = {"decode_seqs": 40, "decode_steps": 4, "kv_usage": 0.2, "ragged": False,
            "n_chunks": 0, "chunk_tokens": 0, "state_slots_used": 40, "state_slots_total": 64,
            "ssm_scan_tokens": 0, "ssm_scan_segments": 0,
            "window_pages_used": 340, "window_pages_total": 680,
            "window_tokens_resident": 40 * 520, "context_tokens_live": 40 * 1250,
            # 40 rows of 1250 tokens: 20 pages of the full cache, 9 of a window
            "decode_pages_live_global": 4 * 40 * 20, "decode_pages_live_window": 4 * 40 * 9,
            "yoco_cross_rows": 160, "yoco_skipped_tokens": 0}
    return {**base, **kw}


def _ctx(iters, trace=None, model=MODEL, captures=None):
    return {"counters": {"iterations": iters, "trace": captures and {"captures": captures}},
            "model": model, "here": BENCH, "percentile": loadgen.percentile,
            "ready": {"device": {"kind": "TPU v5 lite"},
                      "engine": {"page_size": 64, "num_pages": 4096}},
            "trace": trace}


def _loop(durations_ms, steps, attention=True):
    """A decode-loop module: 8 window calls, 8 on the full cache and 9 state
    updates a step."""
    calls = lambda n: {**({"window_attention_decode": 8 * n, "decode_paged_attention": 8 * n}
                          if attention else {}), "ssm_update": 9 * n}
    return {"jit_decode_loop[ssm_update]": {
        "durations_ms": durations_ms, "kernels": [calls(n) for n in steps]}}


def test_a_step_is_sixteen_attention_calls_or_nine_updates():
    trace = {"modules": _loop([60.0, 64.0, 45.0, 15.5], [4, 4, 3, 1])}
    assert reader("sambay.decode_step_ms")(_ctx([_iter()], trace)) == pytest.approx(15.25)
    # a trace whose attention ran under no `attention` name: counted by the updates
    bare = {"modules": _loop([60.0, 64.0, 45.0, 15.5], [4, 4, 3, 1], attention=False)}
    assert reader("sambay.decode_step_ms")(_ctx([_iter()], bare)) == pytest.approx(15.25)
    # the accepted step readers divide by 32 layers, or by Jamba's period
    assert reader("runner.decode_step_ms")(_ctx([_iter()], {"modules": _loop([45.0], [3])})) is None
    assert reader("sambay.decode_step_ms")(_ctx([_iter()], trace, model=JAMBA)) is None
    assert reader("sambay.decode_step_ms")(_ctx([_iter()])) is None  # untraced
    assert reader("sambay.decode_step_ms")(_ctx([_iter()], {"modules": {}})) is None


def test_the_stream_share_is_the_steps_bytes_over_bandwidth_over_its_time():
    trace = {"modules": _loop([60.0, 60.0], [4, 4])}
    iters = [_iter(decode_seqs=36, decode_pages_live_global=4 * 36 * 20),
             _iter(decode_seqs=44, decode_pages_live_global=4 * 44 * 20),
             _iter(decode_seqs=0, decode_steps=0)]
    # 20 pages a row: a context of at least 19.5 pages
    need = costs_sambay.decode_step_bytes(MODEL, 40, 19.5 * 64)
    got = reader("sambay.decode_stream_pct")(_ctx(iters, trace))
    assert got == pytest.approx(100 * need / PEAK / 15e-3)
    assert 80 < got < 100
    assert reader("sambay.decode_stream_pct")(_ctx(iters)) is None
    assert reader("sambay.decode_stream_pct")(_ctx([], trace)) is None
    assert reader("sambay.decode_stream_pct")(_ctx(iters, trace, model=JAMBA)) is None


def test_the_shared_caches_share_and_the_prefill_saving_are_counters_alone():
    iters = [_iter(), _iter(ragged=True, n_chunks=1, chunk_tokens=256, yoco_skipped_tokens=255,
                            decode_pages_live_global=3 * 40 * 20),
             _iter(decode_seqs=0, decode_steps=0, n_chunks=1, chunk_tokens=512,
                   yoco_skipped_tokens=512, window_pages_total=680)]
    got = reader("yoco.cross_kv_reread_pct")(_ctx(iters))
    ctx_tokens = 19.5 * 64
    assert got == pytest.approx(100 * 8 * 40 * ctx_tokens * 5120
                                / costs_sambay.decode_step_bytes(MODEL, 40, ctx_tokens))
    assert 18 < got < 20
    assert reader("yoco.cross_kv_reread_pct")(_ctx(iters, model=JAMBA)) is None
    assert reader("yoco.prefill_skipped_pct")(_ctx(iters)) == pytest.approx(100 * 767 / 768)
    parent = [{k: v for k, v in i.items() if not k.startswith("yoco")} for i in iters]
    assert reader("yoco.prefill_skipped_pct")(_ctx(parent)) is None  # no such counter
    assert reader("yoco.prefill_skipped_pct")(_ctx([_iter()])) is None  # no chunk


def test_each_decode_kernels_share_is_its_pages_over_bandwidth_over_its_time():
    kernels = {"window_attention_decode": {"calls": 56, "total_s": 56 * 150e-6, "median_us": 150.0},
               "decode_paged_attention": {"calls": 56, "total_s": 56 * 400e-6, "median_us": 400.0},
               "yoco_cross_attention_rows": {"calls": 7, "total_s": 7 * 500e-6, "median_us": 500.0}}
    iters = [_iter(), _iter(ragged=True, n_chunks=1, decode_pages_live_global=3 * 40 * 20,
                            decode_pages_live_window=3 * 40 * 9)]
    trace = {"kernels": kernels}
    full = costs_sambay.decode_call_bytes(MODEL, 40 * 20, 40, 64)
    win = costs_sambay.decode_call_bytes(MODEL, 40 * 9, 40, 64)
    assert reader("kernels.sambay_full_decode_roofline_pct")(_ctx(iters, trace)) == \
        pytest.approx(100 * full / PEAK / 400e-6)
    assert reader("kernels.sambay_window_decode_roofline_pct")(_ctx(iters, trace)) == \
        pytest.approx(100 * win / PEAK / 150e-6)
    for name in ("kernels.sambay_full_decode_roofline_pct", "kernels.sambay_window_decode_roofline_pct"):
        assert 0 < reader(name)(_ctx(iters, trace)) < 100
        assert reader(name)(_ctx(iters)) is None
        assert reader(name)(_ctx(iters, trace, model=JAMBA)) is None
    # the accepted twins reckon MiMo's heads from a layer_pattern: nothing here
    assert reader("kernels.window_decode_roofline_pct")(_ctx(iters, trace)) is None
    assert reader("kernels.gqa_decode_roofline_pct")(_ctx(iters, trace)) is None


def test_the_reused_readers_count_this_models_calls_and_bytes_right():
    """The state kernels' cost functions hang on d_inner and d_state alone, the
    pool readers on the flight recorder's counters, the ragged share on the
    trace: each reads here what it reads on its own cell."""
    assert costs_ssm.ssm_update_call_bytes(MODEL, 40) == costs_ssm.ssm_update_call_bytes(JAMBA, 40)
    iters = [_iter(), _iter(ragged=True, n_chunks=1, chunk_tokens=256, ssm_scan_tokens=296,
                            ssm_scan_segments=41)]
    kernels = {"ssm_update": {"calls": 9 * 7, "total_s": 9 * 7 * 60e-6, "median_us": 60.0},
               "ssm_scan": {"calls": 9, "total_s": 9 * 900e-6, "median_us": 900.0},
               "ragged_paged_attention": {"calls": 1, "total_s": 0.01, "median_us": 1e4},
               "window_attention_ragged": {"calls": 8, "total_s": 0.02, "median_us": 2500.0}}
    trace = {"kernels": kernels, "busy_s": 0.5}
    ctx = _ctx(iters, trace)
    assert reader("kernels.ssm_update_roofline_pct")(ctx) == pytest.approx(
        100 * costs_ssm.ssm_update_call_bytes(MODEL, 40) / PEAK / 60e-6)
    assert reader("kernels.ssm_scan_roofline_pct")(ctx) == pytest.approx(
        100 * costs_ssm.ssm_scan_call_bytes(MODEL, 296, 40, 1) / PEAK / 900e-6)
    assert reader("kernels.ragged_attn_busy_pct")(ctx) == pytest.approx(100 * 0.03 / 0.5)
    assert reader("ssm.state_slots_used_pct")(ctx) == pytest.approx(62.5)
    assert reader("swa.window_pages_used_pct")(ctx) == pytest.approx(50.0)
    assert reader("swa.window_resident_pct")(ctx) == pytest.approx(100 * 520 / 1250)
    for name in REUSED[:2]:
        assert 0 < reader(name)(ctx) < 100


# -- the tree, the mix, the overlay, the entries ----------------------------


def test_make_params_draws_every_matrix_and_fills_every_vector():
    import serve

    c = ModelConfig(**{**MODEL, **rehearsal.rehearsal_sizes(CFG, BENCH)["model"]})
    assert c.layer_kinds == ("mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross")
    drawn = serve.drawn_leaves(c, jnp.float32)
    shapes = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0), jnp.float32))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    by_name = {jax.tree_util.keystr(p): d for (p, _), d in zip(leaves, drawn)}
    assert all(d == (len(a.shape) >= 2 and "A_log" not in jax.tree_util.keystr(p)
                     and not jax.tree_util.keystr(p).endswith(("_w']", "_b']", "['subln']",
                                                              "['b_conv']", "['b_dt']", "['D']",
                                                              "['bqkv']", "['bq']", "['bo']")))
               for (p, a), d in zip(leaves, drawn)), by_name
    params = serve.make_params(c, 7, jax.devices()[0], jnp.float32)
    lam = np.asarray(params["attn"]["lam"])
    assert lam.shape[-2:] == (c.head_dim, 4) and 0.5 < lam.std() * c.head_dim ** 0.5 < 1.5
    assert not np.asarray(params["attn"]["bqkv"]).any() and np.asarray(params["norm_f"]["w"]).all()


def test_the_mix_replays_the_first_typical_window_and_names_its_knee():
    import run
    from test_arithmetic import window_stats

    t = run.read_traffic("reasoning-steady-phi4flash")
    assert t["kind"] == "open_loop" and t["arrival"] == {"process": "poisson"}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64, "max": 4096}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.4, "min": 256, "max": 2048}
    assert (t["lead_in_s"], t["drain_limit_s"]) == (30, 60) and "knee" in t["_why"]
    seconds = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["run_seconds"]
    stats = [window_stats(t, seconds, s) for s in range(1, t["shape_seed"] + 1)]
    assert [s["typical"] for s in stats] == [False] * (t["shape_seed"] - 1) + [True], stats[-1]
    # the longest request fits the server's context
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] <= CFG["server_flags"]["max-seq-len"]


def test_the_configuration_is_the_catalogs_row_and_rehearses_every_kind():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CFG["source"] == row["source_url"] and CFG["reduced"] == []
    assert {k: CFG[k] for k in row["config"]} == row["config"]
    assert MODEL == {**{k: getattr(ModelConfig(**MODEL), k) for k in MODEL}}
    sizes = rehearsal.rehearsal_sizes(CFG, BENCH)
    c = ModelConfig(**sizes["model"])
    assert set(c.layer_kinds) == set(costs_sambay.KINDS) and c.n_layers == 8
    assert sizes["correct_tolerance"] == CFG["rehearse"]["correct_tolerance"]
    for key in ("head_dim", "mamba", "layer_order", "positions", "biases", "lambda",
                "weights", "state", "pools", "tokenizer", "readings"):
        assert key in CFG["assumed"], key


def test_the_benchmark_gained_one_configuration_one_cell_and_the_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "phi-4-mini-flash-reasoning"
    assert bench["configs"][-1]["reduced"] == []
    assert 1 <= len(bench["configs"][-1]["why"]) <= 200
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "phi-4-mini-flash-reasoning", "reasoning-steady-phi4flash", 1)
    assert len(cell["why"]) <= 200 and len(bench["workloads"]) == 7
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == NEW
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for name in REUSED:
        assert by_name[name]["workloads"][-1] == CELL
    moves = {m["name"] for m in bench["end_to_end"]}
    assert all(by_name[n]["moves"] in moves for n in NEW)
