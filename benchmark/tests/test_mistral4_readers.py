"""What PR 33 added to the yardstick: costs_mla.py's arithmetic, the four
readers on hand-made contexts and on the recorded fixture trace, what they
return for a program that has no such counter or kernel (None: the line leaves
the metric out, and nothing raises), and the BENCHMARK.json entries."""

import importlib.util
import json
import os

import pytest

import costs_mla
import loadgen
import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data", "small.xplane.pb")
CELL = "mistral4-chat-steady"
NEW = ["moe.experts_hit_mean", "moe.load_max_share", "moe.held_slot_pct",
       "kernels.mla_decode_roofline_pct"]
with open(os.path.join(BENCH, "configs", "mistral-small-4-119b.json")) as _f:
    MODEL = json.load(_f)["model"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_a_latent_page_is_reckoned_as_the_device_lays_it_out():
    assert costs_mla.latent_width(MODEL) == 320
    # 64 rows x 320 -> 384 lanes x 2 bytes: 1.2 x the shapes' 40960
    assert costs_mla.latent_page_bytes(MODEL, 64) == 64 * 384 * 2 == 49152
    assert costs_mla.latent_page_bytes(MODEL, 8) == 16 * 384 * 2  # bf16 rows pad to 16
    assert costs_mla.latent_page_bytes(MODEL, 64, "int8") == 64 * 384
    v3 = {"kv_lora_rank": 512, "qk_rope_head_dim": 64, "n_heads": 128}
    assert costs_mla.latent_page_bytes(v3, 64) == 64 * 640 * 2


def test_a_decode_call_moves_its_pages_its_query_and_its_output():
    one = costs_mla.decode_call_bytes(MODEL, live_pages=1, rows=1, page_size=64)
    assert one == 49152 + 32 * (320 + 256) * 2
    assert costs_mla.decode_call_bytes(MODEL, 72.5, 10.5, 64) == pytest.approx(
        72.5 * 49152 + 10.5 * 32 * 576 * 2)


def _iter(**kw):
    base = {"decode_seqs": 8, "decode_steps": 4, "decode_pages_live": 8 * 4 * 6, "kv_usage": 0.2,
            "moe_token_slots": 128, "moe_experts_hit": 7.5, "moe_load_max_share": 0.2,
            "moe_held_slots": 30.0}
    return {**base, **kw}


def _ctx(iters, kernels=None):
    return {"counters": {"iterations": iters}, "model": MODEL, "here": BENCH,
            "percentile": loadgen.percentile,
            "ready": {"device": {"kind": "TPU v5 lite"}, "engine": {"page_size": 64, "num_pages": 384}},
            "trace": None if kernels is None else {"kernels": kernels}}


def test_the_counter_readers_on_hand_made_iterations():
    ctx = _ctx([_iter(), _iter(moe_experts_hit=8.5, moe_load_max_share=0.1, moe_held_slots=34.0),
                # a prefill alone: routed tokens, no decode row
                _iter(decode_seqs=0, decode_steps=0, decode_pages_live=0, moe_token_slots=1024,
                      moe_experts_hit=31.0, moe_load_max_share=0.05, moe_held_slots=250.0)])
    assert reader("moe.experts_hit_mean")(ctx) == pytest.approx(8.0)  # decode iterations only
    assert reader("moe.load_max_share")(ctx) == pytest.approx((0.2 + 0.1 + 0.05) / 3)
    assert reader("moe.held_slot_pct")(ctx) == pytest.approx(100 * 314 / 1280)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_or_the_kernel_reads_nothing(name):
    """The parent commit's records have no `moe_held_slots`, a dense model's
    have zeros, a phi-3 trace has no latent kernel: None, and no exception."""
    dense = {"decode_seqs": 8, "decode_steps": 4, "decode_pages_live": 100, "kv_usage": 0.2,
             "moe_token_slots": 0, "moe_experts_hit": 0.0, "moe_load_max_share": 0.0}
    assert reader(name)(_ctx([dense], kernels={"decode_paged_attention": {
        "calls": 10, "total_s": 1e-3, "median_us": 100.0}})) is None
    assert reader(name)(_ctx([])) is None
    if name == "moe.held_slot_pct":
        parent = _iter()
        del parent["moe_held_slots"]
        assert reader(name)(_ctx([parent])) is None


def test_the_roofline_share_is_bytes_over_bandwidth_over_the_time_a_call():
    iters = [_iter(), _iter(decode_seqs=12, decode_pages_live=12 * 4 * 9)]
    pages = (8 * 4 * 6 + 12 * 4 * 9) / 8  # a call: one step of one layer
    rows = (8 * 4 + 12 * 4) / 8
    need = costs_mla.decode_call_bytes(MODEL, pages, rows, 64)
    kernels = {"decode_mla_attention": {"calls": 600, "total_s": 600 * 250e-6, "median_us": 250.0},
               "prefill_mla_attention": {"calls": 30, "total_s": 0.03, "median_us": 1000.0}}
    got = reader("kernels.mla_decode_roofline_pct")(_ctx(iters, kernels))
    assert got == pytest.approx(100 * need / 819e9 / 250e-6)
    assert 0 < got < 100
    assert reader("kernels.mla_decode_roofline_pct")(_ctx(iters, {})) is None
    assert reader("kernels.mla_decode_roofline_pct")(_ctx(iters)) is None  # an untraced run


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_on_the_recorded_trace_there_is_no_latent_kernel_to_read():
    ctx = _ctx([_iter()])
    ctx["trace"] = tr.reduce_file(DATA)
    assert reader("kernels.mla_decode_roofline_pct")(ctx) is None
    assert reader("moe.experts_hit_mean")(ctx) == 7.5  # the counters need no trace


def test_the_benchmark_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "mistral-small-4-119b" and len(cell["why"]) <= 200
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg["file"])) as f:
        assert sorted(json.load(f)["reduced"]) == sorted(cfg["reduced"])
    assert os.path.exists(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:  # by name: later cells append themselves to a list, later PRs to the table
        assert CELL in per[name]["workloads"] and per[name]["moves"] == "tpot_p95_ms"
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    assert per["kernels.mla_decode_roofline_pct"]["workloads"] == [CELL]  # the one latent cell
    # the metrics every cell's line carries are not narrowed to some cells
    for name in ("client.ttft_mean_ms", "engine.decode_batch_mean", "kernels.attn_busy_pct",
                 "device.idle_pct", "runner.compiles_in_window", "sched.preempted_pct"):
        assert "workloads" not in per[name]
