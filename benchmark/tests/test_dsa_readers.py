"""What PR 44 added to the yardstick: costs_dsa.py's arithmetic, the three
readers on hand-made contexts, what they return for a program that has no such
counter or kernel and on another cell's context (None: the line leaves the
metric out, and nothing raises), the generator of questions on shared
documents, and the BENCHMARK.json entries."""

import importlib.util
import json
import os

import numpy as np
import pytest

import costs
import costs_dsa
import costs_mla
import loadgen
from generators import shared_docs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "dsv32-docqa-steady"
NEW = ["dsa.selected_pct", "dsa.decode_stream_pct", "kernels.sparse_mla_decode_roofline_pct"]
with open(os.path.join(BENCH, "configs", "deepseek-v3.2.json")) as _f:
    CFG = json.load(_f)
MODEL = CFG["model"]
with open(os.path.join(BENCH, "configs", "mistral-small-4-119b.json")) as _f:
    MISTRAL = json.load(_f)["model"]
with open(os.path.join(BENCH, "traffic", "docqa-steady-dsv32.json")) as _f:
    TRAFFIC = json.load(_f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- costs_dsa ---------------------------------------------------------------


def test_pages_and_rows_are_reckoned_as_the_device_lays_them_out():
    assert costs_dsa.index_page_bytes(MODEL, 64) == 64 * 128 * 2 == 16384
    assert costs_dsa.index_page_bytes(MODEL, 8) == 16 * 128 * 2  # bf16 rows pad to 16
    assert costs_dsa.latent_row_bytes(MODEL) == 640 * 2  # 576 wide in 640 lanes
    assert costs_dsa.latent_row_bytes(MODEL) * 64 == costs_mla.latent_page_bytes(MODEL, 64)
    assert costs_dsa.indexer_params(MODEL) == 1536 * 64 * 128 + 7168 * (128 + 64) == 13_959_168


def test_the_weights_of_a_step_are_costs_pys_and_the_indexers():
    base = costs.weight_stream_bytes(MODEL, experts_hit=2.5)
    assert costs_dsa.weight_stream_bytes(MODEL, 2.5) == base + 5 * 13_959_168 * 2
    # attention 187.11 M a layer, a dense layer, four expert layers at 2.5
    # experts of 44.04 M beside a router and a shared expert, the head's slice
    assert costs.attn_params(MODEL) == 187_105_280
    assert base == 2 * (5 * 187_105_280 + 3 * 7168 * 18432
                        + 4 * (7168 * 256 + 3 * 7168 * 2048 + 2.5 * 3 * 7168 * 2048)
                        + 7168 * 16160)


def test_a_step_reads_its_live_index_keys_and_its_selected_rows_in_every_layer():
    w = costs_dsa.weight_stream_bytes(MODEL, 3.0)
    got = costs_dsa.decode_step_bytes(MODEL, 3.0, ctx_pages=12 * 400, sel_tokens=12 * 2048,
                                      page_size=64)
    assert got == w + 5 * (12 * 400 * 16384 + 12 * 2048 * 1280)
    one = costs_dsa.sparse_decode_call_bytes(MODEL, sel_tokens=12 * 2048, rows=12)
    assert one == 12 * 2048 * 1280 + 12 * 128 * (576 + 512) * 2


# -- the readers -------------------------------------------------------------


def _iter(**kw):
    base = {"decode_seqs": 12, "decode_steps": 4, "decode_pages_live": 12 * 4 * 400,
            "dsa_ctx_tokens": 12 * 4 * 25600, "dsa_sel_tokens": 12 * 4 * 2048, "kv_usage": 0.7,
            "moe_token_slots": 384, "moe_experts_hit": 2.5, "moe_load_max_share": 0.2,
            "moe_held_slots": 12.0}
    return {**base, **kw}


def _ctx(iters, kernels=None, step_ms=None, model=MODEL):
    trace = None
    if kernels is not None or step_ms is not None:
        layers = int(model["n_layers"])
        trace = {"kernels": kernels or {}, "modules": {} if step_ms is None else {
            "jit_decode_loop[decode_mla_attention]": {
                "durations_ms": [4 * step_ms], "kernels": [{"decode_mla_attention": 4 * layers}]}}}
    return {"counters": {"iterations": iters}, "model": model, "here": BENCH,
            "percentile": loadgen.percentile,
            "ready": {"device": {"kind": "TPU v5 lite"},
                      "engine": {"page_size": 64, "num_pages": 4096}},
            "trace": trace}


def test_the_selected_share_is_selected_over_scored():
    ctx = _ctx([_iter(), _iter(dsa_ctx_tokens=4 * 1000, dsa_sel_tokens=4 * 1000, decode_seqs=1),
                _iter(decode_seqs=0, decode_steps=0, dsa_ctx_tokens=0, dsa_sel_tokens=0)])
    want = 100 * (12 * 4 * 2048 + 4000) / (12 * 4 * 25600 + 4000)
    assert reader("dsa.selected_pct")(ctx) == pytest.approx(want)
    assert want < 15


def test_the_stream_share_is_bytes_over_bandwidth_over_the_step():
    need = costs_dsa.decode_step_bytes(MODEL, 2.5, 12 * 400, 12 * 2048, 64)
    got = reader("dsa.decode_stream_pct")(_ctx([_iter(), _iter()], step_ms=12.0))
    assert got == pytest.approx(100 * need / 819e9 / 12e-3)
    assert 0 < got < 100
    assert reader("dsa.decode_stream_pct")(_ctx([_iter()])) is None  # an untraced run


def test_the_roofline_share_of_the_call_over_the_selected_rows():
    kernels = {"decode_mla_attention": {"calls": 400, "total_s": 400 * 300e-6, "median_us": 300.0},
               "prefill_mla_attention": {"calls": 3, "total_s": 0.03, "median_us": 1000.0}}
    need = costs_dsa.sparse_decode_call_bytes(MODEL, 12 * 2048, 12)
    got = reader("kernels.sparse_mla_decode_roofline_pct")(_ctx([_iter(), _iter()], kernels))
    assert got == pytest.approx(100 * need / 819e9 / 300e-6)
    assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_or_another_cells_context_reads_nothing(name):
    """The parent's records have no `dsa_*` counter, mistral4-chat-steady's
    model has no `index_topk`: None, and no exception, traced or not."""
    parent = {k: v for k, v in _iter().items() if not k.startswith("dsa_")}
    kernels = {"decode_mla_attention": {"calls": 600, "total_s": 0.15, "median_us": 250.0}}
    for ctx in (_ctx([parent], kernels, step_ms=3.0, model=MISTRAL),
                _ctx([parent], kernels, step_ms=3.0), _ctx([parent]), _ctx([]),
                _ctx([_iter(dsa_ctx_tokens=0, dsa_sel_tokens=0)], kernels, step_ms=3.0)):
        assert reader(name)(ctx) is None


# -- the generator -----------------------------------------------------------


def _chains(seed, seconds=50, **over):
    return shared_docs.generate({**TRAFFIC, **over}, seed, seconds, 1000)["chains"]


def test_every_documents_opener_is_due_before_any_question_on_it():
    chains = _chains(7)
    n = TRAFFIC["documents"]
    openers, questions = chains[:n], chains[n:]
    assert [c["due_s"] for c in openers] == [i * TRAFFIC["opener_gap_s"] for i in range(n)]
    docs = [tuple(c["prefix_ids"]) for c in openers]
    spec = TRAFFIC["document_tokens"]
    assert all(spec["min"] <= len(d) <= spec["max"] for d in docs) and len(set(docs)) == n
    for c in openers:
        assert len(c["turns"]) == 1 and c["turns"][0]["max_tokens"] == 2
        assert len(c["turns"][0]["user_ids"]) == 16
    assert questions and min(q["due_s"] for q in questions) >= TRAFFIC["questions_from_s"]
    assert TRAFFIC["questions_from_s"] == TRAFFIC["lead_in_s"] - 20 > max(c["due_s"] for c in openers)
    for q in questions:
        assert tuple(q["prefix_ids"]) in docs  # a whole document, so its pages are shared
        t, = q["turns"]
        assert TRAFFIC["question_tokens"]["min"] <= len(t["user_ids"]) <= TRAFFIC["question_tokens"]["max"]
        assert TRAFFIC["output_tokens"]["min"] <= t["max_tokens"] <= TRAFFIC["output_tokens"]["max"]
    in_window = [q for q in questions if q["due_s"] >= TRAFFIC["lead_in_s"]]
    assert len(in_window) == round(TRAFFIC["rate_rps"] * 50)
    assert max(q["due_s"] for q in questions) < TRAFFIC["lead_in_s"] + 50
    used = {tuple(q["prefix_ids"]) for q in in_window}
    assert len(used) == n  # every document is asked about in the window


def test_every_seed_replays_the_same_due_times_and_sizes():
    a, b = _chains(1), _chains(2**31 + 5)
    assert [c["due_s"] for c in a] == [c["due_s"] for c in b]
    assert [len(c["prefix_ids"]) for c in a] == [len(c["prefix_ids"]) for c in b]
    n = TRAFFIC["documents"]
    for key in (lambda c: len(c["turns"][0]["user_ids"]), lambda c: c["turns"][0]["max_tokens"]):
        la, lb = [key(c) for c in a[n:]], [key(c) for c in b[n:]]
        assert la != lb and sorted(la) == sorted(lb)  # sizes move between neighbours only
    assert a == _chains(1) and a[0]["prefix_ids"] != b[0]["prefix_ids"]
    assert [c["due_s"] for c in _chains(1, shape_seed=TRAFFIC["shape_seed"] + 1)] != \
        [c["due_s"] for c in a]
    # a rehearsal's divisor cuts the documents, not their number
    small = shared_docs.generate(TRAFFIC, 1, 6, 512, 16)["chains"]
    assert all(1024 <= len(c["prefix_ids"]) <= 2048 for c in small)
    assert len({tuple(c["prefix_ids"]) for c in small}) == n


def _question_stats(shape_seed, seconds=50):
    lead = float(TRAFFIC["lead_in_s"])
    window = [c for c in _chains(0, seconds, shape_seed=shape_seed)[TRAFFIC["documents"]:]
              if c["due_s"] >= lead]
    counts = np.bincount([int((c["due_s"] - lead) // 5.0) for c in window], minlength=10).tolist()
    n, total = len(counts), sum(counts)
    num, den = n * sum(c * c for c in counts) - total * total, n * total
    mean = lambda spec: __import__("test_arithmetic")._clipped_mean(spec)
    q_off = np.mean([len(c["turns"][0]["user_ids"]) for c in window]) / mean(TRAFFIC["question_tokens"]) - 1
    o_off = np.mean([c["turns"][0]["max_tokens"] for c in window]) / mean(TRAFFIC["output_tokens"]) - 1
    return 4 * den <= 5 * num <= 6 * den and abs(q_off) <= 0.05 and abs(o_off) <= 0.03


def test_the_mix_replays_the_first_typical_window_of_questions():
    """chat-steady.json's rule over the questions of the window: arrivals per
    5 s with variance / mean in 0.8-1.2, mean question within 5 % and mean
    answer within 3 % of the clipped log-normal's."""
    seeds = range(1, TRAFFIC["shape_seed"] + 1)
    assert [_question_stats(s) for s in seeds] == [False] * (TRAFFIC["shape_seed"] - 1) + [True]


# -- the entries -------------------------------------------------------------


def test_the_benchmark_lists_the_cell_its_configuration_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # every entry by name: a later PR appends to these lists and breaks nothing
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["config"] == "deepseek-v3.2" and cell["traffic"] == "docqa-steady-dsv32"
    cfg = next(c for c in bench["configs"] if c["name"] == "deepseek-v3.2")
    assert cfg["source"] == CFG["source"] and len(cfg["why"]) <= 200
    assert sorted(cfg["reduced"]) == sorted(CFG["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per[name]["workloads"] == [CELL] and per[name]["moves"] == "tpot_p95_ms"
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    for name in ("moe.experts_hit_mean", "moe.load_max_share", "moe.held_slot_pct",
                 "runner.decode_step_ms"):
        assert CELL in per[name]["workloads"]
    # the dense latent kernel's roofline reads whole contexts: not this cell's
    assert CELL not in per["kernels.mla_decode_roofline_pct"]["workloads"]
    assert CELL not in per["model.decode_stream_pct"]["workloads"]


def test_the_configuration_file_keeps_every_catalog_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3.2")
    assert CFG["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if CFG.get(k) != v)
    assert differs == sorted(CFG["reduced"])
    assert CFG["published"] == {k: row["config"][k] for k in CFG["reduced"]}
    for key in ("indexer", "rotary_pairs", "selection", "mtp", "weights", "tokenizer", "readings"):
        assert key in CFG["assumed"], key
    assert "32 v5e chips" in CFG["deployment"]
    flags = CFG["server_flags"]
    assert flags["mixed-prefill-tokens"] == 1024  # the check's long sample: 3 x 1024 + 17
    assert 3 * flags["mixed-prefill-tokens"] + 17 > MODEL["index_topk"]
    assert CFG["rehearse"]["server_flags"]["mixed-prefill-tokens"] * 3 + 17 > \
        CFG["rehearse"]["model"]["index_topk"]
