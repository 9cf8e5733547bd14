"""What PR 46 added to the yardstick: one reader, `kernels.ragged_attn_busy_pct`,
on hand-made trace tables: both of the ragged kernel's names counted, the
expert kernel and the decode kernels not, None where nothing ragged was traced
(and nothing raises), and the BENCHMARK.json entry."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELLS = ["phi3-chat-steady", "jamba2-reasoning-steady", "mimo2-agent-steady"]
NAME = "kernels.ragged_attn_busy_pct"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(kernels, busy_s=2.23):
    return {"trace": None if kernels is None else {"kernels": kernels, "busy_s": busy_s}}


def k(calls, total_s):
    return {"calls": calls, "total_s": total_s, "median_us": 1e6 * total_s / calls}


# mimo2-agent-steady's table by the ledger's PR 45 line: two global calls and
# nine window calls a ragged step under two names, beside what must not count
RAGGED = {"ragged_paged_attention": k(25, 0.174), "ragged_paged_attention.1": k(25, 0.174),
          "window_attention_ragged.8": k(100, 0.117), "window_attention_ragged.9": k(125, 0.142)}
OTHERS = {"routed_experts.35": k(2000, 0.170), "decode_paged_attention.3": k(1600, 0.040),
          "window_attention_decode.2": k(7200, 0.030), "prefill_paged_attention": k(3, 0.002)}


def test_both_names_of_the_ragged_kernel_count_and_nothing_else():
    assert reader(NAME)(_ctx({**RAGGED, **OTHERS})) == pytest.approx(100 * 0.607 / 2.23)
    # the attention kernels' share holds it and the decode and prefill kernels
    assert reader("kernels.attn_busy_pct")(_ctx({**RAGGED, **OTHERS})) == pytest.approx(
        100 * (0.607 + 0.040 + 0.030 + 0.002) / 2.23)


@pytest.mark.parametrize("kernels", [
    {"ragged_paged_attention.1": k(25, 0.174)}, {"window_attention_ragged.9": k(125, 0.142)},
], ids=["global", "window"])
def test_one_name_alone_reads(kernels):
    (only,) = kernels.values()
    assert reader(NAME)(_ctx(kernels)) == pytest.approx(100 * only["total_s"] / 2.23)


@pytest.mark.parametrize("ctx", [
    _ctx(OTHERS),  # a cell whose traffic bypasses the ragged program
    _ctx({}), _ctx(None), {},  # nothing traced
    _ctx(RAGGED, busy_s=0.0),
], ids=["no-ragged-call", "no-kernels", "untraced", "empty", "no-busy-time"])
def test_a_trace_without_the_kernel_reads_nothing(ctx):
    assert reader(NAME)(ctx) is None


def test_the_benchmark_lists_the_metric_on_the_ragged_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [e for e in bench["per_layer"] if e["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "Pallas kernels", "moves": "tpot_p95_ms", "workloads": CELLS}
    assert os.path.exists(os.path.join(BENCH, "layers", NAME + ".py"))
