"""What PR 45 added to the yardstick: one reader, `kernels.dsa_select_busy_pct`,
on hand-made trace tables, what it returns for a program that has no such
kernel (the parent: None, and nothing raises), that `kernels.attn_busy_pct`
does not count the select kernel, and the BENCHMARK.json entry."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "dsv32-docqa-steady"
NAME = "kernels.dsa_select_busy_pct"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(kernels, busy_s=2.9):
    return {"trace": None if kernels is None else {"kernels": kernels, "busy_s": busy_s}}


ATTN = {"decode_mla_attention.9": {"calls": 1400, "total_s": 0.085, "median_us": 60.0}}
SELECT = {"dsa_select.3": {"calls": 1100, "total_s": 0.050, "median_us": 46.0},
          "dsa_select.4": {"calls": 280, "total_s": 0.0125, "median_us": 45.0}}


def test_the_select_kernels_share_of_the_busy_time():
    assert reader(NAME)(_ctx({**ATTN, **SELECT})) == pytest.approx(100 * 0.0625 / 2.9)
    # and the attention kernels' share leaves it out
    assert reader("kernels.attn_busy_pct")(_ctx({**ATTN, **SELECT})) == pytest.approx(
        100 * 0.085 / 2.9)


@pytest.mark.parametrize("ctx", [
    _ctx(ATTN),  # the parent: a sort, no such kernel
    _ctx({}), _ctx(None), {},  # nothing traced
    _ctx(SELECT, busy_s=0.0),
], ids=["parent", "no-kernels", "untraced", "empty", "no-busy-time"])
def test_a_trace_without_the_kernel_reads_nothing(ctx):
    assert reader(NAME)(ctx) is None


def test_the_benchmark_lists_the_metric_on_its_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert m == {"name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
                 "layer": "Pallas kernels", "moves": "tpot_p95_ms", "workloads": [CELL]}
    assert os.path.exists(os.path.join(BENCH, "layers", NAME + ".py"))
