"""The trace reduction on a hand-made trace, and on the small trace recorded
on the chip (benchmark/tests/data/small.xplane.pb; how it was made is in
benchmark/tests/make_small_trace.py)."""

import hashlib
import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert tr.union_length(iv) == 30
    assert tr.gaps_of(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps_of([], 0, 5) == [(0, 5)]


def test_reduce_hand_made_planes():
    ms = 1e6
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit__decode_loop(7)", 0, 40 * ms),
                                            ("jit__ragged_step(9)", 60 * ms, 20 * ms)]},
        {"name": "XLA Ops", "events": [
            ("%while.9 = (s32[]) while(x)", 0, 30 * ms),
            ("%fusion.1 = bf16[2] fusion(a)", 0, 10 * ms), ("%decode_paged_attention.2 = bf16[2] custom-call(a)", 10 * ms, 5 * ms),
            ("%fusion.1 = bf16[2] fusion(a)", 15 * ms, 10 * ms), ("%decode_paged_attention.2 = bf16[2] custom-call(a)", 25 * ms, 5 * ms),
            ("%fusion.3 = bf16[2] fusion(a)", 60 * ms, 20 * ms)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "step", "events": [
        ("engine.decode", 0, 45 * ms), ("engine.mixed", 45 * ms, 50 * ms), ("other", 0, 1)]}]}
    r = tr.merge([tr.reduce_planes([host, dev])])
    assert r["window_s"] == pytest.approx(0.080)
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["kernel_s"] == pytest.approx(0.010)
    m = r["modules"]["jit__decode_loop[decode_paged_attention]"]
    assert m["kernel_calls"] == [2] and m["median_ms"] == pytest.approx(40.0)
    assert "jit__ragged_step[-]" in r["modules"]
    # self time: the while event spans its four children and keeps nothing
    ops = dict(r["device_ops"])
    assert ops["jit__decode_loop[decode_paged_attention]/fusion.1"] == pytest.approx(0.020)
    assert ops["jit__decode_loop[decode_paged_attention]/while.9"] == pytest.approx(0.0)
    # the one gap, 30..60 ms, has its midpoint inside engine.mixed
    assert r["idle_gaps"] == [["engine.mixed", pytest.approx(0.030)]]
    # every execution keeps the calls of each kernel by name; device time per
    # kernel adds up to kernel_s; the program's spans by name
    assert m["kernels"] == [{"decode_paged_attention": 2}]
    assert r["modules"]["jit__ragged_step[-]"]["kernels"] == [{}]
    assert r["kernels"] == {"decode_paged_attention": {
        "calls": 2, "total_s": pytest.approx(0.010), "median_us": pytest.approx(5000.0)}}
    assert r["host_spans"] == {"engine.decode": {"n": 1, "total_s": pytest.approx(0.045)},
                               "engine.mixed": {"n": 1, "total_s": pytest.approx(0.050)}}


def test_a_module_is_labelled_by_its_top_kernel_and_keeps_the_others():
    """Two layers of a latent-attention expert model, two steps: the expert
    kernel runs twice a layer and labels the module; the attention kernel's
    calls are still there for whoever counts steps. Two chips: seconds are
    averaged over them, calls and durations are over both."""
    us = 1e3
    ops, t = [], 0
    for _ in range(2 * 2):  # steps x layers
        for name, d in (("%decode_mla_attention.3 = bf16[2] custom-call(a)", 30 * us),
                        ("%grouped_experts.5 = bf16[2] custom-call(a)", 20 * us),
                        ("%fusion.9 = bf16[2] fusion(a)", 10 * us),
                        ("%grouped_experts.7 = bf16[2] custom-call(a)", 20 * us)):
            ops.append((name, t, d))
            t += d
    planes = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [("jit_decode_loop(3)", 0, t)]},
        {"name": "XLA Ops", "events": ops}]} for i in range(2)]
    r = tr.merge([tr.reduce_planes(planes)])
    m = r["modules"]["jit_decode_loop[grouped_experts]"]
    assert m["n"] == 2 and m["kernel_calls"] == [8, 8]
    assert m["kernels"] == [{"decode_mla_attention": 4, "grouped_experts": 8}] * 2
    assert r["kernels"]["decode_mla_attention"] == {
        "calls": 8, "total_s": pytest.approx(4 * 30e-6), "median_us": pytest.approx(30.0)}
    assert r["kernels"]["grouped_experts"]["calls"] == 16
    assert sum(k["total_s"] for k in r["kernels"].values()) == pytest.approx(r["kernel_s"])


def test_gap_without_host_span_is_unattributed():
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ("a", 0, 10), ("a", 50, 10)]}]}
    r = tr.merge([tr.reduce_planes([dev])])
    assert r["idle_gaps"] == [["unattributed", pytest.approx(40e-9)]]


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace():
    r = tr.reduce_file(DATA)
    assert r["n_devices"] >= 1 and 0 < r["busy_s"] <= r["window_s"]
    assert any("small_step" in m for m in r["modules"])
    assert r["device_ops"] and r["kernel_s"] >= 0
    # every key the reduction had before it kept per-kernel tables has the
    # value it had (sha256 of the parent commit's reduction of this file)
    had = ("busy_s", "captures", "device_ops", "idle_gaps", "kernel_s", "modules", "n_devices",
           "per_capture", "planes", "window_s")
    old = {k: r[k] for k in had}
    old["modules"] = {n: {k: m[k] for k in ("n", "total_s", "median_ms", "durations_ms", "kernel_calls")}
                      for n, m in r["modules"].items()}
    assert hashlib.sha256(json.dumps(old, sort_keys=True).encode()).hexdigest() == \
        "e5b9c916711561e66f91350da4b34fdf4b370976bcde63dd2b8e71f2c3b9c003"
    # the new tables: no custom call in this trace, one span
    assert r["kernels"] == {} and sum(k["total_s"] for k in r["kernels"].values()) == r["kernel_s"]
    assert r["host_spans"] == {"engine.decode": {"n": 1, "total_s": pytest.approx(0.00144173)}}
    assert all(len(m["kernels"]) == m["n"] for m in r["modules"].values())
