"""The trace reduction on a hand-made trace, and on the small trace recorded
on the chip (benchmark/tests/data/small.xplane.pb; how it was made is in
benchmark/tests/make_small_trace.py)."""

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
