"""What PR 38 added to the yardstick: the nine readers of the step loop's host
clock and `_host.undisturbed` on hand-made contexts (a capture's disturbed
seconds left out, None on records without the fields so that the line leaves
the metric out and nothing raises, the percentages on a worked example), and
the BENCHMARK.json entries."""

import importlib.util
import json
import os

import pytest

import _host
import loadgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NEW = ["engine.run_ahead_pct", "engine.exposed_host_pct", "engine.exposed_stage_pct",
       "engine.exposed_emit_pct", "engine.readback_wait_pct", "engine.host_stage_ms",
       "engine.host_emit_ms", "sched.drain_wait_mean_ms", "engine.stall_iters"]
PHASES = ("inbox", "schedule", "prep", "stage", "dispatch", "readback", "emit", "publish")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _it(ts, wall_s=0.050, kind="decode", ahead=True, **host):
    """One iteration as counters.json carries it: the phases default to a
    run-ahead decode iteration that is all readback but 10 ms."""
    rec = {"ts": ts, "wall_s": wall_s, "kind": kind, "ahead": ahead,
           "drain": "" if ahead else "rows"}
    rec.update({f"host_{p}_s": 0.0 for p in PHASES})
    rec.update(host_stage_s=0.004, host_emit_s=0.003, host_readback_s=wall_s - 0.010,
               exposed_s=0.0, exposed_stage_s=0.0, exposed_emit_s=0.0,
               gc_s=0.0)
    rec.update(host)
    return rec


def _ctx(its, captures=None, phases=None):
    return {"counters": {"iterations": its,
                         "trace": {"captures": captures} if captures is not None else None},
            "final": {"phases": phases or []}, "w0_wall": 100.0, "w1_wall": 150.0,
            "percentile": loadgen.percentile}


CAPTURE = {"start_wall": 112.0, "stop_wall": 113.0, "written_s": 0.8}


def test_undisturbed_leaves_out_a_capture_and_what_follows_its_stop():
    its = [_it(111.9), _it(112.0), _it(112.5), _it(113.9), _it(114.8), _it(114.81)]
    kept = _host.undisturbed(_ctx(its, [CAPTURE]))
    # the capture's own second, the 0.8 s its file took and the second after
    assert [i["ts"] for i in kept] == [111.9, 114.81]
    # a run that took no capture (untraced, or `trace` null) keeps them all
    assert _host.undisturbed(_ctx(its, [])) == its
    assert _host.undisturbed(_ctx(its)) == its
    # two captures: outside both
    second = {"start_wall": 120.0, "stop_wall": 121.0, "written_s": 0.5}
    its2 = its + [_it(119.0), _it(120.5), _it(122.4), _it(122.6)]
    assert [i["ts"] for i in _host.undisturbed(_ctx(its2, [CAPTURE, second]))] == [
        111.9, 114.81, 119.0, 122.6]


def test_the_percentages_on_a_worked_example():
    """Ten iterations of 50 ms: eight run ahead (nothing exposed), two drained
    for a joiner (their stage and emit exposed); one more sits in a capture and
    is twenty times as long, so no share may count it."""
    drained = dict(ahead=False, kind="mixed", exposed_s=0.012, exposed_stage_s=0.006,
                   exposed_emit_s=0.004, host_stage_s=0.006, host_emit_s=0.004,
                   host_readback_s=0.030)
    its = [_it(101.0 + k) for k in range(8)] + [_it(110.0, **drained), _it(111.0, **drained)]
    its.append(_it(112.5, wall_s=1.0, exposed_s=0.9, host_stage_s=0.5))
    ctx = _ctx(its, [CAPTURE])
    wall = 10 * 0.050
    assert reader("engine.exposed_host_pct")(ctx) == pytest.approx(100 * 0.024 / wall)
    assert reader("engine.exposed_stage_pct")(ctx) == pytest.approx(100 * 0.012 / wall)
    assert reader("engine.exposed_emit_pct")(ctx) == pytest.approx(100 * 0.008 / wall)
    assert reader("engine.readback_wait_pct")(ctx) == pytest.approx(
        100 * (8 * 0.040 + 2 * 0.030) / wall)
    assert reader("engine.host_stage_ms")(ctx) == pytest.approx((8 * 4.0 + 2 * 6.0) / 10)
    assert reader("engine.host_emit_ms")(ctx) == pytest.approx((8 * 3.0 + 2 * 4.0) / 10)
    # exposed and readback are parts of one wall
    assert reader("engine.exposed_host_pct")(ctx) + reader("engine.readback_wait_pct")(ctx) <= 100
    # run-ahead counts decode iterations of the WHOLE window, the captured one too
    assert reader("engine.run_ahead_pct")(ctx) == pytest.approx(100.0)
    its[0]["ahead"] = False
    assert reader("engine.run_ahead_pct")(ctx) == pytest.approx(100 * 8 / 9)


def test_stall_iters_counts_over_ten_medians_of_the_kind_outside_captures():
    its = [_it(101.0 + 0.1 * k) for k in range(20)]
    its += [_it(104.0 + k, kind="mixed", wall_s=0.200, ahead=False) for k in range(5)]
    ctx = _ctx(its, [CAPTURE])
    assert reader("engine.stall_iters")(ctx) == 0.0
    its.append(_it(105.5, wall_s=0.49))                 # under ten decode medians
    its.append(_it(106.5, wall_s=0.51))                 # over
    its.append(_it(107.5, kind="mixed", wall_s=1.9, ahead=False))  # under ten mixed medians
    its.append(_it(112.2, wall_s=3.0))                  # inside the capture: the profiler's
    assert reader("engine.stall_iters")(ctx) == 1.0


def test_drain_wait_is_a_mean_over_all_the_windows_requests():
    def ph(wall, drain, e2e=1.0):
        return {"wall": wall, "e2e_s": e2e, "ttft_s": 0.2, "prefill_s": 0.15,
                "drain_wait_s": drain}
    phases = [ph(120.0, 0.0), ph(121.0, 0.030), ph(122.0, 0.0), ph(123.0, 0.050),
              ph(100.5, 0.9),   # arrived before the window
              ph(151.5, 0.9, e2e=1.0)]  # and after it
    ctx = _ctx([_it(101.0)], [], phases)
    assert reader("sched.drain_wait_mean_ms")(ctx) == pytest.approx(80.0 / 4)


def test_a_program_without_the_fields_reads_none_and_nothing_raises():
    """The parent of PR 38: records with `ahead` and `wall_s` and none of the
    host clock's fields, a spine without `drain_wait_s`."""
    old = [{"ts": 101.0 + k, "wall_s": 0.05, "kind": "decode", "ahead": True, "drain": "",
            "decode_seqs": 8} for k in range(6)]
    phases = [{"wall": 120.0, "e2e_s": 1.0, "ttft_s": 0.2, "prefill_s": 0.15}]
    ctx = _ctx(old, [CAPTURE], phases)
    for name in ("engine.exposed_host_pct", "engine.exposed_stage_pct",
                 "engine.exposed_emit_pct", "engine.readback_wait_pct",
                 "engine.host_stage_ms", "engine.host_emit_ms", "sched.drain_wait_mean_ms"):
        assert reader(name)(ctx) is None, name
    # what PR 37's records do carry reads a number on the parent too
    assert reader("engine.run_ahead_pct")(ctx) == 100.0
    assert reader("engine.stall_iters")(ctx) == 0.0
    # older still: no `ahead`; and a window with nothing in it
    for i in old:
        del i["ahead"]
    assert reader("engine.run_ahead_pct")(ctx) is None
    empty = _ctx([], [CAPTURE], [])
    assert all(reader(name)(empty) is None for name in NEW)


def test_the_benchmark_lists_the_nine_metrics_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW and at >= 30  # appended together, after PR 36's
    per = {m["name"]: m for m in bench["per_layer"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    for name in NEW:
        m = per[name]
        assert "workloads" not in m  # all three cells run this loop
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in end_to_end and m["layer"] in layers
        assert m["source"] in ("program_span", "program_counter")
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    assert per["sched.drain_wait_mean_ms"]["moves"] == "latency_mean_ms"
    assert per["engine.stall_iters"]["unit"] == "count"
