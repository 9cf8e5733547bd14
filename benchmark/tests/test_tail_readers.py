"""What PR 56 added to the yardstick: the readers of a request's decode account
(`_tail`: the tail of a window by engine-side TPOT, its shares by class of
iteration, `engine.tpot_p95_ms`) and the two readers of PR 55's delivery
fields, on hand-made contexts (a tail of three among twenty, None on a spine
without the keys so that the line leaves the metric out and nothing raises, a
one-token request left out), and the BENCHMARK.json entries."""

import importlib.util
import json
import os

import pytest

import _tail
import loadgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TAIL = ["engine.tpot_p95_ms", "engine.tail_ahead_pct", "engine.tail_cold_pct",
        "sched.tail_mixed_pct", "engine.tail_unowned_pct"]
NEW = TAIL + ["engine.host_deliver_ms", "engine.deliver_under_pct"]
CLASSES = ("ahead", "cold", "mixed", "prefill", "other", "wait")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _req(wall, tokens=101, ttft_s=0.2, lag_s=0.0, **parts):
    """A finished request's spine as serve.py logs it: it ended at `wall`,
    its decode interval is the sum of `parts` (seconds by class), and its
    last token was delivered `lag_s` after the mark that closed it."""
    ph = {f"decode_{c}_s": float(parts.get(c, 0.0)) for c in CLASSES}
    ph["decode_s"] = sum(ph.values())
    ph.update(decode_tokens=tokens, ttft_s=ttft_s, replica=0, wall=wall,
              e2e_s=ttft_s + ph["decode_s"] + lag_s, drain_wait_s=0.0)
    return ph


def _ctx(phases, its=None, captures=None):
    return {"final": {"phases": phases}, "w0_wall": 100.0, "w1_wall": 150.0,
            "counters": {"iterations": its or [],
                         "trace": {"captures": captures} if captures is not None else None},
            "percentile": loadgen.percentile}


def _twenty():
    """Seventeen streams of 100 decoded tokens at 10 ms a token, all of it
    under decodes enqueued ahead, and three that sat under mixed steps and
    drains as well: 20, 18 and 16 ms a token."""
    easy = [_req(110.0 + k, ahead=1.0) for k in range(17)]
    hard = [_req(130.0, ahead=1.0, mixed=0.6, cold=0.3, prefill=0.1),
            _req(131.0, ahead=1.0, mixed=0.5, cold=0.3),
            _req(132.0, ahead=1.0, mixed=0.4, cold=0.1, wait=0.1)]
    return easy, hard


def test_a_tail_of_three_among_twenty():
    easy, hard = _twenty()
    ctx = _ctx(easy[:9] + hard + easy[9:])
    assert _tail.tail(ctx) == hard  # by TPOT, the highest first
    ahead, cold = reader("engine.tail_ahead_pct")(ctx), reader("engine.tail_cold_pct")(ctx)
    mixed, unowned = reader("sched.tail_mixed_pct")(ctx), reader("engine.tail_unowned_pct")(ctx)
    whole = 2.0 + 1.8 + 1.6
    assert ahead == pytest.approx(100 * 3.0 / whole)
    assert cold == pytest.approx(100 * 0.7 / whole)
    assert mixed == pytest.approx(100 * (1.5 + 0.1) / whole)  # fused or alone
    assert unowned == pytest.approx(100 * 0.1 / whole)
    assert ahead + cold + mixed + unowned == pytest.approx(100.0)
    # over ALL the window's requests the same shares read otherwise: that is
    # what sets the tail apart
    assert _tail.share_pct(_tail.decoded(ctx), "decode_ahead_s") == pytest.approx(
        100 * 20.0 / (17.0 + whole))


def test_the_tail_grows_with_the_window_and_never_under_three():
    easy, hard = _twenty()
    assert len(_tail.tail(_ctx(easy[:2]))) == 2  # all there are
    assert len(_tail.tail(_ctx(easy[:5]))) == 3
    many = [_req(101.0 + 0.1 * k, ahead=1.0 + 0.001 * k) for k in range(45)]
    assert len(_tail.tail(_ctx(many))) == 5  # ceil(45 / 10)
    assert _tail.tail(_ctx(many))[0] is many[-1]


def test_engine_tpot_is_the_clients_arithmetic_on_the_engines_stamps():
    easy, hard = _twenty()
    ctx = _ctx(easy + hard)
    got = reader("engine.tpot_p95_ms")(ctx)
    assert got == pytest.approx(loadgen.percentile([10.0] * 17 + [20.0, 18.0, 16.0], 95))
    # e2e_s and ttft_s are stamped at delivery: a last token that went out
    # 50 ms after its mark reads 0.5 ms a token more, on the shares nothing
    late = [_req(140.0, ahead=1.0, lag_s=0.05)]
    assert reader("engine.tpot_p95_ms")(_ctx(late)) == pytest.approx(10.5)
    assert reader("engine.tail_ahead_pct")(_ctx(late)) == pytest.approx(100.0)


def test_a_one_token_request_and_one_outside_the_window_are_left_out():
    easy, hard = _twenty()
    one = _req(120.0, tokens=1)
    one["e2e_s"] = 9.0  # (it would be the highest quotient, were it divided)
    bare = {"replica": 0, "wall": 121.0, "ttft_s": 0.2, "e2e_s": 0.2, "preemptions": 0}
    before = _req(100.5, ahead=5.0)  # arrived before the window opened
    ctx = _ctx(easy[:4] + [one, bare, before])
    assert _tail.decoded(ctx) == easy[:4]
    assert reader("engine.tpot_p95_ms")(ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("name", TAIL)
def test_a_window_with_no_decode_keys_reads_none(name):
    """The parent's program (any before PR 56) finishes its requests with the
    older spine: nothing to read, nothing raised."""
    old = [{"replica": 0, "wall": 110.0 + k, "ttft_s": 0.2, "e2e_s": 1.2,
            "queue_wait_s": 0.01, "prefill_s": 0.19, "drain_wait_s": 0.0}
           for k in range(12)]
    assert reader(name)(_ctx(old)) is None
    assert reader(name)(_ctx([])) is None
    assert reader(name)({"final": {}, "w0_wall": 0.0, "w1_wall": 1.0,
                         "percentile": loadgen.percentile}) is None


def test_the_delivery_readers_on_a_worked_example():
    """Ten iterations: nine delivered under the next program in 0.8 ms, the
    last before an idle pass in 0.3; one more sits in a capture."""
    its = [{"ts": 101.0 + k, "wall_s": 0.05, "host_deliver_s": 0.0008,
            "deliver_under": True} for k in range(9)]
    its.append({"ts": 111.0, "wall_s": 0.05, "host_deliver_s": 0.0003,
                "deliver_under": False})
    its.append({"ts": 112.5, "wall_s": 1.0, "host_deliver_s": 0.2, "deliver_under": False})
    capture = {"start_wall": 112.0, "stop_wall": 113.0, "written_s": 0.8}
    ctx = _ctx([], its, [capture])
    assert reader("engine.host_deliver_ms")(ctx) == pytest.approx((9 * 0.8 + 0.3) / 10)
    assert reader("engine.deliver_under_pct")(ctx) == pytest.approx(90.0)
    # a program older than PR 55 has neither field
    old = [{"ts": 101.0, "wall_s": 0.05, "host_emit_s": 0.003}]
    assert reader("engine.host_deliver_ms")(_ctx([], old)) is None
    assert reader("engine.deliver_under_pct")(_ctx([], old)) is None
    assert reader("engine.deliver_under_pct")(_ctx([], [])) is None


def test_the_entries_are_in_the_benchmark_with_files_beside_them():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(by_name)  # by name: a later append moves them
    ends = {m["name"] for m in bench["end_to_end"]}
    for name in NEW:
        m = by_name[name]
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py")), name
        # every cell's program has the spine and the records: no `workloads`
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}, m
        assert m["source"] == "program_counter" and m["moves"] == "tpot_p95_ms"
        assert m["moves"] in ends
        assert m["layer"] == ("engine scheduler" if name.startswith("sched.")
                              else "engine step loop")
        assert m["unit"] == ("ms" if name.endswith("_ms") else "%")
    assert by_name["engine.tail_ahead_pct"]["better"] == "higher"
    assert by_name["engine.deliver_under_pct"]["better"] == "higher"
    assert all(by_name[n]["better"] == "lower" for n in NEW
               if n not in ("engine.tail_ahead_pct", "engine.deliver_under_pct"))
