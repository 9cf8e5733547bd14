"""The step loop keeps one decode dispatch in flight (engine._loop_once):
iteration N+1 is planned, staged and enqueued on the device-resident tokens
of iteration N before N is read back. Same work, same answers: on a tiny
ModelRunner the streams equal, token for token, those of the same engine on
a runner whose fact says it cannot run ahead; on a recording runner the
order of dispatches and read-backs is the one the drain reasons allow."""

import asyncio
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models.config import get_config
from dynamo_tpu.runtime.context import Context

PAGE = 4


def _runner(name="tiny", **kw):
    args = dict(num_pages=96, page_size=PAGE, max_pages_per_seq=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(8, 16), seed=7,
                dtype=jnp.float32)
    args.update(kw)
    return ModelRunner(get_config(name), **args)


def _engine(runner, ahead, **kw):
    """The engine under test; `ahead` False: the same engine on a runner
    that says it cannot run ahead (every iteration in the serial order)."""
    if not ahead:
        runner.can_run_ahead = False
    args = dict(max_batch=4, chunk_size=8, decode_steps=4,
                mixed_prefill_tokens=0)
    args.update(kw)
    return InferenceEngine(runner, **args)


async def _one(engine, req, out):
    """Serve one request {prompt, n, stop_ids?, after?, cancel_after?,
    sampling?}; its items go to out[rid]. `after` (rid, n): sent once that
    stream has n tokens."""
    if req.get("after"):
        rid, n = req["after"]
        while sum(len(i.get("token_ids") or [])
                  for i in out.get(rid, [])) < n:
            await asyncio.sleep(0.002)
    ctx = Context()
    items = out.setdefault(req["rid"], [])
    stop = {"max_tokens": req["n"], "stop_ids": req.get("stop_ids", [])}
    if not req.get("stop_ids"):
        stop["ignore_eos"] = True
    async for item in engine.generate(
            {"token_ids": req["prompt"],
             "sampling": {"temperature": 0.0, **req.get("sampling", {})},
             "stop": stop}, ctx):
        items.append(item)
        n_tok = sum(len(i.get("token_ids") or []) for i in items)
        if req.get("cancel_after") and n_tok >= req["cancel_after"]:
            ctx.stop_generating()
            break


def _drive(engine, reqs):
    """Serve reqs on a started engine; ({rid: tokens}, {rid: items}, the
    flight records)."""
    out = {}

    async def go():
        await asyncio.gather(*[_one(engine, r, out) for r in reqs])
        await asyncio.sleep(0.05)

    engine.start()
    try:
        asyncio.run(go())
    finally:
        engine.stop()
    toks = {rid: [t for i in items for t in (i.get("token_ids") or [])]
            for rid, items in out.items()}
    return toks, out, engine.recorder.snapshot()


def _ab(make_runner, reqs, **engine_kw):
    """The same requests through the run-ahead engine and the serial one."""
    a = _drive(_engine(make_runner(), True, **engine_kw), reqs)
    b = _drive(_engine(make_runner(), False, **engine_kw), reqs)
    assert not any(r.ahead for r in b[2])
    assert {r.drain for r in b[2] if r.kind == "decode"} <= {"runner"}
    return a, b


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


def _finals(items):
    return [i for i in items if i.get("finish_reason")]


# -- same streams as the serial loop -----------------------------------------


def test_staggered_lengths_end_in_different_iterations():
    """Rows end by length in different iterations: each leaves a pad row
    behind while the others run ahead, then a smaller bucket drains."""
    reqs = [{"rid": f"r{i}", "prompt": _prompt(6, i), "n": n}
            for i, n in enumerate([5, 14, 23, 32])]
    (ta, ia, ra), (tb, _, _) = _ab(_runner, reqs)
    assert ta == tb
    assert all(len(ta[r["rid"]]) == r["n"] for r in reqs)
    assert all(len(_finals(ia[r["rid"]])) == 1 for r in reqs)
    dec = [r for r in ra if r.kind == "decode"]
    assert sum(r.ahead for r in dec) >= len(dec) // 2, [
        (r.ahead, r.drain) for r in dec]
    assert {r.drain for r in dec} <= {"", "cold", "bucket", "rows"}
    assert "bucket" in {r.drain for r in dec}


def test_stop_token_found_one_iteration_late():
    """Without ignore_eos a stop token is seen when its dispatch is read
    back, after the next one was enqueued: the row rides that one for
    nothing, its tokens are dropped, it finishes once."""
    prompt = _prompt(6, 11)
    free, _, _ = _drive(_engine(_runner(), False),
                        [{"rid": "x", "prompt": prompt, "n": 24}])
    stream = free["x"]
    # a token whose first occurrence is mid-dispatch, a few iterations in
    k = next(i for i in range(5, 20) if stream[i] not in stream[:i])
    reqs = [{"rid": "x", "prompt": prompt, "n": 24, "stop_ids": [stream[k]]},
            {"rid": "y", "prompt": _prompt(5, 12), "n": 24}]
    (ta, ia, ra), (tb, ib, _) = _ab(_runner, reqs)
    assert ta == tb and ta["x"] == stream[:k]
    assert [i["finish_reason"] for i in _finals(ia["x"])] == ["stop"]
    assert len(_finals(ia["y"])) == 1 and len(ta["y"]) == 24
    assert any(r.ahead for r in ra)


def test_stop_releases_pages_and_slot_once():
    eng = _engine(_runner("tiny-jamba", num_pages=64, max_pages_per_seq=16,
                          decode_buckets=(2, 4), ragged_buckets=(8, 16)), True)
    prompt = _prompt(6, 21)
    free, _, _ = _drive(eng, [{"rid": "x", "prompt": prompt, "n": 20}])
    k = next(i for i in range(5, 18) if free["x"][i] not in free["x"][:i])
    eng = _engine(_runner("tiny-jamba", num_pages=64, max_pages_per_seq=16,
                          decode_buckets=(2, 4), ragged_buckets=(8, 16)), True)
    toks, items, recs = _drive(eng, [
        {"rid": "x", "prompt": prompt, "n": 20, "stop_ids": [free["x"][k]]},
        {"rid": "y", "prompt": _prompt(5, 22), "n": 20}])
    assert toks["x"] == free["x"][:k] and any(r.ahead for r in recs)
    sch = eng.scheduler
    assert not sch.active and eng.pool.n_free == 64
    assert sorted(sch.side._free) == list(range(1, sch.side.units))


def test_request_arriving_mid_stream_drains_then_resumes():
    reqs = [{"rid": "a", "prompt": _prompt(6, 31), "n": 40},
            {"rid": "b", "prompt": _prompt(9, 32), "n": 12, "after": ("a", 14)}]
    (ta, _, ra), (tb, _, _) = _ab(_runner, reqs)
    assert ta == tb and len(ta["a"]) == 40 and len(ta["b"]) == 12
    kinds = [(r.kind, r.ahead, r.drain) for r in ra]
    first_prefill_b = next(i for i, k in enumerate(kinds)
                           if k[0] == "prefill" and i > 0)
    assert any(a for _, a, _ in kinds[:first_prefill_b]), kinds
    assert any(a for _, a, _ in kinds[first_prefill_b:]), kinds
    assert kinds[first_prefill_b][2] == "prefill"


def test_mixed_iterations_drain_and_streams_match(monkeypatch):
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    # `a` long enough to be decoding still when `b`, sent at its 14th token,
    # is planned: at 36 tokens a warm host often had it done by then
    reqs = [{"rid": "a", "prompt": _prompt(6, 41), "n": 56},
            {"rid": "b", "prompt": _prompt(13, 42), "n": 10, "after": ("a", 14)}]
    (ta, _, ra), (tb, _, _) = _ab(_runner, reqs, mixed_prefill_tokens=8)
    assert ta == tb
    assert any(r.kind == "mixed" and r.drain == "mixed" for r in ra)
    assert any(r.ahead for r in ra)


def test_abort_while_a_dispatch_is_queued():
    reqs = [{"rid": "a", "prompt": _prompt(6, 51), "n": 60, "cancel_after": 9},
            {"rid": "b", "prompt": _prompt(7, 52), "n": 30}]
    (ta, _, ra), (tb, _, _) = _ab(_runner, reqs)
    assert ta["b"] == tb["b"] and len(ta["b"]) == 30
    # the aborted stream is a prefix of the free-running one
    n = min(len(ta["a"]), len(tb["a"]))
    assert n >= 9 and ta["a"][:n] == tb["a"][:n]
    assert any(r.ahead for r in ra)


def test_abort_leaves_no_pages_behind():
    eng = _engine(_runner(), True)
    _drive(eng, [{"rid": "a", "prompt": _prompt(6, 51), "n": 60,
                  "cancel_after": 9}])
    assert not eng.scheduler.active and eng.pool.n_free == 96
    assert eng._inflight is None


def test_small_pool_preempts_after_a_drain():
    """A pool too small for both rows to finish: the scheduler wants a
    preemption while steps are in flight, the loop commits them first and
    the preempted row re-prefills from all its tokens."""
    def make():
        return _runner(num_pages=10, max_pages_per_seq=8)

    reqs = [{"rid": "a", "prompt": _prompt(6, 61), "n": 24},
            {"rid": "b", "prompt": _prompt(6, 62), "n": 24}]
    (ta, ia, ra), (tb, ib, _) = _ab(make, reqs, enable_prefix_cache=False)
    assert ta == tb and all(len(v) == 24 for v in ta.values())
    assert "preempt" in {r.drain for r in ra}
    assert any(_finals(v)[0]["phases"]["preemptions"] for v in ia.values())
    assert any(r.ahead for r in ra)


def test_state_space_model_runs_ahead(monkeypatch):
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")

    def make():
        return _runner("tiny-jamba", decode_buckets=(2, 4),
                       ragged_buckets=(8, 16))

    reqs = [{"rid": "a", "prompt": _prompt(6, 71), "n": 26},
            {"rid": "b", "prompt": _prompt(5, 72), "n": 9},
            {"rid": "c", "prompt": _prompt(11, 73), "n": 12, "after": ("a", 10)}]
    (ta, _, ra), (tb, _, _) = _ab(make, reqs, mixed_prefill_tokens=8)
    assert ta == tb and [len(ta[k]) for k in "abc"] == [26, 9, 12]
    assert any(r.ahead for r in ra)
    assert all(r.state_slots_total for r in ra)


def test_routed_model_counts_load_per_dispatch():
    """Each iteration's expert-load counters are its own dispatch's: with
    one enqueued ahead the records still read what the serial loop's do."""
    def make():
        return _runner("tiny-moe")

    reqs = [{"rid": "a", "prompt": _prompt(6, 81), "n": 21,
             "sampling": {"routed_experts": True}},
            {"rid": "b", "prompt": _prompt(7, 82), "n": 13}]
    (ta, ia, ra), (tb, ib, rb) = _ab(make, reqs)
    assert ta == tb and any(r.ahead for r in ra)

    def load(recs):
        return [(r.decode_seqs, r.decode_steps, r.moe_token_slots,
                 round(r.moe_experts_hit, 6)) for r in recs
                if r.kind == "decode"]

    assert load(ra) == load(rb)
    k = get_config("tiny-moe").n_experts_active
    assert all(slots == rows * steps * k for rows, steps, slots, _ in load(ra))

    def picks(items):
        return [(i["routed_experts"]["start"], i["routed_experts"]["ids"])
                for i in items if "routed_experts" in i]

    assert picks(ia["a"]) == picks(ib["a"]) and picks(ia["a"])
    assert not picks(ia["b"])


def test_readback_waits_for_its_own_dispatch_only(monkeypatch):
    """decode_collect fetches the handle's own arrays and no others, with a
    second dispatch queued behind it."""
    from dynamo_tpu.engine import model_runner as mr

    r = _runner("tiny-moe")
    samp = {"temperature": [0.0, 0.0], "top_k": [0, 0], "top_p": [1.0, 1.0],
            "seeds": [1, 2]}
    pts = [[0, 1, 2, 3], [4, 5, 6, 7]]
    h1 = r.decode_dispatch(2, [5, 6], [0, 0], pts, samp, 1)
    h2 = r.decode_dispatch(2, None, [2, 2], pts, samp, 3, prev=h1)
    assert not r._routed_parts  # each handle holds its own
    theirs = {id(h2.toks), id(h2.last), id(h2.parts[0].load)}
    mine = {id(h1.toks), id(h1.parts[0].load)}
    seen = []
    get = mr.jax.device_get

    def spy(x):
        seen.extend(id(leaf) for leaf in mr.jax.tree_util.tree_leaves(x))
        return get(x)

    monkeypatch.setattr(mr.jax, "device_get", spy)
    t1 = r.decode_collect(h1)
    assert set(seen) == mine and not set(seen) & theirs
    load1 = r.take_moe_load()
    assert load1.ready and load1.result()[0] == 2 * 2 * get_config(
        "tiny-moe").n_experts_active
    t2 = r.decode_collect(h2)
    monkeypatch.undo()
    # chained on the device = fed from the host
    want = r.decode_multi(2, [int(t1[0, -1]), int(t1[1, -1])], [2, 2], pts,
                          samp, 3)
    assert (t2[:2] == want[:2]).all()


def test_chained_dispatch_is_no_new_program():
    """The loop a warm-up compiled from host tokens is the one a dispatch
    chained on device tokens finds: no second variant, nothing compiled."""
    r = _runner()
    samp = {"temperature": [0.0, 0.0], "top_k": [0, 0], "top_p": [1.0, 1.0],
            "seeds": [1, 2]}
    pts = [[0, 1, 2, 3], [4, 5, 6, 7]]
    r.decode_multi(4, [5, 6], [0, 0], pts, samp, 1)
    before = r.compile_stats()["decode_loop"]["variants"]
    h1 = r.decode_dispatch(4, [5, 6], [4, 4], pts, samp, 5)
    h2 = r.decode_dispatch(4, None, [8, 8], pts, samp, 9, prev=h1)
    r.decode_collect(h1), r.decode_collect(h2)
    assert r.compile_stats()["decode_loop"]["variants"] == before
    with pytest.raises(ValueError, match="bucket"):
        r.decode_dispatch(4, None, [12], [pts[0]], samp, 13, prev=h2)


def test_pad_row_keeps_a_place_open():
    """A row that ended becomes a pad row in place: the others' tokens are
    those of a compact batch, and nothing is written for it."""
    r = _runner()
    samp = {"temperature": [0.0] * 3, "top_k": [0] * 3, "top_p": [1.0] * 3,
            "seeds": [1, 2, 3]}
    pts = [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]]
    h1 = r.decode_dispatch(2, [5, 6, 7], [0, 0, 0], pts, samp, 1)
    h2 = r.decode_dispatch(2, None, [2, -1, 2], [pts[0], [], pts[2]], samp,
                           3, prev=h1)
    t1, t2 = r.decode_collect(h1), r.decode_collect(h2)
    r2 = _runner()
    s2 = {k: [v[0], v[2]] for k, v in samp.items()}
    w1 = r2.decode_multi(2, [5, 7], [0, 0], [pts[0], pts[2]], s2, 1)
    w2 = r2.decode_multi(2, [int(w1[0, -1]), int(w1[1, -1])], [2, 2],
                         [pts[0], pts[2]], s2, 3)
    assert (t1[[0, 2]] == w1[:2]).all() and (t2[[0, 2]] == w2[:2]).all()


# -- the order of dispatches and read-backs ----------------------------------


def _recording_sim(**kw):
    from dynamo_tpu.mocker.sim import SimRunner, SimTiming

    class Recording(SimRunner):
        def __init__(self):
            super().__init__(num_pages=64, page_size=PAGE,
                             max_pages_per_seq=16, vocab_size=300,
                             timing=SimTiming(speed=0.0), **kw)
            self.events = []
            self._n = 0

        def decode_dispatch(self, *a, **k):
            h = super().decode_dispatch(*a, **k)
            self._n += 1
            h.n = self._n
            self.events.append(("dispatch", h.n, k.get("prev") is not None))
            return h

        def decode_collect(self, h):
            self.events.append(("collect", h.n))
            return super().decode_collect(h)

    return Recording()


def _order(events):
    """(dispatches enqueued before the one before was collected, all)."""
    ahead = total = 0
    collected = set()
    for ev in events:
        if ev[0] == "collect":
            collected.add(ev[1])
        else:
            total += 1
            if ev[1] > 1 and ev[1] - 1 not in collected:
                ahead += 1
                assert ev[2], "ran ahead without chaining on the device"
    return ahead, total


def test_continuation_dispatches_before_the_readback():
    r = _recording_sim()
    eng = InferenceEngine(r, max_batch=4, chunk_size=8, decode_steps=4,
                          mixed_prefill_tokens=0)
    toks, _, recs = _drive(eng, [{"rid": "a", "prompt": _prompt(6, 1),
                                  "n": 40}])
    ahead, total = _order(r.events)
    assert total == 10 and ahead == 9, r.events
    dec = [x for x in recs if x.kind == "decode"]
    assert [x.ahead for x in dec] == [False] + [True] * 9
    assert dec[0].drain == "cold" and all(x.drain == "" for x in dec[1:])
    assert eng.run_ahead_totals == {"prefill": 1, "cold": 1, "ahead": 9}
    # every dispatch was collected exactly once
    assert sorted(e[1] for e in r.events if e[0] == "collect") == list(
        range(1, 11))
    # and the stream is the serial engine's
    r2 = _recording_sim()
    r2.can_run_ahead = False
    t2, _, _ = _drive(InferenceEngine(r2, max_batch=4, chunk_size=8,
                                      decode_steps=4, mixed_prefill_tokens=0),
                      [{"rid": "a", "prompt": _prompt(6, 1), "n": 40}])
    assert toks == t2 and _order(r2.events)[0] == 0


@pytest.mark.parametrize("reason", ["spec", "penalties", "guided", "rows",
                                    "runner"])
def test_drain_reasons_keep_the_serial_order(reason):
    """Where the next plan needs this one's tokens on the host, or its rows
    are not the rows in flight, the read-back comes first."""
    r = _recording_sim()
    kw = dict(max_batch=4, chunk_size=8, decode_steps=4,
              mixed_prefill_tokens=0)
    req = {"rid": "a", "prompt": _prompt(6, 1), "n": 24}
    reqs = [req]
    if reason == "spec":
        kw.update(spec_ngram=True, mixed_prefill_tokens=8)
    elif reason == "penalties":
        req["sampling"] = {"repetition_penalty": 1.2}
    elif reason == "guided":
        eng = InferenceEngine(r, **kw)
        eng._ahead_blocker = lambda seqs, f=eng._ahead_blocker: (
            f(seqs) or "guided")  # as a row with a DFA reads
        _, _, recs = _drive(eng, reqs)
        assert _order(r.events)[0] == 0
        return
    elif reason == "rows":
        reqs = [req] + [{"rid": f"j{i}", "prompt": _prompt(5, 10 + i),
                         "n": 4, "after": ("a", 5 + 4 * i)} for i in range(3)]
    elif reason == "runner":
        r.can_run_ahead = False
    eng = InferenceEngine(r, **kw)
    _, _, recs = _drive(eng, reqs)
    dec = [x for x in recs if x.kind == "decode"]
    if reason == "rows":
        assert "rows" in {x.drain for x in dec} or "cold" in {
            x.drain for x in dec}
        # a dispatch that followed a joiner's prefill chained on nothing
        for ev in r.events:
            if ev[0] == "dispatch" and not ev[2] and ev[1] > 1:
                assert ("collect", ev[1] - 1) in r.events[:r.events.index(ev)]
        return
    assert dec and not any(x.ahead for x in dec)
    assert {x.drain for x in dec} == {reason}
    assert _order(r.events)[0] == 0


def test_failed_dispatch_commits_what_was_in_flight_first():
    """The error path: the enqueue of N+1 raises with N in flight. N's
    tokens were computed and are delivered; the plan's rows then fail with
    an error item, once, and nothing stays in flight."""
    r = _recording_sim()
    real, calls = r.decode_dispatch, {"n": 0}

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected")
        return real(*a, **k)

    r.decode_dispatch = flaky
    eng = InferenceEngine(r, max_batch=4, chunk_size=8, decode_steps=4,
                          mixed_prefill_tokens=0)
    toks, items, recs = _drive(eng, [{"rid": "a", "prompt": _prompt(6, 1),
                                      "n": 40}])
    # prefill's token + two dispatches of four, then the error
    assert len(toks["a"]) == 9
    assert [i["finish_reason"] for i in _finals(items["a"])] == ["error"]
    assert [e[0] for e in r.events].count("collect") == 2
    assert eng._inflight is None and not eng.scheduler.active
    assert eng.pool.n_free == 64


def test_stop_commits_the_dispatch_in_flight():
    """Shutdown: the loop ends with a dispatch enqueued; it is read back
    and committed, not dropped, and nothing is enqueued ahead once the
    engine is stopping."""
    r = _recording_sim()
    eng = InferenceEngine(r, max_batch=4, chunk_size=8, decode_steps=4,
                          mixed_prefill_tokens=0)
    out = {}

    async def go():
        task = asyncio.ensure_future(_one(
            eng, {"rid": "a", "prompt": _prompt(6, 1), "n": 4000}, out))
        while sum(len(i.get("token_ids") or []) for i in out.get("a", [])) < 9:
            await asyncio.sleep(0.001)
        task.cancel()

    eng.start()
    try:
        asyncio.run(go())
    finally:
        eng.stop()
    assert eng._inflight is None
    d = [e[1] for e in r.events if e[0] == "dispatch"]
    c = [e[1] for e in r.events if e[0] == "collect"]
    assert sorted(c) == d  # every dispatch enqueued was read back once


def test_guided_row_blocks_run_ahead():
    from dynamo_tpu.engine.scheduler import Sequence

    eng = InferenceEngine(_recording_sim(), max_batch=4, chunk_size=8)
    plain = Sequence("p", [1, 2], {}, {})
    guided = Sequence("g", [1, 2], {}, {}, guided_m=object())
    assert eng._ahead_blocker([plain]) is None
    assert eng._ahead_blocker([plain, guided]) == "guided"
    eng._stop.set()
    assert eng._ahead_blocker([plain]) == "shutdown"


def test_scheduler_plans_around_steps_in_flight():
    from dynamo_tpu.engine.kv_pool import PagePool
    from dynamo_tpu.engine.scheduler import (
        Scheduler, Sequence, StepsInFlight)

    pool = PagePool(8, 4)
    sch = Scheduler(pool, max_batch=4, chunk_size=8, decode_steps=4,
                    enable_prefix_cache=False, mixed_prefill_tokens=0)
    a = Sequence("a", [1, 2, 3], {}, {"max_tokens": 6, "stop_ids": []})
    b = Sequence("b", [4, 5, 6], {}, {"max_tokens": 30, "stop_ids": []})
    for s in (a, b):
        sch.add(s)
        sch.complete_prefill(sch.step_plan())
        sch.complete_decode(s, 7, advance_computed=False)
    plan = sch.step_plan()
    assert plan.seqs == [a, b] and plan.n_steps == 4
    a.inflight = b.inflight = 4
    plan = sch.step_plan()  # a has 1 token left past the steps in flight
    assert plan.seqs == [a, b] and plan.n_steps == 1
    assert len(b.pages) * 4 >= b.computed_len + 4 + 1
    a.inflight = 5  # its budget is spent by what is in flight: left out
    plan = sch.step_plan()
    assert plan.seqs == [b] and plan.n_steps == 4
    hog = pool.alloc(pool.n_free)
    b.inflight = 4 + 4 * (len(b.pages) - 1)
    with pytest.raises(StepsInFlight):
        sch.step_plan()
    assert b.state.value == "running"  # nobody was preempted
    pool.release(hog)


# -- the step loop's host clock (runtime/annotations.py, the door) -----------
# Every record carries what the step thread did between the two commits its
# wall runs between, by the profiler's span names, and how much of it ran
# with nothing enqueued on the device (exposed). On a runner whose blocking
# read sleeps, so that the device-bound share is known.

SLEEP = 0.02
HOST = ("inbox", "schedule", "prep", "stage", "dispatch", "readback", "emit",
        "publish")


def _sleepy(runner):
    """`runner` with a device that takes SLEEP to answer a blocking read
    (inside the runner's engine.readback span, where the real wait is)."""
    real = runner._readback

    def slow(x):
        time.sleep(SLEEP)
        return real(x)

    runner._readback = slow
    return runner


def _clocked(kind):
    """A long decode alone, then a joiner against it: served by a prefill of
    its own (`kind` "prefill") or inside a fused mixed step ("mixed")."""
    reqs = [{"rid": "a", "prompt": _prompt(6, 71), "n": 40},
            {"rid": "b", "prompt": _prompt(7, 72), "n": 12, "after": ("a", 12)}]
    kw = {"mixed_prefill_tokens": 8} if kind == "mixed" else {}
    eng = _engine(_sleepy(_runner()), True, **kw)
    _, items, recs = _drive(eng, reqs)
    assert any(r.kind == kind for r in recs), [r.kind for r in recs]
    return eng, items, recs


@pytest.fixture(scope="module", params=["prefill", "mixed"])
def clocked(request):
    import os

    was = os.environ.get("DYN_FUSED_MIXED")
    os.environ["DYN_FUSED_MIXED"] = "1"
    try:
        return (request.param,) + _clocked(request.param)
    finally:
        if was is None:
            os.environ.pop("DYN_FUSED_MIXED", None)
        else:
            os.environ["DYN_FUSED_MIXED"] = was


def _host_sum(r):
    return sum(getattr(r, f"host_{p}_s") for p in HOST)


def test_phases_are_parts_of_the_wall(clocked):
    _, _, _, recs = clocked
    assert len(recs) > 10
    for r in recs:
        parts = [getattr(r, f"host_{p}_s") for p in HOST]
        assert all(p >= 0.0 for p in parts), r
        assert _host_sum(r) <= r.wall_s + 1e-9, (r, _host_sum(r))
        assert 0.0 <= r.exposed_s <= _host_sum(r) - r.host_readback_s + 1e-9, r
        assert r.exposed_stage_s <= r.host_stage_s + 1e-12
        assert r.exposed_emit_s <= r.host_emit_s + 1e-12
        assert r.exposed_stage_s + r.exposed_emit_s <= r.exposed_s + 1e-12
        assert r.gc_s >= 0.0
    clock = clocked[1].step_clock
    assert clock.handles == 0 and not clock.serial  # all collected


def test_readback_holds_the_devices_time_and_is_never_exposed(clocked):
    _, _, _, recs = clocked
    dec = [r for r in recs if r.kind == "decode"]
    assert all(r.host_readback_s >= SLEEP * 0.95 for r in dec), [
        r.host_readback_s for r in dec]
    # a loop that waits for its device is all readback, and none of that
    # wait is the host's: exposed seconds stay small beside it
    # (but where its interval held a compile: the first call of a bucket)
    ahead = [r for r in dec if r.ahead and r.host_dispatch_s < SLEEP]
    assert len(ahead) > 4
    assert all(r.host_readback_s > 0.5 * r.wall_s for r in ahead), ahead
    for r in recs:
        assert r.exposed_s <= r.wall_s - r.host_readback_s + 1e-9, r


def test_exposed_leaves_out_what_ran_under_a_queued_dispatch(clocked):
    """An iteration enqueued ahead was staged and dispatched under the one
    before it, and so was the one after it under it: nothing of its interval
    is exposed but the commit of the last one before a drain. A cold or a
    mixed iteration had nothing queued: its own staging is exposed."""
    kind, _, _, recs = clocked
    by_seq = {r.seq: i for i, r in enumerate(recs)}
    for r in recs:
        nxt = recs[by_seq[r.seq] + 1] if by_seq[r.seq] + 1 < len(recs) else None
        if r.ahead and nxt is not None and nxt.ahead:
            assert r.exposed_s == 0.0 and r.exposed_stage_s == 0.0, r
            assert r.host_stage_s > 0.0 and r.host_dispatch_s > 0.0, r
    cold = [r for r in recs if r.drain == "cold"]
    assert cold
    for r in cold:
        assert r.exposed_stage_s > 0.0, r
        # prep, stage and dispatch of its own enqueue, at the least
        assert r.exposed_s > r.exposed_stage_s + r.exposed_emit_s, r
    joined = [r for r in recs if r.kind == kind]
    assert joined and all(r.drain == kind for r in joined)
    for r in joined:
        assert r.exposed_stage_s > 0.0 and r.exposed_s > r.exposed_stage_s, r


def test_a_joiner_waits_out_the_drain_inside_its_prefill(clocked):
    """`drain_wait_s`: the joiner's chunk was planned while a dispatch was
    in flight, and waited for its commit (one sleepy readback at the least)
    before its own could be enqueued; the request that found the loop idle
    waited for none. The TTFT identity stays exact."""
    _, _, items, _ = clocked
    ph = {rid: _finals(v)[0]["phases"] for rid, v in items.items()}
    assert ph["a"]["drain_wait_s"] == 0.0
    assert ph["b"]["drain_wait_s"] >= SLEEP * 0.95
    for p in ph.values():
        assert p["drain_wait_s"] <= p["prefill_s"]
        assert p["ttft_s"] == pytest.approx(
            p["queue_wait_s"] + p.get("kv_onboard_s", 0.0) + p["prefill_s"],
            abs=1e-12)


def test_idle_sleeps_are_in_no_iteration():
    eng = _engine(_runner(), True)
    toks, _, _ = _drive(eng, [{"rid": "a", "prompt": _prompt(6, 81), "n": 6}])
    assert len(toks["a"]) == 6
    clock = eng.step_clock
    # _drive idles 50 ms after the last token: the sleeps since the last
    # record wait in the clock for the next one to take them to /metrics,
    # and the phases of iterations that found nothing to do were dropped
    from dynamo_tpu.runtime.annotations import WAIT

    assert 0.03 < clock.ns[WAIT] * 1e-9 < 0.5
    assert clock.exposed_ns[WAIT] == clock.ns[WAIT]  # nothing was enqueued
    assert clock.ns[:WAIT] == [0] * WAIT and clock.gc_ns == 0


def test_a_dispatch_dropped_uncollected_is_not_in_flight_for_the_clock():
    """Where the commit of the dispatch in flight raises after the next one
    was enqueued, that one is never collected: the clock must not go on
    reading every later second as hidden under it."""
    from dynamo_tpu.engine import engine as E

    eng = _engine(_runner(), True)
    clock = eng.step_clock
    nxt = E._InFlight([], [], 1, -1, 0.0, {})
    nxt.handle = object()

    def dispatch(plan, prev):
        clock.handles += 1
        return nxt

    def commit():
        raise RuntimeError("publish failed")

    eng._dispatch_decode, eng._commit_inflight = dispatch, commit
    plan = E.DecodePlan([], 1)
    with pytest.raises(RuntimeError):
        eng._step_decode(plan, "cold")
    assert clock.handles == 0 and eng._inflight is None


def test_recorder_off_turns_the_account_off():
    eng = _engine(_runner(), True, recorder_size=0)
    assert eng.step_clock is None
    toks, _, recs = _drive(eng, [{"rid": "a", "prompt": _prompt(6, 82), "n": 9}])
    assert len(toks["a"]) == 9 and recs == []


def test_a_stalled_iteration_logs_who_held_it():
    """The EWMA trigger fires once an excursion; the recorder's writer
    thread (never the step thread) logs one line with the record's phases,
    so an untraced run's log tells a blocked readback from a host phase, a
    collection or a compile. On billed time: the recorder is fed records
    whose walls are chosen, and the writer thread is waited for on an event
    its own log line sets."""
    import logging
    import threading

    from dynamo_tpu.runtime.flight_recorder import FlightRecorder
    from tests.test_flight_recorder import _rec

    class Lines(logging.Handler):
        def __init__(self):
            super().__init__(logging.WARNING)
            self.records, self.logged = [], threading.Event()

        def emit(self, record):
            if record.getMessage().startswith("stalled iteration"):
                self.records.append(record)
                self.logged.set()

    def rec(i, wall_s, readback, **over):
        host = {f"host_{p}_s": 0.0004 for p in HOST + ("deliver",)}
        host.update(host_readback_s=readback, exposed_s=0.0012, gc_s=0.0002)
        return _rec(i, wall_s=wall_s, **host, **over)

    fr = FlightRecorder(capacity=128, anomaly_k=3.0)
    rlog, seen = logging.getLogger("dynamo_tpu.flight_recorder"), Lines()
    level = rlog.level
    rlog.addHandler(seen)
    rlog.setLevel(logging.WARNING)
    try:
        # a steady decode loop, late enough that the trigger is armed ...
        for i in range(49):
            fr.append(rec(i, 0.010, 0.006, ahead=True))
        # ... one read-back that blocked half a second, and on it goes
        held = rec(49, 0.512, 0.5, drain="rows")
        fr.append(held)
        for i in range(50, 56):
            fr.append(rec(i, 0.010, 0.006, ahead=True))
        fired = [r for r in fr.snapshot() if r.anomaly]
        assert fired == [held] and fr.anomalies_fired == 1
        assert seen.logged.wait(120), "the writer thread logged nothing"
    finally:
        rlog.removeHandler(seen)
        rlog.setLevel(level)
    assert [r.threadName for r in seen.records] == ["flight-recorder-dump"]
    assert threading.current_thread().name != "flight-recorder-dump"
    line = seen.records[0].getMessage()
    assert held.host_readback_s >= 0.45
    assert f"seq={held.seq} " in line
    for key in ("kind=decode", "drain=rows", "wall_s=0.5120", "readback=",
                "exposed_s=0.0012", "gc_s=0.0002", "variants_grew=0") + tuple(
                    p + "=" for p in HOST):
        assert key in line, (key, line)
    assert f"readback={held.host_readback_s:.4f}" in line
