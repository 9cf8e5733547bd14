"""Commit, enqueue, deliver (engine._deliver; docs/concurrency.md): at a
drain the step loop commits the dispatch that was in flight, enqueues the
next program and only then hands the committed tokens to their streams and
the iteration's publish to its observers, under that program; every item
of a delivery reaches its event loop in one call.

Driven by hand on the test's thread (`engine._loop_once()`), on a cost
model that fuses mixed plans and records the order of its enqueues and
read-backs, with stand-ins for the event loops that run a hand-off on the
spot and count it: the order of events is the clock."""

import asyncio
import time

import numpy as np
import pytest

from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.runner_api import MixedOut
from dynamo_tpu.engine.scheduler import Sequence
from dynamo_tpu.mocker.sim import SimRunner, SimTiming
from dynamo_tpu.runtime import annotations
from dynamo_tpu.runtime.context import Context

PAGE = 4


class Recording(SimRunner):
    """A SimRunner that takes no time, fuses mixed plans (the two halves
    the engine runs its delivery between) and whose prefill returns at its
    enqueue, as ModelRunner's does; `events` holds every enqueue and
    read-back in the order they happened, and the hand-offs the stand-in
    loops add."""

    prefill_enqueues = True

    def __init__(self, **pool):
        args = dict(num_pages=64, page_size=PAGE, max_pages_per_seq=16)
        args.update(pool)
        super().__init__(vocab_size=300, timing=SimTiming(speed=0.0), **args)
        self.events = []
        self.t_enqueue = []  # time.monotonic() of every enqueue
        self._n = 0

    def _enqueued(self, kind):
        self._n += 1
        self.events.append((kind, self._n))
        self.t_enqueue.append(time.monotonic())
        return self._n

    def prefill(self, *a, **k):
        self._enqueued("prefill")
        return super().prefill(*a, **k)

    def sample_one(self, *a, **k):
        self.events.append(("sample",))
        return super().sample_one(*a, **k)

    def decode_dispatch(self, *a, **k):
        h = super().decode_dispatch(*a, **k)
        h.n = self._enqueued("dispatch")
        return h

    def decode_collect(self, h):
        self.events.append(("collect", h.n))
        return super().decode_collect(h)

    def can_fuse(self, n_decode, n_chunks, *, constrained):
        return not constrained

    def mixed_dispatch(self, n_steps, tokens, positions, page_tables,
                       sampling, step, chunks, adapters=None, side=None):
        toks = self._decode_tokens(n_steps, tokens, positions, None, None,
                                   None, -1)
        rows = [("sim-logits", c["tokens"][-1], c["start"] + len(c["tokens"]))
                for c in chunks]
        return (self._enqueued("mixed_dispatch"), toks, rows)

    def mixed_collect(self, handle):
        n, toks, rows = handle
        self.events.append(("mixed_collect", n))
        return MixedOut(toks, rows, False)


class Loop:
    """Stands in for an event loop: runs a hand-off on the spot, counts it
    and notes what it carried (stream, tokens, finish reason)."""

    def __init__(self, events):
        self.events, self.calls, self.closed = events, 0, False

    def call_soon_threadsafe(self, cb, *args):
        if self.closed:
            raise RuntimeError("Event loop is closed")
        assert cb is engine_mod._put_all, cb
        self.calls += 1
        self.events.append(("handoff", [
            (out.rid, list(item["token_ids"]), item.get("finish_reason"))
            for out, item in args[0]]))
        cb(*args)


class Stream(list):
    """Stands in for a stream's asyncio.Queue."""

    def __init__(self, rid):
        super().__init__()
        self.rid = rid

    put_nowait = list.append

    @property
    def tokens(self):
        return [t for item in self for t in item["token_ids"]]


class Harness:
    def __init__(self, monkeypatch, whole_steps=False, **kw):
        monkeypatch.setenv("DYN_FUSED_MIXED", "1")
        self.runner = r = Recording()
        if whole_steps:  # every call enqueue and read-back in one
            r.can_run_ahead = False
            r.decode_multi = lambda *a, **k: SimRunner.decode_collect(
                r, SimRunner.decode_dispatch(r, *a, **k))
            r.decode_multi_with_prefills = lambda *a, **k: r.mixed_collect(
                r.mixed_dispatch(*a, **k))
        self.events = self.runner.events
        args = dict(max_batch=4, chunk_size=8, decode_steps=4,
                    mixed_prefill_tokens=8, idle_sleep_s=0.0)
        args.update(kw)
        self.engine = InferenceEngine(self.runner, **args)
        self.loop = Loop(self.events)
        self.streams = {}
        self.seqs = {}
        annotations.bind_clock(self.engine.step_clock)
        monkeypatch.setattr(self.engine, "start", lambda: None)

    def close(self):
        annotations.unbind_clock()

    def add(self, rid, n, prompt=None, loop=None, **stop):
        seq = Sequence(
            request_id=rid, prompt=prompt or _prompt(6, len(self.seqs) + 1),
            sampling={"temperature": 0.0},
            stop={"max_tokens": n, "ignore_eos": True, **stop},
            arrival=time.monotonic())
        self.seqs[rid] = seq
        self.streams[rid] = Stream(rid)
        self.engine._streams[rid] = (self.streams[rid], loop or self.loop)
        self.engine._inbox.put(("add", seq))
        return seq

    def run(self, n=1):
        for _ in range(n):
            self.events.append(("iteration",))
            self.engine._loop_once()

    def run_out(self, limit=200):
        """Iterate until every stream has finished and the loop has idled."""
        for _ in range(limit):
            self.run()
            if all(s and s[-1].get("finish_reason")
                   for s in self.streams.values()
                   if s.rid in self.engine._streams):
                self.run()  # (the idle pass)
                return
        raise AssertionError("streams did not finish")


@pytest.fixture
def h(monkeypatch):
    harness = Harness(monkeypatch)
    yield harness
    harness.close()


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(16, 200, n).tolist()


def _outstanding(events, upto):
    """Programs enqueued and not read back after events[:upto]. A prefill
    chunk is read back where its first token is sampled, or never."""
    n = 0
    for ev in events[:upto]:
        if ev[0] in ("dispatch", "mixed_dispatch"):
            n += 1
        elif ev[0] in ("collect", "mixed_collect"):
            n -= 1
    return n


# -- (a) the order at a drain --------------------------------------------------


def test_mixed_drain_enqueues_before_it_delivers(h):
    """A joiner makes a mixed plan while a decode dispatch is in flight:
    that dispatch is read back and committed, the mixed program enqueued,
    and only then do the committed tokens reach their queue, before the
    mixed program is read back."""
    h.add("a", 40)
    h.run(4)  # prefill, cold decode, two ahead
    assert h.engine._inflight is not None
    before = len(h.streams["a"].tokens)
    h.add("b", 8)
    mark = len(h.events)
    h.run()
    ev = [e for e in h.events[mark:] if e[0] != "iteration"]
    kinds = [e[0] for e in ev]
    assert kinds == ["collect", "mixed_dispatch", "handoff", "mixed_collect",
                     "sample", "handoff"], ev
    # the hand-off under the mixed program is the drained dispatch's four
    # tokens, nothing of the mixed step's
    assert ev[2][1] == [("a", h.streams["a"].tokens[before:before + 4], None)]
    assert len(h.streams["a"].tokens) == before + 4
    # of the mixed step's own, the joiner's first token goes out where it
    # was committed (TTFT waits for no staging); a's next four and the
    # publish wait for the next enqueue: the cold decode that follows
    assert [(rid, len(t), f) for rid, t, f in ev[5][1]] == [("b", 1, None)]
    assert [e[0].request_id for e in h.engine._undelivered
            if e[0] is not None] == ["a"]
    assert len(h.engine._undelivered) == 2  # a's item and the publish
    mark = len(h.events)
    h.run()
    ev = [e for e in h.events[mark:] if e[0] != "iteration"]
    assert [e[0] for e in ev] == ["dispatch", "handoff"], ev
    assert [(rid, len(t), f) for rid, t, f in ev[1][1]] == [("a", 4, None)]
    assert h.engine._undelivered == []


def test_every_delivery_but_the_idle_ones_runs_under_a_program(h):
    """Over a run with joiners: whenever items reach a queue a program is
    enqueued and not read back, except where the loop had nothing left to
    enqueue (it delivers before it idles), and the records say which."""
    h.add("a", 30)
    h.run(3)
    h.add("b", 9)
    h.run(2)
    h.add("c", 5)
    h.run_out()
    def first_token(batch):  # (those go out where they are committed)
        return all(len(toks) == 1 and rid != "a" for rid, toks, _ in batch)

    hand = [i for i, e in enumerate(h.events)
            if e[0] == "handoff" and not (i > 3 and first_token(e[1]))]
    bare = [i for i in hand if _outstanding(h.events, i) == 0]
    # only a delivery right before an idle pass has nothing over it
    for i in bare:
        rest = [e[0] for e in h.events[i + 1:] if e[0] != "handoff"]
        assert rest[:1] in ([], ["iteration"]), (i, h.events[i:i + 4])
    assert len(bare) <= 2 and len(hand) - len(bare) >= 6
    recs = h.engine.recorder.snapshot()
    assert recs and sum(r.deliver_under for r in recs) >= len(recs) - 2
    assert not recs[-1].deliver_under  # the last one: nothing left to enqueue
    assert all(r.host_deliver_s >= 0.0 for r in recs)
    assert sum(r.host_deliver_s for r in recs) > 0.0
    for rid, n in (("a", 30), ("b", 9), ("c", 5)):
        assert len(h.streams[rid].tokens) == n


def test_prefill_alone_delivers_under_the_chunk(monkeypatch):
    """No fused mixed step (a joiner's chunk is a dispatch of its own):
    the drained decode's tokens go out once the chunk is enqueued, before
    its first token is sampled and read."""
    h = Harness(monkeypatch, mixed_prefill_tokens=0)
    try:
        h.add("a", 40)
        h.run(4)
        h.add("b", 8)
        mark = len(h.events)
        h.run()
        kinds = [e[0] for e in h.events[mark:] if e[0] != "iteration"]
        assert kinds == ["collect", "prefill", "handoff", "sample",
                         "handoff"], kinds
        hand = [e[1] for e in h.events[mark:] if e[0] == "handoff"]
        assert [rid for rid, _, _ in hand[0]] == ["a"]  # under the chunk
        assert [(rid, len(t)) for rid, t, _ in hand[1]] == [("b", 1)]
    finally:
        h.close()


def test_a_prefill_that_returns_when_done_gets_its_delivery_first(monkeypatch):
    """A cost model's prefill sleeps its chunk out (`prefill_enqueues`
    False): nothing waits across it."""
    h = Harness(monkeypatch, mixed_prefill_tokens=0)
    h.runner.prefill_enqueues = False
    try:
        h.add("a", 40)
        h.run(4)
        h.add("b", 8)
        mark = len(h.events)
        h.run()
        kinds = [e[0] for e in h.events[mark:] if e[0] != "iteration"]
        assert kinds == ["collect", "handoff", "prefill", "sample",
                         "handoff"], kinds
    finally:
        h.close()


def test_a_runner_of_whole_steps_delivers_at_the_commit(monkeypatch):
    """`can_run_ahead` False: every call is enqueue and read-back in one,
    so a commit's items go out at once, as they always did."""
    h = Harness(monkeypatch, whole_steps=True, mixed_prefill_tokens=0)
    try:
        h.add("a", 20)
        for _ in range(4):
            h.run()
            assert h.engine._undelivered == []
        assert len(h.streams["a"].tokens) == 1 + 3 * 4
    finally:
        h.close()


# -- (b) order within a stream -------------------------------------------------


def _in_order(stream, want_n, finish):
    items = list(stream)
    fins = [i for i, it in enumerate(items) if it.get("finish_reason")]
    assert fins == [len(items) - 1], (stream.rid, fins, len(items))
    assert items[-1]["finish_reason"] == finish
    assert len(stream.tokens) == want_n, (stream.rid, len(stream.tokens))


def test_streams_keep_commit_order_and_finish_last(monkeypatch):
    """Joiners, a stop token inside a 4-step dispatch, a length that ends
    mid-dispatch: each stream equals the one a serial engine (every item
    delivered at its commit) produces, and its finish item is its last."""
    def serve(whole_steps, **stop):
        h = Harness(monkeypatch, whole_steps=whole_steps)
        try:
            h.add("a", 30, prompt=_prompt(6, 1), **stop)
            h.run(3)
            h.add("b", 10, prompt=_prompt(7, 2))
            h.run(2)
            h.add("c", 7, prompt=_prompt(5, 3))
            h.run_out()
            return h
        finally:
            h.close()

    a_toks = serve(False).streams["a"].tokens
    # a token first seen inside a dispatch, a few iterations in
    k = next(i for i in range(6, 25)
             if a_toks[i] not in a_toks[:i] and (i - 1) % 4 in (1, 2))
    stop = dict(stop_ids=[a_toks[k]], ignore_eos=False)
    ha, hb = serve(False, **stop), serve(True, **stop)
    _in_order(ha.streams["a"], k, "stop")
    _in_order(ha.streams["b"], 10, "length")
    _in_order(ha.streams["c"], 7, "length")
    for rid in "abc":
        assert ha.streams[rid].tokens == hb.streams[rid].tokens, rid
    assert ha.engine._undelivered == [] and hb.engine._undelivered == []
    assert ha.engine.pool.n_free == hb.engine.pool.n_free == 64


def test_abort_between_commit_and_delivery(h):
    """A stream that went away (its consumer cancelled: `generate` pops the
    stream and queues the abort) after its tokens were committed and before
    they were delivered: they are dropped, nothing raises, its pages go
    back, and the other streams get theirs."""
    h.add("a", 40)
    h.run(4)
    h.add("b", 12)
    h.run()  # the mixed step: its rows' items wait for the next enqueue
    pending = [e for e in h.engine._undelivered if e[0] is not None]
    assert {e[0].request_id for e in pending} == {"a"}
    n_a = len(h.streams["a"])
    h.engine._streams.pop("a")
    h.engine._inbox.put(("abort", "a"))
    h.run(3)
    assert len(h.streams["a"]) == n_a  # nothing reached the dead stream
    assert "a" not in {s.request_id for s in h.engine.scheduler.active}
    del h.streams["a"]
    h.run_out()
    _in_order(h.streams["b"], 12, "length")
    assert h.engine._undelivered == [] and h.engine.pool.n_free == 64


def test_failed_step_says_error_last(h):
    """The enqueue of a decode dispatch raises with one in flight: that
    one's tokens were computed and reach the stream first; the error item
    is the stream's last, and nothing stays pending."""
    real, calls = h.runner.decode_dispatch, {"n": 0}

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("injected")
        return real(*a, **k)

    h.runner.decode_dispatch = flaky
    h.add("a", 40)
    h.run(5)
    _in_order(h.streams["a"], 9, "error")
    assert h.engine._undelivered == [] and h.engine._inflight is None
    assert h.engine.pool.n_free == 64


def test_failed_mixed_step_fails_its_rows_behind_what_they_were_owed(h):
    """The mixed program's enqueue raises at a drain: the drained
    dispatch's tokens still come first, then the error items."""
    h.add("a", 40)
    h.run(4)
    n = len(h.streams["a"].tokens)

    def boom(*a, **k):
        raise RuntimeError("injected")

    h.runner.mixed_dispatch = boom
    h.add("b", 8)
    h.run(2)
    _in_order(h.streams["a"], n + 4, "error")
    _in_order(h.streams["b"], 0, "error")
    assert h.engine._undelivered == []


def test_a_first_token_never_overtakes_its_stream(h):
    """The first tokens that go out at their commit are those with nothing
    of their stream queued before them; what stays keeps its order."""
    x, y = h.add("x", 8), h.add("y", 8)
    h.engine._inbox.get_nowait(), h.engine._inbox.get_nowait()  # (not served)
    h.engine._emit_item(x, {"token_ids": [], "finish_reason": None})  # a chunk's note
    h.engine._emit(x, [5], None)
    h.engine._emit(y, [7], None)
    h.engine._deliver_first_tokens()
    assert [i["token_ids"] for i in h.streams["y"]] == [[7]]
    assert list(h.streams["x"]) == [] and len(h.engine._undelivered) == 2
    assert "ttft_s" in y.phases and "ttft_s" not in x.phases
    h.engine._emit(y, [9], None)  # not a first token any more: it waits
    h.engine._deliver_first_tokens()
    assert [i["token_ids"] for i in h.streams["y"]] == [[7]]
    h.engine._deliver()
    assert [i["token_ids"] for i in h.streams["x"]] == [[], [5]]
    assert [i["token_ids"] for i in h.streams["y"]] == [[7], [9]]


# -- (c) nothing pending across a wait nothing covers ---------------------------


def test_nothing_is_pending_when_the_loop_sleeps(h, monkeypatch):
    seen = []
    real = time.sleep

    def sleep(s):
        seen.append(list(h.engine._undelivered))
        real(0)

    monkeypatch.setattr(time, "sleep", sleep)
    h.add("a", 6)
    h.run_out()
    h.run(2)
    assert seen and all(p == [] for p in seen)
    _in_order(h.streams["a"], 6, "length")
    # the idle pass delivered the last iteration's publish as well
    recs = h.engine.recorder.snapshot()
    assert [r.kind for r in recs] == ["prefill", "decode", "decode"]


def test_nothing_is_pending_after_fail_everything(h):
    h.add("a", 40)
    h.run(4)
    h.add("b", 12)
    h.run()  # the mixed step's items are pending
    assert h.engine._undelivered
    h.engine._fail_everything("worker group broken: test")
    assert h.engine._undelivered == []
    for rid in "ab":
        items = list(h.streams[rid])
        assert [i.get("finish_reason") for i in items].count("error") == 1
        assert items[-1]["finish_reason"] == "error"
    # what the mixed step had committed came before the error
    assert len(h.streams["a"].tokens) == 1 + 4 * 4 and len(
        h.streams["b"].tokens) == 1


def test_nothing_is_pending_after_stop():
    """A started engine, stopped mid-stream: its thread commits what was
    in flight and delivers it; stop() leaves nothing behind."""
    runner = Recording()
    engine = InferenceEngine(runner, max_batch=4, chunk_size=8,
                             decode_steps=4, mixed_prefill_tokens=0)
    got = []

    async def go():
        ctx = Context()
        async for item in engine.generate(
                {"token_ids": _prompt(6, 5),
                 "sampling": {"temperature": 0.0},
                 "stop": {"max_tokens": 4000, "ignore_eos": True}}, ctx):
            got.extend(item["token_ids"])
            if len(got) >= 21:
                break

    engine.start()
    try:
        asyncio.run(go())
    finally:
        engine.stop()
    assert engine._undelivered == [] and engine._inflight is None
    assert len(got) >= 21


def test_a_closed_loop_loses_its_items_and_nothing_else(h):
    """One stream's event loop is gone: its hand-off is dropped; the other
    loop's streams are served and the step thread lives."""
    dead = Loop(h.events)
    h.add("a", 12)
    h.add("b", 12, loop=dead)
    h.run(3)
    dead.closed = True
    h.engine._streams.pop("b")  # (and its stream with it, as generate does)
    h.engine._inbox.put(("abort", "b"))
    del h.streams["b"]
    h.run_out()
    _in_order(h.streams["a"], 12, "length")


# -- (d) one hand-off a loop an iteration ----------------------------------------


def test_one_hand_off_a_loop_an_iteration(h):
    """Four rows on two event loops: each iteration wakes each loop once
    with all of its rows' items, in commit order, however many rows."""
    other = Loop(h.events)
    for i, rid in enumerate("abcd"):
        h.add(rid, 40, loop=other if i % 2 else None)
    h.run(6)  # the prompts' chunks, then all four decode
    assert len(h.engine.scheduler.active) == 4 and all(
        s.tokens for s in h.streams.values())
    for _ in range(4):
        a, b = h.loop.calls, other.calls
        mark = len(h.events)
        h.run()
        assert (h.loop.calls - a, other.calls - b) == (1, 1)
        hand = [e[1] for e in h.events[mark:] if e[0] == "handoff"]
        assert sorted(rid for batch in hand for rid, _, _ in batch) == list(
            "abcd")
        assert all(len(toks) == 4 for batch in hand for _, toks, _ in batch)
    h.run_out()
    iterations = sum(1 for e in h.events if e[0] == "iteration")
    assert h.loop.calls <= iterations and other.calls <= iterations
    for rid in "abcd":
        _in_order(h.streams[rid], 40, "length")


def test_token_items_leave_through_the_batched_hand_off_alone():
    """No per-item wake-up is left in the engine: every
    call_soon_threadsafe that is not a future's answer is the hand-off."""
    import inspect
    import re

    src = inspect.getsource(engine_mod)
    calls = re.findall(r"call_soon_threadsafe\(\s*([\w.]+)", src)
    assert calls.count("_put_all") == 1
    assert not [c for c in calls if "put" in c and c != "_put_all"], calls


# -- (e) the latency spine ---------------------------------------------------------


def test_spine_is_stamped_at_delivery(h):
    """ttft_s = queue_wait_s + kv_onboard_s + prefill_s as ever, and a
    token's stamp is taken where it is delivered: after the enqueue that
    followed its commit, not before it."""
    a = h.add("a", 24)
    h.run(4)
    b = h.add("b", 9)
    h.run()  # the drain: a's four tokens went out under the mixed program
    t_mixed = h.runner.t_enqueue[-1]
    assert h.events[-5][0] == "mixed_dispatch"
    assert a.t_last_emit >= t_mixed
    n_itl = len(a.itl)
    # b's first token went out where it was committed: TTFT is stamped
    assert b.phases["ttft_s"] > 0.0 and b.t_last_emit >= t_mixed
    h.run()  # the cold decode: a's next four go out under it
    assert a.t_last_emit >= h.runner.t_enqueue[-1] > t_mixed
    assert len(a.itl) == n_itl + 4
    h.run_out()
    for seq, n in ((a, 24), (b, 9)):
        final = h.streams[seq.request_id][-1]
        ph = final["phases"]
        assert ph["ttft_s"] == pytest.approx(
            ph["queue_wait_s"] + ph.get("kv_onboard_s", 0.0) + ph["prefill_s"],
            abs=1e-9)
        # one ITL sample a token after the first, stamped at deliveries:
        # together they span first delivery to last
        assert len(ph["itl_s"]) == n - 1
        assert ph["e2e_s"] >= ph["ttft_s"] + sum(ph["itl_s"]) - 1e-6
        assert sum(ph["itl_s"]) == pytest.approx(
            seq.t_last_emit - seq.arrival - ph["ttft_s"], abs=1e-6)
