"""A request's decode time, by what the step loop was doing (engine._charge;
docs/observability.md "Per-request latency spine"): the loop's walls, which
run commit mark to commit mark, are charged by class of iteration
(flight_recorder.iteration_class) to monotone totals, and a stream takes the
differences between the mark of its first token and the mark of its last
onto its spine: `decode_s` and its six parts, and `decode_tokens`.

On billed time: `_loop_once` is driven by hand on the cost model of
tests/test_engine_deliver.py, the engine's and the scheduler's `time` is a
clock that only the runner's read-backs, its prefills and the idle sleep
move, each by a price of its own, so every wall is known to the nanosecond.
No sleep, no wall-clock bound."""

import types

import pytest

from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.engine import scheduler as scheduler_mod
from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.scheduler import Sequence
from dynamo_tpu.runtime import annotations
from dynamo_tpu.runtime.flight_recorder import (
    ITERATION_CLASSES,
    iteration_class,
)
from tests import test_engine_deliver as deliver
from tests.test_engine_deliver import Loop, Recording, Stream, _prompt

MS = 1_000_000  # ns
DECODE, MIXED, PREFILL, IDLE = 10 * MS, 30 * MS, 20 * MS, 5 * MS
PARTS = tuple(f"decode_{c}_s" for c in ITERATION_CLASSES)


class Clock:
    """The engine's `time`: moved by `advance` alone (and by `sleep`)."""

    def __init__(self):
        self.ns = 1_000 * 1_000 * MS

    def advance(self, ns):
        self.ns += ns

    def module(self):
        return types.SimpleNamespace(
            monotonic=lambda: self.ns * 1e-9,
            monotonic_ns=lambda: self.ns,
            time=lambda: 1.7e9 + self.ns * 1e-9,
            time_ns=lambda: int(1.7e18) + self.ns,
            sleep=lambda s: self.advance(int(s * 1e9)))


class Billed(Recording):
    """The recording cost model, which takes no time of its own, billing
    the clock: a decode read-back DECODE, a mixed step's MIXED, a prefill
    chunk PREFILL (its enqueue returns at once, as ModelRunner's does)."""

    def __init__(self, clock, fuses, **pool):
        super().__init__(**pool)
        self.clock, self.fuses = clock, fuses

    def prefill(self, *a, **k):
        self.clock.advance(PREFILL)
        return super().prefill(*a, **k)

    def decode_collect(self, h):
        self.clock.advance(DECODE)
        return super().decode_collect(h)

    def can_fuse(self, n_decode, n_chunks, *, constrained):
        return self.fuses and not constrained

    def mixed_collect(self, handle):
        self.clock.advance(MIXED)
        return super().mixed_collect(handle)


class Harness(deliver.Harness):
    """tests/test_engine_deliver.py's, on the billed runner and clock."""

    def __init__(self, monkeypatch, fuses=True, whole_steps=False, pool=None,
                 **kw):
        monkeypatch.setenv("DYN_FUSED_MIXED", "1")
        self.clock = Clock()
        fake = self.clock.module()
        monkeypatch.setattr(engine_mod, "time", fake)
        monkeypatch.setattr(scheduler_mod, "time", fake)
        self.runner = r = Billed(self.clock, fuses, **(pool or {}))
        if whole_steps:  # every call enqueue and read-back in one
            r.can_run_ahead = False
            r.decode_multi = lambda *a, **k: r.decode_collect(
                r.decode_dispatch(*a, **k))
            r.decode_multi_with_prefills = lambda *a, **k: r.mixed_collect(
                r.mixed_dispatch(*a, **k))
        args = dict(max_batch=4, chunk_size=8, decode_steps=4,
                    mixed_prefill_tokens=8 if fuses else 0,
                    idle_sleep_s=IDLE * 1e-9)
        args.update(kw)
        self.engine = InferenceEngine(self.runner, **args)
        self.events = r.events
        self.loop = Loop(self.events)
        self.streams, self.seqs, self.phases = {}, {}, []
        self.engine.on_phases(self.phases.append)
        annotations.bind_clock(self.engine.step_clock)
        monkeypatch.setattr(self.engine, "start", lambda: None)

    def add(self, rid, n, prompt=None, **extra):
        seq = Sequence(
            request_id=rid, prompt=prompt or _prompt(6, len(self.seqs) + 1),
            sampling={"temperature": 0.0},
            stop={"max_tokens": n, "ignore_eos": True},
            arrival=self.clock.ns * 1e-9, **extra)
        self.seqs[rid] = seq
        self.streams[rid] = Stream(rid)
        self.engine._streams[rid] = (self.streams[rid], self.loop)
        self.engine._inbox.put(("add", seq))
        return seq

    def spine(self, rid):
        return self.streams[rid][-1]["phases"]


@pytest.fixture
def make(monkeypatch):
    made = []

    def _make(**kw):
        made.append(Harness(monkeypatch, **kw))
        return made[-1]

    yield _make
    for h in made:
        h.close()


def _ns(ph, key):
    return round(ph[key] * 1e9)


def _adds_up(ph):
    """The six parts are differences of totals that tile the clock."""
    assert set(PARTS) < set(ph) and "decode_s" in ph, ph
    assert abs(sum(ph[k] for k in PARTS) - ph["decode_s"]) < 1e-9, ph
    assert sum(_ns(ph, k) for k in PARTS) == _ns(ph, "decode_s"), ph
    assert all(ph[k] >= 0.0 for k in PARTS), ph


# -- the rule ------------------------------------------------------------------


@pytest.mark.parametrize("kind, ahead, cls", [
    ("decode", True, "ahead"), ("decode", False, "cold"),
    ("mixed", False, "mixed"), ("mixed", True, "mixed"),
    ("prefill", False, "prefill"), ("prefill_packed", False, "prefill"),
    ("spec_verify", False, "other"), ("", False, "other")])
def test_the_rule_names_an_iterations_class(kind, ahead, cls):
    assert iteration_class(kind, ahead) == cls
    assert cls in ITERATION_CLASSES and "wait" in ITERATION_CLASSES


# -- one stream ----------------------------------------------------------------


def test_a_stream_alone_is_one_cold_decode_and_the_rest_ahead(make):
    """13 tokens: the first from the prompt, then three dispatches of four
    steps; the first of them found nothing in flight, the other two were
    enqueued ahead. Nothing of the prompt's own prefill is in the interval,
    which opens at the mark of the commit that queued the first token."""
    h = make()
    h.add("a", 13)
    h.run_out()
    ph = h.spine("a")
    _adds_up(ph)
    assert ph["decode_tokens"] == 13 == len(h.streams["a"].tokens)
    assert _ns(ph, "decode_cold_s") == DECODE
    assert _ns(ph, "decode_ahead_s") == 2 * DECODE
    assert _ns(ph, "decode_s") == 3 * DECODE
    for k in ("mixed", "prefill", "other", "wait"):
        assert ph[f"decode_{k}_s"] == 0.0, (k, ph)
    # the listeners heard the same spine, once
    assert h.phases == [ph]


def test_the_totals_tile_the_clock(make):
    """Everything the clock moved since the engine was made is in one of
    the six totals, the idle sleeps under `wait`."""
    h = make()
    t0 = h.engine._acct_ns
    h.run(2)  # idle
    h.add("a", 25)
    h.run(3)
    h.add("b", 5)
    h.run_out()
    h.run(3)
    tot = h.engine.class_ns
    assert sum(tot.values()) == h.engine._acct_ns - t0 == h.clock.ns - t0
    assert tot["wait"] >= 5 * IDLE and tot["mixed"] == MIXED
    assert tot["other"] == 0
    recs = h.engine.recorder.snapshot()
    by = dict.fromkeys(ITERATION_CLASSES, 0)
    for r in recs:
        by[iteration_class(r.kind, r.ahead)] += round(r.wall_s * 1e9)
    assert {c: by[c] for c in by if c != "wait"} == {
        c: tot[c] for c in tot if c != "wait"}


def test_a_one_group_stream_has_no_interval(make):
    """A request whose first token is its last has nothing to account."""
    h = make()
    h.add("a", 1)
    h.run_out()
    ph = h.spine("a")
    assert not [k for k in ph if k.startswith("decode_")], ph
    assert h.engine._spine_due == []


# -- another prompt is admitted ------------------------------------------------


def test_a_joiner_costs_the_decoding_stream_a_mixed_step(make):
    """`b` joins while `a` decodes, on a runner that fuses: the mixed step
    and the drained decodes around it are on `a`'s spine, by name; `b`,
    whose interval opens at the mixed step's mark, sat under none of it."""
    h = make()
    h.add("a", 25)
    h.run(4)  # prefill, a cold decode, two enqueued ahead
    h.add("b", 9)
    h.run_out()
    a, b = h.spine("a"), h.spine("b")
    _adds_up(a), _adds_up(b)
    assert a["decode_tokens"] == 25 and b["decode_tokens"] == 9
    assert _ns(a, "decode_mixed_s") == MIXED and a["decode_prefill_s"] == 0.0
    # nothing in flight at the start, and again after the mixed step
    assert _ns(a, "decode_cold_s") == 2 * DECODE
    assert _ns(a, "decode_s") == MIXED + 5 * DECODE
    assert b["decode_mixed_s"] == 0.0 == b["decode_prefill_s"]
    assert _ns(b, "decode_s") == 2 * DECODE  # 8 tokens: cold, ahead
    assert _ns(b, "decode_cold_s") == DECODE


def test_on_a_runner_that_does_not_fuse_it_is_a_prefill(make):
    """No mixed program (`mixed_prefill_tokens` 0): the joiner's chunk is a
    dispatch of its own, some other prompt's prefill."""
    h = make(fuses=False)
    h.add("a", 25)
    h.run(4)
    h.add("b", 9)
    h.run_out()
    a, b = h.spine("a"), h.spine("b")
    _adds_up(a), _adds_up(b)
    assert _ns(a, "decode_prefill_s") == PREFILL and a["decode_mixed_s"] == 0.0
    # every decode of the run lies in a's interval: the first, the one after
    # the chunk, and the one that shed b's row for a smaller bucket drained
    dec = [r for r in h.engine.recorder.snapshot() if r.kind == "decode"]
    assert [r.drain for r in dec if not r.ahead] == ["cold", "cold", "bucket"]
    assert _ns(a, "decode_cold_s") == 3 * DECODE
    assert _ns(a, "decode_ahead_s") == (len(dec) - 3) * DECODE
    assert b["decode_prefill_s"] == 0.0 == b["decode_mixed_s"]


def test_a_two_dispatch_mixed_step_closes_the_rows_it_finishes(make):
    """A mixed plan on a runner that cannot fuse it runs as a decode
    dispatch and the chunks after it, and the chunk's enqueue delivers the
    decode half's items: a row that half finished has its interval closed
    by then (at the half's own charge), under `mixed` like the record."""
    h = make()
    h.runner.fuses = False  # a mixed plan, served as two dispatches
    h.add("a", 13)
    h.run(3)  # prefill, cold decode in flight, one ahead: 5 tokens out
    assert len(h.streams["a"].tokens) == 5
    h.add("b", 5)
    h.run_out()
    recs = h.engine.recorder.snapshot()
    two = [r for r in recs if r.kind == "mixed"]
    assert two and not any(r.fused for r in two)
    a, b = h.spine("a"), h.spine("b")
    _adds_up(a), _adds_up(b)
    assert a["decode_tokens"] == 13 and b["decode_tokens"] == 5
    assert a["decode_mixed_s"] > 0.0 and a["decode_prefill_s"] == 0.0
    # the walls of the records are whole: the halves' charges add up to them
    assert h.engine.class_ns["mixed"] == sum(
        round(r.wall_s * 1e9) for r in two)


def test_the_idle_sleep_is_wait_and_in_no_decode_interval(make):
    h = make()
    h.run(3)
    assert h.engine.class_ns["wait"] == 3 * IDLE
    h.add("a", 9)
    h.run_out()
    assert h.spine("a")["decode_wait_s"] == 0.0


# -- preempted, aborted, forked ------------------------------------------------


def test_a_preempted_request_keeps_one_interval(make):
    """A pool too small for both: the younger is preempted and prefilled
    again; its interval still runs from its first token to its last, the
    recompute inside it like anyone's prefill."""
    h = make(fuses=False, pool=dict(num_pages=10, max_pages_per_seq=8),
             enable_prefix_cache=False)
    h.add("a", 24)
    h.add("b", 24)
    h.run_out()
    spines = [h.spine("a"), h.spine("b")]
    assert sorted(ph["preemptions"] for ph in spines) == [0, 1], spines
    for ph in spines:
        _adds_up(ph)
        assert ph["decode_tokens"] == 24
    hit = next(ph for ph in spines if ph["preemptions"])
    assert hit["decode_prefill_s"] + hit["decode_mixed_s"] > 0.0
    # one interval: it spans what the other decoded while it was out
    other = next(ph for ph in spines if not ph["preemptions"])
    assert hit["decode_s"] > other["decode_ahead_s"]
    assert len(h.phases) == 2


def test_an_aborted_request_carries_nothing_and_breaks_no_listener(make):
    h = make()
    h.add("a", 40)
    h.add("b", 13)
    h.run(4)
    h.engine._inbox.put(("abort", "a"))
    del h.engine._streams["a"]
    h.run_out()
    assert not any(it.get("finish_reason") for it in h.streams["a"])
    assert len(h.phases) == 1 and h.phases[0] is h.spine("b")
    _adds_up(h.spine("b"))
    assert h.engine._spine_due == []


def test_a_failed_step_closes_no_interval(make):
    """The error item goes out at once, before any mark: no decode keys."""
    h = make()
    h.add("a", 40)
    h.run(3)

    def boom(handle):
        raise RuntimeError("device lost")

    h.runner.decode_collect = boom
    h.run(2)
    last = h.streams["a"][-1]
    assert last["finish_reason"] == "error"
    assert not [k for k in last["phases"] if k.startswith("decode_")]


def test_a_forked_branch_accounts_for_itself(make):
    h = make()
    h.add("a", 9, n_branches=2)
    h.run_out()
    finals = [it for it in h.streams["a"] if it.get("finish_reason")]
    assert sorted(it["index"] for it in finals) == [0, 1]
    for it in finals:
        _adds_up(it["phases"])
        assert it["phases"]["decode_tokens"] == 9


# -- other loops ---------------------------------------------------------------


def test_a_whole_step_runner_closes_its_spines(make):
    """`can_run_ahead` False: nothing is ever enqueued ahead."""
    h = make(whole_steps=True)
    h.add("a", 13)
    h.run(2)
    h.add("b", 5)
    h.run_out()
    for rid, n in (("a", 13), ("b", 5)):
        ph = h.spine(rid)
        _adds_up(ph)
        assert ph["decode_tokens"] == n
        assert ph["decode_ahead_s"] == 0.0 and ph["decode_cold_s"] > 0.0
    assert _ns(h.spine("a"), "decode_mixed_s") == MIXED


def test_a_speculating_worker_closes_its_spines(make):
    """Drafts are proposed from host tokens, so every decode is drained
    (`drain` "spec"): verify iterations are `cold`, and the parts add up."""
    h = make(spec_ngram=True, spec_k=2)
    assert h.engine._spec_on
    h.add("a", 16, prompt=[4, 2] * 4)
    h.run(3)
    h.add("b", 6, prompt=[9, 8, 7, 1, 3])
    h.run_out()
    for rid, n in (("a", 16), ("b", 6)):
        ph = h.spine(rid)
        _adds_up(ph)
        assert ph["decode_tokens"] == n == len(h.streams[rid].tokens)
        assert ph["decode_ahead_s"] == 0.0 and ph["decode_other_s"] == 0.0
    assert h.engine.class_ns["ahead"] == 0


# -- where an operator sees it -------------------------------------------------


def _joined(make):
    h = make()
    h.add("a", 25, tp="00-" + "5a" * 16 + "-" + "6b" * 8 + "-01")
    h.run(4)
    h.add("b", 9)
    h.run_out()
    return h


def test_the_stream_span_says_what_it_sat_under(make):
    """The retroactive `worker.stream` span of a traced request carries
    the account as attributes."""
    from dynamo_tpu.runtime.tracing import MemorySpanExporter, set_exporter

    exp = MemorySpanExporter()
    set_exporter(exp)
    try:
        h = _joined(make)
    finally:
        set_exporter(None)
    stream = [s for s in exp.spans if s.name == "worker.stream"]
    assert len(stream) == 1
    ph, attrs = h.spine("a"), stream[0].attributes
    for k in PARTS + ("decode_s", "decode_tokens"):
        assert attrs[k] == ph[k], (k, attrs)
    assert attrs["decode_mixed_s"] > 0.0


async def test_metrics_carry_the_account_with_no_new_wiring(make):
    """worker_common._observe_phases takes every `_s` key of the spine as a
    phase of request_phase_seconds and every count as one of
    request_phase_count: the eight keys show without an edit to it."""
    import re

    from dynamo_tpu.frontend.protocols import ModelCard
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    h = _joined(make)

    class _Eng:
        listeners = []

        def on_kv_event(self, cb): pass
        def on_fpm(self, cb): pass
        def on_phases(self, cb): self.listeners.append(cb)

        async def generate(self, req, ctx):
            yield {"token_ids": [], "finish_reason": "stop"}

        def start(self): pass
        def stop(self): pass

    eng = _Eng()
    rt = DistributedRuntime(discovery=MemDiscovery(realm="decode-account"),
                            event_transport="inproc")
    try:
        w = await serve_worker(rt, eng, ModelCard(name="m"), digest_period_s=0,
                               publish_kv_events=False, publish_fpm=False)
        for cb in eng.listeners:
            for ph in h.phases:
                cb(ph)
        lines = rt.metrics.render().decode().splitlines()
        await w.stop()
    finally:
        await rt.shutdown(drain_timeout=1)

    def values(prefix):
        return {re.search(r'phase="([^"]+)"', ln).group(1):
                float(ln.rsplit(" ", 1)[1])
                for ln in lines if ln.startswith(prefix)}

    seconds = values("dynamo_request_phase_seconds_count{")
    for c in ITERATION_CLASSES:
        assert seconds[f"decode_{c}"] == 2.0, seconds
    assert seconds["decode"] == 2.0
    sums = values("dynamo_request_phase_seconds_sum{")
    assert sums["decode_mixed"] == pytest.approx(MIXED * 1e-9)
    counts = values("dynamo_request_phase_count_total{") or values(
        "dynamo_request_phase_count{")
    assert counts["decode_tokens"] == 25 + 9, counts
