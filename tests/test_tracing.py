"""Distributed tracing: W3C traceparent propagation + spans across the
serving pipeline — one trace id covers the frontend root and the disagg
prefill and decode worker hops (reference lib/runtime/src/logging.rs:76-105
span export + propagation; migration.rs TraceLink)."""

import asyncio
import threading
import time

import pytest

from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.tracing import (
    MemorySpanExporter,
    OtlpSpanExporter,
    parse_traceparent,
    set_exporter,
)


@pytest.fixture
def mem_spans():
    exp = MemorySpanExporter()
    set_exporter(exp)
    yield exp
    set_exporter(None)


def test_traceparent_parse_and_format():
    ctx = parse_traceparent("00-" + "ab" * 16 + "-" + "cd" * 8 + "-01")
    assert ctx.trace_id == "ab" * 16 and ctx.span_id == "cd" * 8
    assert ctx.traceparent == "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    assert parse_traceparent(None) is None
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("00-" + "0" * 32 + "-" + "cd" * 8 + "-01") is None


def test_span_parenting_and_error(mem_spans):
    with tracing.span("root") as root:
        with tracing.span("child", parent=root.traceparent) as child:
            child.set_attribute("k", 1)
        with pytest.raises(ValueError):
            with tracing.span("bad", parent=root.traceparent):
                raise ValueError("boom")
    spans = {s.name: s for s in mem_spans.spans}
    assert spans["child"].context.trace_id == spans["root"].context.trace_id
    assert spans["child"].parent_span_id == spans["root"].context.span_id
    assert spans["root"].parent_span_id is None
    assert spans["bad"].status_error and "boom" in spans["bad"].status_error
    assert spans["child"].end_ns >= spans["child"].start_ns


def test_disabled_tracing_is_noop_but_forwards():
    set_exporter(None)
    md = {"traceparent": "00-" + "11" * 16 + "-" + "22" * 8 + "-01"}
    with tracing.span("x", parent=md["traceparent"]) as s:
        tracing.child_traceparent(md, s)
    # no exporter: metadata untouched so downstream tracers still connect
    assert md["traceparent"].startswith("00-" + "11" * 16)


def test_otlp_wire_format():
    exp = OtlpSpanExporter.__new__(OtlpSpanExporter)  # no thread
    from dynamo_tpu.runtime.tracing import Span, SpanContext

    s = Span(name="n", context=SpanContext("a" * 32, "b" * 16),
             parent_span_id="c" * 16, start_ns=1, end_ns=2, kind=2,
             attributes={"i": 3, "f": 1.5, "b": True, "s": "x"})
    s.record_error("bad")
    w = exp._wire(s)
    assert w["traceId"] == "a" * 32 and w["parentSpanId"] == "c" * 16
    assert w["kind"] == 2  # OTLP SERVER
    attrs = {a["key"]: a["value"] for a in w["attributes"]}
    assert attrs["i"] == {"intValue": "3"}
    assert attrs["b"] == {"boolValue": True}
    assert w["status"]["code"] == 2


def test_otlp_wire_span_events():
    """Phase marks ride OTLP span events with nanosecond stamps."""
    exp = OtlpSpanExporter.__new__(OtlpSpanExporter)  # no thread
    from dynamo_tpu.runtime.tracing import Span, SpanContext

    s = Span(name="n", context=SpanContext("a" * 32, "b" * 16),
             parent_span_id=None, start_ns=1, end_ns=2)
    s.add_event("phase.ttft_s", {"seconds": 0.25})
    s.add_event("migration", {"attempt": 1})
    w = exp._wire(s)
    assert [e["name"] for e in w["events"]] == ["phase.ttft_s", "migration"]
    ev = w["events"][0]
    assert int(ev["timeUnixNano"]) > 0
    assert ev["attributes"] == [
        {"key": "seconds", "value": {"doubleValue": 0.25}}]


def test_otlp_exporter_bounded_queue_and_flush():
    """The span queue is the memory ceiling: overflow drops (counted, not
    raised), and flush() drains within its bound — here via a stubbed
    queue so no exporter thread or network is involved."""
    import queue as queue_mod

    from dynamo_tpu.runtime.tracing import Span, SpanContext, flush_tracing

    exp = OtlpSpanExporter.__new__(OtlpSpanExporter)  # no thread
    exp._q = queue_mod.Queue(maxsize=2)
    exp.dropped = 0
    exp._inflight = 0
    mk = lambda i: Span(name=f"s{i}", context=SpanContext("a" * 32, "b" * 16),
                        parent_span_id=None, start_ns=1, end_ns=2)
    for i in range(5):
        exp.export(mk(i))
    assert exp._q.qsize() == 2 and exp.dropped == 3
    # queue still holding spans and nothing consuming: flush times out
    assert exp.flush(timeout_s=0.1) is False
    while not exp._q.empty():
        exp._q.get_nowait()
    assert exp.flush(timeout_s=0.1) is True
    # inflight batch also blocks the drain until the POST completes
    exp._inflight = 2
    assert exp.flush(timeout_s=0.1) is False
    exp._inflight = 0
    assert exp.flush(timeout_s=0.1) is True
    # module-level flush: True with no exporter, delegates otherwise
    set_exporter(None)
    assert flush_tracing(0.1) is True
    set_exporter(exp)
    try:
        assert flush_tracing(0.1) is True
    finally:
        set_exporter(None)


# -- e2e: one trace across disagg prefill + decode hops ---------------------


async def test_single_trace_spans_disagg_request(mem_spans):
    from dynamo_tpu.bench.goodput import boot_stack, parse_args
    from dynamo_tpu.runtime.context import Context

    args = parse_args([
        "--model", "tiny", "--num-pages", "64", "--page-size", "4",
        "--max-pages-per-seq", "8", "--max-batch", "4", "--chunk-size", "16",
        "--decode-buckets", "1", "2", "4",
        "--prefill-buckets", "8", "16", "32",
        "--disagg-min-prefill-tokens", "8",
    ])
    stack = await boot_stack(args, disagg=True)
    try:
        caller = "00-" + "77" * 16 + "-" + "88" * 8 + "-01"
        ctx = Context(metadata={"model": "tiny", "traceparent": caller})
        req = {
            "token_ids": list(range(40, 56)),  # 16 >= disagg threshold
            "sampling": {"temperature": 0.0},
            "stop": {"max_tokens": 4, "stop_ids": [], "ignore_eos": True},
        }
        out = []
        async for item in stack.entry.chain.generate(req, ctx):
            out.extend(item.get("token_ids") or [])
            if item.get("finish_reason"):
                break
        assert out
    finally:
        await stack.close()

    # background control-plane RPCs (e.g. the router's kv_state resync)
    # legitimately start their own traces — the request's hops must all
    # land in the CALLER's trace
    spans = [s for s in mem_spans.spans if s.context.trace_id == "77" * 16]
    request_names = {s.name for s in mem_spans.spans} - {
        s.name for s in spans}
    assert all("kv_state" in n for n in request_names), \
        f"request-path span escaped the trace: {request_names}"
    names = [s.name for s in spans]
    root = next(s for s in spans if s.name == "frontend.request")
    assert root.parent_span_id == "88" * 8  # continues the caller's span
    prefill = [s for s in spans if "prefill" in s.name]
    decode = [s for s in spans if "decode" in s.name]
    assert prefill and decode, f"need prefill+decode hops, got {names}"
    # every hop hangs off the frontend root through an unbroken parent
    # chain (root -> route.* -> rpc / worker.request -> worker.*): no
    # orphans, no flat siblings pretending to be causality
    by_id = {s.context.span_id: s for s in spans}

    def _reaches_root(s, hops=0):
        if s is root:
            return True
        parent = by_id.get(s.parent_span_id)
        return (parent is not None and hops < 8
                and _reaches_root(parent, hops + 1))

    orphans = [s.name for s in spans if not _reaches_root(s)]
    assert not orphans, f"spans not connected to the root: {orphans}"
    # the route hop sits between the frontend root and the worker hops
    assert any(s.name.startswith("route.") for s in spans), names


async def test_migration_attempt_recorded(mem_spans):
    from dynamo_tpu.frontend.migration import Migration
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.runtime.request_plane import RequestPlaneError

    class Flaky:
        calls = 0

        async def generate(self, request, context):
            Flaky.calls += 1
            if Flaky.calls == 1:
                raise RequestPlaneError("gone", code="disconnected")
                yield
            yield {"token_ids": [1], "finish_reason": "stop"}

    mig = Migration(Flaky(), migration_limit=2)
    out = []
    async for item in mig.generate({"token_ids": [5], "stop": {}}, Context()):
        out.append(item)
    root = next(s for s in mem_spans.spans if s.name == "frontend.request")
    assert root.attributes.get("migration.attempts") == 1


def test_trace_annotations_gate(monkeypatch):
    """NVTX-analog ranges (runtime/annotations.py): no-op context when the
    env gate is off; real jax TraceAnnotation when on."""
    import contextlib

    from dynamo_tpu.runtime import annotations as ann

    monkeypatch.delenv("DYN_ENABLE_JAX_TRACE", raising=False)
    ann._enabled.cache_clear()
    cm = ann.annotate("x", n=1)
    assert isinstance(cm, contextlib.nullcontext)
    # one shared object, whatever the name and arguments: a span that is
    # off allocates nothing per call
    assert cm is ann.annotate("engine.dispatch", family="ragged") is ann._NULL

    monkeypatch.setenv("DYN_ENABLE_JAX_TRACE", "1")
    ann._enabled.cache_clear()
    try:
        with ann.annotate("engine.decode", batch=2):  # must not raise on CPU
            with ann.annotate("engine.prep"):  # children nest
                pass
        assert ann.annotate("engine.emit") is not ann._NULL
    finally:
        ann._enabled.cache_clear()


# -- the door: the step thread's phases (runtime/annotations.py) -------------
@pytest.fixture
def door(monkeypatch):
    """The annotations module with the gate off and a clock bound to this
    thread; unbound again after."""
    from dynamo_tpu.runtime import annotations as ann

    monkeypatch.delenv("DYN_ENABLE_JAX_TRACE", raising=False)
    ann._enabled.cache_clear()
    clock = ann.StepClock()
    ann.bind_clock(clock)
    try:
        yield ann, clock
    finally:
        ann.unbind_clock()
        ann._enabled.cache_clear()


def _iteration(ann):
    """One decode iteration's worth of phases, as the step loop opens them."""
    with ann.phase(ann.INBOX):
        pass
    with ann.phase(ann.SCHEDULE, waiting=3, running=5):
        pass
    with ann.phase(ann.PREP):
        pass
    with ann.phase(ann.STAGE):
        pass
    with ann.phase(ann.DISPATCH, family="decode_loop"):
        pass
    with ann.phase(ann.READBACK):
        pass
    with ann.phase(ann.EMIT):
        pass
    with ann.phase(ann.PUBLISH):
        pass
    with ann.phase(ann.DELIVER):
        pass


def _blank_record():
    from dynamo_tpu.runtime.flight_recorder import IterationRecord

    return IterationRecord(
        seq=0, ts=0.0, wall_s=0.0, kind="decode", decode_seqs=1,
        decode_steps=1, n_chunks=0, chunk_tokens=0, charged_tokens=0,
        ragged=False, fused=False, n_waiting=0, n_running=1, kv_usage=0.0,
        g2_blocks=0, g3_blocks=0, prefetch_hits=0, compile_variants=0)


def test_door_gate_off_allocates_nothing(door):
    import tracemalloc

    ann, clock = door
    tracemalloc.start()
    try:
        for _ in range(50):  # the slots' first big ints, the kwargs' dict
            _iteration(ann)
        before = tracemalloc.get_traced_memory()[0]
        for _ in range(1250):  # 11,250 phases
            _iteration(ann)
        after = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert after - before <= 256, after - before  # nothing a call: a constant
    assert all(n > 0 for n in clock.ns[:ann.WAIT])
    # each call site gets the one object its clock made for the phase
    assert ann.phase(ann.STAGE) is ann.phase(ann.STAGE) is clock._phases[ann.STAGE]
    # and no call's metadata is kept alive until the next (a dict that
    # outlives a young collection is promoted, which full collections count)
    assert all(p.kw is None for p in clock._phases)


def test_door_costs_under_20us_an_iteration(door):
    from dynamo_tpu.runtime.flight_recorder import FlightRecorder

    ann, clock = door
    rec, fr = _blank_record(), FlightRecorder(8)
    null, mono = ann._NULL, time.monotonic_ns

    def account():
        # eight phases, the cut and the record's fields: the whole account
        # of an iteration
        _iteration(ann)
        clock.cut(mono())
        fr.take_clock(rec, clock)

    def yardstick():
        # what the account is made of, bare: nine context entries and
        # twenty clock reads. It slows with the machine as the door does.
        for _ in range(9):
            with null:
                pass
        for _ in range(20):
            mono()

    def best(fn):
        per = []
        for _ in range(31):
            t0 = time.perf_counter()
            for _ in range(200):
                fn()
            per.append((time.perf_counter() - t0) / 200)
        return min(per)

    door_s, bare_s = best(account), best(yardstick)
    # 9 us against 3.4 us on the sandbox; a loaded runner moves both, so
    # the bound that can fail is the ratio
    assert door_s < 20e-6 or door_s < 6 * bare_s, (door_s, bare_s)


def test_door_without_a_clock_is_annotate(monkeypatch):
    """A thread with no clock bound (a warm-up walk, a script, another
    replica's builder): phase() is annotate(), nothing is accounted."""
    from dynamo_tpu.runtime import annotations as ann

    monkeypatch.delenv("DYN_ENABLE_JAX_TRACE", raising=False)
    ann._enabled.cache_clear()
    ann.unbind_clock()
    elsewhere = ann.StepClock()
    seen = []
    t = threading.Thread(target=lambda: (
        ann.bind_clock(elsewhere), seen.append(ann.phase(ann.STAGE))))
    t.start()
    t.join()
    assert seen == [elsewhere._phases[ann.STAGE]]  # bound there, not here
    for i in range(len(ann.PHASES)):
        assert ann.phase(i) is ann._NULL
        assert ann.phase(i, family="x") is ann._NULL
    with ann.phase(ann.DISPATCH):
        ann.synced()
    assert elsewhere.ns == [0] * len(ann.PHASES) and not elsewhere.serial
    monkeypatch.setenv("DYN_ENABLE_JAX_TRACE", "1")
    ann._enabled.cache_clear()
    opened = []
    monkeypatch.setattr(ann, "_trace_annotation",
                        lambda name, **kw: opened.append((name, kw)) or ann._NULL)
    try:
        with ann.phase(ann.DISPATCH, family="ragged"):
            pass
    finally:
        ann._enabled.cache_clear()
    assert opened == [("engine.dispatch", {"family": "ragged"})]


def test_door_gate_on_opens_the_names_it_accounts(door, monkeypatch):
    """With the profiler gate on the door opens a TraceAnnotation (here a
    recording stand-in) under the very name it accounts the seconds to, in
    order, nested as the spans nest, with the call's metadata."""
    import contextlib

    ann, clock = door
    events = []

    def stand_in(name, **kw):
        @contextlib.contextmanager
        def span():
            events.append(("B", name, kw))
            try:
                yield
            finally:
                events.append(("E", name, kw))
        return span()

    monkeypatch.setenv("DYN_ENABLE_JAX_TRACE", "1")
    ann._enabled.cache_clear()
    monkeypatch.setattr(ann, "_trace_annotation", stand_in)
    ticks = iter(range(0, 10**6, 10))
    monkeypatch.setattr(ann, "_clock", lambda: next(ticks))
    _iteration(ann)
    with ann.phase(ann.EMIT):  # a first token sampled inside emit
        with ann.phase(ann.DISPATCH, family="sample"):
            pass
        with ann.phase(ann.READBACK):
            pass
    opened = [n for ev, n, _ in events if ev == "B"]
    assert opened == ["engine." + p for p in (
        "inbox", "schedule", "prep", "stage", "dispatch", "readback", "emit",
        "publish", "deliver", "emit", "dispatch", "readback")]
    assert [n for ev, n, _ in events[-6:]] == [
        "engine.emit", "engine.dispatch", "engine.dispatch",
        "engine.readback", "engine.readback", "engine.emit"]
    kw = {n: k for ev, n, k in events if ev == "B" and k}
    assert kw == {"engine.schedule": {"waiting": 3, "running": 5},
                  "engine.dispatch": {"family": "sample"}}
    # the names accounted are the names opened: every phase that opened a
    # span holds seconds, the others none
    held = {"engine." + p for p, n in zip(ann.PHASES, clock.ns) if n}
    assert held == set(opened)
    # the fake clock ticks 10 ns a read, two reads a phase: the innermost
    # owns, so emit keeps 10 + 3 x 10 around its two children's 10 each
    assert clock.ns[ann.DISPATCH] == 20 and clock.ns[ann.READBACK] == 20
    assert clock.ns[ann.EMIT] == 10 + 30 and clock.ns[ann.STAGE] == 10
    assert sum(clock.ns) == 9 * 10 + 50


def test_door_exposed_is_decided_by_what_is_enqueued(door, monkeypatch):
    """A phase that starts with nothing enqueued and not collected is
    exposed; under a handle in flight or a jit call not read back it is
    hidden; a readback never is exposed; cut() cuts the open phase at the
    commit mark, the recorder empties the interval into the record, and
    clear() forgets an interval that was no iteration's."""
    from dynamo_tpu.runtime.flight_recorder import FlightRecorder

    ann, clock = door
    fr = FlightRecorder(8)
    now = [0]
    monkeypatch.setattr(ann, "_clock", lambda: now[0])

    def run(idx, ns):
        with ann.phase(idx):
            now[0] += ns

    # a cold decode: staged and dispatched with nothing queued
    run(ann.PREP, 5)
    run(ann.STAGE, 7)
    run(ann.DISPATCH, 11)
    assert clock.serial
    clock.handles += 1  # the engine: decode_dispatch returned a handle
    ann.synced()        # (no blocking read happened; harmless)
    # the next iteration, enqueued ahead of its read-back: all hidden
    run(ann.INBOX, 1)
    run(ann.SCHEDULE, 2)
    run(ann.STAGE, 13)
    run(ann.DISPATCH, 17)
    clock.handles += 1
    run(ann.READBACK, 100)
    clock.handles -= 1
    assert not clock.serial
    run(ann.EMIT, 19)  # under the dispatch still in flight: hidden
    rec = _blank_record()
    with ann.phase(ann.PUBLISH):
        now[0] += 3
        clock.cut(now[0])  # the commit mark, inside publish
        fr.take_clock(rec, clock)
        now[0] += 4        # the rest of publish is the next record's
    assert rec.host_stage_s == pytest.approx(20e-9)
    assert rec.host_dispatch_s == pytest.approx(28e-9)
    assert rec.host_readback_s == pytest.approx(100e-9)
    assert rec.host_publish_s == pytest.approx(3e-9)
    assert rec.exposed_s == pytest.approx((5 + 7 + 11) * 1e-9)
    assert rec.exposed_stage_s == pytest.approx(7e-9)
    assert rec.exposed_emit_s == 0.0
    assert clock.ns[ann.PUBLISH] == 4 and clock.ns[ann.STAGE] == 0
    # the last one before a drain: nothing behind it, its commit is exposed
    run(ann.READBACK, 50)
    clock.handles -= 1
    run(ann.EMIT, 6)
    assert clock.exposed_ns[ann.EMIT] == 6 and clock.exposed_ns[ann.READBACK] == 0
    # a serial step: from the jit call to the blocking read the rest hides
    run(ann.STAGE, 8)
    run(ann.DISPATCH, 9)
    run(ann.DISPATCH, 9)   # the decode loop chained on the ragged step
    run(ann.READBACK, 30)
    run(ann.EMIT, 2)
    assert clock.exposed_ns[ann.STAGE] == 8 and clock.exposed_ns[ann.DISPATCH] == 9
    assert clock.exposed_ns[ann.EMIT] == 8
    # a prompt's first token is read back inside emit, outside any readback
    # span: the runner tells the clock
    run(ann.DISPATCH, 1)
    with ann.phase(ann.EMIT):
        now[0] += 5
        ann.synced()
    assert clock.exposed_ns[ann.EMIT] == 8 and not clock.serial
    run(ann.WAIT, 1000)
    clock.clear()  # the loop idled: the phases are no iteration's
    assert clock.ns[:ann.WAIT] == [0] * ann.WAIT and clock.ns[ann.WAIT] == 1000
    fr.take_clock(rec, clock)  # the next record takes the sleeps with it
    assert rec.exposed_s == 0.0 and clock.ns[ann.WAIT] == 0


def test_door_judges_an_open_phase_again_when_the_queue_changes(door, monkeypatch):
    """Exposure is not fixed at a phase's start. A prompt's first token is
    sampled and read inside `emit`: what emit runs before the sampling's
    jit call is as it began, from there to the read it is hidden, and after
    the read (`synced()`, or a readback span) nothing is enqueued, so the
    rest of that emit is exposed."""
    ann, clock = door
    now = [0]
    monkeypatch.setattr(ann, "_clock", lambda: now[0])

    def run(idx, ns):
        with ann.phase(idx):
            now[0] += ns

    # a prefill: staged and dispatched cold, its first token read in emit
    run(ann.STAGE, 5)
    run(ann.DISPATCH, 7)          # the chunk: enqueued
    with ann.phase(ann.EMIT):     # starts hidden under the chunk
        now[0] += 11
        run(ann.DISPATCH, 2)      # the sampling program
        now[0] += 3               # still enqueued: hidden
        ann.synced()              # device_get of the token: queue empty
        now[0] += 13              # detokenize, stream out: exposed
    assert clock.ns[ann.EMIT] == 11 + 3 + 13
    assert clock.exposed_ns[ann.EMIT] == 13 and not clock.serial
    # the same read through a readback span inside emit
    run(ann.DISPATCH, 1)
    with ann.phase(ann.EMIT):
        now[0] += 4
        run(ann.READBACK, 100)
        now[0] += 6
    assert clock.exposed_ns[ann.EMIT] == 13 + 6
    assert clock.exposed_ns[ann.READBACK] == 0
    # a phase that began exposed and enqueues inside itself: hidden after
    with ann.phase(ann.EMIT):
        now[0] += 8
        run(ann.DISPATCH, 9)
        now[0] += 10
    assert clock.exposed_ns[ann.EMIT] == 13 + 6 + 8
    assert clock.exposed_ns[ann.DISPATCH] == 7 + 1 + 9  # all but the sampling
    # a handle in flight keeps everything hidden whatever is read
    clock.serial = False
    clock.handles = 1
    with ann.phase(ann.EMIT):
        now[0] += 5
        ann.synced()
        now[0] += 5
    assert clock.exposed_ns[ann.EMIT] == 13 + 6 + 8
    # and a readback stays unexposed across a synced() inside it
    clock.handles = 0
    with ann.phase(ann.READBACK):
        now[0] += 5
        ann.synced()
        now[0] += 5
    assert clock.exposed_ns[ann.READBACK] == 0 and clock.ns[ann.READBACK] == 110


def test_door_counts_collections_on_its_own_thread(door):
    import gc

    ann, clock = door
    gc.collect()
    mine = clock.gc_ns
    assert mine > 0
    t = threading.Thread(target=gc.collect)  # no clock bound there
    t.start()
    t.join()
    assert clock.gc_ns == mine
    clock.clear()
    assert clock.gc_ns == 0


# -- dump_timeline --trace: fleet merge, dedupe, partial-failure pulls ------
def _load_dump_timeline():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "dump_timeline", os.path.join(repo, "scripts", "dump_timeline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(trace_id, span_id, name="route.push", flags="01", start=1000,
          end=2000, **attrs):
    return {"name": name, "trace_id": trace_id, "span_id": span_id,
            "parent_span_id": None, "flags": flags, "start_ns": start,
            "end_ns": end, "attributes": attrs}


def test_merge_span_rings_dedupes_and_tracks():
    dt = _load_dump_timeline()
    shared = _span("aa" * 16, "11" * 8)  # same span seen by both workers
    merged = dt.merge_span_rings([
        ("fe", {"spans": [shared,
                          _span("aa" * 16, "22" * 8, "frontend.request")]}),
        ("w0", {"spans": [dict(shared),
                          _span("bb" * 16, "33" * 8, "worker.decode",
                                flags="03")]}),
    ])
    other = merged["otherData"]
    assert other["n_spans"] == 3  # shared span counted once
    assert other["n_traces"] == 2
    slices = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(slices) == 3
    # pid = the worker that recorded it; tid = the trace (stable)
    fe = [e for e in slices if e["pid"] == 0]
    assert {e["name"] for e in fe} == {"route.push", "frontend.request"}
    assert len({e["tid"] for e in fe}) == 1  # one trace -> one lane
    # tail flag (0x02) surfaces in the thread_name metadata
    names = [e for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"]
    tails = [e for e in names if "[tail]" in e["args"]["name"]]
    assert tails and all(("bb" * 16)[:8] in e["args"]["name"]
                         for e in tails)
    # µs conversion from ns
    assert slices[0]["ts"] == 1.0 and slices[0]["dur"] == 1.0


def test_dedupe_targets_first_label_wins(capsys):
    dt = _load_dump_timeline()
    out = dt.dedupe_targets([
        ("fe", "http://h:9090"),
        ("copy", "http://h:9090/"),  # trailing slash: same URL
        ("w1", "http://h:9091"),
    ])
    assert out == [("fe", "http://h:9090"), ("w1", "http://h:9091")]
    assert "duplicate worker URL" in capsys.readouterr().err


def test_dump_timeline_skips_404_and_refused_workers(tmp_path, monkeypatch,
                                                     capsys):
    import http.server
    import json as _json
    import socket
    import sys as _sys
    import threading

    dt = _load_dump_timeline()
    payload = {"spans": [_span("cc" * 16, "44" * 8, "frontend.request")]}

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.server.ok and self.path.startswith("/debug/traces"):
                body = _json.dumps(payload).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_error(404)

        def log_message(self, *a):
            pass

    servers = []
    for ok in (True, False):
        srv = http.server.HTTPServer(("127.0.0.1", 0), H)
        srv.ok = ok
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        servers.append(srv)
    # a refused port: bind, note the port, close the listener
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()
    out = tmp_path / "spans.json"
    urls = [f"http://127.0.0.1:{s.server_address[1]}" for s in servers]
    try:
        monkeypatch.setattr(_sys, "argv", [
            "dump_timeline.py", "--trace", "--out", str(out),
            "--worker", f"good={urls[0]}", "--worker", f"bare={urls[1]}",
            "--worker", f"dead=http://127.0.0.1:{dead_port}",
            "--timeout", "5"])
        assert dt.main() == 0  # partial failure: still a merge
        err = capsys.readouterr().err
        assert "no span ring" in err and "skipping" in err.lower()
        merged = _json.loads(out.read_text())
        assert merged["otherData"]["n_spans"] == 1
        # every pull failing IS an error exit
        monkeypatch.setattr(_sys, "argv", [
            "dump_timeline.py", "--trace", "--out", str(out),
            "--worker", f"dead=http://127.0.0.1:{dead_port}",
            "--timeout", "5"])
        assert dt.main() == 2
    finally:
        for s in servers:
            s.shutdown()
            s.server_close()
