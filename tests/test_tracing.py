"""Distributed tracing: W3C traceparent propagation + spans across the
serving pipeline — one trace id covers the frontend root and the disagg
prefill and decode worker hops (reference lib/runtime/src/logging.rs:76-105
span export + propagation; migration.rs TraceLink)."""

import asyncio

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
