"""Flight recorder + per-request latency spine (fast tier-1 suite).

Covers the observability tentpole: ring wraparound semantics, record
fields against real SimRunner mixed plans, phase-spine monotonicity and
request-plane hop propagation, Chrome-trace export schema (every event
carries ph/ts/pid/name), the /debug/timeline status route, the EWMA
anomaly trigger's fire-once-per-excursion contract (with on-disk dump),
the recorder-on-vs-off byte-identity acceptance, and the
prometheus-free SimpleMetrics text-exposition fallback.
"""

import asyncio
import json
import os
import time
import types

import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.mocker.sim import SimRunner, SimTiming
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.flight_recorder import (
    FlightRecorder,
    IterationRecord,
    to_chrome_trace,
)


def _rec(seq, wall_s=0.004, kind="decode", **over):
    base = dict(
        seq=seq, ts=1700000000.0 + seq * 0.01, wall_s=wall_s, kind=kind,
        decode_seqs=2, decode_steps=4, n_chunks=0, chunk_tokens=0,
        charged_tokens=0, ragged=False, fused=False, n_waiting=0,
        n_running=2, kv_usage=0.25, g2_blocks=0, g3_blocks=0,
        prefetch_hits=0, compile_variants=1,
    )
    base.update(over)
    return IterationRecord(**base)


# -- ring semantics ---------------------------------------------------------


def test_ring_wraparound():
    fr = FlightRecorder(capacity=8, anomaly_k=0.0)
    for i in range(20):
        fr.append(_rec(i))
    assert len(fr) == 8
    assert fr.total_appended == 20
    snap = fr.snapshot()
    assert [r.seq for r in snap] == list(range(12, 20))  # oldest→newest
    assert [r.seq for r in fr.snapshot(3)] == [17, 18, 19]
    assert fr.snapshot(0) == []


def test_disabled_recorder_is_noop():
    fr = FlightRecorder(capacity=0)
    assert not fr.enabled
    fr.append(_rec(0))  # must not raise
    assert len(fr) == 0
    assert fr.snapshot() == []
    assert fr.to_chrome_trace()["traceEvents"][0]["ph"] == "M"
    assert fr.stats()["enabled"] is False


# -- engine integration: record fields vs SimRunner plans -------------------


def _mk_engine(recorder_size=128, decode_base_s=0.0):
    runner = SimRunner(
        num_pages=256, page_size=4, max_pages_per_seq=32,
        timing=SimTiming(speed=1.0 if decode_base_s else 0.0,
                         decode_base_s=decode_base_s),
    )
    return InferenceEngine(
        runner, max_batch=4, chunk_size=16, recorder_size=recorder_size,
        anomaly_k=0.0,
    )


async def _gen(engine, prompt, max_tokens, metadata=None, first_token=None):
    toks = []
    final = None
    ctx = Context(metadata=metadata or {})
    async for item in engine.generate(
        {"token_ids": prompt, "sampling": {"temperature": 0.0},
         "stop": {"max_tokens": max_tokens, "stop_ids": [],
                  "ignore_eos": True}}, ctx,
    ):
        assert item.get("finish_reason") != "error", item
        toks.extend(item.get("token_ids") or [])
        if first_token is not None and toks:
            first_token.set()
        if item.get("finish_reason"):
            final = item
            break
    return toks, final


async def test_record_fields_vs_sim_mixed_plan():
    """A prefill landing while another sequence decodes must produce a
    kind="mixed" record whose plan-composition fields match what the
    scheduler actually composed, and total chunk_tokens across the run
    must equal the prompt tokens served."""
    engine = _mk_engine(decode_base_s=0.002)
    p1, p2 = list(range(300, 316)), list(range(400, 408))
    engine.start()
    try:
        seen_first = asyncio.Event()
        t1 = asyncio.create_task(
            _gen(engine, p1, 100, first_token=seen_first))
        await asyncio.wait_for(seen_first.wait(), timeout=30)
        t2 = asyncio.create_task(_gen(engine, p2, 4))
        await asyncio.gather(t1, t2)
    finally:
        engine.stop()
    recs = engine.recorder.snapshot()
    assert recs, "no iteration records appended"
    seqs = [r.seq for r in recs]
    assert seqs == sorted(seqs)  # iteration counter is monotonic
    kinds = {r.kind for r in recs}
    assert kinds <= {"prefill", "decode", "mixed"}
    assert "mixed" in kinds, kinds
    # every prompt token was served through some prefill/mixed record
    assert sum(r.chunk_tokens for r in recs) == len(p1) + len(p2)
    mixed = [r for r in recs if r.kind == "mixed"]
    for r in mixed:
        assert r.decode_seqs >= 1 and r.decode_steps >= 1
        assert r.n_chunks >= 1 and r.chunk_tokens > 0
        assert not r.fused  # SimRunner has no fused mixed program
    for r in recs:
        assert 0.0 <= r.kv_usage <= 1.0
        assert r.wall_s >= 0.0 and r.charged_tokens >= 0
        if r.kind == "decode":
            assert r.n_chunks == 0 and r.chunk_tokens == 0
        if r.kind == "prefill":
            assert r.decode_seqs == 0 and r.n_chunks == 1


async def test_run_ahead_fields_and_commit_to_commit_walls():
    """A run of decode iterations: the first starts cold, the rest are
    enqueued ahead (`ahead`, `drain`); `wall_s` runs from commit to commit,
    so the walls tile the loop's busy time: their sum is the run's wall,
    less the idle sleeps around it, and in a run of iterations enqueued
    ahead a decode's wall is the device's step time and not device plus
    host."""
    import time

    step = 0.010
    engine = _mk_engine(decode_base_s=step)
    engine.start()
    try:
        t0 = time.monotonic()
        toks, _ = await _gen(engine, [1, 2, 3, 4, 5], 41)
        total = time.monotonic() - t0
    finally:
        engine.stop()
    assert len(toks) == 41
    recs = engine.recorder.snapshot()
    dec = [r for r in recs if r.kind == "decode"]
    assert [r.kind for r in recs] == ["prefill"] + ["decode"] * 10
    assert [(r.ahead, r.drain) for r in dec] == [(False, "cold")] + [
        (True, "")] * 9
    assert recs[0].drain == "prefill" and not recs[0].ahead
    # every commit mark is taken BEFORE the commit's items are handed to the
    # client (the delivery follows the mark, since PR 55), so the client's
    # clock, which stops on the last item, has seen every wall end. (Until
    # then the last mark came AFTER the emit, inside publish, and a loaded
    # machine could stop the client's clock first: walls 0.4459 against
    # 0.4386 under six workers.) The first wall starts where the loop last
    # woke from an idle sleep, which may be a moment before t0.
    walls = sum(r.wall_s for r in recs)
    assert walls <= total + 0.005 and walls >= total - 0.05, (walls, total)
    # the delivery: each iteration's items and publish went out under the
    # program enqueued after its commit (the prompt's first token under the
    # cold decode, a decode's tokens under the one enqueued ahead of its
    # commit), but the last, after which nothing was left to enqueue
    assert [r.deliver_under for r in recs] == [True] * 10 + [False]
    assert all(r.host_deliver_s >= 0.0 for r in recs)
    assert sum(r.host_deliver_s for r in recs) > 0.0
    for r in recs:
        host = sum(getattr(r, f"host_{p}_s") for p in (
            "inbox", "schedule", "prep", "stage", "dispatch", "readback",
            "emit", "publish", "deliver"))
        assert host <= r.wall_s + 1e-9, r
    # 4 fused steps of `step` + per-seq + dispatch overhead a dispatch
    device = 4 * (step + 0.0003) + 0.002
    mid = sorted(r.wall_s for r in dec[2:])[len(dec[2:]) // 2]
    assert device * 0.9 <= mid <= device * 1.5, (mid, device)
    # records stay in commit order while `ts` (staging began) runs ahead
    assert [r.seq for r in recs] == sorted(r.seq for r in recs)
    assert engine.run_ahead_totals == {"prefill": 1, "cold": 1, "ahead": 9}



@pytest.mark.parametrize("window,n_global", [(0, 0), (6, 0), (6, 1)])
async def test_record_counts_live_decode_pages(window, n_global):
    """decode_pages_live: over an iteration's decode rows and fused steps,
    the pages a row's context holds, from the first its sliding window
    shows (mean over layers where sliding and global alternate); 0 on a
    record with no decode half. One request alone, so every decode
    iteration's count follows from the tokens it emitted."""
    engine = _mk_engine()
    engine.runner.config = types.SimpleNamespace(
        sliding_window=window, n_layers=2, sw_period=2 if n_global else 1,
        sw_global_residue=1)
    prompt, n_out, ps = list(range(300, 311)), 9, 4
    engine.start()
    try:
        toks, _ = await _gen(engine, prompt, n_out)
    finally:
        engine.stop()
    assert len(toks) == n_out

    def pages(n, w):
        return (n - 1) // ps - (max(n - w, 0) // ps if w else 0) + 1

    recs = engine.recorder.snapshot()
    n, seen = len(prompt), 0  # context before the first decode step
    for r in recs:
        if not r.decode_seqs:
            assert r.decode_pages_live == 0
            continue
        lens = range(n + 1, n + r.decode_steps + 1)
        sliding = sum(pages(m, window) for m in lens)
        full = sum(pages(m, 0) for m in lens)
        want = round((full * n_global + sliding * (2 - n_global)) / 2)
        assert r.decode_pages_live == want, (r, n)
        assert r.decode_pages_live <= full
        n += r.decode_steps
        seen += 1
    assert seen >= 2


@pytest.mark.parametrize("model", ["tiny", "tiny-gemma3"])
def test_record_counts_live_ragged_pairs(monkeypatch, model):
    """ragged_pages_live: the live (work unit, page) pairs one layer's
    ragged call walked, on a real runner's mixed iteration (one decode row
    beside a 9-token chunk), against the rule written out from the
    positions (the mean over layers where sliding and global alternate);
    0 on an iteration no ragged program ran, and there decode_pages_live
    keeps step 0, which a ragged iteration hands to the ragged kernel."""
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.engine.scheduler import Sequence
    from dynamo_tpu.models.config import get_config

    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    ps, qb = 4, 8
    c = get_config(model)
    runner = ModelRunner(c, num_pages=96, page_size=ps, max_pages_per_seq=16,
                         decode_buckets=(1, 2, 4), prefill_buckets=(8, 16),
                         seed=7)
    engine = InferenceEngine(runner, max_batch=4, chunk_size=16,
                             mixed_prefill_tokens=16)

    def add(rid, prompt):
        engine._inbox.put(("add", Sequence(
            request_id=rid, prompt=prompt, sampling={"temperature": 0.0},
            stop={"max_tokens": 32, "stop_ids": []},
            arrival=time.monotonic())))

    def pairs(q_lens, q_starts, kv_lens, window):
        n, lo = 0, 0
        for ln, start, kv in zip(q_lens, q_starts, kv_lens):
            hi = lo + ln
            for b in range(lo // qb, (hi - 1) // qb + 1):
                blo, bhi = max(lo, b * qb), min(hi, (b + 1) * qb)
                qpos0, rows = start + blo - lo, bhi - blo
                last = min(qpos0 + rows - 1, kv - 1) // ps
                first = max(qpos0 - window + 1, 0) // ps if window else 0
                n += last - min(first, last) + 1
            lo = hi
        return n

    a, b = [4, 2, 4, 2, 7, 5, 1, 3, 9, 8, 6], [9, 8, 7, 1, 3, 1, 4, 1, 5]
    add("a", a)
    engine._loop_once()  # a's prefill
    engine._loop_once()  # a decodes alone
    engine._commit_inflight()  # (it stays in flight until the next plan)
    engine._deliver()  # (its record waits for the next enqueue, or this)
    engine._flush_late_record()
    alone = engine.recorder.snapshot()[-1]
    assert alone.kind == "decode" and alone.ragged_pages_live == 0
    assert alone.decode_pages_live > 0
    pos = engine.scheduler.active[0].computed_len  # a's next position
    add("b", b)
    engine._loop_once()  # a's decode + b's only chunk, one ragged dispatch
    engine._deliver()
    engine._flush_late_record()
    rec = engine.recorder.snapshot()[-1]
    assert (rec.kind, rec.ragged, rec.n_chunks, rec.decode_seqs) == (
        "mixed", True, 1, 1), rec
    plan = ([1, len(b)], [pos, 0], [pos + 1, len(b)])
    full = pairs(*plan, 0)
    want = full
    if c.sliding_window:
        n_global = sum(l % c.sw_period == c.sw_global_residue
                       for l in range(c.n_layers))
        want = round((full * n_global + pairs(*plan, c.sliding_window)
                      * (c.n_layers - n_global)) / c.n_layers)
        assert want < full  # the window cuts a page of the decode row
    assert rec.ragged_pages_live == want, rec
    # the decode kernel ran steps 1 .. n-1 only
    tail = sum((pos + t) // ps + 1 for t in range(1, rec.decode_steps))
    if not c.sliding_window:
        assert rec.decode_pages_live == tail, rec


# -- latency spine ----------------------------------------------------------


async def test_phase_spine_monotonic_and_hop_propagation():
    """Upstream hop stamps (frontend/router durations riding
    ctx.metadata) must survive into the final item's phases next to the
    engine-side stamps, and the engine stamps must be internally
    consistent: ttft <= e2e, every duration non-negative."""
    engine = _mk_engine()
    engine.start()
    try:
        toks, final = await _gen(
            engine, list(range(300, 312)), 8,
            metadata={"phases": {"frontend_queue_s": 0.25, "route_s": 0.125,
                                 "bogus": "dropped"},
                      "migration_attempt": 2},
        )
    finally:
        engine.stop()
    assert len(toks) == 8
    ph = final["phases"]
    # hop propagation: upstream durations arrive verbatim, non-numerics drop
    assert ph["frontend_queue_s"] == 0.25
    assert ph["route_s"] == 0.125
    assert "bogus" not in ph
    assert ph["migration_attempts"] == 2.0
    # engine-side spine: present and monotonically consistent
    assert 0.0 <= ph["queue_wait_s"] <= ph["e2e_s"]
    assert 0.0 <= ph["ttft_s"] <= ph["e2e_s"]
    itl = ph.get("itl_s", [])
    assert isinstance(itl, list) and all(v >= 0.0 for v in itl)
    assert len(itl) <= 512


def test_frontend_finish_phases_folds_e2e_and_events():
    from dynamo_tpu.frontend.migration import Migration

    events = []
    root = types.SimpleNamespace(
        add_event=lambda name, attributes=None: events.append(name))
    item = {"finish_reason": "stop",
            "phases": {"queue_wait_s": 0.01, "ttft_s": 0.02,
                       "itl_s": [0.001]}}
    Migration._finish_phases(item, root, time.monotonic() - 1.0)
    assert item["phases"]["frontend_e2e_s"] >= 1.0
    assert "phase.ttft_s" in events and "phase.frontend_e2e_s" in events
    assert "phase.itl_s" not in events  # lists are not scalar span events
    # a worker item with no phase dict still gets the frontend stamp
    bare = {"finish_reason": "stop", "phases": "corrupt"}
    Migration._finish_phases(bare, root, time.monotonic())
    assert isinstance(bare["phases"], dict)
    assert "frontend_e2e_s" in bare["phases"]


# -- Chrome-trace export ----------------------------------------------------


def _trace_records():
    out = [_rec(i) for i in range(4)]
    out.append(_rec(4, kind="mixed", n_chunks=2, chunk_tokens=24,
                    charged_tokens=32, ragged=True, fused=True))
    out.append(_rec(5, wall_s=0.5, anomaly=True))
    return out


def test_chrome_trace_schema():
    trace = to_chrome_trace(_trace_records(), pid=7)
    body = json.dumps(trace)  # must be pure-JSON serializable
    assert json.loads(body)["displayTimeUnit"] == "ms"
    events = trace["traceEvents"]
    assert events
    for ev in events:
        for key in ("ph", "ts", "pid", "name"):
            assert key in ev, (key, ev)
        assert ev["pid"] == 7
    slices = [e for e in events if e["ph"] == "X"]
    assert len(slices) == 6
    for s in slices:
        assert s["dur"] >= 0 and s["name"] in ("prefill", "decode", "mixed")
    mixed = [s for s in slices if s["name"] == "mixed"][0]
    assert mixed["args"]["charged_tokens"] == 32
    assert mixed["args"]["ragged"] is True
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert counters == {"queue", "scheduled_tokens", "kv"}
    instants = [e for e in events if e["ph"] == "i"]
    assert len(instants) == 1 and instants[0]["name"] == "anomaly"
    # slices are ordered by wall-clock like the ring
    assert [s["ts"] for s in slices] == sorted(s["ts"] for s in slices)


def test_chrome_trace_draws_the_host_phases_inside_their_iteration():
    """The step thread's phases are child slices of the iteration's
    `dispatch` slice: true lengths, laid end to end from its start, inside
    it (they add up to at most the wall), the exposed seconds in `args`."""
    rec = _rec(3, wall_s=0.050, host_inbox_s=0.001, host_schedule_s=0.002,
               host_stage_s=0.006, host_dispatch_s=0.004,
               host_readback_s=0.030, host_emit_s=0.003, host_publish_s=0.001,
               exposed_s=0.005, exposed_stage_s=0.004, exposed_emit_s=0.0,
               ahead=False, drain="rows", gc_s=0.0005)
    events = to_chrome_trace([_rec(2), rec], pid=1)["traceEvents"]
    slices = [e for e in events if e["ph"] == "X"]
    parents = [e for e in slices if e["name"] == "decode"]
    kids = [e for e in slices if e["name"].startswith("engine.")]
    assert len(parents) == 2  # a record without the clock draws no children
    assert [k["name"] for k in kids] == [
        "engine.inbox", "engine.schedule", "engine.stage", "engine.dispatch",
        "engine.readback", "engine.emit", "engine.publish"]  # prep was 0.0
    parent = parents[1]
    assert parent["args"]["exposed_s"] == 0.005 and parent["args"]["drain"] == "rows"
    assert parent["args"]["gc_s"] == 0.0005 and parent["args"]["ahead"] is False
    assert "compile_calls" not in parent["args"]
    at = parent["ts"]
    for k in kids:
        assert k["tid"] == parent["tid"] and k["ts"] == pytest.approx(at)
        at += k["dur"]
    assert at <= parent["ts"] + parent["dur"] + 1e-6
    by = {k["name"]: k for k in kids}
    assert by["engine.readback"]["dur"] == pytest.approx(30000.0)
    assert by["engine.stage"]["args"] == {"exposed_s": 0.004}
    assert by["engine.emit"]["args"] == {"exposed_s": 0.0}
    assert "args" not in by["engine.readback"]
    json.dumps(events)


async def test_host_seconds_reach_metrics_by_phase_and_exposure():
    """/metrics: dynamo_engine_host_seconds_total{phase, exposed}, added to
    by the recorder as it empties the step clock into each record (with the
    idle sleeps since the record before as phase "wait"); and the spine's
    drain_wait_s as one more phase of request_phase_seconds."""
    from dynamo_tpu.frontend.protocols import ModelCard
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    rt = DistributedRuntime(discovery=MemDiscovery(realm="hostsec"),
                            event_transport="inproc")
    engine = _mk_engine(decode_base_s=0.002)
    w = await serve_worker(rt, engine, ModelCard(name="m"))
    try:
        toks, _ = await _gen(engine, [1, 2, 3, 4, 5], 24)
        assert len(toks) == 24
    finally:
        await w.stop()
    text = rt.metrics.render().decode()
    series = {}
    for line in text.splitlines():
        if line.startswith("dynamo_engine_host_seconds_total{"):
            labels, value = line.rsplit(" ", 1)
            ph = labels.split('phase="')[1].split('"')[0]
            ex = labels.split('exposed="')[1].split('"')[0]
            series[ph, ex] = float(value)
    from dynamo_tpu.runtime.annotations import PHASES, RECORD_PHASES

    assert set(series) == {(p, e) for p in PHASES for e in ("true", "false")}
    recs = engine.recorder.snapshot()
    for p in RECORD_PHASES:
        assert series[p, "true"] + series[p, "false"] == pytest.approx(
            sum(getattr(r, f"host_{p}_s") for r in recs), abs=1e-6)
    assert sum(series[p, "true"] for p in RECORD_PHASES) == pytest.approx(
        sum(r.exposed_s for r in recs), abs=1e-6)
    assert series["emit", "false"] > 0.0  # emitted under a dispatch in flight
    assert series["readback", "true"] == 0.0
    # the loop slept until the request came, with nothing enqueued
    assert series["wait", "true"] > 0.0 and series["wait", "false"] == 0.0
    assert 'phase="drain_wait"' in text


async def test_debug_timeline_route():
    """/debug/timeline on the status server returns the recorder's
    Chrome-trace JSON (404 before a source is installed)."""
    aiohttp = pytest.importorskip("aiohttp")
    from dynamo_tpu.runtime.status import StatusServer

    fr = FlightRecorder(capacity=16, anomaly_k=0.0)
    for i in range(6):
        fr.append(_rec(i))
    srv = StatusServer(types.SimpleNamespace(metrics=None),
                      port=0, host="127.0.0.1")
    base = await srv.start()
    try:
        async with aiohttp.ClientSession() as http:
            async with http.get(f"{base}/debug/timeline") as resp:
                assert resp.status == 404  # no source yet
            srv.add_timeline(lambda last_n=None: fr.to_chrome_trace(last_n))
            async with http.get(f"{base}/debug/timeline") as resp:
                assert resp.status == 200
                trace = await resp.json()
            async with http.get(f"{base}/debug/timeline?last_n=2") as resp:
                bounded = await resp.json()
    finally:
        await srv.stop()
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(slices) == 6
    for ev in trace["traceEvents"]:
        for key in ("ph", "ts", "pid", "name"):
            assert key in ev
    assert len([e for e in bounded["traceEvents"] if e["ph"] == "X"]) == 2


# -- anomaly trigger --------------------------------------------------------


def test_anomaly_fires_once_per_excursion(tmp_path):
    dump_dir = str(tmp_path / "dumps")
    fr = FlightRecorder(
        capacity=64, anomaly_k=3.0, anomaly_min_samples=8,
        anomaly_dump_dir=dump_dir, anomaly_dump_last_n=16,
    )
    seq = 0
    for _ in range(12):  # warmup: steady 4ms baseline
        fr.append(_rec(seq, wall_s=0.004))
        seq += 1
    assert fr.anomalies_fired == 0
    # sustained excursion: 5 stalled iterations -> ONE fire, on the first
    fired = []
    for _ in range(5):
        r = _rec(seq, wall_s=1.0)
        fr.append(r)
        fired.append(r.anomaly)
        seq += 1
    assert fired == [True, False, False, False, False]
    assert fr.anomalies_fired == 1
    # the stall must not have dragged the EWMA up
    assert fr.stats()["ewma_s"]["decode"] < 0.01
    # recovery re-arms; the next excursion fires exactly once more
    for _ in range(3):
        fr.append(_rec(seq, wall_s=0.004))
        seq += 1
    for _ in range(2):
        fr.append(_rec(seq, wall_s=1.0))
        seq += 1
    assert fr.anomalies_fired == 2
    # per-kind independence: a fresh kind has its own warmup
    fr.append(_rec(seq, wall_s=5.0, kind="prefill"))
    assert fr.anomalies_fired == 2
    # the daemon writer lands both dumps on disk as valid JSON
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        files = sorted(os.listdir(dump_dir)) if os.path.isdir(dump_dir) else []
        files = [f for f in files if f.endswith(".json")]
        if len(files) >= 2:
            break
        time.sleep(0.02)
    assert len(files) == 2, files
    with open(os.path.join(dump_dir, files[0]), encoding="utf-8") as f:
        dump = json.load(f)
    assert dump["trigger_seq"] == 12
    assert dump["k"] == 3.0
    assert dump["trigger"]["anomaly"] is True
    assert dump["records"], "dump carries no ring records"
    # the ring snapshot predates the trigger's own append
    assert dump["records"][-1]["seq"] == 11


def test_anomaly_trigger_without_dump_dir_only_counts():
    fr = FlightRecorder(capacity=32, anomaly_k=2.0, anomaly_min_samples=4)
    for i in range(6):
        fr.append(_rec(i, wall_s=0.004))
    fr.append(_rec(6, wall_s=1.0))
    assert fr.anomalies_fired == 1
    assert fr.dumps_written == 0 and fr.dumps_dropped == 0


# -- recorder on/off byte identity ------------------------------------------


async def _serve_prompts(recorder_size):
    engine = _mk_engine(recorder_size=recorder_size)
    engine.start()
    try:
        prompts = [list(range(300 + 10 * i, 300 + 10 * i + 6 + i))
                   for i in range(4)]
        outs = await asyncio.gather(
            *[_gen(engine, p, 8) for p in prompts])
        return [toks for toks, _ in outs], engine.recorder
    finally:
        engine.stop()


async def test_recorder_on_off_byte_identity():
    """Acceptance: the recorder must be observability-only — identical
    token outputs with the ring on and off."""
    on, rec_on = await _serve_prompts(recorder_size=256)
    off, rec_off = await _serve_prompts(recorder_size=0)
    assert on == off, (on, off)
    assert rec_on.total_appended > 0
    assert rec_off.total_appended == 0


# -- metrics fallback (satellite) -------------------------------------------


def test_simple_metrics_text_exposition():
    """prometheus_client-free fallback: dict counters rendering a minimal
    text exposition (the container has the real client, so the fallback
    is exercised directly)."""
    from dynamo_tpu.runtime.metrics import SimpleMetrics

    m = SimpleMetrics(labels={"dynamo_namespace": "ns"})
    c = m.counter("requests_total", "requests")
    c.inc()
    c.inc(2)
    m.gauge("queue_depth", "depth").set(7)
    h = m.child(dynamo_component="engine").histogram(
        "request_phase_seconds", "phase latency", phase="ttft")
    h.observe(0.5)
    h.observe(1.5)
    text = m.render().decode()
    lines = text.splitlines()
    assert "# TYPE dynamo_requests_total counter" in lines
    assert "# TYPE dynamo_queue_depth gauge" in lines
    assert "# TYPE dynamo_request_phase_seconds histogram" in lines

    def value(prefix):
        hits = [ln for ln in lines if ln.startswith(prefix)]
        assert len(hits) == 1, (prefix, hits)
        assert 'dynamo_namespace="ns"' in hits[0]
        return float(hits[0].rsplit(" ", 1)[1])

    assert value("dynamo_requests_total{") == 3.0
    assert value("dynamo_queue_depth{") == 7.0
    assert value("dynamo_request_phase_seconds_count{") == 2
    assert value("dynamo_request_phase_seconds_sum{") == 2.0
    hist_line = [ln for ln in lines
                 if ln.startswith("dynamo_request_phase_seconds_count")][0]
    assert 'phase="ttft"' in hist_line
    assert 'dynamo_component="engine"' in hist_line
    # shared store: children share series, render is idempotent
    assert m.render() == m.render()


async def test_spine_counts_stay_out_of_the_seconds_histogram():
    """worker_common._observe_phases: only a `_s` key of the spine is a
    duration; its counts (preemptions, prefill_iters, migration_attempts)
    go to request_phase_count, summed, and never into a seconds histogram."""
    import re

    from dynamo_tpu.frontend.protocols import ModelCard
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    class _Eng:
        def __init__(self):
            self.listeners = []

        def on_kv_event(self, cb): pass
        def on_fpm(self, cb): pass
        def on_phases(self, cb): self.listeners.append(cb)

        async def generate(self, req, ctx):
            yield {"token_ids": [], "finish_reason": "stop"}

        def start(self): pass
        def stop(self): pass

    eng = _Eng()
    rt = DistributedRuntime(discovery=MemDiscovery(realm="spine-counts"),
                            event_transport="inproc")
    try:
        w = await serve_worker(rt, eng, ModelCard(name="m"), digest_period_s=0,
                               publish_kv_events=False, publish_fpm=False)
        spine = {"ttft_s": 0.25, "prefill_s": 0.2, "prefill_iters": 3,
                 "preemptions": 1, "migration_attempts": 2.0,
                 "itl_s": [0.01, 0.03], "trace_id": "ab"}
        for cb in eng.listeners:
            cb(spine)
            cb(dict(spine, preemptions=0))
        lines = rt.metrics.render().decode().splitlines()
        await w.stop()
    finally:
        await rt.shutdown(drain_timeout=1)

    def values(prefix):
        return {re.search(r'phase="([^"]+)"', ln).group(1):
                float(ln.rsplit(" ", 1)[1])
                for ln in lines if ln.startswith(prefix)}

    seconds = values("dynamo_request_phase_seconds_count{")
    assert seconds == {"ttft": 2.0, "prefill": 2.0, "itl": 4.0}, seconds
    counts = values("dynamo_request_phase_count_total{") or values(
        "dynamo_request_phase_count{")
    assert counts == {"prefill_iters": 6.0, "preemptions": 1.0,
                      "migration_attempts": 4.0}, counts
