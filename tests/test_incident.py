"""Black-box incident forensics (runtime/incident.py +
scripts/dyn_incident.py): the armed capturer writes one versioned,
rate-limited, disk-bounded JSONL bundle per incident, and replay
re-scores the bundle's own evidence to the same verdict every time.

The fleet test is the acceptance loop from the issue: a seeded FleetSim
chaos run with an impossible ITL target breaches, writes EXACTLY the
rate-limited bundle count, and `dyn_incident.py replay` reproduces the
BREACH verdict deterministically from the bundle alone."""

import asyncio
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass

import pytest

from dynamo_tpu.runtime.incident import (
    BUNDLE_SCHEMA,
    IncidentCapturer,
    jsonable,
    list_bundles,
    read_bundle,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _wait_captured(cap, n, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = cap.stats()
        if st["captured"] >= n and st["pending"] == 0:
            return st
        time.sleep(0.01)
    raise AssertionError(f"capturer never reached {n} bundles: {cap.stats()}")


async def _await_captured(cap, n, timeout_s=8.0):
    """Loop-friendly wait: the SLO watch that pulls the trigger runs on
    THIS event loop, so the poll must yield to it."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = cap.stats()
        if st["captured"] >= n and st["pending"] == 0:
            return st
        await asyncio.sleep(0.05)
    raise AssertionError(f"capturer never reached {n} bundles: {cap.stats()}")


# -- serialization ----------------------------------------------------------
@dataclass
class _Probe:
    x: int
    label: str


def test_jsonable_coerces_live_snapshot_shapes():
    out = jsonable({
        (123, "decode"): _Probe(1, "w0"),      # Worker tuple key
        "set": {"only"},
        "nested": [{"deep": (1, 2)}],
        "opaque": object,
    })
    assert out["123.decode"] == {"x": 1, "label": "w0"}
    assert out["set"] == ["only"]
    assert out["nested"] == [{"deep": [1, 2]}]
    assert isinstance(out["opaque"], str)  # repr fallback, never a raise
    json.dumps(out)  # the whole point: always serializable


# -- capturer unit tests ----------------------------------------------------
def test_bundle_schema_roundtrip_and_failing_source(tmp_path):
    cap = IncidentCapturer(str(tmp_path), min_interval_s=0.0)
    try:
        cap.register("slo", lambda: {"state": "BREACH"})
        cap.register("broken", lambda: 1 / 0)
        cap.register("digests", lambda: {("w", 0): [1, 2]})
        assert cap.trigger("slo_breach", {"targets": ["itl_p50"]})
        _wait_captured(cap, 1)
    finally:
        cap.close()
    paths = list_bundles(str(tmp_path))
    assert len(paths) == 1
    assert os.path.basename(paths[0]).endswith("-0001-slo_breach.jsonl")
    b = read_bundle(paths[0])
    h = b["header"]
    assert h["schema"] == BUNDLE_SCHEMA and h["v"] == 1
    assert h["reason"] == "slo_breach"
    assert h["detail"] == {"targets": ["itl_p50"]}
    # registration order == section order, and a failing source records
    # an error line instead of voiding the bundle
    assert h["sections"] == ["slo", "broken", "digests"]
    assert b["sections"]["slo"] == {"state": "BREACH"}
    assert "ZeroDivisionError" in b["sections"]["broken"]["error"]
    assert b["sections"]["digests"] == {"w.0": [1, 2]}
    assert cap.stats()["errors"] == 1
    # non-bundle files must be rejected, not misread
    junk = tmp_path / "incident-x.jsonl"
    junk.write_text('{"schema": "something_else"}\n')
    with pytest.raises(ValueError):
        read_bundle(str(junk))


def test_trigger_rate_limited_and_refused_after_close(tmp_path):
    cap = IncidentCapturer(str(tmp_path), min_interval_s=60.0)
    try:
        cap.register("s", lambda: 1)
        assert cap.trigger("slo_breach") is True
        # a storm of follow-on triggers (sustained breach, anomaly
        # cascade) collapses into the one accepted bundle
        for _ in range(5):
            assert cap.trigger("recorder_anomaly") is False
        st = _wait_captured(cap, 1)
        assert st["captured"] == 1 and st["suppressed"] == 5
    finally:
        cap.close()
    assert cap.trigger("slo_breach") is False  # closed: refuse, don't raise
    assert len(list_bundles(str(tmp_path))) == 1


def test_prune_keeps_newest_max_bundles(tmp_path):
    cap = IncidentCapturer(str(tmp_path), min_interval_s=0.0, max_bundles=2)
    try:
        cap.register("s", lambda: 1)
        for i in range(5):
            assert cap.trigger(f"r{i}")
        _wait_captured(cap, 5)
    finally:
        cap.close()
    names = [os.path.basename(p) for p in list_bundles(str(tmp_path))]
    assert len(names) == 2
    # newest survive: filenames carry the seq, so order is checkable
    assert names[0].split("-")[2] == "0004" and "r3" in names[0]
    assert names[1].split("-")[2] == "0005" and "r4" in names[1]


# -- the acceptance loop: seeded chaos day -> one bundle -> replay ----------
async def test_fleet_breach_writes_one_bundle_replay_is_deterministic(
        tmp_path, monkeypatch):
    from dynamo_tpu.mocker.fleet import FaultSchedule, FleetSim
    from dynamo_tpu.runtime import tracing

    ring = tracing.SpanRing(capacity=4096, keep_prob=1.0)
    tracing.set_exporter(ring)
    out_dir = str(tmp_path / "incidents")
    sim = FleetSim(n_workers=3, router_mode="kv", seed=7, speed=0.02,
                   idle_sleep_s=0.01, migration_backoff_base_s=0.01,
                   sick_cooldown_s=0.3, digest_period_s=0.2,
                   digest_window_s=3.0,
                   slo="itl:p50<0.000001",  # every decode breaches
                   incident_dir=out_dir, incident_min_interval_s=60.0)
    try:
        await sim.start()
        # determinism: the SLO watch must be the ONE trigger that wins the
        # rate-limit slot, so disarm the per-worker EWMA anomaly trigger
        for w in sim.workers:
            rec = getattr(w.engine, "recorder", None)
            if rec is not None:
                rec.anomaly_k = 0.0
        # ... and the sanitizer's loop-lag gauge: on a loaded machine a
        # 250 ms event-loop stall files a violation that takes the slot
        if sim.sanitizer is not None:
            sim.sanitizer.watchdog_lag_s = float("inf")
        report = await sim.run(
            scenarios=("agentic", "json"), n_sessions=4, rps=10.0,
            fault_schedule=FaultSchedule.parse("kill@0.6:w2"))
        assert report["slo_state"] == "BREACH"
        stats = await _await_captured(sim.incidents, 1)
    finally:
        await sim.stop()
        tracing.set_exporter(None)
    # exactly the rate-limited count: one breach transition, one bundle —
    # the sustained breach after it is suppressed, not re-captured
    paths = list_bundles(out_dir)
    assert len(paths) == 1, (paths, stats)
    assert stats["captured"] == 1
    b = read_bundle(paths[0])
    assert b["header"]["reason"] == "slo_breach"
    assert "itl_p50" in b["header"]["detail"]["targets"]
    s = b["sections"]
    assert s["slo"]["state"] == "BREACH"
    assert s["digests"], "bundle must carry the digest window"
    assert s["recorder"], "bundle must carry recorder rings (calibration)"
    assert s["traces"]["n"] > 0, "bundle must carry the span ring"
    assert s["routing"]["decisions"], "bundle must carry routing audits"
    # live_state counts ALIVE workers at capture time: the kill may land
    # before or after the breach transition
    assert s["live_state"]["n_workers"] in (2, 3)
    assert s["faults"].get("kill") in (None, 1)  # capture may precede it
    json.dumps(b)  # fully JSON round-trippable

    # spans joinable by rid: a routed request's decision maps to spans
    dyn_incident = _load_script("dyn_incident")
    rid = s["routing"]["decisions"][-1]["rid"]
    joined = dyn_incident.join_rid(b, rid)
    assert joined["routing"]
    assert joined["trace_ids"], f"no spans joined for rid {rid}"
    # the route hop's span is in the trace; the frontend root may still
    # be open at capture time (spans export at END — a mid-flight
    # request's root isn't in the ring yet)
    assert any(sp["name"].startswith("route.") for sp in joined["spans"])

    # deterministic replay: the verdict is a pure function of the bundle
    v1 = dyn_incident.offline_verdict(b)
    v2 = dyn_incident.offline_verdict(read_bundle(paths[0]))
    assert v1 == v2
    assert v1["captured_state"] == "BREACH"
    assert v1["replay_state"] == "BREACH" and v1["reproduced"] is True
    assert v1["targets"].get("itl_p50") == "BREACH"
    # and the CLI agrees (rc 0 == reproduced)
    assert dyn_incident.main(["replay", paths[0]]) == 0
    assert dyn_incident.main(["list", out_dir]) == 0
    assert dyn_incident.main(["show", paths[0], "--section", "slo"]) == 0


@pytest.mark.slow
async def test_replay_sim_rehearses_calibrated_twin(tmp_path):
    """--sim forks a SimTiming.fit_records-calibrated twin from the
    bundle's live_state and re-runs it under the reconstructed fault
    schedule (deep-budget: boots a second fleet)."""
    from dynamo_tpu.mocker.fleet import FaultSchedule, FleetSim

    out_dir = str(tmp_path / "incidents")
    sim = FleetSim(n_workers=2, router_mode="kv", seed=11, speed=0.01,
                   idle_sleep_s=0.01, migration_backoff_base_s=0.01,
                   sick_cooldown_s=0.3, digest_period_s=0.2,
                   slo="itl:p50<0.000001",
                   incident_dir=out_dir, incident_min_interval_s=60.0)
    try:
        await sim.start()
        for w in sim.workers:
            rec = getattr(w.engine, "recorder", None)
            if rec is not None:
                rec.anomaly_k = 0.0
        await sim.run(scenarios=("json",), n_sessions=3, rps=10.0,
                      fault_schedule=FaultSchedule.parse("kill@0.5:w1"))
        await _await_captured(sim.incidents, 1)
    finally:
        await sim.stop()
    [path] = list_bundles(out_dir)
    dyn_incident = _load_script("dyn_incident")
    bundle = read_bundle(path)
    out = await dyn_incident.rehearse(bundle, duration_s=1.0, n_sessions=2,
                                      rps=6.0)
    assert out["requests"] > 0
    assert out["calibration"] is not None  # fit from the bundle's records
    # fault counters captured so far replay as a compressed schedule
    # (empty when the breach beat the kill to the trigger)
    assert isinstance(out["faults_replayed"], str)
