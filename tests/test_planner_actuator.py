"""Planner actuation engine: sense -> decide -> rehearse -> apply.

The anti-flap contract carries most of the weight here: hysteresis (a
single burst spike moves nothing), cooldown (an applied target goes
quiet), and the flap guard (the inverse direction is refused outright)
are each pinned by a test, because a flapping actuator is worse than no
actuator. The shadow tests pin the rejection semantics — a twin verdict
of "no improvement" kills the decision before any connector/drain call —
and the journal tests pin attribution: every applied action must
round-trip to its decision, trigger, and verdict.
"""

import asyncio
import json
import time

import pytest

from dynamo_tpu.planner.actuator import (
    Actuator,
    ActuatorConfig,
    Decision,
    DecisionJournal,
    worker_key,
)
from dynamo_tpu.planner.connector import VirtualConnector
from dynamo_tpu.planner.observer import FleetLoadObserver
from dynamo_tpu.planner.shadow import StaticOracle, metric_for_decision
from dynamo_tpu.planner.slo import (
    BREACH,
    OK,
    SloEngine,
    SloPolicy,
    SloTarget,
)
from dynamo_tpu.runtime.fleet_observer import (
    FleetObserver,
    hist_observe,
    new_hist,
)

GOOD = [0.005] * 100
BAD = [1.0] * 100


def _hist_of(values):
    h = new_hist()
    for v in values:
        hist_observe(h, v)
    return h


def _digest(worker, seq, now, ttft=None, itl=None, running=1, waiting=0,
            kv=0.3, act=None, spec=None):
    phases = {}
    if ttft is not None:
        phases["ttft"] = _hist_of(ttft)
    if itl is not None:
        phases["itl"] = _hist_of(itl)
    d = {"worker": list(worker), "seq": seq, "ts": now, "period_s": 2.0,
         "phases": phases,
         "queue": {"n_running": running, "n_waiting": waiting,
                   "kv_usage": kv}}
    if act is not None:
        d["act"] = act
    if spec is not None:
        d["spec"] = spec
    return d


def _policy():
    # ttft p99 < 20ms, itl p50 < 20ms; burn = frac_over / 0.5
    return SloPolicy(
        targets=[SloTarget("ttft", 0.99, 0.02), SloTarget("itl", 0.5, 0.02)],
        fast_window_s=30.0, slow_window_s=120.0,
        breach_burn=1.0, min_samples=8)


class _Recorder:
    """Recording connector + retune/drain sinks."""

    def __init__(self):
        self.scales = []
        self.retunes = []
        self.drains = []

    async def scale_to(self, component, target):
        self.scales.append((component, int(target)))

    def acked(self):
        return len(self.scales)

    async def retune(self, worker, params):
        self.retunes.append((tuple(worker), dict(params)))
        return True

    async def drain(self, worker):
        self.drains.append(tuple(worker))
        return True


def _world(n_workers=2, window_s=60.0):
    obs = FleetObserver(None, window_s=window_s)
    return obs, SloEngine(obs, _policy()), FleetLoadObserver(obs, window_s)


def _feed(obs, now, n_workers=2, ttft=None, waiting=0, seq0=1, n=1, **kw):
    """n digests per worker ending at `now` (2s apart)."""
    for w in range(n_workers):
        for i in range(n):
            obs.ingest(
                _digest((w + 1, 0), seq0 + i, now - 2.0 * (n - 1 - i),
                        ttft=ttft, waiting=waiting, **kw),
                now=now - 2.0 * (n - 1 - i))


def _actuator(slo, loads, clock, *, connector=None, shadow=None,
              affinity=None, retune_fn=None, drain_fn=None, replicas=2,
              **cfg_kw):
    kw = dict(hysteresis_ticks=3, cooldown_s=60.0, flap_guard_s=300.0,
              min_samples=1, waiting_high=1.0)
    kw.update(cfg_kw)
    cfg = ActuatorConfig(**kw)
    return Actuator(loads, slo, connector, cfg, shadow=shadow,
                    affinity=affinity, retune_fn=retune_fn,
                    drain_fn=drain_fn, replicas_fn=lambda: replicas,
                    clock=clock)


# -- anti-flap ---------------------------------------------------------------

async def test_single_spike_moves_nothing():
    """Hysteresis: one breached tick (burst spike) proposes nothing; the
    streak resets once the condition clears, so a later single spike
    starts from zero again — zero flapping by construction."""
    obs, slo, loads = _world()
    rec = _Recorder()
    t = [1000.0]
    act = _actuator(slo, loads, lambda: t[0], connector=rec)
    now = time.time()
    _feed(obs, now, ttft=BAD, waiting=4)
    await act.tick(now)  # streak 1 of 3
    assert rec.scales == [] and len(act.journal) == 0
    # breach clears: healthy traffic ages the spike out of the window
    _feed(obs, now + 200, ttft=GOOD, seq0=10, n=3)
    await act.tick(now + 200)
    assert act._streaks.get("fleet_breach") is None  # streak reset
    # a second isolated spike starts over at 1
    _feed(obs, now + 210, ttft=BAD, waiting=4, seq0=20)
    await act.tick(now + 210)
    assert rec.scales == [] and len(act.journal) == 0


async def test_sustained_breach_scales_up_once_then_cooldown():
    obs, slo, loads = _world()
    rec = _Recorder()
    t = [1000.0]
    act = _actuator(slo, loads, lambda: t[0], connector=rec)
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    assert rec.scales == [("decode", 3)]  # replicas 2 -> 3, exactly once
    d = act.journal.decisions()[-1]
    assert d.status == "applied"
    assert d.action["kind"] == "scale" and d.action["direction"] == 1
    assert d.trigger["rule"] == "fleet_breach"
    assert "ttft_p99" in d.trigger["slo"]
    # the same condition sustains: cooldown holds the next firing
    for i in range(3, 6):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    assert rec.scales == [("decode", 3)]
    skipped = [x for x in act.journal.decisions() if x.status == "skipped"]
    assert skipped and "cooldown" in skipped[-1].note


async def test_flap_guard_refuses_inverse_direction():
    """Scale-up applied at t, fleet goes idle: the scale-down proposal
    inside flap_guard_s is refused even though its own gates pass."""
    obs, slo, loads = _world()
    rec = _Recorder()
    t = [1000.0]
    act = _actuator(slo, loads, lambda: t[0], connector=rec,
                    cooldown_s=0.0, running_low=2.0, kv_low=1.0)
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    assert rec.scales == [("decode", 3)]
    # breach ages out -> idle fleet (waiting 0, running low, kv low)
    idle = now + 200
    for i in range(3):
        _feed(obs, idle + 2 * i, ttft=GOOD, waiting=0, seq0=10 + i, n=2)
        await act.tick(idle + 2 * i)
    assert rec.scales == [("decode", 3)]  # no down-scale
    skipped = [x for x in act.journal.decisions()
               if x.status == "skipped" and "flap-guard" in x.note]
    assert skipped and skipped[-1].action["direction"] == -1
    # past the guard window the down-scale is admitted
    t[0] += 301.0
    for i in range(3, 6):
        _feed(obs, idle + 2 * i, ttft=GOOD, waiting=0, seq0=10 + i, n=2)
        await act.tick(idle + 2 * i)
    assert rec.scales == [("decode", 3), ("decode", 1)]


# -- shadow rehearsal --------------------------------------------------------

async def test_shadow_rejection_blocks_apply():
    obs, slo, loads = _world()
    rec = _Recorder()
    oracle = StaticOracle(improves=False, predicted_s=9.9)
    act = _actuator(slo, loads, lambda: 0.0, connector=rec, shadow=oracle)
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    assert rec.scales == []  # the twin said no
    assert oracle.rehearsals == 1
    d = act.journal.decisions()[-1]
    assert d.status == "rejected"
    assert d.verdict == {"improves": False, "oracle": "static",
                         "predicted_s": 9.9}
    # a rejected decision sets no cooldown: the engine may re-propose
    # (and re-rehearse) as the world evolves
    assert not act._cooldown_until


async def test_shadow_failure_is_advisory():
    """A crashing oracle must not wedge actuation: the decision applies,
    with the error recorded on its verdict."""
    obs, slo, loads = _world()
    rec = _Recorder()

    class _Boom:
        async def rehearse(self, d):
            raise RuntimeError("fork exploded")

    act = _actuator(slo, loads, lambda: 0.0, connector=rec, shadow=_Boom())
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    assert rec.scales == [("decode", 3)]
    d = act.journal.decisions()[-1]
    assert d.status == "applied"
    assert d.verdict["oracle"] == "error"
    assert "fork exploded" in d.verdict["error"]


async def test_condition_clearing_during_rehearsal_goes_stale():
    """The world moved while the twin ran: the re-validation after the
    rehearsal await (the DYN-A007 re-check) must drop the decision."""
    obs, slo, loads = _world()
    rec = _Recorder()
    now = time.time()

    class _SlowClear:
        async def rehearse(self, d):
            # breach ages out while the fork runs
            _feed(obs, time.time(), ttft=GOOD, seq0=50, n=3)
            obs._digests.clear()  # hard-clear history: only GOOD remains
            _feed(obs, time.time(), ttft=GOOD, seq0=1, n=3)
            return {"improves": True, "oracle": "static"}

    act = _actuator(slo, loads, lambda: 0.0, connector=rec,
                    shadow=_SlowClear())
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    assert rec.scales == []
    d = act.journal.decisions()[-1]
    assert d.status == "stale"


# -- drain -------------------------------------------------------------------

async def test_drains_breach_worker_with_bound_session_count():
    obs, slo, loads = _world()
    rec = _Recorder()

    class _Aff:
        def snapshot(self):
            return {"by_instance": {"1": 3}}

    act = _actuator(slo, loads, lambda: 0.0, drain_fn=rec.drain,
                    affinity=_Aff())
    now = time.time()
    for i in range(3):
        # worker (1,0) breaches alone; (2,0) stays healthy
        obs.ingest(_digest((1, 0), 1 + i, now + 2 * i, ttft=BAD),
                   now=now + 2 * i)
        obs.ingest(_digest((2, 0), 1 + i, now + 2 * i, ttft=GOOD),
                   now=now + 2 * i)
        await act.tick(now + 2 * i)
    assert rec.drains == [(1, 0)]
    d = act.journal.decisions()[-1]
    assert d.status == "applied" and d.action["kind"] == "drain"
    assert d.trigger["worker"] == "1.0"
    assert d.trigger["bound_sessions"] == 3  # surfaced for the operator
    assert "1.0" in act._draining
    # while draining, the same worker is not re-proposed
    for i in range(3, 6):
        obs.ingest(_digest((1, 0), 1 + i, now + 2 * i, ttft=BAD),
                   now=now + 2 * i)
        obs.ingest(_digest((2, 0), 1 + i, now + 2 * i, ttft=GOOD),
                   now=now + 2 * i)
        await act.tick(now + 2 * i)
    assert rec.drains == [(1, 0)]


# -- retunes (fast loop) -----------------------------------------------------

async def test_spec_k_retune_follows_accept_rate():
    obs, slo, loads = _world()
    rec = _Recorder()
    act = _actuator(slo, loads, lambda: 0.0, retune_fn=rec.retune)
    now = time.time()
    for i in range(3):
        # low accept on (1,0): drafts are wasted verify rows -> K down;
        # high accept on (2,0): headroom -> K up
        obs.ingest(_digest((1, 0), 1 + i, now + 2 * i, ttft=GOOD,
                           act={"spec_k": 4, "mixed_prefill_tokens": 256},
                           spec={"accept_rate": 0.1, "drafted": 200}),
                   now=now + 2 * i)
        obs.ingest(_digest((2, 0), 1 + i, now + 2 * i, ttft=GOOD,
                           act={"spec_k": 4, "mixed_prefill_tokens": 256},
                           spec={"accept_rate": 0.95, "drafted": 200}),
                   now=now + 2 * i)
        await act.tick(now + 2 * i)
    assert ((1, 0), {"spec_k": 3}) in rec.retunes
    assert ((2, 0), {"spec_k": 5}) in rec.retunes
    rules = {d.trigger["rule"] for d in act.journal.decisions()
             if d.status == "applied"}
    assert rules == {"spec_accept_low", "spec_accept_high"}


async def test_spec_retune_abstains_below_min_drafted():
    obs, slo, loads = _world()
    rec = _Recorder()
    act = _actuator(slo, loads, lambda: 0.0, retune_fn=rec.retune)
    now = time.time()
    for i in range(4):
        obs.ingest(_digest((1, 0), 1 + i, now + 2 * i, ttft=GOOD,
                           act={"spec_k": 4},
                           spec={"accept_rate": 0.05, "drafted": 10}),
                   now=now + 2 * i)
        await act.tick(now + 2 * i)
    assert rec.retunes == []  # 10 drafts is noise, not a measurement


async def test_ratio_shift_on_ttft_burn_retunes_fleet():
    """TTFT burning while ITL is fine + prefills queued: the
    prefill:decode ratio moves toward prefill by growing the fleet's
    mixed pool budget multiplicatively from the digest-reported median."""
    obs, slo, loads = _world()
    rec = _Recorder()
    act = _actuator(slo, loads, lambda: 0.0, retune_fn=rec.retune)
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, itl=GOOD, waiting=2, seq0=1 + i,
              act={"mixed_prefill_tokens": 256, "spec_k": 0})
        await act.tick(now + 2 * i)
    # 256 * 1.5 = 384, delivered to every sensed worker
    assert rec.retunes == [((1, 0), {"mixed_prefill_tokens": 384}),
                           ((2, 0), {"mixed_prefill_tokens": 384})]
    d = [x for x in act.journal.decisions() if x.status == "applied"][-1]
    assert d.trigger["rule"] == "ttft_burn"
    assert d.action["target"] == "fleet:mixed"


# -- journal -----------------------------------------------------------------

async def test_journal_roundtrips_through_jsonl(tmp_path):
    obs, slo, loads = _world()
    rec = _Recorder()
    path = str(tmp_path / "journal.jsonl")
    act = _actuator(slo, loads, lambda: 0.0, connector=rec,
                    shadow=StaticOracle(improves=True),
                    journal_path=path)
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    assert rec.scales == [("decode", 3)]
    # every transition is one line; load folds to final state per id
    lines = [json.loads(x)
             for x in open(path).read().splitlines()]
    assert [x["status"] for x in lines] == ["rehearsed", "applied"]
    j = DecisionJournal.load(path)
    assert len(j) == 1
    d = j.decisions()[0]
    live = act.journal.decisions()[0]
    assert d.status == "applied"
    assert d.decision_id == live.decision_id
    assert d.action == live.action and d.trigger == live.trigger
    assert d.verdict == {"improves": True, "oracle": "static"}
    assert j.counts == {"applied": 1}


def test_journal_ring_is_bounded():
    j = DecisionJournal(capacity=4)
    for i in range(10):
        j.record(Decision(i, 0.0, {}, {"kind": "scale", "target": "d"},
                          status="applied"))
    assert len(j) == 4
    assert [d.decision_id for d in j.decisions()] == [6, 7, 8, 9]
    assert j.counts["applied"] == 10  # counters survive eviction


async def test_debug_payload_attributes_every_applied_action():
    obs, slo, loads = _world()
    rec = _Recorder()
    act = _actuator(slo, loads, lambda: 0.0, connector=rec,
                    shadow=StaticOracle(improves=True))
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    p = act.debug_payload()
    assert p["ticks"] == 3
    assert p["journal"]["counts"] == {"applied": 1}
    assert p["acked"] == 1
    assert p["inflight"] == [] and p["draining"] == []
    assert "scale:decode" in p["cooldowns"]
    (d,) = p["journal"]["decisions"]
    # the attribution chain: action -> trigger -> verdict, one payload
    assert d["status"] == "applied"
    assert d["action"]["params"]["replicas"] == 3
    assert d["trigger"]["rule"] == "fleet_breach"
    assert d["verdict"]["oracle"] == "static"
    assert json.dumps(p)  # JSON-serializable end to end


# -- connector handshake -----------------------------------------------------

async def test_scale_decision_rides_virtual_connector(tmp_path):
    obs, slo, loads = _world()
    conn = VirtualConnector(tmp_path / "decisions")
    act = _actuator(slo, loads, lambda: 0.0, connector=conn)
    now = time.time()
    for i in range(3):
        _feed(obs, now + 2 * i, ttft=BAD, waiting=4, seq0=1 + i)
        await act.tick(now + 2 * i)
    lines = (tmp_path / "decisions" / "decisions.jsonl").read_text()
    (d,) = [json.loads(x) for x in lines.splitlines()]
    assert d["component"] == "decode" and d["target_replicas"] == 3
    assert conn.acked() == 0  # nothing realized the decision yet


# -- decision -> rehearsal metric mapping ------------------------------------

def test_metric_for_decision_mapping():
    def mk(trigger, kind="scale"):
        return Decision(1, 0.0, trigger, {"kind": kind, "target": "x"})

    assert metric_for_decision(
        mk({"rule": "fleet_breach", "slo": ["ttft_p99"]})) == \
        ("ttft_p99", "ttft_p99_s")
    assert metric_for_decision(
        mk({"rule": "itl_burn", "target": "itl_p50"})) == \
        ("itl_p50", "itl_p50_s")
    # spec retunes are scored on ITL regardless of trigger detail
    assert metric_for_decision(
        mk({"rule": "spec_accept_low", "worker": "1.0"}, kind="retune")) == \
        ("itl_p50", "itl_p50_s")
    # unknown triggers fall back to the headline metric
    assert metric_for_decision(mk({})) == ("ttft_p99", "ttft_p99_s")


# -- worker-side knob surface ------------------------------------------------

def test_engine_retune_clamps_to_compile_time_commitments():
    from dynamo_tpu.mocker.__main__ import build_mock_engine, parse_args
    from dynamo_tpu.runtime.fleet_observer import DigestBuilder

    engine, _ = build_mock_engine(parse_args(
        ["--speed", "0", "--mixed-prefill-tokens", "256",
         "--spec-ngram", "--spec-k", "4"]))
    try:
        # SimRunner compiles no bucket (static_shapes): tokens move freely
        out = engine.retune(mixed_prefill_tokens=512, spec_k=2)
        assert out["mixed_prefill_tokens"] == 512 and out["spec_k"] == 2
        assert engine.scheduler.mixed_prefill_tokens == 512
        # a compiled runner caps tokens at the init-registered bucket
        engine.runner.static_shapes = True
        out = engine.retune(mixed_prefill_tokens=100000)
        assert out["mixed_prefill_tokens"] == 256
        # a device-draft runner caps K at the init ring size
        engine._spec_device_draft = True
        out = engine.retune(spec_k=99)
        assert out["spec_k"] == 4
        assert out["mixed_prefill_seqs"] >= 1
        assert engine.retunes == 3
        # the digest act block carries the knob state fleet-wide
        act = DigestBuilder(1).build(engine, 1.0)["act"]
        assert act == {"mixed_prefill_tokens": 256,
                       "mixed_prefill_seqs": 8,
                       "spec_k": 4, "retunes": 3}
    finally:
        engine.stop()


# -- the loop in the twin ----------------------------------------------------

async def test_fleet_sim_actuates_scale_up_end_to_end():
    """FleetSim with the actuator live: an impossible TTFT SLO holds the
    fleet in BREACH, the engine decides scale-up, the decision rides the
    VirtualConnector file handshake, the sim's poller realizes it (new
    worker spawned, ack appended), and the run report attributes it."""
    from dynamo_tpu.mocker.fleet import FleetSim
    from dynamo_tpu.planner.actuator import ActuatorConfig

    sim = FleetSim(
        n_workers=2, speed=0.0, idle_sleep_s=0.01,
        digest_period_s=0.25, digest_window_s=3.0,
        migration_backoff_base_s=0.01, sick_cooldown_s=0.3,
        slo="ttft:p99<0.000001,itl:p50<10",  # TTFT can never meet this
        actuate=True, shadow=StaticOracle(improves=True),
        actuator_config=ActuatorConfig(
            tick_interval_s=0.2, hysteresis_ticks=2, cooldown_s=30.0,
            flap_guard_s=60.0, min_samples=1, waiting_high=0.0),
    )
    await sim.start()
    try:
        report = await sim.run(scenarios=("burst",), n_sessions=10,
                               rps=6.0, time_scale=1.0)
        # the poller must get a turn after the last decision lands
        for _ in range(40):
            if sim.alive_workers() > 2 and sim.connector.acked() >= 1:
                break
            await asyncio.sleep(0.1)
    finally:
        final = sim.alive_workers()
        acked = sim.connector.acked()
        payload = sim.actuator.debug_payload()
        scaled = dict(sim.scale_events)
        await sim.stop()
    assert final == 3, payload
    assert acked >= 1
    assert report["actuation"]["counts"].get("applied", 0) >= 1
    # (the report is built when the run ends; on a loaded machine the
    # poller realizes the decision after that, within the wait above)
    assert (report["actuation"]["scale_events"] or scaled).get("up") == 1
    (d,) = [x for x in payload["journal"]["decisions"]
            if x["status"] == "applied"]
    assert d["trigger"]["rule"] == "fleet_breach"
    # cooldown + flap guard held: exactly one scale event, no flap
    assert report["actuation"]["scale_events"].get("down") is None
