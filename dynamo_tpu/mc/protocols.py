"""dynmc specs over the REAL control-plane protocols.

Each spec instantiates production classes — AdmissionQueue, KvIndexer,
PrefetchManager, Migration, spawn_tracked — and fakes only their I/O
planes (disk thread, event subscriber, request plane, wall clock), so
the interleavings the explorer enumerates are interleavings of the
actual shipped code. The buggy twins (`_UnbufferedIndexer`,
`_NoAdoptPrefetch`, `_EpochlessIndexer`) reproduce the pre-fix behavior
of the two ordering bugs dynmc surfaced; regression tests replay the
committed shrunk schedules against BOTH: the twin must violate, the
production class must pass — proving the schedule still exercises the
race and the fix still closes it.

SPECS / FIXTURES at the bottom are the CLI registry
(`scripts/dynmc.py`).
"""

from __future__ import annotations

import asyncio
import logging
from typing import Any, Dict, List, Optional

from dynamo_tpu.engine.runner_api import Runner
from dynamo_tpu.mc.faults import Fault, cancel_task
from dynamo_tpu.mc.spec import (
    InvariantViolation,
    LostWakeupFixture,
    Spec,
    SpecEnv,
)

_silent = logging.getLogger("dynamo_tpu.mc.silent")
_silent.addHandler(logging.NullHandler())
_silent.propagate = False

W = (1, 0)  # the worker under test, everywhere


def _iv(cond: bool, msg: str) -> None:
    if not cond:
        raise InvariantViolation(msg)


# ---------------------------------------------------------------------------
# admission_queue — grant hand-off under cancel/timeout churn
# ---------------------------------------------------------------------------

class AdmissionQueueSpec(Spec):
    """Three requesters park against a saturated AdmissionQueue; capacity
    frees two slots over time; one requester may be cancelled mid-wait
    (client disconnect). Contract: nobody parks forever (every waiter
    resolves as granted / queue_timeout / cancelled), a grant landing on
    a cancelled waiter is passed on, and no more grants are delivered
    than slots were freed."""

    name = "admission_queue"

    def build(self, env: SpecEnv) -> None:
        from dynamo_tpu.router.queue import AdmissionConfig, AdmissionQueue
        from dynamo_tpu.runtime.request_plane import RequestPlaneError

        q = AdmissionQueue(
            AdmissionConfig(busy_blocks=10, max_depth=8, max_wait_s=5.0),
            load_fn=lambda w: 100.0,           # permanently saturated
            workers_fn=lambda: [W],
        )
        env.data["q"] = q
        env.data["outcomes"] = {}

        async def requester(rid: str, pri: int) -> None:
            try:
                await q.acquire(pri)
                env.data["outcomes"][rid] = "granted"
            except RequestPlaneError as e:
                env.data["outcomes"][rid] = e.code
            except asyncio.CancelledError:
                env.data["outcomes"][rid] = "cancelled"
                raise

        async def capacity() -> None:
            await asyncio.sleep(1.0)
            q.notify(1)
            await asyncio.sleep(1.0)
            q.notify(1)

        env.spawn("req_a", requester("a", 0))
        env.spawn("req_b", requester("b", 1))
        env.spawn("req_c", requester("c", 2))
        env.spawn("capacity", capacity())

    def faults(self, env: SpecEnv) -> list:
        return [cancel_task("cancel_req_b", lambda loop: env.task("req_b"))]

    def invariant(self, env: SpecEnv) -> None:
        q = env.data["q"]
        outcomes: Dict[str, str] = env.data["outcomes"]
        for rid in ("a", "b", "c"):
            t = env.task(f"req_{rid}")
            _iv(t is not None and t.done(),
                f"requester {rid} parked forever (lost wakeup)")
            _iv(rid in outcomes, f"requester {rid} finished with no outcome")
        granted = sum(1 for o in outcomes.values() if o == "granted")
        _iv(granted <= 2, f"{granted} grants delivered for 2 freed slots")
        _iv(q.depth == 0, f"queue depth {q.depth} at quiescence")


# ---------------------------------------------------------------------------
# prefetch_ttl — hint-TTL expiry racing an in-flight disk read
# ---------------------------------------------------------------------------

class _FakeHostTier:
    quantize = False

    def __init__(self) -> None:
        self.blocks: Dict[int, Optional[int]] = {}

    def __contains__(self, h: int) -> bool:
        return h in self.blocks

    def put(self, hashes, parents, k, v) -> None:
        for h, p in zip(hashes, parents):
            self.blocks[h] = p

    def put_block(self, h, parent, k, v) -> None:
        self.blocks[h] = parent

    def get(self, hashes):
        for h in hashes:
            if h not in self.blocks:
                raise KeyError(h)
        return (None, None)


class _FakeDisk:
    """Disk tier whose async read completes on a virtual timer, checking
    the two contracts the real writer thread depends on: at most one
    read in flight per hash, and the eviction pin held for the read's
    whole flight (DiskKvPool pins are a SET — a double pin/unpin pair
    silently drops protection early)."""

    def __init__(self, env: SpecEnv, blocks, latency: float) -> None:
        self.env = env
        self.blocks = set(blocks)
        self.latency = latency
        self.pinned: set = set()
        self.inflight: List[int] = []
        env.data.setdefault("disk_violations", [])

    def pin(self, h: int) -> None:
        self.pinned.add(h)

    def unpin(self, h: int) -> None:
        self.pinned.discard(h)

    def __contains__(self, h: int) -> bool:
        return h in self.blocks

    def read_block_async(self, h: int, cb) -> bool:
        if h in self.inflight:
            self.env.data["disk_violations"].append(
                f"duplicate concurrent read of block {h}")
        self.inflight.append(h)

        def _complete() -> None:
            self.inflight.remove(h)
            if h not in self.pinned:
                self.env.data["disk_violations"].append(
                    f"read of block {h} completed UNPINNED "
                    "(eviction window while file IO in flight)")
            cb(h, None, None, None, True)

        self.env.loop.call_later(self.latency, _complete)
        return True


class _FakeInbox:
    """Engine inbox: ops land back on the (virtual) step thread as
    schedulable callbacks."""

    def __init__(self, env: SpecEnv) -> None:
        self.env = env
        self.mgr = None  # wired after the manager exists

    def put(self, item) -> None:
        op, payload = item
        if op == "prefetch_disk":
            self.env.loop.call_soon(self.mgr.on_disk_read, *payload)


class _SimRunner(Runner):
    # holds_kv stays False => PrefetchManager runs in sim mode
    def import_pages(self, pages, seq, payload) -> None:
        pass


class _FakeMetricsNode:
    def child(self, **kw):
        return self

    def counter(self, name, help=""):
        return self

    def inc(self, n: int = 1) -> None:
        pass


class _FakeEngine:
    def __init__(self, env: SpecEnv, pool, tiered) -> None:
        self.pool = pool
        self.host_pool = tiered
        self.runner = _SimRunner()
        self._inbox = _FakeInbox(env)


class _Tiered:
    def __init__(self, host, disk) -> None:
        self.host = host
        self.disk = disk


class PrefetchTtlSpec(Spec):
    """A disk-resident block is hinted; the read's latency exceeds the
    hint TTL, so tick() expires the job mid-read; a re-hint for the same
    block lands while the read is still in flight. Contract (checked by
    the fake disk + pin accounting): never two concurrent reads of one
    hash, the disk pin covers every read's full flight, and at teardown
    every pin — disk and device — is released."""

    name = "prefetch_ttl"
    manager_cls: Any = None  # default: production PrefetchManager

    def build(self, env: SpecEnv) -> None:
        from dynamo_tpu.engine.kv_pool import PagePool
        from dynamo_tpu.kvbm.prefetch import PrefetchManager

        cls = self.manager_cls or PrefetchManager
        pool = PagePool(8, 16)
        disk = _FakeDisk(env, blocks=[101], latency=0.2)
        tiered = _Tiered(_FakeHostTier(), disk)
        engine = _FakeEngine(env, pool, tiered)
        mgr = cls(
            engine, max_inflight=2, hint_ttl_s=0.1, pin_ttl_s=0.2,
            metrics=_FakeMetricsNode(), clock=env.loop.time,
        )
        engine._inbox.mgr = mgr
        env.data.update(mgr=mgr, pool=pool, disk=disk)

        async def hinter() -> None:
            mgr.on_hint({"hashes": [101], "parents": [None]})

        async def ticker() -> None:
            for _ in range(8):
                await asyncio.sleep(0.06)
                mgr.tick()

        async def rehinter() -> None:
            await asyncio.sleep(0.15)
            mgr.on_hint({"hashes": [101], "parents": [None]})

        t_hint = env.spawn("hinter", hinter())
        t_tick = env.spawn("ticker", ticker())
        t_rehint = env.spawn("rehinter", rehinter())

        async def closer() -> None:
            # production stop() runs after the step thread joined — i.e.
            # strictly after every hint/tick; model that ordering, then
            # leave the in-flight read time to drain before stopping
            await asyncio.gather(t_hint, t_tick, t_rehint)
            await asyncio.sleep(0.5)
            mgr.stop()

        env.spawn("closer", closer())

    def invariant(self, env: SpecEnv) -> None:
        mgr, pool, disk = env.data["mgr"], env.data["pool"], env.data["disk"]
        for v in env.data["disk_violations"]:
            raise InvariantViolation(v)
        _iv(not disk.inflight, f"reads still in flight: {disk.inflight}")
        _iv(not disk.pinned, f"leaked disk pins: {sorted(disk.pinned)}")
        _iv(not mgr._reading, f"_reading not drained: {sorted(mgr._reading)}")
        _iv(not mgr._jobs, f"jobs leaked past stop(): {list(mgr._jobs)}")
        _iv(not pool.pinned, f"leaked device pins: {sorted(pool.pinned)}")


class _NoAdoptPrefetch:
    """Pre-fix on_hint: always queues a fresh job, double-dispatching the
    disk read when the previous job's read is still in flight. Built
    lazily so importing this module never constructs it by accident."""

    def __new__(cls, *a, **kw):
        from dynamo_tpu.kvbm.prefetch import QUEUED, PrefetchManager, _Job

        class _Twin(PrefetchManager):
            def on_hint(self, hint):
                hashes = [int(h) for h in (hint.get("hashes") or [])]
                parents = list(hint.get("parents") or [])
                if not hashes:
                    return
                self.stats["hints"] += 1
                now = self._clock()
                for i, h in enumerate(hashes):
                    if h in self._jobs or h in self.pool.by_hash:
                        continue
                    parent = parents[i] if i < len(parents) else None
                    parent = int(parent) if parent is not None else None
                    self._jobs[h] = _Job(h, parent, now,
                                         now + self.hint_ttl_s)
                    self._queue.append(h)
                    self.stats["hinted_blocks"] += 1
                self._pump()

        return _Twin(*a, **kw)


class PrefetchTtlBuggySpec(PrefetchTtlSpec):
    name = "prefetch_ttl_buggy"
    expect_violation = True
    manager_cls = _NoAdoptPrefetch


# ---------------------------------------------------------------------------
# indexer_resync — live events racing the seed/recovery dump
# ---------------------------------------------------------------------------

class _NullSub:
    def connect(self, address: str) -> None:
        pass

    def disconnect(self, address: str) -> None:
        pass


class _FakeWorkerState:
    """The worker's own ground truth: feeder events mutate it in the same
    breath they are emitted toward the indexer, and the dump endpoint
    snapshots it at call time (the RPC *response* may still arrive after
    later events — exactly the production race)."""

    def __init__(self) -> None:
        self.blocks: Dict[int, Optional[int]] = {1: None, 2: 1}
        self.last = 3

    def emit(self, idx, event_id: int, kind: str, h: int) -> None:
        from dynamo_tpu.router.protocols import RouterEvent

        if kind == "store":
            self.blocks[h] = None
        else:
            self.blocks.pop(h, None)
        self.last = event_id
        idx._apply(RouterEvent(worker=W, event_id=event_id, kind=kind,
                               block_hashes=[h], parent_hash=None))

    def dump(self, delay: float, alive=None):
        """Snapshot at call time, delivered `delay` later. `alive()`
        models production `_dump_worker`, which raises for an instance
        discovery no longer lists — a dump STARTED after removal fails;
        one captured before and landing after is the epoch guard's job."""

        async def _dump(instance_id: int) -> Dict[str, Any]:
            if alive is not None and not alive():
                raise RuntimeError(f"worker {instance_id:x} gone")
            snap = {"blocks": [(h, p) for h, p in self.blocks.items()],
                    "last_event_id": self.last}
            await asyncio.sleep(delay)
            return snap

        return _dump


class IndexerResyncSpec(Spec):
    """A seed resync (dump RPC in flight for 0.05 virtual seconds) races
    two live events: store(3) at ev4 and remove(1) at ev5. Sequential
    model: whatever the interleaving, the index must converge to the
    worker's true final state {2, 3} with watermark 5 — the unbuffered
    indexer wipes live-applied events with the older snapshot,
    resurrects the removed block, and rewinds the watermark."""

    name = "indexer_resync"
    indexer_cls: Any = None

    def build(self, env: SpecEnv) -> None:
        from dynamo_tpu.router.indexer import KvIndexer
        from dynamo_tpu.router.radix_tree import BlockIndex

        cls = self.indexer_cls or KvIndexer
        truth = _FakeWorkerState()
        idx = cls(_NullSub(), index=BlockIndex(),
                  dump_fn=truth.dump(delay=0.05))
        env.data.update(idx=idx, truth=truth)

        async def resyncer() -> None:
            await idx.resync_worker(W)

        async def feeder() -> None:
            await asyncio.sleep(0.01)
            truth.emit(idx, 4, "store", 3)
            await asyncio.sleep(0.01)
            truth.emit(idx, 5, "remove", 1)

        env.spawn("resyncer", resyncer())
        env.spawn("feeder", feeder())

    def invariant(self, env: SpecEnv) -> None:
        idx, truth = env.data["idx"], env.data["truth"]
        got = set(idx.index.worker_blocks.get(W, set()))
        want = set(truth.blocks)
        # the explorer may stall the loop past DUMP_TIMEOUT_S, in which
        # case the snapshot never applies and only the live events count:
        # degraded ({3}) but correct — a later resync would backfill. What
        # must NEVER appear: the removed block resurrected or the stored
        # block lost ({1, 2} — the unbuffered wipe-and-rewind signature).
        live_only = {3}
        _iv(got in (want, live_only),
            f"index diverged from worker truth: {sorted(got)} != "
            f"{sorted(want)} (lost/resurrected blocks across resync)")
        _iv(idx._last_event_id.get(W) == truth.last,
            f"watermark rewound: {idx._last_event_id.get(W)} != "
            f"{truth.last} — the rewind window re-applies or drops events")


class _UnbufferedIndexer:
    """Pre-fix resync_worker: no event buffering, no epoch guard — the
    dump lands over whatever the live stream did during the await."""

    def __new__(cls, *a, **kw):
        from dynamo_tpu.router.indexer import KvIndexer
        from dynamo_tpu.router.protocols import RouterEvent

        class _Twin(KvIndexer):
            async def resync_worker(self, worker):
                if self._dump_fn is None:
                    return
                try:
                    dump = await asyncio.wait_for(
                        self._dump_fn(worker[0]),
                        timeout=self.DUMP_TIMEOUT_S)
                except asyncio.CancelledError:
                    raise
                except (asyncio.TimeoutError, Exception):
                    return
                self.index.remove_worker(worker)
                blocks = {int(h): (int(p) if p is not None else None)
                          for h, p in dump.get("blocks", [])}
                emitted = set()
                for h0 in list(blocks):
                    chain = []
                    h = h0
                    while (h is not None and h not in emitted
                           and h in blocks):
                        chain.append(h)
                        h = blocks[h]
                    for h in reversed(chain):
                        self.index.apply_event(
                            RouterEvent(worker=worker, event_id=0,
                                        kind="store", block_hashes=[h],
                                        parent_hash=blocks[h]),
                            ttl=self.ttl)
                        emitted.add(h)
                self._last_event_id[worker] = int(
                    dump.get("last_event_id", 0))

        return _Twin(*a, **kw)


class IndexerResyncBuggySpec(IndexerResyncSpec):
    name = "indexer_resync_buggy"
    expect_violation = True
    indexer_cls = _UnbufferedIndexer


# ---------------------------------------------------------------------------
# indexer_churn — discovery delete racing an in-flight resync
# ---------------------------------------------------------------------------

class IndexerChurnSpec(Spec):
    """A discovery delete (remove_worker) lands while the worker's resync
    dump is in flight. Contract: once removed, the worker must stay out
    of the index — a resync completing afterwards must not repopulate it
    with a corpse's blocks (the epoch guard)."""

    name = "indexer_churn"
    indexer_cls: Any = None

    def build(self, env: SpecEnv) -> None:
        from dynamo_tpu.router.indexer import KvIndexer
        from dynamo_tpu.router.radix_tree import BlockIndex

        cls = self.indexer_cls or KvIndexer
        truth = _FakeWorkerState()
        env.data["alive"] = True
        idx = cls(_NullSub(), index=BlockIndex(),
                  dump_fn=truth.dump(delay=0.05,
                                     alive=lambda: env.data["alive"]))
        env.data.update(idx=idx)

        async def resyncer() -> None:
            await idx.resync_worker(W)

        async def remover() -> None:
            await asyncio.sleep(0.03)
            env.data["alive"] = False
            idx.remove_worker(W)

        env.spawn("resyncer", resyncer())
        env.spawn("remover", remover())

    def invariant(self, env: SpecEnv) -> None:
        idx = env.data["idx"]
        ghost = sorted(idx.index.worker_blocks.get(W, set()))
        _iv(not ghost,
            f"removed worker resurrected in the index with blocks {ghost}")
        _iv(W not in idx._last_event_id,
            "removed worker still has an event watermark")


class IndexerChurnBuggySpec(IndexerChurnSpec):
    name = "indexer_churn_buggy"
    expect_violation = True
    indexer_cls = _UnbufferedIndexer


# ---------------------------------------------------------------------------
# migration_handoff — mid-stream worker death and token replay
# ---------------------------------------------------------------------------

class _FlakyEngine:
    """Request-plane fake: two concurrent streams; stream 'a' dies with a
    migratable disconnect after two tokens, the retry finishes it."""

    def __init__(self, env: SpecEnv) -> None:
        self.env = env
        self.attempts: Dict[str, int] = {}

    async def generate(self, request, context):
        from dynamo_tpu.runtime.request_plane import RequestPlaneError

        rid = request["rid"]
        attempt = self.attempts.get(rid, 0) + 1
        self.attempts[rid] = attempt
        base = list(request["token_ids"])
        if rid == "a" and attempt == 1:
            await asyncio.sleep(0.01)
            yield {"token_ids": [101]}
            await asyncio.sleep(0.01)
            yield {"token_ids": [102]}
            await asyncio.sleep(0.01)
            raise RequestPlaneError("worker died", code="disconnected")
        # a retry must carry the already-delivered tokens in its prompt
        self.env.data["replayed"][rid] = base
        await asyncio.sleep(0.01)
        yield {"token_ids": [103], "finish_reason": "stop"}


class MigrationHandoffSpec(Spec):
    """Two requests stream through Migration concurrently; one worker
    connection dies mid-stream. Contract: downstream consumers see every
    token exactly once and in order, the retry's prompt replays exactly
    the tokens already delivered, and the non-failing stream is
    unaffected."""

    name = "migration_handoff"

    def build(self, env: SpecEnv) -> None:
        from dynamo_tpu.frontend.migration import Migration
        from dynamo_tpu.runtime.context import Context

        env.data["replayed"] = {}
        env.data["tokens"] = {"a": [], "b": []}
        engine = _FlakyEngine(env)
        mig = Migration(engine, migration_limit=3, backoff_base_s=0.05)
        env.data.update(engine=engine, mig=mig)

        async def consume(rid: str) -> None:
            ctx = Context(request_id=rid)
            req = {"rid": rid, "token_ids": [1, 2], "stop": {}}
            async for item in mig.generate(req, ctx):
                env.data["tokens"][rid].extend(item.get("token_ids") or [])

        env.spawn("stream_a", consume("a"))
        env.spawn("stream_b", consume("b"))

    def invariant(self, env: SpecEnv) -> None:
        toks = env.data["tokens"]
        _iv(toks["a"] == [101, 102, 103],
            f"stream a delivered {toks['a']} != [101, 102, 103] "
            "(token lost or double-delivered across migration)")
        _iv(toks["b"] == [103], f"stream b delivered {toks['b']} != [103]")
        _iv(env.data["replayed"].get("a") == [1, 2, 101, 102],
            f"retry prompt {env.data['replayed'].get('a')} != "
            "[1, 2, 101, 102] (delivered tokens not folded into replay)")
        _iv(env.data["engine"].attempts == {"a": 2, "b": 1},
            f"attempt counts {env.data['engine'].attempts}")


# ---------------------------------------------------------------------------
# spawn_tracked — fire-and-forget lifecycle accounting
# ---------------------------------------------------------------------------

class SpawnTrackedSpec(Spec):
    """Three tracked background tasks: one finishes, one raises, one is
    cancelled by a fault mid-sleep. Contract: the strong-ref registry
    returns to its baseline (no leak, no premature GC window), the raise
    is consumed by the done-callback (never reaches the loop's unhandled
    sink), and cancellation is not logged as a failure."""

    name = "spawn_tracked"

    def build(self, env: SpecEnv) -> None:
        from dynamo_tpu.runtime.tasks import spawn_tracked, tracked_count

        env.data["baseline"] = tracked_count()
        env.data["done"] = []

        async def ok() -> None:
            await asyncio.sleep(0.01)
            env.data["done"].append("ok")

        async def boom() -> None:
            await asyncio.sleep(0.02)
            raise ValueError("background failure")

        async def sleeper() -> None:
            await asyncio.sleep(5.0)
            env.data["done"].append("sleeper")

        env.data["victim"] = spawn_tracked(
            sleeper(), name="victim", logger=_silent)
        spawn_tracked(ok(), name="ok", logger=_silent)
        spawn_tracked(boom(), name="boom", logger=_silent)

    def faults(self, env: SpecEnv) -> list:
        return [Fault("kill_sleeper",
                      lambda loop: env.data["victim"].cancel(),
                      when=lambda loop: not env.data["victim"].done())]

    def invariant(self, env: SpecEnv) -> None:
        from dynamo_tpu.runtime.tasks import tracked_count

        _iv(tracked_count() == env.data["baseline"],
            f"tracked-task registry leaked "
            f"{tracked_count() - env.data['baseline']} task(s)")
        _iv("ok" in env.data["done"], "completed task lost its side effect")


# ---------------------------------------------------------------------------
# actuator_apply — decide->rehearse->apply claim protocol (DYN-A007)
# ---------------------------------------------------------------------------

class _ActLoads:
    """One busy worker row; only the attributes the Actuator senses."""

    class _Row:
        worker = W
        n_samples = 8
        mean_waiting = 10.0
        mean_running = 4.0
        kv_usage = 0.9
        prefill_tok_s = 100.0
        decode_tok_s = 100.0

    def loads(self, now=None):
        return [self._Row()]


class _ActSlo:
    """Permanently breached fleet view: the condition never clears, so
    re-validation after the rehearsal await always passes — the CLAIM is
    the only thing standing between two overlapping ticks."""

    class _Policy:
        breach_burn = 2.0

    policy = _Policy()

    def evaluate(self, now=None):
        from dynamo_tpu.planner.slo import BREACH

        return {"state": BREACH,
                "fleet": {"ttft_p99": {"phase": "ttft", "state": BREACH,
                                       "fast": {"burn": 4.0}}},
                "workers": {}}


class _ActConnector:
    """Recording connector with a yield inside the apply — the window a
    second unclaimed tick would need to double-send."""

    def __init__(self, applied):
        self.applied = applied

    async def scale_to(self, component, target):
        await asyncio.sleep(0)
        self.applied.append((component, int(target)))


class _SlowOracle:
    """Rehearsal that parks across a timer: the decide->apply span is
    forced open so the explorer can land a whole second tick inside it."""

    async def rehearse(self, decision):
        await asyncio.sleep(0.01)
        return {"improves": True, "oracle": "static"}


class ActuatorApplySpec(Spec):
    """Three actuation ticks race over a breached fleet (the live shape:
    the periodic loop fires while an operator-triggered tick runs, or
    two frontends share a decisions root), with one tick cancellable
    mid-flight (actuator.stop during a rehearsal). The
    decide->rehearse->apply span crosses the rehearsal await, so the
    REAL Actuator claims the (kind, target) in `_inflight` BEFORE
    awaiting and re-checks after (planner/actuator.py `_execute`).
    Contract: the breach is acted on at most once — overlapping ticks
    must not double-scale — exactly once when nothing is cancelled,
    decisions reach terminal journal status, and no claim outlives its
    tick (cancellation included: the finally must release)."""

    name = "actuator_apply"

    actuator_cls = None  # default: the production Actuator

    def build(self, env: SpecEnv) -> None:
        from dynamo_tpu.planner.actuator import Actuator, ActuatorConfig

        applied: List[Any] = []
        env.data["applied"] = applied
        cls = self.actuator_cls or Actuator
        act = cls(
            _ActLoads(), _ActSlo(), _ActConnector(applied),
            ActuatorConfig(hysteresis_ticks=1, cooldown_s=1e9,
                           flap_guard_s=1e9, min_samples=1,
                           waiting_high=1.0),
            shadow=_SlowOracle(),
            replicas_fn=lambda: 1,
            clock=env.loop.time,
        )
        env.data["act"] = act

        async def ticker(name: str) -> None:
            try:
                await act.tick()
            except asyncio.CancelledError:
                env.data["cancelled"] = True
                raise

        env.spawn("tick_a", ticker("a"))
        env.spawn("tick_b", ticker("b"))
        env.spawn("tick_c", ticker("c"))

    def faults(self, env: SpecEnv) -> list:
        return [cancel_task("cancel_tick_b",
                            lambda loop: env.task("tick_b"))]

    def invariant(self, env: SpecEnv) -> None:
        act = env.data["act"]
        applied = env.data["applied"]
        cancelled = env.data.get("cancelled", False)
        for t in ("tick_a", "tick_b", "tick_c"):
            task = env.task(t)
            _iv(task is not None and task.done(), f"{t} parked forever")
        _iv(len(applied) <= 1,
            f"breach applied {len(applied)}x (claim protocol broken: "
            f"{applied})")
        if not cancelled:
            _iv(len(applied) == 1, "sustained breach never acted on")
        _iv(not act._inflight, f"leaked in-flight claims: {act._inflight}")
        from dynamo_tpu.planner.actuator import TERMINAL

        stuck = [d for d in act.journal.decisions()
                 if d.status not in TERMINAL]
        # a cancelled tick may orphan ITS decision mid-rehearsal; any
        # other non-terminal decision is a journaling bug
        _iv(len(stuck) <= (1 if cancelled else 0),
            f"decisions stuck non-terminal: "
            f"{[(d.decision_id, d.status) for d in stuck]}")


class _RacyActuator:
    """Buggy twin: claims the target AFTER the rehearsal await — the
    pre-claim-protocol shape. Two overlapping ticks both pass the gates,
    both rehearse, both apply: a double-scale."""

    def __new__(cls, *a, **kw):
        from dynamo_tpu.planner.actuator import Actuator

        class _Twin(Actuator):
            async def _execute(self, d):
                key = d.target_key
                if key in self._inflight:
                    self._finish(d, "skipped", note="in-flight")
                    return
                self._record(d, "rehearsed")
                d.verdict = await self.shadow.rehearse(d)  # BUG: no claim
                self._inflight.add(key)                    # ...until here
                try:
                    if await self._apply(d):
                        self._cooldown_until[key] = (
                            self.clock() + self.config.cooldown_s)
                        self._finish(d, "applied")
                    else:
                        self._finish(d, "failed")
                finally:
                    self._inflight.discard(key)

        return _Twin(*a, **kw)


class ActuatorApplyBuggySpec(ActuatorApplySpec):
    name = "actuator_apply_buggy"
    expect_violation = True
    actuator_cls = _RacyActuator


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# production specs: every interleaving must hold (mc_ok gate)
SPECS: Dict[str, Any] = {
    s.name: s for s in (
        AdmissionQueueSpec,
        PrefetchTtlSpec,
        IndexerResyncSpec,
        IndexerChurnSpec,
        MigrationHandoffSpec,
        SpawnTrackedSpec,
        ActuatorApplySpec,
    )
}

# known-bad twins + seeded fixture: the checker must FIND a violation
FIXTURES: Dict[str, Any] = {
    s.name: s for s in (
        LostWakeupFixture,
        PrefetchTtlBuggySpec,
        IndexerResyncBuggySpec,
        IndexerChurnBuggySpec,
        ActuatorApplyBuggySpec,
    )
}

ALL_SPECS: Dict[str, Any] = {**SPECS, **FIXTURES}
