"""Always-on engine flight recorder: a fixed-size ring of per-iteration
records plus an EWMA-based anomaly trigger.

The engine step loop appends ONE `IterationRecord` per dispatched
iteration (engine/engine.py `_loop_once`): what the scheduler composed
(decode batch x fused steps, packed prefill chunks and their real vs
charged tokens, ragged vs padded program, fused vs two-dispatch), what it
cost (dispatch + host-sync wall time), and what the world looked like
(admission-queue depth, KV occupancy per tier, prefetch hits,
compile-family cache growth). The ring is the answer to "what was the
engine doing at 14:03:07" without any profiler attached — vLLM's
stat-logger loop and Orca's iteration-level scheduling both treat the
iteration as the unit of observability, and so does this.

Design constraints (enforced by the DYN-R004 dynlint rule):
- `append()` and everything it calls run on the engine STEP thread —
  no blocking I/O, no locks shared with slow consumers, no allocation
  beyond the record itself. The ring is a preallocated list; EWMA math
  is a few floats; anomaly dumps hand a snapshot to a daemon thread via
  `put_nowait` and drop on overflow.
- Readers (`snapshot()`, the /debug/timeline exporter) tolerate torn
  reads: records are immutable once appended, so the worst case is a
  just-overwritten slot appearing once, never a half-written record.

Anomaly trigger: per-kind EWMA of iteration wall time; an iteration
exceeding `ewma * anomaly_k` (after `anomaly_min_samples` warmup) fires
ONCE per excursion — the trigger re-arms only after a sub-threshold
iteration of the same kind, so a sustained stall produces one dump, not
one per iteration. A fired trigger hands the record to the daemon
thread, which logs ONE line that says who held the iteration (`stall_line`:
the record's phases, `exposed_s`, `gc_s`, the compiled variants' growth)
and, where `anomaly_dump_dir` is set, writes the last N records (snapshot
at fire time) as JSON under it and (optionally) opens a `jax.profiler`
capture window so the NEXT stall of a recurring pathology lands in a real
trace.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional

from dynamo_tpu.runtime.annotations import (
    EMIT, PHASES, RECORD_PHASES, STAGE, StepClock)

log = logging.getLogger("dynamo_tpu.flight_recorder")


@dataclass(slots=True)
class IterationRecord:
    """One engine iteration, as the scheduler composed and the runner
    executed it. All counters that read "cumulative" are monotonically
    increasing process totals sampled at append time (deltas between
    consecutive records give per-iteration rates).

    `wall_s` runs from commit to commit: from the moment the iteration
    before this one was published (or the loop last found nothing to do)
    to the moment this one was. The step loop keeps one decode dispatch in
    flight, so an iteration's own enqueue-to-commit span overlaps its
    neighbours' and would count the device's time twice; commit to commit
    it is what a token waits for an iteration, and the records' walls add
    up to the loop's busy time: everything but its idle sleeps
    (`engine.wait`), the inbox and the scheduler included. `ts` is when
    the iteration's staging began, which for an iteration enqueued ahead
    is before the one before it was committed.

    `ahead`: the iteration was planned, staged and enqueued before the
    iteration before it was read back (its rows took their first tokens
    on the device). `drain` says why not, "" where it was: "cold" nothing
    was in flight (the iteration before was no plain decode, or the loop
    idled), "rows" the plan held a row the dispatch in flight did not
    (a joiner), "bucket" the rows left fit a smaller decode bucket,
    "prefill" / "mixed" the plan carried prompt chunks, "preempt" the
    scheduler needed a preemption, "spec" / "guided" / "penalties" the
    next plan needs this one's tokens on the host, "runner" the runner
    cannot run ahead (pipeline or sequence parallel programs, a multi-host
    group), "shutdown" the engine is stopping.

    The step loop's host clock (runtime/annotations.py, the door): what
    the step thread did between the two commits `wall_s` runs between,
    whichever iteration the work was for (under run-ahead the staging in
    an iteration's interval is the NEXT iteration's, the delivery after a
    drain the iteration BEFORE's). `host_<phase>_s` for the nine phases
    the profiler's spans name (`engine.inbox`, `schedule`, `prep`,
    `stage`, `dispatch`, `readback`, `emit`, `publish`, `deliver`): seconds
    inside that span and no span inside it, so they add up to at most
    `wall_s` and the rest is the loop's own glue. `host_readback_s` is
    the time the step thread was blocked on the device: the device-bound
    share of the loop. `host_emit_s` is the commit half of an emit (stop
    checks, the guided DFA, the rows' items), `host_deliver_s` the other
    half: the latency spine, the hand-off of the items to the event
    loops, the FPM and KV-event listeners and the record's append, which
    the engine runs once the next program is enqueued (docs/concurrency.md,
    "Commit, enqueue, deliver"). `deliver_under`: THIS iteration's items
    and publish were delivered while a program of the engine's was
    enqueued and not collected (the step clock's `handles`), so the device
    had work meanwhile; False where nothing was left to enqueue (the
    loop idled after it), a step failed, or the engine stopped.
    `exposed_s`: the part of the other eight that ran
    while the step thread had NOTHING enqueued on the device and not
    collected, so the device was provably idle for want of the host;
    `exposed_stage_s`, `exposed_emit_s` its two largest owners. What is
    not exposed is hidden under a queued program (where the host outlasts
    the program the device idles all the same: the device trace judges
    that). `gc_s`: seconds of garbage collection on the step thread
    inside the interval (they lie inside whatever phase ran; usually
    0.0). All 0.0 where the recorder is off (nothing is recorded then)
    or the runner names no phase. (`engine.wait`, the idle sleeps, is in
    no iteration's wall and on no record: /metrics carries it as one more
    phase of `dynamo_engine_host_seconds_total`.)

    The `moe_*` fields are a routed model's expert load, reduced on
    the device from the router's picks over real rows (padding masked)
    and read back with the sampled tokens; 0 / 0.0 for a dense model.
    The load is over the experts this engine HOLDS: all `n_experts`, or
    the `n_experts_held` from `expert_first` on of a chip that holds a
    share (the picks themselves stay ids over the router's full width).
    `moe_token_slots` [token-slots] real tokens x experts a token, over
    the iteration's forwards (rows x steps x k + chunk tokens x k);
    `moe_held_slots` [token-slots] those of them that fell to held
    experts, mean over expert layers (all of them where every expert is
    held; a quarter where a quarter is and the routing is even);
    `moe_experts_hit` [experts, of the held] how many were picked at
    least once in a forward, mean over expert layers and forwards (what
    a step reads of the expert weights where only the picked are read);
    `moe_load_max_share` [fraction] the share of a forward's real tokens
    that picked the layer's fullest held expert, the same mean (k /
    n_experts is an even load, 1.0 is every token on one expert: a
    straggler).
    A forward is one pass of the layers: a decode step, a prefill chunk
    set, a ragged step.
    `moe_experts_listed` [work-list entries] is the expert kernel's unit
    of work (ops/moe_experts.py): the entries of the work lists of hit
    experts the iteration's kernels walked, summed over forwards and
    expert layers, taken from the live count each kernel was given (not
    recomputed from the picks); 0 where the dense path ran (a prefill
    chunk, a CPU, int8 experts, a mesh). Over an iteration whose forwards
    all took the kernel it equals `moe_experts_hit` x forwards x expert
    layers: padding rows list nothing.

    `decode_pages_live` [pages] is the decode kernel's unit of work: what
    one layer's calls walked this iteration, the sum over decode rows and
    fused steps of the pages a row's context holds (from the first page
    its sliding window shows to the page of its newest token; a model
    whose layers alternate sliding and global counts the mean over
    layers). From the positions the engine holds on the host: no device
    work. Beside it `decode_seqs x decode_steps` gives rows, and the
    kernel's device seconds over it the cost of a live page.

    `dsa_ctx_tokens` / `dsa_sel_tokens` [tokens] are a model with an
    indexer's (ModelConfig.has_indexer; 0 for every other): over the
    iteration's decode rows and fused steps, the cached tokens ONE layer's
    indexer scored (a row's context, position + t + 1 at step t) and the
    tokens its attention then read, min(context, index_topk). Their ratio
    is the share of the cached context a decode step attends to; times the
    layers they are the index keys read and the latent rows gathered. From
    the positions the engine holds on the host, like `decode_pages_live`.

    `ragged_pages_live` [pairs] is the ragged (mixed-step) kernel's unit
    of work: the live (work unit, page) pairs one layer's call walked,
    0 where no ragged program ran. A work unit is the rows of one 8-row
    q block that belong to one segment (a decode row, a verify row with
    its draft, a prefill chunk); it sees the pages from the first its
    sliding window shows to the page of its last row's position (the
    mean over layers where sliding and global alternate). Counted by the
    runner from the dispatch's host metadata (MixedOut.pages_live): no
    device work. The kernel's device seconds over it is the cost of a
    live pair; over `NW x MP` (ops.ragged_paged_attention.ragged_work_cap
    of the T bucket, times the page table's width) it is the share of
    the old (NW, MP) grid that was live. A ragged iteration's decode rows
    are walked here at step 0 and by the decode kernel from step 1 on, so
    `decode_pages_live` leaves their step 0 out."""

    seq: int               # engine iteration number (monotonic)
    ts: float              # wall clock (time.time()) at iteration start
    wall_s: float          # commit to commit (above)
    kind: str              # "prefill" | "decode" | "mixed"
    decode_seqs: int       # decode batch rows this iteration
    decode_steps: int      # fused decode steps (T)
    n_chunks: int          # packed prefill chunks served
    chunk_tokens: int      # real prefill tokens served
    charged_tokens: int    # tokens the dispatch was CHARGED for (padding
    #   and bucket round-up included; == chunk_tokens when unknowable)
    ragged: bool           # ragged flat-token program vs padded fallback
    fused: bool            # one fused dispatch vs decode+prefill halves
    n_waiting: int         # admission queue depth after the step
    n_running: int
    kv_usage: float        # G1 device pool occupancy fraction
    g2_blocks: int         # host-tier resident blocks (0 = tier off)
    g3_blocks: int         # disk-tier resident blocks (0 = tier off)
    prefetch_hits: int     # cumulative prefetched-block claims
    compile_variants: int  # cumulative compiled jit variants (all families)
    decode_pages_live: int = 0  # live KV pages walked (see the docstring)
    ragged_pages_live: int = 0  # live (unit, page) pairs of a ragged step
    dsa_ctx_tokens: int = 0  # an indexer's model: tokens one layer's
    dsa_sel_tokens: int = 0  # selection scored, and kept (docstring)
    anomaly: bool = False  # this iteration fired the EWMA trigger
    ahead: bool = False    # enqueued before the one before was read back
    drain: str = ""        # why not (the closed set above); "" where ahead
    # the step thread's host clock over the wall's interval (docstring)
    host_inbox_s: float = 0.0
    host_schedule_s: float = 0.0
    host_prep_s: float = 0.0
    host_stage_s: float = 0.0
    host_dispatch_s: float = 0.0
    host_readback_s: float = 0.0
    host_emit_s: float = 0.0
    host_publish_s: float = 0.0
    host_deliver_s: float = 0.0
    deliver_under: bool = False   # delivered under an enqueued program
    exposed_s: float = 0.0        # of those but readback, nothing enqueued
    exposed_stage_s: float = 0.0
    exposed_emit_s: float = 0.0
    gc_s: float = 0.0             # collections on the step thread
    # speculative decoding: mean tokens emitted per speculating row this
    # iteration (accepted drafts + the verified/bonus token; 0.0 when no
    # row speculated) — the per-step multi-token factor the ITL spine
    # divides by, surfaced in the fleet digest
    accepted_per_step: float = 0.0
    # agentic session-tree serving
    guided_rows: int = 0       # constraint-masked decode rows this iteration
    # routed experts (see the docstring; engine `_record_iteration`)
    moe_token_slots: int = 0
    moe_experts_hit: float = 0.0
    moe_load_max_share: float = 0.0
    moe_held_slots: float = 0.0
    moe_experts_listed: int = 0
    # a model with state-space layers (0 for every other): sequences that
    # hold a state slot after the step and the slots there are (scratch
    # left out); what the selective-scan kernel was given this iteration
    # (tokens and segments of its flat axis: a ragged step's decode rows,
    # one token each, and chunks; a standalone prefill's chunk), 0 where
    # only the decode loop's one-token update ran
    state_slots_used: int = 0
    state_slots_total: int = 0
    ssm_scan_tokens: int = 0
    ssm_scan_segments: int = 0
    # a model with KDA layers (models/ling.py; it keeps state slots too):
    # what its kernels were given this iteration, a KDA layer: rows of the
    # decode loop's one-token `kda_update` summed over its steps, and the
    # tokens and segments (chunks) of `kda_chunk`
    kda_update_rows: int = 0
    kda_chunk_tokens: int = 0
    kda_chunk_segments: int = 0
    # a model with a window pool (0 for every other): the window pool's
    # pages in use after the step and the pages there are (scratch left
    # out); the tokens of context those pages hold and the tokens of
    # context alive (the active sequences' computed lengths: what a uniform
    # pool would hold for the window layers too); and `decode_pages_live`
    # by kind: the live pages one global layer's, and one window layer's,
    # decode-kernel calls walk over the iteration's steps (the pages given
    # back as they leave the window are the side cache's own total, on
    # /metrics as `dynamo_window_pages_freed_total`)
    window_pages_used: int = 0
    window_pages_total: int = 0
    window_tokens_resident: int = 0
    context_tokens_live: int = 0
    decode_pages_live_window: int = 0
    decode_pages_live_global: int = 0
    # a model whose cross-decoder runs on the sampled rows alone
    # (models/sambay.py; it keeps a state slot and window pages too): the
    # rows it ran on this iteration (every decode row of every step, the
    # last row of each segment of a ragged step, a chunk served alone that
    # ended its prompt) and the chunk tokens it did not run on, as the
    # runner counted its dispatches (Runner.fill_record)
    yoco_cross_rows: int = 0
    yoco_skipped_tokens: int = 0
    # causal tracing: trace ids of the requests this iteration served
    # (bounded by the engine at append time) — joins the per-iteration
    # timeline to the distributed span rings and incident bundles
    trace_ids: List[str] = field(default_factory=list)


# the record's fields for the step clock's phases, in the clock's order
_HOST_FIELDS = tuple(f"host_{p}_s" for p in RECORD_PHASES)

# What the step loop was doing between two commit marks, by class of
# iteration: the engine's running totals (`InferenceEngine.class_ns`) and a
# request's `decode_<class>_s` on the latency spine are keyed by these
# (docs/observability.md "Run-ahead"). `wait` is no iteration's: the time
# between a mark and the loop's next one when it found nothing to enqueue.
ITERATION_CLASSES = ("ahead", "cold", "mixed", "prefill", "other", "wait")


def iteration_class(kind: str, ahead: bool) -> str:
    """The class of an iteration, from its record's `kind` and `ahead`: a
    plain decode is `ahead` where it was enqueued before the one before it
    was read back and `cold` where that one was drained first (whatever
    `drain` says why: a joiner, a bucket, speculation's verify); `mixed`
    carried prompt chunks beside decode rows, as one dispatch or two;
    `prefill` served prompt chunks alone; `other` is any kind the loop
    grows that this rule has not been told of."""
    if kind == "decode":
        return "ahead" if ahead else "cold"
    if kind == "mixed":
        return "mixed"
    if kind in ("prefill", "prefill_packed"):
        return "prefill"
    return "other"


@dataclass
class _AnomalyDump:
    """What the writer thread is handed when the trigger fires: the
    record that fired, how far the compiled variants grew over the record
    before it, and (where dumps are written) a snapshot of the ring."""

    fired_ts: float
    trigger: IterationRecord
    ewma_s: float
    k: float
    variants_grew: int = 0
    records: List[IterationRecord] = field(default_factory=list)


class FlightRecorder:
    """Fixed-size iteration ring + EWMA anomaly trigger.

    `capacity <= 0` builds a disabled recorder: `append()` is a no-op
    and every surface reports empty — the A/B knob for the overhead
    bench and the `--recorder-size 0` worker flag."""

    def __init__(
        self,
        capacity: int = 4096,
        *,
        anomaly_k: float = 4.0,        # fire when wall > ewma * k (0 = off)
        anomaly_min_samples: int = 32,  # per-kind warmup before arming
        anomaly_dump_dir: Optional[str] = None,  # None = count, don't dump
        anomaly_dump_last_n: int = 256,
        anomaly_profile_ms: int = 0,   # >0: jax.profiler window per dump
        ewma_alpha: float = 0.05,
    ):
        self.capacity = max(0, int(capacity))
        self.enabled = self.capacity > 0
        self._ring: List[Optional[IterationRecord]] = [None] * self.capacity
        self._n = 0  # total records ever appended
        self.anomaly_k = float(anomaly_k)
        self.anomaly_min_samples = int(anomaly_min_samples)
        self.anomaly_dump_dir = anomaly_dump_dir
        self.anomaly_dump_last_n = int(anomaly_dump_last_n)
        self.anomaly_profile_ms = int(anomaly_profile_ms)
        self._alpha = float(ewma_alpha)
        self._ewma: Dict[str, float] = {}      # kind -> smoothed wall_s
        self._ewma_n: Dict[str, int] = {}      # kind -> samples folded in
        self._armed: Dict[str, bool] = {}      # kind -> trigger re-armed
        self.anomalies_fired = 0
        self.dumps_written = 0
        self.dumps_dropped = 0   # writer queue full at fire time
        self._dump_q: "queue.Queue[_AnomalyDump]" = queue.Queue(maxsize=4)
        self._dump_thread: Optional[threading.Thread] = None
        # metrics are bind-time optional (worker_common re-homes them onto
        # the status-port hierarchy); None until bound
        self._m_anomalies = None
        self._metrics = None
        self._m_host = None  # per PHASES index: (hidden, exposed) counters
        # anomaly-fire hooks (incident capture arming): called on the STEP
        # thread with the triggering record — handlers must be hand-off
        # cheap (put_nowait into their own queue), never blocking I/O
        self._anomaly_hooks: List[Any] = []

    def on_anomaly(self, cb) -> None:
        """Register cb(rec: IterationRecord) fired when the EWMA trigger
        trips. Runs on the engine step thread — the handler must hand off
        (DYN-R004 applies to it exactly like it applies to append)."""
        self._anomaly_hooks.append(cb)

    def bind_metrics(self, metrics) -> None:
        """Re-home the fired-dumps counter onto a shared MetricsHierarchy
        (the worker calls this with runtime.metrics at serve time)."""
        node = metrics.child(dynamo_component="flight_recorder")
        self._m_anomalies = node.counter(
            "flight_recorder_anomalies_total",
            "iterations that exceeded the EWMA*k wall-time threshold")
        self._metrics = metrics  # the step clock's seconds: take_clock

    # -- hot path (engine step thread; DYN-R004: no blocking I/O) ----------
    def take_clock(self, rec: IterationRecord, clock: StepClock) -> None:
        """Empty the step thread's host clock, whose interval the engine
        has just closed at `rec`'s commit mark (`StepClock.cut`), into
        `rec`'s host fields and, where /metrics is bound, into
        `dynamo_engine_host_seconds_total{phase, exposed}` (there with the
        idle sleeps since the record before, `phase="wait"`)."""
        ns, ex = clock.ns, clock.exposed_ns
        exposed = 0
        for i, name in enumerate(_HOST_FIELDS):
            setattr(rec, name, ns[i] * 1e-9)
            exposed += ex[i]
        rec.exposed_s = exposed * 1e-9
        rec.exposed_stage_s = ex[STAGE] * 1e-9
        rec.exposed_emit_s = ex[EMIT] * 1e-9
        rec.gc_s = clock.gc_ns * 1e-9
        if self._metrics is not None:
            if self._m_host is None:
                # made once, by the first record of an engine with a clock
                # (a mocker's recorder never gets here and shows no series)
                self._m_host = [tuple(self._metrics.counter(
                    "engine_host_seconds_total",
                    "step-thread seconds by phase of the iteration (wait: "
                    "the idle sleeps between iterations), and by whether "
                    "nothing was enqueued on the device meanwhile",
                    phase=ph, exposed=label) for label in ("false", "true"))
                    for ph in PHASES]
            for i, (hidden, shown) in enumerate(self._m_host):
                e = ex[i]
                if ns[i] != e:
                    hidden.inc((ns[i] - e) * 1e-9)
                if e:
                    shown.inc(e * 1e-9)
        clock.clear(wait=True)

    def append(self, rec: IterationRecord) -> None:
        if not self.enabled:
            return
        self._record_anomaly(rec)
        self._ring[self._n % self.capacity] = rec
        self._n += 1

    def _record_anomaly(self, rec: IterationRecord) -> None:
        """EWMA threshold check + fire-once-per-excursion bookkeeping.
        Runs on the step thread: the dump itself is handed off via
        put_nowait and written elsewhere."""
        if self.anomaly_k <= 0.0:
            return
        kind = rec.kind
        ewma = self._ewma.get(kind)
        n = self._ewma_n.get(kind, 0)
        if (ewma is not None and n >= self.anomaly_min_samples
                and rec.wall_s > ewma * self.anomaly_k):
            if self._armed.get(kind, True):
                self._armed[kind] = False
                rec.anomaly = True
                self.anomalies_fired += 1
                if self._m_anomalies is not None:
                    self._m_anomalies.inc()
                # the stall line and the dump are the writer thread's
                prev = self._ring[(self._n - 1) % self.capacity]
                dump = _AnomalyDump(
                    fired_ts=rec.ts, trigger=rec, ewma_s=ewma,
                    k=self.anomaly_k,
                    variants_grew=0 if prev is None else (
                        rec.compile_variants - prev.compile_variants),
                    records=self.snapshot(self.anomaly_dump_last_n)
                    if self.anomaly_dump_dir else [],
                )
                try:
                    self._dump_q.put_nowait(dump)
                except queue.Full:
                    self.dumps_dropped += 1
                self._ensure_dump_thread()
                for hook in self._anomaly_hooks:
                    try:
                        hook(rec)
                    except Exception:  # pragma: no cover
                        log.exception("anomaly hook failed")
            # anomalous samples do NOT move the EWMA: the baseline keeps
            # tracking steady state so a sustained stall stays anomalous
            return
        self._armed[kind] = True
        if ewma is None:
            self._ewma[kind] = rec.wall_s
        else:
            self._ewma[kind] = ewma + self._alpha * (rec.wall_s - ewma)
        self._ewma_n[kind] = n + 1

    def _ensure_dump_thread(self) -> None:
        if self._dump_thread is None or not self._dump_thread.is_alive():
            self._dump_thread = threading.Thread(
                target=self._dump_loop, name="flight-recorder-dump",
                daemon=True)
            self._dump_thread.start()

    # -- readers / cold path ------------------------------------------------
    def __len__(self) -> int:
        return min(self._n, self.capacity)

    @property
    def total_appended(self) -> int:
        return self._n

    def snapshot(self, last_n: Optional[int] = None) -> List[IterationRecord]:
        """Oldest-to-newest copy of the ring (or its last `last_n`
        records). Tolerates concurrent appends: a record overwritten
        mid-read is simply the newer one."""
        if not self.enabled:
            return []
        n = self._n
        count = min(n, self.capacity)
        if last_n is not None:
            count = min(count, max(0, int(last_n)))
        out: List[IterationRecord] = []
        for i in range(n - count, n):
            rec = self._ring[i % self.capacity]
            if rec is not None:
                out.append(rec)
        return out

    def to_chrome_trace(self, last_n: Optional[int] = None,
                        pid: int = 0) -> Dict[str, Any]:
        return to_chrome_trace(self.snapshot(last_n), pid=pid)

    def stats(self) -> Dict[str, Any]:
        """One-line counters for goodput extras / status surfaces."""
        return {
            "enabled": self.enabled,
            "capacity": self.capacity,
            "appended": self._n,
            "anomalies_fired": self.anomalies_fired,
            "dumps_written": self.dumps_written,
            "dumps_dropped": self.dumps_dropped,
            "ewma_s": {k: round(v, 6) for k, v in self._ewma.items()},
        }

    # -- dump plane (daemon thread: blocking I/O is fine here) --------------
    def _dump_loop(self) -> None:
        while True:
            try:
                dump = self._dump_q.get(timeout=30.0)
            except queue.Empty:
                return  # idle: let the thread die; refired on next anomaly
            log.warning("%s", stall_line(dump.trigger, dump.variants_grew))
            if not self.anomaly_dump_dir:
                continue
            try:
                self._write_dump(dump)
                self.dumps_written += 1
            except OSError:
                log.warning("anomaly dump write failed", exc_info=True)
            if self.anomaly_profile_ms > 0:
                self._profile_window()

    def _write_dump(self, dump: _AnomalyDump) -> str:
        os.makedirs(self.anomaly_dump_dir, exist_ok=True)
        path = os.path.join(
            self.anomaly_dump_dir,
            f"flight_dump_{dump.trigger.seq:08d}.json")
        payload = {
            "fired_ts": dump.fired_ts,
            "ewma_s": dump.ewma_s,
            "k": dump.k,
            "trigger_seq": dump.trigger.seq,
            # the trigger record itself: the ring snapshot was taken
            # before the trigger was appended, so it rides separately
            "trigger": asdict(dump.trigger),
            "records": [asdict(r) for r in dump.records],
        }
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        return path

    def _profile_window(self) -> None:
        """Best-effort jax.profiler capture window after a dump: the
        recurring pathology's NEXT occurrence lands in a real device
        trace. Off unless anomaly_profile_ms > 0; harmless in mocker
        processes where jax is absent."""
        try:
            import jax

            prof_dir = os.path.join(self.anomaly_dump_dir or ".",
                                    "anomaly_profile")
            jax.profiler.start_trace(prof_dir)
            time.sleep(self.anomaly_profile_ms / 1000.0)
            jax.profiler.stop_trace()
        except Exception:
            log.debug("anomaly profiler window unavailable", exc_info=True)


def stall_line(rec: IterationRecord, variants_grew: int) -> str:
    """One line that says who held a stalled iteration: a long `readback`
    is the device or the machine under it, a long host phase is the host,
    `gc_s` a collection inside one, and variants that grew a compile."""
    phases = " ".join(f"{p}={getattr(rec, name):.4f}"
                      for p, name in zip(RECORD_PHASES, _HOST_FIELDS))
    return (f"stalled iteration seq={rec.seq} kind={rec.kind} "
            f"drain={rec.drain or 'ahead'} wall_s={rec.wall_s:.4f} {phases} "
            f"exposed_s={rec.exposed_s:.4f} gc_s={rec.gc_s:.4f} "
            f"variants_grew={variants_grew}")


# -- Perfetto / Chrome-trace export -----------------------------------------

# track (tid) layout inside the engine process
_TID_SCHED = 0
_TID_DISPATCH = 1
_TID_SAMPLE = 2
_TID_KV = 3


def to_chrome_trace(records: List[IterationRecord],
                    pid: int = 0) -> Dict[str, Any]:
    """Render iteration records as Chrome-trace JSON (chrome://tracing /
    Perfetto "Open trace file"). Tracks: scheduler (queue counters),
    dispatch (one X slice per iteration), sample (emitted-token counter +
    anomaly instants), kv (tier occupancy counters). Every event carries
    the required ph/ts/pid/name keys; timestamps are wall-clock
    microseconds."""
    events: List[Dict[str, Any]] = [
        {"ph": "M", "ts": 0, "pid": pid, "name": "process_name",
         "args": {"name": "dynamo_tpu engine"}},
    ]
    for tid, tname in ((_TID_SCHED, "scheduler"), (_TID_DISPATCH, "dispatch"),
                       (_TID_SAMPLE, "sample"), (_TID_KV, "kv tiers")):
        events.append({"ph": "M", "ts": 0, "pid": pid, "tid": tid,
                       "name": "thread_name", "args": {"name": tname}})
    for rec in records:
        ts_us = rec.ts * 1e6
        events.append({
            "ph": "X", "ts": ts_us, "dur": max(0.0, rec.wall_s) * 1e6,
            "pid": pid, "tid": _TID_DISPATCH, "name": rec.kind,
            "args": {
                "seq": rec.seq,
                "decode_seqs": rec.decode_seqs,
                "decode_steps": rec.decode_steps,
                "n_chunks": rec.n_chunks,
                "chunk_tokens": rec.chunk_tokens,
                "charged_tokens": rec.charged_tokens,
                "ragged": rec.ragged,
                "fused": rec.fused,
                "compile_variants": rec.compile_variants,
                "ahead": rec.ahead,
                "drain": rec.drain,
                "exposed_s": rec.exposed_s,
                "gc_s": rec.gc_s,
                "trace_ids": list(getattr(rec, "trace_ids", []) or []),
            },
        })
        # the step thread's phases as child slices: true lengths, laid end
        # to end in the loop's order from the slice's start (the record
        # keeps seconds by phase, not when each began)
        at = ts_us
        for phase, name in zip(RECORD_PHASES, _HOST_FIELDS):
            dur = getattr(rec, name) * 1e6
            if dur <= 0.0:
                continue
            child = {"ph": "X", "ts": at, "dur": dur, "pid": pid,
                     "tid": _TID_DISPATCH, "name": "engine." + phase}
            if phase in ("stage", "emit"):
                child["args"] = {
                    "exposed_s": getattr(rec, f"exposed_{phase}_s")}
            events.append(child)
            at += dur
        events.append({
            "ph": "C", "ts": ts_us, "pid": pid, "tid": _TID_SCHED,
            "name": "queue",
            "args": {"waiting": rec.n_waiting, "running": rec.n_running},
        })
        events.append({
            "ph": "C", "ts": ts_us, "pid": pid, "tid": _TID_SAMPLE,
            "name": "scheduled_tokens",
            "args": {"tokens": rec.decode_seqs * rec.decode_steps
                     + rec.chunk_tokens},
        })
        events.append({
            "ph": "C", "ts": ts_us, "pid": pid, "tid": _TID_KV,
            "name": "kv",
            "args": {"g1_usage": rec.kv_usage, "g2_blocks": rec.g2_blocks,
                     "g3_blocks": rec.g3_blocks,
                     "prefetch_hits": rec.prefetch_hits},
        })
        if rec.anomaly:
            events.append({
                "ph": "i", "ts": ts_us, "pid": pid, "tid": _TID_SAMPLE,
                "name": "anomaly", "s": "p",
                "args": {"wall_s": rec.wall_s, "kind": rec.kind},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
