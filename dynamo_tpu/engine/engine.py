"""InferenceEngine: the native TPU serving engine as an AsyncEngine.

Bridges the asyncio worker process and the blocking JAX step loop: requests
enter via `generate()` (the standard worker protocol — PreprocessedRequest
in, engine-output items out), a dedicated step thread runs the
scheduler/runner loop, and sampled tokens flow back through per-request
asyncio queues (one cross-thread hop per engine step, not per token).

Fills the role the reference delegates to vLLM/SGLang/TRT-LLM AsyncLLM
(components/src/dynamo/vllm/handlers.py), natively on TPU.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import queue as thread_queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Dict, List, Optional

import numpy as np

from dynamo_tpu.engine.kv_pool import KvEvent, NoSpace, PagePool

from dynamo_tpu.engine import side_cache
from dynamo_tpu.engine.runner_api import BucketOverflowError, Runner
from dynamo_tpu.engine.scheduler import (
    StepsInFlight,
    DecodePlan,
    MixedPlan,
    PrefillPlan,
    Scheduler,
    SchedulerStats,
    Sequence,
    SeqState,
)
from dynamo_tpu.engine.ngram_draft import (
    accept_deterministic,
    accept_tree,
    propose as ngram_propose,
    propose_tree as ngram_propose_tree,
)
from dynamo_tpu.frontend.protocols import engine_output
from dynamo_tpu.models.config import mean_over_layers
from dynamo_tpu.runtime.annotations import (
    DELIVER,
    EMIT,
    INBOX,
    PREP,
    PUBLISH,
    SCHEDULE,
    WAIT,
    StepClock,
    annotate,
    bind_clock,
    phase,
    unbind_clock,
)
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.flight_recorder import (
    ITERATION_CLASSES,
    FlightRecorder,
    IterationRecord,
    iteration_class,
)
from dynamo_tpu.runtime import tracing

log = logging.getLogger("dynamo_tpu.engine")

# per-request ITL sample cap: bounds the spine's memory on long generations
_ITL_CAP = 512

# inbox ops that only queue or drop something on the host: every other one
# may block, so what is undelivered goes out before it (_drain_inbox)
_INBOX_LIGHT = frozenset((
    "add", "abort", "add_kv", "embed", "prefetch", "prefetch_disk",
    "prefetch_obj", "obj_event"))

# cached on a matcher whose schema exceeded the device DFA table budget,
# so the build (and its warning) happens once per matcher, not per dispatch
_OVER_BUDGET = object()


@dataclass
class ForwardPassMetrics:
    """Per-iteration engine metrics published for the planner (analog of
    reference FPM, docs/design-docs/planner-design.md:237-246)."""

    ts: float
    kind: str  # "prefill" | "decode"
    wall_time_s: float
    scheduled_tokens: int
    n_running: int
    n_waiting: int
    kv_usage: float


class GuidedMaskContext:
    """Per-dispatch host state that advances guided DFAs BETWEEN the steps
    of a fused decode loop (docs/agentic_serving.md). The runner's ordered
    io_callback calls `ctx(t, prev_tokens)` once per fused step; the
    context advances a COPY of each guided row's DFA state by the token
    that row sampled at step t-1 and returns the [B, V] sampling mask for
    step t. The engine's per-emitted-token `_guided_advance` stays
    authoritative — these copies exist only so constrained rows can ride
    full `decode_steps` loops instead of collapsing the whole plan to
    n_steps=1.

    `pending_advance=True` marks a context whose fed tokens have not been
    folded into the states yet (the ragged tail loop: tok0 was sampled on
    device by the ragged step), so the t=0 call advances too. A row whose
    copy hits EOS or desyncs goes all-True for the remaining steps — the
    engine discards tokens past a finish anyway."""

    def __init__(self, B: int, vocab: int, rows, pending_advance: bool = False):
        self.B = int(B)
        self.vocab = int(vocab)
        # row: [batch index, matcher, state copy, alive]
        self.rows = [[int(i), m, int(s), True] for i, m, s in rows]
        self.pending_advance = bool(pending_advance)
        self.calls = 0

    def _row_mask(self, m, state) -> np.ndarray:
        row = m.allowed(state)
        if not row.any():
            # degrade exactly like Engine._guided_mask: force EOS rather
            # than sampling garbage from an unextendable constraint
            row = row.copy()
            eos = m.lifter.eos_id
            if 0 <= eos < row.shape[0]:
                row[eos] = True
        return row

    def __call__(self, t, prev_tokens) -> np.ndarray:
        self.calls += 1
        t = int(t)
        mask = np.ones((self.B, self.vocab), bool)
        for row in self.rows:
            idx, m, state, alive = row
            if not alive:
                continue
            if t > 0 or self.pending_advance:
                tok = int(prev_tokens[idx])
                if tok == m.lifter.eos_id:
                    row[3] = False
                    continue
                try:
                    row[2] = state = m.advance(state, tok)
                except ValueError:
                    # desync (padding row fed a masked-out token, or the
                    # authoritative engine already finished the request)
                    row[3] = False
                    continue
            mask[idx] = self._row_mask(m, state)[: self.vocab]
        return mask


class _InFlight:
    """A plain decode dispatch the step loop has enqueued and not read
    back: `rows` its sequences by place, `live` which of them it really
    serves (the others are pad rows holding a place), `T` its fused steps,
    `handle` the runner's (None: the runner read it back itself, into
    `sampled`), and what the iteration's record needs from its enqueue:
    `ts` when its staging began, `rinfo` its composition."""

    __slots__ = ("rows", "live", "T", "n_lp", "ts", "rinfo", "handle",
                 "sampled")

    def __init__(self, rows, live, T, n_lp, ts, rinfo):
        self.rows, self.live, self.T, self.n_lp = rows, live, T, n_lp
        self.ts, self.rinfo = ts, rinfo
        self.handle = self.sampled = None


class InferenceEngine:
    def __init__(
        self,
        runner: Runner,
        *,
        max_batch: int = 64,
        chunk_size: int = 512,
        decode_steps: int = 4,
        mixed_prefill_tokens: int = 256,  # per-iteration prefill token POOL
        #   when co-scheduled with decode, fair-shared across packed chunks
        #   (0 = strict prefill-first alternation)
        mixed_prefill_seqs: int = 8,  # max distinct prefills packed per
        #   iteration (1 = legacy single-chunk MixedPlan)
        mixed_min_chunk: int = 16,  # fair-share floor per packed sequence
        idle_sleep_s: float = 0.002,
        host_kv_blocks: int = 0,  # G2 host-tier capacity (0 = disabled)
        disk_kv_blocks: int = 0,  # G3 disk-tier capacity (needs G2 enabled)
        disk_kv_root: Optional[str] = None,
        disk_kv_bytes: Optional[int] = None,  # G3 byte budget: exceeding
        #   it spills LRU blocks down to G4 even with block slots free
        obj_kv_root: Optional[str] = None,  # G4 object store (fs backend /
        #   shared mount; S3 via kvbm.object_store.S3Backend)
        slice_id: Optional[str] = None,  # topology label (ICI island) for
        #   link-class routing; advertised as kv_slice metadata
        kv_tier_quantize: bool = False,  # store demoted G2/G3/G4 blocks as
        #   int8 + per-(token, head) scales (kvbm/quant.py) — ~2x effective
        #   cold-tier capacity; promotion dequantizes, or passes through
        #   natively when the device pools are int8 (kv_quantize)
        onboard_layer_groups: int = 1,  # stream tier onboarding in this
        #   many contiguous layer groups (FlowKV-style overlap of transfer
        #   with the first layers' compute; 1 = whole-sequence import)
        prefetch: bool = False,  # router-hinted tier promotion ahead of
        #   dispatch (kvbm/prefetch.py; needs host_kv_blocks > 0)
        prefetch_max_inflight: int = 4,  # concurrent G3→G2 reads
        prefetch_bandwidth_mbps: float = 0.0,  # promoted bytes/s (0 = off)
        prefetch_hint_ttl_s: float = 10.0,  # unserved hint cancellation
        prefetch_pin_ttl_s: float = 5.0,  # promoted-block pin lifetime
        tokenizer_spec: str = "byte",  # guided decoding lifts byte DFAs to
        #   token masks against THIS tokenizer (must match the frontend's)
        recorder_size: int = 4096,  # flight-recorder ring capacity (0 = off)
        anomaly_k: float = 4.0,  # iteration wall > EWMA*k fires the trigger
        anomaly_dump_dir: Optional[str] = None,  # None = count, don't dump
        anomaly_dump_last_n: int = 256,  # ring records per anomaly dump
        anomaly_profile_ms: int = 0,  # >0: jax.profiler window per dump
        spec_ngram: bool = False,  # n-gram/prompt-lookup speculative
        #   decoding: draft from each sequence's own token history, verify
        #   as K+1-token ragged rows in the mixed dispatch
        spec_k: int = 4,  # draft tokens proposed per sequence per step
        spec_max_tokens: int = 0,  # per-iteration cap on drafted tokens
        #   (0 = bounded only by the mixed pool leftover)
        spec_branches: int = 1,  # tree speculation: candidate draft
        #   branches per sequence per verify iteration. 1 = linear-K
        #   (exact PR 8 behavior). >1 adds alternate-continuation verify
        #   rows sharing the sequence's trunk KV via PagePool.fork_table
        #   ref-sharing; acceptance walks the branch trie emitting target
        #   samples (distribution-preserving at any temperature), then
        #   the winning branch's forked table is adopted and the losers
        #   released — see docs/spec_decode.md
        spec_device_draft: Optional[bool] = None,  # device-resident
        #   n-gram proposal (runner draft_step): history lives in a
        #   device ring, the suffix match runs as one jitted gather over
        #   all slots, and the proposal readback is the only host touch
        #   (sanitizer label draft_readback). None = auto (on when the
        #   runner has draft_step); False forces the host-side scan
        enable_prefix_cache: bool = True,  # content-addressed KV reuse
        #   (session-tree warm turns; off = every prompt prefills cold —
        #   the A/B knob bench_agentic flips)
        sanitize: Optional[bool] = None,  # runtime sanitizer (transfer
        #   guard, recompile tripwire, lock-order recorder, pool audit);
        #   None = follow DYN_SAN env
        sanitizer: Optional[Any] = None,  # pre-built Sanitizer to share
        #   across engines (fleet-sim); overrides `sanitize`
    ):
        self.runner = runner
        # fused mixed dispatch (one program and one host sync per
        # iteration instead of two) — but the fused program adds one
        # compile unit per (decode bucket x prefill bucket) combination,
        # which on cold CPU test rigs inflates first-request TTFT for no
        # latency benefit. Default: fuse on accelerators, not on cpu;
        # DYN_FUSED_MIXED=0/1 overrides for A/Bs.
        import os as _os

        _fuse_env = _os.environ.get("DYN_FUSED_MIXED", "").lower()
        if _fuse_env in ("1", "true", "on", "yes"):
            self.fused_mixed = True
        elif _fuse_env in ("0", "false", "off", "no"):
            self.fused_mixed = False
        else:
            self.fused_mixed = runner.platform != "cpu"
        # a runner with no one-dispatch program for a mixed plan says so
        self.fused_mixed = self.fused_mixed and runner.fuses_mixed
        # cross-worker KVBM onboarding: worker_common injects an async
        # callable(hint) -> payload that pulls blocks from a peer's
        # kv_host_fetch endpoint (None = feature off)
        self.remote_kv_fetch = None
        # what a sequence keeps beside its KV pages (Runner.side_kind), its
        # pool sized on the runner for this scheduler's limits. What moves
        # KV by pages alone, or rolls tokens back, is refused here in the
        # side cache's words and not met halfway at run time
        self.side = side_cache.for_runner(
            runner, max_batch=max_batch, chunk_size=chunk_size,
            decode_steps=decode_steps,
            mixed_prefill_tokens=mixed_prefill_tokens,
            mixed_prefill_seqs=mixed_prefill_seqs)
        if self.side is not None:
            name = runner.config.name
            if host_kv_blocks > 0 or disk_kv_blocks > 0 or obj_kv_root or prefetch:
                raise ValueError(self.side.refusal(
                    name, "tier demotion of KV blocks (G2-G4, prefetch)"))
            if spec_ngram:
                raise ValueError(self.side.refusal(
                    name, "speculative decoding (n-gram drafts verified in "
                    "the mixed step)"))
            if enable_prefix_cache:
                log.info("prefix cache off: %s", self.side.no_prefix)
                enable_prefix_cache = False
        self.pool = PagePool(runner.num_pages, runner.page_size)
        # fork-on-branch CoW: the pool copies a forked tail page's device
        # KV through the runner
        self.pool.copy_hook = runner.copy_pages
        self.host_pool = None
        self._host_events: List[KvEvent] = []
        self.kv_tier_quantize = bool(kv_tier_quantize)
        self.onboard_layer_groups = max(1, int(onboard_layer_groups))
        # per-tier EWMA of measured per-block onboard seconds (the phase
        # spine's kv_onboard_s attributed to the deepest tier each chain
        # touched, plus the remote-pull leg). Published in fleet digests;
        # the router's topology-aware placement consumes it as the live
        # transfer-cost model.
        self.kv_onboard_ewma: Dict[str, Dict[str, float]] = {}
        self.slice_id = str(slice_id) if slice_id is not None else None
        if (disk_kv_blocks > 0 or obj_kv_root) and host_kv_blocks <= 0:
            log.warning(
                "disk/object KV tiers ignored: they spill from the G2 host "
                "tier — also set host_kv_blocks > 0",
            )
        if host_kv_blocks > 0:
            from dynamo_tpu.kvbm.disk_pool import DiskKvPool, TieredKv
            from dynamo_tpu.kvbm.host_pool import HostKvPool

            host = HostKvPool(capacity_blocks=host_kv_blocks,
                              quantize=kv_tier_quantize)
            disk = None
            if disk_kv_blocks > 0:
                import tempfile

                disk = DiskKvPool(
                    disk_kv_root or tempfile.mkdtemp(prefix="dyn_kv_g3_"),
                    capacity_blocks=disk_kv_blocks,
                    quantize=kv_tier_quantize,
                    capacity_bytes=disk_kv_bytes,
                )
            obj = None
            if obj_kv_root:
                from dynamo_tpu.kvbm.object_store import FsBackend, ObjectKvPool

                obj = ObjectKvPool(FsBackend(obj_kv_root),
                                   quantize=kv_tier_quantize)
                # shared-tier residency events for the router's G4 index
                # (fires from the writer/spill thread → step thread)
                obj.store_listener = self._on_obj_stored
            self.host_pool = TieredKv(host, disk, obj)
            self.pool.evict_hook = self._offload_page
            self.host_pool.on_evict(self._on_host_evicted)
        self.prefetch = None
        if prefetch and self.host_pool is not None:
            from dynamo_tpu.kvbm.prefetch import PrefetchManager

            self.prefetch = PrefetchManager(
                self,
                max_inflight=prefetch_max_inflight,
                bandwidth_mbps=prefetch_bandwidth_mbps,
                hint_ttl_s=prefetch_hint_ttl_s,
                pin_ttl_s=prefetch_pin_ttl_s,
            )
        elif prefetch:
            log.warning(
                "prefetch requested without a host KV tier "
                "(host_kv_blocks=0); disabled")
        self.scheduler = Scheduler(
            self.pool,
            max_batch=max_batch,
            chunk_size=chunk_size,
            max_seq_pages=runner.max_pages_per_seq,
            max_seq_tokens=runner.max_seq_len,
            decode_steps=decode_steps,
            enable_prefix_cache=enable_prefix_cache,
            mixed_prefill_tokens=mixed_prefill_tokens,
            mixed_prefill_seqs=mixed_prefill_seqs,
            mixed_min_chunk=mixed_min_chunk,
            host_tier=self.host_pool,
            host_onboard=self._onboard_from_host if self.host_pool is not None else None,
            spec_max_tokens=spec_max_tokens,
            spec_seg_budget=runner.spec_seg_budget,
            side=self.side,
        )
        # n-gram speculative decoding (docs/spec_decode.md): drafts ride
        # the mixed dispatch as ragged verify rows, so both the runner
        # verify hook and a non-zero mixed pool are required
        self.spec_ngram = bool(spec_ngram)
        self.spec_k = max(1, int(spec_k))
        self._spec_on = (
            self.spec_ngram
            and mixed_prefill_tokens > 0
            and runner.has_verify_spec
        )
        if self.spec_ngram and not self._spec_on:
            log.warning(
                "spec_ngram requested but unavailable "
                "(runner verify_spec=%s, mixed_prefill_tokens=%d); disabled",
                runner.has_verify_spec, mixed_prefill_tokens,
            )
        # tree speculation: extra candidate branches per sequence ride the
        # same verify dispatch as independent segments on forked page
        # tables (trunk KV ref-shared); 1 = linear-K, the PR 8 contract
        self.spec_branches = max(1, int(spec_branches))
        # device-resident n-gram proposal: auto-on when the runner carries
        # the draft_step ring (ModelRunner jitted gather / SimRunner numpy
        # twin); the host scan remains as fallback and for A/Bs
        if spec_device_draft is None:
            spec_device_draft = runner.has_draft_ring
        self._spec_device_draft = (
            bool(spec_device_draft) and runner.has_draft_ring
        )
        self._draft_slots: Dict[str, int] = {}  # rid -> history-ring slot
        self._draft_free: List[int] = []
        self._draft_synced: Dict[str, int] = {}  # rid -> tokens mirrored
        self._draft_D = 0  # per-iteration append capacity (ring bucket)
        if self._spec_on and self._spec_device_draft:
            # allocate + WARM the ring at construction: the draft jit's
            # compile must land before the sanitizer's recompile tripwire
            # freezes the per-family variant counts (warmup_steps)
            self._draft_D = runner.ensure_draft_ring(max_batch, self.spec_k)
            self._draft_free = list(range(max_batch))
        # cumulative counters for goodput extras["spec"] / fleet digests
        self.spec_stats = {
            "drafted": 0, "accepted": 0, "rejected": 0,
            "verify_rows": 0, "verify_iters": 0, "spec_emitted": 0,
            "tree_rows": 0, "tree_switches": 0,
        }
        # The scheduler caps a mixed plan at max_batch decode rows +
        # mixed_prefill_tokens chunk tokens, so registering that exact sum
        # as a ragged T bucket makes the token budget BE the compile
        # bucket: a full mixed iteration compiles (and reuses) one ragged
        # variant instead of rounding up to the next power of two.
        runner.ensure_ragged_bucket(mixed_prefill_tokens + max_batch)
        # planner retune ceilings: the ragged bucket registered above and
        # the draft ring sized below are compile-time commitments — a
        # live retune (engine.retune) may move knobs DOWN and back up to
        # these init values, never past them (a new compile family on the
        # warm path is exactly what the recompile tripwire forbids)
        self._mixed_tokens_init = int(mixed_prefill_tokens)
        self._spec_k_init = self.spec_k
        self.retunes = 0
        self.idle_sleep_s = idle_sleep_s
        self._inbox: thread_queue.Queue = thread_queue.Queue()
        self._streams: Dict[str, tuple[asyncio.Queue, asyncio.AbstractEventLoop]] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._step_counter = 0
        self.fpm_history: List[ForwardPassMetrics] = []
        self._fpm_listeners: List[Any] = []
        self._kv_listeners: List[Any] = []
        self._phase_listeners: List[Any] = []
        # always-on iteration flight recorder (runtime/flight_recorder.py);
        # recorder_size=0 builds the disabled no-op variant for A/Bs
        self.recorder = FlightRecorder(
            recorder_size,
            anomaly_k=anomaly_k,
            anomaly_dump_dir=anomaly_dump_dir,
            anomaly_dump_last_n=anomaly_dump_last_n,
            anomaly_profile_ms=anomaly_profile_ms,
        )
        self._rec_prev_charged = 0  # runner packed_tokens_charged watermark
        # routed experts (docs/observability.md): whether the runner's
        # step programs hand out the router's picks; the picks fetched for
        # the asking requests of this iteration, rid -> the item's
        # `routed_experts`, until _emit_item sends them; the last
        # iteration's record while its expert-load counters are still on
        # the device; and what /metrics shows of them (worker_common)
        self._routed_ok = bool(runner.routed)
        self._routed_out: Dict[str, tuple] = {}  # rid -> (item field,
        #   whether it holds one position per emitted token: a decode row)
        self._rec_late: Optional[tuple] = None  # (IterationRecord, MoeLoad)
        # what commits have left for the clients and the observers, in
        # commit order, until _deliver hands it over (under the next
        # program where one is enqueued first): a stream's item as
        # (seq, item, whether its tokens stamp the latency spine), an
        # iteration's publish as (None, its ForwardPassMetrics, None or
        # (its IterationRecord or None, its MoeLoad or None))
        self._undelivered: List[tuple] = []
        # the step loop's decode dispatch in flight (_loop_once), the
        # moment the last iteration was committed (walls run from it), and
        # iterations by how they were enqueued: "ahead" of the read-back
        # of the one before, or the reason not (IterationRecord.drain)
        self._inflight: Optional[_InFlight] = None
        self._t_mark = time.monotonic()
        self.run_ahead_totals: Dict[str, int] = {}
        # what the loop has spent between its marks, by class of iteration
        # (flight_recorder.iteration_class), ns: monotone totals that tile
        # the loop's clock from `_acct_ns` back, of which a request's spine
        # takes two copies (_charge); and the sequences whose first or last
        # token the commit at hand has queued, until its mark
        self.class_ns: Dict[str, int] = dict.fromkeys(ITERATION_CLASSES, 0)
        self._acct_ns = time.monotonic_ns()
        self._spine_due: List[tuple] = []  # (Sequence, its last?)
        # the step thread's host clock (runtime/annotations.py, the door):
        # bound to the step thread while the loop runs, emptied into each
        # iteration's record; on with the recorder and off with it
        self.step_clock: Optional[StepClock] = (
            StepClock() if self.recorder.enabled else None)
        self.moe_totals = {"token_slots_total": 0, "held_slots_total": 0.0,
                           "experts_hit": 0.0, "load_max_share": 0.0}
        # sick peers for cross-worker pulls: instance -> retry-after time
        self._remote_fetch_backoff: Dict[int, float] = {}
        # disaggregation state
        self._parked: Dict[str, tuple] = {}  # rid -> (Sequence, deadline)
        self._spec_sampling_warned: set = set()
        self._kv_pending: List[Sequence] = []  # disagg-decode awaiting space
        self.parked_ttl_s = 60.0
        self._embed_pending: List[tuple] = []  # (tokens, future, loop)
        # guided decoding: tokenizer-lifted constraint compile cache
        self.tokenizer_spec = tokenizer_spec
        self._guided_lifter = None
        self._guided_cache: Dict[str, Any] = {}
        self._guided_lock = threading.Lock()
        self._lifter_lock = threading.Lock()  # one-time TokenLifter build
        # runtime sanitizer: off unless asked (arg or DYN_SAN env). The
        # import is local so mocker processes that never arm it pay one
        # cheap module load at most.
        from dynamo_tpu.runtime.sanitizer import Sanitizer, env_enabled

        if sanitizer is not None:
            self.sanitizer = sanitizer
        elif sanitize or (sanitize is None and env_enabled()):
            self.sanitizer = Sanitizer()
        else:
            self.sanitizer = None
        if self.sanitizer is not None:
            san = self.sanitizer
            self._guided_lock = san.wrap_lock(
                self._guided_lock, "engine.guided_cache"
            )
            self._lifter_lock = san.wrap_lock(
                self._lifter_lock, "engine.lifter"
            )
            runner.attach_sanitizer(san)
        # called (from the step thread) on unrecoverable engine failure
        # (multi-host GroupBroken): the worker wires it to process exit
        self._fatal_cb = None
        # RL admin surface (reference lib/rl role): pause gates NEW
        # admissions during weight refreshes; weights_version counts
        # successful reloads
        self.paused = False
        self.weights_version = 0

    async def update_weights(self, orbax_path: str) -> int:
        """Swap serving weights from an orbax snapshot on the STEP thread
        (never racing an in-flight jit dispatch). Returns the new
        weights_version. Pause first for a clean cut between rollouts —
        running sequences otherwise continue on the new weights."""
        self.start()
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("reload_weights", (orbax_path, fut, loop)))
        return await fut

    def on_fatal(self, cb) -> None:
        self._fatal_cb = cb

    def retune(self, *, mixed_prefill_tokens: Optional[int] = None,
               mixed_prefill_seqs: Optional[int] = None,
               spec_k: Optional[int] = None) -> Dict[str, int]:
        """Planner actuation surface: adjust the co-scheduling knobs of a
        LIVE engine. Each knob is an int the step thread reads fresh
        every iteration (plain attribute stores are atomic under the
        GIL), so no pause is needed. Up-retunes are clamped to the
        compile-time commitments made at construction: the ragged bucket
        registered for `mixed_prefill_tokens + max_batch` and the draft
        ring sized for the initial K — exceeding either would mint a new
        compile family on the warm path. A DOWNWARD K retune on a
        device-draft runner re-keys the draft jit (bounded: at most
        init-K variants ever exist); strict-sanitizer deployments that
        retune K should pre-warm the alternate Ks. Returns the values
        actually in effect (callers journal these, not what they asked
        for)."""
        sched = self.scheduler
        if mixed_prefill_tokens is not None:
            cap = (self._mixed_tokens_init if self.runner.static_shapes
                   else max(self._mixed_tokens_init, mixed_prefill_tokens))
            sched.mixed_prefill_tokens = max(0, min(int(mixed_prefill_tokens),
                                                    cap))
        if mixed_prefill_seqs is not None:
            sched.mixed_prefill_seqs = max(1, int(mixed_prefill_seqs))
        if spec_k is not None:
            cap = (self._spec_k_init if self._spec_device_draft
                   else max(self._spec_k_init, int(spec_k)))
            self.spec_k = max(1, min(int(spec_k), cap))
        self.retunes += 1
        return {
            "mixed_prefill_tokens": sched.mixed_prefill_tokens,
            "mixed_prefill_seqs": sched.mixed_prefill_seqs,
            "spec_k": self.spec_k,
        }

    def _fail_everything(self, message: str) -> None:
        """Terminate every active/waiting/pending sequence with an error
        item (clients see a proper stream end and can migrate)."""
        seqs = list(self.scheduler.active) + list(self.scheduler.waiting)
        seqs += [s for s in self._kv_pending]
        for seq in seqs:
            try:
                self.scheduler.abort(seq.request_id)
            except Exception:
                # fail-everything must visit EVERY sequence even when one
                # abort races its normal finish; note it, keep going
                log.debug("abort of %s during fail-everything raced",
                          seq.request_id, exc_info=True)
            try:
                self._emit_item(seq, {
                    "finish_reason": "error", "error": message,
                    "token_ids": [],
                })
            except Exception:
                log.debug("error emit to %s failed during fail-everything "
                          "(stream already gone)", seq.request_id,
                          exc_info=True)
        self._deliver()  # (behind whatever their streams were still owed)

    # -- guided decoding ---------------------------------------------------
    def _compile_guided(self, spec: Dict[str, Any]):
        """Wire spec → GuidedMatcher (cached per spec+engine). Runs in an
        executor (DFA compilation for a big schema can take ~100ms).
        Double-checked locking: the lock only guards cache lookups and
        the insert — DFA compilation and the per-vocab lift happen
        OUTSIDE it, so one slow schema never serializes every concurrent
        guided request. A racing build of the same spec keeps the first
        inserted matcher (both are equivalent; ours is dropped). Only the
        TokenLifter (one per engine, the truly expensive vocab scan) is
        built under its own lock exactly once."""
        import json as _json

        key = _json.dumps(spec, sort_keys=True)
        with self._guided_lock:
            hit = self._guided_cache.get(key)
            if hit is not None:
                return hit
        from dynamo_tpu.guided import compile_regex, compile_structural

        kind = spec.get("kind")
        if kind == "regex":
            dfa = compile_regex(spec["pattern"])
        elif kind == "structural":
            dfa = compile_structural(spec)
        else:
            raise ValueError(f"unknown guided kind {kind!r}")
        matcher = self._get_lifter().lift(dfa)
        with self._guided_lock:
            hit = self._guided_cache.get(key)
            if hit is not None:
                return hit  # racer inserted first; equivalent matcher
            # small cap: each matcher holds up to _ROW_CACHE_MAX full-vocab
            # rows, so this bounds worker memory at tens of MB, not GB
            while len(self._guided_cache) >= 32:
                self._guided_cache.pop(next(iter(self._guided_cache)))
            self._guided_cache[key] = matcher
            return matcher

    def _get_lifter(self):
        lifter = self._guided_lifter
        if lifter is not None:
            return lifter
        with self._lifter_lock:
            if self._guided_lifter is None:
                from dynamo_tpu.frontend.tokenizer import load_tokenizer
                from dynamo_tpu.guided.token_mask import TokenLifter

                self._guided_lifter = TokenLifter.for_tokenizer(
                    load_tokenizer(self.tokenizer_spec),
                    self.runner.vocab_size,
                )
            return self._guided_lifter

    def _guided_mask(self, seq: Sequence) -> Optional[np.ndarray]:
        """Sampling mask for a constrained sequence. An all-False row (no
        token in this vocab can extend the constraint — possible when the
        tokenizer lacks a needed byte) degrades to force-EOS so the
        sequence stops instead of emitting garbage."""
        m = seq.guided_m
        if m is None:
            return None
        mask = m.allowed(seq.guided_s)
        if not mask.any():
            log.warning(
                "request %s: no token can extend the constraint from state "
                "%d — forcing EOS", seq.request_id, seq.guided_s,
            )
            if 0 <= m.lifter.eos_id < len(mask):
                mask = mask.copy()
                mask[m.lifter.eos_id] = True
        return mask

    def _guided_device_plan(self, seqs: List[Sequence]):
        """Device-resident guided plan for a fused multi-step dispatch:
        (tables, row_entries, pending) for the runner's _guided_op, or
        None when ANY constrained row's schema exceeds the device-table
        cell budget — the whole batch then keeps the host io_callback
        mask_fn (guided/device_table.py; a mixed device/host batch would
        need a second masking path in the loop for no warm-loop win).
        Tables compile once per matcher and ride the matcher's cache, so
        admission churn never rebuilds them; the runner keeps the staged
        combination device-resident across dispatches."""
        from dynamo_tpu.guided.device_table import build_device_table

        tables: List[Any] = []
        index: Dict[int, int] = {}
        rows: List[Any] = [None] * len(seqs)
        for i, s in enumerate(seqs):
            m = s.guided_m
            if m is None:
                continue
            tab = getattr(m, "_device_table", None)
            if tab is None:
                tab = build_device_table(m)
                if tab is None:
                    tab = _OVER_BUDGET
                    log.warning(
                        "guided schema exceeds the device DFA table "
                        "budget (DYN_GUIDED_DEVICE_MAX_ELEMS) — batches "
                        "containing it keep the host mask callback",
                    )
                m._device_table = tab  # matcher-lifetime cache
            if tab is _OVER_BUDGET:
                return None
            ti = index.get(tab.uid)
            if ti is None:
                ti = len(tables)
                index[tab.uid] = ti
                tables.append(tab)
            rows[i] = (ti, int(s.guided_s))
        if not tables:
            return None
        return (tables, rows, False)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is None:
            # what the step loop imports on its first line, imported here:
            # the package pulls in jax (~0.7 s cold), and a loop that
            # spends its first second importing serves nothing meanwhile
            # (a mocker fleet's planner saw an idle fleet and scaled down)
            import dynamo_tpu.parallel.multihost  # noqa: F401

            self._thread = threading.Thread(target=self._loop, name="engine-step", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
            self._deliver()  # (the loop's own last one, where it died)
            self._flush_late_record()
        if self.prefetch is not None:
            self.prefetch.stop()
        if self.sanitizer is not None:
            live = (len(self.scheduler.active) + len(self.scheduler.waiting)
                    + len(self._kv_pending))
            self.sanitizer.audit_pool(self.pool, live_seqs=live)

    def _san_scope(self, where: str):
        """Transfer-guard scope for a steady-state dispatch (no-op
        nullcontext when the sanitizer is off)."""
        san = self.sanitizer
        if san is None:
            return contextlib.nullcontext()
        return san.transfer_scope(where)

    def on_fpm(self, cb) -> None:
        """cb(ForwardPassMetrics) from the step thread."""
        self._fpm_listeners.append(cb)

    def on_kv_event(self, cb) -> None:
        """cb(List[KvEvent]) from the step thread."""
        self._kv_listeners.append(cb)

    def on_phases(self, cb) -> None:
        """cb(phases: Dict[str, float]) from the step thread, once per
        finished request (worker_common feeds /metrics histograms)."""
        self._phase_listeners.append(cb)

    # -- AsyncEngine protocol ----------------------------------------------
    async def generate(self, request: Dict[str, Any], context: Context) -> AsyncIterator[Any]:
        self.start()
        loop = asyncio.get_running_loop()
        out: asyncio.Queue = asyncio.Queue()
        rid = context.id
        self._streams[rid] = (out, loop)

        if self.paused:
            yield {
                "finish_reason": "error",
                "error": "worker paused (weight update in progress)",
                "token_ids": [],
            }
            self._streams.pop(rid, None)
            return
        annotations = request.get("annotations") or {}
        if annotations.get("kind") == "embedding":
            fut: asyncio.Future = loop.create_future()
            self._inbox.put(
                ("embed", ([int(t) for t in request.get("token_ids") or [0]], fut, loop))
            )
            try:
                vec = await fut
                yield {"embedding": vec, "finish_reason": "stop", "token_ids": []}
            finally:
                self._streams.pop(rid, None)
            return

        seq = Sequence(
            request_id=rid,
            prompt=[int(t) for t in request.get("token_ids") or [0]],
            sampling=request.get("sampling") or {},
            stop=request.get("stop") or {},
            arrival=time.monotonic(),
            disagg=annotations.get("disagg"),
            kv_import=request.get("kv_import"),
            adapter=request.get("adapter"),
            guided=request.get("guided"),
            logit_bias=request.get("logit_bias"),
        )
        # latency spine: upstream hops (frontend, router) stamped their
        # locally-measured durations into ctx.metadata["phases"]; seed the
        # sequence's phase dict so the final item carries the whole spine.
        # Durations only — monotonic clocks don't compare across processes.
        upstream = context.metadata.get("phases")
        if isinstance(upstream, dict):
            seq.phases.update({
                k: float(v) for k, v in upstream.items()
                if isinstance(v, (int, float))
            })
        # causal trace: remember the route span this request arrived
        # under; the step thread reconstructs the worker's phase spans
        # from the spine at finish (see _emit_worker_spans)
        tp = context.metadata.get("traceparent")
        if isinstance(tp, str):
            seq.tp = tp
        if context.metadata.get("migration_attempt"):
            seq.phases["migration_attempts"] = float(
                context.metadata["migration_attempt"])
        # n>1 sampling: fork-on-branch after prefill (the trunk KV is
        # shared copy-on-write, so n choices cost one prefill). Disagg
        # roles stream exactly one completion per worker — no fan-out.
        if seq.disagg is None:
            seq.n_branches = max(1, min(16, int(seq.sampling.get("n") or 1)))
        if self.side is not None and (
                seq.disagg is not None or seq.n_branches > 1
                or seq.kv_import is not None):
            what = ("disaggregated serving (KV export and import by pages)"
                    if seq.disagg is not None or seq.kv_import is not None
                    else "n > 1 sampling (fork-on-branch over shared pages)")
            yield {"finish_reason": "error", "token_ids": [],
                   "error": self.side.refusal(self.runner.config.name, what)}
            self._streams.pop(rid, None)
            return
        if seq.logit_bias and (
            self.runner.has_draft
            or self.runner.pp
            or not self.runner.supports_logit_bias
        ):
            # spec-decode verify can't honor a biased target distribution,
            # the PP loop has no bias operand, and sim runners have no
            # bias plumbing — reject up front rather than silently sample
            # the unbiased distribution (a dropped ban is a safety bug)
            yield {
                "finish_reason": "error",
                "error": "logit_bias is unsupported on this worker",
                "token_ids": [],
            }
            self._streams.pop(rid, None)
            return
        if seq.sampling.get("routed_experts"):
            why = self._routed_refusal()
            if why:
                # a path with no output for the picks refuses by name; a
                # stream that silently lacked them would read as a gap
                yield {
                    "finish_reason": "error",
                    "error": f"routed_experts is unsupported on this worker: {why}",
                    "token_ids": [],
                }
                self._streams.pop(rid, None)
                return
        if seq.guided and self.runner.has_draft:
            # speculative verify can't honor per-token masks; silently
            # dropping the constraint would hand back schema-invalid output
            # with finish_reason "stop" — reject up front instead
            yield {
                "finish_reason": "error",
                "error": "guided decoding is unsupported on a "
                         "speculative-decoding worker",
                "token_ids": [],
            }
            self._streams.pop(rid, None)
            return
        if seq.guided:
            try:
                seq.guided_m = await loop.run_in_executor(
                    None, self._compile_guided, seq.guided
                )
                seq.guided_s = seq.guided_m.start
                # disagg decode continuation: the prefill worker already
                # generated the trailing N prompt tokens under this
                # constraint — replay them so the DFA state matches
                n_adv = int(request.get("guided_advanced") or 0)
                for t in seq.prompt[len(seq.prompt) - n_adv:] if n_adv else []:
                    seq.guided_s = seq.guided_m.advance(seq.guided_s, int(t))
            except Exception as e:
                yield {
                    "finish_reason": "error",
                    "error": f"guided decoding spec rejected: {e}",
                    "token_ids": [],
                }
                self._streams.pop(rid, None)
                return
        # reject prompts that can NEVER be admitted (more pages than the
        # pool/per-seq cap) — without this the sequence waits forever and
        # head-of-line-blocks every request behind it
        PS = self.pool.page_size
        cap_tokens = min(self.scheduler.max_seq_pages, self.pool.num_pages) * PS
        if self.scheduler.max_seq_tokens:
            # the model context also bounds the PROMPT: prefilling past
            # the rope-valid range yields garbage logits, not an error
            cap_tokens = min(cap_tokens, self.scheduler.max_seq_tokens)
        if len(seq.prompt) + 1 > cap_tokens:
            yield {
                "finish_reason": "error",
                "error": (
                    f"prompt of {len(seq.prompt)} tokens exceeds this "
                    f"worker's KV capacity ({cap_tokens - 1} tokens)"
                ),
                "token_ids": [],
            }
            self._streams.pop(rid, None)
            return
        mm = request.get("mm")
        if mm:
            import numpy as np

            from dynamo_tpu.tokens.hashing import mm_content_seed

            arr = np.frombuffer(mm["data"], dtype=np.dtype(mm["dtype"])).reshape(mm["shape"])
            seq.mm_embeds = arr  # [n_img_tokens, E]
            seq.mm_positions = [int(p) for p in mm["positions"]]
            seq.mm_seed = mm_content_seed(mm["data"])
        if seq.adapter:
            try:
                seq.adapter_idx = self.runner.adapter_slot(seq.adapter)
            except KeyError:
                yield {
                    "finish_reason": "error",
                    "error": f"unknown LoRA adapter {seq.adapter!r}",
                    "token_ids": [],
                }
                self._streams.pop(rid, None)
                return
        remote = request.get("kv_remote_host")
        if (remote and self.host_pool is not None
                and self.remote_kv_fetch is not None):
            # pull the peer's lower-tier blocks into the LOCAL host tier
            # before admission; the inbox is FIFO, so the import lands
            # before the scheduler sees the request
            await self._pull_remote_host(remote)
        if seq.disagg == "decode" and seq.kv_import is not None:
            self._inbox.put(("add_kv", seq))
        else:
            self._inbox.put(("add", seq))
        finished = False
        n_done = 0
        try:
            while True:
                if context.is_stopped:
                    return
                get = asyncio.create_task(out.get())
                stop_wait = asyncio.create_task(context.wait_stopped())
                done, pending = await asyncio.wait(
                    {get, stop_wait}, return_when=asyncio.FIRST_COMPLETED
                )
                for t in pending:
                    t.cancel()
                if get not in done:
                    return
                item = get.result()
                yield item
                if item.get("finish_reason"):
                    # a branched request streams one finish per choice;
                    # the stream ends when every branch has finished
                    n_done += 1
                    if n_done >= seq.n_branches:
                        finished = True
                        return
        finally:
            # runs on normal end, cancel, AND consumer break/close
            self._streams.pop(rid, None)
            # GIL-atomic discard; without it the warned-id set grows
            # unbounded on a long-lived spec-decode worker (ADVICE r3)
            self._spec_sampling_warned.discard(rid)
            if not finished:
                self._inbox.put(("abort", rid))

    def _routed_refusal(self) -> Optional[str]:
        """Why this worker cannot stream `routed_experts` (None: it can)."""
        r = self.runner
        if r.pp:
            return "the pipeline-parallel programs do not return the picks"
        if r.sp_enabled:
            return "sequence-parallel prefill does not return the picks"
        if r.has_draft:
            return "speculative decoding with a draft model does not return the picks"
        if self._spec_on:
            return ("speculative verify (n-gram drafting, the draft ring) "
                    "does not return the picks")
        if not self._routed_ok:
            return "the model it serves has no routed experts"
        return None

    async def _pull_remote_host(self, hint: Dict[str, Any]) -> None:
        """Best-effort remote-G2 pull (reference onboarding session
        search→pull, lib/kvbm-engine/docs/architecture.md). Failures fall
        back to recompute — never block admission on a sick peer."""
        hashes = [int(h) for h in hint.get("hashes") or []]
        parents = list(hint.get("parents") or [])
        if not hashes or len(parents) != len(hashes):
            return
        if (self.host_pool is not None
                and self.host_pool.match(hashes) >= len(hashes)):
            return  # already local (e.g. the prefetch hint pulled them)
        peer = int(hint.get("instance") or 0)
        now = time.monotonic()
        if now < self._remote_fetch_backoff.get(peer, 0.0):
            return  # peer recently failed: recompute instead of stalling
        t0 = time.perf_counter()
        try:
            # bounded timeout: a wedged peer must cost little — the
            # fallback (recompute) is always available (covers the
            # fetcher's up-to-2s discovery wait plus the transfer)
            payload = await asyncio.wait_for(
                self.remote_kv_fetch(hint), timeout=5.0
            )
        except Exception as e:
            self._remote_fetch_backoff[peer] = now + 30.0
            log.info("remote host-tier pull failed (%s); recomputing", e)
            return
        n = int((payload or {}).get("n") or 0)
        if n <= 0:
            return
        # the peer-pull leg of the transfer-cost model: remote blocks then
        # onboard from local G2, so the total remote cost the router sees
        # is ewma[remote] + ewma[host]. When the router tagged the hint
        # with the link class, the same sample also feeds the per-class
        # EWMA (remote_ici / remote_dcn) the link-aware selector prefers.
        elapsed = time.perf_counter() - t0
        self._note_onboard([], n, elapsed, tier="remote")
        link = hint.get("link")
        if link in ("ici", "dcn"):
            self._note_onboard([], n, elapsed, tier=f"remote_{link}")
        self._inbox.put(("host_import", (hashes[:n], parents[:n], payload)))

    async def prefetch_hint_async(self, hint: Dict[str, Any]) -> bool:
        """Router `kv_prefetch` hint ingress (worker_common endpoint):
        promote the hinted blocks up the KVBM ladder before the request
        itself arrives. A hint with a `remote` leg first pulls the peer's
        G2 blocks into the local host tier (the cross-worker machinery the
        admission path uses) — the inbox is FIFO, so the import lands
        before the promotion looks for it."""
        if self.prefetch is None:
            return False
        remote = hint.get("remote")
        if (remote and self.host_pool is not None
                and self.remote_kv_fetch is not None):
            await self._pull_remote_host(remote)
        self._inbox.put(("prefetch", hint))
        return True

    # -- step loop (dedicated thread) --------------------------------------
    def _loop(self) -> None:
        from dynamo_tpu.parallel.multihost import GroupBroken

        log.info("engine step loop started (fused_mixed=%s)",
                 self.fused_mixed)
        # compiles on this thread that no step family sees count in this
        # runner's compile_stats()["other"]
        self.runner.name_step_thread()
        if self.step_clock is not None:
            bind_clock(self.step_clock)
        self._acct_ns = time.monotonic_ns()
        self._t_mark = self._acct_ns * 1e-9
        if self._routed_ok:
            # what the runner holds of dispatches that were not this
            # engine's (a warm-up walk) is none of its first iteration's load
            self.runner.take_moe_load()
        while not self._stop.is_set():
            try:
                self._loop_once()
            except GroupBroken as e:
                # a multi-host group member died: limping along would hang
                # the next program's collectives — fail EVERY request
                # loudly and tell the process to exit so the supervisor
                # restarts the whole group (requests migrate to other
                # workers meanwhile). This catch sits OUTSIDE _loop_once
                # so inbox paths (exports, imports, embeds, evict hooks)
                # get the same fail-fast as the step itself.
                log.critical("worker group broken: %s — failing all "
                             "requests and shutting down", e)
                self._fail_everything(f"worker group broken: {e}")
                self._stop.set()
                cb = self._fatal_cb
                if cb is not None:
                    try:
                        cb()
                    except Exception:
                        # the callback is the worker's process-exit hook;
                        # its failure must not mask the fatal path itself
                        log.exception("fatal callback failed")
                break
        try:
            # stopping with a dispatch enqueued: its tokens were computed,
            # so they are committed like any other
            self._commit_inflight()
        except Exception:
            log.exception("commit of the dispatch in flight failed")
        self._deliver()
        unbind_clock()
        log.info("engine step loop stopped")

    def _loop_once(self) -> None:
        """One iteration: plan it, enqueue it, and read back, emit and
        publish what is due. A plain decode dispatch is left in flight
        (`_inflight`) and read back only after the NEXT iteration is
        planned, staged and enqueued on its device-resident tokens, so
        the host's share of an iteration runs while the device works and
        not between its programs. That holds while the next plan is the
        rows in flight, in their places (a row that ended keeps its place
        as a pad row), and needs nothing of their tokens on the host;
        whenever it does not (`_why_not_ahead` names why) what is in
        flight is committed first and the iteration at hand runs in the
        serial order: plan, enqueue, read back, emit, publish. Either way
        a commit only queues what the clients and the observers are owed
        (`_undelivered`), and `_deliver` hands it over once the next
        program is enqueued, under it: the device waits for the half of a
        commit the next plan needs and for no more."""
        from dynamo_tpu.parallel.multihost import GroupBroken

        sched = self.scheduler
        with phase(INBOX):
            self._drain_inbox()  # dynlint: disable=DYN-J006 — embed readback (.tolist in _run_embeds) is a request-boundary transfer; sanitizer allowlists it as "embed_readback"
            self._propose_drafts()
        with phase(SCHEDULE, waiting=len(sched.waiting),
                   running=len(sched.active)):
            try:
                plan = sched.step_plan()
                why = self._why_not_ahead(plan)
            except StepsInFlight:
                plan, why = None, "preempt"
        if self._inflight is not None and why is not None:
            # the plan was made around steps in flight (rows they finish
            # left out, positions past them): commit them, then plan on
            # what they brought. A prompt whose chunk that plan held was
            # admitted already and waits out the commit inside its
            # prefill_s: the spine's drain_wait_s, the first token's price
            # for the dispatch in flight
            t_drain = time.monotonic()
            self._commit_inflight()
            t_drain = time.monotonic() - t_drain
            for seq in _prefill_seqs(plan):
                if "ttft_s" not in seq.phases:
                    seq.phases["drain_wait_s"] = seq.phases.get(
                        "drain_wait_s", 0.0) + t_drain
            with phase(SCHEDULE, waiting=len(sched.waiting),
                       running=len(sched.active)):
                plan = sched.step_plan()
                if why == "idle":  # the commit left something to plan
                    why = self._why_not_ahead(plan)
        if plan is None:
            # nothing to enqueue: nothing stays pending across a wait that
            # no program covers
            self._deliver()
            self._flush_late_record()
            if not sched.has_work():
                with phase(WAIT):
                    time.sleep(self.idle_sleep_s)
            now_ns = time.monotonic_ns()
            self._charge("wait", now_ns)
            self._t_mark = now_ns * 1e-9
            if self.step_clock is not None:
                self.step_clock.clear()  # as the wall: no iteration's
            return
        if isinstance(plan, DecodePlan) and not (
                self.runner.has_draft or any(s.spec_draft for s in plan.seqs)):
            self._step_decode(plan, why)
            return
        # walls run from commit to commit (IterationRecord.wall_s)
        t0, ts_wall = self._t_mark, time.time()
        # plan-composition fields for this iteration's flight record;
        # branches fill in what they actually served
        rinfo = {"decode_seqs": 0, "decode_steps": 0, "n_chunks": 0,
                 "chunk_tokens": 0, "fused": False, "ragged": False,
                 "spec_rows": 0, "spec_drafted": 0, "spec_emitted": 0,
                 "drain": why or "cold"}
        if isinstance(plan, MixedPlan):
            _dseqs = plan.decode.seqs
        elif isinstance(plan, DecodePlan):
            _dseqs = plan.seqs
        else:
            _dseqs = []
        rinfo["guided_rows"] = sum(
            1 for s in _dseqs if s.guided_m is not None
        )
        if _dseqs and self.recorder.enabled:
            at = [s.computed_len for s in _dseqs]
            self._note_pages_live(
                rinfo, at, getattr(plan, "decode", plan).n_steps)
        decode_done = False
        try:
            if isinstance(plan, PrefillPlan):
                self._run_prefill(plan)
                kind, n_tok = "prefill", len(plan.chunk)
                rinfo.update(n_chunks=1, chunk_tokens=len(plan.chunk))
            elif isinstance(plan, MixedPlan):
                spec = any(s.spec_draft for s in plan.decode.seqs)
                if spec and self._mixed_fusible(plan):
                    # verify rows + packed prefill chunks share ONE ragged
                    # flat-token dispatch (the tentpole path)
                    res = self._run_spec_verify(plan.decode, plan.prefills)
                    if res is None:
                        spec = False  # drafts shed; plain paths below
                    else:
                        chunk_logits, sinfo = res
                        served = plan.prefills[:len(chunk_logits)]
                        rinfo.update(
                            decode_seqs=len(plan.decode.seqs),
                            decode_steps=1,
                            n_chunks=len(served),
                            chunk_tokens=sum(len(p.chunk) for p in served),
                            fused=True, **sinfo,
                        )
                        decode_done = True
                        self._finish_packed_prefills(served, chunk_logits)
                        kind = "mixed"
                        n_tok = (len(plan.decode.seqs) + sinfo["spec_drafted"]
                                 + sum(len(p.chunk) for p in served))
                elif spec:
                    # two-dispatch split (cpu / non-fused runners): the
                    # verify dispatch serves the decode half, the packed
                    # prefill path serves the chunks
                    res = self._run_spec_verify(plan.decode, [])
                    if res is None:
                        spec = False
                    else:
                        _, sinfo = res
                        decode_done = True
                        t1 = self._half_mark()
                        self._publish_fpm(
                            "decode", t1 - t0, len(plan.decode.seqs)
                        )
                        self._run_prefills(plan.prefills)
                        kind = "prefill"
                        n_tok = sum(len(p.chunk) for p in plan.prefills)
                        t0 = t1
                        rinfo.update(
                            decode_seqs=len(plan.decode.seqs),
                            decode_steps=1,
                            n_chunks=len(plan.prefills),
                            chunk_tokens=n_tok, **sinfo,
                        )
                if spec:
                    pass  # served above
                elif self._mixed_fusible(plan):
                    served, out = self._run_mixed_dispatch(plan)
                    n_chunk_tok = sum(len(p.chunk) for p in served)
                    rinfo.update(
                        decode_seqs=len(plan.decode.seqs),
                        decode_steps=plan.decode.n_steps,
                        n_chunks=len(served),
                        chunk_tokens=n_chunk_tok,
                        fused=True, ragged=out.ragged,
                        ragged_pages_live=out.pages_live,
                    )
                    # decode tokens are emitted: from here on a failure
                    # (e.g. in a chunk's sampling extras) must only
                    # fail the prefill sequences
                    decode_done = True
                    self._finish_packed_prefills(served, out[1])
                    # one dispatch ran both halves — a per-kind wall split
                    # doesn't exist; observers ignore the mixed kind
                    kind = "mixed"
                    n_tok = (len(plan.decode.seqs) * plan.decode.n_steps
                             + n_chunk_tok)
                else:
                    # decode first: ITL never waits behind prompt
                    # processing. Publish the halves as separate FPM
                    # events so observers fitting per-kind step-time
                    # models keep clean samples.
                    self._run_decode(plan.decode)
                    decode_done = True
                    t1 = self._half_mark()
                    self._publish_fpm(
                        "decode", t1 - t0, len(plan.decode.seqs)
                    )
                    self._run_prefills(plan.prefills)
                    kind = "prefill"
                    n_tok = sum(len(p.chunk) for p in plan.prefills)
                    t0 = t1
                    rinfo.update(
                        decode_seqs=len(plan.decode.seqs),
                        decode_steps=plan.decode.n_steps,
                        n_chunks=len(plan.prefills),
                        chunk_tokens=n_tok,
                    )
            else:
                res = None
                if any(s.spec_draft for s in plan.seqs):
                    res = self._run_spec_verify(plan, [])
                if res is not None:
                    _, sinfo = res
                    kind = "decode"
                    n_tok = len(plan.seqs) + sinfo["spec_drafted"]
                    rinfo.update(decode_seqs=len(plan.seqs),
                                 decode_steps=1, **sinfo)
                else:
                    self._run_decode(plan)
                    kind, n_tok = "decode", len(plan.seqs)
                    rinfo.update(decode_seqs=len(plan.seqs),
                                 decode_steps=plan.n_steps)
        except GroupBroken:
            raise  # unrecoverable: handled by _loop's fail-fast
        except Exception:
            # one bad step (malformed import, shape bug, OOM) must fail
            # ITS sequences, never kill the step thread: a dead loop
            # strands every queued request with no error and no stream
            # end (the failure surfaces only as a distributed hang).
            # For a mixed step whose decode half already completed, only
            # the prefill sequence is at risk — its decode batch has
            # emitted this iteration's tokens and stays healthy.
            if isinstance(plan, PrefillPlan):
                seqs = [plan.seq]
            elif isinstance(plan, MixedPlan):
                pseqs = [p.seq for p in plan.prefills]
                seqs = pseqs if decode_done else (
                    list(plan.decode.seqs) + pseqs
                )
            else:
                seqs = plan.seqs
            self._fail_step(seqs)
            return
        self._publish_step(
            kind, n_tok, ts_wall, rinfo, t0=t0,
            rec_kind="mixed" if isinstance(plan, MixedPlan) else kind)

    def _publish_step(self, kind: str, n_tok: int, ts_wall: float, rinfo,
                      t0: Optional[float] = None,
                      rec_kind: Optional[str] = None) -> None:
        """The end of an iteration's commit: its FPM and flight record
        as the world stands at the commit, queued behind its items for
        `_deliver` (the listeners, the KV events and the record's append
        are the observers' and wait with them), and the mark the next
        iteration's wall runs from. Walls run from that mark, commit to
        commit (IterationRecord.wall_s); `t0`: where a two-dispatch
        iteration published its first half."""
        t_start = self._t_mark
        if t0 is None:
            t0 = t_start
        with phase(PUBLISH):
            if self.sanitizer is not None:
                # arms the transfer guard + freezes the compiled-family
                # baseline after warmup; a new variant past that is a leak
                self.sanitizer.note_step(self.runner)
            # the commit mark: this wall and the host clock's interval end
            # on it and the next ones start on it, so the walls add up to
            # the loop's busy time and a record's phases to at most its wall
            now_ns = time.monotonic_ns()
            self._publish_fpm(
                kind, now_ns * 1e-9 - t0, n_tok, self._record_iteration(
                    ts_wall, now_ns * 1e-9 - t_start, rec_kind or kind,
                    rinfo, now_ns))
            self._t_mark = now_ns * 1e-9
            self._charge(iteration_class(rec_kind or kind,
                                         bool(rinfo.get("ahead"))), now_ns)
        if self._ahead_blocker(()) is not None:
            # the next program is a whole step (a runner that cannot run
            # ahead, speculation) or none (shutdown): no enqueue to deliver
            # under, so at once, as ever
            self._deliver()

    def _charge(self, cls: str, now_ns: int) -> None:
        """Charge the loop's time since the last charge to class `cls`
        (flight_recorder.ITERATION_CLASSES) and open or close the decode
        interval of every sequence whose first or last token was committed
        since (`_emit`): the first takes a copy of the totals, the last
        puts the differences on its spine, `decode_s` and its six parts,
        which add up to it because the totals tile the clock. Called where
        the commit mark moves, with the mark (`time.monotonic_ns`)."""
        tot = self.class_ns
        tot[cls] += now_ns - self._acct_ns
        self._acct_ns = now_ns
        if not self._spine_due:
            return
        for seq, last in self._spine_due:
            t0 = seq.decode_mark
            if not last:
                if t0 is None:
                    seq.decode_mark = dict(tot)
                continue
            ph, whole = seq.phases, 0
            for c, ns in tot.items():
                ph[f"decode_{c}_s"] = (ns - t0[c]) * 1e-9
                whole += ns - t0[c]
            ph["decode_s"] = whole * 1e-9
            ph["decode_tokens"] = seq.decode_tokens
        self._spine_due.clear()

    def _half_mark(self) -> float:
        """Where a two-dispatch mixed iteration has committed its decode
        half: that half of the wall is charged here, to the class its one
        record's wall goes to, so that a row it finished closes its decode
        interval before the chunks' enqueue delivers its last item. The
        commit mark itself stays (the record's wall is the whole
        iteration's). Returns the moment (time.monotonic's seconds)."""
        now_ns = time.monotonic_ns()
        self._charge("mixed", now_ns)
        return now_ns * 1e-9

    def _fail_step(self, seqs) -> None:
        """One bad step fails ITS sequences and never the step thread."""
        log.exception(
            "engine step failed; erroring %d sequence(s)", len(seqs)
        )
        for seq in seqs:
            try:
                self._emit(seq, [], "error")
                self.scheduler.abort(seq.request_id)
            except Exception:
                log.exception("failed to fail sequence %s", seq.request_id)
        self._recover_poisoned_pools()
        # (the error items queued behind what their streams were still owed)
        self._deliver()

    # -- the decode dispatch in flight ---------------------------------------
    def _ahead_blocker(self, seqs: List[Sequence]) -> Optional[str]:
        """Why a decode dispatch of these rows is read back before the
        next one is planned, whatever that plan will be: the next plan
        needs its tokens on the host (None: it may wait in flight)."""
        if not self.runner.can_run_ahead:
            return "runner"  # PP / SP programs, a multi-host group
        if self._stop.is_set():
            return "shutdown"
        if self._spec_on or self.runner.has_draft:
            return "spec"  # drafts are proposed from host tokens
        if any(s.guided_m is not None for s in seqs):
            return "guided"  # the DFA state advances at commit
        if _batch_penalties(seqs):
            return "penalties"  # the histories are host tokens
        return None

    def _why_not_ahead(self, plan) -> Optional[str]:
        """None where `plan` can be enqueued before the dispatch in flight
        is read back; else the reason it cannot, one of a closed set
        (IterationRecord.drain). With nothing in flight the reason is the
        one that left nothing there ("cold": the iteration before was no
        plain decode, or the loop idled)."""
        if plan is None:
            return "idle"
        if isinstance(plan, PrefillPlan):
            return "prefill"
        if isinstance(plan, MixedPlan):
            return "mixed"
        why = self._ahead_blocker(plan.seqs)
        fl = self._inflight
        if why is not None or fl is None:
            return why or "cold"
        # stable places: every row of the plan continues the row of the
        # dispatch in flight that sits where it will sit; nobody joins
        here = {id(s) for s, live in zip(fl.rows, fl.live) if live}
        if any(id(s) not in here for s in plan.seqs):
            return "rows"
        if self.runner.decode_bucket(len(plan.seqs)) < self.runner.decode_bucket(
                len(fl.rows)):
            return "bucket"  # the rows left fit a smaller program
        return None

    def _commit_inflight(self) -> None:
        """Commit the decode dispatch in flight, if there is one."""
        fl, self._inflight = self._inflight, None
        if fl is not None:
            self._commit_decode(fl)

    def _step_decode(self, plan: DecodePlan, why: Optional[str]) -> None:
        """A plain decode iteration. `why` None: it is enqueued on the
        tokens of the dispatch in flight, which is read back, committed,
        published and delivered after, under it. Then it stays in flight
        itself unless its own rows rule that out."""
        from dynamo_tpu.parallel.multihost import GroupBroken

        prev = self._inflight if why is None else None
        try:
            nxt = self._dispatch_decode(plan, prev)
        except GroupBroken:
            raise
        except Exception:
            self._commit_inflight()
            self._fail_step([s for s in plan.seqs
                             if s.state == SeqState.RUNNING])
            return
        nxt.rinfo["ahead"] = prev is not None
        nxt.rinfo["drain"] = "" if prev is not None else (why or "cold")
        try:
            self._commit_inflight()
        except BaseException:
            # nxt is dropped uncollected: not in flight for the clock either
            if nxt.handle is not None and self.step_clock is not None:
                self.step_clock.handles -= 1
            raise
        # (enqueued ahead: the commit ran under nxt, and its delivery does)
        self._deliver()
        if self._ahead_blocker(plan.seqs) is None:
            self._inflight = nxt
        else:
            self._commit_decode(nxt)

    def _dispatch_decode(self, plan: DecodePlan,
                         prev: Optional["_InFlight"]) -> "_InFlight":
        """Prep, stage and enqueue a plain decode plan: plan.n_steps fused
        iterations in one jit with on-device token feedback. On `prev`
        (the dispatch in flight) the rows keep prev's places and take
        their first tokens from its last ones on the device; a row of
        prev the plan left out (ended, aborted, its budget spent by the
        steps in flight) becomes a pad row: position -1, no pages, the
        scratch state slot."""
        with annotate("engine.decode", batch=len(plan.seqs),
                      steps=plan.n_steps), self._san_scope("decode"):
            with phase(PREP):
                ts_wall = time.time()
                T = plan.n_steps
                if prev is None:
                    rows, live = list(plan.seqs), [True] * len(plan.seqs)
                else:
                    rows = prev.rows
                    planned = {id(s) for s in plan.seqs}
                    live = [id(s) in planned for s in rows]
                seqs = plan.seqs
                rinfo = {"decode_seqs": len(seqs), "n_chunks": 0,
                         "chunk_tokens": 0, "fused": False, "ragged": False,
                         "guided_rows": sum(
                             1 for s in seqs if s.guided_m is not None)}
                # a row in flight sits `inflight` steps past what is
                # committed; the scheduler's page look-ahead covers that
                positions = [s.computed_len + s.inflight if ok else -1
                             for s, ok in zip(rows, live)]
                tables = [s.pages if ok else [] for s, ok in zip(rows, live)]
                T, mkw = self._decode_extras(rows, T, False)
                step0 = self._step_counter + 1
                self._step_counter += T
                n_lp = _batch_logprobs(seqs)
                histories = (
                    [list(s.tokens) for s in rows]
                    if _batch_penalties(seqs) else None
                )
                if (n_lp >= 0 or histories is not None) and self.runner.pp:
                    # the PP decode loop has no logprob/penalty wiring yet —
                    # drop the extras with a warning (same contract as spec
                    # decode) instead of letting a raise inside the shared
                    # dispatch error EVERY sequence in the plan
                    for s in seqs:
                        if _batch_logprobs([s]) >= 0 or _batch_penalties([s]):
                            self._warn_spec_once(
                                s.request_id,
                                "logprobs/penalties are unsupported on "
                                "pipeline-parallel workers and were ignored",
                            )
                    n_lp, histories = -1, None
                if n_lp >= 0 or histories is not None:
                    mkw.update(n_logprobs=n_lp, histories=histories,
                               prompt_lens=[s.n_prompt0 for s in rows])
                if self.side is not None:
                    op = self.side.operand
                    mkw["side"] = [op(s) if ok else None
                                   for s, ok in zip(rows, live)]
                if self.recorder.enabled:
                    at = [p for p in positions if p >= 0]
                    self._note_pages_live(rinfo, at, T)
                rinfo["decode_steps"] = T
                sp = _sampling_params(rows)
                adapters = [s.adapter_idx for s in rows]
                args = (T, [s.tokens[-1] for s in rows], positions, tables,
                        sp, step0)
            rinfo["step"] = self._step_counter
            fl = _InFlight(rows, live, T, n_lp, ts_wall, rinfo)
            if self.runner.can_run_ahead:
                fl.handle = self.runner.decode_dispatch(
                    *args, adapters=adapters, **mkw,
                    prev=None if prev is None else prev.handle)
                if self.step_clock is not None:
                    # enqueued and not collected: what the host does from
                    # here to its decode_collect is hidden under it
                    self.step_clock.handles += 1
            else:
                # a runner of whole steps (a multi-host group replays
                # decode_multi): the readback is part of the call
                self._deliver()
                fl.sampled = self.runner.decode_multi(
                    *args, adapters=adapters, **mkw)
            for s, ok in zip(rows, live):
                if ok:
                    s.inflight += T
        # what the commit before this enqueue left pending (a drain: the
        # dispatch that was in flight, a mixed or prefill iteration) goes
        # out under this program (a runner of whole steps left nothing:
        # _publish_step)
        self._deliver()
        return fl

    def _finish_decode(self, fl: "_InFlight") -> None:
        """Read a decode dispatch back, commit its tokens and emit them.
        A row whose sequence is finished or aborted by now (a stop token
        found in the dispatch before this one, an abort at the inbox) is
        skipped: its pages and state slot went back once, when it
        finished, and what this dispatch wrote for it lies at positions
        past its computed_len, in pages no one else read
        (docs/concurrency.md)."""
        with annotate("engine.decode", batch=sum(fl.live), steps=fl.T), \
                self._san_scope("decode"):
            sampled = fl.sampled
            if fl.handle is not None:
                try:
                    sampled = self.runner.decode_collect(fl.handle)
                finally:
                    if self.step_clock is not None:
                        self.step_clock.handles -= 1
            lp = None
            if fl.n_lp >= 0:
                sampled, lp = sampled
            self._collect_routed(fl.rows, fl.T, [], fl.live)
            with phase(EMIT):
                self._commit_decoded(fl.rows, sampled, lp, fl)

    def _commit_decode(self, fl: "_InFlight") -> None:
        """_finish_decode and the iteration's publish: the half of a
        decode iteration that runs under the next dispatch where one was
        enqueued ahead."""
        from dynamo_tpu.parallel.multihost import GroupBroken

        try:
            self._finish_decode(fl)
        except GroupBroken:
            raise
        except Exception:
            # (aborting them zeroes what they had in flight)
            self._fail_step([s for s, ok in zip(fl.rows, fl.live)
                             if ok and s.state == SeqState.RUNNING])
            return
        self._publish_step("decode", sum(fl.live), fl.ts, fl.rinfo)

    def _record_iteration(self, ts: float, wall: float, kind: str,
                          rinfo: Dict[str, Any], now_ns: int) -> tuple:
        """Assemble this iteration's flight record as the world stands at
        its commit (step thread; cheap field reads only — see DYN-R004):
        (the record, None where the recorder is off; its expert-load
        counters, None for a dense model), for `_deliver` to settle and
        append. `now_ns`: the commit mark `wall` ends on
        (time.monotonic_ns)."""
        rec = self.recorder
        outcome = "ahead" if rinfo.get("ahead") else rinfo.get("drain", "")
        self.run_ahead_totals[outcome] = self.run_ahead_totals.get(
            outcome, 0) + 1
        # a routed model's expert-load counters came back with the sampled
        # tokens; taken every iteration so the runner forgets the dispatch
        load = self.runner.take_moe_load() if self._routed_ok else None
        if not rec.enabled:
            return None, load
        st = self.scheduler.stats
        g2 = g3 = 0
        if self.host_pool is not None:
            g2 = len(self.host_pool.host)
            if self.host_pool.disk is not None:
                g3 = len(self.host_pool.disk)
        hits = self.prefetch.stats["hits"] if self.prefetch is not None else 0
        variants = 0
        for fam in self.runner.compile_families().values():
            variants += fam.variants
        charged = rinfo["chunk_tokens"]
        cum = self.runner.charged_tokens()
        if cum is not None:
            # a cost model (SimRunner) keeps an honest cumulative
            # padded-charge counter; its per-iteration delta is the real
            # charged-token figure
            delta = cum - self._rec_prev_charged
            self._rec_prev_charged = cum
            if delta > 0:
                charged = delta
        trace_ids: List[str] = []
        if tracing.enabled():
            # bounded join key: the traces this iteration served (string
            # parses over <=8 cached traceparents — step-thread cheap)
            for s in self.scheduler.active[:8]:
                pctx = tracing.parse_traceparent(s.tp)
                if pctx is not None and pctx.trace_id not in trace_ids:
                    trace_ids.append(pctx.trace_id)
        record = IterationRecord(
            seq=rinfo.get("step", self._step_counter),
            ts=ts,
            wall_s=wall,
            kind=kind,
            decode_seqs=rinfo["decode_seqs"],
            decode_steps=rinfo["decode_steps"],
            n_chunks=rinfo["n_chunks"],
            chunk_tokens=rinfo["chunk_tokens"],
            charged_tokens=charged,
            ragged=rinfo["ragged"],
            fused=rinfo["fused"],
            n_waiting=st.n_waiting,
            n_running=st.n_running,
            kv_usage=st.kv_usage,
            g2_blocks=g2,
            g3_blocks=g3,
            prefetch_hits=hits,
            compile_variants=variants,
            decode_pages_live=rinfo.get("pages_live", 0) - (
                rinfo.get("pages_step0", 0) if rinfo["ragged"] else 0),
            ragged_pages_live=rinfo.get("ragged_pages_live", 0),
            dsa_ctx_tokens=rinfo.get("dsa_ctx", 0),
            dsa_sel_tokens=rinfo.get("dsa_sel", 0),
            accepted_per_step=(
                rinfo.get("spec_emitted", 0) / rinfo["spec_rows"]
                if rinfo.get("spec_rows") else 0.0
            ),
            guided_rows=rinfo.get("guided_rows", 0),
            ahead=bool(rinfo.get("ahead")),
            drain=rinfo.get("drain", ""),
            trace_ids=trace_ids,
        )
        if self.side is not None:
            self.side.record(record, rinfo, self.scheduler.active)
        self.runner.fill_record(record)
        clock = self.step_clock  # (there is one: rec.enabled)
        clock.cut(now_ns)
        rec.take_clock(record, clock)
        return record, load

    def _layer_mean(self, kinds) -> int:
        """(on a global layer, on a sliding one) as the mean over the
        model's layers."""
        c = self.runner.config
        full, sliding = kinds
        return (mean_over_layers(c, full, sliding)
                if c is not None and c.sliding_window else full)

    def _note_pages_live(self, rinfo, positions, n_steps: int) -> None:
        """An iteration's `pages_live` and, step 0 apart (a ragged program
        walks it, not the decode kernel), `pages_step0`; by kind of layer
        beside the means, for a window-pool model's record."""
        kinds = self._decode_pages_live_kinds(positions, n_steps)
        step0 = self._decode_pages_live_kinds(positions, 1)
        rinfo["pages_live"] = self._layer_mean(kinds)
        rinfo["pages_step0"] = self._layer_mean(step0)
        rinfo["pages_live_kinds"], rinfo["pages_step0_kinds"] = kinds, step0
        # (a cost model's config may be no ModelConfig: no indexer then)
        topk = getattr(self.runner.config, "index_topk", 0)
        if topk:
            # what one layer's selection saw and kept over the steps
            ctx = [n for p in positions for n in range(p + 1, p + n_steps + 1)]
            rinfo["dsa_ctx"] = sum(ctx)
            rinfo["dsa_sel"] = sum(min(n, topk) for n in ctx)

    def _decode_pages_live_kinds(self, positions, n_steps: int):
        """IterationRecord.decode_pages_live by kind of layer, (on one of
        global attention, on one under the sliding window), for decode rows
        at these positions before the step: at fused step t a row's context
        is position + t + 1 tokens, and the device runs every row for all
        n_steps (tokens past a stop are dropped on the host)."""
        ps = self.pool.page_size
        c = self.runner.config
        window = c.sliding_window if c is not None else 0
        full = sliding = 0
        for p in positions:
            for n in range(p + 1, p + n_steps + 1):
                last = (n - 1) // ps
                full += last + 1
                sliding += last - max(n - window, 0) // ps + 1
        return full, sliding

    def _settle_record(self, record, load) -> None:
        """Append an iteration's record (None: the recorder is off) with
        its expert-load counters (`load` None: a dense model), which also
        go to the /metrics totals. Where a prefill chunk that sampled
        nothing was never read back, its counters are still on the
        device: the record is held until the next iteration has
        synchronised, or the engine idles or stops (_flush_late_record),
        instead of blocking on the device here."""
        if load is not None and not load.ready:
            self._rec_late = (record, load)
            return
        if load is not None:
            slots, hit, share, held, listed = load.result()
            t = self.moe_totals
            t["token_slots_total"] += slots
            t["held_slots_total"] += held
            t["experts_hit"], t["load_max_share"] = hit, share
            if record is not None:
                record.moe_token_slots = slots
                record.moe_experts_hit = hit
                record.moe_load_max_share = share
                record.moe_held_slots = held
                record.moe_experts_listed = listed
        if record is not None:
            self.recorder.append(record)

    def _flush_late_record(self) -> None:
        """Append the record _settle_record held back, in its place:
        before any later iteration's."""
        if self._rec_late is not None:
            record, load = self._rec_late
            self._rec_late = None
            load.result()  # now it may wait: the device has moved on
            self._settle_record(record, load)

    def _recover_poisoned_pools(self) -> None:
        """A step that fails AFTER its jit dispatch consumed the donated
        KV pools leaves them deleted — every later step would raise
        'Array has been deleted' and the worker degrades into an error
        loop while still registered healthy. Detect that, rebuild zeroed
        pools, and fail everything whose device KV was lost (waiting
        sequences keep: they own no pages yet and prefill from scratch).
        Host/disk tiers keep their copies — those bytes are real."""
        if not self.runner.pools_deleted():
            return
        log.error("KV pools were consumed by a failed step; rebuilding "
                  "(all device-cached blocks lost)")
        # host/disk tiers keep their copies (those bytes are real) and
        # pending disagg imports stay admittable into the fresh pools
        self._flush_kv_state("error", drop_pending=False, clear_tiers=False)

    def _flush_kv_state(self, error_message: str, *, drop_pending: bool,
                        clear_tiers: bool) -> None:
        """Fail active sequences, release parked entries, zero the device
        pools + prefix cache; optionally drop queued disagg imports and
        flush the lower KV tiers (weight-update policy invalidation)."""
        for seq in list(self.scheduler.active):
            try:
                if error_message == "error":
                    self._emit(seq, [], "error")
                else:
                    self._emit_item(seq, {
                        "finish_reason": "error", "error": error_message,
                        "token_ids": [],
                    })
                self.scheduler.abort(seq.request_id)
            except Exception:
                log.exception("failed to fail sequence %s", seq.request_id)
        for rid, (seq, _) in list(self._parked.items()):
            try:
                self._parked.pop(rid, None)
                self.scheduler.release_parked(seq)
            except Exception:
                log.exception("failed to release parked %s", rid)
        if drop_pending:
            pending, self._kv_pending = self._kv_pending, []
            for seq in pending:
                try:
                    self._emit_item(seq, {
                        "finish_reason": "error", "error": error_message,
                        "token_ids": [],
                    })
                except Exception:
                    log.debug("error emit to pending %s failed (stream "
                              "already gone)", seq.request_id, exc_info=True)
        self._deliver()  # (before the device is waited for)
        self.runner.reset_kv_pools()
        self.pool.reset()
        if clear_tiers and self.host_pool is not None:
            self.host_pool.clear()
        self._publish_kv_events()

    def _drain_inbox(self) -> None:
        while True:
            try:
                op, arg = self._inbox.get_nowait()
            except thread_queue.Empty:
                break
            if op not in _INBOX_LIGHT:
                # the op may wait for the device, a disk or a peer
                self._deliver()
            if op == "add":
                self.scheduler.add(arg)
            elif op == "abort":
                self.scheduler.abort(arg)
                # forked branches live under derived ids; an abort of the
                # parent stream must tear them down too or their pages
                # leak until the (never-coming) finish
                for bid in [
                    s.request_id
                    for s in list(self.scheduler.active)
                    + list(self.scheduler.waiting)
                    if s.branch_of == arg
                ]:
                    self.scheduler.abort(bid)
                parked = self._parked.pop(arg, None)
                if parked is not None:
                    self.scheduler.release_parked(parked[0])
                self._kv_pending = [s for s in self._kv_pending if s.request_id != arg]
                # step-thread discard: the asyncio-side discard can race a
                # warn for a still-batched sequence (the abort lands after
                # the step that warned); this one runs on the warning
                # thread itself, after the sequence left the scheduler
                self._spec_sampling_warned.discard(arg)
            elif op == "add_kv":
                self._kv_pending.append(arg)
            elif op == "export":
                rid, fut, loop, discard = arg
                self._export_parked(rid, fut, loop, discard)
            elif op == "export_meta":
                rid, fut, loop = arg
                self._export_meta(rid, fut, loop)
            elif op == "export_chunk":
                rid, start, n, last, fut, loop = arg
                self._export_chunk(rid, start, n, last, fut, loop)
            elif op == "export_device":
                rid, fut, loop = arg
                self._export_parked_device(rid, fut, loop)
            elif op == "embed":
                self._embed_pending.append(arg)
            elif op == "host_export":
                hashes, fut, loop = arg
                self._host_export(hashes, fut, loop)
            elif op == "host_import":
                self._host_import(*arg)
            elif op == "prefetch":
                if self.prefetch is not None:
                    self.prefetch.on_hint(arg)
            elif op == "prefetch_disk":
                if self.prefetch is not None:
                    self.prefetch.on_disk_read(*arg)
            elif op == "prefetch_obj":
                if self.prefetch is not None:
                    self.prefetch.on_obj_read(*arg)
            elif op == "obj_event":
                h, parent = arg
                self._host_events.append(
                    KvEvent("store", [h], parent, tier="obj"))
            elif op == "reload_weights":
                path, fut, loop = arg
                try:
                    self.runner.reload_params(path)
                    # ALL cached KV was computed under the old policy:
                    # serving it against the new weights silently mixes
                    # policies (caught by the RL parity test)
                    self._flush_kv_state(
                        "weights updated mid-flight; retry",
                        drop_pending=True,  # queued disagg imports carry
                        # old-policy KV bytes — admitting them would mix
                        clear_tiers=True,
                    )
                    self.weights_version += 1
                    loop.call_soon_threadsafe(
                        _set_future, fut, self.weights_version
                    )
                except Exception as e:
                    log.exception("weight reload failed")
                    loop.call_soon_threadsafe(_set_future_exc, fut, e)
        if self._kv_pending or self._embed_pending:
            self._deliver()  # (imports and the encoder wait for the device)
        self._admit_kv_pending()
        self._expire_parked()
        self._run_embeds()
        if self.prefetch is not None:
            self.prefetch.tick()

    def _kv_layout_mismatch(self, payload: Dict[str, Any]) -> Optional[str]:
        """Non-None when a host-staged payload can't be imported into the
        local pool: produced under a different pool layout version
        (mixed-version cluster) or a different page geometry (L, PS, Hk, D)
        — a peer serving a different model or page size. A differing TP
        degree is NOT a mismatch (dense full-head wire, see
        model_runner.kv_arrays_to_payload). Device payloads are
        same-process buffers and never re-sliced."""
        from dynamo_tpu.engine.model_runner import kv_payload_incompatible

        if payload.get("device"):
            return None
        page_shape = self.runner.kv_page_shape
        wire_dtype = self.runner.kv_wire_dtype
        parts = payload.get("chunks") or ([payload] if payload.get("data") else [])
        for p in parts:
            if not p.get("k"):
                continue
            if page_shape is not None:
                bad = kv_payload_incompatible(p, page_shape, wire_dtype)
            else:  # sim runners without pools: version check only
                from dynamo_tpu.engine.model_runner import KV_WIRE_LAYOUT_VERSION

                bad = (
                    None if p.get("layout") == KV_WIRE_LAYOUT_VERSION
                    else f"layout {p.get('layout')} != {KV_WIRE_LAYOUT_VERSION}"
                )
            if bad:
                return bad
        return None

    def _admit_kv_pending(self) -> None:
        """Disagg-decode sequences: admit + import transferred KV pages."""
        still: List[Sequence] = []
        for seq in self._kv_pending:
            bad = self._kv_layout_mismatch(seq.kv_import or {})
            if bad:
                # checked BEFORE admit_with_kv marks the prompt computed:
                # fall back to local prefill (recompute) — never error the
                # request for a peer's stale wire format, and never adopt
                # transposed bytes
                log.warning(
                    "P->D KV payload rejected (%s); recomputing %s locally",
                    bad, seq.request_id,
                )
                seq.kv_import = None
                self.scheduler.add(seq)
                continue
            try:
                self._admit_one_kv(seq, still)
            except Exception as admit_err:
                from dynamo_tpu.parallel.multihost import GroupBroken as _GB

                if isinstance(admit_err, _GB):
                    raise  # unrecoverable: _loop's fail-fast handles it
                # a malformed/corrupt transfer payload (bad shape metadata,
                # truncated bytes) must fail THIS request, not kill the
                # step thread — this runs from _drain_inbox, outside the
                # step-loop guard
                log.exception("KV import failed; erroring %s", seq.request_id)
                try:
                    self._emit(seq, [], "error")
                    self.scheduler.abort(seq.request_id)
                except Exception:
                    log.exception("failed to fail sequence %s", seq.request_id)
        self._kv_pending = still

    def _admit_one_kv(self, seq: Sequence, still: List[Sequence]) -> None:
        seq.tokens = list(seq.prompt)
        seq.n_prompt0 = len(seq.prompt)
        if not self.scheduler.admit_with_kv(seq):
            still.append(seq)
            return
        payload = seq.kv_import or {}
        seq.kv_import = None
        n_kv_pages = (len(seq.prompt) - 1 + self.pool.page_size - 1) // self.pool.page_size
        target = seq.pages[seq.n_shared_pages:n_kv_pages]
        if target and payload.get("device"):
            # colocated transfer: staged buffers are already on device
            self.runner.import_pages_device(
                target, seq.n_shared_pages, payload["k"], payload["v"]
            )
        elif target and payload.get("chunks"):
            # chunked host-staged transfer: each chunk covers global
            # pages [offset, offset+n); skip the prefix-cache-shared
            # span and scatter the rest
            ns = seq.n_shared_pages
            for ch in payload["chunks"]:
                off, n = int(ch.get("offset", 0)), int(ch["n_pages"])
                lo, hi = max(off, ns), min(off + n, n_kv_pages)
                if lo >= hi or not ch.get("data"):
                    continue
                self.runner.import_pages(seq.pages[lo:hi], lo - off, ch)
        elif target and payload.get("data"):
            self.runner.import_pages(target, seq.n_shared_pages, payload)
        if self.runner.has_draft:
            # transferred KV covers the target model only; rebuild the
            # draft pools by (cheap) draft prefill — starting after the
            # prefix-cache-shared pages, whose draft KV the sequence
            # that populated them already wrote
            toks = seq.prompt[:-1]
            chunk = self.scheduler.chunk_size
            shared = seq.n_shared_pages * self.pool.page_size
            for start in range(shared, len(toks), chunk):
                self.runner.draft_prefill(
                    toks[start : start + chunk], start, seq.pages,
                    prior_len=start,
                )

    def _run_embeds(self) -> None:
        """Batch all pending embedding requests into one encoder pass."""
        if not self._embed_pending:
            return
        batch, self._embed_pending = self._embed_pending, []
        try:
            vecs = self.runner.embed([t for t, _, _ in batch])
            for i, (_, fut, loop) in enumerate(batch):
                loop.call_soon_threadsafe(_set_future, fut, vecs[i].tolist())
        except Exception as e:  # pragma: no cover
            log.exception("embed batch failed")
            for _, fut, loop in batch:
                loop.call_soon_threadsafe(_set_future_exc, fut, e)
            from dynamo_tpu.parallel.multihost import GroupBroken as _GB

            if isinstance(e, _GB):
                raise  # unrecoverable: _loop's fail-fast handles it

    def _expire_parked(self) -> None:
        if not self._parked:
            return
        now = time.monotonic()
        for rid in [r for r, (s, dl) in self._parked.items() if dl < now]:
            seq, _ = self._parked.pop(rid)
            self.scheduler.release_parked(seq)

    def _export_parked_device(self, rid: str, fut, loop) -> None:
        """Colocated P→D: gather the parked pages into device staging
        buffers on THIS engine's step thread (the only thread allowed to
        touch this runner's pools — they are donated every step)."""
        entry = self._parked.pop(rid, None)
        if entry is None:
            loop.call_soon_threadsafe(_set_future, fut, None)
            return
        seq, _ = entry
        n_kv_pages = self._n_prompt_pages(seq)
        k, v = self.runner.export_pages_device(seq.pages[:n_kv_pages])
        self.scheduler.release_parked(seq)
        loop.call_soon_threadsafe(
            _set_future, fut,
            {"device": True, "k": k, "v": v, "n_pages": n_kv_pages},
        )

    async def export_parked_kv_device(self, request_id: str):
        """Device-resident parked-KV export (same-process decode engine
        imports the staged buffers without a host round trip)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("export_device", (request_id, fut, loop)))
        return await fut

    def _n_prompt_pages(self, seq) -> int:
        """Pages a parked prompt's KV occupies (export side). The import
        side deliberately uses one page less when the prompt's final token
        starts a fresh page (_admit_kv_pending: ceil((len-1)/ps)) — the
        decode step recomputes that token's KV as it generates."""
        return (len(seq.prompt) + self.pool.page_size - 1) // self.pool.page_size

    def _export_meta(self, rid: str, fut, loop) -> None:
        """Page count of a parked request (no pop — the stream export
        reads chunk by chunk while the request stays parked)."""
        entry = self._parked.get(rid)
        if entry is None:
            loop.call_soon_threadsafe(_set_future, fut, None)
            return
        seq, _ = entry
        loop.call_soon_threadsafe(_set_future, fut, self._n_prompt_pages(seq))

    def _export_chunk(self, rid: str, start: int, n: int, last: bool, fut, loop) -> None:
        """Export pages [start, start+n) of a parked request; `last` pops
        and releases. Runs on the step thread between steps, so each chunk
        read interleaves with decode work instead of one long pool read."""
        entry = self._parked.get(rid)
        if entry is None:
            loop.call_soon_threadsafe(_set_future, fut, None)
            return
        seq, _ = entry
        # an actively-consumed transfer must not expire between chunks: a
        # multi-GB pull interleaved with decode steps can legitimately
        # outlive the parked TTL, so each chunk read renews the lease
        self._parked[rid] = (seq, time.monotonic() + self.parked_ttl_s)
        payload = self.runner.export_pages(seq.pages[start : start + n])
        payload["offset"] = start
        # importers validate coverage against this before trusting the
        # stream (a truncated transfer must recompute, never half-import)
        payload["total_pages"] = self._n_prompt_pages(seq)
        if last:
            self._parked.pop(rid, None)
            self.scheduler.release_parked(seq)
        loop.call_soon_threadsafe(_set_future, fut, payload)

    def _export_parked(self, rid: str, fut, loop, discard: bool = False) -> None:
        entry = self._parked.pop(rid, None)
        if entry is None:
            loop.call_soon_threadsafe(fut.set_result, None)
            return
        seq, _ = entry
        payload = None
        if not discard:
            n_kv_pages = self._n_prompt_pages(seq)
            payload = self.runner.export_pages(seq.pages[:n_kv_pages])
        self.scheduler.release_parked(seq)
        loop.call_soon_threadsafe(fut.set_result, payload)

    def _mm_chunk(self, seq: Sequence, start: int, n: int):
        """Multimodal embeddings falling inside [start, start+n) of the
        prompt, re-based to chunk-local offsets (None if none do)."""
        if seq.mm_embeds is None:
            return None
        idx = [
            (i, p - start)
            for i, p in enumerate(seq.mm_positions)
            if start <= p < start + n
        ]
        if not idx:
            return None
        import numpy as np

        rows, offs = zip(*idx)
        return {"embeds": np.ascontiguousarray(seq.mm_embeds[list(rows)]),
                "offsets": list(offs)}

    def _collect_routed(self, seqs, n_steps: int, prefills,
                        live=None) -> None:
        """After a dispatch and before its emits: where a request of it
        asked (`sampling.routed_experts`), fetch the dispatch's picks and
        keep each asking request's share for the item _emit_item sends
        next: {"start": p, "ids": [position][expert layer][k]} for the
        consecutive positions p, p+1, ... whose forward just ran (a decode
        row: the positions of its n_steps input tokens, of which
        _emit_item keeps those that produced an emitted token). Nothing is
        fetched, and no dispatch differs, when nobody asked."""
        if not self._routed_ok:
            return
        want_d = [i for i, s in enumerate(seqs)
                  if s.sampling.get("routed_experts")
                  and (live is None or (
                      live[i] and s.state == SeqState.RUNNING))]
        want_c = [i for i, p in enumerate(prefills)
                  if p.seq.sampling.get("routed_experts")]
        if not (want_d or want_c):
            return
        decode, chunks = self.runner.routed_picks()
        for i in want_d:  # decode [steps, L_moe, rows, k]
            s = seqs[i]
            self._routed_out[s.request_id] = ({
                "start": s.computed_len,
                "ids": _nested_ints(decode[:n_steps, :, i]),
            }, True)
        for i in want_c:  # chunks[i] [L_moe, n, k]
            p = prefills[i]
            self._routed_out[p.seq.request_id] = ({
                "start": p.start_pos,
                "ids": _nested_ints(chunks[i].transpose(1, 0, 2)),
            }, False)

    def _run_prefill(self, plan: PrefillPlan) -> None:
        with annotate("engine.prefill", tokens=len(plan.chunk)):
            self._run_prefill_inner(plan)

    def _run_prefills(self, plans: List[PrefillPlan]) -> None:
        """Non-fused execution of a packed chunk set. Runners exposing
        `prefill_packed` (the mocker, whose step-time model charges one
        dispatch for the whole set) get all chunks in one call; others
        (PP, interpreter fallback) run the chunks sequentially —
        scheduling still packs, only the dispatch is serial."""
        if (not self.runner.has_prefill_packed or len(plans) <= 1
                or self.runner.has_draft
                or any(
                    self._mm_chunk(p.seq, p.start_pos, len(p.chunk))
                    is not None
                    for p in plans
                )):
            for plan in plans:
                self._run_prefill(plan)
            return
        with annotate("engine.prefill_packed", chunks=len(plans),
                      tokens=sum(len(p.chunk) for p in plans)):
            self._deliver()  # (a whole step: it returns when it is done)
            logits_rows = self.runner.prefill_packed([
                {
                    "tokens": p.chunk,
                    "start": p.start_pos,
                    "table": p.seq.pages,
                    "prior": p.start_pos,
                    "adapter": p.seq.adapter_idx,
                }
                for p in plans
            ])
            with phase(EMIT):
                for plan, lg in zip(plans, logits_rows):
                    self.scheduler.complete_prefill(plan)
                    self._finish_prefill(plan, lg)
            self._deliver_first_tokens()

    def _run_prefill_inner(self, plan: PrefillPlan) -> None:
        seq = plan.seq
        with phase(PREP):
            mm_chunk = self._mm_chunk(seq, plan.start_pos, len(plan.chunk))
        if not self.runner.prefill_enqueues:
            self._deliver()
        logits = self.runner.prefill(
            plan.chunk,
            plan.start_pos,
            seq.pages,
            prior_len=plan.start_pos,
            adapter=seq.adapter_idx,
            mm=mm_chunk,
            **({"side": self.side.operand(seq)}
               if self.side is not None else {}),
            # (nobody reads the logits of a chunk that does not end its
            # prompt: a runner that can leave work out for that is told)
            **({"sampled": False} if self.runner.skips_unsampled
               and not plan.is_last_chunk else {}),
        )
        if self.runner.has_draft and seq.disagg != "prefill":
            # keep the draft model's KV pools in lockstep so spec decode
            # can propose over the full context (skipped on disagg-prefill
            # workers: draft KV isn't exported — the decode worker rebuilds
            # it on admission)
            self.runner.draft_prefill(
                plan.chunk, plan.start_pos, seq.pages, prior_len=plan.start_pos,
                mm=mm_chunk,
            )
        # the chunk is enqueued and nobody has waited for it (its first
        # token is sampled and read below, inside emit)
        self._deliver()
        self._collect_routed([], 0, [plan])
        with phase(EMIT):
            self.scheduler.complete_prefill(plan)
            self._finish_prefill(plan, logits)
        self._deliver_first_tokens()

    def _finish_prefill(self, plan: PrefillPlan, logits) -> None:
        """Post-chunk bookkeeping shared by the standalone and fused mixed
        dispatch paths: sample the first token on the LAST chunk (guided
        mask / logprobs / penalties variants), then park (disagg) or start
        the sequence RUNNING."""
        seq = plan.seq
        if not plan.is_last_chunk:
            if seq.request_id in self._routed_out:
                # an asking request hears of every chunk: an item with no
                # token, only the chunk's `routed_experts`
                self._emit_item(seq, engine_output([], None))
            return
        bias1 = None
        if seq.logit_bias:
            rows = _batch_biases([seq], self.runner)
            if rows is not None:
                bias1 = rows[0]
        first_lp = None
        mask1 = self._guided_mask(seq)
        n_lp1 = _batch_logprobs([seq])
        kw1 = {"mask": mask1} if mask1 is not None else {}
        if bias1 is not None:
            kw1["bias"] = bias1
        if n_lp1 >= 0 or _batch_penalties([seq]):
            token, first_lp = self.runner.sample_one_ex(
                logits, _sampling_params([seq]), self._next_step(),
                history=list(seq.tokens) if _batch_penalties([seq]) else None,
                n_logprobs=n_lp1, **kw1,
            )
        else:
            token = self.runner.sample_one(
                logits, _sampling_params([seq]), self._next_step(), **kw1,
            )
        # fork BEFORE the parent's DFA advance: each branch samples its
        # own first token from these logits under the same pre-advance
        # constraint state the parent's token was sampled under
        if (seq.n_branches > 1 and seq.branch_of is None
                and seq.disagg is None and not seq.branches_spawned):
            self._fork_branches(seq, logits, mask1, bias1)
        self._guided_advance(seq, token)
        if seq.disagg == "prefill":
            # disagg: first token + transfer handle; pages stay pinned for
            # the decode worker's pull (disagg-serving.md bootstrap model)
            self.scheduler.park(seq)
            self._parked[seq.request_id] = (
                seq, time.monotonic() + self.parked_ttl_s
            )
            extra = {}
            if first_lp is not None:
                extra["logprobs"] = [_first_lp_entry(first_lp, seq)]
            self._emit_item(
                seq,
                engine_output(
                    [token],
                    "prefill_complete",
                    kv_transfer={
                        "request_id": seq.request_id,
                        "prompt_len": len(seq.prompt),
                        "first_token": token,
                    },
                    **extra,
                ),
            )
            return
        reason = self.scheduler.complete_decode(seq, token, advance_computed=False)
        emitted = token if reason != "stop" else None
        lp_entries = None
        if first_lp is not None and emitted is not None:
            lp_entries = [_first_lp_entry(first_lp, seq)]
        self._emit(
            seq, [token] if emitted is not None else [], reason,
            logprobs=lp_entries,
        )

    def _fork_branches(self, seq: Sequence, logits, mask1, bias1) -> None:
        """Fan a just-prefilled sequence out into n_branches siblings.

        Each branch shares the parent's complete trunk pages by reference
        (copy-on-write: only the partial tail page is duplicated via the
        pool's copy_hook), inherits the pre-advance guided DFA state, and
        samples its own first token from the parent's prefill logits —
        one prefill pass serves n choices. A branch that can't get pages
        or a batch slot emits an indexed error item; the parent and the
        other branches are unaffected."""
        seq.branches_spawned = True  # a preempted parent must not re-fork
        PS = self.pool.page_size
        n_shared = seq.computed_len // PS
        for k in range(1, seq.n_branches):
            branch = Sequence(
                request_id=f"{seq.request_id}#b{k}",
                prompt=list(seq.prompt),
                sampling=dict(seq.sampling),
                stop=seq.stop,
                arrival=seq.arrival,
                adapter=seq.adapter,
                adapter_idx=seq.adapter_idx,
                logit_bias=seq.logit_bias,
                mm_embeds=seq.mm_embeds,
                mm_positions=seq.mm_positions,
                mm_seed=seq.mm_seed,
                guided=seq.guided,
                guided_m=seq.guided_m,
                guided_s=seq.guided_s,
                branch_of=seq.request_id,
                branch_index=k,
            )
            if branch.sampling.get("seed") is not None:
                # mirror the frontend fan-out's choice-seed derivation so
                # seeded non-greedy branches diverge deterministically
                branch.sampling["seed"] = int(branch.sampling["seed"]) + k
            try:
                pages = self.pool.fork_table(seq.pages, n_shared)
            except NoSpace:
                self._emit_item(branch, engine_output(
                    [], "error",
                    error="no KV pages free to fork this choice",
                ))
                continue
            if not self.scheduler.adopt_branch(branch, seq, pages):
                self._emit_item(branch, engine_output(
                    [], "error",
                    error="no batch slot free to fork this choice",
                ))
                continue
            kwb = {"mask": mask1} if mask1 is not None else {}
            if bias1 is not None:
                kwb["bias"] = bias1
            tok = self.runner.sample_one(
                logits, _sampling_params([branch]), self._next_step(), **kwb,
            )
            self._guided_advance(branch, tok)
            reason = self.scheduler.complete_decode(
                branch, tok, advance_computed=False
            )
            self._emit(branch, [tok] if reason != "stop" else [], reason)

    def _finish_packed_prefills(self, prefills, chunk_logits) -> None:
        """Bookkeeping for prefill chunks whose KV landed in a shared
        dispatch, with per-chunk isolation: one chunk's sampling extras
        failing must not error sibling prefills (or the already-emitted
        decode half)."""
        from dynamo_tpu.parallel.multihost import GroupBroken

        with phase(EMIT):
            for pplan, lg in zip(prefills, chunk_logits):
                try:
                    self.scheduler.complete_prefill(pplan)
                    self._finish_prefill(pplan, lg)
                except GroupBroken:
                    raise
                except Exception:
                    log.exception(
                        "packed chunk bookkeeping failed; erroring %s",
                        pplan.seq.request_id,
                    )
                    try:
                        self._emit(pplan.seq, [], "error")
                        self.scheduler.abort(pplan.seq.request_id)
                    except Exception:
                        log.exception("failed to fail sequence %s",
                                      pplan.seq.request_id)
                    self._recover_poisoned_pools()
        self._deliver_first_tokens()

    # -- speculative decoding (n-gram drafting + ragged verify) -------------
    def _warn_spec_once(self, rid: str, what: str) -> None:
        """One-shot (per request) warning that speculation was degraded;
        the set is pruned when the request finishes or aborts, so a
        long-lived worker's memory stays bounded."""
        if rid in self._spec_sampling_warned:
            return
        self._spec_sampling_warned.add(rid)
        log.warning("request %s: %s", rid, what)

    def _propose_drafts(self) -> None:
        """Propose this iteration's draft tokens (step thread, before
        step_plan so the scheduler can charge them against the mixed
        pool). Speculation is opportunistic per iteration and per
        SEQUENCE: guided and logit-bias rows simply never draft — they
        ride the verify dispatch as single plain rows whose mask/bias
        plumb through verify_spec's always-present sampling operands —
        while free rows in the same batch keep drafting. Only
        logprobs/penalties still pause the whole batch: the verify
        program has no logprob report or penalty count table, so
        partial speculation would silently drop those extras for every
        row in the shared dispatch."""
        running = [
            s for s in self.scheduler.active if s.state == SeqState.RUNNING
        ]
        for s in running:
            s.spec_draft = []
            s.spec_tree = []
        if not self._spec_on or not running:
            return
        blocked = [
            s for s in running
            if _batch_logprobs([s]) >= 0 or _batch_penalties([s])
        ]
        if blocked:
            for s in blocked:
                self._warn_spec_once(
                    s.request_id,
                    "logprobs/penalties sampling is incompatible with "
                    "speculative verification — speculation paused while "
                    "this request is in the batch",
                )
            return
        free: List[Sequence] = []
        for s in running:
            if s.guided_m is not None or s.logit_bias:
                # per-sequence pause: this row stays a plain 1-token
                # verify row (masked/biased); siblings keep speculating
                self._warn_spec_once(
                    s.request_id,
                    "guided/bias row rides the verify dispatch without "
                    "drafting (per-sequence speculation pause)",
                )
                continue
            free.append(s)
        if self.spec_branches > 1:
            # tree mode keeps the host scan (branch enumeration needs
            # every suffix-match site, which the device ring's
            # single-winner gather doesn't surface)
            for s in free:
                tree = self.runner.spec_draft_tree(
                    s.tokens[-1], s.computed_len,
                    self.spec_k, self.spec_branches,
                )
                if tree is None:
                    tree = ngram_propose_tree(
                        s.tokens, self.spec_k, self.spec_branches
                    )
                if tree and tree[0]:
                    s.spec_draft = [int(t) for t in tree[0]]
                    # siblings clipped to the primary's length: the
                    # scheduler charged pages/segments for that shape
                    s.spec_tree = [
                        [int(t) for t in b[: len(tree[0])]]
                        for b in tree[1:] if b
                    ]
            return
        # linear K: an oracle (SimRunner A/B knob) answers first, per row
        # (None where there is none); rows it declines go through ONE
        # fused device-ring proposal when the runner carries the ring,
        # with the host suffix scan as the last fallback
        pending: List[Sequence] = []
        for s in free:
            draft = self.runner.spec_draft(
                s.tokens[-1], s.computed_len, self.spec_k)
            if draft is None:
                pending.append(s)
            else:
                s.spec_draft = [int(t) for t in draft]
        device: Dict[str, List[int]] = {}
        if self._spec_device_draft and pending:
            device = self._device_draft(pending)
        for s in pending:
            draft = device.get(s.request_id)
            if draft is None:
                draft = ngram_propose(s.tokens, self.spec_k)
            s.spec_draft = [int(t) for t in draft] if draft else []

    def _device_draft(self, seqs: List[Sequence]) -> Dict[str, List[int]]:
        """One fused device proposal for every free speculating row:
        per-row token deltas append into the runner's history ring and
        the jitted suffix-match gather proposes k tokens per slot — the
        draft side of the warm loop touches the host exactly once (the
        [slots, k] proposal readback). Returns rid -> draft; rows that
        couldn't get a ring slot are absent (the host scan serves them).
        Bit-identical to ngram_draft.propose while the history fits the
        ring window (model_runner.DRAFT_RING_WINDOW)."""
        if not self._draft_free and not self._draft_slots:
            return {}  # ring was never allocated (disabled after init)
        live = {s.request_id for s in seqs}
        for rid in [r for r in self._draft_slots if r not in live]:
            # finished/preempted/now-guided rows hand their slot back;
            # a row that resumes simply resets into a fresh slot
            self._draft_free.append(self._draft_slots.pop(rid))
            self._draft_synced.pop(rid, None)
        updates: List[tuple] = []
        for s in seqs:
            rid = s.request_id
            slot = self._draft_slots.get(rid)
            delta = len(s.tokens) - self._draft_synced.get(rid, 0)
            if slot is None:
                if not self._draft_free:
                    continue  # more rows than slots: host scan fallback
                slot = self._draft_free.pop()
                self._draft_slots[rid] = slot
                delta = -1  # fresh slot: force the cold reset below
            if delta < 0 or delta > self._draft_D:
                self.runner.draft_ring_reset(slot, s.tokens)
            elif delta:
                updates.append((slot, s.tokens[-delta:]))
            self._draft_synced[rid] = len(s.tokens)
        drafts, n_prop = self.runner.draft_step(updates, self.spec_k)
        out: Dict[str, List[int]] = {}
        for s in seqs:
            slot = self._draft_slots.get(s.request_id)
            if slot is not None:
                n = int(n_prop[slot])
                out[s.request_id] = [int(t) for t in drafts[slot][:n]]
        return out

    def _run_spec_verify(self, dplan: DecodePlan, prefills):
        """ONE ragged flat-token dispatch verifying every speculating
        row's draft (a K+1-token segment: the last real token + K draft
        tokens) alongside the plain decode rows and, on fused runners,
        the packed prefill chunks. Acceptance is the deterministic
        (one-hot q) specialization of spec_decode.accept_and_finalize:
        emit target samples through the first mismatch (+ bonus token on
        a full match), so temperature-0 output is byte-identical to
        plain decode. Rejected drafts cost nothing durable — their KV
        sits past computed_len on unshared pages and the next step
        overwrites it, so pages never leak and the prefix-hash lineage
        (tokens/hashing.py) only ever advances over committed tokens.

        Returns (chunk_logits, rinfo_spec) or None when the runner
        can't shape the dispatch (drafts are dropped; the caller reruns
        the plain path)."""
        seqs = dplan.seqs
        with annotate("engine.spec_verify", batch=len(seqs),
                      chunks=len(prefills)):
            with phase(PREP):
                drafts = [list(s.spec_draft) for s in seqs]
                trees = [list(s.spec_tree) for s in seqs]
                for s in seqs:
                    s.spec_draft = []  # consumed (or shed) either way
                    s.spec_tree = []
                tokens = [s.tokens[-1] for s in seqs]
                positions = [s.computed_len for s in seqs]
                tables = [s.pages for s in seqs]
                step0 = self._next_step()
                chunks = [
                    {
                        "tokens": p.chunk, "start": p.start_pos,
                        "table": p.seq.pages, "prior": p.start_pos,
                        "adapter": p.seq.adapter_idx,
                    }
                    for p in prefills
                ]
                n_drafted = sum(len(d) for d in drafts)
                # tree speculation: each extra branch is an INDEPENDENT verify
                # segment on a forked page table — trunk (committed) pages are
                # ref-shared, only the speculative tail is fresh, so branch KV
                # writes never collide with the primary row's. Branch rows are
                # appended AFTER every primary row, which keeps the row-indexed
                # mask/bias dicts below valid, and they reuse the owning
                # sequence's sampling params + seed: identical branch prefixes
                # then yield identical target samples, the trie invariant
                # accept_tree's walk relies on.
                sp = _sampling_params(seqs)
                branch_rows: List[List[int]] = [[] for _ in seqs]
                forks: List[List[List[int]]] = [[] for _ in seqs]
                n_branch_tok = 0
                if any(trees):
                    PS = self.pool.page_size
                    for i, s in enumerate(seqs):
                        if not drafts[i]:
                            trees[i] = []  # branches never ride without a primary
                        for b in trees[i]:
                            try:
                                fork = self.pool.fork_table(
                                    s.pages, n_shared=s.computed_len // PS
                                )
                            except NoSpace:
                                break  # pool pressure: shed remaining branches
                            branch_rows[i].append(len(tokens))
                            forks[i].append(fork)
                            tokens.append(s.tokens[-1])
                            positions.append(s.computed_len)
                            tables.append(fork)
                            drafts.append([int(t) for t in b])
                            n_branch_tok += len(b) + 1
                            for kf in sp:
                                sp[kf].append(sp[kf][i])
                        trees[i] = trees[i][: len(forks[i])]

                def _release_forks(i: int) -> None:
                    for f in forks[i]:
                        if f is not None:
                            self.pool.release(f)
                    forks[i] = []
                # guided/bias rows never draft (_propose_drafts), so each owns
                # exactly ONE verify position; its mask/bias rides the dispatch's
                # always-present sampling operands (row-aligned dicts)
                vkw: Dict[str, Any] = {}
                masks = {
                    i: self._guided_mask(s)
                    for i, s in enumerate(seqs) if s.guided_m is not None
                }
                if masks:
                    vkw["masks"] = masks
                brows = _batch_biases(seqs, self.runner)
                if brows is not None:
                    vkw["biases"] = {
                        i: brows[i] for i, s in enumerate(seqs) if s.logit_bias
                    }
                n_branch_rows = sum(len(r) for r in branch_rows)
            self._deliver()  # (a whole step; nothing is left where drafts run)
            try:
                with self._san_scope("spec_verify"):
                    out = self.runner.verify_spec(
                        tokens, positions, tables, drafts,
                        sp, step0, chunks=chunks, **vkw,
                    )
            except BucketOverflowError as e:
                for i in range(len(seqs)):
                    _release_forks(i)  # no KV was committed to them
                log.warning(
                    "spec verify overflows runner buckets (%s); dropping "
                    "this iteration's drafts", e,
                )
                return None
            rows, chunk_logits = out
            with phase(EMIT):
                n_rows = sum(1 for d in drafts[: len(seqs)] if d)
                accepted = emitted_spec = tree_sw = 0
                taken: List[List[int]] = []  # per sequence, what it accepts
                for i, seq in enumerate(seqs):
                    if forks[i]:
                        emitted, winner = accept_tree(
                            [drafts[i]] + trees[i],
                            [rows[i]] + [rows[r] for r in branch_rows[i]],
                        )
                        if winner > 0:
                            # adopt the winning branch's forked table BEFORE
                            # committing: its fresh tail pages hold the KV of
                            # the accepted suffix (the primary's tail is stale
                            # past the first divergence). Trunk pages are
                            # shared, so the swap moves one reference; the old
                            # table's speculative tail goes back to the pool.
                            old = seq.pages
                            seq.pages = forks[i][winner - 1]
                            forks[i][winner - 1] = None
                            self.pool.release(old)
                            tree_sw += 1
                        _release_forks(i)  # losers (and fork-side trunk refs)
                    else:
                        emitted = accept_deterministic(drafts[i], rows[i])
                    if drafts[i]:
                        accepted += len(emitted) - 1
                        emitted_spec += len(emitted)
                    taken.append(emitted)
                self._commit_decoded(seqs, taken)
        st = self.spec_stats
        st["verify_iters"] += 1
        st["verify_rows"] += n_rows
        st["drafted"] += n_drafted
        st["accepted"] += accepted
        st["rejected"] += n_drafted - accepted
        st["spec_emitted"] += emitted_spec
        st["tree_rows"] += n_branch_rows
        st["tree_switches"] += tree_sw
        return chunk_logits, {
            "spec_rows": n_rows,
            # billing-honest: branch rows cost len+1 flat tokens each on
            # the dispatch, exactly what the scheduler charged (_spec_cost)
            "spec_drafted": n_drafted + n_branch_tok,
            "spec_emitted": emitted_spec,
            "ragged": out.ragged,
            "ragged_pages_live": out.pages_live,
        }

    def _mixed_fusible(self, plan: MixedPlan) -> bool:
        """Whether this MixedPlan can run as ONE dispatch (runner
        decode_multi_with_prefills). What the runner's programs carry is
        the runner's to say (can_fuse); what these requests need beyond
        that keeps the two-dispatch path."""
        seqs = plan.decode.seqs
        if not self.fused_mixed or not self.runner.can_fuse(
                len(seqs), len(plan.prefills),
                constrained=any(s.guided_m is not None or s.logit_bias
                                for s in seqs)):
            return False
        if _batch_logprobs(seqs) >= 0 or _batch_penalties(seqs):
            return False
        if any(p.seq.logit_bias for p in plan.prefills):
            return False  # chunk-side bias keeps the two-dispatch path
        for pplan in plan.prefills:
            if self._mm_chunk(
                pplan.seq, pplan.start_pos, len(pplan.chunk)
            ) is not None:
                return False  # multimodal chunks ride the standalone prefill
        return True

    def _decode_extras(self, seqs: List[Sequence], T: int,
                       pending_advance: bool):
        """The guided-mask and logit-bias keywords of a decode batch's
        dispatch, and the steps it may fuse: (T, kw).

        Constrained rows need a fresh mask per sampled token. Over a
        multi-step loop they get it from the device-resident DFA plan —
        state advance and mask gather in-XLA, ZERO host syncs per step —
        or, for a schema over the device-table budget, from a host
        callback that advances a COPY of each row's DFA state by the
        device-sampled feedback token between fused steps; both produce
        byte-identical masks on bounded schemas (tests/test_guided.py).
        A runner without that plumbing (guided_fused: the PP loop) runs
        one step under a static mask, and T comes back 1.

        `pending_advance`: step 0 is not the loop's own (a mixed
        dispatch: the ragged step samples it under `masks`, and its token
        is not yet folded into the states the loop starts from)."""
        kw: Dict[str, Any] = {}
        guided_rows = [i for i, s in enumerate(seqs) if s.guided_m is not None]
        if guided_rows:
            vocab = seqs[guided_rows[0]].guided_m.lifter.vocab_size
            loop = T > 1 and self.runner.guided_fused
            if not loop:
                T = 1
            if pending_advance or not loop:
                masks = np.ones((len(seqs), vocab), bool)
                for i in guided_rows:
                    masks[i] = self._guided_mask(seqs[i])
                kw["masks"] = masks
            if loop:
                gdev = self._guided_device_plan(seqs)
                if gdev is not None:
                    kw["guided_dev"] = gdev
                else:
                    kw["mask_fn"] = GuidedMaskContext(
                        len(seqs), vocab,
                        [(i, seqs[i].guided_m, seqs[i].guided_s)
                         for i in guided_rows],
                        pending_advance=pending_advance,
                    )
        biases = _batch_biases(seqs, self.runner)
        if biases is not None:
            kw["biases"] = biases
        return T, kw

    def _commit_decoded(self, seqs: List[Sequence], rows, lp=None,
                        fl: Optional["_InFlight"] = None) -> None:
        """The one commit of a decode batch's tokens (plain, mixed, and
        both speculations), inside the caller's engine.emit span: rows[i]
        holds, in order, the tokens sequence i may take from this
        dispatch. Each is committed until one finishes the sequence
        (tokens sampled past a stop are dropped here), the guided DFA
        follows every token that did not, and the row leaves as one item.
        `lp`: the decode loop's stacked logprob report, indexed like rows.
        `fl`: the dispatch these came from where it may have waited in
        flight: its pad rows, and rows whose sequence has finished or been
        aborted since it was enqueued, take nothing and are never
        complete_decode'd a second time."""
        for i, seq in enumerate(seqs):
            if fl is not None:
                if not fl.live[i] or seq.state != SeqState.RUNNING:
                    continue
                seq.inflight = max(0, seq.inflight - fl.T)
            emit: List[int] = []
            lp_entries: Optional[List[Dict[str, Any]]] = None
            if lp is not None and seq.sampling.get("logprobs") is not None:
                lp_entries = []
            reason = None
            for j, token in enumerate(rows[i]):
                token = int(token)
                reason = self.scheduler.complete_decode(seq, token)
                if not reason:
                    self._guided_advance(seq, token)
                if reason != "stop":
                    emit.append(token)
                    if lp_entries is not None:
                        lp_entries.append(_lp_entry(lp, i, j, seq))
                if reason:
                    break
            self._emit(seq, emit, reason, logprobs=lp_entries or None)

    def _run_mixed_dispatch(self, plan: MixedPlan):
        """The fused dispatch + decode-half bookkeeping: the decode
        batch's fused steps and the packed prefill chunk set share a
        single jitted program — one dispatch and one host sync per
        iteration instead of 1 + n_chunks. Returns (the chunks served,
        the runner's MixedOut: their last-token logits and which program
        ran); the caller finishes the prefill half separately so a
        failure THERE only fails prefill sequences (the decode tokens are
        already emitted)."""
        seqs = plan.decode.seqs
        prefills = list(plan.prefills)
        with annotate("engine.mixed", batch=len(seqs),
                      steps=plan.decode.n_steps, chunks=len(prefills),
                      chunk=sum(len(p.chunk) for p in prefills)):
            with phase(PREP):
                tokens = [s.tokens[-1] for s in seqs]
                positions = [s.computed_len for s in seqs]
                tables = [s.pages for s in seqs]
                # guided rows ride the fused program: step 0 samples under
                # the ragged step's mask operand, steps 1..T-1 under the
                # decode loop's per-step masks (_decode_extras)
                T, mixkw = self._decode_extras(seqs, plan.decode.n_steps, True)
                step0 = self._step_counter + 1
                self._step_counter += T
                sp = _sampling_params(seqs)
                adapters = [s.adapter_idx for s in seqs]
            while True:
                # Bucket-overflow degradation: a pack the runner can't
                # shape (pack/chunk/T bucket exceeded) sheds its newest
                # chunk and retries. Shed chunks were never
                # complete_prefill'd, so the scheduler re-plans them
                # verbatim next iteration (planning is side-effect-free;
                # their pages are already held). Chunks are shed strictly
                # from the tail: what is served is a prefix of the plan's.
                try:
                    out = self._mixed_step(
                        T, tokens, positions, tables, sp, step0,
                        [
                            {
                                "tokens": p.chunk,
                                "start": p.start_pos,
                                "table": p.seq.pages,
                                "prior": p.start_pos,
                                "adapter": p.seq.adapter_idx,
                                **({"side": self.side.operand(p.seq)}
                                   if self.side is not None else {}),
                            }
                            for p in prefills
                        ],
                        adapters=adapters,
                        **mixkw,
                        **({"side": [self.side.operand(s) for s in seqs]}
                           if self.side is not None else {}),
                    )
                    break
                except BucketOverflowError as e:
                    if len(prefills) <= 1:
                        raise  # even one chunk won't fit any shape
                    shed = prefills.pop()
                    log.warning(
                        "mixed pack overflows runner buckets (%s); "
                        "deferring chunk of %s to the next iteration",
                        e, shed.seq.request_id,
                    )
            self._collect_routed(seqs, T, prefills)
            with phase(EMIT):
                self._commit_decoded(seqs, out[0])
        return prefills, out

    def _mixed_step(self, *args, **kw):
        """The runner's fused mixed step with, between its enqueue and
        its readback, the delivery of what the commit before it left
        pending (at a drain: the dispatch that was in flight). A runner
        of whole steps has no such seam and nothing pending."""
        r = self.runner
        if not r.can_run_ahead:
            self._deliver()
            return r.decode_multi_with_prefills(*args, **kw)
        handle = r.mixed_dispatch(*args, **kw)
        clock = self.step_clock
        if clock is not None:
            clock.handles += 1  # enqueued and not collected, as a decode's
        try:
            self._deliver()
            return r.mixed_collect(handle)
        finally:
            if clock is not None:
                clock.handles -= 1

    def _run_decode(self, plan: DecodePlan) -> None:
        """A decode batch run and read back on the spot: the decode half
        of a two-dispatch mixed iteration, and every decode of a worker
        with a draft model."""
        if self.runner.has_draft and self._run_draft_decode(plan):
            return
        self._finish_decode(self._dispatch_decode(plan, None))

    def _run_draft_decode(self, plan: DecodePlan) -> bool:
        """Draft-model speculation: R fused draft-propose + target-verify
        rounds; each round yields 1..gamma+1 tokens per sequence. False
        where the batch cannot take it and nothing ran. Tokens sampled
        past a stop are discarded host-side."""
        seqs = plan.seqs
        T = plan.n_steps
        if _batch_logprobs(seqs) >= 0 or _batch_penalties(seqs):
            # the speculative verify distribution can't honor
            # logprobs/penalties: warn once per offending request and
            # fall back to the PLAIN decode path, which does. The
            # draft model's KV pools skip these positions — that costs
            # draft acceptance on later iterations (verify still
            # corrects every token), never correctness.
            for s in seqs:
                if _batch_logprobs([s]) >= 0 or _batch_penalties([s]):
                    self._warn_spec_once(
                        s.request_id,
                        "logprobs/penalties are incompatible with "
                        "speculative verification — falling back to "
                        "non-speculative decode",
                    )
            return False
        with annotate("engine.decode", batch=len(seqs), steps=T), \
                self._san_scope("decode"):
            with phase(PREP):
                tokens = [s.tokens[-1] for s in seqs]
                positions = [s.computed_len for s in seqs]
                page_tables = [s.pages for s in seqs]
                step0 = self._step_counter + 1
                gamma = self.runner.spec_gamma
                # (guided requests were rejected at admission on draft
                # workers, so no mask handling is needed on this path)
                # Near a token budget (T < gamma+1) shrink gamma instead of
                # falling back to plain decode — the plain path writes no
                # draft KV, which would leave batch-wide draft-pool holes
                # (gamma=0 is plain decoding plus the draft bookkeeping)
                if T < gamma + 1:
                    gamma, R = T - 1, 1
                else:
                    R = T // (gamma + 1)
                self._step_counter += R
            self._deliver()  # (a whole step)
            toks, counts = self.runner.spec_decode_multi(
                R, tokens, positions, page_tables, _sampling_params(seqs),
                step0, gamma=gamma, adapters=[s.adapter_idx for s in seqs],
            )
            with phase(EMIT):
                self._commit_decoded(seqs, [
                    [t for r in range(R) for t in toks[i, r, : counts[i, r]]]
                    for i in range(len(seqs))
                ])
        return True

    def _guided_advance(self, seq: Sequence, token: int) -> None:
        """Advance a sequence's constraint DFA past an accepted token. A
        desync (should be impossible while masks are honored) drops the
        constraint and logs rather than killing the whole batch."""
        m = seq.guided_m
        if m is None or token == m.lifter.eos_id:
            return
        try:
            seq.guided_s = m.advance(seq.guided_s, int(token))
        except ValueError as e:
            log.error("request %s: %s — constraint dropped", seq.request_id, e)
            seq.guided_m = None

    def _next_step(self) -> int:
        self._step_counter += 1
        return self._step_counter

    # -- emission ----------------------------------------------------------
    # Two halves (docs/concurrency.md, "Commit, enqueue, deliver"). The
    # commit half, `_emit` / `_emit_item`, builds a stream's item and
    # queues it; `_deliver` stamps the latency spine, closes a finished
    # request's phases and hands every queued item to its event loop, one
    # call a loop, with the iterations' publishes in their places between.
    def _emit(
        self,
        seq: Sequence,
        token_ids: List[int],
        finish: Optional[str],
        logprobs: Optional[List[Dict[str, Any]]] = None,
    ) -> None:
        """Queue a row's tokens for its stream; they stamp the latency
        spine (`ttft_s`, `itl`) when they are delivered, which is when
        the client can see them."""
        extra = {"logprobs": logprobs} if logprobs else {}
        seq.decode_tokens += len(token_ids)
        # the decode interval (_charge) opens at the mark of the commit
        # that queued the stream's first tokens and closes at the mark of
        # the one that queued its last; a stream that ends with its first
        # group, or on an error, has none
        if seq.decode_mark is None:
            if token_ids and not finish:
                self._spine_due.append((seq, False))
        elif finish and finish != "error":
            self._spine_due.append((seq, True))
        self._emit_item(seq, engine_output(token_ids, finish, **extra), True)

    def _emit_item(self, seq: Sequence, item: Dict[str, Any],
                   spine: bool = False) -> None:
        """Queue `item` for `seq`'s stream, behind everything queued
        before it. What the item holds of the dispatch is taken here, at
        the commit (the `routed_experts` the runner handed out for it);
        what it holds of the clock is `_deliver`'s."""
        if self._routed_out:  # empty unless a request of this dispatch asked
            routed, per_token = self._routed_out.pop(
                seq.request_id, (None, False))
            if routed is not None:
                if per_token:
                    # a decode row: one position per emitted token (a step
                    # whose token was dropped at a stop, or that ran past
                    # the stop, has no reader)
                    routed["ids"] = routed["ids"][: len(item["token_ids"])]
                if routed["ids"]:
                    item["routed_experts"] = routed
        self._undelivered.append((seq, item, spine))

    def _deliver(self) -> None:
        """Hand over what the commits since the last call have queued, in
        commit order: each stream's items to its event loop, ALL of them
        in one `call_soon_threadsafe` a loop (a wake-up of the loop's
        thread costs a write to its self-pipe and a contest for the
        interpreter lock, a row an iteration where each item made its
        own), and then each iteration's publish (its FPM to the listeners,
        the KV events, its record to the ring), in commit order. The
        step loop calls this right after it has enqueued a program and
        before it blocks on one, so that the device works meanwhile;
        before the idle sleep, a whole-step runner's call, a failure's
        recovery and the loop's end, so that nothing waits across a wait
        no program covers. An item never overtakes an earlier one of its
        stream, and a finish item is its stream's last. Never raises."""
        queued = self._undelivered
        if queued:
            self._undelivered = []
            self._hand_over(queued)

    def _hand_over(self, queued: List[tuple]) -> None:
        """_deliver's work, on these entries of the queue."""
        with phase(DELIVER):
            clock = self.step_clock
            # a program of this engine's is enqueued and not collected (the
            # step clock's own test of what is hidden)
            under = clock is not None and bool(clock.handles or clock.serial)
            now = time.monotonic()
            batches: Dict[Any, list] = {}  # event loop -> [(queue, item)]
            publishes = []
            for seq, item, spine in queued:
                try:
                    if seq is None:  # a publish: (None, its FPM, settled)
                        publishes.append((item, spine))
                        continue
                    if spine and item["token_ids"]:
                        self._stamp_spine(seq, len(item["token_ids"]), now)
                    if item.get("finish_reason"):
                        self._close_phases(seq, item, now)
                    if seq.branch_of is not None or seq.n_branches > 1:
                        # branched choices multiplex the parent's stream;
                        # the index tells the consumer which choice each
                        # item belongs to
                        item.setdefault("index", seq.branch_index)
                    entry = self._streams.get(seq.branch_of or seq.request_id)
                    if entry is not None:
                        batches.setdefault(entry[1], []).append(
                            (entry[0], item))
                except Exception:
                    log.exception("delivery of an item failed")
            for loop, pairs in batches.items():
                try:
                    loop.call_soon_threadsafe(_put_all, pairs)
                except RuntimeError:  # the loop is closed: nobody listens
                    log.debug("hand-off to a closed event loop dropped "
                              "%d item(s)", len(pairs), exc_info=True)
            # the observers after the clients: a listener's milliseconds
            # are not a token's
            for m, settled in publishes:
                try:
                    self._deliver_publish(m, settled, under)
                except Exception:
                    log.exception("delivery of an iteration's publish failed")

    def _deliver_first_tokens(self) -> None:
        """Deliver, of what is queued, the prompts' first tokens alone,
        where they were committed: a first token is the one item whose
        wait a client feels whole (TTFT), there is one a joiner and not
        one a row, and nothing of its stream is queued before it. The
        rows' items and the publishes keep their places and wait for the
        next enqueue."""
        first, rest, held = [], [], set()
        for entry in self._undelivered:
            seq, item, spine = entry
            if seq is not None:
                stream = seq.branch_of or seq.request_id
                if (spine and item["token_ids"] and stream not in held
                        and "ttft_s" not in seq.phases):
                    first.append(entry)
                    continue
                held.add(stream)  # (nothing overtakes what stays)
            rest.append(entry)
        if first:
            self._undelivered = rest
            self._hand_over(first)

    def _deliver_publish(self, m: ForwardPassMetrics, settled,
                         under: bool) -> None:
        """An iteration's publish, delivered: the FPM to the listeners,
        the KV events since the last one, and (where it
        closed an iteration: `settled`, _record_iteration's pair) the
        flight record to the ring, in commit order."""
        self.fpm_history.append(m)
        if len(self.fpm_history) > 2048:
            del self.fpm_history[:1024]
        for cb in self._fpm_listeners:
            try:
                cb(m)
            except Exception:  # pragma: no cover
                log.exception("fpm listener failed")
        if settled is None:
            return  # the first half of a two-dispatch iteration
        self._publish_kv_events()
        record, load = settled
        self._flush_late_record()
        if record is not None:
            record.deliver_under = under
        self._settle_record(record, load)

    def _stamp_spine(self, seq: Sequence, n_tokens: int, now: float) -> None:
        """Latency spine: the first delivered token fixes TTFT; later
        groups contribute per-token ITL samples (bounded list — a long
        generation keeps its first _ITL_CAP samples)."""
        if "ttft_s" not in seq.phases:
            if seq.arrival:
                ph = seq.phases
                ph["ttft_s"] = max(0.0, now - seq.arrival)
                # what admission did not explain: first admission to
                # the first delivered token (the prompt's chunks, the
                # iterations between them, a re-prefill after a
                # preemption), so that ttft_s = queue_wait_s +
                # kv_onboard_s + prefill_s by construction
                ph["prefill_s"] = max(0.0, ph["ttft_s"] - ph.get(
                    "queue_wait_s", 0.0) - ph.get("kv_onboard_s", 0.0))
        elif seq.t_last_emit and len(seq.itl) < _ITL_CAP:
            # a multi-token group (fused steps, accepted speculative
            # drafts) contributes ONE ITL sample PER TOKEN — the step
            # wall divided across the group — so itl percentiles, SLO
            # burn rates, and goodput weight a 4-token step as 4 fast
            # inter-token gaps, not one slow one
            per = max(0.0, now - seq.t_last_emit) / n_tokens
            n = min(n_tokens, _ITL_CAP - len(seq.itl))
            seq.itl.extend([per] * n)
        seq.t_last_emit = now

    def _close_phases(self, seq: Sequence, item: Dict[str, Any],
                      now: float) -> None:
        """The final item carries the request's phase spine downstream
        (loadgen/goodput aggregate it; the frontend adds span events)."""
        phases = dict(seq.phases)
        phases["preemptions"] = seq.n_preemptions
        # inside prefill_s: what its chunks waited for the commit of a
        # dispatch in flight (_loop_once); 0.0 where they waited for none
        phases.setdefault("drain_wait_s", 0.0)
        if seq.arrival:
            phases["e2e_s"] = max(0.0, now - seq.arrival)
        if seq.itl:
            phases["itl_s"] = list(seq.itl)
        pctx = tracing.parse_traceparent(seq.tp)
        if pctx is not None:
            # trace id rides the spine so digests / incident bundles
            # can join aggregates back to individual traces
            phases["trace_id"] = pctx.trace_id
        try:
            self._emit_worker_spans(seq, phases, item.get("finish_reason"))
        except Exception:  # pragma: no cover
            log.exception("worker span synthesis failed")
        item.setdefault("phases", phases)
        for cb in self._phase_listeners:
            try:
                cb(phases)
            except Exception:  # pragma: no cover
                log.exception("phase listener failed")

    def _emit_worker_spans(self, seq: Sequence, phases: Dict[str, Any],
                           finish: str) -> None:
        """Synthesize the worker's phase spans retroactively at finish.

        The phase spine measures durations on the step thread; only at
        the final item is the whole story known, so the spans are
        reconstructed from (now - e2e) backwards instead of holding live
        spans open across engine iterations: queue -> kv_onboard
        (tier-labeled) -> prefill -> stream, all children of one
        worker.request span parented on the route span's traceparent."""
        if seq.tp is None or not tracing.enabled():
            return
        e2e = float(phases.get("e2e_s") or 0.0)
        if e2e <= 0.0:
            return
        end_ns = time.time_ns()
        t0 = end_ns - int(e2e * 1e9)
        root = tracing.record_span(
            "worker.request", t0, end_ns, parent=seq.tp,
            attributes={
                "request.id": seq.request_id,
                "finish_reason": finish,
                "n_tokens": len(seq.tokens),
                "preemptions": seq.n_preemptions,
            })
        if root is None:
            return
        wtp = root.traceparent
        qw = max(0.0, float(phases.get("queue_wait_s") or 0.0))
        ob = max(0.0, float(phases.get("kv_onboard_s") or 0.0))
        ttft = max(qw + ob, float(phases.get("ttft_s") or 0.0))
        # clamp each cut into [t0, end_ns] — clock skew between the
        # spine's monotonic stamps and this wall-clock anchor must not
        # produce a child escaping its parent
        cut = [min(end_ns, t0 + int(s * 1e9))
               for s in (qw, qw + ob, ttft)]
        attrs = {"request.id": seq.request_id}
        tracing.record_span("worker.queue", t0, cut[0], parent=wtp,
                            attributes=attrs)
        if ob > 0.0:
            tracing.record_span(
                "worker.kv_onboard", cut[0], cut[1], parent=wtp,
                attributes=dict(attrs, **{
                    "kv.tier": seq.onboard_tier or "G2"}))
        tracing.record_span("worker.prefill", cut[1], cut[2], parent=wtp,
                            attributes=attrs)
        tracing.record_span(
            "worker.stream", cut[2], end_ns, parent=wtp,
            attributes=dict(
                attrs, n_itl_samples=len(seq.itl),
                # what the stream sat under, by class of iteration
                **{k: v for k, v in phases.items()
                   if k.startswith("decode_")}))

    # -- disagg export (called from the asyncio side) -----------------------
    async def export_host_blocks(self, hashes: List[int]) -> Dict[str, Any]:
        """Serve a peer's cross-worker onboarding pull (runs the lower-tier
        read on the step thread — the pools are step-thread state)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("host_export", ([int(h) for h in hashes], fut, loop)))
        return await fut

    async def export_parked_kv(
        self, request_id: str, discard: bool = False
    ) -> Optional[Dict[str, Any]]:
        """Pull a parked request's KV pages (runs the device read on the
        step thread between steps); releases the parked pages. discard=True
        releases without reading (early-finished disagg requests)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("export", (request_id, fut, loop, discard)))
        return await fut

    async def export_parked_kv_stream(self, request_id: str, chunk_pages: int = 16):
        """Chunked parked-KV export (reference disagg-serving.md bootstrap
        handoff: the decode worker pulls KV in bounded pieces instead of
        one monolithic message). Each chunk is read on the step thread
        between decode steps, so a 70B-scale transfer neither stalls
        decode for its full duration nor materializes the whole prompt's
        KV in one host buffer. Yields payload dicts carrying "offset"."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("export_meta", (request_id, fut, loop)))
        total = await fut
        if total is None:
            return
        chunk_pages = max(1, int(chunk_pages))
        for start in range(0, total, chunk_pages):
            n = min(chunk_pages, total - start)
            last = start + n >= total
            fut = loop.create_future()
            self._inbox.put(
                ("export_chunk", (request_id, start, n, last, fut, loop))
            )
            payload = await fut
            if payload is None:  # parked entry expired mid-stream
                return
            yield payload

    def _publish_fpm(self, kind: str, wall: float, n_tok: int,
                     settled: Optional[tuple] = None) -> None:
        """Queue a dispatch's ForwardPassMetrics, as the scheduler stands
        now, for `_deliver` (`_deliver_publish`); `settled`: the record
        and expert load of the iteration it closes."""
        st = self.scheduler.stats
        self._undelivered.append((None, ForwardPassMetrics(
            ts=time.time(),
            kind=kind,
            wall_time_s=wall,
            scheduled_tokens=n_tok,
            n_running=st.n_running,
            n_waiting=st.n_waiting,
            kv_usage=st.kv_usage,
        ), settled))

    def _publish_kv_events(self) -> None:
        events = self.pool.drain_events() + self._host_events
        self._host_events = []
        if not events:
            return
        for cb in self._kv_listeners:
            try:
                cb(events)
            except Exception:  # pragma: no cover
                log.exception("kv listener failed")

    # -- KVBM G2 tier (step-thread callbacks) -------------------------------
    def _offload_page(self, page: int, block_hash: int, parent: Optional[int]) -> None:
        """Device page being evicted → copy its KV to the host tier."""
        from dynamo_tpu.engine.model_runner import kv_payload_to_arrays

        arrays = kv_payload_to_arrays(self.runner.export_pages([page]))
        k, v = arrays if arrays is not None else (None, None)
        self.host_pool.put([block_hash], [parent], k, v)
        self._host_events.append(KvEvent("store", [block_hash], parent, tier="host"))

    def _on_host_evicted(self, hashes: List[int]) -> None:
        self._host_events.append(KvEvent("remove", hashes, tier="host"))
        if getattr(self.host_pool, "obj", None) is not None:
            # terminal tier is G4: the block left the shared store too,
            # so the router's obj_index residency must expire with it
            self._host_events.append(KvEvent("remove", hashes, tier="obj"))

    def _on_obj_stored(self, block_hash: int, parent: Optional[int]) -> None:
        """G4 store_listener — may fire from the writer/spill thread, so
        hand the event to the step thread via the inbox (the KvEvent list
        is step-thread-owned)."""
        self._inbox.put(("obj_event", (block_hash, parent)))

    def _host_export(self, hashes: List[int], fut, loop) -> None:
        """Serve a peer's cross-worker onboarding pull: the leading run of
        `hashes` resident in this worker's lower tiers, as a KV payload
        (reference kvbm-engine onboarding sessions: the remote-G2 read)."""
        from dynamo_tpu.engine.model_runner import kv_arrays_to_payload

        out: Dict[str, Any] = {"n": 0}
        if self.host_pool is not None and hashes:
            n = self.host_pool.match(hashes)
            if n:
                try:
                    k, v = self.host_pool.get(hashes[:n])
                except Exception:
                    # eviction races raise KeyError; G3/G4 reads can raise
                    # IO/network errors — a peer's pull must never kill the
                    # step thread, so fail the export, not the loop
                    log.warning("host export failed; replying empty",
                                exc_info=True)
                    n = 0
                    k = v = None
                if k is None and self.runner.holds_kv:
                    # real engine with hash-only entries (data lost, e.g. a
                    # shared G4 object deleted): advertising n>0 without
                    # data would spread phantom residency cluster-wide
                    n = 0
                out["n"] = n
                if n and k is not None:
                    out.update(kv_arrays_to_payload(k, v))
        loop.call_soon_threadsafe(_set_future, fut, out)

    def _host_import(self, hashes: List[int], parents: List[Optional[int]],
                     payload: Dict[str, Any]) -> None:
        """Blocks pulled from a peer's lower tier land in the local G2 (the
        admission path then onboards them like any host-tier hit). Emits
        host store events so the router's lower-tier credits follow."""
        from dynamo_tpu.engine.model_runner import kv_payload_to_arrays

        if self.host_pool is None or not hashes:
            return
        try:
            # geometry/dtype validated at INGEST: a mismatched peer block
            # stored into G2 would otherwise pass host_pool and explode as
            # an unhandled KvWireLayoutMismatch at onboard time
            arrays = kv_payload_to_arrays(
                payload, self.runner.kv_page_shape, self.runner.kv_wire_dtype,
            )
        except Exception:
            # mixed-version peer (KvWireLayoutMismatch) or corrupt bytes:
            # drop the pull — admission recomputes; never adopt the blocks
            log.warning("peer KV payload rejected; recomputing", exc_info=True)
            return
        k, v = arrays if arrays is not None else (None, None)
        self.host_pool.put(hashes, parents, k, v)
        self._host_events.append(
            KvEvent("store", list(hashes), parents[0] if parents else None,
                    tier="host")
        )

    def _onboard_from_host(self, pages: List[int], hashes: List[int],
                           seq: Optional[Sequence] = None) -> bool:
        """Host-tier blocks → device pages during admission. Returns False
        when a matched block was evicted between match and get (lower-tier
        LRU churn under memory pressure) — the scheduler then recomputes
        instead of trusting a partial import.

        Imports stream in `onboard_layer_groups` layer slabs (FlowKV);
        when both the tier AND the device pools are int8-quantized the
        blocks pass through natively (no dequantize/requantize). Measured
        transfer time feeds the per-tier kv_onboard_ewma that topology-
        aware routing consumes."""
        from dynamo_tpu.engine.model_runner import kv_arrays_to_payload

        if self.prefetch is not None:
            # any of these blocks still mid-promotion arrived LATE: this
            # synchronous import wins, the prefetch job is cancelled (a
            # duplicate in-flight import dedups via pool.register)
            self.prefetch.note_sync_onboard(hashes)
        tiers = (self.host_pool.residency(hashes)
                 if hasattr(self.host_pool, "residency")
                 else ["host"] * len(hashes))
        if seq is not None and tiers:
            # deepest rung dominates the transfer — it labels the
            # worker.kv_onboard span (same attribution as the EWMA)
            order = {"host": 0, "disk": 1, "obj": 2}
            label = {"host": "G2", "disk": "G3", "obj": "G4"}
            deepest = max(tiers, key=lambda t: order.get(t, -1))
            seq.onboard_tier = label.get(deepest, deepest)
        groups = self.onboard_layer_groups
        t0 = time.perf_counter()
        try:
            payload = self._native_quant_payload(hashes, tiers)
            k = v = None
            if payload is None:
                k, v = self.host_pool.get(hashes)
        except KeyError:
            log.info("lower-tier block evicted before onboard; recomputing")
            return False
        if payload is not None:
            self.runner.import_pages(pages, 0, payload, layer_groups=groups)
            self._note_onboard(tiers, len(hashes), time.perf_counter() - t0)
            return True
        if k is None:
            # real engines need bytes (a hash-indexed block whose data is
            # gone — e.g. a shared G4 object deleted externally — must be
            # recomputed, not trusted); sim runners track KV at hash level
            # only and None is their normal case — but the transfer still
            # takes wall time, so charge the import (SimRunner sleeps it;
            # without this, mocker prefetch A/Bs would credit the
            # synchronous path with a free onboard)
            if self.runner.holds_kv:
                log.info("lower-tier block has no data; recomputing")
                return False
            self.runner.import_pages(
                pages, 0, {"sim": True, "data": True, "n_pages": len(pages)},
                layer_groups=groups)
            self._note_onboard(tiers, len(hashes), time.perf_counter() - t0)
            return True
        self.runner.import_pages(pages, 0, kv_arrays_to_payload(k, v),
                                 layer_groups=groups)
        self._note_onboard(tiers, len(hashes), time.perf_counter() - t0)
        return True

    def _native_quant_payload(self, hashes: List[int], tiers: List[str]):
        """int8+scales pass-through payload when the whole chain is
        G2-resident, the tier quantizes, and the device pools are int8
        (kv_quantize) — else None (dense path). Raises KeyError on
        eviction races like host_pool.get."""
        if not self.runner.kv_quantize:
            return None
        host = getattr(self.host_pool, "host", self.host_pool)
        if not getattr(host, "quantize", False):
            return None
        if any(t != "host" for t in tiers):
            return None
        from dynamo_tpu.kvbm.quant import is_quantized_block
        from dynamo_tpu.engine.model_runner import kv_quant_arrays_to_payload

        blocks = [host.get_block_raw(h) for h in hashes]
        if not blocks or not all(
            is_quantized_block(k) and is_quantized_block(v)
            for k, v in blocks
        ):
            return None
        kq = np.stack([b[0]["q"] for b in blocks], axis=1)
        ks = np.stack([b[0]["s"] for b in blocks], axis=1)
        vq = np.stack([b[1]["q"] for b in blocks], axis=1)
        vs = np.stack([b[1]["s"] for b in blocks], axis=1)
        return kv_quant_arrays_to_payload(kq, ks, vq, vs)

    def _note_onboard(self, tiers: List[str], n_blocks: int,
                      elapsed_s: float, tier: Optional[str] = None) -> None:
        """Fold one measured onboard into the per-tier per-block EWMA.
        A chain spanning tiers is attributed to its DEEPEST tier — the
        rung that dominated the transfer time (G3 file reads dwarf the
        G2 memcpy above them)."""
        if tier is None:
            order = {"host": 0, "disk": 1, "obj": 2}
            tier = "host"
            for t in tiers:
                if order.get(t, -1) > order[tier]:
                    tier = t
        per_block = elapsed_s / max(1, n_blocks)
        e = self.kv_onboard_ewma.get(tier)
        if e is None:
            self.kv_onboard_ewma[tier] = {"s_per_block": per_block,
                                          "n": n_blocks}
            return
        alpha = 0.25
        e["s_per_block"] = alpha * per_block + (1 - alpha) * e["s_per_block"]
        e["n"] += n_blocks


def _put_all(pairs) -> None:
    """On the event loop's side of `_deliver`'s one hand-off: every item
    into its stream's queue, in the order they were committed."""
    for out, item in pairs:
        out.put_nowait(item)


def _set_future(fut: asyncio.Future, value) -> None:
    if not fut.done():
        fut.set_result(value)


def _set_future_exc(fut: asyncio.Future, exc: Exception) -> None:
    if not fut.done():
        fut.set_exception(exc)


def _stable_seed(request_id: str) -> int:
    """Process-independent sampling seed so a migrated/retried request samples
    the same stream on whichever worker replays it (Python's hash() is salted
    per process)."""
    import hashlib

    d = hashlib.blake2b(request_id.encode(), digest_size=4).digest()
    return int.from_bytes(d, "big") & 0x7FFFFFFF


def _sampling_params(seqs: List[Sequence]) -> Dict[str, list]:
    """Plain host lists; the runner converts to device arrays (keeps the
    mocker's SimRunner — and thus mocker processes — entirely jax-free)."""
    return {
        "temperature": [float(s.sampling.get("temperature", 1.0)) for s in seqs],
        "top_k": [int(s.sampling.get("top_k", 0)) for s in seqs],
        "top_p": [float(s.sampling.get("top_p", 1.0)) for s in seqs],
        "seeds": [
            (s.sampling.get("seed") if s.sampling.get("seed") is not None
             else _stable_seed(s.request_id))
            for s in seqs
        ],
        "rep": [float(s.sampling.get("repetition_penalty", 1.0)) for s in seqs],
        "freq": [float(s.sampling.get("frequency_penalty", 0.0)) for s in seqs],
        "presence": [float(s.sampling.get("presence_penalty", 0.0)) for s in seqs],
    }


def _batch_biases(seqs: List[Sequence], runner):
    """[n, V] f32 additive logit-bias rows for the batch, or None when no
    sequence carries one (out-of-range token ids are ignored — the
    preprocessor validates, but the wire is untrusted)."""
    if not any(s.logit_bias for s in seqs):
        return None
    vocab_size = runner.vocab_size
    rows = np.zeros((len(seqs), vocab_size), np.float32)
    for i, s in enumerate(seqs):
        if not s.logit_bias:
            continue
        cached = getattr(s, "_bias_row", None)
        if cached is None or cached.shape[0] != vocab_size:
            cached = np.zeros(vocab_size, np.float32)
            for tok, b in s.logit_bias:
                t = int(tok)
                if 0 <= t < vocab_size:
                    cached[t] = float(b)
            s._bias_row = cached  # constant for the sequence's lifetime
        rows[i] = cached
    return rows


def _prefill_seqs(plan) -> List[Sequence]:
    """The sequences whose prompt chunks `plan` carries."""
    if isinstance(plan, PrefillPlan):
        return [plan.seq]
    if isinstance(plan, MixedPlan):
        return [p.seq for p in plan.prefills]
    return []


def _batch_penalties(seqs: List[Sequence]) -> bool:
    """True when any sequence in the batch asked for a repetition/
    frequency/presence penalty (switches on the token-history transfer +
    on-device count table; no-op rows keep default parameters)."""
    return any(
        float(s.sampling.get("repetition_penalty", 1.0)) != 1.0
        or float(s.sampling.get("frequency_penalty", 0.0)) != 0.0
        or float(s.sampling.get("presence_penalty", 0.0)) != 0.0
        for s in seqs
    )


def _batch_logprobs(seqs: List[Sequence]) -> int:
    """Top-N logprob report size for the batch (-1 = nobody asked). One
    compiled variant serves the whole batch; the report width is bucketed
    to a fixed menu because it is a jit-static argument — arbitrary widths
    would let clients induce a fresh decode-loop compile per request.
    Per-sequence responses are trimmed to each request's own N."""
    want = [int(s.sampling.get("logprobs") or 0)
            for s in seqs if s.sampling.get("logprobs") is not None]
    if not want:
        return -1
    mx = max(want)
    for b in (0, 5, 20):
        if mx <= b:
            return b
    return 20


def _nested_ints(picks) -> List[List[List[int]]]:
    """A host array of expert ids [positions, L_moe, k] as the stream
    carries it: nested lists of Python ints. (Not `.tolist()`: dynlint
    reads that name as a device sync inside the step loop, DYN-J006.)"""
    return [[[int(e) for e in ids] for ids in pos] for pos in picks]


def _first_lp_entry(first_lp, seq: Sequence) -> Dict[str, Any]:
    """Prefill-first-token logprob record, trimmed to the sequence's own
    requested top-N (the compiled report width is the bucketed batch max)."""
    n = int(seq.sampling.get("logprobs") or 0)
    return {
        "logprob": first_lp[0],
        "top_ids": first_lp[1][:n],
        "top_logprobs": first_lp[2][:n],
    }


def _lp_entry(lp, i: int, j: int, seq: Sequence) -> Dict[str, Any]:
    """One emitted token's logprob record from the decode loop's stacked
    report, trimmed to the sequence's own requested top-N."""
    tok_lp, ids, vals = lp
    n = int(seq.sampling.get("logprobs") or 0)
    return {
        "logprob": float(tok_lp[i, j]),
        "top_ids": [int(t) for t in ids[i, j, :n]],
        "top_logprobs": [float(v) for v in vals[i, j, :n]],
    }
