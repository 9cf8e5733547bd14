"""Continuous-batching scheduler with chunked prefill, prefix-cache reuse,
and recompute-preemption.

Pure host logic (no JAX): decides, per engine iteration, either one prefill
chunk (single sequence) or one decode step (whole running batch) — the
vLLM-style alternating schedule the reference's mocker also models
(lib/mocker: "simulates KV allocation, prefix caching, batching,
preemption"). The engine executes the plan on the ModelRunner.

Invariants:
- `computed_len` = tokens whose KV is in the pool. While RUNNING,
  computed_len == len(tokens) - 1 (the newest sampled token's KV is written
  by the next decode step).
- prefix-matched pages are complete and shared (read-only); writes happen
  only at positions >= computed_len, which always land on unshared pages.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Dict, List, Optional, Tuple

from dynamo_tpu.engine.kv_pool import NoSpace, PagePool
from dynamo_tpu.tokens.hashing import block_hashes, hash_block, request_seed


def _chain_seed(seq: "Sequence") -> Optional[int]:
    """Hash-chain seed: LoRA adapters and multimodal content each fork the
    block lineage (K/V depends on both; equal token ids under different
    adapters or images must never share cache blocks)."""
    return request_seed(seq.adapter, seq.mm_seed)

log = logging.getLogger("dynamo_tpu.engine.scheduler")

class SeqState(Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    RUNNING = "running"
    FINISHED = "finished"


@dataclass
class Sequence:
    request_id: str
    prompt: List[int]
    sampling: Dict[str, Any]
    stop: Dict[str, Any]
    arrival: float = 0.0
    # disaggregation (docs/design-docs/disagg-serving.md roles):
    #   None = aggregated; "prefill" = compute KV + first token then park;
    #   "decode" = KV arrives via transfer, skip prefill compute
    disagg: Optional[str] = None
    kv_import: Any = None  # opaque page payload for disagg-decode admission
    adapter: Optional[str] = None  # LoRA adapter name (None = base model)
    adapter_idx: int = 0  # resolved slot (engine sets at admission)
    logit_bias: Any = None  # [[token_id, bias], ...] (OpenAI logit_bias)
    # multimodal: embeddings for image-placeholder positions (np [n, E]),
    # their absolute prompt positions, and a content hash for KV isolation
    mm_embeds: Any = None
    mm_positions: Any = None
    mm_seed: Optional[int] = None
    # guided decoding: wire spec (dict), compiled GuidedMatcher, DFA state
    guided: Any = None
    guided_m: Any = None
    guided_s: int = 0
    state: SeqState = SeqState.WAITING
    tokens: List[int] = field(default_factory=list)  # prompt + generated
    pages: List[int] = field(default_factory=list)
    computed_len: int = 0
    inflight: int = 0  # decode steps dispatched and not yet committed (the
    #   engine runs one dispatch ahead): its next position is computed_len +
    #   inflight, and a budget those steps spend is spent
    n_shared_pages: int = 0  # leading pages from prefix-cache hits
    side: Any = None  # what the sequence holds in the scheduler's side
    #   cache (engine/side_cache.py), which alone sets it; None: nothing
    hash_chain: List[int] = field(default_factory=list)  # registered block hashes
    finish_reason: Optional[str] = None
    n_preemptions: int = 0
    n_prompt0: int = 0  # original prompt length (preemption rewrites prompt)
    # latency spine (runtime/flight_recorder.py docs): locally-measured
    # phase durations, seeded with upstream-hop stamps from ctx.metadata
    # and attached to the final emitted item as item["phases"]
    phases: Dict[str, float] = field(default_factory=dict)
    # causal trace: the traceparent this request arrived with (route
    # span); the engine synthesizes the worker's queue/onboard/prefill/
    # stream spans under it retroactively at finish
    tp: Optional[str] = None
    # deepest KV tier the admission onboard touched (G2/G3/G4) — labels
    # the worker.kv_onboard span
    onboard_tier: Optional[str] = None
    itl: List[float] = field(default_factory=list)  # bounded ITL samples
    t_last_emit: float = 0.0  # monotonic time of the last token emission
    # the decode interval (engine._charge): tokens queued for the stream,
    # and the engine's totals by class of iteration as they stood at the
    # commit mark of its first token (None until then)
    decode_tokens: int = 0
    decode_mark: Optional[Dict[str, int]] = None
    # speculative decoding: draft tokens proposed for THIS iteration
    # (engine sets before step_plan; the scheduler trims them to the
    # mixed token budget; the engine consumes and clears after verify)
    spec_draft: List[int] = field(default_factory=list)
    # tree speculation: EXTRA candidate branches beyond spec_draft (which
    # is branch 0). Each rides the verify dispatch as its own segment on
    # a forked page table sharing the trunk; the scheduler charges every
    # branch's tokens against the mixed pool and sheds branches before
    # it trims the primary draft (a branch is strictly optional work)
    spec_tree: List[List[int]] = field(default_factory=list)
    # fork-on-branch (n>1 sampling): the parent carries n_branches; each
    # forked sibling carries branch_of=<parent request_id> and its choice
    # index, and shares the parent's trunk pages copy-on-write
    n_branches: int = 1
    branch_of: Optional[str] = None
    branch_index: int = 0
    # set after the parent's first prefill forks (or fails to fork) its
    # siblings: a preempted parent re-prefills, and re-forking would emit
    # duplicate finish items for choice indices that already streamed
    branches_spawned: bool = False

    @property
    def n_generated(self) -> int:
        return len(self.tokens) - self.n_prompt0

    @property
    def prompt_remaining(self) -> int:
        return max(0, len(self.prompt) - self.computed_len)


@dataclass
class PrefillPlan:
    seq: Sequence
    chunk: List[int]
    start_pos: int
    is_last_chunk: bool


@dataclass
class DecodePlan:
    seqs: List[Sequence]
    n_steps: int = 1  # fused decode iterations (multi-step decode)


@dataclass
class MixedPlan:
    """One engine iteration that co-schedules the running decode batch
    with a token-budgeted SET of prefill chunks (vLLM-style chunked
    prefill, extended to ragged packing — the semantics the reference's
    planner models, docs/design-docs/planner-design.md:262). Decode runs
    first so ITL never waits behind prompt processing; the chunks come
    from distinct PREFILL sequences and their combined length is capped
    at `mixed_prefill_tokens`, so the prefill cost per iteration stays
    bounded no matter how many prompts are in flight."""

    prefills: List[PrefillPlan]
    decode: DecodePlan

    @property
    def prefill(self) -> PrefillPlan:
        """Oldest chunk — compatibility accessor for single-chunk-era
        call sites (and the natural chunk for single-chunk fallbacks)."""
        return self.prefills[0]


@dataclass
class SchedulerStats:
    """Per-iteration ForwardPassMetrics feed (planner observes these)."""

    n_waiting: int = 0
    n_running: int = 0
    scheduled_tokens: int = 0
    kv_usage: float = 0.0


class StepsInFlight(Exception):
    """The plan needs to preempt a sequence that has decode steps in
    flight. A preemption rewrites a sequence's prompt from its committed
    tokens, so the engine commits what is in flight first and plans again
    (Scheduler._preempt)."""


class Scheduler:
    def __init__(
        self,
        pool: PagePool,
        *,
        max_batch: int = 64,
        chunk_size: int = 512,
        max_seq_pages: int = 128,
        enable_prefix_cache: bool = True,
        decode_steps: int = 1,
        mixed_prefill_tokens: int = 256,
        mixed_prefill_seqs: int = 8,
        mixed_min_chunk: int = 16,
        host_tier=None,  # HostKvPool-like: .match(hashes) -> n
        host_onboard=None,  # cb(pages, hashes, seq=None) -> bool (G2→G1)
        max_seq_tokens: int = 0,  # model context length (0 = page cap only)
        spec_max_tokens: int = 0,  # per-iteration cap on speculative
        #   draft tokens (0 = bounded by the mixed pool leftover alone)
        spec_seg_budget: int = 0,  # sampled-row slots one ragged dispatch
        #   offers (decode rows + chunks + verify tokens); 0 = unbounded
        side=None,  # engine/side_cache.SideCache: what a sequence of this
        #   model keeps beside its KV pages (Runner.side_kind); None: nothing
    ):
        self.pool = pool
        self.side = side
        if side is not None:
            if enable_prefix_cache or host_tier is not None:
                raise ValueError(side.no_prefix)
            side.check_limits(pool.page_size, max_batch)
        self.max_batch = max_batch
        self.chunk_size = chunk_size
        self.max_seq_pages = max_seq_pages
        # rope-validity cap: page capacity bounds what FITS, the model's
        # max_seq_len bounds what is NUMERICALLY MEANINGFUL — a request
        # without max_tokens must stop at the context limit, not push
        # positions past the rope table into garbage logits
        self.max_seq_tokens = int(max_seq_tokens or 0)
        self.enable_prefix_cache = enable_prefix_cache
        self.decode_steps = decode_steps
        # co-scheduling budget: when decode work exists, this is the POOL
        # of prefill tokens per iteration, fair-shared across up to
        # `mixed_prefill_seqs` PREFILL sequences (oldest first, at least
        # `mixed_min_chunk` tokens each) and run IN THE SAME iteration as
        # the decode dispatch (0 = legacy strict prefill-first
        # alternation; mixed_prefill_seqs=1 = legacy single-chunk cap).
        # With no running sequences the full chunk_size still applies —
        # the budget trades TTFT for bounded ITL only when both compete.
        self.mixed_prefill_tokens = mixed_prefill_tokens
        self.mixed_prefill_seqs = max(1, mixed_prefill_seqs)
        self.mixed_min_chunk = max(1, mixed_min_chunk)
        self.spec_max_tokens = max(0, spec_max_tokens)
        self.spec_seg_budget = max(0, spec_seg_budget)
        self.host_tier = host_tier
        self.host_onboard = host_onboard
        self.waiting: deque[Sequence] = deque()
        self.active: List[Sequence] = []
        self.stats = SchedulerStats()
        # prompt tokens served from warm KV (prefix/tree reuse): these
        # never charge the mixed_prefill_tokens pool — chunking starts at
        # computed_len, so only the un-reused suffix is prefill work
        self.reused_prefix_tokens = 0
        self.prompt_tokens_total = 0  # denominator for the tree hit rate

    # -- API ---------------------------------------------------------------
    def add(self, seq: Sequence) -> None:
        seq.tokens = list(seq.prompt)
        seq.n_prompt0 = len(seq.prompt)
        self.waiting.append(seq)

    def abort(self, request_id: str) -> None:
        for i, s in enumerate(self.active):
            if s.request_id == request_id:
                self._finish(s, "cancelled")
                return
        for s in list(self.waiting):
            if s.request_id == request_id:
                s.state = SeqState.FINISHED
                s.finish_reason = "cancelled"
                self.waiting.remove(s)
                return

    def has_work(self) -> bool:
        return bool(self.waiting or self.active)

    def step_plan(self) -> Optional[PrefillPlan | DecodePlan | MixedPlan]:
        """`_step_plan`, then (a model with a side cache) what the plan's
        chunks need of it, taken now that the plan is final."""
        plan = self._step_plan()
        if self.side is None or plan is None or isinstance(plan, DecodePlan):
            return plan
        return self._side_cover_for_chunks(plan)

    def _step_plan(self) -> Optional[PrefillPlan | DecodePlan | MixedPlan]:
        """Admit what fits, then plan this iteration's work.

        With `mixed_prefill_tokens > 0` the plan co-schedules: the whole
        running batch decodes every iteration, and a token-budgeted set
        of prefill chunks from distinct PREFILL sequences rides along
        (MixedPlan). The budget is fair-shared oldest-first with a
        per-seq minimum so one long prompt cannot starve the rest, and
        leftover share from short prompts flows to the next in line.
        Strict prefill-first alternation (mixed_prefill_tokens=0) stalls
        every decode for the full chunk pipeline of each arriving
        prompt — the ITL inflation the reference planner's
        chunked-prefill model exists to avoid."""
        self._admit()
        prefill_seqs = [s for s in self.active if s.state == SeqState.PREFILL]
        prefill_seq = prefill_seqs[0] if prefill_seqs else None
        running = [s for s in self.active if s.state == SeqState.RUNNING]
        if prefill_seq is not None and (
            not running or self.mixed_prefill_tokens <= 0
        ):
            return self._plan_prefill(prefill_seq)
        if not running:
            self._update_stats(0)
            return None
        # fuse up to decode_steps iterations, bounded by the per-seq budget
        # remaining (max_tokens / context cap) so fused steps aren't wasted
        cap = self.max_seq_pages * self.pool.page_size
        if self.max_seq_tokens:
            cap = min(cap, self.max_seq_tokens)
        n_steps = self.decode_steps
        live = []
        for s in running:
            # steps in flight count as taken: step plans cap n_steps by
            # every row's budget, so a row that ends by length ends exactly
            # at a dispatch's last step, and one whose budget the steps in
            # flight spend has no place in this plan (it finishes when they
            # are committed)
            budget = min(
                cap - s.computed_len,
                int((s.stop or {}).get("max_tokens", 1 << 30)) - s.n_generated,
            ) - s.inflight
            if s.inflight and budget <= 0:
                continue
            live.append(s)
            n_steps = min(n_steps, max(1, budget))
        running = live
        # prefill chunks claim the pool FIRST (planning is side-effect
        # free) so a speculation burst can never starve real prefills —
        # verify rows are charged from the pool's leftover only
        pplans = self._plan_prefills(prefill_seqs) if prefill_seq else []
        self._trim_spec(running, pplans, cap)
        spec_tokens = sum(self._spec_cost(s) for s in running)
        if spec_tokens:
            # verify rows and fused multi-step decode don't mix: a verify
            # dispatch already advances speculating rows by up to K+1
            n_steps = 1
        running = self._ensure_decode_capacity(running, lookahead=n_steps)
        if not running:
            if prefill_seq is not None:
                return self._plan_prefill(prefill_seq)
            self._update_stats(0)
            return None
        spec_tokens = sum(self._spec_cost(s) for s in running)
        if prefill_seq is None:
            self._update_stats(len(running) * n_steps + spec_tokens)
            return DecodePlan(running, n_steps)
        self._update_stats(
            len(running) * n_steps + spec_tokens
            + sum(len(p.chunk) for p in pplans)
        )
        return MixedPlan(prefills=pplans, decode=DecodePlan(running, n_steps))

    @staticmethod
    def _spec_cost(s: Sequence) -> int:
        """Charged verify tokens for one sequence: the primary draft's
        tokens (its +1 verify position is the row's own decode slot)
        plus EVERY token of every extra tree branch (a branch row's
        position-0 entry has no decode slot to hide behind — all
        len(b)+1 entries are extra flat tokens and sampled rows; the
        twin bills them identically, keeping tree A/Bs honest)."""
        return len(s.spec_draft) + sum(len(b) + 1 for b in s.spec_tree)

    def _trim_spec(
        self, running: List[Sequence], pplans: List[PrefillPlan], cap: int
    ) -> None:
        """Fit this iteration's draft tokens to the budgets that keep the
        verify dispatch inside the registered compile bucket: drafted
        tokens charge the `mixed_prefill_tokens` pool AFTER prefill
        chunks took their share (the verified +1 token per row is the
        row's own decode slot), an optional absolute per-iteration cap,
        and the ragged dispatch's sampled-row slots when the engine set
        one. Per sequence, a draft is also clipped to the tokens the
        request can still legally generate."""
        if self.mixed_prefill_tokens <= 0:
            for s in running:
                s.spec_draft = []
                s.spec_tree = []
            return
        left = self.mixed_prefill_tokens - sum(len(p.chunk) for p in pplans)
        if self.spec_max_tokens:
            left = min(left, self.spec_max_tokens)
        seg_left = None
        if self.spec_seg_budget:
            # one sampled-row slot per decode row and per chunk; each
            # drafted token needs one more (its verify position is gathered)
            seg_left = self.spec_seg_budget - len(running) - len(pplans)
        for s in running:
            if not s.spec_draft:
                s.spec_tree = []  # branches never ride without a primary
                continue
            take = min(len(s.spec_draft), max(0, left))
            if seg_left is not None:
                take = min(take, max(0, seg_left))
            # KV for fed draft tokens lands at computed_len+1 .. +take:
            # stay inside the page/context cap
            take = min(take, max(0, cap - s.computed_len - 1))
            remaining = (
                int((s.stop or {}).get("max_tokens", 1 << 30)) - s.n_generated
            )
            take = min(take, max(0, remaining))
            if take < len(s.spec_draft):
                # the primary draft itself was trimmed — branches are
                # strictly optional, shed them all before clipping it
                s.spec_tree = []
            s.spec_draft = s.spec_draft[:take]
            left -= take
            if seg_left is not None:
                seg_left -= take
            # extra tree branches: each costs len(b)+1 flat tokens AND
            # len(b)+1 sampled-row slots (no decode slot of its own) plus
            # one ragged segment; shed whole branches from the tail when
            # the leftover can't carry them. Branches longer than the
            # (possibly clipped) primary are clipped to it — the fork's
            # page capacity is only guaranteed that far.
            kept: List[List[int]] = []
            for b in s.spec_tree:
                b = b[:take]
                cost = len(b) + 1
                if not b or cost > max(0, left) or (
                    seg_left is not None and cost > max(0, seg_left)
                ):
                    continue
                kept.append(b)
                left -= cost
                if seg_left is not None:
                    seg_left -= cost
            s.spec_tree = kept

    # -- admission ---------------------------------------------------------
    def _admit(self) -> None:
        while self.waiting and len(self.active) < self.max_batch:
            seq = self.waiting[0]
            if not self._try_allocate(seq):
                break
            self.waiting.popleft()
            self.active.append(seq)
            seq.state = SeqState.PREFILL
            # latency spine: WAITING -> PREFILL transition ends queue_wait
            # (first admission only — preemption re-admits don't reset it)
            if seq.arrival and "queue_wait_s" not in seq.phases:
                seq.phases["queue_wait_s"] = max(
                    0.0, time.monotonic() - seq.arrival)

    def _try_allocate(self, seq: Sequence) -> bool:
        PS = self.pool.page_size
        prompt = seq.prompt
        matched_pages: List[int] = []
        hashes: List[int] = []
        use_cache = self.enable_prefix_cache and seq.n_preemptions == 0
        max_shared = (len(prompt) - 1) // PS
        if use_cache:
            matched_pages, hashes = self.pool.match_prefix(prompt, _chain_seed(seq))
            # never share the page containing the final prompt token: its
            # logits must be recomputed, so cap the match below it
            while len(matched_pages) > max_shared:
                self.pool.release([matched_pages.pop()])
                hashes.pop()

        # G2 host-tier continuation: blocks beyond the device match that the
        # host pool holds get onboarded into freshly-allocated pages
        host_n = 0
        host_hashes: List[int] = []
        if use_cache and self.host_tier is not None and self.host_onboard is not None:
            all_hashes = block_hashes(prompt, PS, _chain_seed(seq))
            candidates = all_hashes[len(matched_pages):max_shared]
            host_n = self.host_tier.match(candidates)
            host_hashes = candidates[:host_n]

        match_len = len(matched_pages) * PS
        # pages for the rest of the prompt plus the first generated token
        need = -(-(len(prompt) + 1) // PS) - len(matched_pages)
        try:
            fresh = self.pool.alloc(need)
        except NoSpace:
            self.pool.release(matched_pages)
            return False
        if self.side is not None:
            # and what the side cache keeps for it, as far as the least
            # first chunk it can be given (the rest comes with each chunk,
            # `_side_cover_for_chunks`): without it the sequence waits,
            # whatever the KV pool has
            least = (self.mixed_min_chunk if self.mixed_prefill_tokens > 0
                     else self.chunk_size)
            try:
                self.side.admit(seq, min(len(prompt), least))
            except NoSpace:
                self.pool.release(fresh)
                self.side.waits += 1
                return False

        if host_n:
            t_onboard = time.monotonic()
            if self.host_onboard(fresh[:host_n], host_hashes, seq):
                # latency spine: lower-tier KV promotion paid at admission
                seq.phases["kv_onboard_s"] = (
                    seq.phases.get("kv_onboard_s", 0.0)
                    + (time.monotonic() - t_onboard))
                parent = hashes[-1] if hashes else _chain_seed(seq)
                for page, h in zip(fresh[:host_n], host_hashes):
                    canonical = self.pool.register(page, h, parent)
                    if canonical != page:  # raced with another registration
                        self.pool._ref_inc(canonical)
                        self.pool.release([page])
                        fresh[fresh.index(page)] = canonical
                    parent = h
                hashes = hashes + host_hashes
                match_len = (len(matched_pages) + host_n) * PS

        seq.pages = matched_pages + fresh
        seq.n_shared_pages = len(matched_pages)
        seq.hash_chain = hashes
        seq.computed_len = match_len
        self.reused_prefix_tokens += match_len
        if seq.n_preemptions == 0:  # re-admits would double-count
            self.prompt_tokens_total += len(prompt)
        return True

    # -- the side cache ------------------------------------------------------
    def _release_side(self, seq: Sequence) -> None:
        if self.side is not None:
            self.side.release(seq)

    def _side_cover_for_chunks(self, plan):
        """What the side cache keeps for a final plan's chunks (its decode
        rows got theirs with their KV pages, `_ensure_decode_capacity`). A
        chunk of a mixed plan that finds no unit is left out of this
        iteration; a lone chunk that finds none cannot be served at all."""
        chunks = [plan] if isinstance(plan, PrefillPlan) else plan.prefills
        kept = []
        for p in chunks:
            try:
                self.side.cover(p.seq, p.start_pos,
                                p.start_pos + len(p.chunk) - 1)
                kept.append(p)
            except NoSpace:
                self.side.waits += 1
        if isinstance(plan, PrefillPlan):
            if kept:
                return plan
            raise RuntimeError(
                f"no unit of the {self.side.kind} cache for a prefill chunk "
                f"of {len(plan.chunk)} tokens with nothing else to run: "
                f"{self.side.units} units are too few for this batch and "
                "chunk size")
        if not kept:
            return plan.decode
        plan.prefills[:] = kept
        return plan

    # -- prefill -----------------------------------------------------------
    def _plan_prefill(
        self, seq: Sequence, max_tokens: Optional[int] = None
    ) -> PrefillPlan:
        start = seq.computed_len
        budget = self.chunk_size if max_tokens is None else min(
            self.chunk_size, max(1, max_tokens)
        )
        end = min(len(seq.prompt), start + budget)
        return PrefillPlan(
            seq=seq,
            chunk=seq.prompt[start:end],
            start_pos=start,
            is_last_chunk=end == len(seq.prompt),
        )

    def _plan_prefills(self, cands: List[Sequence]) -> List[PrefillPlan]:
        """Fair-share the `mixed_prefill_tokens` pool across up to
        `mixed_prefill_seqs` PREFILL sequences, oldest first.

        Each packed sequence is offered at least `mixed_min_chunk`
        tokens (so progress is never sliced to nothing under load) and
        at most its equal share of what is left — a long prompt at the
        head of the line cannot drain the pool, and budget a short
        prompt leaves unused flows to the sequences behind it."""
        plans: List[PrefillPlan] = []
        left = self.mixed_prefill_tokens
        for i, seq in enumerate(cands):
            if left <= 0 or len(plans) >= self.mixed_prefill_seqs:
                break
            slots = min(len(cands) - i, self.mixed_prefill_seqs - len(plans))
            share = max(self.mixed_min_chunk, left // max(1, slots))
            plan = self._plan_prefill(seq, max_tokens=min(share, left))
            if plan.chunk:
                plans.append(plan)
                left -= len(plan.chunk)
        return plans

    def complete_prefill(self, plan: PrefillPlan) -> None:
        seq = plan.seq
        seq.computed_len += len(plan.chunk)
        # latency spine: iterations in which a chunk of this prompt ran
        # (a count, not a duration: no `_s`)
        seq.phases["prefill_iters"] = seq.phases.get("prefill_iters", 0) + 1
        self._register_complete_pages(seq)
        if plan.is_last_chunk:
            seq.state = SeqState.RUNNING

    def park(self, seq: Sequence) -> None:
        """Disagg-prefill: KV computed; hold pages (still ref'd) for the
        decode worker's pull, out of the active set."""
        seq.state = SeqState.FINISHED
        seq.finish_reason = "prefill_complete"
        self._release_side(seq)
        if seq in self.active:
            self.active.remove(seq)

    def release_parked(self, seq: Sequence) -> None:
        self.pool.release(seq.pages)
        seq.pages = []

    def admit_with_kv(self, seq: Sequence) -> bool:
        """Disagg-decode admission: allocate pages for the full (computed)
        prompt; caller imports transferred KV into the non-shared pages and
        the sequence starts RUNNING with no prefill pass.

        The prompt's last token is the prefill-sampled token whose KV is
        *not* yet computed, so computed_len = len(prompt) - 1."""
        if len(self.active) >= self.max_batch:
            return False
        if self.side is not None:
            raise ValueError(
                "admission with transferred KV is not built for a model "
                f"with a {self.side.kind} cache beside its pages: pages do "
                "not carry it")
        if not self._try_allocate(seq):
            return False
        seq.computed_len = len(seq.prompt) - 1
        seq.state = SeqState.RUNNING
        self.active.append(seq)
        self._register_complete_pages(seq)
        return True

    def adopt_branch(
        self, branch: Sequence, parent: Sequence, pages: List[int]
    ) -> bool:
        """Admit a fork-on-branch sibling directly into the running batch.

        The caller (engine._fork_branches) already fork_table'd the
        parent's pages — the shared trunk is ref-bumped and the partial
        tail copied — so the branch starts exactly where the parent is:
        same computed KV, same hash chain, one prefill-sampled token away
        from its first decode step. No prefill pass, no allocation."""
        if len(self.active) >= self.max_batch or self.side is not None:
            # (what a side cache holds for the parent cannot be forked: the
            # engine asks for no branch)
            self.pool.release(pages)
            return False
        branch.tokens = list(parent.tokens)
        branch.n_prompt0 = parent.n_prompt0
        branch.pages = pages
        branch.computed_len = parent.computed_len
        branch.n_shared_pages = parent.n_shared_pages
        branch.hash_chain = list(parent.hash_chain)
        branch.state = SeqState.RUNNING
        self.active.append(branch)
        return True

    # -- decode ------------------------------------------------------------
    def _ensure_decode_capacity(
        self, running: List[Sequence], lookahead: int = 1
    ) -> List[Sequence]:
        """Each running seq needs page slots for positions computed_len ..
        computed_len+lookahead-1, past the steps it has in flight; on pool
        exhaustion preempt the youngest sequences (recompute-style), which
        StepsInFlight puts off until nothing is in flight."""
        survivors: List[Sequence] = []
        for seq in running:
            if seq.state != SeqState.RUNNING:  # preempted by an earlier turn
                continue
            # a speculating row writes KV for its fed draft tokens at
            # computed_len+1 .. +K in the SAME dispatch, so its lookahead
            # is the draft length + 1, not the fused step count
            last_pos = seq.computed_len + seq.inflight + max(
                lookahead, len(seq.spec_draft) + 1
            ) - 1
            while True:
                need = last_pos // self.pool.page_size + 1 - len(seq.pages)
                try:
                    if need > 0:
                        seq.pages.extend(self.pool.alloc(need))
                    if self.side is not None:
                        # (the steps in flight read what they were given:
                        # a unit freed here is written by nothing before
                        # they are done, the device runs dispatches in order)
                        self.side.cover(
                            seq, seq.computed_len + seq.inflight, last_pos)
                    survivors.append(seq)
                    break
                except NoSpace:
                    victim = self._pick_victim(exclude=seq)
                    if victim is None:
                        self._preempt(seq)
                        break
                    self._preempt(victim)
                    if victim in survivors:
                        survivors.remove(victim)
        return survivors

    def _pick_victim(self, exclude: Sequence) -> Optional[Sequence]:
        for seq in reversed(self.active):  # youngest first
            if seq is not exclude and seq.state == SeqState.RUNNING:
                return seq
        return None

    def _preempt(self, seq: Sequence) -> None:
        if seq.inflight:
            # its prompt would be rewritten from tokens that lack the ones
            # in flight: the engine commits them and plans again
            raise StepsInFlight()
        log.info("preempting %s (recompute)", seq.request_id)
        self.pool.release(seq.pages)
        self._release_side(seq)  # it prefills again from position 0
        seq.pages = []
        seq.hash_chain = []
        seq.n_shared_pages = 0
        seq.computed_len = 0
        seq.n_preemptions += 1
        seq.spec_draft = []  # stale drafts must not ride the re-admission
        seq.spec_tree = []
        seq.state = SeqState.WAITING
        # re-admit with prompt = all tokens so far (already-emitted ones are
        # not re-emitted; generation resumes with the next sampled token)
        seq.prompt = list(seq.tokens)
        self.active.remove(seq)
        self.waiting.appendleft(seq)

    def complete_decode(
        self, seq: Sequence, new_token: int, advance_computed: bool = True
    ) -> Optional[str]:
        """Append a sampled token; returns finish_reason if the engine-level
        stop fires (frontend-level stop strings are handled downstream).

        advance_computed=True for decode steps (the step wrote the fed
        token's KV at position computed_len); False for the token sampled
        from prefill logits (its KV is written by the *next* decode step) —
        the invariant computed_len == len(tokens) - 1 must hold either way.
        """
        if advance_computed:
            seq.computed_len += 1
        seq.tokens.append(new_token)
        self._register_complete_pages(seq)

        stop = seq.stop or {}
        reason = None
        if (
            not stop.get("ignore_eos")
            and new_token in (stop.get("stop_ids") or [])
            and seq.n_generated > int(stop.get("min_tokens") or 0)
        ):
            reason = "stop"
        elif seq.n_generated >= int(stop.get("max_tokens", 1 << 30)):
            reason = "length"
        elif len(seq.tokens) >= self.max_seq_pages * self.pool.page_size:
            reason = "length"
        elif self.max_seq_tokens and len(seq.tokens) >= self.max_seq_tokens:
            reason = "length"
        if reason:
            self._finish(seq, reason)
        return reason

    def _finish(self, seq: Sequence, reason: str) -> None:
        seq.state = SeqState.FINISHED
        seq.finish_reason = reason
        self.pool.release(seq.pages)
        self._release_side(seq)
        seq.pages = []
        seq.inflight = 0  # what is still queued for it is dropped at commit
        seq.spec_draft = []
        seq.spec_tree = []
        if seq in self.active:
            self.active.remove(seq)

    # -- prefix registration ----------------------------------------------
    def _register_complete_pages(self, seq: Sequence) -> None:
        """Register pages that became complete (content-addressed) so other
        requests can share them; source of router 'store' events."""
        if not self.enable_prefix_cache:
            return
        PS = self.pool.page_size
        n_complete = min(seq.computed_len // PS, len(seq.pages))
        while len(seq.hash_chain) < n_complete:
            i = len(seq.hash_chain)
            parent = seq.hash_chain[-1] if seq.hash_chain else _chain_seed(seq)
            h = hash_block(parent, seq.tokens[i * PS : (i + 1) * PS])
            canonical = self.pool.register(seq.pages[i], h, parent)
            if canonical != seq.pages[i]:
                # another seq registered this block first; swap to the
                # canonical page and free ours
                self.pool._ref_inc(canonical)
                self.pool.release([seq.pages[i]])
                seq.pages[i] = canonical
            seq.hash_chain.append(h)

    # -- stats -------------------------------------------------------------
    def _update_stats(self, scheduled: int) -> None:
        self.stats = SchedulerStats(
            n_waiting=len(self.waiting),
            n_running=len([s for s in self.active if s.state == SeqState.RUNNING]),
            scheduled_tokens=scheduled,
            kv_usage=self.pool.usage(),
        )
