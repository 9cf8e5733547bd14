"""What a sequence keeps beside its KV pages, named once.

A *side cache* is a second pool on the runner (`Runner.side_kind`) whose
units the host hands out a sequence: a kind, a size in units, an allocator,
what a step hands its program for a row, what an iteration records, the
`/metrics` series, and the sentence with which it refuses what moves KV by
pages alone. The scheduler calls `admit` / `cover` / `release` where it
takes and gives back KV pages, the engine `operand` / `record`, the worker
`gauges`; none of them knows which kind a model has, nor how many. Two
kinds exist: `StateSlots` (models/jamba.py and models/ling.py: one slot of
recurrent state a sequence, Mamba's or KDA's) and
`WindowPages` (models/mimo.py: a second page table into the window layers'
pool). A model that keeps both (models/sambay.py: `side_kind`
"state+window") gets `Composed` over one of each: a sequence holds the list
of what it holds in each part, and takes all of it or nothing. A pool that
rides the KV page table (models/mla.py's index keys) is no side cache:
pages carry it.

A family with a new kind writes its model module's `SIDE` record
(models/toolkit.SideCacheOps) and one class here (docs/FAMILIES.md); one
that keeps kinds that exist names them in its `SIDE` and writes nothing
here.

No jax here: mocker processes import the scheduler.
"""

from __future__ import annotations

from typing import Any, Iterable, Iterator, List, Optional, Tuple

from dynamo_tpu.engine.kv_pool import NoSpace, PagePool
from dynamo_tpu.engine.runner_api import Runner, refusal


class SideCache:
    """The host side of a runner's second pool. `Sequence.side` is what a
    sequence holds here (None: nothing) and is this object's alone to set.
    A kind sets `kind` (its Runner.side_kind), `units` (of the runner's
    pool, the scratch unit 0 among them) and `no_prefix` (why its scheduler
    runs without a prefix cache)."""

    waits = 0  # admissions put off, and chunks a plan dropped, for want of
    #   a unit where the KV pool had room (the scheduler counts them)

    @classmethod
    def need(cls, runner: Runner, **limits) -> int:
        """Units a scheduler of these limits needs of this kind."""
        raise NotImplementedError

    @classmethod
    def build(cls, runner: Runner, units: int) -> "SideCache":
        """The cache over `units` units the runner holds."""
        raise NotImplementedError

    @classmethod
    def for_runner(cls, runner: Runner, **limits) -> "SideCache":
        return cls.build(runner,
                         runner.ensure_side_cache(cls.need(runner, **limits)))

    def check_limits(self, page_size: int, max_batch: int) -> None:
        """ValueError where this cache cannot serve a scheduler of these."""

    def admit(self, seq, first_tokens: int) -> None:
        """What `seq` needs to become active and run a first chunk of
        `first_tokens` tokens. Raises NoSpace with nothing taken."""

    def cover(self, seq, first_query: int, last_query: int) -> None:
        """What queries at first_query .. last_query of an admitted `seq`
        read and write. Raises NoSpace with nothing taken."""

    def release(self, seq) -> None:
        """Everything `seq` holds, back (preempt, finish, abort, park)."""

    def operand(self, seq) -> Any:
        """What a step hands the runner for `seq`'s row (`side=`; a pad
        row's is None, the scratch unit)."""
        return seq.side

    def record(self, record, rinfo: dict, active: List[Any]) -> None:
        """This iteration's fields of the IterationRecord."""

    def gauges(self) -> Iterator[Tuple[str, str, float]]:
        """(name, help, value) of each /metrics gauge a worker sets."""
        return iter(())

    def refusal(self, model_name: str, what: str) -> str:
        return refusal(self.kind, model_name, what)


class StateSlots(SideCache):
    """One slot of recurrent state a sequence (a model with state-space
    layers, models/jamba.py, or with KDA layers, models/ling.py): a free
    list over the runner's state pool, slot 0 scratch. A sequence's first
    token starts from zeros whatever the slot held, so a slot needs no
    clearing."""

    kind = "state"
    no_prefix = (
        "a model with state-space layers matches no prefix: a cached block is "
        "usable only with the recurrent state at its boundary, which nothing "
        "snapshots, so its scheduler runs without the prefix cache and without "
        "a host tier (and publishes no stored blocks)")

    def __init__(self, units: int, kda: bool = False):
        self.units = int(units)
        self.kda = kda  # the slots hold KDA states (models/ling.py)
        # free slots, lowest first; 0 is scratch and never handed out
        self._free: List[int] = list(range(self.units - 1, 0, -1))

    @classmethod
    def need(cls, runner: Runner, *, max_batch: int, **_limits) -> int:
        # one slot for every sequence that can be active (a chunk a step
        # packs beside the batch is an active sequence's), and scratch
        return max_batch + 1

    @classmethod
    def build(cls, runner: Runner, units: int):
        # (a cost model's runner may carry no ModelConfig)
        return cls(units, kda=getattr(runner.config, "is_kda", False))

    def check_limits(self, page_size: int, max_batch: int) -> None:
        if self.units - 1 < max_batch:
            raise ValueError(
                f"{self.units} state slots (one of them scratch) do not "
                f"give each of max_batch {max_batch} active sequences its own")

    @property
    def used(self) -> int:
        return max(0, self.units - 1 - len(self._free))

    def admit(self, seq, first_tokens: int) -> None:
        # (every slot holder is active and there are max_batch slots, so
        # one is free wherever the scheduler has a row free)
        seq.side = self._free.pop()

    def release(self, seq) -> None:
        if seq.side:
            self._free.append(seq.side)
        seq.side = None

    def record(self, record, rinfo: dict, active: List[Any]) -> None:
        record.state_slots_used = self.used
        record.state_slots_total = self.units - 1
        if self.kda:
            # no ragged step: every chunk is `kda_chunk`'s, every decode
            # step `kda_update`'s
            record.kda_update_rows = rinfo["decode_seqs"] * rinfo["decode_steps"]
            record.kda_chunk_segments = rinfo["n_chunks"]
            record.kda_chunk_tokens = rinfo["chunk_tokens"]
            return
        # the scan's work: every prefill chunk (standalone or in the
        # ragged step) and the ragged step's decode rows, segments of
        # one token. The decode loop's steps run the one-token update.
        rows = rinfo["decode_seqs"] if rinfo["ragged"] else 0
        record.ssm_scan_segments = rinfo["n_chunks"] + rows
        record.ssm_scan_tokens = rinfo["chunk_tokens"] + rows

    def gauges(self) -> Iterator[Tuple[str, str, float]]:
        yield ("state_slots_used",
               "sequences that hold a state slot (scratch left out)",
               self.used)
        yield ("state_slots_total",
               "state slots a sequence can be given (scratch left out)",
               max(0, self.units - 1))


class WindowTable(list):
    """A sequence's second page table, into the window pool, indexed by the
    same logical page as `Sequence.pages`; 0 (scratch) where the page is
    freed or not yet needed. `lo`: every entry below this logical page is
    freed."""

    lo = 0


class WindowPages(SideCache):
    """The window layers' pages (a model whose window and global layers keep
    caches of their own): a PagePool over the runner's window pool, page 0
    scratch. A page is given back once it lies wholly below what any later
    query can see."""

    kind = "window"
    no_prefix = (
        "a model with a window pool matches no prefix: a cached block's window "
        "layers kept the last sliding_window tokens alone and their older pages "
        "are freed, which nothing snapshots, so its scheduler runs without the "
        "prefix cache and without a host tier (and publishes no stored blocks)")

    def __init__(self, units: int, page_size: int, window: int):
        self.units = int(units)
        self.window = int(window)
        self.pool = PagePool(self.units, page_size)
        self.pool.alloc(1)  # page 0: scratch, never handed out
        self.freed = 0  # pages given back as they left the window

    @classmethod
    def need(cls, runner: Runner, *, max_batch: int, chunk_size: int,
             decode_steps: int, mixed_prefill_tokens: int,
             mixed_prefill_seqs: int) -> int:
        # sized from what the scheduler can have in flight at once: every
        # active sequence the pages its fused decode steps see and write,
        # and on top the chunks of one iteration (one standalone chunk of
        # chunk_size, or mixed_prefill_seqs chunks of mixed_prefill_tokens
        # together), and scratch page 0
        from dynamo_tpu.models.mimo import window_pages_needed

        ps, w = runner.page_size, runner.config.sliding_window
        a_row = window_pages_needed(w, ps, max(1, decode_steps))
        mixed = (mixed_prefill_seqs * window_pages_needed(w, ps, 1)
                 + -(-mixed_prefill_tokens // ps) + mixed_prefill_seqs)
        return (1 + max_batch * a_row
                + max(window_pages_needed(w, ps, chunk_size), mixed))

    @classmethod
    def build(cls, runner: Runner, units: int):
        return cls(units, runner.page_size, runner.config.sliding_window)

    def check_limits(self, page_size: int, max_batch: int) -> None:
        if self.window <= 0 or self.pool.page_size != page_size:
            raise ValueError(
                "a window pool needs the model's sliding window and the "
                "KV pool's page size")

    def admit(self, seq, first_tokens: int) -> None:
        # the window pages of the least first chunk it can be given (the
        # rest come with each chunk): without them it waits, whatever the
        # KV pool has
        table = WindowTable()
        self._cover(table, 0, first_tokens - 1)
        seq.side = table

    def cover(self, seq, first_query: int, last_query: int) -> None:
        self._cover(seq.side, first_query, last_query)

    def _cover(self, table: WindowTable, first_query: int,
               last_query: int) -> None:
        """Give `table` the window pages that queries at first_query ..
        last_query read and write, and take back the ones wholly below
        what any query from first_query on can see (`live_pages`' rule:
        below page (first_query - window + 1) // PS): their entries point
        at scratch page 0, which no kernel's walk visits. Raises NoSpace
        with nothing taken."""
        # (the model's to state; imported here so that importing this
        # module pulls in no jax)
        from dynamo_tpu.models.mimo import window_first_live_page

        lo = window_first_live_page(first_query, self.window,
                                    self.pool.page_size)
        hi = last_query // self.pool.page_size
        if len(table) <= hi:
            table.extend([0] * (hi + 1 - len(table)))
        dead = [p for p in table[table.lo:lo] if p]
        missing = [j for j in range(max(lo, table.lo), hi + 1)
                   if not table[j]]
        # what leaves the window comes back first: a row at its steady
        # state gives one page and takes one
        if self.pool.n_free + len(dead) < len(missing):
            raise NoSpace(f"need {len(missing)} window pages")
        if dead:
            self.pool.release(dead)
            self.freed += len(dead)
            for j in range(table.lo, lo):
                table[j] = 0
        table.lo = max(table.lo, lo)
        for j, page in zip(missing, self.pool.alloc(len(missing))):
            table[j] = page

    def release(self, seq) -> None:
        if seq.side:
            self.pool.release([p for p in seq.side if p])
        seq.side = None

    def tokens_resident(self, active: Iterable[Any]) -> int:
        """Tokens of context the window pool holds for the active sequences
        (a page in use counts up to the sequence's computed length)."""
        PS = self.pool.page_size
        return sum(
            min((j + 1) * PS, s.computed_len) - j * PS
            for s in active if s.side
            for j in range(s.side.lo, len(s.side))  # (below lo: all freed)
            if s.side[j] and j * PS < s.computed_len)

    def record(self, record, rinfo: dict, active: List[Any]) -> None:
        record.window_pages_total = self.pool.num_pages - 1
        record.window_pages_used = self.pool.num_pages - 1 - self.pool.n_free
        record.window_tokens_resident = self.tokens_resident(active)
        record.context_tokens_live = sum(s.computed_len for s in active)
        kinds = rinfo.get("pages_live_kinds")
        if kinds:
            step0 = rinfo["pages_step0_kinds"] if rinfo["ragged"] else (0, 0)
            record.decode_pages_live_global = kinds[0] - step0[0]
            record.decode_pages_live_window = kinds[1] - step0[1]

    def gauges(self) -> Iterator[Tuple[str, str, float]]:
        yield ("window_pages_used",
               "window-pool pages in use (scratch left out)",
               self.pool.num_pages - 1 - self.pool.n_free)
        yield ("window_pages_total",
               "window-pool pages a sequence can be given (scratch left out)",
               self.pool.num_pages - 1)
        yield ("window_pages_freed_total",
               "window pages given back as they left the window",
               self.freed)
        yield ("window_admission_waits_total",
               "admissions and chunks put off for want of a window page "
               "where the global pool had room",
               self.waits)


class _Part:
    """A sequence as ONE part of a composed cache sees it: `side` is the
    part's entry of the list the sequence holds; whatever else a part reads
    of a sequence (`computed_len`) is the sequence's."""

    __slots__ = ("seq", "i")

    def __init__(self, seq, i: int):
        self.seq, self.i = seq, i

    @property
    def side(self):
        return None if self.seq.side is None else self.seq.side[self.i]

    @side.setter
    def side(self, held) -> None:
        if self.seq.side is not None:
            self.seq.side[self.i] = held

    def __getattr__(self, name):
        return getattr(self.seq, name)


class Composed(SideCache):
    """Several kinds at once (models/sambay.py: a state slot AND window
    pages): a sequence holds the list of what it holds in each part, in the
    parts' order, and a step's operand for its row is that tuple. `admit`
    gives back what the earlier parts gave when a later one has none;
    at most one part's `cover` takes anything, so a `cover` that raises has
    taken nothing."""

    def __init__(self, parts: List[SideCache]):
        if sum(type(p).cover is not SideCache.cover for p in parts) > 1:
            raise ValueError(
                "a composed side cache can undo no `cover`: at most one of "
                "its parts may take units chunk by chunk")
        self.parts = list(parts)
        self.kind = "+".join(p.kind for p in parts)
        self.units = tuple(p.units for p in parts)
        self.no_prefix = " ".join(p.no_prefix for p in parts)
        self._waits = 0

    @classmethod
    def for_kinds(cls, kinds, runner: Runner, **limits) -> "Composed":
        units = runner.ensure_side_cache(
            tuple(k.need(runner, **limits) for k in kinds))
        return cls([k.build(runner, u) for k, u in zip(kinds, units)])

    @property
    def waits(self) -> int:
        return self._waits

    @waits.setter
    def waits(self, n: int) -> None:  # (every part's gauge says the whole's)
        self._waits = n
        for p in self.parts:
            p.waits = n

    def _each(self, seq):
        return ((p, _Part(seq, i)) for i, p in enumerate(self.parts))

    def check_limits(self, page_size: int, max_batch: int) -> None:
        for p in self.parts:
            p.check_limits(page_size, max_batch)

    def admit(self, seq, first_tokens: int) -> None:
        seq.side = [None] * len(self.parts)
        taken = []
        try:
            for p, view in self._each(seq):
                p.admit(view, first_tokens)
                taken.append((p, view))
        except Exception:  # (NoSpace; a free list that ran dry)
            for p, view in taken:
                p.release(view)
            seq.side = None
            raise

    def cover(self, seq, first_query: int, last_query: int) -> None:
        for p, view in self._each(seq):
            p.cover(view, first_query, last_query)

    def release(self, seq) -> None:
        for p, view in self._each(seq):
            p.release(view)
        seq.side = None

    def operand(self, seq) -> Any:
        return None if seq.side is None else tuple(seq.side)

    def record(self, record, rinfo: dict, active: List[Any]) -> None:
        for i, p in enumerate(self.parts):
            p.record(record, rinfo, [_Part(s, i) for s in active])

    def gauges(self) -> Iterator[Tuple[str, str, float]]:
        for p in self.parts:
            yield from p.gauges()


KINDS = {cls.kind: cls for cls in (StateSlots, WindowPages)}


def for_runner(runner: Runner, **limits) -> Optional[SideCache]:
    """The side cache of `runner`'s kind (or kinds), its pool sized on the runner for a
    scheduler of these limits (max_batch, chunk_size, decode_steps,
    mixed_prefill_tokens, mixed_prefill_seqs); None where a sequence keeps
    nothing beside its pages."""
    if runner.side_kind is None:
        return None
    kinds = [KINDS[k] for k in runner.side_kind.split("+")]
    if len(kinds) == 1:
        return kinds[0].for_runner(runner, **limits)
    return Composed.for_kinds(kinds, runner, **limits)
