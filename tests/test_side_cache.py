"""The side cache's seam (engine/side_cache.py): a kind the scheduler, the
engine and the door have never heard of is served by them unedited; every
refusal is its kind's sentence; a runner that says nothing has none."""

import subprocess
import sys

import pytest

from dynamo_tpu.engine import side_cache
from dynamo_tpu.engine.kv_pool import NoSpace, PagePool
from dynamo_tpu.engine.runner_api import DEVICE_STEPS, Runner, refusal
from dynamo_tpu.engine.scheduler import (
    DecodePlan, MixedPlan, PrefillPlan, Scheduler, SeqState, Sequence,
)
from dynamo_tpu.engine.side_cache import Composed, SideCache, StateSlots, WindowPages

PS = 4


class Counter(SideCache):
    """A toy third kind: interchangeable units, one for every PS tokens a
    sequence's queries have reached; nothing comes back before release.
    `Sequence.side` is the count the sequence holds."""

    kind = "toy"
    no_prefix = "a toy matches no prefix"

    def __init__(self, units: int):
        self.units = units
        self.free = units - 1  # (unit 0 is scratch, as everywhere)
        self.released = []

    def _take(self, held: int, last_query: int) -> int:
        need = last_query // PS + 1 - held
        if need > self.free:
            raise NoSpace(f"need {need} toy units")
        self.free -= max(0, need)
        return held + max(0, need)

    def admit(self, seq, first_tokens):
        seq.side = self._take(0, first_tokens - 1)

    def cover(self, seq, first_query, last_query):
        seq.side = self._take(seq.side, last_query)

    def release(self, seq):
        if seq.side:
            self.free += seq.side
            self.released.append(seq.request_id)
        seq.side = None


def _sched(units, **kw):
    kw = {"max_batch": 3, "chunk_size": 8, "decode_steps": 2,
          "mixed_prefill_tokens": 8, "mixed_prefill_seqs": 2,
          "mixed_min_chunk": 4, "enable_prefix_cache": False, **kw}
    side = Counter(units)
    return Scheduler(PagePool(64, PS), side=side, **kw), side


def _seq(rid, n, max_tokens=30):
    return Sequence(request_id=rid, prompt=list(range(1, n + 1)), sampling={},
                    stop={"max_tokens": max_tokens})


def _prefill(sched, seq):
    """Run `seq` alone through its chunks and its first sampled token."""
    while seq.state != SeqState.RUNNING:
        plan = sched.step_plan()
        assert isinstance(plan, PrefillPlan) and plan.seq is seq
        sched.complete_prefill(plan)
    sched.complete_decode(seq, 7, advance_computed=False)


def test_a_kind_nobody_has_heard_of_is_admitted_and_covered_chunk_by_chunk():
    sched, side = _sched(20)
    a = _seq("a", 20)
    sched.add(a)
    held = []
    while a.state != SeqState.RUNNING:
        plan = sched.step_plan()
        held.append(a.side)
        sched.complete_prefill(plan)
    # admission took the least first chunk's unit, each chunk what it reaches
    assert held == [2, 4, 5] and side.free == 19 - 5
    sched.complete_decode(a, 7, advance_computed=False)
    plan = sched.step_plan()
    assert isinstance(plan, DecodePlan) and plan.n_steps == 2
    assert a.side == 6  # positions 20 and 21 open the sixth
    assert side.operand(a) == 6 and side.waits == 0 and side.released == []


def test_an_admission_the_side_cache_refuses_leaves_the_sequence_waiting():
    sched, side = _sched(2)  # one unit beside scratch
    a, b = _seq("a", 4), _seq("b", 4)
    sched.add(a), sched.add(b)
    plan = sched.step_plan()
    assert plan.seq is a and a.side == 1
    assert b.state == SeqState.WAITING and b.side is None and not b.pages
    assert side.waits == 1 and sched.pool.n_free == 64 - 2  # b's pages went back
    sched.abort("a")
    assert sched.step_plan().seq is b and b.side == 1


def test_a_mixed_plans_chunk_that_finds_no_unit_is_left_out_alone():
    sched, side = _sched(6, decode_steps=1)
    a, b, c = _seq("a", 4), _seq("b", 12), _seq("c", 12)
    sched.add(a)
    _prefill(sched, a)
    sched.add(b), sched.add(c)
    plan = sched.step_plan()  # both admitted, a chunk of 4 each beside a's row
    assert isinstance(plan, MixedPlan) and [p.seq for p in plan.prefills] == [b, c]
    assert (a.side, b.side, c.side) == (2, 1, 1) and side.free == 1
    for p in plan.prefills:
        sched.complete_prefill(p)
    sched.complete_decode(a, 7)
    plan = sched.step_plan()  # each next chunk opens a unit; one is left
    assert isinstance(plan, MixedPlan) and [p.seq for p in plan.prefills] == [b]
    assert plan.decode.seqs == [a] and (b.side, c.side) == (2, 1)
    assert side.waits == 1 and c.computed_len == 4 and side.released == []
    sched.complete_prefill(plan.prefills[0])
    sched.complete_decode(a, 7)
    plan = sched.step_plan()  # none is left: the rows decode alone
    assert isinstance(plan, DecodePlan) and side.waits == 3


def test_a_lone_chunk_that_finds_no_unit_says_what_is_too_few():
    sched, side = _sched(2)
    sched.add(_seq("a", 8))
    with pytest.raises(RuntimeError, match="no unit of the toy cache for a "
                       "prefill chunk of 8 tokens.*2 units are too few"):
        sched.step_plan()


@pytest.mark.parametrize("how", ["preempt", "finish", "abort", "park"])
def test_every_way_out_gives_the_units_back_exactly_once(how):
    sched, side = _sched(20)
    a = _seq("a", 6, max_tokens=2)
    sched.add(a)
    _prefill(sched, a)
    sched.step_plan()
    assert a.side == 2 and side.free == 17
    if how == "preempt":
        sched._preempt(a)
        assert a.state == SeqState.WAITING
    elif how == "finish":
        assert sched.complete_decode(a, 7) == "length"
    elif how == "abort":
        sched.abort("a")
        sched.abort("a")
    else:
        sched.park(a)
    assert side.released == ["a"] and a.side is None and side.free == 19
    if how == "preempt":  # and it is admitted again from nothing
        sched.step_plan()
        assert a.side == 2 and side.released == ["a"]


def test_a_side_cache_rules_out_the_prefix_cache_in_its_own_words():
    with pytest.raises(ValueError, match="a toy matches no prefix"):
        Scheduler(PagePool(8, PS), side=Counter(4), enable_prefix_cache=True)
    with pytest.raises(ValueError, match="a toy matches no prefix"):
        Scheduler(PagePool(8, PS), side=Counter(4), enable_prefix_cache=False,
                  host_tier=object())
    sched, _ = _sched(4)
    b = _seq("b", 4)
    with pytest.raises(ValueError, match="transferred KV"):
        sched.admit_with_kv(b)
    assert sched.adopt_branch(_seq("c", 4), b, []) is False


@pytest.mark.parametrize("kind, words, cache", [
    ("state", "a model with state-space layers", lambda: StateSlots(3)),
    ("window", "a model with a window pool", lambda: WindowPages(3, PS, 8)),
    ("indexer", "a model with an indexer", None),
])
def test_every_refusal_is_its_kinds_sentence_and_names_model_and_feature(
        kind, words, cache):
    said = refusal(kind, "some-model", "Some feature")
    assert said.startswith(f"Some feature is not built for {words} (some-model): ")
    others = [refusal(k, "some-model", "Some feature")
              for k in ("state", "window", "indexer") if k != kind]
    assert said not in others
    if cache is None:  # rides the page table: no side cache of that kind
        assert kind not in side_cache.KINDS
        return
    side = cache()
    assert side_cache.KINDS[kind] is type(side) and side.kind == kind
    assert side.refusal("some-model", "Some feature") == said
    assert side.no_prefix.startswith(f"{words} matches no prefix: ")


@pytest.mark.parametrize("is_kda", [False, True])
def test_state_slots_serve_two_families_and_record_each_ones_kernels(is_kda):
    """StateSlots under a Mamba model and under a model with KDA layers
    (models/ling.py): the same slots, sized the same way, the same sentence
    at the door; an iteration records the family's own counters of what its
    kernels were given, and the other family's stay 0."""
    import dataclasses
    import types

    from dynamo_tpu.runtime.flight_recorder import IterationRecord

    class R(Runner):
        side_kind = "state"
        config = types.SimpleNamespace(is_kda=is_kda)

        def ensure_side_cache(self, units):
            self.side_units = units
            return units

    r = R()
    side = side_cache.for_runner(r, max_batch=4, chunk_size=8, decode_steps=2,
                                 mixed_prefill_tokens=8, mixed_prefill_seqs=1)
    assert type(side) is StateSlots and side.units == r.side_units == 5
    assert side.kda is is_kda
    assert side.refusal("m", "X") == refusal("state", "m", "X")
    names = {f.name for f in dataclasses.fields(IterationRecord)}
    kda = ("kda_update_rows", "kda_chunk_tokens", "kda_chunk_segments")
    ssm = ("ssm_scan_tokens", "ssm_scan_segments")
    assert set(kda + ssm) <= names
    rec = types.SimpleNamespace(**{n: 0 for n in kda + ssm})
    side.record(rec, {"decode_seqs": 3, "decode_steps": 2, "n_chunks": 1,
                      "chunk_tokens": 9, "ragged": False}, [])
    assert (rec.state_slots_used, rec.state_slots_total) == (0, 4)
    assert tuple(getattr(rec, n) for n in kda) == ((6, 9, 1) if is_kda else (0, 0, 0))
    assert tuple(getattr(rec, n) for n in ssm) == ((0, 0) if is_kda else (9, 1))
    # a runner with no ModelConfig at all (a cost model's) serves Mamba's
    R.config = None
    assert side_cache.for_runner(R(), max_batch=2).kda is False


def _both(slots=3, pages=4):
    return Composed([StateSlots(slots), WindowPages(pages, PS, 8)])


@pytest.mark.parametrize("short", ["state", "window"])
def test_a_composed_cache_admits_all_of_it_or_nothing(short):
    """A sequence of a model with two kinds takes a unit of each or waits: a
    NoSpace from the second part gives the first's back, and one from the
    first never reaches the second."""
    side = _both()
    slots, window = side.parts
    a, b = _seq("a", 8), _seq("b", 8)
    if short == "state":
        side.admit(a, 4), side.admit(_seq("x", 4), 4)  # both slots gone
    else:
        side.admit(a, 8)  # two of the three window pages gone
    free = (len(slots._free), window.pool.n_free)
    with pytest.raises((NoSpace, IndexError)):
        side.admit(b, 8)
    assert b.side is None and side.operand(b) is None
    assert (len(slots._free), window.pool.n_free) == free
    side.release(b)  # (a sequence that holds nothing gives nothing back)
    assert (len(slots._free), window.pool.n_free) == free


def test_a_composed_cache_is_each_part_under_the_names_it_has():
    import types

    side = _both(pages=6)
    slots, window = side.parts
    assert side.kind == "state+window" and side.units == (3, 6)
    side.check_limits(PS, 2)
    with pytest.raises(ValueError, match="3 state slots"):
        side.check_limits(PS, 3)
    with pytest.raises(ValueError, match="undo no `cover`"):
        Composed([WindowPages(4, PS, 8), WindowPages(4, PS, 8)])
    a = _seq("a", 12)
    side.admit(a, 4)
    slot, table = a.side
    assert slot == 1 and [p for p in table if p] == [1] and side.operand(a) == (1, table)
    side.cover(a, 4, 11)  # the chunk by chunk part alone takes more
    assert a.side[0] == 1 and len([p for p in a.side[1] if p]) == 3
    with pytest.raises(NoSpace):  # four more pages where two are free and
        side.cover(a, 12, 27)     # one would come back: nothing moves
    assert len([p for p in a.side[1] if p]) == 3 and window.pool.n_free == 2
    a.computed_len = 12
    rec = types.SimpleNamespace()
    side.record(rec, {"decode_seqs": 2, "decode_steps": 3, "n_chunks": 2,
                      "chunk_tokens": 20, "ragged": False}, [a])
    assert (rec.state_slots_used, rec.state_slots_total) == (1, 2)
    assert (rec.window_pages_used, rec.window_pages_total) == (3, 5)
    assert rec.window_tokens_resident == rec.context_tokens_live == 12
    assert (rec.ssm_scan_tokens, rec.ssm_scan_segments) == (20, 2)
    # (what the forward ran on is the runner's to say, not a pool's)
    assert not hasattr(rec, "yoco_cross_rows")
    side.waits += 1
    gauges = {name: value for name, _, value in side.gauges()}
    assert gauges["state_slots_used"] == 1 and gauges["window_pages_used"] == 3
    assert gauges["window_admission_waits_total"] == side.waits == 1
    side.release(a)
    assert a.side is None and slots.used == 0 and window.pool.n_free == 5
    # the door's sentences are both kinds', and the scheduler's too
    said = side.refusal("m", "X")
    assert said == refusal("state+window", "m", "X") == " ".join(
        refusal(k, "m", "X") for k in ("state", "window"))
    assert StateSlots.no_prefix in side.no_prefix and WindowPages.no_prefix in side.no_prefix
    with pytest.raises(ValueError, match="state-space layers matches no prefix"):
        Scheduler(PagePool(8, PS), side=_both(), max_batch=2, enable_prefix_cache=True)


def test_a_runner_of_two_kinds_gets_the_composed_cache_sized_a_kind():
    import types

    class R(Runner):
        side_kind = "state+window"
        page_size = PS
        config = types.SimpleNamespace(sliding_window=8, is_kda=False)

        def ensure_side_cache(self, units):
            self.side_units = tuple(u + 1 for u in units)  # (it may hold more)
            return self.side_units

    r = R()
    side = side_cache.for_runner(r, max_batch=4, chunk_size=8, decode_steps=2,
                                 mixed_prefill_tokens=8, mixed_prefill_seqs=1)
    assert type(side) is Composed
    assert [type(p) for p in side.parts] == [StateSlots, WindowPages]
    assert side.units == r.side_units and side.units[0] == 4 + 1 + 1
    assert side.parts[1].window == 8 and side.parts[1].pool.page_size == PS
    sched = Scheduler(PagePool(64, PS), side=side, max_batch=4, chunk_size=8,
                      decode_steps=2, mixed_prefill_tokens=8,
                      mixed_prefill_seqs=1, enable_prefix_cache=False)
    a = _seq("a", 12)
    sched.add(a)
    _prefill(sched, a)
    assert a.side[0] == 1 and any(a.side[1])
    sched.abort("a")
    assert a.side is None and side.parts[0].used == 0


def test_a_runner_that_says_nothing_has_no_side_cache():
    r = Runner()
    assert (r.side_kind, r.side_units, r.side_unit_bytes) == (None, 0, 0)
    assert r.ensure_side_cache(5) == 0
    assert "ensure_side_cache" in DEVICE_STEPS
    assert side_cache.for_runner(r, max_batch=4) is None
    # the door has four names for it and the steps one keyword
    assert sorted(n for n in vars(Runner) if "side" in n) == [
        "ensure_side_cache", "side_kind", "side_unit_bytes", "side_units"]


def test_importing_the_side_cache_pulls_in_no_jax():
    code = ("import sys; import dynamo_tpu.engine.side_cache, "
            "dynamo_tpu.engine.scheduler; "
            "assert 'jax' not in sys.modules, 'jax came with it'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
