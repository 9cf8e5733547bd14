"""The names benchmark/serve.py's warm-up holds fixed in the program.

`warm_lattice` walks the compile lattice through the runner's and the
scheduler's names (ROADMAP D10 lists them), and only a `benchmark` PR may
edit it; nothing else under tests/ imports it. A program PR that renames
one of those names, or adds a step the walk does not meet, would fail on
the chip as a witness. Here it fails in tier-1: the walk runs on a tiny
CPU engine, then a scripted sequence of plans (every kind traffic forms)
must find every program already compiled.
"""

import importlib.util
import os
import threading
import time

import pytest

from dynamo_tpu import worker
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import DecodePlan, MixedPlan, PrefillPlan, Sequence
from dynamo_tpu.models.config import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _serve_module():
    spec = importlib.util.spec_from_file_location(
        "_bench_serve_pins", os.path.join(ROOT, "benchmark", "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _seq(rid, prompt, max_tokens):
    return Sequence(
        request_id=rid, prompt=list(prompt),
        sampling={"temperature": 0.0},
        stop={"max_tokens": max_tokens, "stop_ids": []},
        arrival=time.monotonic(),
    )


# tiny-jamba: a model with state-space layers, whose steps also take state
# slots: the walk passes none (positionally, dummies), so slots must be data
# under a trailing keyword and never a shape. tiny-mimo: a model with a window
# pool, whose steps take the rows' window page tables under the same keyword
# (the walk's default: every entry scratch)
@pytest.mark.parametrize("preset", ["tiny", "tiny-jamba", "tiny-mimo"])
def test_warm_lattice_meets_every_program_traffic_reaches(monkeypatch, preset):
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    args = worker.parse_args([
        "--model", preset, "--max-batch", "4", "--chunk-size", "16",
        "--mixed-prefill-tokens", "12", "--mixed-prefill-seqs", "2",
        "--mixed-min-chunk", "4",
    ])
    runner = ModelRunner(
        get_config(preset), num_pages=96, page_size=4, max_pages_per_seq=16,
        decode_buckets=(2, 4), prefill_buckets=(8, 16),
        ragged_buckets=(8, 16), seed=7,
    )
    engine, _ = worker.build_engine(args, runner=runner)
    engine.scheduler.decode_steps = 2  # the engine's own default is 4
    assert engine.fused_mixed and runner.ragged_mixed

    warm = _serve_module().warm_lattice(engine)
    assert warm["compile"]["ragged"]["variants"] == 2, warm
    assert warm["compile"]["mixed"]["calls"] == 0, warm

    # every kind of plan, in the order a script can force: a prompt longer
    # than a chunk (prefill without, then with prior context), decode
    # alone, a late arrival (one chunk + decode at decode_steps steps),
    # another beside a row one token from its limit (one chunk + decode at
    # 1 step), then two at once (two chunks + decode)
    arrivals = {
        0: [_seq("a", range(1, 21), 64)],
        4: [_seq("b", range(3, 13), 2)],
        5: [_seq("c", range(5, 11), 64)],
        6: [_seq("d", range(2, 8), 64), _seq("e", range(4, 9), 64)],
    }
    kinds = []
    step_plan = engine.scheduler.step_plan

    def recording():
        plan = step_plan()
        if isinstance(plan, PrefillPlan):
            kinds.append(("prefill", plan.start_pos > 0))
        elif isinstance(plan, MixedPlan):
            kinds.append(("mixed", len(plan.prefills), plan.decode.n_steps))
        elif isinstance(plan, DecodePlan):
            kinds.append(("decode", plan.n_steps))
        return plan

    engine.scheduler.step_plan = recording
    out = {}

    def drive():
        # as InferenceEngine._loop starts: compiles on this thread that no
        # family sees count in this runner's `other`
        runner.name_step_thread()
        out["before"] = runner.compile_stats()
        for it in range(10):
            for seq in arrivals.get(it, ()):
                engine._inbox.put(("add", seq))
            engine._loop_once()
        out["after"] = runner.compile_stats()

    t = threading.Thread(target=drive)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()

    assert ("prefill", False) in kinds and ("prefill", True) in kinds, kinds
    assert ("decode", 2) in kinds, kinds
    assert ("mixed", 1, 2) in kinds and ("mixed", 1, 1) in kinds, kinds
    assert any(k[0] == "mixed" and k[1] == 2 for k in kinds), kinds
    before, after = out["before"], out["after"]
    assert after["ragged"]["calls"] > before["ragged"]["calls"]
    for fam in after:
        assert after[fam]["variants"] == before[fam]["variants"], (
            fam, before[fam], after[fam], kinds)
    assert after["other"] == before["other"]


def test_warm_lattice_of_a_model_without_a_fused_mixed_program(monkeypatch):
    """tiny-dsa (latent attention over an indexer's selection): the runner
    says it has no one-dispatch program for a mixed plan, so with mixed-prefill
    tokens stated, and fusing asked for, the walk compiles decode and prefill
    buckets alone, and every plan the scheduler forms (chunks co-scheduled with
    decoding rows as two dispatches, contexts on both sides of index_topk)
    finds its programs compiled."""
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    args = worker.parse_args([
        "--model", "tiny-dsa", "--max-batch", "4", "--chunk-size", "16",
        "--mixed-prefill-tokens", "12", "--mixed-prefill-seqs", "1",
        "--mixed-min-chunk", "4",
    ])
    runner = ModelRunner(
        get_config("tiny-dsa"), num_pages=96, page_size=4, max_pages_per_seq=16,
        decode_buckets=(2, 4), prefill_buckets=(8, 16), seed=7)
    engine, _ = worker.build_engine(args, runner=runner)
    engine.scheduler.decode_steps = 2
    assert not runner.fuses_mixed and not engine.fused_mixed

    warm = _serve_module().warm_lattice(engine)
    assert warm["compile"]["mixed"]["variants"] == 0, warm
    assert warm["compile"]["ragged"]["variants"] == 0, warm
    assert warm["compile"]["decode_loop"]["variants"] == 4, warm  # 2 buckets x 2 steps

    arrivals = {
        0: [_seq("a", range(1, 21), 64)],  # 20 tokens: past index_topk 8
        4: [_seq("b", range(3, 8), 2)],    # 5 tokens: at most index_topk
        5: [_seq("c", range(5, 19), 64)],
        6: [_seq("d", range(2, 8), 64), _seq("e", range(4, 9), 64)],
    }
    kinds = []
    step_plan = engine.scheduler.step_plan

    def recording():
        plan = step_plan()
        if isinstance(plan, PrefillPlan):
            kinds.append(("prefill", plan.start_pos > 0))
        elif isinstance(plan, MixedPlan):
            kinds.append(("mixed", len(plan.prefills), plan.decode.n_steps))
        elif isinstance(plan, DecodePlan):
            kinds.append(("decode", plan.n_steps))
        return plan

    engine.scheduler.step_plan = recording
    out = {}

    def drive():
        runner.name_step_thread()
        out["before"] = runner.compile_stats()
        for it in range(12):
            for seq in arrivals.get(it, ()):
                engine._inbox.put(("add", seq))
            engine._loop_once()
        out["after"] = runner.compile_stats()

    t = threading.Thread(target=drive)
    t.start()
    t.join(timeout=180)
    assert not t.is_alive()

    assert ("prefill", False) in kinds and ("prefill", True) in kinds, kinds
    assert ("decode", 2) in kinds and any(k[0] == "mixed" for k in kinds), kinds
    before, after = out["before"], out["after"]
    assert after["mixed"]["calls"] == after["ragged"]["calls"] == 0
    assert after["forward"]["calls"] > before["forward"]["calls"]
    for fam in after:
        assert after[fam]["variants"] == before[fam]["variants"], (
            fam, before[fam], after[fam], kinds)
    assert after["other"] == before["other"]


def test_the_doors_phases_are_the_spans_the_reduction_owns_gaps_by():
    """The step thread's phases (runtime/annotations.py, the door) open the
    spans `benchmark/layers/_idle.CHILDREN` names, and the iteration record
    carries the same names: a phase renamed, added or dropped on one side
    would make the idle shares and the host clock read different things."""
    import dataclasses

    from dynamo_tpu.runtime import annotations
    from dynamo_tpu.runtime.flight_recorder import IterationRecord

    spec = importlib.util.spec_from_file_location(
        "_bench_idle_pins", os.path.join(ROOT, "benchmark", "layers", "_idle.py"))
    idle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(idle)
    # (engine.deliver, PR 55, is no reader's owner: idle_gaps names it
    # should it ever own a gap, and no idle share counts it)
    assert sorted(set(annotations.SPAN_NAMES) - {"engine.deliver"}) == sorted(
        idle.CHILDREN)
    assert annotations.SPAN_NAMES[annotations.WAIT] == "engine.wait"
    fields = {f.name for f in dataclasses.fields(IterationRecord)}
    assert {f"host_{p}_s" for p in annotations.RECORD_PHASES} == {
        f for f in fields if f.startswith("host_")}
    assert {"exposed_s", "exposed_stage_s", "exposed_emit_s", "gc_s"} <= fields
