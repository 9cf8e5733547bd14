"""The engine iteration on the profiler's clock (CPU, tiny model): host
spans that tile an iteration, stable names on the step programs, the
compile counter that sees every program, and the phase spine's prefill
keys. What each is for is in PERF.md section 3."""

import asyncio
import contextlib
import re
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.runtime import annotations

PARENTS = {"engine.decode", "engine.mixed", "engine.prefill",
           "engine.prefill_packed", "engine.spec_verify"}
TABLE_A = PARENTS | {
    "engine.wait", "engine.inbox", "engine.schedule", "engine.prep",
    "engine.stage", "engine.dispatch", "engine.readback", "engine.emit",
    "engine.publish", "engine.deliver"}
# trace_reduce.owner() looks back over this many spans started before a gap
LOOKBACK = 8


def _runner(**kw):
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config

    args = dict(num_pages=96, page_size=4, max_pages_per_seq=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(8, 16), seed=7)
    args.update(kw)
    return ModelRunner(get_config("tiny"), **args)


async def _serve(engine, prompts, max_tokens=6):
    """Serve `prompts` at once; the final item of each (it has the spine)."""
    from dynamo_tpu.runtime.context import Context

    async def one(p):
        last = None
        async for item in engine.generate(
                {"token_ids": p, "sampling": {"temperature": 0.0},
                 "stop": {"max_tokens": max_tokens, "stop_ids": []}}, Context()):
            assert item.get("finish_reason") != "error", item
            if item.get("finish_reason"):
                last = item
        return last

    return await asyncio.gather(*[one(p) for p in prompts])


class _Recorder:
    """Stands in for TraceAnnotation, which annotate() and the door
    (annotations.phase) both open with the gate on: begin/end events of
    the step thread."""

    def __init__(self):
        self.events = []
        self.step_thread = None

    def __call__(self, name, **kw):
        @contextlib.contextmanager
        def span():
            if name == "engine.inbox":
                self.step_thread = threading.get_ident()
            mine = threading.get_ident() == self.step_thread
            if mine:
                self.events.append(("B", name))
            try:
                yield
            finally:
                if mine:
                    self.events.append(("E", name))
        return span()

    def iterations(self):
        """[[(name, [child names in order])]]: the top-level spans of each
        whole iteration, cut at engine.inbox."""
        out, cur, stack = [], None, []
        for ev, name in self.events:
            if ev == "B":
                if not stack:
                    if name == "engine.inbox":
                        cur = []
                        out.append(cur)
                    if cur is not None:
                        cur.append((name, []))
                elif cur is not None:
                    assert len(stack) <= 2, ("nested deeper than a child", stack, name)
                    cur[-1][1].append(name)
                stack.append(name)
            else:
                assert stack.pop() == name
        return [it for it in out if it]


@pytest.fixture(scope="module")
def recorded():
    """One engine run under the recorder: a long decode alone, then two
    prompts arriving against it (fused mixed steps), then idle."""
    import os

    from dynamo_tpu.engine.engine import InferenceEngine

    rec = _Recorder()
    saved = (annotations._trace_annotation, os.environ.get("DYN_FUSED_MIXED"),
             os.environ.get("DYN_ENABLE_JAX_TRACE"))
    annotations._trace_annotation = rec
    os.environ["DYN_FUSED_MIXED"] = "1"
    os.environ["DYN_ENABLE_JAX_TRACE"] = "1"
    annotations._enabled.cache_clear()
    try:
        engine = InferenceEngine(_runner(), max_batch=4, chunk_size=8,
                                 mixed_prefill_tokens=8)
        engine.start()
        try:
            async def drive():
                lead = asyncio.ensure_future(
                    _serve(engine, [[4, 2, 4, 2, 7, 5]], max_tokens=24))
                await asyncio.sleep(0.5)
                late = await _serve(engine, [[9, 8, 7, 1], list(range(1, 10))])
                out = (await lead) + late
                await asyncio.sleep(0.1)  # a few idle iterations
                return out

            finals = asyncio.run(drive())
        finally:
            engine.stop()
    finally:
        annotations._trace_annotation = saved[0]
        for key, was in zip(("DYN_FUSED_MIXED", "DYN_ENABLE_JAX_TRACE"),
                            saved[1:]):
            if was is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = was
        annotations._enabled.cache_clear()
    return rec, finals


STEP = ["engine.stage", "engine.dispatch"]
# a decode iteration has two halves, each under a step parent of its own:
# the enqueue (prep, stage, dispatch) and the commit (readback, emit), which
# the loop runs AFTER the next iteration's enqueue where it ran ahead
ENQUEUE = ["engine.prep"] + STEP
COMMIT = ["engine.readback", "engine.emit"]
# what the commit before left for the clients and the observers goes out
# right after the program is enqueued and before anything blocks on it
# (engine._deliver); no span where nothing was left
DELIVER = ["engine.deliver"]
WANT = {
    # parent -> the children it may show
    "engine.decode": [ENQUEUE, COMMIT],
    "engine.mixed": [
        ["engine.prep"] + steps + d + ["engine.readback", "engine.emit"]
        for steps in (STEP, STEP + STEP) for d in ([], DELIVER)],
    # (a prompt's first token goes out where it is committed: after emit)
    "engine.prefill": [["engine.prep"] + STEP + d + ["engine.emit"] + f
                       for d in ([], DELIVER) for f in ([], DELIVER)],
}
LETTER = {"engine.inbox": "I", "engine.schedule": "S", "engine.publish": "P",
          "engine.mixed": "M", "engine.emit": "E", "engine.prefill": "F",
          "engine.wait": "W", "engine.deliver": "L"}
# the top-level spans of an iteration, D / C a decode's enqueue / commit:
# what is in flight is committed first where the plan cannot run ahead of
# it (CP, then a second schedule), then the plan at hand: a decode enqueued
# (D: left in flight; DCP: ahead of the commit of the one before, or
# committed at once), a mixed step, a prefill, or nothing. L, a delivery at
# the top level: after a decode's enqueue (what a drain or a mixed or
# prefill iteration left), after a commit under a decode enqueued ahead,
# after a mixed step's emit (its prompts' first tokens), before the loop
# idles
TOP = re.compile(r"^IS(CPS)?(DL?(CPL?)?|MEL?P|FP|L?W?)$")


def _letters(it):
    return "".join(
        LETTER.get(n) or ("D" if c[:1] == ["engine.prep"] else "C")
        for n, c in it)


@pytest.mark.parametrize("parent", sorted(WANT))
def test_iteration_spans_tile_their_parent(recorded, parent):
    """One decode, one mixed and one prefill iteration each emit table A's
    names, the children inside their parent and in order, few enough that
    the reduction's look-back still reaches the span that owns a gap."""
    rec, _ = recorded
    its = [it for it in rec.iterations() if any(n == parent for n, _ in it)]
    assert its, f"no {parent} iteration ran; saw " + str(
        sorted({n for it in rec.iterations() for n, _ in it}))
    for it in its:
        assert TOP.match(_letters(it)), (_letters(it), it)
        for name, children in it:
            assert name in TABLE_A and set(children) <= TABLE_A, it
            if name in WANT:
                assert children in WANT[name], (name, children)
                assert len(children) <= LOOKBACK
            else:
                assert children == [], (name, children)


def test_decode_runs_ahead_under_the_recorder(recorded):
    """The long decode of the recording ran ahead: iterations whose enqueue
    precedes the commit of the one before (D then C), every readback, emit
    and publish still inside or right after a step parent, and every
    decode enqueued was committed once."""
    rec, finals = recorded
    tops = [_letters(it) for it in rec.iterations()]
    # (the cold decode delivers the prompt's first token, the ones enqueued
    # ahead what they committed, under themselves)
    assert "ISDL" in tops and "ISDCPL" in tops, tops
    flat = "".join(tops)
    assert flat.count("D") == flat.count("C"), tops
    assert all(f["finish_reason"] == "length" for f in finals)


def test_idle_iteration_waits(recorded):
    rec, _ = recorded
    idle = [it for it in rec.iterations() if ("engine.wait", []) in it]
    assert idle, "the engine never idled under the recorder"
    assert [n for n, _ in idle[-1]] == [
        "engine.inbox", "engine.schedule", "engine.wait"]


def test_gate_off_every_call_site_gets_a_shared_object(monkeypatch):
    """With DYN_ENABLE_JAX_TRACE unset a span allocates nothing: a step
    parent gets the one shared nullcontext from annotate(), a phase the
    one context manager its clock made for it (the door)."""
    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.engine import model_runner as runner_mod
    from dynamo_tpu.engine.engine import InferenceEngine

    monkeypatch.delenv("DYN_ENABLE_JAX_TRACE", raising=False)
    annotations._enabled.cache_clear()
    got = []

    def spy(name, **kw):
        cm = annotations.annotate(name, **kw)
        got.append((name, cm))
        return cm

    def door(idx, **kw):
        cm = annotations.phase(idx, **kw)
        got.append((annotations.SPAN_NAMES[idx], cm))
        return cm

    monkeypatch.setattr(engine_mod, "annotate", spy)
    monkeypatch.setattr(engine_mod, "phase", door)
    monkeypatch.setattr(runner_mod, "phase", door)
    engine = InferenceEngine(_runner(), max_batch=4, chunk_size=8)
    engine.start()
    try:
        asyncio.run(_serve(engine, [[4, 2, 4, 2, 7, 5]], max_tokens=3))
    finally:
        engine.stop()
    names = {n for n, _ in got}
    assert {"engine.inbox", "engine.schedule", "engine.prefill", "engine.decode",
            "engine.prep", "engine.stage", "engine.dispatch", "engine.readback",
            "engine.emit", "engine.publish", "engine.deliver"} <= names, names
    assert names <= TABLE_A, names - TABLE_A
    made = {p.name: p for p in engine.step_clock._phases}
    for name, cm in got:
        if name in PARENTS:
            assert cm is annotations._NULL, name
        else:
            assert cm is made[name], name


class _LowerSpy:
    """A family's jitted function that also notes the name of the module
    each call lowers to (`module @jit_<name>`)."""

    def __init__(self, fn):
        self.fn, self.modules = fn, set()

    def __call__(self, *a, **k):
        text = self.fn.lower(*a, **k).as_text()
        self.modules.add(re.match(r"module @(\S+)", text).group(1))
        return self.fn(*a, **k)

    def _cache_size(self):
        return self.fn._cache_size()


def test_step_programs_carry_their_names(monkeypatch):
    """Each family's lowered module is `jit_<family function>`, none
    `jit__unknown`, and so are the un-familied jits of the serving path."""
    r = _runner()
    spies = {}
    for name, fam in r.compile_families().items():
        spies[name] = fam._fn = _LowerSpy(fam._fn)
    pts = [list(range(i * 4, (i + 1) * 4)) for i in range(3)]
    samp = {"temperature": [0.0], "top_k": [0], "top_p": [1.0], "seeds": [1]}
    chunk = [{"tokens": [1, 2, 3], "start": 0, "table": pts[2], "prior": 0,
              "adapter": 0}]
    r.prefill([4, 2, 4, 2], 0, pts[0], 0)
    r.decode_multi(1, [5], [4], pts[:1], samp, 1)
    r.decode_multi_with_prefills(1, [5], [5], pts[:1], samp, 2, chunk)
    r.ragged_mixed = False  # the padded program
    r.decode_multi_with_prefills(1, [5], [6], pts[:1], samp, 3, chunk)
    r.ensure_draft_ring(2, 2)
    want = {"forward": "jit_forward", "decode_loop": "jit_decode_loop",
            "ragged": "jit_ragged_step", "mixed": "jit_mixed_loop",
            "draft": "jit_draft_ring_step"}
    assert {k: s.modules for k, s in spies.items()} == {
        k: {v} for k, v in want.items()}

    r.copy_pages(0, 1)
    r.sample_one_ex(jnp.zeros(r.config.vocab_size), samp, 1, n_logprobs=0)
    for fn, name in ((r._jit_copy_page, "copy_page"),
                     (r._jit_sample_one_ex, "sample_one_ex"),
                     (r._jit_sample, "sample")):
        assert fn.__name__ == name


def test_compile_counter_sees_eager_programs_once():
    """An eager slice of a new shape on a thread that named the runner
    counts one compile in `other` and in no family; on a thread that named
    none it counts nowhere; a family call that compiles counts its own."""
    r, r2 = _runner(), _runner()
    pts = list(range(4))
    r.prefill([4, 2, 4, 2], 0, pts, 0)  # meets the eager logits[0, 0] too
    x = jnp.arange(53)
    jnp.asarray(x[:3])

    def on_thread(fn):
        out = []
        t = threading.Thread(target=lambda: out.append(fn()))
        t.start()
        t.join(timeout=120)
        assert not t.is_alive()
        return out[0]

    def named_slice():
        r.name_step_thread()
        before = r.compile_stats()
        np.asarray(x[:7])
        return before, r.compile_stats()

    before, after = on_thread(named_slice)
    assert after["other"]["variants"] == before["other"]["variants"] + 1
    assert after["other"]["calls"] == before["other"]["calls"] + 1
    assert {k: v for k, v in after.items() if k != "other"} == {
        k: v for k, v in before.items() if k != "other"}
    assert r2.compile_stats()["other"]["variants"] == 0  # another replica

    def unnamed_slice():
        np.asarray(x[:11])
        return r.compile_stats()

    assert on_thread(unnamed_slice) == after

    def named_family_call():
        r.name_step_thread()
        before = r.compile_stats()
        r.prefill(list(range(1, 13)), 0, pts, 0)  # the 16 bucket: new variant
        return before, r.compile_stats()

    before, after = on_thread(named_family_call)
    assert after["forward"]["variants"] == before["forward"]["variants"] + 1
    assert after["other"] == before["other"]
    assert set(after) == {"forward", "decode_loop", "mixed", "ragged", "draft",
                          "other"}
    assert set(after["other"]) == set(after["forward"])


def test_family_counts_programs_not_call_signatures():
    """The first step on a fresh KV pool sees the sharding the pool was
    allocated under; the step hands it back under the equivalent sharding
    XLA reports, so the same step again is a new entry of jit's dispatch
    cache that resolves to the program already built. That is a call
    signature, not a compiled variant (on the chip it read
    `runner.compiles_in_window` 1 with nothing compiled, PERF.md section
    7, PR 25); a new bucket still counts."""
    r = _runner()
    samp = {"temperature": [0.0], "top_k": [0], "top_p": [1.0], "seeds": [1]}
    fam = r.compile_families()["decode_loop"]

    def step(tokens):
        n = len(tokens)
        r.decode_multi(1, tokens, [0] * n, [[i] for i in range(n)],
                       {k: v * n for k, v in samp.items()}, 0)
        return fam._cache_size(), fam.stats()

    size1, first = step([3])
    assert first["variants"] == 1 and first["compile_s"] > 0
    size2, again = step([3])
    assert size2 == size1 + 1, "the pool came back under the same sharding"
    assert (again["variants"], again["compile_s"]) == (1, first["compile_s"])
    assert step([3])[0] == size2
    assert step([3, 5])[1]["variants"] == 2


def test_spine_decomposes_ttft(recorded):
    """A finished request's spine: prefill_s, prefill_iters, preemptions,
    and ttft_s as the sum of its three parts."""
    _, finals = recorded
    assert len(finals) == 3
    for item in finals:
        ph = item["phases"]
        assert ph["prefill_iters"] >= 1 and ph["preemptions"] == 0, ph
        parts = ph["queue_wait_s"] + ph.get("kv_onboard_s", 0.0) + ph["prefill_s"]
        assert abs(ph["ttft_s"] - parts) < 1e-3, ph
        assert ph["prefill_s"] > 0.0
    # the 9-token prompt met a live decode row: 8 tokens a mixed step
    assert finals[2]["phases"]["prefill_iters"] >= 2, finals[2]["phases"]


def test_preempted_request_says_so():
    """Two requests that cannot both fit the pool: the younger is
    preempted and re-prefilled once, and its spine reads preemptions 1."""
    from dynamo_tpu.engine.engine import InferenceEngine

    engine = InferenceEngine(
        _runner(num_pages=6, page_size=2, max_pages_per_seq=8), max_batch=4,
        chunk_size=8, enable_prefix_cache=False)
    engine.start()
    try:
        finals = asyncio.run(_serve(engine, [[1, 2, 3], [4, 5, 6]], max_tokens=7))
    finally:
        engine.stop()
    got = sorted(f["phases"]["preemptions"] for f in finals)
    assert got == [0, 1], [f["phases"] for f in finals]
    hit = [f["phases"] for f in finals if f["phases"]["preemptions"]][0]
    assert hit["prefill_iters"] >= 2  # the prompt, and its recompute
