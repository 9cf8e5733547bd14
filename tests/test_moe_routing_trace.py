"""The expert layer says what it did (CPU, toy sizes, float32): the step
programs of a routed model return the router's picks, a request that
asks (`sampling.routed_experts`) gets them in its stream, every
iteration carries the expert-load counters, and a dense model's programs
are what they were. docs/observability.md, "Routed experts"."""

import asyncio
import dataclasses
import importlib.util
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model_runner as runner_mod
from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.flight_recorder import IterationRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 1 dense + 2 expert layers, latent attention, 8 experts, 2 a token: rides
# the padded mixed program; and a GQA routed toy, which rides the ragged one
MLA = get_config("tiny-mla-moe").with_(n_experts=8)
GQA = get_config("tiny-moe")
PAGE, CHUNK = 4, 8
# the lead decodes while the others prefill; the last prompt is longer than
# two chunks, so its chunks ride mixed iterations at several decode rows
DRIVE = [(6, 30), (7, 18), (11, 14), (21, 6)]


def _runner(config, **kw):
    args = dict(num_pages=128, page_size=PAGE, max_pages_per_seq=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(8, 16), seed=7,
                dtype=jnp.float32)
    args.update(kw)
    return ModelRunner(config, **args)


def _engine(runner, **kw):
    return InferenceEngine(runner, max_batch=4, chunk_size=CHUNK,
                           mixed_prefill_tokens=CHUNK, mixed_prefill_seqs=2, **kw)


def _prompts(config, sizes=DRIVE, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, config.vocab_size, n).tolist(), k) for n, k in sizes]


async def _serve(engine, reqs, ask):
    """Serve `reqs` [(prompt, n_out)] together, every one in the inbox before
    the step thread starts, so that the iterations are the same every time.
    `ask` is one flag or one per request. Returns each request's items."""
    asks = ask if isinstance(ask, (list, tuple)) else [ask] * len(reqs)

    async def one(prompt, n_out, a):
        sampling = {"temperature": 0.0, **({"routed_experts": True} if a else {})}
        return [it async for it in engine.generate(
            {"token_ids": prompt, "sampling": sampling,
             "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}},
            Context())]

    start, engine.start = engine.start, lambda: None
    try:
        tasks = [asyncio.ensure_future(one(p, k, a)) for (p, k), a in zip(reqs, asks)]
        while engine._thread is None and engine._inbox.qsize() < len(reqs):
            await asyncio.sleep(0.01)
    finally:
        engine.start = start
    engine.start()
    out = await asyncio.gather(*tasks)
    for items in out:
        assert items[-1]["finish_reason"] == "length", items[-1]
    return out


def _drive(runner, reqs, ask, **kw):
    """A fresh engine (an empty prefix cache) on `runner`: (items per
    request, the flight records, calls and variants by family)."""
    before = runner.compile_stats()
    engine = _engine(runner, **kw)
    try:
        out = asyncio.run(_serve(engine, reqs, ask))
    finally:
        engine.stop()  # joins the step thread: the last record is in
    records = engine.recorder.snapshot()
    after = runner.compile_stats()
    delta = {f: (after[f]["calls"] - before[f]["calls"],
                 after[f]["variants"] - before[f]["variants"])
             for f in after if f != "other"}
    return out, records, delta


def _tokens(items):
    return [t for it in items for t in it["token_ids"]]


def _streamed(items):
    """{position: picks [L_moe][k]} and the positions in stream order."""
    order, picks = [], {}
    for it in items:
        r = it.get("routed_experts")
        if r:
            for j, ids in enumerate(r["ids"]):
                order.append(r["start"] + j)
                picks[r["start"] + j] = ids
    return picks, order


@pytest.fixture(scope="module")
def fused():
    """The fused mixed step, which the CPU leaves off by default."""
    mp = pytest.MonkeyPatch()
    mp.setenv("DYN_FUSED_MIXED", "1")
    yield
    mp.undo()


@pytest.fixture(scope="module")
def served(fused):
    """Each toy served three times on one runner: every request asking, none
    asking, asking again (the second and third meet warm programs)."""
    out = {}
    for name, config in (("mla", MLA), ("gqa", GQA)):
        runner = _runner(config)
        reqs = _prompts(config)
        out[name] = dict(
            runner=runner, reqs=reqs, config=config,
            ask=_drive(runner, reqs, True), quiet=_drive(runner, reqs, False),
            again=_drive(runner, reqs, True))
    return out


# -- (1) the picks are the router's ----------------------------------------


def _reference_picks_mla(config, params, tokens):
    """[S][L_moe] sets of experts: benchmark/reference/mla_moe_decoder.py
    (plain float32, the whole sequence at once) made to say what it routes."""
    spec = importlib.util.spec_from_file_location(
        "ref_mla_moe", os.path.join(REPO, "benchmark", "reference", "mla_moe_decoder.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    model = dataclasses.asdict(config)
    inv, m, soft = ref.rope_table(model)
    sizes = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (bool, int, float, str))))
    tok = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tok.shape[0], dtype=jnp.int32)
    inv = jnp.asarray(inv, jnp.float32)
    h = params["embed"][tok].astype(jnp.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for l in range(config.n_layers):
            dense = l < config.n_dense_layers
            stack, i = ((params["layers_dense"], l) if dense
                        else (params["layers"], l - config.n_dense_layers))
            lp = jax.tree.map(lambda a: a[i], stack)
            if not dense:
                x = ref._rms(ref._attention(h, lp, pos, model, inv, m, soft),
                             lp["mlp_norm"].astype(jnp.float32), config.norm_eps)
                sel, _, _ = ref.route(x @ lp["w_router"].astype(jnp.float32),
                                      lp.get("router_bias"), model)
                out.append(np.asarray(sel))
            h, _ = ref._step(h, lp, pos, inv, sizes, m, soft)
    return [[set(layer[s].tolist()) for layer in out] for s in range(len(tokens))]


def _reference_picks_gqa(config, params, tokens):
    """The same for the GQA toy, which the benchmark's reference does not
    cover: the model's own forward over the whole sequence as one prefill
    (another program and another row layout than any the engine ran)."""
    S = len(tokens)
    pages = -(-S // PAGE)
    k, v = llama.make_kv_pool(config, pages + 1, PAGE, dtype=jnp.float32)
    _, _, _, sel = llama.forward(
        config, params, jnp.asarray([tokens]), jnp.arange(S)[None, :], k, v,
        jnp.arange(pages, dtype=jnp.int32)[None, :], jnp.asarray([S]),
        return_routed=True)
    sel = np.asarray(sel)  # [L_moe, 1, S, k]
    return [[set(sel[l, 0, s].tolist()) for l in range(sel.shape[0])] for s in range(S)]


@pytest.mark.parametrize("name", ["mla", "gqa"])
def test_streamed_picks_are_the_float32_recomputation(served, name):
    """Prompt + output, through standalone chunks, fused mixed iterations and
    the decode loop at one to four rows: at every position and expert layer
    the streamed experts are, as a set, what a plain recomputation picks."""
    sv = served[name]
    reference = _reference_picks_mla if name == "mla" else _reference_picks_gqa
    items_all, records, delta = sv["ask"]
    fused_family = "mixed" if name == "mla" else "ragged"
    assert delta[fused_family][0] > 0, delta
    assert max(r.decode_seqs for r in records) >= 3
    n_layers = sv["config"].n_layers - sv["config"].n_dense_layers
    for (prompt, n_out), items in zip(sv["reqs"], items_all):
        toks = _tokens(items)
        assert len(toks) == n_out
        seq = prompt + toks[:-1]
        picks, _ = _streamed(items)
        want = reference(sv["config"], sv["runner"].params, seq)
        for p in range(len(seq)):
            assert len(picks[p]) == n_layers
            assert all(len(ids) == sv["config"].n_experts_active for ids in picks[p])
            assert [set(ids) for ids in picks[p]] == want[p], (name, p)


# -- (2) coverage ----------------------------------------------------------


@pytest.mark.parametrize("name", ["mla", "gqa"])
def test_every_position_once_and_in_order(served, name):
    sv = served[name]
    for (prompt, n_out), items in zip(sv["reqs"], sv["ask"][0]):
        _, order = _streamed(items)
        assert order == list(range(len(prompt) + n_out - 1))
        # a chunk that samples nothing still sends its picks, in an item
        # with no token: the 21-token prompt takes three chunks or more
        starts = [it["routed_experts"]["start"] for it in items if not it["token_ids"]]
        if len(prompt) > 2 * CHUNK:
            assert len(starts) >= 2 and starts[0] == 0 and starts == sorted(set(starts))


def test_prefix_cache_hit_leaves_the_gap_start_shows(fused):
    """Positions whose forward did not run here are absent: a second request
    over the same prompt starts after the cached pages."""
    runner = _runner(MLA)
    prompt = _prompts(MLA, [(19, 3)])[0]
    engine = _engine(runner)
    try:
        first = asyncio.run(_serve(engine, [prompt], True))[0]
        again = asyncio.run(_serve(engine, [prompt], True))[0]
    finally:
        engine.stop()
    assert _streamed(first)[1] == list(range(19 + 3 - 1))
    picks, order = _streamed(again)
    hit = order[0]
    assert hit > 0 and hit % PAGE == 0, order
    assert order == list(range(hit, 19 + 3 - 1))
    assert all(picks[p] == _streamed(first)[0][p] for p in order)
    assert _tokens(first) == _tokens(again)


# -- (3) asking changes nothing ----------------------------------------------


@pytest.mark.parametrize("name", ["mla", "gqa"])
def test_asking_changes_no_token_and_no_dispatch(served, name):
    sv = served[name]
    ask, quiet, again = sv["ask"], sv["quiet"], sv["again"]
    for a, q, g in zip(ask[0], quiet[0], again[0]):
        assert _tokens(a) == _tokens(q) == _tokens(g)
        assert not any("routed_experts" in it for it in q)
    # the same iterations, composed the same way
    shape = lambda recs: [(r.kind, r.fused, r.ragged, r.decode_seqs, r.decode_steps,
                           r.n_chunks, r.chunk_tokens) for r in recs]
    assert shape(ask[1]) == shape(quiet[1]) == shape(again[1])
    # the same calls by family, asking or not; and once warm no new variant
    assert {f: c for f, (c, _) in ask[2].items()} == \
        {f: c for f, (c, _) in quiet[2].items()} == \
        {f: c for f, (c, _) in again[2].items()}
    assert all(v == 0 for _, v in quiet[2].values()), quiet[2]
    assert all(v == 0 for _, v in again[2].values()), again[2]
    fused_family = "mixed" if name == "mla" else "ragged"
    assert quiet[2][fused_family][0] > 0 and again[2][fused_family][0] > 0
    assert any(r.fused for r in again[1])


def test_mixed_asking_and_quiet_rows_share_a_dispatch(served):
    """One asking row beside three that do not: it alone gets picks, and they
    are the picks it got when everyone asked."""
    sv = served["mla"]
    out, _, _ = _drive(sv["runner"], sv["reqs"], [False, False, True, False])
    for i, items in enumerate(out):
        assert _tokens(items) == _tokens(sv["ask"][0][i])
        assert (_streamed(items)[0] == _streamed(sv["ask"][0][i])[0]) if i == 2 \
            else not _streamed(items)[1]


# -- (4) a dense model is what it was ---------------------------------------


def test_dense_programs_return_what_they_returned(fused):
    """forward and each step program of a dense model: the output tree of
    the parent commit (3, 5, 5 and 4 outputs, no routed leaf), the routed
    model's one more; a dense engine's records read 0."""
    for config, extra in ((get_config("tiny"), 0), (GQA, 1)):
        r = _runner(config)
        assert r.routed == bool(extra)
        assert len(r.prefill([1, 2, 3], 0, [1], prior_len=0).shape) == 1
        samp = {"temperature": [0.0], "top_k": [0], "top_p": [1.0], "seeds": [0],
                "rep": [1.0], "freq": [0.0], "presence": [0.0]}
        B, MP = 1, r.max_pages_per_seq
        packed = jnp.zeros(B * (1 + MP) + 1, jnp.int32)
        sp = r._device_sampling(samp, B)
        dec = jax.eval_shape(
            lambda *a: runner_mod._decode_loop(config, "jnp", None, 2, -1, *a),
            r.params, jnp.zeros(B, jnp.int32), packed, None, None, None,
            r.k_pool, r.v_pool, sp)
        assert len(dec) == 5 + extra
        ptok = jnp.zeros((1, 8), jnp.int32)
        mix = jax.eval_shape(
            lambda *a, **k: runner_mod._mixed_loop(config, "jnp", None, 2, *a, **k),
            r.params, ptok, ptok, jnp.zeros((1, MP), jnp.int32), jnp.ones(1, jnp.int32),
            jnp.full(1, 7, jnp.int32), None, jnp.zeros(B, jnp.int32), packed, r.k_pool, r.v_pool, sp,
            **({"prows": jnp.int32(1)} if extra else {}))
        assert len(mix) == 5 + extra
        fwd = jax.eval_shape(lambda *a: r._jit_forward._fn(*a, attn_impl="jnp"),
                      r.params, ptok, ptok, r.k_pool, r.v_pool,
                      jnp.zeros((1, MP), jnp.int32), jnp.ones(1, jnp.int32))
        assert len(fwd) == 3 + extra
        if extra:
            assert set(dec[5]) == {"decode", "load"}
            assert set(mix[5]) == {"chunks", "decode", "load"}
            assert set(fwd[3]) == {"chunks", "load"}
            assert dec[5]["decode"].shape == (2, config.n_layers, B, config.n_experts_active)
        assert len(r._routed_parts) == extra  # the prefill above
        toks, chunk_logits = r.decode_multi_with_prefill(
            2, [1], [0], [[1]], samp, 1, [1] * 5, 0, [2, 3], 0)
        assert toks.shape == (1, 2)
        assert len(r._routed_parts) == 3 * extra  # + the ragged step and its loop
    with pytest.raises(ValueError, match="routed experts"):
        llama.forward(get_config("tiny"), None, jnp.zeros((1, 1), jnp.int32),
                      None, None, None, None, None, return_routed=True)
    dense = _runner(get_config("tiny"))
    _, records, _ = _drive(dense, _prompts(get_config("tiny"), DRIVE[:2]), False)
    assert records and all(
        (r.moe_token_slots, r.moe_experts_hit, r.moe_load_max_share) == (0, 0.0, 0.0)
        for r in records)


# -- (5) the counters -------------------------------------------------------


def _count(picks, n_experts):
    """numpy twin of models/moe.routing_stats over forwards: picks
    [forwards][L_moe, tokens, k] -> (slots, hit, share, held slots, listed)
    as the record has them; every expert is held here, so every slot is a
    held one (a held share: tests/test_mistral4.py), and on the CPU the
    dense path runs, which lists nothing (the kernel's count:
    tests/test_moe_experts_kernel.py)."""
    slots, hit, share, units = 0, 0.0, 0.0, 0
    for f in picks:
        L, T, k = f.shape
        slots += T * k
        for l in range(L):
            load = np.bincount(f[l].ravel(), minlength=n_experts)
            hit += (load > 0).sum()
            share += load.max() / T
            units += 1
    return slots, hit / units, share / units, float(slots), 0


@pytest.mark.parametrize("name", ["mla", "gqa"])
def test_counters_are_the_count_of_the_picks(served, name):
    """A 3-row decode in the 4-bucket, then a mixed dispatch with a packed
    chunk pair in a wider pack: the counters of each against a numpy count
    of the picks it handed out, padded rows and padded chunk rows left out."""
    sv = served[name]
    r, c = sv["runner"], sv["config"]
    samp = lambda n: {"temperature": [0.0] * n, "top_k": [0] * n, "top_p": [1.0] * n,
                      "seeds": [0] * n, "rep": [1.0] * n, "freq": [0.0] * n,
                      "presence": [0.0] * n}
    r.take_moe_load()
    r.prefill([5, 6, 7, 8, 9, 10], 0, [1, 2], prior_len=0)
    assert not runner_mod.MoeLoad(list(r._routed_parts), 1).ready  # nothing
    # was read back yet: the engine would hold the iteration's record
    _, chunks = r.routed_picks()
    got = r.take_moe_load()
    want = _count([chunks[0]], c.n_experts)
    assert got.result()[0] == want[0] == 6 * c.n_experts_active
    np.testing.assert_allclose(got.result()[1:], want[1:], atol=1e-6)

    r.decode_multi(3, [3, 4, 5], [4, 4, 4], [[1, 2], [3, 4], [5, 6]], samp(3), 1)
    decode, _ = r.routed_picks()
    assert decode.shape[0] == 3 and decode.shape[2] == 3
    got = r.take_moe_load()
    assert got.ready
    want = _count(list(decode), c.n_experts)
    assert got.result()[0] == want[0] == 3 * 3 * c.n_experts_active
    np.testing.assert_allclose(got.result()[1:], want[1:], atol=1e-6)

    three = [{"tokens": [7 + j] * n, "start": 0, "prior": 0, "adapter": 0,
              "table": [10 + 2 * j, 11 + 2 * j]} for j, n in enumerate((5, 3, 4))]
    r.decode_multi_with_prefills(
        2, [3, 4, 5], [4, 4, 4], [[1, 2], [3, 4], [5, 6]], samp(3), 1, three)
    decode, chunks = r.routed_picks()
    assert [ch.shape[1] for ch in chunks] == [5, 3, 4] and decode.shape[0] == 2
    got = r.take_moe_load()
    if name == "mla":  # padded: one forward over the chunks, then the steps
        forwards = [np.concatenate(chunks, axis=1)] + list(decode)
    else:  # ragged: step 0 and the chunks share a forward
        forwards = [np.concatenate([decode[0]] + chunks, axis=1)] + list(decode[1:])
    want = _count(forwards, c.n_experts)
    assert got.result()[0] == want[0] == (3 * 2 + 12) * c.n_experts_active
    np.testing.assert_allclose(got.result()[1:], want[1:], atol=1e-6)


@pytest.mark.parametrize("name", ["mla", "gqa"])
def test_every_iteration_carries_the_counters(served, name):
    sv = served[name]
    k = sv["config"].n_experts_active
    for _, records, _ in (sv["ask"], sv["quiet"]):
        for r in records:
            assert r.moe_token_slots == k * (r.decode_seqs * r.decode_steps + r.chunk_tokens)
            assert 1.0 <= r.moe_experts_hit <= sv["config"].n_experts
            assert k / sv["config"].n_experts - 1e-6 <= r.moe_load_max_share <= 1.0
        streamed = sum(len(_streamed(items)[1]) for items in sv["ask"][0])
        # every forward position but the ones decoded past a request's end
        assert sum(r.moe_token_slots for r in records) >= k * streamed
    assert [(r.moe_token_slots, r.moe_experts_hit, r.moe_load_max_share)
            for r in sv["ask"][1]] == \
        [(r.moe_token_slots, r.moe_experts_hit, r.moe_load_max_share)
         for r in sv["quiet"][1]]


# -- (6) scalars, and /metrics ---------------------------------------------


def test_new_record_fields_are_scalars(served):
    rec = served["mla"]["ask"][1][0]
    new = {"moe_token_slots": int, "moe_experts_hit": float, "moe_load_max_share": float}
    fields = {f.name for f in dataclasses.fields(IterationRecord)}
    for name, kind in new.items():
        assert name in fields and type(getattr(rec, name)) is kind
    assert all(isinstance(getattr(rec, f), (bool, int, float, str, list)) for f in fields)


async def test_metrics_show_the_three_series(served):
    from dynamo_tpu.frontend.protocols import ModelCard
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    sv = served["mla"]
    engine = _engine(sv["runner"])
    rt = DistributedRuntime(discovery=MemDiscovery(realm="moe-metrics"),
                            event_transport="inproc")
    try:
        w = await serve_worker(rt, engine, ModelCard(name="m"), digest_period_s=0,
                               publish_kv_events=False, publish_fpm=False)
        out = await _serve(engine, sv["reqs"][:2], False)
        assert all(_tokens(o) for o in out)
        for _ in range(100):  # the idle flush and the last FPM hook
            # (and the commit of a dispatch enqueued ahead for rows that
            # have ended since: its forwards count too)
            if (engine._rec_late is None and engine._inflight is None
                    and not engine._undelivered
                    and not engine.scheduler.has_work()):
                break
            await asyncio.sleep(0.01)
        engine._publish_fpm("decode", 0.0, 0)  # (the hook refreshes /metrics)
        for _ in range(100):  # the loop's idle pass delivers it
            if not engine._undelivered:
                break
            await asyncio.sleep(0.01)
        lines = rt.metrics.render().decode().splitlines()
        total = engine.moe_totals["token_slots_total"]
        await w.stop()
    finally:
        await rt.shutdown(drain_timeout=1)
        engine.stop()

    def value(name):
        got = [float(ln.rsplit(" ", 1)[1]) for ln in lines
               if re.match(rf"dynamo_{name}(\{{| )", ln)]
        assert len(got) == 1, (name, got)
        return got[0]

    assert value("moe_token_slots_total") == total > 0
    assert 1.0 <= value("moe_experts_hit") <= MLA.n_experts
    assert 0.0 < value("moe_load_max_share") <= 1.0


# -- (7) the paths that cannot carry the picks say so ------------------------


def _refusal(**flags):
    """InferenceEngine._routed_refusal as admission calls it, on a worker
    that could carry the picks but for `flags`."""
    runner = types.SimpleNamespace(pp=False, sp_enabled=False, has_draft=False)
    eng = types.SimpleNamespace(runner=runner, _spec_on=False, _routed_ok=True)
    for k, v in flags.items():
        setattr(eng if hasattr(eng, k) else runner, k, v)
    return InferenceEngine._routed_refusal(eng)


@pytest.mark.parametrize("flag,word", [
    ("pp", "pipeline-parallel"), ("sp_enabled", "sequence-parallel"),
    ("has_draft", "draft model"), ("_spec_on", "speculative verify")])
def test_refusing_paths_refuse_by_name(flag, word):
    assert _refusal() is None
    assert word in _refusal(**{flag: True})
    assert "no routed experts" in _refusal(_routed_ok=False)


def _ask_once(engine):
    async def ask():
        return [it async for it in engine.generate(
            {"token_ids": [1, 2, 3], "sampling": {"routed_experts": True},
             "stop": {"max_tokens": 2}}, Context())]
    try:
        return asyncio.run(ask())
    finally:
        engine.stop()


def test_a_speculating_worker_refuses_the_request(served):
    """Through generate(): one error item that names the path, no stream
    that silently lacks the picks."""
    items = _ask_once(_engine(served["gqa"]["runner"], spec_ngram=True))
    assert len(items) == 1 and items[0]["finish_reason"] == "error"
    assert "routed_experts" in items[0]["error"]
    assert "speculative verify" in items[0]["error"]


def test_a_dense_worker_refuses_the_request(fused):
    items = _ask_once(_engine(_runner(get_config("tiny"))))
    assert len(items) == 1 and items[0]["finish_reason"] == "error"
    assert "no routed experts" in items[-1]["error"]


# -- (8) the expert mesh hands out the same picks ---------------------------


def test_expert_mesh_hands_out_the_dense_paths_picks():
    """moe_ep on a 4-device expert mesh against the one-device path: the
    same picks for every token of a prefill and of a decode step."""
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    c = GQA.with_(n_experts=8)
    params = llama.init_params(c, jax.random.PRNGKey(2), dtype=jnp.float32)
    mesh = make_mesh(MeshConfig(expert=4), jax.devices()[:4])
    rng = np.random.default_rng(0)
    for B, S in ((1, 16), (4, 1)):
        toks = jnp.asarray(rng.integers(1, c.vocab_size, (B, S)), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        pages = -(-S // PAGE)
        table = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
        outs = []
        for m in (None, mesh):
            k, v = llama.make_kv_pool(c, B * pages + 1, PAGE, dtype=jnp.float32)
            logits, _, _, sel = jax.jit(
                lambda p, *a, m=m: llama.forward(c, p, *a, mesh=m, return_routed=True)
            )(params, toks, pos, k, v, table, jnp.full((B,), S, jnp.int32))
            outs.append((np.asarray(logits), np.asarray(sel)))
        assert outs[0][1].shape == (c.n_layers, B, S, c.n_experts_active)
        assert np.array_equal(np.sort(outs[0][1], -1), np.sort(outs[1][1], -1))
        np.testing.assert_allclose(outs[0][0], outs[1][0], atol=1e-4)
