"""The names serve.py's check of a routed model holds fixed in the program:
the payload field `sampling.routed_experts` and the stream's
`routed_experts: {"start", "ids"}` (docs/observability.md, "Routed experts").
A tiny latent-attention expert engine on the CPU serves the check's own
sample through `serve.served` with the field asked, and the assembled picks
must cover every position once, with the model's expert layers and k; then
`serve.reference_check` itself runs on it, float32, both passes. A program PR
that renames `routed_experts`, `start` or `ids` fails here and not in a cell's
`correct`. (Issue 32 wanted this in tier-1, tests/test_harness_pins.py; a
`benchmark` PR may not add there, so it is by hand until a PR that may touch
tests/ moves it: PERF.md section 7.)"""

import asyncio
import dataclasses
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models.config import get_config

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


serve = _load("serve.py", "bench_serve_picks")
ref = _load(os.path.join("reference", "mla_moe_decoder.py"), "bench_reference_picks")

CONFIGS = {
    "one-dense-layer": get_config("tiny-mla-moe").with_(n_experts=8),
    "no-dense-layer": get_config("tiny-mla-moe").with_(n_experts=8, n_dense_layers=0, n_layers=2),
}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def engine(request):
    mp = pytest.MonkeyPatch()
    mp.setenv("DYN_FUSED_MIXED", "1")  # the CPU leaves the fused mixed step off
    config = CONFIGS[request.param]
    runner = ModelRunner(config, num_pages=128, page_size=4, max_pages_per_seq=32,
                         decode_buckets=(1, 2, 4, 8), prefill_buckets=(8, 16), seed=7,
                         dtype=jnp.float32)
    eng = InferenceEngine(runner, max_batch=8, chunk_size=16, mixed_prefill_tokens=16,
                          mixed_prefill_seqs=2)
    yield eng
    eng.stop()
    mp.undo()


def test_served_assembles_the_picks_of_every_position(engine):
    c = engine.runner.config
    sample = serve.check_prompts(c, engine.scheduler, np.random.default_rng(5), rehearse=True)
    got = asyncio.run(serve.served(engine, sample, logprobs=False, picks=True))
    for (ids, n_out), (toks, lps, picks) in zip(sample, got):
        assert len(toks) == n_out and lps == []
        assert picks is not None, "the stream did not cover 0 .. n_prompt + n_out - 2 in order"
        assert picks.shape == (len(ids) + n_out - 1, c.n_layers - c.n_dense_layers,
                               c.n_experts_active) and picks.dtype == np.int32
        assert picks.min() >= 0 and picks.max() < c.n_experts
        assert (np.sort(picks, -1)[..., 1:] != np.sort(picks, -1)[..., :-1]).all()
    # not asked: the same tokens, and nothing assembled
    quiet = asyncio.run(serve.served(engine, sample, logprobs=False))
    assert [g[0] for g in quiet] == [g[0] for g in got] and all(g[2] is None for g in quiet)


def test_reference_check_follows_them_through_both_passes(engine):
    model = dataclasses.asdict(engine.runner.config)
    res = asyncio.run(serve.reference_check(ref, model, engine, 11, 1e-3, True, margin=1e-5))
    assert res["ok"], res
    for name in ("logprobs", "ragged"):
        r = res[name]
        assert r["picks"] > 0 and r["inadmissible"] == 0 and r["need_max"] <= 1e-5
        assert r["max_decode_rows"] > 1
    assert res["logprobs"]["max_abs_logprob_err"] < 1e-3
    assert res["ragged"]["calls"]["mixed"] > 0  # latent attention rides the padded mixed program


def test_a_dense_worker_refuses_the_field():
    """Why a dense model's payload must stay what it is: `served` raises on
    the engine's error item."""
    runner = ModelRunner(get_config("tiny"), num_pages=64, page_size=4, max_pages_per_seq=16,
                         decode_buckets=(1, 2), prefill_buckets=(8, 16), seed=7)
    eng = InferenceEngine(runner, max_batch=2, chunk_size=16)
    try:
        with pytest.raises(RuntimeError, match="routed_experts is unsupported"):
            asyncio.run(serve.served(eng, [([1, 2, 3, 4], 2)], logprobs=False, picks=True))
    finally:
        eng.stop()
