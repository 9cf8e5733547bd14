"""Loadgen/goodput harness + offline replay tests."""

import pytest

from dynamo_tpu.bench.loadgen import (
    RequestResult,
    compute_goodput,
    generate_trace,
    load_trace,
    save_trace,
)


def test_trace_generation_and_roundtrip(tmp_path):
    trace = generate_trace(50, rps=10, isl_mean=100, osl_mean=20, prefix_groups=4, seed=1)
    assert len(trace) == 50
    assert all(trace[i].ts <= trace[i + 1].ts for i in range(49))
    assert any(r.prefix_group >= 0 for r in trace)
    p = tmp_path / "t.jsonl"
    save_trace(trace, str(p))
    again = load_trace(str(p))
    assert [r.ts for r in again] == [r.ts for r in trace]


def test_goodput_slo_accounting():
    results = [
        RequestResult(ok=True, ttft_s=0.1, total_s=1.0, osl=10),   # meets
        RequestResult(ok=True, ttft_s=5.0, total_s=6.0, osl=10),   # ttft miss
        RequestResult(ok=True, ttft_s=0.1, total_s=10.0, osl=10),  # itl miss
        RequestResult(ok=False, error="boom"),
    ]
    rep = compute_goodput(results, duration_s=10.0, ttft_slo_s=2.0, itl_slo_s=0.5)
    assert rep.n_ok == 3 and rep.n_slo_met == 1
    assert rep.goodput_tok_s == pytest.approx(1.0)
    assert rep.throughput_tok_s == pytest.approx(3.0)


async def test_offline_replay_end_to_end():
    from dynamo_tpu.replay import parse_args, run_replay

    args = parse_args([
        "--workers", "2", "--requests", "20", "--rps", "100",
        "--speed", "0", "--router-mode", "kv", "--prefix-groups", "3",
    ])
    report = await run_replay(args)
    assert report["n_ok"] == 20
    assert report["output_tokens"] > 0
    assert report["goodput_tok_s"] > 0


def test_sim_timing_fit_recovers_model():
    """Fitting FPM records generated from a known SimTiming recovers its
    parameters (the real-run → calibrated-mocker path)."""
    from dynamo_tpu.engine.engine import ForwardPassMetrics
    from dynamo_tpu.mocker.sim import SimTiming

    truth = SimTiming(decode_base_s=0.006, decode_per_seq_s=0.0004,
                      prefill_base_s=0.003, prefill_per_token_s=0.00005)
    T = 4
    hist = []
    for b in (1, 2, 4, 8, 16, 32):
        wall = 0.002 + T * (truth.decode_base_s + b * truth.decode_per_seq_s)
        hist.append(ForwardPassMetrics(ts=0, kind="decode", wall_time_s=wall,
                                       scheduled_tokens=b * T, n_running=b,
                                       n_waiting=0, kv_usage=0.1))
    for n in (16, 64, 256, 512):
        wall = truth.prefill_base_s + n * truth.prefill_per_token_s
        hist.append(ForwardPassMetrics(ts=0, kind="prefill", wall_time_s=wall,
                                       scheduled_tokens=n, n_running=1,
                                       n_waiting=0, kv_usage=0.1))

    fit = SimTiming.fit(hist, decode_steps=T)
    assert abs(fit.decode_per_seq_s - truth.decode_per_seq_s) / truth.decode_per_seq_s < 0.05
    assert abs(fit.prefill_per_token_s - truth.prefill_per_token_s) / truth.prefill_per_token_s < 0.05
    # intercept folds dispatch overhead: decode_base >= truth's
    assert fit.decode_base_s >= truth.decode_base_s * 0.9
    # dict-form records (off the event plane) work too
    as_dicts = [m.__dict__ for m in hist]
    fit2 = SimTiming.fit(as_dicts, decode_steps=T)
    assert abs(fit2.decode_per_seq_s - fit.decode_per_seq_s) < 1e-9


# -- goodput bench against the real stack -----------------------------------


def _goodput_args(extra=()):
    from dynamo_tpu.bench.goodput import parse_args

    return parse_args([
        "--model", "tiny", "--num-pages", "64", "--page-size", "4",
        "--max-pages-per-seq", "8", "--max-batch", "4", "--chunk-size", "16",
        "--decode-buckets", "1", "2", "4",
        "--prefill-buckets", "8", "16", "32",
        "--n-requests", "12", "--rps", "20", "--isl", "12", "--osl", "6",
        "--ttft-slo", "30", "--itl-slo", "30",
        *extra,
    ])


async def test_goodput_real_engine_aggregated():
    from dynamo_tpu.bench.goodput import run_goodput

    rep = await run_goodput(_goodput_args())
    assert rep.n_requests == 12
    assert rep.n_ok == 12, "all requests must succeed through the stack"
    assert rep.goodput_tok_s > 0
    # osl is drawn per-request around the mean; with generous SLOs every
    # token is good tokens
    assert rep.n_slo_met == 12
    assert rep.output_tokens > 0
    assert rep.ttft_p50_s > 0 and rep.itl_p50_s >= 0


async def test_goodput_real_engine_disagg():
    from dynamo_tpu.bench.goodput import run_goodput

    rep = await run_goodput(_goodput_args(
        ["--disagg", "--disagg-min-prefill-tokens", "8"]
    ))
    assert rep.n_ok == 12
    assert rep.goodput_tok_s > 0
    # one replica per device: the decode and the prefill engine must not
    # both land on device 0 (tests run on 8 virtual CPU devices)
    assert sorted(rep.extras["worker_devices"]) == [["cpu:0"], ["cpu:1"]]


async def test_goodput_mocker_plane_ceiling():
    """Mocker mode: the serving-plane throughput ceiling (SURVEY §2.9) —
    frontend pipeline + router + TCP with a simulated accelerator."""
    from dynamo_tpu.bench.goodput import run_goodput

    rep = await run_goodput(_goodput_args(
        ["--mocker", "--n-requests", "24", "--rps", "100", "--osl", "8"]
    ))
    assert rep.n_ok == 24
    assert rep.throughput_tok_s > 0
    # SLO accounting distinguishes goodput from raw throughput
    assert rep.goodput_tok_s <= rep.throughput_tok_s + 1e-9


async def test_goodput_mocker_over_nats_plane_twice():
    """--request-plane nats boots an in-process broker, measures the SLO
    shape through broker subjects, and restores DYN_NATS_URL on close —
    a SECOND boot in the same process must get a fresh broker instead of
    dialing the first one's dead port."""
    import os

    from dynamo_tpu.bench.goodput import parse_args, run_goodput

    argv = ["--mocker", "--request-plane", "nats", "--isl", "32",
            "--osl", "8", "--n-requests", "6", "--rps", "8",
            "--workers", "1"]
    for _ in range(2):
        report = await run_goodput(parse_args(argv))
        assert report.n_ok == 6, report
        assert "DYN_NATS_URL" not in os.environ
