"""The percentile, due-time and window arithmetic on hand-made samples, and
the generators' promise that every seed replays the same trace."""

import pytest

import loadgen
import numpy as np

from generators import open_loop
from generators.common import drawn_gaps, drawn_lengths


def test_percentile_interpolates_between_closest_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert loadgen.percentile(xs, 0) == 10.0
    assert loadgen.percentile(xs, 50) == 30.0
    assert loadgen.percentile(xs, 95) == pytest.approx(48.0)
    assert loadgen.percentile(xs, 100) == 50.0
    assert loadgen.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        loadgen.percentile([], 50)


def _turn(due, sent, chunks, max_tokens, done=True, prompt=5):
    t = loadgen.Turn(0, 0, due, max_tokens, prompt)
    t.sent, t.chunks = sent, chunks
    t.done = chunks[-1][0] + 0.001 if (done and chunks) else None
    n = sum(c[1] for c in chunks)
    t.usage = {"completion_tokens": n, "prompt_tokens": prompt}
    return t


def test_latency_runs_from_due_time_not_from_send():
    # due at 100.0 but sent 0.4 s late: the wait counts
    t = _turn(100.0, 100.4, [(100.5, 1), (100.9, 4)], 5)
    e = loadgen.end_to_end([t], 99.0, 110.0)
    assert e["ttft_p50_ms"] == pytest.approx(500.0)
    assert e["late_p95_ms"] == pytest.approx(400.0)
    # (last - first) / (tokens - 1)
    assert e["tpot_p95_ms"] == pytest.approx(400.0 / 4)
    assert (e["attempted"], e["failed"]) == (1, 0)


def test_sample_is_turns_due_in_window_and_tokens_are_those_streamed_in_it():
    before = _turn(95.0, 95.0, [(96.0, 1), (101.0, 3)], 4)      # due before, streams inside
    inside = _turn(102.0, 102.0, [(103.0, 2), (111.0, 2)], 4)   # due inside, ends after
    short = _turn(104.0, 104.0, [(104.5, 1)], 4)                # came back short: failed
    hung = _turn(105.0, 105.0, [], 4, done=False)               # never answered: failed
    e = loadgen.end_to_end([before, inside, short, hung], 100.0, 110.0)
    assert (e["attempted"], e["failed"]) == (3, 2)
    assert e["n_ttft"] == 1 and e["ttft_p50_ms"] == pytest.approx(1000.0)
    # tokens stamped inside [100, 110): 3 (before) + 2 (inside) + 1 (short)
    assert e["out_tok_s"] == pytest.approx(6 / 10.0)


def test_a_wrong_token_count_is_a_failure():
    t = _turn(1.0, 1.0, [(1.1, 1), (1.2, 2)], 4)
    assert not t.ok
    t = _turn(1.0, 1.0, [(1.1, 1), (1.2, 3)], 4)
    assert t.ok
    t.usage["prompt_tokens"] = 6  # the frontend tokenised to another length
    assert not t.ok


def test_words_round_trip():
    ids = [1, 31999, 7]
    assert loadgen.ids_of(loadgen.text_of(ids)) == ids
    assert loadgen.ids_of(" t5 t6") == [5, 6]


SPEC = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32, "max": 1024}


def test_draws_are_independent_clipped_and_fill_the_span():
    rng = np.random.default_rng(5)
    a = drawn_lengths(SPEC, 2000, rng)
    assert min(a) >= 32 and max(a) <= 1024
    assert 235 <= sorted(a)[1000] <= 280
    assert drawn_lengths(SPEC, 2000, np.random.default_rng(5)) == a
    g = drawn_gaps({"process": "poisson"}, 2000, 500.0, rng)
    assert sum(g) == pytest.approx(500.0)
    # exponential gaps: coefficient of variation 1, not the stratified ~0.9
    # with its largest gap cut off; counts per bin disperse like Poisson's
    assert 0.9 <= np.std(g) / np.mean(g) <= 1.1
    counts = np.histogram(np.cumsum(g), bins=100, range=(0, 500))[0]
    assert 0.7 <= counts.var() / counts.mean() <= 1.3
    burst = drawn_gaps({"process": "gamma", "cv": 2.5}, 2000, 500.0, rng)
    assert sum(burst) == pytest.approx(500.0) and 2.0 <= np.std(burst) / np.mean(burst) <= 3.0
    with pytest.raises(ValueError):
        drawn_gaps({"process": "weibull"}, 4, 1.0, rng)


def test_a_seed_replays_the_same_trace_and_only_swaps_neighbours():
    p = {"rate_rps": 4.0, "arrival": {"process": "poisson"}, "prompt_tokens": SPEC,
         "output_tokens": SPEC, "lead_in_s": 5, "shape_seed": 3}
    a = open_loop.generate(p, 1, 20, 1000)["chains"]
    b = open_loop.generate(p, 2**31 + 5, 20, 1000)["chains"]
    assert len(a) == len(b) == 100
    # the same arrival times for every seed; sizes move between neighbours only
    assert [c["due_s"] for c in a] == [c["due_s"] for c in b]
    for key in (lambda c: len(c["turns"][0]["user_ids"]), lambda c: c["turns"][0]["max_tokens"]):
        la, lb = [key(c) for c in a], [key(c) for c in b]
        # lead-in (20 requests) and window are drawn apart, blocks of 4 in each
        assert la != lb and all(sorted(la[i:i + 4]) == sorted(lb[i:i + 4])
                                for i in range(0, 100, 4))
    assert a[0]["due_s"] == 0.0 and a[20]["due_s"] == pytest.approx(5.0)
    assert a == open_loop.generate(p, 1, 20, 1000)["chains"]
    assert a[0]["turns"][0]["user_ids"] != b[0]["turns"][0]["user_ids"]
    # another shape_seed is another trace
    c = open_loop.generate(dict(p, shape_seed=4), 1, 20, 1000)["chains"]
    assert [x["due_s"] for x in c] != [x["due_s"] for x in a]


def test_a_traffic_mix_can_extend_another(tmp_path, monkeypatch):
    import run

    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "base.json").write_text(
        '{"kind": "open_loop", "rate_rps": 1.0, "lead_in_s": 10}')
    (tmp_path / "traffic" / "faster.json").write_text('{"extends": "base", "rate_rps": 2.5}')
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    assert run.read_traffic("faster") == {"kind": "open_loop", "rate_rps": 2.5, "lead_in_s": 10}
    assert run.read_traffic("base")["rate_rps"] == 1.0
