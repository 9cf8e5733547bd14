"""The percentile, due-time and window arithmetic on hand-made samples, and
the generators' promise that every seed replays the same trace."""

import json
import math
import os

import pytest

import loadgen
import numpy as np

from generators import open_loop
from generators.common import drawn_gaps, drawn_lengths

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


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


def _mixes():
    d = os.path.join(BENCH, "traffic")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


def _run_seconds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["run_seconds"]


@pytest.mark.parametrize("mix", _mixes())
def test_every_traffic_file_has_what_its_generator_reads_and_names_its_knee(mix):
    """With `extends` laid over its base: the mix's own generator draws from the
    file alone (a key it reads and does not find is a KeyError here), the rate is
    positive, and the `_why` names the knee that rate is a share of."""
    import run

    t = run.read_traffic(mix)
    assert float(t["rate_rps"]) > 0
    assert run.load_generator(t["kind"]).generate(t, 7, _run_seconds(), 1000)["chains"]
    assert "knee" in t["_why"], f"{mix}: the _why names no knee"


def _clipped_mean(spec):
    """Mean of a log-normal length clamped to [min, max]."""
    mu, s = math.log(spec["median"]), float(spec["sigma"])
    lo, hi = float(spec["min"]), float(spec["max"])
    cdf = lambda z: 0.5 * (1.0 + math.erf(z / math.sqrt(2.0)))
    za, zb = (math.log(lo) - mu) / s, (math.log(hi) - mu) / s
    return lo * cdf(za) + math.exp(mu + s * s / 2) * (cdf(zb - s) - cdf(za - s)) + hi * (1.0 - cdf(zb))


def window_stats(traffic, seconds, shape_seed):
    """The window the mix's generator draws at `shape_seed` (any `--seed`: a seed
    only swaps neighbours), and whether it is typical by chat-steady.json's rule:
    arrivals per 5 s with variance / mean in 0.8-1.2 (the variance over the counts
    as they are, in whole numbers so that 0.8 itself is inside), mean prompt within
    5 % and mean output within 3 % of the clipped distribution's."""
    import run

    t = dict(traffic, shape_seed=shape_seed)
    lead = float(t["lead_in_s"])
    window = [c for c in run.load_generator(t["kind"]).generate(t, 0, seconds, 2)["chains"]
              if c["due_s"] >= lead]
    counts = np.bincount([int((c["due_s"] - lead) // 5.0) for c in window],
                         minlength=math.ceil(seconds / 5.0)).tolist()
    n, total = len(counts), sum(counts)
    num, den = n * sum(c * c for c in counts) - total * total, n * total  # variance / mean = num / den
    plens = [len(c["prefix_ids"]) + len(c["turns"][0]["user_ids"]) for c in window]
    olens = [c["turns"][0]["max_tokens"] for c in window]
    p_off = float(np.mean(plens)) / _clipped_mean(t["prompt_tokens"]) - 1.0
    o_off = float(np.mean(olens)) / _clipped_mean(t["output_tokens"]) - 1.0
    return {"requests": len(window), "counts_per_5s": counts, "dispersion": num / den,
            "prompt_off": p_off, "output_off": o_off,
            "prompt_tokens": sum(plens), "output_tokens": sum(olens),
            "typical": 4 * den <= 5 * num <= 6 * den and abs(p_off) <= 0.05 and abs(o_off) <= 0.03}


# the steady mixes that say they replay the FIRST typical window of shape_seed 1, 2, 3, ...
# A mix that wants another realisation (a burst, a saturated cell) is not listed.
FIRST_TYPICAL = ["chat-steady", "chat-steady-mistral4", "reasoning-steady-jamba2", "agent-steady-mimo2"]


@pytest.mark.parametrize("mix", FIRST_TYPICAL)
def test_a_steady_mix_replays_the_first_typical_window(mix):
    import run

    t = run.read_traffic(mix)
    stats = [window_stats(t, _run_seconds(), s) for s in range(1, t["shape_seed"] + 1)]
    assert [s["typical"] for s in stats] == [False] * (t["shape_seed"] - 1) + [True], (mix, stats[-1])


# -- bytes a step moves, by architecture (costs.py) --------------------------

PHI3 = {"vocab_size": 32064, "dim": 3072, "n_layers": 32, "n_heads": 32, "n_kv_heads": 32,
        "ffn_dim": 8192, "sliding_window": 2047}
# Moonlight-16B-A3B's published sizes (config.json, deepseek_v3), all 27 layers
MOONLIGHT = {"vocab_size": 163840, "dim": 2048, "n_layers": 27, "n_heads": 16, "n_kv_heads": 16,
             "ffn_dim": 11264, "attn_type": "mla", "kv_lora_rank": 512, "q_lora_rank": 0,
             "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "v_head_dim": 128, "n_experts": 64,
             "n_experts_active": 6, "moe_ffn_dim": 1408, "n_shared_experts": 2, "n_dense_layers": 1}


def test_cost_integers_of_a_dense_decoder_are_what_they_were():
    import costs

    assert costs.weight_stream_bytes(PHI3) == 7_444_758_528
    assert costs.kv_bytes_per_token(PHI3) == 393_216
    # an explicit head size is honoured; without experts `experts_hit` is idle
    assert costs.attn_params({**PHI3, "head_dim_override": 128}) == 4 * 3072 * 32 * 128
    assert costs.weight_stream_bytes(PHI3, experts_hit=64) == 7_444_758_528


def test_cost_integers_of_latent_attention_and_experts():
    import costs

    assert costs.attn_params(MOONLIGHT) == 13_762_560
    assert 3 * MOONLIGHT["dim"] * MOONLIGHT["ffn_dim"] == 69_206_016
    assert costs.expert_layer_params(MOONLIGHT, 0) == 17_432_576
    assert costs.expert_layer_params(MOONLIGHT, 1) - 17_432_576 == 8_650_752
    assert MOONLIGHT["dim"] * MOONLIGHT["vocab_size"] == 335_544_320
    assert costs.weight_stream_bytes(MOONLIGHT) == 5_158_207_488  # the floor: 6 experts a layer
    assert costs.weight_stream_bytes(MOONLIGHT, experts_hit=64) == 31_248_875_520
    assert costs.kv_bytes_per_token(MOONLIGHT) == 31_104
    # more experts hit, more bytes; never past all of them, never under none
    got = [costs.weight_stream_bytes(MOONLIGHT, experts_hit=n) for n in range(0, 70)]
    assert all(a < b for a, b in zip(got[:64], got[1:65])) and got[64] == got[69]
    assert costs.weight_stream_bytes(MOONLIGHT, experts_hit=-3) == got[0]
    # a compressed query: two matrices in place of one
    q = {**MOONLIGHT, "q_lora_rank": 1536}
    assert costs.attn_params(q) - costs.attn_params(MOONLIGHT) == \
        2048 * 1536 + 1536 * 16 * 192 - 2048 * 16 * 192
    # a shared expert of a stated width (Qwen2-MoE) wins over count x width
    assert costs.expert_layer_params({**MOONLIGHT, "shared_expert_ffn_dim": 5000}, 0) == \
        2048 * 64 + 3 * 2048 * 5000
    # no window on a latent cache: every live token counts
    assert costs.decode_step_bytes(MOONLIGHT, [100, 5000], experts_hit=8) == \
        costs.weight_stream_bytes(MOONLIGHT, experts_hit=8) + 5100 * 31_104
