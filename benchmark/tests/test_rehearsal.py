"""run.py --rehearse end to end on the CPU at a tiny size, through serve.py
and `python -m dynamo_tpu.frontend`, every cell of BENCHMARK.json, and one
cell that is in no BENCHMARK.json: a latent-attention expert configuration
(tests/data/fixture-mla-moe.json, with its own `rehearse` group) under
`chat-steady`, run from a scratch root that holds its own BENCHMARK.json, to
show that such a cell comes in as files and entries. A rehearsal prints names
and counts and no value of any metric; without --rehearse and without a TPU
the command fails and prints no result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _run(*extra, timeout=900, root=ROOT):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *extra],
        cwd=root, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", _cells())
def test_rehearsal(cell, trace):
    r = _run("--workload", cell, "--seed", str(2**31 + 17), "--seconds", "6",
             "--trace", str(trace), "--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    assert "runner.compiles_in_window" in last["metric_names"] if trace else \
        "setup_s" in last["metric_names"]


def test_without_a_tpu_it_fails_and_prints_no_result():
    r = _run("--workload", _cells()[0], "--seed", "1", "--seconds", "2", "--trace", "0",
             timeout=300)
    assert r.returncode != 0
    assert not r.stdout.strip().splitlines()[-1].startswith("{")


def _scratch_root(root):
    """A root with the program and the benchmark linked in and a
    BENCHMARK.json of its own: the repo's, with the fixture configuration and
    one cell of it in place of the repo's configurations and cells."""
    for name in ("dynamo_tpu", "benchmark"):
        os.symlink(os.path.join(ROOT, name), root / name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{
        "name": "fixture-mla-moe", "source": "benchmark/tests/data/fixture-mla-moe.json",
        "file": "benchmark/tests/data/fixture-mla-moe.json", "reduced": [],
        "why": "latent attention, routed and shared experts, one dense layer"}]
    bench["workloads"] = [{"name": "fixture-chat-steady", "config": "fixture-mla-moe",
                           "traffic": "chat-steady", "chips": 1, "why": "harness fixture"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture(scope="module")
def scratch_root(tmp_path_factory):
    return _scratch_root(tmp_path_factory.mktemp("bench_root"))


def _fixture_check(r) -> dict:
    line = next(ln for ln in r.stdout.splitlines() if "] reference check " in ln)
    return json.loads(line.split("reference check ", 1)[1])[0]


@pytest.mark.parametrize("trace", [0, 1])
def test_a_latent_attention_expert_cell_comes_in_as_files(scratch_root, trace):
    r = _run("--workload", "fixture-chat-steady", "--seed", str(2**31 + 17), "--seconds", "6",
             "--trace", str(trace), "--rehearse", root=scratch_root)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    # the check went through the latent-attention expert reference, which
    # followed the served picks of every position (no token left out) and
    # found each admissible, and pass two rode the padded mixed program (the
    # ragged one shuts latent attention out)
    check = _fixture_check(r)
    for name in ("logprobs", "ragged"):
        c = check[name]
        assert len(c["per_prompt"]) == 5 and c["tokens"] == sum(p["tokens"] for p in c["per_prompt"])
        assert c["inadmissible"] == 0 and c["need_max"] <= c["margin"]
        assert c["picks"] == 2 * sum(p["prompt"] + p["tokens"] - 1 for p in c["per_prompt"])
    assert check["ragged"]["calls"]["mixed"] > 0 and check["ragged"]["calls"]["ragged"] == 0
    assert "runner.compiles_in_window" in last["metric_names"] if trace else \
        "setup_s" in last["metric_names"]
    # every number compared stands beside its limit, last on standard error
    compared = [ln.split() for ln in r.stderr.strip().splitlines() if ln.startswith("compared ")]
    assert {"r0.logprobs.inadmissible", "r0.ragged.need_max", "r0.logprobs.max_abs_logprob_err",
            "http_greedy_repeat_differs"} <= {c[1] for c in compared}
    assert r.stderr.strip().splitlines()[-1].startswith("compared ")


FAULT = """# the timed path broken underneath: the program's router hands back gate
# weights 1.3 times what it computed (its picks are what they were)
import dynamo_tpu.ops.moe_dispatch as _m

_sound = _m.router_topk


def _router_topk(*a, **kw):
    weights, sel = _sound(*a, **kw)
    return weights * 1.3, sel


_m.router_topk = _router_topk
"""


def test_with_the_expert_layer_broken_underneath_it_is_not_correct(tmp_path):
    """The rest of a run as it is (no look for a chip: --rehearse), the
    program's expert layer broken through a sitecustomize.py in the scratch
    root (run.py puts its root on the children's PYTHONPATH). The first expert
    layer's picks are still the router's; its output is not what the
    reference computes for them, so the logprobs fail, and the second expert
    layer's router, fed that output, makes picks the reference's scores did
    not nearly make."""
    root = _scratch_root(tmp_path)
    (tmp_path / "sitecustomize.py").write_text(FAULT)
    r = _run("--workload", "fixture-chat-steady", "--seed", str(2**31 + 17), "--seconds", "6",
             "--trace", "0", "--rehearse", root=root)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is False
    check = _fixture_check(r)
    c = check["logprobs"]
    assert not check["ok"] and not c["ok"]
    assert c["max_abs_logprob_err"] > c["tolerance"] or c["inadmissible"] > 0
    assert any(ln.startswith("compared ") for ln in r.stderr.splitlines())
