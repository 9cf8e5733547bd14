"""run.py --rehearse end to end on the CPU at a tiny size, through serve.py
and `python -m dynamo_tpu.frontend`, every cell of BENCHMARK.json. A
rehearsal prints names and counts and no value of any metric; without
--rehearse and without a TPU the command fails and prints no result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _run(*extra, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=timeout)


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
