"""Fast replica spin-up (SURVEY.md §5.4 — the TPU analog of the
reference's CRIU/GMS/ModelExpress stack, lib/gpu_memory_service/README.md):
a restarted worker must (a) load weights from the orbax snapshot instead of
re-parsing safetensors and (b) reuse persisted XLA executables instead of
recompiling. The recompile check is exact: a warm process must add ZERO new
entries to the persistent compilation cache."""

import json
import os
import subprocess
import sys

from dynamo_tpu.models.config import get_config
from tests.test_weights import _write_hf_checkpoint

_SCRIPT = r"""
import json, os, sys, time
t0 = time.time()
import dynamo_tpu
from dynamo_tpu.worker import build_runner, parse_args

snap_dir, ckpt_dir = sys.argv[1:3]
# the cache is placed from outside (JAX_COMPILATION_CACHE_DIR), as for a
# deployed worker
assert dynamo_tpu.enable_compilation_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
warm = os.path.isdir(snap_dir) and bool(os.listdir(snap_dir))
args = parse_args([
    "--checkpoint", ckpt_dir, "--orbax-cache", snap_dir,
    "--num-pages", "32", "--page-size", "4", "--max-seq-len", "32",
])
runner, config = build_runner(args)
built = time.time() - t0
# exercise the compiled surface a serving worker hits: one prefill bucket,
# one decode dispatch (sample fused), one single-token sample
s = {"temperature": [0.0], "top_k": [0], "top_p": [1.0], "seeds": [0]}
logits = runner.prefill(list(range(8)), 0, [0, 1, 2], 0)
tok = runner.sample_one(logits, s, 1)
runner.decode_multi(2, [tok], [8], [[0, 1, 2]], s, 2)
print(json.dumps({
    "warm_params": warm,
    "build_s": built,
    "ready_s": time.time() - t0,
}))
"""


def _run(cache_dir, snap_dir, ckpt_dir):
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, snap_dir, ckpt_dir],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_ENABLE_COMPILATION_CACHE="1",  # conftest turns it off
                 JAX_COMPILATION_CACHE_DIR=cache_dir),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_restart_warm_start_skips_parse_and_recompile(tmp_path):
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    _write_hf_checkpoint(ckpt, get_config("tiny"))
    cache = str(tmp_path / "xla-cache")
    snap = str(tmp_path / "snap")

    cold = _run(cache, snap, str(ckpt))
    assert not cold["warm_params"], "first run must be cold"
    assert os.path.isdir(snap) and os.listdir(snap), "snapshot must be saved"
    entries = set(os.listdir(cache))
    assert entries, "compilation cache must be populated"

    warm = _run(cache, snap, str(ckpt))
    assert warm["warm_params"], "second run must load the orbax snapshot"
    # the decisive fast-resume check: zero NEW executables compiled
    assert set(os.listdir(cache)) == entries, (
        "warm start must not recompile any program"
    )


def test_compilation_cache_rule(monkeypatch, tmp_path):
    """One rule for every JAX entry point: JAX_COMPILATION_CACHE_DIR set →
    no directory is set in code (JAX reads the variable itself); unset →
    one fixed path inside the checkout, never a temp name."""
    import jax

    import dynamo_tpu

    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert dynamo_tpu.enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0

    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".jax_cache")
    assert dynamo_tpu.enable_compilation_cache() == fixed
    assert updates["jax_compilation_cache_dir"] == fixed
