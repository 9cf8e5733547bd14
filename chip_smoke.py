#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python chip_smoke.py              one TPU chip (run it through the chip tool)
    python chip_smoke.py --chips 4    the same drive against --tensor-parallel 4
    python chip_smoke.py --rehearse   tiny model on the CPU: debugs THIS script
                                      only, labels itself, proves nothing

Drives the README's own entry points, nothing bespoke, at the full published
widths of llama-3.2-3b (dim 3072, 28 layers, 24/8 heads, vocab 128256) in
bf16 with seeded random weights:

  1. kernel gate   scripts/tpu_parity.py — every Pallas kernel compiled by
                   Mosaic and compared to the f32 jnp reference
  2. serve         python -m dynamo_tpu.worker + python -m dynamo_tpu.frontend
                   --router-mode kv over file discovery; this process is the
                   HTTP client: /v1/models, two identical greedy completions
                   (bytes and logprobs must match), one streaming chat
                   completion (SSE chunks, finish_reason, usage), then one
                   long decode with a burst of 8 mixed-length prompts landing
                   on it, after which the worker's /metrics must show the
                   ragged family compiled and the padded mixed family unused
  3. restart       SIGTERM the worker (must drain and exit 0), start it again,
                   repeat the first request: the compile cache gains ZERO
                   entries and the answer is unchanged

This process never imports jax (a chip belongs to one process at a time);
only its children touch the device, one at a time, launched with
JAX_PLATFORMS=tpu so that JAX itself raises when there is no chip. Every
phase failure is a nonzero exit with no result line. On success the last
stdout line is {"ok": true, "device": {"platform", "kind", "count"}} with the
device as JAX reported it to the children; the line before it is the summary.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

MODEL = "llama-3.2-3b"
# one page at PS 64 is 28 x 64 x 8 x 128 x 2 B x (K, V) = 7.34 MB: 768 pages
# are 5.6 GB beside 6.4 GB of bf16 weights on a 16 GB chip
WORKER_SHAPE = ["--page-size", "64", "--num-pages", "768", "--max-seq-len", "4096"]
REHEARSE_MODEL = "tiny"
REHEARSE_SHAPE = ["--page-size", "16", "--num-pages", "256", "--max-seq-len", "1024"]


DEADLINE_S = 1150  # the chip check allows 1200 s, compilation included


class PhaseFailed(Exception):
    pass


T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


# -- children -----------------------------------------------------------------


class Child:
    """One child process with its output in a log file. Every child is
    registered in CHILDREN and killed at exit, whatever happened."""

    def __init__(self, name: str, argv: list, env: dict):
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        CHILDREN.append(self)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def kill(self) -> None:
        if self.alive():
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        self._log.close()


CHILDREN: list = []


def fail(phase: str, why: str, child: Child = None) -> None:
    if child is not None:
        why += f"\n--- tail of {child.log_path} ---\n{child.tail()}"
    raise PhaseFailed(f"{phase}: {why}")


def run_to_end(phase: str, name: str, argv: list, env: dict, timeout: float) -> str:
    """Run a child to completion; its stdout+stderr as text. Nonzero exit
    or timeout fails the phase."""
    child = Child(name, argv, env)
    try:
        rc = child.proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        fail(phase, f"{name} still running after {timeout:.0f}s", child)
    if rc != 0:
        fail(phase, f"{name} exited {rc}", child)
    return child.tail(1 << 20)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- http ---------------------------------------------------------------------


def http(url: str, body: dict = None, timeout: float = 600.0):
    """(status, body bytes); a refused connection is (0, b"")."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except (urllib.error.URLError, ConnectionError, socket.timeout):
        return 0, b""


def wait_for(phase: str, what: str, probe, child: Child, timeout: float):
    """Poll probe() until it returns a truthy value; fail when the child
    dies or the deadline passes."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not child.alive():
            fail(phase, f"{child.name} exited {child.proc.returncode} "
                        f"while waiting for {what}", child)
        got = probe()
        if got:
            return got
        time.sleep(0.5)
    fail(phase, f"timed out after {timeout:.0f}s waiting for {what}", child)


def metric(text: str, name: str, **labels) -> float:
    """One sample of a Prometheus text exposition (None when absent)."""
    for line in text.splitlines():
        if not line.startswith(name + "{"):
            continue
        head, _, value = line.rpartition(" ")
        if all(f'{k}="{v}"' in head for k, v in labels.items()):
            return float(value)
    return None


# -- phases -------------------------------------------------------------------


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def phase_gate(env: dict, rehearse: bool) -> dict:
    argv = [sys.executable, "scripts/tpu_parity.py"]
    if rehearse:
        argv.append("--interpret")
    out = run_to_end("kernel gate", "gate", argv, env, timeout=900)
    result = json.loads(out.strip().splitlines()[-1])
    if not result["ok"] or result["interpreted"] != rehearse:
        fail("kernel gate", f"gate reported {result}")
    for row in result["checks"]:
        log(f"  gate  {row['name']}: max|Δ|={row['max_abs_err']:.4f}")
    return result


def phase_native(env: dict) -> dict:
    out = run_to_end(
        "native libraries", "native",
        [sys.executable, "-c",
         "import json; from dynamo_tpu.native.build import native_report; "
         "print(json.dumps(native_report()))"],
        env, timeout=300,
    )
    report = json.loads(out.strip().splitlines()[-1])
    if shutil.which("g++") and set(report.values()) != {"c++"}:
        fail("native libraries",
             f"g++ is on PATH but a library fell back to Python: {report}")
    return report


def start_worker(tag: str, model_args: list, chips: int, env: dict, disc: str,
                 status_port: int) -> Child:
    argv = [
        sys.executable, "-m", "dynamo_tpu.worker", *model_args,
        "--status-port", str(status_port),
        "--discovery-backend", "file", "--discovery-root", disc,
    ]
    if chips > 1:
        argv += ["--tensor-parallel", str(chips)]
    return Child(f"worker-{tag}", argv, env)


def worker_device(status_port: int):
    code, body = http(f"http://127.0.0.1:{status_port}/debug/device", timeout=10)
    return json.loads(body) if code == 200 else None


def check_device(dev: dict, args) -> None:
    """No hidden CPU, no hidden jnp: what the worker says it runs on."""
    want = {"platform": "cpu", "attn_impl": "jnp"} if args.rehearse else {
        "platform": "tpu", "attn_impl": "pallas"}
    want.update(fused_mixed=True, ragged_mixed=True)
    got = {k: dev.get(k) for k in want}
    if got != want:
        fail("serve", f"worker reports {got}, expected {want} ({dev})")
    shards = dev["kv_shards"]
    if (len(dev["device_ids"]) != args.chips
            or len(set(shards["devices"])) != args.chips):
        fail("serve", f"expected KV shards on {args.chips} distinct devices, "
                      f"worker reports devices {dev['device_ids']} and "
                      f"shards {shards}")


def completion(base: str, model: str, prompt: str, max_tokens: int,
               unrouted_ok: bool = False) -> dict:
    """One greedy completion with logprobs, checked for shape and finite
    values. unrouted_ok: None instead of failing while the frontend has no
    worker to route to (the restart phase polls with this very request —
    a different probe would compile shapes the first life never saw)."""
    code, body = http(f"{base}/v1/completions", {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0.0, "logprobs": 1, "ignore_eos": True,
    })
    if code != 200:
        if unrouted_ok:
            return None
        fail("serve", f"/v1/completions -> {code}: {body[:500]!r}")
    out = json.loads(body)
    choice = out["choices"][0]
    lps = choice["logprobs"]["token_logprobs"]
    if (out["usage"]["completion_tokens"] != max_tokens
            or choice["finish_reason"] != "length" or len(lps) != max_tokens
            or not all(lp == lp and -1e4 < lp <= 0.0 for lp in lps)):
        fail("serve", f"completion is not {max_tokens} tokens with finite "
                      f"logprobs: {out}")
    # what must be identical across repeats: the text and every logprob
    return {"text": choice["text"], "logprobs": choice["logprobs"]}


def stream_chat(base: str, model: str) -> dict:
    req = urllib.request.Request(
        f"{base}/v1/chat/completions",
        data=json.dumps({
            "model": model, "stream": True, "max_tokens": 12,
            "temperature": 0.0, "ignore_eos": True,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": "Say something."}],
        }).encode(),
        headers={"Content-Type": "application/json"},
    )
    chunks, finish, usage, done = 0, None, None, False
    with urllib.request.urlopen(req, timeout=600) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            chunk = json.loads(payload)
            chunks += 1
            for c in chunk.get("choices") or []:
                finish = c.get("finish_reason") or finish
            usage = chunk.get("usage") or usage
    if not (done and chunks >= 2 and finish == "length" and usage
            and usage["completion_tokens"] == 12):
        fail("serve", f"chat stream: chunks={chunks} finish={finish} "
                      f"usage={usage} done={done}")
    return {"chunks": chunks, "finish_reason": finish, "usage": usage}


def mixed_drive(base: str, model: str) -> dict:
    """One long decode, then ~0.4 s later a burst of 8 prompts of mixed
    lengths: chunked prefills pack against the live decode row, which is
    the only way a mixed (ragged) plan forms."""
    lengths = [32, 700, 64, 200, 450, 33, 128, 600]

    def one(n_prompt: int, max_tokens: int) -> int:
        prompt = ("The quick brown fox jumps over the lazy dog. " * 20)[:n_prompt]
        code, body = http(f"{base}/v1/completions", {
            "model": model, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0.0, "ignore_eos": True,
        })
        if code != 200:
            fail("serve", f"mixed drive request -> {code}: {body[:500]!r}")
        return json.loads(body)["usage"]["completion_tokens"]

    with concurrent.futures.ThreadPoolExecutor(max_workers=9) as pool:
        long_decode = pool.submit(one, 48, 160)
        time.sleep(0.4)
        burst = [pool.submit(one, n, 8) for n in lengths]
        got = [f.result() for f in burst] + [long_decode.result()]
    if got != [8] * len(lengths) + [160]:
        fail("serve", f"mixed drive completion tokens {got}")
    return {"prompt_lengths": lengths, "completion_tokens": got}


def families(status_port: int) -> dict:
    """Compiled variants and compile seconds per step-function family,
    as the worker's /metrics exposes them."""
    code, body = http(f"http://127.0.0.1:{status_port}/metrics", timeout=30)
    if code != 200:
        fail("serve", f"worker /metrics -> {code}")
    text = body.decode()
    return {
        fam: {
            "variants": metric(text, "dynamo_compile_variants", family=fam),
            "compile_s": metric(text, "dynamo_compile_seconds_total", family=fam),
        }
        for fam in ("forward", "decode_loop", "ragged", "mixed")
    }


def check_families(status_port: int) -> dict:
    fams = families(status_port)
    v = {k: f["variants"] for k, f in fams.items()}
    if not (v["ragged"] and v["ragged"] >= 1 and v["decode_loop"] >= 1
            and v["forward"] >= 1 and v["mixed"] == 0):
        fail("serve", f"compile families after the mixed drive: {fams} "
                      "(want ragged/decode_loop/forward >= 1, mixed == 0)")
    return fams


def run(args) -> dict:
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        fail("start", f"the program is not beside this script ({ROOT})")
    os.makedirs(LOG_DIR, exist_ok=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # the persistent compile cache is part of what phase 3 checks
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    if args.rehearse:
        # the CPU stands in for the chip; fused+ragged dispatch is forced
        # on because the CPU default leaves it off
        env.update(JAX_PLATFORMS="cpu", DYN_FUSED_MIXED="1")
        env.pop("XLA_FLAGS", None)
        if args.chips > 1:
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.chips}")
    else:
        # no fallback: without a chip JAX raises in the first child
        env["JAX_PLATFORMS"] = "tpu"
    cache_dir = env.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    model, shape = ((REHEARSE_MODEL, REHEARSE_SHAPE) if args.rehearse
                    else (MODEL, WORKER_SHAPE))
    model_args = ["--model", model, *shape]
    summary = {
        "rehearsal": args.rehearse, "chips": args.chips, "model": model,
        "cache_dir": cache_dir,
        "cache_entries": {"start": cache_entries(cache_dir)},
    }

    log("phase 0: native libraries")
    summary["native"] = phase_native(env)
    log(f"  {summary['native']}")

    log("phase 1: kernel gate (compiled Pallas vs f32 reference)")
    gate = phase_gate(env, args.rehearse)
    summary["gate"] = {r["name"]: round(r["max_abs_err"], 5)
                       for r in gate["checks"]}
    summary["cache_entries"]["after_gate"] = cache_entries(cache_dir)
    device = gate["device"]

    log("phase 2: serve (worker + frontend, HTTP client)")
    disc = tempfile.mkdtemp(prefix="chip_smoke_disc_")
    status_port, http_port = free_port(), free_port()
    base = f"http://127.0.0.1:{http_port}"
    t_start = time.monotonic()
    worker = start_worker("cold", model_args, args.chips, env, disc, status_port)
    dev = wait_for("serve", "the worker's /debug/device",
                   lambda: worker_device(status_port), worker, timeout=600)
    summary["ready_s"] = {"cold": round(time.monotonic() - t_start, 1)}
    check_device(dev, args)
    log(f"  worker ready in {summary['ready_s']['cold']}s on "
        f"{dev['platform']} {dev['device_kind']!r} {dev['device_ids']}: "
        f"attn_impl={dev['attn_impl']} fused_mixed={dev['fused_mixed']} "
        f"ragged_mixed={dev['ragged_mixed']}")
    if (dev["platform"], dev["device_kind"]) != (
            device["platform"], device["kind"]):
        fail("serve", f"worker device {dev['platform']}/{dev['device_kind']} "
                      f"differs from the gate's {device}")
    frontend = Child("frontend", [
        sys.executable, "-m", "dynamo_tpu.frontend", "--router-mode", "kv",
        "--http-host", "127.0.0.1", "--http-port", str(http_port),
        "--discovery-backend", "file", "--discovery-root", disc,
    ], env)

    def model_listed():
        code, body = http(f"{base}/v1/models", timeout=10)
        return code == 200 and model in [
            m["id"] for m in json.loads(body).get("data", [])]

    wait_for("serve", f"{model} in /v1/models", model_listed, frontend, 120)
    log("  /v1/models lists the model")
    prompt = "The capital of France is"
    t_req = time.monotonic()
    first = completion(base, model, prompt, 16)
    summary["first_request_s"] = {"cold": round(time.monotonic() - t_req, 1)}
    second = completion(base, model, prompt, 16)
    if first != second:
        fail("serve", f"identical greedy requests differ:\n{first}\n{second}")
    log(f"  two identical greedy completions match ({len(first['text'])} "
        f"chars, 16 logprobs)")
    summary["chat_stream"] = stream_chat(base, model)
    log(f"  chat stream: {summary['chat_stream']}")
    summary["mixed_drive"] = mixed_drive(base, model)
    summary["families"] = check_families(status_port)
    log(f"  mixed drive served; families {summary['families']}")
    dev = worker_device(status_port)
    summary["worker"] = dev
    summary["cache_entries"]["after_serve"] = cache_entries(cache_dir)
    if summary["cache_entries"]["after_serve"] == 0:
        fail("serve", f"the worker compiled {summary['families']} but the "
                      f"compile cache at {cache_dir} is empty: the restart "
                      "check would pass vacuously")

    log("phase 3: restart (SIGTERM, warm start, same answer, no new compile)")
    worker.proc.send_signal(signal.SIGTERM)
    try:
        rc = worker.proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        fail("restart", "worker did not exit within 120s of SIGTERM", worker)
    if rc != 0:
        fail("restart", f"worker exited {rc} on SIGTERM, expected 0", worker)
    t_start = time.monotonic()
    worker2 = start_worker("warm", model_args, args.chips, env, disc,
                           status_port)
    wait_for("restart", "the restarted worker's /debug/device",
             lambda: worker_device(status_port), worker2, timeout=600)
    summary["ready_s"]["warm"] = round(time.monotonic() - t_start, 1)

    t_req = time.monotonic()
    again = wait_for(
        "restart", "the frontend to route to the restarted worker",
        lambda: completion(base, model, prompt, 16, unrouted_ok=True),
        worker2, timeout=180)
    summary["first_request_s"]["warm"] = round(time.monotonic() - t_req, 1)
    if again != first:
        fail("restart", f"answer changed across the restart:\n{first}\n{again}")
    summary["cache_entries"]["after_restart"] = cache_entries(cache_dir)
    new = (summary["cache_entries"]["after_restart"]
           - summary["cache_entries"]["after_serve"])
    if new != 0:
        fail("restart", f"warm restart added {new} compile cache entries "
                        f"({summary['cache_entries']})")
    summary["families_warm"] = families(status_port)
    log(f"  warm ready in {summary['ready_s']['warm']}s (cold "
        f"{summary['ready_s']['cold']}s), first request "
        f"{summary['first_request_s']['warm']}s (cold "
        f"{summary['first_request_s']['cold']}s), answer unchanged, 0 new "
        f"cache entries of {summary['cache_entries']['after_restart']}")
    return {"summary": summary, "device": device}


def main(argv=None) -> int:
    p = argparse.ArgumentParser("chip_smoke")
    p.add_argument("--chips", type=int, default=1,
                   help="tensor-parallel degree of the worker (run with the "
                        "chip tool's --chips 4)")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny model on the CPU, interpreted kernels: debugs "
                        "this script, proves nothing about the chip")
    args = p.parse_args(argv)

    def on_deadline(signum, frame):
        raise PhaseFailed(f"not finished after {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    try:
        result = run(args)
    except PhaseFailed as e:
        print(f"chip_smoke FAILED — {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for child in CHILDREN:
            child.kill()
    label = "REHEARSAL (CPU, proves nothing about the chip) " if args.rehearse else ""
    print(f"{label}summary: {json.dumps(result['summary'])}", flush=True)
    if args.rehearse:
        print("REHEARSAL passed — not a chip result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": result["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
