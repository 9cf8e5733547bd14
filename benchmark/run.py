#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <name> ... --rehearse   (CPU, tiny sizes:
        debugs the harness, measures nothing, prints no metric value)

This process never imports jax: a chip belongs to one process, and that one is
benchmark/serve.py. It reads the cell, its configuration file and its traffic
file (found by the names in BENCHMARK.json; no cell is named in this code),
starts serve.py and `python -m dynamo_tpu.frontend --router-mode kv` as
children, is itself the open-loop generator and the HTTP client, and prints
the one result line last. `setup_s` runs from the start of this process to
the first due request.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "layers")]  # loadgen, generators/, costs, readers

import loadgen  # noqa: E402
import rehearsal  # noqa: E402

CHILDREN: list = []


def log(msg: str) -> None:
    print(f"[run +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


class Failed(Exception):
    pass


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_generator(kind: str):
    """generators/<kind>.py, imported as a package member (they share
    generators/common.py)."""
    return importlib.import_module(f"generators.{kind}")


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def read_traffic(name: str) -> dict:
    """traffic/<name>.json; a mix that names another under `extends` is that
    mix with its own keys laid over it (the same lengths at another rate)."""
    own = read_json(os.path.join(HERE, "traffic", name + ".json"))
    if "extends" not in own:
        return own
    return {**read_traffic(own.pop("extends")), **own}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body=None, timeout: float = 60):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class Child:
    def __init__(self, name: str, argv: list, env: dict, run_dir: str):
        self.name = name
        self.log_path = os.path.join(run_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=self._log,
                                     stderr=subprocess.STDOUT, start_new_session=True)
        CHILDREN.append(self)

    def tail(self, n: int = 3000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def stop(self, grace: float = 10.0) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=grace)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        self._log.close()


def wait_for(what: str, cond, timeout: float, watch: list) -> None:
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        try:
            if cond():
                return
        except Exception:
            pass
        for c in watch:
            if c.proc.poll() is not None:
                raise Failed(f"{c.name} exited {c.proc.returncode} while waiting for "
                             f"{what}\n--- {c.log_path} ---\n{c.tail()}")
        time.sleep(0.1)
    raise Failed(f"{what}: not within {timeout:.0f}s" + "".join(
        f"\n--- {c.log_path} ---\n{c.tail()}" for c in watch))


def write_tokenizer(path: str, vocab: int) -> None:
    """A WordLevel tokenizer.json whose every id decodes to a word: the
    stream then carries a non-empty chunk for every sampled token, which the
    byte tokenizer (ids >= 256 decode to nothing) does not."""
    with open(path, "w") as f:
        json.dump({
            "version": "1.0", "truncation": None, "padding": None,
            "added_tokens": [], "normalizer": None,
            "pre_tokenizer": {"type": "WhitespaceSplit"},
            "post_processor": None, "decoder": None,
            "model": {"type": "WordLevel",
                      "vocab": {loadgen.word(i): i for i in range(vocab)},
                      "unk_token": loadgen.word(0)},
        }, f)


def parse_args(argv=None):
    p = argparse.ArgumentParser("benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0, choices=[0, 1])
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--rate-scale", type=float, default=1.0,
                   help="sweeps only: multiply the traffic file's rate")
    return p.parse_args(argv)


def run(args) -> dict:
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise Failed(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = read_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = read_traffic(cell["traffic"])
    chips = int(cell["chips"])
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        raise Failed("the program (dynamo_tpu/) is not beside the benchmark")

    model = dict(cfg["model"])
    divisor = 1
    if args.rehearse:
        reh = rehearsal.rehearsal_sizes(cfg, HERE)
        model, divisor = reh["model"], reh["length_divisor"]
    vocab = int(model["vocab_size"])

    run_dir = os.path.join(ROOT, ".bench_runs", args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(os.path.join(run_dir, "discovery"))
    tok_path = os.path.join(run_dir, "tokenizer.json")
    write_tokenizer(tok_path, vocab)

    if args.rate_scale != 1.0:  # every generator names its rate rate_rps
        traffic["rate_rps"] = float(traffic["rate_rps"]) * args.rate_scale
    gen = load_generator(traffic["kind"])
    sched = gen.generate(traffic, args.seed, args.seconds, vocab, divisor)
    chains = sched["chains"]
    log(f"cell {cell['name']}: config {cell['config']} ({cfg_entry['file']}), traffic "
        f"{cell['traffic']} kind={traffic['kind']}, chips {chips}, {len(chains)} chains, "
        f"{sum(len(c['turns']) for c in chains)} turns scheduled")

    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    fenv = dict(env, JAX_PLATFORMS="cpu")  # the frontend never needs a chip
    fenv.pop("XLA_FLAGS", None)
    if args.rehearse:
        env.update(JAX_PLATFORMS="cpu", DYN_FUSED_MIXED="1",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    else:
        env["JAX_PLATFORMS"] = "tpu"  # no fallback: without a chip JAX raises
        env.pop("XLA_FLAGS", None)

    http_port, status_port = free_port(), free_port()
    base = f"http://127.0.0.1:{http_port}"
    serve = Child("serve", [
        sys.executable, os.path.join(HERE, "serve.py"), "--config",
        os.path.join(ROOT, cfg_entry["file"]), "--run-dir", run_dir,
        "--seed", str(args.seed), "--chips", str(chips), "--trace", str(args.trace),
        "--tokenizer", tok_path] + (["--rehearse"] if args.rehearse else []),
        env, run_dir)
    frontend = Child("frontend", [
        sys.executable, "-m", "dynamo_tpu.frontend", "--router-mode", "kv",
        "--http-host", "127.0.0.1", "--http-port", str(http_port),
        "--status-port", str(status_port),
        "--discovery-backend", "file",
        "--discovery-root", os.path.join(run_dir, "discovery")], fenv, run_dir)

    ready_path = os.path.join(run_dir, "ready.json")
    wait_for("serve.py ready", lambda: os.path.exists(ready_path), 1100, [serve, frontend])
    ready = read_json(ready_path)
    log(f"serve ready in {ready['ready_s']:.1f}s: device {ready['device']}, "
        f"replicas {[{k: round(v, 1) for k, v in r.items() if k.endswith('_s')} for r in ready['replicas']]}")
    log("device_report " + json.dumps(ready["device_report"]))
    log("reference check " + json.dumps(ready["correct"]))
    name = ready["model"]

    def listed() -> bool:
        return name in [m["id"] for m in http_json(f"{base}/v1/models", timeout=10).get("data", [])]

    wait_for(f"{name} in /v1/models", listed, 120, [serve, frontend])

    def all_routable() -> bool:
        return http_json(f"http://127.0.0.1:{status_port}/debug/fleet",
                         timeout=10).get("n_workers", 0) >= chips

    try:
        wait_for("every replica in the fleet view", all_routable, 15, [serve, frontend])
    except Failed:
        log("fleet view did not show every replica within 15s; going on")

    # over HTTP: two identical greedy requests return identical tokens
    probe = {"model": name, "prompt": loadgen.text_of(list(range(1, 25))),
             "max_tokens": 8, "temperature": 0.0, "ignore_eos": True}
    a = http_json(f"{base}/v1/completions", probe, timeout=300)
    b = http_json(f"{base}/v1/completions", probe, timeout=300)
    ta, tb = a["choices"][0]["text"], b["choices"][0]["text"]
    identical = ta == tb and len(loadgen.ids_of(ta)) == 8
    log(f"http greedy repeat identical={identical} ({len(loadgen.ids_of(ta))} tokens)")

    async def drive():
        t_offer = time.monotonic() + 0.3
        w0 = t_offer + float(traffic["lead_in_s"])
        w1 = w0 + args.seconds
        off = time.time() - time.monotonic()
        with open(os.path.join(run_dir, "window.json.tmp"), "w") as f:
            json.dump({"t0_wall": w0 + off, "t1_wall": w1 + off}, f)
        os.replace(os.path.join(run_dir, "window.json.tmp"),
                   os.path.join(run_dir, "window.json"))
        turns = await loadgen.play(chains, f"{base}/v1/completions", name, t_offer,
                                   w1, float(traffic["drain_limit_s"]))
        return turns, t_offer, w0, w1, off

    turns, t_offer, w0, w1, off = asyncio.run(drive())
    setup_s = t_offer - T0
    e2e = loadgen.end_to_end(turns, w0, w1)
    log(f"window {args.seconds:.0f}s: sample {e2e['attempted']} turns due inside, "
        f"{e2e['failed']} failed, {e2e['n_ttft']} TTFT and {e2e['n_tpot']} TPOT samples; "
        f"{len(turns)} turns offered in all; errors {e2e['errors']}")
    log("client " + json.dumps({k: v for k, v in e2e.items() if k != "errors"}))
    log("in flight through the window (1 s steps): "
        + " ".join(map(str, loadgen.in_flight_series(turns, w0, w1))))

    routing = None
    try:
        routing = http_json(f"http://127.0.0.1:{status_port}/debug/routing?last_n=1024",
                            timeout=30)
    except Exception as e:
        log(f"/debug/routing unavailable: {e}")
    counters_path = os.path.join(run_dir, "counters.json")
    wait_for("serve.py counters", lambda: os.path.exists(counters_path), 60, [serve])
    open(os.path.join(run_dir, "stop"), "w").close()
    final_path = os.path.join(run_dir, "final.json")
    wait_for("serve.py final", lambda: os.path.exists(final_path), 240, [])
    try:
        serve.proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        log("serve.py slow to exit; killing")
    counters, final = read_json(counters_path), read_json(final_path)
    if final.get("trace_error"):
        log("trace reduction failed: " + final["trace_error"])

    ctx = {
        "e2e": e2e, "turns": turns, "w0": w0, "w1": w1, "w0_wall": w0 + off,
        "w1_wall": w1 + off, "seconds": args.seconds, "ready": ready,
        "counters": counters, "final": final, "trace": final.get("trace"),
        "routing": routing, "config": cfg, "model": model, "chips": chips,
        "percentile": loadgen.percentile, "here": HERE,
    }
    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            reader = load_module(os.path.join(HERE, "layers", m["name"] + ".py"),
                                 "layer_" + m["name"].replace(".", "_").replace("-", "_"))
            try:
                v = reader.read(ctx)
            except Exception as e:
                log(f"reader {m['name']} failed: {type(e).__name__}: {e}")
                v = None
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in bench["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            if m["name"] not in values:
                raise Failed(f"end-to-end metric {m['name']} has no value "
                             f"(sample {e2e['attempted']}, failed {e2e['failed']})")
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    peak = max((d.get("peak_bytes_in_use", 0) for d in final["memory"].values()), default=0)
    device = dict(ready["device"], memory_peak_bytes=int(peak))
    result = {
        "correct": bool(identical and all(c["ok"] for c in ready["correct"])),
        "attempted": e2e["attempted"], "failed": e2e["failed"],
        "metrics": metrics, "device": device,
    }
    tr = final.get("trace")
    if args.trace and tr and tr.get("busy_s"):
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"][:10],
                               "idle_gaps": tr["idle_gaps"][:10]}
        log("modules " + json.dumps({k: {"n": v["n"], "median_ms": v["median_ms"],
                                         "total_s": v["total_s"]}
                                     for k, v in tr["modules"].items()}))
    # every number that decided `correct`, beside its limit: last in the line
    result["compared"] = {**ready.get("compared", {}),
                          "http_greedy_repeat_differs": [int(not identical), 0]}
    return result


def main() -> int:
    args = parse_args()
    rc, result = 1, None
    try:
        result = run(args)
        rc = 0
    except Failed as e:
        log(f"FAILED: {e}")
    except Exception as e:
        import traceback

        log("FAILED: " + "".join(traceback.format_exception(e))[-3000:])
    finally:
        for c in reversed(CHILDREN):
            c.stop()
    if rc != 0 or result is None:
        return rc or 1
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        # a rehearsal measures nothing: names only, no value of any metric
        print(json.dumps({"rehearsal": True, "correct": result["correct"],
                          "attempted": result["attempted"], "failed": result["failed"],
                          "metric_names": sorted(result["metrics"]),
                          "device": {k: result["device"][k] for k in ("platform", "kind", "count")}}),
              flush=True)
        return 0 if result["correct"] and result["attempted"] > 0 else 1
    if result["device"]["platform"] != "tpu":
        log("not a TPU: no result")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
