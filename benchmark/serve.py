"""The one process that holds the cell's chips: N one-chip replicas of the
configuration, each a ModelRunner + InferenceEngine registered with
serve_worker over file discovery and the TCP request plane, exactly as
`python -m dynamo_tpu.worker` builds them (its own argument parser and
build_engine are used, so every flag not in the configuration file is the
worker's default). run.py starts it; it never prints the result line.

Files in --run-dir, the only channel to run.py:
  ready.json     written when weights, replicas, warm-up and the reference
                 check are done (device, correctness, compile counts)
  window.json    written by run.py: wall-clock start and end of the window
  counters.json  written after the window: counters at its start and end,
                 the iterations and finished requests inside it
  stop           written by run.py: shut down
  final.json     written last: memory peak, trace reduction (traced runs)
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[serve +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- weights ---------------------------------------------------------------


def drawn_leaves(config, dtype) -> list:
    """For each leaf of `llama.init_params(config, key, dtype)`, in flatten
    order: does its value depend on the key? Read off the program's own
    jaxpr (traced at the full configuration, nothing computed): an output
    that no equation connects to the key is one the program fills (norm
    weights at 1.0 or 0.0, `router_bias`, projection biases). No list of
    leaf names is kept here."""
    import jax

    from dynamo_tpu.models import llama

    jaxpr = jax.make_jaxpr(lambda k: llama.init_params(config, k, dtype))(
        jax.random.PRNGKey(0)).jaxpr
    keyed = {id(v) for v in jaxpr.invars}
    for eqn in jaxpr.eqns:
        if any(id(v) in keyed for v in eqn.invars):
            keyed.update(id(v) for v in eqn.outvars)
    return [id(v) in keyed for v in jaxpr.outvars]


def params_program(config, dtype):
    """`build(key) -> tree`: the function make_params jits. A leaf the
    program draws: normal x fan_in^-0.5 (fan_in `shape[-2]`, `embed`:
    `shape[-1]`), every leading axis (layers, experts) mapped over with a key
    split for it, so one `[in, out]` f32 temporary exists at a time. A leaf
    the program fills (`drawn_leaves`): the program's own value, taken from
    `init_params` inside the same program, where XLA drops the draws nobody
    reads."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    shapes = jax.eval_shape(
        lambda: llama.init_params(config, jax.random.PRNGKey(0), dtype))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    drawn = drawn_leaves(config, dtype)

    def build(key):
        fills = jax.tree_util.tree_leaves(
            llama.init_params(config, jax.random.PRNGKey(0), dtype))
        out = []
        for i, (path, sd) in enumerate(leaves):
            if not drawn[i]:
                out.append(fills[i])
                continue
            k = jax.random.fold_in(key, i)
            name = getattr(path[-1], "key", str(path[-1]))
            fan_in = sd.shape[-1] if name == "embed" else sd.shape[-2]

            def leaf(kk, lead=sd.shape[:-2], shape=sd.shape[-2:], fan_in=fan_in, dt=sd.dtype):
                if not lead:
                    return (jax.random.normal(kk, shape, jnp.float32)
                            * (fan_in ** -0.5)).astype(dt)
                return jax.lax.map(lambda kj: leaf(kj, lead[1:]),
                                   jax.random.split(kk, lead[0]))

            out.append(leaf(k))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make_params(config, seed: int, device, dtype):
    """The whole parameter tree on `device` from the seed in ONE jitted
    call, in the type it is served in: no host arrays, no eager per-leaf
    programs, and f32 temporaries of one matrix at most (the program's own
    init_params peaks at 13.48 GB for a 6.4 GB model, PERF.md PR 21)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    key = jax.random.key(int(seed) % (2**31 - 1), impl="rbg")
    fn = jax.jit(params_program(config, dtype), out_shardings=SingleDeviceSharding(device))
    return fn(jax.device_put(key, device))


# -- warm-up ---------------------------------------------------------------


def _samp(n: int) -> dict:
    return {"temperature": [0.0] * n, "top_k": [0] * n, "top_p": [1.0] * n,
            "seeds": [0] * n, "rep": [1.0] * n, "freq": [0.0] * n,
            "presence": [0.0] * n}


def warm_lattice(engine) -> dict:
    """Compile (or load from the persistent cache) every step-program
    variant the engine's flags allow, by walking the lattice and not by
    hoping traffic meets it:
      decode_loop  decode bucket <= max_batch  x  n_steps 1..decode_steps
      ragged       T bucket <= max_batch + mixed_prefill_tokens
      decode_loop  again, chained on a ragged step: bucket x n_steps-1
      forward      prefill bucket <= chunk_size  x  prior context or none
    and the eager slices of the ragged path's results (see below); where the
    runner has no ragged program for the model, the padded mixed program's
      mixed        decode bucket x n_steps x prefill bucket x chunks packed
    Inputs are dummies that write page 0..n of an empty pool."""
    from dynamo_tpu.engine.model_runner import _next_bucket

    r, s = engine.runner, engine.scheduler
    ps = r.page_size
    t0 = time.monotonic()
    b_max = _next_bucket(r.decode_buckets, s.max_batch)
    for b in [x for x in r.decode_buckets if x <= b_max]:
        for n in range(1, s.decode_steps + 1):
            r.decode_multi(n, [1] * b, [0] * b, [[0]] * b, _samp(b), 1)
    if s.mixed_prefill_tokens > 0 and r.ragged_mixed and engine.fused_mixed:
        t_max = _next_bucket(r.ragged_buckets, s.max_batch + s.mixed_prefill_tokens)
        for t in [x for x in r.ragged_buckets if x <= t_max]:
            pages = list(range(1, 2 + (t - 1) // ps))
            r.decode_multi_with_prefill(
                1, [1], [0], [[0]], _samp(1), 1,
                [1] * (t - 1), 0, pages, 0)
        # a mixed iteration's steps 2..n run through decode_loop chained on
        # the ragged step's device-resident tokens: other variants than the
        # host-token ones above (they compiled inside the window at first)
        for b in [x for x in r.decode_buckets if x <= b_max]:
            for n in range(2, s.decode_steps + 1):
                r.decode_multi_with_prefill(
                    n, [1] * b, [0] * b, [[0]] * b, _samp(b), 1,
                    [1] * 8, 0, [1], 0)
        # the ragged path also runs small eager programs on its results,
        # one per shape: `sampled[:B]` per (T bucket, decode bucket) and
        # `seg_logits[n_dec : n_dec + k]` plus the walk over its k rows per
        # (T bucket, chunks packed). They are in no family and no counter,
        # and a fresh cache compiles them inside the window one by one
        # (the first run of a checkout read 50 % slower), so meet them here
        t_buckets = [x for x in r.ragged_buckets if x <= t_max]
        for t in t_buckets:
            for b in [x for x in r.decode_buckets if x <= b_max and x < t]:
                n = t - b
                r.decode_multi_with_prefill(
                    1, [1] * b, [0] * b, [[0]] * b, _samp(b), 1,
                    [1] * n, 0, list(range(1, 2 + n // ps)), 0)
            for k in range(2, s.mixed_prefill_seqs + 1):
                sizes = [(t - 1) // k] * k
                sizes[-1] += (t - 1) - sum(sizes)
                per = 1 + max(sizes) // ps
                chunks = [{"tokens": [1] * n, "start": 0, "prior": 0, "adapter": 0,
                           "table": list(range(1 + j * per, 1 + (j + 1) * per))}
                          for j, n in enumerate(sizes)]
                _, rows = r.decode_multi_with_prefills(
                    1, [1], [0], [[0]], _samp(1), 1, chunks)
                for _, lg in zip(chunks, rows):
                    r.sample_one(lg, _samp(1), 1)
    elif s.mixed_prefill_tokens > 0 and engine.fused_mixed and not r.pp:
        # a model the ragged program shuts out (latent attention) rides the
        # padded mixed program: decode bucket x n_steps x prefill bucket of
        # the longest chunk x chunks packed (one chunk is a program of its
        # own, two and more pad to a pack bucket). Traffic reaches every
        # point: the scheduler fuses decode_steps steps, fewer when a row
        # nears its max_tokens; any number of rows up to max_batch decode;
        # a prompt's last chunk has any length up to the budget; up to
        # mixed_prefill_seqs chunks share it. So the configuration's flags
        # are the bound, and a configuration of this kind has to set them:
        # --max-batch 16 --mixed-prefill-tokens 256 --mixed-prefill-seqs 1
        # is 5 x 4 x 5 = 100 programs, the worker's defaults 560
        c_max = _next_bucket(r.prefill_buckets, s.mixed_prefill_tokens)
        k_max = _next_bucket(r.pack_buckets, s.mixed_prefill_seqs)
        packs = [k for k in r.pack_buckets if 2 <= k <= k_max] if s.mixed_prefill_seqs > 1 else []
        bs = [x for x in r.decode_buckets if x <= b_max]
        sbs = [x for x in r.prefill_buckets if x <= c_max]
        log(f"padded mixed lattice: {len(bs)} decode buckets x {s.decode_steps} steps x "
            f"{len(sbs)} prefill buckets x {1 + len(packs)} packings")
        for b in bs:
            for n in range(1, s.decode_steps + 1):
                for sb in sbs:
                    per = 1 + sb // ps
                    dec = (n, [1] * b, [0] * b, [[0]] * b, _samp(b), 1)
                    r.decode_multi_with_prefill(*dec, [1] * sb, 0, list(range(1, 1 + per)), 0)
                    for k in packs:
                        r.decode_multi_with_prefills(*dec, [
                            {"tokens": [1] * sb, "start": 0, "prior": 0, "adapter": 0,
                             "table": list(range(1 + j * per, 1 + (j + 1) * per))}
                            for j in range(k)])
    s_max = _next_bucket(r.prefill_buckets, s.chunk_size)
    logits = None
    for sb in [x for x in r.prefill_buckets if x <= s_max]:
        pages = list(range(1, 3 + sb // ps))
        logits = r.prefill([1] * sb, 0, pages, prior_len=0)
        logits = r.prefill([1] * sb, ps, pages, prior_len=ps)
    if logits is not None:
        r.sample_one(logits, _samp(1), 1)
    return {"warm_s": time.monotonic() - t0, "compile": r.compile_stats()}


# -- correctness -----------------------------------------------------------


def check_prompts(config, sched, rng, rehearse: bool):
    """The sample the reference check serves: [(prompt ids, tokens asked)].
    Prompt 0 is the lead: it is started alone and decodes while the others
    prefill, long enough to outlast them. Then three short prompts and one
    long one, longer than a mixed-prefill chunk and, where the model has a
    sliding window, longer than the window, so that its chunks and its decode
    steps attend across the window's edge."""
    n_prompt, n_out = (12, 5) if rehearse else (48, 9)
    shorts = [n_prompt + 7 * j for j in range(1, 4)]
    chunk = max(int(sched.mixed_prefill_tokens), 16)
    win = int(getattr(config, "sliding_window", 0) or 0)
    long = (win + 153) if win else (3 * chunk + 17)
    long = min(long, int(config.max_seq_len) - n_out - 1)
    iters = -(-(sum(shorts) + long) // chunk) + 2
    n_lead = n_out + int(sched.decode_steps) * iters
    sizes = [(n_prompt, n_lead)] + [(n, n_out) for n in shorts + [long]]
    return [(rng.integers(1, config.vocab_size, size=n).tolist(), k) for n, k in sizes]


async def served(engine, sample, logprobs: bool, picks: bool = False):
    """Serve the whole sample through the engine at once, greedy:
    [(tokens, logprobs, picks)] per prompt. The lead starts alone; the rest
    are submitted when its first token is out, so their prefills meet a live
    decode row and all of them then decode side by side in one batch.
    `picks`: also ask for the experts the router chose (`routed_experts`,
    docs/observability.md) and assemble them, int32 [n_prompt + n_out - 1,
    L_moe, k], ids over the router's full width; None for a stream whose
    items do not cover positions 0 .. n_prompt + n_out - 2 once each and in
    order, and where nothing was asked."""
    import numpy as np

    from dynamo_tpu.runtime.context import Context

    lead_out = asyncio.Event()

    async def one(i: int, ids, n_out: int):
        if i > 0:
            await lead_out.wait()
        toks, lps, routed, in_order = [], [], [], True
        sampling = {"temperature": 0.0, **({"logprobs": 0} if logprobs else {}),
                    **({"routed_experts": True} if picks else {})}
        payload = {"token_ids": list(ids), "sampling": sampling,
                   "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}}
        try:
            async for item in engine.generate(payload, Context()):
                toks += list(item.get("token_ids") or [])
                lps += [e["logprob"] for e in item.get("logprobs") or []]
                r = item.get("routed_experts")
                if r:
                    in_order = in_order and r["start"] == len(routed)
                    routed += r["ids"]
                if toks:
                    lead_out.set()
                if item.get("finish_reason"):
                    if item["finish_reason"] == "error":
                        raise RuntimeError(f"engine error: {item.get('error')}")
                    break
        finally:
            lead_out.set()  # a lead that fails must not hang the rest
        covered = picks and in_order and len(routed) == len(ids) + len(toks) - 1
        return toks, lps, np.asarray(routed, np.int32) if covered else None

    return await asyncio.gather(*(one(i, ids, k) for i, (ids, k) in enumerate(sample)))


# One number, here and in no configuration: the (position, expert layer)
# pairs of a check whose served picks may lie beyond the configuration's
# margin. Twelve seeds at each of two published sizes held it (PERF.md
# section 6, PR 32), so a rule that forgives no pick stands.
INADMISSIBLE_ALLOWED = 0
UNBOUNDED_NEED = 1e9  # how a need that no margin admits (inf) reads in the check's line


def follows(ref, model: dict) -> bool:
    """Does the check of this model follow the served picks? Where the
    reference offers the followed mode and the model routes at all."""
    return bool(hasattr(ref, "follow_at") and model.get("n_experts"))


def routing_margin(ref, model: dict, stated, where: str):
    """The configuration's `correct_routing_margin` (None for a check that
    does not follow). It has no default: a reading at the configuration's own
    sizes, stated where the check follows and nowhere else."""
    if follows(ref, model) != (stated is not None):
        raise ValueError(
            f"{where}: its reference " + (
                "follows the served picks, so it has to state `correct_routing_margin`"
                if stated is None else
                "does not follow served picks, so `correct_routing_margin` means nothing"))
    return None if stated is None else float(stated)


def check_against_reference(ref, model: dict, params, sample, got, tol: float,
                            margin=None) -> dict:
    """Teacher-force the plain float32 reference on prompt + served tokens.
    Where the engine gave logprobs, compare the logprob of each served token
    (`max`, `mean`); in every case measure how far the served token lies
    under the reference's best one (`gap`): a greedy token picked from logits
    that are each within `tol` lies within 2 x tol of it. A routed model
    (`margin` stated): the reference computes every expert layer with the
    experts the program served (`follow_at`), after holding each served set
    against its own float32 selection scores: a set that those scores would
    have to move by more than `margin` to choose is inadmissible, and none
    may be. No token is left out, of a routed model's check or a dense one's."""
    import numpy as np

    worst, total, gap, n, short, per = 0.0, 0.0, 0.0, 0, False, []
    pairs, differ, inadmissible, need_max = 0, 0, 0, 0.0
    for (ids, n_out), (toks, lps, picks) in zip(sample, got):
        if len(toks) != n_out or len(lps) not in (0, n_out) or \
                (margin is not None and picks is None):
            short = True
            continue
        seq = np.asarray(list(ids) + toks[:-1], np.int32)
        at = list(range(len(ids) - 1, len(seq)))
        if margin is not None:
            logp, need = ref.follow_at(model, params, seq, at, picks)
            pairs, differ = pairs + need.size, differ + int((need > 0).sum())
            inadmissible += int((need > margin).sum())
            need_max = max(need_max, float(min(need.max(initial=0.0), UNBOUNDED_NEED)))
        else:
            logp = ref.logprobs_at(model, params, seq, at)
        want = logp[np.arange(len(toks)), np.asarray(toks)]  # logp [len(at), V] f32
        under = float((logp.max(axis=-1) - want).max())
        row = {"prompt": len(ids), "tokens": len(toks), "gap": round(under, 4)}
        if lps:
            err = np.abs(want - np.asarray(lps))
            worst, total = max(worst, float(err.max())), total + float(err.sum())
            row.update(max=round(float(err.max()), 4), mean=round(float(err.mean()), 4))
        gap, n = max(gap, under), n + len(toks)
        per.append(row)
    out = {"max_abs_logprob_err": worst, "mean_abs_logprob_err": total / max(n, 1),
           "max_gap_under_best": gap, "tokens": n, "tolerance": tol, "per_prompt": per}
    if margin is not None:
        out.update(picks=pairs, picks_differ=differ, inadmissible=inadmissible,
                   need_max=need_max, margin=margin)
    out["ok"] = bool(n > 0 and not short
                     and all(out[k] <= limit for k, limit in limits_of(out).items()))
    return out


def limits_of(res: dict) -> dict:
    """{number compared: its limit} of one pass's result. The bound on the
    worst token is the tolerance; the mean over tokens is held to a third of
    it, which is the steadier of the two readings; a greedy token picked from
    logits that are each within the tolerance lies within twice that of the
    reference's best; and of a routed model's picks none may be inadmissible."""
    tol = res["tolerance"]
    out = {"max_abs_logprob_err": tol, "mean_abs_logprob_err": tol / 3,
           "max_gap_under_best": 2 * tol}
    if "inadmissible" in res:
        out["inadmissible"] = INADMISSIBLE_ALLOWED
    return out


def compared(checks: list) -> dict:
    """{short name: [number, limit]} over the replicas' checks, for the
    result line: every number that decided `correct` beside its limit (the
    pass without logprobs compares no logprob)."""
    out = {}
    for i, check in enumerate(checks):
        for name in ("logprobs", "ragged"):
            for k, limit in limits_of(check[name]).items():
                if name == "logprobs" or "logprob_err" not in k:
                    out[f"r{i}.{name}.{k}"] = [check[name][k], limit]
            if "need_max" in check[name]:  # what made a pick inadmissible, in the scores' unit
                out[f"r{i}.{name}.need_max"] = [check[name]["need_max"], check[name]["margin"]]
            # too few tokens or picks streamed, never two rows decoding at
            # once, or the fused program not met: the pass's own verdict
            out[f"r{i}.{name}.failed"] = [int(not check[name]["ok"]), 0]
    return out


def load_reference(cfg: dict):
    return load_module(os.path.join(HERE, "reference", cfg["reference"] + ".py"),
                       "bench_reference")


async def reference_check(ref, model: dict, engine, seed: int, tol: float,
                          rehearse: bool, margin=None, params=None) -> dict:
    """One replica's check, in two passes over samples of the same shape.
    With logprobs: each served token's logprob against the reference. A
    request that asks for logprobs never rides the fused mixed step (the
    engine splits such an iteration into a prefill and a decode dispatch),
    so this pass checks prefill chunks on a prior context, the decode loop at
    several rows and the window's edge, and not the ragged program. Without
    logprobs: the same drive goes through the ragged mixed step every request
    takes under load, and the served greedy tokens are held to the
    reference's best token (a model the ragged program shuts out rides the
    padded mixed program there). A pass that never decoded two rows at once,
    or a second pass that missed the fused program the engine runs, has
    checked too little and fails. Both passes of a routed model (`margin`
    stated) also ask for the router's picks, which changes no dispatch. The
    reference reads the tree the runner serves, or `params` where the program
    holds its weights in another form (a control under `quantize`)."""
    import numpy as np

    r, s = engine.runner, engine.scheduler
    mixed_on = bool(s.mixed_prefill_tokens > 0 and r.ragged_mixed and engine.fused_mixed)
    padded_on = bool(s.mixed_prefill_tokens > 0 and engine.fused_mixed and not mixed_on
                     and not r.pp)
    out = {"ok": True}
    for name, logprobs in (("logprobs", True), ("ragged", False)):
        sample = check_prompts(engine.runner.config, s,
                               np.random.default_rng([seed, int(logprobs)]), rehearse)
        calls0 = {k: v.get("calls", 0) for k, v in r.compile_stats().items()}
        t0, m0 = time.time(), time.monotonic()
        got = await served(engine, sample, logprobs, picks=margin is not None)
        m1 = time.monotonic()
        res = check_against_reference(ref, model, r.params if params is None else params,
                                      sample, got, tol, margin)
        log(f"check pass {name}: served in {m1 - m0:.1f}s, "
            f"reference in {time.monotonic() - m1:.1f}s")
        res["calls"] = {k: v.get("calls", 0) - calls0.get(k, 0)
                        for k, v in r.compile_stats().items()}
        res["max_decode_rows"] = max(
            (rec.decode_seqs for rec in engine.recorder.snapshot() if rec.ts >= t0), default=0)
        fused = "ragged" if mixed_on else "mixed" if padded_on else None
        res["ok"] = bool(res["ok"] and res["max_decode_rows"] > 1 and (
            logprobs or fused is None or res["calls"].get(fused, 0) > 0))
        out[name] = res
        out["ok"] = out["ok"] and res["ok"]
    return out


# -- the run ---------------------------------------------------------------


def parse_args(argv=None):
    p = argparse.ArgumentParser("benchmark/serve.py")
    p.add_argument("--config", required=True)
    p.add_argument("--run-dir", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--trace-seconds", type=float, default=1.0)
    p.add_argument("--trace-captures", type=int, default=3)
    p.add_argument("--tokenizer", required=True)
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args(argv)


def snapshot(engines) -> list:
    out = []
    for e in engines:
        st = e.runner.compile_stats()
        out.append({
            "compile": {k: {"variants": v["variants"],
                            "compile_s": v.get("compile_s", 0.0),
                            "calls": v.get("calls", 0)} for k, v in st.items()},
            "reused_prefix_tokens": e.scheduler.reused_prefix_tokens,
            "prompt_tokens_total": e.scheduler.prompt_tokens_total,
            "wall": time.time(),
        })
    return out


async def amain(args) -> int:
    with open(args.config) as f:
        cfg = json.load(f)
    model = dict(cfg["model"])
    flags = dict(cfg["server_flags"])
    tol, stated = float(cfg["correct_tolerance"]), cfg.get("correct_routing_margin")
    if args.rehearse:
        reh = load_module(os.path.join(HERE, "rehearsal.py"),
                          "bench_rehearsal").rehearsal_sizes(cfg, HERE)
        model, flags, tol = reh["model"], reh["server_flags"], reh["correct_tolerance"]
        stated = reh["correct_routing_margin"]

    import jax
    import jax.numpy as jnp

    import dynamo_tpu
    from dynamo_tpu import worker as worker_mod
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    cache_dir = dynamo_tpu.enable_compilation_cache()
    ref = load_reference(cfg)
    margin = routing_margin(ref, model, stated, args.config + (
        " (its `rehearse` group)" if args.rehearse else ""))
    devices = jax.devices()
    plat = devices[0].platform
    log(f"jax {jax.__version__} platform={plat} kind={devices[0].device_kind!r} "
        f"devices={len(devices)} cache={cache_dir}")
    if plat != "tpu" and not args.rehearse:
        log("no TPU: refusing to measure (use --rehearse to debug the harness)")
        return 3
    if len(devices) < args.chips:
        log(f"the cell asks for {args.chips} chips, JAX reports {len(devices)}")
        return 3
    devices = devices[: args.chips]

    config = ModelConfig(**model)
    wargs = worker_mod.parse_args(
        [x for k, v in flags.items() for x in (f"--{k}", str(v))]
        + ["--tokenizer", args.tokenizer, "--model-name", config.name,
           "--discovery-backend", "file",
           "--discovery-root", os.path.join(args.run_dir, "discovery")])
    mpps = -(-wargs.max_seq_len // wargs.page_size)

    def build(i: int):
        t = time.monotonic()
        params = make_params(config, args.seed, devices[i], jnp.bfloat16)
        jax.block_until_ready(params)
        t_w = time.monotonic() - t
        runner = ModelRunner(
            config, None, devices=[devices[i]], num_pages=wargs.num_pages,
            page_size=wargs.page_size, max_pages_per_seq=mpps, params=params,
            spec_gamma=wargs.spec_gamma, quantize=wargs.quantize,
            kv_quantize=wargs.kv_quantize)
        engine, card = worker_mod.build_engine(wargs, runner=runner)
        warm = warm_lattice(engine)
        log(f"replica {i}: weights {t_w:.1f}s, lattice {warm['warm_s']:.1f}s "
            f"{ {k: v['variants'] for k, v in warm['compile'].items()} }")
        return engine, card, {"weights_s": t_w, **warm}

    built = [None] * len(devices)

    def build_into(i: int) -> None:
        built[i] = build(i)

    # replicas build side by side: compiles and cache loads release the GIL
    threads = [threading.Thread(target=build_into, args=(i,)) for i in range(len(devices))]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        await asyncio.sleep(0.2)
    if any(b is None for b in built):
        log("a replica failed to build")
        return 4
    engines = [b[0] for b in built]
    report = engines[0].runner.device_report()
    log("device_report " + json.dumps({k: report[k] for k in (
        "platform", "device_kind", "attn_impl", "attn_impl_reason", "ragged_mixed")}
        | {"fused_mixed": bool(engines[0].fused_mixed)}))

    # the reference check, outside the timed window and before serving
    import numpy as np

    t = time.monotonic()
    checks = [await reference_check(ref, model, e, args.seed, tol, args.rehearse, margin)
              for e in engines]
    log(f"reference check {time.monotonic() - t:.1f}s: {json.dumps(checks)}")

    # phase spine of every finished request, with the wall time it ended
    phases_log: list = []
    for i, e in enumerate(engines):
        def on_phases(ph, i=i):
            phases_log.append({"replica": i, "wall": time.time(), **{
                k: v for k, v in ph.items() if isinstance(v, (int, float))}})
        e.on_phases(on_phases)

    runtimes, workers = [], []
    for i, (engine, card, _) in enumerate(built):
        rt = DistributedRuntime(discovery_backend="file", root=wargs.discovery_root)
        w = await serve_worker(rt, engine, card, namespace=wargs.namespace,
                               component=wargs.component, endpoint=wargs.endpoint,
                               digest_period_s=wargs.digest_period)
        runtimes.append(rt)
        workers.append(w)

    def mem() -> dict:
        out = {}
        for d in devices:
            st = d.memory_stats() or {}
            out[str(d.id)] = {k: int(st[k]) for k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in st}
        return out

    sched = engines[0].scheduler
    write_json(os.path.join(args.run_dir, "ready.json"), {
        "device": {"platform": plat, "kind": devices[0].device_kind, "count": len(devices)},
        "model": config.name, "correct": checks, "compared": compared(checks),
        "replicas": [b[2] for b in built],
        "device_report": report, "memory": mem(), "ready_s": time.monotonic() - T0,
        "engine": {"max_batch": sched.max_batch, "decode_steps": sched.decode_steps,
                   "mixed_prefill_tokens": sched.mixed_prefill_tokens,
                   "chunk_size": sched.chunk_size, "page_size": wargs.page_size,
                   "num_pages": wargs.num_pages},
    })
    log("ready")

    stop_path = os.path.join(args.run_dir, "stop")
    window_path = os.path.join(args.run_dir, "window.json")
    trace_dir = os.path.join(args.run_dir, "trace")
    window = None
    while not os.path.exists(stop_path):
        if os.path.exists(window_path):
            with open(window_path) as f:
                window = json.load(f)
            break
        await asyncio.sleep(0.1)
    trace_info = None
    if window is not None:
        await asyncio.sleep(max(0.0, window["t0_wall"] - time.time()))
        at0 = snapshot(engines)
        if args.trace:
            # the device side of a capture holds about half a second however
            # long it is left open, so several short ones, one after another
            # from a quarter of the window on, each in a directory of its own
            span = window["t1_wall"] - window["t0_wall"]
            await asyncio.sleep(max(0.0, window["t0_wall"] + span / 4 - time.time()))
            caps = []
            while len(caps) < args.trace_captures and \
                    time.time() + args.trace_seconds + 1 < window["t1_wall"]:
                ts = time.time()
                await asyncio.to_thread(jax.profiler.start_trace,
                                        os.path.join(trace_dir, f"c{len(caps)}"))
                await asyncio.sleep(args.trace_seconds)
                te = time.time()
                await asyncio.to_thread(jax.profiler.stop_trace)
                caps.append({"start_wall": ts, "stop_wall": te, "written_s": time.time() - te})
                await asyncio.sleep(0.5)
            trace_info = {"captures": caps}
            log(f"trace captures {json.dumps(caps)}")
        await asyncio.sleep(max(0.0, window["t1_wall"] - time.time()))
        at1 = snapshot(engines)
        iters = []
        for i, e in enumerate(engines):
            for rec in e.recorder.snapshot():
                if window["t0_wall"] <= rec.ts < window["t1_wall"]:
                    # every scalar field of the record, so that a counter a
                    # later PR adds reaches its reader with no edit here
                    iters.append({"replica": i, **{
                        k: v for k, v in dataclasses.asdict(rec).items()
                        if isinstance(v, (bool, int, float, str))}})
        write_json(os.path.join(args.run_dir, "counters.json"),
                   {"at0": at0, "at1": at1, "iterations": iters, "trace": trace_info})
        while not os.path.exists(stop_path):
            await asyncio.sleep(0.1)

    final = {"memory": mem(), "phases": phases_log}
    if trace_info is not None:
        reduce_mod = load_module(os.path.join(HERE, "trace_reduce.py"), "bench_trace_reduce")
        try:
            final["trace"] = reduce_mod.reduce_dir(trace_dir, n_devices=len(devices))
        except Exception as e:  # the run still reports what it has
            log(f"trace reduction failed: {type(e).__name__}: {e}")
            final["trace_error"] = f"{type(e).__name__}: {e}"
    write_json(os.path.join(args.run_dir, "final.json"), final)
    log("final written; shutting down")
    for e in engines:
        e.stop()
    for rt in runtimes:
        try:
            await asyncio.wait_for(rt.shutdown(drain_timeout=1), timeout=5)
        except Exception:
            pass
    return 0


def main() -> None:
    args = parse_args()
    os.makedirs(args.run_dir, exist_ok=True)
    if args.trace:
        # host spans (engine.decode / engine.mixed / engine.prefill) ride the
        # profiler's own clock, so idle gaps on the device find an owner
        os.environ["DYN_ENABLE_JAX_TRACE"] = "1"
    rc = asyncio.run(amain(args))
    sys.stdout.flush()
    os._exit(rc)  # engine step threads and zmq sockets must not hold the exit


if __name__ == "__main__":
    main()
