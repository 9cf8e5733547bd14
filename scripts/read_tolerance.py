"""Read a configuration's `correct_tolerance` by benchmark/README.md's rule:
through benchmark/serve.py's own `make_params` and `reference_check`, at the
configuration's own sizes and server flags, for each seed the sound bf16
program and its controls: the same weights under `quantize: int8`, and, for a
model with state-space layers, the program with the recurrent state `S` kept
in bfloat16 (a pool this script lays under the runner: the program has no
such option), for a model with sinks on its window layers the program with
the sink left out (`no-sink`: the same tree served under a configuration
whose `sink_window` is off); for a model with an indexer the mechanism's own
two (`recent`: the selection replaced by the most recent `index_topk` tokens;
`no-selection`: every cached token attended to); for a model with KDA layers
`state-bf16` likewise, `no-decay` (the decay held at 1: the same tree served
under a `kda_gate_lower` of -1e-9) and `chunk-end` (every prefill chunk leaves
zeros in its slot: a state lost at a chunk boundary). Chip only (like serve.py it refuses a CPU unless --rehearse).

    python scripts/read_tolerance.py --config benchmark/configs/<name>.json \
        --seeds 3600000300:3600000312 [--tolerance 0.1] [--out chiprun_out/tol.jsonl]

One JSON line a (seed, variant): per pass the worst and the mean error and the
gap under the reference's best, the larger of the two passes, and `ok` under
--tolerance. A routed model whose check follows the served picks also needs
`--margin` (a provisional one wide enough that nothing trips, by the rule):
the line then carries `need_max` and `inadmissible`, the larger of the passes.
"""

import argparse
import asyncio
import contextlib
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine(config, dev, wargs, mpps, params, variant: str):
    """A runner and an engine as benchmark/serve.py builds them, for one
    variant of the program."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu import worker
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models import jamba, ling

    if variant == "no-decay":  # alpha held at 1: the delta rule without its gate
        config = config.with_(kda_gate_lower=-1e-9)
    if variant == "no-sink":
        config = config.with_(sink_window=False)
    if variant == "no-selection":  # every cached token attended to
        config = config.with_(index_topk=config.max_seq_len)
    runner = ModelRunner(
        config, None, devices=[dev], num_pages=wargs.num_pages,
        page_size=wargs.page_size, max_pages_per_seq=mpps, params=params,
        quantize="int8" if variant == "int8" and not _is_quantized(params) else None)
    engine = worker.build_engine(wargs, runner=runner)[0]
    if variant == "state-bf16":
        # the program keeps S in float32 and has no option for anything
        # else: the control lays a bfloat16 pool of the engine's own size
        # under the runner before any sequence owns a slot
        mod = ling if config.is_kda else jamba
        runner.state = jax.device_put(mod.make_state_pool(
            config, runner.side_units, jnp.bfloat16, runner.dtype), dev)
    return engine


@contextlib.contextmanager
def _state_lost_at_chunk_ends(on: bool):
    """The `chunk-end` control of a model with KDA layers: while its programs
    are traced, a prefill chunk hands back zeros for the state it leaves, so
    every chunk and the first decode step start from an empty slot: what a
    slot lost, or never stored, at a chunk boundary would give."""
    from dynamo_tpu.ops import kda

    if not on:
        yield
        return
    sound = {n: getattr(kda, n) for n in ("kda_chunk", "kda_chunk_jnp")}

    def lossy(form):
        def chunk(*a, **kw):
            o, S = form(*a, **kw)
            return o, S * 0
        return chunk

    for n, form in sound.items():  # the chip's form and the CPU's
        setattr(kda, n, lossy(form))
    try:
        yield
    finally:
        for n, form in sound.items():
            setattr(kda, n, form)


@contextlib.contextmanager
def _recent_tokens_selected(on: bool):
    """The `recent` control of a model with an indexer: while its programs
    are traced (the engine's first steps), the index score of a cached token
    is its position, so the selection keeps the most recent index_topk
    tokens, a sliding window: what a careless change could leave behind."""
    from dynamo_tpu.models import mla

    if not on:
        yield
        return
    import jax.numpy as jnp

    sound = mla.index_scores
    mla.index_scores = lambda qi, wi, keys: jnp.broadcast_to(
        jnp.arange(keys.shape[1], dtype=jnp.float32),
        qi.shape[:2] + (keys.shape[1],))
    try:
        yield
    finally:
        mla.index_scores = sound


class _NeedsByKind:
    """The reference, noting on its way the largest need of each kind that
    `follow_at` hands the check in one array: the expert layers' picks first
    (`n_moe` columns), then, where the reference follows a served selection
    too, the layers' selections."""

    def __init__(self, ref, n_moe: int):
        self._ref, self._n_moe = ref, n_moe
        self.largest = {}

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def follow_at(self, model, params, seq, at, picks):
        logp, need = self._ref.follow_at(model, params, seq, at, picks)
        for kind, part in (("picks", need[:, : self._n_moe]),
                           ("selection", need[:, self._n_moe:])):
            if part.size:
                self.largest[kind] = max(self.largest.get(kind, 0.0),
                                         float(min(part.max(), 1e9)))
        return logp, need


def _is_quantized(params) -> bool:
    from dynamo_tpu.models.quant import is_quantized

    return any(is_quantized(v) for v in params["layers"].values())


def _int8_beside_a_host_copy(params):
    """(the tree under `quantize: int8`, the tree the reference reads) for a
    model whose bf16 tree and its int8 copy do not fit the chip together
    (10.85 GB + 4.1): the layer stack goes to the host first (the reference
    moves one layer at a time back), then the device tree is quantized with
    donation, leaf by leaf. The caller's `params` is consumed."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.quant import quantize_params

    ref_tree = {**params, "layers": jax.device_get(params["layers"]),
                "embed": jnp.copy(params["embed"])}
    if params.get("lm_head") is not None:
        ref_tree["lm_head"] = jnp.copy(params["lm_head"])
    return quantize_params(params, mode="int8", donate=True), ref_tree


async def main(args) -> int:
    import jax
    import jax.numpy as jnp

    import dynamo_tpu
    from dynamo_tpu import worker
    from dynamo_tpu.models.config import ModelConfig

    serve = _load(os.path.join(ROOT, "benchmark", "serve.py"), "bench_serve")
    with open(args.config) as f:
        cfg = json.load(f)
    model, flags = dict(cfg["model"]), dict(cfg["server_flags"])
    if args.rehearse:
        import rehearsal

        reh = rehearsal.rehearsal_sizes(cfg, os.path.join(ROOT, "benchmark"))
        model, flags = reh["model"], reh["server_flags"]
    dynamo_tpu.enable_compilation_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU: a tolerance is read on the chip (--rehearse debugs this script)")
        return 3
    config = ModelConfig(**model)
    ref = _NeedsByKind(serve.load_reference(cfg), config.n_layers - config.n_dense_layers)
    wargs = worker.parse_args(
        [x for k, v in flags.items() for x in (f"--{k}", str(v))]
        + ["--tokenizer", "byte", "--model-name", config.name])
    mpps = -(-wargs.max_seq_len // wargs.page_size)
    # (int8 last: where the tree is large it consumes the seed's params)
    variants = (["sound"] + (["state-bf16"] if config.is_hybrid else [])
                + (["state-bf16", "no-decay", "chunk-end"] if config.is_kda else [])
                + (["no-sink"] if config.sink_window else [])
                + (["recent", "no-selection"] if config.has_indexer else [])
                + ["int8"])
    lo, hi = (int(x) for x in args.seeds.split(":"))
    upto = {v: int(n) for v, n in (x.split("=") for x in args.upto)}
    out = open(args.out, "a") if args.out else None
    params = None
    for seed in range(lo, hi):
        params = None  # (frees the last seed's tree where --only left int8 out)
        params = serve.make_params(config, seed, dev, jnp.bfloat16)
        for variant in [v for v in variants if not args.only or v in args.only]:
            if seed >= upto.get(variant, hi):
                continue
            t0 = time.monotonic()
            ref.largest = {}
            served, ref_tree = params, params if variant == "int8" else None
            if variant == "int8" and args.offload:
                served, ref_tree = _int8_beside_a_host_copy(params)
                del params
            engine = await asyncio.to_thread(  # off the event loop
                _engine, config, dev, wargs, mpps, served, variant)
            del served
            with _recent_tokens_selected(variant == "recent"), \
                    _state_lost_at_chunk_ends(variant == "chunk-end"):
                res = await serve.reference_check(
                    ref, model, engine, seed, args.tolerance, args.rehearse, args.margin,
                    params=ref_tree)
            del ref_tree
            engine.stop()
            row = {"seed": seed, "variant": variant, "ok": res["ok"],
                   "tolerance": args.tolerance, "seconds": round(time.monotonic() - t0, 1)}
            for name in ("logprobs", "ragged"):
                row[name] = {k: res[name][k] for k in (
                    "max_abs_logprob_err", "mean_abs_logprob_err", "max_gap_under_best",
                    "tokens", "max_decode_rows", "ok")}
            row["worst"] = res["logprobs"]["max_abs_logprob_err"]
            row["mean"] = res["logprobs"]["mean_abs_logprob_err"]
            row["gap"] = max(res[n]["max_gap_under_best"] for n in ("logprobs", "ragged"))
            if args.margin is not None:
                row["need_max"] = max(res[n]["need_max"] for n in ("logprobs", "ragged"))
                row["inadmissible"] = max(res[n]["inadmissible"] for n in ("logprobs", "ragged"))
                row["picks_differ"] = max(res[n].get("picks_differ", 0) for n in ("logprobs", "ragged"))
                row["need_by_kind"] = dict(ref.largest)
                row["margin"] = args.margin
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
            del engine
    return 0


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", required=True, help="lo:hi (hi excluded)")
    p.add_argument("--tolerance", type=float, default=10.0)
    p.add_argument("--margin", type=float, default=None)
    p.add_argument("--offload", action="store_true",
                   help="int8: keep the reference's layer stack on the host "
                        "(a tree that does not fit the chip beside its int8 copy)")
    p.add_argument("--only", nargs="*", default=None)
    p.add_argument("--upto", nargs="*", default=[], metavar="VARIANT=SEED",
                   help="a variant only for the seeds below this one")
    p.add_argument("--out", default=None)
    p.add_argument("--rehearse", action="store_true")
    rc = asyncio.run(main(p.parse_args()))
    sys.stdout.flush()
    os._exit(rc)
