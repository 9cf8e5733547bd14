"""`python -m dynamo_tpu.worker` — native TPU engine worker process.

Analog of reference `python -m dynamo.vllm` (components/src/dynamo/vllm/
main.py worker startup call stack, SURVEY.md §3.2), with the JAX engine in
place of vLLM: parse args → build runner/engine → register model card in
discovery → serve the generate endpoint over the request plane.
"""

from __future__ import annotations

import argparse
import asyncio
import logging

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.frontend.protocols import ModelCard
from dynamo_tpu.models.config import get_config
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.logging_util import configure_logging

log = logging.getLogger("dynamo_tpu.worker")

# attached shm weight stages pinned for the process lifetime (their numpy
# views back device_put and snapshot writes; unmapping would invalidate)
_SHM_STAGES: list = []


def parse_args(argv=None):
    p = argparse.ArgumentParser("dynamo_tpu.worker")
    p.add_argument("--model", default="tiny", help="model config preset name")
    p.add_argument("--checkpoint", default=None,
                   help="HF safetensors checkpoint dir (config derived from its config.json)")
    p.add_argument("--model-name", default=None, help="served model name (default: config name)")
    p.add_argument("--experts-held", type=int, default=0,
                   help="routed experts this worker holds: one chip's share "
                        "of an expert-parallel deployment (the router stays "
                        "at full width, the worker computes its own experts' "
                        "part of each layer and reads only their tensors "
                        "from a checkpoint); 0 = all of them")
    p.add_argument("--expert-first", type=int, default=0,
                   help="id of the first routed expert held (with --experts-held)")
    p.add_argument("--shm-weights", default=None, metavar="NAME",
                   help="host shared-memory weight staging (gpu_memory_"
                        "service analog): attach the staged tree if a "
                        "host peer published it, else load cold and "
                        "publish for peers/restarts")
    p.add_argument("--orbax-cache", default=None,
                   help="params snapshot dir: load if present, else save "
                        "after build (fast worker restarts — the snapshot-"
                        "restore role of the reference's fast-restart path)")
    p.add_argument("--namespace", default="dyn")
    p.add_argument("--component", default="tpu-worker")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--tokenizer", default="byte", help="'byte' or path to tokenizer.json")
    p.add_argument("--http-address", default=None, metavar="HOST:PORT",
                   help="this pod's direct-mode HTTP frontend address, "
                        "published for the Envoy ext-proc endpoint picker "
                        "(env DYN_HTTP_ADDRESS; operators set it from the "
                        "pod IP)")
    p.add_argument("--engine-sidecar", default=None, metavar="HOST:PORT",
                   help="attach an OUT-OF-PROCESS engine over gRPC "
                        "(python -m dynamo_tpu.sidecar) instead of "
                        "building one in this process")
    p.add_argument("--profiler-port", type=int, default=0,
                   help="start the XLA profiler server on this port for "
                        "TensorBoard capture (0 = off); pair with "
                        "DYN_ENABLE_JAX_TRACE=1 for engine-phase ranges")
    # parallelism (mesh axes)
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--tensor-parallel", type=int, default=1)
    p.add_argument("--expert-parallel", type=int, default=1)
    p.add_argument("--seq-parallel", type=int, default=1)
    p.add_argument("--pipeline-parallel", type=int, default=1,
                   help="GPipe stages over a pipe mesh axis (dense GQA "
                        "family; composes with no other axis yet)")
    # KV cache
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--max-seq-len", type=int, default=4096)
    p.add_argument("--host-kv-blocks", type=int, default=0,
                   help="G2 host-DRAM KV tier capacity in blocks (0 = off)")
    p.add_argument("--disk-kv-blocks", type=int, default=0,
                   help="G3 disk KV tier capacity in blocks (needs G2 on)")
    p.add_argument("--disk-kv-root", default=None,
                   help="G3 tier directory (default: a temp dir)")
    p.add_argument("--obj-kv-root", default=None,
                   help="G4 object-store root (shared mount; enables the "
                        "terminal KV tier)")
    p.add_argument("--kv-tier-quantize", action="store_true",
                   help="store demoted G2/G3/G4 blocks as int8 + per-"
                        "(token, head) scales (~1.9x blocks per byte at "
                        "D=128); G1 device hits stay full precision")
    p.add_argument("--onboard-layer-groups", type=int, default=1,
                   help="stream tier onboarding in this many layer-group "
                        "slabs so prefill starts after the first slab "
                        "lands (1 = whole-sequence import)")
    p.add_argument("--prefetch", action="store_true",
                   help="router-hinted predictive KV promotion (needs "
                        "--host-kv-blocks > 0); advertises kv_prefetch so "
                        "routers send tier-promotion hints ahead of dispatch")
    p.add_argument("--prefetch-max-inflight", type=int, default=4,
                   help="max concurrent G3->G2 disk reads per worker")
    p.add_argument("--prefetch-bandwidth-mbps", type=float, default=0.0,
                   help="promotion bandwidth budget in MB/s (0 = unlimited)")
    p.add_argument("--prefetch-hint-ttl-s", type=float, default=10.0,
                   help="drop a hint whose request never arrives after this")
    p.add_argument("--prefetch-pin-ttl-s", type=float, default=5.0,
                   help="how long promoted blocks stay pinned against eviction")
    # batching
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--chunk-size", type=int, default=512)
    p.add_argument("--mixed-prefill-tokens", type=int, default=256,
                   help="per-iteration prefill token POOL when co-scheduled "
                        "with decode: fair-shared across up to "
                        "--mixed-prefill-seqs packed chunks from distinct "
                        "sequences (0 = strict prefill-first). Align with a "
                        "prefill bucket: the set pads to the next bucket")
    p.add_argument("--mixed-prefill-seqs", type=int, default=8,
                   help="max distinct prefills packed per iteration "
                        "(1 = legacy single-chunk MixedPlan)")
    p.add_argument("--mixed-min-chunk", type=int, default=16,
                   help="fair-share floor: each packed sequence is offered "
                        "at least this many prefill tokens per iteration")
    # speculative decoding
    p.add_argument("--draft-model", default=None,
                   help="draft model config preset (enables speculative decoding)")
    p.add_argument("--draft-checkpoint", default=None,
                   help="HF safetensors dir for draft weights")
    p.add_argument("--spec-gamma", type=int, default=4,
                   help="draft tokens proposed per target verify pass")
    p.add_argument("--spec-draft-model", default=None, metavar="PRESET",
                   help="alias for --draft-model: route speculation through "
                        "a separate draft model instead of n-gram lookup")
    p.add_argument("--spec-ngram", action="store_true",
                   help="draft-model-free speculation: propose the next K "
                        "tokens by prompt/history n-gram lookup and verify "
                        "them as ragged rows of the mixed dispatch")
    p.add_argument("--spec-k", type=int, default=4,
                   help="n-gram draft length K (verify rows are K+1 tokens)")
    p.add_argument("--spec-max-tokens", type=int, default=0,
                   help="per-iteration cap on drafted tokens admitted to "
                        "the verify dispatch (0 = the leftover mixed "
                        "prefill token budget)")
    # multi-LoRA
    p.add_argument("--lora", action="append", default=[],
                   help="serve a LoRA adapter: NAME=<peft_dir> (HF PEFT "
                        "safetensors) or bare NAME (random factors, dev). "
                        "Repeatable; each name becomes a servable model.")
    p.add_argument("--lora-rank", type=int, default=8,
                   help="rank for randomly-initialized dev adapters")
    p.add_argument("--lora-slots", type=int, default=0,
                   help="EXTRA free adapter slots beyond --lora specs, for "
                        "runtime registration via the rl load_adapter op")
    p.add_argument("--quantize", default=None, choices=[None, "int8", "fp8"],
                   help="weight-only quantization (halves decode HBM weight "
                        "traffic; fp8 = e4m3 per-channel)")
    p.add_argument("--kv-quantize", default=None, choices=[None, "int8"],
                   help="int8 KV-cache pools with per-vector scales (~48%% "
                        "less KV stream per decode step; transfers/offload "
                        "stay bf16 so mixed fleets interoperate)")
    # infra
    p.add_argument("--disagg-role", default=None, choices=[None, "prefill", "decode", "both"],
                   help="disaggregation role; prefill workers park KV for decode pulls")
    p.add_argument("--disagg-chunk-pages", type=int, default=16,
                   help="P->D KV pull chunk size in pages (0 = one message)")
    p.add_argument("--shadow", action="store_true",
                   help="active/passive failover: load+warm the engine but "
                        "only register when the active worker's discovery "
                        "record disappears (shadow-engine-failover analog)")
    p.add_argument("--vision", action="store_true",
                   help="serve a vision encoder (multimodal EPD): publishes "
                        "the encode endpoint + vision card info")
    p.add_argument("--image-token-id", type=int, default=None,
                   help="placeholder token id (default: vocab_size - 1)")
    p.add_argument("--status-port", type=int, default=0,
                   help="serve /live /health /metrics on this port (0 = off)")
    p.add_argument("--digest-period", type=float, default=2.0,
                   help="fleet digest publish period in seconds (0 = off; "
                        "docs/observability.md Fleet view)")
    # flight recorder (observability; docs/observability.md)
    p.add_argument("--recorder-size", type=int, default=4096,
                   help="flight-recorder ring capacity in iterations "
                        "(0 = recorder off)")
    p.add_argument("--anomaly-k", type=float, default=4.0,
                   help="iteration wall time > EWMA*k fires the anomaly "
                        "trigger (dump + optional profile window)")
    p.add_argument("--anomaly-dump-dir", default=None,
                   help="directory for anomaly ring dumps (unset = no dumps)")
    p.add_argument("--anomaly-dump-last-n", type=int, default=256,
                   help="ring records written per anomaly dump")
    p.add_argument("--anomaly-profile-ms", type=int, default=0,
                   help="jax.profiler capture window on anomaly, in ms "
                        "(0 = off; traces land under the dump dir)")
    p.add_argument("--sanitize", action="store_true",
                   help="arm the runtime sanitizer: transfer_guard around "
                        "steady-state dispatches, recompile tripwire, "
                        "lock-order recorder, task/pool audits (DYN_SAN=1 "
                        "is the env equivalent)")
    p.add_argument("--discovery-backend", default=None)
    p.add_argument("--discovery-root", default=None)
    p.add_argument("--request-plane", default=None, choices=[None, "tcp", "nats"],
                   help="RPC transport: tcp (default) or nats broker "
                        "subjects (env DYN_REQUEST_PLANE / DYN_NATS_URL)")
    # multi-host worker group (parallel/multihost.py): N processes form one
    # logical worker over a single jax.distributed global mesh. Process 0
    # serves; 1..N-1 replay its step stream. Mesh axis sizes above refer to
    # the GLOBAL device count.
    p.add_argument("--mh-coordinator", default=None,
                   help="host:port of the group coordinator (rank 0); "
                        "enables multi-host mode")
    p.add_argument("--mh-num-processes", type=int, default=1)
    p.add_argument("--mh-process-id", type=int, default=0)
    p.add_argument("--mh-step-port", type=int, default=0,
                   help="leader step-plane port (required when "
                        "--mh-num-processes > 1)")
    p.add_argument("--mh-local-devices", type=int, default=None,
                   help="virtual CPU devices per process (tests)")
    return p.parse_args(argv)


def _lora_kwargs(args, config) -> dict:
    """Load every --lora spec up front: duplicate names are an error (a
    repeat would silently keep the first checkpoint's weights), and the
    stacked tree's targets are the union of what the checkpoints actually
    adapt (a PEFT adapter touching MLP projections must not be silently
    half-applied)."""
    extra = int(getattr(args, "lora_slots", 0) or 0)
    if not args.lora:
        if extra > 0:
            # dynamic-only: free slots for rl load_adapter, nothing at boot
            args._lora_factors = []
            return {"lora_slots": extra, "lora_rank": args.lora_rank}
        return {}
    from dynamo_tpu.models import lora as lora_mod

    names = [s.partition("=")[0] for s in args.lora]
    dupes = {n for n in names if names.count(n) > 1}
    if dupes:
        raise SystemExit(f"duplicate --lora adapter names: {sorted(dupes)}")
    loaded = []
    targets = set()
    for i, spec in enumerate(args.lora):
        name, _, path = spec.partition("=")
        if path:
            factors = lora_mod.load_peft_adapter(path, config)
        else:
            factors = lora_mod.random_adapter(config, rank=args.lora_rank, seed=100 + i)
        targets.update(k[:-2] for k in factors)
        loaded.append((name, factors))
    # mixed-rank checkpoints share one stacked tree: zero-pad factors up to
    # the max rank (padded rows/cols contribute nothing to A @ B)
    import numpy as np

    rank = max(
        [args.lora_rank] + [f[k].shape[-1] for _, f in loaded for k in f if k.endswith("_a")]
    )
    for _, factors in loaded:
        for k, arr in list(factors.items()):
            r = arr.shape[-1] if k.endswith("_a") else arr.shape[-2]
            if r == rank:
                continue
            pad = [(0, 0)] * arr.ndim
            pad[-1 if k.endswith("_a") else -2] = (0, rank - r)
            factors[k] = np.pad(arr, pad)
    args._lora_factors = loaded
    return {
        "lora_slots": len(loaded) + extra,
        "lora_rank": rank,
        "lora_targets": tuple(sorted(targets)),
    }


def _jax_versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def build_runner(args, save_snapshot_ok: bool = True) -> tuple[ModelRunner, "object"]:
    """Construct the ModelRunner (and its model config) from CLI args —
    shared by the serving leader and multi-host follower replicas, which
    must build bit-identical runners (same config/seed/checkpoint).
    save_snapshot_ok=False suppresses the cold orbax-cache write: in a
    group every process sees the same args, and N concurrent writers
    would corrupt one snapshot directory — only the leader writes."""
    import os

    # resolve the model CONFIG first (config.json only — no weights);
    # every warm tier below validates against it
    if args.checkpoint:
        from dynamo_tpu.engine.hub import fetch_model
        from dynamo_tpu.engine.weights import config_from_hf

        # --checkpoint accepts hub repo ids too (hf://org/name or
        # org/name); local dirs pass through untouched (hub.rs role)
        args.checkpoint = fetch_model(args.checkpoint, config_only=True)
        config = config_from_hf(args.checkpoint, name=args.model_name or args.model)
    else:
        config = get_config(args.model)
    if args.experts_held or args.expert_first:
        config = config.with_(n_experts_held=args.experts_held,
                              expert_first=args.expert_first)

    params = None
    # warm tier 1 — host-shm staging (gpu_memory_service analog,
    # engine/shm_weights.py): a peer on this host (or our own previous
    # incarnation) already holds the tree in /dev/shm — attach zero-copy
    # views and skip disk entirely. The stage carries a model-config
    # fingerprint; a stale stage for a DIFFERENT model under the same
    # name is ignored (and later REPLACED by our publish — the fallback
    # is free: just load cold).
    shm_stage = None
    shm_meta = {
        "model": config.name, "vocab": config.vocab_size, "dim": config.dim,
        "n_layers": config.n_layers, "n_heads": config.n_heads,
        "n_kv_heads": config.n_kv_heads,
    }
    if getattr(args, "shm_weights", None):
        from dynamo_tpu.engine import shm_weights

        stage = shm_weights.attach(args.shm_weights)
        if stage is not None:
            if stage.meta == shm_meta:
                log.info(
                    "fast restart: attached %d staged arrays (%.1f MB shm) "
                    "as %r", stage.n_arrays, stage.nbytes / 1e6,
                    args.shm_weights,
                )
                params = stage.params
                shm_stage = stage
                # pin the mapping for the life of the process: the views
                # feed device_put now and any later snapshot write
                _SHM_STAGES.append(stage)
            else:
                log.warning(
                    "shm stage %r fingerprint %s does not match model "
                    "config %s; loading cold (our publish will replace "
                    "the stale stage)", args.shm_weights, stage.meta,
                    shm_meta,
                )
                stage.close()
    # warm tier 2 — orbax snapshot: short-circuits the expensive HF
    # checkpoint load (that is the whole point of fast restart)
    snapshot_present = bool(
        args.orbax_cache
        and os.path.isdir(args.orbax_cache)
        and os.listdir(args.orbax_cache)
    )
    if params is None and snapshot_present:
        from dynamo_tpu.engine.weights import load_orbax

        log.info("fast restart: loading params snapshot %s", args.orbax_cache)
        params = load_orbax(args.orbax_cache)
        embed = params.get("embed")
        if embed is None or tuple(embed.shape) != (config.vocab_size, config.dim):
            raise SystemExit(
                f"snapshot {args.orbax_cache} does not match model config "
                f"{config.name} (embed {getattr(embed, 'shape', None)} vs "
                f"{(config.vocab_size, config.dim)}); delete the snapshot "
                "to rebuild it"
            )
    # cold — HF checkpoint weights
    if params is None and args.checkpoint:
        from dynamo_tpu.engine.hub import fetch_model
        from dynamo_tpu.engine.weights import load_hf_checkpoint

        args.checkpoint = fetch_model(args.checkpoint)  # now the weights
        params = load_hf_checkpoint(args.checkpoint, config)
    # re-warm whichever tier is empty: the snapshot is written even when
    # params came from shm (a host reboot clears /dev/shm; disk must not
    # depend on which peer happened to boot first), and the shm stage is
    # published from any cold/snapshot load (publish replaces atomically,
    # so a stale other-model stage under our name is repaired here too)
    save_snapshot = bool(
        args.orbax_cache and params is not None and not snapshot_present
    )
    if (getattr(args, "shm_weights", None) and shm_stage is None
            and params is not None):
        from dynamo_tpu.engine import shm_weights

        shm_weights.publish(args.shm_weights, params, meta=shm_meta)
    mesh = MeshConfig(
        data=args.data_parallel,
        model=args.tensor_parallel,
        expert=args.expert_parallel,
        seq=args.seq_parallel,
        pipe=getattr(args, "pipeline_parallel", 1),
    )
    max_pages_per_seq = -(-args.max_seq_len // args.page_size)
    draft_config = draft_params = None
    if getattr(args, "spec_draft_model", None) and not args.draft_model:
        args.draft_model = args.spec_draft_model
    if args.draft_model or args.draft_checkpoint:
        if args.draft_checkpoint:
            from dynamo_tpu.engine.weights import config_from_hf, load_hf_checkpoint

            draft_config = config_from_hf(
                args.draft_checkpoint, name=args.draft_model or "draft"
            )
            draft_params = load_hf_checkpoint(args.draft_checkpoint, draft_config)
        else:
            draft_config = get_config(args.draft_model)
    runner = ModelRunner(
        config,
        mesh,
        num_pages=args.num_pages,
        page_size=args.page_size,
        max_pages_per_seq=max_pages_per_seq,
        params=params,
        draft_config=draft_config,
        draft_params=draft_params,
        spec_gamma=args.spec_gamma,
        quantize=args.quantize,
        kv_quantize=args.kv_quantize,
        **_lora_kwargs(args, config),
    )
    for name, factors in getattr(args, "_lora_factors", []):
        runner.register_adapter(name, factors)
    if save_snapshot and save_snapshot_ok:
        from dynamo_tpu.engine.weights import save_orbax

        log.info("writing params snapshot to %s", args.orbax_cache)
        save_orbax(params, args.orbax_cache)
    return runner, config


def build_engine(args, runner=None) -> tuple[InferenceEngine, ModelCard]:
    if runner is None:
        runner, config = build_runner(args)
    else:
        # multi-host leader: runner was built (and wrapped) by the caller
        config = runner.config
    mesh = runner.mesh_config
    engine = InferenceEngine(
        runner, max_batch=args.max_batch, chunk_size=args.chunk_size,
        mixed_prefill_tokens=getattr(args, "mixed_prefill_tokens", 256),
        mixed_prefill_seqs=getattr(args, "mixed_prefill_seqs", 8),
        mixed_min_chunk=getattr(args, "mixed_min_chunk", 16),
        host_kv_blocks=args.host_kv_blocks,
        disk_kv_blocks=args.disk_kv_blocks, disk_kv_root=args.disk_kv_root,
        obj_kv_root=args.obj_kv_root,
        kv_tier_quantize=getattr(args, "kv_tier_quantize", False),
        onboard_layer_groups=getattr(args, "onboard_layer_groups", 1),
        prefetch=getattr(args, "prefetch", False),
        prefetch_max_inflight=getattr(args, "prefetch_max_inflight", 4),
        prefetch_bandwidth_mbps=getattr(args, "prefetch_bandwidth_mbps", 0.0),
        prefetch_hint_ttl_s=getattr(args, "prefetch_hint_ttl_s", 10.0),
        prefetch_pin_ttl_s=getattr(args, "prefetch_pin_ttl_s", 5.0),
        tokenizer_spec=args.tokenizer,
        recorder_size=getattr(args, "recorder_size", 4096),
        anomaly_k=getattr(args, "anomaly_k", 4.0),
        anomaly_dump_dir=getattr(args, "anomaly_dump_dir", None),
        anomaly_dump_last_n=getattr(args, "anomaly_dump_last_n", 256),
        anomaly_profile_ms=getattr(args, "anomaly_profile_ms", 0),
        spec_ngram=getattr(args, "spec_ngram", False),
        spec_k=getattr(args, "spec_k", 4),
        spec_max_tokens=getattr(args, "spec_max_tokens", 0),
        sanitize=getattr(args, "sanitize", None) or None,
    )
    if getattr(args, "shm_weights", None) or args.orbax_cache:
        # RL weight hot-swap: after update_weights the WARM TIERS hold a
        # superseded policy — a crash-restart would attach the stale shm
        # stage, or (shm gone) reload the old orbax snapshot from disk
        # and republish THAT, serving the old policy next to refreshed
        # peers. On every swap: drop the shm stage and refresh the orbax
        # cache from the new snapshot (atomic dir swap), so the restart
        # invariant holds: the warm tiers always contain the weights
        # being served. (Without --orbax-cache a restart falls back to
        # the ORIGINAL checkpoint — choose warm tiers accordingly for RL
        # workers.)
        _inner_update = engine.update_weights
        _stage_name = getattr(args, "shm_weights", None)
        _cache_dir = args.orbax_cache

        def _refresh_snapshot(src: str) -> None:
            import os
            import shutil as _sh
            import tempfile as _tf

            if os.path.realpath(src) == os.path.realpath(_cache_dir):
                return
            parent = os.path.dirname(os.path.abspath(_cache_dir)) or "."
            tmp = _tf.mkdtemp(prefix=".orbax_swap_", dir=parent)
            new = os.path.join(tmp, "new")
            _sh.copytree(src, new)
            old = os.path.join(tmp, "old")
            if os.path.exists(_cache_dir):
                os.rename(_cache_dir, old)
            os.rename(new, _cache_dir)
            _sh.rmtree(tmp, ignore_errors=True)

        async def _update_and_invalidate(path: str) -> int:
            import asyncio as _aio

            version = await _inner_update(path)
            if _stage_name:
                from dynamo_tpu.engine import shm_weights as _shm

                _shm.unlink(_stage_name)
            if _cache_dir:
                try:
                    await _aio.to_thread(_refresh_snapshot, path)
                except Exception:
                    log.exception(
                        "orbax cache refresh from %s failed — a restart "
                        "would reload the superseded snapshot", path,
                    )
            log.info("warm tiers refreshed after weight update v%d", version)
            return version

        engine.update_weights = _update_and_invalidate
    vision = None
    if args.vision:
        from dynamo_tpu.models.vision import TINY_VISION, VisionConfig

        import dataclasses as _dc

        vcfg = _dc.replace(
            TINY_VISION if config.dim <= 256 else VisionConfig(),
            out_dim=config.dim,
        )
        args._vision_config = vcfg
        vision = {
            "image_token_id": (
                args.image_token_id if args.image_token_id is not None
                else config.vocab_size - 1
            ),
            "n_image_tokens": vcfg.n_patches,
            "image_size": vcfg.image_size,
        }
    card = ModelCard(
        name=args.model_name or config.name,
        tokenizer=args.tokenizer,
        context_length=args.max_seq_len,
        kv_block_size=args.page_size,
        adapters=[s.partition("=")[0] for s in args.lora],
        vision=vision,
        runtime_config={
            "mesh": list(mesh.shape),
            "num_pages": args.num_pages,
            "max_batch": args.max_batch,
        },
    )
    return engine, card


async def async_main(args) -> None:
    configure_logging()
    if args.profiler_port:
        from dynamo_tpu.runtime.annotations import start_profiler_server

        start_profiler_server(args.profiler_port)
    kw = {}
    if args.discovery_root:
        kw["root"] = args.discovery_root
    if getattr(args, "request_plane", None):
        kw["request_plane"] = args.request_plane
    runtime = DistributedRuntime(discovery_backend=args.discovery_backend, **kw)
    spec = getattr(args, "_mh_spec", None)
    plane = None
    if getattr(args, "engine_sidecar", None):
        # out-of-process engine (reference lib/sidecar role): this worker
        # owns discovery + request plane; generate calls forward over gRPC
        from dynamo_tpu.frontend.protocols import ModelCard
        from dynamo_tpu.sidecar import SidecarEngine

        if args.vision:
            raise SystemExit(
                "--vision requires an in-process engine (the encoder runs "
                "next to the model); drop it or run without --engine-sidecar"
            )
        engine = SidecarEngine(args.engine_sidecar)
        health = await engine.health(timeout=30.0)
        card = ModelCard(
            name=args.model_name or health.get("model") or args.model,
            tokenizer=args.tokenizer,
            context_length=args.max_seq_len,
            kv_block_size=args.page_size,
        )
    elif spec is not None:
        # multi-host leader: accept the follower connections first, then
        # build the runner (followers build theirs concurrently) and wrap
        # it so every device-touching call replays group-wide
        from dynamo_tpu.parallel import multihost as mh

        plane = mh.StepPlaneLeader(spec.step_port, spec.num_processes - 1)
        plane.wait_followers()
        # weight load / shm attach polls and compiles: off the loop so
        # startup never stalls heartbeats already running on it (DYN-A001)
        leader_runner, _ = await asyncio.to_thread(build_runner, args)
        engine, card = await asyncio.to_thread(
            build_engine, args, runner=mh.ReplicatingRunner(leader_runner, plane)
        )
    else:
        engine, card = await asyncio.to_thread(build_engine, args)
    group_broken_box = [False]
    stop_box = []  # filled with (loop, stop_ev) once serving starts
    if plane is not None and hasattr(engine, "on_fatal"):
        # multi-host group leader: a dead follower is unrecoverable
        # (GroupBroken) — exit nonzero so the supervisor restarts the
        # whole group. Wired BEFORE the worker serves: a request hitting
        # an already-broken group on the very first step must still
        # trigger the exit path.
        def _group_fatal():
            group_broken_box[0] = True
            if stop_box:
                lp, ev = stop_box[0]
                lp.call_soon_threadsafe(ev.set)

        engine.on_fatal(_group_fatal)
    if args.vision:
        import jax

        from dynamo_tpu.frontend.encoder import ENCODE_ENDPOINT, EncodeEngine
        from dynamo_tpu.models import vision as vision_mod

        vparams = vision_mod.init_params(args._vision_config, jax.random.PRNGKey(7))
        await runtime.serve_endpoint(
            f"{args.namespace}/{ENCODE_ENDPOINT}",
            EncodeEngine(args._vision_config, vparams),
        )
    status = None
    if args.status_port:
        from dynamo_tpu.runtime.status import StatusServer

        status = StatusServer(runtime, port=args.status_port)
        # SidecarEngine has no step thread — the remote engine's health is
        # its own; this check then only covers the local process
        status.add_check(
            "engine", lambda: getattr(engine, "_thread", True) is not None
        )
        _rec = getattr(engine, "recorder", None)
        if _rec is not None and _rec.enabled:
            from dynamo_tpu.runtime.flight_recorder import to_chrome_trace

            status.add_timeline(
                lambda last_n=None: to_chrome_trace(_rec.snapshot(last_n))
            )
        _runner = getattr(engine, "runner", None)
        if _runner is not None and _runner.device_report():
            # GET /debug/device: the platform, devices and dispatch paths
            # actually in effect (chip_smoke.py asserts on it — a worker
            # that came up on the CPU must not pass for one on the chip)
            status.add_debug("device", lambda _q: {
                **_runner.device_report(),
                "fused_mixed": bool(engine.fused_mixed),
                "jax": _jax_versions(),
                "compile": _runner.compile_stats(),
            })
        _san = getattr(engine, "sanitizer", None)
        if _san is not None:
            # GET /debug/sanitizer: violations + counters (layout_checked
            # proves the DYN-S layout guard ran at the warm transition)
            status.add_debug("sanitizer", lambda _q: _san.report())
        await status.start()
    from dynamo_tpu.worker_common import serve_worker

    path = f"{args.namespace}/{args.component}/{args.endpoint}"
    shadow = None
    worker = None
    if args.shadow:
        # active/passive failover (runtime/shadow.py): the engine above is
        # already warm (weights + jit + pools); hold it out of discovery
        # until the active worker's record disappears, then register — the
        # restart skips the model load, matching the reference's
        # shadow-engine-failover recovery path.
        from dynamo_tpu.runtime.shadow import ShadowServer

        async def _activate():
            return await serve_worker(
                runtime, engine, card,
                namespace=args.namespace, component=args.component,
                endpoint=args.endpoint, disagg_role=args.disagg_role,
                disagg_chunk_pages=args.disagg_chunk_pages,
                http_address=args.http_address,
                digest_period_s=args.digest_period,
            )

        shadow = ShadowServer(
            runtime, path, activate=_activate, metadata={"model": card.name}
        )
        await shadow.start()
        print(f"worker standing by as shadow for {path}", flush=True)
    else:
        worker = await serve_worker(
            runtime, engine, card,
            namespace=args.namespace, component=args.component, endpoint=args.endpoint,
            disagg_role=args.disagg_role,
            disagg_chunk_pages=args.disagg_chunk_pages,
            http_address=args.http_address,
            digest_period_s=args.digest_period,
        )
        print(f"worker serving {card.name} at {path}", flush=True)
    promotion_failed = False
    group_broken = False
    try:
        stop_ev = asyncio.Event()
        import signal

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop_ev.set)
            except NotImplementedError:  # pragma: no cover
                pass
        if shadow is not None:
            # a failed promotion must kill the process (exit nonzero so
            # the supervisor restarts it) — not leave an invisible zombie
            # that neither serves nor stands by
            shadow.promoted.add_done_callback(
                lambda f: stop_ev.set() if f.exception() is not None else None
            )
        stop_box.append((loop, stop_ev))
        if group_broken_box[0]:
            stop_ev.set()  # broke before we started waiting
        await stop_ev.wait()
        group_broken = group_broken_box[0]
        if group_broken:
            print("worker group BROKEN; exiting for restart", flush=True)
        elif (shadow is not None and shadow.promoted.done()
                and shadow.promoted.exception() is not None):
            promotion_failed = True
            print("shadow promotion FAILED; exiting", flush=True)
        else:
            print("draining...", flush=True)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        # teardown steps are individually guarded: after a group break the
        # jax.distributed coordination service is already unhealthy and a
        # raising cleanup step must not mask the intended exit code
        async def _safe(coro):
            try:
                await coro
            except Exception:
                log.exception("teardown step failed")

        if shadow is not None:
            await _safe(shadow.stop())
            if shadow.promoted.done() and shadow.promoted.exception() is None:
                worker = shadow.promoted.result()
        if worker is not None:
            await _safe(worker.stop())
        if status is not None:
            await _safe(status.stop())
        if plane is not None:
            try:
                plane.close()  # releases followers from their replay loops
            except Exception:
                # best-effort: after a group break the plane socket may
                # already be dead; the exit path below is what matters
                log.debug("step-plane close failed during teardown",
                          exc_info=True)
        await _safe(runtime.shutdown())
    if promotion_failed:
        raise SystemExit(1)
    if group_broken:
        # bypass interpreter teardown: the coordination service raises on
        # atexit with a dead rank, which would repaint the exit code
        import os as _os
        import sys as _sys

        _sys.stdout.flush()
        _os._exit(13)


def main(argv=None) -> None:
    import dynamo_tpu

    args = parse_args(argv)
    # before ANY jit: every process (leader, followers, single) must see
    # the cache so a restarted replica skips recompilation
    cache = dynamo_tpu.enable_compilation_cache()
    configure_logging()
    log.info("persistent compilation cache at %s", cache)
    if args.mh_coordinator and args.mh_num_processes > 1:
        from dynamo_tpu.parallel import multihost as mh

        if not args.mh_step_port:
            raise SystemExit("--mh-step-port is required for a multi-host group")
        spec = mh.MultihostSpec(
            coordinator=args.mh_coordinator,
            num_processes=args.mh_num_processes,
            process_id=args.mh_process_id,
            step_port=args.mh_step_port,
            local_devices=args.mh_local_devices,
        )
        mh.initialize(spec)
        if not spec.is_leader:
            configure_logging()
            # connect BEFORE building: runner construction device_puts over
            # the global mesh, which needs every process participating —
            # the leader only starts ITS build once all followers are
            # connected, so connecting late deadlocks the group
            sock = mh.follower_connect(
                spec.leader_host, spec.step_port, spec.process_id
            )
            runner, _ = build_runner(args, save_snapshot_ok=False)
            print(f"follower {spec.process_id} replaying for {spec.coordinator}",
                  flush=True)
            try:
                mh.follower_loop(runner, sock)
            finally:
                sock.close()
            return
        args._mh_spec = spec
    try:
        asyncio.run(async_main(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
