"""`python -m dynamo_tpu.sidecar` — run the native engine behind gRPC.

The engine (and the TPU) live in this process; a separate
`python -m dynamo_tpu.worker --engine-sidecar HOST:PORT` process owns
discovery/request-plane and forwards requests here (reference
lib/sidecar role: engine and runtime restart independently)."""

from __future__ import annotations

import argparse
import asyncio

from dynamo_tpu.runtime.logging_util import configure_logging
from dynamo_tpu.sidecar import EngineSidecarServer


def parse_args(argv=None):
    from dynamo_tpu.worker import parse_args as worker_args

    # reuse the worker's engine-shaping flags; add the listen port
    p = argparse.ArgumentParser("dynamo_tpu.sidecar", add_help=False)
    p.add_argument("--grpc-port", type=int, default=9345)
    ns, rest = p.parse_known_args(argv)
    wargs = worker_args(rest)
    wargs.grpc_port = ns.grpc_port
    return wargs


async def async_main(args) -> None:
    from dynamo_tpu.worker import build_engine

    configure_logging()
    # build off the loop: shm weight attach polls with time.sleep and the
    # runner may compile — neither belongs on the event loop (DYN-A001)
    engine, card = await asyncio.to_thread(build_engine, args)
    engine.start()
    server = EngineSidecarServer(
        engine, model_name=card.name, port=args.grpc_port
    )
    await server.start()
    print(f"sidecar serving {card.name} on :{server.port}", flush=True)
    try:
        await asyncio.Event().wait()
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        await server.stop()
        engine.stop()


def main(argv=None) -> None:
    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()  # before any jit
    try:
        asyncio.run(async_main(parse_args(argv)))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
