"""How benchmark/tests/data/small.xplane.pb was made (on the chip):
    python3 benchmark/tests/make_small_trace.py chiprun_out/small_trace
A few steps of one small jitted program with a host span around them."""

import glob
import shutil
import sys

import jax
import jax.numpy as jnp


def small_step(x, w):
    for _ in range(3):
        x = jnp.tanh(x @ w)
    return x


def main(out_dir: str) -> None:
    f = jax.jit(small_step)
    x, w = jnp.ones((256, 512), jnp.bfloat16), jnp.ones((512, 512), jnp.bfloat16)
    f(x, w).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("engine.decode"):
        for _ in range(4):
            x = f(x, w)
        x.block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(f"{out_dir}/plugins/profile/*/*.xplane.pb")[0]
    shutil.copy(src, f"{out_dir}/small.xplane.pb")
    print(src, jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
