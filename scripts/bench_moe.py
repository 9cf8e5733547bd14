"""Microbench: the routed experts of a decode step, every held expert
against the hit experts' work list.

The table (`python scripts/bench_moe.py`, on the chip): the benchmark
cell's geometry (`mistral-small-4-119b`: dim 4096, expert width 2048, 32
held experts of a router 128 wide from expert 32 on, 4 picks a row, bf16),
x rows 8, 25 and 32 x 1, 8, 17 and 32 of the held experts picked by some
row. Each line is one JSON object: `ms_dense`, the `moe.experts` scope as
models/moe.py ran it for every forward until PR 34 (every held expert over
every row, the routed terms picked out afterwards), and `ms_listed`, the
work-list kernel (ops/moe_experts.py), with the GB/s of the LISTED
experts' bytes beside it. `ms_listed` keeps the picks fixed across calls,
so the list is built once above them; `ms_relisted` makes the picks hang
on the previous call's output, as in a layer scan, so every call builds
its list (the difference is what the list costs a layer). `--tiles 256,512`
adds the kernel at those ffn tiles.

Timing rule (scripts/bench_attn.py's): many calls fused in one jit via
lax.scan with a data dependency (out feeds the next call's rows), then ONE
device_get. The weights are layer-stacked [L, n_held, ...] and read at a
traced layer, as the model hands them over.

All device arrays are built inside main(): module import must never
initialize a JAX backend (DYN-J003).
"""

import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.ops.moe_experts import hit_work_list, routed_experts

ITERS = 64
HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")
LAYERS, LAYER = 2, 1  # the stacked weights, and the layer read
CELL = dict(E=4096, F=2048, n_held=32, first=32, n_experts=128, k=4)
ROWS, LISTED = (8, 25, 32), (1, 8, 17, 32)


def dense_experts(x, we_gate, we_up, we_down, sel, weights, first, n_held):
    """models/moe.py's `moe.experts` scope as it stood before the work
    list: x [T, E], one layer's weights -> [T, E]."""
    def one_expert(wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    expert_out = jax.vmap(one_expert)(we_gate, we_up, we_down)  # [n, T, E]
    local = sel - first
    here = (local >= 0) & (local < n_held)
    weights = jnp.where(here, weights, 0)
    local = jnp.clip(local, 0, n_held - 1)
    sel_out = jnp.take_along_axis(
        expert_out.transpose(1, 0, 2), local[..., None], axis=1)  # [T, k, E]
    return jnp.sum(sel_out * weights[..., None], axis=1)


@partial(jax.jit, static_argnames=("impl", "relist", "first", "tile",
                                   "interpret"))
def expert_loop(x, we_gate, we_up, we_down, sel, weights, valid, first,
                impl, relist=False, tile=None, interpret=False):
    """ITERS chained calls on layer LAYER of the stacks."""
    n_held = we_gate.shape[1]

    def body(x, i):
        s = sel
        if relist:  # never true, and XLA cannot know: the picks now hang
            # on the carried rows, so the list is built in every call
            s = s + (x[0, 0] > 3e38).astype(jnp.int32)
        layer = jnp.minimum(i, LAYER)
        if impl == "listed":
            work, n_work, wcol = hit_work_list(s, weights, valid, first,
                                               n_held)
            y = routed_experts(x, work, n_work, wcol, we_gate, we_up,
                               we_down, layer, tile=tile,
                               interpret=interpret)
        else:
            y = dense_experts(x, we_gate[layer], we_up[layer],
                              we_down[layer], s, weights, first, n_held)
        # keep the rows' scale from call to call
        return (x + 1e-3 * y.astype(x.dtype)).astype(x.dtype), None

    x, _ = lax.scan(body, x, jnp.arange(ITERS) + LAYER)
    return x


def picks(rows: int, listed: int, g: dict, rng) -> np.ndarray:
    """sel [rows, k]: exactly `listed` of the held experts picked by some
    row (as many as rows x k allows), every other pick an expert held
    elsewhere; a row's picks distinct."""
    k, first = g["k"], g["first"]
    sel = np.zeros((rows, k), np.int32)
    for t in range(rows):
        row = []
        for j in range(k):
            e = first + (t * k + j) % listed
            row.append(e if e not in row else j)  # ids below `first`
        sel[t] = rng.permutation(row)
    return sel


def _time(fn) -> float:
    np.asarray(jax.device_get(fn()))  # warmup + compile
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(fn()))
        times.append((time.perf_counter() - t0) / ITERS * 1e3)
    return min(times)


def make_case(g: dict, rows: int, listed: int, dtype, seed: int = 0):
    rng = np.random.default_rng(seed)
    sel = picks(rows, listed, g, rng)
    w = rng.uniform(0.1, 0.4, sel.shape)
    x = rng.standard_normal((rows, g["E"]))
    return (jnp.asarray(x, dtype), jnp.asarray(sel),
            jnp.asarray(w, dtype), jnp.ones((rows,), bool))


def make_stacks(g: dict, dtype, layers: int = LAYERS):
    """Expert stacks made on the device, a layer at a time."""
    def one(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    n, E, F = g["n_held"], g["E"], g["F"]
    mk = jax.jit(one, static_argnums=(1, 2))
    return (mk(keys[0], (layers, n, E, F), E), mk(keys[1], (layers, n, E, F), E),
            mk(keys[2], (layers, n, F, E), F))


def check(interpret: bool) -> None:
    """Parity at a tiny size: the kernel against the dense scope."""
    g = dict(E=128, F=256, n_held=8, first=8, n_experts=32, k=4)
    stacks = make_stacks(g, jnp.float32)
    for rows, listed in ((5, 1), (8, 8), (16, 5)):
        x, sel, w, valid = make_case(g, rows, listed, jnp.float32)
        got, ref = (expert_loop(x, *stacks, sel, w, valid, g["first"], impl,
                                interpret=interpret)
                    for impl in ("listed", "dense"))
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-3, (rows, listed, err)
    print("parity ok", flush=True)


def bench_table(tiles) -> None:
    g = CELL
    stacks = make_stacks(g, jnp.bfloat16)
    expert_bytes = 3 * g["E"] * g["F"] * 2
    for rows in ROWS:
        x, sel, w, valid = make_case(g, rows, g["n_held"], jnp.bfloat16)
        ms_dense = _time(lambda: expert_loop(
            x, *stacks, sel, w, valid, g["first"], "dense"))
        for listed in LISTED:
            x, sel, w, valid = make_case(g, rows, listed, jnp.bfloat16)
            n = len({int(e) for e in np.asarray(sel).ravel()
                     if e >= g["first"]})
            line = {"rows": rows, "listed": n, "ms_dense": round(ms_dense, 4)}
            for tile in (None,) + tuple(tiles):
                tag = "" if tile is None else f"_tile{tile}"
                ms = _time(lambda: expert_loop(
                    x, *stacks, sel, w, valid, g["first"], "listed",
                    tile=tile))
                line["ms_listed" + tag] = round(ms, 4)
                line["listed_gb_s" + tag] = round(
                    n * expert_bytes / ms / 1e6, 1)
            line["ms_relisted"] = round(_time(lambda: expert_loop(
                x, *stacks, sel, w, valid, g["first"], "listed",
                relist=True)), 4)
            line["ms_listed_at_peak"] = round(
                n * expert_bytes / HBM_BYTES_PER_S * 1e3, 4)
            print(json.dumps(line), flush=True)


def main() -> None:
    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()
    cpu = jax.devices()[0].platform == "cpu"  # pallas needs interpret on CPU
    check(interpret=cpu)
    if cpu:
        print("no accelerator: parity only (nothing timed on a CPU is a "
              "device number)", flush=True)
        return
    tiles = ()
    if "--tiles" in sys.argv:
        tiles = tuple(int(t) for t in
                      sys.argv[sys.argv.index("--tiles") + 1].split(","))
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "geometry": CELL, "iters": ITERS}), flush=True)
    bench_table(tiles)


if __name__ == "__main__":
    main()
