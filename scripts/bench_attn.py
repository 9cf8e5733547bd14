"""Microbench: decode paged attention, what a call and a live page cost.

The table (`python scripts/bench_attn.py`, on the chip): geometries phi-3
(Hk 32, G 1, D 96, window 2047 live) and GQA (Hk 8, G 4, D 128), page size
64, page table 64 wide, x rows 4 and 32 x contexts of 64, 448 and 4096
tokens a row, plus one mixed batch (1, 3, 7, 20 pages and four pad rows)
and the 4-row, 7-page point again under a page table 8 wide. Each line is
one JSON object: us a call, us a live page, and beside them the page's
DMA time at the chip's HBM peak (K + V as the pool holds them, a head dim
padded to 128 lanes). `us_call` times the call with the lengths fixed
across calls, as in a layer scan, where what depends on the lengths alone
is built once; `us_call_relisted` (the 4-row, 7-page points) makes the
lengths depend on the previous call's output, so every call rebuilds it. `--ragged` adds the
ragged-against-padded mixed comparison; `--jnp` the jnp gather path.

Timing rule: many iters fused in one jit via lax.scan with a data
dependency (out feeds next q), then ONE device_get — one dispatch and one
host sync around the whole timed region. The pools are layer-stacked
[L, NP, PS, Hk, D] and read at a traced layer, as the model hands them
over.

All device arrays are built inside main(): module import must never
initialize a JAX backend (DYN-J003), so `python -c "import bench_attn"`
and tooling that imports the script stay platform-neutral.
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

from dynamo_tpu.models.llama import paged_attention_jnp
from dynamo_tpu.ops.paged_attention import decode_paged_attention

B, Hk, G, D = 32, 8, 3, 128  # the ragged comparison's shapes
PS, MP = 64, 8
NP = B * MP + 8
ITERS = 64
HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")
LAYERS, LAYER = 2, 1  # the stacked pools, and the layer read
# the benchmark cell's pool. Not rows x pages: at D 96 XLA gives a pool
# parameter of some thousand pages a layout with the PAGE axis minor (less
# padding than 96 -> 128 lanes), and the copy into the kernel's layout at
# the program's entry then costs more than the 64 calls (my chip run,
# PR 29: 10 ms a dispatch)
POOL_PAGES = 176

GEOMETRIES = {
    "phi-3": dict(Hk=32, G=1, D=96, window=2047),
    "gqa": dict(Hk=8, G=4, D=128, window=None),
}
ROWS, PAGES = (4, 32), (1, 7, 64)  # 64, 448 and 4096 tokens a row
MIXED_PAGES = (1, 3, 7, 20, 0, 0, 0, 0)


@partial(jax.jit, static_argnames=("impl", "relist"),
         donate_argnames=("k_pool", "v_pool"))
def decode_loop(q, k_pool, v_pool, pt, kv_lens, window, impl, relist):
    """ITERS chained calls; the pools are donated and handed back, as
    the step programs carry them."""
    def body(q, i):
        kv = kv_lens
        if relist:  # never true, and XLA cannot know: the lengths now
            # hang on the carried q, so nothing built from them is hoisted
            kv = kv + (q[0, 0, 0, 0] > 3e38).astype(jnp.int32)
        if impl == "pallas":
            o = decode_paged_attention(q, k_pool, v_pool, pt, kv, window,
                                       jnp.minimum(i, LAYER))
        else:
            o = paged_attention_jnp(
                q[:, None], k_pool[LAYER], v_pool[LAYER], pt,
                jnp.maximum(kv - 1, 0)[:, None], kv, window=window,
            )[:, 0]
        return o.astype(q.dtype), None

    q, _ = lax.scan(body, q, jnp.arange(ITERS) + LAYER)
    return q, k_pool, v_pool


def _time(fn) -> float:
    np.asarray(jax.device_get(fn()))  # warmup + compile
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(fn()))
        times.append((time.perf_counter() - t0) / ITERS * 1e6)
    return min(times)


def live_pages(kv_len: int, window, ps: int) -> int:
    if kv_len <= 0:
        return 0
    lo = max(kv_len - window, 0) if window else 0
    return (kv_len - 1) // ps - lo // ps + 1


def bench_point(name, geom, pages, mp, pools, impls, relisted=False) -> None:
    """One line of the table: rows holding `pages[b]` whole pages each
    (0 = a pad row), under a page table `mp` wide. `pools` is the list
    [k_pool, v_pool], rebound to what each donating call hands back."""
    hk, g, d, window = geom["Hk"], geom["G"], geom["D"], geom["window"]
    rows = len(pages)
    rng = np.random.default_rng(0)
    pt = np.zeros((rows, mp), np.int32)
    for b, n in enumerate(pages):
        # drawn from a pool smaller than the rows' contexts, so pages are
        # shared (read-only here); a step never repeats its predecessor's
        # page, so every live page is one DMA
        pt[b, :n] = (rng.integers(1, POOL_PAGES, n).cumsum()
                     + rng.integers(POOL_PAGES)) % POOL_PAGES
    kv_lens = np.asarray(pages, np.int32) * PS
    live = sum(live_pages(int(n), window, PS) for n in kv_lens)
    q = jnp.asarray(rng.standard_normal((rows, hk, g, d)), jnp.bfloat16)
    win = None if window is None else jnp.int32(window)
    tail = (jnp.asarray(pt), jnp.asarray(kv_lens), win)
    page_bytes = 2 * PS * hk * (-(-d // 128) * 128) * pools[0].dtype.itemsize

    def call(impl, relist):
        out, pools[0], pools[1] = decode_loop(
            q, pools[0], pools[1], *tail, impl=impl, relist=relist)
        return out

    line = {"point": name, "rows": rows, "pages_a_row": max(pages),
            "page_table": mp, "live_pages": live,
            "dma_us_page": round(page_bytes / HBM_BYTES_PER_S * 1e6, 3)}
    for impl in impls:
        us = _time(partial(call, impl, False))
        key = "us_call" if impl == "pallas" else f"us_call_{impl}"
        line[key] = round(us, 1)
        if impl == "pallas":
            line["us_live_page"] = round(us / live, 3)
            if relisted:
                line["us_call_relisted"] = round(
                    _time(partial(call, impl, True)), 1)
    print(json.dumps(line), flush=True)


def bench_decode_table(impls) -> None:
    for gname, geom in GEOMETRIES.items():
        shape = (LAYERS, POOL_PAGES, PS, geom["Hk"], geom["D"])
        keys = jax.random.split(jax.random.key(0), 2)
        pools = [jax.random.normal(k, shape, jnp.bfloat16) for k in keys]
        for rows in ROWS:
            for pages in PAGES:
                bench_point(f"{gname} {rows}x{pages}", geom, (pages,) * rows,
                            64, pools, impls, relisted=(rows, pages) == (4, 7))
        bench_point(f"{gname} mixed", geom, MIXED_PAGES, 64, pools, impls)
        bench_point(f"{gname} 4x7 MP8", geom, (7,) * 4, 8, pools, impls)
        del pools


def check_decode(interpret: bool):
    """Numeric agreement with the jnp gather, ragged lengths and a pad
    row; returns (rng, k_pool, v_pool) for the ragged comparison."""
    rng = np.random.default_rng(0)
    k_pool = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP)
                     .astype(np.int32))
    kv = rng.integers(1, PS * MP, B).astype(np.int32)
    kv[:3] = (0, 1, PS * MP)
    kv_lens = jnp.asarray(kv)
    q0 = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    o1 = np.asarray(jax.device_get(decode_paged_attention(
        q0, k_pool, v_pool, pt, kv_lens, interpret=interpret)), np.float32)
    o2 = np.asarray(
        jax.device_get(paged_attention_jnp(
            q0[:, None], k_pool, v_pool, pt,
            jnp.maximum(kv_lens - 1, 0)[:, None], kv_lens,
        )[:, 0]),
        np.float32,
    )
    print("max abs diff:", np.abs(o1 - o2)[kv > 0].max(), flush=True)
    return rng, k_pool, v_pool


def bench_ragged_mixed(rng, k_pool, v_pool) -> None:
    """Ragged mixed dispatch: one flat-token grid vs the padded pair
    (decode batch via decode_paged_attention + [N, S] bucket-padded
    chunks via prefill_paged_attention). Same KV pools; disjoint pages
    per segment. On CPU only numeric parity runs (interpret mode timing
    is meaningless); on TPU the scan-with-dependency timing rule above
    applies."""
    from dynamo_tpu.ops.flash_prefill import prefill_paged_attention
    from dynamo_tpu.ops.ragged_paged_attention import (
        build_ragged_metadata,
        ragged_attention_reference,
        ragged_paged_attention,
    )

    DEC_B, DEC_KV = 8, 256
    CHUNKS = (512, 32, 32, 32)
    S_BUCKET = 512  # chunk bucket the padded path rounds every row up to
    T_REAL = DEC_B + sum(CHUNKS)
    T_B = (T_REAL + 7) // 8 * 8

    q_lens = [1] * DEC_B + list(CHUNKS)
    q_starts = [DEC_KV - 1] * DEC_B + [0] * len(CHUNKS)
    kv_lens_r = [DEC_KV] * DEC_B + list(CHUNKS)
    rows = [list(range(i * MP, (i + 1) * MP)) for i in range(len(q_lens))]
    md = build_ragged_metadata(q_lens, q_starts, kv_lens_r, rows, T_B,
                               max_pages=MP)
    q_flat = jnp.asarray(rng.standard_normal((T_B, Hk, G, D)), jnp.bfloat16)
    seg_pt = jnp.asarray(md["seg_page_table"])
    seg_kvl = jnp.asarray(md["seg_kv_lens"])
    meta = jnp.asarray(md["meta"])

    cu = md["cu_q_lens"]
    q_dec = q_flat[:DEC_B]
    q_pad = jnp.zeros((len(CHUNKS), S_BUCKET, Hk, G, D), jnp.bfloat16)
    for i, n in enumerate(CHUNKS):
        q_pad = q_pad.at[i, :n].set(q_flat[cu[DEC_B + i] : cu[DEC_B + i] + n])
    pt_dec = jnp.asarray(np.asarray(rows[:DEC_B], np.int32))
    kvl_dec = jnp.full((DEC_B,), DEC_KV, jnp.int32)
    pt_chunk = jnp.asarray(np.asarray(rows[DEC_B:], np.int32))
    qs_chunk = jnp.zeros((len(CHUNKS),), jnp.int32)
    ql_chunk = jnp.asarray(np.asarray(CHUNKS, np.int32))
    kvl_chunk = ql_chunk

    if jax.devices()[0].platform == "cpu":
        out = ragged_paged_attention(q_flat, k_pool, v_pool, seg_pt, seg_kvl,
                                     meta, interpret=True)
        ref = ragged_attention_reference(
            q_flat, k_pool, v_pool, jnp.asarray(md["tok_page_table"]),
            jnp.asarray(md["tok_positions"]), jnp.asarray(md["tok_kv_lens"]),
        )
        d = np.abs(np.asarray(out[:T_REAL], np.float32)
                   - np.asarray(ref[:T_REAL], np.float32)).max()
        print(f"ragged mixed (cpu parity only): tokens ragged={T_REAL} "
              f"padded={DEC_B + len(CHUNKS) * S_BUCKET}  max abs diff: {d}",
              flush=True)
        return

    @partial(jax.jit, static_argnames=("impl",))
    def mixed_loop(q_f, q_d, q_p, impl):
        if impl == "ragged":
            def body(q, _):
                o = ragged_paged_attention(q, k_pool, v_pool, seg_pt,
                                           seg_kvl, meta)
                return o.astype(q.dtype), None

            q, _ = lax.scan(body, q_f, None, length=ITERS)
            return q
        def body(carry, _):
            qd, qp = carry
            od = decode_paged_attention(qd, k_pool, v_pool, pt_dec, kvl_dec)
            op = prefill_paged_attention(qp, k_pool, v_pool, pt_chunk,
                                         qs_chunk, ql_chunk, kvl_chunk)
            return (od.astype(qd.dtype), op.astype(qp.dtype)), None

        (qd, _qp), _ = lax.scan(body, (q_d, q_p), None, length=ITERS)
        return qd

    for impl in ("padded", "ragged"):
        out = mixed_loop(q_flat, q_dec, q_pad, impl)
        np.asarray(jax.device_get(out))  # warmup + compile
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = mixed_loop(q_flat, q_dec, q_pad, impl)
            np.asarray(jax.device_get(out))
            times.append((time.perf_counter() - t0) / ITERS * 1e6)
        toks = T_REAL if impl == "ragged" else DEC_B + len(CHUNKS) * S_BUCKET
        print(f"mixed {impl:7s} tokens={toks:5d} per-iter: "
              f"{min(times):8.1f} us", flush=True)


def main() -> None:
    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()
    cpu = jax.devices()[0].platform == "cpu"  # pallas needs interpret on CPU
    rng, k_pool, v_pool = check_decode(interpret=cpu)
    if "--ragged" in sys.argv[1:] or cpu:
        bench_ragged_mixed(rng, k_pool, v_pool)
    if cpu:
        print("no accelerator: parity only (nothing timed on a CPU is a "
              "device number)", flush=True)
        return
    impls = ("pallas", "jnp") if "--jnp" in sys.argv[1:] else ("pallas",)
    bench_decode_table(impls)


if __name__ == "__main__":
    main()
