"""Microbench: decode and ragged paged attention, what a call and a live
page cost.

The table (`python scripts/bench_attn.py`, on the chip): geometries phi-3
(Hk 32, G 1, D 96, window 2047 live), GQA (Hk 8, G 4, D 128) and MQA
(Hk 1, G 20, D 128: ai21-jamba2-3b, over its cell's pool of 2880 pages),
page size 64, page table 64 wide, x rows 4 and 32 x contexts of 64, 448
and 4096 tokens a row, plus one mixed batch (1, 3, 7, 20 pages and four
pad rows), the 4-row, 7-page point again under a page table 8 wide, and
for MQA the cell's decode step (34 rows of 3-34 pages in a bucket of 64).
`mimo-global` and `mimo-window` are mimo-v2-flash's two kinds of layer
(Hk 4, G 16 and Hk 8, G 8 with a sink under a window of 128; keys 256 wide
beside values of 128, over the cell's pools of 4096 and 165 pages under a
page table 96 wide), each with mimo2-agent-steady's decode step: 14 rows
of 30 pages in a bucket of 16, of which the window shows 3.
`mla-256` and `mla-512` are latent attention (`decode_mla_attention`: one
pool, the values its first 256 / 512 columns of 320 / 640 lanes) on the
decode steps of its cells: mistral4-chat-steady's (8 and 32 rows of 5
pages under a page table 64 wide over 6 x 768 pages), ling3-reasoning-
steady's (10 rows of 14 pages in a bucket of 16 under 128 over 3 x 4096)
and dsv32-docqa-steady's selecting arm (4 rows of 32 pages of a gathered
buffer under its identity table, 128 heads).
`--tiles-sweep` (with `--only NAME`) times that step at 1, 2, 4 and 8
pages a grid step where the checkout's kernel has the rule to set
(`step_tiles`), which is how the rule's constant was chosen.
Each line is
one JSON object: us a call, us a live page, and beside them the page's
DMA time at the chip's HBM peak (K + V as the pool holds them, a head dim
padded to 128 lanes). `us_call` times the call with the lengths fixed
across calls, as in a layer scan, where what depends on the lengths alone
is built once; `us_call_relisted` (the 4-row, 7-page points) makes the
lengths depend on the previous call's output, so every call rebuilds it.
`--jnp` adds the jnp gather path. `--ragged` puts the ragged (mixed-step)
kernel's table first (`--ragged --ragged-only`: that table alone): the
cell's plans under its T buckets (288 for a 256-token chunk beside three
decode rows of six pages, at prior 0 and at prior 256; 64 for a 32-row
decode batch beside a 32-token chunk; 256 for a 128-token chunk beside
the 34 decode rows of jamba2-reasoning-steady's step; 288 again for
mimo2-agent-steady's mixed step: 21 decode rows of 30 pages beside a
256-token chunk on 768 tokens of context), us a call with the walk built once
above the calls, us a live (work unit, page) pair, and the live share of
the (NW, MP) grid the kernel took until PR 31; the two `mimo-*` geometries
under their page table 96 wide. `--q-block N` runs the ragged table at
another q block (host and kernel alike); `--ragged --tiles-sweep` times
the cell's mixed step at 1, 2, 4 and 8 pages a grid step where the
checkout's kernel has the rule to set (`ragged_step_tiles`);
`--ragged --routines` times the G = 1 geometries (`MHA_GEOMETRIES`: 4, 8,
16 and 32 KV heads of one query head each) on the cell's mixed step and on
the chunk-heavy plan with the routine forced either way, which is what
`ragged_page_routine`'s G = 1 rule was read from. `--only NAME`
keeps one geometry of both tables. The script runs unchanged on a checkout of an
earlier commit, which is how two are compared.

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

# jamba2-reasoning-steady's decode step: ~34 rows at contexts of 200-2200
# tokens (627 live pages) in the bucket of 64
CELL_PAGES = tuple(3 + (i * 29) % 32 for i in range(34)) + (0,) * 30
# mimo2-agent-steady's decode step: 14 rows at ~1.9 k tokens (30 pages, the
# last one part full so a window of 128 shows 3) in the bucket of 16
MIMO_CELL_TOKENS = tuple(30 * 64 - 5 - 3 * i for i in range(14)) + (0, 0)
GEOMETRIES = {
    "phi-3": dict(Hk=32, G=1, D=96, window=2047),
    "gqa": dict(Hk=8, G=4, D=128, window=None),
    "mqa": dict(Hk=1, G=20, D=128, window=None, pool_pages=2880,
                cell=tuple(n * PS for n in CELL_PAGES)),
    "mimo-global": dict(Hk=4, G=16, D=256, Dv=128, window=None,
                        pool_pages=4096, mp=96, cell=MIMO_CELL_TOKENS),
    "mimo-window": dict(Hk=8, G=8, D=256, Dv=128, window=128, sink=True,
                        pool_pages=165, mp=96, cell=MIMO_CELL_TOKENS),
}
# latent attention (ops/mla_attention.py): one pool [L, NP, PS, 1, Dl], the
# values its first dc columns. A cell's decode step is (label, heads, rows
# live, bucket, pages a row, page table, (layers, pages) of the pool; None:
# the selecting arm's gathered buffer, `bucket * table` pages under the
# identity table and no layer)
LATENT_GEOMETRIES = {
    "mla-256": dict(Dl=320, dc=256, steps=(
        ("mistral4 8 of 8", 32, 8, 8, 5, 64, (6, 768)),
        ("mistral4 32 of 32", 32, 32, 32, 5, 64, (6, 768)))),
    "mla-512": dict(Dl=640, dc=512, steps=(
        ("ling3 10 of 16", 32, 10, 16, 14, 128, (3, 4096)),
        ("dsv32 selected 4 of 4", 128, 4, 4, 32, 32, None))),
}
# one query head a KV head (`--ragged --routines`): the tile routine does Hk
# times the useful products there with no group to fill the rows
MHA_GEOMETRIES = {
    f"mha-{hk}": dict(Hk=hk, G=1, D=128, window=None) for hk in (4, 8, 16, 32)}
ROWS, PAGES = (4, 32), (1, 7, 64)  # 64, 448 and 4096 tokens a row
MIXED_PAGES = (1, 3, 7, 20, 0, 0, 0, 0)


@partial(jax.jit, static_argnames=("impl", "relist"),
         donate_argnames=("k_pool", "v_pool"))
def decode_loop(q, k_pool, v_pool, pt, kv_lens, window, sink, impl, relist):
    """ITERS chained calls; the pools are donated and handed back, as
    the step programs carry them."""
    kw = {} if sink is None else {"sink": sink}

    def body(q, i):
        kv = kv_lens
        if relist:  # never true, and XLA cannot know: the lengths now
            # hang on the carried q, so nothing built from them is hoisted
            kv = kv + (q[0, 0, 0, 0] > 3e38).astype(jnp.int32)
        if impl == "pallas":
            o = decode_paged_attention(q, k_pool, v_pool, pt, kv, window,
                                       jnp.minimum(i, LAYER), **kw)
        else:
            o = paged_attention_jnp(
                q[:, None], k_pool[LAYER], v_pool[LAYER], pt,
                jnp.maximum(kv - 1, 0)[:, None], kv, window=window, **kw,
            )[:, 0]
        # (values narrower than keys: the output repeated to a query's width)
        o = jnp.tile(o, q.shape[-1] // o.shape[-1])
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


def bench_point(name, geom, pages, mp, pools, impls, relisted=False,
                tokens=None) -> None:
    """One line of the table: rows holding `pages[b]` whole pages each
    (0 = a pad row; `tokens`: the rows' lengths, where pages are part
    full), under a page table `mp` wide. `pools` is the list
    [k_pool, v_pool], rebound to what each donating call hands back."""
    hk, g, d, window = geom["Hk"], geom["G"], geom["D"], geom["window"]
    rows, pool_pages = len(pages), pools[0].shape[1]
    rng = np.random.default_rng(0)
    pt = np.zeros((rows, mp), np.int32)
    for b, n in enumerate(pages):
        # drawn from a pool smaller than the rows' contexts, so pages are
        # shared (read-only here); a step never repeats its predecessor's
        # page, so every live page is one DMA
        pt[b, :n] = (rng.integers(1, pool_pages, n).cumsum()
                     + rng.integers(pool_pages)) % pool_pages
    kv_lens = np.asarray(pages, np.int32) * PS
    if tokens is not None:
        kv_lens = np.asarray(tokens, np.int32)
    live = sum(live_pages(int(n), window, PS) for n in kv_lens)
    q = jnp.asarray(rng.standard_normal((rows, hk, g, d)), jnp.bfloat16)
    win = None if window is None else jnp.int32(window)
    sink = (jnp.asarray(rng.standard_normal((hk, g)), jnp.float32)
            if geom.get("sink") else None)
    tail = (jnp.asarray(pt), jnp.asarray(kv_lens), win, sink)
    page_bytes = PS * hk * sum(-(-p.shape[-1] // 128) * 128
                               for p in pools) * pools[0].dtype.itemsize

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


def make_pools(geom):
    """[k_pool, v_pool] of a geometry, layer-stacked."""
    shape = (LAYERS, geom.get("pool_pages", POOL_PAGES), PS, geom["Hk"])
    keys = jax.random.split(jax.random.key(0), 2)
    return [jax.random.normal(k, shape + (w,), jnp.bfloat16)
            for k, w in zip(keys, (geom["D"], geom.get("Dv", geom["D"])))]


def bench_cell_point(gname, geom, pools, impls, label="") -> None:
    """The geometry's cell's decode step, where it has one."""
    tokens = geom.get("cell")
    if tokens:
        live = sum(n > 0 for n in tokens)
        bench_point(f"{gname} cell {live} of {len(tokens)}{label}", geom,
                    tuple(-(-n // PS) for n in tokens), geom.get("mp", 64),
                    pools, impls, relisted=True, tokens=tokens)


def bench_decode_table(impls) -> None:
    for gname, geom in GEOMETRIES.items():
        pools, mp = make_pools(geom), geom.get("mp", 64)
        for rows in ROWS:
            for pages in PAGES:
                bench_point(f"{gname} {rows}x{pages}", geom, (pages,) * rows,
                            mp, pools, impls, relisted=(rows, pages) == (4, 7))
        bench_point(f"{gname} mixed", geom, MIXED_PAGES, mp, pools, impls)
        bench_point(f"{gname} 4x7 MP8", geom, (7,) * 4, 8, pools, impls)
        bench_cell_point(gname, geom, pools, impls)
        del pools


@partial(jax.jit, static_argnames=("dc",), donate_argnames=("pool",))
def latent_loop(q, pool, pt, kv_lens, dc):
    """ITERS chained calls of the latent decode kernel, the pool donated
    and handed back; the walk built once above them where the checkout's
    kernel takes one (models/llama.py builds it above its layer scan)."""
    from dynamo_tpu.ops import mla_attention as mla

    kw = dict(dc=dc, scale=0.1)
    if hasattr(mla, "latent_walk"):
        kw["work"] = mla.latent_walk(q.shape[1], pool, pt, kv_lens)
    stacked = pool.ndim == 5

    def body(q, i):
        layer = (jnp.minimum(i, pool.shape[0] - 1),) if stacked else ()
        o = mla.decode_mla_attention(q, pool, pt, kv_lens, *layer, **kw)
        o = jnp.concatenate([o, o[..., :q.shape[-1] - dc]], axis=-1)
        return o.astype(q.dtype), None

    q, _ = lax.scan(body, q, jnp.arange(ITERS) + 1)
    return q, pool


def bench_latent_table(label="") -> None:
    """`decode_mla_attention` on its cells' decode steps, one JSON line a
    point: us a call, us a live page, the page's DMA time at the HBM peak
    (one block for keys and values)."""
    for gname, geom in LATENT_GEOMETRIES.items():
        dl, dc = geom["Dl"], geom["dc"]
        for name, heads, live_rows, rows, pages, mp, stack in geom["steps"]:
            rng = np.random.default_rng(0)
            shape = (rows * mp,) if stack is None else stack
            pool = jax.random.normal(jax.random.key(0),
                                     shape + (PS, 1, dl), jnp.bfloat16)
            pt = np.arange(rows * mp, dtype=np.int32).reshape(rows, mp)
            if stack is not None:
                pt = rng.integers(stack[1], size=(rows, mp)).astype(np.int32)
            kv = np.where(np.arange(rows) < live_rows, pages * PS - 7, 0)
            q = jnp.asarray(rng.standard_normal((rows, heads, dl)),
                            jnp.bfloat16)
            box = [pool]

            def call():
                out, box[0] = latent_loop(q, box[0], jnp.asarray(pt),
                                          jnp.asarray(kv, jnp.int32), dc=dc)
                return out

            us, live = _time(call), live_rows * pages
            print(json.dumps({
                "point": f"{gname} {name}{label}", "rows": rows,
                "pages_a_row": pages, "page_table": mp, "live_pages": live,
                "dma_us_page": round(PS * dl * 2 / HBM_BYTES_PER_S * 1e6, 3),
                "us_call": round(us, 1),
                "us_live_page": round(us / live, 3)}), flush=True)
            del pool, box


def bench_tiles_sweep(impls) -> None:
    """The cell's decode step at 1, 2, 4 and 8 pages a grid step: the
    kernel's rule (`step_tiles`) replaced for the sweep, the programs
    traced again at each count."""
    import math

    from dynamo_tpu.ops import paged_attention as pa

    if not hasattr(pa, "step_tiles"):
        print("this checkout's kernel has no pages-a-step rule to sweep",
              flush=True)
        return
    rule = pa.step_tiles
    for gname, geom in GEOMETRIES.items():
        pools = make_pools(geom)
        for tiles in (1, 2, 4, 8):
            pa.step_tiles = lambda nbytes, mp, t=tiles: math.gcd(mp, t)
            jax.clear_caches()
            bench_cell_point(gname, geom, pools, impls, f" tiles {tiles}")
        pa.step_tiles = rule
        jax.clear_caches()
        del pools
    from dynamo_tpu.ops import mla_attention as mla

    # (a checkout whose latent kernel takes no walk has no count to set)
    for tiles in (1, 2, 4, 8) if hasattr(mla, "latent_walk") else ():
        pa.step_tiles = lambda nbytes, mp, t=tiles: math.gcd(mp, t)
        jax.clear_caches()
        bench_latent_table(f" tiles {tiles}")
    pa.step_tiles = rule
    jax.clear_caches()


def check_decode(interpret: bool) -> None:
    """Numeric agreement with the jnp gather, ragged lengths and a pad
    row."""
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


# the benchmark cell's mixed plans: (decode rows' contexts, (chunk tokens,
# prior tokens)) under its ragged T buckets and a page table 64 wide
RAGGED_T_BUCKETS = (32, 64, 128, 256, 288)
CELL_MIXED = "chunk 256 @768 + 21 decode"
RAGGED_PLANS = {
    "chunk 256 @0 + 3 decode": ((350, 380, 330), (256, 0)),
    "chunk 256 @256 + 3 decode": ((350, 380, 330), (256, 256)),
    "chunk 32 @0 + 32 decode": (tuple(330 + 3 * i for i in range(32)),
                                (32, 0)),
    # jamba2-reasoning-steady's mixed step: a joiner's prompt beside the
    # decode batch (CELL_PAGES' contexts)
    "chunk 128 @0 + 34 decode": (tuple(n * PS - 7 for n in CELL_PAGES[:34]),
                                 (128, 0)),
    # mimo2-agent-steady's mixed step at 1.44 requests/s: 21 decode rows at
    # ~1.9 k tokens beside a 256-token chunk on 768 tokens of context
    CELL_MIXED: (tuple(30 * PS - 5 - 3 * i for i in range(21)), (256, 768)),
}
Q_BLOCK = 8  # `--q-block`


def ragged_plan(rng, decode_kv, chunk, mp, pool_pages=POOL_PAGES):
    """build_ragged_metadata's output for a plan, page tables drawn from
    the pool as bench_point draws them."""
    from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

    n_tok, prior = chunk
    q_lens = [1] * len(decode_kv) + [n_tok]
    q_starts = [n - 1 for n in decode_kv] + [prior]
    kv_lens = list(decode_kv) + [prior + n_tok]
    rows = [((rng.integers(1, pool_pages, -(-n // PS)).cumsum()
              + rng.integers(pool_pages)) % pool_pages).tolist()
            for n in kv_lens]
    t = min(b for b in RAGGED_T_BUCKETS if b >= sum(q_lens))
    return build_ragged_metadata(q_lens, q_starts, kv_lens, rows, t,
                                 q_block=Q_BLOCK, max_pages=mp)


def ragged_live_pairs(md, window) -> int:
    """Live (work unit, page) pairs of a plan, by the kernel docstring's
    rule: written out here so the script counts the same on a checkout
    whose kernel keeps the (NW, MP) grid."""
    seg, _, _, rows, qpos0 = md["meta"]
    kv = md["seg_kv_lens"][seg]
    last = np.minimum(qpos0 + rows - 1, kv - 1) // PS
    first = (np.maximum(qpos0 - window + 1, 0) if window else 0 * last) // PS
    return int(np.sum(np.where((rows > 0) & (kv > 0), last - first + 1, 0)))


@partial(jax.jit, static_argnames=("relist", "q_block"),
         donate_argnames=("k_pool", "v_pool"))
def ragged_loop(q, k_pool, v_pool, seg_pt, seg_kvl, meta, window, sink,
                relist, q_block):
    """ITERS chained ragged calls on one plan. The walk is built once,
    above the scan, as llama.forward builds it above its layers, where
    the kernel takes one (`relist`: inside, every call)."""
    from dynamo_tpu.ops import ragged_paged_attention as rg

    build = getattr(rg, "ragged_work_list", None)
    page_size, mp = k_pool.shape[2], seg_pt.shape[1]
    kw = {"q_block": q_block, **({} if sink is None else {"sink": sink})}

    def walk(kvl):
        if hasattr(rg, "ragged_walk"):  # the lists of the call's routine
            return (rg.ragged_walk(q.shape[1:3], k_pool, v_pool, seg_pt, kvl,
                                   meta, window, q.shape[0], q_block),)
        return (build(meta, kvl, window, page_size, mp, q.shape[0], q_block),)

    hoisted = walk(seg_kvl) if build and not relist else ()

    def body(q, i):
        kvl, work = seg_kvl, hoisted
        if relist:  # never true, and XLA cannot know (decode_loop)
            kvl = kvl + (q[0, 0, 0, 0] > 3e38).astype(jnp.int32)
            work = walk(kvl) if build else ()
        o = rg.ragged_paged_attention(q, k_pool, v_pool, seg_pt, kvl, meta,
                                      window, jnp.minimum(i, LAYER), *work,
                                      **kw)
        # (values narrower than keys: the output repeated to a query's width)
        o = jnp.tile(o, q.shape[-1] // o.shape[-1])
        return o.astype(q.dtype), None

    q, _ = lax.scan(body, q, jnp.arange(ITERS) + LAYER)
    return q, k_pool, v_pool


def bench_ragged_table(plans=None, label="") -> None:
    """The ragged (mixed-step) kernel on the cell's plans (`plans`: those
    named), one JSON line a point: us a call and us a live (work unit,
    page) pair, with the share of the (NW, MP) grid that is live."""
    for gname, geom in GEOMETRIES.items():
        hk, g, d, window = geom["Hk"], geom["G"], geom["D"], geom["window"]
        pool_pages, mp = geom.get("pool_pages", POOL_PAGES), geom.get("mp", 64)
        pools = make_pools(geom)
        win = None if window is None else jnp.int32(window)
        for name, (decode_kv, chunk) in RAGGED_PLANS.items():
            if plans and name not in plans:
                continue
            rng = np.random.default_rng(0)
            md = ragged_plan(rng, decode_kv, chunk, mp, pool_pages)
            t, nw = md["tok_positions"].shape[0], md["meta"].shape[1]
            q = jnp.asarray(rng.standard_normal((t, hk, g, d)), jnp.bfloat16)
            sink = (jnp.asarray(rng.standard_normal((hk, g)), jnp.float32)
                    if geom.get("sink") else None)
            tail = tuple(jnp.asarray(md[k]) for k in
                         ("seg_page_table", "seg_kv_lens", "meta")) + (win, sink)

            def call(relist):
                out, pools[0], pools[1] = ragged_loop(
                    q, pools[0], pools[1], *tail, relist=relist,
                    q_block=Q_BLOCK)
                return out

            live = ragged_live_pairs(md, window)
            us = _time(partial(call, False))
            line = {"point": f"ragged {gname} {name}{label}", "T": t,
                    "page_table": mp, "units": int(md["n_work"]),
                    "live_pairs": live,
                    "live_share_of_grid": round(live / (nw * mp), 4),
                    "us_call": round(us, 1),
                    "us_live_pair": round(us / live, 3)}
            if name.startswith("chunk 256 @0"):
                line["us_call_relisted"] = round(_time(partial(call, True)), 1)
            print(json.dumps(line), flush=True)
        del pools


def bench_ragged_tiles_sweep() -> None:
    """The cell's mixed step at 1, 2, 4 and 8 pages a grid step: the
    kernel's rule (`ragged_step_tiles`) replaced for the sweep."""
    import math

    from dynamo_tpu.ops import ragged_paged_attention as rg

    if not hasattr(rg, "ragged_step_tiles"):
        print("this checkout's ragged kernel has no pages-a-step rule to "
              "sweep", flush=True)
        return
    rule = rg.ragged_step_tiles
    for tiles in (1, 2, 4, 8):
        rg.ragged_step_tiles = lambda *a, t=tiles: math.gcd(a[-1], t)
        jax.clear_caches()
        try:
            bench_ragged_table((CELL_MIXED,), f" tiles {tiles}")
        except Exception as e:  # a step Mosaic refuses (VMEM)
            print(json.dumps({"tiles": tiles, "refused": str(e)[-300:]}),
                  flush=True)
    rg.ragged_step_tiles = rule
    jax.clear_caches()


def bench_ragged_routines() -> None:
    """The G = 1 geometries with the ragged routine forced by heads and by
    tiles (`ragged_page_routine` replaced for the run), on the cell's mixed
    step and on the plan that is mostly a chunk."""
    from dynamo_tpu.ops import ragged_paged_attention as rg

    if not hasattr(rg, "ragged_page_routine"):
        print("this checkout's ragged kernel has one routine", flush=True)
        return
    rule = rg.ragged_page_routine
    GEOMETRIES.clear()
    GEOMETRIES.update(MHA_GEOMETRIES)
    for routine in ("by_heads", "by_tiles"):
        rg.ragged_page_routine = lambda *a, r=routine: r
        jax.clear_caches()
        bench_ragged_table((CELL_MIXED, "chunk 256 @256 + 3 decode"),
                           f" {routine}")
    rg.ragged_page_routine = rule
    jax.clear_caches()


def check_ragged(interpret: bool) -> None:
    """The first plan against the per-token jnp reference, at the GQA
    geometry."""
    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_attention_reference, ragged_paged_attention,
    )

    rng = np.random.default_rng(0)
    hk, g, d = (GEOMETRIES["gqa"][k] for k in ("Hk", "G", "D"))
    md = ragged_plan(rng, *RAGGED_PLANS["chunk 256 @0 + 3 decode"], 8)
    t = md["tok_positions"].shape[0]
    q = jnp.asarray(rng.standard_normal((t, hk, g, d)), jnp.bfloat16)
    pools = [jnp.asarray(rng.standard_normal((POOL_PAGES, PS, hk, d)),
                         jnp.bfloat16) for _ in range(2)]
    out = ragged_paged_attention(
        q, *pools, *(jnp.asarray(md[k]) for k in
                     ("seg_page_table", "seg_kv_lens", "meta")),
        interpret=interpret)
    ref = ragged_attention_reference(
        q, *pools, *(jnp.asarray(md[k]) for k in
                     ("tok_page_table", "tok_positions", "tok_kv_lens")))
    real = md["tok_positions"] >= 0
    print("ragged max abs diff:", np.abs(
        np.asarray(out, np.float32) - np.asarray(ref, np.float32))[real].max(),
        flush=True)


def main() -> None:
    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()
    cpu = jax.devices()[0].platform == "cpu"  # pallas needs interpret on CPU
    check_decode(interpret=cpu)
    check_ragged(interpret=cpu)
    if cpu:
        print("no accelerator: parity only (nothing timed on a CPU is a "
              "device number)", flush=True)
        return
    args = sys.argv[1:]
    if "--only" in args:
        only = {args[args.index("--only") + 1]}
        for table in (GEOMETRIES, LATENT_GEOMETRIES):
            for name in table.keys() - only:
                del table[name]
    if "--q-block" in args:
        global Q_BLOCK
        Q_BLOCK = int(args[args.index("--q-block") + 1])
    if "--ragged" in args and "--tiles-sweep" in args:
        bench_ragged_tiles_sweep()
        return
    if "--ragged" in args and "--routines" in args:
        bench_ragged_routines()
        return
    if "--ragged" in args:
        bench_ragged_table()
    impls = ("pallas", "jnp") if "--jnp" in args else ("pallas",)
    if "--tiles-sweep" in args:
        bench_tiles_sweep(impls)
    elif "--ragged-only" not in args:
        bench_decode_table(impls)
        bench_latent_table()


if __name__ == "__main__":
    main()
