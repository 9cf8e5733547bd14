"""Hardware kernel-parity gate: compiled Pallas kernels vs the f32 jnp
reference ON THE REAL TPU (the CPU suite only exercises interpret mode —
compiled Mosaic lowering is a different code path and must be revalidated
whenever a chip is available). chip_smoke.py runs it as its first phase.

GQA checks — the cross product, every one REQUIRED to pass compiled:
  kernels     decode (ops/paged_attention; a batch of drawn lengths, and a
              ragged batch: a pad row, 1 token, whole pages, one token
              past them, the whole page table) · prefill
              (ops/flash_prefill) · ragged (ops/ragged_paged_attention,
              the default mixed path)
  variants    bf16 · window+softcap · int8-KV · int8-KV+window+softcap
  geometries  llama-3.2-3b  Hk 8,  G 3, D 128, PS 64, page table 64 wide
              phi-3-mini-4k Hk 32, G 1, D 96,  PS 64, window 2047 with
              contexts past it (a head dim that is not a multiple of the
              128 lanes, and G = 1, are where Mosaic layouts differ)
The ragged layout packs three decode segments and a chunk start into one
q block (the finalize read-modify-write), chunks that cross block
boundaries, a chunk continuing a long prior context, and a padded tail;
"ragged sparse-table" is the serving cell's mixed step (segments of 1 to
8 pages under a page table 64 wide, T 288), and on a host with several
chips it runs again through the tensor-parallel wrapper, kv heads over
all of them.
Each of these reads a layer-STACKED pool [L, NP, PS, Hk, D] at a nonzero
traced layer, as the model's layer scan does; every layer holds other
data, so a kernel that indexed the wrong layer misses the reference.

DeepSeek-V3.2 (`dsa`): one layer of models/mla.py at published widths with its
indexer: a decode step of 1 / 8 / 32 rows on 1 k / 4 k / 32 k cached tokens
against the jnp path of the same layer, the compiled indexer and select kernel
(ops/dsa_select.py; `select_topk` beside it) against numpy's stable argsort of
the chip's own scores (ties planted, rows short of k live), a prefill chunk
on a prior context against float32 attention under the same selection as a
mask. `--time-dsa` prints the device time of the selecting arm's parts instead.

Also run: Gemma geometry (G 2, PS 16) decode/prefill softcap+window on one
layer's pool [NP, PS, Hk, D] (the kernels' rank-4 view), MLA
decode/prefill, MLA int8-latent decode (gates DYN_MLA_INT8_KERNEL; all
three since PR 51 on the latent pool stacked [3, NP, PS, 1, Dl] at layer 2,
and at each layer held bit for bit to the same call on that layer's slab), and
the batched page copy/permute/scatter roundtrip (gates DYN_KV_COPY_KERNEL).

Every check runs even after a failure; an exception (a compiler refusal)
is a failure carrying the compiler's message. The last stdout line is one
JSON object {"ok", "device", "checks": [...]}. Exit 0 = all within
tolerance; 1 = a mismatch or error; 2 = no accelerator (never a skip).
`--only TEXT` runs the checks whose name holds TEXT (after a change to one
kernel; the gate proper is the whole list). `--interpret` runs the same
checks through the Pallas interpreter on any
backend: a rehearsal of this script's own logic, labelled as such, that
says nothing about Mosaic.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.quant import kv_pool_quantize
from dynamo_tpu.models.toolkit import paged_attention_jnp
from dynamo_tpu.ops.flash_prefill import prefill_paged_attention
from dynamo_tpu.ops.paged_attention import (
    decode_paged_attention,
    decode_paged_attention_sharded,
)
from dynamo_tpu.ops.ragged_paged_attention import (
    build_ragged_metadata,
    ragged_paged_attention,
    ragged_paged_attention_sharded,
)

TOL = 3e-2
INTERPRET = False  # set by --interpret (rehearsal only)

# name -> (Hk, G, D, PS, page-table width, window used by windowed variants,
#          longest context drawn)
GEOMETRIES = {
    "llama-3.2-3b": dict(Hk=8, G=3, D=128, PS=64, MP=64, window=100, ctx=512),
    "phi-3-mini-4k": dict(Hk=32, G=1, D=96, PS=64, MP=64, window=2047, ctx=2560),
    # MQA at 20 query heads on one KV head (no multiple of the kernels' row
    # block of 8), as the two attention layers of ai21-jamba2-3b run it:
    # bf16 pools, no window, no softcap
    "ai21-jamba2-3b": dict(Hk=1, G=20, D=128, PS=64, MP=64, window=100, ctx=2560,
                           variants=("bf16",)),
}
# name -> (int8 KV pools, sliding window + logit softcap)
VARIANTS = {
    "bf16": (False, False),
    "window+softcap": (False, True),
    "int8-kv": (True, False),
    "int8-kv+window+softcap": (True, True),
}
SOFTCAP = 30.0
LAYER = 2  # the layer the stacked pools are read at: the last of three


def _max_err(out, ref) -> float:
    return float(
        np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    )


class _Pool:
    """Random layer-stacked K/V pools [3, NP, PS, Hk, D] plus a
    page allocator: every sequence gets its own pages, and page-table
    entries past its context stay 0 (the kernels clamp them away). The
    kernels read layer LAYER; the reference reads `slab()`, that layer's
    [NP, PS, Hk, D]."""

    def __init__(self, rng, geom, n_pages: int, quantized: bool):
        shape = (n_pages, geom["PS"], geom["Hk"], geom["D"])
        self.k = self._stack(jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))
        self.v = self._stack(jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))
        if quantized:
            self.k, self.v = kv_pool_quantize(self.k), kv_pool_quantize(self.v)
        self._free = list(rng.permutation(n_pages))
        self.PS, self.MP = geom["PS"], geom["MP"]

    @staticmethod
    def _stack(slab):
        """The drawn slab as layer LAYER, under two layers of other data
        made from it on the device (negated; pages rolled by one)."""
        return jnp.stack([-slab, jnp.roll(slab, 1, axis=0), slab])

    def slab(self):
        return jax.tree.map(lambda a: a[LAYER], (self.k, self.v))

    def table(self, kv_lens) -> np.ndarray:
        pt = np.zeros((len(kv_lens), self.MP), np.int32)
        for b, n in enumerate(kv_lens):
            need = -(-int(n) // self.PS)
            pt[b, :need] = [self._free.pop() for _ in range(need)]
        return pt


def _ref(q32, pool, pt, positions, kv, window, softcap):
    # f32 reference: the kernels accumulate in f32, and a bf16 jnp
    # reference would add its OWN MXU rounding — compare to f32 ground
    # truth. Only the page-table columns any context reaches are gathered.
    need = max(1, -(-int(np.max(kv)) // pool.PS))
    return paged_attention_jnp(
        q32, *pool.slab(), jnp.asarray(pt[:, :need]), jnp.asarray(positions),
        jnp.asarray(kv), softcap=softcap,
        window=None if window is None else jnp.int32(window),
    )


def _variant(geom, variant):
    quantized, windowed = VARIANTS[variant]
    return (quantized, geom["window"] if windowed else None,
            SOFTCAP if windowed else 0.0)


def _decode_err(geom, variant, rng, kv, mesh=None) -> float:
    """Decode kernel against the f32 reference on rows of `kv` tokens; a
    pad row (kv_len 0) reads no page and must come back finite; through
    the tensor-parallel wrapper where a mesh is given."""
    quantized, window, softcap = _variant(geom, variant)
    B, Hk, G, D = len(kv), geom["Hk"], geom["G"], geom["D"]
    pool = _Pool(rng, geom, int(np.sum(-(-kv // geom["PS"]))) + 4, quantized)
    pt = pool.table(kv)
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    win = None if window is None else jnp.int32(window)
    if mesh is None:
        out = decode_paged_attention(
            q, pool.k, pool.v, jnp.asarray(pt), jnp.asarray(kv), win,
            jnp.int32(LAYER), softcap=softcap, interpret=INTERPRET,
        )
    else:
        out = decode_paged_attention_sharded(
            q, pool.k, pool.v, jnp.asarray(pt), jnp.asarray(kv), mesh,
            window=win, layer=jnp.int32(LAYER), softcap=softcap,
            interpret=INTERPRET,
        )
    ref = _ref(q.astype(jnp.float32)[:, None], pool, pt,
               np.maximum(kv - 1, 0)[:, None], kv, window, softcap)[:, 0]
    out = np.asarray(out, np.float32)
    if not np.isfinite(out[kv == 0]).all():
        return float("inf")
    return _max_err(out[kv > 0], np.asarray(ref)[kv > 0])


def check_decode(geom, variant) -> float:
    rng = np.random.default_rng(0)
    kv = rng.integers(1, geom["ctx"], 8).astype(np.int32)
    kv[0], kv[1] = geom["ctx"], 1  # longest (past the window) and shortest
    return _decode_err(geom, variant, rng, kv)


def check_decode_ragged(geom, variant, mesh=None) -> float:
    """The page walk's edges in one batch: a pad row, 1 token, whole
    pages, one token into a page, the whole page table, and (windowed
    variants) a window that cuts leading pages and one that starts
    mid-page."""
    PS, MP = geom["PS"], geom["MP"]
    kv = np.asarray([0, 1, PS * 3, PS * 3 + 1, PS * MP, geom["ctx"] + 17,
                     PS, 0], np.int32)
    return _decode_err(geom, variant, np.random.default_rng(7), kv, mesh)


def check_prefill(geom, variant) -> float:
    quantized, window, softcap = _variant(geom, variant)
    rng = np.random.default_rng(1)
    S, Hk, G, D = 256, geom["Hk"], geom["G"], geom["D"]
    # a fresh full chunk, a chunk continuing a long prior context (past
    # the window), a short padded chunk after a block-misaligned prior
    qs = np.asarray([0, geom["ctx"] - S, 70, 0], np.int32)
    ql = np.asarray([S, S, 100, 77], np.int32)
    B = len(qs)
    kv = qs + ql
    pool = _Pool(rng, geom, int(np.sum(-(-kv // geom["PS"]))) + 4, quantized)
    pt = pool.table(kv)
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    out = prefill_paged_attention(
        q, pool.k, pool.v, jnp.asarray(pt), jnp.asarray(qs), jnp.asarray(ql),
        jnp.asarray(kv), None if window is None else jnp.int32(window),
        jnp.int32(LAYER), softcap=softcap, interpret=INTERPRET,
    )
    pos = qs[:, None] + np.arange(S, dtype=np.int32)[None, :]
    ref = _ref(q.astype(jnp.float32), pool, pt, pos, kv, window, softcap)
    return max(_max_err(out[b, : ql[b]], ref[b, : ql[b]]) for b in range(B))


def _ragged_err(geom, variant, rng, segs, T, mesh=None) -> float:
    """Ragged kernel against the f32 reference on `segs`, (q_len, q_start)
    each, in a flat axis of T tokens; through the tensor-parallel wrapper
    where a mesh is given."""
    quantized, window, softcap = _variant(geom, variant)
    Hk, G, D = geom["Hk"], geom["G"], geom["D"]
    q_lens = [s[0] for s in segs]
    q_starts = [s[1] for s in segs]
    kv = np.asarray([a + b for a, b in segs], np.int32)
    pool = _Pool(rng, geom, int(np.sum(-(-kv // geom["PS"]))) + 4, quantized)
    pt = pool.table(kv)
    md = build_ragged_metadata(
        q_lens, q_starts, kv, pt, T, max_pages=geom["MP"]
    )
    q = jnp.asarray(rng.standard_normal((T, Hk, G, D)), jnp.bfloat16)
    seg = tuple(jnp.asarray(md[k]) for k in
                ("seg_page_table", "seg_kv_lens", "meta"))
    win = None if window is None else jnp.int32(window)
    if mesh is None:
        out = ragged_paged_attention(
            q, pool.k, pool.v, *seg, win, jnp.int32(LAYER),
            softcap=softcap, interpret=INTERPRET,
        )
    else:
        out = ragged_paged_attention_sharded(
            q, pool.k, pool.v, *seg, mesh, window=win,
            layer=jnp.int32(LAYER), softcap=softcap, interpret=INTERPRET,
        )
    # reference per segment (a B=1, S=q_len row of paged_attention_jnp —
    # what ragged_attention_reference computes per token, without
    # gathering the whole context once for every token)
    worst, lo = 0.0, 0
    for s, (n, start) in enumerate(segs):
        pos = (start + np.arange(n, dtype=np.int32))[None]
        ref = _ref(q[lo : lo + n].astype(jnp.float32)[None], pool,
                   pt[s : s + 1], pos, kv[s : s + 1], window, softcap)[0]
        worst = max(worst, _max_err(out[lo : lo + n], ref))
        lo += n
    # rows covered by no real segment return 0
    return max(worst, _max_err(out[lo:], jnp.zeros_like(out[lo:])))


def check_ragged(geom, variant) -> float:
    long_ctx = geom["ctx"]
    # (q_len, q_start): three decode rows and a chunk start share q block
    # 0; chunks cross 8-row block boundaries; one chunk continues a long
    # prior context; 256 - 195 rows of padded tail
    segs = [(1, long_ctx - 1), (1, 70), (1, 0), (37, 0),
            (100, long_ctx - 100), (9, 3), (1, 129), (45, 64)]
    return _ragged_err(geom, variant, np.random.default_rng(2), segs, 256)


# a sparse page table, as the serving cell's mixed step has it: segments
# of 1 to 8 pages under a table 64 wide, so a few percent of the (work
# unit, page) pairs are live. Decode rows of 1, 6 and 8 pages, a chunk
# continuing a four-page prior, a fresh chunk, 288 - 259 rows of tail
_SPARSE_SEGS = [(1, 0), (1, 380), (1, 511), (150, 256), (106, 0)]


def check_ragged_sparse(geom, variant, mesh=None) -> float:
    return _ragged_err(geom, variant, np.random.default_rng(3),
                       _SPARSE_SEGS, 288, mesh)


def _stacked_err(kernel, pool, ref) -> float:
    """A latent kernel on the pool as the layer scan carries it, read at
    layer LAYER, against the float32 reference on that layer's slab; inf
    unless, at every layer of the stack, the per-layer operand
    (DeepSeek-V3.2's gathered buffer has that form) gives the same bits."""
    for layer in range(LAYER + 1):
        out = kernel(pool, jnp.int32(layer))
        slab = kernel(jax.tree.map(lambda a: a[layer], pool), None)
        if not np.array_equal(np.asarray(out, np.float32), np.asarray(slab, np.float32)):
            return float("inf")
    return _max_err(out, ref)


def check_mla() -> float:
    from dynamo_tpu.ops.mla_attention import decode_mla_attention

    rng = np.random.default_rng(5)
    B, H, dc, dr, NP, PS, MP = 8, 16, 512, 64, 48, 16, 6
    Dl = dc + dr
    q = jnp.asarray(rng.standard_normal((B, H, Dl)), jnp.bfloat16)
    lat = jnp.asarray(rng.standard_normal((NP, PS, 1, Dl)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray(rng.integers(1, MP * PS, B).astype(np.int32))
    scale = (128 + dr) ** -0.5
    qg = q[:, None, None, :, :].transpose(0, 2, 1, 3, 4)
    ref = paged_attention_jnp(
        qg.astype(jnp.float32), lat.astype(jnp.float32),
        lat[..., :dc].astype(jnp.float32), pt, (kv - 1)[:, None], kv,
        scale=scale,
    )[:, 0, 0]
    return _stacked_err(
        lambda pool, layer: decode_mla_attention(
            q, pool, pt, kv, layer, dc=dc, scale=scale, interpret=INTERPRET),
        _Pool._stack(lat), ref)


def check_mla_prefill() -> float:
    from dynamo_tpu.ops.mla_attention import prefill_mla_attention

    rng = np.random.default_rng(7)
    B, S, H, dc, dr, NP, PS, MP = 2, 128, 16, 512, 64, 40, 16, 16
    Dl = dc + dr
    q = jnp.asarray(rng.standard_normal((B, S, H, Dl)), jnp.bfloat16)
    lat = jnp.asarray(rng.standard_normal((NP, PS, 1, Dl)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray([0, 64], np.int32)
    ql = np.asarray([128, 128], np.int32)
    kv = jnp.asarray(qs + ql)
    scale = (128 + dr) ** -0.5
    pos = np.zeros((B, S), np.int32)
    for b in range(B):
        pos[b] = np.arange(qs[b], qs[b] + S)
    ref = paged_attention_jnp(
        q.astype(jnp.float32)[:, :, None], lat.astype(jnp.float32),
        lat[..., :dc].astype(jnp.float32), pt, jnp.asarray(pos), kv,
        scale=scale,
    )[:, :, 0]
    return _stacked_err(
        lambda pool, layer: prefill_mla_attention(
            q, pool, pt, jnp.asarray(qs), jnp.asarray(ql), kv, layer, dc=dc,
            scale=scale, interpret=INTERPRET),
        _Pool._stack(lat), ref)


def check_mla_int8() -> float:
    """int8 latent pool through the MLA decode kernel: the per-token
    scale tile is the Mosaic-risk piece (DYN_MLA_INT8_KERNEL stays opt-in
    until this passes compiled)."""
    from dynamo_tpu.ops.mla_attention import decode_mla_attention

    rng = np.random.default_rng(15)
    B, H, dc, dr, NP, PS, MP = 8, 16, 512, 64, 48, 16, 6
    Dl = dc + dr
    q = jnp.asarray(rng.standard_normal((B, H, Dl)), jnp.bfloat16)
    lat_dense = jnp.asarray(rng.standard_normal((NP, PS, 1, Dl)), jnp.bfloat16)
    stack = kv_pool_quantize(_Pool._stack(lat_dense))
    lat_q = jax.tree.map(lambda a: a[LAYER], stack)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray(rng.integers(1, MP * PS, B).astype(np.int32))
    scale = (128 + dr) ** -0.5
    v_view = {"q": lat_q["q"][..., :dc], "s": lat_q["s"]}
    ref = paged_attention_jnp(
        q.astype(jnp.float32)[:, None, None], lat_q, v_view, pt,
        (kv - 1)[:, None], kv, scale=scale,
    )[:, 0, 0]
    return _stacked_err(
        lambda pool, layer: decode_mla_attention(
            q, pool, pt, kv, layer, dc=dc, scale=scale, interpret=INTERPRET),
        stack, ref)


def check_gemma_decode() -> float:
    """Softcap + sliding-window + scalar-scaled decode (Gemma-2 family,
    G 2, PS 16): the kernel's window rides as a scalar-prefetch operand."""
    rng = np.random.default_rng(11)
    B, Hk, G, D, NP, PS, MP = 8, 8, 2, 128, 48, 16, 6
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray(rng.integers(1, MP * PS, B).astype(np.int32))
    scale, cap, win = 0.35 ** -0.5, 30.0, 24
    out = decode_paged_attention(
        q, k, v, pt, kv, jnp.int32(win), scale=scale, softcap=cap,
        interpret=INTERPRET,
    )
    ref = paged_attention_jnp(
        q.astype(jnp.float32)[:, None],
        k.astype(jnp.float32), v.astype(jnp.float32), pt,
        (kv - 1)[:, None], kv, scale=scale, softcap=cap,
        window=jnp.int32(win),
    )[:, 0]
    return _max_err(out, ref)


def check_gemma_prefill() -> float:
    """Softcap + sliding-window flash prefill (per-row window mask and
    low-clamped page DMAs) in compiled Mosaic."""
    rng = np.random.default_rng(12)
    B, S, Hk, G, D, NP, PS, MP = 2, 128, 8, 2, 128, 40, 16, 16
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray([64, 0], np.int32)
    ql = np.asarray([128, 128], np.int32)
    kv = jnp.asarray(qs + ql)
    cap, win = 30.0, 48
    out = prefill_paged_attention(
        q, k, v, pt, jnp.asarray(qs), jnp.asarray(ql), kv, jnp.int32(win),
        softcap=cap, interpret=INTERPRET,
    )
    pos = np.zeros((B, S), np.int32)
    for b in range(B):
        pos[b] = np.arange(qs[b], qs[b] + S)
    ref = paged_attention_jnp(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        pt, jnp.asarray(pos), kv, softcap=cap, window=jnp.int32(win),
    )
    return _max_err(out, ref)


# mimo-v2-flash's two kinds of attention layer (models/mimo.py): keys of 192
# in pools 256 wide (ModelConfig.key_pool_dim) beside values of 128, a sink
# logit a head on the window layers, the window a traced scalar
MIMO_KINDS = {"global": dict(Hk=4, G=16, window=None, sink=False),
              "window": dict(Hk=8, G=8, window=128, sink=True)}


def _mimo_operands(kind: str, rng, NP: int, L: int = 3):
    g = MIMO_KINDS[kind]
    Hk, PS, dk, dkp, dv = g["Hk"], 64, 192, 256, 128
    k = np.zeros((L, NP, PS, Hk, dkp), np.float32)
    k[..., :dk] = rng.standard_normal((L, NP, PS, Hk, dk))
    v = rng.standard_normal((L, NP, PS, Hk, dv))
    sink = (jnp.asarray(rng.uniform(-1, 4, (Hk, g["G"])), jnp.float32)
            if g["sink"] else None)
    win = None if g["window"] is None else jnp.int32(g["window"])

    def queries(*lead):
        q = np.zeros(lead + (Hk, g["G"], dkp), np.float32)
        q[..., :dk] = rng.standard_normal(lead + (Hk, g["G"], dk))
        return jnp.asarray(q, jnp.bfloat16)

    return (g, jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16),
            sink, win, queries, dk ** -0.5)


def check_mimo_decode(kind: str) -> float:
    """The decode kernel at two head sizes (with the sink and the window on
    the window kind) over a stacked pool at layer 1: 32 rows whose contexts
    run from one token to 2,400, a pad row among them."""
    rng = np.random.default_rng(40)
    g, k, v, sink, win, queries, scale = _mimo_operands(kind, rng, 400)
    B, MP = 32, 40
    kv = rng.integers(1, 2400, B).astype(np.int32)
    kv[3], kv[7] = 0, 1
    pt = np.zeros((B, MP), np.int32)
    free = list(rng.permutation(np.arange(1, 400)))
    for b in range(B):  # (pages are shared between rows: reads alone)
        n = -(-int(kv[b]) // 64)
        pt[b, :n] = [free[(b * 7 + j) % len(free)] for j in range(n)]
    q = queries(B)
    name = "window_attention_decode" if g["window"] else None
    out = decode_paged_attention(
        q, k, v, jnp.asarray(pt), jnp.asarray(kv), win, jnp.int32(1), sink=sink,
        scale=scale, name=name, interpret=INTERPRET)
    ref = paged_attention_jnp(
        q.astype(jnp.float32)[:, None], k[1].astype(jnp.float32),
        v[1].astype(jnp.float32), jnp.asarray(pt),
        jnp.maximum(jnp.asarray(kv) - 1, 0)[:, None], jnp.asarray(kv),
        scale=scale, window=win, sink=sink)[:, 0]
    live = (kv > 0)[:, None, None, None]
    return _max_err(jnp.where(live, out, 0), jnp.where(live, ref, 0))


def check_mimo_ragged(kind: str) -> float:
    """The ragged kernel likewise: decode rows and two chunks, one across
    the window's edge on a prior context."""
    from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

    rng = np.random.default_rng(41)
    g, k, v, sink, win, queries, scale = _mimo_operands(kind, rng, 200)
    q_lens, starts = [1, 1, 1, 150, 96], [700, 63, 0, 300, 0]
    kvls = [s + n for s, n in zip(starts, q_lens)]
    free = list(rng.permutation(np.arange(1, 200)))
    rows = [[free.pop() for _ in range(-(-n // 64))] for n in kvls]
    T, MP = 288, 16
    md = build_ragged_metadata(q_lens, starts, kvls, rows, T, max_pages=MP)
    q = queries(T)
    out = ragged_paged_attention(
        q, k, v, *(jnp.asarray(md[n]) for n in ("seg_page_table", "seg_kv_lens", "meta")),
        win, jnp.int32(1), sink=sink, scale=scale,
        name="window_attention_ragged" if g["window"] else None, interpret=INTERPRET)
    ref = paged_attention_jnp(
        q.astype(jnp.float32)[:, None], k[1].astype(jnp.float32),
        v[1].astype(jnp.float32), jnp.asarray(md["tok_page_table"]),
        jnp.maximum(jnp.asarray(md["tok_positions"]), 0)[:, None],
        jnp.asarray(md["tok_kv_lens"]), scale=scale, window=win, sink=sink)[:, 0]
    real = (jnp.asarray(md["tok_positions"]) >= 0)[:, None, None, None]
    return _max_err(jnp.where(real, out, 0), jnp.where(real, ref, 0))


def check_mimo_prefill(kind: str) -> float:
    """The flash-prefill kernel likewise: a 256-token chunk on 300 tokens of
    prior context."""
    rng = np.random.default_rng(42)
    g, k, v, sink, win, queries, scale = _mimo_operands(kind, rng, 40)
    S, MP = 256, 16
    qs, ql = np.asarray([300], np.int32), np.asarray([256], np.int32)
    pt = jnp.asarray(rng.permutation(np.arange(1, 40))[:MP][None].astype(np.int32))
    q = queries(1, S)
    out = prefill_paged_attention(
        q, k, v, pt, jnp.asarray(qs), jnp.asarray(ql), jnp.asarray(qs + ql), win,
        jnp.int32(1), sink=sink, scale=scale,
        name="window_attention_prefill" if g["window"] else None, interpret=INTERPRET)
    ref = paged_attention_jnp(
        q.astype(jnp.float32), k[1].astype(jnp.float32), v[1].astype(jnp.float32),
        pt, jnp.asarray(qs[:, None] + np.arange(S)[None]), jnp.asarray(qs + ql),
        scale=scale, window=win, sink=sink)
    return _max_err(out, ref)


# DeepSeek-V3.2's layer at published widths (the cell dsv32-docqa-steady): one
# layer of models/mla.py with its indexer, the selection and the attention over
# the selected rows, on pools of 64-token pages at a nonzero layer.
def _dsa_layer(rows: int, S: int, ctx: int, seed: int = 44):
    """(config, layer weights, hidden states, pools, page table, positions,
    kv_lens): `rows` sequences of `ctx` cached tokens whose last S are this
    step's, the pools drawn (every layer other data), index keys of unit size."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config

    c = get_config("deepseek-v3.2").with_(n_layers=2, n_dense_layers=0, n_experts=8,
                                          n_expert_groups=0, topk_groups=0)
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    # the attention's leaves alone, drawn as init_params draws them (norms 1,
    # the LayerNorm's bias 0): the layer's experts are no part of the check
    shapes = jax.eval_shape(lambda: llama.init_params(c, k[0]))["layers"]
    lp = {}
    for i, (n, sd) in enumerate(sorted(shapes.items())):
        if n.startswith(("we_", "ws_", "w_router", "router_bias")):
            continue
        if sd.ndim == 2:  # [L, d]: a norm's weight, or the LayerNorm's bias
            lp[n] = jnp.full(sd.shape[1:], 0.0 if n == "ik_norm_b" else 1.0, sd.dtype)
        else:
            lp[n] = (jax.random.normal(jax.random.fold_in(k[0], i), sd.shape[1:], jnp.float32)
                     * sd.shape[-2] ** -0.5).astype(sd.dtype)
    PS, MP = 64, -(-ctx // 64)
    NP = rows * MP + 1
    kp = jax.random.normal(k[1], (2, NP, PS, 1, c.mla_pool_dim), jnp.bfloat16)
    ip = jax.random.normal(k[2], (2, NP, PS, 1, c.index_head_dim), jnp.bfloat16)
    h = jax.random.normal(k[3], (rows, S, c.dim), jnp.bfloat16)
    pt = jnp.asarray(1 + np.random.default_rng(seed).permutation(rows * MP)
                     .reshape(rows, MP).astype(np.int32))
    pos = jnp.broadcast_to(jnp.arange(ctx - S, ctx, dtype=jnp.int32), (rows, S))
    return c, lp, h, kp, ip, pt, pos, jnp.full((rows,), ctx, jnp.int32)


@functools.lru_cache(maxsize=None)
def _dsa_layer_fn(impl, c):
    from dynamo_tpu.models.mla import _mla_attention

    return jax.jit(lambda lp, h, kp, ip, pt, pos, kv: _mla_attention(
        c, lp, h, kp, jnp.int32(1), pt, pos, pos, kv, attn_impl=impl,
        q_start=pos[:, 0], q_len=jnp.full(pos.shape[:1], pos.shape[1], jnp.int32),
        ik_pool=ip)[0])


def _dsa_run(impl, c, lp, h, kp, ip, pt, pos, kv):
    return _dsa_layer_fn(impl, c)(lp, h, kp, ip, pt, pos, kv)


def check_dsa_decode(rows: int, ctx: int) -> float:
    """A decode step of `rows` rows on `ctx` cached tokens: the chip's path
    (the latent kernel on the pool at or below index_topk, on the gathered
    buffer above it) against the jnp path of the same layer."""
    args = _dsa_layer(rows, 1, ctx)
    return _max_err(_dsa_run("jnp" if INTERPRET else "pallas", *args),
                    _dsa_run("jnp", *args))


def check_dsa_selection(rows: int, ctx: int) -> float:
    """The compiled indexer and select kernel on the chip: the selected SET of
    every row is numpy's stable argsort of the chip's own scores (ties towards
    the lower position), handed over as the pool's cells in position order
    (live first: a third of the rows have fewer live positions than k) with
    the same set as bit words, and the scores are the float32 ones to bf16's
    rounding. `select_topk` is held to the same set beside it. Returns the
    share of selected positions that differ plus the scores' largest relative
    error."""
    from dynamo_tpu.models import mla
    from dynamo_tpu.ops.dsa_select import dsa_select

    c, lp, h, kp, ip, pt, pos, kv = _dsa_layer(rows, 1, ctx)
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    hi, di = c.index_n_heads, c.index_head_dim
    qi = jax.random.normal(k[0], (rows, 1, hi, di), jnp.bfloat16)
    wi = jax.random.normal(k[1], (rows, 1, hi), jnp.float32) * (hi * di) ** -0.5
    keys = ip[1, pt].reshape(rows, -1, di)
    keys = keys.at[:, 5::7].set(keys[:, 4::7][:, : keys[:, 5::7].shape[1]])  # ties
    C = keys.shape[1]
    K = min(c.index_topk, C)
    n_live = np.full((rows,), C, np.int32)
    n_live[1::3] = K - 5 - np.arange(len(n_live[1::3]))
    live = np.arange(C)[None, :] < n_live[:, None]
    scores = jnp.where(live, jax.jit(mla.index_scores)(qi, wi, keys)[:, 0], -jnp.inf)
    host = np.asarray(scores)
    want = np.sort(np.argsort(-host, axis=-1, kind="stable")[:, :K], -1)
    sorted_ = np.sort(np.asarray(jax.jit(lambda x: mla.select_topk(x, K))(scores)), -1)
    cells, words = jax.jit(lambda x, t, n: dsa_select(x, t, n, k=K, interpret=INTERPRET))(
        scores, pt, jnp.asarray(n_live))
    tables, PS = np.asarray(pt), C // pt.shape[1]
    want_cells = np.take_along_axis(tables, want // PS, axis=1) * PS + want % PS
    as_mask = np.zeros((rows, C), bool)
    np.put_along_axis(as_mask, want, True, axis=-1)
    bad_words = (mla.unpack_chosen(np.asarray(words), C) != (as_mask & live)).mean()
    ref = np.einsum("bhc,bh->bc", np.maximum(np.einsum(
        "bhd,bcd->bhc", np.asarray(qi[:, 0], np.float32), np.asarray(keys, np.float32)), 0),
        np.asarray(wi[:, 0]))
    rel = float(np.abs(np.where(live, host - ref, 0)).max() / np.abs(ref).max())
    wrong = ((np.asarray(cells) != want_cells).any() or bad_words > 0
             or (sorted_ != want).any())  # a set: one position off fails
    return float(wrong) + rel


def check_dsa_prefill(S: int, ctx: int) -> float:
    """A chunk of S tokens ending at `ctx`: the layer's selecting arm (XLA
    ops: the selection as a mask by a radix select, attention a block of
    queries by a block of pages at a time with a running softmax) against
    float32 attention in one piece under the sorted selection as a mask."""
    from dynamo_tpu.models import mla
    from dynamo_tpu.models.toolkit import attn_score_scale

    c, lp, h, kp, ip, pt, pos, kv = _dsa_layer(1, S, ctx)
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    H, dc, Dl = c.n_heads, c.kv_lora_rank, c.mla_cache_dim
    hi, di = c.index_n_heads, c.index_head_dim
    q_abs = jax.random.normal(k[0], (1, S, H, dc), jnp.bfloat16) * dc ** -0.5
    q_r = jax.random.normal(k[1], (1, S, H, Dl - dc), jnp.bfloat16) * dc ** -0.5
    qi = jax.random.normal(k[2], (1, S, hi, di), jnp.bfloat16)
    wi = jax.random.normal(k[3], (1, S, hi), jnp.float32)
    scale = attn_score_scale(c, c.qk_nope_head_dim + c.qk_rope_head_dim)
    out, _ = jax.jit(lambda *a: mla._selected_attention(
        c, *a, attn_impl="pallas", dc=dc, scale=scale))(
        kp, ip, jnp.int32(1), q_abs, q_r, qi, wi, pt, pos, kv)

    def masked(kp, ip, q_abs, q_r, qi, wi, pt, pos):
        keys = ip[1, pt].reshape(1, -1, di)
        lat = kp[1, pt].reshape(-1, c.mla_pool_dim)[:, :Dl].astype(jnp.float32)
        sc = mla.index_scores(qi, wi, keys)[0]
        live = jnp.arange(sc.shape[-1])[None, :] <= pos[0][:, None]
        idx = mla.select_topk(jnp.where(live, sc, -jnp.inf), c.index_topk)
        mask = jnp.zeros(sc.shape, bool).at[jnp.arange(S)[:, None], idx].set(True) & live
        q = jnp.concatenate([q_abs, q_r], -1)[0].astype(jnp.float32)
        outs = []
        for h0 in range(0, H, 16):  # heads in blocks: [S, 16, C] f32 at a time
            s_ = jnp.einsum("shd,cd->shc", q[:, h0:h0 + 16], lat) * scale
            p = jax.nn.softmax(jnp.where(mask[:, None], s_, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("shc,cd->shd", p, lat[:, :dc]))
        return jnp.concatenate(outs, axis=1)[None]

    return _max_err(out, jax.jit(masked)(kp, ip, q_abs, q_r, qi, wi, pt, pos))


def time_dsa() -> None:
    """`--time-dsa` (chip only): device time of the selecting arm's parts at
    the cell's sizes, one JSON line a point, by the host clock around
    block_until_ready, the median of five calls."""
    from dynamo_tpu.models import mla
    from dynamo_tpu.ops.mla_attention import decode_mla_attention

    def med(fn, *a):
        jax.block_until_ready(fn(*a))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ts.append((time.perf_counter() - t0) * 1e3)
        return round(sorted(ts)[2], 3)

    from dynamo_tpu.ops.dsa_select import dsa_select

    def chained_us(fn, x, reps=20):
        """Device time of one fn(x) in us: `reps` calls in one program, each
        fed by the one before, less the same loop around nothing."""
        def loop(f):
            def body(_, carry):
                outs = f(x + carry)
                return carry + sum(o.ravel()[0].astype(jnp.float32) for o in outs) * 0.0
            return jax.jit(lambda: jax.lax.fori_loop(0, reps, body, jnp.float32(0.0)))

        full, bare = med(loop(fn)), med(loop(lambda y: (y[:, :1],)))
        return round((full - bare) * 1e3 / reps, 1)

    for rows, S, ctx in [(4, 1, 36864), (8, 1, 32768), (16, 1, 24576), (32, 1, 32768),
                         (1, 256, 24576), (1, 1024, 4096), (1, 1024, 16384), (1, 1024, 32768)]:
        c, lp, h, kp, ip, pt, pos, kv = _dsa_layer(rows, S, ctx)
        hi, di, Dl, dc = c.index_n_heads, c.index_head_dim, c.mla_pool_dim, c.kv_lora_rank
        k = jax.random.split(jax.random.PRNGKey(1), 3)
        qi = jax.random.normal(k[0], (rows, S, hi, di), jnp.bfloat16)
        wi = jax.random.normal(k[1], (rows, S, hi), jnp.float32)
        gather_keys = jax.jit(lambda ip, pt: ip[1, pt].reshape(rows, -1, di))
        keys = gather_keys(ip, pt)
        scores = jax.jit(mla.index_scores)(qi, wi, keys)
        K = c.index_topk
        idx = jax.jit(lambda x: mla.select_topk(x, K))(scores)
        row = {"rows": rows, "S": S, "ctx": ctx,
               "key_gather_ms": med(gather_keys, ip, pt),
               "index_scores_ms": med(jax.jit(mla.index_scores), qi, wi, keys),
               "top_k_ms": med(jax.jit(lambda x: mla.select_topk(x, K)), scores),
               "topk_mask_ms": med(jax.jit(lambda x: mla.topk_mask(x, K)), scores)}
        if S == 1:
            row["dsa_select_ms"] = med(
                jax.jit(lambda x, t, n: dsa_select(x[:, 0], t, n, k=K)), scores, pt, kv)
            # the host's clock holds ~0.9 ms of dispatch a call: the device's
            # own time a call, from 20 calls chained in one program
            row["top_k_dev_us"] = chained_us(
                lambda x: mla.select_topk(x, K, with_mask=True), scores[:, 0])
            row["dsa_select_dev_us"] = chained_us(
                lambda x: dsa_select(x, pt, kv, k=K), scores[:, 0])
            lat_flat = kp.reshape(2, -1, Dl)
            gather = jax.jit(lambda lf, i: lf[1, i[:, 0]].reshape(rows * K // 64, 64, 1, Dl))
            sel = gather(lat_flat, idx)
            q = jax.random.normal(k[2], (rows, c.n_heads, Dl), jnp.bfloat16)
            own = jnp.arange(rows * K // 64, dtype=jnp.int32).reshape(rows, -1)
            n = jnp.full((rows,), K, jnp.int32)
            row["latent_gather_ms"] = med(gather, lat_flat, idx)
            row["kernel_on_selected_ms"] = med(
                lambda *a: decode_mla_attention(*a, dc=dc, scale=0.1352), q, sel, own, n)
            row["kernel_on_whole_context_ms"] = med(
                lambda *a: decode_mla_attention(*a, dc=dc, scale=0.1352),
                q, kp, pt, kv, jnp.int32(1))
        row["layer_ms"] = med(lambda *a: _dsa_run("pallas", c, *a), lp, h, kp, ip, pt, pos, kv)
        print(json.dumps(row), flush=True)


def check_block_copy() -> float:
    from dynamo_tpu.ops.block_copy import gather_pages, scatter_pages

    rng = np.random.default_rng(6)
    pool = jnp.asarray(rng.standard_normal((3, 32, 16, 8, 128)), jnp.bfloat16)
    idx = jnp.asarray([7, 0, 19, 30], jnp.int32)
    out = gather_pages(pool, idx, interpret=INTERPRET)
    ref = np.asarray(pool)[:, [7, 0, 19, 30]]
    d1 = _max_err(out, ref)
    hm = gather_pages(pool, idx, head_major=True, interpret=INTERPRET)
    d2 = _max_err(hm, ref.transpose(0, 1, 3, 2, 4))
    dst = jnp.zeros_like(pool)
    back = scatter_pages(dst, jnp.asarray([1, 2, 3, 4], jnp.int32), out,
                         interpret=INTERPRET)
    d3 = _max_err(np.asarray(back)[:, 1:5], ref)
    return max(d1, d2, d3)


def _ssm_operands(rng, T: int, state_dtype: str):
    """A state pool [3, 9, N, d // 128, 128] and T tokens' operands at
    ai21-jamba2-3b's widths (N 16, d 5120): steps of 0.001-0.5 and decays
    of -(1..16), as its random tree has them."""
    from dynamo_tpu.ops import ssm

    N, d = 16, 5120
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = f(3, 9, N, *ssm.state_shape(d)).astype(state_dtype)
    dt = jax.nn.softplus(f(T, d) - 3.0)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, d))
    return pool, (f(T, d), dt, f(T, N), f(T, N), A)


def check_ssm_update(state_dtype: str) -> float:
    """The decode step's state update: 13 live rows of a bucket of 16, one
    of them a sequence's first token; the padding rows move nothing."""
    from dynamo_tpu.ops import ssm

    rng = np.random.default_rng(7)
    pool, ops = _ssm_operands(rng, 16, state_dtype)
    # eight sequences' slots, then five rows on the scratch slot (warm-up's
    # dummies share it, and race on it), then padding
    slots = jnp.asarray([5, 1, 8, 2, 7, 3, 6, 4] + [0] * 8, jnp.int32)
    live = jnp.arange(16) < 13
    fresh = jnp.arange(16) == 2
    y0, p0 = ssm.ssm_update_jnp(pool, LAYER, slots, live, fresh, *ops)
    y1, p1 = ssm.ssm_update(pool, LAYER, slots, live, fresh, *ops,
                            interpret=INTERPRET)
    keep = [1, 2, 3, 4, 5, 6, 7, 8]
    untouched = _max_err(np.asarray(p1)[:LAYER], np.asarray(pool)[:LAYER])
    return max(_max_err(y1[:8], y0[:8]), _max_err(y1[13:], y0[13:]),
               _max_err(np.asarray(p1)[LAYER, keep], np.asarray(p0)[LAYER, keep]),
               untouched)


def check_ssm_scan(state_dtype: str) -> float:
    """The ragged step's flat axis: five decode rows (segments of one
    token), a chunk that starts its sequence, one that goes on from its
    slot, and a tail of padding, 64 tokens in all."""
    from dynamo_tpu.ops import ssm

    rng = np.random.default_rng(8)
    T = 64
    pool, ops = _ssm_operands(rng, T, state_dtype)
    lens = [1, 1, 1, 1, 1, 23, 30]
    slot = [5, 1, 8, 2, 7, 3, 6]
    fresh = [0, 0, 0, 0, 0, 1, 0]
    start = np.cumsum([0] + lens[:-1])
    seg_of = np.repeat(np.arange(len(lens)), lens)
    seg_of = np.concatenate([seg_of, np.full(T - len(seg_of), len(lens) - 1)])
    off = np.arange(T) - start[seg_of]
    flags = ssm.scan_flags(jnp.arange(T) < sum(lens), jnp.asarray(off == 0),
                           jnp.asarray(off == np.asarray(lens)[seg_of] - 1),
                           jnp.asarray(np.asarray(fresh)[seg_of] != 0))
    tok_slot = jnp.asarray(np.asarray(slot)[seg_of], jnp.int32)
    y0, p0 = ssm.ssm_scan_jnp(pool, LAYER, tok_slot, flags, *ops)
    y1, p1 = ssm.ssm_scan(pool, LAYER, tok_slot, flags, *ops, interpret=INTERPRET)
    return max(_max_err(y1, y0), _max_err(p1, p0))


def _kda_operands(rng, T: int, bound: bool = False):
    """T tokens' operands at ling-3.0-flash-vl's widths (32 heads of 128):
    q and k L2-normed a head, log-decays in (-5, 0) (`bound`: every channel at
    the lower bound), beta in (0, 1)."""
    H, d = 32, 128
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    g = jnp.full((T, H, d), -5.0 * (1 - 1e-6)) if bound else (
        -5.0 * jax.nn.sigmoid(2 * f(T, H, d) - 4))
    return (unit(f(T, H, d)) * d ** -0.5, unit(f(T, H, d)), f(T, H, d), g,
            jax.nn.sigmoid(f(T, H)))


def check_kda_update() -> float:
    """The decode step's delta-rule update: 13 live rows of a bucket of 16,
    one of them a sequence's first token; the padding rows move nothing."""
    from dynamo_tpu.ops import kda

    rng = np.random.default_rng(17)
    pool = jnp.asarray(rng.standard_normal((3, 9, 32, 128, 128)), jnp.float32)
    ops = _kda_operands(rng, 16)
    slots = jnp.asarray([5, 1, 8, 2, 7, 3, 6, 4] + [0] * 8, jnp.int32)
    live = jnp.arange(16) < 13
    fresh = jnp.arange(16) == 2
    with jax.default_matmul_precision("highest"):
        y0, p0 = kda.kda_update_jnp(pool, LAYER, slots, live, fresh, *ops)
    y1, p1 = kda.kda_update(pool, LAYER, slots, live, fresh, *ops,
                            interpret=INTERPRET)
    keep = [1, 2, 3, 4, 5, 6, 7, 8]
    untouched = _max_err(np.asarray(p1)[:LAYER], np.asarray(pool)[:LAYER])
    return max(_max_err(y1[:8], y0[:8]), _max_err(y1[13:], y0[13:]),
               _max_err(np.asarray(p1)[LAYER, keep], np.asarray(p0)[LAYER, keep]),
               untouched)


def check_kda_chunk(T: int, bound: bool) -> float:
    """A prefill chunk from a carried-in state against the token-by-token
    recurrence in float32 (x 100: the gate's tolerance is bf16 attention's,
    this kernel's is float32's, 5e-4 on outputs of ~0.3)."""
    from dynamo_tpu.ops import kda

    rng = np.random.default_rng(18 + T)
    S0 = jnp.asarray(rng.standard_normal((32, 128, 128)), jnp.float32)
    ops = _kda_operands(rng, T, bound)
    with jax.default_matmul_precision("highest"):
        o0, s0 = jax.jit(kda.kda_recurrence)(S0, *ops)
    o1, s1 = kda.kda_chunk(S0, *ops, interpret=INTERPRET)
    return 100 * max(_max_err(o1, o0), _max_err(s1, s0))


def all_checks():
    """(name, thunk) for every check, GQA cross product first."""
    checks = []
    for gname, geom in GEOMETRIES.items():
        for kname, fn in (("decode", check_decode),
                          ("decode ragged-batch", check_decode_ragged),
                          ("prefill", check_prefill),
                          ("ragged", check_ragged),
                          ("ragged sparse-table", check_ragged_sparse)):
            for variant in geom.get("variants", VARIANTS):
                checks.append((f"{kname} {variant} @{gname}",
                               functools.partial(fn, geom, variant)))
    if len(jax.devices()) > 1:
        # the tensor-parallel wrapper: kv heads over every device there is
        from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(model=len(jax.devices())))
        for gname, geom in GEOMETRIES.items():
            if geom["Hk"] % len(jax.devices()):
                continue  # one KV head: nothing to shard
            # (a shard's page is 1 / devices of the pool's: the walks are
            # built outside shard_map and must count a step's pages as the
            # shard's kernel does)
            for variant in VARIANTS:
                checks.append((
                    f"ragged sparse-table sharded {variant} @{gname}",
                    functools.partial(check_ragged_sparse, geom, variant,
                                      mesh)))
                checks.append((
                    f"decode ragged-batch sharded {variant} @{gname}",
                    functools.partial(check_decode_ragged, geom, variant,
                                      mesh)))
    checks += [
        ("gemma decode (softcap+window, G 2, PS 16)", check_gemma_decode),
        ("gemma prefill (softcap+window, G 2, PS 16)", check_gemma_prefill),
        ("mla decode bf16", check_mla),
        ("mla prefill bf16", check_mla_prefill),
        ("mla decode int8-latent", check_mla_int8),
        ("block copy/permute/scatter", check_block_copy),
    ]
    for kind in MIMO_KINDS:
        checks += [
            (f"mimo decode {kind} (dk 192 in 256, dv 128) @mimo-v2-flash",
             functools.partial(check_mimo_decode, kind)),
            (f"mimo ragged {kind} @mimo-v2-flash",
             functools.partial(check_mimo_ragged, kind)),
            (f"mimo prefill {kind} @mimo-v2-flash",
             functools.partial(check_mimo_prefill, kind)),
        ]
    for rows, ctx in ((1, 1024), (8, 4096), (32, 4096), (1, 32768), (8, 32768), (32, 32768)):
        checks.append((f"dsa decode {rows} rows ctx {ctx} @deepseek-v3.2",
                       functools.partial(check_dsa_decode, rows, ctx)))
    checks += [
        ("dsa selection 4 rows ctx 36864 @deepseek-v3.2",
         functools.partial(check_dsa_selection, 4, 36864)),
        ("dsa selection 8 rows ctx 4096 @deepseek-v3.2",
         functools.partial(check_dsa_selection, 8, 4096)),
        ("dsa selection 32 rows ctx 32768 @deepseek-v3.2",
         functools.partial(check_dsa_selection, 32, 32768)),
        ("dsa prefill 256 on ctx 4096 @deepseek-v3.2",
         functools.partial(check_dsa_prefill, 256, 4096)),
        ("dsa prefill 256 on ctx 32768 @deepseek-v3.2",
         functools.partial(check_dsa_prefill, 256, 32768)),
    ]
    for dt in ("float32", "bfloat16"):
        checks += [
            (f"ssm_update {dt} state @ai21-jamba2-3b",
             functools.partial(check_ssm_update, dt)),
            (f"ssm_scan {dt} state @ai21-jamba2-3b",
             functools.partial(check_ssm_scan, dt)),
        ]
    checks += [
        ("kda_update @ling-3.0-flash-vl", check_kda_update),
        ("kda_chunk 512 tokens @ling-3.0-flash-vl",
         functools.partial(check_kda_chunk, 512, False)),
        ("kda_chunk 200 tokens at the decay bound @ling-3.0-flash-vl",
         functools.partial(check_kda_chunk, 200, True)),
    ]
    return checks


def main(argv=None) -> int:
    global INTERPRET
    argv = sys.argv[1:] if argv is None else argv
    INTERPRET = "--interpret" in argv

    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    print(f"backend: {device}", flush=True)
    if INTERPRET:
        print("REHEARSAL: Pallas interpreter — says nothing about Mosaic",
              flush=True)
    elif dev[0].platform == "cpu":
        print("FAIL: no accelerator backend (this gate checks compiled "
              "Mosaic; a CPU run proves nothing)", flush=True)
        return 2
    if "--time-dsa" in argv:
        time_dsa()
        return 0
    only = argv[argv.index("--only") + 1] if "--only" in argv else ""
    results = []
    for name, fn in [c for c in all_checks() if only in c[0]]:
        t0 = time.monotonic()
        row = {"name": name, "tol": TOL}
        try:
            row["max_abs_err"] = fn()
            row["ok"] = bool(row["max_abs_err"] < TOL)
            detail = f"max|Δ|={row['max_abs_err']:.4f} (tol {TOL})"
        except Exception as e:  # a compiler refusal is a result, not a crash
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:2000]
            detail = row["error"]
        row["seconds"] = round(time.monotonic() - t0, 2)
        results.append(row)
        print(f"{'PASS' if row['ok'] else 'FAIL'} {name}: {detail} "
              f"[{row['seconds']}s]", flush=True)
    ok = all(r["ok"] for r in results)
    print(json.dumps({"ok": ok, "interpreted": INTERPRET, "device": device,
                      "checks": results}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
