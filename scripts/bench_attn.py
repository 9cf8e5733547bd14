"""Microbench: decode paged attention, Pallas kernel vs jnp gather.

Bench shapes: Hk=8, D=128 (llama-3.2-3b), B=32, PS=64, MP=8, kv_len=256.
Timing rule: many iters fused in one jit via lax.scan with a data
dependency (out feeds next q), then ONE device_get — one dispatch and one
host sync around the whole timed region.

All device arrays are built inside main(): module import must never
initialize a JAX backend (DYN-J003), so `python -c "import bench_attn"`
and tooling that imports the script stay platform-neutral.
"""

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

B, Hk, G, D = 32, 8, 3, 128
PS, MP = 64, 8
NP = B * MP + 8
ITERS = 64


@partial(jax.jit, static_argnames=("impl",))
def loop(q, k_pool, v_pool, pt, kv_lens, impl):
    def body(q, _):
        if impl == "pallas":
            o = decode_paged_attention(q, k_pool, v_pool, pt, kv_lens)
        else:
            o = paged_attention_jnp(
                q[:, None], k_pool, v_pool, pt, kv_lens[:, None] - 1, kv_lens
            )[:, 0]
        return o.astype(q.dtype), None

    q, _ = lax.scan(body, q, None, length=ITERS)
    return q


def bench_decode(kv_len: int) -> None:
    rng = np.random.default_rng(0)
    k_pool = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(
        np.stack([np.arange(i * MP, (i + 1) * MP) for i in range(B)]).astype(np.int32)
    )
    kv_lens = jnp.full((B,), kv_len, jnp.int32)
    q0 = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)

    cpu = jax.devices()[0].platform == "cpu"  # pallas needs interpret on CPU

    for impl in ("jnp",) if cpu else ("jnp", "pallas"):
        out = loop(q0, k_pool, v_pool, pt, kv_lens, impl)
        np.asarray(jax.device_get(out))  # warmup + compile
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = loop(q0, k_pool, v_pool, pt, kv_lens, impl)
            np.asarray(jax.device_get(out))
            times.append((time.perf_counter() - t0) / ITERS * 1e6)
        print(f"kv_len={kv_len} {impl:7s} per-iter: {min(times):8.1f} us",
              flush=True)

    # numeric agreement
    o1 = np.asarray(jax.device_get(decode_paged_attention(
        q0, k_pool, v_pool, pt, kv_lens, interpret=cpu)), np.float32)
    o2 = np.asarray(
        jax.device_get(paged_attention_jnp(
            q0[:, None], k_pool, v_pool, pt, kv_lens[:, None] - 1, kv_lens
        )[:, 0]),
        np.float32,
    )
    print("max abs diff:", np.abs(o1 - o2).max(), flush=True)
    bench_ragged_mixed(rng, k_pool, v_pool)


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
    kv_len = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    bench_decode(kv_len)


if __name__ == "__main__":
    main()
