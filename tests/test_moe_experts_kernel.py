"""The routed experts of a forward of few rows as one kernel over a work
list of hit experts (ops/moe_experts.py), on the CPU in interpret mode:
against the dense path of models/moe.py (every held expert over every
row), which is what every forward ran until PR 34 and what the CPU, a
prefill chunk, int8 experts and a mesh still run. And the counter that
says how often the kernel engages, on a tiny engine."""

import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models.config import ModelConfig, get_config
from dynamo_tpu.models.moe import EXPERT_STACKS, _moe_block, experts_kernel_stack
from dynamo_tpu.ops import moe_experts
from dynamo_tpu.runtime.context import Context

E, F, LAYERS, LAYER = 128, 256, 2, 1
# one chip's share of a wide router (the benchmark cell's: 32 of 128 from
# 32 on, 4 a token) and a whole small layer (8 experts, 2 a token: at a
# handful of rows every expert is hit and the list is full)
SHARE = ModelConfig(name="share", dim=E, moe_ffn_dim=F, n_experts=128,
                    n_experts_active=4, n_experts_held=32, expert_first=32)
WHOLE = ModelConfig(name="whole", dim=E, moe_ffn_dim=F, n_experts=8,
                    n_experts_active=2)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel in interpret mode, and a tile budget that cuts float32
    experts into two ffn tiles (bf16 ones stay whole)."""
    monkeypatch.setattr(moe_experts, "routed_experts", functools.partial(
        moe_experts.routed_experts, interpret=True))
    monkeypatch.setattr(moe_experts, "TILE_BUDGET_BYTES", 2 * 3 * E * 128 * 4)


def _layer(c: ModelConfig, dtype, seed: int):
    """(stacks [LAYERS, n_held, ...], layer LAYER's slice of them with the
    router, as the layer scan hands a layer to the dense path)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = c.experts_held

    def w(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    stacks = (w(ks[0], LAYERS, n, E, F), w(ks[1], LAYERS, n, E, F),
              w(ks[2], LAYERS, n, F, E))
    lp = {"w_router": w(ks[3], E, c.n_experts),
          **{k: s[LAYER] for k, s in zip(EXPERT_STACKS, stacks)}}
    return stacks, lp


def _held_picks(c: ModelConfig, sel: np.ndarray) -> list:
    """Per row, the held experts (indices into the held stack) it picked."""
    local = sel - c.expert_first
    return [set(r[(r >= 0) & (r < c.experts_held)].tolist()) for r in local]


def _mask(c: ModelConfig, sel: np.ndarray, rows: str):
    """Which rows are real. `all`; `pad`: the rows after the first are
    padding where they pick a held expert no other row picks (so a padding
    row that listed would add an expert to the list and a term to the
    sum); `none`: only rows with no held pick are real."""
    held = _held_picks(c, sel)
    T = len(held)
    if rows == "all":
        return np.ones(T, bool)
    if rows == "none":
        return np.array([not h for h in held])
    valid = np.ones(T, bool)
    for t in range(1, T):
        others = set().union(*(held[u] for u in range(T) if u != t and valid[u]))
        if held[t] - others:
            valid[t] = False
    return valid


CASES = (
    [("share", T, rows) for T in (5, 25, 32) for rows in ("all", "pad", "none")]
    + [("share", 1, "all"), ("share", 1, "none")]
    + [("whole", T, "all") for T in (1, 5, 25, 32)]
    + [("whole", T, "pad") for T in (5, 25)]
)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layout, T, rows", CASES)
def test_kernel_is_the_dense_path_over_the_hit_experts(interpreted, layout, T,
                                                      rows, dtype, tol):
    c = SHARE if layout == "share" else WHOLE
    dense = jax.jit(lambda lp, x: _moe_block(c, lp, x))
    for seed in range(64):  # the first draw that can show what `rows` asks
        stacks, lp = _layer(c, dtype, seed)
        x = jax.random.normal(jax.random.PRNGKey(100 + seed), (T, 1, E)).astype(dtype)
        want, want_sel, zero = dense(lp, x)
        sel = np.asarray(want_sel)[:, 0]
        valid = _mask(c, sel, rows)
        if rows == "all" or (rows == "pad" and not valid.all()) or (
                rows == "none" and valid.any()):
            break
    else:
        pytest.fail(f"no draw of 64 shows {rows!r} at T={T}")
    assert int(zero) == 0
    router = {"w_router": lp["w_router"]}  # the stacks stay out of the scan's slice
    got, got_sel, listed = jax.jit(
        lambda lp, x, v, *st: _moe_block(c, lp, x, None, v, st + (jnp.int32(LAYER),))
    )(router, x, jnp.asarray(valid)[:, None], *stacks)

    # the picks are the dense path's, bit for bit (the route scope is shared)
    assert np.asarray(got_sel).tobytes() == np.asarray(want_sel).tobytes()
    # the list holds the held experts the REAL rows picked, and no other
    held = _held_picks(c, sel)
    hit = set().union(*(h for h, v in zip(held, valid) if v))
    assert int(listed) == len(hit)
    if rows == "pad":
        assert len(set().union(*held)) > len(hit)  # a padding row would have listed more
    if layout == "whole" and rows == "all" and T >= 25:
        assert int(listed) == c.n_experts  # all hit: the full list
    got, want = (np.asarray(a, np.float32)[:, 0] for a in (got, want))
    if rows == "none":  # nothing listed: the routed part is exactly 0
        assert int(listed) == 0 and not got[valid].any()
    np.testing.assert_allclose(got[valid], want[valid], rtol=tol, atol=tol)
    assert not got[~valid].any()  # a padding row weighs nothing


def test_the_row_bound_widens_only_under_a_router_that_leaves_experts_unread():
    """32 rows for every router the benchmark had before PR 49 (64 rows of 8
    picks reach 87 % of 256 experts, of 4 picks 87 % of 128), 64 for 8 picks
    of 512 (63 %); and `experts_kernel_stack` asks it."""
    assert [moe_experts.row_bound(n, k) for n, k in
            ((128, 4), (256, 8), (128, 6), (4, 2), (16, 4))] == [moe_experts.MAX_ROWS] * 5
    assert moe_experts.row_bound(512, 8) == moe_experts.WIDE_ROWS == 64
    wide = WHOLE.with_(n_experts=512, n_experts_active=8)
    layers = {k: jnp.zeros((1, 512, 128, 128), jnp.bfloat16)
              for k in ("we_gate", "we_up", "we_down")}
    assert experts_kernel_stack(wide, layers, 64, None, "pallas") is not None
    assert experts_kernel_stack(wide, layers, 65, None, "pallas") is None
    assert experts_kernel_stack(WHOLE, layers, 64, None, "pallas") is None


@pytest.mark.parametrize("layers", [
    {"we_gate": {"q": jnp.zeros((2, 8, E, F), jnp.int8), "s": jnp.ones((2, 8, 1, F))}},
    {"we_gate": jnp.zeros((2, 8, E, F), jnp.bfloat16)},
], ids=["int8", "bf16"])
def test_the_kernel_engages_on_what_the_forward_can_see(layers):
    """Pallas in use, few rows, unquantized experts, no expert / model mesh
    axis: everything else keeps the dense path."""
    layers = {**layers, "we_up": layers["we_gate"], "we_down": layers["we_gate"]}
    plain = not isinstance(layers["we_gate"], dict)
    mesh = lambda **axes: type("M", (), {"shape": axes})()
    take = lambda rows, mesh=None, impl="pallas": experts_kernel_stack(
        WHOLE, layers, rows, mesh, impl) is not None
    assert take(1) is plain and take(moe_experts.MAX_ROWS) is plain
    assert not take(moe_experts.MAX_ROWS + 1)  # a prefill chunk
    assert not take(8, impl="jnp") and not take(8, impl="ring")  # the CPU; SP
    assert not take(8, mesh(model=4)) and not take(8, mesh(expert=2, model=1))
    assert take(8, mesh(model=1, data=1, expert=1)) is plain
    assert not experts_kernel_stack(WHOLE, {}, 8, None, "pallas")  # no experts


# -- the counter, on a tiny engine ---------------------------------------------


def _serve(runner, reqs):
    engine = InferenceEngine(runner, max_batch=4, chunk_size=64)

    async def one(prompt, n_out):
        return [it async for it in engine.generate(
            {"token_ids": prompt, "sampling": {"temperature": 0.0},
             "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}},
            Context())]

    async def drive():
        return await asyncio.gather(*(one(p, k) for p, k in reqs))

    try:
        asyncio.run(drive())
    finally:
        engine.stop()  # joins the step thread: the last record is in
    return engine.recorder.snapshot()


def _runner(config, **kw):
    return ModelRunner(config, num_pages=64, page_size=4, max_pages_per_seq=16,
                       decode_buckets=(1, 2, 4), prefill_buckets=(8, 64), seed=5,
                       dtype=jnp.float32, **kw)


def test_listed_is_the_hit_count_where_the_kernel_ran(monkeypatch):
    """`IterationRecord.moe_experts_listed` is the kernels' own live count:
    on a held share served through the Pallas path it equals the record's
    `moe_experts_hit` x forwards x expert layers (padding rows of a decode
    bucket and of a chunk list nothing), it is 0 for a forward over the row
    bound (the dense path) and on a dense model."""
    import dynamo_tpu.ops.mla_attention as mla_ops

    for mod, name in ((moe_experts, "routed_experts"),
                      (mla_ops, "decode_mla_attention"),
                      (mla_ops, "prefill_mla_attention")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name),
                                                         interpret=True))
    c = get_config("tiny-mistral4")  # 16 experts, 4 a token, experts 4..7 held
    L_moe = c.n_layers - c.n_dense_layers
    rng = np.random.default_rng(1)
    prompt = lambda n: rng.integers(1, c.vocab_size, n).tolist()
    # three rows in the 4-bucket (a padding row a step), then a prompt whose
    # chunk takes the 64-bucket: over the row bound
    records = _serve(_runner(c, attn_impl="pallas"),
                     [(prompt(5), 6), (prompt(7), 5), (prompt(3), 7)])
    records += _serve(_runner(c, attn_impl="pallas"), [(prompt(40), 2)])
    kernel, dense = [], []
    for r in records:
        # off the fused path every chunk is a forward of its own
        forwards = r.decode_steps * (r.decode_seqs > 0) + r.n_chunks
        if not forwards:
            continue
        (dense if r.chunk_tokens > moe_experts.MAX_ROWS else kernel).append(r)
        if r.chunk_tokens > moe_experts.MAX_ROWS:
            assert r.moe_experts_listed == 0 and r.moe_experts_hit > 0
        else:
            assert abs(r.moe_experts_listed - r.moe_experts_hit * forwards * L_moe) < 1e-3
    assert len(dense) == 1 and sum(r.moe_experts_listed for r in kernel) > 0
    assert any(r.kind == "decode" and r.decode_seqs == 3 for r in kernel)  # a padding row

    plain = _serve(_runner(get_config("tiny")), [([5, 9, 2, 7], 4)])
    assert plain and all(r.moe_experts_listed == 0 for r in plain)
