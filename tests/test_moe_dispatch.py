"""EP all-to-all MoE dispatch on the 8-device CPU mesh: with lossless
capacity it must match the dense top-k reference exactly; with tight
capacity it degrades by dropping, not corrupting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.moe_dispatch import moe_dense_reference, moe_ep
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")


def _setup(n_experts=8, T=64, E=32, F=48, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, E)) * 0.5, jnp.float32)
    wr = jnp.asarray(rng.standard_normal((E, n_experts)) * 0.2, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((n_experts, E, F)) * 0.2, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((n_experts, E, F)) * 0.2, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((n_experts, F, E)) * 0.2, jnp.float32)
    return x, wr, wg, wu, wd


@pytest.mark.parametrize("ep", [2, 4, 8])
def test_moe_ep_matches_dense(ep):
    mesh = make_mesh(MeshConfig(expert=ep, data=8 // ep))
    x, wr, wg, wu, wd = _setup()
    k = 2
    # capacity_factor = n_experts/k guarantees losslessness
    out, sel = moe_ep(x, wr, wg, wu, wd, mesh, n_experts_active=k,
                      capacity_factor=wg.shape[0] / k, axis="expert")
    ref = moe_dense_reference(x, wr, wg, wu, wd, k)
    d = np.abs(np.asarray(out) - np.asarray(ref)).max()
    assert d < 1e-4, d
    assert sel.shape == (x.shape[0], k) and sel.dtype == jnp.int32


def test_moe_ep_tight_capacity_drops_not_corrupts():
    mesh = make_mesh(MeshConfig(expert=4, data=2))
    x, wr, wg, wu, wd = _setup(seed=3)
    out, _ = moe_ep(x, wr, wg, wu, wd, mesh, n_experts_active=2,
                    capacity_factor=0.5, axis="expert")
    ref = moe_dense_reference(x, wr, wg, wu, wd, 2)
    # some tokens dropped → not equal, but finite and bounded
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out)).max() <= np.abs(np.asarray(ref)).max() * 3
