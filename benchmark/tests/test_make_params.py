"""serve.py's make_params against the program's own init_params: the same
tree for any architecture (leaves of any rank, a second stack of dense layers,
leaves the program fills and does not draw), and for a dense tree the bits the
harness built before it took any other (digests computed on commit 4e1edb4)."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS, ModelConfig

import rehearsal

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _serve():
    spec = importlib.util.spec_from_file_location("bench_serve", os.path.join(BENCH, "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


serve = _serve()

CONFIGS = {
    "tiny": PRESETS["tiny"],
    "tiny-moe-shared": PRESETS["tiny-moe-shared"],
    # leading dense layer (a second stack), router bias, two shared experts
    "tiny-mla-moe": PRESETS["tiny-mla-moe"].with_(n_shared_experts=2),
    "tiny-mla-q": PRESETS["tiny-mla-q"],
    "tiny-gemma2": PRESETS["tiny-gemma2"],  # zero-centred norms, tied head
    "tiny-qwen2": PRESETS["tiny-qwen2"],  # bf16 projection biases the program zeroes
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_make_params_returns_the_programs_tree(name):
    c = CONFIGS[name]
    got = serve.make_params(c, 5, jax.devices()[0], jnp.bfloat16)
    # two keys: a leaf equal under both is one the program fills
    a = llama.init_params(c, jax.random.PRNGKey(1), jnp.bfloat16)
    b = llama.init_params(c, jax.random.PRNGKey(2), jnp.bfloat16)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(a)
    drawn = serve.drawn_leaves(c, jnp.bfloat16)
    n_filled = 0
    for (path, g), x, y, d in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                  jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), drawn):
        where = name + jax.tree_util.keystr(path)
        assert g.shape == x.shape and g.dtype == x.dtype, where
        g32, same = np.asarray(g, np.float32), bool(np.array_equal(np.asarray(x), np.asarray(y)))
        assert np.isfinite(g32).all(), where
        assert d == (not same), where  # the jaxpr's verdict is the two keys' verdict
        if same:
            n_filled += 1
            assert np.array_equal(np.asarray(g), np.asarray(x)), where
        else:
            fan_in = g.shape[-1] if where.endswith("['embed']") else g.shape[-2]
            assert g32.std() * fan_in ** 0.5 == pytest.approx(1.0, abs=0.15), where
    assert n_filled >= 3  # at least the norms
    if c.is_moe:
        for leaf in ("we_gate", "we_up", "we_down"):
            w = np.asarray(got["layers"][leaf], np.float32)
            assert w.shape[:2] == (c.n_layers - c.n_dense_layers, c.n_experts)
            flat = w.reshape(-1, *w.shape[2:])
            for i in range(len(flat)):
                for j in range(i):
                    assert not np.array_equal(flat[i], flat[j]), (leaf, i, j)
    if c.is_moe and c.n_dense_layers:
        assert got["layers_dense"]["w_gate"].shape == (c.n_dense_layers, c.dim, c.ffn_dim)
    if "router_bias" in got["layers"]:
        assert not np.asarray(got["layers"]["router_bias"]).any()
    if c.norm_zero_centered:
        assert not np.asarray(got["norm_f"]).any()


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _phi3_rehearsed() -> ModelConfig:
    with open(os.path.join(BENCH, "configs", "phi-3-mini-4k.json")) as f:
        return ModelConfig(**rehearsal.rehearsal_sizes(json.load(f), BENCH)["model"])


# sha256 over path, type, shape and bytes of every leaf, from benchmark/serve.py
# as it stood on the parent commit (4e1edb4), CPU, bfloat16
PARENT = {
    ("tiny", 7): "e42d5e31091a9d9f2f182cf041482b4c663e00f5e4acac92600428d80e3e590e",
    ("tiny", 2**31 + 17): "ccaa59a86fbe24eee436c8e0b7203ec68804a55103a83b01628b36a6d955b839",
    ("phi3-rehearse", 7): "5c131300cbf3d79a6335a61083c00042ef9cebd3b3de746e1344cec59267761c",
    ("phi3-rehearse", 2**31 + 17): "2ca6fff4f532a37671bf0efc1d50284a9ce9c189f2e6b06b156a9b70e93d177e",
}


@pytest.mark.parametrize("name,seed", sorted(PARENT))
def test_a_dense_tree_is_bit_for_bit_the_parents(name, seed):
    c = PRESETS["tiny"] if name == "tiny" else _phi3_rehearsed()
    got = serve.make_params(c, seed, jax.devices()[0], jnp.bfloat16)
    assert _digest(got) == PARENT[(name, seed)]


def test_rehearsal_overlay():
    with open(os.path.join(BENCH, "configs", "phi-3-mini-4k.json")) as f:
        phi = json.load(f)
    with open(os.path.join(BENCH, "rehearse.json")) as f:
        base = json.load(f)
    # a configuration without the group rehearses at rehearse.json's sizes
    assert "rehearse" not in phi
    r = rehearsal.rehearsal_sizes(phi, BENCH)
    want = {**phi["model"], **base["model"]}
    want["n_kv_heads"] = base["model"]["n_heads"]  # phi-3's ratio of 1 is kept
    assert r["model"] == want
    assert r["server_flags"] == {**phi["server_flags"], **base["server_flags"]}
    assert r["correct_tolerance"] == base["correct_tolerance"]
    assert r["correct_routing_margin"] is None  # a dense model states none
    assert r["length_divisor"] == base["length_divisor"]
    # its own group is laid over rehearse.json's, key by key
    with open(os.path.join(BENCH, "tests", "data", "fixture-mla-moe.json")) as f:
        fix = json.load(f)
    r = rehearsal.rehearsal_sizes(fix, BENCH)
    own = fix["rehearse"]
    assert r["model"]["sliding_window"] == 0 and r["model"]["dim"] == base["model"]["dim"]
    assert r["model"]["n_experts"] == own["model"]["n_experts"]
    assert r["model"]["n_shared_experts"] == fix["model"]["n_shared_experts"]  # untouched
    assert r["model"]["n_kv_heads"] == r["model"]["n_heads"]  # 16 / 16 kept as 4 / 4
    assert r["server_flags"]["max-batch"] == own["server_flags"]["max-batch"]
    assert r["server_flags"]["page-size"] == base["server_flags"]["page-size"]
    assert r["correct_tolerance"] == own["correct_tolerance"]
    # the margin is the group's own: the configuration's full-size one, had it
    # one, would say nothing about the rehearsal's sizes
    assert r["correct_routing_margin"] == own["correct_routing_margin"] == 2 ** -5
    assert rehearsal.rehearsal_sizes(
        {**fix, "correct_routing_margin": 0.5, "rehearse": {
            k: v for k, v in own.items() if k != "correct_routing_margin"}},
        BENCH)["correct_routing_margin"] is None
    ModelConfig(**r["model"])  # every key is a field of the program's
    # a group that names its own n_kv_heads keeps it; a model with no heads to
    # keep a ratio of needs none
    gqa = {"model": {"n_heads": 32, "n_kv_heads": 8}, "server_flags": {},
           "rehearse": {"model": {"n_kv_heads": 2}}}
    assert rehearsal.rehearsal_sizes(gqa, BENCH)["model"]["n_kv_heads"] == 2
    del gqa["rehearse"]
    assert rehearsal.rehearsal_sizes(gqa, BENCH)["model"]["n_kv_heads"] == 1
    assert "n_kv_heads" not in rehearsal.rehearsal_sizes(
        {"model": {"state_dim": 16}, "server_flags": {}}, BENCH)["model"]
