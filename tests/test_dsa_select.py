"""ops/dsa_select.py, the select kernel of a DeepSeek-V3.2 decode step,
interpreted on the CPU: the chosen SET is numpy's stable argsort of the same
scores (ties towards the lower position) and `select_topk`'s, handed over as
the pool's cells in position order (live first) and as `pack_chosen`'s words.
Compiled Mosaic is held to the same on the chip (`scripts/tpu_parity.py --only
dsa`) and compiled for a described v5e in tests/test_mosaic_compile.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import mla
from dynamo_tpu.ops.dsa_select import dsa_select


def _row(kind, rng, C, K):
    """(scores f32 [C] before the dead are masked, n_live) of one row."""
    x = rng.standard_normal(C).astype(np.float32)
    n_live = C
    if kind == "ties":  # a few hundred equals straddle the threshold
        x = (np.round(x, 1) + 0.0).astype(np.float32)
    elif kind == "short":  # fewer live than K: dead ones fill, after the live
        n_live = int(rng.integers(1, K))
    elif kind == "exact":
        n_live = K
    elif kind == "odd":  # -inf, denormals and the largest float among the live
        x[rng.integers(0, C, C // 8)] = -np.inf
        x[rng.integers(0, C, C // 8)] = np.float32(1e-40)
        x[rng.integers(0, C, C // 8)] = np.float32(-1e-40)
        x[rng.integers(0, C, 3)] = np.finfo(np.float32).max
        n_live = C - 7
    elif kind == "zeros":  # the threshold among +0.0 and -0.0
        x = np.where(x > 1.6, x, np.where(x > 0, 0.0, -0.0)).astype(np.float32)
        x[-200:] = -1.0
    else:
        assert kind == "plain"
    return x, n_live


KINDS = ("plain", "ties", "short", "exact", "odd", "zeros")


@pytest.mark.parametrize("rows, C, K, PS, kinds", [
    (1, 4096, 2048, 64, ("ties",)),
    (1, 36864, 2048, 64, ("short",)),
    (1, 4096, 2048, 64, ("zeros",)),
    (4, 4096, 2048, 64, ("plain", "ties", "short", "exact")),
    (4, 4096, 2048, 64, ("odd", "zeros", "ties", "short")),
    (4, 36864, 2048, 64, ("ties", "short", "exact", "odd")),
    (32, 4096, 2048, 64, KINDS),
    (32, 36864, 2048, 64, KINDS),
    (2, 4096, 4096, 16, ("plain", "short")),  # K = C: nothing moves
    (1, 65536, 2048, 64, ("ties",)),  # past 16 bits of positions: two prefix counts
    (3, 40, 8, 4, ("ties", "short", "plain")),  # a test model's: C under a lane tile
    (5, 1000, 100, 8, KINDS),  # neither C nor K nor C / 32 a multiple of 128
], ids=lambda v: "-".join(v) if isinstance(v, tuple) else str(v))
def test_the_select_kernel_picks_the_sorts_set(rows, C, K, PS, kinds):
    rng = np.random.default_rng(rows * C + K)
    drawn = [_row(kinds[b % len(kinds)], rng, C, K) for b in range(rows)]
    n_live = np.asarray([n for _, n in drawn], np.int32)
    live = np.arange(C)[None, :] < n_live[:, None]
    scores = np.where(live, np.stack([x for x, _ in drawn]), -np.inf).astype(np.float32)
    MP = C // PS
    pt = np.stack([rng.permutation(4 * MP)[:MP] for _ in range(rows)]).astype(np.int32)

    cells, words = dsa_select(jnp.asarray(scores), jnp.asarray(pt),
                              jnp.asarray(n_live), k=K, interpret=True)
    cells, words = np.asarray(cells), np.asarray(words)
    assert cells.shape == (rows, K) and words.shape == (rows, mla.chosen_words(C))

    idx, mask = mla.select_topk(jnp.asarray(scores), K, with_mask=True)
    idx, mask = np.sort(np.asarray(idx), -1), np.array(mask)
    by_numpy = np.sort(np.argsort(-scores, axis=-1, kind="stable")[:, :K], -1)
    for b in range(rows):
        kind = kinds[b % len(kinds)]
        # numpy holds -0.0 equal to +0.0; the sort and the kernel put it under
        want = idx[b] if kind == "zeros" else by_numpy[b]
        assert kind == "zeros" or (idx[b] == by_numpy[b]).all()
        assert (cells[b] == pt[b, want // PS] * PS + want % PS).all(), (b, kinds)
        n_sel = min(int(n_live[b]), K)  # the live ones first, and all of them
        assert (want[:n_sel] < n_live[b]).all() and (want[n_sel:] >= n_live[b]).all()
        # `with_mask` rebuilds the set by comparing floats, which flushes a
        # denormal threshold and ties the two zeros: there the sort's own set
        as_sorted = np.zeros(C, bool)
        as_sorted[want] = True
        assert kind in ("odd", "zeros") or (mask[b] == as_sorted).all()
        mask[b] = as_sorted
    assert (words == np.asarray(mla.pack_chosen(jnp.asarray(mask & live)))).all()
