"""`ops/grouped_matmul.py:tile_plan`, the map from grid step to (group, row
tile) that a layer's grouped products walk, against what it replaces on the
kernel path: the installed megablox's `make_group_metadata` on the same
groups. CPU only, nothing of the kernel runs."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.ops.grouped_matmul import TILE_ROWS, tile_plan


def spread(total: int, X: int, seed: int) -> np.ndarray:
    """`total` rows over X groups, unevenly, some groups empty."""
    rng = np.random.default_rng(seed)
    weights = rng.dirichlet(np.full(X, 0.3))
    return np.bincount(rng.choice(X, total, p=weights), minlength=X)


def one_at(X: int, where: dict) -> np.ndarray:
    counts = np.zeros(X, np.int64)
    for g, c in where.items():
        counts[g] = c
    return counts


# name: (counts, rows of x). `rows` is what the products are called with: no
# multiple of the tile where the case says so, more than the groups hold
# where a share leaves rows behind its last group.
CASES = {
    "empty-head": (one_at(16, {5: 100, 6: 28, 9: 300, 15: 84}), 512),
    "empty-middle": (one_at(16, {0: 130, 1: 126, 14: 1, 15: 255}), 512),
    "empty-tail": (one_at(16, {0: 7, 1: 505}), 512),
    "ends-on-a-tile": (one_at(16, {2: 128, 3: 256, 4: 1, 7: 127, 8: 128}), 640),
    "all-in-one": (one_at(64, {37: 640}), 640),
    "one-a-group": (np.ones(128, np.int64), 128),
    "one-a-group-of-few-rows": (np.ones(16, np.int64), 16),
    "a-share-rows-behind": (spread(150, 16, 1), 512),
    "a-share-nothing-held": (np.zeros(16, np.int64), 640),
    "a-share-one-row-held": (one_at(64, {63: 1}), 640),
    "m512-x16": (spread(512, 16, 2), 512),
    "m640-x64": (spread(640, 64, 3), 640),
    "m640-x64-share": (spread(90, 64, 4), 640),
    "m1024-x128": (spread(1024, 128, 5), 1024),
    "m1024-x128-even": (np.full(128, 8, np.int64), 1024),
    "m20480-x64": (spread(20480, 64, 6), 20480),
    "m20480-x64-share": (spread(2600, 64, 7), 20480),
    "m20480-x128": (spread(20480, 128, 8), 20480),
    "rows-no-multiple-of-a-tile": (spread(74, 16, 9), 74),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_plan_is_the_library_metadata_of_the_layers_groups(name):
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    counts, rows = CASES[name]
    X = counts.shape[0]
    assert counts.sum() <= rows
    sizes = jnp.asarray(counts, jnp.int32)
    padded = rows + -rows % TILE_ROWS
    (offsets, groups, m_tiles), num = megablox.make_group_metadata(
        group_sizes=sizes, m=padded, tm=TILE_ROWS,
        start_group=jnp.int32(0), num_nonzero_groups=X,
        visit_empty_groups=False)
    plan = jax.jit(tile_plan, static_argnums=1)(sizes, rows)
    n = int(num)
    assert int(plan.num_tiles) == n
    assert n <= plan.group_ids.shape[0] == groups.shape[0] == padded // TILE_ROWS + X - 1
    np.testing.assert_array_equal(plan.group_offsets, offsets)
    np.testing.assert_array_equal(plan.group_ids[:n], groups[:n])
    np.testing.assert_array_equal(plan.m_tile_ids[:n], m_tiles[:n])
    # The padding behind `num_tiles` is never walked, and indexes nothing
    # that is not there all the same.
    assert plan.group_ids.dtype == plan.m_tile_ids.dtype == jnp.int32
    assert 0 <= int(plan.group_ids.min()) and int(plan.group_ids.max()) < X
    assert 0 <= int(plan.m_tile_ids.min())
    assert int(plan.m_tile_ids.max()) < padded // TILE_ROWS
    # No empty group is visited, and every row of a group lies in a tile
    # that one of its visits names.
    visited = np.asarray(plan.group_ids[:n])
    assert (counts[visited] > 0).all()
    assert set(visited) == set(np.flatnonzero(counts))


@pytest.mark.parametrize("X, rows", [(64, 640), (128, 1024), (64, 20480)])
def test_the_plan_lowers_to_dense_operations_only(X, rows):
    """What made the library's metadata slow on a TPU: a scatter walks its
    updates one by one, a `while` pays a launch every turn."""
    text = jax.jit(tile_plan, static_argnums=1).lower(
        jax.ShapeDtypeStruct((X,), jnp.int32), rows).as_text()
    for op in ("sort", "scatter", "gather", "while"):
        assert op not in text, op
    assert f"{rows // TILE_ROWS + X - 1}x{X}" in text  # the one comparison
