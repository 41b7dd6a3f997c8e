"""`ops/grouped_matmul.py:tile_plan`, the map from grid step to (group, row
tile) that a layer's grouped products walk, against what it replaces on the
kernel path: the installed megablox's `make_group_metadata` on the same
groups; and `weight_tile`, the rule that cuts an expert's matrix into the
kernel's weight tiles, at the widths of the configurations on file. CPU only,
nothing of the kernel runs."""

import importlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.ops.grouped_matmul import (
    TILE_MAX, TILE_ROWS, VMEM_BYTES, gmm, tile_bytes, tile_plan, weight_tile)


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


# ---- the weight tile ----------------------------------------------------------

CONFIGS = pathlib.Path(__file__).parents[2] / "perf" / "configs"
SPARSE = ["sdar-30b-a3b-v5e1", "qwen3-next-80b-a3b-v5e1",
          "k-exaone-236b-a23b-v5e1", "kimi-linear-48b-a3b-v5e1"]
# The tiles of the configurations whose widths the rule before PR 52 divided
# already (Kimi-Linear's 2304 it did not): with these tiles their compiled
# programs are what they were.
PINNED = {
    "sdar-30b-a3b-v5e1": {"up": (2048, 768), "down": (768, 2048)},
    "qwen3-next-80b-a3b-v5e1": {"up": (2048, 512), "down": (512, 2048)},
    "k-exaone-236b-a23b-v5e1": {"up": (1024, 2048), "down": (1024, 2048)},
}


@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("config", SPARSE)
def test_the_weight_tile_at_the_widths_on_file(config, product):
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    hidden, mid = cfg["hidden_size"], cfg["moe_intermediate_size"]
    k, n = (hidden, mid) if product == "up" else (mid, hidden)
    tk, tn = weight_tile(k, n, 2)
    assert tile_bytes(tk, tn, 2) <= VMEM_BYTES
    if config in PINNED:
        assert (tk, tn) == PINNED[config][product]
    # No remainder tile: its product costs a whole tile's and, along k, the
    # kernel's mask of both operands at every step.
    assert k % tk == 0 and n % tn == 0, (k, n, tk, tn)


@pytest.mark.parametrize("itemsize", [2, 4])
def test_the_weight_tile_is_whole_strips_within_the_bytes_and_even_where_it_can_be(
        itemsize):
    widths = range(TILE_ROWS, 8192 + 1, TILE_ROWS)
    for k in widths:
        for n in widths:
            tk, tn = weight_tile(k, n, itemsize)
            assert tile_bytes(tk, tn, itemsize) <= VMEM_BYTES, (k, n)
            assert tn <= TILE_MAX, (k, n)
            for d, t in ((k, tk), (n, tn)):
                assert t % TILE_ROWS == 0 and 0 < t <= d, (k, n)
                # As many tiles as it takes, and equal wherever that many
                # equal tiles are whole strips.
                if (d / -(-d // t)) % TILE_ROWS == 0:
                    assert d % t == 0, (k, n, tk, tn)


def pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from pallas_calls(inner)


@pytest.mark.parametrize("k, n, tile, selects", [
    (2304, 1024, (2304, 1024), 1),  # gate, up: the whole matrix, one k step
    (1024, 2304, (1024, 1152), 1),  # down: two even passes over n
    (2304, 2048, (1152, 2048), 1),  # too large whole: two even k steps, no mask
    (2100, 2048, (1152, 2048), 3),  # a k of no whole strips is still masked
], ids=["kimi-up", "kimi-down", "even-k-steps", "k-remainder"])
def test_the_traced_kernel_walks_the_tile_and_masks_only_a_remainder(
        k, n, tile, selects):
    """The kernel as traced at Kimi-Linear's shapes and beside them (abstract
    operands, nothing lowered for a device): its block shapes are the tile,
    its grid the even count, and the one `select` left is the store's row
    mask; `masked` adds one an operand only where k leaves a remainder."""
    X, rows = 32, 1024
    steps = rows // TILE_ROWS + X - 1

    def abstract(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    traced = gmm.trace(
        abstract((rows, k), jnp.bfloat16), abstract((X, k, n), jnp.bfloat16),
        abstract((X + 1,)), abstract((steps,)), abstract((steps,)),
        abstract(()), abstract(()))
    (call,) = pallas_calls(traced.jaxpr.jaxpr)
    grid = call.params["grid_mapping"]
    blocks = [
        tuple(getattr(b, "block_size", None) for b in m.block_shape)
        for m in grid.block_mappings]
    tk, tn = tile
    assert blocks == [(TILE_ROWS, tk), (None, tk, tn), (TILE_ROWS, tn)]
    assert (grid.grid[0], grid.grid[2]) == (-(-n // tn), -(-k // tk))
    assert str(call.params["jaxpr"]).count("select_n") == selects
