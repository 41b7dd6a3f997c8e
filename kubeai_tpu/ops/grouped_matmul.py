"""Grouped matrix product: rows sorted by group, each group's rows times that
group's matrix. What a sparsely computed expert layer is made of
(`ops/experts.py:moe_sparse`).

`grouped_matmul(x [m, k], w [groups, k, n], sizes [groups])` multiplies rows
`sum(sizes[:g]) .. sum(sizes[:g + 1])` of `x` by `w[g]`. A group without rows
costs nothing: its matrix is not read.

Where kernels run (`ops/dispatch.py`) it is a Pallas kernel, `gmm`: the
grid, index maps, accumulator and store mask of the grouped matmul that
ships with JAX (`jax.experimental.pallas.ops.tpu.megablox`), with tiles that
take a [k, n] matrix of an expert in as few steps as fit: at a few rows an
expert the work is streaming each expert's weights once, and small tiles
leave the DMA engine waiting on the grid.

**Who builds the tile map, and why the stack is NL x X groups wide on the
weight side only.** The kernel walks a map from grid step to (group, row
tile). `moe_sparse` builds it ONCE a layer from the layer's own X counts
(`tile_plan`: seven small dense fusions) and hands it to the layer's three
products with the layer's number. The weights stay the whole stack,
`[NL * X, k, n]`, read in place: the kernel's right-hand index map adds
`layer * X` to the map's group, so no layer's experts are sliced out (a
kernel's sliced operand is copied, 1.2 GB a layer at SDAR's sizes). Until
PR 47 the stack was NL x X groups wide on the row side too: the library's
`gmm` built its own metadata over all NL * X sizes, of which X held rows,
with two scatter-adds and a scatter of a thousand updates and a `while`
loop, some sixty instructions a routed layer (PERF.md section 6, PR 47).

**The tile rule** (`weight_tile`): n is cut into EQUAL tiles of whole 128
strips no wider than `TILE_MAX` (the output's and the accumulator's width),
k into as few equal tiles as keep what the kernel holds (`tile_bytes`: two
buffers each of the row, weight and output tiles and a float32 accumulator)
within `VMEM_BYTES`, 12 of Mosaic's default 16 MiB of scoped VMEM on a v5e;
the whole of k is one tile where that fits, and n takes more tiles only once
k is down to one strip. A count that would leave a shorter last tile gives
way to the next that divides the strips, if one under twice it does; where
none does (17 strips in two tiles, a k that is no multiple of 128 in more
than one) the last tile is shorter, and along k the kernel then masks both
operands at every step. Measured at three shapes: 128 narrow experts of
2048 x 768 (SDAR-30B-A3B; one tile `[2048, 768]`, 3 MiB: 0.63 ms a product at
8 rows an expert on a v5e, 78% of the HBM roofline, against 1.9 ms for
`jax.lax.ragged_dot`, the reference path here; my chip run, PR 36), 16 wide
experts of 6144 x 2048 (K-EXAONE-236B-A23B's share; five tiles `[1280,
2048]` would hold 12.7 MiB, so six of `[1024, 2048]`, 4 MiB a buffer; PERF.md
section 6, PR 46, has the chip's reading), and 32 experts of 2304 x 1024
(Kimi-Linear-48B-A3B's share), the one width on file that 2,048 does not
divide: until PR 52 a tile was at most 2,048 each way and 4 MiB, which gave
it `[2048, 1024]` and a remainder of 256 rows that cost a whole tile's
product, masked, behind a copy an eighth the size. The chip's three
readings there (my chip runs, PR 52; the kernel alone at 128 held rows over
29 of 32 experts, us a touched expert, gate/up and down): that rule 10.6 and
9.3 (55% and 62% of the time the bytes take); two equal tiles, `[1152, 1024]`
and `[1024, 1152]`, 7.7 and 7.4; the whole matrix as one tile, 7.0 and 7.2.
Alone, one k step reads 5-9% under two (15% where experts are visited for
several row tiles: the weight block is then fetched once) and two n passes
read like one: so k whole, n in halves. Inside the decode chunk of
`kimi-linear-48b-a3b.gen-sat` one k step and two read the same: the products
went from 8.88 to 6.3 ms a step under either (PERF.md section 6, PR 52).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeai_tpu.ops import dispatch

TILE_ROWS = 128  # rows a tile; sorted rows are padded to a multiple of it
TILE_MAX = 2048  # widest n tile: the output's and the accumulator's width
# Of Mosaic's default 16 MiB of scoped VMEM on a v5e, what the kernel's own
# tiles may take; the rest is the compiler's (a product before it is added).
VMEM_BYTES = 12 << 20


def tile_bytes(tk: int, tn: int, itemsize: int) -> int:
    """What the kernel holds in VMEM at a weight tile [tk, tn]: two buffers
    each of the row, weight and output tiles, and a float32 accumulator."""
    return (2 * itemsize * (TILE_ROWS * tk + tk * tn + TILE_ROWS * tn)
            + 4 * TILE_ROWS * tn)


def weight_tile(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn) of the weight tile for a [k, n] matrix an expert: n in equal
    tiles of whole strips of TILE_ROWS within TILE_MAX, k in as few equal
    tiles as keep `tile_bytes` within VMEM_BYTES (one, the whole of k, where
    that fits); more tiles along n only once k is down to a strip. Where that
    count leaves a shorter last tile and a count under twice it divides the
    dimension's strips, that count is taken."""

    def even(d: int, count: int) -> int:
        tile = -(-d // count)
        return tile if count == 1 else -(-tile // TILE_ROWS) * TILE_ROWS

    def no_remainder(d: int, count: int) -> int:
        for c in range(count, 2 * count):
            if d % (c * TILE_ROWS) == 0:
                return c
        return count

    tiles_k, tiles_n = 1, -(-n // TILE_MAX)
    while True:
        tk, tn = even(k, tiles_k), even(n, tiles_n)
        if tile_bytes(tk, tn, itemsize) <= VMEM_BYTES:
            return (even(k, no_remainder(k, tiles_k)),
                    even(n, no_remainder(n, tiles_n)))
        if tk > TILE_ROWS:
            tiles_k += 1
        else:
            tiles_n += 1


class TilePlan(NamedTuple):
    """What the kernel's grid walks, for one set of groups over `m` sorted
    rows (megablox's `GroupMetadata` and its `num_tiles`)."""

    group_offsets: jnp.ndarray  # [X + 1]: group g holds rows offsets[g] .. offsets[g + 1]
    group_ids: jnp.ndarray  # [tiles_m + X - 1]: the group of grid step v
    m_tile_ids: jnp.ndarray  # [tiles_m + X - 1]: the row tile of grid step v
    num_tiles: jnp.ndarray  # []: grid steps that do any work


def tile_plan(counts: jnp.ndarray, rows: int) -> TilePlan:
    """The map from grid step to (group, row tile) for `counts` [X] rows a
    group over `rows` sorted rows (padded up to whole tiles of TILE_ROWS).
    A group without rows is visited by no step; a row tile that two groups
    share is visited once a group, in group order; rows behind the last
    group are visited by none. Entries from `num_tiles` on are padding.

    Four comparisons against an iota, each summed over its groups, and
    nothing else: running sums as `[X, X]` triangles, a visit's group as the
    number of groups whose visits have all passed, its row tile as the
    visit's number less the tiles revisited by then (a group that starts
    inside a tile visits again the tile the group before it ended in). A
    TPU runs the whole as seven small fusions. megablox's
    `make_group_metadata` gives the same arrays through `cumsum`, `repeat`,
    `histogram`, `take` and `roll`: nine `reduce-window`s, two scatter-adds,
    a scatter and a `while` loop, each of the latter walking its groups one
    by one."""
    (X,) = counts.shape
    tiles_m = -(-rows // TILE_ROWS)

    def grid(n):  # ([n, X] of i, [n, X] of g)
        return (jax.lax.broadcasted_iota(jnp.int32, (n, X), 0),
                jax.lax.broadcasted_iota(jnp.int32, (n, X), 1))

    i, g = grid(X + 1)
    offsets = jnp.where(g < i, counts, 0).sum(1)
    starts, ends = offsets[:-1], offsets[1:]
    tiles = jnp.where(
        counts == 0, 0, (ends + TILE_ROWS - 1) // TILE_ROWS - starts // TILE_ROWS)
    i, g = grid(X)
    tile_ends = jnp.where(g <= i, tiles, 0).sum(1)
    revisits = (counts > 0) & (starts % TILE_ROWS != 0)
    visit, g = grid(tiles_m + X - 1)
    # Of groups 0 .. X - 2, those whose visits have all passed: at most
    # X - 1, so the padding names the last group.
    passed = ((tile_ends <= visit) & (g < X - 1)).sum(1, dtype=jnp.int32)
    revisited = (revisits & (tile_ends <= visit + tiles)).sum(1, dtype=jnp.int32)
    return TilePlan(
        offsets, passed,
        jnp.minimum(visit[:, 0] - revisited, tiles_m - 1),
        tile_ends[-1],
    )


# The kernel below follows `gmm` of jax/experimental/pallas/ops/tpu/megablox/
# gmm.py (Copyright 2024 The JAX Authors, Apache License 2.0): its grid, index
# maps, accumulator and store mask, without the paths this repo never takes
# (an existing output, a transposed right-hand side, a shard of the groups).
# What is new is where the tile map comes from (`tile_plan`, handed in) and
# `layer`: the map speaks of one layer's X groups, the right-hand index map
# adds `layer * X`, and the stack of every layer's groups is read in place.
# In HLO and in a device trace the kernel bears this function's name, as the
# library's did: `gmm.N`.
@functools.partial(jax.jit, static_argnames=("interpret",))
def gmm(x, w, group_offsets, group_ids, m_tile_ids, num_tiles, layer, *, interpret=False):
    m, k = x.shape
    n = w.shape[-1]
    X = group_offsets.shape[0] - 1
    tm = TILE_ROWS
    tk, tn = weight_tile(k, n, w.dtype.itemsize)
    tiles_k, k_rem = -(-k // tk), k % tk
    tiles_n = -(-n // tn)
    x = jnp.pad(x, ((0, -m % tm), (0, 0)))
    rows = x.shape[0]

    def kernel(offsets, groups, m_tiles, layer, x_ref, w_ref, out_ref, acc):
        del layer
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        def masked(tile, dim):
            # The last k tile of a k that is no multiple of tk reads past it.
            if not k_rem:
                return tile
            inside = jax.lax.broadcasted_iota(jnp.int32, tile.shape, dim) < k_rem
            keep = (k_i < tiles_k - 1) | inside
            return jnp.where(keep, tile.astype(jnp.float32), 0).astype(tile.dtype)

        acc[...] += jax.lax.dot_general(
            masked(x_ref[...], 1), masked(w_ref[...], 0),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(k_i == tiles_k - 1)
        def _store():
            # Only the rows of this step's group: a row tile that two groups
            # share keeps what the group before wrote.
            group = groups[step]
            row = m_tiles[step] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, tn), 0)
            mine = (row >= offsets[group]) & (row < offsets[group + 1])
            out_ref[...] = jax.lax.select(
                mine, acc[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def x_index(n_i, step, k_i, offsets, groups, m_tiles, layer):
        return m_tiles[step], k_i

    def w_index(n_i, step, k_i, offsets, groups, m_tiles, layer):
        return layer[0] * X + groups[step], k_i, n_i

    def out_index(n_i, step, k_i, offsets, groups, m_tiles, layer):
        return m_tiles[step], n_i

    # bf16 products are exact in the kernel's f32 accumulator in one pass,
    # and Mosaic takes no other precision for bf16 operands (the tests'
    # process-wide "float32" would reach the kernel's dot).
    exact = "default" if x.dtype == jnp.bfloat16 else "highest"
    with jax.default_matmul_precision(exact):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                in_specs=[
                    pl.BlockSpec((tm, tk), x_index),
                    pl.BlockSpec((None, tk, tn), w_index),
                ],
                out_specs=pl.BlockSpec((tm, tn), out_index),
                grid=(tiles_n, num_tiles, tiles_k),
                scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=2 * rows * k * n, transcendentals=0,
                bytes_accessed=(
                    x.size * x.dtype.itemsize * tiles_n
                    + k * n * w.dtype.itemsize * group_ids.shape[0]
                    + rows * n * x.dtype.itemsize)),
            interpret=interpret,
        )(group_offsets, group_ids, m_tile_ids, jnp.reshape(layer, (1,)), x, w)
    return out[:m]


def grouped_matmul(
    x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray, *,
    layer=0, plan: TilePlan | None = None,
) -> jnp.ndarray:
    """`sizes` [X] rows a group; `w` holds X groups, or a stack of them
    (`[NL * X, k, n]`) of which `sizes` speaks of those of `layer`. `plan`:
    `tile_plan(sizes, rows of x)`, from a caller that has several products
    over the same rows."""
    X = sizes.shape[0]
    mode = dispatch.kernel_mode()
    if mode == "reference":
        if w.shape[0] != X:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((w.shape[0],), sizes.dtype), sizes, (layer * X,))
        return jax.lax.ragged_dot(x, w, sizes)
    if plan is None:
        plan = tile_plan(sizes, x.shape[0])
    # A Mosaic kernel cannot be partitioned by GSPMD: it runs whole on every
    # device (the engine refuses a sparse family a tp axis).
    call = dispatch.on_every_device(
        functools.partial(gmm, interpret=mode == "interpret"), n_in=7, n_out=1,
    )
    return call(x, w, *plan, jnp.asarray(layer, jnp.int32))
