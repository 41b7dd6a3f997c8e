"""Grouped matrix product: rows sorted by group, each group's rows times that
group's matrix. What a sparsely computed expert layer is made of
(`models/mixtral.py:_moe_sparse`).

`grouped_matmul(x [m, k], w [groups, k, n], sizes [groups])` multiplies rows
`sum(sizes[:g]) .. sum(sizes[:g + 1])` of `x` by `w[g]`. A group without rows
costs nothing: its matrix is not read.

Where kernels run (`ops/dispatch.py`) it is the Pallas grouped matmul that
ships with JAX (`jax.experimental.pallas.ops.tpu.megablox.gmm`), with tiles
that take a [k, n] matrix of an expert in as few steps as fit: at a few rows
an expert the work is streaming each expert's weights once, and small tiles
leave the DMA engine waiting on the grid.

**The tile rule** (`weight_tile`): the weight tile is `[min(k, TILE_MAX),
min(n, TILE_MAX)]`, then halved along k (rows stay whole and contiguous),
and along n once k is down to `TILE_ROWS`, until it is at most `TILE_BYTES`: the kernel
holds two of them (double-buffered) beside its row, output and accumulator
tiles, inside Mosaic's default 16 MiB of scoped VMEM on a v5e. Measured at
two shapes: 128 narrow experts of 2048 x 768 (SDAR-30B-A3B; tile `[2048,
768]`, 3 MiB, what the rule leaves as it was: 0.63 ms a product at 8 rows an
expert on a v5e, 78% of the HBM roofline, against 1.9 ms for
`jax.lax.ragged_dot`, the reference path here; my chip run, PR 36), and 16
wide experts of 6144 x 2048 (K-EXAONE-236B-A23B's share; `[2048, 2048]`
would be 8 MiB a buffer, the whole scoped VMEM for two, so the rule gives
`[1024, 2048]`, 4 MiB; PERF.md section 6, PR 46, has the chip's reading).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import dispatch

TILE_ROWS = 128  # rows a tile; sorted rows are padded to a multiple of it
TILE_MAX = 2048  # widest k or n tile: [2048, 768] bf16 is 3 MiB a buffer
TILE_BYTES = 4 << 20  # most bytes of one weight tile; the kernel holds two


def weight_tile(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn) of the weight tile for a [k, n] matrix an expert."""
    tk, tn = min(k, TILE_MAX), min(n, TILE_MAX)
    while tk * tn * itemsize > TILE_BYTES:
        if tk > TILE_ROWS:
            tk //= 2
        else:
            tn //= 2
    return tk, tn


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_pallas(x, w, sizes, *, interpret=False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = x.shape
    n = w.shape[-1]
    # In HLO and in a device trace the kernel bears the library function's
    # name: `gmm.N`. bf16 products are exact in the kernel's f32 accumulator
    # in one pass, and Mosaic takes no other precision for bf16 operands
    # (the tests' process-wide "float32" would reach the kernel's dot).
    exact = "default" if x.dtype == jnp.bfloat16 else "highest"
    with jax.default_matmul_precision(exact):
        out = gmm(
            jnp.pad(x, ((0, -m % TILE_ROWS), (0, 0))), w, sizes,
            preferred_element_type=x.dtype,
            tiling=(TILE_ROWS, *weight_tile(k, n, w.dtype.itemsize)),
            interpret=interpret,
        )
    return out[:m]


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray) -> jnp.ndarray:
    mode = dispatch.kernel_mode()
    if mode == "reference":
        return jax.lax.ragged_dot(x, w, sizes)
    # A Mosaic kernel cannot be partitioned by GSPMD: it runs whole on every
    # device (the engine refuses a sparse family a tp axis).
    call = dispatch.on_every_device(
        functools.partial(_grouped_pallas, interpret=mode == "interpret"),
        n_in=3, n_out=1,
    )
    return call(x, w, sizes)
