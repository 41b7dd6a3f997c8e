"""Grouped matrix product: rows sorted by group, each group's rows times that
group's matrix. What a sparsely computed expert layer is made of
(`models/mixtral.py:_moe_sparse`).

`grouped_matmul(x [m, k], w [groups, k, n], sizes [groups])` multiplies rows
`sum(sizes[:g]) .. sum(sizes[:g + 1])` of `x` by `w[g]`. A group without rows
costs nothing: its matrix is not read.

Where kernels run (`ops/dispatch.py`) it is the Pallas grouped matmul that
ships with JAX (`jax.experimental.pallas.ops.tpu.megablox.gmm`), with tiles
that take a whole [k, n] matrix of an expert in one or two steps: at 8 rows an
expert the work is streaming each expert's weights once, and small tiles
leave the DMA engine waiting on the grid (0.63 ms a product of 128 experts of
2048 x 768 on a v5e, 78% of the HBM roofline, against 1.9 ms for
`jax.lax.ragged_dot`, the reference path here; my chip run, PR 36).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import dispatch

TILE_ROWS = 128  # rows a tile; sorted rows are padded to a multiple of it
TILE_MAX = 2048  # widest k or n tile: [2048, 768] bf16 is 3 MiB a buffer


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
            tiling=(TILE_ROWS, min(k, TILE_MAX), min(n, TILE_MAX)),
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
