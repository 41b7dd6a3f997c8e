"""One rule for "kernel or reference", and the mesh wrapper kernels need.

Every attention dispatch (paged decode, paged verify, fused decode, flash
prefill) asks `kernel_mode()`:

  "compiled"  — the default backend is a TPU: the Pallas kernel is called,
                and a shape it cannot take RAISES. Nothing on a TPU hands a
                bf16 pool or an aligned S >= 256 prefill to a `jnp`
                reference.
  "interpret" — tests set `FORCE_INTERPRET` to drive the same kernel code
                (and the same `shard_map` wrapper) through the Pallas
                interpreter on the CPU.
  "reference" — any other backend: the `jnp` reference.

Three cases keep the `jnp` path on every backend, by design: an int8
{"q8", "scale"} pool (the kernels read bf16 pages; ROADMAP A4), a prefill
whose padded length is under 256 or not a multiple of 128 (the short
buckets), and a Gemma-2 prefill, whose logit softcap the flash kernel does
not carry (its window rides the layer scan as a traced value beside the
softcap, so it stays with it). A sliding window alone is no such case: the
flash kernel takes a static `window` and skips the blocks wholly outside
it (`ops/pallas_attention.py`), which is how a family that mixes window
and global layers prefills both kinds.

`kernel_mode()` is the only way to choose: no dispatch and no kernel takes
a mode or an `interpret` argument.

Mosaic kernels cannot be partitioned by GSPMD, so under a mesh each
`pallas_call` runs inside `jax.shard_map`, manual over every mesh axis.
The mesh is the context mesh (`jax.set_mesh`), which the engine enters
around each of its jitted calls (`Engine.jit`, the only `jax.jit` in the
engine); KV heads split by the rule the engine places its pools by
(`parallel/sharding.py:kv_heads_axis`).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from kubeai_tpu.parallel.sharding import kv_heads_axis

# Tests flip this to run the kernel path, interpreted, off-TPU.
FORCE_INTERPRET = False


def kernel_mode() -> str:
    if jax.default_backend() == "tpu":
        return "compiled"
    return "interpret" if FORCE_INTERPRET else "reference"


def over_kv_heads(fn, num_kv_heads: int, head_dims: tuple):
    """`fn` (one pallas_call) as it must run under the context mesh: inside
    a shard_map that is manual over every mesh axis, with argument i split
    over the KV-heads axis along its `head_dims[i]`-th dimension (None:
    replicated) and the result split like argument 0.

    Where the engine replicates its cache (`kv_heads_axis` is None: GQA
    with fewer KV heads than tp shards) the call runs replicated too. With
    no context mesh (a bare single-device call), or inside a region that
    is already manual over every axis, `fn` is returned as is.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or set(mesh.manual_axes) == set(mesh.axis_names):
        return fn
    axis = kv_heads_axis(mesh.shape, num_kv_heads)
    specs = tuple(
        P() if d is None else P(*[None] * d, axis) for d in head_dims
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=specs, out_specs=specs[0], check_vma=False
    )


def on_every_device(fn, n_in: int, n_out: int):
    """`fn` (one pallas_call over whole arrays) as it must run under the
    context mesh where nothing of it is split: inside a shard_map that is
    manual over every mesh axis, every argument and result replicated. With
    no context mesh, or inside a region that is already manual over every
    axis, `fn` is returned as is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or set(mesh.manual_axes) == set(mesh.axis_names):
        return fn
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * n_in,
        out_specs=(P(),) * n_out if n_out > 1 else P(), check_vma=False,
    )
