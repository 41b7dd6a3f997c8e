"""Logical-axis sharding rules (GSPMD) for model parameters and activations.

The reference has no sharding code at all — tensor parallelism is an opaque
`--tensor-parallel-size` engine arg (reference: charts/models/values.yaml:128,
SURVEY.md §2 "Parallelism accounting"). Here it is explicit: every parameter
and activation carries *logical* axis names, and a `ShardingRules` table maps
them to physical mesh axes. Megatron-style TP for transformers:

  - attn qkv / mlp up+gate: column-parallel (shard output feature dim on tp)
  - attn out / mlp down:    row-parallel    (shard input feature dim on tp)
  - embeddings:             shard vocab on tp
  - activations:            batch on dp, optionally sequence on sp

XLA inserts the psum/all-gather collectives over ICI; we never write NCCL-
style comms by hand.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeai_tpu.parallel.mesh import (
    AXIS_DATA,
    AXIS_PIPELINE,
    AXIS_SEQ,
    AXIS_TENSOR,
)

# Logical axis names used across models.
BATCH = "batch"
LAYERS = "layers"  # stacked-layer axis (pipeline stages shard it)
SEQUENCE = "sequence"
VOCAB = "vocab"
EMBED = "embed"
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
MLP = "mlp"
EXPERT = "expert"
KV_SLOTS = "kv_slots"  # KV-cache slot (request) axis
LORA_RANK = "lora_rank"


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Map logical axis name -> physical mesh axis (or None = replicate)."""

    rules: tuple[tuple[str, str | None], ...] = (
        (LAYERS, AXIS_PIPELINE),  # pp=1 meshes: axis size 1 → replicated
        (BATCH, AXIS_DATA),
        (SEQUENCE, AXIS_SEQ),
        (VOCAB, AXIS_TENSOR),
        (EMBED, None),
        (HEADS, AXIS_TENSOR),
        (KV_HEADS, AXIS_TENSOR),
        (HEAD_DIM, None),
        (MLP, AXIS_TENSOR),
        (EXPERT, AXIS_TENSOR),  # MoE experts reuse the tp axis (see mesh.py)
        (KV_SLOTS, AXIS_DATA),
        (LORA_RANK, None),
    )

    def physical(self, logical_axis: str | None) -> str | None:
        if logical_axis is None:
            return None
        for name, phys in self.rules:
            if name == logical_axis:
                return phys
        raise KeyError(f"no sharding rule for logical axis {logical_axis!r}")

    def spec(self, logical_axes: tuple[str | None, ...]) -> P:
        return P(*(self.physical(a) for a in logical_axes))


DEFAULT_RULES = ShardingRules()


def kv_heads_axis(
    mesh_shape, num_kv_heads: int, rules: ShardingRules = DEFAULT_RULES
) -> str | None:
    """The mesh axis a KV cache of `num_kv_heads` heads is split over, or
    None where it is replicated: GQA with a head count the axis does not
    divide (each shard then attends its local q heads against the full
    KV). The engine places its pools by this rule (`kv_cache_rules`) and
    the attention kernels' shard_map splits by it (ops/dispatch.py), so
    the two cannot disagree."""
    axis = rules.physical(KV_HEADS)
    if axis is None or num_kv_heads % mesh_shape.get(axis, 1):
        return None
    return axis


def kv_cache_rules(
    mesh: Mesh, num_kv_heads: int, rules: ShardingRules = DEFAULT_RULES
) -> ShardingRules:
    """`rules`, with KV heads replicated where `kv_heads_axis` says so."""
    if kv_heads_axis(mesh.shape, num_kv_heads, rules) is not None:
        return rules
    return ShardingRules(
        rules=tuple(
            (name, None if name == KV_HEADS else phys)
            for name, phys in rules.rules
        )
    )


def logical_to_physical(
    logical_axes: tuple[str | None, ...],
    rules: ShardingRules = DEFAULT_RULES,
) -> P:
    return rules.spec(logical_axes)


def named_sharding(
    mesh: Mesh,
    logical_axes: tuple[str | None, ...],
    rules: ShardingRules = DEFAULT_RULES,
) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(logical_axes))


def shard_params(
    params: Any,
    logical_specs: Any,
    mesh: Mesh,
    rules: ShardingRules = DEFAULT_RULES,
) -> Any:
    """Device-put a param pytree according to a matching pytree of logical
    axis tuples. Works for host → sharded-device transfer (weight loading)."""

    def _put(x, axes):
        return jax.device_put(x, named_sharding(mesh, axes, rules))

    return jax.tree.map(_put, params, logical_specs)


def param_shardings(
    logical_specs: Any, mesh: Mesh, rules: ShardingRules = DEFAULT_RULES
) -> Any:
    """Pytree of NamedShardings (for jit in_shardings/out_shardings)."""
    return jax.tree.map(
        lambda axes: named_sharding(mesh, axes, rules),
        logical_specs,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )
