"""Device-mesh construction from TPU slice topologies.

The reference exposes TPU topology only as GKE nodeSelectors on resource
profiles (reference: charts/kubeai/values-gke.yaml:18-41,
`google-tpu-v5e-1x1|2x2|2x4` with `gke-tpu-topology: 2x2` etc.). Here the
same topology string drives an actual `jax.sharding.Mesh`: within a slice,
axes map onto ICI; across slices/hosts, the data axis rides DCN.

Axes (logical):
  dp  — data parallel (whole-request replication; across slices → DCN)
  pp  — pipeline parallel (layer stages; see parallel/pipeline.py)
  tp  — tensor parallel (weight sharding; within slice → ICI)
  sp  — sequence parallel (ring attention for long context; ICI)
  ep  — expert parallel (MoE; ICI)

`ep` is folded over the same devices as `tp` via mesh axis reuse: MoE layers
reinterpret the tensor axis as the expert axis (common TPU practice — keeps
one physical mesh).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DATA = "dp"
AXIS_PIPELINE = "pp"
AXIS_TENSOR = "tp"
AXIS_SEQ = "sp"
AXIS_EXPERT = "ep"

# Standard mesh axis order. tp innermost: adjacent devices share the fastest
# ICI links, and tensor-parallel collectives (psum of partial matmul results)
# are the most latency-sensitive. pp outermost after dp: stage hops are
# point-to-point and the least latency-sensitive.
MESH_AXES = (AXIS_DATA, AXIS_PIPELINE, AXIS_SEQ, AXIS_TENSOR)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. Product must equal the device count."""

    dp: int = 1
    sp: int = 1
    tp: int = 1
    pp: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.pp * self.sp * self.tp

    def axis_sizes(self) -> tuple[int, int, int, int]:
        return (self.dp, self.pp, self.sp, self.tp)


def parse_topology(topology: str) -> tuple[int, ...]:
    """Parse a GKE-style TPU topology string like '2x2' or '2x2x4'.

    Mirrors the `gke-tpu-topology` nodeSelector values the reference's TPU
    resource profiles use (reference: charts/kubeai/values-gke.yaml:26-41).
    """
    if not re.fullmatch(r"\d+(x\d+)*", topology):
        raise ValueError(f"invalid TPU topology {topology!r}")
    return tuple(int(p) for p in topology.split("x"))


def topology_num_chips(topology: str) -> int:
    return math.prod(parse_topology(topology))


def mesh_from_topology(
    topology: str,
    *,
    tp: int | None = None,
    sp: int = 1,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh for one TPU slice described by a topology string.

    By default the whole slice is tensor-parallel (tp = chip count), matching
    the reference's catalog choice of `--tensor-parallel-size=<chips>`
    (reference: charts/models/values.yaml:128).
    """
    n = topology_num_chips(topology)
    if tp is None:
        tp = n // sp
    cfg = MeshConfig(dp=n // (tp * sp), sp=sp, tp=tp)
    return build_mesh(cfg, devices=devices)


def build_mesh(
    cfg: MeshConfig, *, devices: Sequence[jax.Device] | None = None
) -> Mesh:
    """Build a Mesh with axes (dp, pp, sp, tp) over the given devices."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if cfg.num_devices != len(devices):
        raise ValueError(
            f"mesh {cfg} needs {cfg.num_devices} devices, got {len(devices)}"
        )
    arr = np.asarray(devices).reshape(cfg.axis_sizes())
    return Mesh(arr, MESH_AXES)


class NoAccelerator(RuntimeError):
    pass


def require_accelerator() -> jax.Device:
    """The default backend's first device — unless JAX got there by
    falling back. With no accelerator in reach JAX warns and carries on
    on the CPU; a server or a benchmark that carried on with it would
    answer from the wrong machine. The CPU is accepted only where it was
    asked for by name (JAX_PLATFORMS / jax_platforms = cpu, as the tests
    do)."""
    device = jax.devices()[0]
    if device.platform == "cpu" and "cpu" not in (
        jax.config.jax_platforms or ""
    ):
        raise NoAccelerator(
            "JAX found no accelerator and fell back to the CPU; set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    return device


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    if device is None:
        device = jax.devices()[0]
    return build_mesh(MeshConfig(), devices=[device])
