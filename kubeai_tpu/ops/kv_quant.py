"""Int8 KV-cache quantization: per-token-per-head symmetric scales.

The paged KV pool is the serving-time HBM ceiling (weights are already
int8-able via engine/quantization.py); storing pages as int8 halves KV
bytes on chip AND on the wire — every disagg handoff, peer prefix fetch
and objstore spill ships the quantized pages verbatim.

Layout. A quantized pool leaf is a dict — the same dispatch idiom the
weight quantizer uses ({"w8", "scale"} leaves):

    {"q8":    int8    [..., page, KVH, D]   quantized pages
     "scale": float32 [..., page, KVH]      per-token-per-head scales}

Scale granularity is per (token, kv-head): each token's K (or V) row of
D values quantizes independently,

    scale = max(|row|) / 127   (clamped to SCALE_FLOOR)
    q8    = round(row / scale) ∈ [-127, 127]

which is what makes the pool APPEND-ONLY under quantization: a decode
step writes one new token's rows without ever re-scaling resident
tokens, so pages are immutable once written — the property the prefix
cache's content-hash chains and the disagg byte-identity guarantee
depend on. Coarser per-page scales would halve the scale overhead but
force a page re-quantize on every append, breaking both.

Capacity math (the sim in benchmarks/kv_quant_sim.py asserts it): one
token-layer costs 2*KVH*D*2 bytes in bf16 and 2*KVH*(D + 4) in int8
(+4 = the f32 scale), a 2D/(D+4) capacity factor — 1.94x at D=128.

Dequantization happens inside the attention read (the reference path
multiplies the gathered int8 pages by their gathered scales in f32);
the Pallas kernels stay bf16-only, so a quantized pool always takes the
reference path — acceptable because int8 KV targets capacity, and the
ref path is the tier-1/CPU path anyway. A fused int8 Pallas kernel is
the natural upgrade once validated on hardware.
"""

from __future__ import annotations

import jax.numpy as jnp

# Scales below this clamp to it: a zero-variance row (all-zero K/V, e.g.
# scratch pages) quantizes to zeros and dequantizes back to exact zeros.
SCALE_FLOOR = 1e-8

# Engine-facing dtype names (EngineConfig.kv_dtype / --kv-dtype / CRD
# kvCache.dtype). "" means unset and resolves to bfloat16.
KV_DTYPES = ("bfloat16", "int8")


def resolve_kv_dtype(name: str) -> str:
    """Normalize a kv-dtype knob; raises ValueError on unknown names."""
    name = (name or "").strip().lower()
    if name == "":
        return "bfloat16"
    if name not in KV_DTYPES:
        raise ValueError(
            f"kv dtype {name!r} not in {KV_DTYPES}"
        )
    return name


def is_quantized_kv(pool) -> bool:
    """True for a quantized pool leaf ({"q8", "scale"} dict)."""
    return isinstance(pool, dict) and "q8" in pool and "scale" in pool


def kv_pages_shape(pool) -> tuple:
    """The page-array shape regardless of quantization."""
    return (pool["q8"] if is_quantized_kv(pool) else pool).shape


def kv_pool_nbytes(pool) -> int:
    """Resident bytes of one pool leaf (pages + scales when quantized)."""
    if is_quantized_kv(pool):
        return int(pool["q8"].nbytes + pool["scale"].nbytes)
    return int(pool.nbytes)


def make_quantized_pool(shape, scale_dtype=jnp.float32, sharding=None) -> dict:
    """Zeroed quantized pool: pages [..., page, KVH, D] int8 + scales
    [..., page, KVH] f32 (zero scale is fine — rows are written before
    they are ever read, and masked junk dequantizes to 0). `sharding` is
    a {"q8", "scale"} dict of Shardings the leaves are created under."""
    sharding = sharding or {"q8": None, "scale": None}
    return {
        "q8": jnp.zeros(shape, jnp.int8, device=sharding["q8"]),
        "scale": jnp.zeros(shape[:-1], scale_dtype, device=sharding["scale"]),
    }


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., D] -> (int8 [..., D], f32 scales [...]): symmetric per-row
    quantization over the last (head_dim) axis."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax / 127.0, SCALE_FLOOR)
    q8 = jnp.clip(
        jnp.round(x32 / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q8, scale


def dequantize_kv(
    q8: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16
) -> jnp.ndarray:
    """(int8 [..., D], scales [...]) -> [..., D] in `dtype`."""
    return (q8.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


def kv_capacity_factor(head_dim: int, scale_bytes: int = 4) -> float:
    """Slot-capacity multiplier of int8 KV vs bf16 at equal HBM budget:
    bytes-per-token-per-head 2*D (bf16) over D + scale_bytes (int8)."""
    return (2.0 * head_dim) / (head_dim + scale_bytes)
