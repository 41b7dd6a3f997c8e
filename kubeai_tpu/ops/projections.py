"""The attention projections' way from `[..., H*D]` values to heads.

A forward that runs a few rows a slot (decode: one; a verify window or a
block of positions: a handful) is bound by its weights' bytes, and there
the form of the three projections decides what the TPU compiler does with
the layer-stacked `wq` / `wk` / `wv`. Written as
`einsum("be,eh->bh", h, w).reshape(B, 1, H, D)`, the reshape is folded into
the dot, the dot wants its output heads-major and the weight transposed,
and the compiled decode chunk of Mistral-7B on a v5e (16 layers, 24 slots;
AOT, PERF.md section 6, PR 39) then

  - transposes the WHOLE stacked weight once a chunk in the chunk's entry
    (`copy.22 = bf16[16,4096,4096]{1,2,0} copy(%params__layers____wq__)`,
    `copy.21 = bf16[16,4096,1024]` for `wk`: 0.626 GiB of temporaries),
  - moves one layer's transposed weight into fast memory every layer of
    every step, computing nothing
    (`constant_dynamic-slice_fusion.4 = bf16[1,4096,4096]{1,2,0:...S(1)}`,
    `.5` for `wk`), and only then
  - multiplies from there (`fusion.193 = bf16[24,32,128]{2,0,1}`):

1.1 ms a decode step for the 0.66 ms that `wq`'s bytes need. With the flat
values held as values before the reshape, each projection is ONE fusion
that reads the stacked parameter where it lies (`bf16[24,4096]`,
`bf16[24,1024]` x2), the chunk has no copy of a stacked weight, no
`constant_dynamic-slice_fusion` and 0 bytes of temporaries; Mixtral's tp=4
shard compiles the same way (0.126 GiB -> 0). The mathematics is what the
source has always said: the same three dots on the same operands, bf16 in,
f32 accumulation, a bf16 value out.

A prefill runs hundreds of rows a prompt, is bound by the MXU and keeps
the folded form and its compiled programs: `split_heads` decides by the
rows a slot, which it reads off its input. tests/unit/
test_decode_pool_in_place.py holds both cells' compiled chunks to this.
"""

from __future__ import annotations

import jax

# Fewer rows a slot than any prefill runs: EngineConfig.buckets() starts
# at 16. Decode is 1, a verify window speculate + 1, a block forward
# `block_length`.
HELD_BELOW_ROWS = 16


def split_heads(q, k, v, num_heads: int, num_kv_heads: int, head_size: int):
    """q [B, H*D] or [B, S, H*D], k and v [..., KVH*D] -> q [..., H, D],
    k and v [..., KVH, D]. At few rows a slot (no S axis, or S under
    HELD_BELOW_ROWS) the flat values are held as values first, so that
    the compiler cannot fold the head reshape into the dots that made
    them (the module's docstring has what it does when it can)."""
    rows = q.shape[1] if q.ndim == 3 else 1
    if rows < HELD_BELOW_ROWS:
        q, k, v = jax.lax.optimization_barrier((q, k, v))
    lead = q.shape[:-1]
    return (
        q.reshape(*lead, num_heads, head_size),
        k.reshape(*lead, num_kv_heads, head_size),
        v.reshape(*lead, num_kv_heads, head_size),
    )
