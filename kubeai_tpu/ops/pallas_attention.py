"""Pallas TPU flash attention for prefill.

Online-softmax attention computed block-by-block so the [S, S] logits
matrix never materializes in HBM — the prefill hot op for long context.
Grid: (batch, q-head, q-block); the kernel loops over k-blocks up to the
causal frontier (skipping fully-masked blocks entirely). With a sliding
`window` (a window layer of a family that mixes window and global
attention) a query sees that many positions, its own the last, and the loop
starts at the first k-block that holds any of them: blocks wholly outside
the window are skipped like those past the frontier.

GQA: the q-head grid axis maps each q head onto its kv head (h // group).

Numerics: fp32 accumulation in VMEM scratch; bf16 in/out. head_dim is
padded to 128 lanes; q/k blocks are 128 rows, so the sequence must be a
multiple of 128 (anything else raises — ops.attention.prefill_attention
routes the short and unaligned buckets to the jnp reference first).

Usage: flash_causal_prefill(q, k, v) — same contract as the jnp reference.
It is the kernel, not a dispatch: it runs compiled, or interpreted where
tests force that (ops/dispatch.py), and off a TPU it raises otherwise. Under
a mesh the pallas_call runs inside shard_map, KV heads split over tp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kubeai_tpu.ops import dispatch

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, 1, BQ, D]
    k_ref,  # [1, 1, S, D]
    v_ref,  # [1, 1, S, D]
    o_ref,  # [1, 1, BQ, D]
    *,
    block_q: int,
    block_k: int,
    seq_len: int,
    scale: float,
    mask_block: int,
    window: int,
):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, D]

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros_like(q)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if mask_block > 1:
            # Causal between blocks of `mask_block` positions, full inside
            # one; `mask_block` divides the tiles, so the frontier below
            # is the causal one.
            seen = k_pos < (q_pos // mask_block + 1) * mask_block
        else:
            seen = q_pos >= k_pos
        if window > 0:
            seen = seen & (q_pos - k_pos < window)
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    # Causal frontier: k blocks strictly after this q block are all masked.
    num_k = (qi + 1) * block_q // block_k
    # Window: the first k block that holds a position the block's first
    # query still sees (0 with no window: the loop is what it was).
    first_k = (
        jnp.maximum(qi * block_q - window + 1, 0) // block_k if window > 0
        else 0
    )
    m, l, acc = jax.lax.fori_loop(first_k, num_k, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_q", "block_k", "interpret", "scale", "group", "mask_block",
        "window",
    ),
)
def _flash_bhsd(
    q: jnp.ndarray,  # [B, H, S, D]
    k: jnp.ndarray,  # [B, KVH, S, D] — NOT expanded; the q-head grid axis
    v: jnp.ndarray,  #                 maps h -> kv head h // group in the
    block_q: int = 128,  #              BlockSpec, so GQA costs no extra HBM
    block_k: int = 128,
    interpret: bool = False,
    scale: float = 1.0,
    group: int = 1,
    mask_block: int = 1,
    window: int = 0,
) -> jnp.ndarray:
    B, H, S, D = q.shape
    grid = (B, H, S // block_q)
    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_len=S,
        scale=scale,
        mask_block=mask_block,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)
            ),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v)


def flash_causal_prefill(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, KVH, D]
    v: jnp.ndarray,
    *,
    block: int = 128,
    mask_block: int = 1,
    window: int = 0,
) -> jnp.ndarray:
    """Flash attention with the causal_prefill_attention contract
    (`mask_block` > 1: its block mask, for a divisor of `block`; `window` >
    0: its sliding window, not with a block mask)."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    if block % mask_block:
        raise ValueError(
            f"a mask block of {mask_block} does not divide the {block}-row tile"
        )
    if S < block or S % block:
        raise ValueError(
            f"flash prefill needs a sequence that is a multiple of {block}, "
            f"got {S}"
        )
    if window > 0 and mask_block > 1:
        raise ValueError("flash prefill takes a window or a block mask, not both")

    group = H // KVH
    # [B, S, H, D] -> [B, H, S, D]. K/V keep their KVH heads — the kernel's
    # q-head grid axis maps onto kv head h // group in the BlockSpec, so
    # GQA never materializes ×group KV in HBM.
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)

    # Pad head_dim to the 128-lane tile.
    Dp = max(128, ((D + 127) // 128) * 128)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        qt, kt, vt = (jnp.pad(x, pad) for x in (qt, kt, vt))

    out = dispatch.over_kv_heads(
        functools.partial(
            _flash_bhsd, block_q=block, block_k=block,
            interpret=dispatch.kernel_mode() == "interpret",
            scale=D ** -0.5, group=group, mask_block=mask_block,
            window=window,
        ),
        KVH, (1, 1, 1),
    )(qt, kt, vt)
    if Dp != D:
        out = out[..., :D]
    return jnp.moveaxis(out, 1, 2)  # [B, S, H, D]
