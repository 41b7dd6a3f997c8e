"""Attention ops for prefill and decode against a slot-based KV cache.

TPU-first design notes:
  - Static shapes everywhere: the KV cache is a fixed [slots, max_len, ...]
    buffer; per-sequence lengths arrive as arrays and become masks, never
    Python control flow — one compiled graph serves all requests.
  - GQA is expressed by reshaping q to [kv_heads, group, ...] so the MXU
    sees large batched matmuls instead of head-repeated memory traffic.
  - Softmax in float32; logits never materialize wider than [*, S] blocks.
  - A Pallas flash-attention kernel (kubeai_tpu.ops.pallas_attention) is
    used for long-prefill when available; these jnp versions are the
    reference semantics and the CPU/test fallback.

The reference has no attention code at all — it runs vLLM images
(reference: internal/modelcontroller/engine_vllm.go:12-167 renders the Pod;
the kernels live in the external image). This module is the TPU-native
replacement for that delegated compute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import dispatch

NEG_INF = -1e30


def _gqa_reshape(q: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """[B, S, H, D] -> [B, S, KVH, G, D]."""
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv_heads, h // num_kv_heads, d)


def causal_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, KVH, D]
    v: jnp.ndarray,  # [B, S, KVH, D]
    *,
    q_offset: jnp.ndarray | int = 0,  # positions of q within the sequence
    scale: float | None = None,
    logit_softcap: float | None = None,  # Gemma-2 tanh capping
    window: jnp.ndarray | int | None = None,  # sliding window; traced OK,
    #   <= 0 disables (lets a layer scan alternate local/global layers)
    mask_block: int = 1,  # > 1: causal between blocks of this many
    #   positions, full inside one (generation by diffusion over blocks)
) -> jnp.ndarray:
    """Causal self-attention over a freshly computed prompt segment.

    `q_offset` supports chunked prefill: q tokens are at absolute positions
    offset..offset+S-1 while k/v cover positions 0..S-1 of the same buffer.
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    qg = _gqa_reshape(q * scale, kvh)  # [B, S, KVH, G, D]
    # [B, KVH, G, Sq, Sk]
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    )
    if logit_softcap is not None:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    q_pos = jnp.arange(s) + q_offset
    k_pos = jnp.arange(k.shape[1])
    if mask_block > 1:
        # Row i sees column j iff j // B <= i // B.
        mask = k_pos[None, :] < (q_pos[:, None] // mask_block + 1) * mask_block
    else:
        mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        mask = mask & (
            (win <= 0) | (q_pos[:, None] - k_pos[None, :] < win)
        )
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


@jax.named_scope("prefill_attention")
def prefill_attention(q, k, v, mask_block: int = 1, window: int = 0):
    """The prefill kernel, chosen by shape: aligned buckets of 256 tokens
    and up take the Pallas flash kernel wherever kernels run
    (ops/dispatch.py: a TPU, or tests forcing the interpreter); the short
    and unaligned buckets keep `causal_prefill_attention`.
    `mask_block` > 1 is a block-diffusion family's mask: causal between
    blocks of that many positions, full inside one. `window` > 0 is a
    window layer's: a query sees that many positions, its own the last.
    Values may be narrower than queries and keys (latent attention expanded:
    keys of 192, values of 128): both kernels take one width, so the values
    ride in zero-padded to the keys' and what comes back is cut to theirs."""
    S, d, dv = q.shape[1], q.shape[-1], v.shape[-1]
    if dv != d:
        v = jnp.pad(v, ((0, 0),) * (v.ndim - 1) + ((0, d - dv),))
    if dispatch.kernel_mode() != "reference" and S >= 256 and S % 128 == 0:
        from kubeai_tpu.ops.pallas_attention import flash_causal_prefill

        out = flash_causal_prefill(
            q, k, v, mask_block=mask_block, window=window
        )
    else:
        out = causal_prefill_attention(
            q, k, v, mask_block=mask_block, window=window or None
        )
    return out if dv == d else out[..., :dv]


def chunked_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, D] — the new chunk's queries
    k_cache: jnp.ndarray,  # [B, L, KVH, D] — cache already containing the chunk
    v_cache: jnp.ndarray,  # [B, L, KVH, D]
    chunk_start: jnp.ndarray,  # [B] absolute position of q[:, 0]
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,  # Gemma-2 tanh capping
    window: jnp.ndarray | int | None = None,  # sliding window; <= 0 = off
) -> jnp.ndarray:
    """Attention of a prefill chunk against the full cache prefix (causal).

    Softcap/window follow the same order as causal_prefill_attention /
    decode_attention (cap the raw logits, then mask), so a chunked Gemma
    prefill is bit-consistent with the whole-prompt path."""
    b, s, h, d = q.shape
    kvh = k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5
    qg = _gqa_reshape(q * scale, kvh)
    logits = jnp.einsum(
        "bqkgd,blkd->bkgql", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    )
    if logit_softcap is not None:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    q_pos = chunk_start[:, None] + jnp.arange(s)[None, :]  # [B, Sq]
    l_pos = jnp.arange(k_cache.shape[1])  # [L]
    mask = q_pos[:, :, None] >= l_pos[None, None, :]  # [B, Sq, L]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        mask = mask & (
            (win <= 0)
            | (q_pos[:, :, None] - l_pos[None, None, :] < win)
        )
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgql,blkd->bqkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # [B, H, D] — one new token per slot
    k_cache: jnp.ndarray,  # [B, L, KVH, D]
    v_cache: jnp.ndarray,  # [B, L, KVH, D]
    lengths: jnp.ndarray,  # [B] valid cache length per slot (incl. new token)
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,  # Gemma-2 tanh capping
    window: jnp.ndarray | int | None = None,  # sliding window; <= 0 = off
) -> jnp.ndarray:
    """Single-token decode attention against a dense [B, L] cache with
    length mask (family.decode_step: the draft model's cache)."""
    b, h, d = q.shape
    kvh = k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5
    qg = (q * scale).reshape(b, kvh, h // kvh, d)  # [B, KVH, G, D]
    logits = jnp.einsum(
        "bkgd,blkd->bkgl", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    )
    if logit_softcap is not None:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    l_pos = jnp.arange(k_cache.shape[1])
    mask = l_pos[None, :] < lengths[:, None]  # [B, L]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        mask = mask & ((win <= 0) | (l_pos[None, :] >= lengths[:, None] - win))
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgl,blkd->bkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)
