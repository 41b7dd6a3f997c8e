"""Paged decode attention: block-table paging, ragged lengths, TPU kernel.

The decode hot op. A dense [slots, max_seq_len] cache reads
O(B * max_seq_len) of KV per step regardless of true lengths; paging
reads only the pages a sequence actually occupies. Two implementations with one contract:

  ref_paged_decode_attention — jnp gather-through-block-tables reference
      (every backend but a TPU; see ops/dispatch.py for the one rule).
  paged_decode_attention     — Pallas TPU kernel. Grid (slots, max_pages);
      each DMA carries a full page across ALL kv heads (the block's last
      two dims are the full (KVH, D) — a Mosaic tiling requirement) and a
      static in-kernel unroll attends each head. Block tables + lengths
      are SCALAR-PREFETCHED so the BlockSpec index_map selects each
      slot's next real page for DMA. Pages past a slot's length re-map
      to the slot's LAST valid page — consecutive grid steps with an
      unchanged block index elide the copy and pl.when skips their
      compute, so the bytes read are sum(ceil(len/page)) pages. The time
      is not: a grid step costs about 0.1 us per pool operand whether or
      not a page is there (timed alone on a v5e in PR 28 on this grid
      with the stacked pool: 24 slots x 32 page slots x 2 pools = 154 us
      a layer with the attention taken out), so it follows B*max_pages.
      pp stages and an explicit per_layer layout run it; the decode path
      of a bf16 pool is the stacked kernel below, which walks the live
      pages instead of a grid.

Sliding-window (Gemma-2) and logit softcap are supported in both paths:
window masks keys at positions < length - window.

Int8 KV (ops/kv_quant.py): a pool passed as a {"q8", "scale"} dict is
a quantized pool. The reference path gathers pages AND scales through
the block tables and dequantizes in f32 before attention; the write
helpers quantize each new token's rows on append. The Pallas kernels
are bf16-only, so quantized pools always dispatch to the reference path,
on a TPU too (int8 KV buys capacity, not kernel speed — see kv_quant
module docs).

The reference operator has no attention code — it runs vLLM images whose
PagedAttention this replaces TPU-natively (reference:
internal/modelcontroller/engine_vllm.go:12-167 renders the Pod; kernels
live in the external image; charts/kubeai/values.yaml:45).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeai_tpu.ops import dispatch
from kubeai_tpu.parallel.sharding import kv_heads_axis

NEG_INF = -1e30

# The two decode layouts of a paged pool (measured against each other on
# the chip in PR 25: PERF.md section 6). "fused": the stacked [NL, ...] pool
# is read in place by a layer-indexed kernel and written once after the layer
# scan (paged_decode_attention_fused); what every bf16 pool takes.
# "per_layer": scatter-then-attend on one layer's pool
# (paged_decode_attention); what a quantized pool takes, since the Pallas
# kernels read bf16 only. The pool's kind is the only input: no option names
# a layout (a test names one at a forward, `attn_kernel=`).
def decode_layout(*, quantized: bool) -> str:
    """The decode layout a pool takes, by its kind (`quantized` = a
    {"q8", "scale"} pool)."""
    return "per_layer" if quantized else "fused"


def _accum_head(
    q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref, kh,
    *, scale, logit_softcap, zero_masked_p,
):
    """One kv head's online-softmax update over the current page block.
    Shared by the decode and verify kernels; `zero_masked_p` guards rows
    that can be FULLY masked (verify: speculative rows past a window).
    Scratch refs are [KVH, rows, ...] — indexing the LEADING dim keeps
    every VMEM access tile-aligned regardless of the per-head row count."""
    q = q_ref[0, kh].astype(jnp.float32) * scale  # [rows, D]
    k = k_ref[0, :, kh].astype(jnp.float32)  # [page, D]
    v = v_ref[0, :, kh].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [rows, page]
    if logit_softcap is not None:
        s = jnp.tanh(s / logit_softcap) * logit_softcap
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[kh]
    l_prev = l_ref[kh]
    acc_prev = acc_ref[kh]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if zero_masked_p:
        # Fully-masked rows keep m = NEG_INF; zero their contributions.
        p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[kh] = m_new
    l_ref[kh] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[kh] = acc_prev * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )


# ---- functional reference ----------------------------------------------------


def ref_paged_decode_attention(
    q: jnp.ndarray,  # [B, H, D] one new token per slot
    k_pages: jnp.ndarray,  # [P, page, KVH, D] this layer's page pool
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP] page ids, -1 = unallocated
    lengths: jnp.ndarray,  # [B] valid tokens per slot (incl. the new one)
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,
    window: jnp.ndarray | int | None = None,  # sliding window (Gemma-2);
    #   traced scalars OK, <= 0 disables — layer scans alternate
    #   local/global layers with one compiled graph
) -> jnp.ndarray:
    """Gather pages into a virtual contiguous view, then masked attention.
    Semantics oracle for the kernel; CPU/test fallback path. Accepts
    quantized {"q8", "scale"} pools — pages and scales gather through
    the same block tables and dequantize in f32."""
    from kubeai_tpu.ops.kv_quant import is_quantized_kv

    b, h, d = q.shape
    bt = jnp.maximum(block_tables, 0)  # -1 -> scratch page 0 (masked below)
    if is_quantized_kv(k_pages):
        kvh = k_pages["q8"].shape[2]
        k = k_pages["q8"][bt].astype(jnp.float32)  # [B, MP, page, KVH, D]
        v = v_pages["q8"][bt].astype(jnp.float32)
        k = k * k_pages["scale"][bt].astype(jnp.float32)[..., None]
        v = v * v_pages["scale"][bt].astype(jnp.float32)[..., None]
    else:
        kvh = k_pages.shape[2]
        k = k_pages[bt].astype(jnp.float32)
        v = v_pages[bt].astype(jnp.float32)
    mp, page = k.shape[1], k.shape[2]
    k = k.reshape(b, mp * page, kvh, d)
    v = v.reshape(b, mp * page, kvh, d)
    scale = scale if scale is not None else d ** -0.5
    qg = (q * scale).reshape(b, kvh, h // kvh, d)
    logits = jnp.einsum(
        "bkgd,blkd->bkgl", qg.astype(jnp.float32), k
    )
    if logit_softcap is not None:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    pos = jnp.arange(mp * page)
    mask = pos[None, :] < lengths[:, None]  # [B, L]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        mask = mask & (
            (win <= 0) | (pos[None, :] >= lengths[:, None] - win)
        )
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgl,blkd->bkgd", probs, v)
    return out.reshape(b, h, d).astype(q.dtype)


# ---- Pallas kernel -----------------------------------------------------------


def _paged_kernel(
    # scalar-prefetch
    bt_ref,  # [B, MP] int32 block tables
    len_ref,  # [B] int32 lengths
    win_ref,  # [1] int32 sliding window (<= 0 = disabled)
    # blocks
    q_ref,  # [1, KVH, G, D]
    k_ref,  # [1, page, KVH, D] — the page selected by the index_map
    v_ref,  # [1, page, KVH, D]
    o_ref,  # [1, KVH, G, D]
    # scratch (carried across the page grid dimension)
    m_ref,  # [KVH, G, 1] f32
    l_ref,  # [KVH, G, 1] f32
    acc_ref,  # [KVH, G, D] f32
    *,
    page_size: int,
    kvh: int,
    group: int,
    scale: float,
    logit_softcap: float | None,
):
    # Grid is (slots, pages): one DMA per (slot, page) carries ALL kv
    # heads of that page — Mosaic requires the block's last two dims to
    # be full (KVH, D) here, and the single fetch serves every head.
    b = pl.program_id(0)
    i = pl.program_id(1)
    mp = pl.num_programs(1)

    length = len_ref[b]
    win = win_ref[0]
    n_pages = pl.cdiv(length, page_size)
    # First page holding in-window keys (0 when the window is off):
    # pages below it contribute nothing and their compute is skipped
    # (their DMA is elided by the index_map clamp).
    first = jnp.where(
        win > 0, jnp.maximum(length - win, 0) // page_size, 0
    )

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((i >= first) & (i < n_pages))
    def _attend():
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (group, page_size), 1
        )
        valid = pos < length
        valid = valid & ((win <= 0) | (pos >= length - win))
        for kh in range(kvh):  # static unroll: one [G,page] dot per head
            _accum_head(
                q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref, kh,
                scale=scale, logit_softcap=logit_softcap,
                zero_masked_p=False,
            )

    @pl.when(i == mp - 1)
    def _finalize():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)  # [KVH, G, D]
        o_ref[0] = out.astype(o_ref.dtype)


def _page_index(b, i, bt_ref, len_ref, win_ref, *, page_size):
    """Index map for k/v pages: slot b's i-th page. Outside the live range
    (past the last page, or below the sliding window's first page), KEEP
    RETURNING the nearest live page — an unchanged block index between
    consecutive grid steps elides the DMA entirely."""
    length = len_ref[b]
    win = win_ref[0]
    last = jnp.maximum(pl.cdiv(length, page_size) - 1, 0)
    first = jnp.where(
        win > 0, jnp.maximum(length - win, 0) // page_size, 0
    )
    clamped = jnp.clip(i, first, last)
    page_id = jnp.maximum(bt_ref[b, clamped], 0)
    return page_id, 0, 0, 0


@functools.partial(
    jax.jit,
    static_argnames=("scale", "logit_softcap", "interpret"),
)
def _paged_pallas(
    q,  # [B, KVH, G, D]
    k_pages,  # [P, page, KVH, D]
    v_pages,
    block_tables,  # [B, MP]
    lengths,  # [B]
    window,  # [1] int32, <= 0 disables
    *,
    scale: float,
    logit_softcap: float | None,
    interpret: bool,
):
    b, kvh, g, d = q.shape
    p, page, _, _ = k_pages.shape
    mp = block_tables.shape[1]

    kernel = functools.partial(
        _paged_kernel,
        page_size=page,
        kvh=kvh,
        group=g,
        scale=scale,
        logit_softcap=logit_softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec(
                (1, kvh, g, d),
                lambda b_, i_, bt, ln, wn: (b_, 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page, kvh, d),
                functools.partial(_page_index, page_size=page),
            ),
            pl.BlockSpec(
                (1, page, kvh, d),
                functools.partial(_page_index, page_size=page),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, kvh, g, d),
            lambda b_, i_, bt, ln, wn: (b_, 0, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, 1), jnp.float32),
            pltpu.VMEM((kvh, g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, window, q, k_pages, v_pages)


def _check_page_size(page_size: int) -> None:
    """The k/v block is (1, page, KVH, D) — its last two dims are the FULL
    array dims, so the block shape imposes no divisibility rule; but
    in-kernel values use `page` as a sublane/lane dim ([page, D] loads,
    [rows, page] logits), which wants the f32 sublane tile."""
    if page_size % 8:
        raise ValueError(
            f"paged attention kernels need page_size % 8 == 0, got "
            f"{page_size}"
        )


def _window_arg(window) -> jnp.ndarray:
    return jnp.asarray([0 if window is None else window], jnp.int32).reshape(1)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [P, page, KVH, D]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    lengths: jnp.ndarray,  # [B]
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Paged decode attention: the Pallas kernel on a TPU, the reference
    elsewhere, an int8 pool always on the reference (ops/dispatch.py)."""
    from kubeai_tpu.ops.kv_quant import is_quantized_kv

    b, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    mode = dispatch.kernel_mode()
    if mode == "reference" or is_quantized_kv(k_pages):
        return ref_paged_decode_attention(
            q, k_pages, v_pages, block_tables, lengths,
            scale=scale, logit_softcap=logit_softcap, window=window,
        )
    _check_page_size(k_pages.shape[1])
    kvh = k_pages.shape[2]
    call = dispatch.over_kv_heads(
        functools.partial(
            _paged_pallas, scale=scale, logit_softcap=logit_softcap,
            interpret=mode == "interpret",
        ),
        kvh, (1, 2, 2, None, None, None),
    )
    out = call(
        q.reshape(b, kvh, h // kvh, d), k_pages, v_pages, block_tables,
        lengths, _window_arg(window),
    )
    return out.reshape(b, h, d)


def ref_paged_verify_attention(
    q: jnp.ndarray,  # [B, K, H, D] — K speculative positions per slot
    k_pages: jnp.ndarray,  # [P, page, KVH, D]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    positions: jnp.ndarray,  # [B] absolute position of query 0
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Multi-query paged attention for SPECULATIVE VERIFY: query k sits at
    absolute position positions+k and attends keys at cols <= positions+k
    (the K window's KV is already scattered into the pages). Gather-based
    reference — speculative windows are small (K <= 8), so the extra HBM
    read vs a dedicated kernel is bounded; a multi-query Pallas kernel is
    the upgrade path."""
    b, kq, h, d = q.shape
    kvh = k_pages.shape[2]
    bt = jnp.maximum(block_tables, 0)
    k = k_pages[bt]
    v = v_pages[bt]
    mp, page = k.shape[1], k.shape[2]
    L = mp * page
    k = k.reshape(b, L, kvh, d)
    v = v.reshape(b, L, kvh, d)
    scale = scale if scale is not None else d ** -0.5
    qg = (q * scale).reshape(b, kq, kvh, h // kvh, d)
    logits = jnp.einsum(
        "bqkgd,blkd->bkgql", qg.astype(jnp.float32), k.astype(jnp.float32)
    )  # [B, KVH, G, K, L]
    if logit_softcap is not None:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    col = jnp.arange(L)
    q_abs = positions[:, None] + jnp.arange(kq)[None, :]  # [B, K]
    mask = col[None, None, :] <= q_abs[:, :, None]  # [B, K, L]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        mask = mask & (
            (win <= 0) | (col[None, None, :] > q_abs[:, :, None] - win)
        )
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgql,blkd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, kq, h, d).astype(q.dtype)


def _paged_verify_kernel(
    # scalar-prefetch
    bt_ref,  # [B, MP]
    pos_ref,  # [B] absolute position of query 0
    win_ref,  # [1] sliding window (<= 0 off)
    # blocks
    q_ref,  # [1, KVH, K*G, D]
    k_ref,  # [1, page, KVH, D]
    v_ref,  # [1, page, KVH, D]
    o_ref,  # [1, KVH, K*G, D]
    # scratch
    m_ref,  # [KVH, K*G, 1] f32
    l_ref,  # [KVH, K*G, 1] f32
    acc_ref,  # [KVH, K*G, D] f32
    *,
    page_size: int,
    kvh: int,
    scale: float,
    spec_k: int,
    group: int,
    logit_softcap: float | None,
):
    # Grid (slots, pages); every kv head of a page rides one DMA (the
    # block's last two dims must be the full (KVH, D) on TPU).
    b = pl.program_id(0)
    i = pl.program_id(1)
    mp = pl.num_programs(1)
    pos = pos_ref[b]
    win = win_ref[0]
    kq = spec_k * group
    # Keys exist up to absolute position pos + spec_k - 1.
    n_pages = pl.cdiv(pos + spec_k, page_size)
    # First page with any in-window key (query 0 is the lowest row);
    # pages below it are provably all-masked — skip their compute (the
    # index_map clamp already elides their DMA).
    first = jnp.where(
        win > 0, jnp.maximum(pos - win + 1, 0) // page_size, 0
    )

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((i >= first) & (i < n_pages))
    def _attend():
        col = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (kq, page_size), 1
        )
        row_pos = pos + (
            jax.lax.broadcasted_iota(jnp.int32, (kq, page_size), 0) // group
        )
        valid = col <= row_pos
        valid = valid & ((win <= 0) | (col > row_pos - win))
        for kh in range(kvh):  # static unroll: one [KQ,page] dot per head
            _accum_head(
                q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref, kh,
                scale=scale, logit_softcap=logit_softcap,
                zero_masked_p=True,
            )

    @pl.when(i == mp - 1)
    def _finalize():
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)  # [KVH, KQ, D]
        o_ref[0] = out.astype(o_ref.dtype)


def _verify_page_index(b, i, bt_ref, pos_ref, win_ref, *, page_size, spec_k):
    """Clamp to the slot's live page range so out-of-range grid steps
    revisit a live page (DMA elided)."""
    pos = pos_ref[b]
    win = win_ref[0]
    last = jnp.maximum(pl.cdiv(pos + spec_k, page_size) - 1, 0)
    first = jnp.where(
        win > 0, jnp.maximum(pos - win + 1, 0) // page_size, 0
    )
    clamped = jnp.clip(i, first, last)
    return jnp.maximum(bt_ref[b, clamped], 0), 0, 0, 0


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec_k", "group", "scale", "logit_softcap", "interpret",
    ),
)
def _paged_verify_pallas(
    q,  # [B, KVH, K*G, D]
    k_pages,
    v_pages,
    block_tables,
    positions,  # [B]
    window,  # [1] int32
    *,
    spec_k: int,
    group: int,
    scale: float,
    logit_softcap: float | None,
    interpret: bool,
):
    b, kvh, kq, d = q.shape
    page = k_pages.shape[1]
    mp = block_tables.shape[1]
    kernel = functools.partial(
        _paged_verify_kernel,
        page_size=page,
        kvh=kvh,
        scale=scale,
        spec_k=int(spec_k),
        group=int(group),
        logit_softcap=logit_softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec(
                (1, kvh, kq, d),
                lambda b_, i_, bt, ps, wn: (b_, 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page, kvh, d),
                functools.partial(
                    _verify_page_index, page_size=page, spec_k=int(spec_k)
                ),
            ),
            pl.BlockSpec(
                (1, page, kvh, d),
                functools.partial(
                    _verify_page_index, page_size=page, spec_k=int(spec_k)
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, kvh, kq, d),
            lambda b_, i_, bt, ps, wn: (b_, 0, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((kvh, kq, 1), jnp.float32),
            pltpu.VMEM((kvh, kq, 1), jnp.float32),
            pltpu.VMEM((kvh, kq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, kq, d), q.dtype),
        interpret=interpret,
    )(block_tables, positions, window, q, k_pages, v_pages)


def paged_verify_attention(
    q: jnp.ndarray,  # [B, K, H, D]
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,  # [B]
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Multi-query paged verify attention (speculative decoding's verify
    pass; see ref_paged_verify_attention for semantics), dispatched like
    paged_decode_attention."""
    b, spec_k, h, d = q.shape
    kvh = k_pages.shape[2]
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5
    mode = dispatch.kernel_mode()
    if mode == "reference":
        return ref_paged_verify_attention(
            q, k_pages, v_pages, block_tables, positions,
            scale=scale, logit_softcap=logit_softcap, window=window,
        )
    _check_page_size(k_pages.shape[1])
    # [B, K, H, D] -> [B, KVH, K*G, D]: row r = query r//G, q-head-in-group
    # r%G, so the kernel's row//group recovers the query index.
    qk = jnp.moveaxis(
        q.reshape(b, spec_k, kvh, group, d), 1, 2
    ).reshape(b, kvh, spec_k * group, d)
    call = dispatch.over_kv_heads(
        functools.partial(
            _paged_verify_pallas, spec_k=spec_k, group=group, scale=scale,
            logit_softcap=logit_softcap, interpret=mode == "interpret",
        ),
        kvh, (1, 2, 2, None, None, None),
    )
    out = call(
        qk, k_pages, v_pages, block_tables, positions, _window_arg(window)
    )
    out = jnp.moveaxis(
        out.reshape(b, kvh, spec_k, group, d), 2, 1
    )  # [B, K, KVH, G, D]
    return out.reshape(b, spec_k, h, d)


# ---- stacked-pool decode kernel (pool read in place, deferred scatter) --------
#
# The decode layout of every bf16 pool. Scatter-then-attend inside the layer
# scan moves the pool instead of reading it (timed on a v5e in PR 25, PERF.md
# section 6: more than half of the decode chunk's device time):
#   1. lax.scan slices each layer's [P, page, KVH, D] pool out of the
#      stacked array and writes the updated slice back — a full KV-pool
#      round-trip through HBM every decode step even though only B
#      tokens/layer change — and the chunk scan that carries the pools
#      copies each whole pool once a step.
#   2. pallas_call is opaque to XLA, so the sliced operand MATERIALIZES
#      (no fusion into the kernel).
# This kernel takes the FULL [NL, ...] pool where it lies in HBM plus a
# scalar-prefetched layer index (no slicing, no materialization) and attends
# the NEW token as an explicit extra column merged when a slot finishes (so
# the pool stays read-only and the scatter defers to ONE batched write after
# the layer scan). That write indexes the layer where a shard holds fewer
# than 8 KV heads (`_write_token_rows` says why): as a slice, the layer made
# the compiler lay a 2-KV-head pool out for the write in a way this kernel
# cannot read, and copy it whole every decode step.
#
# Its time follows the live KV (timed alone on a v5e in PR 28, PERF.md
# section 6). A grid over (slots, page slots) with one BlockSpec a page cost
# 0.1 us per slot, page slot and pool whether or not a page was there (24 x
# 32 x 2 of them: 154 us a layer with the attention taken out), so there is
# no such grid: ONE invocation walks the live pages of the slots that hold
# any, in order, with two cursors. The fetch cursor starts each page's K and
# V copies (128 KiB each at Mistral's shapes, straight from the pool) into a
# ring of VMEM buffers, `depth` pages ahead of the compute cursor, which
# waits for its page, attends it and hands the buffer back. A slot that
# holds no page costs a few scalar instructions. Its jitted wrapper is named
# `_paged_pallas...` because the benchmark's `paged_attn_ms` finds the custom
# call by that prefix.


def _live_page_range(bt_ref, pos_ref, win_ref, b, *, page_size, max_pages):
    """(first, n_pages): slot b's pages [first, n_pages) hold the OLD tokens
    a decode step attends. Both cursors of the kernel ask here. A slot that
    holds no page (its block-table row starts with -1: freed, or never
    admitted) has none whatever its position says (the engine advances every
    row's position, a free row's too, and clears only the row), so it
    attends its own new token alone."""
    pos = pos_ref[b]
    win = win_ref[0]
    n_pages = jnp.where(
        bt_ref[b, 0] < 0, 0, jnp.minimum(pl.cdiv(pos, page_size), max_pages)
    )
    first = jnp.where(
        win > 0, jnp.maximum(pos + 1 - win, 0) // page_size, 0
    )
    return first, n_pages


# A cursor is (slot, page): it walks the live pages of the slots that have
# any, in order; slot == nb means past the end (rows are read at min(slot, nb
# - 1), so a finished cursor reads nothing out of bounds). `live(b)` is slot
# b's (first, n_pages). The latent pool's kernel (ops/latent_attention.py)
# walks with the same two.


def _first_live(live, nb, b):
    """The first slot >= b that has a live page, at its first one."""
    def dead(b):
        first, n_pages = live(jnp.minimum(b, nb - 1))
        return (b < nb) & (first >= n_pages)

    b = jax.lax.while_loop(dead, lambda b: b + 1, b)
    return b, live(jnp.minimum(b, nb - 1))[0]


def _advance(live, nb, b, i):
    _, n_pages = live(jnp.minimum(b, nb - 1))
    # Dead slots are walked over only when a slot is left.
    return jax.lax.cond(
        i + 1 < n_pages, lambda: (b, i + 1),
        lambda: _first_live(live, nb, b + 1),
    )


def _split_bf16(p):
    """f32 p as three bf16 pieces whose sum is p (8 + 8 + 8 mantissa bits):
    p goes to the MXU against a bf16 V at full precision, in bf16 passes,
    with no cast of V."""
    hi = p.astype(jnp.bfloat16)
    rest = p - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _fused_attend_page(
    q, k, v, pos, lo, off, m_ref, l_ref, acc_ref,
    *, scale, logit_softcap, kvh, group,
):
    """One online-softmax update of all heads over one page: k, v are its
    [page, KVH, D] blocks, q the slot's [H, D] query rows, off the page's
    first token position.

    A page is attended as it lies: its block read as the [page * KVH, D]
    matrix it is (row = token, head), so one q x K^T dot gives every head's
    scores [H, page * KVH] and one p x V dot every head's values, with the
    pairs of a query row and another head's column masked out. Eight times
    the useful multiplies on a matrix unit that was idle, in place of a
    strided head slice, two casts, a transpose and two 4-row f32 dots per
    head and page. Operands enter the dots in the dtype they are stored in
    (bf16 x bf16 products are exact in the f32 accumulator; an f32 pool is
    multiplied in f32); scale and softcap act on the f32 scores; p stays
    f32, as three bf16 pieces when V is bf16. A block's new rows come as the
    [cols, D] matrix already (column = token, head, like a page's)."""
    h = kvh * group
    cols, d = (k.shape[0] * kvh, k.shape[2]) if k.ndim == 3 else k.shape
    if q.dtype != k.dtype:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    # One bf16 pass is exact for bf16 operands; anything else in f32.
    precision = (
        jax.lax.Precision.DEFAULT if k.dtype == jnp.bfloat16
        else jax.lax.Precision.HIGHEST
    )
    col = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 0) // group
    tok = col // kvh
    # Old tokens only (the new one is merged at the slot's end), in-window.
    valid = (
        ((col % kvh) == row_head) & (tok < pos - off) & (tok >= lo - off)
    )
    s = jax.lax.dot_general(
        q, k.reshape(cols, d), (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    ) * scale  # [H, cols]
    if logit_softcap is not None:
        s = jnp.tanh(s / logit_softcap) * logit_softcap
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[:] = m_new
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    v = v.reshape(cols, d)
    if v.dtype == jnp.bfloat16:
        pv = jnp.dot(
            jnp.concatenate(_split_bf16(p), axis=0), v,
            precision=precision, preferred_element_type=jnp.float32,
        )  # [3H, D]: one pass of V for the three pieces
        pv = pv[:h] + pv[h:2 * h] + pv[2 * h:]
    else:
        pv = jnp.dot(
            p, v, precision=precision, preferred_element_type=jnp.float32
        )
    acc_ref[:] = acc_ref[:] * alpha + pv


def _paged_fused_kernel(
    # scalar-prefetch
    bt_ref,  # [B, MP] int32 block tables
    pos_ref,  # [B] int32 OLD lengths (the new token's position)
    win_ref,  # [1] int32 sliding window (<= 0 = disabled)
    layer_ref,  # [1] int32 layer index into the stacked pool
    # whole arrays in VMEM
    q_ref,  # [B, H, D], H = KVH * G, kv-head major
    kn_ref,  # [B, H, D] the new token's K (not yet in the pool), per q head;
    vn_ref,  # rows > 1: [B, cols, D], the new rows as one page-like matrix
    # whole arrays where they lie (HBM)
    k_hbm,  # [NL, P, page, KVH, D]
    v_hbm,
    o_ref,  # [B, H, D] VMEM
    # scratch
    k_buf,  # [depth, page, KVH, D] the ring
    v_buf,
    sems,  # DMA semaphores [2, depth]
    m_ref,  # [H, 1] f32, the slot being attended
    l_ref,  # [H, 1] f32
    acc_ref,  # [H, D] f32
    *,
    page_size: int,
    kvh: int,
    group: int,
    depth: int,
    rows: int,
    scale: float,
    logit_softcap: float | None,
):
    """`rows` = 1: one new token a slot, `group` query heads a KV head.
    `rows` = R > 1: a block of R new positions a slot, full inside itself.
    Its R x G query rows a KV head all see the same old columns, so they
    are the kernel's `group` and the walk over the pages is the same walk;
    only the merge of the new rows differs."""
    nb, mp = bt_ref.shape
    layer = layer_ref[0]
    win = win_ref[0]
    live = functools.partial(
        _live_page_range, bt_ref, pos_ref, win_ref,
        page_size=page_size, max_pages=mp,
    )
    attend = functools.partial(
        _fused_attend_page, m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref,
        scale=scale, logit_softcap=logit_softcap, kvh=kvh, group=group,
    )

    def reset():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def merge_block(b):
        """The block's own rows, every one seen by every one: a page of
        `rows` tokens that is not in the pool yet; then normalize."""
        attend(q_ref[b], kn_ref[b], vn_ref[b], rows, 0, 0)
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        o_ref[b] = out.astype(o_ref.dtype)

    first_live = functools.partial(_first_live, live, nb)
    advance = functools.partial(_advance, live, nb)

    def copies(b, i, buf):
        page_id = jnp.maximum(bt_ref[jnp.minimum(b, nb - 1), i], 0)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, page_id], k_buf.at[buf], sems.at[0, buf]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, page_id], v_buf.at[buf], sems.at[1, buf]
            ),
        )

    def fetch(b, i, buf):
        @pl.when(b < nb)
        def _start():
            for copy in copies(b, i, buf):
                copy.start()

    if rows == 1:
        # A slot that holds no page attends its new token alone: a softmax
        # over one column is 1, so its output is that token's V. Slots with
        # pages overwrite their rows below.
        o_ref[...] = vn_ref[...].astype(o_ref.dtype)
    else:
        # A slot that holds no page attends its block alone.
        def alone(b, carry):
            first, n_pages = live(b)

            @pl.when(first >= n_pages)
            def _():
                reset()
                merge_block(b)

            return carry

        jax.lax.fori_loop(0, nb, alone, 0)

    first_slot, first_page = first_live(jnp.int32(0))
    fb, fi = first_slot, first_page
    for buf in range(depth):  # fill the ring
        fetch(fb, fi, buf)
        fb, fi = advance(fb, fi)

    def attend_next(carry):
        j, cb, ci, fb, fi = carry  # pages done; compute and fetch cursors
        buf = j % depth
        first, n_pages = live(cb)
        pos = pos_ref[cb]

        @pl.when(ci == first)
        def _init():
            reset()

        for copy in copies(cb, ci, buf):
            copy.wait()
        attend(
            q_ref[cb], k_buf[buf], v_buf[buf], pos,
            jnp.where(win > 0, pos + 1 - win, 0),  # first in-window position
            ci * page_size,
        )
        fetch(fb, fi, buf)  # the buffer is free again

        @pl.when(ci + 1 >= n_pages)
        def _finalize():
            if rows > 1:
                merge_block(cb)
                return
            # Merge the new token as one extra column (always valid — it
            # is the query's own position, inside any window), normalize.
            q = q_ref[cb].astype(jnp.float32) * scale  # [H, D]
            kn = kn_ref[cb].astype(jnp.float32)
            vn = vn_ref[cb].astype(jnp.float32)
            s_new = jnp.sum(q * kn, axis=-1, keepdims=True)  # [H, 1]
            if logit_softcap is not None:
                s_new = jnp.tanh(s_new / logit_softcap) * logit_softcap
            m_prev = m_ref[:]
            m_fin = jnp.maximum(m_prev, s_new)
            p = jnp.exp(s_new - m_fin)
            alpha = jnp.exp(m_prev - m_fin)
            l_fin = l_ref[:] * alpha + p
            acc_fin = acc_ref[:] * alpha + p * vn
            out = acc_fin / jnp.maximum(l_fin, 1e-30)  # [H, D]
            o_ref[cb] = out.astype(o_ref.dtype)

        return (j + 1, *advance(cb, ci), *advance(fb, fi))

    jax.lax.while_loop(
        lambda carry: carry[1] < nb, attend_next,
        (jnp.int32(0), first_slot, first_page, fb, fi),
    )


# The ring of page buffers: as many pages of K and V as fit in this many
# bytes of VMEM, 2 to 8. One page ahead hides a copy's latency; alone on a
# v5e 2, 4 and 8 pages read the same within 2% (PR 28).
_FUSED_RING_BYTES = 1 << 20


def fused_ring_depth(page: int, kvh: int, d: int, itemsize: int) -> int:
    """Pages the fetch cursor runs ahead, from the shapes the kernel sees
    (Mistral's 128 KiB page: 4; Gemma-2's 256 KiB: 2; a tp=4 shard's 32
    KiB: 8)."""
    return max(2, min(8, _FUSED_RING_BYTES // (2 * page * kvh * d * itemsize)))


# The columns a block's new rows are padded to: one lane tile, so the dots
# that merge them have the shapes the dots over a page have.
_NEW_ROW_COLS = 128


@functools.partial(
    jax.jit,
    static_argnames=("scale", "logit_softcap", "interpret"),
)
def _paged_pallas_stacked(
    q,  # [B, KVH, G, D]; a block of R rows: [B, KVH, R * G, D]
    k_pages,  # [NL, P, page, KVH, D] FULL stacked pool
    v_pages,
    k_new,  # [B, KVH, D]; a block of R rows: [B, R, KVH, D]
    v_new,
    block_tables,  # [B, MP]
    positions,  # [B] old lengths
    window,  # [1] int32
    layer,  # [1] int32
    *,
    scale: float,
    logit_softcap: float | None,
    interpret: bool,
):
    b, kvh, g, d = q.shape
    h = kvh * g
    _, _, page, _, _ = k_pages.shape
    depth = fused_ring_depth(page, kvh, d, k_pages.dtype.itemsize)
    rows = k_new.shape[1] if k_new.ndim == 4 else 1
    if rows == 1:
        # The new token's K and V once per query head, so the kernel merges
        # it row by row.
        new = [jnp.repeat(x, g, axis=1) for x in (k_new, v_new)]
    else:
        # The block's rows as a page would hold them ([token, head] rows of
        # D), padded with rows the kernel masks.
        pad = -(-max(_NEW_ROW_COLS, rows * kvh) // kvh) - rows
        new = [
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, -1, d)
            for x in (k_new, v_new)
        ]

    kernel = functools.partial(
        _paged_fused_kernel,
        page_size=page,
        kvh=kvh,
        group=g,
        depth=depth,
        rows=rows,
        scale=scale,
        logit_softcap=logit_softcap,
    )
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(1,),
        in_specs=[in_vmem, in_vmem, in_vmem, in_place, in_place],
        out_specs=in_vmem,
        scratch_shapes=[
            pltpu.VMEM((depth, page, kvh, d), k_pages.dtype),
            pltpu.VMEM((depth, page, kvh, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        block_tables, positions, window, layer,
        # Query heads as rows [H, D], kv-head major.
        q.reshape(b, h, d), *new, k_pages, v_pages,
    )
    return out.reshape(b, kvh, g, d)


def ref_paged_decode_attention_fused(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D] stacked pools
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, KVH, D]
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    positions: jnp.ndarray,  # [B] OLD lengths (new token's position)
    layer: jnp.ndarray,  # scalar int32
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Reference semantics for the fused kernel: attention over the
    resident pages of `layer` PLUS the new token as an explicit extra
    column at position `positions`. Bit-equivalent (up to fp reorder) to
    scatter-then-attend with lengths = positions + 1."""
    b, h, d = q.shape
    kvh = k_pages.shape[3]
    kp = jax.lax.dynamic_index_in_dim(
        k_pages, layer, axis=0, keepdims=False
    )
    vp = jax.lax.dynamic_index_in_dim(
        v_pages, layer, axis=0, keepdims=False
    )
    bt = jnp.maximum(block_tables, 0)
    k = kp[bt]  # [B, MP, page, KVH, D]
    v = vp[bt]
    mp, page = k.shape[1], k.shape[2]
    L = mp * page
    k = k.reshape(b, L, kvh, d)
    v = v.reshape(b, L, kvh, d)
    # Append the new token as column L.
    k = jnp.concatenate([k, k_new[:, None].astype(k.dtype)], axis=1)
    v = jnp.concatenate([v, v_new[:, None].astype(v.dtype)], axis=1)
    scale = scale if scale is not None else d ** -0.5
    qg = (q * scale).reshape(b, kvh, h // kvh, d)
    logits = jnp.einsum(
        "bkgd,blkd->bkgl", qg.astype(jnp.float32), k.astype(jnp.float32)
    )
    if logit_softcap is not None:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    col = jnp.arange(L + 1)
    # Columns < positions are old tokens (none for a slot that holds no
    # page, whatever its position says); column L is the new token.
    old = (col[None, :] < positions[:, None]) & (block_tables[:, :1] >= 0)
    mask = old | (col[None, :] == L)
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        lengths = positions + 1
        in_win = (win <= 0) | (col[None, :] >= lengths[:, None] - win)
        mask = mask & (in_win | (col[None, :] == L))
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgl,blkd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_decode_attention_fused(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D] stacked pools
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, KVH, D]
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    positions: jnp.ndarray,  # [B] OLD lengths
    layer: jnp.ndarray | int,  # layer index into the stacked pool
    *,
    scale: float | None = None,
    logit_softcap: float | None = None,
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Fused paged decode attention: reads the layer's resident pages
    straight out of the STACKED pool (no per-layer slice materialization)
    and folds the not-yet-scattered new token in as an extra column, so
    the caller can defer all KV-cache writes to one batched scatter after
    the layer scan. Dispatched like paged_decode_attention."""
    b, h, d = q.shape
    kvh = k_pages.shape[3]
    scale = scale if scale is not None else d ** -0.5
    layer_arr = jnp.asarray(layer, jnp.int32)
    mode = dispatch.kernel_mode()
    if mode == "reference":
        return ref_paged_decode_attention_fused(
            q, k_pages, v_pages, k_new, v_new, block_tables, positions,
            layer_arr, scale=scale, logit_softcap=logit_softcap,
            window=window,
        )
    _check_page_size(k_pages.shape[2])
    call = dispatch.over_kv_heads(
        functools.partial(
            _paged_pallas_stacked, scale=scale, logit_softcap=logit_softcap,
            interpret=mode == "interpret",
        ),
        kvh, (1, 3, 3, 1, 1, None, None, None, None),
    )
    out = call(
        q.reshape(b, kvh, h // kvh, d), k_pages, v_pages, k_new, v_new,
        block_tables, positions, _window_arg(window), layer_arr.reshape(1),
    )
    return out.reshape(b, h, d)


def ref_paged_block_attention_fused(
    q: jnp.ndarray,  # [B, R, H, D] a block of R new positions a slot
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D] stacked pools
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, R, KVH, D] the block's own K (not in the pool)
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    positions: jnp.ndarray,  # [B] OLD lengths (the block's first position)
    layer: jnp.ndarray,  # scalar int32
    *,
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference semantics of the fused kernel at R rows a slot: every row
    of the block attends the resident pages of `layer` below `positions`
    and all R rows of the block itself (full inside a block)."""
    b, r, h, d = q.shape
    kvh = k_pages.shape[3]
    kp = jax.lax.dynamic_index_in_dim(k_pages, layer, axis=0, keepdims=False)
    vp = jax.lax.dynamic_index_in_dim(v_pages, layer, axis=0, keepdims=False)
    bt = jnp.maximum(block_tables, 0)
    k = kp[bt].reshape(b, -1, kvh, d)  # [B, L, KVH, D]
    v = vp[bt].reshape(b, -1, kvh, d)
    L = k.shape[1]
    k = jnp.concatenate([k, k_new.astype(k.dtype)], axis=1)
    v = jnp.concatenate([v, v_new.astype(v.dtype)], axis=1)
    scale = scale if scale is not None else d ** -0.5
    qg = (q * scale).reshape(b, r, kvh, h // kvh, d)
    logits = jnp.einsum(
        "bqkgd,blkd->bkgql", qg.astype(jnp.float32), k.astype(jnp.float32)
    )
    col = jnp.arange(L + r)
    old = (col[None, :] < positions[:, None]) & (block_tables[:, :1] >= 0)
    mask = old | (col[None, :] >= L)
    logits = jnp.where(mask[:, None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgql,blkd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, r, h, d).astype(q.dtype)


def paged_block_attention_fused(
    q: jnp.ndarray,  # [B, R, H, D]
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D] stacked pools
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, R, KVH, D]
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MP]
    positions: jnp.ndarray,  # [B] OLD lengths (the block's first position)
    layer: jnp.ndarray | int,
    *,
    scale: float | None = None,
) -> jnp.ndarray:
    """The fused decode attention at R new rows a slot (a block that is
    full inside itself): the stacked pool read in place by the same kernel,
    the rows of a block folded into its query group. Dispatched like
    paged_decode_attention_fused; the pool stays read-only."""
    b, r, h, d = q.shape
    if r == 1:  # one new token a slot: today's program
        return paged_decode_attention_fused(
            q[:, 0], k_pages, v_pages, k_new[:, 0], v_new[:, 0],
            block_tables, positions, layer, scale=scale,
        )[:, None]
    kvh = k_pages.shape[3]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    layer_arr = jnp.asarray(layer, jnp.int32)
    mode = dispatch.kernel_mode()
    if mode == "reference":
        return ref_paged_block_attention_fused(
            q, k_pages, v_pages, k_new, v_new, block_tables, positions,
            layer_arr, scale=scale,
        )
    _check_page_size(k_pages.shape[2])
    call = dispatch.over_kv_heads(
        functools.partial(
            _paged_pallas_stacked, scale=scale, logit_softcap=None,
            interpret=mode == "interpret",
        ),
        kvh, (1, 3, 3, 2, 2, None, None, None, None),
    )
    # [B, R, KVH, G, D] -> [B, KVH, R * G, D]: a KV head's R x G query rows.
    qk = jnp.moveaxis(q.reshape(b, r, kvh, g, d), 1, 2).reshape(b, kvh, r * g, d)
    out = call(
        qk, k_pages, v_pages, k_new, v_new, block_tables, positions,
        _window_arg(None), layer_arr.reshape(1),
    )
    return jnp.moveaxis(out.reshape(b, kvh, r, g, d), 2, 1).reshape(b, r, h, d)


# ---- paged cache writes (decode + admission) ---------------------------------


def token_page_coords(
    block_tables: jnp.ndarray,  # [B, MP]
    positions: jnp.ndarray,  # [B] absolute position of the new token
    page_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(page_ids [B], offsets [B]) for one new token per slot. Unallocated
    entries (-1) AND positions past the block table (a speculative window
    can poke beyond max_seq_len near the context end — jnp gather CLAMPS
    out-of-bounds indices, which would silently hit a live page) map to
    the reserved scratch page 0."""
    mp = block_tables.shape[1]
    slot_idx = jnp.arange(block_tables.shape[0])
    pidx = positions // page_size
    page_ids = block_tables[slot_idx, jnp.minimum(pidx, mp - 1)]
    page_ids = jnp.where(pidx < mp, page_ids, -1)
    return jnp.maximum(page_ids, 0), positions % page_size


def scatter_decode_token(
    k_pages: jnp.ndarray,  # [P, page, KVH, D] (one layer)
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, KVH, D]
    v_new: jnp.ndarray,
    page_ids: jnp.ndarray,  # [B]
    offsets: jnp.ndarray,  # [B]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write one token per slot through the block tables (decode step).
    Quantized pools quantize-on-append: each new row gets its own scale,
    so resident tokens are never re-scaled (pages stay immutable)."""
    from kubeai_tpu.ops.kv_quant import is_quantized_kv, quantize_kv

    if is_quantized_kv(k_pages):
        k8, ks = quantize_kv(k_new)
        v8, vs = quantize_kv(v_new)
        return (
            {
                "q8": k_pages["q8"].at[page_ids, offsets].set(k8),
                "scale": k_pages["scale"].at[page_ids, offsets].set(ks),
            },
            {
                "q8": v_pages["q8"].at[page_ids, offsets].set(v8),
                "scale": v_pages["scale"].at[page_ids, offsets].set(vs),
            },
        )
    k_pages = k_pages.at[page_ids, offsets].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[page_ids, offsets].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def batched_sequence_page_coords(
    bt_rows: jnp.ndarray,  # [A, MP] block-table rows (one per admission)
    lengths: jnp.ndarray,  # [A] true lengths
    seq_len: int,  # padded (bucket) length
    page_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(page_ids [A, S], offsets [A, S]) for prefilled sequences. Padded
    tail positions (>= length) and unallocated entries (-1) write into
    the reserved scratch page 0."""
    pos = jnp.arange(seq_len)
    page_ids = jnp.maximum(bt_rows[:, pos // page_size], 0)
    page_ids = jnp.where(pos[None, :] < lengths[:, None], page_ids, 0)
    return page_ids, jnp.broadcast_to(pos % page_size, page_ids.shape)


def _write_token_rows(pool, rows, page_ids, offsets):
    """pool[l, page_ids[i], offsets[i]] = rows[l, i] for every layer l and
    every index i of `page_ids` (of any rank), in the one of two forms that
    keeps the pool in the row-major layout it arrives in and the attention
    kernel reads (PERF.md section 6, PR 35).

    Where one shard's [KVH, D] rows fill the TPU's (8, 128) tile, the layer
    is a slice: a window is [NL, KVH, D], few and large. Where they do not
    (2 KV heads a chip: Mixtral at tp=4) the compiler would put the layers
    of such a window next to D to fill its tile, carry the pool round the
    decode chunk in that layout and copy it whole to the row-major one every
    decode step; there the layer is an INDEX and a window one token's row.
    That form costs 65-95 ns a window on a v5e, 16 times as many of them,
    which is why it is not the only one."""
    mesh = jax.sharding.get_abstract_mesh()
    kvh = pool.shape[3]
    axis = None if mesh.empty else kv_heads_axis(mesh.shape, kvh)
    heads_a_shard = kvh // mesh.shape[axis] if axis else kvh
    rows = rows.astype(pool.dtype)
    if heads_a_shard % 8 == 0:
        return pool.at[:, page_ids, offsets].set(rows)
    layers = jnp.arange(pool.shape[0]).reshape((-1,) + (1,) * page_ids.ndim)
    return pool.at[layers, page_ids[None], offsets[None]].set(rows)


def _write_quantized_rows(pool, q8, scale, page_ids, offsets):
    """The same write into an int8 pool and its per-row scales."""
    return {
        "q8": _write_token_rows(pool["q8"], q8, page_ids, offsets),
        "scale": _write_token_rows(pool["scale"], scale, page_ids, offsets),
    }


def batched_scatter_sequence(
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D]
    v_pages: jnp.ndarray,
    k_seq: jnp.ndarray,  # [NL, A, S, KVH, D]
    v_seq: jnp.ndarray,
    page_ids: jnp.ndarray,  # [A, S]
    offsets: jnp.ndarray,  # [A, S]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write A prefilled sequences through their block tables in one
    static-shape scatter (batched admission). Quantized pools quantize
    each token row on the way in (prefill output is bf16)."""
    from kubeai_tpu.ops.kv_quant import is_quantized_kv, quantize_kv

    if is_quantized_kv(k_pages):
        k8, ks = quantize_kv(k_seq)
        v8, vs = quantize_kv(v_seq)
        return (
            _write_quantized_rows(k_pages, k8, ks, page_ids, offsets),
            _write_quantized_rows(v_pages, v8, vs, page_ids, offsets),
        )
    return (
        _write_token_rows(k_pages, k_seq, page_ids, offsets),
        _write_token_rows(v_pages, v_seq, page_ids, offsets),
    )


# ---- a window layer's ring ----------------------------------------------------
#
# A layer that attends the last `window` positions keeps, a slot, a ring of
# `ring_pages` pages in a pool of its own, however long the sequence: the
# page that holds positions `[p * page, (p + 1) * page)` is ring page `p %
# ring`, pool page `1 + slot * ring + p % ring` (page 0 is scratch, as in the
# global pool). `ring` is the most pages a window can touch, so the page a
# new token opens holds only positions that no later query sees. A slot's
# ring is fixed when the pool is built: nothing allocates on the step's path,
# and the block table below is a function of the slot alone.


def ring_pages(window: int, page_size: int) -> int:
    """Pages of a slot's ring: the most that `window` consecutive positions
    touch (`window / page_size + 1` where the page divides the window)."""
    return -(-(window - 1) // page_size) + 1


def ring_block_tables(block_tables: jnp.ndarray, ring: int) -> jnp.ndarray:
    """The window pool's block tables, in the global pool's form ([B, MP],
    entry p = the pool page that holds positions `p * page ..`, wrapped), so
    the kernels read a ring as they read a page list: with the window as
    their argument they start at the first in-window page and read at most
    `ring` pages a slot. A slot that holds no global page (its row starts
    with -1: freed, or never admitted) holds no ring page either."""
    b, mp = block_tables.shape
    pages = (
        1 + jnp.arange(b, dtype=jnp.int32)[:, None] * ring
        + jnp.arange(mp, dtype=jnp.int32)[None, :] % ring
    )
    return jnp.where(block_tables[:, :1] >= 0, pages, -1)


def ring_scatter_sequence(
    k_ring: jnp.ndarray,  # [NL, 1 + slots * ring, page, KVH, D]
    v_ring: jnp.ndarray,
    k_seq: jnp.ndarray,  # [NL, A, S, KVH, D] prefilled (padded) sequences
    v_seq: jnp.ndarray,
    slots: jnp.ndarray,  # [A] the rows' slots (>= slots: a padding row)
    lengths: jnp.ndarray,  # [A] true lengths
    ring: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write the positions of A prefilled sequences that stay in their
    slots' rings: the last `ring` pages of each (whole pages, so the tail is
    `ring * page` positions of the S computed, and its live targets are
    distinct). Positions at or past a row's length, and padding rows, go to
    scratch page 0."""
    page = k_ring.shape[2]
    num_slots = (k_ring.shape[1] - 1) // ring
    S = k_seq.shape[2]
    T = min(S, ring * page)
    last = (jnp.maximum(lengths, 1) - 1) // page  # the page of the last token
    start = jnp.maximum(last - ring + 1, 0) * page  # [A]
    pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [A, T]
    live = (pos < lengths[:, None]) & (slots[:, None] < num_slots)
    at = jnp.minimum(pos, S - 1)[None, :, :, None, None]
    page_ids = jnp.where(
        live, 1 + slots[:, None] * ring + (pos // page) % ring, 0
    )
    return batched_scatter_sequence(
        k_ring, v_ring,
        jnp.take_along_axis(k_seq, at, axis=2),
        jnp.take_along_axis(v_seq, at, axis=2),
        page_ids, pos % page,
    )


def sequence_page_coords(
    bt_row: jnp.ndarray,  # [MP] the slot's block-table row
    length: jnp.ndarray,  # scalar true length
    seq_len: int,  # padded (bucket) length
    page_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-sequence view of batched_sequence_page_coords."""
    ids, offs = batched_sequence_page_coords(
        bt_row[None], jnp.asarray(length)[None], seq_len, page_size
    )
    return ids[0], offs[0]


def scatter_sequence(
    k_pages: jnp.ndarray,  # [NL, P, page, KVH, D]
    v_pages: jnp.ndarray,
    k_seq: jnp.ndarray,  # [NL, S, KVH, D]
    v_seq: jnp.ndarray,
    page_ids: jnp.ndarray,  # [S]
    offsets: jnp.ndarray,  # [S]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-sequence view of batched_scatter_sequence."""
    return batched_scatter_sequence(
        k_pages, v_pages, k_seq[:, None], v_seq[:, None],
        page_ids[None], offsets[None],
    )


def scatter_sequence_prequantized(
    k_pages: dict,  # quantized pools {"q8", "scale"}
    v_pages: dict,
    k8_seq: jnp.ndarray,  # [NL, S, KVH, D] int8 — wire bytes, verbatim
    ks_seq: jnp.ndarray,  # [NL, S, KVH] f32 scales
    v8_seq: jnp.ndarray,
    vs_seq: jnp.ndarray,
    page_ids: jnp.ndarray,  # [S]
    offsets: jnp.ndarray,  # [S]
) -> tuple[dict, dict]:
    """Scatter ALREADY-QUANTIZED rows (a KV handoff import): the int8
    values and their scales pass through untouched — re-quantizing would
    break the byte-identity a quantized handoff round-trip guarantees."""
    return (
        _write_quantized_rows(k_pages, k8_seq, ks_seq, page_ids, offsets),
        _write_quantized_rows(v_pages, v8_seq, vs_seq, page_ids, offsets),
    )
