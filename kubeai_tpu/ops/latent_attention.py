"""Attention against a LATENT page pool (multi-head latent attention, MLA).

A latent layer keeps, a token, ONE row: the compressed key-value `c` (`rank`
numbers, normed) and the key part every head shares (`rope` numbers; the name
is the architecture's, a NoPE model rotates nothing), from which every head's
key and value are linear maps: `k_h = [W_k,h c, kpe]`, `v_h = W_v,h c`. The
pool is `[layers, pages, page, W]`, one row a token, `W = rank + rope` padded
up to the 128 lanes (576 -> 640: an array whose minor dimension is 576 is
held in 640 on a TPU whatever it says, so the pad costs no byte that a
576-wide pool would save, and every row is one aligned copy). Pad lanes are
zero in the rows and in the queries.

**Decode** reads the pool in the ABSORBED form: head h's score against a row
is `q_nope,h . W_k,h c + q_pe,h . kpe = [W_k,h^T q_nope,h, q_pe,h] . row`, so
with the query folded through `W_k` every head attends the SAME rows (one
shared key of `W`, 32 query heads), and the weighted sum of the rows' first
`rank` numbers goes through `W_v` afterwards. A step reads each live page
once a layer: ONE kernel invocation walks the slots that have any (the
page-pool kernel's two cursors, `ops/paged_attention.py`), and its unit of
work is a BLOCK of `block_pages` consecutive pages of one slot: up to that
many page copies (a slot's pages are not neighbours in the pool) into ONE
entry `[G * page, W]` of a ring of VMEM buffers, attended as one matrix,
scores and values both taken from it. A turn of the walk is a latency chain
(copy wait, scores, maximum, exp, values, one update of the sums: 0.40 us
on a v5e, where a 64-row page streams in 0.125) and a page of 64 rows is
half a tile of the matrix unit to either product; a block shares the chain
and fills the tiles (PERF.md section 6, PR 51 has the kernel alone by `G`,
and `_BLOCK_COLS` below what was chosen from it). The attend is
this kernel's own and not the page pool's: one shared key row for all heads
and no head mask, window or softcap, where that one masks `page x KV heads`
columns that are full tiles already. A slot's last block fetches its live
pages only; the rest of its columns are masked. The new token's row is not
in the pool yet: it is merged as one more column when a slot's blocks are
done, and the family writes the rows of all its latent layers in one scatter
after the layer scan (`write_latent_rows`).

**Prefill** uses the expanded form (keys of `nope + rope`, values of their
own width) through `ops/attention.py:prefill_attention`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubeai_tpu.ops import dispatch

LANES = 128
NEG_INF = -1e30
# The decode kernel's ring in VMEM. An entry is a BLOCK: `block_pages` pages
# of one slot side by side, `[G * page, W]`, attended as one matrix. A block
# is sized by its columns, `_BLOCK_COLS` rows of the pool whatever the page,
# and the ring holds as many blocks as fit in `_RING_BYTES`, two at least, so
# that the next block's copies run under this one's products (640 KiB a block
# and 3 blocks at the 48B configuration's 64 x 640 bf16 pages). Alone on a
# v5e at that configuration (PR 51, PERF.md section 6): 0.53 us a 64-row page
# at one page a block (a page is half a tile of the matrix unit to both
# products and the compiler pads it to a whole one), 0.33 at 2, 0.22 at 4,
# 0.17 at 8 and 0.16 at 16, which was not worth twice the ring and emptier
# last blocks; 3 or 6 blocks in the ring read the same.
_RING_BYTES = 2 << 20
_BLOCK_COLS = 512


def latent_row_width(rank: int, rope: int) -> int:
    """What a token's row is stored in: `rank + rope` up to whole lanes."""
    return -(-(rank + rope) // LANES) * LANES


def write_latent_rows(pool, rows, page_ids, offsets):
    """pool[l, page_ids[i], offsets[i]] = rows[l, i] for every latent layer
    l and every index i of `page_ids` (of any rank): `pool` [layers, pages,
    page, W], `rows` [layers, *page_ids.shape, W]. The layer is an INDEX, so
    that a window of the scatter is one token's row: as a slice (`[:,
    page_ids, offsets]`, a window of [layers, W]) the compiler laid the pool
    out with its 3 layers next to W to fill a tile, carried it round the
    decode chunk so and copied it whole, 2 GB, to the row-major layout the
    kernel reads every step (AOT, PR 50; `ops/paged_attention.py:
    _write_token_rows` met the same at 2 KV heads a shard)."""
    layers = jnp.arange(pool.shape[0]).reshape((-1,) + (1,) * page_ids.ndim)
    return pool.at[layers, page_ids[None], offsets[None]].set(
        rows.astype(pool.dtype))


def ref_latent_decode_attention(
    q, pool, new, block_tables, positions, layer, *, scale: float, rank: int
):
    """The decode attention in `jnp`: `q` [B, H, W] (absorbed, pad lanes
    zero), `pool` [layers, pages, page, W], `new` [B, W] the new token's
    rows, `positions` [B] the OLD lengths. Returns [B, H, rank]: the
    softmax-weighted sum of the first `rank` numbers of a slot's resident
    rows and its new one."""
    b = q.shape[0]
    rows = jax.lax.dynamic_index_in_dim(pool, layer, axis=0, keepdims=False)
    rows = rows[jnp.maximum(block_tables, 0)]  # [B, MP, page, W]
    L = rows.shape[1] * rows.shape[2]
    rows = jnp.concatenate(
        [rows.reshape(b, L, -1), new[:, None].astype(rows.dtype)], axis=1
    ).astype(jnp.float32)
    logits = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32) * scale, rows)
    col = jnp.arange(L + 1)
    # Columns < positions are old tokens (none for a slot that holds no
    # page); column L is the new token.
    old = (col[None, :] < positions[:, None]) & (block_tables[:, :1] >= 0)
    mask = old | (col[None, :] == L)
    probs = jax.nn.softmax(jnp.where(mask[:, None], logits, NEG_INF), axis=-1)
    out = jnp.einsum("bhl,blr->bhr", probs, rows[..., :rank])
    return out.astype(q.dtype)


def block_pages(page: int, width: int, itemsize: int, max_pages: int) -> int:
    """Pages of one slot the decode kernel attends as ONE block, from the
    shapes it sees: as many as make `_BLOCK_COLS` columns, in a ring that
    keeps two blocks at least, of a block table `max_pages` wide."""
    fit = _RING_BYTES // (2 * page * width * itemsize)
    return max(1, min(_BLOCK_COLS // page, fit, max_pages))


def _latent_decode_kernel(
    # scalar-prefetch
    bt_ref,  # [B, MP] int32 block tables
    pos_ref,  # [B] int32 OLD lengths
    layer_ref,  # [1] int32 layer of the stacked pool
    # whole arrays in VMEM
    q_ref,  # [B, H, W] absorbed queries
    new_ref,  # [B, 1, W] the new token's rows (a slot a leading index)
    # the pool where it lies (HBM)
    pool_hbm,  # [NL, P, page, W]
    o_ref,  # [B, H, rank] VMEM
    # scratch
    buf,  # [depth, G * page, W] the ring: an entry is a block of G pages
    sems,  # DMA semaphores [depth, G], one a page copy
    m_ref,  # [H, 1] f32, the slot being attended
    l_ref,  # [H, 1] f32
    acc_ref,  # [H, rank] f32
    *,
    page_size: int,
    block: int,
    depth: int,
    scale: float,
    rank: int,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from kubeai_tpu.ops.paged_attention import (
        _advance, _first_live, _split_bf16,
    )

    nb, mp = bt_ref.shape
    layer = layer_ref[0]
    cols = block * page_size

    def pages(b):
        """Slot b's pages [0, n) hold its old tokens (none where its
        block-table row starts with -1, whatever its position says)."""
        return jnp.where(
            bt_ref[b, 0] < 0, 0,
            jnp.minimum(pl.cdiv(pos_ref[b], page_size), mp),
        )

    def live(b):
        """What the cursors walk: slot b's blocks [0, cdiv(pages, G))."""
        return 0, pl.cdiv(pages(b), block)

    first_live = functools.partial(_first_live, live, nb)
    advance = functools.partial(_advance, live, nb)

    def copies(b, i, entry):
        """(page is live, its copy) for the G pages of slot b's block i. The
        pages of a slot are not neighbours in the pool: one copy a page, and
        a last block's pages past the slot's last are neither started nor
        waited for."""
        b = jnp.minimum(b, nb - 1)
        n = pages(b)
        for g in range(block):
            at = i * block + g
            page_id = jnp.maximum(bt_ref[b, jnp.minimum(at, mp - 1)], 0)
            yield at < n, pltpu.make_async_copy(
                pool_hbm.at[layer, page_id],
                buf.at[entry, pl.ds(g * page_size, page_size)],
                sems.at[entry, g],
            )

    def fetch(b, i, entry):
        for is_live, copy in copies(b, i, entry):
            pl.when((b < nb) & is_live)(copy.start)

    def attend(q, rows, pos, off):
        """One online-softmax update of all heads over one block: `rows` its
        [G * page, W] matrix (row = token; scores and values both read from
        it), `q` the slot's [H, W] queries, `off` the block's first position.
        One shared key "head": every query head sees every column. Operands
        enter the products as stored (bf16 x bf16 is exact in the f32
        accumulator), p stays f32 as three bf16 pieces, and the values'
        product runs over the `rank` lanes that are returned."""
        if q.dtype != rows.dtype:
            q, rows = q.astype(jnp.float32), rows.astype(jnp.float32)
        precision = (
            jax.lax.Precision.DEFAULT if rows.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST
        )
        heads = q.shape[0]
        col = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32,
        ) * scale  # [H, G * page]
        # Old tokens only; a page that was not fetched lies past them.
        s = jnp.where(col < pos - off, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        values = rows[:, :rank]
        if values.dtype == jnp.bfloat16:
            pv = jnp.dot(
                jnp.concatenate(_split_bf16(p), axis=0), values,
                precision=precision, preferred_element_type=jnp.float32,
            )  # [3H, rank]: one pass of the rows for the three pieces
            pv = pv[:heads] + pv[heads:2 * heads] + pv[2 * heads:]
        else:
            pv = jnp.dot(
                p, values, precision=precision,
                preferred_element_type=jnp.float32,
            )
        acc_ref[:] = acc_ref[:] * alpha + pv

    # A masked column's p is 0, and 0 x what VMEM held is 0 only if that is
    # finite: a last block's unfetched pages read what the ring held before.
    buf[...] = jnp.zeros_like(buf)
    first_slot, first_block = first_live(jnp.int32(0))
    fb, fi = first_slot, first_block
    for entry in range(depth):  # fill the ring
        fetch(fb, fi, entry)
        fb, fi = advance(fb, fi)

    def attend_next(carry):
        j, cb, ci, fb, fi = carry  # blocks done; compute and fetch cursors
        entry = j % depth
        _, n_blocks = live(cb)

        @pl.when(ci == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        for is_live, copy in copies(cb, ci, entry):
            pl.when(is_live)(copy.wait)
        attend(q_ref[cb], buf[entry], pos_ref[cb], ci * cols)
        fetch(fb, fi, entry)  # the entry is free again

        @pl.when(ci + 1 >= n_blocks)
        def _finalize():
            # The new token's row as one more column, then normalize.
            q = q_ref[cb].astype(jnp.float32) * scale  # [H, W]
            row = new_ref[cb].astype(jnp.float32)  # [1, W]
            s_new = jnp.sum(q * row, axis=-1, keepdims=True)  # [H, 1]
            m_prev = m_ref[:]
            m_fin = jnp.maximum(m_prev, s_new)
            p = jnp.exp(s_new - m_fin)
            alpha = jnp.exp(m_prev - m_fin)
            l_fin = l_ref[:] * alpha + p
            out = acc_ref[:] * alpha + p * row[:, :rank]
            o_ref[cb] = (out / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)

        return (j + 1, *advance(cb, ci), *advance(fb, fi))

    jax.lax.while_loop(
        lambda carry: carry[1] < nb, attend_next,
        (jnp.int32(0), first_slot, first_block, fb, fi),
    )


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _latent_decode_pallas(
    q, pool, new, block_tables, positions, layer, *, scale, rank, interpret
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, w = q.shape
    page = pool.shape[2]
    block = block_pages(page, w, pool.dtype.itemsize, block_tables.shape[1])
    depth = max(
        2, min(8, _RING_BYTES // (block * page * w * pool.dtype.itemsize)))
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, page_size=page, block=block, depth=depth,
            scale=scale, rank=rank,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[in_vmem, in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=in_vmem,
            scratch_shapes=[
                pltpu.VMEM((depth, block * page, w), pool.dtype),
                pltpu.SemaphoreType.DMA((depth, block)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(block_tables, positions, layer, q, new[:, None], pool)
    # A slot that holds no page attends its new token alone: a softmax over
    # one column is 1 (the kernel does not visit such a slot).
    alone = (block_tables[:, 0] < 0) | (positions <= 0)
    return jnp.where(
        alone[:, None, None], new[:, None, :rank].astype(out.dtype), out
    )


def latent_decode_attention(
    q, pool, new, block_tables, positions, layer, *, scale: float, rank: int
):
    """One new token a slot against the resident rows of `layer` of the
    stacked pool, read in place, plus the not-yet-written new row (see
    `ref_latent_decode_attention` for the arguments). Dispatched like the
    page pool's decode attention (`ops/dispatch.py`)."""
    layer = jnp.asarray(layer, jnp.int32)
    mode = dispatch.kernel_mode()
    if mode == "reference":
        return ref_latent_decode_attention(
            q, pool, new, block_tables, positions, layer, scale=scale,
            rank=rank,
        )
    from kubeai_tpu.ops.paged_attention import _check_page_size

    _check_page_size(pool.shape[2])
    call = dispatch.on_every_device(
        functools.partial(
            _latent_decode_pallas, scale=scale, rank=rank,
            interpret=mode == "interpret",
        ),
        n_in=6, n_out=1,
    )
    return call(q, pool, new, block_tables, positions, layer.reshape(1))
