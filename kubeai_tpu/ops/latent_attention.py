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
once a layer: ONE kernel invocation walks the live pages of the slots that
have any (the page-pool kernel's walk, `ops/paged_attention.py`), each page
one copy into a ring of VMEM buffers, scores and values both taken from the
buffer. The new token's row is not in the pool yet: it is merged as one more
column when a slot's pages are done, and the family writes the rows of all
its latent layers in one scatter after the layer scan (`write_latent_rows`).

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
_RING_BYTES = 1 << 20  # of page buffers in VMEM, 2 to 8 pages


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
    buf,  # [depth, page, W] the ring
    sems,  # DMA semaphores [depth]
    m_ref,  # [H, 1] f32, the slot being attended
    l_ref,  # [H, 1] f32
    acc_ref,  # [H, W] f32
    *,
    page_size: int,
    depth: int,
    scale: float,
    rank: int,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from kubeai_tpu.ops.paged_attention import (
        _advance, _first_live, _fused_attend_page,
    )

    nb, mp = bt_ref.shape
    heads = q_ref.shape[1]
    layer = layer_ref[0]

    def live(b):
        """Slot b's pages [0, n_pages) hold its old tokens (none where its
        block-table row starts with -1, whatever its position says)."""
        return 0, jnp.where(
            bt_ref[b, 0] < 0, 0,
            jnp.minimum(pl.cdiv(pos_ref[b], page_size), mp),
        )

    first_live = functools.partial(_first_live, live, nb)
    advance = functools.partial(_advance, live, nb)
    # One shared key and value "head" of W: every query head sees every
    # column of a page.
    attend = functools.partial(
        _fused_attend_page, m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref,
        scale=scale, logit_softcap=None, kvh=1, group=heads,
    )

    def copy(b, i, slot):
        page_id = jnp.maximum(bt_ref[jnp.minimum(b, nb - 1), i], 0)
        return pltpu.make_async_copy(
            pool_hbm.at[layer, page_id], buf.at[slot], sems.at[slot]
        )

    def fetch(b, i, slot):
        @pl.when(b < nb)
        def _start():
            copy(b, i, slot).start()

    first_slot, first_page = first_live(jnp.int32(0))
    fb, fi = first_slot, first_page
    for slot in range(depth):  # fill the ring
        fetch(fb, fi, slot)
        fb, fi = advance(fb, fi)

    def attend_next(carry):
        j, cb, ci, fb, fi = carry  # pages done; compute and fetch cursors
        slot = j % depth
        _, n_pages = live(cb)

        @pl.when(ci == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        copy(cb, ci, slot).wait()
        page = buf[slot]  # keys and values both: read once
        attend(q_ref[cb], page, page, pos_ref[cb], 0, ci * page_size)
        fetch(fb, fi, slot)  # the buffer is free again

        @pl.when(ci + 1 >= n_pages)
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
            out = (acc_ref[:] * alpha + p * row) / jnp.maximum(l_fin, 1e-30)
            o_ref[cb] = out[:, :rank].astype(o_ref.dtype)

        return (j + 1, *advance(cb, ci), *advance(fb, fi))

    jax.lax.while_loop(
        lambda carry: carry[1] < nb, attend_next,
        (jnp.int32(0), first_slot, first_page, fb, fi),
    )


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _latent_decode_pallas(
    q, pool, new, block_tables, positions, layer, *, scale, rank, interpret
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, w = q.shape
    page = pool.shape[2]
    depth = max(2, min(8, _RING_BYTES // (page * w * pool.dtype.itemsize)))
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, page_size=page, depth=depth, scale=scale,
            rank=rank,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[in_vmem, in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=in_vmem,
            scratch_shapes=[
                pltpu.VMEM((depth, page, w), pool.dtype),
                pltpu.SemaphoreType.DMA((depth,)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, w), jnp.float32),
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
