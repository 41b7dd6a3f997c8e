"""Paged decode attention: jnp reference vs dense oracle vs Pallas kernel
(interpret mode on CPU)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine.paged_cache import PageAllocator, set_block_table
from kubeai_tpu.ops.attention import decode_attention
from kubeai_tpu.ops.paged_attention import (
    batched_scatter_sequence,
    batched_sequence_page_coords,
    paged_decode_attention,
    paged_decode_attention_fused,
    ref_paged_decode_attention,
    ref_paged_decode_attention_fused,
    scatter_decode_token,
    scatter_sequence,
    scatter_sequence_prequantized,
    sequence_page_coords,
    token_page_coords,
)

B, KVH, G, D, PAGE, MP = 3, 2, 4, 32, 8, 4
H = KVH * G
P = 1 + B * MP  # pool: scratch page 0 + full reservation
L_MAX = MP * PAGE
# Widest error of PR 27's stacked kernel on the bf16 test's inputs.
PARENT_BF16_ERR = 0.00748


@pytest.fixture(autouse=True)
def kernels_interpreted(monkeypatch):
    """The dispatches take the Pallas kernels, interpreted (the `ref_*`
    functions are called by name where a test wants the reference)."""
    from kubeai_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)


def _setup(lengths, seed=0):
    """Build equivalent dense [B, L, KVH, D] caches and paged pools."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_dense = np.zeros((B, L_MAX, KVH, D), np.float32)
    v_dense = np.zeros((B, L_MAX, KVH, D), np.float32)
    k_pages = np.zeros((P, PAGE, KVH, D), np.float32)
    v_pages = np.zeros((P, PAGE, KVH, D), np.float32)
    alloc = PageAllocator(P, PAGE, max_pages_per_slot=MP)
    bt = jnp.full((B, MP), -1, jnp.int32)
    for s, ln in enumerate(lengths):
        pages = alloc.ensure(s, ln)
        bt = set_block_table(bt, s, pages)
        kv = rng.standard_normal((2, ln, KVH, D)).astype(np.float32)
        k_dense[s, :ln] = kv[0]
        v_dense[s, :ln] = kv[1]
        for t in range(ln):
            k_pages[pages[t // PAGE], t % PAGE] = kv[0, t]
            v_pages[pages[t // PAGE], t % PAGE] = kv[1, t]
    return (
        q,
        jnp.asarray(k_dense),
        jnp.asarray(v_dense),
        jnp.asarray(k_pages),
        jnp.asarray(v_pages),
        bt,
        jnp.asarray(lengths, jnp.int32),
    )


def test_reference_matches_dense_oracle():
    q, kd, vd, kp, vp, bt, lengths = _setup([5, 17, 32])
    ref = ref_paged_decode_attention(q, kp, vp, bt, lengths)
    dense = decode_attention(q, kd, vd, lengths)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(dense), atol=1e-5)


def test_kernel_matches_reference():
    q, _, _, kp, vp, bt, lengths = _setup([5, 17, 32])
    got = paged_decode_attention(q, kp, vp, bt, lengths)
    want = ref_paged_decode_attention(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
    )


def test_kernel_softcap_and_window():
    q, kd, vd, kp, vp, bt, lengths = _setup([9, 26, 31], seed=3)
    for cap, win in ((30.0, None), (None, 12), (50.0, 7)):
        got = paged_decode_attention(
            q, kp, vp, bt, lengths,
            logit_softcap=cap, window=win,
        )
        want = ref_paged_decode_attention(
            q, kp, vp, bt, lengths, logit_softcap=cap, window=win
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
        )
        # Window actually changes the result (keys fall out of range).
        if win is not None:
            full = ref_paged_decode_attention(
                q, kp, vp, bt, lengths, logit_softcap=cap
            )
            assert float(jnp.max(jnp.abs(got - full))) > 1e-4


def _fused_setup(old_lengths, n_layers=3, seed=0):
    """Stacked [NL, ...] pools holding each slot's OLD tokens, plus a new
    token's K/V per layer that is NOT yet scattered."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_pages = np.zeros((n_layers, P, PAGE, KVH, D), np.float32)
    v_pages = np.zeros((n_layers, P, PAGE, KVH, D), np.float32)
    alloc = PageAllocator(P, PAGE, max_pages_per_slot=MP)
    bt = jnp.full((B, MP), -1, jnp.int32)
    for s, ln in enumerate(old_lengths):
        pages = alloc.ensure(s, ln + 1)  # room for the new token
        bt = set_block_table(bt, s, pages)
        kv = rng.standard_normal((2, n_layers, ln, KVH, D)).astype(np.float32)
        for t in range(ln):
            k_pages[:, pages[t // PAGE], t % PAGE] = kv[0, :, t]
            v_pages[:, pages[t // PAGE], t % PAGE] = kv[1, :, t]
    k_new = jnp.asarray(rng.standard_normal((B, KVH, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, KVH, D)), jnp.float32)
    return (
        q, jnp.asarray(k_pages), jnp.asarray(v_pages), k_new, v_new, bt,
        jnp.asarray(old_lengths, jnp.int32),
    )


def test_fused_reference_matches_scatter_then_attend():
    """The fused path (pool read-only + new-token column) must equal the
    original scatter-then-attend semantics with lengths = positions+1."""
    q, kp, vp, kn, vn, bt, pos = _fused_setup([5, 17, 30], seed=7)
    for layer in range(kp.shape[0]):
        fused = ref_paged_decode_attention_fused(
            q, kp, vp, kn, vn, bt, pos, jnp.int32(layer)
        )
        pids, offs = token_page_coords(bt, pos, PAGE)
        kl, vl = scatter_decode_token(kp[layer], vp[layer], kn, vn, pids, offs)
        want = ref_paged_decode_attention(q, kl, vl, bt, pos + 1)
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(want), atol=1e-5, rtol=1e-5
        )


def test_fused_kernel_matches_reference():
    q, kp, vp, kn, vn, bt, pos = _fused_setup([5, 17, 30], seed=11)
    for layer in (0, 2):
        got = paged_decode_attention_fused(
            q, kp, vp, kn, vn, bt, pos, layer,
        )
        want = ref_paged_decode_attention_fused(
            q, kp, vp, kn, vn, bt, pos, jnp.int32(layer)
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
        )


@pytest.mark.slow
def test_fused_kernel_softcap_and_window():
    q, kp, vp, kn, vn, bt, pos = _fused_setup([9, 26, 31], seed=13)
    for cap, win in ((30.0, None), (None, 12), (50.0, 7)):
        got = paged_decode_attention_fused(
            q, kp, vp, kn, vn, bt, pos, 1,
            logit_softcap=cap, window=win,
        )
        want = ref_paged_decode_attention_fused(
            q, kp, vp, kn, vn, bt, pos, jnp.int32(1),
            logit_softcap=cap, window=win,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
        )
        # Window semantics must also match scatter-then-attend.
        pids, offs = token_page_coords(bt, pos, PAGE)
        kl, vl = scatter_decode_token(kp[1], vp[1], kn, vn, pids, offs)
        oracle = ref_paged_decode_attention(
            q, kl, vl, bt, pos + 1, logit_softcap=cap, window=win
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(oracle), atol=1e-4, rtol=1e-4
        )


def test_fused_empty_slot_returns_value_of_new_token():
    """A slot with zero old tokens attends only its own new token."""
    q, kp, vp, kn, vn, bt, pos = _fused_setup([0, 8, 3], seed=17)
    out = ref_paged_decode_attention_fused(
        q, kp, vp, kn, vn, bt, pos, jnp.int32(0)
    )
    want0 = jnp.broadcast_to(
        vn[0][:, None, :], (KVH, G, D)
    ).reshape(H, D)
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(want0), atol=1e-5
    )
    got = paged_decode_attention_fused(q, kp, vp, kn, vn, bt, pos, 0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(out), atol=1e-4, rtol=1e-4
    )


def _stacked_inputs(b, kvh, g, d, old_lengths, *, page=8, mp=4, dtype,
                    n_layers=2, seed=0, dead=()):
    """Random stacked pools (every page filled: what lies past a length or
    in nobody's page must not matter), block tables that cover each slot's
    old tokens and its new one, all -1 for the slots in `dead`."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + b * mp

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    bt = np.full((b, mp), -1, np.int32)
    for s, ln in enumerate(old_lengths):
        if s not in dead:
            n = min(-(-(ln + 1) // page), mp)
            bt[s, :n] = 1 + s * mp + np.arange(n)
    return (
        rand(b, kvh * g, d),
        rand(n_layers, n_pages, page, kvh, d),
        rand(n_layers, n_pages, page, kvh, d),
        rand(b, kvh, d), rand(b, kvh, d), jnp.asarray(bt),
        jnp.asarray(old_lengths, jnp.int32),
    )


@pytest.mark.parametrize(
    "cap, win", [(None, None), (30.0, None), (None, 12), (50.0, 7)],
    ids=["plain", "softcap", "window", "softcap-window"],
)
@pytest.mark.parametrize(
    "kvh, g, d", [(8, 4, 128), (2, 4, 128), (8, 2, 256)],
    ids=["mistral", "mixtral-tp4-shard", "gemma2"],
)
def test_stacked_kernel_matches_reference_at_the_served_head_shapes(
    kvh, g, d, cap, win
):
    """KV heads fold into one dot a page: the head counts and widths the
    served families bring (8 x 4 x 128; 2 x 4 x 128 a chip at tp=4;
    8 x 2 x 256 with softcap and a window), at a tiny page."""
    q, kp, vp, kn, vn, bt, pos = _stacked_inputs(
        3, kvh, g, d, [5, 17, 30], dtype=jnp.float32, seed=kvh + d)
    got = paged_decode_attention_fused(
        q, kp, vp, kn, vn, bt, pos, 1, logit_softcap=cap, window=win)
    want = ref_paged_decode_attention_fused(
        q, kp, vp, kn, vn, bt, pos, jnp.int32(1),
        logit_softcap=cap, window=win)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)
    if win is not None:  # keys really fall out of the window
        full = ref_paged_decode_attention_fused(
            q, kp, vp, kn, vn, bt, pos, jnp.int32(1), logit_softcap=cap)
        assert float(jnp.max(jnp.abs(got - full))) > 1e-4


def test_a_slot_that_holds_no_page_attends_only_its_new_token():
    """The engine advances a freed slot's position with everyone's, and
    clears only its block-table row: the row says the slot is dead, so its
    stale position (1,500 here, far past the table) buys no work, leaves
    every live slot's output as it was to the bit, and its own output is
    its new token's value."""
    from kubeai_tpu.ops.paged_attention import _live_page_range

    live = _stacked_inputs(
        3, KVH, G, D, [5, 17, 30], page=PAGE, mp=MP, dtype=jnp.float32,
        seed=21)
    q, kp, vp, kn, vn, bt, pos = _stacked_inputs(
        4, KVH, G, D, [5, 17, 30, 1500], page=PAGE, mp=MP,
        dtype=jnp.float32, seed=22, dead=(3,))
    # The same three live slots, beside a dead fourth.
    q, kn, vn = (x.at[:3].set(y) for x, y in zip((q, kn, vn),
                                                   (live[0], live[3], live[4])))
    kp, vp = (x.at[:, : live[1].shape[1]].set(y)
              for x, y in zip((kp, vp), (live[1], live[2])))
    assert (np.asarray(bt[:3]) == np.asarray(live[5])).all()
    assert (np.asarray(bt[3]) == -1).all() and int(pos[3]) == 1500
    for win in (None, 12):
        without = paged_decode_attention_fused(*live[:6], live[6], 1,
                                               window=win)
        got = paged_decode_attention_fused(q, kp, vp, kn, vn, bt, pos, 1,
                                           window=win)
        np.testing.assert_array_equal(np.asarray(got[:3]),
                                      np.asarray(without))
        own = jnp.broadcast_to(vn[3][:, None, :], (KVH, G, D)).reshape(H, D)
        assert np.isfinite(np.asarray(got[3])).all()
        np.testing.assert_allclose(np.asarray(got[3]), np.asarray(own),
                                   atol=1e-6)
        # The reference reads the row the same way.
        want = ref_paged_decode_attention_fused(
            q, kp, vp, kn, vn, bt, pos, jnp.int32(1), window=win)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
    # The helper both cursors of the kernel ask: no page for the dead slot,
    # the usual range for a live one, never more pages than the table has.
    w = jnp.asarray([0], jnp.int32)
    rng = dict(page_size=PAGE, max_pages=MP)
    assert [int(x) for x in _live_page_range(bt, pos, w, 3, **rng)] == [0, 0]
    assert [int(x) for x in _live_page_range(bt, pos, w, 2, **rng)] == [0, 4]
    w12 = jnp.asarray([12], jnp.int32)  # 30 old tokens, keys from 19 on
    assert [int(x) for x in _live_page_range(bt, pos, w12, 2, **rng)] == [2, 4]
    alive = bt.at[3, 0].set(1)  # the stale position alone: capped at MP
    assert [int(x) for x in _live_page_range(alive, pos, w, 3, **rng)] == [0, MP]


def test_stacked_kernel_on_a_bf16_pool_against_the_f32_reference():
    """The pool the engine serves from: bf16 K and V enter the dots as
    they are stored. Against the reference computed in float32 on the same
    bf16 values, the kernel is as close as the kernel it replaced was on
    these inputs (0.00748 at the widest: the rounding of a bf16 output)."""
    q, kp, vp, kn, vn, bt, pos = _stacked_inputs(
        3, 8, 4, 128, [5, 17, 30], dtype=jnp.bfloat16, seed=31)
    got = paged_decode_attention_fused(q, kp, vp, kn, vn, bt, pos, 1)
    assert got.dtype == jnp.bfloat16
    f32 = [x.astype(jnp.float32) for x in (q, kp, vp, kn, vn)]
    want = ref_paged_decode_attention_fused(*f32, bt, pos, jnp.int32(1))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err <= PARENT_BF16_ERR, err


def test_window_matches_dense_masked_oracle():
    q, kd, vd, kp, vp, bt, lengths = _setup([20, 32, 11], seed=5)
    win = 6
    got = ref_paged_decode_attention(q, kp, vp, bt, lengths, window=win)
    # Dense oracle: zero out everything outside [len-win, len) by masking
    # via lengths on a shifted cache is awkward; recompute with explicit
    # softmax instead.
    b, h, d = q.shape
    qg = (q * (d ** -0.5)).reshape(b, KVH, G, d).astype(jnp.float32)
    logits = jnp.einsum("bkgd,blkd->bkgl", qg, kd.astype(jnp.float32))
    pos = jnp.arange(L_MAX)
    mask = (pos[None, :] < lengths[:, None]) & (
        pos[None, :] >= lengths[:, None] - win
    )
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    want = jnp.einsum(
        "bkgl,blkd->bkgd", jax.nn.softmax(logits, -1),
        vd.astype(jnp.float32),
    ).reshape(b, h, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_scatter_token_roundtrip():
    q, _, _, kp, vp, bt, lengths = _setup([5, 17, 32])
    kp_all = jnp.stack([kp])  # [NL=1, ...] not needed; per-layer API
    rng = np.random.default_rng(7)
    k_new = jnp.asarray(rng.standard_normal((B, KVH, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, KVH, D)), jnp.float32)
    positions = lengths  # write at the next position
    # All slots have room in their allocated pages? Ensure via allocator
    # semantics in _setup: lengths 5,17,32 -> pages cover ceil(len/8)*8 =
    # 8,24,32; position 32 for slot 2 needs page 5th -> NOT allocated.
    # Use positions within allocation instead.
    positions = jnp.asarray([5, 17, 24], jnp.int32)
    page_ids, offsets = token_page_coords(bt, positions, PAGE)
    kp2, vp2 = scatter_decode_token(kp, vp, k_new, v_new, page_ids, offsets)
    for s in range(B):
        pid, off = int(page_ids[s]), int(offsets[s])
        np.testing.assert_allclose(
            np.asarray(kp2[pid, off]), np.asarray(k_new[s]), atol=0
        )
        np.testing.assert_allclose(
            np.asarray(vp2[pid, off]), np.asarray(v_new[s]), atol=0
        )


def test_scatter_sequence_matches_paged_layout():
    rng = np.random.default_rng(11)
    NL, S, ln = 2, 16, 13
    alloc = PageAllocator(P, PAGE, max_pages_per_slot=MP)
    pages = alloc.ensure(0, ln)
    bt = set_block_table(jnp.full((B, MP), -1, jnp.int32), 0, pages)
    kp = jnp.zeros((NL, P, PAGE, KVH, D), jnp.float32)
    vp = jnp.zeros((NL, P, PAGE, KVH, D), jnp.float32)
    k_seq = jnp.asarray(rng.standard_normal((NL, S, KVH, D)), jnp.float32)
    v_seq = jnp.asarray(rng.standard_normal((NL, S, KVH, D)), jnp.float32)
    page_ids, offsets = sequence_page_coords(
        bt[0], jnp.asarray(ln), S, PAGE
    )
    kp2, vp2 = scatter_sequence(kp, vp, k_seq, v_seq, page_ids, offsets)
    for t in range(ln):
        pid = pages[t // PAGE]
        np.testing.assert_allclose(
            np.asarray(kp2[:, pid, t % PAGE]),
            np.asarray(k_seq[:, t]),
            atol=0,
        )
    # Padded tail landed in scratch page 0, not in any allocated page.
    for t in range(ln, S):
        assert int(page_ids[t]) == 0


# ---- the page write's values ---------------------------------------------------
#
# `batched_scatter_sequence` indexes the layer where a shard holds fewer than
# 8 KV heads (PR 35: there a [NL, KVH, D] window made the TPU compiler carry
# the pool in another layout than the kernel reads) and slices it elsewhere.
# The layer as a slice is the oracle for both.


def _layer_sliced_write(pool, rows, page_ids, offsets):
    return pool.at[:, page_ids, offsets].set(rows.astype(pool.dtype))


def _write_case(nl, kvh, kind, seed):
    """(rng, pool shape, page_ids, offsets): a decode step's [B, 1]
    coordinates, or an admission's [A, S] with padded tails and a padding row
    (length 0) on scratch page 0."""
    rng = np.random.default_rng(seed)
    page, mp, d = 8, 4, 16
    rows_n = 5
    n_pages = 1 + rows_n * mp
    bt = rng.permutation(n_pages - 1).reshape(rows_n, mp) + 1  # all distinct
    if kind == "decode":
        positions = jnp.asarray(rng.integers(0, page * mp, rows_n), jnp.int32)
        ids, offs = token_page_coords(jnp.asarray(bt, jnp.int32), positions,
                                      page)
        ids, offs = ids[:, None], offs[:, None]
    else:
        lengths = jnp.asarray([13, 24, 0, 1, 17], jnp.int32)
        ids, offs = batched_sequence_page_coords(
            jnp.asarray(bt, jnp.int32), lengths, 24, page)
        assert int((ids == 0).sum()) == 24 * rows_n - int(lengths.sum())
    shape = (nl, n_pages, page, kvh, d)
    return rng, shape, ids, offs


@pytest.mark.parametrize("kind", ["decode", "admission"])
@pytest.mark.parametrize("nl,kvh", [(16, 8), (16, 2), (3, 1)])
def test_the_page_write_equals_the_layer_sliced_one_bf16(nl, kvh, kind):
    rng, shape, ids, offs = _write_case(nl, kvh, kind, seed=nl * 10 + kvh)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    seq = (nl,) + ids.shape + shape[3:]
    # float32 rows, as a prefill in float32 would hand them: the write casts.
    k_seq = jnp.asarray(rng.standard_normal(seq), jnp.float32)
    v_seq = jnp.asarray(rng.standard_normal(seq), jnp.float32)
    kp2, vp2 = jax.jit(batched_scatter_sequence)(kp, vp, k_seq, v_seq, ids,
                                                 offs)
    assert kp2.dtype == kp.dtype and kp2.shape == kp.shape
    for got, pool, rows in ((kp2, kp, k_seq), (vp2, vp, v_seq)):
        want = _layer_sliced_write(pool, rows, ids, offs)
        # Every live page bit for bit; scratch page 0 takes the padding, in
        # whatever order, and nothing reads it.
        np.testing.assert_array_equal(
            np.asarray(got[:, 1:]).view(np.uint16),
            np.asarray(want[:, 1:]).view(np.uint16))
    assert not np.array_equal(np.asarray(kp2[:, 1:]), np.asarray(kp[:, 1:]))


@pytest.mark.parametrize("kvh,tp,indexed", [
    (8, 1, False),   # Mistral on one chip: the rows fill the (8, 128) tile
    (8, 4, True),    # Mixtral at tp=4: 2 KV heads a chip
    (2, 1, True),
    (32, 4, False),  # 8 a chip
    (8, 8, True),    # 1 a chip
])
def test_the_page_write_takes_the_form_the_shard_needs(kvh, tp, indexed):
    """What chooses is what the code sees: the pool's KV heads and the mesh
    it is traced under (`kv_heads_axis`, the rule that places the pool)."""
    from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh

    rng, shape, ids, offs = _write_case(3, kvh, "admission", seed=kvh + tp)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    k_seq = jnp.asarray(
        rng.standard_normal((3,) + ids.shape + shape[3:]), jnp.bfloat16)
    write = jax.jit(batched_scatter_sequence)
    mesh = build_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
    with jax.set_mesh(mesh):
        text = write.lower(kp, kp, k_seq, k_seq, ids, offs).as_text()
        got, _ = write(kp, kp, k_seq, k_seq, ids, offs)
    # A scatter that takes the layer as an index inserts it: three inserted
    # window dimensions (layer, page, offset) and not two.
    dims = set(re.findall(r"inserted_window_dims = \[([\d, ]+)\]", text))
    assert dims == ({"0, 1, 2"} if indexed else {"1, 2"}), dims
    want = _layer_sliced_write(kp, k_seq, ids, offs)
    np.testing.assert_array_equal(
        np.asarray(got[:, 1:]).view(np.uint16),
        np.asarray(want[:, 1:]).view(np.uint16))


def _int8_pool(rng, shape):
    return {
        "q8": jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
        "scale": jnp.asarray(rng.uniform(0.01, 1.0, shape[:-1]), jnp.float32),
    }


@pytest.mark.parametrize("kind", ["decode", "admission"])
@pytest.mark.parametrize("nl,kvh", [(16, 8), (16, 2), (3, 1)])
def test_the_page_write_equals_the_layer_sliced_one_int8(nl, kvh, kind):
    from kubeai_tpu.ops.kv_quant import quantize_kv

    rng, shape, ids, offs = _write_case(nl, kvh, kind, seed=nl * 10 + kvh + 1)
    kp, vp = _int8_pool(rng, shape), _int8_pool(rng, shape)
    seq = (nl,) + ids.shape + shape[3:]
    k_seq = jnp.asarray(rng.standard_normal(seq), jnp.bfloat16)
    v_seq = jnp.asarray(rng.standard_normal(seq), jnp.bfloat16)
    kp2, vp2 = jax.jit(batched_scatter_sequence)(kp, vp, k_seq, v_seq, ids,
                                                 offs)

    @jax.jit  # quantized as the write quantizes: inside one compiled program
    def layer_sliced(pool, rows):
        q8, scale = quantize_kv(rows)
        return {"q8": _layer_sliced_write(pool["q8"], q8, ids, offs),
                "scale": _layer_sliced_write(pool["scale"], scale, ids, offs)}

    for got, pool, rows in ((kp2, kp, k_seq), (vp2, vp, v_seq)):
        want = layer_sliced(pool, rows)
        for leaf in ("q8", "scale"):
            assert got[leaf].dtype == pool[leaf].dtype
            np.testing.assert_array_equal(
                np.asarray(got[leaf][:, 1:]), np.asarray(want[leaf][:, 1:]))
            assert not np.array_equal(
                np.asarray(got[leaf][:, 1:]), np.asarray(pool[leaf][:, 1:]))


@pytest.mark.parametrize("nl,kvh", [(16, 8), (16, 2), (3, 1)])
def test_the_prequantized_write_equals_the_layer_sliced_one(nl, kvh):
    """A hand-off import: one sequence's int8 rows and scales, verbatim."""
    rng, shape, ids, offs = _write_case(nl, kvh, "admission", seed=nl + kvh)
    ids, offs = ids[0], offs[0]  # one sequence of 24, 13 live
    kp, vp = _int8_pool(rng, shape), _int8_pool(rng, shape)
    seq = (nl, ids.shape[0]) + shape[3:]
    new = [jnp.asarray(rng.integers(-127, 128, seq), jnp.int8),
           jnp.asarray(rng.uniform(0.01, 1.0, seq[:-1]), jnp.float32),
           jnp.asarray(rng.integers(-127, 128, seq), jnp.int8),
           jnp.asarray(rng.uniform(0.01, 1.0, seq[:-1]), jnp.float32)]
    kp2, vp2 = jax.jit(scatter_sequence_prequantized)(kp, vp, *new, ids, offs)
    for got, pool, (q8, scale) in ((kp2, kp, new[:2]), (vp2, vp, new[2:])):
        for leaf, rows in (("q8", q8), ("scale", scale)):
            want = _layer_sliced_write(pool[leaf], rows, ids, offs)
            np.testing.assert_array_equal(
                np.asarray(got[leaf][:, 1:]), np.asarray(want[:, 1:]))


def test_allocator_oversubscription_and_rollback():
    alloc = PageAllocator(num_pages=5, page_size=8)  # 4 usable pages
    assert alloc.free_pages == 4
    alloc.ensure(0, 16)  # 2 pages
    with pytest.raises(Exception):
        alloc.ensure(1, 9 * 8)  # too many -> rollback
    assert alloc.free_pages == 2  # slot 1 holds nothing
    alloc.release(0)
    assert alloc.free_pages == 4


@pytest.mark.slow
def test_verify_kernel_matches_reference():
    """Multi-query verify kernel (interpret mode) vs the gather
    reference, incl. softcap/window and ragged base positions."""
    from kubeai_tpu.ops.paged_attention import (
        paged_verify_attention,
        ref_paged_verify_attention,
    )

    K = 3
    lengths = [5, 17, 28]  # position of query 0 per slot = length
    q_, kd, vd, kp, vp, bt, _len = _setup(
        [l + K for l in lengths], seed=13
    )  # allocate pages covering the K window
    rng = np.random.default_rng(14)
    q = jnp.asarray(rng.standard_normal((B, K, H, D)), jnp.float32)
    positions = jnp.asarray(lengths, jnp.int32)
    for cap, win in ((None, None), (40.0, None), (None, 9), (25.0, 6)):
        got = paged_verify_attention(
            q, kp, vp, bt, positions,
            logit_softcap=cap, window=win,
        )
        want = ref_paged_verify_attention(
            q, kp, vp, bt, positions, logit_softcap=cap, window=win,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
        )


def test_verify_reference_row0_matches_decode():
    """Verify row 0 must equal single-token decode attention on the same
    cache state (the speculative stream's first token is the vanilla
    decode token)."""
    from kubeai_tpu.ops.paged_attention import ref_paged_verify_attention

    q, kd, vd, kp, vp, bt, lengths = _setup([6, 14, 27], seed=15)
    rng = np.random.default_rng(16)
    qk = jnp.asarray(rng.standard_normal((B, 2, H, D)), jnp.float32)
    # decode semantics: new token at position `length-?`... use positions
    # = lengths - 1 so query 0 attends exactly `lengths` keys.
    positions = lengths - 1
    ver = ref_paged_verify_attention(qk, kp, vp, bt, positions)
    dec = ref_paged_decode_attention(qk[:, 0], kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(ver[:, 0]), np.asarray(dec), atol=1e-5
    )
