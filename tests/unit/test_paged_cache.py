"""Paged KV cache: allocator bookkeeping + paged gather/scatter must be
semantically identical to a contiguous [slots, max_len] cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine.paged_cache import (
    OutOfPages,
    PageAllocator,
    SequenceTooLong,
    PagedKVCache,
    gather_slot_kv,
    insert_sequence,
    scatter_token,
    set_block_table,
)
from kubeai_tpu.ops.attention import decode_attention

NL, PAGE, KVH, D = 2, 8, 2, 16
SLOTS, MAX_LEN, N_PAGES = 3, 64, 16


def mk_cache():
    return PagedKVCache.create(
        NL, N_PAGES, PAGE, SLOTS, MAX_LEN, KVH, D, dtype=jnp.float32
    )


def test_allocator_grow_release_exhaust():
    # 5 pages, page 0 reserved as scratch -> 4 usable.
    alloc = PageAllocator(num_pages=5, page_size=8)
    assert alloc.free_pages == 4
    p = alloc.ensure(0, 9)  # 2 pages
    assert len(p) == 2 and alloc.free_pages == 2
    assert 0 not in p  # scratch page never handed out
    assert alloc.ensure(0, 10) == p  # no growth needed
    alloc.ensure(1, 16)  # 2 more
    assert alloc.free_pages == 0
    with pytest.raises(OutOfPages):
        alloc.ensure(2, 1)
    alloc.release(0)
    assert alloc.free_pages == 2
    # Released pages are reusable.
    assert len(alloc.ensure(2, 16)) == 2


def test_allocator_rollback_and_caps():
    alloc = PageAllocator(num_pages=4, page_size=8, max_pages_per_slot=2)
    # Needing 3 pages with only 3 free but cap 2 -> typed rejection.
    with pytest.raises(SequenceTooLong):
        alloc.ensure(0, 17)
    # Partial-allocation rollback: 3 free, request needs 3+... slot A takes
    # 2 (cap), then exhaust: B wants 2 with 1 free -> OutOfPages AND holds 0.
    alloc.ensure(0, 16)
    assert alloc.free_pages == 1
    with pytest.raises(OutOfPages):
        alloc.ensure(1, 16)
    assert alloc.free_pages == 1  # rolled back, nothing held
    assert alloc.pages_for(1) == []


def test_paged_lifecycle_matches_contiguous():
    """Simulate two requests (prefill insert + decode scatters) and check
    the gathered view + attention equal a contiguous reference cache."""
    rng = np.random.default_rng(0)
    cache = mk_cache()
    alloc = PageAllocator(N_PAGES, PAGE)

    # Contiguous reference: [NL, slots, L, KVH, D]
    ref_k = np.zeros((NL, SLOTS, MAX_LEN, KVH, D), np.float32)
    ref_v = np.zeros_like(ref_k)
    lengths = np.zeros((SLOTS,), np.int32)

    # Admission: slot 0 with 11 tokens, slot 2 with 5 tokens (page=8:
    # exercises partial pages and non-adjacent slots).
    for slot, plen in ((0, 11), (2, 5)):
        k_seq = rng.standard_normal((NL, 16, KVH, D)).astype(np.float32)
        v_seq = rng.standard_normal((NL, 16, KVH, D)).astype(np.float32)
        pages = alloc.ensure(slot, plen)
        cache.block_tables = set_block_table(cache.block_tables, slot, pages)
        cache = insert_sequence(
            cache, jnp.asarray(k_seq), jnp.asarray(v_seq), slot, plen
        )
        ref_k[:, slot, :plen] = k_seq[:, :plen]
        ref_v[:, slot, :plen] = v_seq[:, :plen]
        lengths[slot] = plen

    # Decode: 6 steps of per-slot token writes (slot 1 inactive).
    for _step in range(6):
        k_new = rng.standard_normal((NL, SLOTS, KVH, D)).astype(np.float32)
        v_new = rng.standard_normal((NL, SLOTS, KVH, D)).astype(np.float32)
        positions = lengths.copy()
        for slot in (0, 2):
            pages = alloc.ensure(slot, int(lengths[slot]) + 1)
            cache.block_tables = set_block_table(
                cache.block_tables, slot, pages
            )
        cache = scatter_token(
            cache, jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(positions),
        )
        for slot in (0, 2):
            ref_k[:, slot, positions[slot]] = k_new[:, slot]
            ref_v[:, slot, positions[slot]] = v_new[:, slot]
            lengths[slot] += 1

    gk, gv = gather_slot_kv(cache)
    # Compare only valid prefixes (beyond-length content is masked junk).
    for slot in range(SLOTS):
        L = int(lengths[slot])
        np.testing.assert_allclose(
            np.asarray(gk)[:, slot, :L], ref_k[:, slot, :L], rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(gv)[:, slot, :L], ref_v[:, slot, :L], rtol=1e-6
        )

    # Attention over the gathered view == attention over the reference for
    # ACTIVE slots (an unallocated slot's virtual view is page-0 junk; the
    # engine never consumes inactive-slot outputs).
    q = rng.standard_normal((SLOTS, 4, D)).astype(np.float32)
    active = [0, 2]
    for layer in range(NL):
        out_paged = decode_attention(
            jnp.asarray(q), gk[layer], gv[layer],
            jnp.asarray(np.maximum(lengths, 1)),
        )
        out_ref = decode_attention(
            jnp.asarray(q),
            jnp.asarray(ref_k[layer]),
            jnp.asarray(ref_v[layer]),
            jnp.asarray(np.maximum(lengths, 1)),
        )
        np.testing.assert_allclose(
            np.asarray(out_paged)[active],
            np.asarray(out_ref)[active],
            rtol=1e-5,
            atol=1e-6,
        )


def test_page_reuse_after_release_no_leakage():
    """A freed slot's pages, reallocated to another slot, must not leak old
    content into the new slot's valid region."""
    rng = np.random.default_rng(1)
    cache = mk_cache()
    alloc = PageAllocator(N_PAGES, PAGE)

    pages = alloc.ensure(0, 16)
    cache.block_tables = set_block_table(cache.block_tables, 0, pages)
    poison = np.full((NL, 16, KVH, D), 99.0, np.float32)
    cache = insert_sequence(
        cache, jnp.asarray(poison), jnp.asarray(poison), 0, 16
    )
    alloc.release(0)
    cache.block_tables = set_block_table(cache.block_tables, 0, [])

    fresh = rng.standard_normal((NL, 8, KVH, D)).astype(np.float32)
    pages2 = alloc.ensure(1, 6)
    cache.block_tables = set_block_table(cache.block_tables, 1, pages2)
    cache = insert_sequence(
        cache, jnp.asarray(fresh), jnp.asarray(fresh), 1, 6
    )
    gk, _ = gather_slot_kv(cache)
    np.testing.assert_allclose(
        np.asarray(gk)[:, 1, :6], fresh[:, :6], rtol=1e-6
    )
    # Beyond length 6, stale 99s may remain — that's exactly what the
    # length mask exists for; assert the valid prefix is clean.
    assert not np.any(np.asarray(gk)[:, 1, :6] == 99.0)


# ---- prefix-cache eviction bookkeeping (cluster KV-sharing audit) -----------
#
# Once holdings are published cluster-wide, a stale _hash_to_page entry
# surviving eviction would let lookup() adopt a page whose content was
# overwritten by its new owner — a silent token-identity corruption. These
# tests pin the invariant: eviction strips BOTH hash mappings atomically
# with the idle-pool removal.


def _alloc_with_idle(num_pages=5):
    """Allocator with slot 0's registered pages parked in the idle LRU."""
    alloc = PageAllocator(num_pages=num_pages, page_size=8)
    pages = alloc.ensure(0, 16)  # 2 pages
    hashes = [b"h0" * 8, b"h1" * 8]
    alloc.register(hashes, pages)
    alloc.release(0)  # registered pages park idle, ref 0
    assert alloc.cached_idle_pages == 2
    return alloc, pages, hashes


def test_eviction_strips_hash_mappings():
    alloc, pages, hashes = _alloc_with_idle()
    # 2 plain-free pages remain; taking 3 forces one LRU eviction.
    alloc.ensure(1, 24)
    evicted = pages[0]  # LRU = first parked
    assert evicted not in alloc._page_to_hash
    assert hashes[0] not in alloc._hash_to_page
    assert alloc.lookup(hashes) == []  # chain head gone -> full miss
    # The surviving idle page keeps BOTH mappings.
    assert alloc._hash_to_page[hashes[1]] == pages[1]
    assert alloc._page_to_hash[pages[1]] == hashes[1]
    # And holdings() mirrors the registration state exactly.
    assert alloc.holdings() == [hashes[1]]


def test_eviction_fires_spill_hook_then_deregisters():
    alloc, pages, hashes = _alloc_with_idle()
    seen = []
    alloc.on_evict = lambda page, h: seen.append((page, h))
    alloc.ensure(1, 24)
    assert seen == [(pages[0], hashes[0])]
    # A raising hook must not break allocation or leak mappings.
    alloc.on_evict = lambda page, h: 1 / 0
    alloc.ensure(2, 8)  # evicts the second idle page
    assert hashes[1] not in alloc._hash_to_page
    assert pages[1] not in alloc._page_to_hash


def test_seed_unowned_parks_idle_and_adoptable():
    alloc = PageAllocator(num_pages=5, page_size=8)
    hashes = [b"a" * 16, b"b" * 16]
    seeded = alloc.seed_unowned(hashes)
    assert seeded is not None and all(p is not None for p in seeded)
    assert alloc.cached_idle_pages == 2
    assert alloc.holdings() == hashes
    # Ordinary admission path adopts the seeded chain.
    hit = alloc.lookup(hashes)
    assert hit == seeded
    alloc.adopt(0, hit)
    assert alloc.cached_idle_pages == 0
    assert alloc.pages_for(0) == seeded
    # Already-registered hashes consume no page and come back None.
    again = alloc.seed_unowned([hashes[0], b"c" * 16])
    assert again[0] is None and again[1] is not None


def test_seed_unowned_rolls_back_on_exhaustion():
    alloc = PageAllocator(num_pages=3, page_size=8)  # 2 usable pages
    before = alloc.free_pages
    assert alloc.seed_unowned([b"x" * 16, b"y" * 16, b"z" * 16]) is None
    assert alloc.free_pages == before  # nothing held by the failed seed
    assert alloc.holdings() == []
