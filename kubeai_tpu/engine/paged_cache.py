"""Paged KV cache: block-table paging over a shared page pool.

A [slots, max_seq_len] reservation per slot is simple, but its HBM
scales with the worst case. Paging allocates fixed-size pages on demand
from a shared pool, so memory scales with the TOKENS ACTUALLY RESIDENT,
buying more concurrent slots per chip under mixed-length traffic (the
vLLM insight, rebuilt TPU-style: static shapes — the pool and block
tables are fixed-size buffers; only their CONTENTS change).

Layout:
  k_pages / v_pages: [NL, n_pages, page_size, KVH, D]
  block_tables:      [slots, max_pages_per_slot] int32 (page ids; -1 free)
  state:             {name: [state layers, slots, ...]}: what a slot owns
                     beside its pages in a family some of whose layers keep
                     a state of fixed size and no keys and values (NL above
                     is then the layers that own pages); or, in a family
                     some of whose layers attend a sliding window, the
                     WINDOW pool {"k_window", "v_window": [window layers, 1 +
                     slots * ring, page, KVH, D]} in which slot s owns pages
                     1 + s * ring .. as a ring, however long its sequence
                     (NL above is then the global layers; docs/concepts/
                     window-cache.md); {} for every other
  latent:            a family whose page layers keep ONE row a token from
                     which keys and values are both read (multi-head latent
                     attention) has one pool, `k_pages` [NL, n_pages, page,
                     row], and no second: `v_pages` is None (docs/concepts/
                     latent-cache.md). Block tables, allocator and `state`
                     are what they are for any other
  host allocator:    free-list of page ids (bookkeeping outside jit)

Ops (jit-safe, tested against contiguous semantics):
  gather_slot_kv     — virtual [slots, L] view for decode attention
  scatter_token      — write one token's K/V per slot through the tables
  insert_sequence    — write a prefilled sequence through the tables

This is the engine's only KV cache (engine.py); the decode kernels that
read pages in place are in ops/paged_attention.py. The ops below are the
functional reference they are tested against.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


class OutOfPages(RuntimeError):
    pass


@dataclasses.dataclass
class PagedKVCache:
    # A pool is either a plain [NL, n_pages, page, KVH, D] array or an
    # int8-quantized {"q8": int8 pages, "scale": f32 [NL, n_pages, page,
    # KVH]} dict (ops/kv_quant.py) — the same leaf-dispatch idiom the
    # weight quantizer uses, so jit plumbing and layer scans carry both
    # unchanged.
    k_pages: jax.Array | dict  # [NL, n_pages, page, KVH, D]
    v_pages: jax.Array | dict | None  # None: `k_pages` is a latent pool
    block_tables: jax.Array  # [slots, max_pages] int32, -1 = unallocated
    # Row `slot` of each pool is that slot's, as its page list is: written
    # whole by an admission, updated in place by every decode step
    # (docs/concepts/hybrid-state.md).
    state: dict = dataclasses.field(default_factory=dict)

    @property
    def quantized(self) -> bool:
        from kubeai_tpu.ops.kv_quant import is_quantized_kv

        return is_quantized_kv(self.k_pages)

    @property
    def pages_shape(self) -> tuple:
        from kubeai_tpu.ops.kv_quant import kv_pages_shape

        return kv_pages_shape(self.k_pages)

    @property
    def page_size(self) -> int:
        return self.pages_shape[2]

    @property
    def num_pages(self) -> int:
        return self.pages_shape[1]

    @property
    def max_pages_per_slot(self) -> int:
        return self.block_tables.shape[1]

    def nbytes(self) -> int:
        """Resident pool bytes (pages + scales when quantized)."""
        from kubeai_tpu.ops.kv_quant import kv_pool_nbytes

        return kv_pool_nbytes(self.k_pages) + (
            0 if self.v_pages is None else kv_pool_nbytes(self.v_pages))

    def state_nbytes(self) -> dict:
        """Resident bytes of each state pool, by name."""
        return {name: int(pool.nbytes) for name, pool in self.state.items()}

    @staticmethod
    def create(
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_slots: int,
        max_seq_len: int,
        kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
        pool_sharding=None,  # a Sharding, or {"q8", "scale"} of them (int8)
        table_sharding=None,
        state: dict | None = None,  # ModelFamily.recurrent_state(cfg)
        state_sharding=None,
        window: dict | None = None,  # ModelFamily.kv_layers(cfg) + "ring"
        latent: dict | None = None,  # ModelFamily.latent_pages(cfg)
    ) -> "PagedKVCache":
        """Buffers are created under their shardings (None = the default
        device), never whole on one device and re-placed afterwards.
        `num_layers` are the layers that own pages by the sequence's length
        (`num_pages` of them: the pool that can run out). With `window` a
        second pool over its `window_layers` holds every slot's ring of
        `ring` pages and a scratch page: no allocator, no growth. With
        `latent` the page pool is ONE pool of its rows (`kv_heads` and
        `head_dim` are not asked), and there is no second."""
        from kubeai_tpu.ops.kv_quant import make_quantized_pool

        if window and (state or dtype in (jnp.int8, "int8")):
            raise ValueError(
                "a window pool goes with a bf16 page pool and no recurrent state"
            )

        if latent and (window or dtype in (jnp.int8, "int8")):
            raise ValueError(
                "a latent pool goes with no window pool and is not quantized"
            )

        max_pages = -(-max_seq_len // page_size)
        shape = (num_layers, num_pages, page_size, kv_heads, head_dim)
        if latent:
            k_pages = jnp.zeros(
                (*shape[:3], *latent["row"]), latent["dtype"],
                device=pool_sharding,
            )
            v_pages = None
        elif dtype in (jnp.int8, "int8"):
            k_pages = make_quantized_pool(shape, sharding=pool_sharding)
            v_pages = make_quantized_pool(shape, sharding=pool_sharding)
        else:
            k_pages = jnp.zeros(shape, dtype, device=pool_sharding)
            v_pages = jnp.zeros(shape, dtype, device=pool_sharding)
        return PagedKVCache(
            k_pages=k_pages,
            v_pages=v_pages,
            block_tables=jnp.full(
                (num_slots, max_pages), -1, jnp.int32, device=table_sharding
            ),
            state={
                name: jnp.zeros(
                    (state["state_layers"], num_slots, *shape), dt,
                    device=state_sharding,
                )
                for name, (shape, dt) in (state["pools"] if state else {}).items()
            } or {
                name: jnp.zeros(
                    (window["window_layers"], 1 + num_slots * window["ring"],
                     page_size, kv_heads, head_dim), dtype, device=state_sharding,
                )
                for name in (("k_window", "v_window") if window else ())
            },
        )


jax.tree_util.register_dataclass(
    PagedKVCache, ["k_pages", "v_pages", "block_tables", "state"], []
)


class SequenceTooLong(RuntimeError):
    pass


class PageAllocator:
    """Host-side free-list with optional prefix-cache sharing. The device
    never sees allocation — only the resulting block tables.

    Page 0 is RESERVED as a scratch page and never handed out: jit-safe
    ops clamp unallocated block-table entries (-1) to 0, so reads hit
    masked junk and writes land in scratch — never in a live sequence.

    Prefix caching (the vLLM automatic-prefix-cache idea, host-side
    bookkeeping only): immutable full-page prompt prefixes register under
    a content-hash chain. A later prompt whose leading pages hash to a
    registered chain ADOPTS those pages read-only instead of recomputing
    them — pages then carry a slot refcount, and pages whose refcount
    drops to zero park in an LRU idle pool (still lookupable) that the
    free path evicts from only when the free list runs dry. The reference
    exploits engine prefix caches only ACROSS replicas (CHWBL routing,
    docs/benchmarks/prefix-aware-load-balancing.md); this gives the
    in-tree engine the per-replica half of that headline."""

    def __init__(
        self, num_pages: int, page_size: int,
        max_pages_per_slot: int | None = None,
    ):
        from collections import OrderedDict

        self.page_size = page_size
        self.max_pages_per_slot = max_pages_per_slot
        self._free = list(range(1, num_pages))  # page 0 reserved
        # slot -> allocated page ids, in order.
        self._owned: dict[int, list[int]] = {}
        # Prefix-cache state. A page is in exactly one of: _free, owned
        # (refcount >= 1), or _idle (refcount 0 but still registered).
        self._ref: dict[int, int] = {}
        self._hash_to_page: dict[bytes, int] = {}
        self._page_to_hash: dict[int, bytes] = {}
        self._idle: "OrderedDict[int, None]" = OrderedDict()  # LRU -> MRU
        # Optional spill hook: called as on_evict(page, hash) just before
        # an idle page's registration is destroyed by eviction, while the
        # device page still holds the registered content. Wired by the
        # engine when KV objstore spill is enabled; must never raise.
        self.on_evict = None

    @property
    def free_pages(self) -> int:
        """Pages an ensure() can still obtain (idle cached pages are
        reclaimable by eviction)."""
        return len(self._free) + len(self._idle)

    @property
    def cached_idle_pages(self) -> int:
        return len(self._idle)

    def pages_for(self, slot: int) -> list[int]:
        return list(self._owned.get(slot, []))

    def _take_free(self) -> int | None:
        if self._free:
            return self._free.pop()
        if self._idle:
            # Eviction MUST strip both hash mappings atomically with the
            # idle-pool removal: once holdings are published cluster-wide
            # a stale _hash_to_page entry would let lookup() adopt a page
            # whose content has been overwritten by its new owner —
            # silently corrupting token-identity. Regression-tested in
            # tests/unit/test_paged_cache.py.
            page, _ = self._idle.popitem(last=False)  # evict LRU
            h = self._page_to_hash.pop(page)
            del self._hash_to_page[h]
            if self.on_evict is not None:
                try:
                    self.on_evict(page, h)
                except Exception:
                    pass
            del self._ref[page]
            return page
        return None

    def ensure(self, slot: int, length: int) -> list[int]:
        """Grow slot's allocation to cover `length` tokens. Returns the page
        list. Raises OutOfPages when the pool is exhausted (pages taken in
        the failed call are rolled back, so a deferred admission holds
        nothing) and SequenceTooLong past the per-slot block-table cap."""
        need = -(-length // self.page_size)
        if self.max_pages_per_slot is not None and need > self.max_pages_per_slot:
            raise SequenceTooLong(
                f"{length} tokens need {need} pages > per-slot cap "
                f"{self.max_pages_per_slot}"
            )
        owned = self._owned.setdefault(slot, [])
        # Capacity check BEFORE touching the idle cache: _take_free
        # destroys an evicted page's hash entries, so an allocation that
        # cannot succeed must not strip the cache on its way to the
        # OutOfPages it was always going to raise.
        if need - len(owned) > len(self._free) + len(self._idle):
            raise OutOfPages(
                f"page pool exhausted ({need} needed for slot {slot})"
            )
        taken: list[int] = []
        while len(owned) + len(taken) < need:
            page = self._take_free()
            if page is None:  # unreachable given the check above
                self._free.extend(taken)
                raise OutOfPages(
                    f"page pool exhausted ({need} needed for slot {slot})"
                )
            taken.append(page)
        for page in taken:
            self._ref[page] = 1
        owned.extend(taken)
        return list(owned)

    def _decref(self, page: int) -> None:
        n = self._ref.get(page, 1) - 1
        if n > 0:
            self._ref[page] = n
        elif page in self._page_to_hash:
            # Still registered: park in the idle LRU, content intact.
            self._ref[page] = 0
            self._idle[page] = None
        else:
            self._ref.pop(page, None)
            self._free.append(page)

    def release(self, slot: int) -> None:
        for page in self._owned.pop(slot, []):
            self._decref(page)

    # ---- prefix cache ------------------------------------------------------

    def lookup(self, hashes: list[bytes]) -> list[int]:
        """Longest registered prefix of the hash chain -> its pages, in
        order. Hit pages are NOT reserved — call adopt() to take refs."""
        pages: list[int] = []
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is None:
                break
            pages.append(page)
        return pages

    def adopt(self, slot: int, pages: list[int]) -> None:
        """Prepend shared pages to slot's allocation (before any ensure()
        growth), taking a reference on each; idle pages come off the LRU."""
        owned = self._owned.setdefault(slot, [])
        assert not owned, "adopt() must seed an empty slot"
        for page in pages:
            self._ref[page] = self._ref.get(page, 0) + 1
            self._idle.pop(page, None)
        owned.extend(pages)

    def unadopt(self, slot: int) -> None:
        """Roll back an adopt() whose follow-up ensure() failed."""
        for page in self._owned.pop(slot, []):
            self._decref(page)

    def register(self, hashes: list[bytes], pages: list[int]) -> None:
        """Publish a slot's immutable full prompt pages under their chain
        hashes. First registration of a hash wins (concurrent identical
        prompts produce identical content anyway); a page already
        registered under another hash keeps its original entry."""
        for h, page in zip(hashes, pages):
            if h in self._hash_to_page or page in self._page_to_hash:
                continue
            self._hash_to_page[h] = page
            self._page_to_hash[page] = h

    def holdings(self) -> list[bytes]:
        """Every chain hash currently registered (owned-and-registered or
        parked idle) — the replica's advertisable prefix-cache contents.
        Advisory only: routing built on this is a hint; admission always
        re-verifies through lookup(), so staleness can cost performance
        but never correctness."""
        return list(self._hash_to_page.keys())

    def seed_unowned(self, hashes: list[bytes]) -> list[int] | None:
        """Allocate pages for externally fetched prefix content (peer KV
        fetch / objstore fill): one page per NOVEL hash, registered and
        parked straight into the idle LRU with refcount 0 — no slot owns
        them; the next admission adopts them through the ordinary
        lookup()/adopt() path. Returns the page ids aligned with `hashes`
        (None entries mark hashes that were already registered locally and
        need no write), or None if the pool cannot supply every novel page
        (partial seeding is rolled back so a failed fetch holds nothing).
        """
        # Novelty is decided ONCE, before any page is taken: taking pages
        # can evict idle entries, which may deregister a hash classified
        # as already-held — it must still consume no page (its chain link
        # just breaks, shortening future lookups; never a correctness
        # issue because admission re-verifies content by hash).
        novel = {h for h in hashes if h not in self._hash_to_page}
        taken: list[int] = []
        for _ in range(len(novel)):
            page = self._take_free()
            if page is None:
                self._free.extend(taken)
                return None
            taken.append(page)
        it = iter(taken)
        out: list[int | None] = []
        for h in hashes:
            if h not in novel:
                out.append(None)
                continue
            page = next(it)
            self._hash_to_page[h] = page
            self._page_to_hash[page] = h
            self._ref[page] = 0
            self._idle[page] = None
            out.append(page)
        return out



def set_block_table(
    block_tables: jax.Array, slot: int, pages: list[int]
) -> jax.Array:
    row = jnp.full((block_tables.shape[1],), -1, jnp.int32)
    if pages:
        row = row.at[: len(pages)].set(jnp.asarray(pages, jnp.int32))
    return block_tables.at[slot].set(row)


def gather_slot_kv(cache: PagedKVCache) -> tuple[jax.Array, jax.Array]:
    """Materialize the virtual contiguous view [NL, slots, L_max, KVH, D].

    L_max = max_pages_per_slot * page_size. Unallocated pages (-1) index
    page 0 — garbage that decode attention masks via per-slot lengths.
    This is the functional reference; the paged-attention kernel reads
    pages in place and never materializes this view.
    """
    from kubeai_tpu.ops.kv_quant import dequantize_kv

    bt = jnp.maximum(cache.block_tables, 0)  # -1 -> reserved scratch page 0
    if cache.quantized:
        k = dequantize_kv(
            cache.k_pages["q8"][:, bt], cache.k_pages["scale"][:, bt]
        )
        v = dequantize_kv(
            cache.v_pages["q8"][:, bt], cache.v_pages["scale"][:, bt]
        )
    else:
        k = cache.k_pages[:, bt]  # [NL, slots, max_pages, page, KVH, D]
        v = cache.v_pages[:, bt]
    nl, slots, mp, page, kvh, d = k.shape
    return (
        k.reshape(nl, slots, mp * page, kvh, d),
        v.reshape(nl, slots, mp * page, kvh, d),
    )


def scatter_token(
    cache: PagedKVCache,
    k_new: jax.Array,  # [NL, slots, KVH, D] one token per slot
    v_new: jax.Array,
    positions: jax.Array,  # [slots] absolute position of the token
) -> PagedKVCache:
    """Write one token per slot through the block tables (decode step)."""
    from kubeai_tpu.ops.kv_quant import quantize_kv

    page = cache.page_size
    slot_idx = jnp.arange(cache.block_tables.shape[0])
    page_ids = cache.block_tables[slot_idx, positions // page]  # [slots]
    # Unallocated slots (-1) write into the RESERVED scratch page 0 — safe
    # because the allocator never hands page 0 to a live sequence.
    page_ids = jnp.maximum(page_ids, 0)
    offsets = positions % page
    if cache.quantized:
        k8, ks = quantize_kv(k_new)
        v8, vs = quantize_kv(v_new)
        k_pages = {
            "q8": cache.k_pages["q8"].at[:, page_ids, offsets].set(k8),
            "scale": cache.k_pages["scale"].at[:, page_ids, offsets].set(ks),
        }
        v_pages = {
            "q8": cache.v_pages["q8"].at[:, page_ids, offsets].set(v8),
            "scale": cache.v_pages["scale"].at[:, page_ids, offsets].set(vs),
        }
        return PagedKVCache(k_pages, v_pages, cache.block_tables)
    k_pages = cache.k_pages.at[:, page_ids, offsets].set(
        k_new.astype(cache.k_pages.dtype)
    )
    v_pages = cache.v_pages.at[:, page_ids, offsets].set(
        v_new.astype(cache.v_pages.dtype)
    )
    return PagedKVCache(k_pages, v_pages, cache.block_tables)


def insert_sequence(
    cache: PagedKVCache,
    k_seq: jax.Array,  # [NL, S, KVH, D] prefilled sequence (padded)
    v_seq: jax.Array,
    slot: int,
    length: int,
) -> PagedKVCache:
    """Write a prefilled sequence through slot's block table (admission)."""
    from kubeai_tpu.ops.kv_quant import quantize_kv

    page = cache.page_size
    bt = cache.block_tables
    k_pages, v_pages = cache.k_pages, cache.v_pages
    n_pages = -(-length // page)
    for p in range(n_pages):
        pid = bt[slot, p]
        pid = jnp.maximum(pid, 0)
        start = p * page
        count = min(page, length - start)
        ks = k_seq[:, start : start + count]
        vs = v_seq[:, start : start + count]
        if cache.quantized:
            k8, ksc = quantize_kv(ks)
            v8, vsc = quantize_kv(vs)
            k_pages = {
                "q8": k_pages["q8"].at[:, pid, :count].set(k8),
                "scale": k_pages["scale"].at[:, pid, :count].set(ksc),
            }
            v_pages = {
                "q8": v_pages["q8"].at[:, pid, :count].set(v8),
                "scale": v_pages["scale"].at[:, pid, :count].set(vsc),
            }
        else:
            k_pages = k_pages.at[:, pid, :count].set(ks.astype(k_pages.dtype))
            v_pages = v_pages.at[:, pid, :count].set(vs.astype(v_pages.dtype))
    return PagedKVCache(k_pages, v_pages, bt)
