"""Automatic prefix caching (paged engine): allocator sharing semantics
and engine-level stream exactness.

The engine half mirrors vLLM's automatic-prefix-cache behavior rebuilt
host-side over the paged pool: full prompt pages register under an
adapter-aware content-hash chain, later prompts adopt matching prefixes
read-only and prefill only their suffix. The reference's prefix story is
cross-replica routing only (CHWBL, docs/benchmarks/
prefix-aware-load-balancing.md); per-replica caching is the engine half
it delegates to vLLM."""

import jax
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.paged_cache import OutOfPages, PageAllocator
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.models import llama

# ---- allocator-level (fast) -------------------------------------------------


def _hashes(n):
    return [bytes([i]) * 16 for i in range(n)]


def test_allocator_register_lookup_adopt_refcount():
    a = PageAllocator(num_pages=9, page_size=4)
    pages = a.ensure(0, 12)  # 3 pages
    h = _hashes(3)
    a.register(h, pages)
    assert a.lookup(h) == pages
    assert a.lookup(h[:2]) == pages[:2]
    assert a.lookup([b"x" * 16]) == []

    # Adopt onto another slot: refcount 2; creator release keeps them live.
    a.adopt(1, pages[:2])
    a.ensure(1, 12)  # grows with 1 new page
    a.release(0)
    assert a.lookup(h) == pages  # page 3 idle-cached, 1+2 still referenced
    assert a.cached_idle_pages == 1
    # Releasing the adopter parks all three in the idle pool (the
    # adopter's private third page was never registered -> truly freed).
    a.release(1)
    assert a.cached_idle_pages == 3
    assert a.lookup(h) == pages  # cache survives zero references


def test_allocator_idle_eviction_lru_order():
    a = PageAllocator(num_pages=4, page_size=4)  # 3 usable pages
    p0 = a.ensure(0, 4)
    a.register(_hashes(1), p0)
    a.release(0)
    p1 = a.ensure(1, 4)
    h1 = [b"\xaa" * 16]
    a.register(h1, p1)
    a.release(1)
    assert a.cached_idle_pages == 2 and len(a._free) == 1
    # Demand 3 pages: takes the free one, then evicts the LRU cached page
    # (p0) while keeping the more recent one.
    got = a.ensure(2, 12)
    assert len(got) == 3
    assert a.lookup(_hashes(1)) == []  # evicted
    # p1's hash entry was evicted too (all three pages are now owned).
    assert a.lookup(h1) == []
    with pytest.raises(OutOfPages):
        a.ensure(3, 4)


def test_allocator_adopt_rollback_on_oom():
    a = PageAllocator(num_pages=4, page_size=4)  # 3 usable
    shared = a.ensure(0, 8)
    a.register(_hashes(2), shared)
    # Slot 1 adopts both shared pages then needs 2 more -> only 1 free.
    a.adopt(1, shared)
    with pytest.raises(OutOfPages):
        a.ensure(1, 16)
    a.unadopt(1)
    # Rollback restored refcounts: releasing the creator parks both.
    a.release(0)
    assert a.cached_idle_pages == 2


def test_allocator_register_first_wins():
    a = PageAllocator(num_pages=8, page_size=4)
    p0 = a.ensure(0, 4)
    p1 = a.ensure(1, 4)
    h = _hashes(1)
    a.register(h, p0)
    a.register(h, p1)  # duplicate content from a concurrent admission
    assert a.lookup(h) == p0
    a.release(1)  # unregistered page goes straight to the free list
    assert a.cached_idle_pages == 0


# ---- engine-level (slow: real compiles) -------------------------------------

CFG = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0))
BASE = dict(num_slots=4, max_seq_len=256, page_size=16, prefill_chunk=32,
            decode_chunk=4)


def _mk(prefix_cache=False, **kw):
    merged = dict(BASE, **kw)
    return Engine(
        "llama", CFG, PARAMS,
        cfg=EngineConfig(prefix_cache=prefix_cache, **merged),
    )


def _prompts():
    rng = np.random.default_rng(0)
    system = rng.integers(1, CFG.vocab_size, 80).tolist()
    return [
        system + rng.integers(1, CFG.vocab_size, 20).tolist(),
        system + rng.integers(1, CFG.vocab_size, 33).tolist(),
        rng.integers(1, CFG.vocab_size, 40).tolist(),
    ]


@pytest.mark.slow
def test_prefix_cache_streams_match_vanilla():
    prompts = _prompts()
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    want = _mk().generate(prompts, sp)
    eng = _mk(prefix_cache=True)
    assert eng.generate(prompts, sp) == want  # cold: intra-batch sharing
    assert eng.prefix_stats["hit_tokens"] > 0
    warm_before = eng.prefix_stats["hit_tokens"]
    assert eng.generate(prompts, sp) == want  # warm: idle-pool revival
    assert eng.prefix_stats["hit_tokens"] > warm_before + 100


@pytest.mark.slow
def test_prefix_cache_seeded_sampling_matches():
    prompts = _prompts()[:2]
    sp = SamplingParams(temperature=0.8, top_k=20, max_tokens=10, seed=7)
    want = _mk().generate(prompts, sp)
    eng = _mk(prefix_cache=True)
    eng.generate(prompts, sp)  # populate
    assert eng.generate(prompts, sp) == want


@pytest.mark.slow
def test_prefix_cache_eviction_under_pressure():
    """Tiny pool: distinct prompts churn the cache; eviction must keep
    admission live and streams exact."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, CFG.vocab_size, 48).tolist() for _ in range(6)]
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    # 4 pages/prompt resident + decode growth; pool of 17 forces reuse.
    want = _mk(num_pages=17, num_slots=2).generate(prompts, sp)
    eng = _mk(prefix_cache=True, num_pages=17, num_slots=2)
    assert eng.generate(prompts, sp) == want
    # Run the set again: some prefixes were evicted, some hit; exactness
    # must hold either way.
    assert eng.generate(prompts, sp) == want


@pytest.mark.slow
def test_prefix_cache_adapter_generation_invalidation():
    """New weights hot-swapped into a reused adapter slot must not hit
    KV cached under the old weights."""
    rng = np.random.default_rng(5)
    E, H, D, NL = (
        CFG.hidden_size, CFG.num_heads, CFG.head_size, CFG.num_layers,
    )

    def weights(scale):
        A = (rng.standard_normal((NL, E, 8)) * scale).astype(np.float32)
        B = (rng.standard_normal((NL, 8, H * D)) * scale).astype(np.float32)
        return {"wq": (A, B)}

    prompt = rng.integers(1, CFG.vocab_size, 64).tolist()
    sp = SamplingParams(temperature=0.0, max_tokens=8)

    eng = _mk(prefix_cache=True, max_adapters=1, max_lora_rank=8)
    w1, w2 = weights(8.0), weights(-8.0)
    eng.load_adapter("a", w1)
    out1 = eng.generate([prompt], sp, adapter="a")
    eng.generate([prompt], sp, adapter="a")  # warm hit under w1
    hit1 = eng.prefix_stats["hit_tokens"]
    assert hit1 > 0
    eng.unload_adapter("a")
    eng.load_adapter("a", w2)
    out2 = eng.generate([prompt], sp, adapter="a")
    # Different weights -> the old cache entries must not have been used:
    # compare against a FRESH engine with w2 (ground truth, no cache).
    fresh = _mk(max_adapters=1, max_lora_rank=8)
    fresh.load_adapter("a", w2)
    assert out2 == fresh.generate([prompt], sp, adapter="a")
    assert out1 != out2  # the swap actually changed the function


@pytest.mark.slow
def test_prefix_cache_pages_shared_not_duplicated():
    """Two live requests over the same prefix hold the SAME pages
    (refcount 2), so resident-page count reflects sharing."""
    rng = np.random.default_rng(9)
    system = rng.integers(1, CFG.vocab_size, 64).tolist()
    p1 = system + [5, 6, 7]
    p2 = system + [8, 9, 10, 11]
    eng = _mk(prefix_cache=True)
    total = eng._alloc.free_pages
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    eng.generate([p1], sp)
    eng.generate([p2], sp)
    # p2 adopted p1's 4 system pages instead of allocating fresh copies:
    # everything released/idle now, and the idle pool holds ONE copy of
    # the shared prefix.
    assert eng._alloc.free_pages == total
    shared = eng._alloc.lookup(eng._prefix_hashes(system, 0))
    assert len(shared) == 4


def test_prefix_cache_config_validation():
    with pytest.raises(ValueError, match="prefill_chunk"):
        _mk(prefix_cache=True, prefill_chunk=0)


def test_allocator_failed_ensure_preserves_cache():
    """An allocation that cannot succeed must not strip the idle cache on
    its way to OutOfPages (a deferred head-of-queue request would
    otherwise wipe the cache every scheduler step)."""
    a = PageAllocator(num_pages=4, page_size=4)  # 3 usable
    p = a.ensure(0, 8)
    h = _hashes(2)
    a.register(h, p)
    a.release(0)
    assert a.cached_idle_pages == 2 and len(a._free) == 1
    with pytest.raises(OutOfPages):
        a.ensure(1, 16)  # needs 4 > 3 available
    assert a.lookup(h) == p  # cache intact
    assert a.cached_idle_pages == 2


@pytest.mark.slow
def test_prefix_hit_never_mutates_adopted_pages():
    """Adopted prefix pages are shared read-only: a hit admission (whose
    suffix chunks and final scatter run) must leave their contents
    byte-identical — recomputing cached positions through a different
    XLA program than the one that produced them would silently corrupt
    concurrent readers."""
    rng = np.random.default_rng(11)
    p1 = rng.integers(1, CFG.vocab_size, 104).tolist()
    eng = _mk(prefix_cache=True)
    sp = SamplingParams(temperature=0.0, max_tokens=2)
    eng.generate([p1], sp)
    hashes = eng._prefix_hashes(p1, 0)
    pages = eng._alloc.lookup(hashes[: len(p1) // 16])
    assert len(pages) == 6
    before_k = np.asarray(eng.cache.k_pages[:, pages])
    before_v = np.asarray(eng.cache.v_pages[:, pages])
    # Short suffix (< prefill_chunk): exercises the forward-padded final
    # chunk, the case where back-alignment would recompute cached
    # positions.
    p2 = p1 + [1, 2, 3]
    eng.generate([p2], sp)
    assert eng.prefix_stats["hit_tokens"] >= 96
    np.testing.assert_array_equal(
        np.asarray(eng.cache.k_pages[:, pages]), before_k
    )
    np.testing.assert_array_equal(
        np.asarray(eng.cache.v_pages[:, pages]), before_v
    )


@pytest.mark.slow
def test_prefix_cache_short_prompts_take_batched_path():
    """Prompts at or under prefill_chunk admit through the BATCHED
    prefill with the cache enabled (regression: the batch tuple grew a
    hashes element that every consumer must unpack), and full pages
    still register."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, CFG.vocab_size, 20).tolist() for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    want = _mk().generate(prompts, sp)
    eng = _mk(prefix_cache=True)
    assert eng.generate(prompts, sp) == want
    assert eng.prefix_stats["prompt_tokens"] == 60
    # 20 tokens = 1 full 16-token page each -> registered and hittable.
    assert eng.generate(prompts, sp) == want
    assert eng.prefix_stats["hit_tokens"] >= 48


@pytest.mark.slow
def test_prefix_cache_near_max_seq_len_prompt():
    """A prompt whose cached prefix would push the padded suffix chunk
    past the staging buffer (cached_len + prefill_chunk > max_seq_len)
    must cap the hit instead of letting dynamic_update_slice clamp the
    write offset — the clamp would corrupt staged KV and scatter it
    into shared pages."""
    rng = np.random.default_rng(31)
    p1 = rng.integers(1, CFG.vocab_size, 250).tolist()  # near max 256
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    want = _mk(max_seq_len=256).generate([p1], sp)
    eng = _mk(prefix_cache=True, max_seq_len=256)
    assert eng.generate([p1], sp) == want  # registers 15 full pages
    hashes = eng._prefix_hashes(p1, 0)
    pages = eng._alloc.lookup(hashes)
    before_k = np.asarray(eng.cache.k_pages[:, pages])
    # Resubmission: uncapped, the hit would be 240 tokens and the padded
    # chunk would start at 240 with C=32 -> 272 > 256.
    assert eng.generate([p1], sp) == want
    assert eng.prefix_stats["hit_tokens"] > 0
    np.testing.assert_array_equal(
        np.asarray(eng.cache.k_pages[:, pages]), before_k
    )


@pytest.mark.slow
def test_prefix_cache_qwen_family():
    """Qwen (llama computation + q/k/v biases) supports chunked prefill
    and therefore the prefix cache — regression for the family-name
    gate that excluded it."""
    import dataclasses as dc

    qcfg = dc.replace(llama.LlamaConfig.tiny(), attention_bias=True)
    qparams = llama.init_params(qcfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(13)
    system = rng.integers(1, qcfg.vocab_size, 48).tolist()
    prompts = [system + rng.integers(1, qcfg.vocab_size, 12).tolist()
               for _ in range(2)]
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    base = dict(num_slots=2, max_seq_len=256, page_size=16, prefill_chunk=32)
    want = Engine("qwen", qcfg, qparams, cfg=EngineConfig(**base)).generate(
        prompts, sp
    )
    eng = Engine(
        "qwen", qcfg, qparams,
        cfg=EngineConfig(prefix_cache=True, **base),
    )
    assert eng.generate(prompts, sp) == want
    assert eng.prefix_stats["hit_tokens"] > 0


@pytest.mark.slow
def test_prefix_cache_preemption_with_shared_pages():
    """Decode-time pool exhaustion with the cache on: preempted victims
    hold ADOPTED (shared) pages, so preemption decrefs rather than
    frees, resumes recompute without the cache (forced-token path), and
    streams still exactly match the unconstrained engine."""
    rng = np.random.default_rng(23)
    system = rng.integers(1, CFG.vocab_size, 32).tolist()
    prompts = [system + rng.integers(1, CFG.vocab_size, 4).tolist()
               for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_tokens=120)
    want = _mk(num_slots=3).generate(prompts, sp)
    # 120-token generations need ~10 pages per sequence (30 total) but
    # the pool holds 16 usable -> decode-time preemption while the
    # system-prefix pages are shared between live slots.
    tight = _mk(prefix_cache=True, num_slots=3, num_pages=1 + 16)
    assert tight.generate(prompts, sp) == want
    # Allocator bookkeeping intact after the churn: everything released,
    # cache survivors are idle, refcounts drained.
    assert all(v == 0 for v in tight._alloc._ref.values())
    assert tight._alloc.free_pages == 16
