"""Paged-cache engine: equivalence with the cache-free forward, page
accounting, oversubscription preemption with recompute resume."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.models import llama

CFG = llama.LlamaConfig.tiny()
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0))


def _make(**kw):
    defaults = dict(num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4)
    defaults.update(kw)
    return Engine("llama", CFG, PARAMS, cfg=EngineConfig(**defaults))


def _prompts(n, rng=None, vocab=CFG.vocab_size):
    rng = rng or np.random.default_rng(42)
    return [
        rng.integers(1, vocab, rng.integers(3, 40)).tolist()
        for _ in range(n)
    ]


def test_the_cache_is_the_page_pool():
    from kubeai_tpu.engine.paged_cache import PagedKVCache

    eng = Engine(
        "llama", CFG, PARAMS, cfg=EngineConfig(num_slots=2, max_seq_len=64)
    )
    assert isinstance(eng.cache, PagedKVCache)
    assert eng.kv_cache_info()["num_pages"] == eng.cfg.effective_num_pages()


def test_a_family_without_a_paged_forward_is_refused():
    """Not a silent second cache: the page pool is the only one."""
    from kubeai_tpu.models.registry import ModelFamily, get_model_family

    full = get_model_family("llama")
    stub = ModelFamily("dense-only-stub", **{k: getattr(full, k) for k in (
        "config_from_hf", "tiny_config", "init_params", "param_specs",
        "prefill", "decode_step")})
    assert stub.decode_step_paged is None
    with pytest.raises(ValueError, match="dense-only-stub"):
        Engine(stub, CFG, PARAMS, cfg=EngineConfig(num_slots=2, max_seq_len=64))


def _family_world(name, dtype=None):
    """(family, tiny config, params): llama, mixtral (MoE FFN) and gemma-2
    (sliding window on every other layer, attention and final softcap).
    `dtype` overrides the weights' (the configs' own is bfloat16)."""
    from kubeai_tpu.models import gemma, mixtral

    if name == "llama":
        cfg, init, key = CFG, llama.init_params, 0
    elif name == "mixtral":
        cfg, init, key = mixtral.MixtralConfig.tiny(), mixtral.init_params, 1
    else:
        cfg, init, key = dataclasses.replace(
            gemma.GemmaConfig.tiny(), sandwich_norms=True,
            attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
            query_pre_attn_scalar=16.0, sliding_window=8,
        ), gemma.init_params, 2
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    family = "gemma" if name == "gemma2" else name
    return family, cfg, init(cfg, jax.random.PRNGKey(key))


def check_against_cache_free_forward(name, prompt_lens, sp, **engine_kw):
    """Serve random prompts of `prompt_lens` from a paged engine over
    float32 weights and pool (no near tie decides a token), then re-run
    `family.prefill` on prompt + tokens so far, with no cache of any kind,
    and put its last-position logits through the sampler at the fold-in
    value the engine uses (the context's length): every served token
    must be the one that comes out."""
    from kubeai_tpu.engine.sampling import sample
    from kubeai_tpu.models.registry import get_model_family

    family, cfg, params = _family_world(name, dtype=jnp.float32)
    ecfg = EngineConfig(**{
        "num_slots": 3, "max_seq_len": 64, "page_size": 16,
        "decode_chunk": 4, "cache_dtype": jnp.float32, **engine_kw,
    })
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in prompt_lens]
    outs = Engine(family, cfg, params, cfg=ecfg).generate(prompts, sp)
    assert [len(o) for o in outs] == [sp.max_tokens] * len(prompts)

    # One row a served token: the context that produced it, right-padded.
    contexts = [p + o[:i] for p, o in zip(prompts, outs)
                for i in range(len(o))]
    lengths = np.asarray([len(c) for c in contexts], np.int32)
    tokens = np.zeros((len(contexts), ecfg.max_seq_len), np.int32)
    for row, c in zip(tokens, contexts):
        row[: len(c)] = c
    logits = jax.jit(get_model_family(family).prefill, static_argnums=1)(
        params, cfg, jnp.asarray(tokens), jnp.asarray(lengths))[0]
    n = len(contexts)
    want = sample(
        logits,
        jnp.full((n,), (sp.seed or 0) & 0xFFFFFFFF, jnp.uint32),
        jnp.asarray(lengths),
        jnp.full((n,), sp.temperature, jnp.float32),
        jnp.full((n,), sp.top_k, jnp.int32),
        jnp.full((n,), sp.top_p, jnp.float32),
    )
    assert [t for o in outs for t in o] == np.asarray(want).tolist()


@pytest.mark.parametrize("name", ["llama", "gemma2", "mixtral"])
def test_paged_greedy_equals_the_cache_free_forward(name):
    """The independent check of the paged path: batched admission, the
    decode chunk (of 4, so several) and the in-place kernel's reference,
    across a page boundary (16) and, for gemma-2, past the window (8)."""
    check_against_cache_free_forward(
        name, (5, 13, 19), SamplingParams(temperature=0.0, max_tokens=6))


@pytest.mark.slow
@pytest.mark.parametrize(
    "sp",
    [
        SamplingParams(temperature=0.0, max_tokens=12),
        SamplingParams(temperature=0.9, top_k=20, max_tokens=10, seed=123),
    ],
    ids=["greedy", "seeded-sampling"],
)
def test_paged_equals_the_cache_free_forward_long(sp):
    """The long form: more prompts than slots, more tokens, and the
    seeded sampler's position fold-in."""
    check_against_cache_free_forward("llama", (3, 9, 17, 26, 33, 39), sp)


@pytest.fixture(scope="module", params=["llama", "mixtral", "gemma2"])
def layouts(request):
    """One family's engines over the same weights: the two kinds of pool
    (the pool's kind decides the layout), and a bf16 pool under the family
    with its decode forward held to the per-layer layout, the reference."""
    import copy

    from testutil import per_layer_forward

    from kubeai_tpu.models.registry import get_model_family

    family, cfg, params = _family_world(request.param)
    held = copy.copy(get_model_family(family))
    held.decode_step_paged = per_layer_forward(held)

    def mk(family=family, **kw):
        return Engine(family, cfg, params, cfg=EngineConfig(
            num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4, **kw))

    return {"bf16": mk(), "per_layer": mk(held), "int8": mk(kv_dtype="int8"),
            "vocab": cfg.vocab_size}


def test_layout_follows_the_pool(layouts):
    """The pool's kind decides, with no field to say otherwise: a bf16 pool
    is read and written in place (`stacked`), an int8 pool takes
    scatter-then-attend; /v1/state's `kv_cache` block names it."""
    bf16, int8 = layouts["bf16"], layouts["int8"]
    assert (bf16.decode_kernel, bf16.kv_layout) == ("fused", "stacked")
    assert (int8.decode_kernel, int8.kv_layout) == ("per_layer", "per_layer")
    assert bf16.kv_cache_info()["kv_layout"] == "stacked"
    assert int8.kv_cache_info()["kv_layout"] == "per_layer"
    assert "decode_kernel" not in {
        f.name for f in dataclasses.fields(EngineConfig)}


@pytest.mark.parametrize(
    "sp",
    [
        SamplingParams(temperature=0.0, max_tokens=12),
        SamplingParams(temperature=0.9, top_k=20, max_tokens=10, seed=123),
    ],
    ids=["greedy", "seeded-sampling"],
)
def test_stacked_layout_equals_scatter_then_attend(layouts, sp):
    """Token for token, across chunk boundaries (decode_chunk=4) and, for
    gemma-2, past the sliding window of 8."""
    prompts = _prompts(5, vocab=layouts["vocab"])
    assert layouts["bf16"].generate(prompts, sp) == (
        layouts["per_layer"].generate(prompts, sp))


def test_int8_pool_with_nothing_set_boots_and_generates(layouts):
    prompts = _prompts(3, np.random.default_rng(7), layouts["vocab"])
    out = layouts["int8"].generate(
        prompts, SamplingParams(temperature=0.0, max_tokens=8))
    assert [len(o) for o in out] == [8, 8, 8]


@pytest.mark.slow
def test_pages_released_on_completion():
    eng = _make()
    total = eng._alloc.free_pages
    outs = eng.generate(_prompts(5), SamplingParams(temperature=0.0, max_tokens=6))
    assert len(outs) == 5
    assert eng._alloc.free_pages == total  # all pages returned


@pytest.mark.slow
def test_oversubscribed_pool_defers_admission():
    # Pool holds ~1.5 max sequences; 4 slots want in. Admission defers,
    # everyone completes eventually.
    eng = _make(num_pages=1 + 12)  # 12 usable pages of 16 toks
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    outs = eng.generate(_prompts(4), sp)
    assert all(len(o) == 8 for o in outs)


@pytest.mark.slow
def test_preemption_recompute_matches_unconstrained():
    """Decode-time pool exhaustion preempts the youngest request; its
    recompute resume must reproduce exactly the unconstrained stream."""
    rng = np.random.default_rng(3)
    # Long generations force page growth mid-decode.
    prompts = [rng.integers(1, CFG.vocab_size, 20).tolist() for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_tokens=40)
    want = _make().generate(prompts, sp)

    tight = _make(num_pages=1 + 9)  # pages for ~2 sequences
    got = tight.generate(prompts, sp)
    assert got == want

    # Seeded sampling also replays identically across preemption.
    sp2 = SamplingParams(temperature=0.8, top_k=16, max_tokens=30, seed=9)
    want2 = _make().generate(prompts, sp2)
    got2 = _make(num_pages=1 + 9).generate(prompts, sp2)
    assert got2 == want2


def test_pool_too_small_for_one_sequence_rejected():
    with pytest.raises(ValueError):
        _make(num_pages=4)  # < max_seq_len/page_size + scratch


@pytest.mark.slow
def test_cancel_frees_pages():
    eng = _make()
    total = eng._alloc.free_pages
    sp = SamplingParams(temperature=0.0, max_tokens=50)
    rid = eng.add_request(list(range(1, 30)), sp)
    eng.step()
    assert eng._alloc.free_pages < total
    eng.cancel(rid)
    assert eng._alloc.free_pages == total
    eng.step()  # stale block-table rows must not crash the next step


@pytest.mark.slow
def test_ring_prefill_serving_path(monkeypatch):
    """Sequence parallelism is a SERVING path: an engine whose mesh has
    sp>1 prefills with ring attention (sequence sharded over sp, K/V
    rotated via ppermute) and produces the same greedy stream as a
    single-device engine."""
    devs = jax.devices()
    if len(devs) < 2:
        import pytest

        pytest.skip("needs 2 virtual devices")
    from kubeai_tpu.parallel import ring_attention as ra
    from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh

    calls = {"n": 0}
    orig = ra.ring_attention_sharded

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(ra, "ring_attention_sharded", spy)

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, CFG.vocab_size, 40).tolist() for _ in range(2)]
    sp_param = SamplingParams(temperature=0.0, max_tokens=6)

    mesh = build_mesh(MeshConfig(sp=2), devices=devs[:2])
    eng_sp = Engine(
        "llama", CFG, PARAMS, mesh=mesh,
        cfg=EngineConfig(num_slots=2, max_seq_len=128, page_size=16),
    )
    got = eng_sp.generate(prompts, sp_param)
    assert calls["n"] > 0, "ring attention never engaged in serving prefill"

    want = _make(num_slots=2).generate(prompts, sp_param)
    assert got == want


@pytest.mark.slow
def test_speculative_greedy_matches_vanilla():
    """Prompt-lookup speculation emits EXACTLY the vanilla stream —
    greedy, including repetitive prompts where acceptance is high and a
    max_seq_len-boundary case."""
    rng = np.random.default_rng(21)
    repetitive = ([7, 8, 9, 10] * 12)[:40]  # n-grams repeat → accepts
    prompts = [
        repetitive,
        rng.integers(1, CFG.vocab_size, 23).tolist(),
        rng.integers(1, CFG.vocab_size, 9).tolist(),
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=30)
    want = _make().generate(prompts, sp)
    eng = _make(speculate=4, spec_adaptive=False)
    assert eng._spec == 4
    got = eng.generate(prompts, sp)
    assert got == want

    # Boundary: generation runs into max_seq_len mid-window.
    long_prompt = ([3, 4, 5] * 40)[:110]
    sp2 = SamplingParams(temperature=0.0, max_tokens=64)
    want2 = _make().generate([long_prompt], sp2)
    got2 = _make(speculate=4, spec_adaptive=False).generate([long_prompt], sp2)
    assert got2 == want2


@pytest.mark.slow
def test_speculative_seeded_matches_vanilla():
    rng = np.random.default_rng(22)
    prompts = [
        ([5, 6] * 20)[:30],
        rng.integers(1, CFG.vocab_size, 17).tolist(),
    ]
    sp = SamplingParams(temperature=0.9, top_k=12, max_tokens=20, seed=77)
    want = _make().generate(prompts, sp)
    got = _make(speculate=3, spec_adaptive=False).generate(prompts, sp)
    assert got == want


@pytest.mark.slow
def test_speculative_accepts_on_repetitive_text():
    """On repetitive context the lookup proposals are right, so steps
    emit >1 token — fewer device steps than tokens."""
    eng = _make(speculate=4, spec_adaptive=False)
    prompt = ([11, 12, 13, 14, 15] * 10)[:45]
    sp = SamplingParams(temperature=0.0, max_tokens=24)
    out = eng.generate([prompt], sp)[0]
    assert len(out) == 24
    # steps counter: admission + N spec steps; acceptance must have
    # compressed 24 tokens into fewer than 24 decode steps.
    assert eng._steps < 24, f"no acceptance: {eng._steps} steps"


def test_ngram_proposer():
    propose = Engine._ngram_propose
    ctx = np.asarray([1, 2, 3, 9, 1, 2, 3], np.int32)
    # suffix [1,2,3] matched at start → proposes the continuation [9, ...]
    got = propose(ctx, 3)
    assert got[0] == 9
    # No match anywhere: repeat-last fallback.
    got = propose(np.asarray([4, 5, 6], np.int32), 2)
    assert list(got) == [6, 6]


def test_ngram_indexed_matches_scan_proposer():
    """The O(γ) incremental index must propose exactly what the full
    rescan proposes, across growing contexts."""
    from kubeai_tpu.engine.engine import _Request

    rng = np.random.default_rng(31)
    tokens = rng.integers(1, 6, 200).tolist()  # small vocab → many repeats
    req = _Request(rid=0, prompt=tokens[:20], params=SamplingParams(), seed=0)
    req.ctx = np.empty(512, np.int32)
    req.ctx[:20] = tokens[:20]
    req.ctx_len = 20
    req.ngram_idx = {n: {} for n in (3, 2, 1)}
    req.ngram_upto = {n: 0 for n in (3, 2, 1)}
    for t in tokens[20:]:
        req.ctx[req.ctx_len] = t
        req.ctx_len += 1
        want = Engine._ngram_propose(req.ctx[: req.ctx_len], 4)
        got = Engine._ngram_propose_indexed(req, 4)
        assert list(got) == list(want), req.ctx_len


@pytest.mark.slow
def test_chunked_prefill_paged_matches_whole_prompt():
    """prefill_chunk in PAGED mode (staged chunks -> page scatter) emits
    exactly the whole-prompt paged stream, greedy and seeded; short
    prompts (<= chunk) keep using the batched admission path."""
    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(1, CFG.vocab_size, n).tolist() for n in (5, 23, 40, 61)
    ]
    for sp in (
        SamplingParams(temperature=0.0, max_tokens=10),
        SamplingParams(temperature=0.9, top_k=12, max_tokens=8, seed=77),
    ):
        want = _make().generate(prompts, sp)
        assert _make(prefill_chunk=16).generate(prompts, sp) == want


@pytest.mark.slow
def test_chunked_prefill_paged_preemption_resume():
    """A preempted long-prompt request re-admits through the chunked
    path with its forced token; the stream must match unconstrained."""
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, CFG.vocab_size, 30).tolist() for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_tokens=40)
    want = _make(prefill_chunk=16).generate(prompts, sp)
    tight = _make(prefill_chunk=16, num_pages=1 + 9)
    assert tight.generate(prompts, sp) == want


@pytest.mark.slow
def test_chunked_prefill_nondivisible_tail():
    """ceil(plen/C)*C > max_seq_len used to make the final chunk's
    dynamic_update_slice CLAMP its start and silently corrupt staged KV;
    the backward-aligned final chunk must match whole-prompt output,
    and the cache-free forward."""
    rng = np.random.default_rng(17)
    prompt = rng.integers(1, CFG.vocab_size, 97).tolist()  # 7*16 = 112 > 100
    sp = SamplingParams(temperature=0.0, max_tokens=3)
    want = _make(max_seq_len=100).generate([prompt], sp)
    got = _make(max_seq_len=100, prefill_chunk=16).generate([prompt], sp)
    assert got == want
    check_against_cache_free_forward(
        "llama", (97,), sp, max_seq_len=100, prefill_chunk=16)


@pytest.mark.slow
def test_adaptive_speculation_streams_match_vanilla():
    """With spec_adaptive (default), the engine may interleave speculative
    windows and fused chunks based on measured throughput — the emitted
    stream must be identical to vanilla decoding either way."""
    rng = np.random.default_rng(31)
    prompts = [
        ([4, 5, 6] * 15)[:33],               # repetitive: spec-friendly
        rng.integers(1, CFG.vocab_size, 21).tolist(),  # random: chunk-friendly
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=40)
    want = _make().generate(prompts, sp)
    eng = _make(speculate=4)  # spec_adaptive defaults True
    got = eng.generate(prompts, sp)
    assert got == want
    # Both arms were sampled at least once (epsilon-greedy bootstrap).
    assert eng._mode_calls.get("spec", 0) >= 1
    assert eng._mode_calls.get("chunk", 0) >= 1


def test_adaptive_pick_follows_measured_throughput():
    """The mode chooser is epsilon-greedy on the tokens/s EMAs: after both
    arms are sampled it runs the winner, probing the loser periodically."""
    eng = _make(speculate=4, spec_probe_every=8)
    # Bootstrap: first two calls per arm (call 1 = compile, not folded).
    assert eng._spec_pick() is True
    eng._spec_observe("spec", 4, 1.0)
    assert eng._spec_pick() is True
    eng._spec_observe("spec", 4, 1.0)      # spec EMA = 4 tok/s
    assert eng._spec_pick() is False
    eng._spec_observe("chunk", 16, 1.0)
    assert eng._spec_pick() is False
    eng._spec_observe("chunk", 16, 1.0)    # chunk EMA = 16 tok/s
    # Winner (chunk) runs; the losing arm is probed on the probe boundary.
    picks = [eng._spec_pick() for _ in range(16)]
    assert picks.count(False) >= 14           # chunk dominates
    assert picks.count(True) >= 1             # spec re-probed
    # A workload shift (spec suddenly fast) flips the choice after probes.
    for _ in range(4):
        eng._spec_observe("spec", 100, 1.0)
    assert eng._spec_pick() is True


def test_adaptive_off_always_speculates():
    eng = _make(speculate=4, spec_adaptive=False)
    assert all(eng._spec_pick() for _ in range(50))


@pytest.mark.slow
def test_speculation_on_sp_mesh_matches_single_device():
    """Speculation composes with sequence parallelism: ring-attention
    prefill over sp + the speculative verify (GSPMD over the same mesh)
    emit the vanilla single-device stream — greedy on a repetitive
    prompt where acceptance is high."""
    devs = jax.devices()
    if len(devs) < 2:
        import pytest as _pytest

        _pytest.skip("needs 2 virtual devices")
    from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh

    repetitive = ([7, 8, 9, 10] * 12)[:40]
    prompts = [repetitive, [1, 2, 3, 4]]
    sp_param = SamplingParams(temperature=0.0, max_tokens=12)
    want = _make(num_slots=2).generate(prompts, sp_param)
    mesh = build_mesh(MeshConfig(sp=2), devices=devs[:2])
    eng = Engine(
        "llama", CFG, PARAMS, mesh=mesh,
        cfg=EngineConfig(num_slots=2, max_seq_len=128, page_size=16,
                         speculate=4, spec_adaptive=False),
    )
    assert eng.generate(prompts, sp_param) == want
    assert eng.spec_stats["accepted"] > 0
