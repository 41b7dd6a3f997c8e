"""Draft-model speculative decoding.

A small same-family draft proposes the speculative window instead of
prompt-lookup. The contract under test:
  1. EXACTNESS — the emitted stream is bit-identical to vanilla decoding
     no matter how bad the draft is (verify truncates at the first
     mismatch against the target's own seeded sampler).
  2. ACCEPTANCE — on non-repetitive text, where prompt-lookup collapses
     (its proposals come from n-gram repeats), a draft that agrees with
     the target keeps acceptance high. Using the TARGET ITSELF as the
     draft gives an agreement ceiling of 100%, so greedy acceptance must
     be exactly γ per window — and measurably above prompt-lookup's on
     the same prompts.
"""

import dataclasses as dc

import jax
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.models import llama

CFG = dc.replace(llama.LlamaConfig.tiny(), num_layers=2)
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0))
# A smaller, independently-initialized draft (disagrees with the target
# most of the time — the exactness tests' worst case).
DRAFT_CFG = dc.replace(
    llama.LlamaConfig.tiny(), num_layers=1, hidden_size=32,
    intermediate_size=64,
)
DRAFT_PARAMS = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(7))


def _mk(speculate=0, draft=None, **kw):
    defaults = dict(
        num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4,
        spec_adaptive=False,
    )
    defaults.update(kw)
    return Engine(
        "llama", CFG, PARAMS,
        cfg=EngineConfig(speculate=speculate, **defaults),
        draft=draft,
    )


def _prompts(n, seed=42):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, CFG.vocab_size, rng.integers(5, 40)).tolist()
        for _ in range(n)
    ]


@pytest.mark.slow
def test_draft_spec_greedy_matches_vanilla():
    prompts = _prompts(5)
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    want = _mk().generate(prompts, sp)
    eng = _mk(speculate=3, draft=(DRAFT_CFG, DRAFT_PARAMS))
    assert eng._draft  # the draft path is actually active
    assert eng.generate(prompts, sp) == want


@pytest.mark.slow
def test_draft_spec_seeded_matches_vanilla():
    prompts = _prompts(4, seed=9)
    sp = SamplingParams(temperature=0.9, top_k=20, max_tokens=12, seed=31)
    want = _mk().generate(prompts, sp)
    got = _mk(speculate=3, draft=(DRAFT_CFG, DRAFT_PARAMS)).generate(
        prompts, sp
    )
    assert got == want


@pytest.mark.slow
def test_draft_spec_multiple_batches_reuse_slots():
    """Slot reuse: draft KV rows from a finished request must not leak
    into the next request admitted to the same slot."""
    sp = SamplingParams(temperature=0.0, max_tokens=8)
    eng = _mk(speculate=3, draft=(DRAFT_CFG, DRAFT_PARAMS))
    want = _mk()
    for seed in (1, 2):
        prompts = _prompts(6, seed=seed)  # > num_slots: forces reuse
        assert eng.generate(prompts, sp) == want.generate(prompts, sp)


@pytest.mark.slow
def test_self_draft_acceptance_is_total_where_lookup_collapses():
    """Target-as-draft on random (non-repetitive) prompts: greedy
    proposals are the target's own argmax chain, so every window accepts
    all γ tokens — while prompt-lookup on the same prompts accepts
    (nearly) nothing. This is the draft's reason to exist.

    float32: the draft chain (dense-cache attention) and verify (paged
    multi-query path) are different implementations, and a random-init
    tiny model's flat logits near-tie often enough in bf16 to break
    draft/target agreement ~20% of the time (exactness is unaffected —
    verify corrects every mismatch); f32 removes the ties so the
    agreement ceiling is actually reachable."""
    import jax.numpy as jnp

    cfg32 = dc.replace(CFG, dtype=jnp.float32)
    params32 = llama.init_params(cfg32, jax.random.PRNGKey(0))
    prompts = _prompts(4, seed=5)
    sp = SamplingParams(temperature=0.0, max_tokens=16)

    def mk32(**kw):
        return Engine(
            "llama", cfg32, params32,
            cfg=EngineConfig(
                num_slots=4, max_seq_len=128, page_size=16,
                decode_chunk=4, spec_adaptive=False, speculate=3,
                cache_dtype=jnp.float32,
            ),
            **kw,
        )

    eng_draft = mk32(draft=(cfg32, params32))
    out_draft = eng_draft.generate(prompts, sp)
    s = eng_draft.spec_stats
    assert s["windows"] > 0
    assert s["accepted"] == s["proposed"], s  # 100% acceptance

    eng_lookup = mk32()
    out_lookup = eng_lookup.generate(prompts, sp)
    sl = eng_lookup.spec_stats
    assert out_draft == out_lookup  # both exact vs vanilla
    draft_rate = s["accepted"] / s["proposed"]
    lookup_rate = sl["accepted"] / max(1, sl["proposed"])
    assert draft_rate > lookup_rate + 0.5, (draft_rate, lookup_rate)


@pytest.mark.slow
def test_draft_with_chunked_prefill_matches_vanilla():
    """Round-5 composition: chunked TARGET admission keeps the draft's
    dense cache in sync via the draft's own chunked prefill
    (_draft_admit_chunked), so long prompts stream exactly like vanilla
    with a disagreeing draft."""
    rng = np.random.default_rng(17)
    prompts = [
        rng.integers(1, CFG.vocab_size, n).tolist() for n in (50, 90, 12)
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    want = _mk().generate(prompts, sp)
    eng = _mk(speculate=3, draft=(DRAFT_CFG, DRAFT_PARAMS), prefill_chunk=16)
    assert eng.generate(prompts, sp) == want


@pytest.mark.slow
def test_draft_with_prefix_cache_accepts():
    """--draft-url + --prefix-cache coexist: a prefix-hit admission
    still draft-prefills the FULL prompt (the draft shares no pages), so
    target-as-draft acceptance stays total and streams stay exact."""
    rng = np.random.default_rng(19)
    system = rng.integers(1, CFG.vocab_size, 48).tolist()
    prompts = [system + rng.integers(1, CFG.vocab_size, 10).tolist()
               for _ in range(2)]
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    want = _mk().generate(prompts, sp)
    eng = _mk(
        speculate=3, draft=(CFG, PARAMS),  # target-as-draft: 100% agree
        prefill_chunk=16, prefix_cache=True,
    )
    assert eng.generate(prompts, sp) == want
    assert eng.prefix_stats["hit_tokens"] > 0  # second prompt hit
    s = eng.spec_stats
    assert s["accepted"] == s["proposed"]  # ceiling acceptance held


def test_draft_without_speculation_rejected():
    """A draft is explicit caller intent — dropping it silently would
    hide the misconfiguration."""
    with pytest.raises(ValueError, match="speculate == 0"):
        _mk(speculate=0, draft=(DRAFT_CFG, DRAFT_PARAMS))
    # Nor where the family has no verify forward (mixtral has none).
    from kubeai_tpu.models import mixtral

    mcfg = mixtral.MixtralConfig.tiny()
    mparams = mixtral.init_params(mcfg, jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="unavailable"):
        Engine(
            "mixtral", mcfg, mparams, draft=(mcfg, mparams),
            cfg=EngineConfig(num_slots=2, max_seq_len=64, speculate=3),
        )


@pytest.mark.slow
def test_adaptive_chunk_windows_keep_draft_synced():
    """spec_adaptive (the default) interleaves chunk-mode windows, which
    advance sequences without the draft proposing; the catch-up pass must
    keep the draft cache in lockstep so spec windows AFTER a chunk window
    still accept (target-as-draft in f32 ⇒ acceptance stays total)."""
    import jax.numpy as jnp

    cfg32 = dc.replace(CFG, dtype=jnp.float32)
    params32 = llama.init_params(cfg32, jax.random.PRNGKey(0))
    prompts = _prompts(4, seed=11)
    sp = SamplingParams(temperature=0.0, max_tokens=24)
    eng = Engine(
        "llama", cfg32, params32,
        cfg=EngineConfig(
            num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4,
            speculate=3, spec_adaptive=True, spec_probe_every=2,
            cache_dtype=jnp.float32,
        ),
        draft=(cfg32, params32),
    )
    want = Engine(
        "llama", cfg32, params32,
        cfg=EngineConfig(
            num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4,
            cache_dtype=jnp.float32,
        ),
    )
    assert eng.generate(prompts, sp) == want.generate(prompts, sp)
    s = eng.spec_stats
    assert eng._mode_calls.get("chunk", 0) >= 2  # chunk windows DID run
    if s["windows"]:
        assert s["accepted"] == s["proposed"], s
