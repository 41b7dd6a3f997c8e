"""Engine tests: continuous batching semantics, determinism, slot reuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.models import llama
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(
        "llama",
        cfg,
        params,
        cfg=EngineConfig(num_slots=4, max_seq_len=64),
    )


GREEDY = SamplingParams(temperature=0.0, max_tokens=8)


@pytest.mark.slow
def test_greedy_generation_deterministic(tiny_engine):
    prompt = [1, 2, 3, 4, 5]
    out1 = tiny_engine.generate([prompt], GREEDY)[0]
    out2 = tiny_engine.generate([prompt], GREEDY)[0]
    assert out1 == out2
    assert len(out1) == 8


def test_batched_equals_sequential(tiny_engine):
    """Continuous batching must not change greedy outputs."""
    prompts = [[1, 2, 3], [9, 8, 7, 6], [5, 5, 5, 5, 5], [2, 4]]
    batched = tiny_engine.generate(prompts, GREEDY)
    for p, want in zip(prompts, batched):
        got = tiny_engine.generate([p], GREEDY)[0]
        assert got == want, f"prompt {p}: {got} != {want}"


def test_more_requests_than_slots(tiny_engine):
    """6 requests on 4 slots: queueing + slot reuse must work."""
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    outs = tiny_engine.generate(prompts, GREEDY)
    assert all(len(o) == 8 for o in outs)
    # Same prompt queued late == run alone.
    solo = tiny_engine.generate([prompts[5]], GREEDY)[0]
    assert outs[5] == solo


def test_streaming_step_api(tiny_engine):
    rid = tiny_engine.add_request([3, 1, 4, 1, 5], GREEDY)
    seen, reasons = [], []
    while tiny_engine.has_work():
        for ev in tiny_engine.step():
            if ev.rid == rid:
                seen.append(ev.token)
                reasons.append(ev.finish_reason)
    assert len(seen) == 8
    assert reasons[-1] == "length" and all(r == "" for r in reasons[:-1])
    # Finished requests are evicted (no leak).
    assert rid not in tiny_engine._requests
    # Streaming == blocking for the same prompt.
    assert seen == tiny_engine.generate([[3, 1, 4, 1, 5]], GREEDY)[0]


def test_cancel_and_seeded_reproducibility(tiny_engine):
    # Cancel a pending request.
    rid = tiny_engine.add_request([1, 2, 3], GREEDY)
    assert tiny_engine.cancel(rid)
    assert not tiny_engine.cancel(rid)  # already gone
    assert tiny_engine.num_pending == 0

    # A seeded request replays identically even with different batch-mates.
    seeded = SamplingParams(temperature=0.9, top_k=20, max_tokens=6, seed=123)
    a = tiny_engine.generate([[4, 5, 6]], seeded)[0]
    b = tiny_engine.generate([[4, 5, 6], [7, 7, 7], [1, 9, 2]], seeded)[0]
    assert a == b


def test_top_p_zero_degrades_to_greedy(tiny_engine):
    near_greedy = SamplingParams(temperature=1.0, top_p=0.0, max_tokens=6)
    got = tiny_engine.generate([[2, 3, 4]], near_greedy)[0]
    want = tiny_engine.generate([[2, 3, 4]], SamplingParams(temperature=0.0, max_tokens=6))[0]
    assert got == want


@pytest.mark.slow
def test_max_tokens_and_eos():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        "llama",
        cfg,
        params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64),
    )
    # Find what greedy emits first, then use it as the EOS token.
    first = eng.generate([[1, 2, 3]], GREEDY)[0][0]
    eng2 = Engine(
        "llama",
        cfg,
        params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64),
        eos_token_ids=(first,),
    )
    out = eng2.generate([[1, 2, 3]], GREEDY)[0]
    assert out == [first]  # stopped immediately at EOS


@pytest.mark.slow
def test_sharded_engine_tp_matches_single(devices8):
    """TP over a 4-device mesh must give identical greedy tokens."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_slots=2, max_seq_len=64)
    eng1 = Engine("llama", cfg, params, cfg=ecfg)
    mesh = build_mesh(MeshConfig(dp=1, sp=1, tp=4), devices=devices8[:4])
    eng4 = Engine("llama", cfg, params, mesh=mesh, cfg=ecfg)
    prompts = [[1, 2, 3, 4], [10, 20, 30]]
    out1 = eng1.generate(prompts, GREEDY)
    out4 = eng4.generate(prompts, GREEDY)
    assert out1 == out4


@pytest.mark.slow
def test_pipelined_stepping_equivalent():
    """The overlapped loop must emit the synchronous loop's token stream,
    one chunk late."""
    from testutil import synchronous

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    base = synchronous(Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=3, max_seq_len=64, decode_chunk=4),
    ))
    piped = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=3, max_seq_len=64, decode_chunk=4),
    )
    assert piped._overlap and not base._overlap
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [2, 2]]  # > slots: queueing
    want = base.generate(prompts, GREEDY)
    got = piped.generate(prompts, GREEDY)
    assert got == want
    assert not piped.has_work()  # drain complete, no stuck inflight

    # Streaming events still carry correct finish reasons.
    rid = piped.add_request([3, 1, 4], GREEDY)
    evs = []
    while piped.has_work():
        evs.extend(e for e in piped.step() if e.rid == rid)
    assert [e.token for e in evs] == piped.generate([[3, 1, 4]], GREEDY)[0]
    assert evs[-1].finished and evs[-1].finish_reason == "length"


@pytest.mark.slow
def test_int8_quantized_engine_close_to_bf16():
    """int8 weight-only quantization: engine runs and greedy outputs stay
    highly consistent with full precision on short generations."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    base = Engine("llama", cfg, params, cfg=EngineConfig(num_slots=2, max_seq_len=64))
    q8 = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64, quantization="int8"),
    )
    prompts = [[1, 2, 3, 4], [9, 8, 7]]
    want = base.generate(prompts, GREEDY)
    got = q8.generate(prompts, GREEDY)
    # Per-channel int8 on a tiny model: first tokens should agree.
    for w, g in zip(want, got):
        assert w[0] == g[0]
    assert all(len(g) == 8 for g in got)

    # TP-sharded quantized engine also runs (specs tree mirrors quant tree).
    import jax as _jax
    devs = _jax.devices()
    if len(devs) >= 2:
        mesh = build_mesh(MeshConfig(dp=1, sp=1, tp=2), devices=devs[:2])
        q8tp = Engine(
            "llama", cfg, params, mesh=mesh,
            cfg=EngineConfig(num_slots=2, max_seq_len=64, quantization="int8"),
        )
        assert q8tp.generate(prompts, GREEDY) == got


@pytest.mark.slow
def test_chunked_prefill_matches_bucketed():
    """prefill_chunk engine path == whole-prompt path, greedy-token exact."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    base = Engine("llama", cfg, params,
                  cfg=EngineConfig(num_slots=2, max_seq_len=64))
    chunked = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64, prefill_chunk=8),
    )
    prompts = [list(range(1, 21)), [5, 6, 7]]  # 20 toks (3 chunks) + short
    want = base.generate(prompts, GREEDY)
    got = chunked.generate(prompts, GREEDY)
    assert got == want


@pytest.mark.slow
def test_chunked_prefill_with_lora_and_seeds():
    import numpy as np

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    r, E, H, D, NL = 4, cfg.hidden_size, cfg.num_heads, cfg.head_size, cfg.num_layers
    A = (rng.standard_normal((NL, E, r)) * 0.8).astype(np.float32)
    B = (rng.standard_normal((NL, r, H * D)) * 0.8).astype(np.float32)
    mk = lambda pc: Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64, prefill_chunk=pc,
                         max_adapters=1, max_lora_rank=8),
    )
    base, chunked = mk(0), mk(8)
    for e in (base, chunked):
        e.load_adapter("fin", {"wq": (A, B)})
    prompt = list(range(1, 19))
    sp = SamplingParams(temperature=0.8, top_k=30, max_tokens=6, seed=42)
    assert base.generate([prompt], sp, adapter="fin") == chunked.generate(
        [prompt], sp, adapter="fin"
    )


def test_engine_config_field_count():
    """A ratchet on the engine's knobs, lowered by each PR that removes
    one and raised by none (ROADMAP.md C4: every field is a path somebody
    has to keep working)."""
    import dataclasses

    assert len(dataclasses.fields(EngineConfig)) <= 18
