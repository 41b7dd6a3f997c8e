"""Pipeline parallelism: GPipe stages over the pp mesh axis must compute
exactly what the sequential layer scan computes — including on the REAL
llama trunk layer — on the virtual multi-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.models import llama
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh
from kubeai_tpu.parallel.pipeline import pipeline_forward


@pytest.fixture
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


def _synthetic_layers(nl=4, e=16, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal((nl, e, e)) * 0.3, jnp.float32),
        "b": jnp.asarray(rng.standard_normal((nl, e)) * 0.1, jnp.float32),
    }


def _synthetic_fn(x, lp):
    return x + jnp.tanh(x @ lp["w"] + lp["b"])


def _scan_ref(layer_fn, params, x):
    return jax.lax.scan(lambda h, p: (layer_fn(h, p), None), x, params)[0]


@pytest.mark.parametrize("pp,microbatches", [(2, 2), (4, 4), (4, 8), (2, 1)])
def test_pipeline_matches_scan_synthetic(devices8, pp, microbatches):
    params = _synthetic_layers(nl=8)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    mesh = build_mesh(MeshConfig(pp=pp), devices=devices8[:pp])
    got = pipeline_forward(_synthetic_fn, params, x, mesh, microbatches)
    want = _scan_ref(_synthetic_fn, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pipeline_single_stage_passthrough(devices8):
    params = _synthetic_layers(nl=4)
    x = jnp.ones((4, 16), jnp.float32)
    mesh = build_mesh(MeshConfig(pp=1), devices=devices8[:1])
    got = pipeline_forward(_synthetic_fn, params, x, mesh, 2)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_scan_ref(_synthetic_fn, params, x)),
        atol=1e-6,
    )


def test_pipeline_llama_trunk(devices8):
    """The REAL llama trunk layer, staged pp=2 over its stacked params:
    final hidden states must match the sequential trunk exactly."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 12)), jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)

    mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
    got = pipeline_forward(
        lambda h, lp: llama.trunk_layer(h, lp, cfg),
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params["layers"]),
        x,
        mesh,
        microbatches=2,
    )
    want = _scan_ref(
        lambda h, lp: llama.trunk_layer(h, lp, cfg),
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params["layers"]),
        x,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_pipeline_validation_errors(devices8):
    params = _synthetic_layers(nl=5)  # not divisible by 2 stages
    mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
    with pytest.raises(ValueError):
        pipeline_forward(
            _synthetic_fn, params, jnp.ones((4, 16)), mesh, 2
        )
    params = _synthetic_layers(nl=4)
    with pytest.raises(ValueError):
        pipeline_forward(
            _synthetic_fn, params, jnp.ones((5, 16)), mesh, 2  # 5 % 2
        )


# ---- pipeline parallelism as a SERVING path --------------------------------
# The engine on a pp>1 mesh (stage-local layers + stage-local KV pages,
# models/llama.py decode_step_paged_pp) must stream exactly what the
# single-device engine streams.

import dataclasses as _dc

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams


def _pp_world(devices, pp, num_layers=4, microbatches=0):
    cfg = _dc.replace(llama.LlamaConfig.tiny(), num_layers=num_layers)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        num_slots=4, max_seq_len=96, decode_chunk=4,
        pp_microbatches=microbatches,
    )
    ref = Engine("llama", cfg, params, cfg=ecfg)
    mesh = build_mesh(MeshConfig(pp=pp), devices=devices[:pp])
    eng = Engine("llama", cfg, params, mesh=mesh, cfg=ecfg)
    return cfg, params, ref, eng


PP_PROMPTS = [
    [1, 2, 3, 4, 5, 6, 7],
    [9, 8, 7],
    [11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21],
    [30, 31],
]


@pytest.mark.parametrize("pp,microbatches", [(2, 0), (4, 0), (2, 4)])
@pytest.mark.slow
def test_engine_pp_matches_single_device(devices8, pp, microbatches):
    _, _, ref, eng = _pp_world(devices8, pp, microbatches=microbatches)
    sp = SamplingParams(temperature=0.0, max_tokens=24)
    assert eng.generate(PP_PROMPTS, sp) == ref.generate(PP_PROMPTS, sp)


@pytest.mark.parametrize("mesh_kw", [dict(pp=2, tp=2), dict(pp=2, tp=2, dp=2)])
@pytest.mark.slow
def test_engine_pp_tp_composed_matches_single_device(devices8, mesh_kw):
    """pp × tp (the 70B/v5e-8 shape, pp=2×tp=4 scaled down): the pp
    shard_map is manual over pp only, so Megatron tp sharding stays
    GSPMD-managed inside each stage. Greedy streams must match the
    single-device engine. float32 model: tp's GSPMD collectives inside
    the manual region legitimately reorder float ops, and in bf16 a
    random-init tiny model near-ties often enough to flip a greedy
    argmax; in f32 a flip needs a ~1e-7 logit tie."""
    cfg = _dc.replace(
        llama.LlamaConfig.tiny(), num_layers=4, dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        num_slots=4, max_seq_len=96, decode_chunk=4,
        cache_dtype=jnp.float32,
    )
    ref = Engine("llama", cfg, params, cfg=ecfg)
    n = 1
    for v in mesh_kw.values():
        n *= v
    mesh = build_mesh(MeshConfig(**mesh_kw), devices=devices8[:n])
    eng = Engine("llama", cfg, params, mesh=mesh, cfg=ecfg)
    sp = SamplingParams(temperature=0.0, max_tokens=24)
    assert eng.generate(PP_PROMPTS, sp) == ref.generate(PP_PROMPTS, sp)


@pytest.mark.slow
def test_decode_pp_tp_logits_match_single_device(devices8):
    """Function-level pp×tp check with a fixed paged-cache state:
    logits and (non-scratch) pool writes must match the single-device
    per-layer path to f32 tolerance."""
    import numpy as np

    from kubeai_tpu.parallel import sharding as psh

    cfg = _dc.replace(
        llama.LlamaConfig.tiny(), num_layers=4, dtype=jnp.float32
    )
    params0 = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(pp=2, tp=2), devices=devices8[:4])
    params = psh.shard_params(
        params0, llama.param_specs(cfg), mesh, psh.DEFAULT_RULES
    )
    B, NL, page = 4, 4, 16
    KVH, D = cfg.num_kv_heads, cfg.head_size
    n_pages = 1 + B * 2
    pool_sh = psh.named_sharding(
        mesh, (psh.LAYERS, None, None, psh.KV_HEADS, None),
        psh.DEFAULT_RULES,
    )
    rng = np.random.default_rng(0)
    kv0 = jnp.asarray(
        rng.standard_normal((NL, n_pages, page, KVH, D)) * 0.1, jnp.float32
    )
    vv0 = jnp.asarray(
        rng.standard_normal((NL, n_pages, page, KVH, D)) * 0.1, jnp.float32
    )
    kp = jax.device_put(kv0, pool_sh)
    vp = jax.device_put(vv0, pool_sh)
    bt = jnp.asarray([[1, 2], [3, 4], [5, 6], [7, 8]], jnp.int32)
    tokens = jnp.asarray([1, 2, 3, 4], jnp.int32)
    positions = jnp.asarray([20, 17, 9, 5], jnp.int32)
    lg_pp, kp1, vp1 = llama.decode_step_paged_pp(
        params, cfg, tokens, positions, kp, vp, bt,
        mesh=mesh, microbatches=2,
    )
    lg, kp2, vp2 = llama.decode_step_paged(
        params0, cfg, tokens, positions, kv0, vv0, bt,
        attn_kernel="per_layer",
    )
    np.testing.assert_allclose(
        np.asarray(lg_pp, np.float32), np.asarray(lg, np.float32), atol=1e-5
    )
    # Page 0 is the off-schedule scratch sink — it legitimately differs.
    np.testing.assert_allclose(
        np.asarray(kp1, np.float32)[:, 1:],
        np.asarray(kp2, np.float32)[:, 1:], atol=1e-5,
    )


@pytest.mark.slow
def test_engine_pp_seeded_sampling_matches(devices8):
    _, _, ref, eng = _pp_world(devices8, 2)
    sp = SamplingParams(temperature=0.9, seed=13, max_tokens=16)
    assert eng.generate(PP_PROMPTS, sp) == ref.generate(PP_PROMPTS, sp)


@pytest.mark.slow
def test_engine_pp_lora_matches(devices8):
    cfg = _dc.replace(llama.LlamaConfig.tiny(), num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    r = 4
    E, H, D, NL = cfg.hidden_size, cfg.num_heads, cfg.head_size, cfg.num_layers
    A = (rng.standard_normal((NL, E, r)) * 0.2).astype(np.float32)
    B = (rng.standard_normal((NL, r, H * D)) * 0.2).astype(np.float32)
    ecfg = EngineConfig(
        num_slots=4, max_seq_len=96, decode_chunk=4, max_adapters=1,
        max_lora_rank=8,
    )
    ref = Engine("llama", cfg, params, cfg=ecfg)
    mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
    eng = Engine("llama", cfg, params, mesh=mesh, cfg=ecfg)
    for e in (ref, eng):
        e.load_adapter("fin", {"wq": (A, B)})
    sp = SamplingParams(temperature=0.0, max_tokens=20)
    want = [ref.generate([p], sp, adapter="fin")[0] for p in PP_PROMPTS[:2]]
    got = [eng.generate([p], sp, adapter="fin")[0] for p in PP_PROMPTS[:2]]
    assert got == want


def test_engine_pp_validation(devices8):
    cfg = llama.LlamaConfig.tiny()  # 2 layers
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(pp=4), devices=devices8[:4])
    with pytest.raises(ValueError, match="not divisible"):
        Engine("llama", cfg, params, mesh=mesh,
               cfg=EngineConfig(num_slots=4, max_seq_len=64))


@pytest.mark.slow
def test_engine_pp_int8_matches_single_device_int8(devices8):
    """int8 weight-only quantization composes with pp: the quantized
    stacked layer tree (w8 + scales, all with the leading [NL] axis)
    shards over pp exactly like bf16 layers, and _w() dequantizes inside
    each stage. Streams must match the single-device int8 engine."""
    cfg = _dc.replace(llama.LlamaConfig.tiny(), num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        num_slots=4, max_seq_len=96, decode_chunk=4, quantization="int8"
    )
    ref = Engine("llama", cfg, params, cfg=ecfg)
    mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
    eng = Engine("llama", cfg, params, mesh=mesh, cfg=ecfg)
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    assert eng.generate(PP_PROMPTS, sp) == ref.generate(PP_PROMPTS, sp)


# ---- round-5 compositions: pp × sp, speculation under pp -------------------


@pytest.mark.slow
def test_engine_pp_sp_matches_single_device(devices8):
    """pp × sp: ring-attention prefill over the sp axis composing with
    GPipe-staged decode over pp. Decode microbatch inputs replicate over
    sp (decode is single-token; sequence has nothing to shard), so the
    stream must match the single-device engine bit-exactly. f32 model:
    the ring's online-softmax accumulation order differs from dense
    prefill, and bf16 near-ties on a random-init tiny model would flip
    greedy argmax."""
    cfg = _dc.replace(
        llama.LlamaConfig.tiny(), num_layers=4, dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        num_slots=4, max_seq_len=96, decode_chunk=4,
        cache_dtype=jnp.float32,
    )
    ref = Engine("llama", cfg, params, cfg=ecfg)
    mesh = build_mesh(MeshConfig(pp=2, sp=2), devices=devices8[:4])
    eng = Engine("llama", cfg, params, mesh=mesh, cfg=ecfg)
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    assert eng.generate(PP_PROMPTS, sp) == ref.generate(PP_PROMPTS, sp)


@pytest.mark.parametrize("mesh_kw", [dict(pp=2), dict(pp=2, tp=2)])
@pytest.mark.slow
def test_engine_pp_speculation_matches_vanilla(devices8, mesh_kw):
    """Prompt-lookup speculation under pipeline parallelism
    (decode_verify_paged_pp: GPipe-staged verify with stage-local KV)
    must emit the exact vanilla stream — same accept/reject semantics as
    the single-mesh verify, which shares its per-layer body."""
    cfg = _dc.replace(
        llama.LlamaConfig.tiny(), num_layers=4, dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    base = dict(num_slots=4, max_seq_len=96, cache_dtype=jnp.float32)
    n = 1
    for v in mesh_kw.values():
        n *= v
    mesh = build_mesh(MeshConfig(**mesh_kw), devices=devices8[:n])
    ref = Engine("llama", cfg, params, cfg=EngineConfig(**base))
    eng = Engine(
        "llama", cfg, params, mesh=mesh,
        cfg=EngineConfig(speculate=3, spec_adaptive=False, **base),
    )
    sp = SamplingParams(temperature=0.0, max_tokens=16)
    assert eng.generate(PP_PROMPTS, sp) == ref.generate(PP_PROMPTS, sp)


@pytest.mark.slow
def test_engine_pp_speculation_accepts_on_repetitive_text(devices8):
    """Acceptance (not just equivalence): on repetitive context the
    staged verify must compress tokens into fewer decode steps, proving
    the pp verify path actually accepts proposals."""
    cfg = _dc.replace(llama.LlamaConfig.tiny(), num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
    eng = Engine(
        "llama", cfg, params, mesh=mesh,
        cfg=EngineConfig(
            num_slots=4, max_seq_len=96, speculate=4, spec_adaptive=False,
        ),
    )
    prompt = ([11, 12, 13, 14, 15] * 10)[:45]
    out = eng.generate([prompt], SamplingParams(temperature=0.0, max_tokens=24))[0]
    assert len(out) == 24
    assert eng._steps < 24, f"no acceptance under pp: {eng._steps} steps"
    assert eng.spec_stats["accepted"] > 0


def test_engine_pp_draft_rejected(devices8):
    """A draft model under pp is a misconfiguration (the draft's layer
    stack would shard over pp and all-gather every step) — explicit
    error, not silent fallback."""
    cfg = _dc.replace(llama.LlamaConfig.tiny(), num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
    with pytest.raises(ValueError, match="pipeline"):
        Engine(
            "llama", cfg, params, mesh=mesh, draft=(cfg, params),
            cfg=EngineConfig(num_slots=4, max_seq_len=96, speculate=3),
        )
