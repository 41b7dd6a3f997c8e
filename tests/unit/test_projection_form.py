"""The few-row forwards hold their q / k / v projections before the head
reshape (kubeai_tpu/ops/projections.py says what the TPU compiler does
otherwise; tests/unit/test_decode_pool_in_place.py holds the compiled
chunks to it). Here, on the CPU: the held form computes what the plain
`einsum(...).reshape(...)` form computes, in every family and with a bias
and an adapter where a family has them; a prefill is not held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.models import gemma, llama, mixtral
from kubeai_tpu.ops import projections

B, PAGE, PAGES_A_SLOT = 3, 8, 2
POSITIONS = jnp.array([3, 9, 12], jnp.int32)


def _filled(key, shape, dtype):
    return (0.3 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _pools(cfg, dtype):
    shape = (cfg.num_layers, 1 + B * PAGES_A_SLOT, PAGE, cfg.num_kv_heads,
             cfg.head_size)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    bt = 1 + jnp.arange(B * PAGES_A_SLOT, dtype=jnp.int32).reshape(B, -1)
    return _filled(k1, shape, dtype), _filled(k2, shape, dtype), bt


def _llama(dtype, bias=False, lora=False):
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=dtype, attention_bias=bias)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    if bias:  # init_params leaves them zero
        layers = dict(params["layers"])
        for i, name in enumerate(("bq", "bk", "bv")):
            layers[name] = _filled(
                jax.random.PRNGKey(20 + i), layers[name].shape, dtype)
        params = {**params, "layers": layers}
    extra = ()
    if lora:
        bufs = llama.init_lora_buffers(cfg, n_adapters=3, max_rank=4)
        keys = iter(jax.random.split(jax.random.PRNGKey(30), 8))
        bufs = jax.tree.map(
            lambda a: _filled(next(keys), a.shape, a.dtype).at[0].set(0), bufs)
        extra = (bufs, jnp.array([0, 1, 2], jnp.int32))
    return cfg, params, extra


def _decode_paged(module, cfg, params, dtype, extra=()):
    kp, vp, bt = _pools(cfg, dtype)
    toks = jnp.array([5, 17, 40], jnp.int32)
    return (lambda p, *a: module.decode_step_paged(p, cfg, *a),
            (params, toks, POSITIONS, kp, vp, bt, *extra))


def _decode_dense(module, cfg, params, dtype, extra=()):
    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    shape = (cfg.num_layers, B, 16, cfg.num_kv_heads, cfg.head_size)
    kc, vc = _filled(k1, shape, dtype), _filled(k2, shape, dtype)
    toks = jnp.array([5, 17, 40], jnp.int32)
    return (lambda p, *a: module.decode_step(p, cfg, *a),
            (params, toks, POSITIONS, kc, vc, *extra))


def _llama_case(forward, dtype, **kw):
    cfg, params, extra = _llama(dtype, **kw)
    if forward == "verify":
        kp, vp, bt = _pools(cfg, dtype)
        toks = jnp.arange(1, 1 + B * 3, dtype=jnp.int32).reshape(B, 3)
        return (lambda p, *a: llama.decode_verify_paged(p, cfg, *a),
                (params, toks, jnp.array([3, 9, 11], jnp.int32), kp, vp, bt,
                 *extra))
    build = _decode_paged if forward == "paged" else _decode_dense
    return build(llama, cfg, params, dtype, extra)


def _mixtral_case(forward, dtype):
    cfg = dataclasses.replace(mixtral.MixtralConfig.tiny(), dtype=dtype)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(1))
    build = _decode_paged if forward == "paged" else _decode_dense
    return build(mixtral, cfg, params, dtype)


def _sdar_case(dtype):
    cfg = dataclasses.replace(mixtral.MixtralConfig.tiny_sdar(), dtype=dtype)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(2))
    kp, vp, bt = _pools(cfg, dtype)
    toks = jnp.arange(1, 1 + B * cfg.block_length, dtype=jnp.int32).reshape(B, -1)
    return (lambda p, *a: mixtral.block_forward_paged(p, cfg, *a),
            (params, toks, jnp.array([4, 8, 12], jnp.int32), kp, vp, bt))


def _gemma_case(forward, dtype):
    cfg = dataclasses.replace(gemma.GemmaConfig.tiny(), dtype=dtype)
    params = gemma.init_params(cfg, jax.random.PRNGKey(3))
    build = _decode_paged if forward == "paged" else _decode_dense
    return build(gemma, cfg, params, dtype)


F32, BF16 = jnp.float32, jnp.bfloat16
CASES = {
    "llama-paged": lambda: _llama_case("paged", BF16),
    "llama-paged-f32": lambda: _llama_case("paged", F32),
    "llama-paged-bias": lambda: _llama_case("paged", BF16, bias=True),
    "llama-paged-lora": lambda: _llama_case("paged", BF16, lora=True),
    "llama-paged-bias-lora": lambda: _llama_case(
        "paged", BF16, bias=True, lora=True),
    "llama-dense-bias-lora": lambda: _llama_case(
        "dense", BF16, bias=True, lora=True),
    "llama-verify-bias-lora": lambda: _llama_case(
        "verify", BF16, bias=True, lora=True),
    "mixtral-paged": lambda: _mixtral_case("paged", BF16),
    "mixtral-dense": lambda: _mixtral_case("dense", BF16),
    "sdar-block": lambda: _sdar_case(BF16),
    "gemma-paged": lambda: _gemma_case("paged", BF16),
    "gemma-dense": lambda: _gemma_case("dense", BF16),
}


def _barriers(jitted, *args):
    return jitted.lower(*args).as_text().count("optimization_barrier")


def _compiled(fn, args):
    """(outputs, barriers in the lowered text) of `fn` under a jit of its
    own, so that nothing traced for the other form is found again."""
    jitted = jax.jit(lambda *a: fn(*a))
    return jitted(*args), _barriers(jitted, *args)


@pytest.mark.parametrize("case", sorted(CASES))
def test_held_projections_equal_the_plain_form(case, monkeypatch):
    """Logits and the new K / V (the written pools, or the block's rows) of
    one few-row forward, the projections held against the plain form: the
    same three dots on the same operands, so equal to the bit on the CPU,
    whose compiler rounds each projection to the model's dtype in both."""
    fn, args = CASES[case]()
    held, held_barriers = _compiled(fn, args)
    monkeypatch.setattr(projections, "HELD_BELOW_ROWS", 0)
    plain, plain_barriers = _compiled(fn, args)
    # One barrier a traced layer (the layer scan's body is traced once).
    assert (held_barriers, plain_barriers) == (1, 0)
    assert len(held) == len(plain) == 3
    for h, p in zip(held, plain):
        assert h.dtype == p.dtype and h.shape == p.shape
        assert bool(jnp.isfinite(h.astype(jnp.float32)).all())
        np.testing.assert_array_equal(np.asarray(h), np.asarray(p))


def test_a_prompts_rows_are_not_held():
    """The choice is made by the rows a slot, read off the input: a prefill
    and a prefill chunk keep the folded form at their smallest bucket, in
    the family whose one `_qkv` serves both and in the one whose prefill
    has code of its own."""
    prompt = jnp.ones((2, projections.HELD_BELOW_ROWS), jnp.int32)
    lengths = jnp.array([16, 9], jnp.int32)
    cfg, params, _ = _llama(F32)
    assert _barriers(jax.jit(
        lambda p: llama.prefill(p, cfg, prompt, lengths)), params) == 0
    mcfg = mixtral.MixtralConfig.tiny()
    mparams = mixtral.init_params(mcfg, jax.random.PRNGKey(1))
    assert _barriers(jax.jit(
        lambda p: mixtral.prefill(p, mcfg, prompt, lengths)), mparams) == 0
    slot = jnp.zeros((mcfg.num_layers, 32, mcfg.num_kv_heads, mcfg.head_size))
    assert _barriers(jax.jit(
        lambda p: mixtral.prefill_chunk(
            p, mcfg, prompt[:1], jnp.int32(0), jnp.int32(16), slot, slot)),
        mparams) == 0
    short = prompt[:, :projections.HELD_BELOW_ROWS - 1]
    assert _barriers(jax.jit(
        lambda p: mixtral.prefill(p, mcfg, short, lengths)), mparams) == 1


def test_split_heads_shapes():
    q = jnp.arange(2 * 3 * 8.0).reshape(2, 3, 8)
    k = v = jnp.arange(2 * 3 * 4.0).reshape(2, 3, 4)
    qh, kh, vh = projections.split_heads(q, k, v, 4, 2, 2)
    assert (qh.shape, kh.shape, vh.shape) == ((2, 3, 4, 2), (2, 3, 2, 2),
                                             (2, 3, 2, 2))
    np.testing.assert_array_equal(qh.reshape(2, 3, 8), q)
    qh, kh, vh = projections.split_heads(q[:, 0], k[:, 0], v[:, 0], 4, 2, 2)
    assert (qh.shape, kh.shape) == ((2, 4, 2), (2, 2, 2))
