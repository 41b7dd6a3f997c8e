"""The plain references against the program at the tiny size, in float32:
prefill and then paged decode through the engine must give the tokens the
reference's full forward pass puts first, and prefill's logits must agree."""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(name):
    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        cfg = json.load(f)
    return cfg, importlib.import_module("perf.reference." + cfg["reference"])


@pytest.mark.parametrize("name", ["tiny-mistral", "tiny-mixtral"])
def test_prefill_logits_agree_with_the_reference(name):
    from kubeai_tpu.models.registry import get_model_family

    cfg, ref = load(name)
    family = get_model_family(cfg["architectures"][0])
    mcfg = dataclasses.replace(family.config_from_hf(cfg), dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          jax.jit(lambda k: ref.served_params(cfg, k))(key))
    # The served tree has the program's own structure and shapes.
    want = jax.eval_shape(lambda: family.init_params(mcfg))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(lambda a: a.shape, want)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 45).tolist()
    logits, _, _ = family.prefill(params, mcfg, jnp.asarray([tokens]), jnp.asarray([45]))
    (expect,) = ref.forward(cfg, key, [(tokens, [44])], pad_to=64, rows_pad=8)
    # float32 both sides: only the order of summation differs.
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(expect[0]), atol=2e-5)


@pytest.mark.parametrize("name", ["tiny-mistral", "tiny-mixtral"])
def test_engine_decode_through_the_page_pool_follows_the_reference(name):
    from kubeai_tpu.engine import Engine, EngineConfig
    from kubeai_tpu.engine.sampling import SamplingParams
    from kubeai_tpu.models.registry import get_model_family

    cfg, ref = load(name)
    family = get_model_family(cfg["architectures"][0])
    mcfg = dataclasses.replace(family.config_from_hf(cfg), dtype=jnp.float32)
    key = jax.random.PRNGKey(4)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          jax.jit(lambda k: ref.served_params(cfg, k))(key))
    engine = Engine(family, mcfg, params, cfg=EngineConfig(
        num_slots=2, max_seq_len=128, page_size=16, cache_dtype=jnp.float32))
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 21).tolist()
    (served,) = engine.generate([prompt], SamplingParams(temperature=0.0, max_tokens=20))
    seq = prompt + list(served[:-1])
    rows = list(range(20, 20 + len(served)))
    (logits,) = ref.forward(cfg, key, [(seq, rows)], pad_to=64, rows_pad=32)
    logits = np.asarray(logits)
    gap = logits.max(-1) - logits[np.arange(len(served)), np.asarray(served)]
    assert len(served) == 20
    assert gap.max() < 1e-4  # float32 through the cache: rounding only


def test_lower_precisions_move_the_reference():
    cfg, ref = load("tiny-mistral")
    key = jax.random.PRNGKey(5)
    tokens = list(range(40))
    (full,) = ref.forward(cfg, key, [(tokens, [39])], pad_to=64, rows_pad=8)
    for quant, least in (("fp8", 1e-3), ("int8", 1e-4)):
        (low,) = ref.forward(cfg, key, [(tokens, [39])], quant=quant, pad_to=64, rows_pad=8)
        assert float(jnp.max(jnp.abs(low - full))) > least
    with pytest.raises(ValueError):
        ref.forward(cfg, key, [(tokens, [39])], quant="int4", pad_to=64, rows_pad=8)
