"""perf/costs.py against counts worked by hand."""

import json
import os

import pytest

from perf import costs

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(HERE)), "perf", "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_mistral_layer_and_weights_by_hand():
    m = cfg("mistral-7b-v5e1")
    # q 4096x4096, k and v 4096x1024, o 4096x4096; three 4096x14336 MLP mats
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert costs.layer_params(m) == layer == 218_103_808
    weights = 2 * (16 * (layer + 2 * 4096) + 4096 + 32768 * 4096)
    assert costs.weight_bytes(m) == weights
    assert weights / 1e9 == pytest.approx(7.248, abs=1e-3)
    # keys and values: 2 x 16 layers x 8 heads x 128 x 2 bytes = 64 KiB a token
    assert costs.kv_bytes_per_token(m) == 65536


def test_decode_bytes_split_over_chips_and_count_kv_once():
    m = cfg("mistral-7b-v5e1")
    one = costs.decode_step_bytes_per_chip(m, 1000, 1)
    assert one == costs.weight_bytes(m) + 1000 * 65536
    assert costs.decode_step_bytes_per_chip(m, 1000, 4) == one / 4


def test_mixtral_counts_every_expert_for_bytes_and_two_for_flops():
    x = cfg("mixtral-8x7b-v5e4")
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    expert = 3 * 4096 * 14336
    assert costs.layer_params(x) == attn + 4096 * 8 + 8 * expert
    assert costs.layer_params(x, active_only=True) == attn + 4096 * 8 + 2 * expert
    assert costs.weight_bytes(x) / 4 / 1e9 == pytest.approx(11.68, abs=0.01)
    flops = costs.prefill_flops_per_token(x)
    assert flops == 16 * 2 * (attn + 4096 * 8 + 2 * expert)


def test_prefill_flops_add_attention_against_the_context():
    m = cfg("mistral-7b-v5e1")
    base = costs.prefill_flops_per_token(m)
    assert base == 16 * 2 * 218_103_808
    # QK^T and PV: 2 x (2 x 32 heads x 128) per earlier token per layer
    assert costs.prefill_flops_per_token(m, context=100) == base + 16 * 4 * 32 * 128 * 100


def test_a_references_own_count_comes_before_this_files():
    """The hook an architecture brings its counts through. By hand, a latent
    cache of rank 512 with a rotary part of 64, bf16: (512 + 64) x 2 bytes =
    1,152 bytes a token a layer, where `kv_bytes_per_token` would count
    2 x KVH x D."""
    import types

    from perf import readers

    m = cfg("mistral-7b-v5e1")
    latent = types.SimpleNamespace(
        kv_bytes_per_token=lambda hf: hf["num_hidden_layers"] * (512 + 64) * 2,
        decode_step_bytes_per_chip=lambda hf, tokens, chips: (
            costs.weight_bytes(hf) + tokens * latent.kv_bytes_per_token(hf)) / chips)
    assert costs.of(latent, "kv_bytes_per_token")(m) == 16 * 1152 == 18_432
    assert costs.of(latent, "weight_bytes") is costs.weight_bytes
    assert costs.of(None, "kv_bytes_per_token") is costs.kv_bytes_per_token
    with pytest.raises(KeyError):
        costs.of(latent, "no_such_count")
    # `decode_hbm_share` asks the same way: 10,000 resident tokens, a chunk of
    # 8 steps that took 80 ms on the device, 819 GB/s.
    obs = {"polled": {"kv_tokens": [10_000.0]}, "hf": m, "chips": 1,
           "engine": {"decode_chunk": 8}, "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"modules": {"jit__decode_chunk": {"count": 1, "total_s": 0.08}}}}
    spec = {"reader": "decode_hbm_share", "module": "^jit__decode_chunk"}
    own = readers.read(spec, {**obs, "reference": latent})
    assert own == pytest.approx(
        100 * (costs.weight_bytes(m) + 10_000 * 18_432) / 819e9 / 0.01)
    plain = readers.read(spec, obs)
    assert plain == pytest.approx(
        100 * (costs.weight_bytes(m) + 10_000 * 65_536) / 819e9 / 0.01)
    assert plain > own
