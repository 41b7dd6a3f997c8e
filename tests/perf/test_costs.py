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
