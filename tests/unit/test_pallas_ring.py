"""Pallas flash attention (interpret mode) + ring attention vs the jnp
reference semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.ops.attention import causal_prefill_attention
from kubeai_tpu.ops.pallas_attention import flash_causal_prefill
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh
from kubeai_tpu.parallel.ring_attention import (
    ring_attention_sharded,
    ring_causal_attention,
)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    from kubeai_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)


def _mk(B=1, S=256, H=4, KVH=2, D=64, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, KVH, D)).astype(np.float32)
    v = rng.standard_normal((B, S, KVH, D)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_flash_matches_reference_interpret(kernels_interpreted):
    q, k, v = _mk()
    want = causal_prefill_attention(q, k, v)
    got = flash_causal_prefill(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
    )


def test_flash_gqa_and_padded_head_dim(kernels_interpreted):
    # D=64 exercises the pad-to-128 path; KVH=1 the max-group GQA path.
    q, k, v = _mk(B=2, S=128, H=4, KVH=1, D=64, seed=1)
    want = causal_prefill_attention(q, k, v)
    got = flash_causal_prefill(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
    )


def test_flash_refuses_unaligned_seq_and_dispatch_routes_it():
    """The kernel raises on a sequence it cannot tile; the choice of the
    prefill kernel sends the short and unaligned buckets to the jnp path."""
    from kubeai_tpu.ops.attention import prefill_attention

    q, k, v = _mk(S=100)  # 100 % 128 != 0
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_causal_prefill(q, k, v)
    want = causal_prefill_attention(q, k, v)
    got = prefill_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_ring_attention_matches_full(devices8):
    mesh = build_mesh(MeshConfig(dp=1, sp=8, tp=1), devices=devices8)
    q, k, v = _mk(B=2, S=64 * 8, H=4, KVH=2, D=32, seed=2)
    want = causal_prefill_attention(q, k, v)
    got = ring_attention_sharded(q, k, v, mesh)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
    )


def test_ring_attention_sp4_gqa(devices8):
    mesh = build_mesh(MeshConfig(dp=2, sp=4, tp=1), devices=devices8)
    q, k, v = _mk(B=2, S=32 * 4, H=8, KVH=2, D=16, seed=3)
    want = causal_prefill_attention(q, k, v)
    got = ring_attention_sharded(q, k, v, mesh)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
    )
