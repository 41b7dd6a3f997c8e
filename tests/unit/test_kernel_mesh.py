"""The shard_map wrapper the chip needs is the code CPU tests run too: with
the kernel path forced (Pallas interpreter), a tp-sharded decode step and a
>= 256-token prefill go through the same `dispatch.over_kv_heads` call a TPU
takes, on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.models import llama
from kubeai_tpu.ops import dispatch
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh


@pytest.fixture
def forced_kernels(monkeypatch):
    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", True)
    assert dispatch.kernel_mode() == "interpret"


def _cfg():
    return llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=16, rope_theta=10000.0,
        max_position_embeddings=1024,
    )


def _engine(mesh, cfg, params):
    return Engine(
        "llama", cfg, params, mesh=mesh,
        cfg=EngineConfig(
            num_slots=2, max_seq_len=512, page_size=16, decode_chunk=2,
            max_admit_batch=1,
        ),
    )


def test_tp_sharded_serving_runs_the_kernels_under_shard_map(
    devices8, forced_kernels, monkeypatch
):
    """Prompt of 300 tokens -> the 512 bucket -> flash prefill; decode ->
    the paged kernel; both on a dp=2 x tp=4 mesh with KV heads split four
    ways. Tokens must match the same engine on the jnp reference path."""
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(dp=2, tp=4), devices=devices8)
    prompt = [int(t) for t in np.random.default_rng(0).integers(1, 256, 300)]
    sp = SamplingParams(temperature=0.0, max_tokens=4)

    calls = []
    real = dispatch.over_kv_heads

    def spy(fn, num_kv_heads, head_dims):
        calls.append(jax.sharding.get_abstract_mesh().shape)
        return real(fn, num_kv_heads, head_dims)

    monkeypatch.setattr(dispatch, "over_kv_heads", spy)
    got = _engine(mesh, cfg, params).generate([prompt], sp)
    # Flash prefill, then paged decode, each traced under the engine mesh.
    assert len(calls) >= 2
    assert all(c.get("tp") == 4 for c in calls)

    monkeypatch.setattr(dispatch, "FORCE_INTERPRET", False)
    want = _engine(mesh, cfg, params).generate([prompt], sp)
    assert got == want


@pytest.mark.parametrize("kernel", ["per_layer", "stacked"])
@pytest.mark.parametrize(
    "mesh_cfg, kv_heads_per_shard",
    [
        (MeshConfig(dp=2, tp=4), 1),  # 4 KV heads split four ways
        # num_kv_heads % tp != 0: the engine replicates the cache, and the
        # kernel call runs replicated with it instead of refusing.
        (MeshConfig(dp=1, tp=8), 4),
    ],
    ids=["kv-heads-on-tp", "gqa-replicated"],
)
def test_kernel_call_is_manual_over_every_mesh_axis(
    devices8, forced_kernels, monkeypatch, mesh_cfg, kv_heads_per_shard,
    kernel,
):
    """What Mosaic demands on the chip: inside the wrapper no mesh axis is
    left to GSPMD, and each shard sees its own KV heads: of one layer's
    pool, and of the stacked pool the decode step reads in place."""
    from kubeai_tpu.ops import paged_attention as pa

    seen = {}
    name = "_paged_pallas" if kernel == "per_layer" else "_paged_pallas_stacked"
    real = getattr(pa, name)

    def probe(q, k_pages, *rest, **kw):
        am = jax.sharding.get_abstract_mesh()
        seen["manual"] = set(am.manual_axes) == set(am.axis_names)
        seen["kvh"] = k_pages.shape[-2]
        return real(q, k_pages, *rest, **kw)

    monkeypatch.setattr(pa, name, probe)
    q = jnp.ones((2, 8, 16), jnp.float32)
    pool = jnp.ones((3, 16, 4, 16), jnp.float32)
    bt = jnp.asarray([[1, -1], [2, -1]], jnp.int32)
    lens = jnp.asarray([5, 9], jnp.int32)
    with jax.set_mesh(build_mesh(mesh_cfg, devices=devices8)):
        if kernel == "per_layer":
            out = jax.jit(pa.paged_decode_attention)(q, pool, pool, bt, lens)
        else:
            stacked = jnp.ones((2,) + pool.shape, jnp.float32)
            new = jnp.ones((2, 4, 16), jnp.float32)
            out = jax.jit(pa.paged_decode_attention_fused)(
                q, stacked, stacked, new, new, bt, lens - 1, 1)
    assert seen == {"manual": True, "kvh": kv_heads_per_shard}
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-5)


def test_the_kernel_wrapper_splits_where_the_engine_places_the_pool():
    """One rule (parallel/sharding.py:kv_heads_axis) feeds both the pool's
    sharding and the kernels' shard_map specs."""
    from kubeai_tpu.parallel import sharding as psh

    assert psh.kv_heads_axis({"dp": 2, "tp": 4}, 8) == "tp"
    assert psh.kv_heads_axis({"dp": 1, "tp": 8}, 4) is None
    assert psh.kv_heads_axis({"tp": 1}, 3) == "tp"  # a size-1 axis divides all
    mesh = build_mesh(MeshConfig(dp=1, tp=8), devices=jax.devices()[:8])
    assert psh.kv_cache_rules(mesh, 8) is psh.DEFAULT_RULES
    assert psh.kv_cache_rules(mesh, 4).physical(psh.KV_HEADS) is None
    assert psh.kv_cache_rules(mesh, 4).physical(psh.HEADS) == "tp"


def test_every_engine_jit_enters_the_mesh():
    """The kernels find their mesh in the context `Engine.jit` enters. A
    bare `jax.jit` on the serving path would trace its kernels with no
    mesh, which lowers on one device and is refused by Mosaic only at
    tp > 1 on the chip — so there is none besides `Engine.jit` itself."""
    import inspect

    from kubeai_tpu.engine import engine, server

    assert inspect.getsource(engine).count("jax.jit(") == 1
    assert "jax.jit(" in inspect.getsource(engine.Engine.jit)
    assert "jax.jit(" not in inspect.getsource(server)
