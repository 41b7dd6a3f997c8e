"""Ahead-of-time compiles of the block family's two programs for a described
v5e, at the real size of `sdar-30b-a3b.decode-sat`: the decode chunk (2 blocks
a slot, up to 10 forwards) and the prefill-admit. `tests/perf/aot.py` lowers
the one-token family's programs by their arguments; a family with another
step brings its own (perf/README.md, "A generator of its own"). Nothing runs;
a compile that passes is not a chip run."""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import aot  # noqa: E402  (tests/perf/aot.py)
from test_aot_v5e import HBM, topo  # noqa: E402, F401  (the described v5e:2x2)


def compile_block_cell(topo, cfg: dict, *, admit: int, bucket: int):  # noqa: F811
    """{"decode": stats, "decode_text", "prefill": stats, "prefill_text",
    "weights": stats} of the block family's engine on one described chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from kubeai_tpu.engine.engine import Engine, EngineConfig
    from kubeai_tpu.models.registry import get_model_family
    from kubeai_tpu.ops import dispatch
    from kubeai_tpu.parallel import sharding as psh
    from kubeai_tpu.parallel.mesh import MESH_AXES, MeshConfig

    family = get_model_family(cfg["architectures"][0])
    mcfg = family.config_from_hf(cfg)
    ecfg = EngineConfig(**cfg["engine"])
    mesh_cfg = MeshConfig(**cfg["mesh"])
    mesh = Mesh(np.asarray(topo.devices[: mesh_cfg.num_devices]).reshape(
        mesh_cfg.axis_sizes()), MESH_AXES)
    rules = psh.DEFAULT_RULES
    cache_rules = psh.kv_cache_rules(mesh, mcfg.num_kv_heads, rules)
    pool_sharding = psh.named_sharding(
        mesh, (psh.LAYERS, None, None, psh.KV_HEADS, None), cache_rules)

    eng = Engine.__new__(Engine)
    eng.family, eng.model_cfg, eng.cfg, eng.mesh = family, mcfg, ecfg, mesh
    eng._pp, eng._pp_microbatches, eng._spec, eng._draft = 1, 0, 0, None
    eng._kv_quant, eng.cache_mode, eng.decode_kernel = False, "paged", "fused"
    eng._bt_sharding = psh.named_sharding(mesh, (None, None), cache_rules)
    eng.jit = lambda fn, **kw: jax.jit(fn, **kw)
    saved = dispatch.kernel_mode
    dispatch.kernel_mode = lambda: "compiled"
    try:
        eng._build_jits_paged(pool_sharding)

        def abstract(shape, dtype, sharding):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        rep = psh.named_sharding(mesh, (None,), rules)
        rep2 = psh.named_sharding(mesh, (None, None), rules)
        shardings = psh.param_shardings(family.param_specs(mcfg), mesh)
        reference = importlib.import_module("perf.reference." + cfg["reference"])
        served = lambda k: reference.served_params(cfg, k)  # noqa: E731
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        out = {}
        with jax.set_mesh(mesh):
            out["weights"] = jax.jit(served, out_shardings=shardings).lower(
                key).compile().memory_analysis()
            params = jax.tree.map(
                lambda s, sh: abstract(s.shape, s.dtype, sh),
                jax.eval_shape(served, key), shardings)
            mp = -(-ecfg.max_seq_len // ecfg.page_size)
            pool = abstract(
                (mcfg.num_layers, ecfg.effective_num_pages(), ecfg.page_size,
                 mcfg.num_kv_heads, mcfg.head_size), ecfg.cache_dtype, pool_sharding)
            B = ecfg.num_slots
            bt = abstract((B, mp), jnp.int32, eng._bt_sharding)
            state = {
                "tokens": abstract((B, mcfg.block_length), jnp.int32, rep2),
                "positions": abstract((B,), jnp.int32, rep),
                "seeds": abstract((B,), jnp.uint32, rep),
                "temp": abstract((B,), jnp.float32, rep),
                "topk": abstract((B,), jnp.int32, rep),
                "topp": abstract((B,), jnp.float32, rep),
                "lora_idx": abstract((B,), jnp.int32, rep),
            }
            c = eng._decode_jit.lower(params, pool, pool, bt, state, None).compile()
            out["decode"], out["decode_text"] = c.memory_analysis(), c.as_text()
            c = eng._prefill_admit_jit.lower(
                params,
                abstract((admit, bucket), jnp.int32, rep2),
                abstract((admit, 6), jnp.int32, rep2),
                abstract((admit, 2), jnp.float32, rep2),
                abstract((admit, mp), jnp.int32, rep2),
                pool, pool, bt, state, None).compile()
            out["prefill"], out["prefill_text"] = c.memory_analysis(), c.as_text()
        return out
    finally:
        dispatch.kernel_mode = saved


# A pool-shaped array that an instruction other than the programs' own
# parameters, tuples and in-place kernels produces: a whole-pool copy or a
# slice of it (PR 25's fault, PR 35's at tp=4).
POOL_OP = re.compile(
    r"= bf16\[7,\d+,64,4,128\]\S* (copy|dynamic-slice|dynamic-update-slice)\(")


def test_sdar_block_chunk_and_admit_fit_one_chip_with_the_pool_in_place(topo):  # noqa: F811
    cfg = aot.load_config("sdar-30b-a3b-v5e1")
    out = compile_block_cell(topo, cfg, admit=8, bucket=256)
    for graph in ("weights", "decode", "prefill"):
        assert aot.peak_bytes(out[graph]) < HBM, graph
    # 9.97 GB of weights and 0.94 GB of pages are the arguments.
    assert 10.0 * 2**30 < out["decode"].argument_size_in_bytes < 10.5 * 2**30
    for text in (out["decode_text"], out["prefill_text"]):
        assert not POOL_OP.search(text), POOL_OP.search(text).group(0)
    assert "%_paged_pallas_stacked" in out["decode_text"]  # the paged kernel
    # The grouped products, under the name perf/layer_metrics/
    # moe_experts_roofline.json finds them by in a trace.
    assert re.search(r"%gmm(\.\d+)? = ", out["decode_text"])
    assert re.search(r"%gmm(\.\d+)? = ", out["prefill_text"])
