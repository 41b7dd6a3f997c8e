"""Ahead-of-time compiles of the window family's two programs for a described
v5e, at the real size of `k-exaone-236b-a23b.gen-sat`: the decode chunk (64
slots: the global pool over the 2 global layers, every page reserved, and the
window pool of 3 ring pages a slot over the 6 window layers) and the largest
prefill-admit the cell's traffic meets (1 x 2048). `tests/perf/aot.py` lowers
the other families' programs by their arguments; a family whose programs take
one more (the window pool) brings its own (perf/README.md, "A generator of its
own"). Peaks are bounded from above only. Nothing runs; a compile that passes
is not a chip run."""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import aot  # noqa: E402  (tests/perf/aot.py)
from test_aot_qwen3_next import materialised  # noqa: E402
from test_aot_v5e import HBM, topo  # noqa: E402, F401  (the described v5e:2x2)

GIB = 2**30


def compile_window_cell(topo, cfg: dict, *, admit: int, bucket: int,  # noqa: F811
                        what=("decode", "prefill")):
    """{"decode": stats, "decode_text", "prefill": stats, "prefill_text",
    "weights": stats} of the window family's engine on one described chip."""
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
    eng._kv_quant, eng.decode_kernel, eng._chunk_fn = False, "fused", None
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
            B, win = ecfg.num_slots, eng._window
            page = (ecfg.page_size, mcfg.num_kv_heads, mcfg.head_size)
            pool = abstract((win["global_layers"], ecfg.effective_num_pages(), *page),
                            ecfg.cache_dtype, pool_sharding)
            ring = abstract((win["window_layers"], 1 + B * win["ring"], *page),
                            ecfg.cache_dtype, eng._state_sharding)
            pools = {"k_window": ring, "v_window": ring}
            bt = abstract((B, mp), jnp.int32, eng._bt_sharding)
            state = {
                "tokens": abstract((B,), jnp.int32, rep),
                "positions": abstract((B,), jnp.int32, rep),
                "seeds": abstract((B,), jnp.uint32, rep),
                "temp": abstract((B,), jnp.float32, rep),
                "topk": abstract((B,), jnp.int32, rep),
                "topp": abstract((B,), jnp.float32, rep),
                "lora_idx": abstract((B,), jnp.int32, rep),
            }
            if "decode" in what:
                c = eng._decode_jit.lower(
                    params, pool, pool, bt, state, None, pools).compile()
                out["decode"], out["decode_text"] = c.memory_analysis(), c.as_text()
            if "prefill" in what:
                c = eng._prefill_admit_jit.lower(
                    params,
                    abstract((admit, bucket), jnp.int32, rep2),
                    abstract((admit, 6), jnp.int32, rep2),
                    abstract((admit, 2), jnp.float32, rep2),
                    abstract((admit, mp), jnp.int32, rep2),
                    pool, pool, bt, state, None, pools).compile()
                out["prefill"], out["prefill_text"] = c.memory_analysis(), c.as_text()
        return out
    finally:
        dispatch.kernel_mode = saved


# An array of either pool's shape that an instruction other than the
# programs' own parameters, tuples and in-place kernels produces: a
# whole-pool copy or a slice of it.
POOL = r"= bf16\[(2,4097|6,193),64,8,128\]\S* "
POOL_OP = re.compile(POOL + r"(copy|dynamic-slice|dynamic-update-slice)\(")
# One layer's slice of a stacked weight, produced by an instruction of its
# own that computes nothing: attention's four, the dense layer's, the shared
# expert's, an expert's.
WEIGHT_SLICE = re.compile(
    r"^\s+%\S+ = bf16\[(\d+,)*(6144,8192|6144,1024|8192,6144|6144,18432|18432,6144"
    r"|6144,2048|2048,6144|6144,128)\]\S* (fusion|copy|dynamic-slice)\(")


def test_kexaone_chunk_and_admit_fit_one_chip_with_both_pools_in_place(topo):  # noqa: F811
    cfg = aot.load_config("k-exaone-236b-a23b-v5e1")
    out = compile_window_cell(topo, cfg, admit=1, bucket=2048)
    for graph in ("weights", "decode", "prefill"):
        assert aot.peak_bytes(out[graph]) < 15.0 * GIB < HBM, graph
    # 11.96 GB of weights, 2.15 GB of global pages and 0.30 GB of rings are
    # the arguments (13.4 GiB); the chunk's temporaries stay under 0.3 GiB.
    assert out["decode"].argument_size_in_bytes < 13.5 * GIB
    assert out["decode"].temp_size_in_bytes < 0.3 * GIB
    assert not POOL_OP.search(out["decode_text"])
    # The admission's writes are scatters in place on the donated pools.
    assert not POOL_OP.search(out["prefill_text"])
    assert not materialised(out["decode_text"], WEIGHT_SLICE)
    # The kernels, under the names the per-layer metrics find them by.
    assert "%_paged_pallas_stacked" in out["decode_text"]
    assert re.search(r"%gmm(\.\d+)? = ", out["decode_text"])
    assert re.search(r"%gmm(\.\d+)? = ", out["prefill_text"])
    assert "_flash_bhsd" in out["prefill_text"]
