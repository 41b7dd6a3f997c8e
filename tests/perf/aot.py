"""Ahead-of-time compiles of the serving graphs for a described v5e.

The TPU compiler is installed in the sandbox and compiles for a chip that is
described and not attached. Nothing here runs on a device: `compile_cell`
builds the engine's own two jitted functions (the decode chunk and the
batched prefill-admit) without placing anything, lowers them with abstract
arguments sharded on the described mesh, and returns XLA's per-device memory
analysis. Call it from inside a test or a script, never at import.
"""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "perf", "configs", name + ".json")) as f:
        return json.load(f)


def compile_cell(topo, cfg: dict, *, admit: int, bucket: int,
                 engine_overrides: dict | None = None, what=("decode", "prefill")):
    """{"decode": CompiledMemoryStats, "prefill": ..., "weights": ...}."""
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
    ecfg = EngineConfig(**{**cfg["engine"], **(engine_overrides or {})})
    mesh_cfg = MeshConfig(**cfg["mesh"])
    devices = np.asarray(topo.devices[: mesh_cfg.num_devices]).reshape(
        mesh_cfg.axis_sizes())
    mesh = Mesh(devices, MESH_AXES)
    rules = psh.DEFAULT_RULES
    cache_rules = psh.kv_cache_rules(mesh, mcfg.num_kv_heads, rules)
    pool_sharding = psh.named_sharding(
        mesh, (psh.LAYERS, None, None, psh.KV_HEADS, None), cache_rules)

    eng = Engine.__new__(Engine)
    eng.family, eng.model_cfg, eng.cfg, eng.mesh = family, mcfg, ecfg, mesh
    eng._pp, eng._pp_microbatches, eng._spec, eng._draft = 1, 0, 0, None
    eng._kv_quant, eng.cache_mode = False, "paged"
    # Nothing set: the model takes the decode layout from the pool's kind,
    # which is what the engine runs. An override that names a layout is
    # handed on (tests/unit/test_decode_pool_in_place.py compiles the
    # per-layer layout to show its guard is not blind); once the program
    # drops the field, nothing here has to change.
    eng.decode_kernel = getattr(ecfg, "decode_kernel", "") or None
    eng._bt_sharding = psh.named_sharding(mesh, (None, None), cache_rules)
    eng._chunk_fn = None
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
        make = jax.jit(lambda k: reference.served_params(cfg, k),
                       out_shardings=shardings)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        out = {}
        with jax.set_mesh(mesh):
            make_c = make.lower(key).compile()
            out["weights"] = make_c.memory_analysis()
            params = jax.tree.map(
                lambda s, sh: abstract(s.shape, s.dtype, sh),
                jax.eval_shape(lambda k: reference.served_params(cfg, k), key),
                shardings)
            n_pages = ecfg.effective_num_pages()
            mp = -(-ecfg.max_seq_len // ecfg.page_size)
            pool = abstract(
                (mcfg.num_layers, n_pages, ecfg.page_size, mcfg.num_kv_heads,
                 mcfg.head_size), ecfg.cache_dtype, pool_sharding)
            B = ecfg.num_slots
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
                    params, pool, pool, bt, state, None).compile()
                out["decode"] = c.memory_analysis()
                out["decode_text"] = c.as_text()
            if "prefill" in what:
                c = eng._prefill_admit_jit.lower(
                    params,
                    abstract((admit, bucket), jnp.int32, rep2),
                    abstract((admit, 6), jnp.int32, rep2),
                    abstract((admit, 2), jnp.float32, rep2),
                    abstract((admit, mp), jnp.int32, rep2),
                    pool, pool, bt, state, None).compile()
                out["prefill"] = c.memory_analysis()
        return out
    finally:
        dispatch.kernel_mode = saved


def peak_bytes(stats) -> int:
    """Arguments + outputs + temporaries - aliased (donated) bytes."""
    return (stats.argument_size_in_bytes + stats.output_size_in_bytes
            + stats.temp_size_in_bytes - stats.alias_size_in_bytes)
