"""Ahead-of-time compiles of the hybrid family's two programs for a described
v5e, at the real size of `qwen3-next-80b-a3b.decode-sat`: the decode chunk
(64 slots: the page pool over the 4 attention layers, the state pools over
the 12 Gated DeltaNet layers) and an 8 x 256 prefill-admit.
`tests/perf/aot.py` lowers the other families' programs by their arguments; a
family whose programs take one more (the state pools) brings its own
(perf/README.md, "A generator of its own"). Peaks are bounded from above
only. Nothing runs; a compile that passes is not a chip run."""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import aot  # noqa: E402  (tests/perf/aot.py)
from test_aot_v5e import HBM, topo  # noqa: E402, F401  (the described v5e:2x2)


def compile_hybrid_cell(topo, cfg: dict, *, admit: int, bucket: int):  # noqa: F811
    """{"decode": stats, "decode_text", "prefill": stats, "prefill_text",
    "weights": stats} of the hybrid family's engine on one described chip."""
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
            B = ecfg.num_slots
            rec = family.recurrent_state(mcfg)
            pool = abstract(
                (rec["page_layers"], ecfg.effective_num_pages(), ecfg.page_size,
                 mcfg.num_kv_heads, mcfg.head_size), ecfg.cache_dtype, pool_sharding)
            pools = {
                name: abstract((rec["state_layers"], B, *shape), dtype,
                               eng._state_sharding)
                for name, (shape, dtype) in rec["pools"].items()}
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
            c = eng._decode_jit.lower(
                params, pool, pool, bt, state, None, pools).compile()
            out["decode"], out["decode_text"] = c.memory_analysis(), c.as_text()
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


# An array of the page pool's or the recurrent pool's shape that an
# instruction other than the programs' own parameters, tuples and in-place
# kernels produces: a whole-pool copy or a slice of it. (The admission's
# write of its rows into the recurrent pool IS a dynamic-update-slice, in
# place on the donated argument: the decode chunk has none.)
POOL = r"= (bf16\[4,\d+,64,2,256\]|f32\[12,64,32,128,128\])\S* "
POOL_OP = re.compile(POOL + r"(copy|dynamic-slice|dynamic-update-slice)\(")
POOL_COPY = re.compile(POOL + r"(copy|dynamic-slice)\(")
# One layer's (or one period's) slice of a stacked weight, produced by an
# instruction of its own: a copy that computes nothing (150 MB a period of
# `in_qkvz` when the scan sliced the DeltaNet layers a period at a time).
WEIGHT_SLICE = re.compile(
    r"^\s+%\S+ = bf16\[(\d+,)?(2048,12288|4096,2048|2048,8192|2048,512|512,2048)\]"
    r"\S* (fusion|copy|dynamic-slice)\(")


def materialised(text: str, pattern) -> list[str]:
    """Instructions matching `pattern` that stand in a computation of their
    own right (a loop body, the entry), not inside a fusion: a slice fused
    into the product that reads it is no copy."""
    hits, inside = [], ""
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(2)
        elif not inside.startswith("fused_computation") and pattern.search(line):
            hits.append(line.strip()[:160])
    return hits


def test_qwen3_next_chunk_and_admit_fit_one_chip_with_the_pools_in_place(topo):  # noqa: F811
    cfg = aot.load_config("qwen3-next-80b-a3b-v5e1")
    out = compile_hybrid_cell(topo, cfg, admit=8, bucket=256)
    for graph in ("weights", "decode", "prefill"):
        assert aot.peak_bytes(out[graph]) < HBM, graph
    # 8.85 GB of weights, 1.07 GB of pages and 1.65 GB of state are the
    # arguments; a chunk's temporaries stay under half a GiB.
    assert out["decode"].argument_size_in_bytes < 11.2 * 2**30
    assert out["decode"].temp_size_in_bytes < 0.5 * 2**30
    assert aot.peak_bytes(out["decode"]) < 11.7 * 2**30
    assert not POOL_OP.search(out["decode_text"])
    assert not POOL_COPY.search(out["prefill_text"])
    assert not materialised(out["decode_text"], WEIGHT_SLICE)
    # The kernels, under the names the per-layer metrics find them by.
    assert "%_paged_pallas_stacked" in out["decode_text"]
    assert re.search(r"%_gdn_update_pallas(\.\d+)? = ", out["decode_text"])
    assert re.search(r"%gmm(\.\d+)? = ", out["decode_text"])
    assert re.search(r"%gmm(\.\d+)? = ", out["prefill_text"])
