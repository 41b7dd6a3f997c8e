"""Ahead-of-time compiles of the latent hybrid family's two programs for a
described v5e, at the real size of `kimi-linear-48b-a3b.gen-sat`: the decode
chunk (128 slots: ONE latent pool over the 3 MLA layers, the state pools over
the 9 KDA layers) and the largest admission (2 x 2048).
`tests/perf/aot.py` builds two page pools by name; a family whose page pool is
one array of rows without heads, and whose programs take the state pools
besides, brings its own helper (perf/README.md, "A generator of its own").
Peaks are bounded from above only. Nothing runs; a compile that passes is not
a chip run."""

import importlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import aot  # noqa: E402  (tests/perf/aot.py)
from test_aot_qwen3_next import materialised  # noqa: E402
from test_aot_v5e import HBM, topo  # noqa: E402, F401  (the described v5e:2x2)

CONFIG = "kimi-linear-48b-a3b-v5e1"


def compile_latent_cell(topo, cfg: dict, *, admit: int, bucket: int):  # noqa: F811
    """{"decode": stats, "decode_text", "prefill": stats, "prefill_text",
    "weights": stats} of the latent hybrid family's engine on one described
    chip."""
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

    eng = Engine.__new__(Engine)
    eng.family, eng.model_cfg, eng.cfg, eng.mesh = family, mcfg, ecfg, mesh
    eng._pp, eng._pp_microbatches, eng._spec, eng._draft = 1, 0, 0, None
    eng._kv_quant, eng.decode_kernel, eng._chunk_fn = False, "fused", None
    eng._bt_sharding = psh.named_sharding(mesh, (None, None), rules)
    eng.jit = lambda fn, **kw: jax.jit(fn, **kw)
    whole = eng._state_sharding  # the latent pool's and the state pools'
    saved = dispatch.kernel_mode
    dispatch.kernel_mode = lambda: "compiled"
    try:
        eng._build_jits_paged(whole)

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
            rec, latent = family.recurrent_state(mcfg), family.latent_pages(mcfg)
            pool = abstract(
                (rec["page_layers"], ecfg.effective_num_pages(), ecfg.page_size,
                 *latent["row"]), latent["dtype"], whole)
            pools = {
                name: abstract((rec["state_layers"], B, *shape), dtype, whole)
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
            # No second pool: the programs take None where K / V families
            # give `v_pages`.
            c = eng._decode_jit.lower(
                params, pool, None, bt, state, None, pools).compile()
            out["decode"], out["decode_text"] = c.memory_analysis(), c.as_text()
            c = eng._prefill_admit_jit.lower(
                params,
                abstract((admit, bucket), jnp.int32, rep2),
                abstract((admit, 6), jnp.int32, rep2),
                abstract((admit, 2), jnp.float32, rep2),
                abstract((admit, mp), jnp.int32, rep2),
                pool, None, bt, state, None, pools).compile()
            out["prefill"], out["prefill_text"] = c.memory_analysis(), c.as_text()
        return out
    finally:
        dispatch.kernel_mode = saved


# An array of the latent pool's, the recurrent pool's or the convolution
# pool's shape that an instruction other than the programs' own parameters,
# tuples and in-place kernels produces: a whole-pool copy or a slice of it.
# (The admission's write of its rows into the state pools IS a
# dynamic-update-slice or a scatter, in place on the donated argument.)
POOL = (r"= (bf16\[3,\d+,64,640\]|f32\[9,128,32,128,128\]|bf16\[9,128,36864\])"
        r"\S* ")
POOL_OP = re.compile(POOL + r"(copy|dynamic-slice|dynamic-update-slice)\(")
POOL_COPY = re.compile(POOL + r"(copy|dynamic-slice)\(")
# One layer's slice of a stacked mixer weight produced by an instruction of
# its own: a copy that computes nothing.
WEIGHT_SLICE = re.compile(
    r"^\s+%\S+ = bf16\[(\d+,)?(2304,12288|4096,2304|2304,6144|512,4096|2304,9216)\]"
    r"\S* (fusion|copy|dynamic-slice)\(")


def test_kimi_linear_chunk_and_admit_fit_one_chip_with_the_pools_in_place(topo):  # noqa: F811
    cfg = aot.load_config(CONFIG)
    assert cfg["engine"] == {
        "num_slots": 128, "max_seq_len": 4096, "max_admit_batch": 2,
        # Two buckets between the powers of two (PERF.md section 6, PR 50,
        # the refusal round); 768 and 1536 do not compile.
        "prefill_buckets": [512, 1024, 1280, 1792, 2048]}
    out = compile_latent_cell(topo, cfg, admit=2, bucket=2048)
    for graph in ("weights", "decode", "prefill"):
        assert aot.peak_bytes(out[graph]) < HBM, graph
    # 7.68 GB of weights, 2.50 GB of state and 2.01 GB of latent rows are the
    # arguments (11.35 GiB); a chunk's temporaries stay under a quarter GiB,
    # the 2 x 2048 admission's under a GiB and a quarter.
    assert out["decode"].argument_size_in_bytes < 11.5 * 2**30
    assert out["decode"].temp_size_in_bytes < 0.25 * 2**30
    assert aot.peak_bytes(out["decode"]) < 11.75 * 2**30
    assert out["prefill"].temp_size_in_bytes < 1.25 * 2**30
    assert aot.peak_bytes(out["prefill"]) < 12.75 * 2**30
    # Neither the latent pool nor the recurrent pool is copied or sliced,
    # in either program; the decode chunk moves the convolution pool on in
    # place (a dynamic-update-slice on the donated argument) and copies it
    # nowhere. (The admission re-lays the 85 MB convolution pool around its
    # scatter over slots, as it does for every family with such a pool.)
    recurrent_or_latent = re.compile(POOL.replace(r"|bf16\[9,128,36864\]", ""))
    for text in (out["decode_text"], out["prefill_text"]):
        assert not re.search(
            recurrent_or_latent.pattern + r"(copy|dynamic-slice)\(", text)
    assert not POOL_COPY.search(out["decode_text"])
    assert not re.search(
        r"= bf16\[3,\d+,64,640\]\S* dynamic-update-slice\(", out["decode_text"])
    assert not materialised(out["decode_text"], WEIGHT_SLICE)
    # The kernels, under the names the per-layer metrics find them by.
    assert re.search(r"%_latent_decode_pallas(\.\d+)? = ", out["decode_text"])
    assert re.search(r"%_gdn_update_pallas(\.\d+)? = ", out["decode_text"])
    assert re.search(r"%gmm(\.\d+)? = ", out["decode_text"])
    assert re.search(r"%gmm(\.\d+)? = ", out["prefill_text"])
    assert "_paged_pallas" not in out["decode_text"]  # no K / V pool to read


def test_a_576_wide_pool_is_held_in_640_once_the_kernel_reads_it(topo):  # noqa: F811
    """What settled the row's width: the kernel's operand is tiled (8, 128),
    so a pool declared 576 wide is a 640-wide buffer to it, and its 576-wide
    page is no aligned slice. The row is stored in 640, pad lanes zero."""
    import functools

    import jax
    import jax.numpy as jnp
    import pytest
    from jax.sharding import SingleDeviceSharding

    from kubeai_tpu.ops import latent_attention as la

    one = SingleDeviceSharding(topo.devices[0])

    def lower(width):
        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        fn = functools.partial(
            la._latent_decode_pallas, scale=192 ** -0.5, rank=512, interpret=False)
        return jax.jit(fn).lower(
            arg((128, 32, width), jnp.bfloat16), arg((3, 8193, 64, width), jnp.bfloat16),
            arg((128, width), jnp.bfloat16), arg((128, 64), jnp.int32),
            arg((128,), jnp.int32), arg((1,), jnp.int32))

    assert la.latent_row_width(512, 64) == 640
    stats = lower(640).compile().memory_analysis()
    assert stats.temp_size_in_bytes == 0  # the pool is read where it lies
    with pytest.raises(Exception, match=r"3x8193x64x640xbf16.*|aligned to tiling"):
        lower(576).compile()
