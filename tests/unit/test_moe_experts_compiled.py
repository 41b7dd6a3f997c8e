"""Guard on the COMPILED expert layer of `qwen3-next-80b-a3b.decode-sat`'s
decode chunk: beside its three grouped products a routed layer spends a few
dense instructions on their tile map (`ops/grouped_matmul.py:tile_plan`), not
the sixty of the library's group metadata over `NL x X` groups with their
scatters and their `while` (PR 47), and the products are still the kernel
`gmm` that the per-layer metrics find by that name.

One ahead-of-time compile of the decode program alone, for a described v5e
(nothing runs; a compile that passes is not a chip run): 17 s. The family's
programs take the state pools as one more argument than `tests/perf/aot.py`
passes, so the lowering is written out here as
`tests/perf/test_aot_qwen3_next.py:compile_hybrid_cell` writes it, less the
admission and the weights' program, which would make it 80 s."""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "perf"))

import aot  # noqa: E402  (tests/perf/aot.py)
from test_decode_pool_in_place import topo  # noqa: E402, F401  (the described v5e:2x2)


def compiled_decode_chunk(topo, cfg: dict) -> str:
    """The compiled text of the hybrid family's decode chunk on one
    described chip, at the cell's sizes."""
    import importlib

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
        shardings = psh.param_shardings(family.param_specs(mcfg), mesh)
        reference = importlib.import_module("perf.reference." + cfg["reference"])
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
        with jax.set_mesh(mesh):
            params = jax.tree.map(
                lambda s, sh: abstract(s.shape, s.dtype, sh),
                jax.eval_shape(lambda k: reference.served_params(cfg, k), key),
                shardings)
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
                name: abstract((B,), dtype, rep)
                for name, dtype in (
                    ("tokens", jnp.int32), ("positions", jnp.int32),
                    ("seeds", jnp.uint32), ("temp", jnp.float32),
                    ("topk", jnp.int32), ("topp", jnp.float32),
                    ("lora_idx", jnp.int32))}
            return eng._decode_jit.lower(
                params, pool, pool, bt, state, None, pools).compile().as_text()
    finally:
        dispatch.kernel_mode = saved


# What an instruction count leaves out: they compute nothing.
FREE = {"tuple", "get-tuple-element", "bitcast", "constant", "parameter"}
SCOPE = re.compile(r"/(moe_\w+|gdn_\w+|gated_attention|lm_head|sample)(?=/|$)")


def instructions_in_scope(text: str, scope: str) -> dict[str, list[tuple[str, str]]]:
    """computation -> [(instruction name, opcode)] of the instructions that
    stand in a computation of their own right (a loop body, the entry; not
    inside a fusion) and whose innermost named scope is `scope`."""
    found: dict[str, list[tuple[str, str]]] = {}
    inside = ""
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(2)
            continue
        inst = re.match(r"^\s+(ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if (not inst or not name or inst.group(3) in FREE
                or inside.startswith("fused_computation")):
            continue
        scopes = SCOPE.findall(name.group(1))
        if scopes and scopes[-1] == scope:
            found.setdefault(inside, []).append((inst.group(2), inst.group(3)))
    return found


def test_a_routed_layer_spends_a_few_dense_instructions_beside_its_products(topo):  # noqa: F811
    cfg = aot.load_config("qwen3-next-80b-a3b-v5e1")
    text = compiled_decode_chunk(topo, cfg)
    found = instructions_in_scope(text, "moe_experts")
    # The layer loop's body holds a period of 4 layers, each of them routed.
    period = cfg["full_attention_interval"]
    body = max(found.values(), key=len)
    kernels = [name for name, op in body if op == "custom-call"]
    assert len(kernels) == 3 * period
    assert all(re.match(r"gmm(\.\d+)?$", name) for name in kernels), kernels
    # The products' `silu * up` and the tile map: 8 a layer where the
    # library's metadata was 55 to 60.
    others = [(name, op) for name, op in body if op != "custom-call"]
    assert period <= len(others) <= 15 * period, (len(others), others)
    # Nothing of the expert layer's scope, anywhere in the program, walks
    # its groups one by one.
    everywhere = {op for insts in found.values() for _, op in insts}
    assert not everywhere & {"while", "scatter", "sort", "gather"}, everywhere
