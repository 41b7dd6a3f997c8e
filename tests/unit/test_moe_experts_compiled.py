"""Guards on the COMPILED routed and recurrent layers of
`qwen3-next-80b-a3b.decode-sat`'s decode chunk. Beside its three grouped
products a routed layer spends a few dense instructions on their tile map
(`ops/grouped_matmul.py:tile_plan`), not the sixty of the library's group
metadata over `NL x X` groups with their scatters and their `while` (PR 47),
and the products are still the kernel `gmm` that the per-layer metrics find by
that name. Its 640 assignments are put in order and summed back by a
comparison and two small products, with no sort, scatter or gather
(`ops/experts.py:dispatch`, `combine`), and the convolution of a
recurrent layer reads its taps as slices of the pool, with no relayout of the
window (`models/qwen3_next.py:_conv_step`) (PR 48). An admission's
assignments are still sorted.

One ahead-of-time compile of the decode program alone, for a described v5e
(nothing runs; a compile that passes is not a chip run): 17 s, shared by the
tests of this file. The family's programs take the state pools as one more
argument than `tests/perf/aot.py` passes, so the lowering is written out here
as `tests/perf/test_aot_qwen3_next.py:compile_hybrid_cell` writes it, less the
admission and the weights' program, which would make it 80 s."""

import os
import re
import sys

import pytest

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


def lines_in_scope(text: str, scope: str) -> list[str]:
    """Every instruction's line, inside fusions too, whose innermost named
    scope is `scope`."""
    found = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        scopes = SCOPE.findall(name.group(1)) if name else []
        if scopes and scopes[-1] == scope:
            found.append(line)
    return found


@pytest.fixture(scope="module")
def decode_chunk(topo):  # noqa: F811
    """(the cell's configuration, its decode chunk's compiled text)."""
    cfg = aot.load_config("qwen3-next-80b-a3b-v5e1")
    return cfg, compiled_decode_chunk(topo, cfg)


def test_a_routed_layer_spends_a_few_dense_instructions_beside_its_products(decode_chunk):
    cfg, text = decode_chunk
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


# Instructions a routed layer, read at PR 48: 5 and 7. Until then 9 and 11,
# among them a sort, two scatters (`bincount`, the inverse permutation) and
# the two row gathers, 4.5 to 11.2 us each on the chip (PERF.md section 5).
BOOKS_A_LAYER = {"moe_dispatch": 8, "moe_combine": 10}


@pytest.mark.parametrize("scope", list(BOOKS_A_LAYER))
def test_a_decode_steps_assignments_are_ordered_and_summed_where_they_lie(
        decode_chunk, scope):
    """640 assignments a layer: ranked by one comparison, gathered and
    summed by two small products (`ops/experts.py:dispatch`,
    `combine`)."""
    cfg, text = decode_chunk
    found = instructions_in_scope(text, scope)
    body = max(found.values(), key=len)
    period = cfg["full_attention_interval"]
    assert period <= len(body) <= BOOKS_A_LAYER[scope] * period, (len(body), body)
    # Anywhere in the program, inside fusions too.
    inside = " ".join(lines_in_scope(text, scope))
    assert not re.search(r" (sort|scatter|gather|while)\(", inside)


def test_the_convolution_reads_its_taps_where_they_lie(decode_chunk):
    """`models/qwen3_next.py:_conv_step`: no copy or transpose of the K - 1
    inputs a slot keeps (they were re-laid-out as a `[B, K, C]` window three
    times a layer), and no sum over a 4-row sublane axis."""
    cfg, text = decode_chunk
    C = (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
         + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])
    lines = lines_in_scope(text, "gdn_conv")
    assert lines
    moved = [
        line for line in lines
        if re.search(r" (copy|transpose)\(", line)
        and re.search(r"\[[\d,]*\b(%d|%d)\b[\d,]*\]" % (C, 3 * C), line)]
    assert not moved, moved
    assert not [line for line in lines if "T(4,128)" in line]


def test_an_admissions_assignments_are_still_sorted(topo):  # noqa: F811
    """Over `RANK_BY_COMPARISON_MAX` (two 128-token prompts of this cell are
    2,560 assignments a layer) the comparison's square costs what the sort
    it replaces costs, and soon more: the same function, traced with more
    rows, keeps the sort."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from kubeai_tpu.ops import dispatch
    from kubeai_tpu.ops import experts as experts_ops

    one = SingleDeviceSharding(topo.devices[0])

    def compiled(rows):
        shapes = [
            jax.ShapeDtypeStruct(shape, dtype, sharding=one)
            for shape, dtype in (
                ((rows, 2048), jnp.bfloat16), ((rows, 10), jnp.int32),
                ((rows, 10), jnp.float32), ((2, 64, 2048, 512), jnp.bfloat16),
                ((2, 64, 512, 2048), jnp.bfloat16))]

        def layer(x, topi, probs, w_in, w_out):
            experts = {"w_gate": w_in, "w_up": w_in, "w_down": w_out}
            return experts_ops.moe_sparse(x, experts, jnp.int32(1), topi, probs, 0)

        return jax.jit(layer).lower(*shapes).compile().as_text()

    saved = dispatch.kernel_mode
    dispatch.kernel_mode = lambda: "compiled"
    try:
        assert 64 * 10 <= experts_ops.RANK_BY_COMPARISON_MAX < 256 * 10
        admission, step = compiled(256), compiled(64)
    finally:
        dispatch.kernel_mode = saved
    assert re.search(r" sort\(", admission) and "gmm" in admission
    assert not re.search(r" (sort|scatter|gather)\(", step) and "gmm" in step
