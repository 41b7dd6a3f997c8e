"""Guard on the COMPILED decode chunk of the benchmark's cells, the one-chip
one and the four-chip one (one shard of a tp=4 pool holds 2 KV heads): the KV
page pool is read and written where it lies, and so are the stacked attention
projection weights (PR 39). Ahead-of-time compiles for a described v5e
(nothing runs; a compile that passes is not a chip run), through the
benchmark's own helper `tests/perf/aot.py`, which this file only reads."""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "perf"))

import aot  # noqa: E402  (tests/perf/aot.py)

HBM = 15.75 * 2**30  # what the TPU compiler allows a v5e program
GIB = 2**30


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An entry written for a chip that is not attached cannot be read back.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", True)


# Per configuration: what the compiler may hold beside the arguments, and the
# program's peak (GiB a chip). Mixtral's chunk held 1.159 / 13.096 with six
# pool copies until the page write took the layer as an index (AOT, PR 35).
# `window`: the page write's `update_window_dims`. Eight KV heads a chip
# take [NL, KVH, D] windows (the layer a slice), two take a token's
# [KVH, D] row (the layer an index): ops/paged_attention.py:_write_token_rows.
CELLS = {
    "mistral-7b-v5e1": {"temp": 1.0, "peak": 11.5, "what": ("decode",),
                        "window": "{0,2,3}"},
    "mixtral-8x7b-v5e4": {"temp": 0.5, "peak": 12.5,
                          "what": ("decode", "prefill"), "window": "{2,3}"},
}


def pool_shapes(cfg: dict) -> tuple[str, str]:
    """(stacked pool, one layer's pool) of ONE chip, as HLO prints them: the
    compiled text is a shard's program, and KV heads split over tp."""
    from kubeai_tpu.engine.engine import EngineConfig
    from kubeai_tpu.parallel.sharding import kv_heads_axis

    ecfg = EngineConfig(**cfg["engine"])
    kvh = cfg["num_key_value_heads"]
    axis = kv_heads_axis(cfg["mesh"], kvh)
    layer = (ecfg.effective_num_pages(), ecfg.page_size,
             kvh // cfg["mesh"].get(axis, 1),
             cfg["hidden_size"] // cfg["num_attention_heads"])
    dims = lambda shape: ",".join(str(d) for d in shape)  # noqa: E731
    return dims((cfg["num_hidden_layers"],) + layer), dims(layer)


@pytest.fixture(scope="module")
def cell():
    """(configuration, stacked-pool shape, one layer's pool shape) of the
    one-chip cell."""
    cfg = aot.load_config("mistral-7b-v5e1")
    return (cfg,) + pool_shapes(cfg)


@pytest.fixture(scope="module")
def compiled(topo):
    """name -> the cell's decode chunk (and, for the four-chip cell, its
    prefill-admit 8 x 256) as the engine builds them with nothing set;
    compiled once a module."""
    done = {}

    def get(name):
        if name not in done:
            done[name] = aot.compile_cell(
                topo, aot.load_config(name), admit=8, bucket=256,
                what=CELLS[name]["what"])
        return done[name]

    return get


POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def pool_movers(hlo: str, shapes: tuple[str, ...], ops=POOL_MOVES,
                fused=POOL_MOVES) -> list[str]:
    """Instructions that produce an array of one of `shapes` by moving one:
    a `copy`, `dynamic-slice` or `dynamic-update-slice` (`ops`), alone or
    as a fusion the compiler named after one of them (`fused`)."""
    moved = []
    for line in hlo.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.-]+) = bf16\[([\d,]+)\]\S* (\S+?)\(", line)
        if m is None or m.group(2) not in shapes:
            continue
        name, op = m.group(1), m.group(3)
        if op in ops or (op == "fusion" and re.search("|".join(fused), name)):
            moved.append(line.strip()[:160])
    return moved


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_chunk_keeps_no_pool_sized_temporary(compiled, name):
    # Two whole-pool copies were 3.94 GiB of temporaries (AOT, PR 25).
    stats = compiled(name)["decode"]
    assert stats.temp_size_in_bytes < CELLS[name]["temp"] * GIB
    assert aot.peak_bytes(stats) < CELLS[name]["peak"] * GIB


@pytest.mark.parametrize("name", sorted(CELLS))
def test_no_instruction_moves_a_pool(compiled, name):
    stacked, layer = pool_shapes(aot.load_config(name))
    text = compiled(name)["decode_text"]
    assert f"bf16[{stacked}]" in text  # the shapes are right
    assert pool_movers(text, (stacked, layer)) == []


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_page_write_takes_the_window_the_shard_needs(compiled, name):
    """Mistral's write stays the coarse one (a token's row a window costs
    its cells 0.7% of a decode step and 2.3 ms a chat admission call: PERF.md
    section 6, PR 35), Mixtral's the fine one that keeps the pool's layout."""
    stacked, _ = pool_shapes(aot.load_config(name))
    writes = re.findall(
        r"= bf16\[" + stacked + r"\]\S* scatter\([^\n]*"
        r"update_window_dims=(\{[\d,]+\})[^\n]*kv_page_write",
        compiled(name)["decode_text"])
    assert writes == [CELLS[name]["window"]] * 2, writes  # K and V


def test_the_four_chip_admission_keeps_no_pool_sized_temporary(compiled):
    """Mixtral's prefill-admit 8 x 256 copied both pools in and out round
    its page write: 0.566 GiB of temporaries, 0.190 since (AOT, PR 35)."""
    stats = compiled("mixtral-8x7b-v5e4")["prefill"]
    assert stats.temp_size_in_bytes < 0.3 * GIB
    assert aot.peak_bytes(stats) < 12.5 * GIB


def projection_shapes(cfg: dict) -> tuple[dict, int]:
    """({"stacked": shapes, "layer": shapes}, bytes of the smallest stacked
    one) of the attention projection weights `wq` and `wk` / `wv` on ONE
    chip, as HLO prints them: `[NL, E, heads x D]` with the heads split
    over tp, and one layer of it with and without its leading 1."""
    tp = cfg["mesh"].get("tp", 1)
    nl, e = cfg["num_hidden_layers"], cfg["hidden_size"]
    d = cfg.get("head_dim") or e // cfg["num_attention_heads"]
    outs = sorted({cfg["num_attention_heads"] * d // tp,
                   cfg["num_key_value_heads"] * d // tp})
    shapes = {
        "stacked": tuple(f"{nl},{e},{o}" for o in outs),
        "layer": tuple(f"{lead}{e},{o}" for o in outs for lead in ("1,", "")),
    }
    return shapes, nl * e * outs[0] * 2


def weight_movers(hlo: str, cfg: dict) -> list[str]:
    """Instructions that copy or transpose a stacked projection weight, or
    a fusion that only moves one layer of it (the
    `constant_dynamic-slice_fusion` that took a layer's transposed `wq`
    into fast memory and computed nothing: PERF.md section 6, PR 39). A
    bare `dynamic-slice` is not counted: inside the fusion that multiplies
    it is how a layer is read where it lies. `wo` has `wq`'s shape and is
    held to the same."""
    shapes, _ = projection_shapes(cfg)
    return pool_movers(
        hlo, shapes["stacked"] + shapes["layer"], ops=("copy", "transpose"),
        fused=("copy", "transpose", "dynamic-slice"))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_no_instruction_moves_a_stacked_projection_weight(compiled, name):
    """The decode layer's q / k / v projections each read the stacked
    parameter where it lies (kubeai_tpu/ops/projections.py)."""
    cfg = aot.load_config(name)
    shapes, _ = projection_shapes(cfg)
    text = compiled(name)["decode_text"]
    for stacked in shapes["stacked"]:
        assert f"bf16[{stacked}]" in text  # the shapes are right
    assert weight_movers(text, cfg) == []


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_chunk_keeps_no_weight_sized_temporary(compiled, name):
    """A transposed copy of `wq` and `wk` was the chunk's 0.626 GiB of
    temporaries (0.126 GiB a tp=4 shard); 0 since (AOT, PR 39). Under the
    smallest stacked projection weight: 128 MiB, 32 MiB a shard."""
    _, smallest = projection_shapes(aot.load_config(name))
    assert smallest == {"mistral-7b-v5e1": 128, "mixtral-8x7b-v5e4": 32}[
        name] * 2**20
    assert compiled(name)["decode"].temp_size_in_bytes < smallest


def test_weight_movers_sees_the_folded_projections(topo, cell, monkeypatch):
    """The guard above is not blind: with the plain
    `einsum(...).reshape(...)` form in the decode layer the compiler
    transposes both stacked weights in the chunk's entry and moves a
    layer of each in every layer of every step."""
    from kubeai_tpu.ops import projections

    monkeypatch.setattr(projections, "HELD_BELOW_ROWS", 0)
    cfg = cell[0]
    out = aot.compile_cell(topo, cfg, admit=1, bucket=128, what=("decode",))
    shapes, smallest = projection_shapes(cfg)
    moved = weight_movers(out["decode_text"], cfg)
    for stacked in shapes["stacked"]:
        assert any(f"bf16[{stacked}]" in m and " copy(" in m for m in moved), moved
    assert any("dynamic-slice_fusion" in m for m in moved), moved
    assert out["decode"].temp_size_in_bytes > 4 * smallest


def test_pool_movers_sees_the_per_layer_layout(topo, cell, monkeypatch):
    """The guard above is not blind: scatter-then-attend inside the layer
    scan is full of what it looks for. No engine option names a layout, so
    the family's forward is held to it (`attn_kernel="per_layer"`)."""
    from testutil import per_layer_forward

    from kubeai_tpu.models.registry import get_model_family

    cfg, stacked, layer = cell
    family = get_model_family(cfg["architectures"][0])
    monkeypatch.setattr(
        family, "decode_step_paged", per_layer_forward(family))
    out = aot.compile_cell(topo, cfg, admit=1, bucket=128, what=("decode",))
    moved = pool_movers(out["decode_text"], (stacked, layer))
    assert any("copy" in m for m in moved), moved
    assert any("dynamic-update-slice" in m for m in moved), moved
    assert out["decode"].temp_size_in_bytes > 3 * GIB


def kernel_calls(hlo: str) -> list[str]:
    """Names of the Mosaic custom calls. The trace names a custom call as
    the HLO does: after the jitted wrapper round the `pallas_call`."""
    return re.findall(r"%?([\w.]+) = [^=]*custom-call\([^\n]*"
                      r'custom_call_target="tpu_custom_call"', hlo)


def test_the_kernel_is_named_for_the_trace_metric(compiled):
    """`perf/layer_metrics/paged_attn_ms*.json` match `^_paged_pallas`."""
    names = kernel_calls(compiled("mistral-7b-v5e1")["decode_text"])
    assert names, "no tpu_custom_call in the decode chunk"
    assert all(re.match(r"_paged_pallas", n) for n in names), names


def test_the_kernel_lowers_at_two_kv_heads_a_chip(compiled):
    """The configuration on file for the four-chip cell: Mixtral at tp=4
    leaves the kernel 2 KV heads (a [page, 2, 128] block, 8 query rows),
    the narrowest tiling the fold of heads into one dot has to lower at.
    The kernel reads the pool as the chunk's parameters hold it: the cases
    above hold this chunk to no pool-shaped copy, as they hold Mistral's."""
    out = compiled("mixtral-8x7b-v5e4")
    names = kernel_calls(out["decode_text"])
    assert names and all(re.match(r"_paged_pallas", n) for n in names), names
    assert aot.peak_bytes(out["decode"]) < HBM


def test_thirty_two_slots_fit(topo, cell):
    out = aot.compile_cell(topo, cell[0], admit=1, bucket=128,
                           what=("decode",),
                           engine_overrides={"num_slots": 32})
    assert aot.peak_bytes(out["decode"]) < HBM
    assert out["decode"].temp_size_in_bytes < GIB
