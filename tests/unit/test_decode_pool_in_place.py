"""Guard on the COMPILED decode chunk of the benchmark's one-chip cell: the
KV page pool is read and written where it lies. Ahead-of-time compiles for a
described v5e (nothing runs; a compile that passes is not a chip run), through
the benchmark's own helper `tests/perf/aot.py`, which this file only reads."""

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


@pytest.fixture(scope="module")
def cell():
    """(configuration, stacked-pool shape, one layer's pool shape) as HLO
    prints them."""
    from kubeai_tpu.engine.engine import EngineConfig

    cfg = aot.load_config("mistral-7b-v5e1")
    ecfg = EngineConfig(**cfg["engine"])
    layer = (ecfg.effective_num_pages(), ecfg.page_size,
             cfg["num_key_value_heads"],
             cfg["hidden_size"] // cfg["num_attention_heads"])
    dims = lambda shape: ",".join(str(d) for d in shape)  # noqa: E731
    return cfg, dims((cfg["num_hidden_layers"],) + layer), dims(layer)


@pytest.fixture(scope="module")
def decode(topo, cell):
    """The cell's decode chunk as the engine builds it with nothing set."""
    return aot.compile_cell(topo, cell[0], admit=1, bucket=128,
                            what=("decode",))


def pool_movers(hlo: str, shapes: tuple[str, ...]) -> list[str]:
    """Instructions that produce a pool-shaped array by moving one: a
    `copy`, `dynamic-slice` or `dynamic-update-slice`, alone or as a
    fusion the compiler named after them."""
    moved = []
    for line in hlo.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.-]+) = bf16\[([\d,]+)\]\S* (\S+?)\(", line)
        if m is None or m.group(2) not in shapes:
            continue
        name, op = m.group(1), m.group(3)
        if op in ("copy", "dynamic-slice", "dynamic-update-slice") or (
                op == "fusion" and re.search(
                    r"copy|dynamic-slice|dynamic-update-slice", name)):
            moved.append(line.strip()[:160])
    return moved


def test_the_chunk_keeps_no_pool_sized_temporary(decode):
    # Two whole-pool copies were 3.94 GiB of temporaries (AOT, PR 25).
    assert decode["decode"].temp_size_in_bytes < GIB
    assert aot.peak_bytes(decode["decode"]) < 11.5 * GIB


def test_no_instruction_moves_a_pool(decode, cell):
    _, stacked, layer = cell
    assert f"bf16[{stacked}]" in decode["decode_text"]  # the shapes are right
    assert pool_movers(decode["decode_text"], (stacked, layer)) == []


def test_pool_movers_sees_the_per_layer_layout(topo, cell):
    """The guard above is not blind: scatter-then-attend inside the layer
    scan is full of what it looks for."""
    cfg, stacked, layer = cell
    out = aot.compile_cell(topo, cfg, admit=1, bucket=128, what=("decode",),
                           engine_overrides={"decode_kernel": "per_layer"})
    moved = pool_movers(out["decode_text"], (stacked, layer))
    assert any("copy" in m for m in moved), moved
    assert any("dynamic-update-slice" in m for m in moved), moved
    assert out["decode"].temp_size_in_bytes > 3 * GIB


def kernel_calls(hlo: str) -> list[str]:
    """Names of the Mosaic custom calls. The trace names a custom call as
    the HLO does: after the jitted wrapper round the `pallas_call`."""
    return re.findall(r"%?([\w.]+) = [^=]*custom-call\([^\n]*"
                      r'custom_call_target="tpu_custom_call"', hlo)


def test_the_kernel_is_named_for_the_trace_metric(decode):
    """`perf/layer_metrics/paged_attn_ms*.json` match `^_paged_pallas`."""
    names = kernel_calls(decode["decode_text"])
    assert names, "no tpu_custom_call in the decode chunk"
    assert all(re.match(r"_paged_pallas", n) for n in names), names


def test_the_kernel_lowers_at_two_kv_heads_a_chip(topo):
    """The configuration on file for the four-chip cell: Mixtral at tp=4
    leaves the kernel 2 KV heads (a [page, 2, 128] block, 8 query rows),
    the narrowest tiling the fold of heads into one dot has to lower at.
    (Its chunk still moves the pool, as PR 27's did: PERF.md section 7.)"""
    out = aot.compile_cell(topo, aot.load_config("mixtral-8x7b-v5e4"),
                           admit=1, bucket=128, what=("decode",))
    names = kernel_calls(out["decode_text"])
    assert names and all(re.match(r"_paged_pallas", n) for n in names), names
    assert aot.peak_bytes(out["decode"]) < HBM


def test_thirty_two_slots_fit(topo, cell):
    out = aot.compile_cell(topo, cell[0], admit=1, bucket=128,
                           what=("decode",),
                           engine_overrides={"num_slots": 32})
    assert aot.peak_bytes(out["decode"]) < HBM
    assert out["decode"].temp_size_in_bytes < GIB
