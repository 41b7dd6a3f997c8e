"""Ahead-of-time compiles for a described v5e:2x2, at the cells' real sizes:
they guard both configurations on every later PR at no chip time. Nothing
runs; a compile that passes is not a chip run."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402

import aot  # noqa: E402  (tests/perf/aot.py)

HBM = 15.75 * 2**30  # what the TPU compiler allows a v5e program


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


def test_mistral_one_chip_largest_graphs_fit(topo):
    cfg = aot.load_config("mistral-7b-v5e1")
    out = aot.compile_cell(topo, cfg, admit=8, bucket=2048)
    for graph in ("weights", "decode", "prefill"):
        assert aot.peak_bytes(out[graph]) < HBM, graph
    # 24 slots x 2048 tokens x 64 KiB of pages are 3 GiB; the weights 7 GiB.
    assert out["decode"].argument_size_in_bytes > 10 * 2**30
    assert "tpu_custom_call" in out["decode_text"]  # the paged kernel is there


def test_mistral_slots_fit_in_eights_up_to_64_and_72_are_refused(topo):
    """Decode reads and writes the pool in place (PR 25), so what a slot
    costs is its pages: 8 slots x 2048 tokens x 64 KiB are 1 GiB. Compile
    upward in eights to the first count the compiler refuses (72 today).

    Each bound faces the waste it fears and no other way: the chunk's
    arguments are the weights and the pages and are held from both sides,
    its temporaries and its peak from above only. A program that drops
    temporaries (0.63 GiB today, copies of the stacked attention
    projections: PERF.md section 5) or fits 72 slots is a better one."""
    cfg = aot.load_config("mistral-7b-v5e1")
    fits = {}
    for slots in range(32, 129, 8):
        try:
            out = aot.compile_cell(topo, cfg, admit=1, bucket=128, what=("decode",),
                                   engine_overrides={"num_slots": slots})
        except Exception as e:
            assert re.search("RESOURCE_EXHAUSTED|memory", str(e)), e
            break
        fits[slots] = out["decode"]
    else:
        pytest.fail("128 slots compiled: 16 GiB of pages beside 7 GiB of weights")
    assert slots >= 72 and sorted(fits) == list(range(32, slots, 8))
    # 7.0 GiB of weights and 32 slots x 2048 tokens x 64 KiB = 4 GiB of pages.
    assert 10.9 * 2**30 < fits[32].argument_size_in_bytes < 11.2 * 2**30
    assert fits[32].temp_size_in_bytes < 0.8 * 2**30
    assert aot.peak_bytes(fits[32]) < 11.8 * 2**30  # 11.63 GiB (AOT, PR 25)
    assert all(aot.peak_bytes(fits[n + 8]) - aot.peak_bytes(fits[n])
               == pytest.approx(2**30, rel=0.01) for n in (32, 40, 48, 56))


def test_mixtral_tp4_graphs_fit_and_carry_collectives(topo):
    cfg = aot.load_config("mixtral-8x7b-v5e4")
    out = aot.compile_cell(topo, cfg, admit=8, bucket=256)
    for graph in ("weights", "decode", "prefill"):
        assert aot.peak_bytes(out[graph]) < HBM, graph
    # Per chip: a quarter of 46.7 GB of weights.
    assert 10 * 2**30 < out["decode"].argument_size_in_bytes < 13.5 * 2**30
    assert "all-reduce" in out["decode_text"]
    assert "tpu_custom_call" in out["decode_text"]
