"""Native C++ components vs the pure-Python oracles."""

import numpy as np
import pytest

from kubeai_tpu.native import NativeCHWBL, load_native, xxhash64_native
from kubeai_tpu.routing.chwbl import CHWBL
from kubeai_tpu.routing.xxhash import xxhash64

pytestmark = pytest.mark.skipif(
    load_native() is None, reason="native library unavailable (no g++?)"
)


def test_native_xxhash_matches_python():
    rng = np.random.default_rng(0)
    cases = [b"", b"a", b"abc", b"x" * 100, bytes(rng.integers(0, 256, 1000))]
    for data in cases:
        assert xxhash64_native(data) == xxhash64(data), data[:16]


def test_native_ring_matches_python_ring():
    py = CHWBL(load_factor=1.25, replication=64)
    nat = NativeCHWBL(load_factor=1.25, replication=64)
    eps = [f"10.0.0.{i}:8000" for i in range(5)]
    for e in eps:
        py.add(e)
        nat.add(e)
    rng = np.random.default_rng(1)
    for trial in range(300):
        loads = {e: int(rng.integers(0, 10)) for e in eps}
        key = f"prefix-{rng.integers(0, 50)}"
        assert nat.get(key, loads) == py.get(key, loads), (key, loads)


def test_native_ring_adapter_walk_and_removal():
    py = CHWBL(replication=64)
    nat = NativeCHWBL(replication=64)
    eps = ["a:1", "b:1", "c:1"]
    for e in eps:
        py.add(e)
        nat.add(e)
    loads = {e: 0 for e in eps}
    for i in range(50):
        assert nat.get(f"k{i}", loads, {"b:1"}) == py.get(f"k{i}", loads, {"b:1"})
    py.remove("b:1")
    nat.remove("b:1")
    loads2 = {"a:1": 0, "c:1": 0}
    for i in range(50):
        assert nat.get(f"k{i}", loads2) == py.get(f"k{i}", loads2)


def test_native_ring_bounded_load_displacement():
    py = CHWBL(load_factor=1.0, replication=64)
    nat = NativeCHWBL(load_factor=1.0, replication=64)
    for e in ("a:1", "b:1"):
        py.add(e)
        nat.add(e)
    loads = {"a:1": 100, "b:1": 0}
    for i in range(20):
        assert nat.get(f"k{i}", loads) == py.get(f"k{i}", loads)


def test_prebuilt_library_loads_without_a_toolchain(monkeypatch, tmp_path, caplog):
    """`make` missing: a library not older than its source is still good;
    a stale or absent one is refused, and the downgrade is logged."""
    import os
    import shutil

    from kubeai_tpu import native

    def no_make(*a, **kw):
        raise FileNotFoundError("make")

    shutil.copy(native._SO_PATH, tmp_path / "libkubeai_native.so")
    src = tmp_path / "kubeai_native.cpp"
    src.write_text("// source")
    so = str(tmp_path / "libkubeai_native.so")
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SO_PATH", so)
    monkeypatch.setattr(native.subprocess, "run", no_make)

    os.utime(src, (1000, 1000))
    os.utime(so, (2000, 2000))
    assert native._build() is True
    src.unlink()  # an image that ships only the library
    assert native._build() is True

    src.write_text("// edited since the library was built")
    os.utime(src, (3000, 3000))
    with caplog.at_level("WARNING", logger=native.__name__):
        assert native._build() is False
        os.remove(so)
        assert native._build() is False
    assert caplog.text.count("pure-Python") == 2
