"""ctypes bindings for the native C++ data-plane library.

Built from what git holds: `make` runs on first use and rebuilds the
git-ignored .so whenever the source is newer (a one-second compile, a no-op
when up to date), so a stale library left lying in the tree is never
loaded. Where `make` cannot run (an image that ships the library without a
toolchain), a library that is not older than its source is loaded as it
is. Otherwise everything degrades to the pure-Python implementations, and
says so once in the log.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

log = logging.getLogger(__name__)

_LIB = None
_LOCK = threading.Lock()
_TRIED = False

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libkubeai_native.so")


def _build() -> bool:
    """True when an up-to-date library is in place."""
    src = os.path.join(_NATIVE_DIR, "kubeai_native.cpp")
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return os.path.exists(_SO_PATH)
    except (subprocess.SubprocessError, OSError) as e:
        # No toolchain here. A library shipped prebuilt is still good
        # unless the source has moved on since it was built.
        current = os.path.exists(_SO_PATH) and (
            not os.path.exists(src)
            or os.path.getmtime(_SO_PATH) >= os.path.getmtime(src)
        )
        if not current:
            log.warning(
                "native library not built (%s) and no current %s; using the "
                "pure-Python hash and ring", e, _SO_PATH,
            )
        return current


def load_native():
    """Returns the loaded library or None."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            log.warning(
                "native library %s does not load (%s); using the "
                "pure-Python hash and ring", _SO_PATH, e,
            )
            return None
        lib.kubeai_xxhash64.restype = ctypes.c_uint64
        lib.kubeai_xxhash64.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
        ]
        lib.kubeai_ring_new.restype = ctypes.c_void_p
        lib.kubeai_ring_new.argtypes = [ctypes.c_double, ctypes.c_int]
        lib.kubeai_ring_free.argtypes = [ctypes.c_void_p]
        lib.kubeai_ring_add.restype = ctypes.c_int
        lib.kubeai_ring_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.kubeai_ring_remove.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.kubeai_ring_lookup.restype = ctypes.c_int
        lib.kubeai_ring_lookup.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
            ctypes.c_char_p,
        ]
        _LIB = lib
        return _LIB


def xxhash64_native(data: bytes, seed: int = 0) -> int | None:
    lib = load_native()
    if lib is None:
        return None
    return lib.kubeai_xxhash64(data, len(data), seed)


class NativeCHWBL:
    """Native consistent-hash ring with bounded loads (see chwbl.py for
    the contract; the Python CHWBL is the oracle)."""

    def __init__(self, load_factor: float = 1.25, replication: int = 256):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.kubeai_ring_new(load_factor, replication)
        self._ids: dict[str, int] = {}
        self._names: list[str] = []
        self._lock = threading.Lock()

    def __del__(self):
        if getattr(self, "_h", None) and getattr(self, "_lib", None):
            self._lib.kubeai_ring_free(self._h)
            self._h = None

    def add(self, endpoint: str) -> None:
        with self._lock:
            eid = self._lib.kubeai_ring_add(self._h, endpoint.encode())
            self._ids[endpoint] = eid
            while len(self._names) <= eid:
                self._names.append("")
            self._names[eid] = endpoint

    def remove(self, endpoint: str) -> None:
        with self._lock:
            self._lib.kubeai_ring_remove(self._h, endpoint.encode())
            eid = self._ids.pop(endpoint, None)
            if eid is not None and eid < len(self._names):
                self._names[eid] = ""

    def get(
        self,
        key: str,
        loads: dict[str, int],
        adapter_endpoints: set[str] | None = None,
    ) -> str | None:
        with self._lock:
            n = len(self._names)
            if n == 0:
                return None
            arr = (ctypes.c_int64 * n)()
            for name, load in loads.items():
                eid = self._ids.get(name)
                if eid is not None:
                    arr[eid] = load
            mask = None
            if adapter_endpoints is not None:
                mask_bytes = bytearray(n)
                for name in adapter_endpoints:
                    eid = self._ids.get(name)
                    if eid is not None:
                        mask_bytes[eid] = 1
                mask = bytes(mask_bytes)
            kb = key.encode()
            eid = self._lib.kubeai_ring_lookup(
                self._h, kb, len(kb), arr, n, mask
            )
            if eid < 0 or eid >= len(self._names) or not self._names[eid]:
                return None
            return self._names[eid]
