"""Serverless-grade engine cold start: snapshot restore-first boot.

Replica birth used to cost full HF-weight conversion plus XLA
compilation on every scale-from-zero, preemption repair, and planner
preemption. This module makes it cost a streamed restore instead
(PAPERS.md: SLINFER — replica birth should be a snapshot restore, not a
recompilation):

  1. `ColdStartManager.acquire_params` asks the `SnapshotStore` for a
     snapshot keyed by (model, engine-config fingerprint, mesh shape,
     snapshot version). Hit → chunk-parallel fetch + orbax restore of
     the post-conversion param tree, and the bundled JAX persistent
     compilation cache makes the first jit ~a cache read. Miss or
     `SnapshotMismatch` (NEVER serve a stale layout) → the full load
     path, unchanged.
  2. After warm-up (so the compilation cache holds the serving graphs),
     `maybe_publish` writes the snapshot back on first boot — the next
     replica of this exact configuration restores.

Every boot is phase-timed (`fetch` / `restore` / `load` / `compile` /
`warmup`) into `ColdStartTracker`, exported as `kubeai_coldstart_*`
metrics and on `/v1/state` so the fleet's demand forecaster can price
each model's measured cold-start cost into prewarm and preemption
decisions.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import shutil
import tempfile
import time
from collections.abc import Mapping

logger = logging.getLogger(__name__)

# Phase vocabulary (fixed so dashboards and the forecaster can rely on
# it): restore-path boots time fetch/restore, full-load boots time load;
# compile (first generate, jit) and warmup (second generate,
# steady-state) are measured on both paths.
PHASES = ("fetch", "restore", "load", "compile", "warmup")

# Snapshot events exported with counter semantics.
EVENTS = ("restored", "published", "mismatch", "absent", "error")


class ColdStartTracker:
    """Per-phase wall timings for one engine boot (injectable clock so
    the fake-clock sim drives it deterministically)."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._t0 = clock()
        self._total: float | None = None
        self.phases: dict[str, float] = {}
        self.events: list[str] = []
        self.restored = False
        self.fingerprint = ""

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = self._clock()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + (
                self._clock() - t0
            )

    def event(self, name: str) -> None:
        self.events.append(name)

    def finish(self) -> float:
        self._total = self._clock() - self._t0
        return self._total

    @property
    def total_s(self) -> float:
        return self._total if self._total is not None else (
            self._clock() - self._t0
        )

    def snapshot(self) -> dict:
        """The `/v1/state` cold_start block (and the metric source)."""
        return {
            "restored": self.restored,
            "fingerprint": self.fingerprint,
            "phases": dict(self.phases),
            "total_s": round(self.total_s, 6),
            "events": list(self.events),
        }


def mesh_signature(mesh) -> list:
    """Deterministic mesh identity for the snapshot key: axis sizes when
    the mesh exposes a name->size mapping, device-grid shape otherwise.
    Any change here must miss the snapshot — a tree sharded for a
    different slice shape is a stale layout."""
    shape = getattr(mesh, "shape", None)
    if isinstance(shape, Mapping):
        return [f"{k}={v}" for k, v in shape.items()]
    devices = getattr(mesh, "devices", None)
    if devices is not None and hasattr(devices, "shape"):
        return list(devices.shape)
    return []


# Where the persistent compilation cache lives when the environment does
# not say: one fixed, git-ignored path inside the checkout. The directory
# is part of the cache key, so a path that moves (a temp dir, a pid, a
# time) never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """The one rule for JAX's persistent compilation cache; every process
    that compiles serving graphs (server main, bench.py, chip_smoke.py's
    children) calls it before its first compile. Where
    JAX_COMPILATION_CACHE_DIR is set JAX already points there and no
    directory is set in code; where it is not, the cache goes to
    DEFAULT_CACHE_DIR. The thresholds are zeroed so every serving graph
    is kept (the defaults skip compiles under a second). Returns the
    directory in effect."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def _cache_dir_in_effect() -> str | None:
    import jax

    return jax.config.jax_compilation_cache_dir or None


class ColdStartManager:
    """Restore-first boot orchestration for `engine/server.py`.

    With no snapshot URL the manager degrades to a pure phase timer
    around the full load path — `/v1/state` and the coldstart metrics
    stay populated either way."""

    def __init__(
        self,
        snapshot_url: str,
        model_name: str,
        engine_config,
        mesh,
        *,
        work_dir: str | None = None,
        clock=time.monotonic,
        store=None,
        publish: bool = True,
    ):
        from kubeai_tpu.objstore import SnapshotStore

        self.enabled = bool(snapshot_url)
        # publish=False boots are restore-only consumers (CRD
        # coldStart.publish): they never write a snapshot back.
        self.publish = publish
        self.model = model_name
        self.tracker = ColdStartTracker(clock)
        # Staging for the param tree and the fetched/published bundle only;
        # the compilation cache stays wherever enable_compilation_cache put
        # it, and is copied out of / into the bundle from there.
        self.work_dir = work_dir or tempfile.mkdtemp(prefix="kubeai-snap-")
        self.params_dir = os.path.join(self.work_dir, "params")
        cfg = (
            dataclasses.asdict(engine_config)
            if dataclasses.is_dataclass(engine_config)
            else dict(engine_config or {})
        )
        self.fingerprint = SnapshotStore.fingerprint(
            model_name, cfg, mesh_signature(mesh)
        )
        self.tracker.fingerprint = self.fingerprint
        self.store = store or (
            SnapshotStore(snapshot_url) if self.enabled else None
        )

    def acquire_params(self, full_load, like=None):
        """Restore the param tree from the snapshot when a complete one
        exists under this boot's fingerprint; otherwise run `full_load`
        (HF conversion). A `SnapshotMismatch` is a hard fallback — the
        mismatched tree is never restored."""
        from kubeai_tpu.objstore import SnapshotMismatch

        manifest = None
        if self.enabled:
            try:
                with self.tracker.phase("fetch"):
                    manifest = self.store.fetch(
                        self.model, self.fingerprint, self.work_dir
                    )
            except SnapshotMismatch as e:
                logger.warning("%s", e)
                self.tracker.event("mismatch")
            except Exception as e:  # noqa: BLE001 — boot must survive the store
                logger.warning("snapshot fetch failed: %s", e)
                self.tracker.event("error")
            else:
                if manifest is None:
                    self.tracker.event("absent")
                else:
                    self._restore_compilation_cache()
        if manifest is not None:
            try:
                from kubeai_tpu.engine.weights import load_native_checkpoint

                with self.tracker.phase("restore"):
                    params = load_native_checkpoint(self.params_dir, like=like)
                self.tracker.restored = True
                self.tracker.event("restored")
                logger.info(
                    "restored snapshot %s/%s", self.model, self.fingerprint
                )
                return params
            except Exception as e:  # noqa: BLE001 — fall back, don't crash-loop
                logger.warning(
                    "snapshot restore failed (%s): falling back to full load",
                    e,
                )
                self.tracker.event("error")
        with self.tracker.phase("load"):
            return full_load()

    def _restore_compilation_cache(self) -> None:
        """Copy the snapshot's bundled cache entries into the directory in
        effect, before the first compile, so the first jit is a read."""
        fetched = os.path.join(self.work_dir, "xla_cache")
        cache_dir = _cache_dir_in_effect()
        if cache_dir and os.path.isdir(fetched):
            shutil.copytree(fetched, cache_dir, dirs_exist_ok=True)

    def maybe_publish(self, params) -> bool:
        """Write-back on first boot, called AFTER warm-up so the bundled
        compilation cache holds the serving graphs. No-op when restore
        succeeded (the key is already complete) or snapshots are off."""
        if not self.enabled or not self.publish or self.tracker.restored:
            return False
        stage = os.path.join(self.work_dir, "publish")
        try:
            from kubeai_tpu.engine.weights import save_native_checkpoint

            shutil.rmtree(stage, ignore_errors=True)
            os.makedirs(stage, exist_ok=True)
            save_native_checkpoint(os.path.join(stage, "params"), params)
            cache_dir = _cache_dir_in_effect()
            if cache_dir and os.path.isdir(cache_dir) and os.listdir(cache_dir):
                shutil.copytree(cache_dir, os.path.join(stage, "xla_cache"))
            self.store.publish(
                self.model,
                self.fingerprint,
                stage,
                meta={"boot_phases": dict(self.tracker.phases)},
            )
            self.tracker.event("published")
            logger.info(
                "published snapshot %s/%s", self.model, self.fingerprint
            )
            return True
        except Exception as e:  # noqa: BLE001 — publish is best-effort
            logger.warning("snapshot publish failed: %s", e)
            self.tracker.event("error")
            return False
        finally:
            shutil.rmtree(stage, ignore_errors=True)
