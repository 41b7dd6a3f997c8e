"""Engine step profiler: a per-phase monotonic timeline of Engine.step.

The engine's step loop is the hot path everyone blames when ITL climbs,
but until now it exported only one number per step (wall duration) — the
answer to "why is ITL high" required guesswork. The profiler breaks each
step into phases:

  schedule   — host-side bookkeeping before the decode dispatch (page
               allocation, speculation arm pick)
  prefill    — the admission pass (scheduler pops + prefill compute)
  decode     — the decode/speculation jit DISPATCH (async under JAX; the
               device wait surfaces in overlap_idle at reap time)
  dispatch   — host→device input staging for the chunk (the block-table
               upload before the decode jit)
  overlap_idle — time the host spends blocked on device compute at reap
               (`block_until_ready`). In the synchronous loop this is
               ~the whole device step; under the overlapped step
               pipeline it shrinks toward zero — the overlap win,
               made visible per step.
  readback   — jax.device_get of the (ready) decode chunk: the actual
               device→host token transfer.
  sample     — host-side token emission (stop checks, slot release)
  routes     — a routed family's host work on the expert sets a step
               read back (`step.routes`, under a reap and under each
               admission call): counting the experts' load and cutting
               the rows of the requests that asked for them. The part
               under an admission call is inside `prefill` as well.
  kv_transfer — paged-KV handoff export/import (disaggregated serving;
               recorded outside the step timeline)

(`host_sync` — the old single bucket covering device wait + transfer —
split into dispatch/readback/overlap_idle when the overlapped step
pipeline landed.)

The engine records plain floats under its own lock — it never touches a
metrics registry from the hot path (same discipline as `Engine._timing`).
The serve loop drains pending observations into the per-phase histogram
(`kubeai_engine_step_phase_seconds`), and a bounded ring of recent step
records backs `POST /v1/profile` on the engine server.

`StepProfiler.span` is the one way a host interval is recorded. A span
named `step.<phase>` adds its duration to that phase of the step that is
open; every span also opens the annotation the profiler was built with
(the engine hands it `jax.profiler.TraceAnnotation`, so this module needs
no JAX), which puts the interval with its attributes on the clock of a
device trace and is inert while no profiler session is open. Spans that
are no phase (`serve.step`, `step.reap`, `step.admit`, `admit.host`,
`admit.wait`, `serve.fanout`, `serve.sync`, `http.emit`, `kv.export`,
`kv.import`) exist only in such a trace; docs/concepts/observability.md has the table.
"""

from __future__ import annotations

import threading
import time
from collections import deque

# Canonical phase vocabulary (metric label values; docs list them).
PHASES = (
    "schedule", "prefill", "decode", "dispatch", "overlap_idle",
    "readback", "sample", "routes", "kv_transfer",
)
_PHASE_OF_SPAN = {"step." + p: p for p in PHASES}


class Span:
    """One host interval: `with profiler.span(name, **attrs) as sp`.
    `sp.seconds` holds the duration once the block has ended, also when
    it ended in an exception."""

    __slots__ = ("_prof", "_phase", "_ann", "_t0", "seconds")

    def __init__(self, prof: "StepProfiler", name: str, attrs: dict):
        self._prof = prof
        self._phase = _PHASE_OF_SPAN.get(name)
        self._ann = prof._annotate(name, **attrs) if prof._annotate else None
        self.seconds = 0.0

    def note(self, **attrs) -> None:
        """Attributes known only inside the block (an admission's batch
        is built after its span opens)."""
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        phases = self._prof._open_step
        if self._phase is not None and phases is not None:
            phases[self._phase] = phases.get(self._phase, 0.0) + self.seconds


class StepProfiler:
    """Bounded ring of per-step phase timelines + a drainable list of
    (phase, seconds) observations for histogram export. Thread-safe; all
    methods are cheap enough for the engine lock's critical section."""

    def __init__(self, maxlen: int = 256, wall=time.time, annotate=None):
        self._cond = threading.Condition()
        self._ring: deque[dict] = deque(maxlen=maxlen)
        self._pending: list[tuple[str, float]] = []
        self._wall = wall
        # `annotate(name, **attrs)` -> a context manager with
        # `set_metadata(**attrs)`: jax.profiler.TraceAnnotation, or a fake.
        self._annotate = annotate
        # The phases of the step that is open, between begin_step and
        # end_step. Only the thread inside Engine.step (under the
        # engine lock) opens phase spans, so it needs no lock of its own.
        self._open_step: dict[str, float] | None = None
        self.steps_completed = 0

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def begin_step(self) -> dict[str, float]:
        """Open a step: until `end_step`, `step.<phase>` spans add to the
        returned dict. Outside a step they are trace-only."""
        self._open_step = {}
        return self._open_step

    def end_step(self) -> None:
        self._open_step = None

    def observe(self, phase: str, seconds: float) -> None:
        """One standalone phase observation (e.g. a KV handoff transfer
        that happens outside the step loop)."""
        with self._cond:
            self._pending.append((phase, float(seconds)))

    def observe_step(
        self,
        phases: dict[str, float],
        tokens: int = 0,
        batch: int = 0,
        duration_s: float = 0.0,
    ) -> None:
        """Close one step's record into the ring and queue its phases for
        histogram export. Wakes /v1/profile waiters."""
        with self._cond:
            self.steps_completed += 1
            self._ring.append(
                {
                    "step": self.steps_completed,
                    "ts": self._wall(),
                    "tokens": int(tokens),
                    "batch": int(batch),
                    "duration_s": round(float(duration_s), 9),
                    "phases_s": {
                        k: round(float(v), 9) for k, v in phases.items()
                    },
                }
            )
            self._pending.extend(
                (k, float(v)) for k, v in phases.items()
            )
            self._cond.notify_all()

    def drain(self) -> list[tuple[str, float]]:
        """Hand pending (phase, seconds) observations to the caller (the
        serve loop's histogram sync); clears the queue."""
        with self._cond:
            out, self._pending = self._pending, []
            return out

    def recent(self, n: int | None = None) -> list[dict]:
        with self._cond:
            records = list(self._ring)
        return records if n is None else records[-n:]

    def wait_for_steps(self, n: int, timeout_s: float) -> int:
        """Block until `n` NEW steps complete (or timeout); returns how
        many actually did. Backs /v1/profile's fresh-capture mode."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        with self._cond:
            start = self.steps_completed
            while self.steps_completed - start < n:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(timeout=min(remaining, 0.25))
            return self.steps_completed - start


def phase_totals(records: list[dict]) -> dict[str, float]:
    """Sum each phase across step records — the profile response's
    roll-up (which phase dominates the window)."""
    totals: dict[str, float] = {}
    for rec in records:
        for k, v in (rec.get("phases_s") or {}).items():
            totals[k] = totals.get(k, 0.0) + float(v)
    return {k: round(v, 9) for k, v in totals.items()}
