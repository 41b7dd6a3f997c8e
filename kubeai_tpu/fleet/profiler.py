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
               made visible per step. Its end is what the device queue's
               book (`DeviceQueueBook`, below) takes from it: when the
               chunk waited for is the newest program dispatched, the
               device has nothing queued from that instant on.
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

`DeviceQueueBook` is the engine thread's book of the device's queue: the
seconds the chip stood empty-handed before each dispatch, by what emptied
the queue and what ended the gap, kept with no profiler session open.
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
        starved_s: float = 0.0,
        dispatches: list[str] | tuple = (),
    ) -> None:
        """Close one step's record into the ring and queue its phases for
        histogram export. Wakes /v1/profile waiters. `starved_s` and
        `dispatches` are the step's page of the device queue's book
        (`DeviceQueueBook.end_step`)."""
        with self._cond:
            self.steps_completed += 1
            self._ring.append(
                {
                    "step": self.steps_completed,
                    "ts": self._wall(),
                    "tokens": int(tokens),
                    "batch": int(batch),
                    "duration_s": round(float(duration_s), 9),
                    "starved_s": round(float(starved_s), 9),
                    "dispatches": list(dispatches),
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


# Label values of the book's two series (docs list them).
QUEUE_AFTER = (
    "reap_admission", "reap_seq_cap", "reap_spec", "reap_external",
    "reap_sync", "admit",
)
QUEUE_BEFORE = ("prefill", "decode")
QUEUE_STATES = ("empty", "drained", "busy")


class DeviceQueueBook:
    """The device's queue as the thread that feeds it can know it.

    The engine tells the book four things, each where it happens:
    `dispatching(before)` the moment before it launches a program
    (`before` = prefill | decode), `dispatched(out)` with an output of the
    newest program launched (the tail; None for device work the book
    cannot watch), `waited(out, after)` when it returns from a blocking
    wait on `out`, and `idle()` when the serve loop found no work.

    The queue is OBSERVED EMPTY since t when the host came back from a
    wait on the tail itself: nothing was dispatched behind it, so the
    device has nothing to do until the next dispatch. That dispatch
    records `now - t` under (`after`, `before`): a lower bound of the
    device's gap (the wake-up after the device finished and the launch,
    an admission's staging and uploads and the jit call itself, are
    left out), exact in what it counts. A dispatch
    that did not observe the queue empty asks the tail `is_ready()`:
    ready = `drained`, the device ran dry behind the host's back for an
    unknown time of at most the time since the host last woke from a
    device wait; not ready = `busy`, the dispatch hid behind device work.
    Time in which the engine had no work is dropped: after `idle()` the
    next step counts from its own start.

    Plain values, mutated under the engine lock only (`idle` and `now`
    touch nothing shared); the starved seconds wait in a list with a lock
    of its own until `drain`, like `StepProfiler`'s phases."""

    def __init__(self, clock=time.perf_counter):
        self.now = clock
        self._tail = None
        self._empty: tuple[float, str] | None = None  # (since, after)
        self._woke: float | None = None  # end of the last device wait
        self._idle = False
        # Cumulative, by (before, queue): EngineMetrics folds the deltas in.
        self.dispatches = {
            (b, q): 0 for b in QUEUE_BEFORE for q in QUEUE_STATES
        }
        self._lock = threading.Lock()
        self._starved: list[tuple[str, str, float]] = []  # after, before, s
        self._step_starved = 0.0
        self._step_dispatches: list[str] = []

    def idle(self) -> None:
        """The serve loop found no work: what follows is no starvation."""
        self._idle = True

    def begin_step(self, started: float) -> None:
        """Open a step's page. `started` is `now()` as the step's
        `serve.step` span opened: after an idle spell the queue counts as
        empty, and the host as awake, from there."""
        if self._idle:
            self._idle = False
            if self._empty is not None:
                self._empty = (started, self._empty[1])
            self._woke = started
        self._step_starved = 0.0
        self._step_dispatches = []

    def end_step(self) -> tuple[float, list[str]]:
        """The step's starved seconds and its dispatches, `<before>:<queue>`
        in order."""
        return self._step_starved, self._step_dispatches

    def waited(self, out, after: str) -> None:
        """The host is back from a blocking wait on `out`."""
        self._woke = self.now()
        if out is not None and out is self._tail:
            self._empty = (self._woke, after)

    def dispatching(self, before: str) -> dict:
        """Record the queue's state as a program is about to be launched;
        returns it as span attributes (`queue`, and `starved_ms` or
        `drained_bound_ms` where known)."""
        now = self.now()
        if self._empty is not None:
            since, after = self._empty
            self._empty = None
            seconds = now - since
            with self._lock:
                self._starved.append((after, before, seconds))
            self._step_starved += seconds
            note = {"queue": "empty", "starved_ms": seconds * 1e3}
        elif self._tail is None or self._tail.is_ready():
            note = {"queue": "drained"}
            if self._woke is not None:
                note["drained_bound_ms"] = (now - self._woke) * 1e3
        else:
            note = {"queue": "busy"}
        self.dispatches[before, note["queue"]] += 1
        self._step_dispatches.append(f"{before}:{note['queue']}")
        return note

    def dispatched(self, out) -> None:
        """`out` is an output of the newest program launched (None: device
        work was launched that the book cannot watch)."""
        self._tail = out
        self._empty = None

    def drain(self) -> list[tuple[str, str, float]]:
        """Hand the pending (after, before, seconds) observations to the
        caller (EngineMetrics' histogram); clears the list."""
        with self._lock:
            out, self._starved = self._starved, []
            return out


def phase_totals(records: list[dict]) -> dict[str, float]:
    """Sum each phase across step records — the profile response's
    roll-up (which phase dominates the window)."""
    totals: dict[str, float] = {}
    for rec in records:
        for k, v in (rec.get("phases_s") or {}).items():
            totals[k] = totals.get(k, 0.0) + float(v)
    return {k: round(v, 9) for k, v in totals.items()}
