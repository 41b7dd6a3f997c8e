"""Deterministic overlapped-step-pipeline simulation — fake device
clock, no JAX, no sockets.

Models the engine's step loop (`kubeai_tpu/engine/engine.py
Engine.step`) against a virtual device whose compute time is a modelled
constant per decode chunk, and replays the SAME barrier rules the real
engine enforces:

  * SYNC loop   — dispatch chunk N, wait for the device, read tokens
                  back, run host work (sample / detokenize / SSE), then
                  dispatch chunk N+1. The device idles through the
                  whole host window.
  * OVERLAP loop — dispatch chunk N+1 BEFORE reaping chunk N: the
                  host's readback + sample window runs concurrently
                  with chunk N+1's device compute. Barriers mirror the
                  engine's: a pending admission or a drain forces a
                  reap before state mutates.

Tokens come from a deterministic function of (seed, rid, position) —
exactly the property the real device has (same state in, same token
out) — so any divergence between the sync and overlap streams can only
come from the LOOP's ordering/barrier logic, which is what the
invariants pin:

  (a) SPEEDUP — with modelled host time >= 30% of the synchronous step,
      the overlapped loop decodes >= 1.3x the synchronous throughput;
  (b) TOKEN IDENTITY — byte-identical per-request token streams,
      overlap on vs off, for greedy AND seeded sampling, across the
      paged / chunked-prefill admission models;
  (c) BARRIERS — mid-run arrivals (admission barrier) and a mid-run
      drain (drain barrier) both force a reap and still produce
      identical streams;
  (d) PHASE ACCOUNTING — the overlap win is visible in the phase
      vocabulary: overlap_idle (host blocked on device compute)
      shrinks under overlap while sync pays ~the full device time.

Run directly for a human-readable report:

    python benchmarks/step_overlap_sim.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---- modelled step timings ---------------------------------------------------
#
# One decode chunk (DECODE_CHUNK fused model steps) costs DEVICE_CHUNK_S
# on the virtual device. The host pays DISPATCH_S to stage inputs,
# READBACK_S to transfer the chunk's tokens, and HOST_CHUNK_S of
# sample/detokenize/SSE work per chunk. Host share of the synchronous
# step = (DISPATCH_S + READBACK_S + HOST_CHUNK_S) / sync step — the
# >= 30% premise the speedup invariant requires (asserted below, so
# retuning the model retunes the assertion input, not the check).

DECODE_CHUNK = 8
DEVICE_CHUNK_S = 0.70
DISPATCH_S = 0.02
READBACK_S = 0.05
HOST_CHUNK_S = 0.33
PREFILL_S_PER_CHUNK = 0.08  # one prefill call (whole bucket or one chunk)
PREFILL_CHUNK = 32  # chunked-prefill mode: prompt tokens per prefill call

HOST_S = DISPATCH_S + READBACK_S + HOST_CHUNK_S
SYNC_STEP_S = HOST_S + DEVICE_CHUNK_S
HOST_SHARE = HOST_S / SYNC_STEP_S

VOCAB = 50257


def _token(seed: int, rid: int, position: int) -> int:
    """The virtual device: same (sampler seed, request, position) in,
    same token out — mode- and loop-independent by construction."""
    return (seed * 1000003 + rid * 7919 + (position + 1) * 104729) % VOCAB


class _Request:
    def __init__(self, rid: int, arrival_step: int, prompt_len: int,
                 seed: int, max_tokens: int):
        self.rid = rid
        self.arrival_step = arrival_step  # admitted once this many steps ran
        self.prompt_len = prompt_len
        self.seed = seed
        self.max_tokens = max_tokens
        self.position = prompt_len
        self.out: list[int] = []
        self.done = False


class _Device:
    """Virtual accelerator: a busy-until horizon on the sim clock.
    dispatch() queues work behind whatever is already in flight (the
    data dependency the real engine gets from donated buffers)."""

    def __init__(self):
        self.busy_until = 0.0

    def dispatch(self, now: float, work_s: float) -> float:
        start = max(now, self.busy_until)
        self.busy_until = start + work_s
        return self.busy_until  # ready_at


class _SimEngine:
    """The step loop under test. `mode` picks the admission model
    (paged = batched whole-prompt,
    chunked = per-PREFILL_CHUNK prefill calls); `overlap` picks the
    loop shape. Barrier rules mirror Engine.step/_barrier_locked."""

    def __init__(self, requests, mode: str = "paged",
                 overlap: bool = False, num_slots: int = 4,
                 drain_after_step: int | None = None):
        assert mode in MODES
        self.mode = mode
        self.overlap = overlap
        self.num_slots = num_slots
        self.pending = sorted(requests, key=lambda r: r.rid)
        self.active: dict[int, _Request] = {}
        self.free_slots = list(range(num_slots))
        self.now = 0.0
        self.device = _Device()
        self.inflight = None  # (ready_at, [(slot, req, position0)], len)
        self.steps = 0
        self.draining = False
        self.drain_after_step = drain_after_step
        self.barrier_reaps = 0
        self.phases = {
            "prefill": 0.0, "schedule": 0.0, "dispatch": 0.0,
            "overlap_idle": 0.0, "readback": 0.0, "sample": 0.0,
        }
        self.streams: dict[int, list[int]] = {r.rid: [] for r in requests}

    # -- pieces ---------------------------------------------------------------

    def _arrivals_due(self):
        return [
            r for r in self.pending
            if r.arrival_step <= self.steps and not self.draining
        ]

    def _reap(self, inflight, barrier: bool = False) -> None:
        ready_at, riders, chunk_len = inflight
        if barrier:
            self.barrier_reaps += 1
        idle = max(0.0, ready_at - self.now)
        self.now += idle
        self.phases["overlap_idle"] += idle
        self.now += READBACK_S
        self.phases["readback"] += READBACK_S
        self.now += HOST_CHUNK_S
        self.phases["sample"] += HOST_CHUNK_S
        for k in range(chunk_len):
            for slot, req, pos0 in riders:
                if req.done:
                    continue  # surplus chunk tokens discarded
                tok = _token(req.seed, req.rid, pos0 + k)
                req.out.append(tok)
                req.position += 1
                self.streams[req.rid].append(tok)
                if len(req.out) >= req.max_tokens:
                    req.done = True
                    self.free_slots.append(slot)
                    self.active.pop(slot, None)

    def _barrier(self) -> None:
        if self.inflight is not None:
            inflight, self.inflight = self.inflight, None
            self._reap(inflight, barrier=True)

    def _admit(self) -> None:
        due = self._arrivals_due()
        batch = []
        while due and self.free_slots:
            req = due.pop(0)
            self.pending.remove(req)
            slot = self.free_slots.pop()
            self.active[slot] = req
            batch.append(req)
        if not batch:
            return
        if self.mode == "paged":
            # Batched admission: same-bucket prompts share one call.
            calls = 1
        else:  # chunked prefill: one call per PREFILL_CHUNK tokens
            calls = sum(
                -(-r.prompt_len // PREFILL_CHUNK) for r in batch
            )
        cost = calls * PREFILL_S_PER_CHUNK
        self.now += cost
        self.device.busy_until = max(self.device.busy_until, self.now)
        self.phases["prefill"] += cost
        for req in batch:  # prefill samples the first token
            tok = _token(req.seed, req.rid, req.position)
            req.out.append(tok)
            req.position += 1
            self.streams[req.rid].append(tok)

    # -- the loop -------------------------------------------------------------

    def step(self) -> None:
        if (
            self.drain_after_step is not None
            and self.steps == self.drain_after_step
            and not self.draining
        ):
            # Drain barrier: reap before the drain decision mutates
            # admission state (mirrors Engine.begin_drain).
            self._barrier()
            self.draining = True
        if self.inflight is not None and self._arrivals_due() and self.free_slots:
            # Admission barrier: the slot/page grant must observe the
            # in-flight chunk's stop-driven frees.
            self._barrier()
        self._admit()
        prev, self.inflight = self.inflight, None
        current = None
        if self.active:
            self.now += DISPATCH_S
            self.phases["dispatch"] += DISPATCH_S
            riders = [
                (slot, req, req.position + (prev[2] if prev else 0))
                for slot, req in sorted(self.active.items())
            ]
            ready_at = self.device.dispatch(self.now, DEVICE_CHUNK_S)
            current = (ready_at, riders, DECODE_CHUNK)
            if self.overlap:
                self.inflight = current
                current = None
        self.steps += 1
        if prev is not None:
            self._reap(prev)
        if current is not None:
            self._reap(current)

    def has_work(self) -> bool:
        return bool(self.pending or self.active or self.inflight)

    def run(self) -> dict:
        guard = 0
        while self.has_work():
            # A drained sim stops admitting; pending arrivals are shed.
            if self.draining:
                self.pending = []
            self.step()
            guard += 1
            assert guard < 10_000, "sim did not converge"
        tokens = sum(len(s) for s in self.streams.values())
        return {
            "tokens": tokens,
            "wall_s": round(self.now, 9),
            "tokens_per_s": round(tokens / self.now, 9) if self.now else 0.0,
            "steps": self.steps,
            "barrier_reaps": self.barrier_reaps,
            "phases_s": {k: round(v, 9) for k, v in self.phases.items()},
            "streams": {rid: list(s) for rid, s in self.streams.items()},
        }


# ---- workloads ---------------------------------------------------------------


def _workload(seeded: bool):
    """Six requests, two arriving mid-run (they exercise the admission
    barrier under overlap). Greedy = seed 0 (argmax stands in); seeded
    = per-request sampler seeds."""
    specs = [
        # (rid, arrival_step, prompt_len, max_tokens)
        (0, 0, 64, 128),
        (1, 0, 48, 120),
        (2, 0, 96, 128),
        (3, 0, 32, 112),
        (4, 5, 64, 96),  # mid-run arrival: admission barrier
        (5, 8, 80, 96),  # second wave
    ]
    return [
        _Request(
            rid, arrival, plen,
            seed=(0 if not seeded else 0x9E3779B1 ^ (rid * 2654435761)),
            max_tokens=mt,
        )
        for rid, arrival, plen, mt in specs
    ]


MODES = ("paged", "chunked")


def run_sim() -> dict:
    """Run every (mode x sampling x loop) cell plus the drain scenario;
    purely virtual clock, so the result is bit-deterministic."""
    cells: dict = {}
    for mode in MODES:
        for sampling in ("greedy", "seeded"):
            seeded = sampling == "seeded"
            sync = _SimEngine(
                _workload(seeded), mode=mode, overlap=False
            ).run()
            over = _SimEngine(
                _workload(seeded), mode=mode, overlap=True
            ).run()
            cells[f"{mode}/{sampling}"] = {"sync": sync, "overlap": over}
    # Drain-while-in-flight: barrier reap mid-run, streams of the
    # already-admitted requests still identical between loops.
    drain_sync = _SimEngine(
        _workload(False), mode="paged", overlap=False, drain_after_step=4
    ).run()
    drain_over = _SimEngine(
        _workload(False), mode="paged", overlap=True, drain_after_step=4
    ).run()
    base = cells["paged/greedy"]
    return {
        "host_share": round(HOST_SHARE, 9),
        "speedup": round(
            base["overlap"]["tokens_per_s"] / base["sync"]["tokens_per_s"], 9
        ),
        "cells": cells,
        "drain": {"sync": drain_sync, "overlap": drain_over},
    }


# ---- invariants (tier-1: tests/unit/test_step_overlap_sim.py) ----------------


def check_host_share_premise(result: dict) -> None:
    # The >= 1.3x claim is conditional on host time >= 30% of the sync
    # step; the timing model must actually satisfy the premise.
    assert result["host_share"] >= 0.30, result["host_share"]


def check_overlap_speedup(result: dict) -> None:
    assert result["speedup"] >= 1.3, (
        f"overlap speedup {result['speedup']:.3f} < 1.3x "
        f"(host share {result['host_share']:.2f})"
    )
    # Every cell, not just the headline one, must come out ahead.
    for name, cell in result["cells"].items():
        ratio = cell["overlap"]["tokens_per_s"] / cell["sync"]["tokens_per_s"]
        assert ratio >= 1.2, f"{name}: {ratio:.3f}"


def check_token_identity(result: dict) -> None:
    # Byte-identical streams, overlap on vs off, greedy AND seeded,
    # across all three admission models.
    for name, cell in result["cells"].items():
        assert cell["sync"]["streams"] == cell["overlap"]["streams"], name
        for rid, s in cell["sync"]["streams"].items():
            assert len(s) > 0, (name, rid)


def check_barriers_fire(result: dict) -> None:
    # Mid-run arrivals force admission-barrier reaps under overlap
    # (and none in the sync loop, which never holds a chunk).
    for name, cell in result["cells"].items():
        assert cell["overlap"]["barrier_reaps"] >= 1, name
        assert cell["sync"]["barrier_reaps"] == 0, name
    # The drain scenario reaps at the drain barrier and still matches.
    d = result["drain"]
    assert d["sync"]["streams"] == d["overlap"]["streams"]


def check_phase_accounting(result: dict) -> None:
    # The win is visible in the phase split: sync pays ~the whole
    # device time as overlap_idle; overlap hides most of it.
    cell = result["cells"]["paged/greedy"]
    sync_idle = cell["sync"]["phases_s"]["overlap_idle"]
    over_idle = cell["overlap"]["phases_s"]["overlap_idle"]
    assert over_idle < 0.75 * sync_idle, (sync_idle, over_idle)
    # readback is per-chunk constant work — both loops pay it.
    assert cell["overlap"]["phases_s"]["readback"] > 0
    assert cell["sync"]["phases_s"]["readback"] > 0


ALL_CHECKS = (
    check_host_share_premise,
    check_overlap_speedup,
    check_token_identity,
    check_barriers_fire,
    check_phase_accounting,
)


def main() -> int:
    result = run_sim()
    for chk in ALL_CHECKS:
        chk(result)
        print(f"  PASS {chk.__name__}")
    print(
        f"\nhost share of sync step: {result['host_share']:.1%}"
        f"\noverlap speedup (paged/greedy): {result['speedup']:.2f}x"
    )
    for name, cell in result["cells"].items():
        print(
            f"  {name:16s} sync {cell['sync']['tokens_per_s']:8.2f} tok/s"
            f"  overlap {cell['overlap']['tokens_per_s']:8.2f} tok/s"
            f"  ({cell['overlap']['tokens_per_s'] / cell['sync']['tokens_per_s']:.2f}x,"
            f" {cell['overlap']['barrier_reaps']} barrier reaps)"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
