"""Deterministic cold-start simulation — fake clock, no sockets, no
device work.

Exercises the serverless-grade cold-start loop end to end with the REAL
components (`ColdStartTracker`, `ColdStartManager` + `SnapshotStore`
over a file:// bucket, `DemandForecaster`, `CapacityPlanner`,
`ActuationGovernor`) on a `FakeClock`:

  * BOOT PHASE MODEL — a full-load boot (HF conversion + XLA compile)
    vs a snapshot-restore boot, phase-timed through `ColdStartTracker`
    exactly as `engine/server.py` times them.
  * WARM vs COLD WORLD — one realtime model behind a demand ramp. Both
    worlds run the real planner over a scripted fleet snapshot ring;
    the WARM world wires the forecaster (restore-path boots), the COLD
    world scales reactively (full-load boots). Replicas ordered by the
    plan become Ready one boot-time later; capacity deficits register
    as realtime queue-pressure breaches.
  * SPOT TRIGGER — a rising SpotPreemption bucket orders replacement
    prewarms before the trend fit could notice.
  * MISMATCH — a published snapshot whose manifest is tampered to carry
    a different fingerprint: `fetch` must raise, the manager must fall
    back to the full load, and the mismatched tree must never serve.
  * GOVERNOR — a fenced (invalid-lease) governor must zero every
    prewarm grant; stale telemetry coverage must deny too.
  * PRICING — under a tight chip budget, demand chips flow to the
    expensive-to-boot model first, so preemption lands on the model
    whose replicas restore in seconds.

Invariants (asserted in tier-1 by tests/unit/test_coldstart_sim.py):

  (a) a snapshot-restore boot is >= 5x faster than the full-load boot
      in the phase model;
  (b) the prewarmed replica is Ready BEFORE the forecast spike lands
      (the tick where the cold world first breaches), and the warm
      world sees ZERO realtime queue-pressure breaches while the cold
      world breaches from the spike to the end of the run;
  (c) a fingerprint-mismatched snapshot is NEVER served — boot falls
      back to the full-load path (absent snapshots likewise);
  (d) prewarm actuations respect the governor: a fenced lease or stale
      telemetry zeroes the grant and lands in
      kubeai_prewarm_denied_total.

Run directly for a human-readable report:

    python benchmarks/coldstart_sim.py
"""

from __future__ import annotations

import glob as globmod
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeai_tpu.config.system import GovernorConfig
from kubeai_tpu.crd.model import ColdStart, Model, ModelSpec, Scheduling
from kubeai_tpu.engine.coldstart import ColdStartManager, ColdStartTracker
from kubeai_tpu.fleet import CapacityPlanner, DemandForecaster
from kubeai_tpu.metrics.registry import Metrics
from kubeai_tpu.objstore import SnapshotMismatch, SnapshotStore
from kubeai_tpu.operator import governor as governor_mod
from kubeai_tpu.operator import k8sutils
from kubeai_tpu.testing.faults import FakeClock

# ---- boot phase model --------------------------------------------------------
#
# Durations picked to match the feature's premise (and the restore
# budget the renderer grants): a full load pays weight conversion plus
# XLA compilation; a restore pays a streamed fetch plus a cache-warm
# compile. The 5x invariant is asserted against whatever these sum to,
# so retuning the model retunes the assertion input, not the check.

FULL_PHASES = (("load", 310.0), ("compile", 170.0), ("warmup", 20.0))
RESTORE_PHASES = (
    ("fetch", 12.0), ("restore", 7.0), ("compile", 6.0), ("warmup", 10.0),
)
BOOT_FULL_S = sum(d for _, d in FULL_PHASES)        # 500s
BOOT_RESTORE_S = sum(d for _, d in RESTORE_PHASES)  # 35s

# ---- world constants ---------------------------------------------------------

TICK_S = 10.0
TICKS = 40
TARGET_REQUESTS = 10
MAX_REPLICAS = 8
CHIPS_PER_REPLICA = 4
PLATEAU = 50.0
QUEUE_WAIT_BOUND_S = 3.0  # the realtime queue-pressure SLO


def demand_at(tick: int) -> float:
    """Flat base load, then a linear ramp to a plateau — the 'spike is
    building' trajectory the trend trigger exists for."""
    if tick <= 3:
        return 8.0
    return min(PLATEAU, 8.0 + 2.0 * (tick - 3))


def _boot(phases, *, restored: bool):
    """One engine boot through the real tracker on a fake clock."""
    clock = FakeClock(50.0)
    tr = ColdStartTracker(clock)
    for name, dur in phases:
        with tr.phase(name):
            clock.advance(dur)
    tr.restored = restored
    tr.event("restored" if restored else "published")
    total = tr.finish()
    return total, tr.snapshot()


# ---- scripted fleet ----------------------------------------------------------


class ScriptedFleet:
    """Stands in for FleetStateAggregator: a snapshot ring the world
    appends to. `history()` / `snapshot()` are the only reads the
    forecaster and planner make; `model_coverage` answers the
    governor."""

    def __init__(self, clock, coverage=(1.0, True)):
        self._ring: list[dict] = []
        self._clock = clock
        self._coverage = coverage

    def push(self, models: dict) -> None:
        self._ring.append({"ts": self._clock(), "models": models})
        del self._ring[:-32]

    def snapshot(self):
        return self._ring[-1] if self._ring else None

    def history(self, n=None):
        return self._ring[-n:] if n else list(self._ring)

    def model_coverage(self, model):
        return self._coverage


class _Models:
    def __init__(self, *models):
        self._models = list(models)

    def list_all_models(self):
        return list(self._models)


class _FencedLease:
    """A leadership lease that fails its fence check: writes (including
    prewarm pod orders) must be refused."""

    is_leader = True

    def fence_valid(self) -> bool:
        return False


def _rt_model() -> Model:
    m = Model(
        name="rt",
        spec=ModelSpec(
            url="hf://org/rt",
            engine="KubeAITPU",
            features=["TextGeneration"],
            min_replicas=2,
            max_replicas=MAX_REPLICAS,
            target_requests=TARGET_REQUESTS,
            scheduling=Scheduling(default_priority="realtime"),
            cold_start=ColdStart(
                enabled=True, snapshot_url="gs://snaps/rt"
            ),
        ),
    )
    m.validate()
    return m


# ---- warm / cold worlds ------------------------------------------------------


class ColdStartWorld:
    """One realtime model under the demand ramp, scaled by the real
    planner. `prewarm=True` wires the forecaster and boots replicas
    through the restore path; `prewarm=False` is the reactive baseline
    paying the full load on every boot. `fence=True` additionally wires
    a governor whose lease fails its fence check."""

    def __init__(self, *, prewarm: bool, fence: bool = False):
        self.clock = FakeClock(1000.0)
        self.metrics = Metrics()
        self.fleet = ScriptedFleet(self.clock)
        self.prewarm = prewarm
        self.boot_s = BOOT_RESTORE_S if prewarm else BOOT_FULL_S
        self.model = _rt_model()
        governor = None
        if fence:
            governor = governor_mod.ActuationGovernor(
                leader=_FencedLease(), metrics=self.metrics,
                clock=self.clock,
            )
        self.planner = CapacityPlanner(
            self.fleet,
            _Models(self.model),
            budget_override={
                "v5e-2x2": {
                    "chips": 64, "slice_chips": CHIPS_PER_REPLICA,
                },
            },
            metrics=self.metrics,
            interval_s=TICK_S,
            clock=self.clock,
            governor=governor,
            forecaster=DemandForecaster(self.fleet) if prewarm else None,
        )
        now = self.clock()
        self.ready: list[float] = [now] * self.model.spec.min_replicas
        self.booting: list[float] = []
        self.breach_ticks: list[int] = []
        self.trajectory: list[dict] = []
        self.first_prewarm: dict | None = None
        self.last_record: dict | None = None

    def step(self, tick: int) -> None:
        self.clock.advance(TICK_S)
        now = self.clock()
        # Boots ordered one boot-time ago become Ready.
        self.ready += [t for t in self.booting if t <= now]
        self.booting = [t for t in self.booting if t > now]
        demand = demand_at(tick)
        capacity = float(TARGET_REQUESTS * len(self.ready))
        unserved = max(0.0, demand - capacity)
        if unserved > 0:
            # Requests the ready pool cannot absorb queue past the
            # realtime wait bound within the tick: an SLO breach.
            self.breach_ticks.append(tick)
        served = demand - unserved
        n = len(self.ready)
        endpoints = {
            f"10.0.0.{i + 1}:8000": {
                "active_requests": served / n,
                "stale": False,
                "cold_start": {
                    "total_s": self.boot_s,
                    "restored": self.prewarm,
                },
            }
            for i in range(n)
        }
        total_pods = n + len(self.booting)
        self.fleet.push({
            "rt": {
                "queue": {
                    "depth": unserved,
                    "oldest_wait_s": (
                        QUEUE_WAIT_BOUND_S + 2.0 if unserved else 0.0
                    ),
                    "per_class": {},
                },
                "endpoints": endpoints,
                "pods": {
                    "total": total_pods,
                    "chips": CHIPS_PER_REPLICA * total_pods,
                    "by_disruption": {},
                },
                "replicas": {"unified": n},
            },
        })
        plan = self.planner.tick(force=True)
        rec = plan["models"]["rt"]
        self.last_record = rec
        orders = rec["allocated_replicas"] - total_pods
        for _ in range(max(0, orders)):
            self.booting.append(now + self.boot_s)
        if orders > 0 and rec["prewarm_replicas"] and not self.first_prewarm:
            self.first_prewarm = {
                "tick": tick,
                "ordered_at": now,
                "ready_at": now + self.boot_s,
                "trigger": rec["prewarm_trigger"],
            }
        self.trajectory.append({
            "tick": tick,
            "demand": demand,
            "capacity": capacity,
            "unserved": unserved,
            "allocated": rec["allocated_replicas"],
            "prewarm": rec["prewarm_replicas"],
        })

    def facts(self) -> dict:
        m = self.metrics
        return {
            "breach_ticks": list(self.breach_ticks),
            "trajectory": self.trajectory,
            "first_prewarm": self.first_prewarm,
            "last_record": self.last_record,
            "prewarm_orders_trend": m.prewarm_orders.get(
                model="rt", trigger="trend"
            ),
            "prewarm_denied": m.prewarm_denied.get(model="rt"),
            "fenced_writes": m.leader_fenced_writes.get(),
            "denied_lease": m.governor_denied.get(
                action=governor_mod.ACTION_PREWARM, model="rt",
                reason=governor_mod.DENY_LEASE,
            ),
        }


# ---- spot-trigger scenario ---------------------------------------------------


def run_spot_scenario() -> dict:
    """Two spot preemptions land in the pod inventory: the planner must
    prewarm one replacement per disrupted pod with the 'spot' trigger
    (the early warning outranks the trend fit)."""
    clock = FakeClock(2000.0)
    metrics = Metrics()
    fleet = ScriptedFleet(clock)
    model = _rt_model()
    planner = CapacityPlanner(
        fleet,
        _Models(model),
        budget_override={
            "v5e-2x2": {"chips": 64, "slice_chips": CHIPS_PER_REPLICA},
        },
        metrics=metrics,
        interval_s=TICK_S,
        clock=clock,
        forecaster=DemandForecaster(fleet),
    )
    for disruptions in (0, 0, 2):
        clock.advance(TICK_S)
        fleet.push({
            "rt": {
                "queue": {
                    "depth": 0.0, "oldest_wait_s": 0.0, "per_class": {},
                },
                "endpoints": {
                    "10.0.0.1:8000": {
                        "active_requests": 5.0,
                        "stale": False,
                        "cold_start": {
                            "total_s": BOOT_RESTORE_S, "restored": True,
                        },
                    },
                },
                "pods": {
                    "total": 2,
                    "chips": 2 * CHIPS_PER_REPLICA,
                    "by_disruption": {
                        k8sutils.REASON_SPOT_PREEMPTION: disruptions,
                    },
                },
                "replicas": {"unified": 2},
            },
        })
    plan = planner.tick(force=True)
    rec = plan["models"]["rt"]
    return {
        "record": rec,
        "orders_metric": metrics.prewarm_orders.get(
            model="rt", trigger="spot"
        ),
    }


# ---- mismatch scenario -------------------------------------------------------


class _Mesh:
    shape = {"data": 1, "model": 1}


def run_mismatch_scenario() -> dict:
    """Publish a snapshot over a file:// bucket, then tamper the
    manifest to claim a different fingerprint (a stale overwrite or
    corruption). The store must raise, and the manager must serve the
    full-load params — the mismatched tree never serves. A clean
    config-drift lookup (different fingerprint, nothing published
    there) reads as absent and full-loads too."""
    root = tempfile.mkdtemp(prefix="coldstart-sim-")
    try:
        url = "file://" + os.path.join(root, "snaps")
        store = SnapshotStore(url)
        ecfg = {"num_slots": 8, "max_seq_len": 512}
        mgr = ColdStartManager(
            url, "rt", ecfg, _Mesh(),
            work_dir=os.path.join(root, "boot1"),
            clock=FakeClock(0.0), store=store,
        )
        stage = os.path.join(root, "stage")
        os.makedirs(os.path.join(stage, "params"))
        with open(os.path.join(stage, "params", "arr0.bin"), "wb") as f:
            f.write(b"\x00" * 64)
        store.publish("rt", mgr.fingerprint, stage)
        [man_path] = globmod.glob(
            os.path.join(root, "snaps", "**", "MANIFEST.json"),
            recursive=True,
        )
        with open(man_path) as f:
            man = json.load(f)
        man["fingerprint"] = "deadbeefdeadbeef"
        with open(man_path, "w") as f:
            json.dump(man, f)

        fetch_raised = False
        try:
            store.fetch("rt", mgr.fingerprint, os.path.join(root, "dl"))
        except SnapshotMismatch:
            fetch_raised = True

        sentinel = object()
        served = mgr.acquire_params(lambda: sentinel)

        drift = ColdStartManager(
            url, "rt", {**ecfg, "num_slots": 16}, _Mesh(),
            work_dir=os.path.join(root, "boot2"),
            clock=FakeClock(0.0), store=store,
        )
        served_drift = drift.acquire_params(lambda: sentinel)
        return {
            "fetch_raised": fetch_raised,
            "mismatch_events": list(mgr.tracker.events),
            "mismatch_full_load": served is sentinel,
            "mismatch_restored": mgr.tracker.restored,
            "drift_events": list(drift.tracker.events),
            "drift_full_load": served_drift is sentinel,
            "fingerprints_differ": mgr.fingerprint != drift.fingerprint,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---- governor stale-telemetry denial -----------------------------------------


def run_stale_governor_scenario() -> dict:
    """An armed governor over a stale snapshot ring: a blind forecaster
    must not spend chips."""
    metrics = Metrics()
    gov = governor_mod.ActuationGovernor(
        cfg=GovernorConfig(min_telemetry_coverage=0.5),
        fleet=ScriptedFleet(FakeClock(0.0), coverage=(1.0, False)),
        metrics=metrics,
        clock=FakeClock(0.0),
    )
    return {
        "allowed": gov.allow_prewarm("rt"),
        "denied": metrics.prewarm_denied.get(model="rt"),
        "denied_stale": metrics.governor_denied.get(
            action=governor_mod.ACTION_PREWARM, model="rt",
            reason=governor_mod.DENY_STALE,
        ),
    }


# ---- cold-start-priced preemption --------------------------------------------


def run_pricing_scenario() -> dict:
    """Two standard-class models, identical demand, a budget one chip
    short: the demand fill must favor the expensive-to-boot model so
    the shortfall (throttle -> preemption) lands on the model whose
    replicas restore from a snapshot in seconds."""
    clock = FakeClock(3000.0)
    metrics = Metrics()
    fleet = ScriptedFleet(clock)

    def mk(name: str) -> Model:
        m = Model(
            name=name,
            spec=ModelSpec(
                url=f"hf://org/{name}",
                engine="KubeAITPU",
                features=["TextGeneration"],
                min_replicas=0,
                max_replicas=8,
                target_requests=TARGET_REQUESTS,
                cold_start=ColdStart(
                    enabled=True, snapshot_url="gs://snaps/x"
                ),
            ),
        )
        m.validate()
        return m

    def entry(cost: float, restored: bool) -> dict:
        return {
            "queue": {"depth": 0.0, "oldest_wait_s": 0.0, "per_class": {}},
            "endpoints": {
                "10.0.0.1:8000": {
                    "active_requests": 20.0,
                    "stale": False,
                    "cold_start": {"total_s": cost, "restored": restored},
                },
            },
            "pods": {"total": 2, "chips": 2, "by_disruption": {}},
            "replicas": {"unified": 2},
        }

    planner = CapacityPlanner(
        fleet,
        _Models(mk("cheap"), mk("exp")),
        budget_override={"v5e-1x1": {"chips": 3, "slice_chips": 1}},
        metrics=metrics,
        interval_s=TICK_S,
        clock=clock,
        forecaster=DemandForecaster(fleet),
    )
    clock.advance(1.0)
    fleet.push({
        "cheap": entry(28.0, True),   # restores in seconds
        "exp": entry(420.0, False),   # recompiles for minutes
    })
    plan = planner.tick(force=True)
    return {
        "cheap": plan["models"]["cheap"],
        "exp": plan["models"]["exp"],
    }


# ---- sim driver --------------------------------------------------------------


def run_sim(ticks: int = TICKS) -> dict:
    full_s, full_snap = _boot(FULL_PHASES, restored=False)
    restore_s, restore_snap = _boot(RESTORE_PHASES, restored=True)
    warm = ColdStartWorld(prewarm=True)
    cold = ColdStartWorld(prewarm=False)
    fenced = ColdStartWorld(prewarm=True, fence=True)
    for t in range(ticks):
        warm.step(t)
        cold.step(t)
        fenced.step(t)
    return {
        "ticks": ticks,
        "boot": {
            "full_s": full_s,
            "restore_s": restore_s,
            "full_snapshot": full_snap,
            "restore_snapshot": restore_snap,
        },
        "warm": warm.facts(),
        "cold": cold.facts(),
        "fenced": fenced.facts(),
        "spot": run_spot_scenario(),
        "mismatch": run_mismatch_scenario(),
        "stale_governor": run_stale_governor_scenario(),
        "pricing": run_pricing_scenario(),
    }


# ---- invariant checks (imported by tests/unit/test_coldstart_sim.py) ---------


def check_restore_speedup(result: dict) -> None:
    """(a) Restore-path boot >= 5x faster than full load in the phase
    model, with both boots fully phase-timed by the real tracker."""
    boot = result["boot"]
    assert boot["restore_s"] > 0
    assert boot["full_s"] >= 5.0 * boot["restore_s"], (
        boot["full_s"], boot["restore_s"],
    )
    assert boot["full_snapshot"]["phases"] == dict(FULL_PHASES)
    assert boot["restore_snapshot"]["phases"] == dict(RESTORE_PHASES)
    assert boot["restore_snapshot"]["restored"] is True
    assert boot["full_snapshot"]["restored"] is False
    assert boot["full_snapshot"]["total_s"] == sum(
        d for _, d in FULL_PHASES
    )


def check_prewarm_beats_spike(result: dict) -> None:
    """(b) The warm world's first prewarmed replica is Ready before the
    spike lands (the cold world's first breach tick), the warm world
    never breaches the realtime queue-pressure bound, and the cold
    world breaches from the spike to the end of the run."""
    warm, cold = result["warm"], result["cold"]
    assert warm["breach_ticks"] == [], warm["breach_ticks"]
    assert cold["breach_ticks"], "reactive baseline must breach"
    spike_tick = cold["breach_ticks"][0]
    # The full-load boot never matures inside the run: once demand
    # outruns capacity the baseline stays underwater.
    assert cold["breach_ticks"] == list(
        range(spike_tick, result["ticks"])
    )
    fp = warm["first_prewarm"]
    assert fp is not None, "the trend trigger must order a prewarm"
    assert fp["trigger"] == "trend"
    assert fp["tick"] < spike_tick
    spike_clock = 1000.0 + TICK_S * (spike_tick + 1)
    assert fp["ready_at"] < spike_clock, (fp, spike_clock)
    assert warm["prewarm_orders_trend"] >= 1
    rec = warm["last_record"]
    assert rec["forecast"]["model"] == "rt"
    assert rec["coldstart_cost_s"] == BOOT_RESTORE_S
    # Clamps hold throughout: maxReplicas and the chip budget.
    for point in warm["trajectory"]:
        assert point["allocated"] <= MAX_REPLICAS
        assert point["allocated"] * CHIPS_PER_REPLICA <= 64


def check_spot_trigger(result: dict) -> None:
    """Rising spot preemptions order one replacement per disrupted pod,
    labelled with the 'spot' trigger."""
    rec = result["spot"]["record"]
    assert rec["prewarm_trigger"] == "spot"
    assert rec["prewarm_replicas"] == 2
    assert rec["forecast"]["trigger"] == "spot"
    assert result["spot"]["orders_metric"] == 2


def check_mismatch_never_serves(result: dict) -> None:
    """(c) A fingerprint-mismatched snapshot raises at the store and
    full-loads at the manager; a clean different-fingerprint lookup
    reads as absent and full-loads too. Neither path ever serves a
    restored tree."""
    mm = result["mismatch"]
    assert mm["fetch_raised"] is True
    assert "mismatch" in mm["mismatch_events"]
    assert "restored" not in mm["mismatch_events"]
    assert mm["mismatch_full_load"] is True
    assert mm["mismatch_restored"] is False
    assert mm["fingerprints_differ"] is True
    assert "absent" in mm["drift_events"]
    assert mm["drift_full_load"] is True


def check_governor_gates_prewarm(result: dict) -> None:
    """(d) A fenced lease zeroes every prewarm grant and lands the
    denial in the prewarm-denied and governor counters; stale telemetry
    coverage denies too; the permissive default (warm world) grants."""
    fenced = result["fenced"]
    for point in fenced["trajectory"]:
        assert point["prewarm"] == 0, point
    assert fenced["prewarm_orders_trend"] == 0
    assert fenced["prewarm_denied"] >= 1
    assert fenced["fenced_writes"] >= 1
    assert fenced["denied_lease"] >= 1
    stale = result["stale_governor"]
    assert stale["allowed"] is False
    assert stale["denied"] >= 1 and stale["denied_stale"] >= 1
    assert result["warm"]["prewarm_orders_trend"] >= 1


def check_priced_preemption(result: dict) -> None:
    """Cold-start pricing: the expensive-to-boot model keeps its
    replicas; the cheap-restore model absorbs the shortfall."""
    cheap, exp = result["pricing"]["cheap"], result["pricing"]["exp"]
    assert exp["coldstart_cost_s"] > cheap["coldstart_cost_s"]
    assert exp["allocated_replicas"] == 2
    assert exp["preempted_replicas"] == 0
    assert cheap["allocated_replicas"] == 1
    assert cheap["preempted_replicas"] == 1
    assert cheap["forecast"]["restore_available"] is True
    assert exp["forecast"]["restore_available"] is False


ALL_CHECKS = (
    check_restore_speedup,
    check_prewarm_beats_spike,
    check_spot_trigger,
    check_mismatch_never_serves,
    check_governor_gates_prewarm,
    check_priced_preemption,
)


def main() -> int:
    result = run_sim()
    for chk in ALL_CHECKS:
        chk(result)
        print(f"PASS {chk.__name__}")
    warm, cold = result["warm"], result["cold"]
    print(json.dumps(
        {
            "boot": {
                "full_s": result["boot"]["full_s"],
                "restore_s": result["boot"]["restore_s"],
                "speedup": round(
                    result["boot"]["full_s"]
                    / result["boot"]["restore_s"], 2
                ),
            },
            "warm_breach_ticks": warm["breach_ticks"],
            "cold_breach_ticks": cold["breach_ticks"],
            "first_prewarm": warm["first_prewarm"],
            "prewarm_orders": warm["prewarm_orders_trend"],
            "fenced_denials": result["fenced"]["prewarm_denied"],
            "pricing": {
                name: {
                    "allocated": rec["allocated_replicas"],
                    "preempted": rec["preempted_replicas"],
                    "coldstart_cost_s": rec["coldstart_cost_s"],
                }
                for name, rec in result["pricing"].items()
            },
            "ticks": result["ticks"],
        },
        indent=2, sort_keys=True,
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
