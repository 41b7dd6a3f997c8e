"""Overlapped step pipeline suite: token identity (overlap on vs off,
greedy AND seeded, across paged/slot/chunked-prefill), the conservative
barriers (cancel, drain, handoff export/import) over REAL engines and
real HTTP, the reap that launches no device program, the watchdog/overlap
interaction, topology refusals (pp, lockstep), and the new
dispatch/readback/overlap_idle phase vocabulary."""

import dataclasses as _dc
import threading
import time
import types

import json

import jax
import numpy as np
import pytest

from testutil import http_get, http_post
from tests.unit.test_host_timeline import Recorder

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.engine import EngineDraining, StepOverlapUnsupported
from kubeai_tpu.engine.multihost import LockstepEngine
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.fleet.profiler import PHASES, phase_totals
from kubeai_tpu.metrics.registry import parse_prometheus_text
from kubeai_tpu.models import llama
from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh

pytestmark = pytest.mark.stepperf

TOK = ByteTokenizer()

PROMPTS = [
    [1, 2, 3, 4, 5, 6, 7],
    [9, 8, 7],
    [11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21],
    [30, 31],
]

GREEDY = SamplingParams(temperature=0.0, max_tokens=24)
SEEDED = SamplingParams(temperature=0.9, top_k=8, seed=13, max_tokens=24)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(vocab_size=TOK.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(tiny, overlap, **overrides):
    cfg, params = tiny
    ecfg = EngineConfig(
        **{
            "num_slots": 4, "max_seq_len": 128, "page_size": 16,
            "decode_chunk": 4, "step_overlap": overlap, **overrides,
        }
    )
    return Engine("llama", cfg, params, cfg=ecfg,
                  eos_token_ids=TOK.eos_token_ids)


@pytest.fixture(scope="module")
def pair(tiny):
    """One overlapped + one synchronous paged engine, shared by the
    module's paged-mode tests (engines are reusable once idle)."""
    return _engine(tiny, "on"), _engine(tiny, "off")


def _step_until_inflight(eng, max_steps=64):
    """Step until a decode chunk is held in flight; returns the events
    emitted on the way (prefill first-tokens, earlier chunks)."""
    evs = []
    for _ in range(max_steps):
        evs.extend(eng.step())
        if eng._inflight is not None:
            return evs
    raise AssertionError("engine never held a chunk in flight")


def _collect(out, evs):
    for ev in evs:
        if ev.rid in out:
            out[ev.rid].append(ev.token)


# ---- token identity: overlap on vs off ---------------------------------------


@pytest.mark.parametrize("mode_kw", [
    {},
    {"prefill_chunk": 8},
], ids=["paged", "paged-chunked"])
def test_token_identity_overlap_vs_sync(tiny, pair, mode_kw):
    """Greedy AND seeded streams are byte-identical with the pipeline on."""
    if not mode_kw:
        on, off = pair
    else:
        on = _engine(tiny, "on", **mode_kw)
        off = _engine(tiny, "off", **mode_kw)
    assert on._overlap and not off._overlap
    for sp in (GREEDY, SEEDED):
        assert on.generate(PROMPTS, sp) == off.generate(PROMPTS, sp)


def test_preemption_under_overlap_token_identical(tiny):
    """Page-pool oversubscription preempts mid-decode; the recompute
    resume must replay identically whether or not a chunk was in flight
    when the victim was evicted."""
    kw = dict(num_pages=1 + 9)  # pages for ~2 sequences -> forced eviction
    on, off = _engine(tiny, "on", **kw), _engine(tiny, "off", **kw)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, TOK.vocab_size, 20).tolist() for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_tokens=32)
    assert on.generate(prompts, sp) == off.generate(prompts, sp)
    sp2 = SamplingParams(temperature=0.8, top_k=16, seed=9, max_tokens=24)
    assert on.generate(prompts, sp2) == off.generate(prompts, sp2)


# ---- barriers ----------------------------------------------------------------


def test_cancel_barriers_inflight_and_survivor_is_identical(tiny, pair):
    on, off = pair
    ref = off.generate(PROMPTS[:2], GREEDY)

    r0 = on.add_request(PROMPTS[0], GREEDY)
    r1 = on.add_request(PROMPTS[1], GREEDY)
    out = {r0: [], r1: []}
    _collect(out, _step_until_inflight(on))
    assert on.cancel(r0) is True
    # The barrier reaped BEFORE the slot/pages were released.
    assert on._inflight is None
    while on.has_work():
        _collect(out, on.step())
    assert out[r1] == ref[1]
    # The cancelled stream is a clean prefix of the sync stream.
    assert out[r0] == ref[0][:len(out[r0])]


def test_begin_drain_barriers_inflight_and_finishes_cleanly(tiny):
    # Own engines: draining is terminal for an Engine instance.
    on, off = _engine(tiny, "on"), _engine(tiny, "off")
    ref = off.generate(PROMPTS, GREEDY)

    rids = [on.add_request(p, GREEDY) for p in PROMPTS]
    out = {r: [] for r in rids}
    _collect(out, _step_until_inflight(on))
    on.begin_drain()
    assert on._inflight is None  # exported state must be fully settled
    while on.has_work():
        _collect(out, on.step())
    assert [out[r] for r in rids] == ref
    with pytest.raises(EngineDraining):
        on.add_request(PROMPTS[0], GREEDY)


def test_handoff_export_import_under_overlap(tiny, pair):
    """export/import_handoff mid-flight barrier first; the decoding
    request AND the imported one stream identically to the sync engine
    running the same op sequence."""
    on, off = pair
    sp = SamplingParams(temperature=0.0, max_tokens=16)

    def run(eng):
        rid_a = eng.add_request(PROMPTS[0], sp)
        out = {rid_a: []}
        if eng._overlap:
            _collect(out, _step_until_inflight(eng))
        else:
            _collect(out, eng.step())
        h = eng.export_handoff(PROMPTS[2], sp)
        assert eng._inflight is None
        rid_b, first = eng.import_handoff(h)
        out[rid_b] = [first.token]
        while eng.has_work():
            _collect(out, eng.step())
        return [out[rid_a], out[rid_b]]

    assert run(on) == run(off)


# ---- a reap launches no device program -------------------------------------------


class _ReapWatch:
    """Wraps an engine's decode program and `_process_chunk`, and patches
    `jax.block_until_ready` / `jax.device_get`: every array a reap waits
    for or reads is kept as (barrier, the dispatch that made it or None,
    dispatches made so far, shape). None means the array is no decode
    program's output: something derived from one, so a program launched by
    the reap."""

    def __init__(self, eng, monkeypatch):
        self.born = {}  # id(output) -> (dispatch number, the array, kept alive)
        self.dispatched = 0
        self.barrier = None
        self.touched = []
        decode, process = eng._decode_jit, eng._process_chunk

        def dispatching(*args):
            out = decode(*args)
            self.dispatched += 1
            for leaf in jax.tree_util.tree_leaves(out[0]):
                self.born[id(leaf)] = (self.dispatched, leaf)
            return out

        def reaping(inflight, barrier="none"):
            self.barrier = barrier
            try:
                return process(inflight, barrier)
            finally:
                self.barrier = None

        def watching(real):
            def call(x, *a, **kw):
                if self.barrier is not None:
                    for leaf in jax.tree_util.tree_leaves(x):
                        made = self.born.get(id(leaf), (None,))[0]
                        self.touched.append(
                            (self.barrier, made, self.dispatched, leaf.shape))
                return real(x, *a, **kw)
            return call

        eng._decode_jit, eng._process_chunk = dispatching, reaping
        monkeypatch.setattr(
            jax, "block_until_ready", watching(jax.block_until_ready))
        monkeypatch.setattr(jax, "device_get", watching(jax.device_get))


@pytest.mark.parametrize("overlap", ["off", "on"])
def test_reap_launches_no_device_program(tiny, overlap, monkeypatch):
    """What a reap blocks on and reads IS the decode program's own output,
    the padded [chunk, num_slots] buffer; and while the live rows fall from
    4 to 1 nothing is compiled after the first chunk (the on-device slice
    this guards against was one program per count of live rows)."""
    eng = _engine(tiny, overlap)
    watch = _ReapWatch(eng, monkeypatch)
    rids = [
        eng.add_request(PROMPTS[0], SamplingParams(
            temperature=0.0, max_tokens=n))
        for n in (6, 10, 14, 22)
    ]
    out = {r: [] for r in rids}
    live, late = set(), []  # late: compiled once the first chunk was reaped

    def on_compile(event, _secs, **_kw):
        if (watch.touched
                and event == "/jax/core/compile/backend_compile_duration"):
            late.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        while eng.has_work():
            _collect(out, eng.step())
            live.add(len(eng._active))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    lengths = [len(out[r]) for r in rids]  # the last may meet EOS first
    assert lengths[:3] == [6, 10, 14] and lengths[3] > 14
    assert {4, 3, 2, 1} <= live
    assert late == [], f"{len(late)} programs compiled after the first reap"
    shape = (eng.cfg.decode_chunk, eng.cfg.num_slots)
    assert watch.touched and all(
        made is not None and got == shape
        for _, made, _, got in watch.touched), watch.touched
    # One wait and one read a reap, on the same array.
    assert watch.touched[0::2] == watch.touched[1::2]


def test_reap_of_chunk_n_precedes_the_end_of_chunk_n_plus_1(tiny, monkeypatch):
    """On a step with nothing waiting chunk N+1 is dispatched first and
    chunk N reaped behind it. The reap may touch only chunk N's outputs:
    an array derived after N+1's dispatch is ready only when N+1 is, and
    chunk N's tokens would reach the host a chunk late."""
    eng = _engine(tiny, "on")
    watch = _ReapWatch(eng, monkeypatch)
    [stream] = eng.generate([PROMPTS[0]], SamplingParams(
        temperature=0.0, max_tokens=16))
    assert len(stream) == 16
    assert all(made is not None for _, made, _, _ in watch.touched), (
        "the reap read an array derived on the device")
    behind = [t for t in watch.touched if t[0] == "none" and t[2] > t[1]]
    assert len(behind) >= 2 * 3, watch.touched
    for barrier, made, dispatched, _ in watch.touched:
        if barrier == "none":
            # N+1 is in flight (or, at the tail, nothing is) and the reap
            # waits for N alone.
            assert dispatched - made in (0, 1), watch.touched


def test_step_reaps_counter_by_barrier(tiny, monkeypatch):
    """`Engine.step_reaps` counts what the `step.reap` spans say, and
    `/metrics` carries it as kubeai_engine_step_reaps_total{barrier}."""
    eng = _engine(tiny, "on")
    rec = Recorder()
    monkeypatch.setattr(eng.profiler, "_annotate", rec)
    sp = SamplingParams(temperature=0.0, max_tokens=24)
    r0 = eng.add_request(PROMPTS[0], sp)
    _step_until_inflight(eng)
    eng.step()  # nothing waiting: reaped behind the next dispatch
    assert eng.step_reaps["none"] == 1
    eng.add_request(PROMPTS[1], sp)
    eng.step()  # a prompt waits: reaped first
    assert eng.step_reaps["admission"] == 1
    assert eng._inflight is not None
    assert eng.cancel(r0)  # outside a step
    assert eng.step_reaps["external"] == 1
    while eng.has_work():
        eng.step()
    by_span = dict.fromkeys(eng.step_reaps, 0)
    for reap in rec.named("step.reap"):
        by_span[reap["attrs"]["barrier"]] += 1
    assert eng.step_reaps == by_span
    assert by_span["none"] > 1 and by_span["seq_cap"] == by_span["spec"] == 0

    srv = EngineServer(eng, TOK, "m", host="127.0.0.1", port=0)
    srv.start()
    try:
        addr = f"127.0.0.1:{srv.port}"
        st, _ = http_post(addr, "/v1/completions", {
            "model": "m", "prompt": "count me", "max_tokens": 12,
            "temperature": 0}, timeout=60)
        assert st == 200
        _, body = http_get(addr, "/metrics")
    finally:
        srv.stop()
    parsed = parse_prometheus_text(body.decode())
    on_wire = {
        dict(labels)["barrier"]: value for (name, labels), value
        in parsed.items() if name == "kubeai_engine_step_reaps_total"
    }
    assert on_wire == {k: float(v) for k, v in eng.step_reaps.items()}
    assert on_wire["none"] > by_span["none"]


# ---- phase vocabulary --------------------------------------------------------


def test_phase_vocabulary_host_sync_split(tiny, pair):
    assert "host_sync" not in PHASES
    for name in ("dispatch", "overlap_idle", "readback"):
        assert name in PHASES
    on, off = pair
    for eng in (on, off):
        eng.generate(PROMPTS[:2], SamplingParams(temperature=0.0,
                                                 max_tokens=12))
        totals = phase_totals(eng.profiler.recent())
        assert "host_sync" not in totals
        assert "readback" in totals and "overlap_idle" in totals
        assert "dispatch" in totals  # paged block-table upload
        assert set(totals) <= set(PHASES)


# ---- watchdog / overlap interaction ------------------------------------------


class _InFlightEngine:
    """step() never returns events — but a decode chunk is reported in
    flight. With a FRESH dispatch stamp this is a healthy overlapped
    engine; with an aged-out stamp the reap itself is wedged."""

    def __init__(self, age_s=0.0):
        self.cfg = types.SimpleNamespace(max_seq_len=128)
        self._block = threading.Event()
        self._age_s = age_s
        self._anchor = time.monotonic()

    def loaded_adapters(self):
        return []

    def has_work(self):
        return True

    def step(self):
        self._block.wait(timeout=30)
        return []

    def cancel(self, rid):
        return False

    def inflight_info(self):
        if self._age_s:
            return {"dispatched_at": self._anchor - self._age_s}
        return {"dispatched_at": time.monotonic()}

    num_active = 1
    num_pending = 0


def test_watchdog_trusts_fresh_inflight_dispatch():
    """A dispatched-but-unreaped chunk counts as progress: the watchdog
    must NOT flag a healthy overlapped engine."""
    fired = threading.Event()
    srv = EngineServer(
        _InFlightEngine(), TOK, "m1", host="127.0.0.1", port=0,
        watchdog_timeout=0.2, watchdog_action=fired.set,
    )
    srv.start()
    try:
        time.sleep(1.0)  # 5x the watchdog timeout
        assert srv.healthy()
        assert not srv.wedged
        assert not fired.is_set()
        assert srv.metrics.watchdog_stalls.get() == 0
    finally:
        srv._stop.set()
        srv.engine._block.set()
        srv.stop()


def test_watchdog_fires_when_inflight_reap_is_overdue():
    """An in-flight chunk older than the watchdog budget means the reap
    is wedged — the restart must still fire."""
    fired = threading.Event()
    srv = EngineServer(
        _InFlightEngine(age_s=10.0), TOK, "m1", host="127.0.0.1", port=0,
        watchdog_timeout=0.2, watchdog_action=fired.set,
    )
    srv.start()
    try:
        assert fired.wait(timeout=5.0), "watchdog never fired"
        assert not srv.healthy()
        assert srv.wedged
        assert srv.metrics.watchdog_stalls.get() == 1
    finally:
        srv._stop.set()
        srv.engine._block.set()
        srv.stop()


# ---- topology refusals + knob parsing ----------------------------------------


def test_pp_refuses_explicit_overlap(devices8):
    cfg = _dc.replace(llama.LlamaConfig.tiny(), num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(pp=2), devices=devices8[:2])
    ecfg = EngineConfig(num_slots=4, max_seq_len=96, decode_chunk=4,
                        step_overlap="on")
    with pytest.raises(StepOverlapUnsupported, match="pipeline parallelism"):
        Engine("llama", cfg, params, mesh=mesh, cfg=ecfg)
    # 'auto' silently degrades to the synchronous loop.
    eng = Engine("llama", cfg, params, mesh=mesh,
                 cfg=_dc.replace(ecfg, step_overlap="auto"))
    assert eng._overlap is False


def test_lockstep_refuses_explicit_overlap(tiny):
    with pytest.raises(StepOverlapUnsupported, match="lockstep"):
        LockstepEngine(_engine(tiny, "on"))
    ls = LockstepEngine(_engine(tiny, "auto"))
    assert ls.inner._overlap is False


def test_step_overlap_knob_parsing(tiny):
    with pytest.raises(ValueError, match="step_overlap"):
        _engine(tiny, "sometimes")
    assert _engine(tiny, "auto")._overlap is True  # default-on
    assert _engine(tiny, True)._overlap is True    # bool accepted
    assert _engine(tiny, False)._overlap is False


# ---- over real HTTP ----------------------------------------------------------


def test_http_completions_identical_overlap_vs_sync(tiny, pair):
    on, off = pair
    req = {"model": "m", "prompt": "overlap me", "max_tokens": 12,
           "temperature": 0}
    seeded = {"model": "m", "prompt": "overlap me", "max_tokens": 12,
              "temperature": 0.9, "seed": 13}
    texts = {}
    for name, eng in (("on", on), ("off", off)):
        srv = EngineServer(eng, TOK, "m", host="127.0.0.1", port=0)
        srv.start()
        try:
            addr = f"127.0.0.1:{srv.port}"
            st, body = http_post(addr, "/v1/completions", req, timeout=60)
            assert st == 200
            st2, body2 = http_post(addr, "/v1/completions", seeded,
                                   timeout=60)
            assert st2 == 200
            texts[name] = (
                json.loads(body)["choices"][0]["text"],
                json.loads(body2)["choices"][0]["text"],
            )
        finally:
            srv.stop()
    assert texts["on"] == texts["off"]
