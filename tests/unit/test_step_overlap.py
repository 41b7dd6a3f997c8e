"""Overlapped step pipeline suite: token identity (overlap on vs off,
greedy AND seeded, across paged/slot/chunked-prefill), the conservative
barriers (cancel, drain, handoff export/import) over REAL engines and
real HTTP, the reap that launches no device program, the watchdog/overlap
interaction, which loop each topology runs (pp, lockstep: synchronous),
and the new dispatch/readback/overlap_idle phase vocabulary."""

import dataclasses as _dc
import threading
import time
import types

import json

import jax
import numpy as np
import pytest

from testutil import http_get, http_post, synchronous
from tests.unit.test_host_timeline import Recorder

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.engine import EngineDraining
from kubeai_tpu.engine.multihost import LockstepEngine
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.fleet.profiler import PHASES, DeviceQueueBook, phase_totals
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


def _loop(eng, overlap):
    """`eng` on the loop a case names: "on" is what every engine off a pp
    mesh runs, "off" the synchronous loop as lockstep builds it."""
    assert overlap in ("on", "off")
    return eng if overlap == "on" else synchronous(eng)


def _engine(tiny, overlap, **overrides):
    cfg, params = tiny
    ecfg = EngineConfig(
        **{
            "num_slots": 4, "max_seq_len": 128, "page_size": 16,
            "decode_chunk": 4, **overrides,
        }
    )
    return _loop(Engine("llama", cfg, params, cfg=ecfg,
                        eos_token_ids=TOK.eos_token_ids), overlap)


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
            # Its tokens (a routed family's expert sets with them) and the
            # scalar that says what its sampler ran (PR 44).
            for leaf in jax.tree_util.tree_leaves((out[0], out[-1])):
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
        made is not None and got in (shape, ())
        for _, made, _, got in watch.touched), watch.touched
    # One wait and one read a reap, on the same array; the read brings the
    # same chunk's scalar with it.
    tokens = [t for t in watch.touched if t[3] == shape]
    scalars = [t[:3] for t in watch.touched if t[3] == ()]
    assert tokens[0::2] == tokens[1::2]
    assert scalars == [t[:3] for t in tokens[1::2]]


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
    eng.step()  # a prompt waits and a slot is free: it rides, no barrier
    assert eng.step_reaps["admission"] == 0 and eng.step_reaps["none"] == 2
    for prompt in (PROMPTS[2], PROMPTS[3], PROMPTS[1]):
        eng.add_request(prompt, sp)
    eng.step()  # two slots are free: two of the three ride
    assert eng.step_reaps["admission"] == 0 and len(eng._sched) == 1
    eng.step()  # a prompt waits and NO slot is free: reaped first
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


# ---- an admission rides the device's queue ------------------------------------


class _Running:
    @staticmethod
    def is_ready():
        return False


class _SlowDevice(DeviceQueueBook):
    """The book of a device slower than any host: what was dispatched last
    is still running when the next dispatch asks. (The tiny CPU programs
    end in microseconds, and `is_ready()` would say what the sandbox's
    scheduler did.) A wait on the tail still observes the queue empty."""

    def dispatching(self, before):
        tail = self._tail
        if tail is not None:
            self._tail = _Running()
        try:
            return super().dispatching(before)
        finally:
            self._tail = tail


class _CallLog:
    """The order in which an engine launches its device programs and takes
    its waits: `prefill` (the fused admission call), `staged` (a staged
    admission's last chunk), `decode` (the chunk, or a speculation window),
    `reap:<why>` (`_process_chunk`), `head` (an admission's first tokens
    read), and `pages[` .. `]pages` around the walk that grows the slots'
    pages."""

    def __init__(self, eng):
        self.calls = calls = []
        self.evicted = []  # tokens a victim had when it was preempted
        self.riders = []  # a dispatched chunk's rows, by their `max_tokens`

        def logged(name, label):
            real = getattr(eng, name, None)
            if real is None:
                return

            def call(*a, **kw):
                calls.append(label(*a, **kw) if callable(label) else label)
                return real(*a, **kw)

            setattr(eng, name, call)

        logged("_prefill_admit_jit", "prefill")
        logged("_stage_chunk_last_jit", "staged")
        def chunk(*a, **kw):
            self.riders.append(
                [r.params.max_tokens for r in eng._active.values()])
            return "decode"

        logged("_decode_jit", chunk)
        logged("_spec_jit", "decode")
        logged("_collect_head", "head")
        logged("_process_chunk",
               lambda inflight, barrier="none": f"reap:{barrier}")
        walk, preempt = eng._ensure_decode_pages, eng._preempt

        def walking(*a, **kw):
            calls.append("pages[")
            try:
                return walk(*a, **kw)
            finally:
                calls.append("]pages")

        def preempting(victim):
            self.evicted.append(len(victim.out_tokens))
            return preempt(victim)

        eng._ensure_decode_pages, eng._preempt = walking, preempting

    def take(self, *, walk=False):
        """The calls since the last take (without the page walk's marks
        unless asked for)."""
        out = [c for c in self.calls if walk or "pages" not in c]
        self.calls.clear()
        return out

    def device(self):
        return [c for c in self.calls if c in ("prefill", "staged", "decode")]


def _step(eng, out=None):
    evs = eng.step()
    if out is not None:
        _collect(out, evs)
    return evs


def _barrier_forced(eng):
    """The same engine with the admission barrier taken wherever the
    parent took it: no admission rides."""
    eng._admission_rides = lambda: False
    return eng


def test_an_admission_rides_behind_the_chunk_in_flight(tiny):
    """A chunk in flight, a prompt pending, a slot free: the prefill and
    the next chunk are dispatched behind the chunk in flight with no wait
    between, the chunk in flight is then reaped as an ordinary reap, and
    the first token is read last: no `barrier="admission"` reap, the
    prefill booked `queue="busy"`. The device sees what the barrier's and
    the synchronous loop's device sees, in its order."""
    engines = {"rides": _engine(tiny, "on"),
               "barrier": _barrier_forced(_engine(tiny, "on")),
               "off": _engine(tiny, "off")}
    logs, streams = {}, {}
    for name, eng in engines.items():
        eng.device_queue = _SlowDevice()
        logs[name] = log = _CallLog(eng)
        r0 = eng.add_request(PROMPTS[0], GREEDY)
        out = {r0: []}
        _step(eng, out)
        _step(eng, out)
        assert (eng._inflight is not None) == (name != "off")
        # An idle engine's first request is admitted as it always was.
        assert log.take() == {
            "off": ["prefill", "head", "decode", "reap:none",
                    "decode", "reap:none"],
        }.get(name, ["prefill", "head", "decode", "decode", "reap:none"])
        r1 = eng.add_request(PROMPTS[1], GREEDY)
        out[r1] = []
        evs = _step(eng, out)
        last = eng.profiler.recent()[-1]
        if name == "rides":
            assert log.take() == ["prefill", "decode", "reap:none", "head"]
            # The chunk's tokens, then the admitted request's first.
            assert [ev.rid for ev in evs] == [r0] * 4 + [r1]
            assert last["dispatches"] == ["prefill:busy", "decode:busy"]
            assert last["starved_s"] == 0.0
            assert eng.step_reaps["admission"] == 0
        elif name == "barrier":
            assert log.take() == ["reap:admission", "prefill", "head", "decode"]
            assert [ev.rid for ev in evs] == [r0] * 4 + [r1]
            assert last["dispatches"] == ["prefill:empty", "decode:empty"]
            assert eng.step_reaps["admission"] == 1
        else:
            assert log.take() == ["prefill", "head", "decode", "reap:none"]
            assert [ev.rid for ev in evs] == [r1] + [r0, r1] * 4
        while eng.has_work():
            _step(eng, out)
        streams[name] = [out[r0], out[r1]]
        assert eng._heads == []
    assert streams["rides"] == streams["barrier"] == streams["off"]
    on = engines["rides"]
    assert on.step_reaps["admission"] == 0 and on.step_reaps["none"] >= 6
    assert on.device_queue.dispatches["prefill", "busy"] == 1
    assert on.device_queue.dispatches["prefill", "empty"] == 0
    # The idle engine's admission is all that was ever observed empty.
    assert {(a, b) for a, b, _ in on.device_queue.drain()} == {
        ("admit", "decode")}
    # Only the run-ahead's surplus chunk at the end sets the loops apart.
    device = {n: log.device() for n, log in logs.items()}
    assert device["rides"] == device["barrier"]
    assert device["rides"][:len(device["off"])] == device["off"]
    assert len(device["rides"]) - len(device["off"]) in (0, 1)


def _family_engines(name, **kw):
    """Three engines of one tiny family (admissions ride, the barrier
    forced, the synchronous loop), and what a request asks of it besides
    tokens."""
    from kubeai_tpu.models import mixtral
    from tests.unit.test_engine_paged import _family_world

    if name == "block":
        family, cfg = "SDARMoeForCausalLM", mixtral.MixtralConfig.tiny_sdar()
        params = mixtral.init_params(cfg, jax.random.PRNGKey(3))
    else:
        family, cfg, params = _family_world(
            "gemma2" if name == "gemma" else name)
    ask = {"mixtral": {"routes": True}, "block": {"forwards": True}}.get(
        name, {})
    rides, barrier, off = (
        _loop(Engine(family, cfg, params, eos_token_ids=(), cfg=EngineConfig(**{
            "num_slots": 4, "max_seq_len": 128, "page_size": 16,
            "decode_chunk": 4, **kw})), overlap)
        for overlap in ("on", "on", "off")
    )
    return (rides, _barrier_forced(barrier), off), ask, cfg.vocab_size


def _event_key(ev):
    """An event as plain values: the token, how it ended, and what rode
    on it (expert sets, a block family's forwards)."""
    def plain(x):
        if isinstance(x, (tuple, list)):
            return tuple(plain(v) for v in x)
        return x.tolist() if hasattr(x, "tolist") else x

    return (ev.token, ev.finished, ev.finish_reason, plain(ev.routes),
            plain(ev.forwards))


def _closed_loop(eng, scripts, clients, ask):
    """`clients` streams over `scripts` [(prompt, params)]: a stream sends
    the next script only once its request's finish has reached it, as the
    benchmark's closed loop does, so a prompt arrives while a chunk is in
    flight. Per script, its events in order."""
    todo = list(enumerate(scripts))
    live, out = {}, {i: [] for i in range(len(scripts))}

    def send():
        if todo:
            i, (prompt, sp) = todo.pop(0)
            live[eng.add_request(prompt, sp, **ask)] = i

    for _ in range(clients):
        send()
    while eng.has_work():
        for ev in eng.step():
            out[live[ev.rid]].append(_event_key(ev))
            if ev.finished:
                send()
    return out


@pytest.mark.parametrize("clients", [3, 6], ids=["slot-free", "slots-full"])
@pytest.mark.parametrize("family", ["llama", "mixtral", "block", "gemma"])
def test_closed_loop_equals_the_barrier_and_the_synchronous_engine(
        family, clients):
    """Requests that end and are replaced while a chunk is in flight,
    greedy and seeded, some ended by a stop id and some by their first
    token (a stop id, `max_tokens=1`): every request's events (token,
    finish reason, the expert sets or forwards that rode on it) are those
    of the same engine with the barrier forced and of the synchronous
    engine, in their order. With three streams on four slots a slot is
    free whenever a prompt comes, and no admission that rides forces a
    reap; with six the queue is never empty and a prompt waits for a
    slot."""
    (on, barrier, off), ask, vocab = _family_engines(family)
    rng = np.random.default_rng(41)
    prompts = [rng.integers(1, vocab - 1, n).tolist()
               for n in (5, 11, 3, 20, 7, 2, 14, 9, 4, 17, 6, 12)]
    # A stop id that ends one request on its first token (and others where
    # they come to it): the first token the third prompt is served.
    [[stop]] = off.generate(
        [prompts[2]], SamplingParams(temperature=0.0, max_tokens=1))
    for eng in (on, barrier, off):
        eng.eos_token_ids = (int(stop),)
    scripts = []
    for i, prompt in enumerate(prompts):
        n = (1, 9, 24, 6, 13, 18)[i % 6]
        scripts.append((prompt, SamplingParams(temperature=0.0, max_tokens=n)
                        if i % 2 == 0 else SamplingParams(
                            temperature=0.9, top_k=8, seed=100 + i,
                            max_tokens=n)))
    log = _CallLog(on)
    got = _closed_loop(on, scripts, clients, ask)
    want = _closed_loop(off, scripts, clients, ask)
    assert got == want
    assert _closed_loop(barrier, scripts, clients, ask) == want
    reasons = {events[-1][2] for events in want.values()}
    assert reasons == {"stop", "length"}
    assert want[2] == [(int(stop), True, "stop") + want[2][0][3:]]
    assert len(want[0]) == 1 and want[0][0][1:3] == (True, "length")
    assert all(len(events) >= 1 and events[-1][1] for events in got.values())
    # Some admissions rode (a head read after the chunk behind its call
    # went out), and every call was read once.
    calls = log.calls
    assert calls.count("prefill") == calls.count("head") >= len(scripts) // 4
    rode = sum(
        next(x for x in calls[i + 1:] if x in ("head", "decode")) == "decode"
        for i, c in enumerate(calls) if c == "prefill")
    assert rode >= 2
    # No row known to end with its first token was ever put on a chunk.
    assert log.riders and all(
        family == "block" or n > 1 for chunk in log.riders for n in chunk)
    assert barrier.step_reaps["admission"] > on.step_reaps["admission"]
    if clients == 3:
        # Only the `max_tokens=1` requests kept the barrier.
        assert on.step_reaps["admission"] <= (family != "block") * 2
        assert on.step_reaps["none"] > len(scripts)
    else:
        assert on.step_reaps["admission"] >= 1
    for eng in (on, barrier):
        assert eng._heads == []
        assert not eng._active and len(eng._free_slots) == 4
        assert eng._alloc.free_pages == off._alloc.free_pages


def test_a_first_token_that_is_a_stop_id_frees_the_slot(tiny):
    """The request's row is already in the chunk that went out behind its
    prefill when the host reads the stop id that ends it: slot and pages
    are released there, the chunk's rows for it are dropped as a row that
    stops mid-chunk is, and the next prompt takes the slot behind that
    chunk and is served what the synchronous engine serves it."""
    on, off = _engine(tiny, "on"), _engine(tiny, "off")
    [[first]] = off.generate(
        [PROMPTS[1]], SamplingParams(temperature=0.0, max_tokens=1))
    [want0, want2] = off.generate([PROMPTS[0], PROMPTS[2]], GREEDY)
    log = _CallLog(on)
    r0 = on.add_request(PROMPTS[0], GREEDY)
    out = {r0: []}
    _collect(out, _step_until_inflight(on))
    log.take()
    # A request keeps the stop ids it was added under.
    eos, on.eos_token_ids = on.eos_token_ids, (int(first),)
    r1 = on.add_request(PROMPTS[1], GREEDY)
    on.eos_token_ids = eos
    slot, slot0 = on._free_slots[-1], on._requests[r0].slot
    evs = _step(on, out)
    assert log.take() == ["prefill", "decode", "reap:none", "head"]
    assert evs[-1] == (r1, int(first), True, "stop", None, None)
    # Released after the chunk that carries its row went out.
    assert r1 not in on._requests and slot not in on._active
    assert on._free_slots[-1] == slot and on._alloc.pages_for(slot) == []
    assert (on._bt_host[slot] == -1).all()
    assert sorted(s for s, _ in on._inflight[1]) == sorted([slot0, slot])
    r2 = on.add_request(PROMPTS[2], GREEDY)
    out[r2] = []
    evs = _step(on, out)
    assert on._active[slot].rid == r2  # the slot, behind the chunk in flight
    assert log.take() == ["prefill", "decode", "reap:none", "head"]
    assert {ev.rid for ev in evs} == {r0, r2}  # the chunk's r1 rows: dropped
    while on.has_work():
        assert r1 not in {ev.rid for ev in _step(on, out)}
    assert [out[r0], out[r2]] == [want0, want2]
    assert on.step_reaps["admission"] == 0


def _fallback(tiny, case):
    """An engine with a chunk in flight (where its loop keeps one), a slot
    free, and at the head of its queue the request of `case`: (engine, log,
    rid of the request, the synchronous engine's tokens for it)."""
    kw, draft, resume, sp = {}, None, None, GREEDY
    prompt = PROMPTS[2]
    if case in ("prefix", "chunked"):
        kw = {"prefill_chunk": 8, "prefix_cache": case == "prefix"}
        prompt = list(range(40, 61))  # 21 tokens: past one chunk, one page
    elif case in ("spec", "draft"):
        kw = {"speculate": 2, "spec_adaptive": False}
        draft = tiny if case == "draft" else None
    elif case == "one-token":
        sp = SamplingParams(temperature=0.0, max_tokens=1)
    cfg, params = tiny

    def build(overlap):
        return _loop(Engine("llama", cfg, params, draft=draft, cfg=EngineConfig(
            num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4,
            **kw), eos_token_ids=TOK.eos_token_ids), overlap)

    eng = build("off" if case == "off" else "on")
    ref = eng if case == "off" else build("off")
    if case == "prefix":
        # A first request leaves the prompt's one full page in the cache.
        for e in {eng, ref}:
            e.generate([prompt[:17]], SamplingParams(
                temperature=0.0, max_tokens=2))
    [want] = ref.generate([prompt], sp)
    if case == "resumed":
        resume, want = want[:5], want[5:]
    log = _CallLog(eng)
    eng.add_request(PROMPTS[0], GREEDY)
    eng.step()
    eng.step()
    log.take()
    rid = eng.add_request(prompt, sp, resume_tokens=resume)
    return eng, log, rid, want


@pytest.mark.parametrize(
    "case",
    ["resumed", "prefix", "chunked", "one-token", "spec", "draft", "off"])
def test_each_fallback_keeps_the_old_order(tiny, case):
    """Where the host must see the chunk in flight before it admits (a
    preempted or resumed request, whose re-prefill reads its `out_tokens`;
    the staged `prefix` and `chunked` calls), where the request is known
    to end with its first token (it is never put on a chunk), and where
    the loop keeps no chunk in flight (speculation, a draft model, the
    synchronous loop), the step goes as it went: the reap first, then the
    admission, its first token read BEFORE the chunk is dispatched."""
    eng, log, rid, want = _fallback(tiny, case)
    in_flight = case in ("resumed", "prefix", "chunked", "one-token")
    assert (eng._inflight is not None) == in_flight and eng._free_slots
    assert not eng._admission_rides()
    out = {rid: []}
    _step(eng, out)
    calls = log.take()
    if in_flight:
        call = "prefill" if case in ("resumed", "one-token") else "staged"
        assert calls == ["reap:admission", call, "head", "decode"]
        assert eng.step_reaps["admission"] == 1
        assert (eng.prefix_stats["hit_tokens"] > 0) == (case == "prefix")
        assert eng.profiler.recent()[-1]["dispatches"][0] in (
            "prefill:empty", "prefill:drained")
        if case == "one-token":
            assert rid not in eng._requests
            assert all(n > 1 for n in log.riders[-1])
    else:
        assert calls == ["prefill", "head", "decode", "reap:none"]
        assert eng.step_reaps["admission"] == 0
    while eng.has_work():
        _step(eng, out)
    assert out[rid] == want
    assert eng._heads == []


def test_what_does_not_ride_waits_at_the_head_of_the_queue(tiny):
    """Behind a prompt that rides, a request that may not (here one that
    its first token ends) stays at the head of the queue: the next step
    takes the barrier for it. Nothing is dropped or reordered."""
    on, off = _engine(tiny, "on"), _engine(tiny, "off")
    one = SamplingParams(temperature=0.0, max_tokens=1)
    want = [off.generate([PROMPTS[0]], GREEDY)[0],
            off.generate([PROMPTS[1]], GREEDY)[0],
            off.generate([PROMPTS[3]], one)[0],
            off.generate([PROMPTS[2]], GREEDY)[0]]
    log = _CallLog(on)
    rids = [on.add_request(PROMPTS[0], GREEDY)]
    out = {rids[0]: []}
    _collect(out, _step_until_inflight(on))
    log.take()
    rids += [on.add_request(PROMPTS[1], GREEDY), on.add_request(PROMPTS[3], one),
             on.add_request(PROMPTS[2], GREEDY)]
    out.update({r: [] for r in rids[1:]})
    _step(on, out)
    assert log.take() == ["prefill", "decode", "reap:none", "head"]
    assert [r.rid for r in (on._sched.peek(),)] == [rids[2]]
    assert len(on._sched) == 2 and on.step_reaps["admission"] == 0
    _step(on, out)
    # The barrier: the one-token request, then the prompt behind it (its
    # bucket's own call), each read before the chunk.
    taken = log.take()
    assert taken[0] == "reap:admission" and taken[-1] == "decode"
    assert taken.count("prefill") == taken.count("head") >= 1
    assert len(on._sched) == 0 and on.step_reaps["admission"] == 1
    while on.has_work():
        _step(on, out)
    assert [out[r] for r in rids] == want


def test_no_slot_free_keeps_the_barrier_as_it_was(tiny):
    """A pending prompt with no slot free is what the admission barrier is
    still for: the reap is how a slot is found, and with the device empty
    behind that reap there is nothing to hide the prefill behind: its
    first token is read before the chunk goes out, as ever."""
    cfg, params = tiny
    eng = Engine("llama", cfg, params, cfg=EngineConfig(
        num_slots=2, max_seq_len=128, page_size=16, decode_chunk=4),
        eos_token_ids=TOK.eos_token_ids)
    eng.device_queue = _SlowDevice()
    log = _CallLog(eng)
    short = SamplingParams(temperature=0.0, max_tokens=6)
    rids = [eng.add_request(PROMPTS[0], short),
            eng.add_request(PROMPTS[1], GREEDY)]
    out = {r: [] for r in rids}
    _collect(out, _step_until_inflight(eng))
    log.take()
    rids.append(eng.add_request(PROMPTS[3], GREEDY))
    out[rids[2]] = []
    waited = []
    while len(eng._sched):
        assert not eng._admission_rides()
        _step(eng, out)
        waited.append(log.take())
    # No slot: reaped ahead, nothing admitted. Then the first request's
    # last token frees its slot in a reap that was forced ahead, and the
    # same step admits.
    assert waited[:-1] == [["reap:admission", "decode"]] * (len(waited) - 1)
    assert waited[-1] == ["reap:admission", "prefill", "head", "decode"]
    assert eng.step_reaps["admission"] == len(waited) >= 2
    assert eng.profiler.recent()[-1]["dispatches"] == [
        "prefill:empty", "decode:empty"]
    assert {(a, b) for a, b, _ in eng.device_queue.drain()} >= {
        ("reap_admission", "decode"), ("reap_admission", "prefill")}
    while eng.has_work():
        _step(eng, out)
    ref = _engine(tiny, "off", num_slots=2)
    assert [out[r] for r in rids] == [
        ref.generate([PROMPTS[0]], short)[0],
        *ref.generate([PROMPTS[1], PROMPTS[3]], GREEDY)]


def test_a_pool_short_of_the_heads_pages_takes_the_barrier(tiny):
    """A slot is free and the head is a fresh prompt, but the pool cannot
    give its pages until the chunk in flight has given some back: nothing
    is popped behind the chunk, and the step takes the barrier as ever."""
    kw = dict(num_slots=2, max_seq_len=64, num_pages=1 + 4)
    on, off = _engine(tiny, "on", **kw), _engine(tiny, "off", **kw)
    rng = np.random.default_rng(11)
    first, late = (rng.integers(1, TOK.vocab_size - 1, n).tolist()
                   for n in (40, 20))
    # Ends inside the chunk in flight when `late` comes: 40 + 7 tokens.
    sp0 = SamplingParams(temperature=0.0, max_tokens=7)
    want = [off.generate([first], sp0)[0], off.generate([late], GREEDY)[0]]
    log = _CallLog(on)
    r0 = on.add_request(first, sp0)
    out = {r0: []}
    _collect(out, _step_until_inflight(on))
    _step(on, out)
    log.take()
    r1 = on.add_request(late, GREEDY)
    out[r1] = []
    assert on._free_slots and on._alloc.free_pages < 2
    assert not on._admission_rides()
    _step(on, out)
    assert log.take()[:3] == ["reap:admission", "prefill", "head"]
    assert on.step_reaps["admission"] == 1
    while on.has_work():
        _step(on, out)
    assert [out[r0], out[r1]] == want


def test_a_short_pool_may_evict_a_request_before_its_first_token_is_read(tiny):
    """The walk that grows the slots' pages evicts the youngest request
    when the pool runs short, and the youngest is the one this step has
    just seated, whose first token is still on the device. It then waits
    in the queue, is handed its first token where the others are, and
    resumes from it by recompute behind the barrier. Over every step at
    which the second prompt can arrive while the first one decodes, every
    token is the synchronous engine's."""
    kw = dict(num_slots=2, max_seq_len=64, num_pages=1 + 5)
    on, off = _engine(tiny, "on", **kw), _engine(tiny, "off", **kw)
    rng = np.random.default_rng(7)
    long, late = (rng.integers(1, TOK.vocab_size - 1, n).tolist()
                  for n in (30, 20))
    sp = SamplingParams(temperature=0.0, max_tokens=30)
    want = [off.generate([p], sp)[0] for p in (long, late)]
    log = _CallLog(on)
    rode = 0
    for arrives in range(1, 8):
        r0 = on.add_request(long, sp)
        out = {r0: []}
        for _ in range(arrives):
            _step(on, out)
        log.take()
        r1 = on.add_request(late, sp)
        out[r1] = []
        _step(on, out)
        seat = log.take()
        rode += seat[:2] == ["prefill", "decode"]
        while on.has_work():
            _step(on, out)
        assert [out[r0], out[r1]] == want, arrives
        assert on._heads == [] and not on._active
    assert rode >= 1 and log.evicted, (rode, log.evicted)
    # A victim with no token yet was one this PR seats; it lost nothing.
    assert min(log.evicted) == 0


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_a_prefix_cache_miss_rides_and_a_hit_keeps_the_barrier(family):
    """With the prefix cache on, a prompt that hits nothing takes the
    fused call and rides; its full pages are published when it is seated,
    so that a later prompt that shares them is a hit, which keeps the
    barrier with its staged calls. Tokens are the synchronous engine's."""
    kw = dict(prefill_chunk=32, prefix_cache=True)
    (on, barrier, off), ask, vocab = _family_engines(family, **kw)
    rng = np.random.default_rng(5)
    shared = rng.integers(1, vocab - 1, 20).tolist()
    scripts = [(p, GREEDY) for p in (
        rng.integers(1, vocab - 1, 9).tolist(), shared + [3, 4],
        rng.integers(1, vocab - 1, 5).tolist(), shared + [5],
        shared[:17] + [6, 7, 8], rng.integers(1, vocab - 1, 12).tolist())]
    log = _CallLog(on)
    got = _closed_loop(on, scripts, 2, ask)
    assert got == _closed_loop(off, scripts, 2, ask)
    assert got == _closed_loop(barrier, scripts, 2, ask)
    assert on.prefix_stats["hit_tokens"] == off.prefix_stats["hit_tokens"] > 0
    calls = log.calls
    nxt = [next(x for x in calls[i + 1:] if x in ("head", "decode"))
           for i, c in enumerate(calls) if c in ("prefill", "staged")]
    by_call = {c: {n for cc, n in zip(
        [c for c in calls if c in ("prefill", "staged")], nxt) if cc == c}
        for c in ("prefill", "staged")}
    assert "decode" in by_call["prefill"] and by_call["staged"] == {"head"}
    assert 1 <= on.step_reaps["admission"] <= barrier.step_reaps["admission"]


# ---- the set-up guard: a warm-up pays nothing ---------------------------------


def test_one_token_batches_on_an_idle_engine_dispatch_no_chunk(tiny):
    """What a warm-up does for each (bucket, admit batch) shape: batches of
    `max_tokens=1` requests on an idle engine, drained. Each is one
    prefill call and nothing else: no chunk is dispatched, none reaped."""
    eng = _engine(tiny, "on")
    log = _CallLog(eng)
    one = SamplingParams(temperature=0.0, max_tokens=1)
    for batch in (1, 2, 4, 3):
        for i in range(batch):
            eng.add_request(PROMPTS[i], one)
        while eng.has_work():
            eng.step()
    assert eng._steps == 0 and set(eng.step_reaps.values()) == {0}
    assert set(log.device()) == {"prefill"} and not log.riders
    assert eng._inflight is None and eng._heads == []
    assert eng.device_queue.dispatches["decode", "busy"] == 0


@pytest.mark.parametrize("family", ["llama", "block"])
def test_the_benchmarks_warm_up_runs_the_programs_it_ran(family):
    """`perf/run.py:warm_up` on the tiny engine: the same sequence of
    `_prefill_admit_jit` / `_decode_jit` calls as with the barrier forced
    wherever it used to be taken, and one run of the chunk's loop for a
    family whose admission serves a token."""
    from perf import run, traffic

    (on, barrier, _off), _ask, vocab = _family_engines(
        family, max_admit_batch=4)
    mix = traffic.load_mix("tiny-closed")
    logs = {}
    for name, eng in (("on", on), ("barrier", barrier)):
        logs[name] = log = _CallLog(eng)
        sent = run.warm_up(eng, mix, vocab)
        shapes = run.warm_shapes(eng, mix)
        assert sent == sum(b for _, b in shapes) + eng.cfg.num_slots
        assert eng._heads == [] and not eng.has_work()
        device = log.device()
        if family == "llama":
            # One prefill a shape and no chunk, then the chunk's own run.
            assert device[:len(shapes)] == ["prefill"] * len(shapes)
            assert "decode" not in device[:len(shapes)]
    assert logs["on"].calls == logs["barrier"].calls
    assert on._steps == barrier._steps
    assert on.step_reaps == barrier.step_reaps
    assert on.step_reaps["admission"] == 0


def _leaf():
    a = b = c = None
    return 1


def _hot(depth):
    """A loop of calls at the bottom of `depth` frames."""
    if depth:
        return _hot(depth - 1)
    n = 0
    for _ in range(2000):
        n += _leaf()
    return n


def _under(pad, f):
    if pad:
        return _under(pad - 1, f)
    return f()


def _faults(f):
    import resource

    before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    f()
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before


def test_a_jitted_call_is_entered_from_a_chunk_of_its_own(tiny):
    """What a warm-up's seconds hung on besides its programs: CPython gives
    a 16 KiB chunk of its frame stack back when the frame that opened it
    returns, so a loop whose callees straddle a chunk's end maps and faults
    one in on every call, and which loops do is decided by the depth they
    are called at. `Engine.jit` enters the jitted function from a frame
    that starts a chunk of its own with room below it: the page faults of
    what runs under it no longer depend on the caller's depth."""
    from kubeai_tpu.engine import engine as engine_mod

    own = engine_mod._from_its_own_chunk
    assert own(lambda a, b: a + b, (1, 2)) == 3
    assert own.__code__.co_stacksize * 8 > 64 * 1024 and own.__doc__
    depths = range(0, 160)  # frames of some 16 words: over a whole chunk
    straddling = [_faults(lambda: _under(p, lambda: _hot(5))) for p in depths]
    if max(straddling) < 1000:
        pytest.skip("this interpreter's frame stack does not thrash")
    # At some depth every one of the 2000 calls faulted a chunk in.
    assert max(straddling) >= 2000
    entered = [_faults(lambda: _under(p, lambda: own(_hot, (5,))))
               for p in depths]
    assert max(entered) < 50, max(entered)
    # The engine's jitted calls go through it.
    eng = _engine(tiny, "on")
    seen = []

    def spy(fn, args):
        seen.append(fn)
        return own(fn, args)

    engine_mod._from_its_own_chunk, real = spy, own
    try:
        eng.generate([PROMPTS[0]], SamplingParams(temperature=0.0, max_tokens=6))
    finally:
        engine_mod._from_its_own_chunk = real
    assert len(seen) >= 3  # the prefill and the chunks


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


# ---- which loop a topology runs ----------------------------------------------


@pytest.mark.parametrize(
    "topology", ["one-device", "dp2", "tp2", "pp2", "lockstep", "worker"])
def test_the_loop_follows_the_topology(devices8, monkeypatch, topology):
    """Nothing is set: an engine overlaps unless a second program in flight
    would race its pp stages' hand-offs, and lockstep (host 0's wrapper and
    a worker's loop alike) clears the flag before the first step, since
    every host replays one sequence of steps."""
    from kubeai_tpu.engine import multihost

    cfg = _dc.replace(llama.LlamaConfig.tiny(), num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = None
    if topology in ("dp2", "tp2", "pp2"):
        mesh = build_mesh(MeshConfig(**{topology[:2]: 2}), devices=devices8[:2])
    eng = Engine("llama", cfg, params, mesh=mesh, cfg=EngineConfig(
        num_slots=4, max_seq_len=96, decode_chunk=4))
    if topology == "lockstep":
        assert eng._overlap is True
        eng = LockstepEngine(eng).inner
    elif topology == "worker":
        # The first descriptor says shut down: the loop sets up and returns.
        desc = multihost._control_zeros()
        desc["header"][3] = 1
        monkeypatch.setattr(multihost, "_broadcast", lambda *a, **kw: desc)
        multihost.worker_loop(eng)
    assert eng._overlap is (topology not in ("pp2", "lockstep", "worker"))


@pytest.mark.parametrize("field", ["step_overlap", "decode_kernel"])
def test_no_option_names_a_loop_or_a_layout(field):
    """Both choices are the engine's, from what it observes (the mesh, the
    pool's kind): the fields are gone, not ignored."""
    assert field not in {f.name for f in _dc.fields(EngineConfig)}
    with pytest.raises(TypeError, match=field):
        EngineConfig(**{field: "auto"})


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
