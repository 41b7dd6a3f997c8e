"""The device queue's book (`kubeai_tpu/fleet/profiler.py:DeviceQueueBook`):
seconds the device had nothing queued before each dispatch, by what emptied
the queue and what ended the gap. The book alone on a fake clock with fake
tails, then the tiny CPU engine held to the book's invariants, then the two
series on `/metrics`."""

import time

import jax
import pytest

from test_host_timeline import Recorder
from testutil import synchronous

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineMetrics
from kubeai_tpu.fleet.profiler import (
    QUEUE_AFTER, QUEUE_BEFORE, QUEUE_STATES, DeviceQueueBook, StepProfiler,
)
from kubeai_tpu.models import llama


class Clock:
    def __init__(self):
        self.t = 50.0

    def __call__(self):
        return self.t


class Tail:
    """A program's output: `is_ready` is what the test says it is."""

    def __init__(self, ready=False):
        self.ready = ready

    def is_ready(self):
        return self.ready


@pytest.fixture
def book():
    clock = Clock()
    b = DeviceQueueBook(clock=clock)
    b.begin_step(clock())
    return b, clock


def _counts(b):
    return {k: v for k, v in b.dispatches.items() if v}


# ---- the book alone ---------------------------------------------------------------


def test_synchronous_loop_every_dispatch_after_the_first_is_empty(book):
    b, clock = book
    notes = []
    for _ in range(4):
        notes.append(b.dispatching("decode"))
        chunk = Tail()
        b.dispatched(chunk)
        clock.t += 0.090  # the device computes, the host waits for it
        b.waited(chunk, "reap_sync")
        clock.t += 0.004  # readback, emission, the loop, the next schedule
    # Nothing was ever dispatched before the first: no wait was observed,
    # nothing is in flight, and no bound is known.
    assert notes[0] == {"queue": "drained"}
    assert [n["queue"] for n in notes[1:]] == ["empty"] * 3
    assert [n["starved_ms"] for n in notes[1:]] == [pytest.approx(4.0)] * 3
    assert b.drain() == [("reap_sync", "decode", pytest.approx(0.004))] * 3
    assert b.drain() == []
    assert _counts(b) == {("decode", "drained"): 1, ("decode", "empty"): 3}
    starved, dispatches = b.end_step()
    assert starved == pytest.approx(0.012)
    assert dispatches == ["decode:drained"] + ["decode:empty"] * 3


@pytest.mark.parametrize("finished_early", [False, True],
                         ids=["busy", "drained"])
def test_run_ahead_with_no_barrier(book, finished_early):
    """Chunk N+1 goes out behind N, then the host waits for N: a wait that
    is not on the tail observes nothing. The next dispatch asks the tail."""
    b, clock = book
    n0, n1 = Tail(), Tail()
    b.dispatched(n0)
    note = b.dispatching("decode")  # N+1 behind a running N
    assert note == {"queue": "busy"}
    b.dispatched(n1)
    clock.t += 0.080
    b.waited(n0, "reap_sync")  # barrier=none: N, while N+1 is queued
    clock.t += 0.020  # the host's pass
    n1.ready = finished_early
    note = b.dispatching("decode")
    if finished_early:
        # The run-ahead did not hide the pass: how long the device stood
        # is unknown, at most the time since the host last woke.
        assert note == {"queue": "drained",
                        "drained_bound_ms": pytest.approx(20.0)}
    else:
        assert note == {"queue": "busy"}
    assert b.drain() == []  # the histogram holds observed seconds only
    assert b.end_step()[0] == 0.0
    assert _counts(b) == {("decode", "busy"): 2 - finished_early,
                          **({("decode", "drained"): 1}
                             if finished_early else {})}


@pytest.mark.parametrize("outran", [False, True], ids=["busy", "drained"])
def test_an_admission_that_rides_the_queue(book, outran):
    """The order of a step whose admission rides: chunk N+1 in flight, a
    prompt pending, a slot free. The prefill(s) and chunk N+2 go out behind
    it (`busy`, `busy`), N+1 is then reaped and the first tokens read.
    Neither wait is on the tail: a `waited(..., "admit")` on a head that is
    not the tail does not mark the queue empty, no `after="admit"` second
    is booked, and the next dispatch asks the tail as ever."""
    b, clock = book
    n1 = Tail()
    b.dispatched(n1)
    clock.t += 0.030
    assert b.dispatching("prefill") == {"queue": "busy"}
    head = Tail()
    b.dispatched(head)
    clock.t += 0.002
    assert b.dispatching("prefill") == {"queue": "busy"}  # a second bucket
    head2 = Tail()
    b.dispatched(head2)
    clock.t += 0.002
    assert b.dispatching("decode") == {"queue": "busy"}
    n2 = Tail()
    b.dispatched(n2)
    clock.t += 0.060
    b.waited(n1, "reap_sync")
    clock.t += 0.015
    b.waited(head, "admit")
    b.waited(head2, "admit")
    clock.t += 0.003
    n2.ready = outran
    note = b.dispatching("decode")
    assert note == ({"queue": "drained",
                     "drained_bound_ms": pytest.approx(3.0)}
                    if outran else {"queue": "busy"})
    assert b.drain() == [] and b.end_step() == (0.0, [
        "prefill:busy", "prefill:busy", "decode:busy",
        "decode:drained" if outran else "decode:busy"])
    # The barrier's order in the same book: the wait IS on the tail.
    b.dispatched(n2)
    b.waited(n2, "reap_admission")
    clock.t += 0.002
    assert b.dispatching("prefill") == {
        "queue": "empty", "starved_ms": pytest.approx(2.0)}
    head3 = Tail()
    b.dispatched(head3)
    b.waited(head3, "admit")
    clock.t += 0.001
    assert b.dispatching("decode") == {
        "queue": "empty", "starved_ms": pytest.approx(1.0)}
    assert [(a, c) for a, c, _ in b.drain()] == [
        ("reap_admission", "prefill"), ("admit", "decode")]


def test_admission_step_reap_then_prefill_then_decode(book):
    b, clock = book
    chunk = Tail()
    b.dispatched(chunk)
    clock.t += 0.050
    b.waited(chunk, "reap_admission")  # reaped ahead: a prompt waits
    clock.t += 0.003  # readback, emission, pops and page grants
    first = b.dispatching("prefill")
    head = Tail()
    b.dispatched(head)
    clock.t += 0.015
    b.waited(head, "admit")
    clock.t += 0.001
    second = b.dispatching("prefill")  # another bucket's call
    head2 = Tail()
    b.dispatched(head2)
    clock.t += 0.015
    b.waited(head2, "admit")
    clock.t += 0.002
    decode = b.dispatching("decode")
    assert first == {"queue": "empty", "starved_ms": pytest.approx(3.0)}
    assert second == {"queue": "empty", "starved_ms": pytest.approx(1.0)}
    assert decode == {"queue": "empty", "starved_ms": pytest.approx(2.0)}
    assert b.drain() == [
        ("reap_admission", "prefill", pytest.approx(0.003)),
        ("admit", "prefill", pytest.approx(0.001)),
        ("admit", "decode", pytest.approx(0.002)),
    ]
    assert b.end_step() == (pytest.approx(0.006), [
        "prefill:empty", "prefill:empty", "decode:empty"])


@pytest.mark.parametrize("observed_empty", [True, False])
def test_an_idle_engine_adds_no_starved_second(book, observed_empty):
    b, clock = book
    chunk = Tail(ready=True)
    b.dispatched(chunk)
    clock.t += 0.090
    # A last chunk whose every rider was cancelled is not waited for.
    b.waited(chunk if observed_empty else Tail(), "reap_sync")
    b.end_step()
    for _ in range(3):  # the serve loop finds no work, again and again
        b.idle()
        clock.t += 100.0
    started = clock()  # a request came: its step's `serve.step` opens
    clock.t += 0.0005  # the wait for the engine lock
    b.begin_step(started)
    clock.t += 0.0015
    note = b.dispatching("prefill")
    if observed_empty:
        assert note == {"queue": "empty", "starved_ms": pytest.approx(2.0)}
        assert b.drain() == [("reap_sync", "prefill", pytest.approx(0.002))]
    else:
        assert note == {"queue": "drained",
                        "drained_bound_ms": pytest.approx(2.0)}
        assert b.drain() == []


def test_device_work_the_book_cannot_watch_leaves_the_tail_unknown(book):
    b, clock = book
    chunk = Tail()
    b.dispatched(chunk)
    b.waited(chunk, "reap_external")
    b.dispatched(None)  # a hand-off's own programs, a draft's catch-up
    clock.t += 0.010
    b.waited(None, "reap_sync")  # never the tail
    clock.t += 0.010
    assert b.dispatching("decode") == {
        "queue": "drained", "drained_bound_ms": pytest.approx(10.0)}
    assert b.drain() == []


def test_a_dispatch_clears_what_was_observed(book):
    b, clock = book
    chunk = Tail()
    b.dispatched(chunk)
    b.waited(chunk, "reap_seq_cap")
    clock.t += 0.001
    assert b.dispatching("decode")["queue"] == "empty"
    nxt = Tail()
    b.dispatched(nxt)
    clock.t += 0.001
    assert b.dispatching("decode") == {"queue": "busy"}
    assert [(a, bf) for a, bf, _ in b.drain()] == [("reap_seq_cap", "decode")]


def test_the_step_record_gains_the_books_page():
    prof = StepProfiler()
    prof.observe_step({"decode": 0.001}, tokens=8, batch=2, duration_s=0.1,
                      starved_s=0.0031, dispatches=["prefill:empty",
                                                    "decode:empty"])
    prof.observe_step({"decode": 0.001})
    first, second = prof.recent()
    assert first["starved_s"] == 0.0031
    assert first["dispatches"] == ["prefill:empty", "decode:empty"]
    assert second["starved_s"] == 0.0 and second["dispatches"] == []


# ---- the tiny CPU engine, held to the book ------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _drive(eng, metrics, idle_s=0.0):
    """A run with admissions: two prompts at the start, three more while
    the first two decode (two find a slot free, and under overlap ride the
    device's queue behind the chunk in flight; the third finds none, which
    under overlap is an admission barrier on every step until the reap it
    forces frees one), a last one once the engine has gone idle. Returns
    the seconds of the two drives."""
    sp = SamplingParams(temperature=0.0, max_tokens=14)
    eng.add_request([1, 2, 3], sp)
    eng.add_request(list(range(4, 24)), sp)
    walls = []
    t0 = time.perf_counter()
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        if steps == 2:
            for prompt in ([7, 8, 9, 10], [11, 12, 13], [14, 15]):
                eng.add_request(prompt, sp)
        metrics.sync_engine(eng)
    walls.append(time.perf_counter() - t0)
    eng.device_queue.idle()  # what the serve loop does when it finds no work
    time.sleep(idle_s)
    eng.add_request([11, 12], sp)
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
        metrics.sync_engine(eng)
    walls.append(time.perf_counter() - t0)
    return walls


IDLE_S = 1.0


def _run(tiny, overlap):
    """The drive, once to compile its shapes and once on a fresh book:
    the engine, the second drive's spans, what the metrics folded in of
    it, and its seconds."""
    cfg, params = tiny
    eng = Engine("llama", cfg, params, cfg=EngineConfig(
        num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4))
    if overlap == "off":
        synchronous(eng)
    _drive(eng, EngineMetrics())
    rec = Recorder()
    eng.profiler._annotate = rec
    eng.profiler._ring.clear()
    eng.device_queue = DeviceQueueBook()
    eng.admit_stats["calls"] = 0
    metrics = EngineMetrics()
    return eng, rec, metrics, _drive(eng, metrics, IDLE_S)


@pytest.fixture(scope="module", params=["on", "off"])
def run(request, tiny):
    return request.param, *_run(tiny, request.param)


def _starved_sum(m):
    return sum(m.device_starved.sum_for(after=a, before=b)
               for a in QUEUE_AFTER for b in QUEUE_BEFORE)


def _sum(metric, **want):
    return sum(v for labels, v in metric.samples()
               if all(labels.get(k) == w for k, w in want.items()))


def test_starved_seconds_fit_beside_the_waits_for_the_device(run):
    """The engine thread either waits for the device or leaves it waiting:
    the starved seconds of a window are at most its wall minus the seconds
    inside `step.overlap_idle` and `admit.wait`, and the idle spell between
    the two drives is in neither."""
    _overlap, eng, _rec, m, walls = run
    starved = _starved_sum(m)
    waits = (m.step_phase.sum_for(phase="overlap_idle")
             + m.admit_wait.sum_for())
    assert sum(walls) < IDLE_S  # or the next line would prove nothing
    assert 0 < starved <= sum(walls) - waits
    records = eng.profiler.recent()
    assert sum(r["starved_s"] for r in records) == pytest.approx(
        starved, abs=1e-6)


def test_every_dispatch_is_counted_once_where_it_is_made(run):
    _overlap, eng, rec, m, _walls = run
    decodes = rec.named("step.decode")
    admits = [s for s in rec.named("step.admit") if "kind" in s["attrs"]]
    assert _sum(m.dispatches, before="decode") == len(decodes) > 4
    assert _sum(m.dispatches, before="prefill") == len(admits) >= 3
    assert len(admits) == eng.admit_stats["calls"]
    for span in decodes + admits:
        attrs = span["attrs"]
        assert attrs["queue"] in QUEUE_STATES
        assert ("starved_ms" in attrs) == (attrs["queue"] == "empty")
        assert "drained_bound_ms" not in attrs or attrs["queue"] == "drained"
    by_state = {q: sum(s["attrs"]["queue"] == q for s in decodes + admits)
                for q in QUEUE_STATES}
    assert by_state == {q: _sum(m.dispatches, queue=q) for q in QUEUE_STATES}
    pages = [d for r in eng.profiler.recent() for d in r["dispatches"]]
    assert len(pages) == len(decodes) + len(admits)


def test_empty_dispatches_are_the_histograms_observations(run):
    _overlap, _eng, rec, m, _walls = run
    observed = sum(m.device_starved.get(after=a, before=b)
                   for a in QUEUE_AFTER for b in QUEUE_BEFORE)
    assert observed == _sum(m.dispatches, queue="empty") > 0
    spans = rec.named("step.decode") + rec.named("step.admit")
    on_spans = sum(s["attrs"].get("starved_ms", 0.0) for s in spans) / 1e3
    in_hist = _starved_sum(m)
    assert on_spans == pytest.approx(in_hist, abs=1e-6)


def test_what_emptied_the_queue_follows_the_loop(run):
    overlap, _eng, _rec, m, _walls = run
    after = {a for a in QUEUE_AFTER
             if sum(m.device_starved.get(after=a, before=b)
                    for b in QUEUE_BEFORE)}
    if overlap == "off":
        # Every chunk is reaped with nothing behind it, and a prompt is
        # admitted after such a reap or after another admission.
        assert after == {"reap_sync", "admit"}
        assert _sum(m.dispatches, before="decode", queue="busy") == 0
    else:
        # The prompt that found no slot free forced a reap ahead of every
        # chunk, and at last ahead of its own admission. (The two that
        # found one rode behind the chunk in flight: `busy` on a device
        # slower than its host, which `test_step_overlap.py` pins; these
        # tiny CPU programs often end first, and then read `drained`.)
        assert {"reap_admission", "admit"} <= after
        assert m.device_starved.get(after="reap_admission",
                                    before="prefill") >= 1
    # The first decode chunk after an admission waits for nothing else.
    assert m.device_starved.get(after="admit", before="decode") >= 1


def test_a_speculation_window_is_waited_for_where_it_is_reaped(tiny):
    """Speculation never runs ahead: every window is reaped in the step
    that dispatched it, its wait is a `step.overlap_idle` of its own (not
    hidden in the fused readback), and every dispatch after the first
    finds the queue observed empty."""
    cfg, params = tiny
    eng = Engine("llama", cfg, params, cfg=EngineConfig(
        num_slots=2, max_seq_len=128, page_size=16, speculate=2,
        spec_adaptive=False))
    rec = Recorder()
    eng.profiler._annotate = rec
    eng.add_request([5, 6, 5, 6, 5, 6, 5, 6],
                    SamplingParams(temperature=0.0, max_tokens=10))
    while eng.has_work():
        eng.step()
    decodes = rec.named("step.decode")
    assert len(decodes) >= 3
    assert [s["attrs"]["queue"] for s in decodes[1:]] == (
        ["empty"] * (len(decodes) - 1))
    waits = [s for s in rec.named("step.overlap_idle")
             if s["parent"] == "step.reap"]
    assert len(waits) == len(decodes)
    assert {(a, b) for a, b, _ in eng.device_queue.drain()} == {
        ("admit", "decode"), ("reap_sync", "decode")}


# ---- /metrics -------------------------------------------------------------------------


def test_metrics_show_both_series_with_their_labels(run):
    _overlap, _eng, _rec, m, _walls = run
    text = m.registry.expose()
    assert "# TYPE kubeai_engine_device_starved_seconds histogram" in text
    assert "# TYPE kubeai_engine_dispatches_total counter" in text
    for before in QUEUE_BEFORE:
        for queue in QUEUE_STATES:
            assert (f'kubeai_engine_dispatches_total{{before="{before}",'
                    f'queue="{queue}"}}') in text
    assert ('kubeai_engine_device_starved_seconds_count{after="admit",'
            'before="decode"}') in text


def test_sync_engine_never_moves_a_counter_backward(run, tiny):
    _overlap, eng, _rec, m, _walls = run
    before = {k: m.dispatches.get(before=k[0], queue=k[1])
              for k in eng.device_queue.dispatches}
    count = m.device_starved.get(after="admit", before="decode")
    m.sync_engine(eng)  # nothing new: nothing moves
    assert before == {k: m.dispatches.get(before=k[0], queue=k[1])
                      for k in before}
    # A restarted engine's book starts at zero: the registry keeps its own.
    cfg, params = tiny
    fresh = Engine("llama", cfg, params, cfg=EngineConfig(
        num_slots=2, max_seq_len=64, page_size=16, decode_chunk=4))
    m.sync_engine(fresh)
    assert before == {k: m.dispatches.get(before=k[0], queue=k[1])
                      for k in before}
    assert m.device_starved.get(after="admit", before="decode") == count


def test_the_serve_loops_idle_spell_is_no_starvation(tiny):
    """Through the real server: between two requests the loop finds no work
    and says so, and the second request's first dispatch counts from the
    start of its own step."""
    from test_engine_telemetry import _stream_completion

    from kubeai_tpu.engine.server import EngineServer
    from kubeai_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = llama.LlamaConfig.tiny(vocab_size=tok.vocab_size)
    eng = synchronous(Engine(
        "llama", cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
        cfg=EngineConfig(num_slots=2, max_seq_len=64, decode_chunk=4),
        eos_token_ids=tok.eos_token_ids))
    srv = EngineServer(eng, tok, "tiny", host="127.0.0.1", port=0)
    srv.start()
    try:
        body = {"model": "tiny", "prompt": "hello", "max_tokens": 8,
                "temperature": 0}
        _stream_completion(srv.port, body)  # compiles
        srv.metrics.sync_engine(eng)
        m = srv.metrics
        before = _starved_sum(m)
        time.sleep(IDLE_S)
        t0 = time.perf_counter()
        _stream_completion(srv.port, body)
        wall = time.perf_counter() - t0
        srv.metrics.sync_engine(eng)
        starved = _starved_sum(m) - before
        # The synchronous loop's last reap observed the queue empty; the
        # second prompt's prefill ended that spell, from its step's start.
        assert m.device_starved.get(after="reap_sync", before="prefill") >= 1
        assert 0 < starved < wall < IDLE_S
    finally:
        srv.stop()
