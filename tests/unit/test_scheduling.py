"""SLO-aware scheduling tests (kubeai_tpu/scheduling + its integration
through the engine HTTP server).

The pure-scheduler tests drive a fake clock so WFQ proportional sharing,
strict precedence, deadline-shed feasibility math, and starvation-freedom
are asserted deterministically. The HTTP tests drive the REAL engine
server (tiny llama on CPU, single slot) with mixed-priority clients and
assert ordering via the per-request queue-wait stats the scheduler
exports on /v1/state."""

import json
import threading
import time

import pytest

from kubeai_tpu.scheduling import (
    CLASS_BATCH,
    CLASS_REALTIME,
    CLASS_STANDARD,
    DeadlineInfeasible,
    RequestScheduler,
    SchedulingPolicy,
)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def mk(policy: SchedulingPolicy | None = None):
    clock = FakeClock()
    return RequestScheduler(policy, clock=clock), clock


class Item:
    """Identity-tracked queue item with a debug label."""

    def __init__(self, label: str):
        self.label = label

    def __repr__(self):
        return f"Item({self.label})"


# ---- strict priority precedence ---------------------------------------------


def test_strict_precedence_between_bands():
    sched, _ = mk()
    b = Item("batch")
    s = Item("std")
    r = Item("rt")
    sched.submit(b, priority=CLASS_BATCH)
    sched.submit(s, priority=CLASS_STANDARD)
    sched.submit(r, priority=CLASS_REALTIME)
    # Arrival order was batch, standard, realtime — dispatch order is by
    # band, highest first.
    assert [sched.pop() for _ in range(3)] == [r, s, b]
    assert sched.pop() is None


def test_equal_rate_clients_saturating_single_slot_strict_precedence():
    """Acceptance: two equal-rate clients in different bands against a
    single-slot drain — the higher band takes every dispatch while it
    has work; the lower band only drains afterwards."""
    sched, clock = mk()  # default shares: pure strict precedence
    popped = []
    for _ in range(20):
        sched.submit(Item("rt"), priority=CLASS_REALTIME, client="a")
        sched.submit(Item("batch"), priority=CLASS_BATCH, client="b")
        popped.append(sched.pop())  # single-slot: one dispatch per round
        clock.advance(0.1)
    assert all(i.label == "rt" for i in popped)
    assert sched.class_depths() == {
        CLASS_REALTIME: 0, CLASS_STANDARD: 0, CLASS_BATCH: 20
    }
    # Higher-band arrivals stopped: the batch backlog now drains.
    assert sched.pop().label == "batch"


# ---- weighted fair queueing --------------------------------------------------


def test_wfq_two_clients_2to1_weights_converge_2to1():
    """Acceptance: same band, 2:1 weights, both backlogged — dispatches
    converge to an exact 2:1 ratio."""
    sched, _ = mk()
    for i in range(30):
        sched.submit(Item(f"a{i}"), client="a", weight=2.0)
        sched.submit(Item(f"b{i}"), client="b", weight=1.0)
    got = [sched.pop().label for _ in range(30)]
    a, b = sum(x[0] == "a" for x in got), sum(x[0] == "b" for x in got)
    assert (a, b) == (20, 10)
    # And nothing is lost: the rest drains completely.
    rest = [sched.pop() for _ in range(30)]
    assert all(r is not None for r in rest) and sched.pop() is None


def test_wfq_new_client_joins_at_virtual_time_frontier():
    """A client arriving behind an old backlog starts at the band's
    current virtual time: it is served promptly instead of queueing
    behind every already-issued finish tag."""
    sched, _ = mk()
    for i in range(10):
        sched.submit(Item(f"a{i}"), client="a")
    for _ in range(3):
        assert sched.pop().label.startswith("a")
    late = Item("late")
    sched.submit(late, client="b")
    # Within two pops (not after a's remaining 7), b's entry surfaces.
    assert late in [sched.pop(), sched.pop()]


# ---- deadline-aware admission ------------------------------------------------


def test_deadline_infeasible_shed_with_computed_math():
    """Acceptance: queue of 10 at a measured 2/s drain -> 5s wait; a 1s
    deadline is refused at enqueue with the computed estimate and a
    computed Retry-After."""
    sched, _ = mk()
    sched.observe_service(cost=2.0, seconds=1.0)  # rate = 2 units/s
    for i in range(10):
        sched.submit(Item(f"q{i}"))
    late = Item("late")
    with pytest.raises(DeadlineInfeasible) as exc:
        sched.submit(late, deadline_ms=1000)
    assert exc.value.estimated_wait == pytest.approx(5.0)
    assert exc.value.retry_after == pytest.approx(5.0)  # 10 queued / 2 per s
    assert late not in sched and len(sched) == 10
    assert sched.snapshot()["classes"][CLASS_STANDARD]["shed_total"] == 1
    # A deadline past the estimate is admitted.
    ok = Item("ok")
    sched.submit(ok, deadline_ms=6000)
    assert ok in sched


def test_deadline_feasibility_is_class_aware():
    """A realtime request only waits behind realtime work: the same
    deadline that is infeasible for standard admits for realtime."""
    sched, _ = mk()
    sched.observe_service(cost=1.0, seconds=1.0)  # 1/s
    for i in range(5):
        sched.submit(Item(f"std{i}"), priority=CLASS_STANDARD)
    with pytest.raises(DeadlineInfeasible):
        sched.submit(Item("std-late"), priority=CLASS_STANDARD,
                     deadline_ms=2000)
    rt = Item("rt")
    sched.submit(rt, priority=CLASS_REALTIME, deadline_ms=2000)
    assert rt in sched


def test_deadline_admits_while_rate_unmeasured():
    """No service observations yet -> no feasibility evidence -> admit
    (shedding on a guess would refuse the first request ever queued)."""
    sched, _ = mk()
    for i in range(50):
        sched.submit(Item(f"q{i}"))
    ok = Item("ok")
    sched.submit(ok, deadline_ms=1)
    assert ok in sched


def test_retry_after_is_computed_from_queue_state_not_constant():
    sched, _ = mk()
    sched.observe_service(cost=2.0, seconds=1.0)
    assert sched.retry_after() == pytest.approx(0.25)  # empty queue: floor
    for i in range(10):
        sched.submit(Item(f"q{i}"))
    deep = sched.retry_after()
    assert deep == pytest.approx(5.0)
    for _ in range(5):
        sched.pop()
    half = sched.retry_after()
    assert half == pytest.approx(2.5)
    assert len({0.25, deep, half}) == 3  # varies with depth — never a constant
    for i in range(200):
        sched.submit(Item(f"x{i}"))
    assert sched.retry_after() == pytest.approx(30.0)  # policy ceiling


def test_max_deadline_ms_caps_client_deadlines():
    sched, _ = mk(SchedulingPolicy(max_deadline_ms=500))
    sched.observe_service(cost=1.0, seconds=1.0)  # 1/s
    sched.submit(Item("q0"))  # 1s estimated wait for the next arrival
    # The client asks for 10s, but the operator capped deadlines at 500ms
    # — infeasible against the 1s estimate.
    with pytest.raises(DeadlineInfeasible) as exc:
        sched.submit(Item("late"), deadline_ms=10_000)
    assert exc.value.deadline_s == pytest.approx(0.5)


# ---- anti-starvation queue shares -------------------------------------------


def test_queue_share_prevents_batch_starvation():
    """Acceptance: under sustained realtime arrivals, a batch request
    with a 25% share is dispatched on the 5th pop (credit reaches 1.0
    after four passed-over dispatches) — it does not starve."""
    sched, _ = mk(SchedulingPolicy(queue_shares={CLASS_BATCH: 0.25}))
    b = Item("batch")
    sched.submit(b, priority=CLASS_BATCH)
    popped = []
    for i in range(8):
        sched.submit(Item(f"rt{i}"), priority=CLASS_REALTIME)
        popped.append(sched.pop())
    assert popped[4] is b  # exactly when its 0.25 share came due
    assert all(p.label.startswith("rt") for p in popped[:4])


def test_queue_share_periodic_under_sustained_load():
    """With a 0.25 batch share and both bands backlogged, batch receives
    one dispatch in every five — the share, enforced periodically."""
    sched, _ = mk(SchedulingPolicy(queue_shares={CLASS_BATCH: 0.25}))
    for i in range(20):
        sched.submit(Item(f"b{i}"), priority=CLASS_BATCH)
    got = []
    for i in range(25):
        sched.submit(Item(f"rt{i}"), priority=CLASS_REALTIME)
        got.append(sched.pop().label[0])
    assert got.count("b") == 5
    # Never two batch dispatches in a row while realtime is backlogged.
    assert "bb" not in "".join(got)


def test_higher_band_wins_among_due_bands():
    """When several passed-over bands are due at once, the higher band
    takes the dispatch."""
    sched, _ = mk(SchedulingPolicy(
        queue_shares={CLASS_STANDARD: 0.5, CLASS_BATCH: 0.5}
    ))
    sched.submit(Item("std"), priority=CLASS_STANDARD)
    sched.submit(Item("batch"), priority=CLASS_BATCH)
    for i in range(2):
        sched.submit(Item(f"rt{i}"), priority=CLASS_REALTIME)
        assert sched.pop().label.startswith("rt")
    # Both lower bands now hold credit 1.0; standard outranks batch.
    sched.submit(Item("rt2"), priority=CLASS_REALTIME)
    assert sched.pop().label == "std"


def test_peek_does_not_consume_share_credit():
    """peek() must be side-effect free: a deferred admission (peek
    without pop, e.g. OutOfPages) cannot drain a band's credit."""
    sched, _ = mk(SchedulingPolicy(queue_shares={CLASS_BATCH: 0.5}))
    sched.submit(Item("batch"), priority=CLASS_BATCH)
    sched.submit(Item("rt"), priority=CLASS_REALTIME)
    for _ in range(10):
        assert sched.peek().label == "rt"  # no credit accrual/consumption
    assert sched.pop().label == "rt"


# ---- queue mechanics ---------------------------------------------------------


def test_requeue_front_resumes_before_everything():
    sched, clock = mk()
    first, second = Item("first"), Item("second")
    sched.submit(first)
    sched.submit(second)
    assert sched.pop() is first
    clock.advance(1.0)
    sched.requeue_front(first)  # preemption: resume before `second`
    assert sched.pop() is first
    # Stats count `first` once — preemption is recompute, not a second
    # queue wait.
    assert sched.snapshot()["classes"][CLASS_STANDARD]["admitted_total"] == 1
    assert sched.pop() is second


def test_remove_cancellation_and_introspection():
    sched, clock = mk()
    a, b = Item("a"), Item("b")
    sched.submit(a, priority=CLASS_REALTIME)
    sched.submit(b)
    assert a in sched and len(sched) == 2 and bool(sched)
    assert sorted(i.label for i in sched.items()) == ["a", "b"]
    assert sched.remove(a) is True
    assert sched.remove(a) is False  # already gone
    assert a not in sched
    assert sched.class_depths()[CLASS_REALTIME] == 0
    assert sched.pop() is b and sched.pop() is None
    assert not sched


def test_snapshot_oldest_wait_uses_clock():
    sched, clock = mk()
    sched.submit(Item("old"), priority=CLASS_BATCH)
    clock.advance(3.0)
    sched.submit(Item("young"))
    snap = sched.snapshot()
    assert snap["oldest_wait_s"] == pytest.approx(3.0)
    assert snap["classes"][CLASS_BATCH]["oldest_wait_s"] == pytest.approx(3.0)
    assert snap["classes"][CLASS_STANDARD]["oldest_wait_s"] == pytest.approx(0.0)
    assert snap["depth"] == 2
    assert sched.oldest_wait() == pytest.approx(3.0)


def test_mean_queue_wait_tracked_per_class():
    sched, clock = mk()
    sched.submit(Item("a"))
    clock.advance(2.0)
    assert sched.pop() is not None
    sched.submit(Item("b"))
    clock.advance(4.0)
    assert sched.pop() is not None
    cls = sched.snapshot()["classes"][CLASS_STANDARD]
    assert cls["mean_queue_wait_s"] == pytest.approx(3.0)


def test_service_rate_decays_during_stalls():
    sched, _ = mk(SchedulingPolicy(rate_decay=0.5))
    sched.observe_service(cost=8.0, seconds=1.0)
    assert sched.service_rate() == pytest.approx(8.0)
    # Zero-completion observations are valid and pull the rate down.
    sched.observe_service(cost=0.0, seconds=1.0)
    assert sched.service_rate() == pytest.approx(4.0 / 1.5)


def test_validation_errors():
    with pytest.raises(ValueError):
        SchedulingPolicy(default_priority="urgent").validate()
    with pytest.raises(ValueError):
        SchedulingPolicy(queue_shares={"nope": 0.1}).validate()
    with pytest.raises(ValueError):
        SchedulingPolicy(queue_shares={CLASS_BATCH: 1.0}).validate()
    with pytest.raises(ValueError):
        SchedulingPolicy(max_deadline_ms=-1).validate()
    sched, _ = mk()
    with pytest.raises(ValueError):
        sched.submit(Item("x"), priority="urgent")
    with pytest.raises(ValueError):
        sched.submit(Item("x"), weight=0)
    with pytest.raises(ValueError):
        sched.submit(Item("x"), cost=-1)
    with pytest.raises(ValueError):
        sched.submit(Item("x"), deadline_ms=0)


# ---- fairness simulation invariants (benchmarks/scheduling_fairness.py) -----


def test_fairness_simulation_invariants():
    """The synthetic-arrival fairness sim's summary invariants hold on a
    small configuration — fairness regressions fail tier-1 instead of
    only showing up under production load."""
    import os
    import sys

    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from benchmarks.scheduling_fairness import check_invariants, run_sim

    summary = run_sim(rounds=600)
    violations = check_invariants(summary)
    assert violations == [], violations
    # Spot-check the headline numbers, not just the pass/fail bits.
    assert summary["wfq_ratio_std_a_over_std_b"] == pytest.approx(2.0, rel=0.1)
    waits = summary["mean_wait_s_by_class"]
    assert waits["realtime"] < waits["standard"] < waits["batch"]
    assert summary["deadline_sheds"] > 0
    assert summary["retry_hints_distinct"] >= 2


# ---- HTTP integration: real engine server, single slot ----------------------


@pytest.fixture(scope="module")
def server():
    import jax

    from kubeai_tpu.engine import Engine, EngineConfig
    from kubeai_tpu.engine.server import EngineServer
    from kubeai_tpu.engine.tokenizer import ByteTokenizer
    from kubeai_tpu.models import llama

    tok = ByteTokenizer()
    cfg = llama.LlamaConfig.tiny(vocab_size=tok.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(
        "llama",
        cfg,
        params,
        cfg=EngineConfig(num_slots=1, max_seq_len=512, decode_chunk=4),
        # No EOS: requests deterministically run to max_tokens, so a
        # long blocker reliably occupies the single slot.
        eos_token_ids=(),
    )
    srv = EngineServer(engine, tok, "tiny", host="127.0.0.1", port=0)
    srv.start()
    yield srv
    srv.stop()


def _post(server, path, payload, headers=None):
    """POST returning (status, headers_dict, parsed_body)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
    body = json.dumps(payload).encode()
    conn.request(
        "POST", path, body=body,
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    resp = conn.getresponse()
    data = resp.read()
    hdrs = {k.lower(): v for k, v in resp.getheaders()}
    conn.close()
    return resp.status, hdrs, json.loads(data)


def _state(server) -> dict:
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    conn.request("GET", "/v1/state")
    data = json.loads(conn.getresponse().read())
    conn.close()
    return data


def _completion(server, results, key, max_tokens=4, headers=None):
    status, _, body = _post(
        server,
        "/v1/completions",
        {"model": "tiny", "prompt": "hi", "max_tokens": max_tokens,
         "temperature": 0},
        headers=headers,
    )
    results[key] = (status, time.monotonic(), body)


def _wait(predicate, timeout=30.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def test_mixed_priority_clients_single_slot_ordering(server):
    """Acceptance concurrency test: with the single slot occupied, a
    batch request queued BEFORE a realtime request is served AFTER it,
    and the per-class queue-wait stats on /v1/state agree."""
    results: dict = {}
    blocker = threading.Thread(
        target=_completion, args=(server, results, "blocker"),
        kwargs={"max_tokens": 400},
    )
    blocker.start()
    assert _wait(lambda: _state(server)["slots_active"] == 1)

    # The batch request is long enough to end well after the realtime one
    # when it is served second: at 4 tokens the two ended 2-3 ms apart, and
    # two client threads on a loaded machine read that in either order.
    batch = threading.Thread(
        target=_completion, args=(server, results, "batch"),
        kwargs={"headers": {"X-Priority": "batch", "X-Client-Id": "b"},
                "max_tokens": 100},
    )
    batch.start()
    assert _wait(lambda: _state(server)["requests_pending"] == 1)
    rt = threading.Thread(
        target=_completion, args=(server, results, "rt"),
        kwargs={"headers": {"X-Priority": "realtime", "X-Client-Id": "r"}},
    )
    rt.start()
    assert _wait(lambda: _state(server)["requests_pending"] == 2)
    # Both are queued while the blocker still holds the slot: the
    # scheduler decides the order the slot is granted in.
    st = _state(server)
    assert st["slots_active"] == 1
    assert st["scheduler"]["classes"]["realtime"]["depth"] == 1
    assert st["scheduler"]["classes"]["batch"]["depth"] == 1

    for t in (blocker, batch, rt):
        t.join(timeout=120)
    assert all(r[0] == 200 for r in results.values()), results
    # The realtime request finished before the batch request even though
    # it was queued later.
    assert results["rt"][1] < results["batch"][1]
    sched = _state(server)["scheduler"]["classes"]
    assert sched["realtime"]["admitted_total"] == 1
    assert sched["batch"]["admitted_total"] == 1
    # Queue-wait stats tell the same story: the batch request waited
    # longer (it sat through the realtime request's service too).
    assert (
        sched["realtime"]["mean_queue_wait_s"]
        < sched["batch"]["mean_queue_wait_s"]
    )


def test_queue_full_shed_computed_retry_after_and_depths(server):
    """Satellite: the 429 shed path returns a COMPUTED Retry-After (from
    scheduler state, never the old static "1") plus per-class queue
    depths in the body."""
    results: dict = {}
    blocker = threading.Thread(
        target=_completion, args=(server, results, "blocker"),
        kwargs={"max_tokens": 300},
    )
    blocker.start()
    assert _wait(lambda: _state(server)["slots_active"] == 1)
    filler = threading.Thread(
        target=_completion, args=(server, results, "filler"),
    )
    old_max_queue = server.max_queue
    try:
        filler.start()
        assert _wait(lambda: _state(server)["requests_pending"] == 1)
        server.max_queue = 1
        status, hdrs, body = _post(
            server, "/v1/completions",
            {"model": "tiny", "prompt": "hi", "max_tokens": 2},
        )
        assert status == 429
        retry_after = float(hdrs["retry-after"])  # parses as a number
        assert hdrs["retry-after"] != "1"  # not the old static header
        assert retry_after == pytest.approx(
            body["queue"]["retry_after_s"], abs=0.05
        )
        assert body["queue"]["depths"]["standard"] == 1
        assert set(body["queue"]["depths"]) == {
            "realtime", "standard", "batch"
        }
    finally:
        server.max_queue = old_max_queue
        blocker.join(timeout=120)
        filler.join(timeout=120)


def test_deadline_shed_over_http(server):
    """An infeasible X-Deadline-Ms is rejected at enqueue with 429 and
    the scheduler's computed backoff, instead of timing out after
    queueing."""
    results: dict = {}
    # Ensure the drain rate is measured (a completed request feeds the
    # estimator), then occupy the slot and queue one filler.
    _completion(server, results, "warm", max_tokens=2)
    assert results["warm"][0] == 200
    blocker = threading.Thread(
        target=_completion, args=(server, results, "blocker"),
        kwargs={"max_tokens": 300},
    )
    blocker.start()
    assert _wait(lambda: _state(server)["slots_active"] == 1)
    filler = threading.Thread(
        target=_completion, args=(server, results, "filler"),
    )
    filler.start()
    try:
        assert _wait(lambda: _state(server)["requests_pending"] == 1)
        # 0.01 ms can never be met with queued work ahead.
        status, hdrs, body = _post(
            server, "/v1/completions",
            {"model": "tiny", "prompt": "hi", "max_tokens": 2},
            headers={"X-Deadline-Ms": "0.01"},
        )
        assert status == 429
        assert "infeasible" in body["error"]["message"]
        assert float(hdrs["retry-after"]) > 0
        assert body["queue"]["depths"]["standard"] >= 1
        # The shed shows up in the scheduler's per-class stats.
        assert _state(server)["scheduler"]["classes"]["standard"][
            "shed_total"
        ] >= 1
    finally:
        blocker.join(timeout=120)
        filler.join(timeout=120)
    assert results["filler"][0] == 200  # the feasible request completed


@pytest.mark.parametrize(
    "payload, fragment",
    [
        ({"max_tokens": 0}, "max_tokens"),
        ({"max_tokens": -5}, "max_tokens"),
        ({"max_tokens": "lots"}, "max_tokens"),
        ({"max_tokens": 2.5}, "max_tokens"),
        ({"temperature": "warm"}, "temperature"),
        ({"temperature": -0.5}, "temperature"),
        ({"top_p": 0}, "top_p"),
        ({"top_p": 1.5}, "top_p"),
        ({"top_p": "most"}, "top_p"),
        ({"top_k": 1.5}, "top_k"),
        ({"top_k": -1}, "top_k"),
    ],
)
def test_sampling_validation_returns_400_not_500(server, payload, fragment):
    """Satellite: malformed sampling params answer 400 with a clear
    message (previously a 500 traceback; max_tokens: 0 previously
    silently became 128)."""
    status, _, body = _post(
        server, "/v1/completions",
        {"model": "tiny", "prompt": "hi", **payload},
    )
    assert status == 400
    assert fragment in body["error"]["message"]


def test_scheduling_header_validation_400(server):
    status, _, body = _post(
        server, "/v1/completions",
        {"model": "tiny", "prompt": "hi", "max_tokens": 2},
        headers={"X-Priority": "vip"},
    )
    assert status == 400 and "X-Priority" in body["error"]["message"]
    status, _, body = _post(
        server, "/v1/completions",
        {"model": "tiny", "prompt": "hi", "max_tokens": 2},
        headers={"X-Deadline-Ms": "soon"},
    )
    assert status == 400 and "X-Deadline-Ms" in body["error"]["message"]
    status, _, body = _post(
        server, "/v1/completions",
        {"model": "tiny", "prompt": "hi", "max_tokens": 2},
        headers={"X-Deadline-Ms": "-10"},
    )
    assert status == 400


def test_state_and_metrics_expose_queue_pressure(server):
    """The queue-pressure signal the autoscaler consumes is on both
    /v1/state (scheduler block) and /metrics (per-class gauges)."""
    import http.client

    st = _state(server)
    sched = st["scheduler"]
    assert set(sched["classes"]) == {"realtime", "standard", "batch"}
    for cls in sched["classes"].values():
        for key in ("depth", "oldest_wait_s", "admitted_total",
                    "shed_total", "mean_queue_wait_s"):
            assert key in cls
    assert "retry_after_s" in sched and "service_rate" in sched

    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    assert 'kubeai_engine_queue_depth{class="realtime"}' in text
    assert 'kubeai_engine_queue_oldest_wait_seconds{class="batch"}' in text
    assert "kubeai_engine_sched_service_rate" in text
    assert 'kubeai_engine_queue_shed_total{class="standard"}' in text
