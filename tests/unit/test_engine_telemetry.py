"""Engine request-lifecycle telemetry: TTFT/ITL/queue-wait/e2e histograms
and per-step gauges, driven through the real HTTP server with a fake
engine clock so the recorded latencies are deterministic."""

import json
import threading

import jax
import pytest

from testutil import http_get

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine import engine as engine_mod
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama


class FakeClock:
    """Monotonic fake: every read advances 1ms, so consecutive lifecycle
    events are strictly ordered and every latency is a positive, exact
    multiple of the tick."""

    def __init__(self, tick: float = 0.001):
        self.t = 100.0
        self.tick = tick
        self._lock = threading.Lock()

    def __call__(self) -> float:
        with self._lock:
            self.t += self.tick
            return self.t


@pytest.fixture
def server(monkeypatch):
    monkeypatch.setattr(engine_mod, "_now", FakeClock())
    tok = ByteTokenizer()
    cfg = llama.LlamaConfig.tiny(vocab_size=tok.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64, decode_chunk=4),
        eos_token_ids=tok.eos_token_ids,
    )
    srv = EngineServer(engine, tok, "tiny", host="127.0.0.1", port=0)
    srv.start()
    yield srv
    srv.stop()


def _stream_completion(port: int, body: dict) -> list[dict]:
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(
        "POST", "/v1/completions",
        body=json.dumps({**body, "stream": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    raw = resp.read().decode()
    conn.close()
    assert resp.status == 200
    return [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ") and line != "data: [DONE]"
    ]


def test_streamed_request_populates_latency_histograms(server):
    n_tokens = 8
    events = _stream_completion(
        server.port,
        {"model": "tiny", "prompt": "hello", "max_tokens": n_tokens,
         "temperature": 0},
    )
    assert events[-1]["choices"][0]["finish_reason"] in ("length", "stop")
    # The serve loop drains engine timing after each step; /metrics also
    # syncs, so the scrape below is guaranteed current.
    status, body = http_get(f"127.0.0.1:{server.port}", "/metrics")
    assert status == 200
    m = server.metrics
    assert m.queue_wait.get() == 1
    assert m.prefill.get() == 1
    assert m.ttft.get() == 1
    assert m.e2e.get() == 1
    # One ITL gap per token after the first. (Greedy run to "length";
    # an early "stop" would emit fewer — bound instead of pin.)
    assert 1 <= m.itl.get() <= n_tokens - 1
    # Fake clock: every recorded latency is positive and finite.
    assert m.ttft.sum_for() > 0
    assert m.e2e.sum_for() > m.ttft.sum_for()  # e2e spans past first token


def test_metrics_exposition_has_nonzero_buckets_and_gauges(server):
    """Acceptance: /metrics exposes the four lifecycle histograms with
    nonzero bucket counts plus occupancy/KV-utilization gauges after a
    request runs through the server."""
    _stream_completion(
        server.port,
        {"model": "tiny", "prompt": "abc", "max_tokens": 4,
         "temperature": 0},
    )
    _, body = http_get(f"127.0.0.1:{server.port}", "/metrics")
    text = body.decode()
    from kubeai_tpu.metrics.registry import parse_prometheus_text

    parsed = parse_prometheus_text(text)
    for hist in (
        "kubeai_engine_ttft_seconds",
        "kubeai_engine_inter_token_latency_seconds",
        "kubeai_engine_queue_wait_seconds",
        "kubeai_engine_e2e_seconds",
        "kubeai_engine_prefill_seconds",
    ):
        assert parsed[(f"{hist}_count", ())] > 0, hist
        inf_bucket = parsed[(f"{hist}_bucket", (("le", "+Inf"),))]
        assert inf_bucket > 0, hist
    for gauge in (
        "kubeai_engine_batch_size",
        "kubeai_engine_kv_cache_utilization",
        "kubeai_engine_tokens_per_step",
        "kubeai_engine_step_duration_seconds",
        "kubeai_engine_slots_active",
        "kubeai_engine_requests_pending",
    ):
        assert f"# TYPE {gauge} gauge" in text, gauge


NEW_HISTOGRAMS = (
    "kubeai_engine_admit_host_seconds",
    "kubeai_engine_admit_wait_seconds",
    "kubeai_engine_loop_gap_seconds",
    "kubeai_engine_emit_busy_seconds",
    "kubeai_engine_emit_lag_seconds",
    "kubeai_engine_device_starved_seconds",
)
NEW_COUNTERS = (
    "kubeai_engine_admit_calls_total",
    "kubeai_engine_prefill_tokens_total",
    "kubeai_engine_decode_live_pages_total",
    "kubeai_engine_step_reaps_total",
    "kubeai_engine_dispatches_total",
    "kubeai_engine_sampler_chunks_total",
)


@pytest.fixture
def streamed(server):
    """One streamed request through the server, then a scrape."""
    from kubeai_tpu.metrics.registry import parse_prometheus_text

    n_tokens = 8
    events = _stream_completion(
        server.port,
        {"model": "tiny", "prompt": "hello", "max_tokens": n_tokens,
         "temperature": 0},
    )
    _, body = http_get(f"127.0.0.1:{server.port}", "/metrics")
    return server, events, parse_prometheus_text(body.decode())


@pytest.mark.parametrize("hist", NEW_HISTOGRAMS)
def test_host_timeline_histograms_count_after_one_stream(streamed, hist):
    server, _events, parsed = streamed
    counts = {k: v for k, v in parsed.items() if k[0] == f"{hist}_count"}
    assert counts and all(v > 0 for v in counts.values()), hist
    sums = [v for k, v in parsed.items() if k[0] == f"{hist}_sum"]
    assert all(v >= 0 for v in sums)
    if hist == "kubeai_engine_loop_gap_seconds":
        assert {dict(k[1])["part"] for k in counts} == {"fanout", "sync"}


def test_host_timeline_counters_and_names_after_one_stream(streamed):
    from kubeai_tpu.metrics.registry import lint_registry

    server, events, parsed = streamed
    m = server.metrics
    assert parsed[("kubeai_engine_admit_calls_total", ())] == 1
    useful = parsed[
        ("kubeai_engine_prefill_tokens_total", (("kind", "useful"),))]
    pad = parsed[("kubeai_engine_prefill_tokens_total", (("kind", "pad"),))]
    assert useful == 5 and useful + pad == 16  # "hello" in the 16-bucket
    # One page held the stream's tokens at each chunk it dispatched.
    chunks = parsed[("kubeai_engine_decode_live_pages_total", ())]
    assert 1 <= chunks == server.engine.live_kv["pages_total"] <= 8
    # One wait and one host observation per admission call; they stay
    # inside the step's prefill phase.
    assert m.admit_wait.get() == m.admit_host.get() == 1
    assert (m.admit_host.sum_for() + m.admit_wait.sum_for()
            <= m.step_phase.sum_for(phase="prefill"))
    # One lag observation per event the handler consumed, one busy
    # observation per burst of them.
    tokens = sum(len(e.get("token_ids", ())) for e in events)
    assert m.emit_lag.get() == tokens == m.generated_tokens.get()
    assert 1 <= m.emit_busy.get() <= tokens
    assert m.emit_lag.sum_for() > 0
    # The catalogue's rule: histograms end in _seconds, counters in _total.
    assert lint_registry(m.registry) == []
    names = {inst.name for inst in m.registry.metrics}
    assert set(NEW_HISTOGRAMS + NEW_COUNTERS) <= names


def _sampler_chunks(port: int) -> dict[str, float]:
    from kubeai_tpu.metrics.registry import parse_prometheus_text

    _, body = http_get(f"127.0.0.1:{port}", "/metrics")
    parsed = parse_prometheus_text(body.decode())
    return {
        path: parsed[("kubeai_engine_sampler_chunks_total", (("path", path),))]
        for path in ("argmax", "pool")
    }


def test_sampler_chunks_of_a_greedy_run_are_all_argmax(streamed):
    """What the device ran, chunk by chunk: a greedy stream never enters
    the sampler's candidate pool."""
    server, _events, _parsed = streamed
    chunks = _sampler_chunks(server.port)
    assert chunks["pool"] == 0 and chunks["argmax"] >= 2
    assert chunks == server.engine.sampler_chunks
    # As many as the reaps that read tokens back.
    assert sum(chunks.values()) <= sum(server.engine.step_reaps.values())


def test_sampler_chunks_read_pool_while_a_sampled_stream_lives(server):
    """One sampled stream among greedy ones: `pool` while it lives, then
    `argmax` again for the greedy stream that follows."""
    bodies = [
        {"model": "tiny", "prompt": "hello", "max_tokens": 24,
         "temperature": 0},
        {"model": "tiny", "prompt": "abc", "max_tokens": 12,
         "temperature": 0.9, "seed": 7},
    ]
    threads = [
        threading.Thread(target=_stream_completion, args=(server.port, b))
        for b in bodies
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    mixed = _sampler_chunks(server.port)
    # 11 of its tokens come of decode chunks of 4: three at the least.
    assert mixed["pool"] >= 3
    _stream_completion(server.port, bodies[0])
    after = _sampler_chunks(server.port)
    assert after["pool"] == mixed["pool"]
    assert after["argmax"] >= mixed["argmax"] + 5


def test_event_queue_stamps_each_hand_over():
    import time

    from kubeai_tpu.engine.server import _EventQueue

    q = _EventQueue()
    t0 = time.perf_counter()
    q.put("a")
    q.put("b")
    t1 = time.perf_counter()
    assert not q.empty() and q.qsize() == 2
    assert q.get(timeout=1) == "a"
    first = q.handed_at
    assert q.get_nowait() == "b"
    assert t0 <= first <= q.handed_at <= t1
    assert q.empty()


def test_step_stats_and_kv_utilization_move_during_decode(server):
    """kv_utilization and last_step_stats reflect live decode state."""
    eng = server.engine
    assert eng.kv_utilization() == 0.0
    _stream_completion(
        server.port,
        {"model": "tiny", "prompt": "xyz", "max_tokens": 6,
         "temperature": 0},
    )
    stats = eng.last_step_stats
    assert stats["tokens"] >= 1
    assert stats["duration_s"] > 0
    # All requests done: pool back to empty.
    assert eng.kv_utilization() == 0.0
    # The batch-size gauge saw the request while it ran.
    assert server.metrics.tokens_per_step.get() >= 0
    # The admin snapshot surfaces the same telemetry as JSON.
    _, body = http_get(f"127.0.0.1:{server.port}", "/v1/state")
    state = json.loads(body)
    assert "kv_utilization" in state
    assert state["last_step"]["tokens"] >= 1
    # What the newest decode chunk read: the one stream's one page.
    assert (state["kv_cache"]["live_slots"],
            state["kv_cache"]["live_pages"]) == (1, 1)


def test_itl_records_match_fake_clock_ticks(monkeypatch):
    """Unit-level check against the fake clock, no HTTP: the engine's
    drained timing records carry exact fake-clock multiples."""
    clock = FakeClock(tick=0.001)
    monkeypatch.setattr(engine_mod, "_now", clock)
    tok = ByteTokenizer()
    cfg = llama.LlamaConfig.tiny(vocab_size=tok.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=2, max_seq_len=64, decode_chunk=2),
        eos_token_ids=tok.eos_token_ids,
    )
    from kubeai_tpu.engine.sampling import SamplingParams

    rid = eng.add_request(
        tok.encode("hi"), SamplingParams(temperature=0.0, max_tokens=5)
    )
    events = []
    while eng.has_work():
        events.extend(eng.step())
    timing: dict[str, list[float]] = {}
    exemplars: dict[str, list[str]] = {}
    for rec in eng.drain_timing():
        timing.setdefault(rec[0], []).append(rec[1])
        if len(rec) > 2:
            exemplars.setdefault(rec[0], []).append(rec[2])
    assert len(timing["queue_wait"]) == 1
    assert len(timing["prefill"]) == 1
    assert len(timing["ttft"]) == 1
    assert len(timing["e2e"]) == 1
    n_tokens = len([e for e in events if e.rid == rid])
    assert len(timing["itl"]) == n_tokens - 1
    # ttft = queue_wait + prefill under one clock.
    assert timing["ttft"][0] == pytest.approx(
        timing["queue_wait"][0] + timing["prefill"][0]
    )
    # Every value is a positive multiple of the tick (fake clock always
    # advances between lifecycle events).
    for kind, vals in timing.items():
        for v in vals:
            assert v >= 0, (kind, v)
    assert timing["e2e"][0] > timing["ttft"][0]
    # ttft/itl records carry the request's exemplar tag so the server's
    # histograms can map a bucket back to a request.
    assert exemplars["ttft"] == [f"rid-{rid}"]
    assert all(tag == f"rid-{rid}" for tag in exemplars["itl"])
    # A second drain is empty — records land exactly once.
    assert eng.drain_timing() == []
