"""Preemption-tolerance suite: transparent mid-stream resume (proxy
continuation requests over REAL engines and real HTTP), engine-level
continuation token identity, the step watchdog, the event-boundary fault
injector, and the deterministic chaos simulation's invariants."""

import json
import threading
import time
import types

import http.client

import jax
import pytest

from testutil import http_get, http_post

from kubeai_tpu.crd.model import LoadBalancing, Model, ModelSpec
from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.metrics import Metrics
from kubeai_tpu.models import llama
from kubeai_tpu.operator.k8s.store import KubeStore
from kubeai_tpu.routing import proxy as proxy_mod
from kubeai_tpu.routing.loadbalancer import LoadBalancer
from kubeai_tpu.routing.modelclient import ModelClient
from kubeai_tpu.routing.openai_server import OpenAIServer
from kubeai_tpu.routing.proxy import ModelProxy, _SSEAccumulator
from kubeai_tpu.testing.faults import Fault, FaultPlan, faulty_send

pytestmark = pytest.mark.chaos

TOK = ByteTokenizer()
PROMPT = "the quick brown fox jumps over the lazy dog"


# ---- engine-level continuation (token identity, both prefill modes) --------


def _drain(eng, rids):
    out = {r: [] for r in rids}
    while eng.has_work():
        for ev in eng.step():
            if ev.rid in out:
                out[ev.rid].append(ev.token)
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(vocab_size=TOK.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(tiny, **overrides):
    cfg, params = tiny
    ecfg = EngineConfig(
        **{
            "num_slots": 4, "max_seq_len": 128, "page_size": 16,
            "decode_chunk": 2, **overrides,
        }
    )
    return Engine("llama", cfg, params, cfg=ecfg,
                  eos_token_ids=TOK.eos_token_ids)


@pytest.mark.parametrize("mode_kw", [
    {},
    {"prefill_chunk": 8},
], ids=["paged", "paged-chunked"])
@pytest.mark.parametrize("sampling", [
    {"temperature": 0.0, "seed": 7},
    {"temperature": 0.9, "top_k": 8, "seed": 7},
], ids=["greedy", "seeded"])
def test_engine_continuation_token_identical(tiny, mode_kw, sampling):
    """add_request(resume_tokens=prefix) resumes the sampling RNG at the
    correct step: the continuation equals the uninterrupted tail exactly,
    for greedy AND seeded sampling, in every prefill mode."""
    sp = SamplingParams(max_tokens=24, **sampling)
    prompt = TOK.encode(PROMPT)

    ref_eng = _engine(tiny, **mode_kw)
    ref = _drain(ref_eng, [ref_eng.add_request(prompt, sp)])
    ref_tokens = list(ref.values())[0]
    assert len(ref_tokens) > 8

    cut = 5
    res_eng = _engine(tiny, **mode_kw)  # a DIFFERENT replica resumes
    rid = res_eng.add_request(prompt, sp, resume_tokens=ref_tokens[:cut])
    got = _drain(res_eng, [rid])[rid]
    assert got == ref_tokens[cut:]


def test_engine_continuation_validation(tiny):
    eng = _engine(tiny)
    prompt = TOK.encode("hello")
    with pytest.raises(ValueError, match="max_tokens"):
        eng.add_request(prompt, SamplingParams(max_tokens=3),
                        resume_tokens=[1, 2, 3])
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.add_request(prompt, SamplingParams(max_tokens=1000),
                        resume_tokens=list(range(130)))
    eos = TOK.eos_token_ids[0]
    with pytest.raises(ValueError, match="stop token"):
        eng.add_request(prompt, SamplingParams(max_tokens=24),
                        resume_tokens=[5, eos])


# ---- full-stack transparent stream resume over real HTTP ---------------------


@pytest.fixture(scope="module")
def stack(tiny):
    """Two REAL engine servers (identical weights) behind the routing
    proxy: one model, two endpoints — the minimal preemption-tolerant
    fleet."""
    cfg, params = tiny
    servers = []
    for _ in range(2):
        eng = Engine(
            "llama", cfg, params,
            cfg=EngineConfig(
                num_slots=4, max_seq_len=128, page_size=16, decode_chunk=2,
            ),
            eos_token_ids=TOK.eos_token_ids,
        )
        srv = EngineServer(eng, TOK, "m1", host="127.0.0.1", port=0)
        srv.start()
        servers.append(srv)

    store = KubeStore()
    metrics = Metrics()
    lb = LoadBalancer(store, default_timeout=5, metrics=metrics)
    mc = ModelClient(store)
    front = OpenAIServer(ModelProxy(lb, mc, metrics=metrics), mc)
    front.start()

    m = Model(
        name="m1",
        spec=ModelSpec(
            url="hf://org/x",
            engine="KubeAITPU",
            features=["TextGeneration"],
            autoscaling_disabled=True,
            replicas=2,
            load_balancing=LoadBalancing(),
        ),
    )
    store.create(m.to_dict())
    for i, srv in enumerate(servers):
        store.create({
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {
                "name": f"model-m1-{i}",
                "namespace": "default",
                "labels": {"model": "m1"},
                "annotations": {
                    "model-pod-ip": "127.0.0.1",
                    "model-pod-port": str(srv.port),
                },
            },
            "status": {
                "conditions": [{"type": "Ready", "status": "True"}],
                "podIP": "127.0.0.1",
            },
        })
    lb.sync_model("m1")
    yield store, lb, front, metrics, servers
    front.stop()
    lb.stop()
    for srv in servers:
        srv.stop()


def _reset_breakers(lb):
    """Drop and re-add the model's endpoints: fresh EndpointHealth state,
    so breaker history from a previous test cannot leak forward."""
    lb.group("m1").reconcile_endpoints({})
    lb.sync_model("m1")


def _stream(front, body, headers=None):
    """POST a streaming request through the front door; returns the raw
    SSE transcript (reads until the server closes the stream)."""
    host, _, port = front.address.partition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    conn.request(
        "POST", "/openai/v1/chat/completions",
        body=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    raw = resp.read().decode()
    conn.close()
    return raw


def _deltas(raw):
    """(joined_text, finish_reasons, n_done) from an SSE transcript."""
    text, finishes, dones = "", [], 0
    for line in raw.splitlines():
        if not line.startswith("data: "):
            continue
        data = line[len("data: "):]
        if data == "[DONE]":
            dones += 1
            continue
        chunk = json.loads(data)
        for ch in chunk.get("choices", []):
            delta = (ch.get("delta") or {}).get("content")
            if delta:
                text += delta
            if ch.get("finish_reason"):
                finishes.append(ch["finish_reason"])
    return text, finishes, dones


@pytest.mark.parametrize("sampling", [
    {"temperature": 0.0, "seed": 11},
    {"temperature": 0.8, "top_k": 8, "seed": 11},
], ids=["greedy", "seeded"])
def test_stream_resume_token_identical_over_http(stack, monkeypatch, sampling):
    """THE acceptance bar: a chat stream whose serving replica dies
    mid-generation is resumed on the other replica and is token-identical
    to the uninterrupted stream — the client sees no error event and
    exactly one [DONE]."""
    _, lb, front, metrics, _ = stack
    _reset_breakers(lb)
    body = {
        "model": "m1",
        "messages": [{"role": "user", "content": PROMPT}],
        "stream": True,
        "max_tokens": 32,
        **sampling,
    }
    ref_raw = _stream(front, body)
    ref_text, ref_fin, ref_dones = _deltas(ref_raw)
    assert ref_text and ref_dones == 1

    # Kill the endpoint the next request will pick, at the 2nd SSE event.
    victim, _done = lb.await_best_address("m1")
    _done()
    resumes_before = metrics.proxy_stream_resumes.get(model="m1")
    plan = FaultPlan(
        [Fault(victim, "die_mid_stream", start=1, end=1, after_events=2)]
    )
    monkeypatch.setattr(proxy_mod, "_send", faulty_send(plan, proxy_mod._send))

    raw = _stream(front, body)
    assert "event: error" not in raw
    assert '"finish_reason": "error"' not in raw
    text, finishes, dones = _deltas(raw)
    assert dones == 1
    assert text == ref_text
    assert finishes == ref_fin
    # The resume actually happened (the fault actually fired).
    assert plan.counts[victim] == 1
    assert metrics.proxy_stream_resumes.get(model="m1") == resumes_before + 1
    # The mid-stream death still fed the endpoint's health window.
    snap = lb.group("m1").snapshot()
    assert snap["endpoints"][victim]["consecutive_failures"] >= 1


def test_stream_resume_survives_second_death(stack, monkeypatch):
    """Two consecutive mid-stream deaths (each on the endpoint serving at
    the time) still stitch into one clean stream — bounded resume count
    permitting."""
    _, lb, front, _, _ = stack
    _reset_breakers(lb)
    body = {
        "model": "m1",
        "messages": [{"role": "user", "content": PROMPT}],
        "stream": True, "max_tokens": 32, "temperature": 0.0, "seed": 3,
    }
    ref_text, _, _ = _deltas(_stream(front, body))
    plan = FaultPlan([
        Fault("*", "die_mid_stream", start=1, end=1, after_events=2),
    ])
    monkeypatch.setattr(proxy_mod, "_send", faulty_send(plan, proxy_mod._send))
    raw = _stream(front, body)
    assert "event: error" not in raw
    text, _, dones = _deltas(raw)
    assert dones == 1
    assert text == ref_text
    # Both endpoints died once each (first attempt + first resume), the
    # second resume completed the stream.
    assert sum(plan.counts.values()) >= 3


def test_stream_resume_budget_exhausted_falls_back_to_error(stack, monkeypatch):
    """When every dispatch dies mid-stream, the bounded resume count runs
    dry and the client gets the PR-3 terminal error contract back."""
    _, lb, front, metrics, _ = stack
    _reset_breakers(lb)
    body = {
        "model": "m1",
        "messages": [{"role": "user", "content": PROMPT}],
        "stream": True, "max_tokens": 32, "temperature": 0.0, "seed": 3,
    }
    plan = FaultPlan([Fault("*", "die_mid_stream", after_events=1)])
    monkeypatch.setattr(proxy_mod, "_send", faulty_send(plan, proxy_mod._send))
    failures_before = metrics.proxy_stream_resume_failures.get(model="m1")
    raw = _stream(front, body)
    assert '"finish_reason": "error"' in raw
    assert "event: error" in raw
    assert raw.rstrip().endswith("data: [DONE]")
    assert (
        metrics.proxy_stream_resume_failures.get(model="m1")
        == failures_before + 1
    )
    # Bounded: at most 1 original attempt + MAX_STREAM_RESUMES
    # continuation dispatches (fewer when breaker history from earlier
    # streams opens a circuit first — either way the budget is finite).
    assert 2 <= sum(plan.counts.values()) <= 1 + proxy_mod.MAX_STREAM_RESUMES


def test_unary_requests_unaffected_by_resume_path(stack):
    _, lb, front, _, _ = stack
    _reset_breakers(lb)
    st, body = http_post(
        front.address, "/openai/v1/chat/completions",
        {
            "model": "m1",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 8, "temperature": 0.0, "seed": 1,
        },
    )
    assert st == 200
    out = json.loads(body)
    assert out["choices"][0]["message"]["content"]


# ---- SSE accumulator ---------------------------------------------------------


def test_sse_accumulator_parses_across_chunk_boundaries():
    acc = _SSEAccumulator()
    ev1 = (
        b'data: {"choices": [{"index": 0, "delta": {"content": "hel"}, '
        b'"finish_reason": null}], "token_ids": [104, 101]}\n\n'
    )
    ev2 = (
        b'data: {"choices": [{"index": 0, "delta": {"content": "lo"}, '
        b'"finish_reason": null}], "token_ids": [108]}\n\n'
    )
    blob = ev1 + ev2
    # Feed byte-by-byte: parsing must not depend on TCP segmentation.
    for i in range(len(blob)):
        acc.feed(blob[i:i + 1])
    assert acc.token_ids == [104, 101, 108]
    assert acc.emitted_chars == 5
    assert not acc.finished and not acc.done_seen
    acc.feed(
        b'data: {"choices": [{"index": 0, "delta": {}, '
        b'"finish_reason": "stop"}]}\n\ndata: [DONE]\n\n'
    )
    assert acc.finished and acc.done_seen


def test_sse_accumulator_completions_text_field():
    acc = _SSEAccumulator()
    acc.feed(
        b'data: {"choices": [{"index": 0, "text": "abcd", '
        b'"finish_reason": null}], "token_ids": [1, 2]}\n\n'
    )
    assert acc.emitted_chars == 4
    assert acc.token_ids == [1, 2]


# ---- event-boundary fault injector ------------------------------------------


def test_event_dying_response_is_deterministic():
    from kubeai_tpu.testing.faults import _EventDyingResponse

    class FakeBody:
        def __init__(self, blob, step=3):
            self.blob, self.step = blob, step

        def read1(self, n=-1):
            out, self.blob = self.blob[:self.step], self.blob[self.step:]
            return out

    blob = b"data: one\n\ndata: two\n\ndata: three\n\n"
    # Regardless of the underlying read granularity, exactly 2 complete
    # events come out, then the injected death.
    for step in (1, 3, 7, 1000):
        r = _EventDyingResponse(FakeBody(blob, step), after_events=2)
        assert r.read1() == b"data: one\n\n"
        assert r.read1() == b"data: two\n\n"
        with pytest.raises(ConnectionResetError):
            r.read1()


# ---- step watchdog -----------------------------------------------------------


class _StuckEngine:
    """has_work() forever, step() never progresses — a wedged device."""

    def __init__(self):
        self.cfg = types.SimpleNamespace(max_seq_len=128)
        self._block = threading.Event()

    def loaded_adapters(self):
        return []

    def has_work(self):
        return True

    def step(self):
        self._block.wait(timeout=30)
        return []

    def cancel(self, rid):
        return False

    num_active = 1
    num_pending = 0


def test_watchdog_flips_health_and_fires_action():
    fired = threading.Event()
    srv = EngineServer(
        _StuckEngine(), TOK, "m1", host="127.0.0.1", port=0,
        watchdog_timeout=0.2, watchdog_action=fired.set,
    )
    srv.start()
    try:
        assert fired.wait(timeout=5.0), "watchdog never fired"
        assert not srv.healthy()
        assert srv.wedged
        st, body = http_get(f"127.0.0.1:{srv.port}", "/health")
        assert st == 503
        assert json.loads(body)["status"] == "wedged"
        assert srv.metrics.watchdog_stalls.get() == 1
        assert srv.metrics.watchdog_wedged.get() == 1
    finally:
        srv._stop.set()
        srv.engine._block.set()
        srv.stop()


class _IdleEngine(_StuckEngine):
    def has_work(self):
        return False

    def step(self):
        return []


def test_watchdog_ignores_idle_engine():
    srv = EngineServer(
        _IdleEngine(), TOK, "m1", host="127.0.0.1", port=0,
        watchdog_timeout=0.1, watchdog_action=lambda: None,
    )
    srv.start()
    try:
        time.sleep(0.5)  # several watchdog polls with zero work
        assert srv.healthy()
        st, _ = http_get(f"127.0.0.1:{srv.port}", "/health")
        assert st == 200
    finally:
        srv.stop()


def test_watchdog_tracks_progress_of_live_engine(tiny):
    """A healthy engine serving real work never trips the watchdog even
    with a timeout shorter than the whole generation."""
    eng = _engine(tiny)
    srv = EngineServer(
        eng, TOK, "m1", host="127.0.0.1", port=0,
        # Wall-clock watchdog: 4 s tolerates scheduler stalls under a
        # loaded test box while staying well under the request timeout.
        watchdog_timeout=4.0, watchdog_action=lambda: None,
    )
    srv.start()
    try:
        st, body = http_post(
            f"127.0.0.1:{srv.port}", "/v1/completions",
            {"model": "m1", "prompt": PROMPT, "max_tokens": 24,
             "temperature": 0.0},
            timeout=60,
        )
        assert st == 200
        assert srv.healthy()
        assert srv.metrics.watchdog_stalls.get() == 0
    finally:
        srv.stop()


# ---- engine-server continuation endpoint ------------------------------------


def test_server_rejects_malformed_resume(stack):
    _, _, _, _, servers = stack
    addr = f"127.0.0.1:{servers[0].port}"
    base = {
        "model": "m1", "prompt": "x", "max_tokens": 8, "stream": True,
    }
    for bad, msg in [
        ({"kubeai_resume": "nope"}, "must be an object"),
        ({"kubeai_resume": {"token_ids": []}}, "non-empty"),
        ({"kubeai_resume": {"token_ids": [1.5]}}, "non-empty int list"),
        ({"kubeai_resume": {"token_ids": [1], "emitted": -1}}, ">= 0"),
        ({"kubeai_resume": {"token_ids": [1]}, "n": 2}, "n == 1"),
    ]:
        st, body = http_post(addr, "/v1/completions", {**base, **bad})
        assert st == 400, (bad, body)
        assert msg in json.loads(body)["error"]["message"]


def test_server_resume_too_long_rejected(stack):
    _, _, _, _, servers = stack
    addr = f"127.0.0.1:{servers[0].port}"
    st, body = http_post(
        addr, "/v1/completions",
        {"model": "m1", "prompt": "x", "max_tokens": 4,
         "kubeai_resume": {"token_ids": [1, 2, 3, 4, 5]}},
    )
    assert st == 400
    assert "nothing left to generate" in json.loads(body)["error"]["message"]


# ---- chaos simulation invariants (fast configuration) ------------------------


def test_preemption_simulation_invariants():
    import importlib
    import os
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                     "benchmarks"),
    )
    sim = importlib.import_module("preemption_sim")
    summary = sim.run_sim(
        n_streams=40, tokens_per_stream=24, kill_every=4, rounds=4,
    )
    violations = sim.check_invariants(summary)
    assert violations == [], "\n".join(
        violations + [json.dumps(summary, indent=2)]
    )
