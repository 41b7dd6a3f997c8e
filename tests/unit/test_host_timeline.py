"""The host timeline: `StepProfiler.span` is the one way a host interval is
recorded (phase seconds AND a trace annotation), and the engine's admission
counters are counted where the work happens."""

import jax
import pytest

from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.fleet.profiler import PHASES, StepProfiler
from kubeai_tpu.models import llama


class Recorder:
    """Stands in for jax.profiler.TraceAnnotation: `rec(name, **attrs)` is a
    context manager with `set_metadata`; every closed span is kept."""

    def __init__(self):
        self.closed: list[dict] = []
        self.open: list[str] = []

    def __call__(self, name, **attrs):
        return _Annotation(self, name, attrs)

    def named(self, name):
        return [s for s in self.closed if s["name"] == name]


class _Annotation:
    def __init__(self, rec, name, attrs):
        self.rec, self.span = rec, {"name": name, "attrs": dict(attrs)}

    def set_metadata(self, **attrs):
        self.span["attrs"].update(attrs)

    def __enter__(self):
        self.span["parent"] = self.rec.open[-1] if self.rec.open else None
        self.rec.open.append(self.span["name"])
        return self

    def __exit__(self, *exc):
        assert self.rec.open.pop() == self.span["name"]
        self.span["raised"] = exc[0] is not None
        self.rec.closed.append(self.span)


# ---- the span helper (no JAX) ---------------------------------------------------


def test_span_records_phase_seconds_and_calls_the_annotation():
    rec = Recorder()
    prof = StepProfiler(annotate=rec)
    phases = prof.begin_step()
    with prof.span("step.sample", rows=3) as sp:
        pass
    prof.end_step()
    assert phases == {"sample": sp.seconds} and sp.seconds >= 0
    assert rec.closed == [{"name": "step.sample", "attrs": {"rows": 3},
                           "parent": None, "raised": False}]


def test_spans_nest_and_a_phase_adds_up_over_a_step():
    rec = Recorder()
    prof = StepProfiler(annotate=rec)
    phases = prof.begin_step()
    with prof.span("step.reap", barrier="none") as reap:
        with prof.span("step.readback") as a:
            pass
        with prof.span("step.readback") as b:
            pass
    prof.end_step()
    # step.reap is no phase: it exists only in a trace.
    assert phases == {"readback": pytest.approx(a.seconds + b.seconds)}
    assert reap.seconds >= a.seconds + b.seconds
    assert [(s["name"], s["parent"]) for s in rec.closed] == [
        ("step.readback", "step.reap"), ("step.readback", "step.reap"),
        ("step.reap", None)]


def test_span_survives_an_exception():
    rec = Recorder()
    prof = StepProfiler(annotate=rec)
    phases = prof.begin_step()
    with pytest.raises(KeyError):
        with prof.span("step.decode") as sp:
            raise KeyError("boom")
    assert phases["decode"] == sp.seconds > 0
    assert rec.closed[0]["raised"] is True and rec.open == []


def test_late_attributes_and_phase_spans_outside_a_step():
    rec = Recorder()
    prof = StepProfiler(annotate=rec)
    with prof.span("step.admit") as call:
        call.note(kind="batch", bucket=64)
    # No step is open: a phase span is trace-only (an out-of-step barrier's
    # reap stays out of the per-step histogram, as before).
    with prof.span("step.sample"):
        pass
    assert prof.drain() == []
    assert rec.closed[0]["attrs"] == {"kind": "batch", "bucket": 64}
    # Without an annotation (fleet/ imports no JAX) spans still time.
    bare = StepProfiler()
    phases = bare.begin_step()
    with bare.span("step.schedule") as sp:
        sp.note(ignored=True)
    assert phases == {"schedule": sp.seconds}


@pytest.mark.parametrize("phase", PHASES)
def test_every_phase_has_its_span_name(phase):
    prof = StepProfiler()
    phases = prof.begin_step()
    with prof.span("step." + phase):
        pass
    assert list(phases) == [phase]


# ---- the engine on the span helper ------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _engine(tiny, mesh=None, **kw):
    cfg, params = tiny
    eng = Engine("llama", cfg, params, mesh=mesh, cfg=EngineConfig(
        num_slots=4, max_seq_len=128, page_size=16, decode_chunk=4, **kw))
    rec = Recorder()
    eng.profiler._annotate = rec
    return eng, rec


def _drive(eng, prompts, max_tokens=6):
    for p in prompts:
        eng.add_request(p, SamplingParams(temperature=0.0, max_tokens=max_tokens))
    while eng.has_work():
        eng.step()


def _count_calls(monkeypatch, eng, *names):
    calls = {n: 0 for n in names}
    for n in names:
        def counted(*a, _orig=getattr(eng, n), _n=n):
            calls[_n] += 1
            return _orig(*a)
        monkeypatch.setattr(eng, n, counted)
    return calls


@pytest.mark.parametrize(
    "pp, kw, layout",
    [(1, {}, "stacked"), (1, {"kv_dtype": "int8"}, "per_layer"),
     (2, {}, "per_layer")],
    ids=["bf16-pool", "int8-pool", "pp-stages"],
)
def test_the_decode_span_names_the_kv_layout(tiny, devices8, pp, kw, layout):
    """A compile-time choice: every `step.decode` span of an engine carries
    the same word, the one `/v1/state` gives under `kv_cache`. A stage of
    a pipeline slices its layers' pages out of the stack."""
    from kubeai_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = (
        build_mesh(MeshConfig(pp=pp), devices=devices8[:pp]) if pp > 1
        else None
    )
    eng, rec = _engine(tiny, mesh=mesh, **kw)
    _drive(eng, [[1, 2, 3], [4, 5, 6, 7]])
    spans = rec.named("step.decode")
    assert spans and {s["attrs"]["kv_layout"] for s in spans} == {layout}
    assert eng.kv_cache_info()["kv_layout"] == layout


def test_the_decode_span_counts_the_live_kv(tiny, monkeypatch):
    """`live_slots` / `live_pages` on `step.decode`, the counter the server
    folds in and `/v1/state` say what the allocator holds for the ACTIVE
    slots: a request that finished leaves nothing behind, however many
    steps its slot then stays free."""
    from kubeai_tpu.engine.server import EngineMetrics, engine_state_snapshot

    eng, rec = _engine(tiny)
    page = eng.cfg.page_size
    walks = []  # what each page-growing walk left, read from outside it

    def walked(*a, _orig=eng._ensure_decode_pages, **kw):
        _orig(*a, **kw)
        held = {s: len(eng._alloc.pages_for(s))
                for s in range(eng.cfg.num_slots)}
        walks.append({
            "slots": len(eng._active),
            "pages": sum(-(-r.position // page)
                         for r in eng._active.values()),
            "held_active": sum(held[s] for s in eng._active),
            "held_free": sum(n for s, n in held.items()
                             if s not in eng._active),
            "free_rows": [int(eng._bt_host[s, 0])
                          for s in held if s not in eng._active],
        })

    monkeypatch.setattr(eng, "_ensure_decode_pages", walked)
    short = SamplingParams(temperature=0.0, max_tokens=3)
    long = SamplingParams(temperature=0.0, max_tokens=60)
    eng.add_request(list(range(1, 30)), short)
    eng.add_request(list(range(1, 40)), long)
    while eng.has_work():
        eng.step()
    spans = rec.named("step.decode")
    assert len(spans) == len(walks) >= 8
    for sp, w in zip(spans, walks):
        assert sp["attrs"]["live_slots"] == w["slots"]
        assert sp["attrs"]["live_pages"] == w["pages"]
        # The allocator holds those pages and the chunk's look-ahead, for
        # the active slots only; a free slot's row starts with -1.
        assert w["pages"] <= w["held_active"] <= w["pages"] + 2 * w["slots"]
        assert w["held_free"] == 0 and set(w["free_rows"]) <= {-1}
    # The short request's slot stayed free for several steps.
    alone = [w for w in walks if w["slots"] == 1]
    assert len(alone) >= 4 and walks[0]["slots"] == 2
    assert [w["pages"] for w in alone] == sorted(w["pages"] for w in alone)
    # The last walk sees the long request one or two chunks from its end.
    assert alone[-1]["pages"] in (-(-(39 + 60 - 9) // page),
                                  -(-(39 + 60 - 1) // page))
    total = sum(w["pages"] for w in walks)
    assert eng.live_kv == {"slots": 1, "pages": alone[-1]["pages"],
                           "pages_total": total}
    metrics = EngineMetrics()
    metrics.sync_engine(eng)
    metrics.sync_engine(eng)  # a counter: folded in once
    assert metrics.decode_live_pages.get() == total
    state = engine_state_snapshot(eng)["kv_cache"]
    assert (state["live_slots"], state["live_pages"]) == (
        1, alone[-1]["pages"])


def test_admission_counters_with_mixed_admissions(tiny, monkeypatch):
    """Batches of several buckets, a chunked prompt and a prefix-cache hit:
    useful + pad is the tokens of the shapes that ran, every device call is
    counted once, and host + wait stays inside the prefill phase."""
    eng, rec = _engine(tiny, prefill_chunk=32, prefix_cache=True)
    calls = _count_calls(
        monkeypatch, eng, "_prefill_admit_jit", "_stage_chunk_last_jit")
    shared = list(range(1, 41))  # 40 tokens: two full pages to share
    _drive(eng, [[5, 6, 7], [8, 9, 10, 11], list(range(60, 80))])  # 2 buckets
    _drive(eng, [shared + [50]])          # 41 > chunk: chunked, fills the cache
    _drive(eng, [shared + [51, 52]])      # same prefix: a prefix-cache hit
    admits = rec.named("step.admit")
    admits = [a for a in admits if "kind" in a["attrs"]]  # deferred passes carry none
    kinds = [a["attrs"]["kind"] for a in admits]
    assert set(kinds) == {"batch", "chunked", "prefix"}
    stats = eng.admit_stats
    assert stats["calls"] == len(admits) == (
        calls["_prefill_admit_jit"] + calls["_stage_chunk_last_jit"])
    assert stats["useful_tokens"] == sum(a["attrs"]["useful_tokens"] for a in admits)
    pad = stats["padded_tokens"] - stats["useful_tokens"]
    assert stats["useful_tokens"] + pad == sum(
        a["attrs"]["a_pad"] * a["attrs"]["bucket"] if a["attrs"]["kind"] == "batch"
        else a["attrs"]["padded_tokens"] for a in admits)
    for a in admits:
        at = a["attrs"]
        assert 0 < at["useful_tokens"] <= at["padded_tokens"]
        assert at["batch"] <= at["a_pad"] and a["parent"] == "step.prefill"
    hit = next(a["attrs"] for a in admits if a["attrs"]["kind"] == "prefix")
    assert hit["useful_tokens"] == 42 - 32  # two cached pages skipped
    # The two-bucket drive made one call per bucket.
    assert [a["attrs"]["bucket"] for a in admits[:2]] == [16, 32]
    assert admits[0]["attrs"]["batch"] == 2 and admits[0]["attrs"]["a_pad"] == 2
    timing = eng.drain_timing()
    host = [s for k, s, *_ in timing if k == "admit_host"]
    wait = [s for k, s, *_ in timing if k == "admit_wait"]
    assert len(host) == len(wait) == stats["calls"]
    prefill = sum(s for p, s in eng.profiler.drain() if p == "prefill")
    assert 0 < sum(host) + sum(wait) <= prefill
    # Children of one call: host before and after the wait.
    assert {s["parent"] for s in rec.named("admit.wait")} == {"step.admit"}
    assert len(rec.named("admit.host")) >= 2 * stats["calls"]


def test_phase_totals_keep_their_labels_and_stay_inside_wall_time(tiny):
    """kubeai_engine_step_phase_seconds reads what it read: the same phase
    vocabulary, each step's phases disjoint intervals inside its
    `serve.step`, together most of it."""
    import time

    eng, rec = _engine(tiny)
    t0 = time.perf_counter()
    eng.add_request([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=12))
    eng.step()
    eng.step()  # a chunk is in flight when the next prompt arrives
    _drive(eng, [[4, 5, 6, 7, 8]], max_tokens=12)
    _drive(eng, [[9, 10]], max_tokens=3)
    wall = time.perf_counter() - t0
    drained = eng.profiler.drain()
    totals = {}
    for phase, seconds in drained:
        totals[phase] = totals.get(phase, 0.0) + seconds
    assert set(totals) == {"prefill", "schedule", "dispatch", "decode",
                           "overlap_idle", "readback", "sample"}
    assert set(totals) <= set(PHASES)
    records = eng.profiler.recent()
    assert sum(totals.values()) == pytest.approx(
        sum(sum(r["phases_s"].values()) for r in records), abs=1e-6)
    assert 0.5 * wall < sum(totals.values()) <= wall
    steps = rec.named("serve.step")
    assert len(steps) >= len(records) and steps[0]["attrs"]["step"] == 1
    assert {"step", "batch", "pending"} == set(steps[0]["attrs"])
    # Every phase span and every reap sits under the step.
    reaps = rec.named("step.reap")
    assert reaps and {r["attrs"]["barrier"] for r in reaps} <= {
        "none", "admission", "seq_cap", "spec", "external"}
    # A slot was free whenever a prompt came: no reap was forced ahead.
    assert {r["attrs"]["barrier"] for r in reaps} == {"none"}
    assert all(r["attrs"]["chunk"] == 4 and r["attrs"]["rows"] >= 0 for r in reaps)
    for name in ("step.overlap_idle", "step.readback", "step.sample"):
        assert {s["parent"] for s in rec.named(name)} == {"step.reap"}


def test_an_out_of_step_barrier_is_traced_but_not_a_step_phase(tiny):
    eng, rec = _engine(tiny)
    rid = eng.add_request([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=40))
    eng.step()
    eng.step()  # a chunk is in flight now
    eng.profiler.drain()
    assert eng.cancel(rid)
    reaps = rec.named("step.reap")
    assert reaps[-1]["attrs"]["barrier"] == "external"
    assert eng.profiler.drain() == []
