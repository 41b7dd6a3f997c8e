"""perf/host_spans.py on a small hand-made recording: device programs and
the engine thread's spans on one clock. Times are seconds; the gaps and what
covers them are worked out by hand below."""

import json
import os

import pytest

from perf import host_spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "host_spans_fixture.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def summary(events):
    with open(os.path.join(ROOT, "perf", "configs", "mistral-7b-v5e1.json")) as f:
        hf = json.load(f)
    return host_spans.summarize(events, hf, 197e12)


def test_innermost_segments_cut_nested_spans_into_self_time():
    spans = [["a", 0.0, 10.0, {}], ["b", 1.0, 3.0, {}], ["c", 2.0, 1.0, {}],
             ["b", 6.0, 2.0, {}]]
    assert host_spans.innermost_segments(spans) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"), (3.0, 4.0, "b"),
        (4.0, 6.0, "a"), (6.0, 8.0, "b"), (8.0, 10.0, "a")]
    # Two spans with nothing between or around them.
    assert host_spans.innermost_segments(
        [["x", 5.0, 1.0, {}], ["y", 1.0, 1.0, {}]]) == [
        (1.0, 2.0, "y"), (5.0, 6.0, "x")]


def test_the_engine_thread_is_the_one_that_steps(events, summary):
    assert summary["engine_thread"] == "python/3"
    assert host_spans.engine_line([{"line": "x", "spans": [["http.emit", 0, 1, {}]]}]) is None


def test_a_known_gap_goes_to_the_span_that_covered_it(summary):
    gaps = summary["idle_gaps"]
    by = dict(gaps["by_span"])
    # Gaps: 1.250-1.255 (before take), 1.256-1.300 (before prefill-admit),
    # 1.320-1.330 and 1.610-1.620 (before the decode chunks), 1.580-1.600.
    assert gaps["idle_s"] == pytest.approx(0.005 + 0.044 + 0.010 + 0.020 + 0.010)
    # 1.256-1.300: overlap_idle to 1.257, readback to 1.260, sample to 1.270,
    # step.prefill's own 1 ms, admit.host 1.271-1.299, admit.wait to 1.300.
    pairs = dict(gaps["by_span_and_next_program"])
    assert pairs["step.sample -> jit__prefill_admit"] == pytest.approx(0.010)
    assert pairs["step.readback -> jit__prefill_admit"] == pytest.approx(0.003)
    assert pairs["step.overlap_idle -> jit__prefill_admit"] == pytest.approx(0.001)
    assert pairs["step.overlap_idle -> jit__take"] == pytest.approx(0.005)
    # admit.host: 28 ms in the first step, 12 ms (1.587-1.599) in the second.
    assert pairs["admit.host -> jit__prefill_admit"] == pytest.approx(0.028 + 0.012)
    # Before the decode chunks: admit.wait's tail, admit.host's tail, the
    # self time of step.admit / step.prefill / serve.step, step.decode.
    assert pairs["step.decode -> jit__decode_chunk"] == pytest.approx(0.004 + 0.003)
    assert pairs["admit.host -> jit__decode_chunk"] == pytest.approx(0.003 + 0.004)
    # 1.580-1.585: the loop was between two steps and no span was open.
    assert by[host_spans.NO_SPAN] == pytest.approx(0.005)
    assert gaps["uncovered_share"] == pytest.approx(0.005 / gaps["idle_s"])
    assert sum(by.values()) == pytest.approx(gaps["idle_s"])
    assert gaps["by_span"][0][0] == "admit.host"  # ranked, largest first


def test_self_time_is_duration_minus_children(summary):
    spans = summary["spans"]
    assert spans["step.admit"]["count"] == 2
    assert spans["step.admit"]["total_s"] == pytest.approx(0.053 + 0.028)
    # First call: 53 - (28 + 22 + 3) = 0; second: 28 - (12 + 12 + 4) = 0.
    assert spans["step.admit"]["self_s"] == pytest.approx(0.0, abs=1e-9)
    assert spans["step.reap"]["self_s"] == pytest.approx(0.029 - 0.025)
    assert spans["admit.host"] == {
        "count": 4, "total_s": pytest.approx(0.047), "self_s": pytest.approx(0.047)}
    # Handler threads count too.
    assert spans["http.emit"]["count"] == 1
    assert spans["serve.step"]["count"] == 3


def test_admissions_join_their_device_runs_one_to_one(summary):
    p = summary["prefill"]
    assert (p["admissions"], p["prompts"], p["unmatched_runs"]) == (2, 3, 0)
    assert (p["useful_tokens"], p["padded_tokens"]) == (240, 320)
    assert p["device_s"] == pytest.approx(0.030)
    # 240 useful tokens of a 16-layer Mistral-7B in 30 ms: far under the
    # peak, and never over it.
    assert 0 < p["prefill_mxu_share"] < 100
    from perf import costs
    with open(os.path.join(ROOT, "perf", "configs", "mistral-7b-v5e1.json")) as f:
        hf = json.load(f)
    assert p["prefill_mxu_share"] == pytest.approx(
        100 * 240 * costs.prefill_flops_per_token(hf, 40) / (0.030 * 197e12))


def test_an_admission_cut_by_the_slices_edge_is_left_out(events):
    cut = json.loads(json.dumps(events))
    cut["devices"][0]["modules"] = cut["devices"][0]["modules"][3:]  # from 1.330
    s = host_spans.summarize(cut)
    assert s["prefill"]["admissions"] == 1 and s["prefill"]["useful_tokens"] == 40
    assert "prefill_mxu_share" not in s["prefill"]  # no configuration given


def test_a_trace_without_host_spans_or_without_a_device(events):
    # The parent commit's trace: device programs, no annotations.
    bare = host_spans.summarize({"devices": events["devices"], "host": []})
    assert bare["idle_gaps"] is None and bare["spans"] == {}
    assert host_spans.summarize({"devices": [], "host": events["host"]})["idle_gaps"] is None


def test_span_names_are_told_from_the_profilers_python_events():
    assert host_spans.SPAN.match("step.overlap_idle")
    assert host_spans.SPAN.match("http.emit")
    assert not host_spans.SPAN.match("$threading.py:1018 _bootstrap")
    assert not host_spans.SPAN.match("PjitFunction(_decode_chunk)")


def test_command_line_reads_a_kept_file(tmp_path, capsys):
    out = tmp_path / "kept.json"
    rc = host_spans.main([os.path.join(HERE, "host_spans_fixture.json"),
                          "--keep", str(out)])
    assert rc == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed["idle_gaps"]["by_span"][0][0] == "admit.host"
    kept = json.loads(out.read_text())
    assert "ops" not in kept["devices"][0] and kept["host"]
