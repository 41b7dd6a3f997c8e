"""perf/trace_reduce.py on a small recorded trace, and the reader kinds."""

import json
import os

import pytest

from perf import readers, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "trace_fixture.json")) as f:
        return json.load(f)


def test_union_counts_overlap_once():
    assert trace_reduce.union_seconds([(0, 2), (1, 2), (5, 1)]) == 4
    assert trace_reduce.union_seconds([]) == 0


def test_short_op_names_keep_name_and_shape():
    line = ("%fusion.181 = bf16[24,14336]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[16,"
            "4096,14336]{2,1,0} %get-tuple-element.1276), kind=kOutput")
    assert trace_reduce.short_op(line) == "fusion.181 bf16[24,14336]"
    assert trace_reduce.short_op("%while.35 = (s32[]{:T(128)}, s32[24]) while(") \
        == "while.35 s32[]"
    assert trace_reduce.module_base("jit__decode_chunk(17824356)") == "jit__decode_chunk"


def test_summary_of_the_recorded_slice(events):
    s = trace_reduce.summarize(events)
    ops = events["devices"][0]["ops"]
    assert 0 < s["busy_s"] <= s["window_s"]
    # Container operations (while) are left out of the sums: their time is
    # their children's.
    assert not any(k.startswith("while") for k in s["ops"])
    assert sum(s["ops"].values()) == pytest.approx(
        sum(d for n, _, d in ops if not n.startswith("while")))
    assert len(s["device_ops"]) == 10
    assert s["device_ops"][0][1] >= s["device_ops"][-1][1]
    n, total = trace_reduce.module_stats(s, "^jit__prefill_admit")
    assert n == 1 and total == pytest.approx(events["devices"][0]["modules"][0][2]) \
        or n == 1
    assert trace_reduce.op_seconds(s, "^_paged_pallas") > 0
    assert s["idle_gaps"] and s["idle_gaps"][0][0].startswith("before jit__")


def slice_of(programs, ops):
    return {"devices": [{"name": "/device:TPU:0", "modules": programs, "ops": ops}]}


def test_the_recorded_slice_holds_no_whole_program(events):
    """Its two programs are the slice's first and last: either may be cut,
    so neither is counted a whole one, and `modules` reads what it read."""
    s = trace_reduce.summarize(events)
    assert s["ops_in"] == {}
    assert trace_reduce.module_stats(s, "^jit__decode_chunk")[0] == 1
    assert trace_reduce.module_stats(s, "^jit__decode_chunk", whole=True) == (0, 0)
    assert trace_reduce.op_seconds(s, "^_paged_pallas", "^jit__decode_chunk") == 0


def test_operations_by_the_whole_program_they_ran_in():
    """A chunk cut at each edge of the slice, two whole chunks and an
    admission between: the cut ones count in `modules` and `ops` as they
    always did, and not among the programs a reader divides by."""
    programs = [["jit__chunk(1)", 0.0, 0.04], ["jit__chunk(1)", 0.1, 0.1],
                ["jit__admit(2)", 0.25, 0.02], ["jit__chunk(1)", 0.3, 0.1],
                ["jit__chunk(1)", 0.45, 0.03]]
    ops = [["gmm.1 bf16[8,8]", at, 0.01] for at in (0.0, 0.1, 0.15, 0.3, 0.35, 0.45)]
    ops += [["gmm.1 bf16[8,8]", 0.25, 0.005], ["gmm.9 bf16[64,8]", 0.26, 0.005],
            ["while.2 s32[]", 0.1, 0.1], ["fusion.3 f32[8]", 0.31, 0.002]]
    s = trace_reduce.summarize(slice_of(programs, ops))
    assert trace_reduce.module_stats(s, "^jit__chunk") == (4, pytest.approx(0.27))
    assert trace_reduce.module_stats(s, "^jit__chunk", whole=True) == (
        2, pytest.approx(0.2))
    assert trace_reduce.module_stats(s, "^jit__admit", whole=True) == (
        1, pytest.approx(0.02))
    assert trace_reduce.op_seconds(s, "^gmm") == pytest.approx(0.07)
    assert trace_reduce.op_seconds(s, "^gmm", "^jit__chunk") == pytest.approx(0.04)
    assert trace_reduce.op_seconds(s, "^gmm", "^jit__admit") == pytest.approx(0.01)
    assert trace_reduce.op_seconds(s, "^gmm", "^jit__") == pytest.approx(0.05)
    assert trace_reduce.op_seconds(s, "^gmm", "^jit__no_such") == 0
    assert trace_reduce.ops_in(s, "^gmm", "^jit__chunk") == {
        "gmm.1 bf16[8,8]": {"count": 4, "total_s": pytest.approx(0.04)}}
    assert set(s["ops_in"]["jit__chunk"]["ops"]) == {"gmm.1 bf16[8,8]", "fusion.3 f32[8]"}


@pytest.mark.parametrize("start, program", [
    (0.05, None),                   # inside the program the slice's start cut
    (1.0, "jit__a"), (1.0999, "jit__a"),
    (1.15, None),                   # in the gap between two programs
    (1.2, "jit__b"),
    (1.35, None),                   # inside the program the slice's end cut
])
def test_an_operation_belongs_to_the_program_that_holds_its_start(start, program):
    s = trace_reduce.summarize(slice_of(
        [["jit__b(2)", 1.2, 0.1], ["jit__a(1)", 1.0, 0.1],
         ["jit__a(1)", 0.0, 0.1], ["jit__b(2)", 1.31, 0.1]],
        [["gmm.1 bf16[8,8]", start, 0.01]]))
    assert s["ops"] == {"gmm.1 bf16[8,8]": 0.01}  # the slice's sum keeps it
    assert {k: v["ops"] for k, v in s["ops_in"].items() if v["ops"]} == (
        {program: {"gmm.1 bf16[8,8]": {"count": 1, "total_s": 0.01}}} if program else {})


def test_a_summary_without_programs_answers_a_question_by_program_with_nothing():
    old = {"modules": {}, "ops": {"gmm.1 bf16[8,8]": 1.0}}  # as a test may build one
    assert trace_reduce.op_seconds(old, "^gmm") == 1.0
    assert trace_reduce.op_seconds(old, "^gmm", "^jit__") == 0
    assert trace_reduce.ops_in(old, "^gmm", "^jit__") == {}
    assert trace_reduce.module_stats(old, "^jit__", whole=True) == (0, 0)


def test_no_device_events_is_nothing_to_read():
    assert trace_reduce.summarize({"devices": []}) is None
    assert readers.trace_idle_share({}, {"trace": None}) is None


PROM = """# HELP x
kubeai_engine_step_phase_seconds_sum{phase="prefill"} %s
kubeai_engine_step_phase_seconds_sum{phase="overlap_idle"} %s
kubeai_engine_step_phase_seconds_sum{phase="sample"} %s
kubeai_engine_queue_wait_seconds_sum %s
kubeai_engine_queue_wait_seconds_count %s
"""


def obs():
    return {"metrics0": readers.parse_prometheus(PROM % (1, 10, 1, 0.5, 5)),
            "metrics1": readers.parse_prometheus(PROM % (3, 16, 3, 2.5, 25)),
            "steps0": 100, "steps1": 120}


def test_histogram_readers_use_deltas_of_sum_and_count():
    o = obs()
    assert readers.histogram_mean(
        {"metric": "kubeai_engine_queue_wait_seconds", "scale": 1000.0}, o) \
        == pytest.approx(100.0)
    share = {"metric": "kubeai_engine_step_phase_seconds", "label": "phase"}
    assert readers.histogram_sum_share({**share, "numerator": ["prefill"]}, o) \
        == pytest.approx(20.0)
    assert readers.histogram_sum_share(
        {**share, "numerator": ["prefill", "sample"]}, o) == pytest.approx(40.0)
    assert readers.histogram_sum_per_step({**share, "scale": 1000.0}, o) \
        == pytest.approx(500.0)


def test_a_reader_with_nothing_to_read_returns_none():
    o = obs()
    o["metrics1"] = o["metrics0"]
    assert readers.histogram_mean({"metric": "kubeai_engine_queue_wait_seconds"}, o) is None
    assert readers.polled_mean({"series": "batch"}, {"polled": {}}) is None


def test_rooflines_from_costs_peaks_and_device_time(events):
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "perf",
                           "configs", "mistral-7b-v5e1.json")) as f:
        hf = json.load(f)
    trace = {"modules": {"jit__decode_chunk": {"count": 10, "total_s": 0.8}},
             "ops": {}, "window_s": 1.0, "busy_s": 0.9}
    o = {"trace": trace, "polled": {"kv_tokens": [0.0]}, "hf": hf, "chips": 1,
         "engine": {"decode_chunk": 8},
         "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    # 10 ms a step; the weights alone need 7.248 GB / 819 GB/s = 8.85 ms.
    assert readers.decode_hbm_share({"module": "^jit__decode_chunk"}, o) \
        == pytest.approx(88.5, abs=0.1)
    assert readers.trace_module_mean(
        {"module": "^jit__decode_chunk", "per": "decode_chunk", "scale": 1e3}, o) \
        == pytest.approx(10.0)
    assert readers.trace_idle_share({}, o) == pytest.approx(10.0)
