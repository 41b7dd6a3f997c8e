"""The per-layer metrics that read the device queue's book
(`kubeai_engine_device_starved_seconds`, `kubeai_engine_dispatches_total`):
six data files of reader kinds that were there, six entries appended to
BENCHMARK.json, and a number for each in the traced CPU rehearsals."""

import json
import os
import subprocess
import sys

import pytest

from perf import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LAYER_METRICS = os.path.join(ROOT, "perf", "layer_metrics")
NAMES = {"device_starved_ms_per_step": "ms",
         "starved_before_prefill_share": "%",
         "dispatch_drained_share": "%"}
# suffix -> (the cells that report it, the end-to-end metric it moves, the
# rehearsal that stands in for the first of them on the CPU)
CELLS = {
    "": (["mistral-7b.decode-sat", "mixtral-8x7b.decode-sat",
          "sdar-30b-a3b.decode-sat"], "out_tok_s", "tiny-mistral.closed"),
    ".chat": (["mistral-7b.chat"], "tpot_mean_ms", "tiny-mistral.open"),
}
SERIES = ("kubeai_engine_device_starved_seconds",
          "kubeai_engine_dispatches_total")


def spec_of(name):
    with open(os.path.join(LAYER_METRICS, name + ".json")) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("name", sorted(NAMES))
def test_metric_is_a_data_file_of_a_reader_kind_that_was_there(name, suffix):
    spec = spec_of(name + suffix)
    assert readers.kind(spec["reader"]) is not None
    assert spec["reader"] in (
        "histogram_sum_per_step", "histogram_sum_share", "counter_ratio")
    assert spec == spec_of(name)  # the two cells' twins read alike
    read = [spec[k]["metric"] for k in ("numerator", "denominator")] \
        if spec["reader"] == "counter_ratio" else [spec["metric"]]
    assert set(read) <= set(SERIES)


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("name", sorted(NAMES))
def test_metric_is_named_by_one_entry_whose_cells_report_what_it_moves(
        name, suffix):
    b = benchmark()
    entries = [m for m in b["per_layer"] if m["name"] == name + suffix]
    cells, moves, _ = CELLS[suffix]
    assert entries == [{
        "name": name + suffix, "unit": NAMES[name], "better": "lower",
        "source": "program_counter", "layer": "Device", "moves": moves,
        "workloads": cells}]
    reported_by = next(m for m in b["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(reported_by["workloads"])
    # Appended: the six are the list's last entries.
    assert name + suffix in [m["name"] for m in b["per_layer"][-6:]]


def test_every_data_file_of_the_book_is_named_by_an_entry():
    files = {f[:-len(".json")] for f in os.listdir(LAYER_METRICS)
             if any(s in open(os.path.join(LAYER_METRICS, f)).read()
                    for s in SERIES)}
    assert files == {n + s for n in NAMES for s in CELLS}
    assert files <= {m["name"] for m in benchmark()["per_layer"]}


@pytest.fixture(scope="module", params=sorted(CELLS))
def rehearsal(request, tmp_path_factory):
    """The metrics of a traced CPU rehearsal's last line, and the suffix of
    the cell it stands in for."""
    suffix = request.param
    tmp = tmp_path_factory.mktemp("rehearsal")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    with open(tmp / "out.txt", "w") as fo, open(tmp / "err.txt", "w") as fe:
        rc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
             CELLS[suffix][2], "--seed", str(2**31 + 40), "--seconds", "3",
             "--trace", "1", "--rehearse"],
            stdout=fo, stderr=fe, cwd=ROOT, env=env, timeout=600).returncode
    assert rc == 0, (tmp / "err.txt").read_text()[-2000:]
    line = json.loads((tmp / "out.txt").read_text().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    return line["metrics"], suffix


def test_the_rehearsals_line_holds_the_three_names(rehearsal):
    metrics, suffix = rehearsal
    for name, unit in NAMES.items():
        m = metrics[name + suffix]
        assert m["unit"] == unit
        assert isinstance(m["value"], (int, float)) and m["value"] >= 0
    assert metrics["device_starved_ms_per_step" + suffix]["value"] > 0
    for share in ("starved_before_prefill_share", "dispatch_drained_share"):
        assert metrics[share + suffix]["value"] <= 100.0
    # Starved seconds lie inside the engine thread's time: a step's share
    # of them is under the step and the loop's gap around it.
    assert metrics["device_starved_ms_per_step" + suffix]["value"] <= (
        metrics["step_mean_ms" + suffix]["value"]
        + metrics["loop_gap_ms_per_step" + suffix]["value"])


def test_a_program_without_the_book_reads_nothing_and_raises_nothing():
    """The parent commit has neither series: the share and the ratio are
    left out of its line, the per-step sum reads 0."""
    obs = {"metrics0": {}, "metrics1": {}, "steps0": 10, "steps1": 30}
    assert readers.read(spec_of("starved_before_prefill_share"), obs) is None
    assert readers.read(spec_of("dispatch_drained_share"), obs) is None
    assert readers.read(spec_of("device_starved_ms_per_step"), obs) == 0.0


def test_the_three_read_what_the_series_say():
    text0 = "\n".join([
        'kubeai_engine_device_starved_seconds_sum{after="admit",before="decode"} 1.0',
        'kubeai_engine_device_starved_seconds_sum{after="reap_admission",before="prefill"} 2.0',
        'kubeai_engine_dispatches_total{before="decode",queue="busy"} 5',
        'kubeai_engine_dispatches_total{before="decode",queue="drained"} 1',
        'kubeai_engine_dispatches_total{before="prefill",queue="drained"} 7',
    ])
    text1 = "\n".join([
        'kubeai_engine_device_starved_seconds_sum{after="admit",before="decode"} 1.1',
        'kubeai_engine_device_starved_seconds_sum{after="admit",before="prefill"} 0.05',
        'kubeai_engine_device_starved_seconds_sum{after="reap_admission",before="prefill"} 2.25',
        'kubeai_engine_dispatches_total{before="decode",queue="busy"} 35',
        'kubeai_engine_dispatches_total{before="decode",queue="drained"} 7',
        'kubeai_engine_dispatches_total{before="decode",queue="empty"} 4',
        'kubeai_engine_dispatches_total{before="prefill",queue="drained"} 9',
    ])
    obs = {"metrics0": readers.parse_prometheus(text0),
           "metrics1": readers.parse_prometheus(text1),
           "steps0": 100, "steps1": 140}
    assert readers.read(spec_of("device_starved_ms_per_step"), obs) == (
        pytest.approx(0.4 / 40 * 1000.0))
    assert readers.read(spec_of("starved_before_prefill_share"), obs) == (
        pytest.approx(100.0 * 0.3 / 0.4))
    assert readers.read(spec_of("dispatch_drained_share.chat"), obs) == (
        pytest.approx(100.0 * 6 / 40))
