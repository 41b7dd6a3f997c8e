"""The per-layer metrics that read the program's own host-timeline counters
(admission, the serve loop's gaps, SSE emission): each is a data file naming
an existing reader kind, and each reads a number in the traced rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from perf import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LAYER_METRICS = os.path.join(ROOT, "perf", "layer_metrics")
NAMES = ("admit_host_ms_per_step", "admit_wait_mean_ms", "admit_calls",
         "prefill_useful_tokens", "prefill_pad_tokens", "loop_gap_ms_per_step",
         "emit_busy_ms_per_step", "emit_lag_mean_ms")
CELLS = {"": ("mistral-7b.decode-sat", "out_tok_s"),
         ".chat": ("mistral-7b.chat", "tpot_mean_ms")}


def spec_of(name):
    with open(os.path.join(LAYER_METRICS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The last line of `tiny-mistral.open --trace 1`, which stands in for
    the chat cell on the CPU."""
    tmp = tmp_path_factory.mktemp("rehearsal")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    with open(tmp / "out.txt", "w") as fo, open(tmp / "err.txt", "w") as fe:
        rc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
             "tiny-mistral.open", "--seed", str(2**31 + 24), "--seconds", "3",
             "--trace", "1", "--rehearse"],
            stdout=fo, stderr=fe, cwd=ROOT, env=env, timeout=600).returncode
    assert rc == 0, (tmp / "err.txt").read_text()[-2000:]
    line = json.loads((tmp / "out.txt").read_text().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    return line["metrics"]


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("name", NAMES)
def test_metric_is_a_data_file_of_an_existing_reader_kind(name, suffix):
    spec = spec_of(name + suffix)
    assert spec["reader"] in readers.READERS
    assert spec["metric"].startswith("kubeai_engine_")
    # The two cells' twins read the same series the same way.
    assert spec == spec_of(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"] if m["name"] == name + suffix]
    cell, moves = CELLS[suffix]
    assert len(entries) == 1
    # Its own cell first; a later cell that reports the same end-to-end
    # metric (the four-chip Mixtral cell) may follow.
    assert entries[0]["workloads"][0] == cell and entries[0]["moves"] == moves
    assert entries[0]["source"] == "program_counter"
    assert entries[0]["unit"] == ("ms" if "_ms" in name else "count")
    assert entries[0]["better"] == (
        "higher" if name == "prefill_useful_tokens" else "lower")


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_a_number_in_the_traced_rehearsal(rehearsal, name):
    m = rehearsal[name + ".chat"]
    assert isinstance(m["value"], (int, float)) and m["value"] >= 0
    if name != "prefill_pad_tokens":  # a prompt may fill its bucket
        assert m["value"] > 0


def test_the_rehearsals_counters_agree_with_each_other(rehearsal):
    value = {n: rehearsal[n + ".chat"]["value"] for n in NAMES}
    # Every admitted prompt was computed by some admission call.
    assert 1 <= value["admit_calls"] <= value["prefill_useful_tokens"]
    # Host and wait of admission lie inside the step's prefill phase.
    step_ms = rehearsal["step_mean_ms.chat"]["value"]
    prefill_ms = step_ms * rehearsal["prefill_step_share.chat"]["value"] / 100.0
    assert value["admit_host_ms_per_step"] <= prefill_ms


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise():
    """The parent commit has none of the series: the driver runs these
    files over it too."""
    obs = {"metrics0": {}, "metrics1": {}, "steps0": 0, "steps1": 10}
    for name in NAMES:
        value = readers.read(spec_of(name), obs)
        assert value is None or value == 0
