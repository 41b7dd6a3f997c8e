"""The tiny CPU rehearsals of perf/run.py, end to end: the last line's keys
are exactly the contract's, with what was compared last; a broken timed path
and a lower precision both come out as not correct; off a TPU nothing is
printed."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def run(tmp_path, *args, rehearse=True):
    out, err = tmp_path / "out.txt", tmp_path / "err.txt"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["BENCH_RUN"] = "ignored"
    with open(out, "w") as fo, open(err, "w") as fe:
        rc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "perf", "run.py"), *args,
             *(["--rehearse"] if rehearse else [])],
            stdout=fo, stderr=fe, cwd=ROOT, env=env, timeout=600).returncode
    return rc, out.read_text().splitlines(), err.read_text()


def load_config(cell):
    with open(os.path.join(ROOT, "perf", "configs", cell.split(".")[0] + ".json")) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell,stands_for,trace", [
    ("tiny-mistral.closed", "mistral-7b.decode-sat", 0),
    ("tiny-mistral.open", "mistral-7b.chat", 0),
    ("tiny-mistral.open", "mistral-7b.chat", 1),
    ("tiny-mixtral.closed", "mistral-7b.decode-sat", 0),  # four virtual devices, tp=4
])
def test_rehearsal_prints_the_contracts_last_line(tmp_path, cell, stands_for, trace):
    rc, lines, err = run(tmp_path, "--workload", cell, "--seed", str(2**31 + 11),
                         "--seconds", "2", "--trace", str(trace))
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert list(line) == KEYS  # what was compared comes last
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # never a chip result
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["count"] == (4 if "mixtral" in cell else 1)
    b = load_benchmark()
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in b[section]
               if "workloads" not in m or stands_for in m["workloads"]}
    assert set(line["metrics"]) <= allowed
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], (int, float))
    if trace:
        # Host-clock and counter readers find something to read on the CPU;
        # trace readers find no TPU plane and are left out.
        assert {"step_mean_ms.chat", "queue_wait_mean_ms", "ttft_p95_ms.chat",
                "compiles_in_window.chat"} <= set(line["metrics"])
        assert line["metrics"]["compiles_in_window.chat"]["value"] == 0
        assert "decode_device_ms.chat" not in line["metrics"]
    else:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    compared = [l for l in lines if l.startswith("perf: correct: ") and "limit" in l]
    assert len(compared) == 3  # each number printed beside its limit
    # ... and again in the line, and as the last lines of stderr.
    assert set(line["compared"]) == {"max_gap", "mean_gap", "short", "failed"}
    limits = load_config(cell)["correct"]
    for name, (value, limit) in line["compared"].items():
        assert limit == limits.get(name, 0) and value <= limit
    tail = [l for l in err.splitlines() if l.startswith("perf: compared: ")]
    assert err.rstrip().splitlines()[-1] == tail[-1] and len(tail) == 4
    assert tail[0] == (f"perf: compared: max_gap = {line['compared']['max_gap'][0]}"
                       f"  limit {limits['max_gap']}")


def test_a_broken_timed_path_comes_out_not_correct(tmp_path):
    """The rest of a run with the chip check skipped (--rehearse) and every
    fifth token altered where the engine hands it out."""
    rc, lines, err = run(tmp_path, "--workload", "tiny-mistral.closed", "--seed", "5",
                         "--seconds", "2", "--trace", "0", "--break-path", "token")
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert any("max_gap" in l and "OVER" in l for l in lines)


def test_the_lower_precision_control_fails_the_limits(tmp_path):
    """The control at a size a test can hold: the reference in float8 in
    the program's place reads over the tiny preset's limits."""
    rc, lines, err = run(tmp_path, "--workload", "tiny-mistral.closed", "--seed", "6",
                         "--seconds", "2", "--trace", "0", "--control", "fp8")
    assert rc == 0, err[-2000:]
    limits = load_config("tiny-mistral.closed")["correct"]
    control = next(l for l in lines if l.startswith("perf: control fp8:"))
    nums = dict(re.findall(r"(max_gap|mean_gap) = ([0-9.e+-]+)", control))
    assert float(nums["max_gap"]) > 3 * limits["max_gap"]
    assert float(nums["mean_gap"]) > 3 * limits["mean_gap"]
    assert json.loads(lines[-1])["correct"] is True  # the program itself is sound


def test_off_a_tpu_there_is_no_result_line(tmp_path):
    rc, lines, err = run(tmp_path, "--workload", "mistral-7b.decode-sat", "--seed", "1",
                         "--seconds", "1", "--trace", "0", rehearse=False)
    assert rc != 0
    assert not any(l.startswith("{") for l in lines)
    assert "no TPU" in err
