"""The tiny CPU rehearsals of perf/run.py, end to end: the last line's keys
are exactly the contract's, with what was compared last; a broken timed path
and a lower precision both come out as not correct, for a dense
configuration and for one that routes; off a TPU nothing is printed."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
ROUTED = {"route_rows_bad", "followed_share", "route_trail"}


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
    ("tiny-mixtral.closed", "mixtral-8x7b.decode-sat", 0),  # four virtual devices, tp=4
    ("tiny-mixtral.closed", "mixtral-8x7b.decode-sat", 1),
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
    if trace and "mixtral" in cell:
        # The router's metrics are read where a router is.
        assert {"routes_ms_per_step", "moe_imbalance", "route_followed_share",
                "step_mean_ms"} <= set(line["metrics"])
        assert "collective_share" not in line["metrics"]  # no TPU plane
        assert line["metrics"]["route_followed_share"]["value"] == (
            100.0 * line["compared"]["followed_share"][0])
    elif trace:
        # Host-clock and counter readers find something to read on the CPU;
        # trace readers find no TPU plane and are left out.
        assert {"step_mean_ms.chat", "queue_wait_mean_ms", "ttft_p95_ms.chat",
                "compiles_in_window.chat"} <= set(line["metrics"])
        assert line["metrics"]["compiles_in_window.chat"]["value"] == 0
        assert "decode_device_ms.chat" not in line["metrics"]
    else:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    # A dense configuration compares the three numbers it always compared;
    # one that routes, three more on the routes the run handed over.
    limits = load_config(cell)["correct"]
    dense = {"max_gap", "mean_gap", "short"}
    assert set(limits) == (dense | ROUTED if "mixtral" in cell else dense)
    compared = [l for l in lines if l.startswith("perf: correct: ") and "limit" in l]
    assert len(compared) == len(limits)  # each number printed beside its limit
    # ... and again in the line, and as the last lines of stderr.
    assert set(line["compared"]) == set(limits) | {"failed"}
    assert any("routes asked of every request" in l for l in lines) == ("mixtral" in cell)
    for name, (value, limit) in line["compared"].items():
        assert limit == limits.get(name, 0) and value <= limit
    tail = [l for l in err.splitlines() if l.startswith("perf: compared: ")]
    assert err.rstrip().splitlines()[-1] == tail[-1] and len(tail) == len(limits) + 1
    assert tail[0] == (f"perf: compared: max_gap = {line['compared']['max_gap'][0]}"
                       f"  limit {limits['max_gap']}")


@pytest.mark.parametrize("cell,fault,over", [
    ("tiny-mistral.closed", "token", {"max_gap"}),
    ("tiny-mixtral.closed", "token", {"max_gap"}),
    ("tiny-mixtral.closed", "route", {"followed_share", "route_trail"}),
])
def test_a_broken_timed_path_comes_out_not_correct(tmp_path, cell, fault, over):
    """The rest of a run with the chip check skipped (--rehearse) and a fault
    planted underneath: every fifth token altered where the engine hands it
    out, or the router's expert columns permuted in the served weights."""
    rc, lines, err = run(tmp_path, "--workload", cell, "--seed", "5",
                         "--seconds", "2", "--trace", "0", "--break-path", fault)
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["failed"] == 0
    found = {name for name, (value, limit) in line["compared"].items()
             if value is None or value > limit}
    assert over <= found
    for name in over:
        assert any(name in l and "OVER" in l for l in lines)
    if fault == "route":
        # The rows themselves are whole: the program handed over what it took.
        assert line["compared"]["route_rows_bad"] == [0, 0]
        assert line["compared"]["followed_share"][0] > 0.5


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


def test_the_routed_control_takes_its_own_sets_and_fails_the_limits(tmp_path):
    """A routed reference in float8 takes its own expert sets, the float32
    reference follows those, and the readings land over the limits: on the
    gaps and on the router's two numbers."""
    rc, lines, err = run(tmp_path, "--workload", "tiny-mixtral.closed", "--seed", "6",
                         "--seconds", "2", "--trace", "0", "--control", "fp8")
    assert rc == 0, err[-2000:]
    limits = load_config("tiny-mixtral.closed")["correct"]
    control = next(l for l in lines if l.startswith("perf: control fp8: max_gap"))
    nums = {k: float(v) for k, v in re.findall(r"(\w+) = ([0-9.e+-]+)", control)}
    assert nums["followed_share"] > limits["followed_share"]
    assert nums["mean_gap"] > limits["mean_gap"]
    over = next(l for l in lines if l.startswith("perf: control fp8 lands over: "))
    assert {"followed_share", "mean_gap"} <= set(over.split("over: ")[1].split(", "))
    line = json.loads(lines[-1])
    assert line["correct"] is True  # the program itself is sound
    assert line["compared"]["followed_share"][0] < nums["followed_share"] / 2


def test_off_a_tpu_there_is_no_result_line(tmp_path):
    rc, lines, err = run(tmp_path, "--workload", "mistral-7b.decode-sat", "--seed", "1",
                         "--seconds", "1", "--trace", "0", rehearse=False)
    assert rc != 0
    assert not any(l.startswith("{") for l in lines)
    assert "no TPU" in err
