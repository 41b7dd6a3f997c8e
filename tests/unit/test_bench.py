"""bench.py runs its measurement in-process, names the device in its line,
and measures a TPU or nothing: off a TPU it exits non-zero without a number
unless `--cpu` was typed."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(*flags, timeout=300):
    return subprocess.run(
        [sys.executable, BENCH, *flags],
        capture_output=True, text=True, timeout=timeout,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def _json_lines(stdout):
    return [
        json.loads(l) for l in stdout.splitlines() if l.strip().startswith("{")
    ]


def test_refuses_to_measure_off_tpu():
    """No chip, no `--cpu`: non-zero exit, the platform named, no result
    line a reader could mistake for a device number."""
    out = _run("--smoke")
    assert out.returncode != 0
    assert "'cpu'" in out.stderr and "--cpu" in out.stderr
    assert _json_lines(out.stdout) == []


@pytest.mark.slow
def test_cpu_run_prints_one_line_naming_the_device():
    out = _run("--smoke", "--cpu", "--measure-seconds", "5")
    assert out.returncode == 0, out.stderr[-2000:]
    (line,) = _json_lines(out.stdout)
    assert line["value"] > 0 and line["unit"] == "tok/s"
    assert line["platform"] == "cpu" and line["device_kind"]
    assert line["devices"] >= 1


@pytest.mark.slow
def test_prefill_measure_mode_reports_cache_ab():
    """--measure prefill: admission throughput over shared-prefix
    traffic, with hit accounting when the cache is on."""
    out = _run(
        "--smoke", "--cpu", "--measure", "prefill",
        "--page-size", "8", "--prefill-chunk", "8", "--prefix-cache",
    )
    assert out.returncode == 0, out.stderr[-2000:]
    (line,) = _json_lines(out.stdout)
    assert line["unit"] == "prompt tok/s"
    assert line["value"] > 0
    assert line["hit_tokens"] > 0  # shared prefix actually hit
    assert line["platform"] == "cpu"
