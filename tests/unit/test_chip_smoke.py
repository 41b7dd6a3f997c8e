"""The parts of chip_smoke.py that need no chip: it refuses to run off a
TPU, its checkpoint writer produces what the server's loader reads, and the
compile-cache rule it shares with the server holds."""

import inspect
import json
import os
import subprocess
import sys

import jax
import numpy as np

from kubeai_tpu.engine import coldstart
from kubeai_tpu.engine.weights import load_hf_config, load_params
from kubeai_tpu.models.registry import get_model_family

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def test_default_invocation_fails_off_tpu_and_names_the_platform():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert "no TPU: JAX found platform 'cpu'" in out.stdout
    # Stopped at the device leg: no kernels, no server, no result line.
    assert "starting:" not in out.stdout
    assert '"ok"' not in out.stdout


def test_last_line_is_the_result_object_and_nothing_more(monkeypatch, capsys):
    """The driver reads the last line of stdout: the keys "ok" and "device"
    and no other. What was served is on the labelled line before it."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "cache_dir": "/nonexistent"}
    monkeypatch.setattr(
        chip_smoke, "run_leg", lambda args, deadline: dict(device)
    )
    monkeypatch.setattr(
        chip_smoke, "leg_server",
        lambda device, sizes, deadline: {"boot_to_ready_s": 1.0},
    )
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    summary = json.loads(lines[-2].removeprefix("chip_smoke: summary: "))
    assert summary["depth"] == 16 and summary["mesh"] == "1"
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_parent_module_stays_off_jax():
    """Importing chip_smoke (what the parent process does) pulls in neither
    jax nor the package that imports it."""
    code = (
        "import sys, chip_smoke; "
        "assert 'jax' not in sys.modules and 'kubeai_tpu' not in sys.modules"
    )
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True, timeout=60)


def test_checkpoint_round_trips_through_the_server_loader(tmp_path):
    hf = dict(
        chip_smoke.MISTRAL_7B, vocab_size=512, hidden_size=64,
        intermediate_size=96, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16,
    )
    pool_len = 10_007
    nbytes = chip_smoke.write_checkpoint(str(tmp_path), hf, seed=3, pool_len=pool_len)
    assert nbytes == sum(
        os.path.getsize(tmp_path / f) for f in os.listdir(tmp_path)
        if f.endswith(".safetensors")
    )

    cfg_json = load_hf_config(str(tmp_path))
    family = get_model_family(cfg_json["architectures"][0])
    assert family.name == "llama"
    cfg = family.config_from_hf(cfg_json)
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_size) == (
        2, 4, 2, 16
    )
    assert cfg.rope_theta == 1e6
    params = load_params(family.name, str(tmp_path), cfg)

    # Host arrays in the stacked layout, ready for shard_params.
    assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(params))
    lay = params["layers"]
    assert lay["wq"].shape == (2, 64, 64) and lay["wk"].shape == (2, 64, 32)
    assert lay["w_gate"].shape == (2, 64, 96) and lay["w_down"].shape == (2, 96, 64)
    assert params["embed"].shape == (512, 64)
    assert str(params["embed"].dtype) == "bfloat16"

    # Values: norms are ones; weights are the seeded N(0, 0.02) pool read
    # cyclically; lm_head is zero past the ByteTokenizer's 256 rows.
    np.testing.assert_array_equal(np.asarray(lay["input_norm"], np.float32), 1.0)
    rng = np.random.default_rng(3)
    pool = rng.standard_normal(pool_len, np.float32) * 0.02
    start = int(rng.integers(pool_len))  # the first tensor written: embed
    embed = np.asarray(params["embed"], np.float32).ravel()
    np.testing.assert_allclose(
        embed, np.resize(np.roll(pool, -start), embed.size), rtol=2 ** -8
    )
    w = np.asarray(lay["w_up"], np.float32)
    assert 0.015 < w.std() < 0.025 and np.all(np.isfinite(w))
    head = np.asarray(params["lm_head"], np.float32)
    assert np.any(head[:256] != 0) and not np.any(head[256:])
    # Two layers draw from different offsets.
    assert not np.array_equal(lay["wq"][0], lay["wq"][1])


def _recorded_cache_dir_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    coldstart.enable_compilation_cache()
    return [v for k, v in calls if k == "jax_compilation_cache_dir"]


def test_cache_dir_from_the_environment_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert _recorded_cache_dir_updates(monkeypatch) == []


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert _recorded_cache_dir_updates(monkeypatch) == [coldstart.DEFAULT_CACHE_DIR]
    assert coldstart.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    # Listed in .gitignore, and built from no temp name, pid or time.
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    src = inspect.getsource(coldstart.enable_compilation_cache)
    assert not any(w in src for w in ("tempfile", "mkdtemp", "getpid", "time."))
