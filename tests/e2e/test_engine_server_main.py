"""The engine server's process entrypoint, end to end: a real HF checkpoint
on disk, `python -m kubeai_tpu.engine.server` as a subprocess, driven over
its socket — exactly what runs inside a KubeAITPU engine Pod."""

import json
import os
import signal
import sys
import time

import pytest

from testutil import eventually, http_get, http_post, popen_logged

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    hf_cfg = HFLlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, max_position_embeddings=512,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg)
    d = tmp_path_factory.mktemp("srv-ckpt")
    model.save_pretrained(d, safe_serialization=True)
    return str(d)


def test_server_main_subprocess(checkpoint):
    port = 18477
    env = dict(os.environ)
    # The same entrypoint, held to the CPU by name.
    env["JAX_PLATFORMS"] = "cpu"
    proc = popen_logged(
        [
            sys.executable, "-c",
            "from kubeai_tpu.engine.server import main; import sys; "
            f"sys.exit(main(['--model-url', {checkpoint!r}, "
            f"'--served-model-name', 'tiny', '--port', '{port}', "
            "'--host', '127.0.0.1', '--num-slots', '2', "
            "'--max-seq-len', '64', '--max-adapters', '0', "
            "'--quantization', 'int8']))",
        ],
        cwd=REPO,
        env=env,
    )
    try:
        def healthy():
            if proc.poll() is not None:
                out = proc.output()
                raise AssertionError(f"server died:\n{out[-2000:]}")
            try:
                return http_get(f"127.0.0.1:{port}", "/health", timeout=2)[0] == 200
            except OSError:
                return False

        eventually(healthy, timeout=120, interval=0.5, msg="server healthy")

        status, body = http_get(f"127.0.0.1:{port}", "/v1/models")
        assert status == 200
        assert "tiny" in [m["id"] for m in json.loads(body)["data"]]

        status, body = http_post(
            f"127.0.0.1:{port}",
            "/v1/completions",
            {"model": "tiny", "prompt": "ab", "max_tokens": 4,
             "temperature": 0},
            timeout=60,
        )
        assert status == 200, body
        payload = json.loads(body)
        assert payload["object"] == "text_completion"
        assert payload["choices"][0]["finish_reason"] in ("length", "stop")

        status, body = http_post(
            f"127.0.0.1:{port}",
            "/v1/embeddings",
            {"input": "hello"},
            timeout=60,
        )
        assert status == 200, body
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)


def test_server_main_draft_speculation(checkpoint):
    """The serving plumbing for draft-model speculation: --speculate +
    --draft-url load a second (same-family) model and serve through the
    speculative path. Target-as-draft keeps the run cheap; stream
    exactness is covered by the unit tier (test_draft_spec)."""
    port = 18478
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = popen_logged(
        [
            sys.executable, "-c",
            "from kubeai_tpu.engine.server import main; import sys; "
            f"sys.exit(main(['--model-url', {checkpoint!r}, "
            f"'--served-model-name', 'tiny', '--port', '{port}', "
            "'--host', '127.0.0.1', '--num-slots', '2', "
            "'--max-seq-len', '64', '--max-adapters', '0', "
            "'--speculate', '3', '--spec-adaptive', 'off', "
            f"'--draft-url', {checkpoint!r}]))",
        ],
        cwd=REPO,
        env=env,
    )
    try:
        def healthy():
            if proc.poll() is not None:
                out = proc.output()
                raise AssertionError(f"server died:\n{out[-2000:]}")
            try:
                return http_get(f"127.0.0.1:{port}", "/health", timeout=2)[0] == 200
            except OSError:
                return False

        eventually(healthy, timeout=180, interval=0.5, msg="server healthy")
        status, body = http_post(
            f"127.0.0.1:{port}",
            "/v1/completions",
            {"model": "tiny", "prompt": "abab", "max_tokens": 6,
             "temperature": 0},
            timeout=120,
        )
        assert status == 200, body
        assert json.loads(body)["choices"][0]["finish_reason"] in (
            "length", "stop",
        )
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)


def test_server_main_prefix_cache(checkpoint):
    """--prefix-cache end to end through the process entrypoint: two
    same-prefix completions, the second served with cached prompt pages
    (visible on /metrics). Stream exactness is covered by the unit tier
    (test_prefix_cache)."""
    port = 18479
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = popen_logged(
        [
            sys.executable, "-c",
            "from kubeai_tpu.engine.server import main; import sys; "
            f"sys.exit(main(['--model-url', {checkpoint!r}, "
            f"'--served-model-name', 'tiny', '--port', '{port}', "
            "'--host', '127.0.0.1', '--num-slots', '2', "
            "'--max-seq-len', '256', '--max-adapters', '0', "
            "'--prefix-cache', '--prefill-chunk', '32']))",
        ],
        cwd=REPO,
        env=env,
    )
    try:
        def healthy():
            if proc.poll() is not None:
                out = proc.output()
                raise AssertionError(f"server died:\n{out[-2000:]}")
            try:
                return http_get(f"127.0.0.1:{port}", "/health", timeout=2)[0] == 200
            except OSError:
                return False

        eventually(healthy, timeout=180, interval=0.5, msg="server healthy")
        shared = "x" * 70
        outs = []
        for tail in ("aaa", "bbb"):
            status, body = http_post(
                f"127.0.0.1:{port}",
                "/v1/completions",
                {"model": "tiny", "prompt": shared + tail, "max_tokens": 4,
                 "temperature": 0},
                timeout=120,
            )
            assert status == 200, body
            outs.append(json.loads(body)["choices"][0]["text"])
        status, body = http_get(f"127.0.0.1:{port}", "/metrics")
        metrics = {}
        for line in body.decode().splitlines():
            if line and not line.startswith("#"):
                k, _, v = line.rpartition(" ")
                try:
                    metrics[k] = float(v)
                except ValueError:
                    pass
        assert metrics.get("kubeai_engine_prefix_cached_tokens_total", 0) >= 64
        assert metrics.get("kubeai_engine_prefix_prompt_tokens_total", 0) > 0
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
