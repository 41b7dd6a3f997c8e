"""End-to-end: the full operator stack routing to a REAL engine server
(tiny Llama, byte tokenizer) — the reference's `quickstart` e2e equivalent
(reference: test/e2e/quickstart/test.sh runs a real completion through a
real Ollama backend; here the backend is the in-tree TPU engine on CPU).

Covers: Model create → controller renders pod (engine 'started' by the
test) → LB discovery → chat completion through the operator proxy →
LoRA adapter orchestration end-to-end (controller → engine admin API with
a real PEFT checkpoint from disk → adapter-routed request)."""

import json
import os

import jax
import numpy as np
import pytest

from testutil import eventually, http_get, http_post, popen_logged

from kubeai_tpu.config import System
from kubeai_tpu.crd import metadata as md
from kubeai_tpu.crd.model import Adapter, Model, ModelSpec
from kubeai_tpu.engine import Engine, EngineConfig
from kubeai_tpu.engine.server import EngineServer
from kubeai_tpu.engine.tokenizer import ByteTokenizer
from kubeai_tpu.models import llama
from kubeai_tpu.operator.k8s.store import KubeStore
from kubeai_tpu.operator.manager import Manager


def _save_peft_adapter(tmp_path, cfg, rank=4, seed=7):
    """Write a real PEFT-format LoRA checkpoint (safetensors) to disk."""
    import torch
    from safetensors.torch import save_file

    rng = np.random.default_rng(seed)
    E, H, D, NL = cfg.hidden_size, cfg.num_heads, cfg.head_size, cfg.num_layers
    tensors = {}
    for i in range(NL):
        prefix = f"base_model.model.model.layers.{i}.self_attn.q_proj"
        tensors[f"{prefix}.lora_A.weight"] = torch.tensor(
            (rng.standard_normal((rank, E)) * 12.0).astype(np.float32)
        )
        tensors[f"{prefix}.lora_B.weight"] = torch.tensor(
            (rng.standard_normal((H * D, rank)) * 12.0).astype(np.float32)
        )
    adapter_dir = tmp_path / "fin-lora"
    adapter_dir.mkdir()
    save_file(tensors, str(adapter_dir / "adapter_model.safetensors"))
    (adapter_dir / "adapter_config.json").write_text(
        json.dumps({"r": rank, "lora_alpha": rank, "target_modules": ["q_proj"]})
    )
    return str(adapter_dir)


@pytest.fixture(scope="module")
def real_engine():
    tok = ByteTokenizer()
    cfg = llama.LlamaConfig.tiny(vocab_size=tok.vocab_size)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(
        "llama", cfg, params,
        cfg=EngineConfig(num_slots=4, max_seq_len=128, max_adapters=2,
                         max_lora_rank=8, decode_chunk=4),
        eos_token_ids=tok.eos_token_ids,
    )
    srv = EngineServer(engine, tok, "e2e-model", host="127.0.0.1", port=0)
    srv.start()
    yield srv, cfg
    srv.stop()


def test_quickstart_through_operator(real_engine, tmp_path):
    engine_srv, model_cfg = real_engine
    store = KubeStore()
    cfg = System()
    cfg.allow_pod_address_override = True
    mgr = Manager(store, cfg)
    mgr.start()
    try:
        adapter_dir = _save_peft_adapter(tmp_path, model_cfg)
        m = Model(
            name="e2e-model",
            spec=ModelSpec(
                url="hf://org/e2e-model",
                engine="KubeAITPU",
                features=["TextGeneration"],
                min_replicas=1,
                max_replicas=1,
                adapters=[Adapter(name="fin", url=adapter_dir)],
            ),
            annotations={
                md.MODEL_POD_IP_ANNOTATION: "127.0.0.1",
                md.MODEL_POD_PORT_ANNOTATION: str(engine_srv.port),
            },
        )
        store.create(m.to_dict())

        # Controller creates the pod; mark it ready ("kubelet") — the REAL
        # engine is listening at the annotated address.
        def ready():
            pods = store.list("Pod", "default", {md.POD_MODEL_LABEL: "e2e-model"})
            for pod in pods:
                pod.setdefault("status", {})["conditions"] = [
                    {"type": "Ready", "status": "True"},
                    {"type": "PodScheduled", "status": "True"},
                ]
                pod["status"]["podIP"] = "127.0.0.1"
                try:
                    store.update(pod)
                except Exception:
                    pass
            return pods

        eventually(ready, msg="engine pod created")

        # 1. Base chat completion through the operator front door.
        def chat_ok():
            status, data = http_post(
                mgr.api_address,
                "/openai/v1/chat/completions",
                {
                    "model": "e2e-model",
                    "messages": [{"role": "user", "content": "hello"}],
                    "max_tokens": 8,
                    "temperature": 0,
                },
            )
            return json.loads(data) if status == 200 else None

        payload = eventually(chat_ok, timeout=30, msg="chat completion 200")
        assert payload["object"] == "chat.completion"
        base_text = payload["choices"][0]["message"]["content"]

        # 2. Adapter orchestration: the controller exec-free path loads the
        # PEFT checkpoint into the engine and labels the pod.
        def adapter_labelled():
            pods = store.list("Pod", "default", {md.POD_MODEL_LABEL: "e2e-model"})
            return pods and md.adapter_label("fin") in (
                pods[0]["metadata"].get("labels") or {}
            )

        eventually(adapter_labelled, timeout=30, msg="adapter label on pod")
        status, body = http_get(
            f"127.0.0.1:{engine_srv.port}", "/v1/models"
        )
        assert "fin" in [m["id"] for m in json.loads(body)["data"]]

        # 3. Adapter-suffixed request routes through and generates
        # differently (LoRA weights actually applied).
        status, data = http_post(
            mgr.api_address,
            "/openai/v1/chat/completions",
            {
                "model": "e2e-model_fin",
                "messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 8,
                "temperature": 0,
            },
        )
        assert status == 200, data
        fin_text = json.loads(data)["choices"][0]["message"]["content"]
        assert fin_text != base_text

        # 4. /v1/models through the operator lists model + adapter ids.
        status, body = http_get(mgr.api_address, "/openai/v1/models")
        ids = {m["id"] for m in json.loads(body)["data"]}
        assert {"e2e-model", "e2e-model_fin"} <= ids
    finally:
        mgr.stop()


def test_gs_model_served_through_operator(tmp_path, monkeypatch):
    """Object-store model end-to-end (reference: test/e2e/s3-model): a
    REAL HF checkpoint uploaded to a fake gs:// bucket, resolved and
    lazily loaded by the engine (streamed shard-at-a-time), served
    through the operator front door."""
    torch = pytest.importorskip("torch")
    import sys as _sys

    _sys.path.insert(0, "tests/unit")
    from test_objstore_loader import FakeGCS
    from transformers import LlamaConfig as HFLlama, LlamaForCausalLM

    from kubeai_tpu import objstore
    from kubeai_tpu.engine.weights import (
        load_hf_config,
        load_params,
        resolve_model_dir,
    )
    from kubeai_tpu.models.registry import get_model_family

    tok = ByteTokenizer()
    hf_cfg = HFLlama(
        vocab_size=tok.vocab_size, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256,
    )
    torch.manual_seed(3)
    ckpt = tmp_path / "ckpt"
    LlamaForCausalLM(hf_cfg).save_pretrained(ckpt, safe_serialization=True)

    fake = FakeGCS()
    monkeypatch.setenv("STORAGE_EMULATOR_HOST", fake.endpoint)
    monkeypatch.setenv("KUBEAI_WEIGHTS_CACHE", str(tmp_path / "wcache"))
    try:
        objstore.upload_dir(str(ckpt), "gs://models/e2e-gs")

        # Engine boot path for a gs:// Model url (server.py main() flow).
        model_dir = resolve_model_dir("gs://models/e2e-gs")
        arch = load_hf_config(model_dir)["architectures"][0]
        family = get_model_family(arch)
        mcfg = family.config_from_hf(load_hf_config(model_dir))
        params = load_params(family.name, model_dir, mcfg)
        engine = Engine(
            family, mcfg, params,
            cfg=EngineConfig(num_slots=2, max_seq_len=64),
            eos_token_ids=tok.eos_token_ids,
        )
        srv = EngineServer(engine, tok, "gs-model", host="127.0.0.1", port=0)
        srv.start()

        store = KubeStore()
        cfg = System()
        cfg.allow_pod_address_override = True
        mgr = Manager(store, cfg)
        mgr.start()
        try:
            store.create(
                Model(
                    name="gs-model",
                    spec=ModelSpec(
                        url="gs://models/e2e-gs",
                        engine="KubeAITPU",
                        features=["TextGeneration"],
                        min_replicas=1,
                        max_replicas=1,
                    ),
                    annotations={
                        md.MODEL_POD_IP_ANNOTATION: "127.0.0.1",
                        md.MODEL_POD_PORT_ANNOTATION: str(srv.port),
                    },
                ).to_dict()
            )

            def ready():
                pods = store.list(
                    "Pod", "default", {md.POD_MODEL_LABEL: "gs-model"}
                )
                for pod in pods:
                    pod.setdefault("status", {})["conditions"] = [
                        {"type": "Ready", "status": "True"},
                        {"type": "PodScheduled", "status": "True"},
                    ]
                    pod["status"]["podIP"] = "127.0.0.1"
                    try:
                        store.update(pod)
                    except Exception:
                        pass
                return pods

            eventually(ready, msg="gs engine pod created")

            def chat_ok():
                status, data = http_post(
                    mgr.api_address,
                    "/openai/v1/completions",
                    {"model": "gs-model", "prompt": "object store",
                     "max_tokens": 6, "temperature": 0},
                )
                return json.loads(data) if status == 200 else None

            payload = eventually(chat_ok, timeout=30, msg="gs completion")
            assert payload["usage"]["completion_tokens"] == 6
            # Pod args carry the gs:// url (engine-direct load path).
            pods = store.list("Pod", "default", {md.POD_MODEL_LABEL: "gs-model"})
            args = pods[0]["spec"]["containers"][0]["args"]
            assert "gs://models/e2e-gs" in args
        finally:
            mgr.stop()
            srv.stop()
    finally:
        fake.close()


def test_draft_model_served_through_operator(tmp_path):
    """Round-5 verdict #6, the full chain: a Model with FIRST-CLASS
    draftUrl/speculativeTokens fields → controller renders the engine pod
    → the pod's EXACT rendered args boot a real engine-server subprocess
    (weight locations redirected to a local checkpoint via the cache-dir
    override flags, the same mechanism cacheProfile uses) → the operator
    proxy routes a completion to it → the engine's metrics prove the
    speculative path accepted proposals (target-as-draft ⇒ near-total
    acceptance)."""
    import signal
    import sys

    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    hf_cfg = HFLlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=10000.0, max_position_embeddings=512,
    )
    torch.manual_seed(0)
    ckpt = tmp_path / "spec-ckpt"
    LlamaForCausalLM(hf_cfg).save_pretrained(str(ckpt), safe_serialization=True)

    store = KubeStore()
    cfg = System()
    cfg.allow_pod_address_override = True
    mgr = Manager(store, cfg)
    mgr.start()
    port = 18481
    proc = None
    try:
        m = Model(
            name="spec-model",
            spec=ModelSpec(
                url="hf://org/tiny-target",
                engine="KubeAITPU",
                features=["TextGeneration"],
                min_replicas=1,
                max_replicas=1,
                speculative_tokens=3,
                draft_url="hf://org/tiny-draft",
                args=["--num-slots", "2", "--max-seq-len", "64",
                      "--max-adapters", "0", "--spec-adaptive", "off"],
            ),
            annotations={
                md.MODEL_POD_IP_ANNOTATION: "127.0.0.1",
                md.MODEL_POD_PORT_ANNOTATION: str(port),
            },
        )
        m.spec.validate()
        store.create(m.to_dict())

        def rendered_args():
            pods = store.list(
                "Pod", "default", {md.POD_MODEL_LABEL: "spec-model"}
            )
            if not pods:
                return None
            return pods[0]["spec"]["containers"][0]["args"]

        args = eventually(rendered_args, msg="controller rendered engine pod")
        # The first-class spec fields became engine flags.
        assert args[args.index("--speculate") + 1] == "3"
        assert args[args.index("--draft-url") + 1] == "hf://org/tiny-draft"

        # Boot the rendered args verbatim; later flags win in argparse, so
        # the test appends only the local-port and local-weights overrides
        # (what a cacheProfile mount provides in a real pod).
        boot = args + [
            "--host", "127.0.0.1", "--port", str(port),
            "--model-dir", str(ckpt), "--draft-dir", str(ckpt),
        ]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = popen_logged(
            [
                sys.executable, "-c",
                    "from kubeai_tpu.engine.server import main; import sys; "
                f"sys.exit(main({boot!r}))",
            ],
            cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
            env=env,
        )

        def healthy():
            if proc.poll() is not None:
                out = proc.output()
                raise AssertionError(f"server died:\n{out[-2000:]}")
            # Mark the controller's pod Ready so the LB routes to the
            # (annotated) subprocess address.
            for pod in store.list(
                "Pod", "default", {md.POD_MODEL_LABEL: "spec-model"}
            ):
                pod.setdefault("status", {})["conditions"] = [
                    {"type": "Ready", "status": "True"},
                    {"type": "PodScheduled", "status": "True"},
                ]
                pod["status"]["podIP"] = "127.0.0.1"
                try:
                    store.update(pod)
                except Exception:
                    pass
            try:
                return http_get(
                    f"127.0.0.1:{port}", "/health", timeout=2
                )[0] == 200
            except OSError:
                return False

        eventually(healthy, timeout=240, interval=0.5, msg="draft engine healthy")

        def chat_ok():
            status, data = http_post(
                mgr.api_address,
                "/openai/v1/chat/completions",
                {
                    "model": "spec-model",
                    "messages": [{"role": "user", "content": "abababab"}],
                    "max_tokens": 8,
                    "temperature": 0,
                },
                timeout=120,
            )
            return json.loads(data) if status == 200 else None

        payload = eventually(chat_ok, timeout=60, msg="chat via proxy")
        assert payload["choices"][0]["message"]["content"]

        # spec_stats through the engine's metrics endpoint: the draft
        # proposed and the target accepted (same weights ⇒ acceptance).
        status, body = http_get(f"127.0.0.1:{port}", "/metrics")
        assert status == 200
        metrics = {}
        for line in body.decode().splitlines():
            if line and not line.startswith("#"):
                k, _, v = line.rpartition(" ")
                try:
                    metrics[k.split("{")[0]] = float(v)
                except ValueError:
                    pass
        assert metrics.get("kubeai_engine_spec_proposed_tokens_total", 0) > 0
        assert metrics.get("kubeai_engine_spec_accepted_tokens_total", 0) > 0
    finally:
        if proc is not None:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        mgr.stop()
