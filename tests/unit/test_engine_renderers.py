"""Engine Pod renderer goldens (reference suites: engine_ollama_test.go,
model_source_test.go, pod-spec goldens in pod_plan_test.go)."""

import pytest

from kubeai_tpu.config import System
from kubeai_tpu.crd.model import Model, ModelSpec
from kubeai_tpu.operator.engines import render_pod, resolve_model_config
from kubeai_tpu.operator.engines.common import parse_model_source


@pytest.fixture
def cfg():
    return System().default_and_validate()


def mk(engine, url, **kw):
    spec = ModelSpec(url=url, engine=engine, autoscaling_disabled=True,
                     replicas=1)
    for k, v in kw.items():
        setattr(spec, k, v)
    m = Model(name="m", spec=spec)
    m.validate()
    return m


def render(cfg, model):
    return render_pod(model, cfg, resolve_model_config(model, cfg), "x")


def container(pod):
    return pod["spec"]["containers"][0]


def env_dict(c):
    return {e["name"]: e.get("value") for e in c["env"]}


def test_model_source_parsing():
    s = parse_model_source("ollama://gemma2:2b?pull=always&insecure=true")
    assert s.scheme == "ollama" and s.ref == "gemma2:2b"
    assert s.pull_policy == "always" and s.insecure
    s = parse_model_source("hf://org/repo?model=alias")
    assert s.named_model == "alias"
    s = parse_model_source("pvc://my-claim/sub/path")
    assert s.ref == "my-claim/sub/path"


def test_ollama_renderer_probe_script(cfg):
    m = mk("OLlama", "ollama://gemma2:2b")
    pod = render(cfg, m)
    c = container(pod)
    script = " ".join(c["startupProbe"]["exec"]["command"])
    # pull if missing, rename to the Model name, warm up.
    assert "ollama pull gemma2:2b" in script
    assert "ollama cp gemma2:2b m" in script
    assert "ollama run m" in script
    env = env_dict(c)
    assert env["OLLAMA_KEEP_ALIVE"] == "999999h"

    # pull=never skips the pull entirely.
    m2 = mk("OLlama", "ollama://gemma2:2b?pull=never")
    script2 = " ".join(
        container(render(cfg, m2))["startupProbe"]["exec"]["command"]
    )
    assert "pull" not in script2


def test_vllm_renderer(cfg):
    from kubeai_tpu.crd.model import Adapter

    m = mk("VLLM", "hf://meta-llama/Llama-3.1-8B",
           adapters=[Adapter(name="a1", url="hf://o/a")])
    pod = render(cfg, m)
    c = container(pod)
    assert "--model=meta-llama/Llama-3.1-8B" in c["args"]
    assert "--served-model-name=m" in c["args"]
    assert "--enable-lora" in c["args"]
    assert env_dict(c)["VLLM_ALLOW_RUNTIME_LORA_UPDATING"] == "True"
    # /dev/shm for torch IPC; adapter loader sidecar present.
    vols = {v["name"] for v in pod["spec"]["volumes"]}
    assert "dshm" in vols
    sidecars = [ic["name"] for ic in pod["spec"].get("initContainers", [])]
    assert "loader" in sidecars
    # 3h startup budget.
    sp = c["startupProbe"]
    assert sp["periodSeconds"] * sp["failureThreshold"] >= 3 * 3600


def test_vllm_s3_uses_streamer(cfg):
    m = mk("VLLM", "s3://bucket/path")
    c = container(render(cfg, m))
    assert "--load-format=runai_streamer" in c["args"]
    assert any(e["name"] == "AWS_ACCESS_KEY_ID" for e in c["env"])


def test_fasterwhisper_and_infinity_env(cfg):
    m = mk("FasterWhisper", "hf://Systran/faster-whisper-medium-en",
           features=["SpeechToText"])
    env = env_dict(container(render(cfg, m)))
    assert env["WHISPER__MODEL"] == "Systran/faster-whisper-medium-en"

    m = mk("Infinity", "hf://BAAI/bge-small-en-v1.5",
           features=["TextEmbedding"])
    env = env_dict(container(render(cfg, m)))
    assert env["INFINITY_MODEL_ID"] == "BAAI/bge-small-en-v1.5"
    assert env["INFINITY_SERVED_MODEL_NAME"] == "m"


def test_kubeai_tpu_renderer_topology(cfg):
    m = mk("KubeAITPU", "hf://org/model",
           resource_profile="google-tpu-v5e-2x4:8")
    pod = render(cfg, m)
    c = container(pod)
    # Profile is 1 chip/unit; :8 multiplies to the full 2x4 slice.
    assert c["resources"]["limits"]["google.com/tpu"] == "8"
    assert (
        pod["spec"]["nodeSelector"]["cloud.google.com/gke-tpu-topology"]
        == "2x4"
    )
    env = env_dict(c)
    assert env["TPU_TOPOLOGY"] == "2x4" and env["TPU_CHIPS"] == "8"
    assert "--tpu-topology" in c["args"]
    # A Pod that was given chips leaves the platform to JAX: the engine
    # must fail at boot if the TPU is not there, not serve from the CPU.
    assert "JAX_PLATFORMS" not in env


def test_kubeai_tpu_renderer_cpu_profile_names_the_cpu(cfg):
    """engine.server refuses a CPU it fell back to; a Pod rendered without
    a device is on the CPU on purpose and has to say so, or it exits at
    boot. Holds for the built-in `cpu` profile, for a config-file profile
    that leaves imageName out (deploy/operator.yaml), and for no profile."""
    from kubeai_tpu.config.system import ResourceProfile

    cfg.resource_profiles["plain"] = ResourceProfile(
        requests={"cpu": "1", "memory": "2Gi"},
    )
    for profile in ("cpu:1", "plain:2", ""):
        m = mk("KubeAITPU", "hf://org/model", resource_profile=profile)
        env = env_dict(container(render(cfg, m)))
        assert env["JAX_PLATFORMS"] == "cpu", profile
    # spec.env still has the last word (later entries win in a Pod).
    m = mk("KubeAITPU", "hf://org/model", resource_profile="cpu:1",
           env={"JAX_PLATFORMS": "cpu,tpu"})
    names = [e["name"] for e in container(render(cfg, m))["env"]]
    assert names.count("JAX_PLATFORMS") == 2
    assert container(render(cfg, m))["env"][-1]["value"] == "cpu,tpu"


def test_kubeai_tpu_renderer_compile_cache_on_writable_volume(cfg):
    """Every engine Pod gets JAX_COMPILATION_CACHE_DIR on an emptyDir, so
    enable_compilation_cache never creates a directory inside a (possibly
    read-only) image and a restarted container finds its compiles."""
    for profile in ("cpu:1", "google-tpu-v5e-2x2:4"):
        m = mk("KubeAITPU", "hf://org/model", resource_profile=profile)
        pod = render(cfg, m)
        c = container(pod)
        cache_dir = env_dict(c)["JAX_COMPILATION_CACHE_DIR"]
        mount = [v for v in c["volumeMounts"] if v["mountPath"] == cache_dir]
        assert len(mount) == 1 and not mount[0].get("readOnly")
        vol = [v for v in pod["spec"]["volumes"]
               if v["name"] == mount[0]["name"]]
        assert vol == [{"name": mount[0]["name"], "emptyDir": {}}]


def test_files_projected_via_configmap(cfg):
    from kubeai_tpu.crd.model import File

    m = mk("KubeAITPU", "hf://org/model",
           files=[File(path="/etc/cfg/a.json", content="{}")])
    pod = render(cfg, m)
    mounts = {v["mountPath"] for v in container(pod)["volumeMounts"]}
    assert "/etc/cfg/a.json" in mounts
    vols = [v for v in pod["spec"]["volumes"] if v["name"] == "model-files"]
    assert vols and vols[0]["configMap"]["name"] == "model-m-files"


def test_pvc_source_mounts_readonly(cfg):
    m = mk("KubeAITPU", "pvc://weights-claim/llama")
    pod = render(cfg, m)
    vols = [v for v in pod["spec"]["volumes"] if v["name"] == "model-pvc"]
    assert vols[0]["persistentVolumeClaim"]["claimName"] == "weights-claim"
    mounts = [m_ for m_ in container(pod)["volumeMounts"]
              if m_["name"] == "model-pvc"]
    assert mounts[0]["readOnly"] is True


def test_kubeai_tpu_renderer_speculation_flags(cfg):
    m = mk("KubeAITPU", "hf://org/model", speculative_tokens=4,
           draft_url="hf://org/draft")
    args = container(render(cfg, m))["args"]
    assert args[args.index("--speculate") + 1] == "4"
    assert args[args.index("--draft-url") + 1] == "hf://org/draft"
    # Absent fields render no flags (vanilla decode).
    args2 = container(render(cfg, mk("KubeAITPU", "hf://org/model")))["args"]
    assert "--speculate" not in args2 and "--draft-url" not in args2


def test_kubeai_tpu_renderer_scheduling_flags(cfg):
    from kubeai_tpu.crd.model import Scheduling

    m = mk(
        "KubeAITPU", "hf://org/repo",
        scheduling=Scheduling(
            default_priority="realtime",
            queue_shares={"standard": 0.3, "batch": 0.05},
            max_deadline_ms=30000,
        ),
    )
    args = container(render(cfg, m))["args"]
    assert args[args.index("--default-priority") + 1] == "realtime"
    assert args[args.index("--max-deadline-ms") + 1] == "30000"
    assert args[args.index("--queue-shares") + 1] == "batch=0.05,standard=0.3"
    # No scheduling block -> no flags (engine defaults apply).
    plain = container(render(cfg, mk("KubeAITPU", "hf://org/repo")))["args"]
    assert "--default-priority" not in plain
    assert "--queue-shares" not in plain
    assert "--max-deadline-ms" not in plain


@pytest.mark.coldstart
def test_kubeai_tpu_renderer_coldstart_flags_and_probe(cfg):
    from kubeai_tpu.crd.model import ColdStart

    m = mk(
        "KubeAITPU", "hf://org/model",
        cold_start=ColdStart(enabled=True, snapshot_url="gs://snaps/ai"),
    )
    c = container(render(cfg, m))
    args = c["args"]
    assert args[args.index("--snapshot-url") + 1] == "gs://snaps/ai"
    assert "--snapshot-no-publish" not in args
    # A snapshot-restoring boot skips conversion and most compilation:
    # the startup budget tightens from 3h to 30min.
    sp = c["startupProbe"]
    assert sp["periodSeconds"] * sp["failureThreshold"] <= 30 * 60

    # publish=false renders the restore-only flag.
    m2 = mk(
        "KubeAITPU", "hf://org/model",
        cold_start=ColdStart(
            enabled=True, snapshot_url="gs://snaps/ai", publish=False,
        ),
    )
    assert "--snapshot-no-publish" in container(render(cfg, m2))["args"]


@pytest.mark.coldstart
def test_kubeai_tpu_renderer_no_coldstart_keeps_slow_budget(cfg):
    c = container(render(cfg, mk("KubeAITPU", "hf://org/model")))
    assert "--snapshot-url" not in c["args"]
    assert "--snapshot-no-publish" not in c["args"]
    # Without snapshots the generous full-load budget stays.
    sp = c["startupProbe"]
    assert sp["periodSeconds"] * sp["failureThreshold"] >= 3 * 3600


@pytest.mark.stepperf
def test_kubeai_tpu_renderer_names_no_step_loop(cfg):
    """The engine takes its step loop from its topology and has no flag for
    it: a manifest that still carries `engineStep` renders the arguments of
    one that does not."""
    plain = mk("KubeAITPU", "hf://org/model")
    d = plain.to_dict()
    carried = Model.from_dict(
        {**d, "spec": {**d["spec"], "engineStep": {"overlap": "off"}}})
    args = container(render(cfg, carried))["args"]
    assert args == container(render(cfg, plain))["args"]
    assert not any("overlap" in a for a in args)
