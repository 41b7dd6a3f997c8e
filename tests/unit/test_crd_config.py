"""Model validation + system config tests
(reference suites: test/integration/model_validation_test.go,
internal/config defaulting)."""

import pytest

from kubeai_tpu.config import System
from kubeai_tpu.config.system import system_from_dict, _mini_yaml
from kubeai_tpu.crd.model import (
    Adapter,
    File,
    Model,
    ModelSpec,
    ValidationError,
)


def valid_model(**kw) -> Model:
    spec = ModelSpec(
        url="hf://meta-llama/Llama-3.1-8B-Instruct",
        engine="KubeAITPU",
        features=["TextGeneration"],
        min_replicas=0,
        max_replicas=3,
        resource_profile="google-tpu-v5e-2x2:4",
    )
    for k, v in kw.items():
        setattr(spec, k, v)
    return Model(name="llama-3-1-8b", spec=spec)


def test_valid_model_passes():
    valid_model().validate()


@pytest.mark.parametrize(
    "mutation",
    [
        {"url": ""},
        {"url": "ftp://nope"},
        {"engine": "NotAnEngine"},
        {"features": ["Bogus"]},
        {"min_replicas": -1},
        # nil maxReplicas is VALID (unbounded) — reference parity;
        # minReplicas > maxReplicas is not.
        {"min_replicas": 3, "max_replicas": 2},
        {"cache_profile": "c", "url": "ollama://x", "engine": "OLlama"},
        {"adapters": [Adapter(name="a", url="hf://x")], "engine": "OLlama",
         "url": "ollama://x"},
        {"resource_profile": "nocolon"},
        {"resource_profile": "cpu:0"},
        {"target_requests": 0},
        {"files": [File(path="relative/path", content="x")]},
        {"files": [File(path="/a", content="x"), File(path="/a", content="y")]},
        {"adapters": [Adapter(name="Bad_Name", url="hf://x")]},
        {"adapters": [Adapter(name="a", url="hf://x"), Adapter(name="a", url="hf://y")]},
        {"speculative_tokens": -1},
        {"speculative_tokens": 3, "engine": "VLLM"},
        {"draft_url": "hf://org/draft"},  # requires speculativeTokens >= 1
        {"draft_url": "ollama://draft", "speculative_tokens": 2},
        {"draft_url": "hf://org/draft", "speculative_tokens": 2,
         "engine": "VLLM"},
    ],
)
def test_invalid_specs_rejected(mutation):
    with pytest.raises(ValidationError):
        valid_model(**mutation).validate()


def test_cross_field_engine_url_rules():
    # OLlama requires ollama:// or pvc:// (reference: model_types.go:27-35).
    with pytest.raises(ValidationError):
        valid_model(engine="OLlama").validate()
    valid_model(engine="OLlama", url="ollama://gemma2:2b").validate()
    with pytest.raises(ValidationError):
        valid_model(engine="VLLM", url="ollama://gemma2:2b").validate()


def test_name_rules():
    m = valid_model()
    m.name = "x" * 41
    with pytest.raises(ValidationError):
        m.validate()
    m.name = "Has_Caps"
    with pytest.raises(ValidationError):
        m.validate()


def test_cache_profile_immutable():
    old = valid_model(cache_profile="efs")
    new = valid_model(cache_profile="other")
    with pytest.raises(ValidationError):
        new.validate_update(old)
    # url immutable when cached
    new2 = valid_model(cache_profile="efs", url="hf://other/repo")
    with pytest.raises(ValidationError):
        new2.validate_update(old)


def test_speculation_fields_valid():
    valid_model(speculative_tokens=4).validate()
    valid_model(speculative_tokens=4, draft_url="hf://org/draft").validate()
    m = valid_model(speculative_tokens=4, draft_url="hf://org/draft")
    m2 = Model.from_dict(m.to_dict())
    assert m2.spec.speculative_tokens == 4
    assert m2.spec.draft_url == "hf://org/draft"


def test_model_dict_roundtrip():
    m = valid_model(adapters=[Adapter(name="fin", url="hf://a/b")])
    m2 = Model.from_dict(m.to_dict())
    assert m2.spec == m.spec
    assert m2.name == m.name


def test_system_defaults_and_validation():
    cfg = System().default_and_validate()
    assert "cpu" in cfg.resource_profiles
    assert cfg.resource_profiles["google-tpu-v5e-2x2"].tpu_topology == "2x2"
    assert cfg.model_autoscaling.average_window_count == 60
    assert cfg.model_autoscaling.required_consecutive_scale_downs(30) == 3


def test_system_from_dict_camel_case():
    cfg = system_from_dict(
        {
            "resourceProfiles": {
                "google-tpu-v5e-2x2": {
                    "imageName": "google-tpu",
                    "requests": {"google.com/tpu": 4},
                    "nodeSelector": {"cloud.google.com/gke-tpu-topology": "2x2"},
                }
            },
            "modelAutoscaling": {"interval": "5s", "timeWindow": "10m"},
            "modelRollouts": {"surge": 2},
        }
    ).default_and_validate()
    assert cfg.resource_profiles["google-tpu-v5e-2x2"].requests == {
        "google.com/tpu": "4"
    }
    assert cfg.model_autoscaling.interval_seconds == 5
    assert cfg.model_autoscaling.time_window_seconds == 600
    assert cfg.model_rollouts.surge == 2


def test_mini_yaml_parses_nested_config():
    text = """
resourceProfiles:
  cpu:
    requests:
      cpu: 2
      memory: 4Gi
modelRollouts:
  surge: 1
messaging:
  streams:
    - requestSubscription: mem://requests
      responseTopic: mem://responses
"""
    d = _mini_yaml(text)
    assert d["resourceProfiles"]["cpu"]["requests"]["memory"] == "4Gi"
    assert d["modelRollouts"]["surge"] == 1
    assert d["messaging"]["streams"][0]["responseTopic"] == "mem://responses"


def test_scheduling_block_valid_and_roundtrip():
    from kubeai_tpu.crd.model import Scheduling

    m = valid_model(
        scheduling=Scheduling(
            default_priority="realtime",
            queue_shares={"standard": 0.3, "batch": 0.05},
            max_deadline_ms=30000,
        )
    )
    m.validate()
    d = m.to_dict()
    assert d["spec"]["scheduling"] == {
        "defaultPriority": "realtime",
        "queueShares": {"standard": 0.3, "batch": 0.05},
        "maxDeadlineMs": 30000,
    }
    back = Model.from_dict(d)
    assert back.spec.scheduling == m.spec.scheduling
    # Default (disabled) scheduling is omitted from the manifest.
    assert "scheduling" not in valid_model().to_dict()["spec"]
    assert Model.from_dict(valid_model().to_dict()).spec.scheduling.enabled() is False


@pytest.mark.parametrize(
    "sched_kw, engine",
    [
        ({"default_priority": "urgent"}, "KubeAITPU"),
        ({"queue_shares": {"nope": 0.1}}, "KubeAITPU"),
        ({"queue_shares": {"batch": 1.0}}, "KubeAITPU"),
        ({"queue_shares": {"batch": -0.1}}, "KubeAITPU"),
        ({"max_deadline_ms": -1}, "KubeAITPU"),
        # scheduling: is an in-tree engine feature (like speculation).
        ({"default_priority": "realtime"}, "VLLM"),
    ],
)
def test_scheduling_block_invalid(sched_kw, engine):
    from kubeai_tpu.crd.model import Scheduling

    kw = {"scheduling": Scheduling(**sched_kw), "engine": engine}
    if engine == "VLLM":
        kw["resource_profile"] = ""
    with pytest.raises(ValidationError):
        valid_model(**kw).validate()


def test_queue_pressure_config_parses_and_validates():
    sys_obj = system_from_dict(
        {"modelAutoscaling": {"interval": "5s", "timeWindow": "60s",
                              "queuePressureMaxWait": "7s"}}
    )
    assert sys_obj.model_autoscaling.queue_pressure_max_wait_seconds == 7.0
    sys_obj.default_and_validate()
    from kubeai_tpu.config.system import ConfigError

    sys_obj.model_autoscaling.queue_pressure_max_wait_seconds = -1
    with pytest.raises(ConfigError):
        sys_obj.default_and_validate()


@pytest.mark.stepperf
def test_a_manifest_that_still_carries_engine_step_parses_as_without_it():
    """`spec.engineStep` is no field any more (the engine's loop follows its
    topology): the parser does with it what it does with any block it does
    not know, and what is written back does not carry it."""
    d = valid_model().to_dict()
    carried = {**d, "spec": {**d["spec"], "engineStep": {"overlap": "off"}}}
    back = Model.from_dict(carried)
    back.validate()
    assert back.spec == Model.from_dict(d).spec
    assert "engineStep" not in back.to_dict()["spec"]
    assert not hasattr(back.spec, "engine_step")
