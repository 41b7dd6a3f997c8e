"""Model-config resolution and source handling shared by engine renderers.

Mirrors:
  - profile multiplication + image lookup
    (reference: internal/modelcontroller/model_controller.go:257-355)
  - model source URL parsing with per-scheme Pod additions
    (reference: internal/modelcontroller/model_source.go:82-271)
"""

from __future__ import annotations

import dataclasses
from urllib.parse import parse_qs, urlparse

from kubeai_tpu.config import System, ResourceProfile
from kubeai_tpu.crd.model import Model
from kubeai_tpu.utils.units import multiply_quantity


class ResolutionError(ValueError):
    pass


@dataclasses.dataclass
class ModelSource:
    """Parsed spec.url (reference: internal/modelcontroller/model_source.go:231-271)."""

    scheme: str
    ref: str  # repo id / bucket path / pvc path / ollama model
    params: dict[str, str]

    @property
    def pull_policy(self) -> str:  # ollama ?pull=
        return self.params.get("pull", "")

    @property
    def insecure(self) -> bool:
        return self.params.get("insecure", "") in ("true", "1")

    @property
    def named_model(self) -> str | None:  # ?model= override
        return self.params.get("model")


def parse_model_source(url: str) -> ModelSource:
    parsed = urlparse(url)
    if not parsed.scheme:
        raise ResolutionError(f"model url {url!r} missing scheme")
    ref = (parsed.netloc + parsed.path).strip("/")
    params = {k: v[-1] for k, v in parse_qs(parsed.query).items()}
    return ModelSource(scheme=parsed.scheme, ref=ref, params=params)


@dataclasses.dataclass
class ModelConfig:
    """Everything a renderer needs (resolved profile × count + image + source)."""

    image: str
    requests: dict[str, str]
    limits: dict[str, str]
    node_selector: dict[str, str]
    affinity: dict | None
    tolerations: list[dict]
    scheduler_name: str
    runtime_class_name: str
    profile_name: str
    profile_count: int
    source: ModelSource
    # Scale: replica bounds after autoscaling clamping
    cache_dir: str = ""  # set when cacheProfile in play
    num_hosts: int = 1  # Pods per replica (multi-host TPU slices)

    @property
    def tpu_topology(self) -> str | None:
        from kubeai_tpu.config.system import TPU_TOPOLOGY_SELECTOR

        return self.node_selector.get(TPU_TOPOLOGY_SELECTOR)

    @property
    def tpu_chips(self) -> int:
        v = self.limits.get("google.com/tpu") or self.requests.get("google.com/tpu")
        return int(v) if v else 0

    @property
    def requests_device(self) -> bool:
        """Whether the Pod asks for any device. Devices are extended
        resources, always domain-prefixed (google.com/tpu,
        nvidia.com/gpu); cpu, memory and storage are not. A container
        that requests none has none mounted."""
        return any("/" in k for k in (*self.requests, *self.limits))


def resolve_model_config(model: Model, cfg: System) -> ModelConfig:
    """Profile lookup+multiplication and engine-image selection
    (reference: internal/modelcontroller/model_controller.go:257-355)."""
    profile_name, count = "", 1
    if model.spec.resource_profile:
        name, _, cnt = model.spec.resource_profile.partition(":")
        profile_name, count = name, int(cnt or "1")
    profile = ResourceProfile()
    if profile_name:
        if profile_name not in cfg.resource_profiles:
            raise ResolutionError(
                f"resourceProfile {profile_name!r} not found in system config"
            )
        profile = cfg.resource_profiles[profile_name]

    requests = {k: multiply_quantity(v, count) for k, v in profile.requests.items()}
    limits = {k: multiply_quantity(v, count) for k, v in profile.limits.items()}

    image = model.spec.image
    if not image:
        images = cfg.model_servers.get(model.spec.engine)
        if not images:
            raise ResolutionError(f"no images configured for engine {model.spec.engine}")
        image_name = profile.image_name or "default"
        image = images.get(image_name) or images["default"]

    # spec.sharding overrides the profile's group shape: an explicit
    # hosts-per-replica wins over profile.numHosts, and an explicit ICI
    # topology wins over the profile's topology node selector.
    node_selector = dict(profile.node_selector)
    num_hosts = profile.num_hosts
    if model.spec.sharding.enabled():
        from kubeai_tpu.config.system import TPU_TOPOLOGY_SELECTOR

        if model.spec.sharding.hosts:
            num_hosts = model.spec.sharding.hosts
        if model.spec.sharding.topology:
            node_selector[TPU_TOPOLOGY_SELECTOR] = model.spec.sharding.topology

    return ModelConfig(
        image=image,
        requests=requests,
        limits=limits,
        node_selector=node_selector,
        affinity=profile.affinity,
        tolerations=list(profile.tolerations),
        scheduler_name=profile.scheduler_name,
        runtime_class_name=profile.runtime_class_name,
        profile_name=profile_name,
        profile_count=count,
        source=parse_model_source(model.spec.url),
        num_hosts=num_hosts,
    )


# -- shared pod scaffolding ---------------------------------------------------


def base_pod(model: Model, cfg: System, mcfg: ModelConfig, suffix: str) -> dict:
    """Common Pod scaffold all renderers extend."""
    from kubeai_tpu.crd import metadata as md

    pod = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": f"model-{model.name}-{suffix}",
            "namespace": model.namespace,
            "labels": {
                md.POD_MODEL_LABEL: model.name,
            },
            "annotations": {},
        },
        "spec": {
            "containers": [],
            "restartPolicy": "Always",
            "nodeSelector": dict(mcfg.node_selector),
            "tolerations": list(mcfg.tolerations),
        },
    }
    spec = pod["spec"]
    if mcfg.affinity:
        spec["affinity"] = mcfg.affinity
    if mcfg.scheduler_name:
        spec["schedulerName"] = mcfg.scheduler_name
    if mcfg.runtime_class_name:
        spec["runtimeClassName"] = mcfg.runtime_class_name
    if model.spec.priority_class_name:
        spec["priorityClassName"] = model.spec.priority_class_name
    if cfg.model_server_pods.service_account_name:
        spec["serviceAccountName"] = cfg.model_server_pods.service_account_name
    if cfg.model_server_pods.security_context:
        spec["securityContext"] = cfg.model_server_pods.security_context
    if cfg.model_server_pods.image_pull_secrets:
        spec["imagePullSecrets"] = [
            {"name": n} for n in cfg.model_server_pods.image_pull_secrets
        ]
    return pod


def source_env_and_volumes(model: Model, cfg: System, mcfg: ModelConfig):
    """Per-scheme env/volumes/mounts (reference: model_source.go:82-227)."""
    env: list[dict] = []
    volumes: list[dict] = []
    mounts: list[dict] = []
    src = mcfg.source
    if src.scheme == "hf":
        env.append(
            {
                "name": "HF_TOKEN",
                "valueFrom": {
                    "secretKeyRef": {
                        "name": cfg.secret_names.get("huggingface", "kubeai-huggingface"),
                        "key": "token",
                        "optional": True,
                    }
                },
            }
        )
    elif src.scheme == "s3":
        env.extend(
            [
                {
                    "name": n,
                    "valueFrom": {
                        "secretKeyRef": {
                            "name": cfg.secret_names.get("aws", "kubeai-aws"),
                            "key": k,
                            "optional": True,
                        }
                    },
                }
                for n, k in (
                    ("AWS_ACCESS_KEY_ID", "accessKeyID"),
                    ("AWS_SECRET_ACCESS_KEY", "secretAccessKey"),
                )
            ]
        )
    elif src.scheme == "gs":
        env.append(
            {
                "name": "GOOGLE_APPLICATION_CREDENTIALS",
                "value": "/secrets/gcp/credentials.json",
            }
        )
        volumes.append(
            {
                "name": "gcp-credentials",
                "secret": {
                    "secretName": cfg.secret_names.get("gcp", "kubeai-gcp"),
                    "optional": True,
                },
            }
        )
        mounts.append(
            {"name": "gcp-credentials", "mountPath": "/secrets/gcp", "readOnly": True}
        )
    elif src.scheme == "oss":
        env.extend(
            [
                {
                    "name": n,
                    "valueFrom": {
                        "secretKeyRef": {
                            "name": cfg.secret_names.get("alibaba", "kubeai-alibaba"),
                            "key": k,
                            "optional": True,
                        }
                    },
                }
                for n, k in (
                    ("OSS_ACCESS_KEY_ID", "accessKeyID"),
                    ("OSS_ACCESS_KEY_SECRET", "accessKeySecret"),
                )
            ]
        )
    elif src.scheme == "pvc":
        pvc_name = src.ref.split("/", 1)[0]
        volumes.append(
            {
                "name": "model-pvc",
                "persistentVolumeClaim": {"claimName": pvc_name, "readOnly": True},
            }
        )
        mounts.append({"name": "model-pvc", "mountPath": "/model", "readOnly": True})
    return env, volumes, mounts


def model_env(model: Model) -> list[dict]:
    out = [{"name": k, "value": v} for k, v in sorted(model.spec.env.items())]
    return out


def files_volume(model: Model, files_configmap_name: str):
    """Project spec.files via ConfigMap items
    (reference: internal/modelcontroller/files.go)."""
    if not model.spec.files:
        return [], []
    items = []
    mounts = []
    for i, f in enumerate(model.spec.files):
        key = f"file-{i}"
        items.append({"key": key, "path": f.path.lstrip("/")})
        mounts.append(
            {
                "name": "model-files",
                "mountPath": f.path,
                "subPath": f.path.lstrip("/"),
                "readOnly": True,
            }
        )
    volumes = [
        {
            "name": "model-files",
            "configMap": {"name": files_configmap_name, "items": items},
        }
    ]
    return volumes, mounts
