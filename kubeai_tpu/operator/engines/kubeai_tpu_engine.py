"""Pod renderer for the in-tree TPU engine (the reference has no analog —
its TPU path launches stock vLLM-TPU images, reference:
charts/kubeai/values.yaml:48 + values-gke.yaml:18-41; here the engine is
kubeai_tpu.engine.server running on the slice).

TPU-specific rendering:
  - `google.com/tpu` requests/limits from the resource profile
  - ICI topology flows to the engine via TPU_TOPOLOGY env (mesh shape)
  - generous startup probe budget for sharded weight loading (the
    reference gives vLLM 3h — reference: engine_vllm.go:101-107)
"""

from __future__ import annotations

from kubeai_tpu.config import System
from kubeai_tpu.crd.model import Model
from kubeai_tpu.operator.engines.common import (
    ModelConfig,
    base_pod,
    files_volume,
    model_env,
    source_env_and_volumes,
)

PORT = 8000
# Where the engine keeps JAX's persistent compilation cache in a pod: an
# emptyDir, so a restarted container compiles nothing twice and a
# read-only image is no obstacle. The engine sets no cache directory of
# its own where JAX_COMPILATION_CACHE_DIR is set (engine/coldstart.py).
JAX_CACHE_DIR = "/var/cache/jax"


def kubeai_tpu_pod(
    model: Model, cfg: System, mcfg: ModelConfig, suffix: str,
    role: str = "",
) -> dict:
    """`role` renders one pod of a disaggregated group: the engine gets
    `--role prefill|decode` (+ transfer limits from the CRD block) and
    the pod carries the model-role label the LB's per-role endpoint
    groups key on. "" renders the classic unified replica."""
    pod = base_pod(model, cfg, mcfg, suffix)
    env, volumes, mounts = source_env_and_volumes(model, cfg, mcfg)
    fvols, fmounts = files_volume(model, f"model-{model.name}-files")
    volumes += fvols
    mounts += fmounts

    args = [
        "--model-url", model.spec.url,
        "--served-model-name", model.name,
        "--port", str(PORT),
    ]
    if mcfg.tpu_topology:
        args += ["--tpu-topology", mcfg.tpu_topology]
    if mcfg.cache_dir:
        args += ["--model-dir", mcfg.cache_dir]
    # Speculative decoding from first-class spec fields (CRD validates
    # draftUrl implies speculativeTokens >= 1 and the KubeAITPU engine).
    if model.spec.speculative_tokens > 0:
        args += ["--speculate", str(model.spec.speculative_tokens)]
    if model.spec.draft_url:
        args += ["--draft-url", model.spec.draft_url]
    # Graceful drain: CRD drainTimeoutSeconds, defaulted from the system
    # config resilience block. The same number drives the engine's
    # --drain-timeout, the preStop drain trigger, and (plus slack for
    # the final flush) terminationGracePeriodSeconds — so kubelet's KILL
    # can never race the in-flight completions the engine is waiting on.
    drain_timeout = int(
        model.spec.drain_timeout_seconds
        or cfg.resilience.drain_timeout_seconds
    )
    args += ["--drain-timeout", str(drain_timeout)]
    # Step watchdog: a hung device step flips /health and exits nonzero
    # so kubelet restarts the pod long before the router's circuit
    # breaker could accumulate response-header timeouts.
    args += [
        "--watchdog-timeout",
        f"{cfg.resilience.watchdog_timeout_seconds:g}",
    ]
    # SLO scheduling policy from the CRD scheduling: block (validated to
    # the engine's priority classes at admission).
    sched = model.spec.scheduling
    if sched.default_priority:
        args += ["--default-priority", sched.default_priority]
    if sched.max_deadline_ms:
        args += ["--max-deadline-ms", str(sched.max_deadline_ms)]
    if sched.queue_shares:
        args += [
            "--queue-shares",
            ",".join(
                f"{cls}={share:g}"
                for cls, share in sorted(sched.queue_shares.items())
            ),
        ]
    # Disaggregated serving role (CRD disaggregation: block): the engine
    # flag plus the pod label the LB's role groups key on.
    if role:
        from kubeai_tpu.crd import metadata as md

        args += ["--role", role]
        dis = model.spec.disaggregation
        if dis.max_transfer_mb:
            args += ["--max-transfer-mb", str(dis.max_transfer_mb)]
        if dis.transfer_timeout_seconds:
            args += [
                "--transfer-timeout", f"{dis.transfer_timeout_seconds:g}",
            ]
        pod["metadata"]["labels"][md.POD_ROLE_LABEL] = role
    # Cluster KV sharing (CRD kvSharing: block): the engine publishes
    # held page-hash chains, serves peer page exports, and pulls
    # common-prefix pages from the proxy-suggested X-KV-Source peer.
    # --kv-sharing implies --prefix-cache engine-side.
    kvs = model.spec.kv_sharing
    if kvs.enabled:
        args += ["--kv-sharing"]
        if kvs.fetch_timeout_seconds:
            args += ["--kv-fetch-timeout", f"{kvs.fetch_timeout_seconds:g}"]
        if kvs.max_transfer_mb:
            args += ["--max-transfer-mb", str(kvs.max_transfer_mb)]
        if kvs.spill_url:
            args += ["--kv-spill-url", kvs.spill_url]
    # KV-cache storage dtype (CRD kvCache: block): int8 halves resident
    # KV bytes (~2x slot capacity at equal HBM) and every KV transfer.
    if model.spec.kv_cache.enabled():
        args += ["--kv-dtype", model.spec.kv_cache.dtype]
    # Engine snapshot/restore (CRD coldStart: block): boot restores the
    # post-conversion param tree + compilation cache from the snapshot
    # store instead of re-running HF conversion and XLA compilation.
    cold = model.spec.cold_start
    if cold.enabled:
        args += ["--snapshot-url", cold.snapshot_url]
        if not cold.publish:
            args += ["--snapshot-no-publish"]
    # Adapters are NOT baked into the spec: they hot-swap through the
    # /v1/load_lora_adapter admin API (see operator/adapters.py), so adapter
    # changes never trigger a pod rollout.
    args += list(model.spec.args)

    env.append({"name": "TPU_TOPOLOGY", "value": mcfg.tpu_topology or "1x1"})
    env.append({"name": "TPU_CHIPS", "value": str(mcfg.tpu_chips or 1)})
    # The engine refuses to serve where JAX fell back to the CPU unasked
    # (parallel/mesh.py:require_accelerator). A Pod whose profile requests
    # no device (the `cpu` profile) is on the CPU on purpose, and says so
    # by name.
    if not mcfg.requests_device:
        env.append({"name": "JAX_PLATFORMS", "value": "cpu"})
    env.append({"name": "JAX_COMPILATION_CACHE_DIR", "value": JAX_CACHE_DIR})
    volumes.append({"name": "jax-cache", "emptyDir": {}})
    mounts.append({"name": "jax-cache", "mountPath": JAX_CACHE_DIR})
    env += model_env(model)

    container = {
        "name": "server",
        "image": mcfg.image,
        "args": args,
        "env": env,
        "ports": [{"containerPort": PORT, "name": "http"}],
        "resources": {"requests": mcfg.requests, "limits": mcfg.limits},
        "volumeMounts": mounts,
        # Sharded weight streaming into slice HBM can take a long time on
        # first boot (no cache); same 3h ceiling the reference grants vLLM.
        # Snapshot-restore boots skip conversion and most compilation, so
        # the budget tightens to 30min: a replica stuck that long is
        # broken and should be restarted, not waited on for 3h. (The
        # first full-load boot of a model still fits — publish happens
        # after Ready, and the fallback path only re-runs conversion.)
        "startupProbe": {
            "httpGet": {"path": "/health", "port": PORT},
            "periodSeconds": 10,
            "failureThreshold": 180 if cold.enabled else 1080,
        },
        "readinessProbe": {
            "httpGet": {"path": "/health", "port": PORT},
            "periodSeconds": 10,
        },
        "livenessProbe": {
            "httpGet": {"path": "/health", "port": PORT},
            "periodSeconds": 30,
            "failureThreshold": 3,
        },
        # preStop fires BEFORE kubelet sends SIGTERM: the drain endpoint
        # flips /health to 503 (LB ejection) and stops admission while
        # routing still points here — no request lands on a dying Pod.
        # (kubelet's httpGet hook can only GET; the server accepts GET
        # /v1/drain for exactly this.)
        "lifecycle": {
            "preStop": {
                "httpGet": {"path": "/v1/drain", "port": PORT},
            },
        },
    }
    if cfg.model_server_pods.container_security_context:
        container["securityContext"] = cfg.model_server_pods.container_security_context
    if model.spec.env_from:
        container["envFrom"] = list(model.spec.env_from)

    pod["spec"]["containers"] = [container]
    pod["spec"]["volumes"] = volumes
    # Drain budget + 15s slack for the terminated-straggler flush and
    # process teardown; kubelet's default 30s would KILL mid-drain for
    # any model configured above it.
    pod["spec"]["terminationGracePeriodSeconds"] = drain_timeout + 15
    pod["metadata"]["annotations"]["model-pod-port"] = str(PORT)
    return pod


# ---- multi-host replicas -----------------------------------------------------
#
# A v5e slice larger than 8 chips spans hosts; every host runs the same
# engine process and jax.distributed joins them into one mesh over DCN
# (engine flags --dcn-coordinator/--process-id/--num-processes,
# kubeai_tpu/engine/server.py). The operator's unit becomes a POD GROUP:
# one Pod per host with a stable hostname under a headless Service, host
# 0 as coordinator and the only HTTP-serving endpoint. No reference
# analog (strict one-Pod-per-replica, pod_plan.go:28-156).

DCN_PORT = 8476


def _dns_label(s: str) -> str:
    """Model names are DNS SUBDOMAINS (dots allowed, e.g.
    llama-3.1-8b...), but Service names and Pod hostnames are DNS
    LABELS. Sanitize dots to dashes WITH a short hash of the original —
    plain replacement would collide "llama-3.1" with "llama-3-1" in the
    same namespace."""
    if "." not in s:
        return s
    import hashlib

    digest = hashlib.sha256(s.encode()).hexdigest()[:6]
    return f"{s.replace('.', '-')}-{digest}"


def hosts_service_name(model: Model) -> str:
    return f"model-{_dns_label(model.name)}-hosts"


def multihost_service(model: Model) -> dict:
    """Headless Service giving host Pods stable DNS for the coordinator."""
    from kubeai_tpu.crd import metadata as md

    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "name": hosts_service_name(model),
            "namespace": model.namespace,
            "labels": {md.POD_MODEL_LABEL: model.name},
        },
        "spec": {
            "clusterIP": "None",
            # Pods can only become ready AFTER jax.distributed joins all
            # hosts, and hosts join by resolving each other's per-pod DNS
            # — which must therefore be published for NOT-ready Pods, or
            # the group deadlocks at startup (the StatefulSet peer-
            # discovery pattern).
            "publishNotReadyAddresses": True,
            "selector": {md.POD_MODEL_LABEL: model.name},
            "ports": [{"name": "dcn", "port": DCN_PORT}],
        },
    }


def kubeai_tpu_host_pods(
    model: Model, cfg: System, mcfg: ModelConfig, group: int
) -> list[dict]:
    """Render one replica group: num_hosts Pods with fixed names (stable
    hostnames are part of the coordinator address, so generateName-style
    random suffixes can't be used)."""
    from kubeai_tpu.crd import metadata as md

    svc = hosts_service_name(model)
    label_name = _dns_label(model.name)
    coord_host = f"model-{label_name}-g{group}-h0"
    coordinator = f"{coord_host}.{svc}.{model.namespace}.svc:{DCN_PORT}"
    pods = []
    for h in range(mcfg.num_hosts):
        pod = kubeai_tpu_pod(model, cfg, mcfg, f"g{group}-h{h}")
        spec = pod["spec"]
        spec["hostname"] = f"model-{label_name}-g{group}-h{h}"
        spec["subdomain"] = svc
        c = spec["containers"][0]
        c["args"] += [
            "--dcn-coordinator", coordinator,
            "--process-id", str(h),
            "--num-processes", str(mcfg.num_hosts),
        ]
        c["env"] += [
            {"name": "TPU_COORDINATOR", "value": coordinator},
            {"name": "TPU_PROCESS_ID", "value": str(h)},
            {"name": "TPU_PROCESS_COUNT", "value": str(mcfg.num_hosts)},
            {
                "name": "TPU_WORKER_HOSTNAMES",
                "value": ",".join(
                    f"model-{label_name}-g{group}-h{i}.{svc}"
                    for i in range(mcfg.num_hosts)
                ),
            },
        ]
        if model.spec.sharding.mesh:
            # Logical mesh axis sizes (data/fsdp/tp) for the engine's
            # SpecLayout; rendered in a stable axis order so the pod
            # hash doesn't churn on dict ordering.
            c["env"].append({
                "name": "TPU_MESH",
                "value": ",".join(
                    f"{axis}={model.spec.sharding.mesh[axis]}"
                    for axis in ("data", "fsdp", "tp")
                    if axis in model.spec.sharding.mesh
                ),
            })
        labels = pod["metadata"]["labels"]
        labels[md.POD_GROUP_LABEL] = str(group)
        labels[md.POD_HOST_LABEL] = str(h)
        labels[md.POD_GROUP_SIZE_LABEL] = str(mcfg.num_hosts)
        if h > 0:
            # Workers join the mesh but never serve HTTP: the LB must not
            # route to them.
            pod["metadata"]["annotations"][
                md.MODEL_POD_SERVING_ANNOTATION
            ] = "false"
        pods.append(pod)
    return pods
