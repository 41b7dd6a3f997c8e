"""The Model custom resource (reference: api/k8s/v1/model_types.go).

Python dataclasses standing in for the CRD structs, with `validate()`
enforcing the reference's CEL + kubebuilder rules
(reference: api/k8s/v1/model_types.go:27-35,54-66,210-248) so invalid Models
are rejected at admission just like the real CRD would.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

FEATURE_TEXT_GENERATION = "TextGeneration"
FEATURE_TEXT_EMBEDDING = "TextEmbedding"
FEATURE_SPEECH_TO_TEXT = "SpeechToText"
ALL_FEATURES = (
    FEATURE_TEXT_GENERATION,
    FEATURE_TEXT_EMBEDDING,
    FEATURE_SPEECH_TO_TEXT,
)

# Engines (reference: api/k8s/v1/model_types.go:64-66 enum OLlama;VLLM;
# FasterWhisper;Infinity). KubeAITPU is the in-tree TPU-native engine that
# replaces external vLLM images for the TPU path.
ENGINE_KUBEAI_TPU = "KubeAITPU"
ENGINE_OLLAMA = "OLlama"
ENGINE_VLLM = "VLLM"
ENGINE_FASTER_WHISPER = "FasterWhisper"
ENGINE_INFINITY = "Infinity"
ALL_ENGINES = (
    ENGINE_KUBEAI_TPU,
    ENGINE_OLLAMA,
    ENGINE_VLLM,
    ENGINE_FASTER_WHISPER,
    ENGINE_INFINITY,
)

LB_STRATEGY_LEAST_LOAD = "LeastLoad"
LB_STRATEGY_PREFIX_HASH = "PrefixHash"

URL_SCHEMES = ("hf", "pvc", "ollama", "s3", "gs", "oss")

MAX_NAME_LEN = 40  # reference: api/k8s/v1/model_types.go:248
MAX_FILES = 10  # reference: api/k8s/v1/model_types.go:210-214
MAX_FILE_PATH_LEN = 1024
MAX_FILE_CONTENT_LEN = 100_000


class ValidationError(ValueError):
    pass


@dataclasses.dataclass
class Adapter:
    """(reference: api/k8s/v1/model_types.go:155-170)"""

    name: str = ""
    url: str = ""

    def validate(self) -> None:
        if not re.fullmatch(r"^[a-z0-9]+(?:[-a-z0-9]*[a-z0-9])?$", self.name or ""):
            raise ValidationError(f"adapter name {self.name!r} must be lowercase DNS label")
        if len(self.name) > 63:
            raise ValidationError("adapter name too long")
        if not self.url:
            raise ValidationError("adapter url required")


@dataclasses.dataclass
class File:
    """(reference: api/k8s/v1/model_types.go:210-224)"""

    path: str = ""
    content: str = ""

    def validate(self) -> None:
        if not self.path or len(self.path) > MAX_FILE_PATH_LEN:
            raise ValidationError("file path required, <= 1024 chars")
        if not self.path.startswith("/") or ".." in self.path:
            raise ValidationError(f"file path {self.path!r} must be absolute without '..'")
        if len(self.content) > MAX_FILE_CONTENT_LEN:
            raise ValidationError("file content too large")


@dataclasses.dataclass
class PrefixHash:
    """CHWBL tuning (reference: api/k8s/v1/model_types.go:190-208)."""

    mean_load_percentage: int = 125
    replication: int = 256
    prefix_char_length: int = 100

    def validate(self) -> None:
        if self.mean_load_percentage < 100:
            raise ValidationError("prefixHash.meanLoadPercentage must be >= 100")


@dataclasses.dataclass
class CircuitBreakerSpec:
    """Per-model circuit-breaker tuning (no reference analog — the
    reference trusts readiness probes alone). Every field defaults to 0
    meaning "inherit the system config `resilience:` default"; set
    fields override per model (kubeai_tpu/routing/health.py holds the
    state machine)."""

    # Sliding window of attempt outcomes considered by the rate rule.
    window: int = 0
    # Trip after this many consecutive failures.
    consecutive_failures: int = 0
    # Trip when >= minSamples outcomes are windowed and the failure
    # fraction reaches this rate (percent-free fraction in (0, 1]).
    failure_rate: float = 0.0
    min_samples: int = 0
    # Seconds an open circuit waits before admitting a half-open probe.
    open_seconds: float = 0.0

    def enabled(self) -> bool:
        return bool(
            self.window or self.consecutive_failures or self.failure_rate
            or self.min_samples or self.open_seconds
        )

    def validate(self) -> None:
        if self.window < 0:
            raise ValidationError("circuitBreaker.window must be >= 0")
        if self.consecutive_failures < 0:
            raise ValidationError(
                "circuitBreaker.consecutiveFailures must be >= 0"
            )
        if not 0.0 <= self.failure_rate <= 1.0:
            raise ValidationError(
                "circuitBreaker.failureRate must be in [0, 1], got "
                f"{self.failure_rate}"
            )
        if self.min_samples < 0:
            raise ValidationError("circuitBreaker.minSamples must be >= 0")
        if self.open_seconds < 0:
            raise ValidationError("circuitBreaker.openSeconds must be >= 0")


@dataclasses.dataclass
class LoadBalancing:
    """(reference: api/k8s/v1/model_types.go:172-188)"""

    strategy: str = LB_STRATEGY_LEAST_LOAD
    prefix_hash: PrefixHash = dataclasses.field(default_factory=PrefixHash)
    circuit_breaker: CircuitBreakerSpec = dataclasses.field(
        default_factory=CircuitBreakerSpec
    )

    def validate(self) -> None:
        if self.strategy not in (LB_STRATEGY_LEAST_LOAD, LB_STRATEGY_PREFIX_HASH):
            raise ValidationError(f"unknown loadBalancing.strategy {self.strategy!r}")
        self.prefix_hash.validate()
        self.circuit_breaker.validate()


# Priority classes of the in-tree engine's scheduler
# (kubeai_tpu/scheduling/scheduler.py PRIORITY_CLASSES — duplicated here so
# the CRD layer stays import-light and admission errors mention CRD terms).
SCHEDULING_PRIORITY_CLASSES = ("realtime", "standard", "batch")


@dataclasses.dataclass
class Scheduling:
    """SLO-aware queue discipline for the in-tree engine (no reference
    analog — the reference delegates queueing to vLLM). Rendered as
    engine flags --default-priority / --queue-shares / --max-deadline-ms
    (kubeai_tpu/operator/engines/kubeai_tpu_engine.py)."""

    # Priority class for requests without an X-Priority header.
    # "" = engine default ("standard").
    default_priority: str = ""
    # class -> guaranteed fraction of dispatches while backlogged, e.g.
    # {"batch": 0.05} keeps batch work trickling under realtime load.
    queue_shares: dict[str, float] = dataclasses.field(default_factory=dict)
    # Cap on client X-Deadline-Ms values AND the default admission
    # deadline when none is sent. 0 disables deadline admission.
    max_deadline_ms: int = 0

    def enabled(self) -> bool:
        return bool(
            self.default_priority or self.queue_shares or self.max_deadline_ms
        )

    def validate(self) -> None:
        if (
            self.default_priority
            and self.default_priority not in SCHEDULING_PRIORITY_CLASSES
        ):
            raise ValidationError(
                "scheduling.defaultPriority must be one of "
                f"{SCHEDULING_PRIORITY_CLASSES}, got {self.default_priority!r}"
            )
        for cls, share in self.queue_shares.items():
            if cls not in SCHEDULING_PRIORITY_CLASSES:
                raise ValidationError(
                    f"scheduling.queueShares: unknown class {cls!r}"
                )
            try:
                share = float(share)
            except (TypeError, ValueError):
                raise ValidationError(
                    f"scheduling.queueShares[{cls!r}] must be a number"
                )
            if not 0.0 <= share < 1.0:
                raise ValidationError(
                    f"scheduling.queueShares[{cls!r}] must be in [0, 1), "
                    f"got {share}"
                )
        if self.max_deadline_ms < 0:
            raise ValidationError("scheduling.maxDeadlineMs must be >= 0")


@dataclasses.dataclass
class Tenancy:
    """Per-model overrides for the front door's tenant admission layer
    (kubeai_tpu/fleet/tenancy; system `tenancy:` config holds the
    defaults). DOOR state: enforced before any work is queued, rendered
    into no engine flag or pod spec, and valid for every engine — the
    door fronts them all. A field set to 0 inherits the system default;
    `exempt: true` opts the model out of door admission entirely."""

    requests_per_second: float = 0.0
    request_burst: float = 0.0
    tokens_per_second: float = 0.0
    token_burst: float = 0.0
    window_seconds: float = 0.0
    window_token_budget: int = 0
    exempt: bool = False

    def enabled(self) -> bool:
        return bool(
            self.requests_per_second or self.request_burst
            or self.tokens_per_second or self.token_burst
            or self.window_seconds or self.window_token_budget
            or self.exempt
        )

    def validate(self) -> None:
        for field, value in (
            ("requestsPerSecond", self.requests_per_second),
            ("requestBurst", self.request_burst),
            ("tokensPerSecond", self.tokens_per_second),
            ("tokenBurst", self.token_burst),
            ("windowSeconds", self.window_seconds),
            ("windowTokenBudget", self.window_token_budget),
        ):
            try:
                ok = float(value) >= 0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValidationError(
                    f"tenancy.{field} must be a number >= 0"
                )


@dataclasses.dataclass
class Slo:
    """Per-model service-level objectives for the SLO plane
    (kubeai_tpu/fleet/slo; system `slo:` config holds the defaults and
    the burn-rate windows). Pure observability/control-bias state: the
    evaluator judges these each tick from fleet snapshots, and a breach
    biases scaling — no engine flag or pod spec renders from this
    block. A field set to 0 inherits the system default; a model whose
    resolved targets are all 0 has no objectives and is never judged."""

    ttft_p95_seconds: float = 0.0   # 95% of requests see TTFT <= this
    itl_p99_seconds: float = 0.0    # 99% of tokens see ITL <= this
    availability: float = 0.0       # request success target, e.g. 0.999
    max_shed_rate: float = 0.0      # max fraction door-shed, e.g. 0.05

    def enabled(self) -> bool:
        return bool(
            self.ttft_p95_seconds or self.itl_p99_seconds
            or self.availability or self.max_shed_rate
        )

    def validate(self) -> None:
        for field, value in (
            ("ttftP95Seconds", self.ttft_p95_seconds),
            ("itlP99Seconds", self.itl_p99_seconds),
        ):
            try:
                ok = float(value) >= 0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValidationError(f"slo.{field} must be a number >= 0")
        for field, value in (
            ("availability", self.availability),
            ("maxShedRate", self.max_shed_rate),
        ):
            try:
                ok = 0.0 <= float(value) < 1.0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValidationError(f"slo.{field} must be in [0, 1)")


ROLLOUT_STRATEGY_CANARY = "canary"
ROLLOUT_STRATEGIES = ("", ROLLOUT_STRATEGY_CANARY)


@dataclasses.dataclass
class RolloutJudge:
    """Comparative-judgment thresholds for a progressive rollout: the
    new hash is condemned when it looks WORSE than the old one by these
    margins, from the fleet plane's per-version aggregates. A field set
    to 0 inherits the rollout controller's default."""

    window_seconds: float = 0.0     # observation window per judgment
    ttft_p95_ratio: float = 0.0     # max new/old TTFT p95 ratio, e.g. 1.5
    max_breaker_trips: int = 0      # open circuits tolerated on the new hash

    def validate(self) -> None:
        for field, value in (
            ("windowSeconds", self.window_seconds),
            ("ttftP95Ratio", self.ttft_p95_ratio),
            ("maxBreakerTrips", self.max_breaker_trips),
        ):
            try:
                ok = float(value) >= 0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValidationError(
                    f"rollout.judge.{field} must be a number >= 0"
                )


@dataclasses.dataclass
class Rollout:
    """Progressive-delivery policy for spec-hash changes
    (kubeai_tpu/operator/rollout). Operator-plane state: nothing here
    renders into an engine flag or pod spec — the rollout controller
    paces the pod plan through canary → ramp → complete, the LB
    enforces the canary traffic share at routing time, and the SLO
    machinery judges new vs old comparatively. No `rollout:` block (or
    strategy "") keeps the classic surge rollout byte-identical."""

    strategy: str = ""              # "" = classic surge; "canary"
    canary_percent: float = 10.0    # traffic+replica share of the canary step
    step_seconds: float = 60.0      # dwell per governed step
    max_unavailable: int = 0        # extra replicas replaceable per step
    auto_rollback: bool = True      # pin the old hash on a failed judgment
    judge: RolloutJudge = dataclasses.field(default_factory=RolloutJudge)

    def enabled(self) -> bool:
        return self.strategy == ROLLOUT_STRATEGY_CANARY

    def validate(self) -> None:
        if self.strategy not in ROLLOUT_STRATEGIES:
            raise ValidationError(
                f"rollout.strategy must be one of {ROLLOUT_STRATEGIES}"
            )
        try:
            pct_ok = 0.0 < float(self.canary_percent) <= 100.0
        except (TypeError, ValueError):
            pct_ok = False
        if self.enabled() and not pct_ok:
            raise ValidationError(
                "rollout.canaryPercent must be in (0, 100]"
            )
        for field, value in (
            ("stepSeconds", self.step_seconds),
            ("maxUnavailable", self.max_unavailable),
        ):
            try:
                ok = float(value) >= 0
            except (TypeError, ValueError):
                ok = False
            if not ok:
                raise ValidationError(
                    f"rollout.{field} must be a number >= 0"
                )
        self.judge.validate()


@dataclasses.dataclass
class RoleScaling:
    """Replica bounds for one disaggregated role's pod group. The
    autoscaler writes the applied count into a Model annotation
    (crd.metadata.role_replicas_annotation); these bounds clamp it."""

    min_replicas: int = 1
    max_replicas: int | None = None

    def validate(self, role: str) -> None:
        if self.min_replicas < 1:
            # Disaggregated groups do not scale to zero: a pool with no
            # prefill (or no decode) replicas can serve nothing, and the
            # proxy's fallback would silently absorb the whole model.
            raise ValidationError(
                f"disaggregation.{role}.minReplicas must be >= 1"
            )
        if self.max_replicas is not None and self.max_replicas < self.min_replicas:
            raise ValidationError(
                f"disaggregation.{role}.maxReplicas must be >= minReplicas"
            )


@dataclasses.dataclass
class Disaggregation:
    """Disaggregated prefill/decode serving (kubeai_tpu/disagg; no
    reference analog — the reference's vLLM replicas are monolithic).
    When enabled, the operator renders TWO pod groups (role labels
    prefill/decode, engine flag --role), the LB routes the two-hop
    prefill→decode flow, and the autoscaler scales each role from its
    own bottleneck signal: prefill from queue depth/oldest-wait/TTFT,
    decode from KV utilization and active-slot occupancy."""

    enabled: bool = False
    prefill: RoleScaling = dataclasses.field(default_factory=RoleScaling)
    decode: RoleScaling = dataclasses.field(default_factory=RoleScaling)
    # Queued prefills per prefill replica before another replica is asked
    # for (the prefill-role demand target).
    prefill_target_queue: int = 4
    # Mean engine TTFT (seconds) past which prefill is considered
    # pressured regardless of queue depth. 0 disables the TTFT signal.
    prefill_target_ttft_seconds: float = 0.0
    # KV-pool / slot-occupancy fraction the decode group scales to hold.
    decode_target_utilization: float = 0.8
    # Transfer limits: serialized-handoff size cap (0 = unlimited) and
    # the prefill engine's push timeout toward the decode pool.
    max_transfer_mb: int = 0
    transfer_timeout_seconds: float = 30.0

    def role(self, role: str) -> RoleScaling:
        if role == "prefill":
            return self.prefill
        if role == "decode":
            return self.decode
        raise KeyError(role)

    def validate(self) -> None:
        if not self.enabled:
            return
        self.prefill.validate("prefill")
        self.decode.validate("decode")
        if self.prefill_target_queue < 1:
            raise ValidationError(
                "disaggregation.prefillTargetQueue must be >= 1"
            )
        if self.prefill_target_ttft_seconds < 0:
            raise ValidationError(
                "disaggregation.prefillTargetTtftSeconds must be >= 0"
            )
        if not 0.0 < self.decode_target_utilization <= 1.0:
            raise ValidationError(
                "disaggregation.decodeTargetUtilization must be in (0, 1]"
            )
        if self.max_transfer_mb < 0:
            raise ValidationError(
                "disaggregation.maxTransferMB must be >= 0"
            )
        if self.transfer_timeout_seconds <= 0:
            raise ValidationError(
                "disaggregation.transferTimeoutSeconds must be > 0"
            )


@dataclasses.dataclass
class KVSharing:
    """Cluster-shared prefix/KV cache tier (in-tree engine only; no
    reference analog). When enabled, replicas publish their held
    page-hash chains through /v1/state, the LB routes base-model
    requests to the endpoint holding the deepest matching chain
    (falling back to classic CHWBL when the holdings map is stale or
    empty), and the serving replica pulls the common-prefix KV pages
    from the holding peer over the chunked-HTTP page-export transport
    instead of recomputing them."""

    enabled: bool = False
    # KV page size in tokens — must match the engine's --page-size so
    # the front-door chain hashes line up with the engine's prefix
    # cache keys.
    page_size: int = 16
    # Optional tokenizer directory for the front-door chain computer.
    # Empty = the deterministic byte tokenizer (matches an engine
    # serving without a model directory).
    tokenizer_dir: str = ""
    # Serialized page-export size cap per fetch (0 = unlimited) and the
    # requester's fetch timeout toward the holding peer.
    max_transfer_mb: int = 0
    fetch_timeout_seconds: float = 5.0
    # Optional object-store URL evicted idle pages spill to (and are
    # re-filled from). Empty = in-memory spill only.
    spill_url: str = ""

    def validate(self) -> None:
        if not self.enabled:
            return
        if self.page_size < 1:
            raise ValidationError("kvSharing.pageSize must be >= 1")
        if self.max_transfer_mb < 0:
            raise ValidationError("kvSharing.maxTransferMB must be >= 0")
        if self.fetch_timeout_seconds <= 0:
            raise ValidationError(
                "kvSharing.fetchTimeoutSeconds must be > 0"
            )


SNAPSHOT_URL_SCHEMES = ("gs", "s3", "oss", "file")


@dataclasses.dataclass
class ColdStart:
    """Serverless-grade cold start via engine snapshots (in-tree engine
    only; no reference analog). When enabled, a replica that boots the
    slow path (HF conversion + XLA compile) publishes its post-warmup
    state — orbax params + compilation-cache artifacts — under
    `snapshotURL`, keyed by a fingerprint of (model, engine config,
    mesh shape, snapshot version); later replicas restore from the
    snapshot instead, skipping conversion and most compilation. The
    operator tightens the startup-probe budget accordingly, and the
    capacity planner may prewarm replicas ahead of forecast demand
    (docs/concepts/cold-start.md)."""

    enabled: bool = False
    # Object-store URL the snapshot tree lives under (gs://, s3://,
    # oss://, or file:// for a shared filesystem mount).
    snapshot_url: str = ""
    # Whether a full-load boot publishes its snapshot for later
    # replicas (false = restore-only consumers).
    publish: bool = True
    # Whether the capacity planner may order predictive prewarm
    # replicas for this model.
    prewarm: bool = True

    def validate(self) -> None:
        if not self.enabled:
            return
        if not self.snapshot_url:
            raise ValidationError(
                "coldStart.snapshotURL required when coldStart.enabled"
            )
        scheme = (
            self.snapshot_url.split("://", 1)[0]
            if "://" in self.snapshot_url else ""
        )
        if scheme not in SNAPSHOT_URL_SCHEMES:
            raise ValidationError(
                "coldStart.snapshotURL scheme must be one of "
                f"{list(SNAPSHOT_URL_SCHEMES)}, got {self.snapshot_url!r}"
            )


KV_CACHE_DTYPES = ("bfloat16", "int8")


@dataclasses.dataclass
class KVCacheSpec:
    """Paged KV-cache storage configuration (in-tree engine only).
    dtype "int8" stores pages quantized with per-token-per-head scales
    (engine flag --kv-dtype): ~2x slot capacity at equal HBM and half
    the KV bytes on every disagg handoff, peer prefix fetch and
    objstore spill. Replicas of one model must agree on the dtype —
    bf16 and int8 pools refuse each other's KV on the wire rather
    than cast."""

    dtype: str = ""  # "" = engine default (bfloat16)

    def enabled(self) -> bool:
        return bool(self.dtype)

    def validate(self) -> None:
        if self.dtype and self.dtype not in KV_CACHE_DTYPES:
            raise ValidationError(
                f"kvCache.dtype must be one of {list(KV_CACHE_DTYPES)}"
            )


# Logical mesh axes a sharding block may size (SpecLayout vocabulary:
# data-parallel replicas, FSDP weight shards, tensor-parallel shards).
MESH_AXES = ("data", "fsdp", "tp")

_TOPOLOGY_RE = re.compile(r"^\d+x\d+(x\d+)?$")


@dataclasses.dataclass
class Sharding:
    """Multi-host slice-group serving (in-tree engine only). Declares
    that one replica is a *process group* of `hosts` pods spanning one
    ICI-connected TPU slice of the given `topology` (e.g. "4x4"), with
    the model partitioned over the logical `mesh` axes (data/fsdp/tp).
    The operator then plans, repairs, routes, and bin-packs the group
    as one atomic unit — never a partial group. hosts=0 / topology=""
    inherit the resource profile's values; an explicit value here wins
    over the profile."""

    hosts: int = 0  # host pods per replica; 0 = profile default
    topology: str = ""  # ICI slice topology, e.g. "4x4" / "4x4x4"
    mesh: dict[str, int] = dataclasses.field(default_factory=dict)

    def enabled(self) -> bool:
        return bool(self.hosts or self.topology or self.mesh)

    def validate(self) -> None:
        if self.hosts < 0:
            raise ValidationError("sharding.hosts must be >= 0")
        if self.topology and not _TOPOLOGY_RE.match(self.topology):
            raise ValidationError(
                'sharding.topology must look like "4x4" or "4x4x4", '
                f"got {self.topology!r}"
            )
        for axis, size in self.mesh.items():
            if axis not in MESH_AXES:
                raise ValidationError(
                    f"sharding.mesh axis must be one of {list(MESH_AXES)}, "
                    f"got {axis!r}"
                )
            if not isinstance(size, int) or size < 1:
                raise ValidationError(
                    f"sharding.mesh[{axis!r}] must be an integer >= 1"
                )


@dataclasses.dataclass
class ModelSpec:
    """(reference: api/k8s/v1/model_types.go:36-144)"""

    url: str = ""
    engine: str = ENGINE_KUBEAI_TPU
    features: list[str] = dataclasses.field(default_factory=list)
    adapters: list[Adapter] = dataclasses.field(default_factory=list)
    resource_profile: str = ""  # "name:count"
    cache_profile: str = ""  # immutable (reference: model_types.go:76)
    image: str = ""
    args: list[str] = dataclasses.field(default_factory=list)
    env: dict[str, str] = dataclasses.field(default_factory=dict)
    env_from: list[dict] = dataclasses.field(default_factory=list)
    replicas: int | None = None
    min_replicas: int = 0
    max_replicas: int | None = None
    autoscaling_disabled: bool = False
    target_requests: int = 100  # reference: model_types.go:115
    scale_down_delay_seconds: int = 30  # reference: model_types.go:120
    load_balancing: LoadBalancing = dataclasses.field(default_factory=LoadBalancing)
    files: list[File] = dataclasses.field(default_factory=list)
    priority_class_name: str = ""
    owner: str = ""
    # Speculative decoding (in-tree engine only; no reference analog —
    # there, engine features ride spec.args, model_types.go:85-90):
    # speculativeTokens > 0 turns on prompt-lookup speculation;
    # draftUrl additionally loads a small same-family draft model that
    # proposes instead of the lookup (engine flags --speculate /
    # --draft-url, kubeai_tpu/engine/server.py).
    speculative_tokens: int = 0
    draft_url: str = ""
    # SLO-aware queue discipline (in-tree engine only).
    scheduling: Scheduling = dataclasses.field(default_factory=Scheduling)
    # Front-door tenant admission overrides (door state, every engine).
    tenancy: Tenancy = dataclasses.field(default_factory=Tenancy)
    # Per-model SLO targets (observability/control-bias, every engine).
    slo: Slo = dataclasses.field(default_factory=Slo)
    # Progressive-delivery policy (operator plane, every engine).
    rollout: Rollout = dataclasses.field(default_factory=Rollout)
    # Disaggregated prefill/decode serving (in-tree engine only).
    disaggregation: Disaggregation = dataclasses.field(
        default_factory=Disaggregation
    )
    # Cluster-shared prefix/KV cache tier (in-tree engine only).
    kv_sharing: KVSharing = dataclasses.field(default_factory=KVSharing)
    # Paged KV-cache storage dtype (in-tree engine only).
    kv_cache: KVCacheSpec = dataclasses.field(default_factory=KVCacheSpec)
    # Engine snapshot/restore cold-start path (in-tree engine only).
    cold_start: ColdStart = dataclasses.field(default_factory=ColdStart)
    # Multi-host slice-group serving (in-tree engine only).
    sharding: Sharding = dataclasses.field(default_factory=Sharding)
    # Graceful-drain budget: seconds an engine waits for in-flight
    # generations after SIGTERM / POST /v1/drain before terminating the
    # remainder. 0 = the system config `resilience.drainTimeout`
    # default. Rendered as the engine's --drain-timeout flag plus the
    # Pod's terminationGracePeriodSeconds and preStop hook.
    drain_timeout_seconds: int = 0

    def url_scheme(self) -> str:
        return self.url.split("://", 1)[0] if "://" in self.url else ""

    def validate(self) -> None:
        # url scheme CEL rule (reference: model_types.go:54).
        if not self.url:
            raise ValidationError("spec.url required")
        if self.url_scheme() not in URL_SCHEMES:
            raise ValidationError(
                f"spec.url scheme must be one of {URL_SCHEMES}, got {self.url!r}"
            )
        if self.engine not in ALL_ENGINES:
            raise ValidationError(f"spec.engine must be one of {ALL_ENGINES}")
        for f in self.features:
            if f not in ALL_FEATURES:
                raise ValidationError(f"unknown feature {f!r}")
        # cross-field CEL rules (reference: model_types.go:27-35):
        if self.engine == ENGINE_OLLAMA and self.url_scheme() not in ("ollama", "pvc"):
            raise ValidationError("OLlama engine requires ollama:// or pvc:// url")
        if self.url_scheme() == "ollama" and self.engine != ENGINE_OLLAMA:
            raise ValidationError("ollama:// url requires engine OLlama")
        if self.min_replicas < 0:
            raise ValidationError("minReplicas must be >= 0")
        if self.max_replicas is not None and self.max_replicas < max(self.min_replicas, 1):
            raise ValidationError("maxReplicas must be >= minReplicas and >= 1")
        if self.replicas is not None and self.replicas < 0:
            raise ValidationError("replicas must be >= 0")
        # A nil maxReplicas is VALID (unbounded autoscaling) — reference
        # CEL only relates the bounds when both are set
        # (reference: model_types.go:30, test replicas-1-2-nil-valid).
        if self.cache_profile and self.url_scheme() not in (
            "hf", "s3", "gs", "oss"
        ):
            # reference CEL rule (model_types.go:27).
            raise ValidationError(
                'cacheProfile is only supported with urls of format "hf://", '
                '"s3://", "gs://", or "oss://"'
            )
        if self.adapters and self.engine not in (ENGINE_VLLM, ENGINE_KUBEAI_TPU):
            # reference CEL restricts adapters to VLLM (model_types.go:31);
            # the in-tree TPU engine hot-swaps adapters natively too.
            raise ValidationError(
                "adapters only supported with VLLM or KubeAITPU engines"
            )
        if self.speculative_tokens < 0:
            raise ValidationError("speculativeTokens must be >= 0")
        if (
            self.speculative_tokens or self.draft_url
        ) and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "speculativeTokens/draftUrl require the KubeAITPU engine"
            )
        if self.draft_url:
            if self.speculative_tokens < 1:
                # Mirrors the engine-server flag contract (--draft-url
                # requires --speculate > 0, kubeai_tpu/engine/server.py).
                raise ValidationError(
                    "draftUrl requires speculativeTokens >= 1"
                )
            draft_scheme = (
                self.draft_url.split("://", 1)[0]
                if "://" in self.draft_url else ""
            )
            if draft_scheme not in ("hf", "pvc", "s3", "gs", "oss"):
                raise ValidationError(
                    'draftUrl must use "hf://", "pvc://", "s3://", '
                    f'"gs://", or "oss://", got {self.draft_url!r}'
                )
        self.scheduling.validate()
        if self.scheduling.enabled() and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "spec.scheduling requires the KubeAITPU engine"
            )
        # Deliberately no engine gate: tenancy is door state, enforced
        # before any engine sees the request.
        self.tenancy.validate()
        # Same: SLO targets are judged from the fleet plane — no engine
        # needs to know them.
        self.slo.validate()
        # Same: rollout pacing is operator-plane state; no engine flag
        # or pod spec renders from it.
        self.rollout.validate()
        self.disaggregation.validate()
        if self.disaggregation.enabled and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "spec.disaggregation requires the KubeAITPU engine"
            )
        self.kv_sharing.validate()
        if self.kv_sharing.enabled and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "spec.kvSharing requires the KubeAITPU engine"
            )
        self.kv_cache.validate()
        if self.kv_cache.enabled() and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "spec.kvCache requires the KubeAITPU engine"
            )
        self.cold_start.validate()
        if self.cold_start.enabled and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "spec.coldStart requires the KubeAITPU engine"
            )
        self.sharding.validate()
        if self.sharding.enabled() and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "spec.sharding requires the KubeAITPU engine"
            )
        if self.kv_cache.dtype == "int8" and self.speculative_tokens:
            raise ValidationError(
                "kvCache.dtype=int8 does not compose with "
                "speculativeTokens (the verify kernels read bf16 pools)"
            )
        if self.drain_timeout_seconds < 0:
            raise ValidationError("drainTimeoutSeconds must be >= 0")
        if self.drain_timeout_seconds and self.engine != ENGINE_KUBEAI_TPU:
            raise ValidationError(
                "spec.drainTimeoutSeconds requires the KubeAITPU engine"
            )
        if self.target_requests < 1:
            raise ValidationError("targetRequests must be >= 1")
        if self.scale_down_delay_seconds < 0:
            raise ValidationError("scaleDownDelaySeconds must be >= 0")
        if self.resource_profile:
            parts = self.resource_profile.split(":")
            if len(parts) != 2 or not parts[0]:
                raise ValidationError(
                    'resourceProfile must be "name:count"'
                )
            try:
                count = int(parts[1])
            except ValueError:
                raise ValidationError("resourceProfile count must be an integer")
            if count < 1:
                raise ValidationError("resourceProfile count must be >= 1")
        if len(self.files) > MAX_FILES:
            raise ValidationError(f"at most {MAX_FILES} files allowed")
        seen_paths = set()
        for f in self.files:
            f.validate()
            if f.path in seen_paths:
                raise ValidationError(f"duplicate file path {f.path}")
            seen_paths.add(f.path)
        seen_adapters = set()
        for a in self.adapters:
            a.validate()
            if a.name in seen_adapters:
                raise ValidationError(f"duplicate adapter {a.name}")
            seen_adapters.add(a.name)
        self.load_balancing.validate()


def disagg_role_replicas(model: "Model", role: str) -> int:
    """The replica count a disaggregated role's pod group should run:
    the autoscaler's annotation when present, else the role's floor —
    always clamped into the CRD bounds (and never below 1; a role pool
    at zero can serve nothing)."""
    from kubeai_tpu.crd import metadata as md

    rs = model.spec.disaggregation.role(role)
    raw = model.annotations.get(md.role_replicas_annotation(role))
    try:
        n = int(raw) if raw is not None else rs.min_replicas
    except (TypeError, ValueError):
        n = rs.min_replicas
    n = max(n, rs.min_replicas, 1)
    if rs.max_replicas is not None:
        n = min(n, rs.max_replicas)
    return n


@dataclasses.dataclass
class ModelStatus:
    """(reference: api/k8s/v1/model_types.go:226-239; `conditions` has no
    reference analog — the reference Model publishes bare replica counts)."""

    replicas_all: int = 0
    replicas_ready: int = 0
    cache_loaded: bool = False
    # Kubernetes-style conditions maintained by the reconciler's
    # pod-health pass: Ready / Progressing / Degraded, each a dict with
    # stable `type` / `status` ("True"/"False") / `reason` / `message`
    # keys (reasons documented in docs/concepts/resilience.md).
    conditions: list[dict] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Model:
    """A Model resource instance (metadata + spec + status)."""

    name: str = ""
    namespace: str = "default"
    uid: str = ""
    resource_version: int = 0
    generation: int = 1
    labels: dict[str, str] = dataclasses.field(default_factory=dict)
    annotations: dict[str, str] = dataclasses.field(default_factory=dict)
    finalizers: list[str] = dataclasses.field(default_factory=list)
    deletion_timestamp: float | None = None
    spec: ModelSpec = dataclasses.field(default_factory=ModelSpec)
    status: ModelStatus = dataclasses.field(default_factory=ModelStatus)

    def validate(self) -> None:
        if not self.name:
            raise ValidationError("metadata.name required")
        # name <= 40 chars so name+suffixes fit k8s limits
        # (reference: api/k8s/v1/model_types.go:248).
        if len(self.name) > MAX_NAME_LEN:
            raise ValidationError(f"model name must be <= {MAX_NAME_LEN} chars")
        # DNS-1123 subdomain: dot-separated DNS labels — the reference
        # catalog ships names like "llama-3.1-8b-instruct-tpu"
        # (reference: charts/models/values.yaml). Each label must stand
        # alone ("a..b" / "a.-b" are invalid).
        label = r"[a-z0-9](?:[-a-z0-9]*[a-z0-9])?"
        if not re.fullmatch(rf"{label}(?:\.{label})*", self.name):
            raise ValidationError(
                "model name must be a lowercase DNS subdomain"
            )
        self.spec.validate()

    def validate_update(self, old: "Model") -> None:
        self.validate()
        # cacheProfile is immutable (reference: model_types.go:76-78).
        if old.spec.cache_profile != self.spec.cache_profile:
            raise ValidationError("spec.cacheProfile is immutable")
        if old.spec.url != self.spec.url and old.spec.cache_profile:
            raise ValidationError("spec.url is immutable when cacheProfile is set")

    # -- dict round trip (k8s manifest shape) --------------------------------

    def to_dict(self) -> dict:
        return {
            "apiVersion": "kubeai.org/v1",
            "kind": "Model",
            "metadata": {
                "name": self.name,
                "namespace": self.namespace,
                "uid": self.uid,
                "resourceVersion": str(self.resource_version),
                "generation": self.generation,
                "labels": dict(self.labels),
                "annotations": dict(self.annotations),
                "finalizers": list(self.finalizers),
                **(
                    {"deletionTimestamp": self.deletion_timestamp}
                    if self.deletion_timestamp
                    else {}
                ),
            },
            "spec": _spec_to_dict(self.spec),
            "status": {
                "replicas": {
                    "all": self.status.replicas_all,
                    "ready": self.status.replicas_ready,
                },
                "cache": {"loaded": self.status.cache_loaded},
                **(
                    {"conditions": [dict(c) for c in self.status.conditions]}
                    if self.status.conditions
                    else {}
                ),
            },
        }

    @staticmethod
    def from_dict(d: dict) -> "Model":
        meta = d.get("metadata", {})
        spec = d.get("spec", {})
        status = d.get("status", {}) or {}
        lb = spec.get("loadBalancing", {}) or {}
        ph = lb.get("prefixHash", {}) or {}
        cb = lb.get("circuitBreaker", {}) or {}
        dis = spec.get("disaggregation", {}) or {}
        kvs = spec.get("kvSharing", {}) or {}
        kvc = spec.get("kvCache", {}) or {}
        cold = spec.get("coldStart", {}) or {}
        shd = spec.get("sharding", {}) or {}
        ten = spec.get("tenancy", {}) or {}
        slo = spec.get("slo", {}) or {}
        ro = spec.get("rollout", {}) or {}
        roj = ro.get("judge", {}) or {}

        def _role_scaling(key: str) -> RoleScaling:
            r = dis.get(key) or {}
            return RoleScaling(
                min_replicas=int(r.get("minReplicas", 1) or 1),
                max_replicas=r.get("maxReplicas"),
            )

        return Model(
            name=meta.get("name", ""),
            namespace=meta.get("namespace", "default"),
            uid=meta.get("uid", ""),
            resource_version=int(meta.get("resourceVersion", 0) or 0),
            generation=int(meta.get("generation", 1)),
            labels=dict(meta.get("labels") or {}),
            annotations=dict(meta.get("annotations") or {}),
            finalizers=list(meta.get("finalizers") or []),
            deletion_timestamp=meta.get("deletionTimestamp"),
            spec=ModelSpec(
                url=spec.get("url", ""),
                engine=spec.get("engine", ENGINE_KUBEAI_TPU),
                features=list(spec.get("features") or []),
                adapters=[
                    Adapter(name=a.get("name", ""), url=a.get("url", ""))
                    for a in (spec.get("adapters") or [])
                ],
                resource_profile=spec.get("resourceProfile", ""),
                cache_profile=spec.get("cacheProfile", ""),
                image=spec.get("image", ""),
                args=list(spec.get("args") or []),
                env=dict(spec.get("env") or {}),
                env_from=list(spec.get("envFrom") or []),
                replicas=spec.get("replicas"),
                min_replicas=int(spec.get("minReplicas", 0) or 0),
                max_replicas=spec.get("maxReplicas"),
                autoscaling_disabled=bool(spec.get("autoscalingDisabled", False)),
                target_requests=int(spec.get("targetRequests", 100)),
                scale_down_delay_seconds=int(spec.get("scaleDownDelaySeconds", 30)),
                load_balancing=LoadBalancing(
                    strategy=lb.get("strategy", LB_STRATEGY_LEAST_LOAD),
                    prefix_hash=PrefixHash(
                        mean_load_percentage=int(ph.get("meanLoadPercentage", 125)),
                        replication=int(ph.get("replication", 256)),
                        prefix_char_length=int(ph.get("prefixCharLength", 100)),
                    ),
                    circuit_breaker=CircuitBreakerSpec(
                        window=int(cb.get("window", 0) or 0),
                        consecutive_failures=int(
                            cb.get("consecutiveFailures", 0) or 0
                        ),
                        failure_rate=float(cb.get("failureRate", 0) or 0),
                        min_samples=int(cb.get("minSamples", 0) or 0),
                        open_seconds=float(cb.get("openSeconds", 0) or 0),
                    ),
                ),
                files=[
                    File(path=f.get("path", ""), content=f.get("content", ""))
                    for f in (spec.get("files") or [])
                ],
                priority_class_name=spec.get("priorityClassName", ""),
                owner=spec.get("owner", ""),
                speculative_tokens=int(spec.get("speculativeTokens", 0) or 0),
                draft_url=spec.get("draftUrl", ""),
                drain_timeout_seconds=int(
                    spec.get("drainTimeoutSeconds", 0) or 0
                ),
                scheduling=Scheduling(
                    default_priority=(
                        (spec.get("scheduling") or {}).get("defaultPriority", "")
                    ),
                    queue_shares={
                        k: float(v)
                        for k, v in (
                            (spec.get("scheduling") or {}).get("queueShares")
                            or {}
                        ).items()
                    },
                    max_deadline_ms=int(
                        (spec.get("scheduling") or {}).get("maxDeadlineMs", 0)
                        or 0
                    ),
                ),
                tenancy=Tenancy(
                    requests_per_second=float(
                        ten.get("requestsPerSecond", 0) or 0
                    ),
                    request_burst=float(ten.get("requestBurst", 0) or 0),
                    tokens_per_second=float(
                        ten.get("tokensPerSecond", 0) or 0
                    ),
                    token_burst=float(ten.get("tokenBurst", 0) or 0),
                    window_seconds=float(ten.get("windowSeconds", 0) or 0),
                    window_token_budget=int(
                        ten.get("windowTokenBudget", 0) or 0
                    ),
                    exempt=bool(ten.get("exempt", False)),
                ),
                slo=Slo(
                    ttft_p95_seconds=float(
                        slo.get("ttftP95Seconds", 0) or 0
                    ),
                    itl_p99_seconds=float(slo.get("itlP99Seconds", 0) or 0),
                    availability=float(slo.get("availability", 0) or 0),
                    max_shed_rate=float(slo.get("maxShedRate", 0) or 0),
                ),
                rollout=Rollout(
                    strategy=ro.get("strategy", "") or "",
                    canary_percent=float(
                        ro.get("canaryPercent", 10.0) or 10.0
                    ),
                    step_seconds=float(ro.get("stepSeconds", 60.0) or 60.0),
                    max_unavailable=int(ro.get("maxUnavailable", 0) or 0),
                    auto_rollback=bool(ro.get("autoRollback", True)),
                    judge=RolloutJudge(
                        window_seconds=float(
                            roj.get("windowSeconds", 0) or 0
                        ),
                        ttft_p95_ratio=float(
                            roj.get("ttftP95Ratio", 0) or 0
                        ),
                        max_breaker_trips=int(
                            roj.get("maxBreakerTrips", 0) or 0
                        ),
                    ),
                ),
                disaggregation=Disaggregation(
                    enabled=bool(dis.get("enabled", False)),
                    prefill=_role_scaling("prefill"),
                    decode=_role_scaling("decode"),
                    prefill_target_queue=int(
                        dis.get("prefillTargetQueue", 4) or 4
                    ),
                    prefill_target_ttft_seconds=float(
                        dis.get("prefillTargetTtftSeconds", 0) or 0
                    ),
                    decode_target_utilization=float(
                        dis.get("decodeTargetUtilization", 0.8) or 0.8
                    ),
                    max_transfer_mb=int(dis.get("maxTransferMB", 0) or 0),
                    transfer_timeout_seconds=float(
                        dis.get("transferTimeoutSeconds", 30) or 30
                    ),
                ),
                kv_sharing=KVSharing(
                    enabled=bool(kvs.get("enabled", False)),
                    page_size=int(kvs.get("pageSize", 16) or 16),
                    tokenizer_dir=kvs.get("tokenizerDir", ""),
                    max_transfer_mb=int(kvs.get("maxTransferMB", 0) or 0),
                    fetch_timeout_seconds=float(
                        kvs.get("fetchTimeoutSeconds", 5) or 5
                    ),
                    spill_url=kvs.get("spillURL", ""),
                ),
                kv_cache=KVCacheSpec(
                    dtype=kvc.get("dtype", "") or "",
                ),
                cold_start=ColdStart(
                    enabled=bool(cold.get("enabled", False)),
                    snapshot_url=cold.get("snapshotURL", ""),
                    publish=bool(cold.get("publish", True)),
                    prewarm=bool(cold.get("prewarm", True)),
                ),
                sharding=Sharding(
                    hosts=int(shd.get("hosts", 0) or 0),
                    topology=shd.get("topology", "") or "",
                    mesh={
                        k: int(v)
                        for k, v in (shd.get("mesh") or {}).items()
                    },
                ),
            ),
            status=ModelStatus(
                replicas_all=int(
                    ((status.get("replicas") or {}).get("all", 0))
                ),
                replicas_ready=int(
                    ((status.get("replicas") or {}).get("ready", 0))
                ),
                cache_loaded=bool((status.get("cache") or {}).get("loaded", False)),
                conditions=[
                    dict(c) for c in (status.get("conditions") or [])
                    if isinstance(c, dict)
                ],
            ),
        )


def _spec_to_dict(s: ModelSpec) -> dict:
    d: dict[str, Any] = {
        "url": s.url,
        "engine": s.engine,
        "features": list(s.features),
    }
    if s.adapters:
        d["adapters"] = [{"name": a.name, "url": a.url} for a in s.adapters]
    if s.resource_profile:
        d["resourceProfile"] = s.resource_profile
    if s.cache_profile:
        d["cacheProfile"] = s.cache_profile
    if s.image:
        d["image"] = s.image
    if s.args:
        d["args"] = list(s.args)
    if s.env:
        d["env"] = dict(s.env)
    if s.env_from:
        d["envFrom"] = list(s.env_from)
    if s.replicas is not None:
        d["replicas"] = s.replicas
    d["minReplicas"] = s.min_replicas
    if s.max_replicas is not None:
        d["maxReplicas"] = s.max_replicas
    if s.autoscaling_disabled:
        d["autoscalingDisabled"] = True
    d["targetRequests"] = s.target_requests
    d["scaleDownDelaySeconds"] = s.scale_down_delay_seconds
    d["loadBalancing"] = {
        "strategy": s.load_balancing.strategy,
        "prefixHash": {
            "meanLoadPercentage": s.load_balancing.prefix_hash.mean_load_percentage,
            "replication": s.load_balancing.prefix_hash.replication,
            "prefixCharLength": s.load_balancing.prefix_hash.prefix_char_length,
        },
    }
    cb = s.load_balancing.circuit_breaker
    if cb.enabled():
        cbd: dict[str, Any] = {}
        if cb.window:
            cbd["window"] = cb.window
        if cb.consecutive_failures:
            cbd["consecutiveFailures"] = cb.consecutive_failures
        if cb.failure_rate:
            cbd["failureRate"] = cb.failure_rate
        if cb.min_samples:
            cbd["minSamples"] = cb.min_samples
        if cb.open_seconds:
            cbd["openSeconds"] = cb.open_seconds
        d["loadBalancing"]["circuitBreaker"] = cbd
    if s.drain_timeout_seconds:
        d["drainTimeoutSeconds"] = s.drain_timeout_seconds
    if s.files:
        d["files"] = [{"path": f.path, "content": f.content} for f in s.files]
    if s.priority_class_name:
        d["priorityClassName"] = s.priority_class_name
    if s.owner:
        d["owner"] = s.owner
    if s.speculative_tokens:
        d["speculativeTokens"] = s.speculative_tokens
    if s.draft_url:
        d["draftUrl"] = s.draft_url
    if s.scheduling.enabled():
        sched: dict[str, Any] = {}
        if s.scheduling.default_priority:
            sched["defaultPriority"] = s.scheduling.default_priority
        if s.scheduling.queue_shares:
            sched["queueShares"] = dict(s.scheduling.queue_shares)
        if s.scheduling.max_deadline_ms:
            sched["maxDeadlineMs"] = s.scheduling.max_deadline_ms
        d["scheduling"] = sched
    if s.tenancy.enabled():
        ten: dict[str, Any] = {}
        if s.tenancy.requests_per_second:
            ten["requestsPerSecond"] = s.tenancy.requests_per_second
        if s.tenancy.request_burst:
            ten["requestBurst"] = s.tenancy.request_burst
        if s.tenancy.tokens_per_second:
            ten["tokensPerSecond"] = s.tenancy.tokens_per_second
        if s.tenancy.token_burst:
            ten["tokenBurst"] = s.tenancy.token_burst
        if s.tenancy.window_seconds:
            ten["windowSeconds"] = s.tenancy.window_seconds
        if s.tenancy.window_token_budget:
            ten["windowTokenBudget"] = s.tenancy.window_token_budget
        if s.tenancy.exempt:
            ten["exempt"] = True
        d["tenancy"] = ten
    if s.slo.enabled():
        slo: dict[str, Any] = {}
        if s.slo.ttft_p95_seconds:
            slo["ttftP95Seconds"] = s.slo.ttft_p95_seconds
        if s.slo.itl_p99_seconds:
            slo["itlP99Seconds"] = s.slo.itl_p99_seconds
        if s.slo.availability:
            slo["availability"] = s.slo.availability
        if s.slo.max_shed_rate:
            slo["maxShedRate"] = s.slo.max_shed_rate
        d["slo"] = slo
    if s.rollout.enabled():
        ro = s.rollout
        rod: dict[str, Any] = {
            "strategy": ro.strategy,
            "canaryPercent": ro.canary_percent,
            "stepSeconds": ro.step_seconds,
        }
        if ro.max_unavailable:
            rod["maxUnavailable"] = ro.max_unavailable
        if not ro.auto_rollback:
            rod["autoRollback"] = False
        jd: dict[str, Any] = {}
        if ro.judge.window_seconds:
            jd["windowSeconds"] = ro.judge.window_seconds
        if ro.judge.ttft_p95_ratio:
            jd["ttftP95Ratio"] = ro.judge.ttft_p95_ratio
        if ro.judge.max_breaker_trips:
            jd["maxBreakerTrips"] = ro.judge.max_breaker_trips
        if jd:
            rod["judge"] = jd
        d["rollout"] = rod
    if s.disaggregation.enabled:
        dis = s.disaggregation

        def _role_dict(r: RoleScaling) -> dict:
            out: dict[str, Any] = {"minReplicas": r.min_replicas}
            if r.max_replicas is not None:
                out["maxReplicas"] = r.max_replicas
            return out

        d["disaggregation"] = {
            "enabled": True,
            "prefill": _role_dict(dis.prefill),
            "decode": _role_dict(dis.decode),
            "prefillTargetQueue": dis.prefill_target_queue,
            "decodeTargetUtilization": dis.decode_target_utilization,
            **(
                {"prefillTargetTtftSeconds": dis.prefill_target_ttft_seconds}
                if dis.prefill_target_ttft_seconds
                else {}
            ),
            **(
                {"maxTransferMB": dis.max_transfer_mb}
                if dis.max_transfer_mb
                else {}
            ),
            "transferTimeoutSeconds": dis.transfer_timeout_seconds,
        }
    if s.kv_sharing.enabled:
        kvs = s.kv_sharing
        d["kvSharing"] = {
            "enabled": True,
            "pageSize": kvs.page_size,
            **(
                {"tokenizerDir": kvs.tokenizer_dir}
                if kvs.tokenizer_dir
                else {}
            ),
            **(
                {"maxTransferMB": kvs.max_transfer_mb}
                if kvs.max_transfer_mb
                else {}
            ),
            "fetchTimeoutSeconds": kvs.fetch_timeout_seconds,
            **({"spillURL": kvs.spill_url} if kvs.spill_url else {}),
        }
    if s.kv_cache.enabled():
        d["kvCache"] = {"dtype": s.kv_cache.dtype}
    if s.sharding.enabled():
        shd: dict[str, Any] = {}
        if s.sharding.hosts:
            shd["hosts"] = s.sharding.hosts
        if s.sharding.topology:
            shd["topology"] = s.sharding.topology
        if s.sharding.mesh:
            shd["mesh"] = dict(s.sharding.mesh)
        d["sharding"] = shd
    if s.cold_start.enabled:
        cold = s.cold_start
        d["coldStart"] = {
            "enabled": True,
            "snapshotURL": cold.snapshot_url,
            **({} if cold.publish else {"publish": False}),
            **({} if cold.prewarm else {"prewarm": False}),
        }
    return d
