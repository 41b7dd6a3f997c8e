"""Minimal Prometheus-compatible metrics (text exposition format 0.0.4)."""

from __future__ import annotations

import math
import re
import threading
from collections import defaultdict


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{str(v).replace(chr(92), chr(92)*2).replace(chr(34), chr(92)+chr(34))}"'
        for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    """Exposition value: integral floats render bare (`25`, not `25.0`);
    everything else uses repr's shortest round-trip form so large counters
    survive expose() → parse (the %g default truncates past 6 digits)."""
    if v == int(v) and abs(v) < 2**53:
        return str(int(v))
    return repr(float(v))


def _fmt_le(bound: float) -> str:
    """Canonical `le` label value: `%g`-style (`0.005`, `1`, `+Inf`) so
    int and float bucket bounds render identically."""
    b = float(bound)
    if b == float("inf"):
        return "+Inf"
    return f"{b:g}"


class _Metric:
    def __init__(self, name: str, help_: str, registry: "Registry | None"):
        self.name = name
        self.help = help_
        self._lock = threading.Lock()
        self._values: dict[tuple, float] = defaultdict(float)
        self._label_keys: dict[tuple, dict] = {}
        if registry is not None:
            registry.register(self)

    def _key(self, labels: dict[str, str]) -> tuple:
        k = tuple(sorted(labels.items()))
        self._label_keys[k] = labels
        return k

    def get(self, **labels) -> float:
        with self._lock:
            return self._values.get(tuple(sorted(labels.items())), 0.0)

    def samples(self) -> list[tuple[dict, float]]:
        """Every (labels, value) series of this instrument, sorted by
        label key — the read path for consumers that aggregate across
        label sets (the SLO evaluator sums rejections over tenants and
        reasons). Histograms don't populate scalar values; use their
        get()/sum_for() instead."""
        with self._lock:
            return [
                (dict(self._label_keys[k]), v)
                for k, v in sorted(self._values.items())
            ]

    def remove(self, **labels) -> None:
        """Drop one label-set's series (endpoint churn would otherwise
        accrete stale series forever on long-lived registries)."""
        with self._lock:
            k = tuple(sorted(labels.items()))
            self._values.pop(k, None)
            self._label_keys.pop(k, None)

    def collect(self) -> list[str]:
        with self._lock:
            lines = [
                f"# HELP {self.name} {self.help}",
                f"# TYPE {self.name} {self.TYPE}",
            ]
            if not self._values:
                lines.append(f"{self.name} 0")
            for k, v in sorted(self._values.items()):
                lines.append(
                    f"{self.name}{_fmt_labels(self._label_keys[k])} "
                    f"{_fmt_value(v)}"
                )
            return lines


class Counter(_Metric):
    TYPE = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        with self._lock:
            self._values[self._key(labels)] += amount


class TracingDroppedSpans(Counter):
    """Live view of the process tracer's dropped-span count (export
    queue full, or exporter thread dead). Synced at collect time so
    every registry in the process (operator bundle, engine bundle)
    exposes the same truth without the tracer knowing about registries."""

    def collect(self) -> list[str]:
        from kubeai_tpu.metrics import tracing

        t = tracing._default
        dropped = float(t.dropped) if t is not None else 0.0
        with self._lock:
            self._values[self._key({})] = dropped
        return super().collect()


class ObjstoreRetries(Counter):
    """Live view of the object-store layer's transient-failure retry
    count (5xx/429, connection resets, short reads). Synced from
    `objstore.RETRIES` at collect time — same pattern as
    TracingDroppedSpans, so the operator bundle and the engine bundle
    both expose the process's one true count."""

    def collect(self) -> list[str]:
        from kubeai_tpu import objstore

        with self._lock:
            self._values[self._key({})] = float(objstore.RETRIES["total"])
        return super().collect()


class Gauge(_Metric):
    TYPE = "gauge"

    def inc(self, amount: float = 1.0, **labels) -> None:
        with self._lock:
            self._values[self._key(labels)] += amount

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._values[self._key(labels)] = value


class Histogram(_Metric):
    TYPE = "histogram"
    BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10)

    def __init__(self, name, help_, registry, buckets=None):
        super().__init__(name, help_, registry)
        self.buckets = tuple(buckets or self.BUCKETS)
        self._bucket_counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = defaultdict(float)
        self._counts: dict[tuple, int] = defaultdict(int)
        # Last exemplar (trace/request id) per bucket per label set —
        # index len(buckets) is the +Inf overflow bucket. Deliberately
        # NOT emitted in the 0.0.4 text exposition (parsers here and in
        # the fleet would choke on OpenMetrics `# {...}` suffixes);
        # consumers read them via exemplars() / the admin state payloads.
        self._exemplars: dict[tuple, dict[int, str]] = {}

    def observe(self, value: float, exemplar: str | None = None,
                **labels) -> None:
        with self._lock:
            k = self._key(labels)
            if k not in self._bucket_counts:
                self._bucket_counts[k] = [0] * len(self.buckets)
            # Per-bucket (non-cumulative) counts: only the first bucket
            # that fits increments; collect() produces the cumulative
            # `le` series. Incrementing every bucket >= value here would
            # double-cumulate at collect time.
            idx = len(self.buckets)
            for i, b in enumerate(self.buckets):
                if value <= b:
                    self._bucket_counts[k][i] += 1
                    idx = i
                    break
            self._sums[k] += value
            self._counts[k] += 1
            if exemplar:
                self._exemplars.setdefault(k, {})[idx] = str(exemplar)

    def exemplars(self, **labels) -> dict[str, str]:
        """Last exemplar per bucket for the label set, keyed by the
        bucket's canonical `le` string (`+Inf` for the overflow bucket)."""
        with self._lock:
            per_idx = self._exemplars.get(tuple(sorted(labels.items())), {})
            out: dict[str, str] = {}
            for idx, ex in sorted(per_idx.items()):
                bound = (
                    "+Inf" if idx >= len(self.buckets)
                    else _fmt_le(self.buckets[idx])
                )
                out[bound] = ex
            return out

    def get(self, **labels) -> float:
        """Observation COUNT for the label set (the scalar `_Metric.get`
        would silently read the unused `_values` dict and always say 0)."""
        with self._lock:
            return float(self._counts.get(tuple(sorted(labels.items())), 0))

    def remove(self, **labels) -> None:
        """Drop one label-set's series INCLUDING its bucket/sum/count
        state — the base remove only clears `_values`, which histograms
        don't use, so label churn would accrete series forever."""
        with self._lock:
            k = tuple(sorted(labels.items()))
            self._values.pop(k, None)
            self._label_keys.pop(k, None)
            self._bucket_counts.pop(k, None)
            self._sums.pop(k, None)
            self._counts.pop(k, None)
            self._exemplars.pop(k, None)

    def sum_for(self, **labels) -> float:
        """Sum of observed values for the label set."""
        with self._lock:
            return float(self._sums.get(tuple(sorted(labels.items())), 0.0))

    def collect(self) -> list[str]:
        with self._lock:
            lines = [
                f"# HELP {self.name} {self.help}",
                f"# TYPE {self.name} histogram",
            ]
            for k in sorted(self._counts):
                labels = self._label_keys[k]
                cum = 0
                for i, b in enumerate(self.buckets):
                    cum += self._bucket_counts[k][i]
                    lines.append(
                        f"{self.name}_bucket"
                        f"{_fmt_labels({**labels, 'le': _fmt_le(b)})} {cum}"
                    )
                lines.append(
                    f"{self.name}_bucket"
                    f"{_fmt_labels({**labels, 'le': '+Inf'})} {self._counts[k]}"
                )
                lines.append(
                    f"{self.name}_sum{_fmt_labels(labels)} "
                    f"{_fmt_value(self._sums[k])}"
                )
                lines.append(
                    f"{self.name}_count{_fmt_labels(labels)} {self._counts[k]}"
                )
            return lines


class Registry:
    def __init__(self):
        self._metrics: list[_Metric] = []
        self._lock = threading.Lock()

    def register(self, m: _Metric) -> None:
        with self._lock:
            self._metrics.append(m)

    @property
    def metrics(self) -> list[_Metric]:
        with self._lock:
            return list(self._metrics)

    def expose(self) -> str:
        out: list[str] = []
        with self._lock:
            metrics = list(self._metrics)
        for m in metrics:
            out.extend(m.collect())
        return "\n".join(out) + "\n"


_METRIC_NAME_RE = re.compile(r"^kubeai_[a-z0-9_]+$")


def lint_registry(registry: Registry) -> list[str]:
    """Metric-name hygiene for one registry: names match
    `^kubeai_[a-z0-9_]+$` and are unique, counters end in `_total`,
    histograms in their unit (`_seconds`, or `_ratio` for one of a
    quotient). Returns human-readable violations (empty =
    clean); a unit test walks every instrument bundle through this so new
    instruments can't silently drift from the naming scheme."""
    errors: list[str] = []
    seen: set[str] = set()
    for m in registry.metrics:
        if not _METRIC_NAME_RE.match(m.name):
            errors.append(
                f"{m.name}: does not match ^kubeai_[a-z0-9_]+$"
            )
        if m.name in seen:
            errors.append(f"{m.name}: duplicate metric name in registry")
        seen.add(m.name)
        if isinstance(m, Histogram):
            if not m.name.endswith(("_seconds", "_ratio")):
                errors.append(
                    f"{m.name}: histogram must end in _seconds or _ratio"
                )
        elif isinstance(m, Counter):
            if not m.name.endswith("_total"):
                errors.append(f"{m.name}: counter must end in _total")
    return errors


# -- shared bucket-quantile estimator ---------------------------------------
# One estimator for every consumer of cumulative histogram buckets: the
# fleet aggregator's per-endpoint TTFT/ITL quantiles and the SLO
# evaluator's burn-rate math both read scraped `le` series, and they must
# agree on what "p95" means or an SLO breach and the signal that scaled
# for it would disagree about the same data.


def hist_buckets(
    parsed: dict, name: str
) -> tuple[list[tuple[float, float]], float, float]:
    """Extract one histogram's cumulative buckets from a parsed scrape:
    (sorted [(upper_bound, cumulative_count)], total_count, total_sum).
    Labels beyond `le` are ignored (one endpoint exposes one series per
    histogram); unparseable `le` values are skipped."""
    buckets: list[tuple[float, float]] = []
    total = 0.0
    total_sum = 0.0
    for (metric, labels), value in parsed.items():
        if metric == f"{name}_bucket":
            le = dict(labels).get("le", "")
            try:
                bound = float(le)
            except ValueError:
                continue
            buckets.append((bound, value))
        elif metric == f"{name}_count":
            total = value
        elif metric == f"{name}_sum":
            total_sum = value
    buckets.sort(key=lambda b: b[0])
    return buckets, total, total_sum


def quantiles_from_buckets(
    buckets: list[tuple[float, float]],
    total: float,
    total_sum: float,
    qs: tuple[float, ...] = (0.5, 0.95, 0.99),
) -> dict:
    """Approximate quantiles from cumulative histogram buckets (each
    quantile reports its bucket's upper bound — the standard
    Prometheus-side estimate). `buckets` must be sorted ascending by
    bound. Returns {} when the histogram has no observations or no
    buckets; a quantile landing in the +Inf bucket reports the largest
    finite bound (a meaningless +Inf estimate helps nobody), or +Inf
    when the histogram is a single +Inf bucket."""
    if total <= 0 or not buckets:
        return {}
    out = {
        "count": total,
        "mean_s": round(total_sum / total, 9),
    }
    for q in qs:
        target = q * total
        est = buckets[-1][0]
        for bound, cum in buckets:
            if cum >= target:
                est = bound
                break
        if math.isinf(est):
            finite = [b for b, _ in buckets if not math.isinf(b)]
            est = finite[-1] if finite else float("inf")
        out[f"p{int(q * 100)}_s"] = est
    return out


def count_over_threshold(
    buckets: list[tuple[float, float]], total: float, threshold: float
) -> float:
    """Observations strictly above `threshold`, from cumulative buckets.
    Conservative toward the service: observations in the bucket that
    CONTAINS the threshold count as good (they may be below it), so the
    bound used is the smallest bucket bound >= threshold. A threshold
    past every finite bound yields 0 — the buckets cannot distinguish
    violations up there, and guessing badness would page on rounding."""
    if total <= 0 or not buckets:
        return 0.0
    for bound, cum in buckets:
        if bound >= threshold:
            return max(0.0, total - cum)
    return 0.0


# Request-latency buckets: sub-ms (cache hits, tiny models) through the
# proxy's 600s request budget — an LLM completion legitimately runs minutes.
LATENCY_BUCKETS_S = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)


class Metrics:
    """One operator replica's instrument bundle. Each Manager owns its own
    Metrics so multiple replicas embedded in one process (virtual HA,
    integration tests) don't share counters — sharing would double-count the
    autoscaling signal when the leader scrapes every replica."""

    def __init__(self):
        self.registry = Registry()
        # The autoscaling signal (reference: internal/metrics/metrics.go:16-20;
        # Prom name mapping metrics.go:81-87).
        self.inference_requests_active = Gauge(
            "kubeai_inference_requests_active",
            "Number of in-flight inference requests per model.",
            self.registry,
        )
        self.inference_requests_total = Counter(
            "kubeai_inference_requests_total",
            "Total inference requests per model.",
            self.registry,
        )
        self.chwbl_lookups = Counter(
            "kubeai_chwbl_lookups_total",
            "CHWBL address lookups.",
            self.registry,
        )
        self.chwbl_displacements = Counter(
            "kubeai_chwbl_displacements_total",
            "CHWBL lookups displaced past the hashed endpoint by the bounded-load rule.",
            self.registry,
        )
        # -- cluster KV-sharing: longest-held-prefix routing ----------------
        # Route-time PREDICTION counters; compare against the engine's
        # kubeai_engine_prefix_cached_tokens_total (actual admission hits)
        # to measure how honest the fleet holdings map is.
        self.lb_prefix_route_hits = Counter(
            "kubeai_lb_prefix_route_hits_total",
            "Picks routed to an endpoint advertising at least one held "
            "page of the request's chain (predicted prefix hit), per "
            "model.",
            self.registry,
        )
        self.lb_prefix_route_misses = Counter(
            "kubeai_lb_prefix_route_misses_total",
            "Chain-carrying picks that fell back to classic CHWBL "
            "(stale/empty holdings map or no load-bounded holder), per "
            "model.",
            self.registry,
        )
        # -- front-door request lifecycle (per model) ----------------------
        self.request_duration = Histogram(
            "kubeai_inference_request_duration_seconds",
            "End-to-end front-door request duration per model (receipt to "
            "last body byte).",
            self.registry,
            buckets=LATENCY_BUCKETS_S,
        )
        self.request_ttft = Histogram(
            "kubeai_inference_ttft_seconds",
            "Time from front-door receipt to the first response body chunk "
            "per model (streaming time-to-first-token).",
            self.registry,
            buckets=LATENCY_BUCKETS_S,
        )
        self.proxy_attempts = Counter(
            "kubeai_proxy_attempts_total",
            "Proxy attempts per model (retries make this exceed requests).",
            self.registry,
        )
        self.proxy_retries = Counter(
            "kubeai_proxy_retries_total",
            "Proxy attempts that failed and were retried on another "
            "endpoint, per model.",
            self.registry,
        )
        # -- resilience: circuit breaker + fault accounting ----------------
        self.lb_circuit_state = Gauge(
            "kubeai_lb_circuit_state",
            "Per-endpoint circuit breaker state: 0 closed, 1 half-open, "
            "2 open.",
            self.registry,
        )
        self.lb_circuit_ejections = Counter(
            "kubeai_lb_circuit_ejections_total",
            "Times an endpoint's circuit tripped open (ejected from the "
            "load-balancer candidate set).",
            self.registry,
        )
        self.proxy_midstream_failures = Counter(
            "kubeai_proxy_midstream_failures_total",
            "Streams whose upstream connection died after headers were "
            "sent (each one is either resumed on another endpoint or "
            "terminated with the SSE error event), per model.",
            self.registry,
        )
        self.proxy_stream_resumes = Counter(
            "kubeai_proxy_stream_resumes_total",
            "Mid-stream deaths transparently resumed on another endpoint "
            "via a continuation request (client saw one uninterrupted "
            "stream), per model.",
            self.registry,
        )
        self.proxy_stream_resume_failures = Counter(
            "kubeai_proxy_stream_resume_failures_total",
            "Mid-stream deaths whose resume budget or endpoint pool ran "
            "dry — the client got the terminal SSE error event, per "
            "model.",
            self.registry,
        )
        self.proxy_deadline_exhausted = Counter(
            "kubeai_proxy_deadline_exhausted_total",
            "Requests whose X-Deadline-Ms budget ran out before a retry "
            "could be attempted, per model.",
            self.registry,
        )
        # -- disaggregated serving (two-hop prefill→decode) ----------------
        self.proxy_disagg_requests = Counter(
            "kubeai_proxy_disagg_requests_total",
            "Requests served via the two-hop prefill→decode flow, per "
            "model.",
            self.registry,
        )
        self.proxy_disagg_fallback = Counter(
            "kubeai_proxy_disagg_fallback_total",
            "Disaggregation-enabled requests that fell back to the "
            "unified pool (no role endpoints, open circuits, or a failed "
            "hop), per model.",
            self.registry,
        )
        # -- controller repair / failure observability ---------------------
        self.controller_consecutive_failures = Gauge(
            "kubeai_controller_consecutive_failures",
            "Consecutive reconcile failures per model (0 after a clean "
            "pass) — the backoff-requeue exponent.",
            self.registry,
        )
        self.controller_pod_replacements = Counter(
            "kubeai_controller_pod_replacements_total",
            "Pods delete-and-replaced by the self-healing pod-health "
            "pass, per model and classification reason.",
            self.registry,
        )
        # -- slice groups (multi-host replicas, operator/slicegroup) --------
        self.slicegroup_groups = Gauge(
            "kubeai_slicegroup_groups",
            "Slice groups per model and state (ready|partial|broken) at "
            "the fleet aggregator's last collection — a partial or "
            "broken group is never serving capacity.",
            self.registry,
        )
        self.slicegroup_repairs = Counter(
            "kubeai_slicegroup_repairs_total",
            "Whole-group atomic repairs issued by the group-health "
            "pass, per model and the first broken member's "
            "classification reason.",
            self.registry,
        )
        self.slicegroup_ejections = Counter(
            "kubeai_slicegroup_ejections_total",
            "Slice groups ejected from load-balancer rotation because a "
            "member pod was not ready, disrupted, or terminating while "
            "the coordinator still looked routable, per model.",
            self.registry,
        )
        # -- actuation safety governor (operator/governor) -----------------
        self.governor_actions = Counter(
            "kubeai_governor_actions_total",
            "Destructive control-plane actions authorized by the "
            "governor, per action kind and model.",
            self.registry,
        )
        self.governor_denied = Counter(
            "kubeai_governor_denied_total",
            "Destructive control-plane actions the governor refused, per "
            "action kind, model, and denial reason (budget exhaustion, "
            "stale telemetry, coverage below threshold, invalid lease).",
            self.registry,
        )
        self.governor_budget_remaining = Gauge(
            "kubeai_governor_budget_remaining",
            "Healthy-pod disruptions still allowed in the current "
            "sliding window (scope=cluster), updated on every budget "
            "consultation.",
            self.registry,
        )
        self.governor_telemetry_coverage = Gauge(
            "kubeai_governor_telemetry_coverage",
            "Fraction of the model's endpoints with fresh fleet "
            "telemetry at the governor's last coverage check.",
            self.registry,
        )
        self.governor_static_holds = Counter(
            "kubeai_governor_static_stability_holds_total",
            "Scale-downs held at the last-known-good replica count "
            "because fleet telemetry was absent or stale, per model.",
            self.registry,
        )
        # -- leader election / actuation fencing ---------------------------
        self.leader_is_leader = Gauge(
            "kubeai_leader_is_leader",
            "1 while this replica holds the leadership lease, else 0.",
            self.registry,
        )
        self.leader_transitions = Counter(
            "kubeai_leader_transitions_total",
            "Leadership acquisitions and losses observed by this "
            "replica (direction label: acquired|lost).",
            self.registry,
        )
        self.leader_fenced_writes = Counter(
            "kubeai_leader_fenced_writes_total",
            "Actuation batches dropped because the leadership lease was "
            "expired or not held at write time (split-brain fencing).",
            self.registry,
        )
        # -- kube API client retries (operator/k8s/rest) -------------------
        self.kubeclient_retries = Counter(
            "kubeai_kubeclient_retry_attempts_total",
            "Kube API requests retried after a transient failure, per "
            "HTTP verb and failure reason (429, 5xx, connection error, "
            "conflict).",
            self.registry,
        )
        self.kubeclient_retry_exhausted = Counter(
            "kubeai_kubeclient_retry_exhausted_total",
            "Kube API requests that failed after exhausting the retry "
            "budget, per HTTP verb.",
            self.registry,
        )
        self.kubeclient_watch_reconnects = Counter(
            "kubeai_kubeclient_watch_reconnects_total",
            "Watch stream reconnects per kind (each reconnect waits a "
            "capped exponential backoff with jitter).",
            self.registry,
        )
        # -- autoscaler decision telemetry ---------------------------------
        self.autoscaler_ticks = Counter(
            "kubeai_autoscaler_ticks_total",
            "Completed autoscaler ticks on this replica (leader only).",
            self.registry,
        )
        self.autoscaler_scrape_duration = Histogram(
            "kubeai_autoscaler_scrape_duration_seconds",
            "Wall time of one tick's metrics scrape across all operator "
            "replicas.",
            self.registry,
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0),
        )
        self.autoscaler_signal = Gauge(
            "kubeai_autoscaler_active_requests",
            "Aggregated active-request signal per model at the last tick.",
            self.registry,
        )
        self.autoscaler_average = Gauge(
            "kubeai_autoscaler_average_active_requests",
            "Moving average of the active-request signal per model.",
            self.registry,
        )
        self.autoscaler_desired_replicas = Gauge(
            "kubeai_autoscaler_desired_replicas",
            "Replicas computed from the moving average (before hysteresis "
            "and min/max clamping).",
            self.registry,
        )
        self.autoscaler_applied_replicas = Gauge(
            "kubeai_autoscaler_applied_replicas",
            "Replicas actually applied to the Model spec at the last tick.",
            self.registry,
        )
        self.autoscaler_scale_down_votes = Gauge(
            "kubeai_autoscaler_consecutive_scale_downs",
            "Consecutive scale-down votes pending per model (hysteresis "
            "state; resets on apply or on any non-down tick).",
            self.registry,
        )
        self.autoscaler_queue_depth = Gauge(
            "kubeai_autoscaler_queue_depth",
            "Total requests waiting in the model's engine schedulers at "
            "the last tick (queue-pressure demand signal).",
            self.registry,
        )
        self.autoscaler_queue_oldest_wait = Gauge(
            "kubeai_autoscaler_queue_oldest_wait_seconds",
            "Age of the oldest queued request across the model's engines "
            "at the last tick (queue-pressure staleness signal).",
            self.registry,
        )
        # -- per-role autoscaling (disaggregated prefill/decode groups) ----
        self.autoscaler_role_desired_replicas = Gauge(
            "kubeai_autoscaler_role_desired_replicas",
            "Desired replicas per disaggregated role computed at the last "
            "tick (prefill from queue/TTFT pressure, decode from KV and "
            "slot occupancy), before hysteresis/clamping.",
            self.registry,
        )
        self.autoscaler_role_applied_replicas = Gauge(
            "kubeai_autoscaler_role_applied_replicas",
            "Replicas actually applied to the role's replica annotation "
            "at the last tick.",
            self.registry,
        )
        self.autoscaler_role_signal = Gauge(
            "kubeai_autoscaler_role_signal",
            "The role's raw bottleneck signal at the last tick: queued "
            "prefills (prefill role) or pool utilization fraction "
            "(decode role).",
            self.registry,
        )
        # -- fleet telemetry plane (kubeai_tpu/fleet) -----------------------
        self.fleet_collections = Counter(
            "kubeai_fleet_collections_total",
            "Completed fleet-state aggregation sweeps.",
            self.registry,
        )
        self.fleet_collection_duration = Histogram(
            "kubeai_fleet_collection_duration_seconds",
            "Wall time of one fleet sweep (all endpoints scraped "
            "concurrently, so this tracks the slowest endpoint).",
            self.registry,
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 2.5, 5.0, 10.0),
        )
        self.fleet_endpoints = Gauge(
            "kubeai_fleet_endpoints",
            "Live serving endpoints at the last fleet sweep, per model "
            "and role.",
            self.registry,
        )
        self.fleet_stale_endpoints = Gauge(
            "kubeai_fleet_stale_endpoints",
            "Endpoints whose telemetry is stale (scrape failed or data "
            "older than the staleness bound) at the last sweep, per "
            "model — stale endpoints are flagged and excluded from "
            "aggregates, never silently merged.",
            self.registry,
        )
        self.fleet_queue_depth = Gauge(
            "kubeai_fleet_queue_depth",
            "Fleet-aggregated scheduler queue depth per model (fresh "
            "endpoints only) at the last sweep.",
            self.registry,
        )
        self.fleet_kv_utilization = Gauge(
            "kubeai_fleet_kv_utilization",
            "Mean KV-cache utilization per model and role at the last "
            "sweep.",
            self.registry,
        )
        self.fleet_chips = Gauge(
            "kubeai_fleet_chips",
            "Cluster chip inventory by slice shape (from the pods' "
            "google.com/tpu requests), at the last sweep.",
            self.registry,
        )
        self.fleet_snapshot_ts = Gauge(
            "kubeai_fleet_snapshot_timestamp_seconds",
            "Unix timestamp of the latest fleet snapshot (scrape-side "
            "age = now - this).",
            self.registry,
        )
        self.fleet_endpoint_staleness = Gauge(
            "kubeai_fleet_endpoint_staleness_seconds",
            "Age of each endpoint's last successful telemetry scrape at "
            "the last sweep, per model and endpoint (never-scraped "
            "endpoints export no series — absence is not zero age).",
            self.registry,
        )
        # -- SLO plane (kubeai_tpu/fleet/slo) --------------------------------
        self.slo_evaluations = Counter(
            "kubeai_slo_evaluations_total",
            "Completed SLO evaluation ticks (a fresh fleet snapshot was "
            "judged against every configured objective).",
            self.registry,
        )
        self.slo_skipped_ticks = Counter(
            "kubeai_slo_skipped_ticks_total",
            "SLO evaluation ticks refused per model and reason "
            "(coverage = telemetry coverage below the governor's "
            "minTelemetryCoverage, stale = no fresh fleet snapshot) — a "
            "refused tick judges nothing rather than judging blind.",
            self.registry,
        )
        self.slo_burn_rate = Gauge(
            "kubeai_slo_burn_rate",
            "Error-budget burn rate per model, objective, and window "
            "(1.0 = burning exactly the budget the objective allows).",
            self.registry,
        )
        self.slo_error_budget_remaining = Gauge(
            "kubeai_slo_error_budget_remaining",
            "Fraction of the rolling error budget still unspent per "
            "model and objective (exact ledger arithmetic; negative = "
            "budget exhausted).",
            self.registry,
        )
        self.slo_alert_state = Gauge(
            "kubeai_slo_alert_state",
            "Burn-rate alert state per model and objective: 0 ok, "
            "1 slow burn (warn), 2 fast burn (page).",
            self.registry,
        )
        self.slo_alerts = Counter(
            "kubeai_slo_alerts_total",
            "Burn-rate alert transitions fired per model, objective, and "
            "severity (slow|fast) — increments on entry, not per tick.",
            self.registry,
        )
        self.slo_events = Counter(
            "kubeai_slo_events_total",
            "SLI events judged per model and objective (the ledger's "
            "denominator).",
            self.registry,
        )
        self.slo_bad_events = Counter(
            "kubeai_slo_bad_events_total",
            "SLI events that violated the objective per model and "
            "objective (the ledger's numerator).",
            self.registry,
        )
        # -- cluster capacity planner (kubeai_tpu/fleet/planner) ------------
        self.planner_ticks = Counter(
            "kubeai_planner_ticks_total",
            "Completed capacity-planning ticks (a fresh fleet snapshot "
            "was bin-packed into a plan).",
            self.registry,
        )
        self.planner_stale_ticks = Counter(
            "kubeai_planner_stale_ticks_total",
            "Planning ticks skipped because the fleet snapshot was stale "
            "or missing (the autoscaler falls back to direct per-model "
            "scaling while this grows).",
            self.registry,
        )
        self.planner_preemptions = Counter(
            "kubeai_planner_preemptions_total",
            "Replicas preempted by the capacity plan per model (chips "
            "reclaimed for a higher scheduling class).",
            self.registry,
        )
        self.planner_desired_replicas = Gauge(
            "kubeai_planner_desired_replicas",
            "Unconstrained desired replicas per model and role in the "
            "latest plan (what the model wants before the chip budget).",
            self.registry,
        )
        self.planner_allocated_replicas = Gauge(
            "kubeai_planner_allocated_replicas",
            "Replicas the latest plan allocated per model and role under "
            "the chip budget (the autoscaler's override target).",
            self.registry,
        )
        self.planner_throttled_replicas = Gauge(
            "kubeai_planner_throttled_replicas",
            "Desired-but-unallocated replicas per model in the latest "
            "plan (demand the chip budget could not fit).",
            self.registry,
        )
        self.planner_preempted_replicas = Gauge(
            "kubeai_planner_preempted_replicas",
            "Currently-running replicas the latest plan takes away from "
            "this model despite remaining demand (preemption picks).",
            self.registry,
        )
        self.planner_chips_allocated = Gauge(
            "kubeai_planner_chips_allocated",
            "Chips the latest plan allocated per slice shape.",
            self.registry,
        )
        self.planner_chips_free = Gauge(
            "kubeai_planner_chips_free",
            "Chips the latest plan left idle per slice shape.",
            self.registry,
        )
        self.planner_plan_ts = Gauge(
            "kubeai_planner_plan_timestamp_seconds",
            "Unix timestamp of the latest capacity plan (plan age = "
            "now - this; the autoscaler ignores plans past the "
            "staleness bound).",
            self.registry,
        )
        # -- predictive prewarm (kubeai_tpu/fleet/forecaster) ----------------
        self.prewarm_forecast_demand = Gauge(
            "kubeai_prewarm_forecast_demand",
            "Forecast demand (requests in flight + queued) per model at "
            "the forecast horizon, from the demand forecaster's fit over "
            "the snapshot ring.",
            self.registry,
        )
        self.prewarm_replicas = Gauge(
            "kubeai_prewarm_replicas",
            "Extra replicas the latest plan prewarms per model ahead of "
            "forecast demand (granted from spare chips, actuated through "
            "the governor like any scale-up).",
            self.registry,
        )
        self.prewarm_orders = Counter(
            "kubeai_prewarm_orders_total",
            "Prewarm replica grants ordered by the planner per model and "
            "trigger (trend = rising request-rate fit, spot = "
            "spot-preemption early warning).",
            self.registry,
        )
        self.prewarm_denied = Counter(
            "kubeai_prewarm_denied_total",
            "Prewarm grants the actuation governor refused per model "
            "(fencing or telemetry-coverage gate).",
            self.registry,
        )
        self.prewarm_coldstart_cost = Gauge(
            "kubeai_prewarm_coldstart_cost_seconds",
            "Measured cold-start cost per model (replica-reported boot "
            "total; restore-path replicas report the cheap figure) — "
            "what the planner prices into preemption choices.",
            self.registry,
        )
        self.objstore_retries = ObjstoreRetries(
            "kubeai_objstore_retries_total",
            "Object-store requests retried after a transient failure "
            "(5xx/429, connection reset, short read) across every "
            "client in the process.",
            self.registry,
        )
        # -- per-tenant usage metering (kubeai_tpu/fleet/metering) ----------
        self.tenant_requests = Counter(
            "kubeai_tenant_requests_total",
            "Requests attributed per tenant and model (X-Client-Id, "
            "API-key principal digest, or 'anonymous').",
            self.registry,
        )
        self.tenant_prompt_tokens = Counter(
            "kubeai_tenant_prompt_tokens_total",
            "Prompt tokens consumed per tenant and model.",
            self.registry,
        )
        self.tenant_completion_tokens = Counter(
            "kubeai_tenant_completion_tokens_total",
            "Completion tokens generated per tenant and model.",
            self.registry,
        )
        self.tenant_stream_seconds = Counter(
            "kubeai_tenant_stream_seconds_total",
            "Seconds of open SSE stream time per tenant and model.",
            self.registry,
        )
        self.tenant_shed = Counter(
            "kubeai_tenant_shed_total",
            "Requests answered 429 (shed/rate-limited) per tenant and "
            "model.",
            self.registry,
        )
        # -- front-door tenant admission (kubeai_tpu/fleet/tenancy) ---------
        self.door_admitted = Counter(
            "kubeai_door_admitted_total",
            "Requests the tenant admission layer admitted per model "
            "(the front door's pre-queue gate).",
            self.registry,
        )
        self.door_rejections = Counter(
            "kubeai_door_rejections_total",
            "Requests refused at the door per tenant (label capped; "
            "overflow aggregates into 'other'), model, and reason "
            "(rate | tokens | quota | overload).",
            self.registry,
        )
        self.door_retry_after = Histogram(
            "kubeai_door_retry_after_seconds",
            "Computed Retry-After values handed out with door 429s "
            "(post-jitter).",
            self.registry,
            buckets=(0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
                     300.0),
        )
        self.door_overload = Gauge(
            "kubeai_door_overload",
            "1 while the door's global overload latch is engaged (fleet "
            "queue pressure crossed the high-water mark; clears at the "
            "low-water mark).",
            self.registry,
        )
        self.door_queue_pressure = Gauge(
            "kubeai_door_queue_pressure",
            "Fleet-wide queue depth the door last observed (aggregator "
            "snapshot, or a direct scrape when the snapshot is stale).",
            self.registry,
        )
        self.door_shedding = Gauge(
            "kubeai_door_shedding",
            "1 while the door is shedding the given scheduling class "
            "(priority label; batch sheds first, realtime never).",
            self.registry,
        )
        self.door_tenants_tracked = Gauge(
            "kubeai_door_tenants_tracked",
            "Tenants with live admission state at the door (buckets and "
            "quota windows; idle tenants expire).",
            self.registry,
        )
        # -- door-shard gossip state plane (kubeai_tpu/routing/gossip) ------
        self.gossip_rounds = Counter(
            "kubeai_gossip_rounds_total",
            "Anti-entropy rounds run by the door shard set (each round "
            "push-pulls every shard with one rotated peer).",
            self.registry,
        )
        self.gossip_syncs = Counter(
            "kubeai_gossip_syncs_total",
            "Per-shard pairwise sync attempts by result: ok (state "
            "exchanged), skip (digests already equal), unreachable "
            "(link severed by a partition).",
            self.registry,
        )
        self.gossip_entries_sent = Counter(
            "kubeai_gossip_entries_sent_total",
            "CRDT entries shipped between door shards (delta-state "
            "sync; full state only after crash/heal/churn).",
            self.registry,
        )
        self.gossip_merges = Counter(
            "kubeai_gossip_merges_total",
            "CRDT entries that actually changed when merged (idempotent "
            "re-deliveries do not count).",
            self.registry,
        )
        self.gossip_state_entries = Gauge(
            "kubeai_gossip_state_entries",
            "CRDT entries held in each door shard's replicated state "
            "(shard label).",
            self.registry,
        )
        self.gossip_peer_staleness = Gauge(
            "kubeai_gossip_peer_staleness_seconds",
            "Seconds since each door shard last exchanged state with "
            "each peer (shard, peer labels); the partition detector's "
            "input.",
            self.registry,
        )
        self.gossip_degraded = Gauge(
            "kubeai_gossip_degraded",
            "1 while the door shard is partitioned from at least one "
            "peer and enforcing the conservative local budget split.",
            self.registry,
        )
        self.gossip_breaker_adoptions = Counter(
            "kubeai_gossip_breaker_adoptions_total",
            "Breaker opens adopted from peer door shards via gossip "
            "per model — failures this shard never had to pay for "
            "itself.",
            self.registry,
        )
        # -- federation plane (kubeai_tpu/federation) ------------------------
        self.federation_joins = Counter(
            "kubeai_federation_joins_total",
            "Federation join sweeps: per-cluster fleet snapshots merged "
            "into one federation snapshot (staleness flagged per "
            "cluster, never silently merged).",
            self.registry,
        )
        self.federation_snapshot_ts = Gauge(
            "kubeai_federation_snapshot_timestamp_seconds",
            "Unix timestamp of the latest federation snapshot.",
            self.registry,
        )
        self.federation_cluster_stale = Gauge(
            "kubeai_federation_cluster_stale",
            "1 while the named peer cluster's snapshot is stale or "
            "unreachable (cluster label) — the failover window's input.",
            self.registry,
        )
        self.federation_spillovers = Counter(
            "kubeai_federation_spillovers_total",
            "Requests the federation router spilled to a peer cluster's "
            "door per model and cluster (fires only on local chip "
            "exhaustion, cost-ranked, tenancy headers forwarded intact).",
            self.registry,
        )
        self.federation_spill_errors = Counter(
            "kubeai_federation_spill_errors_total",
            "Spillover dispatches that failed at the peer door per "
            "cluster (the request then falls back to the local queue).",
            self.registry,
        )
        self.federation_failovers = Counter(
            "kubeai_federation_failovers_total",
            "Whole-model failovers the federation planner actuated per "
            "model and (partitioned source) cluster, governor-gated.",
            self.registry,
        )
        self.federation_failbacks = Counter(
            "kubeai_federation_failbacks_total",
            "Failovers reversed after the partitioned cluster healed, "
            "per model and cluster.",
            self.registry,
        )
        self.federation_failover_denied = Counter(
            "kubeai_federation_failover_denied_total",
            "Federation failovers the actuation governor refused per "
            "model (fencing or telemetry-coverage gate).",
            self.registry,
        )
        self.federation_kv_fills = Counter(
            "kubeai_federation_kv_fills_total",
            "KVP1 prefix fills served from a peer cluster's spill store "
            "per cluster (pages adopted instead of recomputed).",
            self.registry,
        )
        self.federation_kv_refusals = Counter(
            "kubeai_federation_kv_refusals_total",
            "Cross-cluster KVP1 fills refused by the quant-header "
            "protocol per cluster (dtype/scheme mismatch — refused, "
            "never cast; the request recomputes locally).",
            self.registry,
        )
        # -- progressive rollouts (kubeai_tpu/operator/rollout) --------------
        self.rollout_phase = Gauge(
            "kubeai_rollout_phase",
            "Rollout phase per model: 0 idle, 1 canary, 2 ramp, "
            "3 rolling back (pin written, condemned hash draining).",
            self.registry,
        )
        self.rollout_canary_share = Gauge(
            "kubeai_rollout_canary_share",
            "Traffic share the load balancer currently allows the "
            "new-hash endpoints of an in-flight rollout per model "
            "(0..1; absent outside a rollout).",
            self.registry,
        )
        self.rollout_steps = Counter(
            "kubeai_rollout_steps_total",
            "Rollout steps taken per model and step kind (start / "
            "widen / promote), each one governor-budgeted.",
            self.registry,
        )
        self.rollout_verdicts = Counter(
            "kubeai_rollout_verdicts_total",
            "Comparative judge verdicts per model and verdict (pass, or "
            "the failing signal: ttft_regression / breaker_trips / "
            "crashloop) — one per judged tick of an in-flight rollout.",
            self.registry,
        )
        self.rollout_rollbacks = Counter(
            "kubeai_rollout_rollbacks_total",
            "Automatic rollbacks per model and reason: the judge "
            "condemned the new hash and pinned the last-good one.",
            self.registry,
        )
        self.rollout_denied = Counter(
            "kubeai_rollout_denied_total",
            "Rollout steps or rollbacks the actuation governor refused "
            "per model and action (fencing, budget, or coverage gate).",
            self.registry,
        )
        # -- tracing export health ------------------------------------------
        self.tracing_dropped_spans = TracingDroppedSpans(
            "kubeai_tracing_dropped_spans_total",
            "Spans dropped by the OTLP exporter (queue full or exporter "
            "thread dead) instead of blocking the request path.",
            self.registry,
        )


# Process-default bundle (single-replica processes, ad-hoc use).
DEFAULT_METRICS = Metrics()
REGISTRY = DEFAULT_METRICS.registry
INFERENCE_REQUESTS_ACTIVE = DEFAULT_METRICS.inference_requests_active
INFERENCE_REQUESTS_TOTAL = DEFAULT_METRICS.inference_requests_total
CHWBL_LOOKUPS = DEFAULT_METRICS.chwbl_lookups
CHWBL_DISPLACEMENTS = DEFAULT_METRICS.chwbl_displacements


def parse_prometheus_text(text: str) -> dict[tuple[str, tuple], float]:
    """Parse exposition text into {(metric, ((label,val),...)): value} —
    the scrape decoder behind the autoscaler and the fleet aggregator
    (reference: modelautoscaler/metrics.go).

    Tolerates real-world exposition the aggregator will meet on the
    wire: `+Inf`/`NaN` sample values, exponent-format floats, trailing
    millisecond timestamps after the value, and `}`/whitespace inside
    quoted label values. Unparseable lines are skipped, never raised —
    one weird family must not blind the whole scrape."""
    out: dict[tuple[str, tuple], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        labels: list[tuple[str, str]] = []
        brace = line.find("{")
        if brace != -1 and (
            " " not in line[:brace] and "\t" not in line[:brace]
        ):
            name = line[:brace]
            closed = _find_label_close(line, brace + 1)
            if closed < 0:
                continue  # unterminated label block
            for pair in _split_label_pairs(line[brace + 1:closed]):
                if "=" not in pair:
                    continue
                k, v = pair.split("=", 1)
                labels.append((k.strip(), _unquote_label_value(v)))
            tail = line[closed + 1:]
        else:
            name, _, tail = line.partition(" ")
        parts = tail.split()
        if not name or not parts:
            continue
        try:
            # float() natively accepts +Inf/-Inf/NaN and exponent forms.
            value = float(parts[0])
        except ValueError:
            continue
        # parts[1], when present, is the optional sample timestamp — it
        # must not be mistaken for the value (the old rsplit was).
        out[(name, tuple(sorted(labels)))] = value
    return out


def _find_label_close(line: str, start: int) -> int:
    """Index of the `}` closing the label block opened before `start`,
    honoring quotes and backslash escapes (a quoted label value may
    legally contain `}`). -1 when unterminated."""
    in_q = esc = False
    for i in range(start, len(line)):
        ch = line[i]
        if esc:
            esc = False
        elif ch == "\\" and in_q:
            esc = True
        elif ch == '"':
            in_q = not in_q
        elif ch == "}" and not in_q:
            return i
    return -1


def _split_label_pairs(s: str) -> list[str]:
    """Split `k1="v1",k2="v2"` on commas outside quoted values. Tracks
    the backslash escape state: an escaped quote (`\\"`) inside a value —
    which `_fmt_labels`'s own escaping produces — must NOT toggle the
    in-quotes flag, or every value containing a quote fails to
    round-trip through `parse_prometheus_text`."""
    pairs, cur, in_q, esc = [], "", False, False
    for ch in s:
        if esc:
            cur += ch
            esc = False
        elif ch == "\\" and in_q:
            cur += ch
            esc = True
        elif ch == '"':
            in_q = not in_q
            cur += ch
        elif ch == "," and not in_q:
            pairs.append(cur)
            cur = ""
        else:
            cur += ch
    if cur:
        pairs.append(cur)
    return pairs


def _unquote_label_value(v: str) -> str:
    """Strip one layer of quotes and undo exposition-format escaping
    (`\\\\` → `\\`, `\\"` → `"`, `\\n` → newline)."""
    if len(v) >= 2 and v[0] == '"' and v[-1] == '"':
        v = v[1:-1]
    out, i = [], 0
    while i < len(v):
        ch = v[i]
        if ch == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            out.append("\n" if nxt == "n" else nxt)
            i += 2
        else:
            out.append(ch)
            i += 1
    return "".join(out)
