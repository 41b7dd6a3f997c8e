"""The engine's HTTP serving front — what runs inside a KubeAITPU engine
Pod (rendered by kubeai_tpu.operator.engines.kubeai_tpu_engine).

Endpoints (OpenAI-compatible surface + the admin seam the operator uses):
  POST /v1/chat/completions   (stream=true → SSE chunks)
  POST /v1/completions
  GET  /v1/models
  GET  /health                ← readiness/liveness probes
  GET  /metrics               ← Prometheus text (engine counters)
  GET  /v1/state              ← admin snapshot (occupancy, spec/prefix stats)
  POST /v1/load_lora_adapter  ← operator adapter orchestration
  POST /v1/unload_lora_adapter   (reference: internal/vllmclient/client.go)

Serving loop: a dedicated thread drives Engine.step() continuously while
work exists; HTTP handler threads enqueue requests and consume per-request
token queues (streaming starts on the first decoded chunk).

Run: python -m kubeai_tpu.engine.server --model-url ... [--tpu-topology 2x2]
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import queue
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler

from kubeai_tpu.httpserver import DeepBacklogHTTPServer

from kubeai_tpu.engine.engine import (
    Engine,
    EngineConfig,
    EngineDraining,
    StepEvent,
)
from kubeai_tpu.engine.routes import encode_block, encode_forwards, join_blocks
from kubeai_tpu.engine.sampling import SamplingParams
from kubeai_tpu.metrics import flightrecorder, tracing
from kubeai_tpu.engine.tokenizer import Tokenizer, load_tokenizer
from kubeai_tpu.metrics.registry import (
    Counter,
    Gauge,
    Histogram,
    ObjstoreRetries,
    Registry,
    TracingDroppedSpans,
)
from kubeai_tpu.scheduling import (
    DeadlineInfeasible,
    PRIORITY_CLASSES,
)
from kubeai_tpu.utils import retryafter

logger = logging.getLogger(__name__)


# Request-phase latencies: cover sub-ms tiny-model CPU tests through the
# 600s request budget.
REQUEST_LATENCY_BUCKETS_S = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)
# Inter-token gaps sit orders of magnitude below request latencies —
# fused decode chunks emit most tokens ~0 apart, chunk boundaries land in
# the ms range, and anything past 2.5s is a stall worth seeing.
ITL_BUCKETS_S = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)


# `_collect` decodes a stream's last 32 to 64 tokens at an event, not all.
DECODE_TAIL_TOKENS = 32


class _EventQueue(queue.Queue):
    """A stream's queue of StepEvents that remembers when each was handed
    over: after `get()` returns an event, `handed_at` is the
    `time.perf_counter()` of its `put()`. One consumer per queue (the
    request's handler thread), which is who reads `handed_at`."""

    handed_at = 0.0

    def _put(self, ev):  # under the queue's mutex, like the base class's
        self.queue.append((ev, time.perf_counter()))

    def _get(self):
        ev, self.handed_at = self.queue.popleft()
        return ev


class EngineMetrics:
    def __init__(self):
        self.registry = Registry()
        self.generated_tokens = Counter(
            "kubeai_engine_generated_tokens_total",
            "Tokens generated.",
            self.registry,
        )
        self.prompt_tokens = Counter(
            "kubeai_engine_prompt_tokens_total",
            "Prompt tokens processed.",
            self.registry,
        )
        self.active_requests = Gauge(
            "kubeai_engine_active_requests",
            "Requests currently queued or decoding.",
            self.registry,
        )
        self.requests_total = Counter(
            "kubeai_engine_requests_total", "Requests served.", self.registry
        )
        self.slots_active = Gauge(
            "kubeai_engine_slots_active",
            "Decode slots currently occupied.",
            self.registry,
        )
        self.requests_pending = Gauge(
            "kubeai_engine_requests_pending",
            "Requests queued for a free slot.",
            self.registry,
        )
        self.spec_proposed = Gauge(
            "kubeai_engine_spec_proposed_tokens_total",
            "Speculative tokens proposed (prompt-lookup or draft).",
            self.registry,
        )
        self.spec_accepted = Gauge(
            "kubeai_engine_spec_accepted_tokens_total",
            "Speculative tokens accepted by verify.",
            self.registry,
        )
        # Monotonically-growing totals exported with COUNTER semantics
        # (they were Gauges once — a `_total` metric that can be `set()`
        # backward breaks every rate() over it); sync_engine folds the
        # engine's cumulative stats in as deltas.
        self.prefix_hit_tokens = Counter(
            "kubeai_engine_prefix_cached_tokens_total",
            "Prompt tokens served from the prefix cache (skipped prefill).",
            self.registry,
        )
        self.prefix_prompt_tokens = Counter(
            "kubeai_engine_prefix_prompt_tokens_total",
            "Prompt tokens seen by prefix-cache admission.",
            self.registry,
        )
        # -- disaggregated serving: KV handoff transfer ---------------------
        self.kv_handoffs = Counter(
            "kubeai_engine_kv_handoffs_total",
            "KV handoffs by direction: exported after prefill (prefill "
            "role) / imported into decode slots (decode role).",
            self.registry,
        )
        self.kv_transfer_bytes = Counter(
            "kubeai_engine_kv_transfer_bytes_total",
            "Serialized KV handoff bytes moved, by direction "
            "(export = pushed to a decode pool, import = received on "
            "/v1/kv/import).",
            self.registry,
        )
        self.kv_transfer_seconds = Histogram(
            "kubeai_engine_kv_transfer_seconds",
            "Wall time of one KV handoff transfer (chunked HTTP push or "
            "receive), by direction.",
            self.registry,
            buckets=REQUEST_LATENCY_BUCKETS_S,
        )
        # -- cluster KV-sharing tier (peer prefix fetch / objstore spill) ---
        self.kv_fetch_attempts = Counter(
            "kubeai_kv_fetch_attempts_total",
            "Prefix KV fetches attempted, by source (peer = /v1/kv/export "
            "on the holding replica, spill = objstore fill).",
            self.registry,
        )
        self.kv_fetch_bytes = Counter(
            "kubeai_kv_fetch_bytes_total",
            "Serialized prefix-page bytes fetched from peers or the "
            "objstore spill tier instead of recomputing prefill.",
            self.registry,
        )
        self.kv_fetch_failures = Counter(
            "kubeai_kv_fetch_failures_total",
            "Prefix KV fetches that failed (timeout, peer death, "
            "malformed blob, pool refusal) and fell back to recompute, "
            "by source.",
            self.registry,
        )
        self.kv_share_pages = Counter(
            "kubeai_engine_kv_share_pages_total",
            "Cluster KV-sharing page movement by direction: exported "
            "(served to a peer), imported (seeded from a peer), spilled "
            "(evicted to objstore), filled (restored from objstore).",
            self.registry,
        )
        self.role_info = Gauge(
            "kubeai_engine_role",
            "1 for this replica's serving role label "
            "(prefill/decode/unified).",
            self.registry,
        )
        self.slot_capacity = Gauge(
            "kubeai_engine_slot_capacity",
            "Configured decode slots — with kubeai_engine_batch_size this "
            "gives the autoscaler slot occupancy.",
            self.registry,
        )
        # -- request-lifecycle latency histograms --------------------------
        self.queue_wait = Histogram(
            "kubeai_engine_queue_wait_seconds",
            "Time a request waited in the pending queue before its "
            "prefill was dispatched.",
            self.registry,
            buckets=REQUEST_LATENCY_BUCKETS_S,
        )
        self.prefill = Histogram(
            "kubeai_engine_prefill_seconds",
            "Prefill dispatch to first sampled token (compute only; "
            "queue wait excluded).",
            self.registry,
            buckets=REQUEST_LATENCY_BUCKETS_S,
        )
        self.ttft = Histogram(
            "kubeai_engine_ttft_seconds",
            "Engine time-to-first-token: request enqueue to first sampled "
            "token (queue wait + prefill).",
            self.registry,
            buckets=REQUEST_LATENCY_BUCKETS_S,
        )
        self.itl = Histogram(
            "kubeai_engine_inter_token_latency_seconds",
            "Gap between consecutive emitted tokens of one request. "
            "Tokens inside one fused decode chunk surface together, so "
            "the distribution is bimodal: ~0 intra-chunk, the device-step "
            "time at chunk boundaries.",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        self.e2e = Histogram(
            "kubeai_engine_e2e_seconds",
            "Request enqueue to final token for completed (stop/length) "
            "requests; cancellations are excluded.",
            self.registry,
            buckets=REQUEST_LATENCY_BUCKETS_S,
        )
        # -- admission, the serve loop's gaps and SSE emission: host time
        # counted where it happens (the spans of the same names are on a
        # /v1/profile trace; docs/concepts/observability.md) --------------
        self.admit_host = Histogram(
            "kubeai_engine_admit_host_seconds",
            "Host time of one admission device call made by Engine.step "
            "(the `admit.host` spans): scheduler pops, page grants, input "
            "staging, uploads and dispatch, and the bookkeeping of the "
            "admitted once their first tokens are back.",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        self.admit_wait = Histogram(
            "kubeai_engine_admit_wait_seconds",
            "Time one admission device call kept the engine thread "
            "blocked on its sampled first tokens (`admit.wait`): whatever "
            "the device still had queued, then the prefill itself.",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        self.admit_calls = Counter(
            "kubeai_engine_admit_calls_total",
            "Admission device calls made by Engine.step (one fused "
            "same-bucket batch, one chunked prompt or one prefix-cache "
            "hit each).",
            self.registry,
        )
        self.step_reaps = Counter(
            "kubeai_engine_step_reaps_total",
            "Decode chunks reaped (waited for, read back, emitted), by "
            "what forced the reap (label `barrier`, as on the `step.reap` "
            "span): none = behind the next dispatched chunk, so the "
            "host's pass hid behind the device; admission / seq_cap / "
            "spec = ahead of it; external = outside a step.",
            self.registry,
        )
        self.sampler_chunks = Counter(
            "kubeai_engine_sampler_chunks_total",
            "Decode chunks reaped, by what their sampler ran on the device "
            "(label `path`; the chunk hands the scalar back beside its "
            "tokens): argmax = every live row greedy, nothing but the "
            "argmax; pool = some live row samples, so every step took the "
            "top-k over the vocabulary, the softmax and the draw.",
            self.registry,
        )
        self.device_starved = Histogram(
            "kubeai_engine_device_starved_seconds",
            "Seconds the device had nothing queued before a dispatch, as "
            "the engine thread observed it: from its return from a "
            "blocking wait on the newest program dispatched (label "
            "`after`: reap_admission / reap_seq_cap / reap_spec / "
            "reap_external = a decode chunk reaped ahead of the next "
            "dispatch, reap_sync = reaped with nothing dispatched behind "
            "it, admit = an admission's first tokens) to the next "
            "dispatch (label `before`: prefill | decode). A LOWER bound "
            "of the device's gap: the wake-up after the device finished "
            "and the launch after the call are left out. Time in which "
            "the engine had no work is not counted.",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        self.dispatches = Counter(
            "kubeai_engine_dispatches_total",
            "Programs Engine.step dispatched (label `before`: prefill = "
            "one per admission device call, decode = one per decode / "
            "speculation / block chunk), by the state of the device's "
            "queue at that moment (label `queue`: empty = observed empty, "
            "its seconds are in kubeai_engine_device_starved_seconds; "
            "drained = not observed empty, yet the newest program had "
            "already finished: the device ran dry behind the host's back "
            "for an unknown time; busy = the dispatch hid behind device "
            "work).",
            self.registry,
        )
        self.decode_live_pages = Counter(
            "kubeai_engine_decode_live_pages_total",
            "KV pages that hold the active slots' tokens (ceil(tokens / "
            "page) a slot), added up once per dispatched decode chunk: "
            "its delta over the chunks of a window is the mean live "
            "pages the decode attention kernel reads a layer. A family "
            "with two kinds of KV layer has a series a pool (label `pool`: "
            "global = ceil(tokens / page) a slot, window = the ring pages "
            "from the first in-window position on, at most a ring a "
            "slot), a family with one latent pool the one series "
            "pool=latent; every other family the one series without a label.",
            self.registry,
        )
        self.decode_page_blocks = Counter(
            "kubeai_engine_decode_page_blocks_total",
            "Blocks of pages the latent decode kernel attends (ceil(live "
            "pages / G) a slot, G pages of one slot one block: "
            "ops/latent_attention.py:block_pages), added up once per "
            "dispatched decode chunk beside decode_live_pages_total: live "
            "pages over G x blocks is the share of a block's columns that "
            "hold a fetched row. The one series pool=latent, of a family "
            "with a latent pool; no series for any other.",
            self.registry,
        )
        self.prefill_tokens = Counter(
            "kubeai_engine_prefill_tokens_total",
            "Token positions computed by admission calls (label `kind`: "
            "useful = prompt tokens that needed computing, pad = the rest "
            "of the padded shape that ran: admit batch rounded up to a "
            "power of two times the bucket, or whole prefill chunks).",
            self.registry,
        )
        self.loop_gap = Histogram(
            "kubeai_engine_loop_gap_seconds",
            "Time the serve loop spends between two Engine.step calls on "
            "the thread that drives the device, per pass that had work "
            "(label `part`: fanout = handing the step's events to the "
            "stream queues, sync = moving the engine's records into this "
            "registry).",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        self.emit_busy = Histogram(
            "kubeai_engine_emit_busy_seconds",
            "Busy time of a request's handler thread per burst of events "
            "(`http.emit`): from its queue handing it an event until the "
            "queue is empty again — detokenize, stop strings, JSON, the "
            "chunked write and its flush. Handler threads share the GIL "
            "with the engine thread.",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        self.emit_lag = Histogram(
            "kubeai_engine_emit_lag_seconds",
            "From the serve loop handing an event to a stream's queue to "
            "the handler having flushed (or, unary, decoded) what it "
            "produced; one observation per event.",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        # -- a routed family's expert load and the hand-over of routes
        # (docs/concepts/expert-routes.md). Nothing moves for a dense one.
        self.moe_expert_tokens = Counter(
            "kubeai_engine_moe_expert_tokens_total",
            "Token-layer assignments per global expert id (label "
            "`expert`), summed over routed layers, over the rows whose "
            "tokens were kept. Over any window their sum is "
            "kubeai_engine_route_rows_total x routed layers x k.",
            self.registry,
        )
        self.moe_imbalance = Histogram(
            "kubeai_engine_moe_imbalance_ratio",
            "The fullest expert's load over the mean load among the kept "
            "rows of one forward pass (a decode step's live slots, an "
            "admission call's prompt rows), one observation per pass and "
            "routed layer. 1.0 = even; experts / k = every row took the "
            "same set.",
            self.registry,
            buckets=(1.0, 1.1, 1.25, 1.5, 1.75, 2, 2.5, 3, 4, 6, 8, 16,
                     32, 64, 128),
        )
        self.route_rows = Counter(
            "kubeai_engine_route_rows_total",
            "Positions whose expert sets the engine read back and whose "
            "tokens were kept (label `kind`: prefill = prompt positions "
            "an admission call computed, decode = decode steps whose "
            "token was emitted).",
            self.registry,
        )
        self.moe_experts_touched = Counter(
            "kubeai_engine_moe_experts_touched_total",
            "Experts that at least one kept row was routed to, summed over "
            "forward passes and routed layers (label `kind` as "
            "kubeai_engine_route_rows_total): over "
            "kubeai_engine_moe_passes_total, the experts a pass reads in a "
            "layer when experts are computed sparsely.",
            self.registry,
        )
        self.moe_passes = Counter(
            "kubeai_engine_moe_passes_total",
            "(Forward pass, routed layer) pairs that kept a row (label "
            "`kind`).",
            self.registry,
        )
        self.moe_assignments = Counter(
            "kubeai_engine_moe_assignments_total",
            "(Kept row, routed layer, taken expert) assignments, by whether "
            "the expert is one this engine holds (label `held`: true | "
            "false; an engine that holds every expert counts all as true). "
            "The share under true is what this chip's part of the routed "
            "sum was computed from: 1 / shares at even routing.",
            self.registry,
        )
        self.state_pool_bytes = Gauge(
            "kubeai_engine_state_pool_bytes",
            "Resident bytes of the state a family keeps beside its pages "
            "(label `kind`: recurrent = the linear-attention layers' "
            "states, conv = their convolutions' last inputs: a slot's "
            "share is the same whatever its length; latent = the one page "
            "pool of a family with latent attention, beside the state it "
            "lives with). Absent for a family all of whose layers keep keys "
            "and values.",
            self.registry,
        )
        self.kv_pool_pages = Gauge(
            "kubeai_engine_kv_pool_pages",
            "Pages of each KV pool of a family that keeps two, or one latent "
            "pool (label `pool`: "
            "global = the layers whose pages a slot takes by its length, "
            "window = the layers that keep a ring of fixed size a slot, "
            "latent = the layers that keep one latent row a token; "
            "label `state`: used, free; the scratch pages left out). Absent "
            "for a family with one kind of KV layer.",
            self.registry,
        )
        self.state_admissions = Counter(
            "kubeai_engine_state_admissions_total",
            "Slots whose state pools an admission wrote (whole: the state "
            "after the prompt's true length, never added to what the slot "
            "held).",
            self.registry,
        )
        self.route_rows_sent = Counter(
            "kubeai_engine_route_rows_sent_total",
            "Rows of expert sets handed to requests that asked for them "
            "(`kubeai_routes: true`), rows recomputed after a preemption "
            "counted each time they are sent.",
            self.registry,
        )
        self.route_requests = Counter(
            "kubeai_engine_route_requests_total",
            "Requests admitted that asked for their expert routes on an "
            "engine that hands them over.",
            self.registry,
        )
        # -- a family that generates by diffusion over blocks
        # (docs/concepts/block-diffusion.md). Nothing moves for any other.
        self.block_forwards = Counter(
            "kubeai_engine_block_forwards_total",
            "Forwards of the model over one slot's block, over the blocks "
            "that served a token (label `kind`: denoise = a forward that "
            "commits rows of a block still masked, commit = the forward "
            "over a finished block that writes its K and V).",
            self.registry,
        )
        self.block_tokens = Counter(
            "kubeai_engine_block_tokens_total",
            "Tokens that denoising forwards committed, over the blocks that "
            "served a token (a last block's rows past max_tokens included).",
            self.registry,
        )
        self.block_program_forwards = Counter(
            "kubeai_engine_block_program_forwards_total",
            "Forwards of the model the decode chunks ran, each over every "
            "slot's block: what a chunk's device time divides by.",
            self.registry,
        )
        self.block_chunks = Counter(
            "kubeai_engine_block_chunks_total",
            "Decode chunks of a family that generates by blocks that were "
            "reaped.",
            self.registry,
        )
        self.forwards_sent = Counter(
            "kubeai_engine_forwards_sent_total",
            "Forwards handed to requests that asked for them "
            "(`kubeai_forwards: true`).",
            self.registry,
        )
        self.forward_requests = Counter(
            "kubeai_engine_forward_requests_total",
            "Requests admitted that asked for their forwards of a family "
            "that generates by blocks.",
            self.registry,
        )
        self._timing_hist = {
            "moe_imbalance": self.moe_imbalance,
            "queue_wait": self.queue_wait,
            "prefill": self.prefill,
            "ttft": self.ttft,
            "itl": self.itl,
            "e2e": self.e2e,
            "admit_host": self.admit_host,
            "admit_wait": self.admit_wait,
        }
        # -- per-decode-step engine-loop gauges ----------------------------
        self.batch_size = Gauge(
            "kubeai_engine_batch_size",
            "Running batch size (occupied decode slots) at the last "
            "engine step.",
            self.registry,
        )
        self.kv_utilization = Gauge(
            "kubeai_engine_kv_cache_utilization",
            "Fraction of KV-cache capacity in use (pages allocated / "
            "pool, or token positions / slot capacity).",
            self.registry,
        )
        self.kv_cache_bytes = Gauge(
            "kubeai_engine_kv_cache_bytes",
            "Resident bytes of the KV-cache pool (pages + quantization "
            "scales) — int8 pools report roughly half a bf16 pool of "
            "equal token capacity.",
            self.registry,
        )
        self.kv_quant_enabled = Gauge(
            "kubeai_engine_kv_quant_enabled",
            "1 when the paged KV cache stores int8 quantized pages "
            "(kv_dtype=int8), else 0.",
            self.registry,
        )
        self.kv_quant_capacity_factor = Gauge(
            "kubeai_engine_kv_quant_capacity_factor",
            "Slot-capacity multiplier of the configured KV dtype vs bf16 "
            "at equal HBM budget (2D/(D+4) under int8, 1.0 under bf16) — "
            "what the autoscaler and capacity planner scale the replica's "
            "effective KV capacity by.",
            self.registry,
        )
        self.tokens_per_step = Gauge(
            "kubeai_engine_tokens_per_step",
            "Tokens emitted by the last engine step (all requests).",
            self.registry,
        )
        self.step_duration = Gauge(
            "kubeai_engine_step_duration_seconds",
            "Wall duration of the last engine step's decode dispatch + "
            "fetch.",
            self.registry,
        )
        # -- engine step profiler (kubeai_tpu/fleet/profiler) ---------------
        self.step_phase = Histogram(
            "kubeai_engine_step_phase_seconds",
            "Wall time per engine-step phase (label `phase`: schedule / "
            "prefill / decode / dispatch / overlap_idle / readback / "
            "sample / routes / kv_transfer) — the per-phase answer to "
            "'why is ITL high'. decode is the async jit DISPATCH; the "
            "device wait surfaces as overlap_idle at reap (shrinking "
            "toward zero under the overlapped step pipeline) and the "
            "token transfer as readback; routes is a routed family's "
            "host work on the expert sets a step read back.",
            self.registry,
            buckets=ITL_BUCKETS_S,
        )
        self.tracing_dropped = TracingDroppedSpans(
            "kubeai_tracing_dropped_spans_total",
            "Spans dropped by the OTLP exporter (queue full or exporter "
            "thread dead) instead of blocking the request path.",
            self.registry,
        )
        # -- scheduler queue-pressure signal (per priority class) ----------
        self.queue_depth = Gauge(
            "kubeai_engine_queue_depth",
            "Requests waiting in the scheduler, per priority class — the "
            "autoscaler's queue-pressure depth signal.",
            self.registry,
        )
        self.queue_oldest_wait = Gauge(
            "kubeai_engine_queue_oldest_wait_seconds",
            "Age of the oldest waiting request per priority class — the "
            "autoscaler's queue-pressure staleness signal.",
            self.registry,
        )
        self.queue_admitted = Gauge(
            "kubeai_engine_queue_admitted_total",
            "Requests dispatched out of the scheduler per priority class.",
            self.registry,
        )
        self.queue_shed = Gauge(
            "kubeai_engine_queue_shed_total",
            "Requests shed at enqueue (infeasible deadline) per priority "
            "class.",
            self.registry,
        )
        self.queue_mean_wait = Gauge(
            "kubeai_engine_queue_mean_wait_seconds",
            "Mean queue wait of dispatched requests per priority class.",
            self.registry,
        )
        self.sched_service_rate = Gauge(
            "kubeai_engine_sched_service_rate",
            "Scheduler drain-rate estimate (requests/second) used for "
            "deadline feasibility and the computed Retry-After.",
            self.registry,
        )
        # -- graceful drain ------------------------------------------------
        self.draining = Gauge(
            "kubeai_engine_draining",
            "1 while the server is draining (refusing new work, "
            "completing in-flight generations), else 0.",
            self.registry,
        )
        self.drain_terminated = Gauge(
            "kubeai_engine_drain_terminated_requests_total",
            "In-flight requests terminated because the drain budget "
            "expired before they completed.",
            self.registry,
        )
        # -- step watchdog ---------------------------------------------------
        self.watchdog_wedged = Gauge(
            "kubeai_engine_watchdog_wedged",
            "1 after the step watchdog detected a hung device step "
            "(health flipped, restart requested), else 0.",
            self.registry,
        )
        self.watchdog_stalls = Counter(
            "kubeai_engine_watchdog_stalls_total",
            "Hung-device-step detections by the engine watchdog.",
            self.registry,
        )
        # -- cold start: snapshot restore-first boot (engine/coldstart) -----
        self.coldstart_phase = Gauge(
            "kubeai_coldstart_phase_seconds",
            "Wall time of each boot phase (label `phase`: fetch/restore "
            "on the snapshot path, load on the full HF-conversion path, "
            "compile/warmup on both) — the per-phase answer to 'why was "
            "this replica slow to Ready'.",
            self.registry,
        )
        self.coldstart_total = Gauge(
            "kubeai_coldstart_total_seconds",
            "End-to-end boot wall time (model resolve through warm-up) — "
            "the measured cold-start cost the capacity planner prices "
            "into prewarm and preemption choices.",
            self.registry,
        )
        self.coldstart_restored = Gauge(
            "kubeai_coldstart_restored",
            "1 when this boot restored the engine snapshot (params + "
            "compilation cache), 0 on the full load path.",
            self.registry,
        )
        self.coldstart_events = Counter(
            "kubeai_coldstart_snapshot_events_total",
            "Snapshot lifecycle events at boot (label `event`: restored, "
            "published, absent, mismatch, error). `mismatch` means the "
            "stored fingerprint disagreed and the boot fell back to full "
            "load — a stale layout is never served.",
            self.registry,
        )
        self.objstore_retries = ObjstoreRetries(
            "kubeai_objstore_retries_total",
            "Object-store requests retried after a transient failure "
            "(5xx/429, connection reset, short read) across every "
            "client in the process.",
            self.registry,
        )

    def record_coldstart(self, cold_start: dict) -> None:
        """Fold a ColdStartTracker snapshot into the boot metrics."""
        for phase, secs in (cold_start.get("phases") or {}).items():
            self.coldstart_phase.set(secs, phase=phase)
        self.coldstart_total.set(float(cold_start.get("total_s", 0.0)))
        self.coldstart_restored.set(1 if cold_start.get("restored") else 0)
        for ev in cold_start.get("events") or ():
            self.coldstart_events.inc(event=ev)

    def observe_timing(
        self, kind: str, seconds: float, exemplar: str | None = None
    ) -> None:
        h = self._timing_hist.get(kind)
        if h is not None:
            h.observe(seconds, exemplar=exemplar)

    def sync_engine(self, engine) -> None:
        """Snapshot engine serving state (the engine owns these counters;
        it records plain host-side values and this method moves them into
        the registry). Called from the serve loop after each step AND at
        /metrics scrape time, so the histograms are current even when the
        loop has gone idle."""
        snap = engine_state_snapshot(engine)
        self.slots_active.set(snap["slots_active"])
        self.requests_pending.set(snap["requests_pending"])
        stats = snap["spec_stats"]
        if stats:
            self.spec_proposed.set(stats["proposed"])
            self.spec_accepted.set(stats["accepted"])
        pstats = snap["prefix_stats"]
        if pstats:
            # Counter semantics over cumulative engine-side stats: fold in
            # the delta since the last sync (never set, never backward).
            self.prefix_hit_tokens.inc(
                max(0.0, pstats["hit_tokens"] - self.prefix_hit_tokens.get())
            )
            self.prefix_prompt_tokens.inc(
                max(
                    0.0,
                    pstats["prompt_tokens"]
                    - self.prefix_prompt_tokens.get(),
                )
            )
        inner = getattr(engine, "inner", engine)  # LockstepEngine proxies
        astats = getattr(inner, "admit_stats", None)
        if astats:
            useful = astats["useful_tokens"]
            for counter, total, labels in (
                (self.admit_calls, astats["calls"], {}),
                (self.prefill_tokens, useful, {"kind": "useful"}),
                (self.prefill_tokens, astats["padded_tokens"] - useful,
                 {"kind": "pad"}),
            ):
                counter.inc(max(0.0, total - counter.get(**labels)), **labels)
        for counter, tally, label in (
            (self.step_reaps, "step_reaps", "barrier"),
            (self.sampler_chunks, "sampler_chunks", "path"),
        ):
            for value, total in getattr(inner, tally, {}).items():
                labels = {label: value}
                counter.inc(max(0.0, total - counter.get(**labels)), **labels)
        book = getattr(inner, "device_queue", None)
        if book is not None:
            for after, before, seconds in book.drain():
                self.device_starved.observe(
                    seconds, after=after, before=before
                )
            for (before, queue), total in book.dispatches.items():
                labels = {"before": before, "queue": queue}
                self.dispatches.inc(
                    max(0.0, total - self.dispatches.get(**labels)), **labels
                )
        live = getattr(inner, "live_kv", None)
        pools = getattr(inner, "kv_pools", lambda: None)()
        if live and pools:
            for pool, book in inner.live_pages().items():
                self.decode_live_pages.inc(max(
                    0.0,
                    book["pages_total"] - self.decode_live_pages.get(pool=pool),
                ), pool=pool)
                if pool == "latent":
                    self.decode_page_blocks.inc(max(
                        0.0,
                        inner.live_blocks["blocks_total"]
                        - self.decode_page_blocks.get(pool=pool),
                    ), pool=pool)
            for pool in pools:
                used = pool["pages_used"]
                self.kv_pool_pages.set(used, pool=pool["kind"], state="used")
                self.kv_pool_pages.set(
                    pool["pages"] - used, pool=pool["kind"], state="free")
        elif live:
            self.decode_live_pages.inc(max(
                0.0, live["pages_total"] - self.decode_live_pages.get()))
        rstats = getattr(inner, "route_stats", None)
        if rstats and getattr(inner, "moe", None):
            for counter, total, labels in (
                *(
                    (self.moe_expert_tokens, int(n), {"expert": str(e)})
                    for e, n in enumerate(rstats["expert_tokens"])
                ),
                (self.route_rows, rstats["rows_prefill"], {"kind": "prefill"}),
                (self.route_rows, rstats["rows_decode"], {"kind": "decode"}),
                *(
                    (counter, rstats[f"{name}_{kind}"], {"kind": kind})
                    for counter, name in ((self.moe_experts_touched, "touched"),
                                          (self.moe_passes, "passes"))
                    for kind in ("prefill", "decode")
                ),
                (self.route_rows_sent, rstats["rows_sent"], {}),
                (self.route_requests, rstats["requests"], {}),
                (self.moe_assignments, rstats["assigned_held"], {"held": "true"}),
                (self.moe_assignments, rstats["assigned_absent"],
                 {"held": "false"}),
            ):
                counter.inc(max(0.0, total - counter.get(**labels)), **labels)
        state_info = getattr(inner, "state_info", None)
        if state_info:
            for kind, nbytes in state_info["pool_bytes"].items():
                self.state_pool_bytes.set(nbytes, kind=kind)
            total = inner.state_stats["admissions"]
            self.state_admissions.inc(
                max(0.0, total - self.state_admissions.get()))
        bstats = getattr(inner, "block_stats", None)
        if bstats and getattr(inner, "block_generation", None):
            for counter, total, labels in (
                (self.block_forwards, bstats["denoise"], {"kind": "denoise"}),
                (self.block_forwards, bstats["commit"], {"kind": "commit"}),
                (self.block_tokens, bstats["tokens"], {}),
                (self.block_program_forwards, bstats["program_forwards"], {}),
                (self.block_chunks, bstats["chunks"], {}),
                (self.forwards_sent, bstats["forwards_sent"], {}),
                (self.forward_requests, bstats["requests"], {}),
            ):
                counter.inc(max(0.0, total - counter.get(**labels)), **labels)
        dstats = getattr(inner, "disagg_stats", None)
        if dstats:
            for direction, count_key, bytes_key in (
                ("export", "exported", "exported_bytes"),
                ("import", "imported", "imported_bytes"),
            ):
                self.kv_handoffs.inc(
                    max(
                        0.0,
                        dstats[count_key]
                        - self.kv_handoffs.get(direction=direction),
                    ),
                    direction=direction,
                )
                self.kv_transfer_bytes.inc(
                    max(
                        0.0,
                        dstats[bytes_key]
                        - self.kv_transfer_bytes.get(direction=direction),
                    ),
                    direction=direction,
                )
        kstats = getattr(inner, "kv_share_stats", None)
        if kstats:
            for direction, key in (
                ("exported", "exported_pages"),
                ("imported", "imported_pages"),
                ("spilled", "spilled_pages"),
                ("filled", "filled_pages"),
            ):
                self.kv_share_pages.inc(
                    max(
                        0.0,
                        kstats[key]
                        - self.kv_share_pages.get(direction=direction),
                    ),
                    direction=direction,
                )
        slots = getattr(getattr(inner, "cfg", None), "num_slots", None)
        if slots is not None:
            self.slot_capacity.set(slots)
        kv_info = snap.get("kv_cache") or {}
        if kv_info:
            self.kv_cache_bytes.set(kv_info.get("pool_bytes", 0))
            self.kv_quant_enabled.set(
                1.0 if kv_info.get("quantized") else 0.0
            )
            self.kv_quant_capacity_factor.set(
                kv_info.get("capacity_factor", 1.0)
            )
        drain = getattr(inner, "drain_timing", None)
        if drain is not None:
            for rec in drain():
                self.observe_timing(
                    rec[0], rec[1],
                    exemplar=rec[2] if len(rec) > 2 else None,
                )
        prof = getattr(inner, "profiler", None)
        if prof is not None:
            for phase, seconds in prof.drain():
                self.step_phase.observe(seconds, phase=phase)
        step_stats = snap["last_step"]
        if step_stats:
            self.batch_size.set(step_stats.get("batch_size", 0))
            self.tokens_per_step.set(step_stats.get("tokens", 0))
            self.step_duration.set(step_stats.get("duration_s", 0.0))
        self.kv_utilization.set(snap["kv_utilization"])
        sched = snap.get("scheduler") or {}
        for cls, stats in (sched.get("classes") or {}).items():
            self.queue_depth.set(stats["depth"], **{"class": cls})
            self.queue_oldest_wait.set(
                stats["oldest_wait_s"], **{"class": cls}
            )
            self.queue_admitted.set(
                stats["admitted_total"], **{"class": cls}
            )
            self.queue_shed.set(stats["shed_total"], **{"class": cls})
            self.queue_mean_wait.set(
                stats["mean_queue_wait_s"], **{"class": cls}
            )
        if sched:
            self.sched_service_rate.set(sched.get("service_rate", 0.0))


# What a request can ask a forward to hand over: the request flag (also the
# key of a response that carries it) and the StepEvent field its blocks come
# on; `_encode_handed` puts them on the wire.
HANDED_OVER = {"kubeai_routes": "routes", "kubeai_forwards": "forwards"}


def _encode_handed(handover: dict | None, handed: dict | None) -> dict:
    """The hand-over keys of a chunk or a choice. `handover` is `{flag:
    whether the engine has any}` for the flags the request set (None or
    empty: it set none, and the response is what it always was); `handed`
    the blocks to send, a flag. A flag the engine has nothing for is null."""
    out = {}
    for flag, on in (handover or {}).items():
        blocks = (handed or {}).get(flag) or ()
        if not on:
            out[flag] = None
        elif flag == "kubeai_routes":
            out[flag] = [encode_block(*b) for b in join_blocks(blocks)]
        else:
            # Packed: one object for all the forwards this chunk carries.
            out[flag] = [encode_forwards(blocks)] if blocks else []
    return out


def engine_state_snapshot(engine) -> dict:
    """Serving-state snapshot shared by /metrics and /v1/state. Occupancy
    comes from the OUTER engine (LockstepEngine's num_pending includes
    adds buffered for the next broadcast — the same counts admission
    uses); spec/prefix stats live only on the inner engine."""
    inner = getattr(engine, "inner", engine)  # LockstepEngine proxies
    kvu = getattr(inner, "kv_utilization", None)
    sched = getattr(inner, "scheduler", None)
    kv_info = getattr(inner, "kv_cache_info", None)
    dev_info = getattr(inner, "device_info", None)
    moe = getattr(inner, "moe", None)
    blocks = getattr(inner, "block_generation", None)
    state_info = getattr(inner, "state_info", None)
    kv_pools = getattr(inner, "kv_pools", lambda: None)()
    return {
        # A family with two kinds of KV layer: each pool's kind, layers,
        # pages, pages a slot, pages in use, bytes, and the window.
        **({"kv_pools": kv_pools} if kv_pools else {}),
        # A family that keeps state beside its pages: the layers of each
        # kind, a slot's bytes and each pool's, by kind.
        **({"state": state_info} if state_info else {}),
        # A family with a router: experts, k, routed layers, and whether
        # this engine hands the routes over. A dense family has no key.
        **({"moe": dict(moe)} if moe else {}),
        # A family that generates by diffusion over blocks: block_length,
        # denoising_steps, confidence_threshold, mask_token_id. A family
        # that generates one token a forward has no key.
        **({"generation": dict(blocks)} if blocks else {}),
        "slots_active": engine.num_active,
        "requests_pending": engine.num_pending,
        "kv_utilization": kvu() if kvu is not None else 0.0,
        # KV dtype / capacity block: quantized replicas advertise their
        # capacity factor here so the autoscaler and capacity planner
        # size against REAL capacity, not the bf16 assumption.
        "kv_cache": kv_info() if kv_info is not None else {},
        # What the engine serves on, as JAX reports it: platform,
        # device_kind, device count and mesh — so nobody has to guess
        # whether a replica found its chips.
        "device": dev_info() if dev_info is not None else {},
        "last_step": dict(getattr(inner, "last_step_stats", {}) or {}),
        "spec_stats": dict(getattr(inner, "spec_stats", {}) or {}),
        "prefix_stats": dict(getattr(inner, "prefix_stats", {}) or {}),
        "kv_share": dict(getattr(inner, "kv_share_stats", {}) or {}),
        # Queue-pressure snapshot: per-class depth/oldest-wait/admitted/
        # shed plus drain rate and the current computed retry hint.
        "scheduler": sched.snapshot() if sched is not None else {},
    }


class EngineServer:
    def __init__(
        self,
        engine: Engine,
        tokenizer: Tokenizer,
        served_model_name: str,
        host: str = "0.0.0.0",
        port: int = 8000,
        adapter_fetcher=None,  # (name, url) -> adapter weight tree
        max_queue: int = 256,
        request_timeout: float = 600.0,
        default_priority: str = "standard",
        max_deadline_ms: int = 0,
        drain_timeout: float = 30.0,
        role: str = "unified",
        max_transfer_mb: int = 0,
        transfer_timeout: float = 30.0,
        watchdog_timeout: float = 0.0,
        watchdog_action=None,
        kv_sharing: bool = False,
        kv_fetch_timeout: float = 5.0,
        kv_spill_store=None,
        cold_start: dict | None = None,
    ):
        self.engine = engine
        self.tokenizer = tokenizer
        self.served_model_name = served_model_name
        self.metrics = EngineMetrics()
        # Always-on flight recorder: scheduler admissions/sheds,
        # preemptions, watchdog/step anomalies land in bounded rings
        # surfaced on /v1/state (the fleet plane bundles its own rings;
        # the engine's travel with its state snapshot).
        self.recorder = flightrecorder.FlightRecorder(ring_size=128)
        engine.on_preempt = self._note_preempt
        # Boot cold-start record (ColdStartTracker.snapshot()): surfaced
        # on /v1/state so the fleet aggregator carries each replica's
        # measured cold-start cost to the planner, and folded into the
        # kubeai_coldstart_* metrics.
        self.cold_start = dict(cold_start or {})
        if self.cold_start:
            self.metrics.record_coldstart(self.cold_start)
        # Disaggregated serving role: "prefill" turns every generate into
        # prefill→handoff (pushed to the decode address the router names);
        # "decode"/"unified" accept handoffs on /v1/kv/import and admit
        # them via X-Disagg-Handoff. "unified" also serves normally — the
        # router's fallback pool.
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        # A family with recurrent state or a window ring beside its pages
        # refuses whatever would move a slot's pages without it
        # (engine.refuse_state_snapshot).
        refuse = getattr(
            getattr(engine, "inner", engine), "refuse_state_snapshot", None
        )
        for what, on in (
            (f"the {role} role of a disaggregated pair", role != "unified"),
            ("kv_sharing", kv_sharing),
            ("a KV spill store", kv_spill_store is not None),
        ):
            if on and refuse is not None:
                refuse(what)
        self.role = role
        self.max_transfer_bytes = max(0, int(max_transfer_mb)) * 1024 * 1024
        self.transfer_timeout = transfer_timeout
        from kubeai_tpu.disagg.transport import HandoffStore

        self._handoffs = HandoffStore()
        # Cluster KV-sharing tier: publish prefix holdings in /v1/state,
        # serve peers' partial-chain fetches on /v1/kv/export, and pull
        # missing prefix pages from the X-KV-Source peer (or the objstore
        # spill store) before admission instead of recomputing prefill.
        self.kv_sharing = bool(kv_sharing)
        self.kv_fetch_timeout = kv_fetch_timeout
        self.kv_spill = kv_spill_store
        if self.kv_spill is not None:
            spill_wire = getattr(engine, "enable_kv_spill", None)
            if spill_wire is None:
                inner = getattr(engine, "inner", None)
                spill_wire = getattr(inner, "enable_kv_spill", None)
            if spill_wire is not None:
                spill_wire(self.kv_spill)
        self.metrics.role_info.set(1, role=role)
        self.adapter_fetcher = adapter_fetcher
        # Scheduling defaults (CRD `scheduling:` block, rendered as engine
        # flags): applied when the request carries no X-Priority /
        # X-Deadline-Ms headers; max_deadline_ms caps client deadlines.
        self.default_priority = default_priority
        self.max_deadline_ms = max_deadline_ms
        # Adapter name -> source path/url it was loaded from. A load for a
        # name whose source CHANGED reloads instead of short-circuiting.
        self._adapter_sources: dict[str, str] = {}
        self.max_queue = max_queue
        self.request_timeout = request_timeout
        self._subscribers: dict[int, _EventQueue] = {}
        self._sub_lock = threading.Lock()
        # The serve loop's and the handlers' host intervals go through the
        # engine's span helper, so they land in the same /v1/profile trace
        # as the step's (an engine stand-in without one gets an inert one).
        from kubeai_tpu.fleet.profiler import StepProfiler

        inner = getattr(engine, "inner", engine)
        prof = getattr(inner, "profiler", None)
        self._span = (prof or StepProfiler()).span
        # The device queue's book (same module), told when the loop idles.
        self._device_queue = getattr(inner, "device_queue", None)
        self._stop = threading.Event()
        self._work = threading.Event()
        # Graceful drain (SIGTERM / POST /v1/drain): refuse new work with
        # 503 + Retry-After, finish in-flight generations up to
        # drain_timeout, then terminate the stragglers cleanly.
        self.drain_timeout = drain_timeout
        self._draining = threading.Event()
        self._drained = threading.Event()
        self._drain_started = 0.0
        self._drain_thread: threading.Thread | None = None
        # Step watchdog: a hung device step (work active, no step
        # progress past watchdog_timeout) flips /health and fires
        # watchdog_action — in production that exits nonzero so kubelet
        # restarts the pod; tests inject a recorder. 0 disables.
        self.watchdog_timeout = watchdog_timeout
        self._watchdog_action = watchdog_action
        self._wedged = False
        self._watchdog_thread: threading.Thread | None = None
        self._loop_thread = threading.Thread(target=self._serve_loop, daemon=True)

        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            _last_status = 200  # recorded for the request span

            def _json(self, status: int, payload: dict, headers: dict | None = None):
                self._last_status = status
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?")[0]
                if path == "/health":
                    if outer.draining:
                        # The LB's health view must eject this replica
                        # while the drain runs.
                        return self._json(
                            503, {"status": "draining", "draining": True}
                        )
                    if outer.healthy():
                        return self._json(200, {"status": "ok"})
                    if outer._wedged:
                        return self._json(
                            503, {"status": "wedged", "wedged": True}
                        )
                    return self._json(503, {"status": "unhealthy"})
                if path == "/v1/drain":
                    # kubelet preStop httpGet can only send GET — the
                    # drain trigger accepts it alongside the POST form.
                    return self._json(202, outer.begin_drain())
                if path == "/metrics":
                    outer.metrics.sync_engine(outer.engine)
                    body = outer.metrics.registry.expose().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path == "/v1/models":
                    data = [
                        {
                            "id": outer.served_model_name,
                            "object": "model",
                            "owned_by": "kubeai-tpu",
                        }
                    ] + [
                        {"id": a, "object": "model", "owned_by": "kubeai-tpu"}
                        for a in outer.engine.loaded_adapters()
                    ]
                    return self._json(200, {"object": "list", "data": data})
                if path == "/v1/state":
                    # Admin snapshot of serving state: what an operator
                    # (or a human) polls to see batching occupancy and
                    # the speculation/prefix-cache effectiveness without
                    # parsing Prometheus text.
                    return self._json(
                        200,
                        outer._with_served_routes({
                            "model": outer.served_model_name,
                            "healthy": outer.healthy(),
                            "draining": outer.draining,
                            "role": outer.role,
                            "pending_handoffs": len(outer._handoffs),
                            "adapters": outer.engine.loaded_adapters(),
                            "kv_sharing": outer.kv_sharing,
                            # Held page-hash chains (hex): the fleet
                            # aggregator joins these into the cluster
                            # who-holds-which-prefix map. Computed only
                            # here (not per step) — it walks the whole
                            # registered-page table.
                            "kv_holdings": outer.kv_holdings(),
                            # Boot cold-start record: restored-or-not,
                            # per-phase timings, snapshot fingerprint.
                            # The aggregator copies this to the planner
                            # as the model's measured cold-start cost.
                            "cold_start": outer.cold_start,
                            # Last-request-per-bucket exemplars: the
                            # "rid-<n>" tags that let an operator jump
                            # from a latency bucket to the request that
                            # last landed in it.
                            "exemplars": {
                                "ttft": outer.metrics.ttft.exemplars(),
                                "itl": outer.metrics.itl.exemplars(),
                            },
                            # Flight-recorder rings: the engine's
                            # discrete decisions (admits, sheds,
                            # preemptions, watchdog) in decision order.
                            "flight_recorder": (
                                outer.recorder.state_payload()
                            ),
                            **engine_state_snapshot(outer.engine),
                        }),
                    )
                return self._json(404, {"error": {"message": "not found"}})

            def do_POST(self):
                path = self.path.split("?")[0]
                if path == "/v1/kv/import":
                    # Binary (possibly chunked) upload: reads its own
                    # body — the JSON decode below must not touch it.
                    return outer._handle_kv_import(self)
                n = int(self.headers.get("Content-Length", 0) or 0)
                raw = self.rfile.read(n) if n else b""
                try:
                    body = json.loads(raw or b"{}")
                except json.JSONDecodeError as e:
                    return self._json(
                        400, {"error": {"message": f"bad JSON: {e}"}}
                    )
                # Continue the trace the operator's proxy started (W3C
                # traceparent), so one trace spans front door → engine.
                # The propagated X-Request-Id lands on the span: one id
                # follows the request front door → proxy attempt → engine.
                attrs = {"http.route": path}
                req_id = self.headers.get("X-Request-Id")
                if req_id:
                    attrs["request.id"] = req_id
                span = tracing.tracer().start_span(
                    f"engine {path}",
                    parent=tracing.parse_traceparent(
                        self.headers.get("traceparent")
                    ),
                    kind=tracing.KIND_SERVER,
                    attributes=attrs,
                )
                self.current_span = span
                self._last_status = 200
                try:
                    try:
                        if path == "/v1/drain":
                            return self._json(202, outer.begin_drain())
                        if path == "/v1/profile":
                            return outer._handle_profile(self, body)
                        if path == "/v1/kv/export":
                            return outer._handle_kv_export(self, body)
                        if path == "/v1/chat/completions":
                            return outer._handle_generate(self, body, chat=True)
                        if path == "/v1/completions":
                            return outer._handle_generate(self, body, chat=False)
                        if path == "/v1/embeddings":
                            return outer._handle_embeddings(self, body)
                        if path == "/v1/load_lora_adapter":
                            return outer._handle_load_adapter(self, body)
                        if path == "/v1/unload_lora_adapter":
                            return outer._handle_unload_adapter(self, body)
                        return self._json(
                            404, {"error": {"message": "not found"}}
                        )
                    except BrokenPipeError as e:
                        span.set_attribute(
                            "http.status_code", self._last_status
                        )
                        span.end(error=str(e) or "client disconnected")
                        raise
                    except Exception as e:
                        logger.exception("handler error")
                        return self._json(
                            500, {"error": {"message": str(e)}}
                        )
                finally:
                    # Handlers signal errors via returned 4xx/5xx JSON,
                    # not exceptions — the span must reflect that, or
                    # every refused request traces as a healthy OK.
                    if not span.end_ns:
                        span.set_attribute(
                            "http.status_code", self._last_status
                        )
                        span.end(
                            error=f"HTTP {self._last_status}"
                            if self._last_status >= 400 else None
                        )

        self.httpd = DeepBacklogHTTPServer((host, port), Handler)
        self._http_thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )

    # -- lifecycle ------------------------------------------------------------

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self) -> None:
        self._loop_thread.start()
        self._http_thread.start()
        if self.watchdog_timeout > 0:
            self._watchdog_thread = threading.Thread(
                target=self._watchdog_loop, daemon=True
            )
            self._watchdog_thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._work.set()
        # Join the serve loop BEFORE anything else broadcasts (multihost
        # shutdown): a step() collective in flight from this thread must
        # finish first or two host-0 collectives interleave undefined.
        if self._loop_thread.is_alive():
            self._loop_thread.join(timeout=30)
        # shutdown() handshakes with serve_forever; on a never-started
        # server it would wait forever.
        if self._http_thread.is_alive():
            self.httpd.shutdown()
        self.httpd.server_close()

    # -- engine loop -----------------------------------------------------------

    def _serve_loop(self) -> None:
        while not self._stop.is_set():
            try:
                if not self.engine.has_work():
                    # An idle engine is not a starved device.
                    if self._device_queue is not None:
                        self._device_queue.idle()
                    self._work.wait(timeout=0.01)
                    self._work.clear()
                    continue
                events = self.engine.step()
                # What follows runs between two steps on the thread that
                # drives the device: every second of it is a second the
                # next dispatch waits. `events` is the tokens the step
                # emitted, on the trace's clock.
                with self._span("serve.fanout", events=len(events)) as fan:
                    # A request's tokens side by side (the sort is stable:
                    # each keeps its order), so its handler wakes to all
                    # of them and not to the first of a chunk.
                    for ev in sorted(events, key=lambda ev: ev.rid):
                        with self._sub_lock:
                            q = self._subscribers.get(ev.rid)
                        if q is not None:
                            q.put(ev)
                # Per-decode-step telemetry: drain the engine's latency
                # records into histograms and refresh the occupancy/KV
                # gauges while they are live (a scrape between steps then
                # sees the batch as it ran, not as it idles).
                with self._span("serve.sync") as sync:
                    self.metrics.sync_engine(self.engine)
                    self.metrics.loop_gap.observe(fan.seconds, part="fanout")
                    self._last_progress = time.monotonic()
                self.metrics.loop_gap.observe(sync.seconds, part="sync")
            except Exception:
                # A dead serving loop must flip /health so the liveness
                # probe restarts the Pod (the blocking LB then stops
                # routing here) — failure detection parity with the
                # reference's probe design (engine_vllm.go liveness).
                logger.exception("serving loop crashed")
                self.recorder.record(
                    flightrecorder.STEP_ANOMALY, "engine",
                    target=self.served_model_name, reason="loop_crash",
                )
                self._loop_dead = True
                return

    _loop_dead = False
    _last_progress = 0.0

    def healthy(self) -> bool:
        return (
            not self._loop_dead
            and not self._wedged
            and not self._stop.is_set()
        )

    def _note_preempt(self, rid: int, client: str) -> None:
        self.recorder.record(
            flightrecorder.SCHED_PREEMPT, "engine_sched",
            target=self.served_model_name, trace_id=f"rid-{rid}",
            client=client or "",
        )

    # -- step watchdog ----------------------------------------------------------

    @property
    def wedged(self) -> bool:
        return self._wedged

    def _watchdog_loop(self) -> None:
        """Detect a hung device step: work is active but the serve loop
        made no step progress for watchdog_timeout. A crashed loop
        already flips /health (_loop_dead); this catches the worse case
        where step() never RETURNS — a wedged XLA dispatch — which no
        exception handler can see. On
        detection /health flips (the LB ejects long before the circuit
        breaker could accumulate response-header timeouts) and
        watchdog_action runs (production: exit nonzero → kubelet
        restarts the pod)."""
        poll = max(0.01, min(self.watchdog_timeout / 4.0, 1.0))
        busy_since: float | None = None
        while not self._stop.wait(timeout=poll):
            try:
                busy = self.engine.has_work()
            except Exception:
                busy = False
            now = time.monotonic()
            if not busy:
                busy_since = None
                continue
            if busy_since is None:
                # Work just (re)appeared: stall time counts from here,
                # not from a _last_progress stamped before an idle gap.
                busy_since = now
            anchor = max(self._last_progress, busy_since)
            # Overlapped stepping: a dispatched-but-unreaped chunk IS
            # progress — the device is computing and the host will reap
            # on the next step — but only within its own reap deadline
            # (the same watchdog budget). An in-flight chunk older than
            # that means the reap itself is wedged (a hung dispatch) and
            # must still trip the restart.
            info_fn = getattr(self.engine, "inflight_info", None)
            if info_fn is not None:
                try:
                    info = info_fn()
                except Exception:
                    info = None
                if info:
                    dispatched_at = float(info.get("dispatched_at", 0.0))
                    if now - dispatched_at <= self.watchdog_timeout:
                        anchor = max(anchor, dispatched_at)
            stalled_for = now - anchor
            if stalled_for <= self.watchdog_timeout:
                continue
            self._wedged = True
            self.metrics.watchdog_wedged.set(1)
            self.metrics.watchdog_stalls.inc()
            self.recorder.record(
                flightrecorder.WATCHDOG, "engine",
                target=self.served_model_name,
                stalled_for_s=round(stalled_for, 3),
                active=self.engine.num_active,
                pending=self.engine.num_pending,
            )
            self.recorder.trigger(
                flightrecorder.TRIGGER_WATCHDOG,
                detail=(
                    f"no step progress for {stalled_for:.1f}s with "
                    f"work active"
                ),
            )
            logger.error(
                "watchdog: no engine step progress for %.1fs with work "
                "active (%d active, %d pending) — flipping /health and "
                "requesting restart",
                stalled_for, self.engine.num_active, self.engine.num_pending,
            )
            if self._watchdog_action is not None:
                try:
                    self._watchdog_action()
                except Exception:
                    logger.exception("watchdog action failed")
            return

    # -- graceful drain ---------------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def begin_drain(self) -> dict:
        """Start the drain sequence (idempotent): stop admitting, let
        in-flight generations finish, terminate stragglers when the
        budget runs out. Returns the status payload /v1/drain answers."""
        if not self._draining.is_set():
            self._drain_started = time.monotonic()
            self._draining.set()
            self.metrics.draining.set(1)
            # Close the admission race at the engine too: a request that
            # slipped past the handler's check still gets refused.
            inner = getattr(self.engine, "inner", self.engine)
            begin = getattr(inner, "begin_drain", None)
            if begin is not None:
                begin()
            self._work.set()
            self._drain_thread = threading.Thread(
                target=self._drain_worker, daemon=True
            )
            self._drain_thread.start()
            logger.info(
                "drain started: %d active, %d pending, budget %.1fs",
                self.engine.num_active, self.engine.num_pending,
                self.drain_timeout,
            )
        return {
            "draining": True,
            "active": self.engine.num_active,
            "pending": self.engine.num_pending,
            "drain_timeout_s": self.drain_timeout,
            "elapsed_s": round(time.monotonic() - self._drain_started, 3),
        }

    def _drain_worker(self) -> None:
        deadline = self._drain_started + self.drain_timeout
        while time.monotonic() < deadline:
            with self._sub_lock:
                streams = len(self._subscribers)
            if (
                streams == 0
                and self.engine.num_active == 0
                and self.engine.num_pending == 0
            ):
                self._drained.set()
                logger.info(
                    "drain complete: all in-flight work finished in %.2fs",
                    time.monotonic() - self._drain_started,
                )
                return
            time.sleep(0.02)
        # Budget exhausted: terminate the remaining streams CLEANLY — a
        # kill sentinel per subscriber makes its collector emit a final
        # chunk and release the slot, instead of the process exit
        # snapping TCP connections mid-token.
        with self._sub_lock:
            leftovers = list(self._subscribers.items())
        for rid, sub in leftovers:
            self.engine.cancel(rid)
            sub.put(
                StepEvent(
                    rid=rid, token=-1, finished=True,
                    finish_reason="cancelled",
                )
            )
        if leftovers:
            self.metrics.drain_terminated.set(len(leftovers))
            logger.warning(
                "drain budget (%.1fs) expired: terminated %d in-flight "
                "request(s)", self.drain_timeout, len(leftovers),
            )
        # Give the collectors a moment to flush their final chunks.
        flush_deadline = time.monotonic() + 2.0
        while time.monotonic() < flush_deadline:
            with self._sub_lock:
                if not self._subscribers:
                    break
            time.sleep(0.02)
        self._drained.set()

    def wait_drained(self, timeout: float | None = None) -> bool:
        """Block until the drain sequence finished (True) or `timeout`
        elapsed (False). The process entrypoint exits on True."""
        return self._drained.wait(
            timeout=self.drain_timeout + 5.0 if timeout is None else timeout
        )

    def _drain_refusal(self, http):
        """503 for work arriving during drain: computed Retry-After (the
        remaining drain budget, jittered through the shared helper — by
        then kubelet has restarted us or the LB moved on) and
        Connection: close so the client's keep-alive doesn't pin a
        dying server."""
        remaining = retryafter.jittered(
            self._drain_started + self.drain_timeout - time.monotonic(),
            min_s=1.0,
        )
        http.close_connection = True
        return http._json(
            503,
            {
                "error": {"message": "server is draining, retry elsewhere"},
                "draining": True,
            },
            headers={
                "Retry-After": retryafter.format_header(remaining),
                "Connection": "close",
            },
        )

    # -- step profiling (kubeai_tpu/fleet/profiler) -----------------------------

    def _handle_profile(self, http, body: dict):
        """POST /v1/profile — capture an N-step per-phase timeline.

        Body (all optional): `steps` (how many step records to return,
        default 16), `fresh` (true = wait for that many NEW steps up to
        `timeout_s` before answering; false = answer from the ring
        immediately), `jax_trace` (additionally wrap the capture window
        in `jax.profiler.trace` when a real device is present — no-op
        safe on CPU, the response carries the trace dir or null)."""
        from kubeai_tpu.fleet.profiler import phase_totals

        inner = getattr(self.engine, "inner", self.engine)
        prof = getattr(inner, "profiler", None)
        if prof is None:
            return http._json(
                400,
                {"error": {"message": "engine exposes no step profiler"}},
            )
        steps = body.get("steps", 16)
        if (
            isinstance(steps, bool)
            or not isinstance(steps, int)
            or not 1 <= steps <= 10_000
        ):
            return http._json(
                400,
                {"error": {"message": "steps must be an int in 1..10000"}},
            )
        timeout_s = body.get("timeout_s", 10.0)
        if (
            isinstance(timeout_s, bool)
            or not isinstance(timeout_s, (int, float))
            or not 0 < timeout_s <= 120
        ):
            return http._json(
                400,
                {"error": {"message": "timeout_s must be in (0, 120]"}},
            )
        fresh = bool(body.get("fresh", False))
        trace_dir = None
        if body.get("jax_trace"):
            # Device-level tracing rides along when the runtime supports
            # it; on CPU (or a runtime without the profiler service) this
            # degrades to the host-side phase timeline alone.
            import tempfile

            try:
                import jax

                trace_dir = tempfile.mkdtemp(prefix="kubeai-profile-")
                jax.profiler.start_trace(trace_dir)
            except Exception:  # noqa: BLE001 — profiling must not 500
                trace_dir = None
        captured = 0
        try:
            if fresh:
                captured = prof.wait_for_steps(steps, float(timeout_s))
        finally:
            if trace_dir is not None:
                try:
                    import jax

                    jax.profiler.stop_trace()
                except Exception:  # noqa: BLE001
                    trace_dir = None
        records = prof.recent(steps)
        return http._json(
            200,
            {
                "object": "engine.profile",
                "model": self.served_model_name,
                "steps_requested": steps,
                "steps_captured": captured if fresh else len(records),
                "steps_completed_total": prof.steps_completed,
                "phase_totals_s": phase_totals(records),
                "steps": records,
                "jax_trace_dir": trace_dir,
            },
        )

    # -- request handling -------------------------------------------------------

    def _resolve_model(self, requested: str) -> tuple[str, str | None] | None:
        """Returns (display_name, adapter_or_None), or None when the name
        matches neither the served model nor a loaded adapter. Engines
        receive the adapter name in the `model` field (the operator's
        apiutils rewrites it — reference: internal/apiutils/request.go:
        190-199); an adapter this replica hasn't loaded must 404 like
        vLLM's admin API does, not silently serve the base model."""
        if requested in self.engine.loaded_adapters():
            return requested, requested
        if not requested or requested == self.served_model_name:
            return self.served_model_name, None
        return None

    def _handle_generate(self, http, body: dict, chat: bool):
        if self._draining.is_set():
            return self._drain_refusal(http)
        # `kubeai_routes: true` asks for the expert sets the program took
        # at every position it computed for this request. Refused, before
        # anything is queued, where this replica cannot hand them over.
        want_routes = body.get("kubeai_routes")
        if want_routes is not None and not isinstance(want_routes, bool):
            return http._json(
                400, {"error": {"message": "kubeai_routes must be a boolean"}}
            )
        # `kubeai_forwards: true` asks a family that generates by blocks
        # for every forward over this request's rows
        # (docs/concepts/block-diffusion.md).
        want_forwards = body.get("kubeai_forwards")
        if want_forwards is not None and not isinstance(want_forwards, bool):
            return http._json(
                400, {"error": {"message": "kubeai_forwards must be a boolean"}}
            )
        hid = (http.headers.get("X-Disagg-Handoff") or "").strip()
        for flag, wanted, refusal in (
            ("kubeai_routes", want_routes, self._routes_refusal),
            ("kubeai_forwards", want_forwards, self._handover_refusal),
        ):
            reason = wanted and (refusal() or (
                "a request admitted from a KV handoff has no prompt rows "
                "to hand over" if hid else ""
            ))
            if reason:
                return http._json(
                    400,
                    {"error": {"message":
                               f"{flag} is not available: {reason}"}},
                )
        if self.role == "prefill":
            # A prefill-role engine NEVER enters decode: every generate
            # becomes prefill → KV handoff pushed to the decode address
            # the router named.
            return self._handle_prefill_generate(http, body, chat)
        if hid:
            return self._handle_decode_from_handoff(http, body, chat, hid)
        model_field = str(body.get("model") or self.served_model_name)
        resolved = self._resolve_model(model_field)
        if resolved is None:
            return http._json(
                404,
                {
                    "error": {
                        "message": f"model {model_field!r} not found "
                        "(not the served model and no such loaded adapter)"
                    }
                },
            )
        display, adapter = resolved
        # n > 1: independent choices as concurrent engine requests. JSON
        # integers only (OpenAI rejects non-integral n; int() would
        # silently truncate 2.9); None means the client omitted it.
        raw_n = body.get("n")
        if raw_n is None:
            n = 1
        elif isinstance(raw_n, bool) or not isinstance(raw_n, int):
            n = 0  # falls through to the 400 below
        else:
            n = raw_n
        if not 1 <= n <= 8:
            return http._json(
                400, {"error": {"message": "n must be an integer in 1..8"}}
            )
        # Continuation request (proxy stream resume after a replica
        # death): `kubeai_resume` carries the tokens another replica
        # already emitted plus how many CHARACTERS of their text reached
        # the client — the stream resumes exactly at that boundary.
        resume_tokens: list[int] = []
        resume_emitted: int | None = None
        raw_resume = body.get("kubeai_resume")
        if raw_resume is not None:
            err = self._validate_resume(raw_resume, n)
            if err is not None:
                return http._json(400, {"error": {"message": err}})
            resume_tokens = [int(t) for t in raw_resume["token_ids"]]
            if "emitted" in raw_resume:
                resume_emitted = int(raw_resume["emitted"])
        # Scheduling identity from headers (the front door and messenger
        # propagate these): priority class, admission deadline, WFQ
        # fairness key. Defaults come from the CRD scheduling block.
        try:
            priority, deadline_ms, sched_client = self._parse_scheduling(
                http.headers, adapter
            )
        except ValueError as e:
            return http._json(400, {"error": {"message": str(e)}})
        # Bounded admission: past this depth requests would only pile onto
        # the scheduler and blow the 600s budget anyway — shed early so
        # the LB retries another replica (reference front-door survives
        # 8000 conc because vLLM sheds; we do our own shedding). All n
        # choices count against the bound. The Retry-After is COMPUTED
        # (queue depth ÷ measured drain rate) and the body carries
        # per-class depths so clients and the LB can back off honestly.
        if self.engine.num_pending + n > self.max_queue:
            return self._shed_response(http, "engine queue full, retry later")

        if chat:
            messages = body.get("messages") or []
            prompt_ids = self.tokenizer.apply_chat_template(messages)
        else:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = prompt[0] if prompt else ""
            prompt_ids = self.tokenizer.encode(str(prompt))
        if not prompt_ids:
            prompt_ids = [0]

        room = self.engine.cfg.max_seq_len - len(prompt_ids) - 1
        if room <= 0:
            return http._json(
                400,
                {
                    "error": {
                        "message": (
                            f"prompt too long: {len(prompt_ids)} tokens "
                            f">= context {self.engine.cfg.max_seq_len}"
                        )
                    }
                },
            )
        if resume_tokens and len(resume_tokens) >= room:
            return http._json(
                400,
                {"error": {"message": (
                    f"resume prefix of {len(resume_tokens)} tokens leaves "
                    f"no room under context {self.engine.cfg.max_seq_len}"
                )}},
            )
        # Sampling-parameter validation: malformed values must 400 with a
        # clear message, never surface as a 500 traceback (and
        # max_tokens: 0 is invalid, not a silent default).
        try:
            sp = self._parse_sampling(body, room)
        except ValueError as e:
            return http._json(400, {"error": {"message": str(e)}})
        if resume_tokens and len(resume_tokens) >= sp.max_tokens:
            return http._json(
                400,
                {"error": {"message": (
                    f"resume prefix of {len(resume_tokens)} tokens >= "
                    f"max_tokens {sp.max_tokens}: nothing left to generate"
                )}},
            )
        if self.kv_sharing and adapter is None and not resume_tokens:
            # Peer/objstore KV prefix fetch BEFORE admission: on success
            # the pages sit unowned in the idle pool and the ordinary
            # prefix-hit admission path below adopts them — on any
            # failure this returns silently and prefill recomputes.
            # Base-model requests only: per-replica LoRA slot seeds make
            # adapter chains incomparable across replicas.
            self._maybe_fetch_prefix(http.headers, prompt_ids, deadline_ms)
        stream = bool(body.get("stream", False))
        # Each choice gets a derived seed so explicit-seed requests stay
        # deterministic AND diverse. With the prefix cache on, choices
        # 2..n hit choice 1's freshly registered prompt pages, so the
        # extra prefills are mostly free.
        import dataclasses as _dc

        reqs: list[tuple[int, _EventQueue, SamplingParams]] = []
        try:
            for i in range(n):
                sub_i = _EventQueue()
                sp_i = (
                    sp if i == 0 or sp.seed is None
                    else _dc.replace(sp, seed=sp.seed + i)
                )

                def register(rid: int, _sub=sub_i) -> None:
                    # Runs under the engine lock, before the request is
                    # visible to step(): no StepEvent can be emitted
                    # unsubscribed.
                    with self._sub_lock:
                        self._subscribers[rid] = _sub

                # kwargs-gated so engine stand-ins (tests) that predate
                # continuation support keep working untouched.
                opt_kw = (
                    {"resume_tokens": resume_tokens}
                    if resume_tokens and i == 0 else {}
                )
                if want_routes:
                    opt_kw["routes"] = True
                if want_forwards:
                    opt_kw["forwards"] = True
                rid_i = self.engine.add_request(
                    prompt_ids, sp_i, adapter=adapter, on_admit=register,
                    priority=priority, client=sched_client,
                    deadline_ms=deadline_ms, **opt_kw,
                )
                reqs.append((rid_i, sub_i, sp_i))
        except DeadlineInfeasible as e:
            # Shed at enqueue: the deadline cannot be met given queue
            # state and the measured drain rate. Cancel any sibling
            # choices that did make it in.
            for rid_i, _, _ in reqs:
                self.engine.cancel(rid_i)
                with self._sub_lock:
                    self._subscribers.pop(rid_i, None)
            self.recorder.record(
                flightrecorder.SCHED_SHED, "engine_sched",
                target=self.served_model_name, priority=priority,
                deadline_ms=deadline_ms, reason=str(e),
            )
            return self._shed_response(
                http, str(e), retry_after=e.retry_after
            )
        except EngineDraining:
            # Drain began between the handler check and admission.
            for rid_i, _, _ in reqs:
                self.engine.cancel(rid_i)
                with self._sub_lock:
                    self._subscribers.pop(rid_i, None)
            return self._drain_refusal(http)
        except KeyError as e:
            # Adapter unloaded between _resolve_model and admission.
            for rid_i, _, _ in reqs:
                self.engine.cancel(rid_i)
                with self._sub_lock:
                    self._subscribers.pop(rid_i, None)
            return http._json(404, {"error": {"message": str(e)}})
        except ValueError as e:
            # Residual continuation validation (e.g. a resume prefix that
            # already ends at a stop token, or a multi-host replica).
            for rid_i, _, _ in reqs:
                self.engine.cancel(rid_i)
                with self._sub_lock:
                    self._subscribers.pop(rid_i, None)
            return http._json(400, {"error": {"message": str(e)}})
        # Metrics only after successful admission, so a failed add_request
        # can't drift the gauge or inflate the counters.
        self.metrics.requests_total.inc(model=display)
        self.metrics.active_requests.inc()
        self.metrics.prompt_tokens.inc(len(prompt_ids) * n)
        self.recorder.record(
            flightrecorder.SCHED_ADMIT, "engine_sched", target=display,
            trace_id=f"rid-{reqs[0][0]}" if reqs else "",
            priority=priority, choices=n,
        )
        self._work.set()
        t0 = time.monotonic()
        span = getattr(http, "current_span", None)
        # None = did not ask; False = asked of a family without a router,
        # which is served and told so; True = its blocks ride along.
        routes = None if not want_routes else self._moe() is not None
        # The same three for a request that asked for its forwards: of a
        # family that generates one token a forward it is served and told so.
        forwards = None if not want_forwards else (
            self._block_generation() is not None
        )
        handover = {
            name: on for name, on in (
                ("kubeai_routes", routes), ("kubeai_forwards", forwards),
            ) if on is not None
        }
        try:
            if stream:
                self._stream_response(http, reqs, display, chat, t0=t0,
                                      span=span,
                                      resume_tokens=resume_tokens,
                                      resume_emitted=resume_emitted,
                                      handover=handover)
            else:
                self._unary_response(http, reqs, display, chat,
                                     len(prompt_ids),
                                     resume_tokens=resume_tokens,
                                     resume_emitted=resume_emitted,
                                     handover=handover)
        finally:
            # The duration the TTFT/e2e histograms see must also be
            # readable off the trace — spans and metrics have to agree.
            if span is not None and not span.end_ns:
                span.set_attribute(
                    "request.duration_s", time.monotonic() - t0
                )
            # Client gone / handler done: release the batch slots if any
            # request is still decoding (no-op after normal completion).
            for rid_i, _, _ in reqs:
                self.engine.cancel(rid_i)
                with self._sub_lock:
                    self._subscribers.pop(rid_i, None)
            self.metrics.active_requests.dec()

    # -- expert routes -----------------------------------------------------------

    def _moe(self) -> dict | None:
        inner = getattr(self.engine, "inner", self.engine)
        return getattr(inner, "moe", None)

    def _block_generation(self) -> dict | None:
        inner = getattr(self.engine, "inner", self.engine)
        return getattr(inner, "block_generation", None)

    def _handover_refusal(self) -> str:
        """Why this replica hands nothing of a forward over to a request,
        whatever the engine ("" = it can)."""
        if self.role != "unified":
            return (
                f"a {self.role}-role replica of a disaggregated pair hands "
                "no expert routes over"
            )
        if getattr(self.engine, "is_lockstep", False):
            return "multi-host replicas hand no expert routes over"
        return ""

    def _routes_refusal(self) -> str:
        """Why a request that asks for its expert routes is refused here
        ("" = it is served: with routes, or without where the family has
        no router)."""
        inner = getattr(self.engine, "inner", self.engine)
        return self._handover_refusal() or getattr(
            inner, "routes_unsupported", ""
        )

    def _with_served_routes(self, state: dict) -> dict:
        """/v1/state's `moe.routes` is what a request would get: false
        too where the engine could but this server refuses."""
        if "moe" in state and self._routes_refusal():
            state["moe"]["routes"] = False
        return state

    # -- scheduling & validation helpers ---------------------------------------

    def _validate_resume(self, raw_resume, n: int) -> str | None:
        """Shape-check a `kubeai_resume` continuation block; returns a
        client-readable error string or None when valid."""
        if getattr(self.engine, "is_lockstep", False):
            return "stream resume is not supported on multi-host replicas"
        if not isinstance(raw_resume, dict):
            return "kubeai_resume must be an object"
        if n != 1:
            return "kubeai_resume requires n == 1"
        toks = raw_resume.get("token_ids")
        if not isinstance(toks, list) or not toks or not all(
            isinstance(t, int) and not isinstance(t, bool) for t in toks
        ):
            return "kubeai_resume.token_ids must be a non-empty int list"
        emitted = raw_resume.get("emitted")
        if emitted is not None and (
            isinstance(emitted, bool)
            or not isinstance(emitted, int)
            or emitted < 0
        ):
            return "kubeai_resume.emitted must be an int >= 0"
        return None

    def _scheduler(self):
        inner = getattr(self.engine, "inner", self.engine)
        return getattr(inner, "scheduler", None)

    def _parse_scheduling(self, headers, adapter):
        """Resolve (priority, deadline_ms, client) from request headers +
        CRD-defaulted server settings. Raises ValueError on malformed
        values (the caller answers 400)."""
        raw_prio = (headers.get("X-Priority") or "").strip().lower()
        if raw_prio and raw_prio not in PRIORITY_CLASSES:
            raise ValueError(
                f"X-Priority must be one of {'/'.join(PRIORITY_CLASSES)}, "
                f"got {raw_prio!r}"
            )
        priority = raw_prio or self.default_priority
        deadline_ms = None
        raw_ddl = (headers.get("X-Deadline-Ms") or "").strip()
        if raw_ddl:
            try:
                deadline_ms = float(raw_ddl)
            except ValueError:
                raise ValueError(
                    f"X-Deadline-Ms must be a number of milliseconds, "
                    f"got {raw_ddl!r}"
                )
            if deadline_ms <= 0:
                raise ValueError("X-Deadline-Ms must be > 0")
        if deadline_ms is None and self.max_deadline_ms > 0:
            # The CRD cap doubles as the default deadline: every request
            # gets feasibility-checked against the operator's bound.
            deadline_ms = float(self.max_deadline_ms)
        elif deadline_ms is not None and self.max_deadline_ms > 0:
            deadline_ms = min(deadline_ms, float(self.max_deadline_ms))
        # WFQ fairness key: explicit client id, else the adapter (tenant
        # workloads commonly map 1:1 to adapters), else one shared key.
        client = (headers.get("X-Client-Id") or "").strip() or (adapter or "")
        return priority, deadline_ms, client

    @staticmethod
    def _parse_sampling(body: dict, room: int) -> SamplingParams:
        """Validate OpenAI sampling fields; raises ValueError with a
        client-readable message on malformed input."""

        def _number(key, default, *, lo=None, hi=None, integer=False):
            raw = body.get(key)
            if raw is None:
                return default
            if isinstance(raw, bool) or not isinstance(raw, (int, float)):
                raise ValueError(f"{key} must be a number, got {raw!r}")
            if integer and not isinstance(raw, int):
                raise ValueError(f"{key} must be an integer, got {raw!r}")
            v = raw
            if lo is not None and v < lo:
                raise ValueError(f"{key} must be >= {lo}, got {v}")
            if hi is not None and v > hi:
                raise ValueError(f"{key} must be <= {hi}, got {v}")
            return v

        max_tokens = body.get("max_tokens")
        if max_tokens is None:
            max_tokens = body.get("max_completion_tokens")
        if max_tokens is None:
            max_tokens = 128
        elif isinstance(max_tokens, bool) or not isinstance(max_tokens, int):
            raise ValueError(
                f"max_tokens must be a positive integer, got {max_tokens!r}"
            )
        elif max_tokens < 1:
            # 0 is a client bug — defaulting it to 128 would silently
            # burn a slot for output the client said it doesn't want.
            raise ValueError(
                f"max_tokens must be >= 1, got {max_tokens}"
            )
        temperature = float(_number("temperature", 1.0, lo=0.0))
        top_p = float(_number("top_p", 1.0, hi=1.0))
        if top_p <= 0.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        top_k = int(_number("top_k", 0, lo=0, integer=True))
        return SamplingParams(
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            max_tokens=min(max_tokens, room),
            seed=body.get("seed"),
            stop=tuple(
                [body["stop"]] if isinstance(body.get("stop"), str)
                else body.get("stop") or []
            ),
        )

    # -- disaggregated serving (kubeai_tpu/disagg) ------------------------------

    def _handle_prefill_generate(self, http, body: dict, chat: bool):
        """Prefill role: tokenize → chunked prefill → export the paged-KV
        handoff → push it to the decode engine the router named
        (X-Disagg-Transfer) → answer a small JSON receipt the router
        turns into the decode hop."""
        from kubeai_tpu.disagg.transport import HTTPTransport, TransferError
        from kubeai_tpu.engine.engine import EngineBusy

        target = (http.headers.get("X-Disagg-Transfer") or "").strip()
        if not target:
            return http._json(
                400,
                {"error": {"message": (
                    "prefill-role engine requires X-Disagg-Transfer: "
                    "<decode host:port> (the router supplies it)"
                )}},
            )
        model_field = str(body.get("model") or self.served_model_name)
        resolved = self._resolve_model(model_field)
        if resolved is None:
            return http._json(
                404,
                {"error": {"message": f"model {model_field!r} not found"}},
            )
        display, adapter = resolved
        raw_n = body.get("n")
        if raw_n not in (None, 1):
            # n > 1 decodes n independent streams from ONE prefill; the
            # two-hop path hands off a single sampler state, so the
            # router routes multi-choice requests to the unified pool.
            return http._json(
                400,
                {"error": {"message":
                           "n > 1 is not supported on the disaggregated "
                           "path; use a unified endpoint"}},
            )
        if chat:
            messages = body.get("messages") or []
            prompt_ids = self.tokenizer.apply_chat_template(messages)
        else:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = prompt[0] if prompt else ""
            prompt_ids = self.tokenizer.encode(str(prompt))
        if not prompt_ids:
            prompt_ids = [0]
        room = self.engine.cfg.max_seq_len - len(prompt_ids) - 1
        if room <= 0:
            return http._json(
                400,
                {"error": {"message": (
                    f"prompt too long: {len(prompt_ids)} tokens >= "
                    f"context {self.engine.cfg.max_seq_len}"
                )}},
            )
        try:
            sp = self._parse_sampling(body, room)
            priority, _deadline, client = self._parse_scheduling(
                http.headers, adapter
            )
        except ValueError as e:
            return http._json(400, {"error": {"message": str(e)}})
        try:
            handoff = self.engine.export_handoff(
                prompt_ids, sp, adapter=adapter, client=client,
                priority=priority, model_name=display,
            )
        except EngineBusy as e:
            return self._shed_response(http, str(e))
        except EngineDraining:
            return self._drain_refusal(http)
        except KeyError as e:
            return http._json(404, {"error": {"message": str(e)}})
        self.metrics.requests_total.inc(model=display)
        self.metrics.prompt_tokens.inc(len(prompt_ids))
        if (
            self.max_transfer_bytes
            and handoff.nbytes() > self.max_transfer_bytes
        ):
            return http._json(
                413,
                {"error": {"message": (
                    f"handoff of {handoff.nbytes()} bytes exceeds the "
                    f"{self.max_transfer_bytes}-byte transfer limit"
                )}},
            )
        hid = (http.headers.get("X-Handoff-Id") or "").strip() or None
        try:
            result = HTTPTransport(
                target, timeout=self.transfer_timeout
            ).send(handoff, handoff_id=hid)
        except TransferError as e:
            logger.warning("handoff push to %s failed: %s", target, e)
            return http._json(502, {"error": {"message": str(e)}})
        self.metrics.kv_transfer_seconds.observe(
            result.seconds, direction="export"
        )
        return http._json(
            200,
            {
                "object": "kv.handoff",
                "handoff_id": result.handoff_id,
                "decode_addr": target,
                "model": display,
                "prompt_tokens": len(prompt_ids),
                "first_token": handoff.first_token,
                "transfer": {
                    "bytes": result.bytes,
                    "seconds": round(result.seconds, 6),
                },
            },
        )

    def _handle_kv_import(self, http):
        """POST /v1/kv/import — receive a serialized handoff (chunked
        upload) into the bounded handoff store; the follow-up generate
        request references it via X-Disagg-Handoff."""
        from kubeai_tpu.disagg.handoff import HandoffError, deserialize
        from kubeai_tpu.disagg.transport import (
            TransferError,
            read_chunked_body,
        )

        if self.role == "prefill":
            return http._json(
                400,
                {"error": {"message":
                           "prefill-role engines do not accept handoffs"}},
            )
        if self._draining.is_set():
            return self._drain_refusal(http)
        t0 = time.monotonic()
        try:
            te = (http.headers.get("Transfer-Encoding") or "").lower()
            if "chunked" in te:
                blob = read_chunked_body(
                    http.rfile, max_bytes=self.max_transfer_bytes
                )
            else:
                n = int(http.headers.get("Content-Length", 0) or 0)
                if self.max_transfer_bytes and n > self.max_transfer_bytes:
                    raise TransferError(
                        f"upload of {n} bytes exceeds the "
                        f"{self.max_transfer_bytes}-byte transfer limit"
                    )
                blob = http.rfile.read(n) if n else b""
        except TransferError as e:
            http.close_connection = True  # unread body bytes may remain
            return http._json(413, {"error": {"message": str(e)}})
        try:
            handoff = deserialize(blob)
        except HandoffError as e:
            return http._json(400, {"error": {"message": str(e)}})
        hid = self._handoffs.put(
            handoff, (http.headers.get("X-Handoff-Id") or "").strip() or None
        )
        seconds = time.monotonic() - t0
        # Bytes are counted at engine import time (disagg_stats via
        # sync_engine) so in-process and HTTP transfers land in the same
        # counter; only the receive latency is observed here.
        self.metrics.kv_transfer_seconds.observe(seconds, direction="import")
        return http._json(
            200, {"handoff_id": hid, "bytes": len(blob)}
        )

    # -- cluster KV-sharing tier -----------------------------------------------

    def kv_holdings(self) -> list[str]:
        """Held page-hash chains (hex) for /v1/state, empty when sharing
        is off (no point shipping the table to the aggregator then)."""
        if not self.kv_sharing:
            return []
        inner = getattr(self.engine, "inner", self.engine)
        holdings = getattr(inner, "prefix_holdings", None)
        return holdings() if holdings is not None else []

    def _handle_kv_export(self, http, body: dict):
        """POST /v1/kv/export — serve a peer's partial-chain prefix fetch:
        JSON {"prefix_hashes": [hex...], "max_bytes": N} in, a KVP1 page
        blob out (possibly empty when nothing of the chain is held). The
        transfer cap is the tighter of the caller's max_bytes and this
        server's own transfer limit."""
        from kubeai_tpu.disagg.handoff import serialize_pages

        if not self.kv_sharing:
            return http._json(
                404, {"error": {"message": "KV sharing is not enabled"}}
            )
        if self._draining.is_set():
            return self._drain_refusal(http)
        hashes = body.get("prefix_hashes")
        if not isinstance(hashes, list) or not all(
            isinstance(h, str) for h in hashes
        ):
            return http._json(
                400,
                {"error": {"message": "prefix_hashes must be a hex list"}},
            )
        max_bytes = body.get("max_bytes", 0)
        if isinstance(max_bytes, bool) or not isinstance(max_bytes, int):
            max_bytes = 0
        cap = max(0, max_bytes)
        if self.max_transfer_bytes:
            cap = (
                min(cap, self.max_transfer_bytes)
                if cap else self.max_transfer_bytes
            )
        inner = getattr(self.engine, "inner", self.engine)
        export_fn = getattr(inner, "export_prefix_pages", None)
        export = export_fn(hashes, cap) if export_fn is not None else None
        if export is None:
            return http._json(
                400,
                {"error": {"message": (
                    "prefix export unavailable (paged prefix cache off "
                    "or malformed chain)"
                )}},
            )
        blob = serialize_pages(export)
        http._last_status = 200
        http.send_response(200)
        http.send_header("Content-Type", "application/octet-stream")
        http.send_header("Content-Length", str(len(blob)))
        http.send_header("X-KV-Pages", str(export.n_pages))
        http.end_headers()
        http.wfile.write(blob)

    def _maybe_fetch_prefix(
        self, headers, prompt_ids: list[int], deadline_ms: int
    ) -> None:
        """Best-effort prefix KV fetch before admission: compute the
        prompt's chain, and when a peer (X-KV-Source, supplied by the
        router only for closed-circuit holders) or the objstore spill
        store holds pages past the local cached depth, pull and seed them
        so admission's ordinary prefix-hit path skips that prefill.
        Unconditional-fallback contract: every failure path returns
        silently and the request recomputes — this method can cost
        latency (bounded by the deadline budget and kv_fetch_timeout)
        but never correctness."""
        import http.client as _hc

        from kubeai_tpu.disagg.handoff import (
            HandoffError,
            deserialize_pages,
        )

        inner = getattr(self.engine, "inner", self.engine)
        compute = getattr(inner, "compute_prefix_chain", None)
        depth_fn = getattr(inner, "cached_prefix_depth", None)
        import_fn = getattr(inner, "import_prefix_pages", None)
        if compute is None or depth_fn is None or import_fn is None:
            return
        t0 = time.monotonic()
        # deadline_ms is None when deadline admission is off entirely.
        budget_s = (
            deadline_ms / 1000.0 if deadline_ms and deadline_ms > 0 else None
        )

        def budget_left() -> float | None:
            if budget_s is None:
                return None
            return budget_s - (time.monotonic() - t0)

        try:
            chain = compute(prompt_ids)
        except Exception:
            return
        # Mirror admission's hit cap: pages past it can never be adopted
        # (the final token must compute its own logits), so fetching them
        # would be pure transfer waste.
        ps = self.engine.cfg.page_size
        chain = chain[: max(0, (len(prompt_ids) - 1) // ps)]
        if not chain:
            return
        depth = depth_fn(chain)
        if depth >= len(chain):
            return  # full local hit; nothing to fetch
        missing = chain[depth:]
        source = (headers.get("X-KV-Source") or "").strip()
        if source:
            left = budget_left()
            if left is not None and left <= 0:
                return
            self.metrics.kv_fetch_attempts.inc(source="peer")
            timeout = self.kv_fetch_timeout
            if left is not None:
                timeout = min(timeout, left)
            conn = None
            try:
                payload = json.dumps(
                    {
                        "prefix_hashes": missing,
                        "max_bytes": self.max_transfer_bytes,
                    }
                ).encode()
                conn = _hc.HTTPConnection(source, timeout=timeout)
                conn.request(
                    "POST", "/v1/kv/export", body=payload,
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                if resp.status != 200:
                    resp.read()
                    raise OSError(f"peer answered {resp.status}")
                blob = resp.read()
                if (
                    self.max_transfer_bytes
                    and len(blob) > self.max_transfer_bytes
                ):
                    raise OSError(
                        f"peer blob of {len(blob)} bytes exceeds the "
                        f"{self.max_transfer_bytes}-byte transfer limit"
                    )
                left = budget_left()
                if left is not None and left <= 0:
                    raise OSError("deadline budget exhausted mid-fetch")
                export = deserialize_pages(blob)
                n = import_fn(export, source="peer")
                if n > 0:
                    self.metrics.kv_fetch_bytes.inc(len(blob))
                    return
            except Exception as e:
                # Broad by contract: a peer dying MID-TRANSFER surfaces
                # as http.client.IncompleteRead (an HTTPException, not an
                # OSError) and a corrupt blob as HandoffError — all of it
                # must degrade to recompute, never fail the request.
                logger.warning("peer KV fetch from %s failed: %s", source, e)
                self.metrics.kv_fetch_failures.inc(source="peer")
            finally:
                if conn is not None:
                    conn.close()
        if self.kv_spill is None:
            return
        # Objstore fill: single-page blobs keyed by chain hash, imported
        # one page at a time so a partial fill still shortens prefill.
        filled = 0
        self.metrics.kv_fetch_attempts.inc(source="spill")
        for h in missing:
            left = budget_left()
            if left is not None and left <= 0:
                break
            try:
                blob = self.kv_spill.get(h)
            except Exception:
                blob = None
            if blob is None:
                break  # chain must stay consecutive; stop at first miss
            try:
                export = deserialize_pages(blob)
                if import_fn(export, source="spill") == 0:
                    break
            except (HandoffError, ValueError):
                break
            filled += 1
            self.metrics.kv_fetch_bytes.inc(len(blob))
        if filled == 0:
            self.metrics.kv_fetch_failures.inc(source="spill")

    def _handle_decode_from_handoff(self, http, body: dict, chat: bool, hid: str):
        """Decode role: admit a previously imported handoff straight into
        a slot (no prefill graph runs) and stream from its first decode
        step. The handoff's first token was sampled by the prefill
        engine — it is emitted here as the stream's first event."""
        from kubeai_tpu.disagg.handoff import HandoffError
        from kubeai_tpu.engine.engine import EngineBusy

        handoff = self._handoffs.pop(hid)
        if handoff is None:
            return http._json(
                404,
                {"error": {"message": f"unknown handoff id {hid!r} "
                           "(expired or already consumed)"}},
            )
        display = handoff.model or self.served_model_name
        sp = SamplingParams(
            temperature=handoff.temperature,
            top_k=handoff.top_k,
            top_p=handoff.top_p,
            max_tokens=handoff.max_tokens,
            seed=handoff.seed,
            stop=tuple(handoff.stop),
        )
        sub = _EventQueue()

        def register(rid: int) -> None:
            with self._sub_lock:
                self._subscribers[rid] = sub

        try:
            rid, first_ev = self.engine.import_handoff(
                handoff, on_admit=register
            )
        except EngineBusy as e:
            return self._shed_response(http, str(e))
        except EngineDraining:
            return self._drain_refusal(http)
        except KeyError as e:
            return http._json(404, {"error": {"message": str(e)}})
        except HandoffError as e:
            return http._json(400, {"error": {"message": str(e)}})
        sub.put(first_ev)
        self.metrics.requests_total.inc(model=display)
        self.metrics.active_requests.inc()
        self.metrics.prompt_tokens.inc(handoff.plen)
        self._work.set()
        stream = bool(body.get("stream", False))
        t0 = time.monotonic()
        span = getattr(http, "current_span", None)
        reqs = [(rid, sub, sp)]
        try:
            if stream:
                self._stream_response(http, reqs, display, chat, t0=t0,
                                      span=span)
            else:
                self._unary_response(http, reqs, display, chat, handoff.plen)
        finally:
            if span is not None and not span.end_ns:
                span.set_attribute(
                    "request.duration_s", time.monotonic() - t0
                )
                span.set_attribute("disagg.handoff_id", hid)
            self.engine.cancel(rid)
            with self._sub_lock:
                self._subscribers.pop(rid, None)
            self.metrics.active_requests.dec()

    def _shed_response(self, http, message: str, retry_after: float | None = None):
        """429 with a COMPUTED Retry-After (queue depth ÷ drain rate, from
        the scheduler — never a constant; jittered ONCE through the
        shared helper so header and body carry the same value) and
        per-class queue depths in the body, so clients and the LB can
        make informed retry decisions."""
        sched = self._scheduler()
        if retry_after is None:
            retry_after = sched.retry_after() if sched is not None else 1.0
        retry_after = retryafter.jittered(retry_after)
        depths = sched.class_depths() if sched is not None else {}
        return http._json(
            429,
            {
                "error": {"message": message},
                "queue": {
                    "depths": depths,
                    "retry_after_s": round(retry_after, 3),
                },
            },
            headers={"Retry-After": retryafter.format_header(retry_after)},
        )

    def _collect(self, rid, sub, sp, on_delta=None, deadline=None,
                 resume_tokens=(), resume_emitted=None, handed=None):
        """Drain tokens; detokenize incrementally; apply stop strings.
        Returns (text, finish_reason, n_completion_tokens).

        request_timeout is a TOTAL budget for the request, not a per-token
        gap — a slow drip must not hold a batch slot indefinitely. With
        n > 1 the caller passes ONE deadline shared by every choice so
        the whole HTTP request stays inside a single budget.

        Continuation: `resume_tokens` seeds the token buffer so stop
        strings and detokenization see the FULL completion, while
        on_delta only fires past `resume_emitted` characters (what the
        dead stream already delivered to the client — defaults to the
        whole resumed text). on_delta receives (delta_text, new_tokens):
        the tokens consumed since its previous call, which streaming
        chunks expose as `token_ids` so the proxy can resume THIS stream
        too if it dies.

        `handed`: for a request that asked for its expert routes or its
        forwards, `{flag: list}`: the lists its events' blocks are
        appended to (`HANDED_OVER` names the event field of each flag);
        `on_delta` takes out what it sends. So that every consumed token
        goes out with its row, `on_delta` is then also called with an
        empty delta when the stream ends with tokens or rows unsent."""
        tokens: list[int] = list(resume_tokens)
        sent_tokens = len(tokens)
        if tokens:
            base_text = self.tokenizer.decode(tokens)
            emitted_len = (
                len(base_text) if resume_emitted is None
                else max(0, min(int(resume_emitted), len(base_text)))
            )
        else:
            emitted_len = 0
        # The text of the stream so far is `head` + the decoded
        # `tokens[head_n:]`: every event needs the whole text (stop
        # strings, the held-back tail), and decoding all of it at every
        # token costs the square of an answer's length.
        head, head_n = "", 0
        finish = "length"
        stopped = None  # the result, once a stop string ended the request
        if deadline is None:
            deadline = time.monotonic() + self.request_timeout

        def owed() -> bool:
            return handed is not None and (
                any(handed.values()) or sent_tokens < len(tokens)
            )

        done = False
        while not done:
            try:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise queue.Empty
                ev = sub.get(timeout=remaining)
            except queue.Empty:
                # Stalled engine or abandoned stream: stop decoding now —
                # otherwise the request keeps a batch slot to max_tokens.
                self.engine.cancel(rid)
                finish = "timeout"
                break
            # One burst: from the queue handing over an event until it is
            # empty again (a decode chunk's tokens arrive together).
            with self._span("http.emit", rid=rid) as emit:
                n_events = 0
                while True:
                    n_events += 1
                    if ev.token < 0:
                        # Drain-kill sentinel: the drain budget expired;
                        # end this stream cleanly with whatever was
                        # generated so far.
                        self.engine.cancel(rid)
                        finish, done = "timeout", True
                        break
                    tokens.append(ev.token)
                    for flag, blocks in (handed or {}).items():
                        blocks.extend(getattr(ev, HANDED_OVER[flag]) or ())
                    self.metrics.generated_tokens.inc()
                    text = head + self.tokenizer.decode(tokens[head_n:])
                    if len(tokens) - head_n >= 2 * DECODE_TAIL_TOKENS:
                        # Move the split up, if the text splits there too.
                        k = len(tokens) - DECODE_TAIL_TOKENS
                        tail = self.tokenizer.decode(tokens[k:])
                        if tail and text.endswith(tail):
                            head, head_n = text[: len(text) - len(tail)], k
                    # Stop strings act on detokenized text (engine core is
                    # token-space only; see sampling.SamplingParams
                    # docstring).
                    stop_hit = None
                    for s in sp.stop:
                        idx = text.find(s, max(0, emitted_len - len(s)))
                        if idx != -1:
                            stop_hit = idx
                            break
                    if stop_hit is not None:
                        if on_delta and (stop_hit > emitted_len or owed()):
                            on_delta(text[emitted_len:stop_hit],
                                     tokens[sent_tokens:])
                            sent_tokens = len(tokens)
                        self.engine.cancel(rid)
                        stopped = (text[:stop_hit], "stop", len(tokens))
                        done = True
                    elif on_delta and len(text) > emitted_len:
                        # Hold back a partial UTF-8 replacement char at
                        # the tail.
                        safe = text[:-1] if text.endswith("�") else text
                        if len(safe) > emitted_len:
                            on_delta(safe[emitted_len:], tokens[sent_tokens:])
                            sent_tokens = len(tokens)
                            emitted_len = len(safe)
                    self.metrics.emit_lag.observe(
                        time.perf_counter() - sub.handed_at
                    )
                    if ev.finished and not done:
                        finish, done = ev.finish_reason or "stop", True
                    if done:
                        break
                    try:
                        ev = sub.get_nowait()
                    except queue.Empty:
                        break
                emit.note(events=n_events)
            self.metrics.emit_busy.observe(emit.seconds)
        if stopped is not None:
            return stopped
        text = self.tokenizer.decode(tokens)
        if on_delta and (len(text) > emitted_len or owed()):
            on_delta(text[emitted_len:], tokens[sent_tokens:])
        return text, finish, len(tokens)

    def _unary_response(self, http, reqs, display, chat, n_prompt,
                        resume_tokens=(), resume_emitted=None, handover=None):
        # Usage counts the tokens actually generated (re-encoding the text
        # diverges around merges/special tokens and from the
        # generated_tokens metric). Choices decode CONCURRENTLY in the
        # engine; draining them in index order is fine — later choices'
        # events buffer in their queues meanwhile.
        choices = []
        total_completion = 0
        any_timeout = False
        deadline = time.monotonic() + self.request_timeout
        for i, (rid, sub, sp_i) in enumerate(reqs):
            # A request that asked for its expert routes (its forwards)
            # gets, in each choice, the tokens served and the blocks as
            # one list (the same blocks a stream's chunks carry, routes
            # joined where they touch); null where the family has no
            # router (does not generate by blocks).
            ids: list[int] = []
            handed = {flag: [] for flag in handover} if handover else None
            text, finish, completion_tokens = self._collect(
                rid, sub, sp_i, deadline=deadline,
                on_delta=(
                    None if handed is None
                    else lambda _text, new_tokens=(): ids.extend(new_tokens)
                ),
                resume_tokens=resume_tokens if i == 0 else (),
                resume_emitted=resume_emitted if i == 0 else None,
                handed=handed,
            )
            if finish == "timeout":
                any_timeout = True
                finish = "length"  # partial result; valid OpenAI value
            total_completion += completion_tokens
            extra = {} if handed is None else {
                "token_ids": [int(t) for t in ids],
                **_encode_handed(handover, handed),
            }
            if chat:
                choices.append(
                    {
                        "index": i,
                        "message": {"role": "assistant", "content": text},
                        "finish_reason": finish,
                        **extra,
                    }
                )
            else:
                choices.append(
                    {"index": i, "text": text, "finish_reason": finish,
                     **extra}
                )
        if any_timeout and total_completion == 0:
            # No choice produced a single token within the budget —
            # stalled OR merely backlogged; either way this replica can't
            # serve it now. 503 (not 500) so the proxy retries a
            # different replica (nothing is on the wire yet in unary).
            # Retry-After from scheduler state (shared helper), not a
            # constant: a backlogged replica's hint should reflect its
            # queue.
            sched = self._scheduler()
            ra = retryafter.jittered(
                sched.retry_after() if sched is not None else 1.0
            )
            return http._json(
                503,
                {"error": {"message": "engine produced no tokens within "
                           f"{self.request_timeout}s"}},
                headers={"Retry-After": retryafter.format_header(ra)},
            )
        usage = {
            "prompt_tokens": n_prompt,
            "completion_tokens": total_completion,
            "total_tokens": n_prompt + total_completion,
        }
        payload = {
            "id": f"cmpl-{uuid.uuid4().hex[:24]}",
            "object": "chat.completion" if chat else "text_completion",
            "created": int(time.time()),
            "model": display,
            "choices": choices,
            "usage": usage,
        }
        http._json(200, payload)

    def _stream_response(self, http, reqs, display, chat, t0=None, span=None,
                         resume_tokens=(), resume_emitted=None, handover=None):
        """SSE stream. With n > 1 the choices stream SEQUENTIALLY in index
        order (each chunk carries its index, which is all the protocol
        requires); later choices decode concurrently and buffer while an
        earlier one streams.

        Every content chunk carries a top-level `token_ids` field — the
        raw tokens behind its delta — which OpenAI clients ignore and
        the routing proxy accumulates so it can resume the stream as a
        continuation request when this replica dies mid-generation. A
        request that asked for its expert routes gets beside it
        `kubeai_routes`: the blocks of the rows computed since its last
        chunk (null where the family has no router); one that asked for
        its forwards `kubeai_forwards`, likewise."""
        http.send_response(200)
        http.send_header("Content-Type", "text/event-stream")
        http.send_header("Cache-Control", "no-cache")
        http.send_header("Transfer-Encoding", "chunked")
        http.end_headers()
        rid_s = f"cmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())

        def send_chunk(obj: dict):
            data = f"data: {json.dumps(obj)}\n\n".encode()
            http.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            http.wfile.flush()

        def send_choice(choice: dict, token_ids=(), handed=None):
            send_chunk(
                {
                    "id": rid_s,
                    "object": (
                        "chat.completion.chunk" if chat else "text_completion"
                    ),
                    "created": created,
                    "model": display,
                    "choices": [choice],
                    **(
                        {"token_ids": [int(t) for t in token_ids]}
                        if token_ids else {}
                    ),
                    **_encode_handed(handover, handed),
                }
            )

        deadline = time.monotonic() + self.request_timeout
        ttft_seen = [False]
        for i, (rid, sub, sp_i) in enumerate(reqs):
            unsent = {flag: [] for flag in handover} if handover else None

            def on_delta(delta_text: str, new_tokens=(), _i=i, _unsent=unsent):
                blocks = None
                if _unsent is not None:
                    blocks = {flag: list(held) for flag, held in _unsent.items()}
                    for held in _unsent.values():
                        held.clear()
                if not ttft_seen[0]:
                    ttft_seen[0] = True
                    if span is not None and t0 is not None:
                        span.set_attribute(
                            "request.ttft_s", time.monotonic() - t0
                        )
                if chat:
                    send_choice(
                        {
                            "index": _i,
                            "delta": {"content": delta_text},
                            "finish_reason": None,
                        },
                        token_ids=new_tokens, handed=blocks,
                    )
                else:
                    send_choice(
                        {"index": _i, "text": delta_text,
                         "finish_reason": None},
                        token_ids=new_tokens, handed=blocks,
                    )

            _text, finish, _n = self._collect(
                rid, sub, sp_i, on_delta=on_delta, deadline=deadline,
                resume_tokens=resume_tokens if i == 0 else (),
                resume_emitted=resume_emitted if i == 0 else None,
                handed=unsent,
            )
            if finish == "timeout":
                # Headers are already on the wire; the best we can do is a
                # valid finish value on the final chunk.
                finish = "length"
            send_choice(
                {"index": i, "delta": {}, "finish_reason": finish}
                if chat
                else {"index": i, "text": "", "finish_reason": finish}
            )
        done = b"data: [DONE]\n\n"
        http.wfile.write(f"{len(done):x}\r\n".encode() + done + b"\r\n")
        http.wfile.write(b"0\r\n\r\n")
        http.wfile.flush()

    # -- embeddings (TextEmbedding feature) -------------------------------------

    def _handle_embeddings(self, http, body: dict):
        if getattr(self.engine, "is_lockstep", False):
            # The embed jit is a separate computation host 0 would enter
            # alone — on a multi-host slice that deadlocks the mesh.
            return http._json(
                400,
                {"error": {"message":
                           "embeddings not supported on multi-host replicas"}},
            )
        fam = self.engine.family
        if getattr(fam, "hidden_states", None) is None:
            return http._json(
                400,
                {"error": {"message": f"model family {fam.name} has no embedding support"}},
            )
        inputs = body.get("input", "")
        if isinstance(inputs, str):
            inputs = [inputs]
        if not inputs or not all(isinstance(i, str) for i in inputs):
            return http._json(
                400, {"error": {"message": "input must be a string or list of strings"}}
            )
        import jax.numpy as jnp
        import numpy as np

        ids = [self.tokenizer.encode(t) or [0] for t in inputs]
        max_len = self.engine.cfg.max_seq_len
        if any(len(i) > max_len for i in ids):
            return http._json(400, {"error": {"message": "input too long"}})
        bucket = self.engine._bucket(max(len(i) for i in ids))
        batch = np.zeros((len(ids), bucket), np.int32)
        for row, i in enumerate(ids):
            batch[row, : len(i)] = i
        lengths = jnp.asarray([len(i) for i in ids], jnp.int32)
        vecs = np.asarray(
            self._embed_jit(self.engine.params, jnp.asarray(batch), lengths)
        )
        total_tokens = int(sum(len(i) for i in ids))
        self.metrics.prompt_tokens.inc(total_tokens)
        return http._json(
            200,
            {
                "object": "list",
                "model": self.served_model_name,
                "data": [
                    {
                        "object": "embedding",
                        "index": i,
                        "embedding": [float(x) for x in vecs[i]],
                    }
                    for i in range(len(ids))
                ],
                "usage": {
                    "prompt_tokens": total_tokens,
                    "total_tokens": total_tokens,
                },
            },
        )

    @property
    def _embed_jit(self):
        if not hasattr(self, "_embed_jit_cached"):
            fam, mcfg = self.engine.family, self.engine.model_cfg
            self._embed_jit_cached = self.engine.jit(
                lambda params, tokens, lengths: fam.hidden_states(
                    params, mcfg, tokens, lengths
                )
            )
        return self._embed_jit_cached

    # -- adapter admin ----------------------------------------------------------

    def _handle_load_adapter(self, http, body: dict):
        name = body.get("lora_name")
        if not name:
            return http._json(400, {"error": {"message": "lora_name required"}})
        path_or_url = body.get("lora_path") or body.get("lora_url") or ""
        if name in self.engine.loaded_adapters():
            # Idempotent only for the SAME source: a changed path/url means
            # the adapter was updated (the operator re-sends on URL-hash
            # change) and must actually reload — short-circuiting here
            # would silently keep serving stale weights forever.
            if self._adapter_sources.get(name) == path_or_url:
                return http._json(
                    200, {"status": "already loaded", "lora_name": name}
                )
            if self.engine.adapter_in_use(name):
                # A reload would be refused after the (possibly large)
                # weight download; answer the 409 before fetching. The
                # engine's own guard re-checks authoritatively.
                return http._json(409, {"error": {"message": (
                    f"adapter {name!r} has in-flight requests; retry "
                    "after they finish"
                )}})
        try:
            if self.adapter_fetcher is not None:
                weights = self.adapter_fetcher(name, path_or_url)
            else:
                from kubeai_tpu.engine.lora_weights import load_peft_adapter

                weights = load_peft_adapter(
                    path_or_url, self.engine.model_cfg,
                    max_rank=self.engine.cfg.max_lora_rank,
                )
            self.engine.load_adapter(name, weights)
        except RuntimeError as e:
            if "in-flight" in str(e):
                # Reload refused while requests decode with the old
                # version; the operator's backoff requeue retries.
                return http._json(409, {"error": {"message": str(e)}})
            logger.exception("adapter load failed")
            return http._json(400, {"error": {"message": str(e)}})
        except Exception as e:
            logger.exception("adapter load failed")
            return http._json(400, {"error": {"message": str(e)}})
        self._adapter_sources[name] = path_or_url
        return http._json(200, {"status": "loaded", "lora_name": name})

    def _handle_unload_adapter(self, http, body: dict):
        name = body.get("lora_name")
        if not name:
            return http._json(400, {"error": {"message": "lora_name required"}})
        try:
            ok = self.engine.unload_adapter(name)
        except RuntimeError as e:
            # In-flight requests still decode with this adapter; the
            # caller (operator adapter reconcile) retries after drain.
            return http._json(409, {"error": {"message": str(e)}})
        if ok:
            self._adapter_sources.pop(name, None)
            return http._json(200, {"status": "unloaded", "lora_name": name})
        return http._json(404, {"error": {"message": f"adapter {name} not found"}})


# ---- process entrypoint ------------------------------------------------------


class _WorkerHealthServer:
    """Minimal /health endpoint for multi-host WORKER processes."""

    def __init__(self, host: str = "0.0.0.0", port: int = 8000):
        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                body = b'{"status": "ok", "role": "worker"}'
                status = 200 if self.path == "/health" else 404
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = DeepBacklogHTTPServer((host, port), Handler)
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        if self._thread.is_alive():
            self.httpd.shutdown()
        self.httpd.server_close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeai-tpu-engine")
    ap.add_argument("--model-url", required=True)
    ap.add_argument("--served-model-name", default="model")
    ap.add_argument("--model-dir", default="", help="pre-downloaded cache dir")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--tpu-topology", default="")
    # Multi-host slices (v5e-4x4 and larger span hosts): every host runs
    # this server process; JAX's distributed runtime wires them into one
    # mesh over DCN for init + ICI for collectives. On GKE these come from
    # the TPU podslice environment (reference parity: the operator treats a
    # replica as one Pod; a multi-host replica is one Pod per host behind
    # the same headless service).
    ap.add_argument("--dcn-coordinator", default=os.environ.get("TPU_COORDINATOR", ""),
                    help="host:port of process 0 (enables jax.distributed)")
    ap.add_argument("--process-id", type=int,
                    default=int(os.environ.get("TPU_PROCESS_ID", "0")))
    ap.add_argument("--num-processes", type=int,
                    default=int(os.environ.get("TPU_PROCESS_COUNT", "1")))
    ap.add_argument("--num-slots", type=int, default=32)
    ap.add_argument("--max-seq-len", type=int, default=4096)
    ap.add_argument("--max-adapters", type=int, default=4)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--quantization", default="", choices=["", "int8"])
    ap.add_argument(
        "--kv-dtype", default="", choices=["", "bfloat16", "int8"],
        help="paged KV-cache storage dtype; int8 stores quantized pages "
        "with per-token-per-head scales (~2x slot capacity at equal "
        "HBM, half the KV bytes on every handoff/fetch/spill) "
        "(CRD kvCache.dtype)",
    )
    ap.add_argument(
        "--speculate", type=int, default=0,
        help="speculative-decoding window (0 = off); prompt-lookup "
        "proposals unless --draft-url provides a draft model",
    )
    ap.add_argument(
        "--spec-adaptive", choices=["on", "off"], default="on",
        help="measure speculative vs chunk decode and run the faster",
    )
    ap.add_argument(
        "--draft-url", default="",
        help="small SAME-FAMILY draft model whose chain proposes the "
        "speculative window (requires --speculate > 0); any model URL "
        "scheme --model-url accepts",
    )
    ap.add_argument(
        "--draft-dir", default="", help="pre-downloaded draft cache dir"
    )
    ap.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="chunked prefill size (0 = whole-prompt bucketed prefill); "
        "one compiled graph for every prompt length",
    )
    ap.add_argument(
        "--default-priority", default="standard",
        choices=list(PRIORITY_CLASSES),
        help="priority class for requests without an X-Priority header "
        "(CRD scheduling.defaultPriority)",
    )
    ap.add_argument(
        "--max-deadline-ms", type=int, default=0,
        help="cap on client X-Deadline-Ms values, and the default "
        "deadline when none is sent; 0 disables deadline admission "
        "(CRD scheduling.maxDeadlineMs)",
    )
    ap.add_argument(
        "--queue-shares", default="",
        help="per-class dispatch shares guaranteeing lower bands a "
        "fraction of admissions under sustained higher-priority load, "
        "e.g. 'standard=0.3,batch=0.05' (CRD scheduling.queueShares)",
    )
    ap.add_argument(
        "--max-queue", type=int, default=256,
        help="pending-queue depth past which requests are shed with 429 "
        "and a computed Retry-After",
    )
    ap.add_argument(
        "--drain-timeout", type=float, default=30.0,
        help="graceful-drain budget in seconds: after SIGTERM or POST "
        "/v1/drain, in-flight generations get this long to finish "
        "before being terminated (CRD spec.drainTimeoutSeconds)",
    )
    ap.add_argument(
        "--watchdog-timeout", type=float, default=120.0,
        help="step-watchdog budget in seconds: with work active and no "
        "engine step progress for this long, /health flips and the "
        "process exits nonzero so Kubernetes restarts the pod "
        "(system config resilience.watchdogTimeout); 0 disables",
    )
    ap.add_argument(
        "--role", default="unified",
        choices=["unified", "prefill", "decode"],
        help="disaggregated serving role: prefill engines run chunked "
        "prefill and push a KV handoff to the decode pool instead of "
        "entering decode; decode engines admit handoffs directly into "
        "slots (POST /v1/kv/import + X-Disagg-Handoff), bypassing the "
        "prefill graphs (CRD spec.disaggregation)",
    )
    ap.add_argument(
        "--max-transfer-mb", type=int, default=0,
        help="cap on one serialized KV handoff (0 = unlimited); uploads "
        "and exports past it answer 413 "
        "(CRD disaggregation.maxTransferMB)",
    )
    ap.add_argument(
        "--transfer-timeout", type=float, default=30.0,
        help="prefill-role push budget toward the decode pool's "
        "/v1/kv/import (CRD disaggregation.transferTimeoutSeconds)",
    )
    ap.add_argument(
        "--prefix-cache", action="store_true",
        help="automatic prefix caching: shared prompt prefixes skip "
        "prefill (pairs with the router's PrefixHash affinity). Implies "
        "a prefill chunk of min(512, max-seq-len/4) when unset — the "
        "adoptable prefix is capped at max-seq-len minus the chunk, so "
        "the chunk must stay well under the context",
    )
    ap.add_argument(
        "--kv-sharing", action="store_true",
        help="cluster-shared prefix/KV tier: publish held page-hash "
        "chains via /v1/state, serve peer page exports on "
        "/v1/kv/export, and pull common-prefix pages from the "
        "X-KV-Source peer before prefill; implies --prefix-cache "
        "(holdings live in the paged prefix cache) "
        "(CRD spec.kvSharing)",
    )
    ap.add_argument(
        "--kv-fetch-timeout", type=float, default=5.0,
        help="budget for one peer KV-page fetch "
        "(CRD kvSharing.fetchTimeoutSeconds)",
    )
    ap.add_argument(
        "--kv-spill-url", default="",
        help="object-store URL evicted idle KV pages spill to and are "
        "re-filled from; empty = in-memory spill "
        "(CRD kvSharing.spillURL)",
    )
    ap.add_argument(
        "--snapshot-url", default="",
        help="object-store URL for engine boot snapshots (post-conversion "
        "param tree + XLA compilation cache, keyed by model/config/mesh "
        "fingerprint): boot restores from it when a matching snapshot "
        "exists and writes one back on the first full-load boot; empty "
        "disables (CRD coldStart.snapshotURL)",
    )
    ap.add_argument(
        "--snapshot-dir", default="",
        help="local staging dir for snapshot fetch/publish (default: a "
        "fresh temp dir). The persistent compilation cache is not kept "
        "here: it lives where JAX_COMPILATION_CACHE_DIR says, else at one "
        "fixed path in the checkout (engine/coldstart.py)",
    )
    ap.add_argument(
        "--snapshot-no-publish", action="store_true",
        help="restore-only consumer: never write a snapshot back after "
        "a full-load boot (CRD coldStart.publish=false)",
    )
    args = ap.parse_args(argv)
    if args.kv_sharing:
        args.prefix_cache = True
    if args.prefix_cache and args.prefill_chunk <= 0:
        args.prefill_chunk = max(32, min(512, args.max_seq_len // 4))

    logging.basicConfig(level=logging.INFO)
    log = logging.getLogger("kubeai-tpu-engine")

    if args.dcn_coordinator and args.num_processes > 1:
        import jax

        jax.distributed.initialize(
            coordinator_address=args.dcn_coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
        log.info(
            "joined distributed runtime: process %d/%d via %s",
            args.process_id, args.num_processes, args.dcn_coordinator,
        )

    from kubeai_tpu.engine.coldstart import (
        ColdStartManager,
        enable_compilation_cache,
    )
    from kubeai_tpu.engine.weights import load_hf_config, resolve_model_dir
    from kubeai_tpu.models.registry import get_model_family
    from kubeai_tpu.parallel.mesh import (
        mesh_from_topology,
        require_accelerator,
        single_device_mesh,
    )

    # Before anything compiles: name the device (no carrying on after JAX
    # fell back to the CPU) and place the compilation cache.
    device = require_accelerator()
    cache_dir = enable_compilation_cache()
    import jax

    log.info(
        "serving on platform=%s device_kind=%s devices=%d; compilation "
        "cache at %s",
        device.platform, device.device_kind, len(jax.devices()), cache_dir,
    )

    model_dir = resolve_model_dir(args.model_url, args.model_dir)
    hf_cfg = load_hf_config(model_dir)
    arch = (hf_cfg.get("architectures") or ["LlamaForCausalLM"])[0]
    family = get_model_family(arch)
    model_cfg = family.config_from_hf(hf_cfg)
    log.info("loading %s (%s) from %s", args.served_model_name, arch, model_dir)

    if family.feature == "SpeechToText":
        from kubeai_tpu.engine.weights import load_params
        from kubeai_tpu.engine.whisper_server import TranscriptionServer

        params = load_params(family.name, model_dir, model_cfg)
        try:
            from transformers import AutoTokenizer

            wtok = AutoTokenizer.from_pretrained(model_dir)
        except Exception:
            wtok = None
        tserver = TranscriptionServer(
            params, model_cfg, args.served_model_name,
            tokenizer=wtok, host=args.host, port=args.port,
        )
        tserver.start()
        log.info("transcription engine serving on %s:%d", args.host, tserver.port)
        try:
            while True:
                time.sleep(5)
        except KeyboardInterrupt:
            tserver.stop()
        return 0

    from kubeai_tpu.engine.weights import load_params as _load_params

    # The mesh comes first now: its shape is part of the snapshot
    # fingerprint (a tree sharded for a different slice must miss).
    mesh = (
        mesh_from_topology(args.tpu_topology)
        if args.tpu_topology
        else single_device_mesh()
    )

    engine_cfg = EngineConfig(
        num_slots=args.num_slots,
        max_seq_len=args.max_seq_len,
        # LoRA is lockstep on multihost: host 0 broadcasts adapter
        # weights to every process (engine/multihost.py).
        max_adapters=args.max_adapters,
        decode_chunk=args.decode_chunk,
        quantization=args.quantization,
        kv_dtype=args.kv_dtype,
        speculate=args.speculate,
        spec_adaptive=args.spec_adaptive == "on",
        prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache,
    )

    # Restore-first boot: a complete snapshot under this (model, config,
    # mesh) fingerprint skips HF conversion — and its bundled compilation
    # cache makes the first jit a cache read. Absence/mismatch falls back
    # to the full load path unchanged.
    coldstart = ColdStartManager(
        args.snapshot_url,
        args.served_model_name,
        engine_cfg,
        mesh,
        work_dir=args.snapshot_dir or None,
        publish=not args.snapshot_no_publish,
    )
    params = coldstart.acquire_params(
        lambda: _load_params(family.name, model_dir, model_cfg)
    )

    draft = None
    if args.draft_url:
        if args.speculate <= 0:
            raise SystemExit("--draft-url requires --speculate > 0")
        draft_dir = resolve_model_dir(args.draft_url, args.draft_dir)
        draft_hf = load_hf_config(draft_dir)
        draft_arch = (draft_hf.get("architectures") or [arch])[0]
        if get_model_family(draft_arch) is not family:
            raise SystemExit(
                f"draft model family ({draft_arch}) must match the "
                f"target's ({arch})"
            )
        draft_cfg = family.config_from_hf(draft_hf)
        draft = (draft_cfg, _load_params(family.name, draft_dir, draft_cfg))
        log.info("loaded draft model (%s) from %s", draft_arch, draft_dir)

    from kubeai_tpu.objstore import KVSpillStore
    from kubeai_tpu.scheduling import RequestScheduler, SchedulingPolicy

    shares: dict[str, float] = {}
    if args.queue_shares:
        for pair in args.queue_shares.split(","):
            pair = pair.strip()
            if not pair:
                continue
            cls, _, share = pair.partition("=")
            shares[cls.strip()] = float(share)
    scheduler = RequestScheduler(
        SchedulingPolicy(
            default_priority=args.default_priority,
            queue_shares=shares,
            max_deadline_ms=args.max_deadline_ms,
        )
    )

    tokenizer = load_tokenizer(model_dir)
    multihost = args.num_processes > 1
    engine = Engine(
        family,
        model_cfg,
        params,
        mesh=mesh,
        cfg=engine_cfg,
        eos_token_ids=tuple(getattr(tokenizer, "eos_token_ids", ())),
        draft=draft,
        scheduler=scheduler,
    )

    if multihost and args.process_id != 0:
        # WORKER host: mirror host 0's ops/steps in lockstep; expose only
        # /health so kubelet probes see the process (never the OpenAI
        # surface — the LB routes to host 0 alone).
        from kubeai_tpu.engine.multihost import worker_loop

        health = _WorkerHealthServer(host=args.host, port=args.port)
        health.start()
        log.info(
            "worker %d/%d: health on %s:%d, entering lockstep loop",
            args.process_id, args.num_processes, args.host, health.port,
        )
        worker_loop(engine)
        health.stop()
        return 0

    if multihost:
        from kubeai_tpu.engine.multihost import LockstepEngine

        engine = LockstepEngine(engine)

    # Warm-up before Ready: compile prefill+decode so the first request
    # doesn't eat compile time (the reference warms Ollama the same way —
    # reference: engine_ollama.go:173-213 probe warm-up). In multihost
    # mode this is the first lockstep broadcast: workers join here.
    # Phase-split for the cold-start record: the first generate carries
    # the jit (or the persistent-cache read on the restore path), the
    # second measures the warmed steady state.
    with coldstart.tracker.phase("compile"):
        engine.generate(
            [[1, 2, 3]], SamplingParams(temperature=0.0, max_tokens=2)
        )
    with coldstart.tracker.phase("warmup"):
        engine.generate(
            [[1, 2, 3]], SamplingParams(temperature=0.0, max_tokens=2)
        )
    # Write-back on first boot: publish AFTER warm-up so the snapshot
    # ships a compilation cache that already holds the serving graphs.
    coldstart.maybe_publish(params)
    coldstart.tracker.finish()
    log.info(
        "warm-up complete (cold start %.2fs, %s)",
        coldstart.tracker.total_s,
        "restored" if coldstart.tracker.restored else "full load",
    )

    def _watchdog_exit():
        # The watchdog already flipped /health; exiting nonzero hands the
        # pod to kubelet's restart policy — a wedged XLA dispatch cannot
        # be recovered in-process.
        log.error(
            "engine watchdog: hung device step — exiting 3 for restart"
        )
        os._exit(3)

    server = EngineServer(
        engine,
        tokenizer,
        args.served_model_name,
        host=args.host,
        port=args.port,
        max_queue=args.max_queue,
        default_priority=args.default_priority,
        max_deadline_ms=args.max_deadline_ms,
        drain_timeout=args.drain_timeout,
        role=args.role,
        max_transfer_mb=args.max_transfer_mb,
        transfer_timeout=args.transfer_timeout,
        watchdog_timeout=args.watchdog_timeout,
        watchdog_action=_watchdog_exit,
        kv_sharing=args.kv_sharing,
        kv_fetch_timeout=args.kv_fetch_timeout,
        kv_spill_store=(
            KVSpillStore(args.kv_spill_url) if args.kv_sharing else None
        ),
        cold_start=coldstart.tracker.snapshot(),
    )
    tracing.configure(service_name=f"kubeai-tpu-engine.{args.served_model_name}")
    server.start()
    log.info("engine serving on %s:%d", args.host, server.port)

    # SIGTERM (pod deletion / rollout) triggers the graceful drain: stop
    # admitting, flip /health so the LB ejects us, finish in-flight work
    # within --drain-timeout, then exit. The renderer sets
    # terminationGracePeriodSeconds above this budget so kubelet's KILL
    # never races the drain.
    import signal

    exit_evt = threading.Event()

    def _drain_and_exit():
        server.begin_drain()
        server.wait_drained()
        exit_evt.set()

    def _on_sigterm(signum, frame):
        log.info("SIGTERM: draining (budget %.1fs)", args.drain_timeout)
        threading.Thread(target=_drain_and_exit, daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (embedded/test use)
    try:
        while not exit_evt.wait(timeout=0.5):
            pass
    except KeyboardInterrupt:
        pass
    server.stop()
    if multihost:
        engine.shutdown()  # release the workers
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
