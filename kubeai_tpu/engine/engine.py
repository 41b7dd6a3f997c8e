"""Continuous-batching inference engine core.

JetStream-style serving loop, in-process:

  add_request() ──► pending queue
                         │ (free slot?)
                 prefill (bucketed S, jitted) ─► scatter KV into pages
                         │
        step(): one batched decode over ALL active slots (jitted, donated
                cache) ─► sample ─► host-side stop checks ─► free slots

TPU-first properties:
  - decode graph compiled ONCE (static [num_slots] batch); prefill compiled
    once per length bucket (powers of two) — bounded recompilation.
  - KV cache buffers are donated through the decode jit: no copy per step.
  - All device work is batched matmuls on the MXU; the host loop only does
    bookkeeping (slot free-lists, stop checks, detokenization upstream).

This engine is what the reference's `engine: VLLM` Pods provide externally
(reference: internal/modelcontroller/engine_vllm.go:12-167); here it is
in-tree and TPU-native. Its admin surface (LoRA load/unload) mirrors
reference: internal/vllmclient/client.go:30-73.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
import types
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from kubeai_tpu.engine.sampling import (
    SamplingParams,
    any_samples,
    sample,
    sample_rows,
)
from kubeai_tpu.models.registry import ModelFamily, get_model_family
from kubeai_tpu.parallel import sharding as psh
from kubeai_tpu.parallel.mesh import single_device_mesh
from kubeai_tpu.scheduling.scheduler import (
    CLASS_RANK,
    CLASS_STANDARD,
    RequestScheduler,
)


def _now() -> float:
    """Monotonic clock behind the engine's latency telemetry (queue-wait,
    prefill, TTFT, ITL, e2e). A module-level hook so fake-clock tests can
    monkeypatch ONE symbol and get deterministic timings."""
    return time.monotonic()


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 8
    max_seq_len: int = 1024
    # The KV cache is a pool of pages of this many tokens, shared by the
    # slots through block tables; decode reads only resident pages.
    page_size: int = 64
    # Page-pool size. 0 = full reservation (num_slots * max_seq_len worth
    # of pages + the reserved scratch page): every slot can reach
    # max_seq_len, no preemption possible. Set smaller to oversubscribe slots —
    # admission defers on pool exhaustion and decode preempts (recompute)
    # the youngest request when it can't grow. Of a family with window
    # layers this is the GLOBAL pool, the one a slot takes pages of by its
    # length and the only one that can run out; the window pool's size
    # follows from num_slots (a fixed ring a slot) and is no setting.
    num_pages: int = 0
    # Batched admission: up to this many same-bucket pending
    # prompts prefill in ONE device call — each dispatch costs a full
    # round trip to the chip, so admission under a request burst is
    # dispatch-bound without batching. Rows pad to the next power of two
    # (bounded compile count).
    max_admit_batch: int = 8
    # Speculative decoding (families with a verify forward):
    # propose this many tokens per step via prompt-lookup (n-gram match
    # against the request's own context — no draft model) and verify all
    # of them in ONE forward. Accepted tokens cost one model pass total,
    # so repetitive/structured text decodes several tokens per step.
    # Acceptance compares against the same seeded sampler the vanilla
    # path uses, so the stream matches vanilla decoding exactly on the
    # reference backend (CPU tests assert it). On TPU, verify runs its
    # own multi-query Pallas kernel mirroring the decode kernel's
    # per-page online-softmax accumulation — near-tie logits may still
    # differ between the two kernels' schedules, but a speculative
    # engine is internally deterministic. Trade-off: speculation replaces the
    # decode_chunk fused scan with one device call per window — on
    # low-acceptance text that is ~1 token per dispatch instead of
    # decode_chunk. 0 = off.
    speculate: int = 0
    # Adaptive fallback (speculate > 0): speculation trades the fused
    # decode_chunk scan for one device call per window, so on
    # low-acceptance text it emits ~1 token per dispatch where chunk mode
    # emits decode_chunk. Rather than guess the dispatch-latency/compute
    # ratio, the engine MEASURES tokens/second of each mode (EMA over
    # decode calls) and runs the faster one, re-probing the losing mode
    # every spec_probe_every decode calls. Streams are identical in both
    # modes (same seeded sampler), so switching is invisible to clients.
    spec_adaptive: bool = True
    spec_probe_every: int = 32
    prefill_buckets: tuple[int, ...] = ()  # default: powers of 2 up to max
    # Chunked prefill: prompts longer than this are prefilled in fixed
    # [1, prefill_chunk] steps — ONE compiled graph for every prompt
    # length and O(chunk * max_seq_len) activation memory (0 = whole-
    # prompt bucketed prefill only). Chunks are staged in a one-slot
    # buffer and scattered into pages on the final chunk. Requires family
    # support.
    prefill_chunk: int = 0
    # Automatic prefix caching (needs prefill_chunk > 0): full
    # prompt pages register under a content-hash chain (adapter-aware)
    # when a request completes admission; a later prompt with the same
    # page-aligned prefix ADOPTS those pages read-only and prefills only
    # its suffix — shared system prompts and multi-turn histories skip
    # most prefill compute. Zero-reference pages park in an LRU idle
    # pool and are evicted only when the free list runs dry, so caching
    # never reduces servable capacity. This is the per-replica half of
    # the reference's prefix-caching story (its cross-replica half, the
    # CHWBL router, ships in routing/chwbl.py; reference headline:
    # docs/benchmarks/prefix-aware-load-balancing.md).
    prefix_cache: bool = False
    cache_dtype: Any = jnp.bfloat16
    # KV-cache quantization: "" / "bfloat16" stores pages in
    # cache_dtype; "int8" stores pages as int8 with per-token-per-head f32
    # scales riding alongside ({"q8", "scale"} pool leaves — see
    # ops/kv_quant.py), roughly doubling slot capacity at equal HBM
    # (2D/(D+4), 1.94x at D=128) and halving every KV byte shipped by
    # disagg handoff, peer prefix fetch and objstore spill. Quantized
    # pools always use the reference attention path (the Pallas decode
    # kernels are bf16-only) inside the scatter-then-attend layout, and
    # do not compose with speculation or pipeline parallelism yet.
    kv_dtype: str = ""
    # Decode steps fused into one device call (lax.scan). Amortizes host
    # dispatch. Tokens a request emits past its stop point within a chunk
    # are discarded host-side; slot rows are independent, so batch-mates
    # are unaffected.
    decode_chunk: int = 8
    # Weight-only quantization: "" (bf16) or "int8" (per-channel symmetric;
    # halves HBM weight traffic on the memory-bound decode path).
    quantization: str = ""
    # LoRA hot-swap: number of simultaneously loaded adapters (0 disables
    # the LoRA path entirely — no extra compute in the compiled graphs).
    max_adapters: int = 0
    max_lora_rank: int = 16
    # Pipeline parallelism (mesh pp axis > 1): decode microbatch count for
    # the GPipe schedule. 0 = the pp stage count (steady-state utilization
    # M/(M+P-1); raise toward num_slots for higher utilization at smaller
    # per-tick batches). Requires a family with decode_step_paged_pp
    # and num_slots % M == 0; composes with dp, tp, sp
    # (ring-attention prefill), int8 quantization, and prompt-lookup
    # speculation.
    pp_microbatches: int = 0

    def buckets(self) -> tuple[int, ...]:
        if self.prefill_buckets:
            return self.prefill_buckets
        b, out = 16, []
        while b < self.max_seq_len:
            out.append(b)
            b *= 2
        out.append(self.max_seq_len)
        return tuple(out)

    def effective_num_pages(self) -> int:
        """Pages of the pool that slots take pages of by their length (the
        global pool of a family that also keeps a window pool), the
        reserved scratch page included."""
        if self.num_pages > 0:
            return self.num_pages
        per_slot = -(-self.max_seq_len // self.page_size)
        return 1 + self.num_slots * per_slot  # +1: reserved scratch page 0


class StepEvent(NamedTuple):
    """One emitted token. `finish_reason` is "" while the request is live,
    else "stop" | "length" | "cancelled" (OpenAI finish_reason semantics).

    `routes` is None unless the request asked for its expert routes
    (`add_request(routes=True)` on an engine whose `moe["routes"]` is
    true): then a tuple of blocks `(start, rows)`, `rows` a
    `[n, routed layers, k]` array of global expert ids, row j the sets
    taken when the program computed position `start + j` of prompt plus
    served tokens. The first token's event carries the prompt's rows
    (from the first computed position, if a prefix was reused); the
    event of token i >= 2 carries row P + i - 2, the position whose
    forward produced it; rows recomputed after a preemption or for a
    resume prefix ride in front of the next token's row, under their
    positions (docs/concepts/expert-routes.md).

    `forwards` is None unless the request asked for its forwards
    (`add_request(forwards=True)`) of a family that generates by blocks:
    then a tuple of `(start, rows, commit, tokens)`, one a forward of the
    model over this request's rows, in the order they ran: `rows` the
    `[n, routed layers, k]` expert sets of positions `start ..`, `commit`
    the offsets of the rows that forward committed and `tokens` what it
    committed them to (both empty for the prompt's forward and for the one
    that writes a finished block's K and V). They ride on the first token
    served of the block they filled (docs/concepts/block-diffusion.md)."""

    rid: int
    token: int
    finished: bool
    finish_reason: str = ""
    routes: tuple | None = None
    forwards: tuple | None = None


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: list[int]
    params: SamplingParams
    seed: int
    adapter_idx: int = 0  # 0 = no adapter
    # Scheduling identity: the priority class the scheduler resolved for
    # this request (preemption prefers evicting the lowest class) and the
    # fairness key it was queued under.
    priority: str = CLASS_STANDARD
    client: str = ""
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    position: int = 0  # absolute position of the next token to decode
    last_token: int = 0
    done: bool = False
    finish_reason: str = ""  # "stop" | "length" (OpenAI semantics)
    stop_token_ids: tuple[int, ...] = ()
    # Incremental context buffer + n-gram last-occurrence index for
    # speculative prompt-lookup (built on first use; appended per emitted
    # token — proposal lookup is O(γ) per step, never an O(L) rescan).
    ctx: Any = None
    ctx_len: int = 0
    ngram_idx: Any = None  # {n: {ngram tuple -> last start index}}
    ngram_upto: Any = None  # {n: window starts indexed so far}
    # Lifecycle timestamps (_now() clock) for the latency telemetry the
    # serve loop drains into histograms. t_enqueue doubles as the "e2e not
    # yet recorded" flag (zeroed after recording); t_admit_start survives
    # preemption so a resumed request keeps its ORIGINAL queue-wait.
    t_enqueue: float = 0.0
    t_admit_start: float = 0.0
    t_prev_token: float = 0.0
    # None unless the request asked for its expert routes: then the
    # blocks computed while it emitted nothing (a resumed admission's
    # recompute), which ride on its next event.
    route_backlog: list | None = None
    # None unless the request asked for its forwards (a family that
    # generates by blocks): the forwards that served no token yet (the
    # prompt's), which ride on its next event.
    forward_backlog: list | None = None


@dataclasses.dataclass
class _Admission:
    """One admission call whose output `head` (first tokens, a routed
    family's expert sets) the host has not read yet. `batch` is the
    call's entries of `_plan_admission`, `launched` what the device
    queue's book holds for the call, `host_s` the seconds of its first
    `admit.host`; `seated`: the admitted already ride a chunk
    (`Engine._seat`)."""

    batch: list
    head: Any
    launched: Any
    cached_len: int
    host_s: float
    seated: bool = False


def _live_temp(state, bt):
    """The slots' temperatures [B] as a decode chunk's sampler reads them:
    a slot that holds no page (free, or just freed) reads as greedy. Its
    row of `state["temp"]` keeps what the request that left had asked for
    until an admission overwrites it; its tokens go nowhere, and it must
    not keep the sampler's candidate pool running for live rows that are
    all greedy."""
    return jnp.where(bt[:, 0] >= 0, state["temp"], 0.0)


def _call(fn, args):
    """`fn(*args)`. As `_from_its_own_chunk` (below) it is entered from a
    frame so large that the interpreter has to start a new chunk of its
    frame stack for it, with room behind it for everything `fn` calls.

    CPython (3.11 on) keeps a thread's Python frames in chunks of 16 KiB
    and gives a chunk back to the system the moment the frame that opened
    it returns. A loop whose callees' frames happen to straddle the end
    of a chunk therefore maps, faults in and unmaps a chunk on every
    call. JAX's tracing and lowering make some hundred thousand Python
    calls at every depth down to a few thousand words, so the time of a
    jitted function's FIRST call swung with the depth it was called at:
    with the number of local variables in `Engine.step`, with who called
    `step`. On the TPU host, where a page fault is dear, that was 22 to
    35 s for one warm-up of 28 prefill shapes, by three words of stack
    (PERF.md section 6, PR 42). From the large frame the depth is always
    the same, and 64 KiB lie in one piece below it: nothing under a
    jitted call crosses a chunk's end. The price is one mapping of
    address space a call, microseconds beside a device program."""
    return fn(*args)


# Over 64 KiB of frame: the interpreter sizes the new chunk at the next
# power of two, 128 KiB, so as many words again stay free behind the frame.
_OWN_CHUNK_WORDS = 8200
_from_its_own_chunk = types.FunctionType(
    _call.__code__.replace(co_stacksize=_OWN_CHUNK_WORDS),
    globals(), "_from_its_own_chunk",
)
_from_its_own_chunk.__doc__ = _call.__doc__


class EngineDraining(RuntimeError):
    """Raised by add_request once drain has begun: the server answers
    503 + Retry-After so the LB moves the request to another replica."""


class EngineBusy(RuntimeError):
    """Raised by the synchronous disaggregation paths (export_handoff /
    import_handoff) when no slot or KV pages are free RIGHT NOW: unlike
    add_request there is no queue to park in, so the server sheds with
    429 and the router re-picks a less-loaded replica."""


class Engine:
    """Single-model, single-mesh continuous-batching engine."""

    def __init__(
        self,
        family: ModelFamily | str,
        model_cfg: Any,
        params: Any,
        mesh: Mesh | None = None,
        cfg: EngineConfig = EngineConfig(),
        rules: psh.ShardingRules = psh.DEFAULT_RULES,
        eos_token_ids: tuple[int, ...] = (),
        draft: tuple[Any, Any] | None = None,
        scheduler: RequestScheduler | None = None,
    ):
        """`draft`: optional (draft_cfg, draft_params) — a small same-family
        model that PROPOSES the speculative window (cfg.speculate > 0)
        instead of prompt-lookup. Prompt-lookup's acceptance collapses on
        non-repetitive text; a draft model proposes from actual model
        probabilities, so acceptance tracks draft/target agreement. The
        draft keeps its own slot KV cache: each window feeds it the true
        last emitted token at its true position, so accepted proposals'
        KV (written during proposal) is correct and rejected positions
        are masked (length = position+1) until overwritten. Verify
        guarantees the emitted stream is exact regardless of proposal
        quality."""
        self.family = (
            get_model_family(family) if isinstance(family, str) else family
        )
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else single_device_mesh()
        self.rules = rules
        self.eos_token_ids = eos_token_ids
        self._lock = threading.Lock()
        self._next_rid = 0
        # Graceful drain: once set, add_request refuses (EngineDraining)
        # while in-flight generations run to completion — the server's
        # drain sequence flips this before it stops the HTTP front so
        # the admission race window is closed at the source.
        self._draining = False
        # SLO-aware pending queue: priority bands with strict precedence,
        # WFQ within a band keyed by client, deadline-aware admission
        # (kubeai_tpu/scheduling). Replaces the former FIFO deque.
        self._sched = scheduler if scheduler is not None else RequestScheduler()
        self._active: dict[int, _Request] = {}  # slot -> request
        self._requests: dict[int, _Request] = {}
        self._free_slots = list(range(cfg.num_slots))
        # In-flight decode chunk (overlapped stepping): (token futures,
        # snapshot of the slot->request map the chunk was dispatched
        # with, chunk length in model steps, monotonic dispatch time,
        # a routed family's expert-set futures or None).
        # The dispatch timestamp feeds the server watchdog: a dispatched
        # chunk counts as progress until its own reap deadline ages out.
        self._inflight: tuple | None = None
        # Base entropy for unseeded requests (per-request seed = base ^ rid).
        self._seed_base = int.from_bytes(np.random.bytes(4), "little")
        self._steps = 0
        # Adaptive speculation: measured tokens/s EMA per decode mode
        # ("spec" | "chunk"); None until a mode's SECOND call (the first
        # includes compile and would poison the estimate).
        self._mode_tps: dict[str, float | None] = {}
        self._mode_calls: dict[str, int] = {}
        self._decode_calls = 0
        # Speculation acceptance: proposed/accepted counts over live
        # slots (windows = spec steps × live slots). Reading it after a
        # run answers "did the proposer earn its keep" — the draft's
        # whole point vs prompt-lookup on non-repetitive text.
        self.spec_stats = {"windows": 0, "proposed": 0, "accepted": 0}
        # Request-lifecycle latency observations, (kind, seconds) with
        # kind ∈ {queue_wait, prefill, ttft, itl, e2e}. The serve loop
        # drains these into histograms (drain_timing) — the engine core
        # never touches a metrics registry, so the hot loop stays free of
        # registry locks.
        self._timing: list[tuple[str, float]] = []
        # Snapshot of the most recent step() for per-decode-step gauges:
        # running batch size, waiting-queue depth, tokens emitted, wall
        # duration.
        self.last_step_stats: dict[str, float] = {}
        # Per-phase step profiler (kubeai_tpu/fleet/profiler): every host
        # interval of step() is a `profiler.span`, which adds `step.<phase>`
        # durations to the open step and puts the interval on a device
        # trace's clock through the TraceAnnotation handed over here. The
        # serve loop drains the phases into a histogram and /v1/profile
        # reads the ring: plain floats, no registry in the hot path.
        from kubeai_tpu.fleet.profiler import DeviceQueueBook, StepProfiler

        self.profiler = StepProfiler(annotate=jax.profiler.TraceAnnotation)
        # The device queue's book (same module): seconds the device had
        # nothing queued before a dispatch, always on. Fed where the work
        # happens: the dispatches in `step.decode` and `_admit_pending`,
        # the ends of `step.overlap_idle` and `admit.wait` (`_collect_head`),
        # and the serve loop's idle branch. EngineMetrics folds it into
        # kubeai_engine_device_starved_seconds / _dispatches_total.
        self.device_queue = DeviceQueueBook()
        # Admission device calls made by step(), and the prompt tokens
        # they computed against the tokens of the shapes they ran
        # (padded - useful = padding); EngineMetrics folds the deltas in.
        self.admit_stats = {"calls": 0, "useful_tokens": 0, "padded_tokens": 0}
        # Admission calls dispatched behind the chunk in flight by the
        # step under way, whose first tokens are still on the device, in
        # dispatch order: empty between steps.
        self._heads: list[_Admission] = []
        # Reaped chunks by what forced the reap (the `step.reap` span's
        # `barrier`): "none" ran behind the next dispatched chunk.
        self.step_reaps = dict.fromkeys(
            ("none", "admission", "seq_cap", "spec", "external"), 0
        )
        # Reaped decode chunks by what their sampler ran ON THE DEVICE (the
        # chunk hands the conditional's predicate back beside its tokens):
        # "argmax" = every live row greedy, the candidate pool not entered.
        self.sampler_chunks = {"argmax": 0, "pool": 0}
        # The KV a decode chunk reads, from the walk that grows the active
        # slots' pages: slots and pages (ceil(tokens / page) each) at the
        # newest dispatch, and the pages summed over every dispatched chunk.
        self.live_kv = {"slots": 0, "pages": 0, "pages_total": 0}
        # Of a family with window layers, the ring pages a window layer's
        # attention reads (at most a ring a slot), the same way.
        self.live_window = {"pages": 0, "pages_total": 0}
        # Of a family with a latent pool, the blocks of several pages its
        # decode kernel attends those pages in, the same way.
        self.live_blocks = {"blocks": 0, "blocks_total": 0}

        self._spec = 0  # resolved speculation window (see below)
        if (
            getattr(self.family, "decode_step_paged", None) is None
            and self._block is None
        ):
            raise ValueError(
                f"family {self.family.name} has no decode_step_paged: the "
                "engine's only KV cache is the page pool"
            )

        # KV quantization: validated here, materialized with the pool
        # below ({"q8", "scale"} pool leaves; ops/kv_quant.py).
        from kubeai_tpu.ops.kv_quant import resolve_kv_dtype
        from kubeai_tpu.ops.paged_attention import decode_layout

        self.kv_dtype = resolve_kv_dtype(cfg.kv_dtype)
        self._kv_quant = self.kv_dtype == "int8"
        # Paged decode layout: the pool's kind decides, nothing else.
        self.decode_kernel = decode_layout(quantized=self._kv_quant)
        if self._block is not None or self._kept_apart is not None:
            self._check_family_engine(draft)
        if self._kv_quant and (cfg.speculate > 0 or draft is not None):
            raise ValueError(
                "kv_dtype='int8' does not compose with speculative "
                "decoding yet (the verify kernels read bf16 pools)"
            )

        # Pipeline parallelism: stage-local layers + KV over the pp mesh
        # axis (GPipe microbatched decode; see models/llama.py
        # decode_step_paged_pp). Composes with dp AND tp — the pp
        # shard_map is manual over pp only (axis_names), so Megatron tp
        # sharding stays GSPMD-managed inside each stage (the 70B/v5e-8
        # plan is pp=2 × tp=4). Composes with sp too (ring-attention
        # prefill; see below). Scope: llama-family.
        self._pp = self.mesh.shape.get("pp", 1)
        self._pp_microbatches = 0
        if self._pp > 1:
            if self._kv_quant:
                raise ValueError(
                    "kv_dtype='int8' does not compose with pipeline "
                    "parallelism yet (the pp shard_map moves raw bf16 "
                    "pools)"
                )
            if getattr(self.family, "decode_step_paged_pp", None) is None:
                raise ValueError(
                    f"family {self.family.name} does not support pipeline "
                    "parallelism (no decode_step_paged_pp)"
                )
            # sp composes: prefill runs ring attention over the sp axis
            # (resolve_prefill binds the mesh) while the pp decode
            # shard_map simply replicates its per-tick microbatch inputs
            # over sp — decode is single-token, so the sequence axis has
            # nothing to shard there.
            if model_cfg.num_layers % self._pp:
                raise ValueError(
                    f"{model_cfg.num_layers} layers not divisible by "
                    f"pp={self._pp} stages"
                )
            m = cfg.pp_microbatches or self._pp
            if cfg.num_slots % m:
                raise ValueError(
                    f"num_slots={cfg.num_slots} not divisible by "
                    f"pp_microbatches={m}"
                )
            self._pp_microbatches = m

        # The layout the compiled decode chunk has: "stacked" = the
        # [NL, ...] pool read and written in place, "per_layer" = a
        # layer's cache sliced out of the stack and written back (int8
        # pools, pp stages). Fixed at compile time, so the step.decode
        # span and /v1/state just name it.
        self.kv_layout = (
            "stacked"
            if self._pp == 1 and self.decode_kernel == "fused"
            else "per_layer"
        )

        # Overlapped stepping: step() dispatches decode chunk N+1 BEFORE
        # reaping chunk N's tokens, so readback, scheduler admission,
        # detokenize and SSE fan-out for chunk N run concurrently with
        # chunk N+1's device compute. Conservative barriers (cancel /
        # release, drain, handoff export/import, prefix-page export/import,
        # and any speculation window) force a reap before state mutates, so
        # greedy AND seeded streams are token-identical to the synchronous
        # loop. pp > 1 already fills the device with microbatch ticks
        # inside ONE call and a second in-flight donated-buffer program
        # would race the stage handoffs, so it keeps the synchronous loop.
        # (Lockstep multihost clears the flag one level up, LockstepEngine,
        # because the engine cannot see its wrapper.)
        self._overlap = self._pp == 1
        # Events reaped OUTSIDE step() (barrier reaps in cancel/drain/
        # handoff/prefix paths): queued here, prepended to the next
        # step()'s return so no token is ever dropped.
        self._pending_events: list[StepEvent] = []

        # Quantize (optional, on the host), then shard params onto the
        # mesh: host arrays go to their shards directly, so no whole tensor
        # lands on one device first.
        specs = self.family.param_specs(model_cfg)
        if cfg.quantization == "int8":
            from kubeai_tpu.engine.quantization import (
                quantize_params,
                quantized_specs,
            )

            params = quantize_params(params)
            specs = quantized_specs(specs, params["layers"])
        elif cfg.quantization:
            raise ValueError(f"unknown quantization {cfg.quantization!r}")
        self.params = psh.shard_params(params, specs, self.mesh, rules)

        # GQA: when tp exceeds the KV-head count the cache can't shard on
        # heads — replicate it across tp (each shard attends with its local
        # q heads against the full KV; standard GQA-on-TPU fallback).
        cache_rules = psh.kv_cache_rules(
            self.mesh, model_cfg.num_kv_heads, rules
        )

        self.prefix_stats = {"lookups": 0, "hit_tokens": 0, "prompt_tokens": 0}
        # Disaggregation accounting (cumulative; the server converts
        # these to counters): handoffs exported after prefill, handoffs
        # imported into decode slots, KV bytes in each direction.
        self.disagg_stats = {
            "exported": 0,
            "imported": 0,
            "exported_bytes": 0,
            "imported_bytes": 0,
        }
        # Cluster KV-sharing accounting (cumulative, server folds into
        # counters): partial-chain pages served to peers / seeded from
        # peers, and objstore spill/fill traffic.
        self.kv_share_stats = {
            "exported_pages": 0,
            "exported_bytes": 0,
            "imported_pages": 0,
            "imported_bytes": 0,
            "spilled_pages": 0,
            "filled_pages": 0,
        }
        from kubeai_tpu.engine.paged_cache import PageAllocator, PagedKVCache

        n_pages = cfg.effective_num_pages()
        self._n_pages = n_pages
        max_pages = -(-cfg.max_seq_len // cfg.page_size)
        # Pages replicated across dp (page ids are global); KV heads on
        # tp; the layer axis shards over pp, so each pipeline stage holds
        # only its own layers' pages.
        pool_sharding = psh.named_sharding(
            self.mesh,
            (psh.LAYERS, None, None, psh.KV_HEADS, None),
            cache_rules,
        )
        if self._latent:
            # One pool of rows without heads: whole on every device (a tp
            # axis is refused), as the state pools beside it.
            pool_sharding = self._state_sharding
        if self._kv_quant:
            # Dict pool leaves: int8 pages shard like bf16 pages; the
            # [NL, pages, page, KVH] scale leaf drops the head_dim
            # axis. device_put and jit out_shardings both take the
            # pytree form.
            pool_sharding = {
                "q8": pool_sharding,
                "scale": psh.named_sharding(
                    self.mesh,
                    (psh.LAYERS, None, None, psh.KV_HEADS),
                    cache_rules,
                ),
            }
        if n_pages - 1 < max_pages:
            raise ValueError(
                f"num_pages={n_pages} cannot hold one max_seq_len "
                f"sequence ({max_pages} pages + scratch); preemption "
                "could not guarantee progress"
            )
        self._bt_sharding = psh.named_sharding(
            self.mesh, (None, None), cache_rules
        )
        # Born sharded: each device allocates only its own part of
        # the pool.
        self.cache = PagedKVCache.create(
            self._page_layers,
            n_pages,
            cfg.page_size,
            cfg.num_slots,
            cfg.max_seq_len,
            model_cfg.num_kv_heads,
            model_cfg.head_size,
            dtype="int8" if self._kv_quant else cfg.cache_dtype,
            pool_sharding=pool_sharding,
            table_sharding=self._bt_sharding,
            state=self._recurrent,
            state_sharding=self._state_sharding,
            window=self._window,
            latent=self._latent,
        )
        self._alloc = PageAllocator(
            n_pages, cfg.page_size, max_pages_per_slot=max_pages
        )
        self._prefix_cache = bool(cfg.prefix_cache)
        if self._prefix_cache:
            if cfg.prefill_chunk <= 0:
                raise ValueError(
                    "prefix_cache needs prefill_chunk > 0 (cache hits "
                    "prefill only the uncached suffix, which runs "
                    "through the staged-chunk path)"
                )
            if self._pp > 1:
                raise ValueError(
                    "prefix_cache does not compose with pipeline "
                    "parallelism yet"
                )
            if (cfg.max_seq_len - cfg.prefill_chunk) // cfg.page_size < 1:
                # The adoptable prefix is capped at max_seq_len -
                # prefill_chunk (the padded suffix chunk must fit the
                # staging buffer); at or past the cap the cache can
                # NEVER hit and every admission pays pure hashing
                # overhead.
                import logging

                logging.getLogger(__name__).warning(
                    "prefix_cache is inert: prefill_chunk=%d leaves "
                    "no adoptable pages under max_seq_len=%d "
                    "(page_size=%d) — shrink prefill_chunk",
                    cfg.prefill_chunk, cfg.max_seq_len, cfg.page_size,
                )
        # Host mirror of the block tables: page growth/release edits
        # this; one small [slots, MP] transfer syncs the device copy
        # before the next decode dispatch (_bt_dirty).
        self._bt_host = np.full((cfg.num_slots, max_pages), -1, np.int32)
        self._bt_dirty = False
        # Chunked prefill staging: chunks write a ONE-slot [NL, L,
        # KVH, D] buffer (the exact layout the chunk graph already
        # speaks); the last chunk scatters the staged sequence through
        # the block tables in the same device call. Costs one slot's
        # KV of extra HBM, keeps the single compiled chunk graph.
        self._stage_k = self._stage_v = None
        if cfg.prefill_chunk > 0:
            self._stage_sharding = psh.named_sharding(
                self.mesh, (None, None, psh.KV_HEADS, None), cache_rules
            )
            stage_shape = (
                self._page_layers,
                cfg.max_seq_len,
                model_cfg.num_kv_heads,
                model_cfg.head_size,
            )
            self._stage_k = jnp.zeros(
                stage_shape, cfg.cache_dtype, device=self._stage_sharding
            )
            self._stage_v = jnp.zeros(
                stage_shape, cfg.cache_dtype, device=self._stage_sharding
            )

        # Per-slot decode state lives ON DEVICE (replicated): steady-state
        # decode then needs ZERO host->device transfers per chunk.
        B = cfg.num_slots
        rep = psh.named_sharding(self.mesh, (None,), rules)
        self._state = {
            # One token a slot; a block of them for a family that fills
            # blocks (positions: the block's first).
            "tokens": jnp.zeros(
                (B, self._block["block_length"]) if self._block else (B,),
                jnp.int32,
                device=psh.named_sharding(self.mesh, (None, None), rules)
                if self._block else rep,
            ),
            "positions": jnp.zeros((B,), jnp.int32, device=rep),
            "seeds": jnp.zeros((B,), jnp.uint32, device=rep),
            "temp": jnp.zeros((B,), jnp.float32, device=rep),
            "topk": jnp.zeros((B,), jnp.int32, device=rep),
            "topp": jnp.ones((B,), jnp.float32, device=rep),
            "lora_idx": jnp.zeros((B,), jnp.int32, device=rep),
        }

        # LoRA adapter buffers: fixed shapes, slot 0 = zeros ("no adapter").
        # Loading an adapter updates a buffer slice — never a recompile.
        self._lora = None
        self._adapter_slots: dict[str, int] = {}
        # slot index -> weight generation (prefix-cache hash seed; index
        # 0 = base model, generation fixed at 0).
        self._adapter_gen: dict[int, int] = {}
        if cfg.max_adapters > 0:
            if not hasattr(self.family, "init_lora_buffers"):
                from kubeai_tpu.models import llama as _llama

                init_fn = _llama.init_lora_buffers
            else:
                init_fn = self.family.init_lora_buffers
            self._lora = init_fn(
                model_cfg, cfg.max_adapters + 1, cfg.max_lora_rank
            )
            self._adapter_free = list(range(1, cfg.max_adapters + 1))

        # Chunked-prefill support is resolved ONCE here.
        self._chunk_fn = None
        if cfg.prefill_chunk > 0:
            self._chunk_fn = getattr(self.family, "prefill_chunk", None)
            if self._chunk_fn is None:
                raise ValueError(
                    f"family {self.family.name} does not support chunked prefill"
                )

        self._draft = None
        if cfg.speculate > 0:
            if (
                getattr(self.family, "decode_verify_paged", None)
                is not None
                and (
                    self._pp == 1
                    or getattr(self.family, "decode_verify_paged_pp", None)
                    is not None
                )
            ):
                self._spec = cfg.speculate
                if draft is not None:
                    if self._pp > 1:
                        # The draft runs the non-pp decode path; its
                        # layer stack would shard over pp and every
                        # draft step would all-gather it. Prompt-lookup
                        # speculation is the pp-compatible mode.
                        raise ValueError(
                            "draft-model speculation does not compose "
                            "with pipeline parallelism (use prompt-"
                            "lookup speculation: speculate>0, no draft)"
                        )
                    dcfg, dparams = draft
                    self._draft_cfg = dcfg
                    # Small drafts often have fewer KV heads than tp: fall
                    # back to replicated KV heads for BOTH the draft's
                    # params and its cache (the same GQA-on-TPU fallback
                    # the main cache uses).
                    dc_rules = psh.kv_cache_rules(
                        self.mesh, dcfg.num_kv_heads, rules
                    )
                    self._draft_params = psh.shard_params(
                        dparams, self.family.param_specs(dcfg), self.mesh,
                        dc_rules,
                    )
                    # The draft's KV is dense, a [max_seq_len] row a slot
                    # (slots on dp, KV heads on tp): family.decode_step's
                    # layout, private to the proposer.
                    self._draft_sharding = psh.named_sharding(
                        self.mesh,
                        (None, psh.KV_SLOTS, None, psh.KV_HEADS, None),
                        dc_rules,
                    )
                    draft_shape = (
                        dcfg.num_layers, cfg.num_slots, cfg.max_seq_len,
                        dcfg.num_kv_heads, dcfg.head_size,
                    )
                    self._dk, self._dv = (
                        jnp.zeros(
                            draft_shape, cfg.cache_dtype,
                            device=self._draft_sharding,
                        )
                        for _ in range(2)
                    )
                    self._draft = True
            else:
                if draft is not None:
                    # A draft is explicit caller intent (weights were
                    # loaded for it) — dropping it silently would hide
                    # the misconfiguration.
                    raise ValueError(
                        "draft model provided but speculation is "
                        f"unavailable (pp={self._pp}, family verify="
                        f"{getattr(self.family, 'decode_verify_paged', None) is not None})"
                    )
                import logging

                logging.getLogger(__name__).warning(
                    "speculate=%d requested but unavailable "
                    "(family verify=%s) — running vanilla decode",
                    cfg.speculate,
                    getattr(self.family, "decode_verify_paged", None)
                    is not None,
                )
        elif draft is not None:
            raise ValueError(
                "draft model provided but cfg.speculate == 0"
            )

        # Expert routes (see `_routes` below): what /v1/state says of a
        # family with a router, None for a dense one.
        self.moe: dict | None = None
        if self.family.routes:
            experts, k, layers = self.family.route_dims(model_cfg)
            self.moe = {
                "experts": int(experts),
                "k": int(k),
                "routed_layers": int(layers),
                "routes": self._routes and not self.routes_unsupported,
            }
            if self.family.held_experts is not None:
                # The share of the router's experts this engine holds,
                # global ids [first, end).
                self.moe["held"] = [
                    int(e) for e in self.family.held_experts(model_cfg)
                ]
        # Cumulative, plain host values (EngineMetrics folds the deltas
        # in): token-layer assignments per global expert id, rows whose
        # tokens were kept by the forward that computed them, rows handed
        # to requests that asked, and such requests.
        self.route_stats = {
            "expert_tokens": np.zeros(
                self.moe["experts"] if self.moe else 0, np.int64
            ),
            "rows_prefill": 0,
            "rows_decode": 0,
            "rows_sent": 0,
            "requests": 0,
            "touched_prefill": 0,
            "touched_decode": 0,
            "passes_prefill": 0,
            "passes_decode": 0,
            # Kept assignments that fell on experts held here and on experts
            # of another share (all are held where the family has no share).
            "assigned_held": 0,
            "assigned_absent": 0,
        }
        # Slots whose state pools an admission wrote (cumulative).
        self.state_stats = {"admissions": 0}
        # A family that generates by blocks (cumulative, like route_stats):
        # forwards of one slot's block by kind, the tokens they committed
        # (both over the blocks that served a token), forwards of the model
        # the chunk programs ran (each over every slot), the chunks reaped,
        # forwards handed to requests that asked, and such requests.
        self.block_stats = {
            "denoise": 0, "commit": 0, "tokens": 0, "program_forwards": 0,
            "chunks": 0, "forwards_sent": 0, "requests": 0,
        }

        self._build_jits_paged(pool_sharding)

    # ---- expert routes ---------------------------------------------------------

    @property
    def routes_unsupported(self) -> str:
        """Why a request that asks for its expert routes is refused ("" =
        it is served; by a dense family without any). Not a setting: the
        pp stage forwards and the verify forwards hand no routes over."""
        if self._pp > 1:
            return "pipeline-parallel stage forwards hand no expert routes over"
        if self._spec:
            return (
                "speculative decoding's verify forwards hand no expert "
                "routes over"
            )
        if self._block:
            return (
                "a family that generates by blocks routes its rows anew at "
                "every forward: ask for kubeai_forwards"
            )
        return ""

    @property
    def _routes(self) -> bool:
        """Whether the compiled programs return the expert sets they took.
        A routed family's always do where they can (the sets feed the load
        counters; a request that asks gets its own rows), so the programs
        are the same whether or not anybody asks; a dense family's are
        what they were."""
        return self.family.routes and self._pp == 1 and not self._spec

    # ---- state beside the pages --------------------------------------------------

    @functools.cached_property
    def _recurrent(self) -> dict | None:
        """What a slot owns beside its pages, as the family says it
        (`ModelFamily.recurrent_state`), None for a family all of whose
        layers keep keys and values. Derived, not set."""
        fn = self.family.recurrent_state
        return fn(self.model_cfg) if fn else None

    @functools.cached_property
    def _window(self) -> dict | None:
        """The layers that keep a ring of fixed size a slot in a pool of
        their own, as the family says them (`ModelFamily.kv_layers`:
        `global_layers`, `window_layers`, `window`) with `ring`, the pages
        of a slot's ring at this engine's page size; None for a family all
        of whose KV layers are of one kind. Derived, not set."""
        fn = self.family.kv_layers
        if fn is None:
            return None
        from kubeai_tpu.ops.paged_attention import ring_pages

        layers = fn(self.model_cfg)
        return {**layers, "ring": ring_pages(layers["window"], self.cfg.page_size)}

    @functools.cached_property
    def _latent(self) -> dict | None:
        """What a token leaves in a page layer of a family whose page layers
        keep ONE latent row a token and no keys and values, as the family
        says it (`ModelFamily.latent_pages`: `row`, `dtype`); None for
        every other. The page pool is then that one pool (`cache.k_pages`;
        `cache.v_pages` is None). Derived, not set."""
        fn = self.family.latent_pages
        return fn(self.model_cfg) if fn else None

    @functools.cached_property
    def _latent_block(self) -> int:
        """Pages of a slot the latent decode kernel attends as one block, as
        the kernel's module derives it from the pool's shapes."""
        from kubeai_tpu.ops.latent_attention import block_pages

        return block_pages(
            self.cfg.page_size, self._latent["row"][0],
            jnp.dtype(self._latent["dtype"]).itemsize, self._bt_host.shape[1],
        )

    @property
    def _page_layers(self) -> int:
        """Layers the page pool is stacked over: those that own pages by
        the sequence's length."""
        rec, win = self._recurrent, self._window
        if win:
            return win["global_layers"]
        return rec["page_layers"] if rec else self.model_cfg.num_layers

    @property
    def _state_sharding(self):
        """Where the state pools live, born and returned: whole on every
        device (a tp axis is refused). One sharding from creation on, so a
        program meets the pools it was compiled for."""
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()
        )

    def _state_pools(self) -> tuple:
        """The pools beside the page pool (recurrent state, or a window
        pool) as the compiled programs take them: one more donated argument
        after `lora`, none for a family without."""
        return (self.cache.state,) if self._beside_pages else ()

    @property
    def _beside_pages(self) -> str | None:
        """What a slot owns beside its pages of the page pool, in words
        (None: nothing; the programs then take no further argument)."""
        if self._recurrent is not None:
            return "recurrent state"
        return "a window ring" if self._window is not None else None

    @property
    def _kept_apart(self) -> str | None:
        """What of a slot's cache is not keys and values in pages of the one
        pool, in words: what it keeps beside its pages, a latent pool, or
        both (None: nothing; `_check_family_engine` is then not asked)."""
        kinds = [
            k for k in (self._beside_pages, self._latent and "a latent pool")
            if k
        ]
        return " and ".join(kinds) or None

    def _check_family_engine(self, draft) -> None:
        """What a family that keeps something beside its pages, or one latent
        pool in place of keys and values, or generates by blocks, is not
        served with: the one table. Beside the pages, each row would need a
        snapshot of a slot's state at a position other than its last, which
        nothing writes yet, or, of a window ring, a rule for positions the
        ring has already forgotten (preemption by recompute needs none: the
        re-admission rebuilds the state, or both pools, from position 0); of
        a latent pool, a chunk graph, a verify forward, a quantizer and a
        wire format that know a row without heads (ROADMAP B4); by blocks,
        each is a composition the one-token step has and the block step does
        not yet (ROADMAP B10)."""
        cfg = self.cfg
        refused = [
            name for name, on in (
                ("prefix_cache", cfg.prefix_cache),
                ("prefill_chunk", cfg.prefill_chunk > 0),
                ("speculate", cfg.speculate > 0 or draft is not None),
                ("kv_dtype int8", self._kv_quant),
                ("max_adapters", cfg.max_adapters > 0),
                ("a pp mesh axis", self.mesh.shape.get("pp", 1) > 1),
                # Sparsely computed experts are one kernel on every device:
                # an expert layer that holds a share is ROADMAP B2.
                ("a tp mesh axis", self.mesh.shape.get("tp", 1) > 1),
            ) if on
        ]
        if refused:
            raise self._not_served_with(", ".join(refused))
        if self._block is not None:
            R = self._block["block_length"]
            if cfg.max_seq_len % R or any(b % R for b in cfg.buckets()):
                raise ValueError(
                    f"max_seq_len and the prefill buckets must be multiples "
                    f"of the block length {R}"
                )

    def _not_served_with(self, what: str) -> ValueError:
        """The one sentence a family's refusals are raised in; the family's
        kind picks its middle."""
        apart = self._kept_apart
        kind = (
            f"keeps {apart} beside its pages" if self._beside_pages
            else f"keeps {apart}" if apart else "generates by blocks"
        )
        return ValueError(
            f"family {self.family.name} {kind} and is not served with: {what}"
        )

    def refuse_state_snapshot(self, what: str) -> None:
        """Raise for `what` where it would need a snapshot of a slot's
        state, or move a slot's keys and values without the state that
        belongs to them (hand-off, pages served to or fetched from a peer,
        spill). The engine asks it of its own calls, the server of its
        options, at construction."""
        if self._kept_apart is not None:
            raise self._not_served_with(what)

    def kv_pools(self) -> list[dict] | None:
        """What /v1/state says of the pools of a family with two kinds of
        KV layer, or with one latent pool (None for any other): kind,
        layers, pages (scratch page left out), the pages a slot can own, the
        pages in use now, bytes; the window pool's `window`, the latent
        pool's `row` (numbers a token leaves a layer)."""
        win = self._window
        total = self._n_pages - 1
        if self._latent:
            return [{
                "kind": "latent", "layers": int(self._page_layers),
                "pages": int(total),
                "pages_per_slot": int(self._bt_host.shape[1]),
                "pages_used": int(total - self._alloc.free_pages),
                "bytes": int(self.cache.nbytes()),
                "row": int(self._latent["row"][0]),
            }]
        if win is None:
            return None
        per_page = (
            2 * self.cfg.page_size * self.model_cfg.num_kv_heads
            * self.model_cfg.head_size * np.dtype(self.cfg.cache_dtype).itemsize
        )
        ring_total = self.cfg.num_slots * win["ring"]
        return [
            {
                "kind": "global", "layers": int(win["global_layers"]),
                "pages": int(total),
                "pages_per_slot": int(self._bt_host.shape[1]),
                "pages_used": int(total - self._alloc.free_pages),
                "bytes": int(win["global_layers"] * (total + 1) * per_page),
            },
            {
                "kind": "window", "layers": int(win["window_layers"]),
                "pages": int(ring_total), "pages_per_slot": int(win["ring"]),
                "pages_used": int(len(self._active) * win["ring"]),
                "bytes": int(win["window_layers"] * (ring_total + 1) * per_page),
                "window": int(win["window"]),
            },
        ]

    def live_pages(self) -> dict | None:
        """The books of the pages a decode chunk reads, a pool of
        `kv_pools()` (None where that is): `pages` at the newest dispatch,
        `pages_total` over every dispatched chunk."""
        if self._latent:
            return {"latent": self.live_kv}
        if self._window:
            return {"global": self.live_kv, "window": self.live_window}
        return None

    @functools.cached_property
    def state_info(self) -> dict | None:
        """What /v1/state says of the state beside the pages (None for a
        family without)."""
        rec = self._recurrent
        if rec is None:
            return None
        pool_bytes = self.cache.state_nbytes()  # fixed when the engine is built
        if self._latent:
            # The latent pool beside them, where a reader of the state
            # pools' bytes looks (`kubeai_engine_state_pool_bytes{kind}`).
            pool_bytes = {**pool_bytes, "latent": int(self.cache.nbytes())}
        return {
            "state_layers": int(rec["state_layers"]),
            "page_layers": int(rec["page_layers"]),
            "bytes_per_slot": {
                name: n // self.cfg.num_slots
                for name, n in self.cache.state_nbytes().items()
            },
            "pool_bytes": pool_bytes,
        }

    # ---- generation by blocks --------------------------------------------------

    @functools.cached_property
    def _block(self) -> dict | None:
        """How a family that generates by diffusion over blocks does it
        (`block_length`, `denoising_steps`, `confidence_threshold`,
        `mask_token_id`: the model's, from its configuration), None for a
        family that generates one token a forward. Derived, not set."""
        gen = getattr(self.family, "block_generation", None)
        return gen(self.model_cfg) if gen else None

    @property
    def block_generation(self) -> dict | None:
        """What /v1/state says of a family that generates by blocks."""
        return self._block

    @property
    def _chunk_blocks(self) -> int:
        """Blocks a slot fills in one decode chunk: `decode_chunk` tokens'
        worth, so a chunk reads back `decode_chunk` tokens a slot as any
        family's does."""
        return max(1, self.cfg.decode_chunk // self._block["block_length"])

    # ---- compiled functions -------------------------------------------------

    def _resolve_prefill(self):
        """Family prefill, with the engine mesh bound when an sp axis is
        live and the family supports ring-attention prefill (llama/qwen):
        makes sequence parallelism a serving path, not a demo."""
        import inspect
        from functools import partial as _partial

        fam = self.family
        if (
            self.mesh.shape.get("sp", 1) > 1
            and "mesh" in inspect.signature(fam.prefill).parameters
        ):
            return _partial(fam.prefill, mesh=self.mesh)
        return fam.prefill

    def jit(self, fn, **kw):
        """jax.jit whose calls run with this engine's mesh as the context
        mesh — where the attention kernels find the mesh their pallas_call
        must be shard_mapped over (ops/dispatch.py). `.lower` is the
        jitted function's, for whoever wants the program and not a run
        (call it under `jax.set_mesh(engine.mesh)`)."""
        jitted = jax.jit(fn, **kw)

        def call(*args):
            with jax.set_mesh(self.mesh):
                return _from_its_own_chunk(jitted, args)

        call.lower = jitted.lower
        return call

    def device_info(self) -> dict:
        """What this engine serves on, as JAX reports it."""
        d = self.mesh.devices.flat[0]
        return {
            "platform": d.platform,
            "device_kind": d.device_kind,
            "count": int(self.mesh.devices.size),
            "mesh": {k: int(v) for k, v in self.mesh.shape.items()},
        }

    def _build_jits_paged(self, pool_sharding) -> None:
        """The compiled paths: admission scatters the prefilled
        sequence through the slot's block-table row; decode scatters one
        token per slot and attends over resident pages only."""
        if self._block:
            return self._build_jits_block(pool_sharding)
        fam, mcfg = self.family, self.model_cfg
        prefill_fn = self._resolve_prefill()
        max_len = self.cfg.max_seq_len
        chunk = max(1, self.cfg.decode_chunk)
        page = self.cfg.page_size
        # A routed family's forwards append their expert sets; each jit
        # below then returns them beside the tokens the host already
        # reads back. A dense family's programs are what they were.
        routed = self._routes
        route_kw = {"routes": True} if routed else {}
        # A family with state beside its pages: its programs take the state
        # pools as one more donated argument and return them, its prefill
        # returns the rows an admission writes after k and v, its decode
        # step the pools after the pages. Every other family's programs
        # are what they were, argument for argument.
        stateful = self._beside_pages is not None
        state_kw = {"state": True} if stateful else {}
        window = self._window
        latent = self._latent is not None
        if self._pp > 1:
            from functools import partial as _partial

            decode_paged = _partial(
                fam.decode_step_paged_pp,
                mesh=self.mesh,
                microbatches=self._pp_microbatches,
            )
        else:
            from functools import partial as _partial

            decode_paged = _partial(
                fam.decode_step_paged, attn_kernel=self.decode_kernel,
                **route_kw,
            )

        def _prefill_admit(
            params, tokens, ints, floats, bt_rows, kp, vp, bt, state, lora,
            *pools,
        ):
            """BATCHED admission: prefill [A, S] prompts → page scatter →
            first-token sample → state update, ONE device call for up to
            max_admit_batch same-bucket prompts (each dispatch is a chip
            round trip — admission under bursts is dispatch-bound).

            ints [A, 6] packs per row [length, slot, seed, top_k,
            adapter, forced]; floats [A, 2] packs [temp, top_p];
            bt_rows [A, MP] are the freshly allocated block-table rows.
            forced >= 0 overrides the sampled token (preemption resume —
            re-sampling could diverge across kernels). PADDING rows use
            slot = num_slots: their scatter indices are out of bounds and
            jit scatters DROP OOB writes, so they touch nothing (their
            page writes go to scratch page 0 via bt_row = -1)."""
            lengths = ints[:, 0]
            slots = ints[:, 1]
            seeds = ints[:, 2].astype(jnp.uint32)
            topk = ints[:, 3]
            adapters = ints[:, 4]
            forced = ints[:, 5]
            temp, topp = floats[:, 0], floats[:, 1]
            if lora is None:
                logits, k_all, v_all, *routes = prefill_fn(
                    params, mcfg, tokens, lengths, **state_kw, **route_kw
                )
            else:
                logits, k_all, v_all, *routes = prefill_fn(
                    params, mcfg, tokens, lengths,
                    lora=lora, lora_idx=adapters, **route_kw,
                )
            if window:
                # The window layers' keys and values: the positions that
                # stay in each slot's ring, and no other, go to its pages.
                from kubeai_tpu.ops.paged_attention import ring_scatter_sequence

                rows = routes.pop(0)
                pools = (
                    dict(zip(("k_window", "v_window"), ring_scatter_sequence(
                        pools[0]["k_window"], pools[0]["v_window"],
                        rows["k_window"], rows["v_window"], slots, lengths,
                        window["ring"],
                    ))),
                )
            elif stateful:
                # The slot's state is overwritten whole, never added to;
                # a padding row's slot is out of range and dropped.
                rows = routes.pop(0)
                pools = (
                    {
                        name: pool.at[:, slots].set(
                            rows[name].astype(pool.dtype), mode="drop"
                        )
                        for name, pool in pools[0].items()
                    },
                )
            # Per-row page coordinates: [A, S] ids/offsets; padded tails
            # (and padding rows) land in reserved scratch page 0.
            from kubeai_tpu.ops.paged_attention import (
                batched_scatter_sequence,
                batched_sequence_page_coords,
            )

            page_ids, offsets = batched_sequence_page_coords(
                bt_rows, lengths, tokens.shape[1], page
            )
            if latent:
                # One pool of rows: `k_all` [page layers, A, S, row], no
                # second pool (`vp` and `v_all` are None).
                from kubeai_tpu.ops.latent_attention import write_latent_rows

                with jax.named_scope("latent_page_write"):
                    kp = write_latent_rows(kp, k_all, page_ids, offsets)
            else:
                kp, vp = batched_scatter_sequence(
                    kp, vp, k_all, v_all, page_ids, offsets
                )
            bt = bt.at[slots].set(bt_rows)
            toks = sample(logits, seeds, lengths, temp, topk, topp)  # [A]
            toks = jnp.where(forced >= 0, forced, toks)
            state = dict(
                tokens=state["tokens"].at[slots].set(toks),
                positions=state["positions"].at[slots].set(lengths),
                seeds=state["seeds"].at[slots].set(seeds),
                temp=state["temp"].at[slots].set(temp),
                topk=state["topk"].at[slots].set(topk),
                topp=state["topp"].at[slots].set(topp),
                lora_idx=state["lora_idx"].at[slots].set(adapters),
            )
            # Routed: the prompts' expert sets [A, S, routed layers, k]
            # ride with the first tokens the admission blocks on.
            head = (toks, routes[0]) if routed else toks
            return (head, kp, vp, bt, state, *pools)

        self._prefill_admit_jit = self.jit(
            _prefill_admit,
            donate_argnums=(5, 6) + ((10,) if stateful else ()),
            out_shardings=(
                None, pool_sharding, pool_sharding, self._bt_sharding, None,
            ) + ((self._state_sharding,) if stateful else ()),
        )

        def _decode_chunk(params, kp, vp, bt, state, lora, *pools):
            """`chunk` paged decode steps fused via lax.scan. The block
            tables are read-only here — page growth happens host-side
            between chunks (the host ensures pages cover position+chunk
            before dispatching). The last output says whether the
            sampler's candidate pool ran (`_live_temp`)."""
            seeds, temp = state["seeds"], _live_temp(state, bt)
            topk, topp = state["topk"], state["topp"]

            def body(carry, _):
                tokens, positions, kp, vp, *pools = carry
                if stateful:
                    logits, kp, vp, *routes = decode_paged(
                        params, mcfg, tokens, positions, kp, vp, bt,
                        state=pools[0],
                    )
                    pools = [routes.pop(0)]
                elif lora is None:
                    logits, kp, vp, *routes = decode_paged(
                        params, mcfg, tokens, positions, kp, vp, bt
                    )
                else:
                    logits, kp, vp, *routes = decode_paged(
                        params, mcfg, tokens, positions, kp, vp, bt,
                        lora=lora, lora_idx=state["lora_idx"],
                    )
                toks = sample(logits, seeds, positions + 1, temp, topk, topp)
                next_pos = jnp.minimum(positions + 1, max_len - 1)
                # Routed: (tokens [B], expert sets [B, routed layers, k])
                # of each step, stacked over the chunk by the scan.
                out = (toks, routes[0]) if routed else toks
                return (toks, next_pos, kp, vp, *pools), out

            (tokens, positions, kp, vp, *pools), toks_seq = jax.lax.scan(
                body,
                (state["tokens"], state["positions"], kp, vp, *pools),
                None,
                length=chunk,
            )
            state = dict(state, tokens=tokens, positions=positions)
            return (toks_seq, kp, vp, state, *pools, any_samples(temp))

        self._decode_jit = self.jit(
            _decode_chunk,
            donate_argnums=(1, 2) + ((6,) if stateful else ()),
            out_shardings=(None, pool_sharding, pool_sharding, None)
            + ((self._state_sharding,) if stateful else ())
            + (None,),
        )

        from kubeai_tpu.ops.paged_attention import (
            scatter_sequence as _scatter_seq,
            sequence_page_coords as _seq_coords,
        )

        def _slot_resume_state(state, ints, floats):
            """Shared handoff-import state update. `ints` packs [length,
            slot, seed, top_k, adapter, first_token]; `floats` packs
            [temp, top_p]."""
            length, slot = ints[0], ints[1]
            seed = ints[2].astype(jnp.uint32)
            topk, adapter, first = ints[3], ints[4], ints[5]
            temp, topp = floats[0], floats[1]
            return dict(
                tokens=state["tokens"].at[slot].set(first),
                positions=state["positions"].at[slot].set(length),
                seeds=state["seeds"].at[slot].set(seed),
                temp=state["temp"].at[slot].set(temp),
                topk=state["topk"].at[slot].set(topk),
                topp=state["topp"].at[slot].set(topp),
                lora_idx=state["lora_idx"].at[slot].set(adapter),
            )

        if not self._kv_quant:

            def _import_handoff(
                ks, vs, ints, floats, bt_row, kp, vp, bt, state
            ):
                """Admit a prefilled KV handoff into a slot WITHOUT any
                prefill compute: scatter the (max_seq_len-padded) imported
                sequence through the freshly allocated block-table row and
                set the slot's decode state so the next decode step resumes
                exactly where the exporting engine's sampler left off.
                Positions >= length scatter into the reserved scratch
                page 0."""
                length = ints[0]
                page_ids, offsets = _seq_coords(bt_row, length, max_len, page)
                kp, vp = _scatter_seq(kp, vp, ks, vs, page_ids, offsets)
                bt = bt.at[ints[1]].set(bt_row)
                return kp, vp, bt, _slot_resume_state(state, ints, floats)

            self._import_handoff_jit = self.jit(
                _import_handoff,
                donate_argnums=(5, 6),
                out_shardings=(
                    pool_sharding, pool_sharding, self._bt_sharding, None,
                ),
            )
        else:
            from kubeai_tpu.ops.paged_attention import (
                scatter_sequence_prequantized as _scatter_preq,
            )

            def _import_handoff_q(
                k8, ksc, v8, vsc, ints, floats, bt_row, kp, vp, bt, state
            ):
                """Quantized handoff import: the wire shipped int8 values
                + scales, and they scatter VERBATIM — re-quantizing a
                dequantized copy would round twice and break the
                byte-identity guarantee the disagg tests assert."""
                length = ints[0]
                page_ids, offsets = _seq_coords(bt_row, length, max_len, page)
                kp, vp = _scatter_preq(
                    kp, vp, k8, ksc, v8, vsc, page_ids, offsets
                )
                bt = bt.at[ints[1]].set(bt_row)
                return kp, vp, bt, _slot_resume_state(state, ints, floats)

            self._import_handoff_jit = self.jit(
                _import_handoff_q,
                donate_argnums=(7, 8),
                out_shardings=(
                    pool_sharding, pool_sharding, self._bt_sharding, None,
                ),
            )

        if self._spec:
            gamma = self._spec
            if self._pp > 1:
                from functools import partial as _partial

                verify = _partial(
                    fam.decode_verify_paged_pp,
                    mesh=self.mesh,
                    microbatches=self._pp_microbatches,
                )
            else:
                verify = fam.decode_verify_paged

            def _spec_step(params, kp, vp, bt, state, proposals, lora):
                """One speculative step: verify [last_token, γ proposals]
                in a single forward; accept the longest prefix where the
                seeded sampler's choice equals the proposal; emit
                accepted+1 tokens. The emitted stream is bit-identical to
                vanilla decoding: choice k is sampled from the same
                logits with the same position fold it would see
                sequentially, and a mismatch truncates the window before
                any diverging context is used."""
                positions = state["positions"]
                seeds, temp = state["seeds"], state["temp"]
                topk, topp = state["topk"], state["topp"]
                tokens_in = jnp.concatenate(
                    [state["tokens"][:, None], proposals], axis=1
                )  # [B, γ+1]
                if lora is None:
                    logits, kp, vp = verify(
                        params, mcfg, tokens_in, positions, kp, vp, bt
                    )
                else:
                    logits, kp, vp = verify(
                        params, mcfg, tokens_in, positions, kp, vp, bt,
                        lora=lora, lora_idx=state["lora_idx"],
                    )
                choices = jnp.stack(
                    [
                        sample(
                            logits[:, k], seeds, positions + k + 1,
                            temp, topk, topp,
                        )
                        for k in range(gamma + 1)
                    ],
                    axis=1,
                )  # [B, γ+1]
                match = (choices[:, :gamma] == proposals).astype(jnp.int32)
                accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                n_emit = accepted + 1  # [B] in 1..γ+1
                new_pos = jnp.minimum(positions + n_emit, max_len - 1)
                last_tok = jnp.take_along_axis(
                    choices, accepted[:, None], axis=1
                )[:, 0]
                state = dict(
                    state, tokens=last_tok, positions=new_pos,
                )
                return choices, n_emit, kp, vp, state

            self._spec_jit = self.jit(
                _spec_step,
                donate_argnums=(1, 2),
                out_shardings=(
                    None, None, pool_sharding, pool_sharding, None,
                ),
            )

        if self._draft:
            dcfg = self._draft_cfg
            gamma = self._spec
            dsh = self._draft_sharding
            decode_draft = fam.decode_step

            def _draft_propose(dparams, dk, dv, tokens, positions):
                """γ+1 greedy draft steps in ONE device call: the chain
                starts from the true last emitted token at its true
                position (keeping the draft's slot KV consistent — see
                Engine.__init__ docstring) and each step's argmax feeds
                the next. The chain runs one step PAST the last proposal
                so proposal γ's own KV is written too: on a fully
                accepted window that token is emitted and the next
                window resumes AFTER it — without the extra step its
                position would be a permanent hole in the draft cache,
                silently poisoning every later window's proposals.
                Returns proposals [B, γ] (the extra step's output is
                dropped)."""

                def step_fn(carry, _):
                    tok, pos, dk, dv = carry
                    logits, dk, dv = decode_draft(
                        dparams, dcfg, tok, pos, dk, dv
                    )
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    nxt_pos = jnp.minimum(pos + 1, max_len - 1)
                    return (nxt, nxt_pos, dk, dv), nxt

                (_, _, dk, dv), props = jax.lax.scan(
                    step_fn,
                    (tokens, positions, dk, dv),
                    None,
                    length=gamma + 1,
                )
                return jnp.moveaxis(props, 0, 1)[:, :gamma], dk, dv

            self._draft_propose_jit = self.jit(
                _draft_propose,
                donate_argnums=(1, 2),
                out_shardings=(None, dsh, dsh),
            )

            draft_prefill = self._resolve_prefill()  # sp-aware, like target

            def _draft_admit(dparams, tokens, lengths, slots, dk, dv):
                """Draft prefill for an admission group: the draft's slot
                rows must hold the prompt KV before the first window
                (padding rows use slot = num_slots; the OOB scatter
                drops them)."""
                _, k_all, v_all = draft_prefill(
                    dparams, dcfg, tokens, lengths
                )
                S = tokens.shape[1]
                dk = dk.at[:, slots, :S].set(k_all.astype(dk.dtype))
                dv = dv.at[:, slots, :S].set(v_all.astype(dv.dtype))
                return dk, dv

            self._draft_admit_jit = self.jit(
                _draft_admit,
                donate_argnums=(4, 5),
                out_shardings=(dsh, dsh),
            )

            def _draft_catchup(dparams, dk, dv, inputs, positions):
                """Teacher-forced draft pass over a chunk-mode window's
                emitted tokens. Adaptive switching runs whole windows in
                chunk mode, which advances sequences WITHOUT writing
                draft KV — without this pass the draft cache desyncs
                permanently after the first chunk window and acceptance
                silently collapses for the rest of each request's life.
                `inputs` is [chunk, B]: the pre-window last token, then
                the window's emitted tokens except its last (which is the
                next call's input)."""

                def step_fn(carry, tok):
                    pos, dk, dv = carry
                    _, dk, dv = decode_draft(dparams, dcfg, tok, pos, dk, dv)
                    return (jnp.minimum(pos + 1, max_len - 1), dk, dv), None

                (_, dk, dv), _ = jax.lax.scan(
                    step_fn, (positions, dk, dv), inputs
                )
                return dk, dv

            self._draft_catchup_jit = self.jit(
                _draft_catchup,
                donate_argnums=(1, 2),
                out_shardings=(dsh, dsh),
            )

            if self.cfg.prefill_chunk > 0:
                draft_chunk_fn = self._chunk_fn

                def _dslot_slice(c, slot):
                    nl, _, L, kvh, d = c.shape
                    sl = jax.lax.dynamic_slice(
                        c, (0, slot, 0, 0, 0), (nl, 1, L, kvh, d)
                    )
                    return sl[:, 0]

                def _dslot_write(c, slot, sl):
                    return jax.lax.dynamic_update_slice(
                        c, sl[:, None].astype(c.dtype), (0, slot, 0, 0, 0)
                    )

                def _draft_chunk(dparams, tokens, ints, dk, dv):
                    """One chunk of draft prefill into the draft's slot
                    row — lets chunked/prefix-hit TARGET admissions keep
                    the draft cache in sync (the batched path's
                    whole-prompt _draft_admit can't serve them). `ints`
                    packs [start, length, slot]."""
                    start, length, slot = ints[0], ints[1], ints[2]
                    ks = _dslot_slice(dk, slot)
                    vs = _dslot_slice(dv, slot)
                    _, ks, vs = draft_chunk_fn(
                        dparams, dcfg, tokens, start, length, ks, vs,
                        want_logits=False,
                    )
                    return _dslot_write(dk, slot, ks), _dslot_write(dv, slot, vs)

                self._draft_chunk_jit = self.jit(
                    _draft_chunk,
                    donate_argnums=(3, 4),
                    out_shardings=(dsh, dsh),
                )

        if self.cfg.prefill_chunk > 0:
            from kubeai_tpu.ops.paged_attention import (
                scatter_sequence,
                sequence_page_coords,
            )

            chunk_fn = self._chunk_fn
            stage_sharding = self._stage_sharding

            def _stage_mid(params, tokens, ints, ks, vs, lora):
                """One non-final chunk into the staging buffer. `ints`
                packs [start, length, adapter]."""
                start, length, adapter = ints[0], ints[1], ints[2]
                _, ks, vs, *routes = chunk_fn(
                    params, mcfg, tokens, start, length, ks, vs,
                    want_logits=False,
                    lora=lora,
                    lora_idx=None if lora is None else adapter[None],
                    **route_kw,
                )
                return (ks, vs, *routes)  # routed: + [C, routed layers, k]

            self._stage_chunk_mid_jit = self.jit(
                _stage_mid,
                donate_argnums=(3, 4),
                out_shardings=(stage_sharding, stage_sharding)
                + ((None,) if routed else ()),
            )

            def _stage_last(
                params, tokens, ints, floats, ks, vs, bt_row, kp, vp, bt,
                state, lora,
            ):
                """Final chunk: logits + staged-KV page scatter + first
                token + slot-state update in one device call. `ints`
                packs [start, length, slot, adapter, seed, top_k,
                forced]; forced >= 0 overrides the sample (preemption
                resume). Staged positions >= length scatter into the
                reserved scratch page 0."""
                start, length, slot = ints[0], ints[1], ints[2]
                adapter, seed = ints[3], ints[4]
                topk, forced = ints[5], ints[6]
                temp, topp = floats[0], floats[1]
                logits, ks, vs, *routes = chunk_fn(
                    params, mcfg, tokens, start, length, ks, vs,
                    want_logits=True,
                    lora=lora,
                    lora_idx=None if lora is None else adapter[None],
                    **route_kw,
                )
                page_ids, offsets = sequence_page_coords(
                    bt_row, length, max_len, page
                )
                kp, vp = scatter_sequence(kp, vp, ks, vs, page_ids, offsets)
                bt = bt.at[slot].set(bt_row)
                tok = sample(
                    logits,
                    seed.astype(jnp.uint32)[None],
                    length[None],
                    temp[None],
                    topk[None],
                    topp[None],
                )[0]
                tok = jnp.where(forced >= 0, forced, tok)
                state = dict(
                    tokens=state["tokens"].at[slot].set(tok),
                    positions=state["positions"].at[slot].set(length),
                    seeds=state["seeds"].at[slot].set(seed.astype(jnp.uint32)),
                    temp=state["temp"].at[slot].set(temp),
                    topk=state["topk"].at[slot].set(topk),
                    topp=state["topp"].at[slot].set(topp),
                    lora_idx=state["lora_idx"].at[slot].set(adapter),
                )
                head = (tok, routes[0]) if routed else tok
                return head, ks, vs, kp, vp, bt, state

            self._stage_chunk_last_jit = self.jit(
                _stage_last,
                donate_argnums=(4, 5, 7, 8, 9),
                out_shardings=(
                    None, stage_sharding, stage_sharding,
                    pool_sharding, pool_sharding, self._bt_sharding, None,
                ),
            )

            if self._prefix_cache:
                S = self.cfg.max_seq_len

                def _stage_from_pages(kp, vp, bt_row, ks, vs):
                    """Materialize a block-table row's pages into the
                    staging buffers (prefix-cache hit: the adopted prefix
                    becomes the context the suffix chunks attend over).
                    Static shapes: the whole row gathers every call;
                    junk past the cached length is masked by the chunk
                    graph's causal frontier and overwritten by the
                    suffix compute. Quantized pools dequantize into the
                    (bf16) staging buffers — the resident pages stay
                    byte-identical; only the staged working copy is
                    floating point."""
                    from kubeai_tpu.ops.kv_quant import (
                        dequantize_kv,
                        is_quantized_kv,
                    )

                    row = jnp.maximum(bt_row, 0)
                    if is_quantized_kv(kp):
                        gk = dequantize_kv(
                            kp["q8"][:, row], kp["scale"][:, row],
                            self.cfg.cache_dtype,
                        )
                        gv = dequantize_kv(
                            vp["q8"][:, row], vp["scale"][:, row],
                            self.cfg.cache_dtype,
                        )
                    else:
                        gk = kp[:, row]  # [NL, MP, page, KVH, D]
                        gv = vp[:, row]
                    nl, mp, pg, kvh, d = gk.shape
                    ks = gk.reshape(nl, mp * pg, kvh, d)[:, :S]
                    vs = gv.reshape(nl, mp * pg, kvh, d)[:, :S]
                    return ks.astype(self.cfg.cache_dtype), vs.astype(
                        self.cfg.cache_dtype
                    )

                self._stage_from_pages_jit = self.jit(
                    _stage_from_pages,
                    donate_argnums=(3, 4),
                    out_shardings=(stage_sharding, stage_sharding),
                )

    # ---- public API ---------------------------------------------------------

    def _build_jits_block(self, pool_sharding) -> None:
        """The two compiled paths of a family that generates by blocks
        (docs/concepts/block-diffusion.md), under the names and arguments
        the one-token family's have, so the step loop calls them alike.

        Admission prefills the prompt's WHOLE blocks under the block mask,
        writes their K and V, and opens the slot's first block with the
        prompt's left-over tokens; it samples nothing. The decode chunk
        fills `_chunk_blocks` blocks a slot: each pass of its loop is one
        forward of the model over every slot's block, after which a slot
        whose block still holds a mask commits rows by the family's rule,
        and a slot whose block is full has its K and V written and moves
        to its next block."""
        from kubeai_tpu.ops.paged_attention import (
            batched_scatter_sequence,
            batched_sequence_page_coords,
        )

        fam, mcfg, how = self.family, self.model_cfg, self._block
        R, mask_id = how["block_length"], how["mask_token_id"]
        page, max_len = self.cfg.page_size, self.cfg.max_seq_len
        slots = self.cfg.num_slots
        nblk = self._chunk_blocks
        max_forwards = nblk * (how["denoising_steps"] + 1)
        prefill_fn = self._resolve_prefill()
        rows = jnp.arange(R)

        def _block_admit(
            params, tokens, ints, floats, bt_rows, kp, vp, bt, state, lora
        ):
            """`_prefill_admit`'s arguments; ints [A, 6] packs per row
            [tokens held, slot, seed, top_k, adapter, unused]. Of the
            tokens held the whole blocks are prefilled and written; the
            rest open the slot's first block, masks after them. Padding
            rows as there: slot = num_slots, bt_row = -1."""
            held, at = ints[:, 0], ints[:, 1]
            whole = held // R * R
            _, k_all, v_all, routes = prefill_fn(
                params, mcfg, tokens, whole, routes=True
            )
            page_ids, offsets = batched_sequence_page_coords(
                bt_rows, whole, tokens.shape[1], page
            )
            kp, vp = batched_scatter_sequence(
                kp, vp, k_all, v_all, page_ids, offsets
            )
            opening = jax.vmap(
                lambda row, start: jax.lax.dynamic_slice(row, (start,), (R,))
            )(tokens, jnp.minimum(whole, tokens.shape[1] - R))
            opening = jnp.where(
                rows[None, :] < (held - whole)[:, None], opening, mask_id
            )
            state = dict(
                tokens=state["tokens"].at[at].set(opening),
                positions=state["positions"].at[at].set(whole),
                seeds=state["seeds"].at[at].set(ints[:, 2].astype(jnp.uint32)),
                temp=state["temp"].at[at].set(floats[:, 0]),
                topk=state["topk"].at[at].set(ints[:, 3]),
                topp=state["topp"].at[at].set(floats[:, 1]),
                lora_idx=state["lora_idx"],
            )
            # The prompts' expert sets [A, S, routed layers, k] are all
            # the admission hands back: no token comes of a prefill.
            return routes, kp, vp, bt.at[at].set(bt_rows), state

        self._prefill_admit_jit = self.jit(
            _block_admit,
            donate_argnums=(5, 6),
            out_shardings=(
                None, pool_sharding, pool_sharding, self._bt_sharding, None,
            ),
        )

        def _block_chunk(params, kp, vp, bt, state, lora):
            seeds, temp = state["seeds"], _live_temp(state, bt)
            topk, topp = state["topk"], state["topp"]
            mp = bt.shape[1]
            slot_idx = jnp.arange(slots)[:, None]
            pooled = any_samples(temp)

            def choose(logits, pos):
                """Sampled tokens [slots, R]; the top ones when every slot
                is greedy (the sampler's top-k over the vocabulary is not
                run then). `sample`'s own conditional with the mask id
                kept out of the greedy branch, so its rows are called
                directly: a second conditional would nest in this one."""
                flat = logits.reshape(slots * R, -1)
                with jax.named_scope("sample"):
                    return jax.lax.cond(
                        pooled,
                        lambda: sample_rows(
                            flat, jnp.repeat(seeds, R), pos.reshape(-1) + 1,
                            jnp.repeat(temp, R), jnp.repeat(topk, R),
                            jnp.repeat(topp, R),
                        ),
                        lambda: jnp.argmax(
                            jnp.where(
                                jnp.arange(flat.shape[-1]) == mask_id,
                                -jnp.inf, flat,
                            ), axis=-1,
                        ).astype(jnp.int32),
                    ).reshape(slots, R)

            def forward(c):
                tokens, positions, left = c["tokens"], c["positions"], c["left"]
                logits, k_new, v_new, routes = fam.block_forward_paged(
                    params, mcfg, tokens, positions, c["kp"], c["vp"], bt,
                    routes=True,
                )
                pos = positions[:, None] + rows[None, :]
                holds_mask = jnp.any(tokens == mask_id, axis=-1)
                denoise = (left > 0) & holds_mask
                write = (left > 0) & ~holds_mask
                filled, commit, _ = fam.block_commit(
                    mcfg, logits, tokens, choose(logits, pos), temp
                )
                commit = commit & denoise[:, None]
                tokens = jnp.where(commit, filled, tokens)
                # A finished block's K and V go to its pages; every other
                # slot's rows to the scratch page.
                pidx = pos // page
                page_ids = jnp.where(
                    (pidx < mp) & write[:, None],
                    bt[slot_idx, jnp.minimum(pidx, mp - 1)], 0,
                )
                kp, vp = batched_scatter_sequence(
                    c["kp"], c["vp"], k_new, v_new,
                    jnp.maximum(page_ids, 0), pos % page,
                )
                # Its tokens go out under the block's number in the chunk.
                here = write[None, :] & (
                    jnp.arange(nblk)[:, None] == (nblk - left)[None, :]
                )
                out = jnp.where(here[:, None, :], tokens.T[None], c["out"])
                f = c["f"]
                record = {
                    "kind": jnp.where(denoise, 1, jnp.where(write, 2, 0))
                    .astype(jnp.int8),
                    "start": positions,
                    "commit": commit,
                    "tokens": tokens,
                    "routes": routes,
                }
                return dict(
                    f=f + 1,
                    tokens=jnp.where(write[:, None], mask_id, tokens),
                    positions=jnp.where(
                        write, jnp.minimum(positions + R, max_len - R),
                        positions,
                    ),
                    left=left - write.astype(left.dtype),
                    kp=kp, vp=vp, out=out,
                    records=jax.tree.map(
                        lambda buf, v: buf.at[f].set(v), c["records"], record
                    ),
                )

            experts, k, layers = fam.route_dims(mcfg)
            from kubeai_tpu.ops.experts import route_dtype

            init = dict(
                f=jnp.int32(0),
                tokens=state["tokens"],
                positions=state["positions"],
                # A slot that holds no page (free, or just freed) sits the
                # chunk out.
                left=jnp.where(bt[:, 0] >= 0, nblk, 0).astype(jnp.int32),
                kp=kp, vp=vp,
                out=jnp.zeros((nblk, R, slots), jnp.int32),
                records={
                    "kind": jnp.zeros((max_forwards, slots), jnp.int8),
                    "start": jnp.zeros((max_forwards, slots), jnp.int32),
                    "commit": jnp.zeros((max_forwards, slots, R), bool),
                    "tokens": jnp.zeros((max_forwards, slots, R), jnp.int32),
                    "routes": jnp.zeros(
                        (max_forwards, slots, R, layers, k),
                        route_dtype(experts),
                    ),
                },
            )
            c = jax.lax.while_loop(
                lambda c: (c["f"] < max_forwards) & jnp.any(c["left"] > 0),
                forward, init,
            )
            state = dict(state, tokens=c["tokens"], positions=c["positions"])
            # [nblk * R, slots] tokens like any chunk's, and what each
            # forward did beside them (the routed family's second output).
            head = (
                c["out"].reshape(nblk * R, slots),
                dict(c["records"], forwards=c["f"]),
            )
            return head, c["kp"], c["vp"], state, pooled

        self._decode_jit = self.jit(
            _block_chunk,
            donate_argnums=(1, 2),
            out_shardings=(None, pool_sharding, pool_sharding, None, None),
        )

    def add_request(
        self,
        prompt_tokens: list[int],
        params: SamplingParams | None = None,
        adapter: str | None = None,
        on_admit=None,
        priority: str | None = None,
        client: str = "",
        deadline_ms: float | None = None,
        resume_tokens: list[int] | None = None,
        routes: bool = False,
        forwards: bool = False,
    ) -> int:
        """Queue a request. `on_admit(rid)` runs under the engine lock
        before the request becomes visible to `step()` — callers use it to
        register event subscribers without racing a concurrent serve loop
        (a request admitted and finished before registration would
        otherwise drop its events).

        Scheduling: `priority` is a class name (None = the scheduler
        policy's default), `client` the WFQ fairness key, `deadline_ms`
        an admission deadline — a deadline the scheduler judges
        infeasible given queue state and the measured drain rate raises
        `DeadlineInfeasible` and the request is NOT queued.

        Continuation: `resume_tokens` is a generation prefix already
        emitted by another replica (proxy stream resume after a
        preemption). The request admits through the same recompute path
        preemption uses — prefill prompt + prefix[:-1] with the first
        token FORCED to prefix[-1] — and step() emits only NEW tokens.
        Because the sampler is seeded and position-folded (stateless
        given (seed, position)), a seeded or greedy continuation is
        token-identical to the uninterrupted stream; unseeded sampling
        resumes with this replica's entropy and stays merely plausible.

        `routes=True` asks for the request's expert routes on its events
        (`StepEvent.routes`). A dense family serves it without any; an
        engine whose forwards hand none over (`routes_unsupported`)
        refuses with ValueError. `forwards=True` asks a family that
        generates by blocks for what each forward over the request's rows
        did (`StepEvent.forwards`); any other family serves it without."""
        if routes and self.routes_unsupported:
            raise ValueError(
                f"expert routes are not available: {self.routes_unsupported}"
            )
        params = params or SamplingParams()
        resume = [int(t) for t in (resume_tokens or [])]
        if resume:
            if len(resume) >= params.max_tokens:
                raise ValueError(
                    f"resume prefix of {len(resume)} tokens >= max_tokens "
                    f"{params.max_tokens}: nothing left to generate"
                )
            if len(prompt_tokens) + len(resume) >= self.cfg.max_seq_len:
                raise ValueError(
                    f"prompt + resume prefix length "
                    f"{len(prompt_tokens) + len(resume)} >= max_seq_len "
                    f"{self.cfg.max_seq_len}"
                )
            if resume[-1] in self.eos_token_ids:
                raise ValueError(
                    "resume prefix already ends at a stop token"
                )
        adapter_idx = 0
        if adapter:
            if self._lora is None:
                raise ValueError("LoRA is disabled (max_adapters=0)")
            if adapter not in self._adapter_slots:
                raise KeyError(f"adapter {adapter!r} not loaded")
            adapter_idx = self._adapter_slots[adapter]
        if len(prompt_tokens) == 0:
            raise ValueError("empty prompt")
        if len(prompt_tokens) >= self.cfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt_tokens)} >= max_seq_len {self.cfg.max_seq_len}"
            )
        with self._lock:
            if self._draining:
                raise EngineDraining("engine is draining")
            rid = self._next_rid
            self._next_rid += 1
            seed = (
                params.seed
                if params.seed is not None
                else (self._seed_base ^ rid)
            ) & 0xFFFFFFFF
            req = _Request(
                rid=rid,
                prompt=list(prompt_tokens),
                params=params,
                seed=seed,
                adapter_idx=adapter_idx,
                client=client,
                # A non-empty out_tokens prefix is what admission reads as
                # "resumed" — the same seat preemption re-admission uses.
                out_tokens=resume,
                stop_token_ids=self.eos_token_ids,
                t_enqueue=_now(),
                route_backlog=[] if routes and self._routes else None,
                forward_backlog=[] if forwards and self._block else None,
            )
            self._requests[rid] = req
            if on_admit is not None:
                try:
                    on_admit(rid)
                except BaseException:
                    del self._requests[rid]
                    raise
            try:
                req.priority = self._sched.submit(
                    req,
                    priority=priority,
                    client=client,
                    deadline_ms=deadline_ms,
                )
            except BaseException:
                # Shed at enqueue (DeadlineInfeasible) or invalid
                # scheduling args: the request never becomes visible.
                del self._requests[rid]
                raise
            if req.route_backlog is not None:
                self.route_stats["requests"] += 1
            if req.forward_backlog is not None:
                self.block_stats["requests"] += 1
            return rid

    def begin_drain(self) -> None:
        """Stop admitting new requests; queued + active work continues
        until finished (or the server's drain budget terminates it)."""
        with self._lock:
            # Overlap barrier: drain decisions (who is still running,
            # what to terminate) must see fully-reaped state.
            self._barrier_locked()
            self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def has_work(self) -> bool:
        return bool(len(self._sched) or self._active or self._inflight)

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def num_pending(self) -> int:
        return len(self._sched)

    @property
    def scheduler(self) -> RequestScheduler:
        """The request scheduler (queue-pressure snapshots, retry hints)."""
        return self._sched

    def drain_timing(self) -> list[tuple]:
        """Pop the accumulated latency observations: (kind, seconds) or
        (kind, seconds, exemplar_tag) with kind ∈ {queue_wait, prefill,
        ttft, itl, e2e} — ttft/itl carry a "rid-<n>" tag so the server's
        histograms keep a last-request exemplar per bucket. A routed
        family also queues (moe_imbalance, ratio) here. The serve
        loop (and the /metrics scrape) observes these into the server's
        histograms; draining transfers ownership so each record lands
        exactly once."""
        with self._lock:
            out, self._timing = self._timing, []
        return out

    def kv_utilization(self) -> float:
        """Fraction of KV-cache capacity in use: allocated pages over the
        pool that slots take pages of by their length (of a family with
        window layers the GLOBAL pool: a slot's ring in the window pool is
        its own from construction, so that pool is never short). Pages
        parked idle in the prefix cache count as free — they are
        reclaimable by any admission."""
        total = self._n_pages - 1  # page 0 is reserved scratch
        if total <= 0:
            return 0.0
        return 1.0 - self._alloc.free_pages / total

    def _bucket(self, n: int) -> int:
        for b in self.cfg.buckets():
            if n <= b:
                return b
        return self.cfg.max_seq_len

    def _pop_pending(self) -> _Request:
        """Dequeue the scheduler's next request for admission, stamping
        the moment it left the queue (queue-wait = this minus t_enqueue;
        prefill = first token minus this). A preempted request keeps its
        original stamp — its re-prefill is recompute, not a second queue
        wait."""
        req = self._sched.pop()
        if not req.t_admit_start:
            req.t_admit_start = _now()
        return req

    def _admission_rides(self) -> bool:
        """Whether the head of the queue can be admitted BEHIND the chunk
        in flight, with no reap first. Decided from what the engine can
        see: a chunk is in flight (so the loop overlaps and runs no
        speculation window, and there is device work to hide the prefill's
        dispatch behind: an idle engine's first request and a warm-up
        admit as they always did), a slot is free now (else the reap is
        how one is found), and the head is a fresh prompt for the fused
        call that its first token cannot end and whose pages the pool can
        give now (else the reap is how they are found). A resumed
        request's re-prefill must see its full `out_tokens`, and the
        `prefix` and `chunked` kinds keep the barrier with their staged
        calls."""
        if self._inflight is None or not (len(self._sched) and self._free_slots):
            return False
        peeked = self._peek_admission()
        pages = -(-peeked[2] // self.cfg.page_size)  # of its `plen` tokens
        return self._fresh_for_queue(peeked) and pages <= self._alloc.free_pages

    def _fresh_for_queue(self, peeked) -> bool:
        """`peeked` (`_peek_admission`) is a fresh prompt for the fused
        call, and the host does not already know that its first token
        ends it (`_check_stop`'s two lengths; no token comes of a block
        family's prefill): such a row is never put on a chunk."""
        req, _seq, plen, resumed, _hashes, _hit, kind = peeked
        ends = not self._block and (
            req.params.max_tokens <= 1 or plen >= self.cfg.max_seq_len
        )
        return kind == "batch" and not resumed and not ends

    def _admit_pending(self, rides: bool = False) -> list[StepEvent]:
        """Admission, BATCHED: same-bucket pending prompts prefill
        in one fused device call (up to cfg.max_admit_batch per call).
        A preempted request resumes by RECOMPUTE — re-prefill prompt +
        already-emitted tokens (minus the last, whose KV the next decode
        step writes) with its first token FORCED to the one already
        emitted.

        Each pass of the loop is one `step.admit` span around one device
        call: its `admit.host` is the pops, page grants, staging, uploads
        and dispatch; the call's first tokens are read by `_collect_head`
        (`admit.wait`, then a second `admit.host`), here inside the
        `step.admit` and before anything else is dispatched.

        `rides` (`_admission_rides`): the chunk in flight was not reaped.
        Only fresh prompts for the fused call are taken (the first request
        that is anything else stays at the head of the queue for the next
        step, which reaps first), and no call is waited for: the device
        wrote the first tokens, positions and sampling state into `state`
        itself, so the admitted are seated with what the host knows
        (`_seat`) and the call joins `_heads`; `step` dispatches the next
        chunk behind it and reads the heads after."""
        emitted: list[StepEvent] = []
        span = self.profiler.span
        C = self.cfg.prefill_chunk
        while len(self._sched) and self._free_slots:
            with span("step.admit") as call:
                with span("admit.host") as host:
                    plan = self._plan_admission(rides)
                    if plan is None:
                        break  # defer: nothing was popped, nothing is held
                    kind, batch, bucket, cached_len = plan
                    queue = self.device_queue.dispatching("prefill")
                    if kind == "batch":
                        head = self._admit_paged_batch(batch, bucket)
                        a_pad = self._head_tokens(head).shape[0]
                        padded = a_pad * bucket
                    else:
                        req, slot, seq, plen = batch[0][:4]
                        head = (
                            self._admit_prefix_hit(
                                req, slot, seq, plen, cached_len
                            )
                            if kind == "prefix"
                            else self._admit_chunked_paged(
                                req, slot, seq, plen, C
                            )
                        )
                        a_pad = 1
                        padded = -(-(plen - cached_len) // C) * C
                    launched = self._launched(head)
                    if rides:
                        for req, slot, _seq, plen, _resumed, hashes in batch:
                            self._seat(req, slot, plen, cached_len, hashes)
                useful = sum(entry[3] for entry in batch) - cached_len
                call.note(
                    kind=kind, bucket=bucket, batch=len(batch), a_pad=a_pad,
                    useful_tokens=useful, padded_tokens=padded, **queue,
                )
                self.admit_stats["calls"] += 1
                self.admit_stats["useful_tokens"] += useful
                self.admit_stats["padded_tokens"] += padded
                admission = _Admission(
                    batch, head, launched, cached_len, host.seconds, rides
                )
                if rides:
                    self._heads.append(admission)
                else:
                    emitted.extend(self._collect_head(admission))
        return emitted

    def _seat(
        self, req: _Request, slot: int, plen: int, cached_len: int, hashes
    ) -> None:
        """Put a fresh admission on the next chunk before the host has
        read its first token: everything the chunk's dispatch asks of the
        host (the slot, `position = plen`, the pages `_grant` gave) is
        known, the token itself is in the device's `state`.
        `_finish_admission` does the rest once the token is read, also
        where the page walk has evicted the request in between (it then
        waits in the queue with its one token, to resume by recompute)."""
        self._note_prefix_admission(req, slot, plen, cached_len, hashes)
        req.position = plen
        self._active[slot] = req

    def _collect_heads(self) -> list[StepEvent]:
        """Read every admission call still in `_heads`, in dispatch
        order."""
        heads, self._heads = self._heads, []
        return [ev for adm in heads for ev in self._collect_head(adm)]

    def _collect_head(self, admission: _Admission) -> list[StepEvent]:
        """The host's second half of one admission call: `admit.wait` is
        the time blocked on its first tokens and nothing else (with a
        chunk dispatched behind the call, what is left of the prefill
        once the chunk it rode behind is reaped), the `admit.host` after
        it the bookkeeping of the admitted: first tokens into
        `out_tokens`, stops, timings, the prompts' expert sets. Returns
        the first tokens' events."""
        span = self.profiler.span
        batch, head, seated = admission.batch, admission.head, admission.seated
        cached_len = admission.cached_len
        emitted: list[StepEvent] = []
        blocks = [None] * len(batch)
        with span("admit.wait") as wait:
            if self._block:
                # No token comes of a block family's prefill: the
                # wait is for the prompts' expert sets alone.
                fetched = jax.device_get(head)
                toks = np.zeros(len(batch), np.int64)
            elif self._routes:
                # The prompts' expert sets come back whole in the
                # transfer that brings the first tokens.
                toks, fetched = jax.device_get(head)
                toks = np.asarray(toks).reshape(-1)
            else:
                toks = np.asarray(head).reshape(-1)
        self.device_queue.waited(admission.launched, "admit")
        if self._routes:
            blocks = self._admission_routes(batch, cached_len, fetched)
        with span("admit.host") as tail:
            for (
                (req, slot, _seq, plen, resumed, hashes), tok, block
            ) in zip(batch, toks, blocks):
                if not resumed and not seated:
                    self._note_prefix_admission(
                        req, slot, plen, cached_len, hashes
                    )
                ev = self._finish_admission(
                    req, slot, plen, int(tok), resumed, block, seated
                )
                if ev is not None:
                    emitted.append(ev)
        self._timing.append(("admit_host", admission.host_s + tail.seconds))
        self._timing.append(("admit_wait", wait.seconds))
        return emitted

    def _launched(self, out):
        """Tell the device queue's book that the newest program launched
        returns `out` (an array, or a tree whose first leaf stands for it:
        a program's outputs are ready together) and return what the book
        holds, for `waited`. With a draft model nothing is held: its
        catch-up and admission programs queue behind the target's, and
        their outputs are donated onward, so the tail is unknown and
        every dispatch reads `drained`."""
        tail = None if self._draft else jax.tree_util.tree_leaves(out)[0]
        self.device_queue.dispatched(tail)
        return tail

    def _head_tokens(self, head):
        """The sampled first tokens of what an admission call returned: a
        routed family's call returns them with its expert sets. (A block
        family's returns the expert sets alone, one row a prompt like the
        tokens: no token comes of its prefill.)"""
        return head[0] if self._routes and not self._block else head

    def _admission_routes(self, batch, cached_len: int, fetched) -> list:
        """One admission call's expert sets, on the host: count them, and
        cut each asking request's block `(start, rows)` (None for the
        others). `fetched` is the fused call's whole `[A, S, routed
        layers, k]` buffer, or the staged chunks' `(start, [C, routed
        layers, k])` in the order they ran: a later chunk overwrites the
        positions it recomputed, as it did in the cache."""
        with self.profiler.span(
            "step.routes", layers=self.moe["routed_layers"],
            k=self.moe["k"],
            asked=sum(
                e[0].route_backlog is not None
                or e[0].forward_backlog is not None for e in batch
            ),
        ) as sp:
            if isinstance(fetched, list):
                plen = batch[0][3]
                rows = np.empty(
                    (plen - cached_len, *fetched[0][1].shape[1:]),
                    fetched[0][1].dtype,
                )
                for start, part in fetched:
                    n = min(len(part), plen - start)
                    rows[start - cached_len : start - cached_len + n] = part[:n]
                per_request = [rows]
                nbytes = sum(part.nbytes for _, part in fetched)
            else:
                # A block family's prefill covers the whole blocks only.
                R = self._block["block_length"] if self._block else 1
                per_request = [
                    fetched[i, : entry[3] // R * R]
                    for i, entry in enumerate(batch)
                ]
                nbytes = fetched.nbytes
            kept = (
                np.concatenate(per_request) if len(per_request) > 1
                else per_request[0]
            )
            self._count_routes(
                kept, np.zeros(len(kept), np.int64), 1, "prefill"
            )
            sp.note(rows=len(kept), bytes=nbytes)
            return [
                (cached_len, rows)
                if entry[0].route_backlog is not None
                or entry[0].forward_backlog is not None
                else None
                for entry, rows in zip(batch, per_request)
            ]

    def _count_routes(
        self, rows: np.ndarray, forward: np.ndarray, n_forwards: int,
        kind: str,
    ) -> None:
        """Fold kept rows `[n, routed layers, k]` into `route_stats`, and
        queue one imbalance reading per forward pass and routed layer:
        the fullest expert's load over the mean load among that
        forward's kept rows (`forward[i]` says which pass row i was in)."""
        n, layers, k = rows.shape
        experts = self.moe["experts"]
        cell = forward[:, None, None] * layers + np.arange(layers)[None, :, None]
        counts = np.bincount(
            (cell * experts + rows).ravel(),
            minlength=n_forwards * layers * experts,
        ).reshape(n_forwards, layers, experts)
        self.route_stats["expert_tokens"] += counts.sum((0, 1))
        self.route_stats["rows_" + kind] += n
        per_forward = np.bincount(forward, minlength=n_forwards)
        live = per_forward > 0
        # Experts that hold a row, summed over (pass, routed layer): what a
        # sparsely computed expert layer reads. An engine that holds a
        # share of the experts reads its own only.
        first, end = self.moe.get("held", (0, experts))
        held = counts[live][..., first:end]
        assigned = int(held.sum())
        self.route_stats["touched_" + kind] += int((held > 0).sum())
        self.route_stats["assigned_held"] += assigned
        self.route_stats["assigned_absent"] += n * layers * k - assigned
        self.route_stats["passes_" + kind] += int(live.sum()) * layers
        ratio = counts[live].max(-1) * experts / (per_forward[live, None] * k)
        self._timing.extend(
            ("moe_imbalance", v) for v in ratio.ravel().tolist()
        )

    def _hand_routes(self, req: _Request, block: tuple) -> tuple:
        """The blocks that ride on the event being made for an asking
        request: what waited for one, then `block`."""
        blocks = (*req.route_backlog, block)
        req.route_backlog.clear()
        self.route_stats["rows_sent"] += sum(len(b[1]) for b in blocks)
        return blocks

    def _peek_admission(self):
        """What the head of the queue asks of an admission, nothing popped
        or held: (req, seq, plen, resumed, hashes, hit, kind). `seq` is
        what its prefill computes, `hit` the cached pages of its prefix,
        `kind` the call it takes: "prefix" with a hit, "chunked" past one
        prefill chunk, else "batch", the fused call."""
        C = self.cfg.prefill_chunk
        req = self._sched.peek()
        resumed = bool(req.out_tokens)
        # A resumed request's last token has no K and V yet (the next
        # decode step writes them); a block family's tokens are served
        # once their block's K and V are written, so all are held.
        seq = req.prompt + (
            req.out_tokens if self._block else req.out_tokens[:-1]
        )
        plen = len(seq)
        hashes = None
        hit = ()
        if self._prefix_cache and not resumed:
            # Memoized per request: a head-of-line admission
            # deferred by OutOfPages would otherwise re-hash its
            # whole prompt every engine step. (Safe across steps:
            # adapter swaps refuse while a pending request
            # references the slot, so the generation in the seed
            # cannot change under a queued request.)
            hashes = getattr(req, "_apc_hashes", None)
            if hashes is None:
                hashes = self._prefix_hashes(seq, req.adapter_idx)
                req._apc_hashes = hashes
            # Cap the hit twice over: at least the final token
            # must compute (its logits seed the first sample),
            # and cached_len + prefill_chunk must fit inside the
            # staging buffer — a padded suffix chunk starting
            # past max_seq_len - C would have its
            # dynamic_update_slice start CLAMPED, silently
            # writing KV at the wrong offset and then scattering
            # it into shared pages.
            cap = min(
                (plen - 1) // self.cfg.page_size,
                max(0, (self.cfg.max_seq_len - C) // self.cfg.page_size),
            )
            hit = self._alloc.lookup(hashes[:cap])
        kind = (
            "prefix" if hit else "chunked" if C > 0 and plen > C else "batch"
        )
        return req, seq, plen, resumed, hashes, hit, kind

    def _plan_admission(self, fresh_only: bool = False):
        """Pop the next admission off the scheduler and grant its slots
        and pages. Returns (kind, entries, bucket, cached_len): "batch"
        is same-bucket prompts for one fused call, "chunked" one long
        prompt for the staged-chunk path, "prefix" one prompt whose first
        cached_len tokens are adopted from the prefix cache (the last two
        are one-at-a-time: the staging buffer holds one sequence, so a
        batch under construction is flushed first and they are taken by
        the next call). None when nothing can be admitted now. An entry
        is (req, slot, seq, plen, resumed, hashes). `fresh_only`: take
        what may ride the device's queue (`_fresh_for_queue`) and leave
        the first request that may not at the head of the queue."""
        C = self.cfg.prefill_chunk
        batch: list[
            tuple[_Request, int, list[int], int, bool, list[bytes] | None]
        ] = []
        bucket = None
        while (
            len(self._sched)
            and self._free_slots
            and len(batch) < max(1, self.cfg.max_admit_batch)
        ):
            peeked = self._peek_admission()
            req, seq, plen, resumed, hashes, hit, kind = peeked
            if fresh_only and not self._fresh_for_queue(peeked):
                break
            if kind != "batch":
                if batch:
                    break
                if self._grant(req, plen, hit) is None:
                    return None
                return (
                    kind,
                    [(req, req.slot, seq, plen, resumed, hashes)],
                    C,
                    len(hit) * self.cfg.page_size,
                )
            b = self._bucket(plen)
            if bucket is None:
                bucket = b
            elif b != bucket:
                break  # same-bucket batching only (no pad blow-up)
            if self._grant(req, plen) is None:
                break
            batch.append((req, req.slot, seq, plen, resumed, hashes))
        return ("batch", batch, bucket, 0) if batch else None

    def _grant(self, req: _Request, plen: int, hit=()) -> int | None:
        """Give the head of the queue the next free slot and the pages
        for plen tokens, the first of them the cached pages `hit`. None,
        with nothing popped and nothing held, when the pool is short."""
        from kubeai_tpu.engine.paged_cache import OutOfPages

        slot = self._free_slots[-1]
        if hit:
            self._alloc.adopt(slot, hit)
        try:
            pages = self._alloc.ensure(slot, plen)
        except OutOfPages:  # ensure() rolled back
            if hit:
                self._alloc.unadopt(slot)
            return None
        self._pop_pending()
        self._free_slots.pop()
        req.slot = slot
        self._set_bt_row(slot, pages)
        return slot

    def _prefix_hashes(self, tokens: list[int], adapter_idx: int) -> list[bytes]:
        """Page-aligned content-hash chain over a prompt. Seeded with the
        adapter slot AND its weight generation, so hot-swapping new
        weights into a reused adapter index can never hit stale KV."""
        import hashlib

        ps = self.cfg.page_size
        gen = self._adapter_gen.get(adapter_idx, 0)
        h = hashlib.blake2b(
            f"apc1:{adapter_idx}:{gen}".encode(), digest_size=16
        ).digest()
        arr = np.asarray(tokens, np.int32)
        out = []
        for i in range(len(tokens) // ps):
            h = hashlib.blake2b(
                h + arr[i * ps : (i + 1) * ps].tobytes(), digest_size=16
            ).digest()
            out.append(h)
        return out

    def _note_prefix_admission(
        self, req: _Request, slot: int, plen: int,
        cached_len: int, hashes: list[bytes] | None,
    ) -> None:
        """Account a fresh admission and publish its immutable full
        prompt pages (pages decode will never write: the first decode
        token lands at position plen, i.e. page plen // page_size).
        `hashes` is the chain the admission loop already computed (None
        when the prefix cache is off). Must run BEFORE _finish_admission
        — a request that finishes on its first token releases the slot
        there, and registration is what lets the released pages park in
        the cache."""
        if not self._prefix_cache or hashes is None:
            return
        self.prefix_stats["lookups"] += 1
        self.prefix_stats["hit_tokens"] += cached_len
        self.prefix_stats["prompt_tokens"] += plen
        n_reg = plen // self.cfg.page_size
        if n_reg == 0:
            return
        self._alloc.register(
            hashes[:n_reg], self._alloc.pages_for(slot)[:n_reg]
        )

    def _admit_prefix_hit(
        self, req: _Request, slot: int, seq: list[int], plen: int,
        cached_len: int,
    ) -> jax.Array:
        """Admission with an adopted cached prefix: materialize the
        prefix pages into the staging buffers, then prefill ONLY the
        suffix through the staged-chunk path (the final chunk scatters
        the staged sequence and samples the first token, exactly as
        chunked admission does)."""
        self._stage_k, self._stage_v = self._stage_from_pages_jit(
            self.cache.k_pages,
            self.cache.v_pages,
            jnp.asarray(self._bt_host[slot]),
            self._stage_k,
            self._stage_v,
        )
        C = self.cfg.prefill_chunk
        arr = np.asarray(seq, np.int32)
        mids = []
        s = cached_len
        while plen - s > C:
            mids.append((s, arr[None, s : s + C]))
            s += C
        # INVARIANT: no chunk may start before cached_len. The adopted
        # prefix pages are SHARED read-only; recomputing their positions
        # here would run a different XLA program than the one that
        # produced them (chunk graph vs bucketed prefill), and the final
        # chunk's scatter would then write not-bit-identical bf16 into
        # pages other requests are concurrently reading. Recompute
        # overlap is only safe WITHIN the suffix (same chunk graph,
        # deterministic), so short suffixes pad forward from cached_len
        # instead of back-aligning into the cached region. (The scatter
        # still rewrites the prefix pages, but with values GATHERED from
        # those very pages — bit-identical by construction.)
        if plen - cached_len >= C:
            last = (plen - C, arr[None, plen - C : plen])
        else:
            # The admission-loop hit cap guarantees this chunk fits the
            # staging buffer; a clamped dynamic_update_slice start would
            # write KV at the wrong offset and scatter it into shared
            # pages.
            assert cached_len + C <= self.cfg.max_seq_len, (
                cached_len, C, self.cfg.max_seq_len,
            )
            padded = np.zeros((1, C), np.int32)
            padded[0, : plen - cached_len] = arr[cached_len:plen]
            last = (cached_len, padded)
        self._draft_admit_chunked(seq, plen, slot)
        return self._run_staged_chunks(req, slot, plen, mids, last)

    def _admit_chunked_paged(
        self, req: _Request, slot: int, seq: list[int], plen: int, C: int
    ) -> jax.Array:
        """Chunked prefill: chunks accumulate in the one-slot
        staging buffer; the final chunk scatters the whole staged sequence
        through the slot's freshly-allocated block-table row."""
        mids, last = self._chunk_plan(seq, plen, C)
        self._draft_admit_chunked(seq, plen, slot)
        return self._run_staged_chunks(req, slot, plen, mids, last)

    def _draft_admit_chunked(self, seq: list[int], plen: int, slot: int) -> None:
        """Chunk the whole prompt into the draft's slot row (the draft
        shares no pages with the target's prefix cache, so even a
        cache-hit admission prefills the draft over the FULL sequence —
        the draft is a fraction of the target's cost)."""
        if not self._draft:
            return
        C = self.cfg.prefill_chunk
        if plen >= C:
            mids, last = self._chunk_plan(seq, plen, C)
            chunks = [*mids, last]
        else:
            padded = np.zeros((1, C), np.int32)
            padded[0, :plen] = np.asarray(seq, np.int32)
            chunks = [(0, padded)]
        for start, tokens in chunks:
            self._dk, self._dv = self._draft_chunk_jit(
                self._draft_params,
                jnp.asarray(tokens),
                jnp.asarray([start, plen, slot], jnp.int32),
                self._dk,
                self._dv,
            )

    def _run_staged_chunks(
        self, req: _Request, slot: int, plen: int, mids, last
    ) -> jax.Array:
        """Run a staged-chunk schedule (mid chunks, then the scattering
        final chunk) — shared by chunked admission and prefix-cache-hit
        suffix prefill so the two paths cannot drift. Like
        _admit_paged_batch it returns the sampled first token ON THE
        DEVICE: the caller decides where the host blocks on it. A routed
        family's comes with every chunk's expert sets, `(token, [(start,
        sets), ...])` in the order the chunks ran."""
        last_start, last_tokens = last
        routes = []
        for start, tokens in mids:
            self._stage_k, self._stage_v, *sets = self._stage_chunk_mid_jit(
                self.params,
                jnp.asarray(tokens),
                jnp.asarray([start, plen, req.adapter_idx], jnp.int32),
                self._stage_k,
                self._stage_v,
                self._lora,
            )
            routes.extend((start, r) for r in sets)
        forced = req.out_tokens[-1] if req.out_tokens else -1
        (
            head,
            self._stage_k,
            self._stage_v,
            self.cache.k_pages,
            self.cache.v_pages,
            self.cache.block_tables,
            self._state,
        ) = self._stage_chunk_last_jit(
            self.params,
            jnp.asarray(last_tokens),
            jnp.asarray(
                [
                    last_start,
                    plen,
                    slot,
                    req.adapter_idx,
                    int(np.uint32(req.seed).view(np.int32)),
                    req.params.top_k,
                    forced,
                ],
                jnp.int32,
            ),
            jnp.asarray(
                [req.params.temperature, req.params.top_p], jnp.float32
            ),
            self._stage_k,
            self._stage_v,
            jnp.asarray(self._bt_host[slot]),
            self.cache.k_pages,
            self.cache.v_pages,
            self.cache.block_tables,
            self._state,
            self._lora,
        )
        if not self._routes:
            return head
        return head[0], [*routes, (last_start, head[1])]

    def _admit_paged_batch(self, batch, bucket: int) -> jax.Array:
        A = len(batch)
        a_pad = 1
        while a_pad < A:
            a_pad *= 2
        mp = self._bt_host.shape[1]
        tokens = np.zeros((a_pad, bucket), np.int32)
        ints = np.zeros((a_pad, 6), np.int32)
        floats = np.zeros((a_pad, 2), np.float32)
        bt_rows = np.full((a_pad, mp), -1, np.int32)
        # Padding rows: length 1, slot out of range (scatter drops it),
        # bt_row -1 (page writes hit scratch), greedy sampling params.
        ints[:, 0] = 1
        ints[:, 1] = self.cfg.num_slots
        floats[:, 1] = 1.0
        for i, (req, slot, seq, plen, _resumed, _hashes) in enumerate(batch):
            tokens[i, :plen] = seq
            ints[i] = [
                plen,
                slot,
                int(np.uint32(req.seed).view(np.int32)),
                req.params.top_k,
                req.adapter_idx,
                # Resume: force the already-emitted last token instead
                # of trusting cross-kernel re-sampling determinism.
                req.out_tokens[-1] if req.out_tokens else -1,
            ]
            floats[i] = [req.params.temperature, req.params.top_p]
            bt_rows[i] = self._bt_host[slot]
        (
            head,
            self.cache.k_pages,
            self.cache.v_pages,
            self.cache.block_tables,
            self._state,
            *pools,
        ) = self._prefill_admit_jit(
            self.params,
            jnp.asarray(tokens),
            jnp.asarray(ints),
            jnp.asarray(floats),
            jnp.asarray(bt_rows),
            self.cache.k_pages,
            self.cache.v_pages,
            self.cache.block_tables,
            self._state,
            self._lora,
            *self._state_pools(),
        )
        if pools:
            (self.cache.state,) = pools
            self.state_stats["admissions"] += A
        if self._draft:
            self._dk, self._dv = self._draft_admit_jit(
                self._draft_params,
                jnp.asarray(tokens),
                jnp.asarray(ints[:, 0]),
                jnp.asarray(ints[:, 1]),
                self._dk,
                self._dv,
            )
        # Tokens [a_pad] (rows past len(batch) are padding); a routed
        # family's come with the call's expert sets.
        return head

    def _finish_admission(
        self, req: _Request, slot: int, plen: int, tok: int,
        resumed: bool = False, block: tuple | None = None,
        seated: bool = False,
    ) -> StepEvent | None:
        """`block`: the admission's expert sets for a request that asked,
        `(first computed position, rows)`. `seated`: the request already
        rides a chunk (`_seat`), or was evicted since and waits in the
        queue; its slot is not touched here unless the token ends it."""
        if self._block:
            return self._finish_block_admission(
                req, slot, plen, resumed, block, seated
            )
        if resumed:
            if req.done:  # finished/cancelled while pending: don't revive
                self._release(req)
                return None
            # tok is the FORCED already-emitted last token; no new event,
            # so the recomputed rows wait for the next token's.
            if block is not None:
                req.route_backlog.append(block)
            req.position = plen
            req.last_token = tok
            self._active[slot] = req
            return None
        # First token of a fresh admission: the whole front half of the
        # request lifecycle resolves here — queue wait (enqueue → dequeue),
        # prefill (dequeue → first token), TTFT (enqueue → first token).
        now = _now()
        self._timing.append(
            ("queue_wait", max(0.0, req.t_admit_start - req.t_enqueue))
        )
        self._timing.append(("prefill", max(0.0, now - req.t_admit_start)))
        self._timing.append(
            ("ttft", max(0.0, now - req.t_enqueue), f"rid-{req.rid}")
        )
        req.t_prev_token = now
        req.out_tokens.append(tok)
        req.position = plen
        req.last_token = tok
        finished = self._check_stop(req)
        if finished:
            self._release(req)
        elif not seated:
            self._active[slot] = req
        return StepEvent(
            req.rid, tok, finished, req.finish_reason,
            None if block is None else self._hand_routes(req, block),
        )

    def _finish_block_admission(
        self, req: _Request, slot: int, plen: int, resumed: bool, block,
        seated: bool = False,
    ) -> None:
        """A block family's admission serves no token: the slot's first
        block is open on the device, and its tokens come of the next
        chunk. TTFT is taken there (`_emit_token`)."""
        if req.done:  # finished/cancelled while pending: don't revive
            self._release(req)
            return None
        if not resumed:
            self._timing.append(
                ("queue_wait", max(0.0, req.t_admit_start - req.t_enqueue))
            )
            self._timing.append(
                ("prefill", max(0.0, _now() - req.t_admit_start))
            )
        if req.forward_backlog is not None and len(block[1]):
            # The prompt's forward: its whole blocks' rows, nothing
            # committed.
            req.forward_backlog.append((0, block[1], (), ()))
        if not seated:
            req.position = plen
            req.last_token = int(req.out_tokens[-1]) if req.out_tokens else 0
            self._active[slot] = req
        return None

    @staticmethod
    def _chunk_plan(seq: list[int], plen: int, C: int):
        """Chunk schedule: full-C mid chunks at 0, C, …; the FINAL chunk
        is aligned BACKWARD to end exactly at plen (start = plen - C), so
        its cache writes never reach past position plen —
        dynamic_update_slice would otherwise CLAMP the start index when
        ceil(plen/C)*C exceeds the buffer length and silently corrupt
        staged KV. Overlapping positions recompute byte-identical KV.
        Returns ([(start, tokens[1, C])...], (last_start, last_tokens))."""
        arr = np.asarray(seq, np.int32)
        n_chunks = -(-plen // C)
        mids = [
            (i * C, arr[None, i * C : (i + 1) * C])
            for i in range(n_chunks - 1)
        ]
        return mids, (plen - C, arr[None, plen - C : plen])

    def _check_stop(self, req: _Request) -> bool:
        if req.last_token in req.stop_token_ids:
            req.done = True
            req.finish_reason = "stop"
        elif len(req.out_tokens) >= req.params.max_tokens:
            req.done = True
            req.finish_reason = "length"
        elif req.position >= self.cfg.max_seq_len:
            # Next decode would write past the cache; the token just emitted
            # needed no cache slot, so capacity is fully used.
            req.done = True
            req.finish_reason = "length"
        return req.done

    def _decode_lookahead(self) -> int:
        """How far positions can advance in one device call. Adaptive
        speculation may run EITHER mode a given step, so cover both."""
        if self._spec:
            chunk = self._spec + 1
            if self.cfg.spec_adaptive:
                chunk = max(chunk, max(1, self.cfg.decode_chunk))
            return chunk
        return max(1, self.cfg.decode_chunk)

    def _ensure_decode_pages(self, inflight_lag: int = 0) -> None:
        """Grow every active slot's pages to cover the next decode chunk.
        Pool exhaustion preempts the YOUNGEST other request (recompute on
        re-admission). Init guarantees the pool holds one full sequence,
        so the loop always terminates with the oldest request served.

        `inflight_lag`: model steps of a dispatched-but-unreaped chunk.
        Host positions LAG the device by that many tokens while a chunk
        is in flight, so coverage extends past the lag or the overlapped
        dispatch would decode into unallocated rows of the block table."""
        from kubeai_tpu.engine.paged_cache import OutOfPages

        chunk = self._decode_lookahead() + max(0, int(inflight_lag))
        page = self.cfg.page_size
        live: dict[int, int] = {}  # slot -> pages that hold its tokens
        for slot, req in sorted(
            self._active.items(), key=lambda kv: kv[1].rid
        ):
            if self._active.get(slot) is not req:
                continue  # preempted by an earlier iteration of this loop
            live[slot] = -(-req.position // page)
            need = min(req.position + chunk + 1, self.cfg.max_seq_len)
            while True:
                before = len(self._alloc.pages_for(slot))
                try:
                    pages = self._alloc.ensure(slot, need)
                except OutOfPages:
                    victims = [
                        r for r in self._active.values() if r is not req
                    ]
                    if not victims:  # cannot happen (init invariant)
                        raise
                    # Victim selection: lowest priority class first (a
                    # batch request must never evict a realtime one),
                    # youngest within a class (least progress lost).
                    victim = max(
                        victims,
                        key=lambda r: (
                            CLASS_RANK.get(r.priority, 0), r.rid
                        ),
                    )
                    live.pop(victim.slot, None)
                    self._preempt(victim)
                    continue
                break
            if len(pages) != before:
                self._set_bt_row(slot, pages)
        self.live_kv["slots"] = len(live)
        self.live_kv["pages"] = sum(live.values())
        if self._latent:
            self.live_blocks["blocks"] = sum(
                -(-n // self._latent_block) for n in live.values())
        if self._window:
            # Pages from the first that holds an in-window position on.
            win = self._window["window"]
            self.live_window["pages"] = sum(
                n - max(self._active[slot].position + 1 - win, 0) // page
                for slot, n in live.items()
            )

    def _set_bt_row(self, slot: int, pages: list[int]) -> None:
        """Update the host block-table mirror for one slot and mark the
        device copy stale (pushed before the next decode dispatch)."""
        row = np.full((self._bt_host.shape[1],), -1, np.int32)
        row[: len(pages)] = pages
        self._bt_host[slot] = row
        self._bt_dirty = True

    def _preempt(self, victim: _Request) -> None:
        """Evict an active request: free its slot + pages, requeue it at
        the FRONT of pending for recompute re-admission (vLLM-style
        preemption, TPU-shaped: static graphs, host-side bookkeeping)."""
        slot = victim.slot
        self._active.pop(slot, None)
        self._free_slots.append(slot)
        victim.slot = -1
        self._alloc.release(slot)
        self._bt_host[slot] = -1
        self._bt_dirty = True
        self._sched.requeue_front(victim)
        # Optional observer (the server's flight recorder): set as a
        # plain attribute so engine stand-ins need no constructor change.
        cb = getattr(self, "on_preempt", None)
        if cb is not None:
            try:
                cb(victim.rid, victim.client)
            except Exception:
                pass

    def _release(self, req: _Request) -> None:
        # Completed requests (not cancellations — a disconnect says
        # nothing about generation latency) record their e2e duration.
        # t_enqueue doubles as the once-only flag: cancel() then a
        # resumed-done _finish_admission both land here.
        if req.finish_reason in ("stop", "length") and req.t_enqueue:
            self._timing.append(("e2e", max(0.0, _now() - req.t_enqueue)))
            req.t_enqueue = 0.0
        # A preempted request can finish (stop/cancel) while waiting in
        # the pending queue — drop it there too, or re-admission would
        # resurrect a done request that leaks its slot and pages forever.
        self._sched.remove(req)
        if req.slot >= 0:
            self._active.pop(req.slot, None)
            self._free_slots.append(req.slot)
            # Free the pages and clear the row BEFORE the next decode:
            # a stale row would scatter the (junk) token of a freed
            # slot into pages that may now belong to a live sequence.
            self._alloc.release(req.slot)
            self._bt_host[req.slot] = -1
            self._bt_dirty = True
            req.slot = -1
        # Finished/cancelled requests leave the table immediately: callers
        # consume tokens from step() events, so retaining them would leak
        # (one _Request per request for the process lifetime).
        self._requests.pop(req.rid, None)

    def cancel(self, rid: int) -> bool:
        """Abort a request (pending or active). Safe mid-stream: the slot's
        stale KV is masked by per-slot lengths when the slot is reused."""
        with self._lock:
            req = self._requests.get(rid)
            if req is None:
                return False
            # Overlap barrier: freeing the slot/pages under an unreaped
            # chunk would let admission reuse them before the reap; reap
            # first so the release mutates fully-settled state.
            self._barrier_locked()
            self._sched.remove(req)
            req.done = True
            req.finish_reason = "cancelled"
            self._release(req)
            return True

    # ---- disaggregated serving: KV handoff export / import ------------------

    def _kv_dtype_name(self) -> str:
        """Wire-format dtype name for KV exports ("int8" for quantized
        pools — the handoff/page-export headers carry it and importers
        refuse on mismatch rather than cast)."""
        return "int8" if self._kv_quant else np.dtype(self.cfg.cache_dtype).name

    def _gather_pages_host(self, pool, idx):
        """Gather pages[:, idx] to host. Returns (values, scales|None):
        quantized pools gather both leaves so exports ship the exact
        resident bytes (never a dequantized copy)."""
        from kubeai_tpu.ops.kv_quant import is_quantized_kv

        if is_quantized_kv(pool):
            return (
                np.asarray(jax.device_get(pool["q8"][:, idx])),
                np.asarray(jax.device_get(pool["scale"][:, idx])),
            )
        return np.asarray(jax.device_get(pool[:, idx])), None

    def _page_wire_nbytes(self) -> int:
        """Payload bytes of ONE page's K+V on the wire (scales included
        when quantized) — the unit every kv_share byte counter uses."""
        mcfg = self.model_cfg
        ps, kvh, d = self.cfg.page_size, mcfg.num_kv_heads, mcfg.head_size
        if self._kv_quant:
            return 2 * mcfg.num_layers * ps * kvh * (d + 4)
        return (
            2 * mcfg.num_layers * ps * kvh * d
            * np.dtype(self.cfg.cache_dtype).itemsize
        )

    def kv_cache_info(self) -> dict:
        """KV-cache capacity facts for /v1/state and the metrics plane:
        dtype, resident pool bytes, and the capacity factor vs a bf16
        pool at equal HBM (2D/(D+4) under int8 — what lets the
        autoscaler's KV-utilization signal and the capacity planner's
        right-sizing see the REAL slot capacity of a quantized replica)."""
        from kubeai_tpu.ops.kv_quant import kv_capacity_factor

        factor = (
            kv_capacity_factor(self.model_cfg.head_size)
            if self._kv_quant else 1.0
        )
        return {
            "dtype": self._kv_dtype_name(),
            "quantized": self._kv_quant,
            "capacity_factor": factor,
            "slot_capacity": int(self.cfg.num_slots),
            "kv_layout": self.kv_layout,
            # Layers the pool is stacked over: those that own pages.
            "page_layers": int(self._page_layers),
            "num_pages": int(self._n_pages),
            "page_size": int(self.cfg.page_size),
            "token_capacity": int((self._n_pages - 1) * self.cfg.page_size),
            "pool_bytes": int(self.cache.nbytes()),
            # What the newest decode chunk had to read (0 before the first).
            "live_slots": self.live_kv["slots"],
            "live_pages": self.live_kv["pages"],
        }

    def export_handoff(
        self,
        prompt_tokens: list[int],
        params: SamplingParams | None = None,
        adapter: str | None = None,
        client: str = "",
        priority: str = "",
        model_name: str = "",
    ):
        """Prefill-role serving: run (chunked) prefill for one request
        SYNCHRONOUSLY, sample its first token, and return a `KVHandoff`
        carrying the paged KV + sampling state — instead of entering
        decode. The slot and pages are borrowed only for the duration of
        this call; with the prefix cache enabled the prompt pages park in
        the idle pool on release, so repeated shared prefixes skip most
        of the prefill compute exactly as unified admission does.

        Raises EngineBusy when no slot/pages are free right now (the
        server sheds 429 and the router re-picks) and EngineDraining once
        drain has begun."""
        self.refuse_state_snapshot("disaggregated hand-off (export)")
        from kubeai_tpu.disagg.handoff import KVHandoff
        from kubeai_tpu.engine.paged_cache import OutOfPages

        params = params or SamplingParams()
        adapter_idx = 0
        if adapter:
            if self._lora is None:
                raise ValueError("LoRA is disabled (max_adapters=0)")
            if adapter not in self._adapter_slots:
                raise KeyError(f"adapter {adapter!r} not loaded")
            adapter_idx = self._adapter_slots[adapter]
        seq = list(prompt_tokens)
        plen = len(seq)
        if plen == 0:
            raise ValueError("empty prompt")
        if plen >= self.cfg.max_seq_len:
            raise ValueError(
                f"prompt length {plen} >= max_seq_len {self.cfg.max_seq_len}"
            )
        with self._lock:
            # Overlap barrier: this borrows a slot + pages synchronously;
            # an unreaped chunk's stop-driven frees must land first.
            self._barrier_locked(launches=True)
            if self._draining:
                raise EngineDraining("engine is draining")
            if not self._free_slots:
                raise EngineBusy("no free prefill slot")
            rid = self._next_rid
            self._next_rid += 1
            seed = (
                params.seed if params.seed is not None
                else (self._seed_base ^ rid)
            ) & 0xFFFFFFFF
            slot = self._free_slots.pop()
            try:
                pages = self._alloc.ensure(slot, plen)
            except OutOfPages:
                self._free_slots.append(slot)
                raise EngineBusy("KV page pool exhausted")
            try:
                self._set_bt_row(slot, pages)
                req = _Request(
                    rid=rid, prompt=seq, params=params, seed=seed,
                    adapter_idx=adapter_idx, client=client,
                    stop_token_ids=self.eos_token_ids,
                )
                t0 = _now()
                C = self.cfg.prefill_chunk
                hashes = self._prefix_hashes(seq, adapter_idx)
                if C > 0 and plen > C:
                    head = self._admit_chunked_paged(req, slot, seq, plen, C)
                else:
                    head = self._admit_paged_batch(
                        [(req, slot, seq, plen, False, None)],
                        self._bucket(plen),
                    )
                # A handoff carries no expert routes (a request that asks
                # of a disaggregated half is refused by the server).
                tok = int(np.asarray(self._head_tokens(head)).reshape(-1)[0])
                self._timing.append(("prefill", max(0.0, _now() - t0)))
                self._timing.append(
                    ("ttft", max(0.0, _now() - t0), f"rid-{rid}")
                )
                # Gather the sequence's pages to host IN TABLE ORDER: the
                # packed-page blob is position-major by construction.
                with self.profiler.span("kv.export", pages=len(pages)) as sp:
                    idx = jnp.asarray(pages, jnp.int32)
                    k_host, k_scales = self._gather_pages_host(
                        self.cache.k_pages, idx
                    )
                    v_host, v_scales = self._gather_pages_host(
                        self.cache.v_pages, idx
                    )
                self.profiler.observe("kv_transfer", sp.seconds)
                if self._prefix_cache:
                    # Publish the prompt pages before release so they park
                    # in the idle LRU instead of returning to the free
                    # list — the prefill-pool half of prefix caching.
                    self._note_prefix_admission(req, slot, plen, 0, hashes)
            finally:
                self._alloc.release(slot)
                self._bt_host[slot] = -1
                self._bt_dirty = True
                self._free_slots.append(slot)
            first_finish = ""
            if tok in self.eos_token_ids:
                first_finish = "stop"
            elif params.max_tokens <= 1:
                first_finish = "length"
            handoff = KVHandoff(
                token_ids=seq,
                first_token=tok,
                first_finish=first_finish,
                page_size=self.cfg.page_size,
                dtype=self._kv_dtype_name(),
                k_pages=k_host,
                v_pages=v_host,
                k_scales=k_scales,
                v_scales=v_scales,
                seed=seed,
                temperature=params.temperature,
                top_k=params.top_k,
                top_p=params.top_p,
                max_tokens=params.max_tokens,
                stop=tuple(params.stop),
                prefix_hashes=tuple(h.hex() for h in hashes),
                adapter=adapter or "",
                client=client,
                priority=priority,
                model=model_name,
            )
            self.disagg_stats["exported"] += 1
            self.disagg_stats["exported_bytes"] += handoff.nbytes()
            return handoff

    def import_handoff(self, handoff, on_admit=None) -> tuple[int, StepEvent]:
        """Decode-role serving: admit a prefilled handoff DIRECTLY into a
        slot — scatter its KV through a fresh block-table row and set the
        slot's sampler state — bypassing every prefill graph. Returns
        (rid, first_event): the first token was sampled by the exporting
        engine, so the caller forwards `first_event` to its subscriber
        itself (step() only emits tokens decoded HERE). `on_admit(rid)`
        runs under the engine lock before the slot becomes visible to
        step(), exactly like add_request's hook.

        The decode stream is token-identical to a unified run: the pages
        hold bit-identical KV bytes, the slot state resumes the same
        seeded sampler at the same position, and decode runs the same
        compiled graph."""
        self.refuse_state_snapshot("disaggregated hand-off (import)")
        from kubeai_tpu.disagg.handoff import HandoffError

        mcfg = self.model_cfg
        nl, _n_pages, _page, kvh, d = handoff.k_pages.shape
        if (nl, kvh, d) != (
            mcfg.num_layers, mcfg.num_kv_heads, mcfg.head_size,
        ):
            raise HandoffError(
                f"handoff geometry [{nl}L,{kvh}KVH,{d}D] does not match "
                f"this model [{mcfg.num_layers}L,{mcfg.num_kv_heads}KVH,"
                f"{mcfg.head_size}D]"
            )
        plen = handoff.plen
        if plen >= self.cfg.max_seq_len:
            raise HandoffError(
                f"handoff length {plen} >= max_seq_len {self.cfg.max_seq_len}"
            )
        expect = self._kv_dtype_name()
        if handoff.dtype != expect or (
            self._kv_quant and not handoff.quantized
        ):
            # Refuse, never cast: an astype here would silently alter KV
            # values while the stream still claims token-identity with
            # the exporting engine.
            raise HandoffError(
                f"handoff KV dtype {handoff.dtype!r} != local pool dtype "
                f"{expect!r}; casting would break token-identity "
                "(re-export from a matching-dtype prefill pool)"
            )
        params = SamplingParams(
            temperature=handoff.temperature,
            top_k=handoff.top_k,
            top_p=handoff.top_p,
            max_tokens=handoff.max_tokens,
            seed=handoff.seed,
            stop=tuple(handoff.stop),
        )
        with self._lock:
            # Overlap barrier: handoff import admits a slot OUTSIDE
            # _admit_pending (bypassing step()'s admission barrier), so
            # reap here before the slot/page grant.
            self._barrier_locked(launches=True)
            if self._draining:
                raise EngineDraining("engine is draining")
            adapter_idx = 0
            if handoff.adapter:
                if (
                    self._lora is None
                    or handoff.adapter not in self._adapter_slots
                ):
                    raise KeyError(
                        f"adapter {handoff.adapter!r} not loaded here"
                    )
                adapter_idx = self._adapter_slots[handoff.adapter]
            rid = self._next_rid
            self._next_rid += 1
            first_ev = StepEvent(
                rid, int(handoff.first_token),
                bool(handoff.first_finish), handoff.first_finish,
            )
            if handoff.first_finish:
                # Finished at its very first token: nothing to decode, no
                # slot to occupy — the caller just emits the final event.
                if on_admit is not None:
                    on_admit(rid)
                self.disagg_stats["imported"] += 1
                self.disagg_stats["imported_bytes"] += handoff.nbytes()
                return rid, first_ev
            if not self._free_slots:
                raise EngineBusy("no free decode slot")
            from kubeai_tpu.engine.paged_cache import OutOfPages

            slot = self._free_slots.pop()
            try:
                pages = self._alloc.ensure(slot, plen)
            except OutOfPages:
                self._free_slots.append(slot)
                raise EngineBusy("KV page pool exhausted")
            now = _now()
            req = _Request(
                rid=rid,
                prompt=list(handoff.token_ids),
                params=params,
                seed=handoff.seed,
                adapter_idx=adapter_idx,
                priority=handoff.priority or CLASS_STANDARD,
                client=handoff.client,
                out_tokens=[int(handoff.first_token)],
                slot=slot,
                position=plen,
                last_token=int(handoff.first_token),
                stop_token_ids=self.eos_token_ids,
                t_enqueue=now,
                t_admit_start=now,
                t_prev_token=now,
            )
            self._requests[rid] = req
            if on_admit is not None:
                try:
                    on_admit(rid)
                except BaseException:
                    del self._requests[rid]
                    self._alloc.release(slot)
                    self._free_slots.append(slot)
                    raise
            self._set_bt_row(slot, pages)
            # Re-page into THIS pool's layout: flatten to token order,
            # zero-pad to max_seq_len (the scatter's static shape) and
            # push through the import graph. Values are copied bit-exact
            # (a dtype mismatch was refused above, never cast).
            with self.profiler.span("kv.import", tokens=plen) as sp:
                k_seq, v_seq = handoff.contiguous_kv()
                pad = np.zeros(
                    (nl, self.cfg.max_seq_len, kvh, d), dtype=k_seq.dtype
                )
                k_pad, v_pad = pad.copy(), pad
                k_pad[:, :plen] = k_seq
                v_pad[:, :plen] = v_seq
                if self._kv_quant:
                    ks_seq, vs_seq = handoff.contiguous_scales()
                    spad = np.zeros(
                        (nl, self.cfg.max_seq_len, kvh), np.float32
                    )
                    ks_pad, vs_pad = spad.copy(), spad
                    ks_pad[:, :plen] = ks_seq
                    vs_pad[:, :plen] = vs_seq
                ints = jnp.asarray(
                    [
                        plen,
                        slot,
                        int(np.uint32(handoff.seed & 0xFFFFFFFF).view(np.int32)),
                        params.top_k,
                        adapter_idx,
                        int(handoff.first_token),
                    ],
                    jnp.int32,
                )
                floats = jnp.asarray(
                    [params.temperature, params.top_p], jnp.float32
                )
                if self._kv_quant:
                    (
                        self.cache.k_pages,
                        self.cache.v_pages,
                        self.cache.block_tables,
                        self._state,
                    ) = self._import_handoff_jit(
                        jnp.asarray(k_pad, jnp.int8),
                        jnp.asarray(ks_pad, jnp.float32),
                        jnp.asarray(v_pad, jnp.int8),
                        jnp.asarray(vs_pad, jnp.float32),
                        ints,
                        floats,
                        jnp.asarray(self._bt_host[slot]),
                        self.cache.k_pages,
                        self.cache.v_pages,
                        self.cache.block_tables,
                        self._state,
                    )
                else:
                    (
                        self.cache.k_pages,
                        self.cache.v_pages,
                        self.cache.block_tables,
                        self._state,
                    ) = self._import_handoff_jit(
                        jnp.asarray(k_pad, self.cfg.cache_dtype),
                        jnp.asarray(v_pad, self.cfg.cache_dtype),
                        ints,
                        floats,
                        jnp.asarray(self._bt_host[slot]),
                        self.cache.k_pages,
                        self.cache.v_pages,
                        self.cache.block_tables,
                        self._state,
                    )
            self.profiler.observe("kv_transfer", sp.seconds)
            # _set_bt_row marked the host mirror dirty; the import graph
            # also set the device row, so the next step's device_put is
            # redundant but harmless (and still needed if OTHER slots'
            # rows changed since the last dispatch).
            if self._prefix_cache and handoff.prefix_hashes:
                n_reg = min(
                    plen // self.cfg.page_size, len(handoff.prefix_hashes)
                )
                if n_reg > 0:
                    self._alloc.register(
                        [bytes.fromhex(h) for h in
                         handoff.prefix_hashes[:n_reg]],
                        pages[:n_reg],
                    )
            self._active[slot] = req
            self.disagg_stats["imported"] += 1
            self.disagg_stats["imported_bytes"] += handoff.nbytes()
            return rid, first_ev

    # ---- cluster KV-sharing tier ------------------------------------------

    def prefix_holdings(self) -> list[str]:
        """Every chain hash (hex) this replica's prefix cache currently
        holds — published via /v1/state so the fleet aggregator can build
        the who-holds-which-prefix map. Advisory: routing hints built on
        it can go stale without harming correctness (admission re-checks
        through lookup())."""
        if not self._prefix_cache:
            return []
        with self._lock:
            return [h.hex() for h in self._alloc.holdings()]

    def cached_prefix_depth(self, hashes_hex: list[str]) -> int:
        """How many leading pages of the chain are held locally right
        now — what a peer fetch would NOT need to transfer."""
        if not self._prefix_cache:
            return 0
        try:
            hashes = [bytes.fromhex(h) for h in hashes_hex]
        except ValueError:
            return 0
        with self._lock:
            return len(self._alloc.lookup(hashes))

    def compute_prefix_chain(self, tokens: list[int]) -> list[str]:
        """Base-model page-hash chain (hex) for a token sequence — the
        engine-side oracle the front door's chain computation must match."""
        return [h.hex() for h in self._prefix_hashes(list(tokens), 0)]

    def export_prefix_pages(self, hashes_hex: list[str], max_bytes: int = 0):
        """Serve a peer's partial-chain fetch: gather the longest locally
        held prefix of the requested chain (optionally truncated to a
        transfer-size cap) to host and wrap it as a `KVPageExport`. Pages
        are copied under the engine lock, so the bytes are a consistent
        snapshot; an empty export means "hold nothing of that chain".
        Base-model chains only — per-replica LoRA slot seeds make adapter
        chains incomparable across replicas."""
        self.refuse_state_snapshot("prefix pages served to a peer")
        from kubeai_tpu.disagg.handoff import KVPageExport

        if not self._prefix_cache:
            return None
        try:
            hashes = [bytes.fromhex(h) for h in hashes_hex]
        except ValueError:
            return None
        mcfg = self.model_cfg
        ps = self.cfg.page_size
        page_nbytes = self._page_wire_nbytes()
        with self._lock:
            # Overlap barrier: the exported bytes must be a settled
            # snapshot — an in-flight chunk is still WRITING pages.
            self._barrier_locked(launches=True)
            pages = self._alloc.lookup(hashes)
            if max_bytes > 0:
                pages = pages[: max_bytes // page_nbytes]
            n = len(pages)
            k_scales = v_scales = None
            if n:
                idx = jnp.asarray(pages, jnp.int32)
                k_host, k_scales = self._gather_pages_host(
                    self.cache.k_pages, idx
                )
                v_host, v_scales = self._gather_pages_host(
                    self.cache.v_pages, idx
                )
            else:
                shape = (
                    mcfg.num_layers, 0, ps, mcfg.num_kv_heads, mcfg.head_size,
                )
                if self._kv_quant:
                    k_host = np.zeros(shape, np.int8)
                    v_host = np.zeros(shape, np.int8)
                    k_scales = np.zeros(shape[:-1], np.float32)
                    v_scales = np.zeros(shape[:-1], np.float32)
                else:
                    dtype = np.dtype(self.cfg.cache_dtype)
                    k_host = np.zeros(shape, dtype)
                    v_host = np.zeros(shape, dtype)
            self.kv_share_stats["exported_pages"] += n
            self.kv_share_stats["exported_bytes"] += n * page_nbytes
        return KVPageExport(
            prefix_hashes=tuple(hashes_hex[:n]),
            page_size=ps,
            dtype=self._kv_dtype_name(),
            k_pages=k_host,
            v_pages=v_host,
            k_scales=k_scales,
            v_scales=v_scales,
        )

    def import_prefix_pages(self, export, source: str = "peer") -> int:
        """Seed fetched prefix pages into the idle pool, unowned: the next
        admission whose chain matches adopts them through the ordinary
        lookup()/adopt() path, so a stale or partial import can only cost
        recompute, never correctness. Geometry, page size AND dtype must
        match exactly — a cast would alter KV values while the chain hash
        still vouches for the original content, silently breaking
        token-identity with the no-sharing baseline. Returns the number of
        pages actually seeded (0 when the pool refuses or everything was
        already held)."""
        self.refuse_state_snapshot("prefix pages fetched from a peer or a spill store")
        from kubeai_tpu.disagg.handoff import HandoffError

        if not self._prefix_cache:
            return 0
        if export.n_pages == 0:
            return 0
        mcfg = self.model_cfg
        nl, _n, page, kvh, d = export.k_pages.shape
        if (nl, kvh, d) != (
            mcfg.num_layers, mcfg.num_kv_heads, mcfg.head_size,
        ):
            raise HandoffError(
                f"page export geometry [{nl}L,{kvh}KVH,{d}D] does not "
                f"match this model [{mcfg.num_layers}L,"
                f"{mcfg.num_kv_heads}KVH,{mcfg.head_size}D]"
            )
        if page != self.cfg.page_size:
            raise HandoffError(
                f"page size {page} != local {self.cfg.page_size} (chain "
                "hashes are page-size-dependent; no re-paging is possible)"
            )
        if export.dtype != self._kv_dtype_name() or (
            self._kv_quant and not export.quantized
        ):
            raise HandoffError(
                f"KV dtype {export.dtype} != local cache dtype "
                f"{self._kv_dtype_name()}; casting would "
                "break token-identity"
            )
        try:
            hashes = [bytes.fromhex(h) for h in export.prefix_hashes]
        except ValueError as e:
            raise HandoffError(f"bad chain hash: {e}") from e
        with self._lock:
            # Overlap barrier: seeding idle-pool pages races an unreaped
            # chunk's frees/allocations — reap before touching the pool.
            self._barrier_locked(launches=True)
            seeded = self._alloc.seed_unowned(hashes)
            if seeded is None:
                return 0
            write = [(i, p) for i, p in enumerate(seeded) if p is not None]
            if write:
                idx = jnp.asarray([p for _, p in write], jnp.int32)
                cols = [i for i, _ in write]
                if self._kv_quant:
                    # Verbatim int8 + scale writes — the chain hash
                    # vouches for these exact quantized bytes.
                    self.cache.k_pages = {
                        "q8": self.cache.k_pages["q8"].at[:, idx].set(
                            jnp.asarray(
                                np.ascontiguousarray(
                                    export.k_pages[:, cols]
                                ),
                                jnp.int8,
                            )
                        ),
                        "scale": self.cache.k_pages["scale"].at[:, idx].set(
                            jnp.asarray(
                                np.ascontiguousarray(
                                    export.k_scales[:, cols]
                                ),
                                jnp.float32,
                            )
                        ),
                    }
                    self.cache.v_pages = {
                        "q8": self.cache.v_pages["q8"].at[:, idx].set(
                            jnp.asarray(
                                np.ascontiguousarray(
                                    export.v_pages[:, cols]
                                ),
                                jnp.int8,
                            )
                        ),
                        "scale": self.cache.v_pages["scale"].at[:, idx].set(
                            jnp.asarray(
                                np.ascontiguousarray(
                                    export.v_scales[:, cols]
                                ),
                                jnp.float32,
                            )
                        ),
                    }
                else:
                    src = np.ascontiguousarray(export.k_pages[:, cols])
                    self.cache.k_pages = self.cache.k_pages.at[:, idx].set(
                        jnp.asarray(src, self.cfg.cache_dtype)
                    )
                    src = np.ascontiguousarray(export.v_pages[:, cols])
                    self.cache.v_pages = self.cache.v_pages.at[:, idx].set(
                        jnp.asarray(src, self.cfg.cache_dtype)
                    )
            key = "imported_pages" if source == "peer" else "filled_pages"
            self.kv_share_stats[key] += len(write)
            if source == "peer":
                self.kv_share_stats["imported_bytes"] += (
                    len(write) * self._page_wire_nbytes()
                )
            return len(write)

    def enable_kv_spill(self, store) -> None:
        """Wire idle-pool eviction to an objstore spill: just before an
        evicted page's registration is destroyed, its K/V bytes are
        snapshotted to `store` keyed by the chain hash, so a later fetch
        for an evicted hot prefix can FILL from the store instead of
        recomputing. The hook runs under the engine lock on the eviction
        path and must never raise (the allocator also guards it)."""
        self.refuse_state_snapshot("a KV spill store")
        from kubeai_tpu.disagg.handoff import KVPageExport, serialize_pages

        def _spill(page: int, h: bytes) -> None:
            idx = jnp.asarray([page], jnp.int32)
            k, k_scales = self._gather_pages_host(self.cache.k_pages, idx)
            v, v_scales = self._gather_pages_host(self.cache.v_pages, idx)
            blob = serialize_pages(
                KVPageExport(
                    prefix_hashes=(h.hex(),),
                    page_size=self.cfg.page_size,
                    dtype=self._kv_dtype_name(),
                    k_pages=k,
                    v_pages=v,
                    k_scales=k_scales,
                    v_scales=v_scales,
                )
            )
            store.put(h.hex(), blob)
            self.kv_share_stats["spilled_pages"] += 1

        self._alloc.on_evict = _spill

    def _spec_pick(self) -> bool:
        """Choose this decode call's mode (True = speculative window,
        False = fused chunk). Epsilon-greedy over measured tokens/s:
        sample each arm once, then run the winner, re-probing the loser
        every cfg.spec_probe_every calls so a workload shift (e.g. the
        batch turning repetitive) is noticed.

        Stream-stability caveat: mode invariance relies on both compiled
        graphs producing the same sampled tokens. Greedy (temperature=0)
        decoding is exactly mode-invariant (verify accepts iff tokens
        match argmax). With temperature>0 the seeded sampler consumes the
        same per-slot key sequence in both modes, but the two graphs may
        differ in logits by ULPs on TPU, so a near-tie sample can flip at
        a mode switch. That is within the API contract (sampling makes no
        cross-process bitwise guarantee) but means tests asserting exact
        seeded streams run on one mode; set spec_adaptive=False when
        bitwise-stable seeded streams matter."""
        if not self.cfg.spec_adaptive:
            return True
        self._decode_calls += 1
        s = self._mode_tps.get("spec")
        c = self._mode_tps.get("chunk")
        if self._mode_calls.get("spec", 0) < 2:
            return True
        if self._mode_calls.get("chunk", 0) < 2:
            return False
        if self._decode_calls % max(2, self.cfg.spec_probe_every) == 0:
            return s <= c  # probe the currently losing arm
        return s > c

    def _spec_observe(self, mode: str, tokens: int, dt: float) -> None:
        """Fold one decode call's throughput into the mode's EMA. The
        first call per mode is counted but not folded — it includes
        compile time and would poison the estimate."""
        calls = self._mode_calls.get(mode, 0) + 1
        self._mode_calls[mode] = calls
        if calls < 2 or dt <= 0 or tokens <= 0:
            return
        tps = tokens / dt
        prev = self._mode_tps.get(mode)
        self._mode_tps[mode] = (
            tps if prev is None else 0.7 * prev + 0.3 * tps
        )

    def step(self) -> list[StepEvent]:
        """Admit pending prefills, then run one fused decode chunk
        (cfg.decode_chunk model steps in a single device call).

        Where the loop overlaps (`_overlap`), the chunk dispatched this call is
        reaped on the NEXT call: the device computes chunk N+1 while the
        host reads back and processes chunk N's tokens (readback,
        admission, detokenize, SSE fan-out all hide behind device
        compute). Conservative barriers reap first wherever overlap
        could change tokens — see _reap_inflight_locked.

        Three kinds of step. With nothing waiting, the next chunk goes out
        behind the chunk in flight, which is then reaped (`barrier=none`).
        With a prompt waiting that `_admission_rides` (a chunk in flight,
        a slot free now, a fresh prompt for the fused call at the head of
        the queue): plan, stage and dispatch the prefill(s) behind the
        chunk in flight, seat the admitted, grow pages, upload the block
        table, dispatch the next chunk behind the prefill, reap the chunk
        that was in flight as an ordinary reap, and only then read each
        prefill's first tokens, in dispatch order. The device runs chunk,
        prefill, chunk as it would have; the host no longer stands between
        them. With a prompt waiting that does not ride (no slot free, a
        resumed request or a `prefix` / `chunked` admission at the head, a
        request its first token is known to end, a pool too short for the
        head's pages now), the admission barrier: reap FIRST
        (`barrier=admission`), then admit, each call's first tokens read
        before anything else is dispatched, then the chunk. So does every
        admission with no chunk in flight (an idle engine, a warm-up, the
        synchronous loop, speculation). Nothing is configured: the engine's
        own state decides.

        Returns a list of StepEvents in emission order: what an
        out-of-step barrier reaped, a barrier's chunk and its admissions'
        first tokens, the reaped chunk's tokens, the first tokens of the
        admissions that rode, and (synchronous loop) this step's chunk.
        """
        span = self.profiler.span
        book = self.device_queue
        started = book.now()
        # The span opens before the lock: a handler thread adding or
        # cancelling a request holds it, and that wait is the step's too.
        with span(
            "serve.step", step=self.profiler.steps_completed + 1,
            batch=len(self._active), pending=len(self._sched),
        ), self._lock:
            # Per-phase timeline for this step (fleet/profiler.py), each a
            # `step.<phase>` span: prefill = admission pass, schedule =
            # host bookkeeping before the decode dispatch, dispatch =
            # block-table upload, decode = jit DISPATCH (async; the device
            # wait lands in overlap_idle and the transfer in readback
            # inside _process_chunk), sample = host token emission.
            phases = self.profiler.begin_step()
            book.begin_step(started)
            emitted: list[StepEvent] = []
            if self._pending_events:
                # Tokens reaped by an out-of-step barrier (cancel, drain,
                # handoff, prefix fetch) — deliver before this step's.
                emitted.extend(self._pending_events)
                self._pending_events.clear()
            # ADMISSION BARRIER, taken where the host must see the chunk
            # in flight before it admits: no slot is free (the reap's
            # stop-driven frees are how one is found), or the head of the
            # queue is a preempted request (its re-prefill must see its
            # full out_tokens), takes the staged `prefix` / `chunked`
            # calls, is known to end with its first token, or needs pages
            # the pool cannot give yet. Also reap before any speculation
            # window: prompt-lookup proposals read out_tokens. Otherwise
            # the admission RIDES the device's queue (`_admission_rides`):
            # the slot it needs was freed by an earlier reap, and the
            # device runs chunk, prefill, next chunk in that order whether
            # or not the host stands between them, each call's pools,
            # tables and `state` being the previous call's outputs.
            rides = self._admission_rides()
            if (
                self._inflight is not None
                and (len(self._sched) or self._spec)
                and not rides
            ):
                emitted.extend(
                    self._reap_inflight_locked(
                        "admission" if len(self._sched) else "spec"
                    )
                )
            with span("step.prefill"):
                emitted.extend(self._admit_pending(rides))
            prev = self._inflight
            self._inflight = None
            current = None
            decode_mode = None
            routes_seq = None  # routed: the chunk's expert sets, on device
            pooled = None  # whether the chunk's sampler ran its pool, on device
            t0 = time.perf_counter()
            if self._active and prev is not None:
                # SEQ-CAP BARRIER: dispatching chunk N+1 before reaping N
                # advances device positions by up to len(N) + chunk. If
                # any slot could cross max_seq_len in that window its
                # decode would write past its block-table row, so reap
                # first — the dispatch below then overshoots by at most
                # one chunk, exactly the envelope the synchronous loop
                # already tolerates (surplus tokens are discarded).
                with span("step.schedule"):
                    horizon = prev[2] + self._decode_lookahead() + 1
                    at_cap = any(
                        req.position + horizon >= self.cfg.max_seq_len
                        for req in self._active.values()
                    )
                if at_cap:
                    emitted.extend(self._process_chunk(prev, "seq_cap"))
                    prev = None
            if self._active:
                with span("step.schedule"):
                    self._ensure_decode_pages(
                        inflight_lag=prev[2] if prev is not None else 0
                    )
                if self._bt_dirty:
                    with span("step.dispatch"):
                        # A copy: the mirror is written in place while the
                        # chunk that reads this upload is in flight (a
                        # release, a grant behind it), and on the CPU an
                        # aligned host array is handed over without one.
                        self.cache.block_tables = jax.device_put(
                            jnp.asarray(self._bt_host.copy()),
                            self._bt_sharding,
                        )
                        self._bt_dirty = False
                self.live_kv["pages_total"] += self.live_kv["pages"]
                self.live_blocks["blocks_total"] += self.live_blocks["blocks"]
                self.live_window["pages_total"] += self.live_window["pages"]
                with span(
                    "step.decode", kv_layout=self.kv_layout,
                    live_slots=self.live_kv["slots"],
                    live_pages=self.live_kv["pages"],
                    # A family that fills blocks: the blocks a slot fills
                    # in this chunk and the most forwards that takes.
                    **(
                        {
                            "blocks": self._chunk_blocks,
                            "forwards": self._chunk_blocks
                            * (self._block["denoising_steps"] + 1),
                        }
                        if self._block else {}
                    ),
                ) as launch:
                    launch.note(**book.dispatching("decode"))
                    if self._spec and self._spec_pick():
                        decode_mode = "spec"
                        if self._draft:
                            proposals, self._dk, self._dv = (
                                self._draft_propose_jit(
                                    self._draft_params,
                                    self._dk,
                                    self._dv,
                                    self._state["tokens"],
                                    self._state["positions"],
                                )
                            )
                        else:
                            proposals = jnp.asarray(self._build_proposals())
                        (
                            choices,
                            n_emit,
                            self.cache.k_pages,
                            self.cache.v_pages,
                            self._state,
                        ) = self._spec_jit(
                            self.params,
                            self.cache.k_pages,
                            self.cache.v_pages,
                            self.cache.block_tables,
                            self._state,
                            proposals,
                            self._lora,
                        )
                        toks_seq = ("spec", choices, n_emit)
                    else:
                        if self._spec:
                            decode_mode = "chunk"
                        pre_tokens = pre_positions = None
                        if self._draft:
                            pre_tokens = self._state["tokens"]
                            pre_positions = self._state["positions"]
                        (
                            toks_seq,
                            self.cache.k_pages,
                            self.cache.v_pages,
                            self._state,
                            *pools,
                            pooled,
                        ) = self._decode_jit(
                            self.params,
                            self.cache.k_pages,
                            self.cache.v_pages,
                            self.cache.block_tables,
                            self._state,
                            self._lora,
                            *self._state_pools(),
                        )
                        if pools:
                            (self.cache.state,) = pools
                        if self._routes:
                            toks_seq, routes_seq = toks_seq
                        if self._draft:
                            # Keep the draft cache in lockstep with the
                            # chunk the target just decoded (see
                            # _draft_catchup).
                            inputs = jnp.concatenate(
                                [pre_tokens[None], toks_seq[:-1]], axis=0
                            )
                            self._dk, self._dv = self._draft_catchup_jit(
                                self._draft_params, self._dk, self._dv,
                                inputs, pre_positions,
                            )
                self._steps += 1
                is_spec = isinstance(toks_seq, tuple)
                self._launched(toks_seq[1] if is_spec else toks_seq)
                chunk_len = 0 if is_spec else int(toks_seq.shape[0])
                current = (
                    toks_seq,
                    list(self._active.items()),
                    chunk_len,
                    time.monotonic(),
                    routes_seq,
                    pooled,
                )
                if self._overlap and not is_spec and not self._spec:
                    # Reap current NEXT call: the device computes through
                    # the host's readback+process of prev. Speculation
                    # windows never overlap — proposals read out_tokens,
                    # and the adaptive arm needs the measured wall time of
                    # every chunk call.
                    self._inflight = current
                    current = None
            if prev is not None:
                emitted.extend(self._process_chunk(prev))
            if current is not None:
                evs = self._process_chunk(current)
                emitted.extend(evs)
                if decode_mode is not None:
                    # Wall time covers dispatch + device + fetch — exactly
                    # the cost the mode choice trades off.
                    self._spec_observe(
                        decode_mode, len(evs), time.perf_counter() - t0
                    )
            step_s = time.perf_counter() - t0
            # The first tokens of the admissions that rode (none where
            # `current` was reaped above: the loop that rides keeps its
            # chunk in flight), read only now: behind the chunk that
            # carries their rows and behind the reap of the chunk they
            # rode behind, so after its events and a step before any token
            # of theirs that a chunk decodes. No head outlives the step
            # that dispatched it. Like the barrier's admissions, their
            # wait is no part of `step_s`.
            emitted.extend(self._collect_heads())
            # Feed the scheduler's drain-rate estimator: completed
            # requests per second of engine-step wall time. Deadline
            # feasibility and the computed Retry-After both divide queue
            # depth by this rate.
            finished = sum(1 for ev in emitted if ev.finished)
            self._sched.observe_service(finished, step_s)
            # Per-decode-step snapshot for the serve loop's gauges. Plain
            # attribute write (already under the engine lock): the metrics
            # registry is never touched from this hot path. The overlap
            # tail — reaping a chunk whose every row finished last step,
            # emitting nothing, with no work left — must not clobber the
            # final real step's numbers with zeros.
            if (
                emitted
                or self._active
                or len(self._sched)
                or current is not None
                or self._inflight is not None
            ):
                self.last_step_stats = {
                    "batch_size": len(self._active),
                    "waiting": len(self._sched),
                    "tokens": len(emitted),
                    "duration_s": step_s,
                }
            self.profiler.end_step()
            # Record only steps that DID something — an idle poll's
            # all-zero timeline would just dilute the ring. A dispatch-
            # only step (overlap holding its first chunk) counts.
            if (
                emitted
                or current is not None
                or prev is not None
                or self._inflight is not None
            ):
                starved_s, dispatches = book.end_step()
                self.profiler.observe_step(
                    phases,
                    tokens=len(emitted),
                    batch=len(self._active),
                    duration_s=step_s,
                    starved_s=starved_s,
                    dispatches=dispatches,
                )
            return emitted

    def _reap_inflight_locked(
        self, barrier: str = "external"
    ) -> list[StepEvent]:
        """Reap the dispatched-but-unreaped chunk NOW (caller holds the
        engine lock). The conservative barrier behind every mutation that
        must observe the chunk's tokens or slot frees: pending
        admissions, cancel, drain, handoff export/import, prefix-page
        export/import, speculation windows. `barrier` names the cause on
        the `step.reap` span. Returns the chunk's events."""
        inflight = self._inflight
        if inflight is None:
            return []
        self._inflight = None
        return self._process_chunk(inflight, barrier)

    def _barrier_locked(self, launches: bool = False) -> None:
        """Barrier for callers OUTSIDE step() (cancel/drain/handoff/
        prefix paths, under the engine lock): reap the in-flight chunk
        and queue its events for the next step() so no token is lost.
        `launches`: the caller goes on to device work of its own (a
        hand-off's prefill, page gathers and scatters), which the device
        queue's book does not watch: the tail is unknown from here."""
        evs = self._reap_inflight_locked()
        if evs:
            self._pending_events.extend(evs)
        if launches:
            self.device_queue.dispatched(None)

    def inflight_info(self) -> dict | None:
        """Snapshot of the dispatched-but-unreaped chunk for the server
        watchdog: {"dispatched_at": monotonic seconds} or None. Lock-free
        read of an atomically swapped tuple — safe from the watchdog
        thread."""
        inflight = self._inflight
        if inflight is None or len(inflight) < 4:
            return None
        return {"dispatched_at": inflight[3]}

    def _process_chunk(
        self, inflight: tuple, barrier: str = "none"
    ) -> list[StepEvent]:
        """Reap one dispatched chunk: wait for it, read its tokens back
        and emit them. `barrier` is what forced the reap ahead of the
        next dispatch (admission | seq_cap | spec | external), "none" for
        the ordinary reap behind it."""
        toks_seq, chunk_slots = inflight[0], inflight[1]
        span = self.profiler.span
        rows = sum(not req.done for _, req in chunk_slots)
        self.step_reaps[barrier] += 1
        with span("step.reap", rows=rows, chunk=inflight[2], barrier=barrier):
            # What emptied the queue if this chunk is the newest program.
            after = "reap_" + ("sync" if barrier == "none" else barrier)
            if isinstance(toks_seq, tuple) and toks_seq[0] == "spec":
                # The wait for the verify forward, which the window's one
                # fused readback would otherwise hide in its own seconds.
                with span("step.overlap_idle"):
                    jax.block_until_ready(toks_seq[1])
                self.device_queue.waited(toks_seq[1], after)
                return self._process_spec(
                    toks_seq[1], toks_seq[2], chunk_slots
                )
            if not rows:
                return []  # every rider cancelled since dispatch — no transfer
            routes_seq = inflight[4]
            # No slice on the device: it would queue behind the next chunk.
            # Device compute the host could NOT hide: ~the whole device
            # step in the synchronous loop, →0 under perfect overlap.
            with span("step.overlap_idle"):
                jax.block_until_ready(toks_seq)
            self.device_queue.waited(toks_seq, after)
            with span("step.readback"):
                # One transfer: the tokens, a routed family's expert sets
                # and the scalar that says what the chunk's sampler ran.
                toks_seq, routes_seq, pooled = jax.device_get(
                    (toks_seq, routes_seq, inflight[5])
                )
            self.sampler_chunks["pool" if pooled else "argmax"] += 1
            if self._block:
                return self._emit_blocks(toks_seq, routes_seq, chunk_slots)
            asked: list[tuple] = []  # (event index, step, slot, req, position)
            kept: list[tuple[int, int]] = []  # (step, slot) whose token was kept
            with span("step.sample"):
                emitted: list[StepEvent] = []
                for k in range(toks_seq.shape[0]):
                    # One timestamp per fused decode step: its tokens
                    # became host-visible together, so intra-step ITL is
                    # genuinely ~0 and the first token after a chunk
                    # boundary carries the gap.
                    now = _now()
                    for slot, req in chunk_slots:
                        if req.done:
                            continue  # surplus chunk tokens discarded
                        if routes_seq is not None:
                            kept.append((k, slot))
                            if req.route_backlog is not None:
                                asked.append(
                                    (len(emitted), k, slot, req, req.position)
                                )
                        self._emit_token(
                            req, int(toks_seq[k, slot]), now, emitted
                        )
            if routes_seq is not None:
                self._chunk_routes(routes_seq, kept, asked, emitted)
            return emitted

    def _chunk_routes(self, routes_seq, kept, asked, emitted) -> None:
        """The host's work on a reaped chunk's expert sets `[chunk, B,
        routed layers, k]`: count the rows whose tokens were kept (a
        step's surplus past a stop is dropped with its token), and put
        each asking request's row on the event of the token it produced:
        the step's input sat at the request's position before the token."""
        with self.profiler.span(
            "step.routes", rows=len(kept), layers=self.moe["routed_layers"],
            k=self.moe["k"], bytes=routes_seq.nbytes,
            asked=len({entry[3].rid for entry in asked}),
        ):
            if kept:
                steps, slots = np.asarray(kept).T
                self._count_routes(
                    routes_seq[steps, slots], steps, len(routes_seq), "decode"
                )
            for i, k, slot, req, position in asked:
                emitted[i] = emitted[i]._replace(
                    routes=self._hand_routes(
                        req, (position, routes_seq[k, slot][None])
                    )
                )

    def _emit_blocks(self, toks_seq, records, chunk_slots) -> list[StepEvent]:
        """A reaped chunk of a family that fills blocks. `toks_seq`
        [blocks * R, slots] holds each slot's finished blocks in order;
        `records` what every forward of the chunk did, [forwards, slots,
        ...]: `kind` (0 = the slot sat it out, 1 = denoising, 2 = the
        forward that writes a finished block), the block's `start`, the
        rows it committed and to what, and the rows' expert sets.

        A token is served in position order: rows of a request's first
        block below its position are its prompt's and are skipped; rows
        past `max_tokens` are dropped with the forwards of blocks that
        served nothing. The forwards of a block ride on the first token it
        served, for a request that asked; all kept forwards feed the expert
        and block counters."""
        R = self._block["block_length"]
        n_forwards = int(records["forwards"])
        kind = records["kind"][:n_forwards]
        nslots = kind.shape[1]
        base = np.zeros(nslots, np.int64)  # first position of the chunk
        for slot, req in chunk_slots:
            base[slot] = req.position // R * R
        served = np.zeros((toks_seq.shape[0] // R, nslots), bool)
        asked: list[tuple] = []  # (event index, block, slot, req)
        with self.profiler.span("step.sample"):
            emitted: list[StepEvent] = []
            for k in range(toks_seq.shape[0]):
                now = _now()
                for slot, req in chunk_slots:
                    if req.done or base[slot] + k < req.position:
                        continue
                    if not served[k // R, slot]:
                        served[k // R, slot] = True
                        if req.forward_backlog is not None:
                            asked.append((len(emitted), k // R, slot, req))
                    self._emit_token(
                        req, int(toks_seq[k, slot]), now, emitted
                    )
        with self.profiler.span(
            "step.routes", layers=self.moe["routed_layers"], k=self.moe["k"],
            forwards=n_forwards, bytes=records["routes"].nbytes,
            asked=len({entry[3].rid for entry in asked}),
        ) as sp:
            # The block of the chunk each (forward, slot) worked on, and
            # whether that block served a token.
            block_of = (records["start"][:n_forwards] - base[None, :]) // R
            kept = (kind > 0) & np.take_along_axis(
                served, np.clip(block_of, 0, len(served) - 1), axis=0
            )
            at, slots_at = np.nonzero(kept)
            routes = records["routes"]
            if len(at):
                self._count_routes(
                    routes[at, slots_at].reshape(-1, *routes.shape[3:]),
                    np.repeat(at, R), n_forwards, "decode",
                )
            stats = self.block_stats
            stats["denoise"] += int((kind[kept] == 1).sum())
            stats["commit"] += int((kind[kept] == 2).sum())
            stats["tokens"] += int(records["commit"][:n_forwards][kept].sum())
            stats["program_forwards"] += n_forwards
            stats["chunks"] += 1
            for i, block, slot, req in asked:
                mine = np.nonzero(kept[:, slot] & (block_of[:, slot] == block))[0]
                handed = [
                    (
                        int(records["start"][f, slot]), routes[f, slot],
                        tuple(np.nonzero(records["commit"][f, slot])[0].tolist()),
                        tuple(records["tokens"][f, slot][
                            records["commit"][f, slot]].tolist()),
                    )
                    for f in mine
                ]
                emitted[i] = emitted[i]._replace(
                    forwards=(*req.forward_backlog, *handed)
                )
                stats["forwards_sent"] += len(req.forward_backlog) + len(handed)
                req.forward_backlog.clear()
            sp.note(rows=int(len(at)) * R)
        return emitted

    def _emit_token(
        self, req: _Request, tok: int, now: float, emitted: list[StepEvent]
    ) -> bool:
        """Append one decoded token to its request and to `emitted`;
        releases the slot and returns True when it finished the request."""
        if req.t_prev_token:
            self._timing.append(
                ("itl", max(0.0, now - req.t_prev_token), f"rid-{req.rid}")
            )
        elif self._block and not req.out_tokens:
            # A block family's first token comes of a chunk, not of the
            # admission.
            self._timing.append(
                ("ttft", max(0.0, now - req.t_enqueue), f"rid-{req.rid}")
            )
        req.t_prev_token = now
        req.out_tokens.append(tok)
        req.position += 1
        req.last_token = tok
        finished = self._check_stop(req)
        emitted.append(StepEvent(req.rid, tok, finished, req.finish_reason))
        if finished:
            self._release(req)
        return finished

    def _process_spec(
        self, choices, n_emit, chunk_slots
    ) -> list[StepEvent]:
        """Emit each slot's accepted+corrected tokens (1..γ+1 per step).
        A stop mid-window discards the remainder, like chunk surplus."""
        span = self.profiler.span
        # ONE fused transfer for both outputs: two sequential device_get
        # calls would pay the host round trip twice per verify step and
        # charge readback for both (a profiler test pins this to one).
        with span("step.readback"):
            choices, n_emit = jax.device_get((choices, n_emit))
            choices = np.asarray(choices)  # [B, γ+1]
            n_emit = np.asarray(n_emit)  # [B]
        with span("step.sample"):
            emitted: list[StepEvent] = []
            now = _now()  # one verify forward produced the whole window
            for slot, req in chunk_slots:
                if req.done:
                    continue
                self.spec_stats["windows"] += 1
                self.spec_stats["proposed"] += self._spec
                self.spec_stats["accepted"] += int(n_emit[slot]) - 1
                for j in range(int(n_emit[slot])):
                    if self._emit_token(
                        req, int(choices[slot, j]), now, emitted
                    ):
                        break
        return emitted

    def _build_proposals(self) -> np.ndarray:
        """Prompt-lookup proposals [num_slots, γ]: the longest suffix
        n-gram (n = 3, 2, 1) of each active request's context that
        occurred earlier proposes its historical continuation (inactive
        slots get zeros; their results are discarded anyway). Contexts
        are kept in per-request incremental buffers — only newly emitted
        tokens append each step."""
        gamma = self._spec
        out = np.zeros((self.cfg.num_slots, gamma), np.int32)
        for slot, req in self._active.items():
            need = len(req.prompt) + len(req.out_tokens)
            if req.ctx is None or need < req.ctx_len:
                req.ctx = np.empty(
                    self.cfg.max_seq_len + gamma + 2, np.int32
                )
                base = req.prompt + req.out_tokens
                req.ctx[: len(base)] = base
                req.ctx_len = len(base)
                req.ngram_idx = {n: {} for n in (3, 2, 1)}
                req.ngram_upto = {n: 0 for n in (3, 2, 1)}
            elif req.ctx_len < need:
                fresh = req.out_tokens[req.ctx_len - len(req.prompt):]
                req.ctx[req.ctx_len:need] = fresh
                req.ctx_len = need
            out[slot] = self._ngram_propose_indexed(req, gamma)
        return out

    @staticmethod
    def _ngram_propose_indexed(req: _Request, gamma: int) -> np.ndarray:
        """O(γ)-per-step lookup: the last-occurrence index is extended
        only over the window starts added since the previous step."""
        ctx, L = req.ctx, req.ctx_len
        for n in (3, 2, 1):
            if L <= n:
                continue
            s = L - n  # the suffix's own start — never indexed
            idx = req.ngram_idx[n]
            for i in range(req.ngram_upto[n], s):
                idx[tuple(ctx[i : i + n].tolist())] = i
            req.ngram_upto[n] = s
            hit = idx.get(tuple(ctx[s:L].tolist()))
            if hit is not None:
                start = hit + n
                prop = ctx[start : min(start + gamma, L)]
                if len(prop):
                    pad = np.full(
                        gamma - len(prop), prop[-1], np.int32
                    )
                    return np.concatenate([prop, pad])
        return np.full(gamma, int(ctx[L - 1]), np.int32)

    @staticmethod
    def _ngram_propose(ctx: np.ndarray, gamma: int) -> np.ndarray:
        L = len(ctx)
        for n in (3, 2, 1):
            if L <= n:
                continue
            suffix = ctx[-n:]
            windows = np.lib.stride_tricks.sliding_window_view(ctx, n)
            hits = np.flatnonzero((windows == suffix).all(axis=1))
            hits = hits[hits < L - n]  # exclude the suffix itself
            if len(hits):
                start = int(hits[-1]) + n
                prop = ctx[start : start + gamma]
                if len(prop):
                    pad = np.full(gamma - len(prop), prop[-1], np.int32)
                    return np.concatenate([prop, pad])
        return np.full(gamma, int(ctx[-1]), np.int32)  # repeat-last fallback

    # ---- LoRA adapter admin (reference: internal/vllmclient/client.go) ------

    def loaded_adapters(self) -> list[str]:
        return sorted(self._adapter_slots)

    def load_adapter(self, name: str, adapter_weights: dict) -> None:
        """Install adapter weights into a free buffer slot. Weights:
        {target: (A [NL, in, r], B [NL, r, out])} with r <= max_lora_rank.
        Scaling (alpha/r) must already be folded into B."""
        if self._lora is None:
            raise ValueError("LoRA is disabled (max_adapters=0)")
        with self._lock:
            if name in self._adapter_slots:
                slot = self._adapter_slots[name]
                if self._adapter_in_use_locked(slot):
                    # Overwriting the slot would flip in-flight streams to
                    # the new weight version mid-generation — same hazard
                    # unload_adapter refuses.
                    raise RuntimeError(
                        f"adapter {name!r} has in-flight requests; retry "
                        "after they finish"
                    )
            else:
                if not self._adapter_free:
                    raise RuntimeError(
                        f"adapter capacity ({self.cfg.max_adapters}) exhausted"
                    )
                slot = self._adapter_free.pop(0)
            r_max = self.cfg.max_lora_rank
            for target, (A, B) in adapter_weights.items():
                if target not in self._lora:
                    raise KeyError(f"unknown LoRA target {target!r}")
                A = jnp.asarray(A)
                B = jnp.asarray(B)
                r = A.shape[-1]
                if r > r_max:
                    raise ValueError(
                        f"adapter rank {r} > max_lora_rank {r_max}"
                    )
                bufA = self._lora[target]["A"]
                bufB = self._lora[target]["B"]
                if r == r_max:
                    # Already slot-shaped (e.g. the lockstep broadcast
                    # payload pads to r_max before shipping).
                    padA = A.astype(bufA.dtype)
                    padB = B.astype(bufB.dtype)
                else:
                    padA = jnp.zeros(bufA.shape[1:], bufA.dtype).at[
                        ..., :r
                    ].set(A.astype(bufA.dtype))
                    padB = jnp.zeros(bufB.shape[1:], bufB.dtype).at[
                        :, :r, :
                    ].set(B.astype(bufB.dtype))
                self._lora[target]["A"] = bufA.at[slot].set(padA)
                self._lora[target]["B"] = bufB.at[slot].set(padB)
            self._adapter_slots[name] = slot
            # New weights in this slot index: prefix-cache entries hashed
            # under the old generation must never hit again.
            self._adapter_gen[slot] = self._adapter_gen.get(slot, 0) + 1

    def adapter_in_use(self, name: str) -> bool:
        """True when the adapter is loaded and any pending/active request
        references it. Advisory (state can change after return) — the
        load/unload guards re-check under the lock; callers use it to
        skip expensive work (e.g. weight downloads) that a 409 would
        discard."""
        if self._lora is None:
            return False
        with self._lock:
            slot = self._adapter_slots.get(name)
            return slot is not None and self._adapter_in_use_locked(slot)

    def _adapter_in_use_locked(self, slot: int) -> bool:
        """True when any pending/active request references the adapter
        slot. Caller holds self._lock (step() holds it for its whole
        body, so mid-admission requests can't be missed). Shared by the
        load/unload guards here and LockstepEngine's pre-broadcast
        mirror."""
        return any(
            r.adapter_idx == slot for r in self._sched.items()
        ) or any(r.adapter_idx == slot for r in self._active.values())

    def unload_adapter(self, name: str) -> bool:
        if self._lora is None or name not in self._adapter_slots:
            return False
        with self._lock:
            slot = self._adapter_slots.get(name)
            if slot is None:
                return False
            # Refuse while any request still decodes (or waits to decode)
            # with this adapter: zeroing the slot would silently flip the
            # stream to base-model output, and a subsequent load could
            # reassign the slot to a DIFFERENT adapter mid-stream.
            if self._adapter_in_use_locked(slot):
                raise RuntimeError(
                    f"adapter {name!r} has in-flight requests; retry after "
                    "they finish"
                )
            del self._adapter_slots[name]
            self._adapter_gen[slot] = self._adapter_gen.get(slot, 0) + 1
            for target in self._lora:
                bufA = self._lora[target]["A"]
                bufB = self._lora[target]["B"]
                self._lora[target]["A"] = bufA.at[slot].set(
                    jnp.zeros(bufA.shape[1:], bufA.dtype)
                )
                self._lora[target]["B"] = bufB.at[slot].set(
                    jnp.zeros(bufB.shape[1:], bufB.dtype)
                )
            self._adapter_free.append(slot)
            return True

    def generate(
        self,
        prompts: list[list[int]],
        params: SamplingParams | None = None,
        adapter: str | None = None,
    ) -> list[list[int]]:
        """Blocking batch generation (tests/benchmarks)."""
        rids = [self.add_request(p, params, adapter=adapter) for p in prompts]
        collected: dict[int, list[int]] = {r: [] for r in rids}
        while self.has_work():
            for ev in self.step():
                if ev.rid in collected:
                    collected[ev.rid].append(ev.token)
        return [collected[r] for r in rids]
