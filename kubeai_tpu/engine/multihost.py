"""Multi-host lockstep serving: one engine program, N processes.

JAX multi-controller SPMD requires EVERY process to enter the same jitted
computation in the same order. Requests, however, arrive only at host 0
(the operator exposes only host 0 to the LB). The bridge is op
BROADCAST: host 0 buffers control ops (admissions, cancels), and each
step() broadcasts a fixed-shape descriptor to all processes via
`multihost_utils.broadcast_one_to_all` (itself a collective every
process enters — workers block there until host 0 acts). All processes
then apply the SAME ops to their local Engine replica and run the SAME
engine.step(): the jitted collectives line up across the slice.

Determinism requirements this module enforces:
  - request ids: all processes call inner.add_request in broadcast
    order, so rid sequences match;
  - sampling seeds: resolved ON HOST 0 (explicit seed or drawn once) and
    shipped in the descriptor — never derived from per-process entropy;
  - page allocation (paged cache): the allocator is a deterministic
    free-list, so identical op streams yield identical block tables on
    every host.

LoRA hot-swap IS lockstep: host 0's admin call broadcasts a control
descriptor carrying the op + adapter name, then (for loads) one
fixed-shape weight payload — adapter A/B matrices zero-padded to
max_lora_rank, so every adapter broadcasts with identical shapes and
the zero padding contributes nothing to the delta. Every process then
installs the same weights into the same buffer slot (slot assignment is
deterministic under identical op order). The broadcast happens INSIDE
load_adapter under the same I/O lock step() holds across its
descriptor→tokens→engine.step() sequence, so the global collective
order stays identical on every process.

The serving analog is JetStream/MaxText-style multihost orchestration;
the reference has no counterpart (one-Pod-per-replica,
pod_plan.go:28-156 — engine-internal distribution lives in vLLM images).
"""

from __future__ import annotations

import dataclasses
import logging
import threading

import numpy as np

from kubeai_tpu.engine.engine import Engine, StepEvent
from kubeai_tpu.engine.sampling import SamplingParams

logger = logging.getLogger(__name__)

MAX_ADMITS = 8  # ops per step (excess stays buffered for the next step)
MAX_CANCELS = 32
# meta columns: plen, seed(int32 bit-cast), top_k, adapter_idx, max_tokens
_META_COLS = 5


@dataclasses.dataclass
class _PendingAdd:
    vrid: int  # the virtual rid handed to the caller
    tokens: list[int]
    params: SamplingParams
    adapter_idx: int = 0
    # Name kept alongside the resolved index so unload_adapter can refuse
    # while this admission is still buffered (the index must stay valid
    # until it broadcasts).
    adapter_name: str | None = None
    cancelled: bool = False


# header[4] adapter op codes
_ADAPTER_NONE, _ADAPTER_LOAD, _ADAPTER_UNLOAD = 0, 1, 2
_ADAPTER_NAME_BYTES = 64


def _control_zeros() -> dict:
    """The per-step control descriptor — small (a few hundred bytes), so
    the common no-admission decode step stays cheap on DCN. The padded
    token matrix broadcasts in a SECOND collective only when
    n_admits > 0 (both sides branch on the same header, so the
    collective sequence stays identical across processes); adapter LOAD
    ops likewise trigger a second, fixed-shape weight broadcast."""
    return {
        # n_admits, n_cancels, step, stop, adapter_op
        "header": np.zeros((5,), np.int32),
        "meta": np.zeros((MAX_ADMITS, _META_COLS), np.int32),
        "floats": np.zeros((MAX_ADMITS, 2), np.float32),  # temp, top_p
        "cancels": np.zeros((MAX_CANCELS,), np.int32),
        "adapter_name": np.zeros((_ADAPTER_NAME_BYTES,), np.uint8),
    }


def _encode_name(name: str) -> np.ndarray:
    raw = name.encode("utf-8")
    if len(raw) > _ADAPTER_NAME_BYTES:
        raise ValueError(
            f"adapter name longer than {_ADAPTER_NAME_BYTES} utf-8 bytes"
        )
    buf = np.zeros((_ADAPTER_NAME_BYTES,), np.uint8)
    buf[: len(raw)] = np.frombuffer(raw, np.uint8)
    return buf


def _decode_name(buf: np.ndarray) -> str:
    return bytes(buf).rstrip(b"\x00").decode("utf-8")


def _lora_payload_zeros(engine: Engine) -> dict:
    """Fixed-shape weight payload: one {target.A/.B} float32 array pair
    per LoRA target, shaped like one buffer slot (rank = max_lora_rank).
    Identical construction on every process ⇒ identical broadcast
    shapes."""
    out = {}
    for target, bufs in engine._lora.items():
        out[target + ".A"] = np.zeros(bufs["A"].shape[1:], np.float32)
        out[target + ".B"] = np.zeros(bufs["B"].shape[1:], np.float32)
    return out


def _payload_to_weights(engine: Engine, payload: dict) -> dict:
    return {
        target: (payload[target + ".A"], payload[target + ".B"])
        for target in engine._lora
    }


def _broadcast(desc, is_source: bool):
    from jax.experimental import multihost_utils

    out = multihost_utils.broadcast_one_to_all(desc, is_source=is_source)
    if isinstance(out, dict):
        return {k: np.asarray(v) for k, v in out.items()}
    return np.asarray(out)


class LockstepEngine:
    """Engine facade for HOST 0: buffers ops, broadcasts them inside
    step(), and drives the inner engine exactly like every worker drives
    theirs. Exposes the Engine surface EngineServer consumes."""

    is_lockstep = True  # server gates non-lockstep paths (embeddings)

    def __init__(self, inner: Engine):
        # Overlapped stepping cannot run under lockstep: every host must
        # replay the SAME op/step sequence, and an unreaped chunk on host
        # 0 would reorder its broadcast schedule relative to the workers'.
        inner._overlap = False
        self.inner = inner
        self._lock = threading.Lock()
        # Serializes every broadcast SEQUENCE (a step's descriptor→
        # tokens→engine.step(), an adapter op's descriptor→payload, a
        # shutdown) so the global collective order is identical on every
        # process.
        self._io_lock = threading.RLock()
        self._adds: list[_PendingAdd] = []
        self._cancels: list[int] = []
        # Cancels that raced step(): their admission batch was popped
        # from _adds but its _rid_map entries weren't populated yet.
        # Resolved at the top of the next step().
        self._unresolved_cancels: list[int] = []
        self._next_virtual_rid = 0
        # virtual rid (handed to callers before broadcast) -> inner rid
        self._rid_map: dict[int, int] = {}
        self._entropy = np.random.default_rng()

    # -- Engine surface used by EngineServer ----------------------------------

    @property
    def cfg(self):
        return self.inner.cfg

    @property
    def family(self):
        return self.inner.family

    @property
    def model_cfg(self):
        return self.inner.model_cfg

    @property
    def params(self):
        return self.inner.params

    @property
    def num_pending(self) -> int:
        with self._lock:
            return len(self._adds) + self.inner.num_pending

    @property
    def num_active(self) -> int:
        return self.inner.num_active

    def _bucket(self, n: int) -> int:
        return self.inner._bucket(n)

    def loaded_adapters(self) -> list[str]:
        return self.inner.loaded_adapters()

    def adapter_in_use(self, name: str) -> bool:
        """Engine-surface parity: the server pre-checks this before
        fetching reload weights. Advisory, like Engine.adapter_in_use."""
        return self.inner.adapter_in_use(name)

    def load_adapter(self, name: str, adapter_weights: dict) -> None:
        """Lockstep adapter install: broadcast the op + padded weights to
        every process, then install locally. Synchronous — returns once
        this process has installed (workers install on their own receive,
        strictly before their next engine collective)."""
        if self.inner._lora is None:
            raise ValueError("LoRA is disabled (max_adapters=0)")
        name_buf = _encode_name(name)
        payload = _lora_payload_zeros(self.inner)
        r_max = self.cfg.max_lora_rank
        for target, (A, B) in adapter_weights.items():
            if target + ".A" not in payload:
                raise KeyError(f"unknown LoRA target {target!r}")
            A = np.asarray(A, np.float32)
            B = np.asarray(B, np.float32)
            r = A.shape[-1]
            if r > r_max:
                raise ValueError(f"adapter rank {r} > max_lora_rank {r_max}")
            # Zero-pad rank to r_max: fixed broadcast shapes, and the
            # padding contributes nothing to x@A@B.
            payload[target + ".A"][..., :r] = A
            payload[target + ".B"][:, :r, :] = B
        desc = _control_zeros()
        desc["header"][4] = _ADAPTER_LOAD
        desc["adapter_name"] = name_buf
        with self._io_lock:
            # Capacity must be validated BEFORE any broadcast: a
            # post-broadcast raise would leave workers' loops dead (or
            # diverged) and the next step() collective hanging.
            if (
                name not in self.inner._adapter_slots
                and not self.inner._adapter_free
            ):
                raise RuntimeError(
                    f"adapter capacity ({self.cfg.max_adapters}) exhausted"
                )
            slot = self.inner._adapter_slots.get(name)
            if slot is not None and self.inner._adapter_in_use_locked(slot):
                # Same-name reload would overwrite weights under in-flight
                # streams; refuse BEFORE the broadcast (pre-broadcast
                # mirror of Engine.load_adapter's guard).
                raise RuntimeError(
                    f"adapter {name!r} has in-flight requests; retry "
                    "after they finish"
                )
            _broadcast(desc, is_source=True)
            payload = _broadcast(payload, is_source=True)
            self.inner.load_adapter(
                name, _payload_to_weights(self.inner, payload)
            )

    def unload_adapter(self, name: str) -> bool:
        if self.inner._lora is None or name not in self.inner._adapter_slots:
            return False
        desc = _control_zeros()
        desc["header"][4] = _ADAPTER_UNLOAD
        desc["adapter_name"] = _encode_name(name)
        with self._io_lock:
            # Buffered admissions hold a resolved slot index; unloading
            # now could let a subsequent load reassign that slot to a
            # DIFFERENT adapter before the admission broadcasts —
            # silently decoding with the wrong weights. Refuse instead.
            # _lock is held across the guard AND the broadcast+unload so
            # add_request can't resolve the slot in between; _io_lock is
            # held by step() across its _adds pop, so a popped-but-not-
            # yet-broadcast batch can't slip past the scan either.
            with self._lock:
                if any(
                    a.adapter_name == name and not a.cancelled
                    for a in self._adds
                ):
                    raise RuntimeError(
                        f"adapter {name!r} has queued requests; retry after "
                        "they admit"
                    )
                slot = self.inner._adapter_slots.get(name)
                if slot is not None and self.inner._adapter_in_use_locked(
                    slot
                ):
                    # Pre-broadcast mirror of Engine.unload_adapter's
                    # in-use refusal: raising AFTER the broadcast would
                    # leave every process refusing identically (states
                    # stay consistent) but wastes a collective round.
                    raise RuntimeError(
                        f"adapter {name!r} has in-flight requests; retry "
                        "after they finish"
                    )
                _broadcast(desc, is_source=True)
                return self.inner.unload_adapter(name)

    def has_work(self) -> bool:
        with self._lock:
            return bool(self._adds or self._cancels) or self.inner.has_work()

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
    ) -> int:
        if resume_tokens:
            # Continuation admission would have to replay the resume
            # prefix identically on every host; until the descriptor
            # carries it, multi-host replicas refuse and the proxy falls
            # back to the terminal-error tail.
            raise ValueError(
                "stream resume is not supported on multi-host replicas"
            )
        # Scheduling args are accepted for API parity with Engine but not
        # broadcast: lockstep admission must replay in identical order on
        # every host, so multi-host replicas keep FIFO ordering (every
        # inner scheduler sees the same default-class submissions and WFQ
        # degenerates to arrival order). Queue-full shedding still
        # applies at the HTTP layer; per-class precedence and deadline
        # shedding are single-host features for now.
        params = params or SamplingParams()
        if adapter and self.inner._lora is None:
            raise ValueError("LoRA is disabled (max_adapters=0)")
        if len(prompt_tokens) == 0:
            raise ValueError("empty prompt")
        if len(prompt_tokens) >= self.inner.cfg.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt_tokens)} >= max_seq_len "
                f"{self.inner.cfg.max_seq_len}"
            )
        # Seeds ship in the descriptor: resolve host-0-side once, masked
        # to 32 bits (clients may send negative / >32-bit seeds; the
        # inner engine masks too, so the fold-in value stays identical).
        seed = (
            params.seed
            if params.seed is not None
            else int(self._entropy.integers(0, 2**31 - 1))
        )
        params = dataclasses.replace(params, seed=seed & 0xFFFFFFFF)
        with self._lock:
            # Resolve to the inner slot index under _lock so it serializes
            # with unload_adapter (which buys _lock for its entire
            # guard→broadcast→unload sequence): either this admission is
            # appended first (the unload guard sees it and refuses) or the
            # unload completes first (the adapter is gone and we raise).
            # The index is deterministic across processes — identical
            # adapter-op order assigns identical slots; the descriptor
            # ships the index.
            adapter_idx = 0
            if adapter:
                slot = self.inner._adapter_slots.get(adapter)
                if slot is None:
                    raise KeyError(f"adapter {adapter!r} not loaded")
                adapter_idx = slot
            rid = self._next_virtual_rid
            self._next_virtual_rid += 1
            if on_admit is not None:
                # Same contract as Engine.add_request: registration is
                # visible before any step can emit events for this rid.
                on_admit(rid)
            self._adds.append(
                _PendingAdd(
                    rid, list(prompt_tokens), params, adapter_idx,
                    adapter or None,
                )
            )
            return rid

    def cancel(self, rid: int) -> bool:
        with self._lock:
            inner_rid = self._rid_map.pop(rid, None)
            if inner_rid is None:
                # Not yet broadcast: tombstone the buffered entry.
                for add in self._adds:
                    if add.vrid == rid and not add.cancelled:
                        add.cancelled = True
                        return True
                if 0 <= rid < self._next_virtual_rid:
                    # Mid-step race: the admission batch holding this rid
                    # is being broadcast right now (popped from _adds, not
                    # yet in _rid_map) — or the request already finished.
                    # Defer; step() resolves or discards it.
                    self._unresolved_cancels.append(rid)
                    return True
                return False
            # Mapping pruned here: a cancelled request emits no further
            # events (the inner engine releases it on cancel), so keeping
            # the entry would only leak.
            self._cancels.append(inner_rid)
            return True

    def step(self) -> list[StepEvent]:
        """One lockstep iteration: broadcast buffered ops, apply, step.

        _io_lock is held from BEFORE the _adds pop: once an admission
        batch leaves the buffer its resolved adapter indices must stay
        valid until they broadcast, and unload_adapter (which serializes
        on _io_lock) could otherwise free a slot in that window after
        its buffered-admission scan found _adds already empty."""
        with self._io_lock:
            return self._step_locked()

    def _step_locked(self) -> list[StepEvent]:
        with self._lock:
            # Resolve cancels that raced the previous step's broadcast
            # window: by now (single stepping thread) their rids are
            # mapped, back in the buffer, or gone (finished) — gone ones
            # are discarded.
            for vrid in self._unresolved_cancels:
                inner = self._rid_map.pop(vrid, None)
                if inner is not None:
                    self._cancels.append(inner)
                    continue
                for add in self._adds:
                    if add.vrid == vrid and not add.cancelled:
                        add.cancelled = True
                        break
            self._unresolved_cancels = []
            batch = self._adds[:MAX_ADMITS]
            self._adds = self._adds[MAX_ADMITS:]
            cancels = self._cancels[:MAX_CANCELS]
            self._cancels = self._cancels[MAX_CANCELS:]
        live = [a for a in batch if not a.cancelled]
        desc = _control_zeros()
        desc["header"][0] = len(live)
        desc["header"][1] = len(cancels)
        desc["header"][2] = 1  # run a decode step
        for i, add in enumerate(live):
            desc["meta"][i] = [
                len(add.tokens),
                np.uint32(add.params.seed).view(np.int32),
                add.params.top_k,
                add.adapter_idx,
                add.params.max_tokens,
            ]
            desc["floats"][i] = [add.params.temperature, add.params.top_p]
        desc["cancels"][: len(cancels)] = cancels

        with self._io_lock:
            out = _broadcast(desc, is_source=True)
            tokens = None
            if live:  # second, payload-sized collective only on admissions
                tokens = np.zeros(
                    (MAX_ADMITS, self.inner.cfg.max_seq_len), np.int32
                )
                for i, add in enumerate(live):
                    tokens[i, : len(add.tokens)] = add.tokens
                tokens = _broadcast(tokens, is_source=True)
            inner_rids = _apply_descriptor(
                self.inner, out, tokens, do_step=False
            )
            with self._lock:
                for add, inner_rid in zip(live, inner_rids):
                    self._rid_map[add.vrid] = inner_rid
            events = self.inner.step()
        # Map inner rids back to the virtual rids callers hold; prune
        # finished mappings so the table doesn't grow unboundedly.
        with self._lock:
            inv = {v: k for k, v in self._rid_map.items()}
            # Events whose inner rid has no live mapping (cancelled mid
            # step) are DROPPED — falling back to the raw inner rid could
            # deliver tokens to a different request's subscriber once the
            # virtual and inner sequences diverge.
            mapped = [
                StepEvent(inv[ev.rid], ev.token, ev.finished,
                          ev.finish_reason)
                for ev in events
                if ev.rid in inv
            ]
            for ev in events:
                if ev.finished and ev.rid in inv:
                    self._rid_map.pop(inv[ev.rid], None)
        return mapped

    def generate(self, prompts, params=None):
        """Convenience parity with Engine.generate (tests)."""
        outs: dict[int, list[int]] = {}
        rids = [self.add_request(p, params) for p in prompts]
        for r in rids:
            outs[r] = []
        while self.has_work():
            for ev in self.step():
                if ev.rid in outs and ev.token is not None:
                    outs[ev.rid].append(ev.token)
        return [outs[r] for r in rids]

    def shutdown(self) -> None:
        """Release the workers (they exit their loop)."""
        desc = _control_zeros()
        desc["header"][3] = 1
        with self._io_lock:
            _broadcast(desc, is_source=True)


def _apply_descriptor(
    engine: Engine, desc: dict, tokens, do_step: bool
) -> list[int]:
    """Apply a broadcast descriptor to the local engine replica. Returns
    the inner rids assigned to this step's admissions (same on every
    process, by construction)."""
    n_admits = int(desc["header"][0])
    n_cancels = int(desc["header"][1])
    # adapter_idx → name (slot assignment is deterministic, so the map
    # is identical on every process).
    slot_names = (
        {v: k for k, v in engine._adapter_slots.items()}
        if engine._lora is not None
        else {}
    )
    rids = []
    for i in range(n_admits):
        plen, seed_bits, top_k, adapter_idx, max_tokens = (
            int(x) for x in desc["meta"][i]
        )
        temp, top_p = (float(x) for x in desc["floats"][i])
        params = SamplingParams(
            temperature=temp,
            top_k=top_k,
            top_p=top_p,
            max_tokens=max_tokens,
            seed=int(np.int32(seed_bits).view(np.uint32)),
        )
        rids.append(
            engine.add_request(
                list(tokens[i, :plen]), params,
                adapter=slot_names.get(adapter_idx),
            )
        )
    for i in range(n_cancels):
        engine.cancel(int(desc["cancels"][i]))
    if do_step and int(desc["header"][2]):
        engine.step()
    return rids


def worker_loop(engine: Engine) -> None:
    """WORKER processes (process_id > 0): receive descriptors forever,
    mirror host 0's ops and steps. Blocks inside the broadcast collective
    while host 0 is idle."""
    # A worker replays host 0's synchronous loop step for step
    # (`LockstepEngine` forces it there): a worker that kept a chunk in
    # flight would admit behind it, and grant slots and pages from a state
    # one reap behind host 0's.
    engine._overlap = False
    logger.info("multihost worker loop running")
    while True:
        desc = _broadcast(_control_zeros(), is_source=False)
        if int(desc["header"][3]):
            logger.info("multihost worker loop: shutdown")
            return
        adapter_op = int(desc["header"][4])
        if adapter_op == _ADAPTER_LOAD:
            payload = _broadcast(
                _lora_payload_zeros(engine), is_source=False
            )
            # Host 0 validated capacity before broadcasting; a local
            # failure here means state divergence — log loudly but keep
            # the loop alive (a dead worker hangs the whole slice's next
            # collective).
            try:
                engine.load_adapter(
                    _decode_name(desc["adapter_name"]),
                    _payload_to_weights(engine, payload),
                )
            except Exception:
                logger.exception("lockstep adapter load failed on worker")
        elif adapter_op == _ADAPTER_UNLOAD:
            try:
                engine.unload_adapter(_decode_name(desc["adapter_name"]))
            except Exception:
                logger.exception("lockstep adapter unload failed on worker")
        tokens = None
        if int(desc["header"][0]):
            tokens = _broadcast(
                np.zeros((MAX_ADMITS, engine.cfg.max_seq_len), np.int32),
                is_source=False,
            )
        _apply_descriptor(engine, desc, tokens, do_step=True)
