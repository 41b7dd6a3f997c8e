"""A reader kind for a family with recurrent state beside its pages and an
expert share: its decode step, and the step's two kernels, against the
roofline.

    {"reader": "hybrid_decode", "what": "hbm_share" | "state_roofline" |
     "experts_roofline", "module": <regex of the decode chunk>,
     "ops": <regex of the kernel: the state update, or the grouped products>}

Every count comes from the trace or the counters. The slots a step ran over
and the rows a grouped product took are the leading dimension of what the
operation returns (`_gdn_update_pallas.1 f32[64,32,128]` ran over 64 slots,
`gmm.3 bf16[640,512]` over 640 assignments). The held experts that hold a row
in a layer are MEASURED over kept rows
(`kubeai_engine_moe_experts_touched_total` over `kubeai_engine_moe_passes_total`,
kind `decode`, which counts held experts only), so no share is counted high.

`hbm_share`: the least time to stream one decode step's bytes (the
reference's `hybrid_decode_bytes`: the touched held experts, the other weights
once, the state read and written for the slots the state kernel ran over, the
resident keys and values as the pool held them over the window) over the mean
device time of the matching programs a step of the chunk (`decode_chunk`).

`state_roofline`: `gdn_update_bytes` (every recurrent state of those slots
read once and written once, in each layer that has one) over the HBM peak,
over the seconds of the matching operations inside the matching programs. The
kernel runs once a layer that has state, so its runs over those layers (the
configuration's) are the steps. It is bound by HBM alone: 2 FLOPs a state
element at most 8 bytes apart.

`experts_roofline`: as `moe_experts_roofline` (PR 38) reads a block family's,
the seconds of the grouped products inside the matching programs that ran
WHOLE inside the slice; a forward runs the products of every layer and needs
the larger of their touched weights' bytes over the HBM bandwidth and their
FLOPs over the MXU peak. This family's chunk is `decode_chunk` forwards, one a
step, so the forwards are the whole chunks times the engine's setting and no
block counter is asked.

Nothing without a trace, the programs, the kernel or (where asked) the
counters; nothing raises on a program that has none of them."""

import re

from perf import costs, readers, trace_reduce

TOUCHED = "kubeai_engine_moe_experts_touched_total"
PASSES = "kubeai_engine_moe_passes_total"
DECODE = {"kind": {"decode"}}
_LEADING = re.compile(r" \w+\[(\d+)[,\]]")


def leading_mean(ops: dict) -> float | None:
    """The mean leading dimension of what the operations return, over the
    times they ran (None where no name carries a shape)."""
    ran = total = 0
    for name, op in ops.items():
        leading = _LEADING.search(name)
        if leading:
            ran += op["count"]
            total += op["count"] * int(leading.group(1))
    return total / ran if ran else None


def experts_touched(obs) -> float | None:
    """Held experts a decode pass routes rows to in a layer, over the window."""
    passes = readers.delta(obs, PASSES, DECODE)
    return readers.delta(obs, TOUCHED, DECODE) / passes if passes > 0 else None


def hbm_share(spec, obs, ops, hbm):
    used, touched = obs["polled"].get("kv_tokens") or [], experts_touched(obs)
    mean, slots = readers._module_mean_s(spec, obs), leading_mean(ops)
    if not used or not touched or mean is None or not slots:
        return None
    need = costs.of(obs["reference"], "hybrid_decode_bytes")(
        obs["hf"], sum(used) / len(used), touched, slots)
    return 100.0 * need / hbm / (mean / obs["engine"]["decode_chunk"])


def state_roofline(spec, obs, ops, hbm):
    seconds, slots = sum(op["total_s"] for op in ops.values()), leading_mean(ops)
    if seconds <= 0 or not slots:
        return None
    s = obs["reference"].sizes(obs["hf"])
    steps = sum(op["count"] for op in ops.values()) / (s["NL"] - s["periods"])
    need = costs.of(obs["reference"], "gdn_update_bytes")(obs["hf"], slots)
    return 100.0 * steps * need / hbm / seconds


def experts_roofline(spec, obs, ops, hbm):
    seconds, touched = sum(op["total_s"] for op in ops.values()), experts_touched(obs)
    chunks, _ = trace_reduce.module_stats(obs["trace"], spec["module"], whole=True)
    assignments = leading_mean(ops)
    if seconds <= 0 or not touched or not chunks or not assignments:
        return None
    hf, reference = obs["hf"], obs["reference"]
    rows = assignments / hf["num_experts_per_tok"]
    forward = max(
        costs.of(reference, "moe_experts_bytes")(hf, touched) / hbm,
        costs.of(reference, "moe_experts_flops")(hf, rows)
        / obs["peaks"]["bf16_flops_per_s"])
    forwards = chunks * obs["engine"]["decode_chunk"]
    return 100.0 * hf["num_hidden_layers"] * forwards * forward / seconds


WHAT = {f.__name__: f for f in (hbm_share, state_roofline, experts_roofline)}


def read(spec, obs):
    tr = obs.get("trace")
    if not tr or not hasattr(obs.get("reference"), "hybrid_decode_bytes"):
        return None
    ops = trace_reduce.ops_in(tr, spec["ops"], spec["module"])
    return WHAT[spec["what"]](spec, obs, ops, obs["peaks"]["hbm_bytes_per_s"])
