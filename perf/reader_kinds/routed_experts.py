"""A reader kind: the grouped products of a routed family's held experts inside
its decode chunk, against the roofline, where not every layer has a router.

    {"reader": "routed_experts", "module": <regex of the decode chunk>,
     "ops": <regex of the grouped products>}

As `hybrid_decode`'s `experts_roofline` (PR 43) and `moe_experts_roofline` (PR
38) read theirs: the seconds of the matching operations inside the matching
programs that ran WHOLE inside the slice; a forward runs the products of every
ROUTED layer (the reference's `routed_layers`: a leading dense layer has none)
and needs, a layer, the larger of its touched weights' bytes over the HBM
bandwidth and its FLOPs over the MXU peak. The held experts that hold a row
in a layer are MEASURED (`kubeai_engine_moe_experts_touched_total` over
`kubeai_engine_moe_passes_total`, kind `decode`), the rows are the products'
own leading dimension over `num_experts_per_tok`, and the forwards are the
whole chunks times the engine's `decode_chunk`.

Nothing without a trace, the programs, the products, the counters or a
reference that says its routed layers; nothing raises on a program that has
none of them."""

from perf import costs, trace_reduce
from perf.reader_kinds.hybrid_decode import experts_touched, leading_mean


def read(spec, obs):
    tr, reference = obs.get("trace"), obs.get("reference")
    if not tr or not hasattr(reference, "routed_layers"):
        return None
    ops = trace_reduce.ops_in(tr, spec["ops"], spec["module"])
    seconds = sum(op["total_s"] for op in ops.values())
    touched = experts_touched(obs)
    chunks, _ = trace_reduce.module_stats(tr, spec["module"], whole=True)
    assignments = leading_mean(ops)
    if seconds <= 0 or not touched or not chunks or not assignments:
        return None
    hf = obs["hf"]
    rows = assignments / hf["num_experts_per_tok"]
    layer = max(
        costs.of(reference, "moe_experts_bytes")(hf, touched)
        / obs["peaks"]["hbm_bytes_per_s"],
        costs.of(reference, "moe_experts_flops")(hf, rows)
        / obs["peaks"]["bf16_flops_per_s"])
    forwards = chunks * obs["engine"]["decode_chunk"]
    return 100.0 * reference.routed_layers(hf) * forwards * layer / seconds
