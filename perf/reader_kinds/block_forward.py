"""A reader kind for a family that generates by blocks: one FORWARD of the
model over every slot's block, where a decode chunk is a number of forwards
that the program decides (`kubeai_engine_block_program_forwards_total` over
`kubeai_engine_block_chunks_total`, both over the window).

    {"reader": "block_forward", "what": "ms" | "hbm_share", "module": <regex>}

`ms`: the mean device time of the matching programs over the forwards a
chunk ran. `hbm_share`: the least time to stream one forward's bytes (the
reference's `block_forward_bytes`: the experts at least one row is routed
to, the rest of the weights, the resident keys and values) over that time,
in per cent. The experts a forward touches in a layer are measured
(`kubeai_engine_moe_experts_touched_total` over
`kubeai_engine_moe_passes_total`, kind `decode`: over kept rows, so never
more than the program read). Nothing without a trace, the programs or the
counters."""

from perf import costs, readers, trace_reduce

FORWARDS = "kubeai_engine_block_program_forwards_total"
CHUNKS = "kubeai_engine_block_chunks_total"
TOUCHED = "kubeai_engine_moe_experts_touched_total"
PASSES = "kubeai_engine_moe_passes_total"
DECODE = {"kind": {"decode"}}


def forwards_per_chunk(obs):
    chunks = readers.delta(obs, CHUNKS)
    return readers.delta(obs, FORWARDS) / chunks if chunks > 0 else None


def experts_touched(obs):
    """Experts a decode forward routes rows to in a layer, over the window."""
    passes = readers.delta(obs, PASSES, DECODE)
    return readers.delta(obs, TOUCHED, DECODE) / passes if passes > 0 else None


def forward_seconds(spec, obs):
    tr, per_chunk = obs.get("trace"), forwards_per_chunk(obs)
    if not tr or not per_chunk:
        return None
    n, total = trace_reduce.module_stats(tr, spec["module"])
    return total / n / per_chunk if n else None


def read(spec, obs):
    seconds = forward_seconds(spec, obs)
    if seconds is None:
        return None
    if spec["what"] == "ms":
        return seconds * 1000.0
    used, touched = obs["polled"].get("kv_tokens") or [], experts_touched(obs)
    if not used or not touched:
        return None
    need = costs.of(obs.get("reference"), "block_forward_bytes")(
        obs["hf"], sum(used) / len(used), touched)
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / seconds
