"""A reader kind: the expert layer's grouped products, in the decode chunk,
against their roofline.

    {"reader": "moe_experts_roofline", "ops": <regex of the grouped products;
     "{rows}" stands for the rows one takes in a decode forward>,
     "module": <regex of the decode chunk>}

Time: the device seconds of the matching operations over the traced slice
(the program's grouped matmul kernel, `gmm` in a trace; a decode forward's
three calls a layer are told from an admission's by their rows: slots x
block length x experts a token). Work, from the reference's counts: each
forward runs the three products of every layer, and needs the larger of
their weight bytes over the HBM bandwidth and their routed FLOPs over the
MXU peak. The experts that hold rows are measured, over kept rows
(reader_kinds/block_forward.py), so the share is never counted high.
Forwards in the slice are the chunk programs in it times the forwards a
chunk ran over the window."""

import importlib.util
import os

from perf import costs, trace_reduce

_spec = importlib.util.spec_from_file_location(
    "perf.reader_kinds.block_forward",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "block_forward.py"))
block_forward = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(block_forward)


def read(spec, obs):
    tr, reference = obs.get("trace"), obs.get("reference")
    per_chunk = block_forward.forwards_per_chunk(obs)
    touched = block_forward.experts_touched(obs)
    if not tr or not per_chunk or not touched or not hasattr(reference, "generation"):
        return None
    hf, peaks = obs["hf"], obs["peaks"]
    rows = obs["engine"]["num_slots"] * reference.generation(hf)["B"]
    seconds = trace_reduce.op_seconds(tr, spec["ops"].replace(
        "{rows}", str(rows * hf["num_experts_per_tok"])))
    chunks, _ = trace_reduce.module_stats(tr, spec["module"])
    if seconds <= 0 or not chunks:
        return None
    forward = max(
        costs.of(reference, "moe_experts_bytes")(hf, touched) / peaks["hbm_bytes_per_s"],
        costs.of(reference, "moe_experts_flops")(hf, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * hf["num_hidden_layers"] * chunks * per_chunk * forward / seconds
