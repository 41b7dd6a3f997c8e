"""A reader kind: the expert layer's grouped products, in the decode chunk,
against their roofline.

    {"reader": "moe_experts_roofline", "ops": <regex of the grouped products>,
     "module": <regex of the decode chunk>}

Time: the device seconds of the matching operations that ran INSIDE the
matching programs (the program's grouped matmul kernel, `gmm` in a trace;
an admission runs the same kernel in a program of its own, and how many rows
a product takes is the program's to choose, so neither tells them apart).
Work, from the reference's counts: each forward runs the products of every
layer, and needs the larger of their weight bytes over the HBM bandwidth
and their routed FLOPs over the MXU peak. The experts that hold rows are
measured, over kept rows (reader_kinds/block_forward.py), so the share is
never counted high. The rows are the trace's own: the leading dimension of
what a product returns (`gmm.24 bf16[1024,768]`: 1,024 assignments, over
the experts a token takes), the mean over the products that ran. Programs
and products are those of the chunks that ran whole inside the slice (its
edges cut a chunk each: a cut one counted as a whole one made this metric
and its time swing by a tenth from run to run); forwards are those chunks
times the forwards a chunk ran over the window."""

import importlib.util
import os
import re

from perf import costs, trace_reduce

_spec = importlib.util.spec_from_file_location(
    "perf.reader_kinds.block_forward",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "block_forward.py"))
block_forward = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(block_forward)

_LEADING = re.compile(r" \w+\[(\d+)[,\]]")


def read(spec, obs):
    tr, reference = obs.get("trace"), obs.get("reference")
    per_chunk = block_forward.forwards_per_chunk(obs)
    touched = block_forward.experts_touched(obs)
    if not tr or not per_chunk or not touched or not hasattr(
            reference, "moe_experts_bytes"):
        return None
    products = trace_reduce.ops_in(tr, spec["ops"], spec["module"])
    seconds = sum(op["total_s"] for op in products.values())
    chunks, _ = trace_reduce.module_stats(tr, spec["module"], whole=True)
    if seconds <= 0 or not chunks:
        return None
    hf, peaks = obs["hf"], obs["peaks"]
    ran = assignments = 0
    for name, op in products.items():
        leading = _LEADING.search(name)
        if leading:
            ran += op["count"]
            assignments += op["count"] * int(leading.group(1))
    rows = assignments / ran / hf["num_experts_per_tok"] if ran else 0.0
    forward = max(
        costs.of(reference, "moe_experts_bytes")(hf, touched) / peaks["hbm_bytes_per_s"],
        costs.of(reference, "moe_experts_flops")(hf, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * hf["num_hidden_layers"] * chunks * per_chunk * forward / seconds
