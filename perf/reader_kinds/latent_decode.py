"""A reader kind: the decode attention over a latent page pool, against the
roofline.

    {"reader": "latent_decode", "module": <regex of the decode chunk>,
     "ops": <regex of the latent decode kernel>}

The kernel runs once a latent layer a decode step, inside the matching
programs that ran WHOLE inside the slice, and reads each live page of the
pool once: its least time is the larger of the resident rows' bytes over the
HBM bandwidth (the reference's `mla_decode_bytes`: a token's `kv_lora_rank +
qk_rope_head_dim` numbers once a layer, NOT the lanes the pool pads them to)
and the absorbed attention's FLOPs over the MXU peak (`mla_decode_flops`).

The resident tokens are counted from below. The program counts the pages
that hold the active slots' tokens once a dispatched chunk
(`kubeai_engine_decode_live_pages_total{pool="latent"}` over
`kubeai_engine_dispatches_total{before="decode"}`); a slot's last page may
hold one token, so a page a slot the kernel ran over (the leading dimension
of what it returns) is taken off before pages become tokens. A share so
counted cannot pass 100% by the count; it reads a per cent or so low.

Nothing without a trace, the programs, the kernel, the counters or a
reference that has the two counts; nothing raises on a program that has none
of them."""

from perf import costs, readers, trace_reduce
from perf.reader_kinds.hybrid_decode import leading_mean

LIVE = "kubeai_engine_decode_live_pages_total"
DISPATCHES = "kubeai_engine_dispatches_total"


def read(spec, obs):
    tr, reference = obs.get("trace"), obs.get("reference")
    if not tr or not hasattr(reference, "mla_decode_bytes"):
        return None
    ops = trace_reduce.ops_in(tr, spec["ops"], spec["module"])
    seconds = sum(op["total_s"] for op in ops.values())
    runs, slots = sum(op["count"] for op in ops.values()), leading_mean(ops)
    chunks = readers.delta(obs, DISPATCHES, {"before": {"decode"}})
    pages = readers.delta(obs, LIVE, {"pool": {"latent"}})
    if seconds <= 0 or not runs or not slots or chunks <= 0 or pages <= 0:
        return None
    hf = obs["hf"]
    tokens = max(pages / chunks - slots, 0.0) * obs["engine"]["page_size"]
    a_run = max(
        costs.of(reference, "mla_decode_bytes")(hf, tokens)
        / obs["peaks"]["hbm_bytes_per_s"],
        costs.of(reference, "mla_decode_flops")(hf, tokens)
        / obs["peaks"]["bf16_flops_per_s"])
    return 100.0 * runs * a_run / seconds or None  # a share is never 0
