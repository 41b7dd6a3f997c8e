"""What the host was doing while the device was idle.

    python3 perf/host_spans.py <trace dir | events.json> [--config FILE] [--keep FILE]

`perf/run.py --trace 1` leaves the profiler's trace under
`perf_out/trace_<pid>`. Its device planes say when each program ran; since
the program records its host intervals as `jax.profiler.TraceAnnotation`s
(`StepProfiler.span`, kubeai_tpu/fleet/profiler.py), the host planes of the
same file say, on the same clock, what the thread that drives the chip was
doing meanwhile. This reads both and prints, as one JSON object:

- `idle_gaps`: every gap between two programs on the first device, cut
  along the innermost host span of the engine thread that covered each
  instant of it. Seconds by span, by (span, program that ended the gap), and
  the share no span covers.
- `spans`: per span name, over every host thread: count, total seconds and
  self seconds (a span's duration minus what its child spans cover).
- `prefill`: each `step.admit` span of kind `batch` joined, one to one and
  inside the slice, with the `jit__prefill_admit` run it launched: useful
  tokens, device seconds and, with `--config`, `prefill_mxu_share` (useful
  FLOPs by perf/costs.py over the chip's peak for those seconds).

Device operations are not grouped by the program's `jax.named_scope`s
(`mlp`, `paged_attention`, ...): `ProfileData` gives an operation event its
HLO text and its device times and no `op_name` metadata, so the scopes show
in XProf and not here.

Two stages, like perf/trace_reduce.py, so the arithmetic is tested without a
chip: `extract` turns the `.xplane.pb` into plain lists (`--keep` writes
them), `summarize` reduces those. Printed for PERF.md; not a benchmark
metric yet.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import costs, trace_reduce  # noqa: E402

HOST_PLANE = "/host:CPU"
# The program's span vocabulary (docs/concepts/observability.md). The host
# planes also hold the profiler's own Python-call events; these prefixes
# tell the two apart.
SPAN = re.compile(r"^(serve|step|admit|http|kv)\.[a-z_]+$")
ENGINE_SPAN = "serve.step"  # only the thread that drives the chip opens it
NO_SPAN = "(no span)"
PREFILL_MODULE = "jit__prefill_admit"
DEVICE_KIND = "TPU v5 lite"  # the one kind perf/peaks.json has, and the cells run on


def extract(xplane_path: str) -> dict:
    """{"devices": as trace_reduce.extract;
    "host": [{"line", "spans": [[name, start_s, dur_s, attrs]]}]}"""
    from jax.profiler import ProfileData

    out = trace_reduce.extract(xplane_path)
    host = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            spans = [[ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9,
                      {k: (v if isinstance(v, (int, float, str)) else str(v))
                       for k, v in dict(ev.stats).items()}]
                     for ev in line.events if SPAN.match(ev.name)]
            if spans:
                host.append({"line": f"{line.name}/{i}", "spans": spans})
    out["host"] = host
    return out


def innermost_segments(spans) -> list[tuple[float, float, str]]:
    """One thread's nested spans -> disjoint (start, end, name) pieces, each
    named by the innermost span open over it. The seconds of the pieces
    with a name are that name's self time."""
    segs: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name) of the open spans
    t = 0.0

    def piece(t0, t1, name):
        if t1 > t0:
            segs.append((t0, t1, name))

    for name, start, dur, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            end, ended = stack.pop()
            piece(t, end, ended)
            t = max(t, end)
        if stack:
            piece(t, start, stack[-1][1])
        stack.append((start + dur, name))
        t = start
    while stack:
        end, ended = stack.pop()
        piece(t, end, ended)
        t = max(t, end)
    return segs


def span_stats(host) -> dict:
    """name -> {"count", "total_s", "self_s"} over every host thread."""
    stats: dict[str, dict] = {}
    for line in host:
        for name, _start, dur, *_ in line["spans"]:
            s = stats.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            s["count"] += 1
            s["total_s"] += dur
        for t0, t1, name in innermost_segments(line["spans"]):
            stats[name]["self_s"] += t1 - t0
    return stats


def engine_line(host) -> dict | None:
    """The thread that drives the chip: the one with the most `serve.step`."""
    def steps(line):
        return sum(1 for s in line["spans"] if s[0] == ENGINE_SPAN)
    best = max(host, key=steps, default=None)
    return best if best is not None and steps(best) else None


def attribute_gaps(modules, segs) -> dict:
    """Idle seconds between consecutive programs, cut along `segs`."""
    by_span: dict[str, float] = {}
    by_pair: dict[str, float] = {}
    total = 0.0
    mods = sorted(modules, key=lambda m: m[1])
    i = 0  # segs are sorted and disjoint; gaps come in time order too
    for prev, nxt in zip(mods, mods[1:]):
        g0, g1 = prev[1] + prev[2], nxt[1]
        if g1 <= g0:
            continue
        total += g1 - g0
        after = trace_reduce.module_base(nxt[0])
        covered = 0.0
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            t0, t1, name = segs[j]
            part = min(t1, g1) - max(t0, g0)
            if part > 0:
                covered += part
                by_span[name] = by_span.get(name, 0.0) + part
                key = f"{name} -> {after}"
                by_pair[key] = by_pair.get(key, 0.0) + part
            j += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            by_span[NO_SPAN] = by_span.get(NO_SPAN, 0.0) + rest
            key = f"{NO_SPAN} -> {after}"
            by_pair[key] = by_pair.get(key, 0.0) + rest

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {"idle_s": total, "by_span": ranked(by_span),
            "by_span_and_next_program": ranked(by_pair),
            "uncovered_share": by_span.get(NO_SPAN, 0.0) / total if total else 0.0}


def join_prefill(modules, spans, hf=None, peak_flops=None) -> dict:
    """Each `step.admit` of kind batch with the prefill-admit run that
    started inside it; admissions cut by the slice's edge are left out."""
    runs = sorted((m for m in modules
                   if trace_reduce.module_base(m[0]) == PREFILL_MODULE),
                  key=lambda m: m[1])
    admits = sorted((s for s in spans if s[0] == "step.admit"
                     and s[3].get("kind") == "batch"), key=lambda s: s[1])
    pairs, r = [], 0
    for _name, start, dur, attrs in admits:
        while r < len(runs) and runs[r][1] < start:
            r += 1
        if r < len(runs) and runs[r][1] < start + dur:
            pairs.append((attrs, runs[r][2]))
            r += 1
    useful = sum(int(a["useful_tokens"]) for a, _ in pairs)
    padded = sum(int(a["padded_tokens"]) for a, _ in pairs)
    prompts = sum(int(a["batch"]) for a, _ in pairs)
    device_s = sum(d for _, d in pairs)
    out = {"admissions": len(pairs), "prompts": prompts,
           "useful_tokens": useful, "padded_tokens": padded,
           "device_s": device_s, "unmatched_runs": len(runs) - len(pairs)}
    if hf and peak_flops and device_s > 0 and prompts:
        # Causal attention: a prompt's tokens see half of it on average.
        flops = useful * costs.prefill_flops_per_token(
            hf, context=useful / prompts / 2)
        out["prefill_mxu_share"] = 100.0 * flops / (device_s * peak_flops)
    return out


def summarize(events: dict, hf=None, peak_flops=None) -> dict:
    devs = [d for d in events.get("devices", []) if d["modules"]]
    host = events.get("host", [])
    engine = engine_line(host)
    out = {"spans": span_stats(host),
           "engine_thread": engine["line"] if engine else None,
           "idle_gaps": None, "prefill": None}
    if devs and engine:
        out["idle_gaps"] = attribute_gaps(
            devs[0]["modules"], innermost_segments(engine["spans"]))
        out["prefill"] = join_prefill(
            devs[0]["modules"], engine["spans"], hf, peak_flops)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory, or a file --keep wrote")
    ap.add_argument("--config", default="",
                    help="the cell's configuration file, for prefill_mxu_share")
    ap.add_argument("--keep", default="", help="write the extracted events here")
    args = ap.parse_args(argv)
    if os.path.isdir(args.trace):
        events = extract(trace_reduce.find_xplane(args.trace))
    else:
        with open(args.trace) as f:
            events = json.load(f)
    if args.keep:  # without the operation events: they are most of the bytes
        with open(args.keep, "w") as f:
            json.dump({**events, "devices": [
                {k: v for k, v in d.items() if k != "ops"}
                for d in events["devices"]]}, f)
    hf = peak = None
    if args.config:
        with open(args.config) as f:
            hf = json.load(f)
        with open(os.path.join(ROOT, "perf", "peaks.json")) as f:
            peak = json.load(f)[DEVICE_KIND]["bf16_flops_per_s"]
    print(json.dumps(summarize(events, hf, peak), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
