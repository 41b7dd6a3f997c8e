"""From a profiler trace to device numbers.

Two stages, so that the arithmetic can be tested without a chip: `extract`
turns the profiler's `.xplane.pb` into plain lists of device events, and
`summarize` turns those into busy time, module and operation durations (over
the slice, and by the whole program an operation ran in), the operations
that took most time and the longest idle gaps. A TPU's plane has a line of
XLA modules (one event per executed program) and a line of XLA operations
(one event per operation inside them).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def extract(xplane_path: str) -> dict:
    """{"devices": [{"name", "modules": [[name, start_s, dur_s]], "ops": [...]}]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices = []
    for plane in data.planes:
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        dev = {"name": plane.name, "modules": [], "ops": []}
        for line in plane.lines:
            key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
            if key is None:
                continue
            for ev in line.events:
                name = short_op(ev.name) if key == "ops" else ev.name
                dev[key].append(
                    [name, ev.start_ns / 1e9, ev.duration_ns / 1e9])
        devices.append(dev)
    return {"devices": devices}


def short_op(name: str) -> str:
    """An operation's HLO line -> `fusion.181 bf16[24,14336]`: its name and
    the shape it produces, which is what tells one fusion from another."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + shape.group(1) if shape else "")


# Operations that only contain others (their time is their children's).
CONTAINERS = re.compile(r"^(while|conditional|call)[.\d]*( |$)")


def module_base(name: str) -> str:
    """`jit__decode_chunk(1234567)` -> `jit__decode_chunk`."""
    return re.sub(r"\(\d+\)$", "", name)


def union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, d in sorted(intervals):
        e = s + d
        if s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _top(totals: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def summarize(events: dict) -> dict | None:
    """Device numbers over the traced slice. Busy time is the union of the
    intervals in which an operation ran, averaged over the devices; the
    slice is from the first event's start to the last one's end, over all
    devices. Per-module and per-operation sums are of the FIRST device: at
    tp > 1 every chip runs the same program. `ops_in` holds the programs
    that ran whole inside the slice, by name, with their operations."""
    devs = [d for d in events.get("devices", []) if d["ops"] or d["modules"]]
    if not devs:
        return None
    spans = [(e[1], e[1] + e[2]) for d in devs for e in d["ops"] + d["modules"]]
    t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    busy = [union_seconds([(e[1], e[2]) for e in (d["ops"] or d["modules"])])
            for d in devs]
    first = devs[0]
    modules: dict[str, list[float]] = {}
    for name, _, dur in first["modules"]:
        modules.setdefault(module_base(name), []).append(dur)
    ops: dict[str, float] = {}
    for name, _, dur in first["ops"]:
        if not CONTAINERS.match(name):
            ops[name] = ops.get(name, 0.0) + dur
    # The programs that lie wholly inside the slice, each with the operations
    # that started inside it. The slice's edges cut the first and the last
    # program on the line (a cut one is there with the part of its duration
    # that the slice saw, and the part of its operations), so those two are
    # left out: what is counted a program is then counted over whole ones.
    mods = sorted(first["modules"], key=lambda e: e[1])
    whole = mods[1:-1]
    starts = [m[1] for m in whole]
    ops_in: dict[str, dict] = {}
    for name, _, dur in whole:
        program = ops_in.setdefault(
            module_base(name), {"count": 0, "total_s": 0.0, "ops": {}})
        program["count"] += 1
        program["total_s"] += dur
    for name, start, dur in first["ops"]:
        if CONTAINERS.match(name):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start > whole[i][1] + whole[i][2]:
            continue
        op = ops_in[module_base(whole[i][0])]["ops"].setdefault(
            name, {"count": 0, "total_s": 0.0})
        op["count"] += 1
        op["total_s"] += dur
    # Idle gaps between programs, named by the program that ended the wait:
    # the host was getting that one ready.
    gaps: dict[str, float] = {}
    for prev, nxt in zip(mods, mods[1:]):
        gap = nxt[1] - (prev[1] + prev[2])
        if gap > 0:
            key = "before " + module_base(nxt[0])
            gaps[key] = gaps.get(key, 0.0) + gap
    return {
        "window_s": t1 - t0,
        "busy_s": sum(busy) / len(busy),
        "modules": {k: {"count": len(v), "total_s": sum(v)}
                    for k, v in modules.items()},
        "ops": ops,
        "ops_in": ops_in,
        "device_ops": _top(ops),
        "idle_gaps": _top(gaps),
    }


def module_stats(summary: dict, pattern: str, whole: bool = False) -> tuple[int, float]:
    """(count, total seconds) over the modules whose name matches: every
    one the slice saw, or only those that ran whole inside it."""
    rx = re.compile(pattern)
    hit = [m for k, m in summary.get("ops_in" if whole else "modules", {}).items()
           if rx.search(k)]
    return sum(m["count"] for m in hit), sum(m["total_s"] for m in hit)


def ops_in(summary: dict, pattern: str, module: str) -> dict:
    """name -> {"count", "total_s"} of the matching operations inside the
    programs whose name matches `module` and that ran whole inside the slice
    (`module_stats(..., whole=True)` counts those programs)."""
    rx, mrx = re.compile(pattern), re.compile(module)
    out: dict[str, dict] = {}
    for mod, program in summary.get("ops_in", {}).items():
        if not mrx.search(mod):
            continue
        for name, op in program["ops"].items():
            if rx.search(name):
                hit = out.setdefault(name, {"count": 0, "total_s": 0.0})
                hit["count"] += op["count"]
                hit["total_s"] += op["total_s"]
    return out


def op_seconds(summary: dict, pattern: str, module: str | None = None) -> float:
    """Seconds in the matching operations: over the slice, or inside the
    programs whose name matches `module` and that ran whole inside it."""
    if module is not None:
        return sum(op["total_s"] for op in ops_in(summary, pattern, module).values())
    rx = re.compile(pattern)
    return sum(v for k, v in summary["ops"].items() if rx.search(k))
