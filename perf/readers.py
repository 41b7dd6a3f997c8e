"""The reader kinds. A per-layer metric is a data file,
`layer_metrics/<name>.json`, that names one of these and gives it its
parameters; a reader that finds nothing to read returns None and the harness
leaves the metric out of the line. A new metric of an existing kind is a
file; a new KIND is a file too, `reader_kinds/<kind>.py` with a function
`read(spec, obs)`, found by its name when a metric first asks for it (the
kinds below come first: a file cannot replace one of them).

`obs` is what one run observed: `metrics0`/`metrics1` (the server's /metrics
text parsed at the window's two edges), `steps0`/`steps1`, `polled` (series
sampled in-process at 5 Hz), `records` (the load generator's), `trace` (the
summary of perf/trace_reduce.py, traced runs only), and the cell's `hf`, `engine`,
`chips`, `peaks`, `seconds`, and `reference`, the configuration's reference
module, which is asked for a cost (bytes, FLOPs) before perf/costs.py is.
"""

from __future__ import annotations

import importlib.util
import os
import re

from perf import costs, e2e, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

_LINE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> dict:
    """name -> list of (labels dict, value)."""
    out: dict[str, list] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line.strip())
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, value))
    return out


def _sum(parsed: dict, name: str, where: dict | None = None) -> float:
    return sum(v for labels, v in parsed.get(name, ())
               if all(labels.get(k) in want for k, want in (where or {}).items()))


def delta(obs, name, where=None, edges=("metrics0", "metrics1")) -> float:
    return _sum(obs[edges[1]], name, where) - _sum(obs[edges[0]], name, where)


def histogram_mean(spec, obs):
    """delta(_sum) / delta(_count): the exact mean over the window."""
    n = delta(obs, spec["metric"] + "_count", spec.get("where"))
    if n <= 0:
        return None
    return delta(obs, spec["metric"] + "_sum", spec.get("where")) / n * spec.get("scale", 1.0)


def histogram_sum_share(spec, obs):
    """delta(_sum) over the labels in `numerator`, as a share of all labels."""
    total = delta(obs, spec["metric"] + "_sum")
    if total <= 0:
        return None
    part = delta(obs, spec["metric"] + "_sum",
                 {spec["label"]: set(spec["numerator"])})
    return 100.0 * part / total


def histogram_sum_per_step(spec, obs):
    """delta(_sum) over every label (or those `where` keeps), per engine
    step completed."""
    steps = obs["steps1"] - obs["steps0"]
    if steps <= 0:
        return None
    return (delta(obs, spec["metric"] + "_sum", spec.get("where"))
            / steps * spec.get("scale", 1.0))


def counter_delta(spec, obs):
    return delta(obs, spec["metric"], spec.get("where"))


def polled_mean(spec, obs):
    xs = obs["polled"].get(spec["series"]) or []
    return sum(xs) / len(xs) * spec.get("scale", 1.0) if xs else None


def observed(spec, obs):
    """A number the harness itself took (e.g. compiles inside the window)."""
    return obs.get(spec["key"])


def loadgen_late_p95(spec, obs):
    return e2e.late_p95_ms(obs["records"], obs["seconds"])


def e2e_metric(spec, obs):
    """An end-to-end quantity reported as a per-layer metric."""
    return e2e.END_TO_END[spec["metric"]](
        obs["loop"], obs["records"], obs["seconds"], obs["drain_s"])


def _module_mean_s(spec, obs):
    if not obs.get("trace"):
        return None
    n, total = trace_reduce.module_stats(obs["trace"], spec["module"])
    return total / n if n else None


def trace_module_mean(spec, obs):
    """Mean device duration of the matching programs, divided by
    `per` (a number, or the name of an engine setting)."""
    mean = _module_mean_s(spec, obs)
    if mean is None:
        return None
    per = spec.get("per", 1)
    per = obs["engine"][per] if isinstance(per, str) else per
    return mean / per * spec.get("scale", 1.0)


def trace_op_per_step(spec, obs):
    """Device time in the matching operations, per run of the matching
    programs, divided by `per` (e.g. per decode step of a chunk)."""
    tr = obs.get("trace")
    if not tr:
        return None
    n, _ = trace_reduce.module_stats(tr, spec["module"])
    total = trace_reduce.op_seconds(tr, spec["ops"])
    if not n or total <= 0:
        return None
    per = spec.get("per", 1)
    per = obs["engine"][per] if isinstance(per, str) else per
    return total / n / per * spec.get("scale", 1.0)


def trace_op_share(spec, obs):
    """Device time in the matching operations, as a share of the slice."""
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * trace_reduce.op_seconds(tr, spec["ops"]) / tr["window_s"]


def trace_idle_share(spec, obs):
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def decode_hbm_share(spec, obs):
    """Least time to stream one decode step's bytes, over its device time.
    Bytes: this chip's share of the weights once and of the resident keys
    and values once (perf/costs.py); resident tokens are the mean the pool
    held over the window."""
    mean = _module_mean_s(spec, obs)
    used = obs["polled"].get("kv_tokens") or []
    if mean is None or not used:
        return None
    step_s = mean / obs["engine"]["decode_chunk"]
    need = costs.of(obs.get("reference"), "decode_step_bytes_per_chip")(
        obs["hf"], sum(used) / len(used), obs["chips"])
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / step_s


READERS = {f.__name__: f for f in (
    histogram_mean, histogram_sum_share, histogram_sum_per_step,
    counter_delta, polled_mean, observed, loadgen_late_p95, e2e_metric,
    trace_module_mean, trace_op_per_step, trace_op_share, trace_idle_share,
    decode_hbm_share,
)}


def kind(name: str, root: str = HERE):
    """The reader of that kind: one of `READERS`, or `read` of
    `reader_kinds/<name>.py`; None where there is neither."""
    if name in READERS:
        return READERS[name]
    path = os.path.join(root, "reader_kinds", name + ".py")
    if not re.fullmatch(r"[A-Za-z0-9_]+", name) or not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("perf.reader_kinds." + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read(spec: dict, obs: dict, root: str = HERE):
    return kind(spec["reader"], root)(spec, obs)
