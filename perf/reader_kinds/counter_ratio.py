"""A reader kind: one counter's delta over another's, over the window.

    {"reader": "counter_ratio",
     "numerator": {"metric": ..., "where": {...}},
     "denominator": {"metric": ..., "where": {...}}, "scale": 1.0}

`where` keeps some labels, as for `counter_delta`. Nothing where the
denominator did not move (a program without the counter)."""

from perf import readers


def read(spec, obs):
    num, den = spec["numerator"], spec["denominator"]
    below = readers.delta(obs, den["metric"], den.get("where"))
    if below <= 0:
        return None
    return (readers.delta(obs, num["metric"], num.get("where")) / below
            * spec.get("scale", 1.0))
