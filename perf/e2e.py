"""From the load generator's records to the end-to-end metrics.

All times are seconds from the start of the measured window, on the client's
side. The window is [0, seconds). Each function returns None when the
records hold nothing to read, and the harness then leaves the metric out.
"""

from __future__ import annotations


def percentile(values, q: float):
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def _due_in_window(records, seconds):
    return [r for r in records
            if r.get("due") is not None and 0.0 <= r["due"] < seconds]


def _ended_in_window(records, seconds):
    return [r for r in records if r.get("ok") and r["events"]
            and 0.0 <= r["events"][-1][0] < seconds]


def _answered(r) -> bool:
    """Finished, or cut after the window's end with a first token in hand."""
    return bool(r["events"] and (r.get("ok") or r.get("cut")))


def counts(loop: str, records, seconds) -> tuple[int, int]:
    """(attempted, failed). Open loop: requests due in the window; one
    that errored, or had no first token by the drain limit, failed. Closed
    loop: requests that ended in the window (a stream cut at the window's
    end is neither)."""
    if loop == "open":
        pool = _due_in_window(records, seconds)
        return len(pool), sum(1 for r in pool if not _answered(r))
    pool = [r for r in records if not r.get("cut") and (
        0.0 <= r.get("end", -1.0) < seconds
        or (not r.get("ok") and 0.0 <= r.get("sent", -1.0) < seconds))]
    return len(pool), sum(1 for r in pool if not r.get("ok"))


def _ttft_ms(q, records, seconds, drain_s):
    vals = []
    for r in _due_in_window(records, seconds):
        if _answered(r):
            vals.append(r["events"][0][0] - r["due"])
        else:
            vals.append(seconds + drain_s - r["due"])
    p = percentile(vals, q)
    return None if p is None else p * 1e3


def ttft_p95_ms(loop, records, seconds, drain_s=0.0):
    """Due time to first token-bearing event, over requests due in the
    window. A failed request misses: it counts as the whole drain limit."""
    return _ttft_ms(95, records, seconds, drain_s)


def ttft_p50_ms(loop, records, seconds, drain_s=0.0):
    return _ttft_ms(50, records, seconds, drain_s)


def tpot_p95_ms(loop, records, seconds, drain_s=0.0):
    """(last token - first token) / (tokens - 1), over requests whose last
    token came inside the window."""
    vals = []
    for r in _ended_in_window(records, seconds):
        n = sum(e[1] for e in r["events"])
        if n > 1:
            vals.append((r["events"][-1][0] - r["events"][0][0]) / (n - 1))
    p = percentile(vals, 95)
    return None if p is None else p * 1e3


def tpot_mean_ms(loop, records, seconds, drain_s=0.0):
    """Time per output token after the first, over ALL the output tokens of
    the requests whose last token came inside the window: the sum of their
    (last - first token time) over the sum of their (tokens - 1)."""
    ended = _ended_in_window(records, seconds)
    tokens = sum(sum(e[1] for e in r["events"]) - 1 for r in ended)
    if tokens <= 0:
        return None
    spent = sum(r["events"][-1][0] - r["events"][0][0] for r in ended)
    return spent / tokens * 1e3


def out_tok_s(loop, records, seconds, drain_s=0.0):
    """Output tokens received inside the window, per second of it."""
    n = sum(e[1] for r in records for e in r["events"]
            if 0.0 <= e[0] < seconds)
    return n / seconds if n else None


def gap_p99_ms(loop, records, seconds, drain_s=0.0):
    """Gaps between successive token-bearing events of one request, pooled
    over all requests, for events inside the window."""
    gaps = []
    for r in records:
        ev = r["events"]
        gaps.extend(b[0] - a[0] for a, b in zip(ev, ev[1:])
                    if 0.0 <= b[0] < seconds)
    p = percentile(gaps, 99)
    return None if p is None else p * 1e3


def late_p95_ms(records, seconds):
    """How late the generator sent, over requests due in the window."""
    p = percentile([r["sent"] - r["due"]
                    for r in _due_in_window(records, seconds) if "sent" in r], 95)
    return None if p is None else p * 1e3


END_TO_END = {
    "ttft_p95_ms": ttft_p95_ms,
    "ttft_p50_ms": ttft_p50_ms,
    "tpot_p95_ms": tpot_p95_ms,
    "tpot_mean_ms": tpot_mean_ms,
    "out_tok_s": out_tok_s,
    "gap_p99_ms": gap_p99_ms,
}
