"""Percentiles and the open-loop timing arithmetic."""

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks (numpy's default). Raises on an empty sample: a metric
    with nothing to read is left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def ttft_ms(records) -> list:
    """Per request: first token's time minus the time the request was DUE
    (not when it was submitted), in ms. A request with no first token (it
    was rejected, failed or never started) is ``inf``: it misses any limit
    and pulls the tail up, it is not dropped from the sample."""
    return [(r["token_times"][0] - r["due"]) * 1e3 if r["token_times"]
            else math.inf for r in records]


def token_gaps_ms(records) -> list:
    """Gaps between consecutive token times, all requests pooled, in ms."""
    gaps = []
    for r in records:
        t = r["token_times"]
        gaps.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return gaps


def lateness_ms(records) -> list:
    """How late the generator ran: submit time minus due time, in ms."""
    return [(r["submitted"] - r["due"]) * 1e3 for r in records
            if r.get("submitted") is not None]


def tokens_in_window(records, t0: float, t1: float) -> int:
    """Tokens whose time lies in ``[t0, t1)``, whichever request they
    belong to."""
    return sum(1 for r in records for t in r["token_times"] if t0 <= t < t1)
