"""The arithmetic between stamps and metrics: percentiles and what counts
as inside the window. Pure functions of plain lists."""
from __future__ import annotations


def percentile(values, p):
    """Percentile with linear interpolation between ranks (numpy's
    default), of any non-empty list; ``None`` of an empty one."""
    vals = sorted(values)
    if not vals:
        return None
    k = (len(vals) - 1) * (p / 100.0)
    lo = int(k)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (k - lo))


def median(values):
    return percentile(values, 50)


def inside(t, window):
    return window[0] <= t <= window[1]


def ttfts(requests, window):
    """Seconds from submission to first token, of every request whose
    first token fell inside the window. A request is a dict with
    ``submit`` and ``token_times``."""
    return [r["token_times"][0] - r["submit"] for r in requests
            if r["token_times"] and inside(r["token_times"][0], window)]


def token_gaps(requests, window):
    """Seconds between successive tokens of one request, pooled over the
    requests, of every gap whose later token fell inside the window."""
    return [b - a for r in requests
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if inside(b, window)]


def tokens_inside(requests, window):
    return sum(inside(t, window) for r in requests
               for t in r["token_times"])
