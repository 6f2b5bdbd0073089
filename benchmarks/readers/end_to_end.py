"""The end-to-end metrics: what a user of the system sees, from the host's
clock alone. Each reader takes the run and returns a number, or ``None``
where the run recorded nothing of its kind."""
from __future__ import annotations

from benchmarks import stats


def _seconds(run):
    t0, t1 = run.samples["window"]
    return t1 - t0


def setup_s(run):
    return run.setup_s


def train_tokens_per_s(run):
    s = run.samples
    if "steps" not in s:
        return None
    return s["steps"] * s["tokens_per_step"] / _seconds(run)


def serve_tokens_per_s(run):
    s = run.samples
    if "requests" not in s:
        return None
    return stats.tokens_inside(s["requests"], s["window"]) / _seconds(run)


def itl_p95_ms(run):
    s = run.samples
    if "requests" not in s:
        return None
    p = stats.percentile(stats.token_gaps(s["requests"], s["window"]), 95)
    return None if p is None else p * 1e3
