"""``decode_hbm_roofline_pct``: the bytes a decode-only step cannot avoid
(``arithmetic_decode.decode_step_bytes``) over ``decode_device_ms`` at the
chip's memory bandwidth. Defined on the step, not on an operation's name:
it reads the same work whatever implements attention or the feed-forward.

The K and V lines come from the program's own count
(``Engine.stats()["decode_lines_seen"]``: the lines the decode-active
rows of every fused decode call could see, and the same cut to the
model's window) and a routed model's experts from its ``moe`` counters.
Both are cumulative over the engine's life and taken once, when the
window closes: a mean over every decode call, those beside a chunk too,
where fewer rows decode, against the device time of the traced window's
decode-only steps. The bytes are counted low rather than high, as in
``moe_hbm_roofline_pct``.

``None`` without a device trace and for a program that keeps no such
count (a parent of the change that brought it)."""
from __future__ import annotations

from benchmarks import arithmetic, arithmetic_decode
from benchmarks.readers import device


def counts(config, stats):
    """``(lines, lines_in_window, experts_hit)`` of a mean decode call,
    from a program's ``stats()`` (``experts_hit`` ``None`` for a dense
    model); ``None`` where the program lacks a count."""
    seen = stats.get("decode_lines_seen")
    if not seen or not seen.get("calls"):
        return None
    hit = None
    if "num_experts" in config:
        moe = stats.get("moe")
        if not moe or not moe["decode_calls"]:
            return None
        hit = sum(moe["experts_hit"]) / moe["decode_calls"]
    return (seen["lines"] / seen["calls"],
            seen["in_window"] / seen["calls"], hit)


def decode_hbm_roofline_pct(run):
    ms = device.decode_device_ms(run)
    counted = counts(run.config, run.samples.get("engine_stats", {}))
    if ms is None or counted is None:
        return None
    lines, in_window, hit = counted
    weights = arithmetic_decode.weight_bytes(run.config, hit)
    kv = arithmetic_decode.kv_bytes(run.config, lines, in_window)
    least_s = (weights + kv) \
        / arithmetic.peaks(run.device_kind)["hbm_bytes_per_s"]
    run.info("decode_step_bytes", bytes_a_step=weights + kv,
             weight_bytes=weights, kv_bytes=kv, lines_seen_a_step=lines,
             lines_in_window_a_step=in_window, experts_hit_a_step=hit,
             least_ms=least_s * 1e3)
    return 100.0 * least_s / (ms * 1e-3)
