"""Per-layer metrics of a routed feed-forward (a model whose
configuration has ``num_experts``): its device time in a decode-only
step from the device trace, that time against the bytes the step cannot
avoid reading, and the imbalance of the experts' load from the program's
counters (``Engine.stats()["moe"]``, which the serving driver keeps as
``run.samples["engine_stats"]``).

Every reader returns ``None`` for a dense configuration, for a program
that keeps no such counters (a parent of the change that brought them),
and, for the two device metrics, without a device trace.

Which operations are the routed feed-forward's is decided by what an
operation IS, never by a fusion's number: by the expert banks' or the
router's dimensions in its text (``[E,h,f]``, ``[E,f,h]``, ``[h,E]``).
The program applies every expert to every row (``nn/routed_ffn.py``), so
those are fusions over whole banks. What the layer does outside them (the
softmax over the experts, the top-k, the scatter of a row's weights) is
counted with the rest of the step; the ``routed_ffn_ops`` information
line gives both parts and their sum, which is the step's busy time.

**Two windows.** The program's counters are cumulative and the serving
driver takes them once, when the window closes: ``moe_expert_imbalance``
is over the engine's life (the check's sample, the warm-up, the window).
``moe_hbm_roofline_pct`` divides bytes from that life's mean of experts
hit a decode call (every step's call, those beside a chunk too, where
fewer rows decode) by the device time of the traced window's
decode-only steps: the bytes are counted low, so the share reads low
rather than high.

A run of a routed cell also prints ``longest_steps``: the window's three
longest ``Engine.step()`` calls with their phases and their launches'
stamps, the longest pause between two steps, and the device's memory
counters (a run in twelve of the first such cell lost seconds of its
window in one place; this line says where, whenever it happens).
"""
from __future__ import annotations

from benchmarks import arithmetic, arithmetic_moe, trace_reduce
from benchmarks.readers import program


def _counters(run):
    if "num_experts" not in run.config:
        return None
    return run.samples.get("engine_stats", {}).get("moe")


def imbalance(expert_tokens):
    """The busiest expert's picks over the mean expert's, of the layer
    where that is largest; ``None`` where nothing was picked."""
    worst = None
    for layer in expert_tokens:
        if sum(layer):
            ratio = max(layer) * len(layer) / sum(layer)
            worst = ratio if worst is None else max(worst, ratio)
    return worst


def longest_steps(steps, launches, t_open, n=3):
    """The ``n`` longest of ``steps`` (``StepRecord``s): milliseconds of
    each phase, seconds since ``t_open``, kind, and every launch of the
    step as ``[program, call -> returned ms, returned -> fetched ms or
    None]``; and the longest pause between one step's end and the next
    one's begin, ``[ms, seconds since t_open]``."""
    by_step = {}
    for r in launches:
        by_step.setdefault(r.step, []).append([
            r.program, (r.dispatched - r.called) * 1e3,
            None if r.fetched is None else (r.fetched - r.dispatched) * 1e3])
    longest = sorted(steps, key=lambda r: r.begin - r.end)[:n]
    pauses = [((b.begin - a.end) * 1e3, b.begin - t_open)
              for a, b in zip(steps, steps[1:])]
    return {"steps": [dict({p: v * 1e3 for p, v in r.phases().items()},
                           at_s=r.begin - t_open, kind=r.kind,
                           launches=by_step.get(r.index, []))
                      for r in longest],
            "longest_pause_ms_at_s": list(max(pauses, default=(0.0, 0.0)))}


def _say_longest_steps(run):
    if "window" not in run.samples:
        return
    rings = program._rings()
    if not rings or not rings[0]:
        return
    window = run.samples["window"]
    steps = program.inside(rings[0], window, "begin", "end")
    import jax
    memory = jax.devices()[0].memory_stats() or {}
    run.info("longest_steps", steps_in_window=len(steps),
             **longest_steps(steps, rings[1], window[0]),
             memory={k: memory[k] for k in (
                 "bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
                 "num_allocs", "bytes_limit") if k in memory})


def moe_expert_imbalance(run):
    """Over the engine's life (the check's sample, the warm-up and the
    window: the driver takes the program's counters once, at the
    window's end)."""
    moe = _counters(run)
    if moe is None:
        return None
    _say_longest_steps(run)
    return imbalance(moe["expert_tokens"])


def marks(config):
    """The texts that name the routed feed-forward's own arrays."""
    e, h, f = (config["num_experts"], config["hidden_size"],
               config["moe_intermediate_size"])
    return (f"[{e},{h},{f}]", f"[{e},{f},{h}]", f"[{h},{e}]")


def is_routed(name, texts):
    return any(t in name for t in texts)


def split_steps(ops, steps, texts):
    """``(routed_ns, rest_ns)`` a step: the self times of the operations
    wholly inside one of ``steps`` ``[(start, end)]``, the routed
    feed-forward's apart from all others, each summed over the steps and
    divided by their number; and the routed operations by name with
    their share. ``ops`` is ``[(name, start, end, self)]``."""
    routed = rest = 0.0
    by_name = {}
    for name, start, end, own in ops:
        if not any(s <= start and end <= e for s, e in steps):
            continue
        if is_routed(name, texts):
            routed += own
            key = name.split(" ")[0].rstrip(".0123456789")
            by_name[key] = by_name.get(key, 0.0) + own
        else:
            rest += own
    n = max(len(steps), 1)
    return routed / n, rest / n, {k: v / n for k, v in by_name.items()}


def _device_split(run):
    """Computed once a run: ``(routed_ms, rest_ms)`` a decode-only step
    of the first device, or ``None``."""
    if hasattr(run, "routed_split"):
        return run.routed_split
    run.routed_split = None
    t = run.trace if run.trace and run.trace["reduced"] else None
    if t is None or "num_experts" not in run.config \
            or "engine_steps" not in run.samples:
        return None
    lo, hi = trace_reduce.window_of(t["events"])
    kinds = [k for _, _, k, _ in run.samples["engine_steps"]]
    steps = [(s, e) for i, (s, e) in trace_reduce.spans_named(
        t["events"], "bench.engine_step").items()
        if s >= lo and e <= hi and kinds[i] == "decode"]
    planes = trace_reduce.device_ops(t["events"])
    if not steps or not planes:
        return None
    ops = trace_reduce.self_times(planes[sorted(planes)[0]])
    routed, rest, by_name = split_steps(ops, steps, marks(run.config))
    if routed <= 0:
        return None
    run.info("routed_ffn_ops", decode_only_steps=len(steps),
             routed_ms=routed * 1e-6, rest_ms=rest * 1e-6,
             sum_ms=(routed + rest) * 1e-6,
             by_operation_ms={k: v * 1e-6 for k, v in sorted(
                 by_name.items(), key=lambda kv: -kv[1])})
    run.routed_split = (routed * 1e-6, rest * 1e-6)
    return run.routed_split


def moe_ffn_device_ms(run):
    """Device self time of the routed feed-forward's operations in an
    ``Engine.step()`` that only decoded."""
    split = _device_split(run)
    return None if split is None else split[0]


def moe_hbm_roofline_pct(run):
    """The bytes a decode step's routed feed-forwards cannot avoid (the
    distinct experts its rows picked, from the program's counters as a
    mean over its decode calls, and the routers:
    ``arithmetic_moe.routed_decode_bytes``) over ``moe_ffn_device_ms`` at
    the chip's memory bandwidth."""
    split, moe = _device_split(run), _counters(run)
    if split is None or moe is None or not moe["decode_calls"]:
        return None
    hit = sum(moe["experts_hit"]) / moe["decode_calls"]
    least_s = arithmetic_moe.routed_decode_bytes(run.config, hit) \
        / arithmetic.peaks(run.device_kind)["hbm_bytes_per_s"]
    run.info("routed_ffn_bytes", experts_hit_a_step=hit,
             bytes_a_step=arithmetic_moe.routed_decode_bytes(run.config,
                                                            hit),
             least_ms=least_s * 1e3)
    return 100.0 * least_s / (split[0] * 1e-3)
