"""Per-layer metrics of a decoder of recurrent, window, one full and
query-only layers (a configuration with ``mb_per_layer``), from the device
trace of a traced run and the program's own counters
(``Engine.stats()["recurrent"]``, which the serving driver keeps under
``run.samples["engine_stats"]``).

Every reader returns ``None`` for another configuration, for a program
that keeps no such counters, and without a device trace.

Which operations are whose is decided by what an operation IS, never by a
fusion's number (``readers/routed.py`` does the same):

* the recurrent layers' and the gated units' **mixers**: an operation
  whose text holds one of their own arrays' dimensions: the state
  ``[.., d_state, d_inner]``, a float32 array of ``d_inner`` (the
  recurrence's inputs), the convolution's inputs ``[.., d_conv - 1 |
  d_conv, d_inner]``, or one of the matrices ``[h, 2 d_inner]``,
  ``[d_inner, rank + 2 d_state]``, ``[rank, d_inner]``, ``[d_inner, h]``.
  One matrix cannot be told by its shape: a gated unit's ``W_in`` and an
  attention layer's ``W_qkv`` are both ``[h, d_inner]`` at the published
  sizes (2560 x 5120), the same bytes and products each, so the gated
  units are given their share by count of the time of all such
  operations (7 of 16). The layers' MLPs are no mixer and are not
  counted;
* the **reads of the one KV layer**: the decode kernel's calls (found by
  the kernel's name) whose block table is the pool's, ``[slots, max_len /
  block_size]`` wide; the calls over the window rings have the ring's
  table, ``[slots, window / block_size]``.

The ``hybrid_ops`` information line gives the parts, the rest of the step
and their sum, which is the step's busy time.

Every run of such a cell, traced or not, also prints ``longest_steps``
(``readers/routed.py``'s line): the window's three longest
``Engine.step()`` calls with their phases and their launches' stamps, the
longest pause between two steps, and the device's memory counters. Three
of this cell's first thirteen runs lost 60-100 of ~1590 steps to a stall
of the host (PERF.md section 7 (i)); this line says where, whenever it
happens.
"""
from __future__ import annotations

import re

from benchmarks import arithmetic, arithmetic_hybrid, trace_reduce
from benchmarks.readers import device, routed

KERNEL = "paged_attention"
BLOCK_SIZE = 16          # ``Engine``'s default, where the traffic gives none


def _counters(run):
    if "mb_per_layer" not in run.config:
        return None
    return run.samples.get("engine_stats", {}).get("recurrent")


def marks(config):
    """``(recurrent, shared)``: the texts that name the recurrent mixers'
    own arrays, and the one that a gated unit's ``W_in`` shares with the
    attention layers' ``W_qkv``."""
    h, di = config["hidden_size"], arithmetic_hybrid.d_inner(config)
    ds, r, dc = (config["mamba_d_state"], config["mamba_dt_rank"],
                 config["mamba_d_conv"])
    own = (f",{ds},{di}]", f",{dc - 1},{di}]", f",{dc},{di}]",
           f"[{h},{2 * di}]", f"[{di},{r + 2 * ds}]", f"[{r},{di}]",
           f"[{di},{h}]")
    return own, f"[{h},{di}]"


def is_recurrent(name, own, di):
    """An operation on one of the mixers' own arrays, or on a float32
    array whose last dimension is ``d_inner`` (the recurrence's inputs)."""
    return any(t in name for t in own) \
        or re.search(rf"f32\[(\d+,)*{di}\]", name) is not None


def gated_share(config):
    """The gated units' share of the operations on an ``[h, d_inner]``
    matrix: theirs over theirs and the attention layers' with keys."""
    n = arithmetic_hybrid.layer_counts(config)
    return n["gmu"] / (n["gmu"] + n["window"] + n["full"])


def split_steps(ops, steps, config, tables):
    """Self times a step, in ns: ``{"recurrent", "shared_kv", "window_kv",
    "rest"}`` of the operations wholly inside one of ``steps`` ``[(start,
    end)]``, each summed over the steps and divided by their number.
    ``ops`` is ``[(name, start, end, self)]``; ``tables`` the texts of
    the pool's and the ring's block tables."""
    own, shared_mark = marks(config)
    di = arithmetic_hybrid.d_inner(config)
    share = gated_share(config)
    out = dict.fromkeys(("recurrent", "shared_kv", "window_kv", "rest"), 0.0)
    for name, start, end, mine in ops:
        if not any(s <= start and end <= e for s, e in steps):
            continue
        if name.startswith(KERNEL):
            key = "shared_kv" if tables[0] in name else \
                "window_kv" if tables[1] in name else "rest"
            out[key] += mine
        elif is_recurrent(name, own, di):
            out["recurrent"] += mine
        elif shared_mark in name:
            out["recurrent"] += share * mine
            out["rest"] += (1.0 - share) * mine
        else:
            out["rest"] += mine
    n = max(len(steps), 1)
    return {k: v / n for k, v in out.items()}


def _device_split(run):
    """Computed once a run: the parts of a decode-only step of the first
    device in ms, or ``None``."""
    if hasattr(run, "hybrid_split"):
        return run.hybrid_split
    run.hybrid_split = None
    t = run.trace if run.trace and run.trace["reduced"] else None
    if t is None or _counters(run) is None \
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
    engine = run.traffic["engine"]
    bs = engine.get("block_size", BLOCK_SIZE)
    tables = tuple(f"s32[{engine['n_slots']},{width // bs}]" for width in
                   (engine["max_len"], run.config["sliding_window"]))
    ops = trace_reduce.self_times(planes[sorted(planes)[0]])
    parts = {k: v * 1e-6 for k, v in split_steps(
        ops, steps, run.config, tables).items()}
    run.info("hybrid_ops", decode_only_steps=len(steps),
             sum_ms=sum(parts.values()), **{k + "_ms": v
                                            for k, v in parts.items()})
    run.hybrid_split = parts
    return parts


def recurrent_device_ms(run):
    """Device self time, a decode-only step, of the recurrent layers' and
    the gated units' mixers."""
    if _counters(run) is not None:
        routed._say_longest_steps(run)
    split = _device_split(run)
    return split["recurrent"] or None if split else None


def shared_kv_attention_device_ms(run):
    """Device self time, a decode-only step, of the decode kernel's calls
    that read the one KV layer (the full layer's and every query-only
    layer's)."""
    split = _device_split(run)
    return split["shared_kv"] or None if split else None


def hybrid_decode_hbm_roofline_pct(run):
    """The bytes a decode-only step cannot avoid
    (``arithmetic_hybrid.decode_step_bytes``) over ``decode_device_ms`` at
    the chip's memory bandwidth. Lines a call are a life-long mean over
    every decode call (``stats()["recurrent"]``), rows a step the mean of
    the traced window's decode-only steps. Names no kernel."""
    ms, r = device.decode_device_ms(run), _counters(run)
    if ms is None or not r or not r["decode_calls"]:
        return None
    steps = run.samples["engine_steps"][run.samples.get("first_step", 0):]
    rows = [n for _, _, kind, n in steps if kind == "decode"]
    if not rows:
        return None
    lines = r["decode_lines_seen"] / r["decode_calls"]
    in_window = r["decode_lines_in_window"] / r["decode_calls"]
    n_rows = sum(rows) / len(rows)
    least = arithmetic_hybrid.decode_step_bytes(run.config, lines,
                                                in_window, n_rows)
    least_s = least / arithmetic.peaks(run.device_kind)["hbm_bytes_per_s"]
    run.info("hybrid_decode_step_bytes", bytes_a_step=least,
             weight_bytes=arithmetic_hybrid.weight_bytes(run.config),
             lines_seen_a_call=lines, lines_in_window_a_call=in_window,
             rows_a_step=n_rows, readers=r["shared_kv_readers"],
             least_ms=least_s * 1e3)
    return 100.0 * least_s / (ms * 1e-3)
