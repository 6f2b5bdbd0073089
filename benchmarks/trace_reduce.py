"""From a profiler trace to device busy time, idle share, the heaviest
device operations and the longest idle gaps.

The reduction is a pure function over a list of events
``(plane, line, name, start_ns, dur_ns)``; ``events_from_xplane`` is the
thin adapter from the file ``jax.profiler`` writes. A device plane is one
whose name starts with ``/device:TPU:``; its operations are the events of
its ``XLA Ops`` line (every line of the plane, if it has no such line: the
layout is printed by the traced run so that this can be checked by eye).
Host annotations are the events of any other plane whose name starts with
``bench.``: the benchmark's own ``jax.profiler.TraceAnnotation`` spans.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION = "bench."
WINDOW = "bench.window"


def events_from_xplane(path):
    """Every device event and every ``bench.`` host annotation of one
    ``.xplane.pb`` file, as ``(plane, line, name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            for ev in line.events:
                if device:
                    out.append((plane.name, line.name, short_name(ev.name),
                                float(ev.start_ns), float(ev.duration_ns)))
                elif ev.name.startswith(ANNOTATION):
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def short_name(name, limit=160):
    """A device operation's name as the trace gives it is its whole HLO
    instruction. Kept: the instruction's name, its result types and the
    start of its operands, without the ``{...}`` layouts, cut at
    ``limit`` characters."""
    return re.sub(r"\{[^{}]*\}", "", name).lstrip("%")[:limit]


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def layout(events):
    """``{plane: {line: number of events}}``: what the trace holds."""
    out = {}
    for plane, line, *_ in events:
        lines = out.setdefault(plane, {})
        lines[line] = lines.get(line, 0) + 1
    return out


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of possibly overlapping ones."""
    merged = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def covered(merged, lo, hi):
    """Length of ``merged`` (a union) inside ``[lo, hi]``."""
    return sum(min(e, hi) - max(s, lo) for s, e in merged
               if e > lo and s < hi)


def device_ops(events):
    """``{device plane: [(name, start, end)]}`` of its operations."""
    planes = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE):
            planes.setdefault(plane, {}).setdefault(line, []).append(
                (name, start, start + dur))
    return {p: lines.get(OPS_LINE) or [ev for evs in lines.values()
                                       for ev in evs]
            for p, lines in planes.items()}


def self_times(ops):
    """``[(name, start, end, self)]``: each operation with the part of its
    interval that no operation nested inside it covers. A ``while`` or a
    ``call`` is on the trace's line beside the operations of its body;
    counted whole it would hide them and count their time twice."""
    out, stack = [], []
    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and start >= stack[-1][2]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(end, stack[-1][2]) - start
        stack.append([name, start, end, end - start])
    out.extend(tuple(e) for e in stack)
    return out


def annotations(events):
    """The benchmark's host spans, ``[(name, start, end)]``, by start."""
    return sorted(((name, start, start + dur)
                   for plane, _, name, start, dur in events
                   if not plane.startswith(DEVICE_PLANE)
                   and name.startswith(ANNOTATION)),
                  key=lambda a: a[1])


def spans_named(events, prefix):
    """``{index: (start, end)}`` of the annotations ``<prefix>:<index>``."""
    out = {}
    for name, start, end in annotations(events):
        head, _, index = name.partition(":")
        if head == prefix and index.isdigit():
            out[int(index)] = (start, end)
    return out


def window_of(events):
    """The traced window in trace time: the ``bench.window`` annotation,
    or else from the first device operation's start to the last one's
    end."""
    for name, start, end in annotations(events):
        if name == WINDOW:
            return start, end
    ops = [op for evs in device_ops(events).values() for op in evs]
    if not ops:
        return None
    return min(s for _, s, _ in ops), max(e for _, _, e in ops)


def label_at(notes, t):
    """The innermost host annotation (other than the window's own) that
    covers time ``t``; its index after ``:`` is dropped."""
    best = None
    for name, start, end in notes:
        if start > t:
            break
        if end >= t and name != WINDOW and (
                best is None or end - start < best[1]):
            best = (name, end - start)
    return best[0].partition(":")[0][len(ANNOTATION):] if best \
        else "unannotated"


def reduce(events, top=10):
    """Busy seconds (the union of the operations' intervals, averaged
    over the device planes), window seconds, idle share, the ``top``
    operations by total self time (of those wholly inside the window) and
    the ``top`` longest gaps of the first device, each under the host
    annotation that covers its midpoint.
    ``None`` where the trace holds no device operation in its window."""
    win = window_of(events)
    ops = device_ops(events)
    if win is None or not ops:
        return None
    lo, hi = win
    merged = {p: union((s, e) for _, s, e in evs) for p, evs in ops.items()}
    busy = sum(covered(m, lo, hi) for m in merged.values()) / len(merged)
    if busy <= 0 or hi <= lo:
        return None
    by_name = {}
    for evs in ops.values():
        for name, s, e, own in self_times(evs):
            if s >= lo and e <= hi:
                by_name[name] = by_name.get(name, 0.0) + own / len(ops)
    notes = annotations(events)
    first = merged[sorted(merged)[0]]
    edges = [lo] + [t for s, e in first if e > lo and s < hi
                    for t in (max(s, lo), min(e, hi))] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], (edges[i] + edges[i + 1]) / 2)
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)
    labelled = [[label_at(notes, mid), length * 1e-9]
                for length, mid in gaps]
    by_label = {}
    for label, seconds in labelled:
        by_label[label] = by_label.get(label, 0.0) + seconds
    return {
        "busy_s": busy * 1e-9, "window_s": (hi - lo) * 1e-9,
        "idle_share": 1.0 - busy / (hi - lo),
        "device_ops": [[n, t * 1e-9] for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": labelled[:top],
        "idle_by_label": sorted(map(list, by_label.items()),
                                key=lambda kv: -kv[1]),
        "merged": merged,
    }


def busy_between(reduced, lo, hi):
    """Busy nanoseconds of the devices (their mean) inside ``[lo, hi]``
    of trace time."""
    merged = reduced["merged"]
    return sum(covered(m, lo, hi) for m in merged.values()) / len(merged)
