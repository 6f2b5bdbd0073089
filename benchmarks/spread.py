#!/usr/bin/env python3
"""Sum up the logs ``prove.sh`` left: per metric, each set's median and
spread (the distance between the first and the third quartile as
``statistics.quantiles(values, n=4)`` gives them, as a share of the
median), the wider of the two, and five times it: the bound to set. No
jax here."""
from __future__ import annotations

import glob
import json
import os
import statistics
import sys


def last_line(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(directory):
    sets = {}
    for path in sorted(glob.glob(os.path.join(directory, "[ab]*.log"))):
        result = last_line(path)
        label = os.path.basename(path)[0]
        if result is None or "metrics" not in result:
            print(f"{path}: no result")
            continue
        if not result["correct"] or result["failed"]:
            print(f"{path}: correct={result['correct']} "
                  f"failed={result['failed']}")
        for name, m in result["metrics"].items():
            sets.setdefault(name, {}).setdefault(label, []).append(m["value"])
        sets.setdefault("memory_peak_bytes", {}).setdefault(label, []).append(
            result["device"]["memory_peak_bytes"])
    for name, by_set in sorted(sets.items()):
        widest = 0.0
        for label, values in sorted(by_set.items()):
            if len(values) < 2:
                print(f"{name} set {label}: {values}")
                continue
            s = spread(values)
            widest = max(widest, s)
            print(f"{name} set {label}: n={len(values)} "
                  f"median={statistics.median(values)!r} "
                  f"spread={100 * s:.3f}% values={values}")
        medians = [statistics.median(v) for v in by_set.values()]
        drift = (abs(medians[1] - medians[0]) / medians[0]
                 if len(medians) == 2 and medians[0] else 0.0)
        print(f"{name}: widest spread {100 * widest:.3f}% -> bound "
              f"{max(5 * widest, 0.01):.4f}; second median differs from "
              f"the first by {100 * drift:.3f}%")
    for label in ("cold", "traced"):
        result = last_line(os.path.join(directory, f"{label}.log"))
        print(f"{label}: {json.dumps(result)}")


if __name__ == "__main__":
    main(sys.argv[1])
