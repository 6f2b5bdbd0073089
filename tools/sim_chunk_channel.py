#!/usr/bin/env python3
"""How widely a closed-loop serve cell spreads over seeds, off the chip.

    python3 tools/sim_chunk_channel.py benchmarks/traffic/agent_closed_16k.json \
        --seeds 192 --sigma 0.7 0.5 0.3

A cell whose every ``Engine.step()`` carries a chunk (long prompts, short
answers) is bound by the one-chunk-a-step channel: the rows that decode are
the slots less those queued for it, and that queue swings with the order
of long and short turns, which is all a seed changes. This replays the
traffic file's own request stream (``benchmarks/traffic.Requests``)
through that channel as the engine schedules it (every client resubmits at
once, the oldest chunked prefill advances one chunk a step, then every
row whose prompt is in decodes one token) with a step costed as

    chunk_ms + chunk_ms_per_k x (the chunk's first position / 1000)
    + decode_ms + decode_ms_per_row x (rows that decode)

and prints, per setting, the median and the quartile spread (as the
driver takes it: ``statistics.quantiles(n=4)`` over the median) of the
tokens a second over all seeds, and how sets of six seeds spread: a new
cell is admitted only under half of each bound (2.2 % here).

The defaults are fitted to the chip's readings of
``kimi_k26_agent_closed_16k`` since the chunk's attention is a kernel
(PERF.md section 6, PR 33): 483.0 tokens/s and 997 steps at sigma 0.3
where the chip read 481.5-485.0 and 997-1002; a chunk 512 positions deeper
is a step 0.7 ms (1.4 %) longer. With the costs of the plain form
(``--chunk-ms 40 --chunk-ms-per-k 5``; PR 32) it gave 293.3 tokens/s, 587
steps and a p95 step of 110.3 ms at sigma 0.7 where the chip read 291.5,
558-586 and 111.1, and 301.7 / 623 / 90.2 against 304.9 / 629 / 91.3 at
0.2. The chip adds noise of its own (about 0.7 % in quadrature), so read a
spread here as a floor. The p95 of the gaps is a p95 of step lengths:
where a depth costs 2.6 % of a step (the plain form's 2.5 ms) and the 95th
percentile lies at the edge between two depths, runs fall on either side
of it and a set of six spreads by that much or by nearly nothing (the chip
read 1.87 % at sigma 0.3 then, where this gave 19 % of the sets over
2.15 %); the host's jitter is not in it, so the chip's p95 gap lies over
this one (59.9-60.9 ms against 54.3).
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import traffic as traffic_mod  # noqa: E402


def one_run(traffic, seed, cost, window):
    """``(tokens a second, p95 gap in ms, steps)`` of one window."""
    requests = traffic_mod.Requests(traffic, 2, seed)
    chunk = traffic["engine"]["prefill_chunk"]
    prefix = traffic["shared_prefix_tokens"]
    live, chunking = {}, []
    t, finished, t_open, tokens, steps, gaps = 0.0, 0, None, 0, 0, []
    while True:
        for client in range(traffic["clients"]):
            if client not in live:
                prompt, out = next(requests)
                # the radix index serves the shared prefix's whole chunks
                r = {"n": len(prompt), "out": out, "done": 0, "last": None,
                     "next": prefix // chunk * chunk, "active": False}
                live[client] = r
                chunking.append(r)
        ms = 0.0
        if chunking:
            head = chunking[0]
            ms += cost["chunk_ms"] + cost["chunk_ms_per_k"] * head["next"] / 1e3
            head["next"] += chunk
            if head["next"] >= head["n"]:
                chunking.pop(0)
                head["active"] = True
        rows = [c for c, r in live.items() if r["active"]]
        ms += cost["decode_ms"] + cost["decode_ms_per_row"] * len(rows)
        t += ms / 1e3
        steps += 1
        for client in rows:
            r = live[client]
            r["done"] += 1
            if t_open is not None and t <= t_open + window:
                tokens += 1
                if r["last"] is not None and r["last"] >= t_open:
                    gaps.append(t - r["last"])
            r["last"] = t
            if r["done"] >= r["out"]:
                del live[client]
                finished += 1
        if t_open is None and finished >= traffic["warmup_requests"]:
            t_open, steps = t, 0
        if t_open is not None and t >= t_open + window:
            return tokens / window, float(np.percentile(gaps, 95)) * 1e3, steps


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("traffic", help="a traffic file of driver serve")
    ap.add_argument("--sigma", type=float, nargs="+", default=None,
                    help="both lengths' sigma, one setting each; "
                         "the file's own without it")
    ap.add_argument("--seeds", type=int, default=96)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--half-bound", type=float, default=0.022)
    ap.add_argument("--gap-half-bound", type=float, default=0.0215)
    ap.add_argument("--chunk-ms", type=float, default=29.2)
    ap.add_argument("--chunk-ms-per-k", type=float, default=1.4)
    ap.add_argument("--decode-ms", type=float, default=10.0)
    ap.add_argument("--decode-ms-per-row", type=float, default=0.16)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        base = json.load(f)
    cost = {k: getattr(args, k) for k in
            ("chunk_ms", "chunk_ms_per_k", "decode_ms", "decode_ms_per_row")}
    # the token values are nothing to the channel: draw none
    traffic_mod.Requests._tokens = lambda self, n: np.zeros((n,), np.int32)
    for sigma in args.sigma or [None]:
        traffic = copy.deepcopy(base)
        if sigma is not None:
            traffic["turn_tokens"]["sigma"] = sigma
            traffic["output_tokens"]["sigma"] = sigma
        runs = [one_run(traffic, 1000 + s, cost, args.seconds)
                for s in range(args.seeds)]
        rate, gap = [r[0] for r in runs], [r[1] for r in runs]
        sets = [spread(rate[i:i + 6]) for i in range(0, args.seeds - 5, 6)]
        gap_sets = [spread(gap[i:i + 6])
                    for i in range(0, args.seeds - 5, 6)]
        print(json.dumps({
            "sigma": [traffic["turn_tokens"]["sigma"],
                      traffic["output_tokens"]["sigma"]],
            "seeds": args.seeds,
            "tokens_per_s_median": statistics.median(rate),
            "tokens_per_s_spread": spread(rate),
            "sets_of_six": len(sets),
            "sets_of_six_spread_median": statistics.median(sets),
            "sets_of_six_spread_max": max(sets),
            "sets_of_six_over_half_bound": sum(
                x > args.half_bound for x in sets) / len(sets),
            "gap_p95_ms_median": statistics.median(gap),
            "gap_p95_spread": spread(gap),
            "gap_p95_sets_of_six_over_half_bound": sum(
                x > args.gap_half_bound for x in gap_sets) / len(gap_sets),
            "steps_median": statistics.median(r[2] for r in runs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
