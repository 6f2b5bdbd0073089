"""Driver ``serve``: ``serving.Engine`` under a closed loop of clients.

The traffic file gives the engine's geometry (``engine``: the keyword
arguments of ``Engine``), the number of ``clients``, the lengths (see
``traffic.Requests``), how many requests complete before the window opens
(``warmup_requests``) and the reference check's sample (``check``).

One thread pumps: it submits for every client whose last request has
finished (no think time), calls ``engine.step()``, and repeats. A request
that finds a free slot is admitted, and a short prompt prefilled, inside
``submit()`` itself; a long prompt is prefilled chunk by chunk inside the
following steps. Stamps are the benchmark's own, on its own clock: a
request's submission just before ``submit()`` is called, and each of its
tokens in the ``on_token`` callback, which the engine calls once the
step's tokens have been fetched from the device.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import models, stats, traffic as traffic_mod

KEYS = {"driver", "about", "engine", "clients", "warmup_requests", "check"} \
    | traffic_mod.Requests.KEYS


def verdict(reference, gaps):
    """The check's two numbers over its rows' gaps (bf16 steps under the
    reference's best), each with the most it may read: the rows over the
    reference's ``LOGIT_TOL_ULPS``, and the worst row. A reference module
    that sets ``ROWS_PER_ROW_OVER`` lets one row in that many (rounded
    down) lie over the tolerance, and then holds every row to its
    ``CEILING_ULPS``; one that sets neither lets no row over, which is the
    worst row held to the tolerance."""
    gaps = np.asarray(gaps, np.float64)
    tolerance = reference.LOGIT_TOL_ULPS
    per = getattr(reference, "ROWS_PER_ROW_OVER", None)
    return {"worst_gap_bf16_steps": {
                "value": float(gaps.max(initial=0.0)),
                "at_most": getattr(reference, "CEILING_ULPS", tolerance)},
            "rows_over_tolerance": {
                "value": int((gaps > tolerance).sum()),
                "at_most": len(gaps) // per if per else 0}}


class Driver:
    def __init__(self, run):
        self.run = run
        self.ref = models.reference(run.config)

    def _submit(self, prompt, n):
        """Submit one request; its record fills as its tokens come."""
        r = {"token_times": [], "prompt_tokens": len(prompt),
             "max_new_tokens": n, "submit": time.perf_counter()}
        r["handle"] = self.engine.submit(
            prompt, max_new_tokens=n, on_token=lambda h, token:
            r["token_times"].append(time.perf_counter()))
        return r

    def _check(self, weights, sample):
        """Teacher-forced, logit-level: the reference scores each sample
        prompt followed by the engine's own tokens in one full forward
        (the sample as one right-padded batch: under a causal mask the
        padding changes nothing before it), and at every generated
        position its logit of the engine's token must lie within the
        tolerance of its largest logit (:func:`verdict` says how many rows
        may not, and by how much). Every number compared, with its limit,
        goes to ``run.compared``."""
        run, reference = self.run, self.ref
        handles = [self._submit(p, n)["handle"] for p, n in sample]
        self.engine.drain()
        run.phase("sample_through_engine")
        unfinished = sum(h.finish_reason != "length" or len(h.tokens) != n
                         for (_, n), h in zip(sample, handles))
        seqs = [np.concatenate([p, np.asarray(h.tokens, np.int32)])
                for (p, _), h in zip(sample, handles)]
        ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
        for row, seq in zip(ids, seqs):
            row[:len(seq)] = seq
        # the rows that predicted each generated token
        rows = np.stack([np.arange(len(p) - 1, len(p) - 1 + n)
                         for p, n in sample])
        z = np.asarray(reference.logits(weights, run.config, ids, rows=rows))
        gaps = [float(zt.max() - zt[tok])
                / reference.bf16_step(np.abs(zt).max())
                for zi, h in zip(z, handles) for zt, tok in zip(zi, h.tokens)]
        limits = verdict(reference, gaps)
        limits["requests_not_finished_by_length"] = {"value": unfinished,
                                                     "at_most": 0}
        failed_on = [k for k, v in limits.items() if v["value"] > v["at_most"]]
        run.compared.update(limits)
        run.info("reference", requests=len(sample),
                 prompt_tokens=[len(p) for p, _ in sample], rows=len(gaps),
                 worst_gap_bf16_steps=limits["worst_gap_bf16_steps"]["value"],
                 tolerance_bf16_steps=reference.LOGIT_TOL_ULPS,
                 ceiling_bf16_steps=limits["worst_gap_bf16_steps"]["at_most"],
                 rows_over_tolerance=limits["rows_over_tolerance"]["value"],
                 rows_allowed_over_tolerance=limits[
                     "rows_over_tolerance"]["at_most"],
                 failed_on=failed_on)
        return not failed_on

    def setup(self):
        from paddle_tpu.serving import Engine

        run, t = self.run, self.run.traffic
        traffic_mod.known(t, KEYS, "traffic of driver serve")
        model = models.build(run.config, run.seed)
        model.eval()
        run.phase("model")
        self.engine = Engine(model, **t["engine"])
        run.phase("engine")
        self.requests = traffic_mod.Requests(t, run.config["vocab_size"],
                                             run.seed)
        # the engine stacked its own copy of the layers' weights. The
        # model's copy, which a deployment would not keep, goes to host
        # memory for the reference (embedding, norm and head are shared
        # with the engine, not copied), before any engine program runs:
        # the decode program's temporaries need the room
        weights = {name: np.asarray(a) if self.ref.engine_copies(name)
                   else a for name, a in models.weights(model).items()}
        del model
        gc.collect()
        run.phase("layers_to_host")
        correct = self._check(weights, self.requests.sample(t["check"]))
        del weights
        run.phase("reference")
        self.live = {}           # client -> record of its open request
        self.finished = []       # records, in order of completion
        self.steps = []          # (t0, t1, kind, active slots)
        self._pump(lambda: len(self.finished) >= t["warmup_requests"])
        run.phase("warmup")
        return correct

    def _pump(self, until, tracer=None, t_open=None):
        """Submit what is due, step, collect, until ``until()``."""
        engine, spans, m = self.engine, self.run.spans, self.engine.metrics
        clients = self.run.traffic["clients"]
        while not until():
            if tracer is not None:
                tracer.tick(time.perf_counter() - t_open)
            with spans.span("submit"):
                for c in range(clients):
                    if c not in self.live:
                        self.live[c] = self._submit(*next(self.requests))
            before = (m.prefills, m.chunk_steps)
            i = len(self.steps)
            t0 = time.perf_counter()
            with spans.span("engine_step", i):
                active = engine.step()
            kind = ("decode" if (m.prefills, m.chunk_steps) == before
                    else "admit")
            self.steps.append((t0, time.perf_counter(), kind, active))
            for c, r in list(self.live.items()):
                if r["handle"].finished:
                    self.finished.append(self.live.pop(c))

    def window(self, seconds, tracer):
        engine, m = self.engine, self.engine.metrics
        first_step = len(self.steps)
        opened = (m.occupancy_sum, m.samples, m.prompt_tokens,
                  m.prefix_hit_tokens)
        t_open = time.perf_counter()
        self._pump(lambda: time.perf_counter() - t_open
                   >= seconds + tracer.extension, tracer, t_open)
        t_close = time.perf_counter()
        tracer.stop()
        closed = (m.occupancy_sum, m.samples, m.prompt_tokens,
                  m.prefix_hit_tokens)
        # submission has stopped; what is in flight finishes outside the
        # window, only so that its failures can be counted
        engine.drain()
        requests = self.finished + list(self.live.values())
        mine = [r for r in requests if r["submit"] >= t_open]
        counters = dict(zip(("occupancy_sum", "samples", "prompt_tokens",
                             "prefix_hit_tokens"),
                            (b - a for a, b in zip(opened, closed))))
        return {"window": (t_open, t_close), "attempted": len(mine),
                "failed": sum(r["handle"].finish_reason != "length"
                              for r in mine),
                "requests": requests, "engine_steps": self.steps,
                "first_step": first_step, "engine_counters": counters,
                "engine_stats": engine.stats()}

    def report(self):
        """Medians and sample counts behind the tails, the mix of step
        kinds, and the counters the program keeps."""
        s = self.run.samples
        ttft = stats.ttfts(s["requests"], s["window"])
        gaps = stats.token_gaps(s["requests"], s["window"])
        steps = s["engine_steps"][s["first_step"]:]
        c = s["engine_counters"]
        self.run.info(
            "serve", requests_first_token_in_window=len(ttft),
            ttft_ms_median=(stats.median(ttft) or 0.0) * 1e3,
            ttft_ms_p95=(stats.percentile(ttft, 95) or 0.0) * 1e3,
            token_gaps=len(gaps),
            itl_ms_median=(stats.median(gaps) or 0.0) * 1e3,
            tokens_in_window=stats.tokens_inside(s["requests"],
                                                 s["window"]),
            engine_steps=len(steps),
            admit_steps=sum(k == "admit" for _, _, k, _ in steps),
            submit_ms_total=sum(b - a for a, b, _ in
                                self.run.spans.by_name.get("submit", ())
                                if a >= s["window"][0]) * 1e3,
            prefix_hit_pct=(100.0 * c["prefix_hit_tokens"]
                            / max(c["prompt_tokens"], 1)),
            prefill_buckets=s["engine_stats"]["prefill_buckets"],
            chunk_program=s["engine_stats"]["chunk_program"],
            preemptions=s["engine_stats"]["preemptions"],
            kv_cache_bytes=s["engine_stats"]["kv_cache_bytes"])

    def close(self):
        pass
