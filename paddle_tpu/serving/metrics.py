"""Latency/throughput ledger for the serving engine.

Per-request: TTFT (submit -> first token out of prefill), inter-token
latencies, tokens/sec. Per-engine: slot occupancy and queue depth sampled
every decode step, admission/eviction counters. Snapshots surface through
``paddle_tpu.profiler.serving_counters()`` (the same counter plumbing as
the eager dispatch cache).

Per-engine too, always on: the ``perf_counter`` stamps the engine takes
inside ``Engine.step()`` and around every program it launches, as
:class:`StepRecord` / :class:`LaunchRecord` / :class:`SubmitRecord`
tuples in bounded rings (``EngineMetrics.steps``, ``.launches``,
``.submits``; :func:`live_metrics` finds every live engine's), with
cumulative seconds by phase beside them. The same stamps feed
``mark_decode`` (so the ITL estimate covers the token fetch) and, when
the span tracer is on, its ``serving.step`` spans.
"""
from __future__ import annotations

import collections
import time
import weakref

from ..observability.metrics import Histogram


def _percentile(values, p):
    """Nearest-rank-with-interpolation percentile (no numpy needed for
    tiny ledgers; matches numpy 'linear')."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    k = (len(vals) - 1) * (p / 100.0)
    lo = int(k)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (k - lo))


class RequestMetrics:
    """Timing ledger of one request (wall-clock, perf_counter based)."""

    def __init__(self):
        self.submit_time = time.perf_counter()
        self.first_token_time = None
        self.finish_time = None
        self.token_times = []          # one stamp per emitted token

    def mark_token(self):
        now = time.perf_counter()
        if self.first_token_time is None:
            self.first_token_time = now
        self.token_times.append(now)

    def mark_finished(self):
        self.finish_time = time.perf_counter()

    @property
    def n_tokens(self):
        return len(self.token_times)

    @property
    def ttft(self):
        """Time to first token (seconds), None until the first token."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def inter_token_latencies(self):
        t = self.token_times
        return [b - a for a, b in zip(t, t[1:])]

    @property
    def tokens_per_sec(self):
        if self.finish_time is None or not self.token_times:
            return None
        dt = self.finish_time - self.submit_time
        return self.n_tokens / dt if dt > 0 else float("inf")


PHASES = ("schedule", "dispatch", "fetch", "emit")
RING = 8192     # records a ring keeps: minutes of steps at 10 ms a step


class StepRecord(collections.namedtuple(
        "StepRecord", "index kind begin scheduled dispatched fetched end "
                      "n_active")):
    """One ``Engine.step()`` that had decode-active rows. ``kind`` is
    ``decode`` (nothing prefilled), ``admit`` (a bucket prefill or a
    chunk ran beside the decode) or ``spec``. The stamps are
    ``time.perf_counter()`` reads, in order: ``begin``; ``scheduled``
    (expiry, admission, the chunk tick, decode capacity and the
    occupancy sample are done); ``dispatched`` (the decode program's
    call has returned: argument copies, upload and enqueue are behind
    it); ``fetched`` (its tokens are on the host); ``end`` (every token
    emitted). A ``spec`` step launches several programs: ``dispatched``
    is the first target launch's (the draft's proposals lie before it)
    and ``fetched`` the last one's; the launches between them are in
    the launch ring under the step's index."""
    __slots__ = ()

    def intervals(self):
        """``[(phase, t0, t1)]`` of the four phases, in order."""
        return list(zip(PHASES, self[2:6], self[3:7]))

    def phases(self):
        """Seconds of the four phases; they sum to ``end - begin``."""
        return {p: t1 - t0 for p, t0, t1 in self.intervals()}


# One engine program invoked: ``program`` as the compile scope names it
# (``prefill:L<bucket>``, ``chunk``, ``decode``, ``spec.verify``,
# ``spec.draft``, ``spec.draft:L<bucket>``), ``step`` the index of the
# ``Engine.step()`` it ran in (None inside ``submit()`` / ``adopt()``),
# ``called`` before the arguments were built, ``dispatched`` when the call
# returned, ``fetched`` when the engine had its result on the host (None
# where it fetches none: a non-final chunk), the request it served (None
# for a batched program), its tokens, and those of them the radix served.
LaunchRecord = collections.namedtuple(
    "LaunchRecord", "program step called dispatched fetched request_id "
                    "tokens radix_tokens")

# One ``Engine.submit()`` or ``adopt()`` that enqueued its request, from
# entry to return: validation, the queue, and, where a slot was free, the
# admission itself (block allocation with radix eviction, a bucket
# prefill's launch: that one also has its ``LaunchRecord``, with no step).
SubmitRecord = collections.namedtuple("SubmitRecord",
                                      "request_id begin end")


class EngineMetrics:
    """Aggregate counters for one Engine; registered in the module-wide
    ledger so profiler.serving_counters() sees every live engine."""

    def __init__(self):
        self.requests_submitted = 0
        self.requests_completed = 0
        self.requests_rejected = 0
        self.requests_timed_out = 0
        self.requests_cancelled = 0
        self.requests_shed = 0
        self.tokens_generated = 0
        self.prefills = 0
        self.decode_steps = 0
        self.occupancy_sum = 0.0       # sum over steps of active/n_slots
        self.queue_depth_sum = 0
        self.peak_queue_depth = 0
        self.samples = 0
        # paged-KV counters: admitted concurrency, pool pressure,
        # prefix sharing and chunked prefill (zero on slot engines)
        self.peak_active = 0           # max concurrently admitted
        self.preemptions = 0           # pool-exhaustion evict+replay
        self.chunked_prefills = 0      # requests that prefilled chunked
        self.chunk_steps = 0           # chunk-program invocations
        self.prefix_hit_tokens = 0     # prompt tokens served from radix
        self.prompt_tokens = 0         # total prompt tokens admitted
        self.cow_copies = 0            # partial tail blocks privatized
        self.pool_occupancy_sum = 0.0  # used/total blocks per sample
        self.pool_samples = 0
        self.pool_low_watermark = None  # min free blocks ever seen
        # speculative decoding (zero on non-speculative engines):
        # verify invocations, fused draft-decode steps, and the
        # proposed/accepted/emitted token ledger behind acceptance_rate
        self.spec_steps = 0
        self.draft_steps = 0
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_emitted_tokens = 0
        # fleet identity (stamped by the engine; None standalone) —
        # bench/chaos ledgers embedding a snapshot attribute it to the
        # replica that produced it
        self.replica = None
        # mesh geometry (stamped by the engine; tp=1 on single-device
        # engines) — surfaces underscoring at a glance in the profiler
        # serving line and the snapshot
        self.tp = 1
        # routed feed-forward (None where the model routes nothing): the
        # counters the programs keep on the device and hand on from call
        # to call, set by the engine; moe_counters() fetches them
        self.moe = None
        # what decode attention has to read (``mark_lines_seen``): the
        # K and V lines the decode-active rows could see, summed over
        # ``calls`` fused decode calls; ``in_window`` is the same with
        # each row cut to the model's window (equal without one)
        self.lines_seen = {"calls": 0, "lines": 0, "in_window": 0}
        # a latent cache's (None elsewhere; ``latent_counters``). Such a
        # cache has no K and V lines, so ``lines_seen`` stays at zero
        # there
        self.latent = None
        # a model of recurrent and window layers beside one KV layer
        # (None elsewhere; ``recurrent_counters``)
        self.recurrent = None
        self.kv_pool_bytes_per_device = None
        self.collectives_per_decode_step = None
        # decode-step wall times, histogram-backed: the ~64-observation
        # rolling window drives the live ITL p50/p95 behind
        # EngineOverloaded.retry_after_s and brownout shedding, while
        # the cumulative buckets export through the observability
        # registry's merged paddle_serving_itl_seconds family
        self.itl_hist = Histogram("serving_itl_seconds_local",
                                  window=64, registry=None)
        # the engine's own stamps (module docstring): the newest RING
        # records of each kind, and what no ring forgets
        self.steps = collections.deque(maxlen=RING)
        self.launches = collections.deque(maxlen=RING)
        self.submits = collections.deque(maxlen=RING)
        self.steps_recorded = 0
        self.launches_recorded = 0
        self.phase_seconds = dict.fromkeys(PHASES, 0.0)
        self.submit_seconds = 0.0
        _register(self)

    def mark_step(self, rec):
        self.steps.append(rec)
        self.steps_recorded += 1
        for phase, t0, t1 in rec.intervals():
            self.phase_seconds[phase] += t1 - t0

    def mark_launch(self, rec):
        self.launches.append(rec)
        self.launches_recorded += 1

    def mark_submit(self, rec):
        self.submits.append(rec)
        self.submit_seconds += rec.end - rec.begin

    def sample(self, occupancy, queue_depth, active=0, pool_free=None,
               pool_total=None):
        self.samples += 1
        self.occupancy_sum += occupancy
        self.queue_depth_sum += queue_depth
        self.peak_queue_depth = max(self.peak_queue_depth, queue_depth)
        self.peak_active = max(self.peak_active, int(active))
        if pool_total:
            self.pool_samples += 1
            self.pool_occupancy_sum += 1.0 - pool_free / pool_total
            self.pool_low_watermark = (
                pool_free if self.pool_low_watermark is None
                else min(self.pool_low_watermark, pool_free))

    def prefix_hit_rate(self):
        """Fraction of admitted prompt tokens served out of the radix
        prefix index instead of freshly-written blocks; None before any
        admission."""
        if not self.prompt_tokens:
            return None
        return self.prefix_hit_tokens / self.prompt_tokens

    def mark_decode(self, duration_s, tokens=1):
        """Record one target-model step (fused decode OR speculative
        verify): ``duration_s`` from the launch's call to its tokens on
        the host (dispatch is asynchronous on the chip; without the
        fetch this would time the enqueue). ``tokens`` is how many
        tokens the step emitted per participating request: the ITL
        histogram records PER-EMITTED-
        TOKEN intervals (``tokens`` observations of
        ``duration_s/tokens``), so the brownout SLO p95 and the
        ``retry_after_s`` hint stay meaningful when one speculative
        step yields >1 token — and stay bit-unchanged at tokens=1 (the
        non-speculative/k=0 path)."""
        self.decode_steps += 1
        n = max(int(tokens), 1)
        per = duration_s / n
        for _ in range(n):
            self.itl_hist.observe(per)

    @staticmethod
    def latent_counters(line_bytes):
        """What a latent cache counts: the bytes of a line as published;
        the latent lines the decode-active rows could see, kept as
        ``lines_seen`` is, over ``decode_calls`` fused decode calls; and
        the lines the last row of a prefill chunk could see, over
        ``chunk_calls`` calls of the chunk program."""
        return {"line_bytes": line_bytes, "decode_calls": 0, "lines": 0,
                "chunk_calls": 0, "chunk_lines": 0}

    @staticmethod
    def recurrent_counters(shared_kv_readers, held):
        """What an engine over recurrent and window layers counts, all on
        the host: the bytes ``held`` by kind (``PagedKVCache
        .bytes_by_kind``: constants of the geometry); ``scan_tokens``, the
        tokens that went through the prefill and chunk scans;
        ``decode_lines_seen``, the lines of the ONE KV layer the
        decode-active rows could see, summed over ``decode_calls`` fused
        decode calls (each of ``shared_kv_readers`` layers reads them), and
        ``decode_lines_in_window``, the same with each row cut to the
        window (what a window layer's ring holds; ``lines_seen`` stays at
        zero here, as for a latent cache: its readers count a llama's
        bytes);
        ``state_resets``, prompts whose first rows started a slot's state
        from zero, and ``state_replays``, those of them that rebuilt it
        from ``prompt + tokens`` after a pre-emption or an ``adopt()``."""
        return dict(held, scan_tokens=0, decode_calls=0, decode_lines_seen=0,
                    decode_lines_in_window=0,
                    shared_kv_readers=int(shared_kv_readers),
                    state_resets=0, state_replays=0)

    def mark_scan(self, tokens, first=False, replay=False):
        """One prefill or chunk call over ``tokens`` of a prompt; its
        ``first`` rows reset the slot's state (a ``replay``: of a request
        that had already emitted tokens)."""
        r = self.recurrent
        if r is not None:
            r["scan_tokens"] += int(tokens)
            r["state_resets"] += bool(first)
            r["state_replays"] += bool(first and replay)

    def mark_chunk(self, end):
        """One call of the chunk program, over the positions before
        ``end``: the last row sees ``end`` lines."""
        self.chunk_steps += 1
        if self.latent is not None:
            self.latent["chunk_calls"] += 1
            self.latent["chunk_lines"] += int(end)

    def mark_lines_seen(self, seen, window=None):
        """One fused decode call whose active rows see ``seen`` lines
        each (a host array: the position a row writes, plus one)."""
        if self.latent is not None:
            self.latent["decode_calls"] += 1
            self.latent["lines"] += int(seen.sum())
            return
        if self.recurrent is not None:
            r = self.recurrent
            r["decode_calls"] += 1
            r["decode_lines_seen"] += int(seen.sum())
            r["decode_lines_in_window"] += int(seen.clip(max=window).sum())
            return
        t = self.lines_seen
        t["calls"] += 1
        t["lines"] += int(seen.sum())
        t["in_window"] += int((seen.clip(max=window) if window
                               else seen).sum())

    def acceptance_rate(self):
        """Fraction of proposed draft tokens the verify pass accepted;
        None before any speculative step."""
        if not self.spec_proposed_tokens:
            return None
        return self.spec_accepted_tokens / self.spec_proposed_tokens

    def itl_estimate(self):
        """Rolling-window median decode-step wall time (seconds), None
        before the first decode — one decode step advances every active
        slot one token, so this IS the current inter-token latency."""
        return self.itl_hist.percentile(50)

    def itl_p95(self):
        """p95 of the rolling decode-step histogram window (seconds) —
        the tail latency that the brownout SLO in serving.resilience
        gates on AND the basis of ``EngineOverloaded.retry_after_s``;
        None before the first decode step."""
        return self.itl_hist.percentile(95)

    def moe_counters(self):
        """The routed layers' load, fetched from the device now (the
        only fetch there is of it: no step makes one). ``expert_tokens``
        ``[layer][expert]``: the picks an expert has computed, over every
        prefill, chunk and decode call, padding and idle slots left out;
        ``experts_hit`` ``[layer]``: the distinct experts a decode call
        touched, summed over ``decode_calls`` calls. An engine that
        holds a share of each layer's experts counts the held ones
        (``expert_tokens`` ``[routed layer][held]``), and beside them
        ``picks``, every pick its rows made on any expert, and
        ``picks_held``, those that landed here. None where the model
        routes nothing."""
        if self.moe is None:
            return None
        import numpy as np
        out = {"expert_tokens": np.asarray(
                   self.moe["expert_tokens"]).tolist(),
               "experts_hit": np.asarray(self.moe["experts_hit"]).tolist(),
               "decode_calls": int(self.moe["decode_calls"])}
        if "picks" in self.moe:
            out["picks"] = int(self.moe["picks"])
            out["picks_held"] = int(np.sum(out["expert_tokens"]))
        return out

    def snapshot(self):
        n = max(self.samples, 1)
        itl = self.itl_estimate()
        p95 = self.itl_p95()
        hr = self.prefix_hit_rate()
        ar = self.acceptance_rate()
        return {
            "spec_steps": self.spec_steps,
            "draft_steps": self.draft_steps,
            "spec_proposed_tokens": self.spec_proposed_tokens,
            "spec_accepted_tokens": self.spec_accepted_tokens,
            "spec_emitted_tokens": self.spec_emitted_tokens,
            "spec_acceptance_rate": (None if ar is None
                                     else round(ar, 4)),
            "requests_submitted": self.requests_submitted,
            "requests_completed": self.requests_completed,
            "requests_rejected": self.requests_rejected,
            "requests_timed_out": self.requests_timed_out,
            "requests_cancelled": self.requests_cancelled,
            "requests_shed": self.requests_shed,
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "decode_steps": self.decode_steps,
            "decode_lines_seen": dict(self.lines_seen),
            "avg_slot_occupancy": round(self.occupancy_sum / n, 4),
            "avg_queue_depth": round(self.queue_depth_sum / n, 4),
            "peak_queue_depth": self.peak_queue_depth,
            "peak_active": self.peak_active,
            "preemptions": self.preemptions,
            "chunked_prefills": self.chunked_prefills,
            "chunk_steps": self.chunk_steps,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens": self.prompt_tokens,
            "prefix_hit_rate": (None if hr is None else round(hr, 4)),
            "cow_copies": self.cow_copies,
            "pool_occupancy": (
                None if not self.pool_samples
                else round(self.pool_occupancy_sum / self.pool_samples,
                           4)),
            "pool_low_watermark": self.pool_low_watermark,
            "itl_estimate_ms": (None if itl is None
                                else round(itl * 1e3, 3)),
            "itl_p95_ms": (None if p95 is None
                           else round(p95 * 1e3, 3)),
            "steps_recorded": self.steps_recorded,
            "launches_recorded": self.launches_recorded,
            "step_phase_seconds": {k: round(v, 6) for k, v in
                                   self.phase_seconds.items()},
            "submit_seconds": round(self.submit_seconds, 6),
            "replica": self.replica,
            "tp": self.tp,
            "kv_pool_bytes_per_device": self.kv_pool_bytes_per_device,
            "collectives_per_decode_step":
                self.collectives_per_decode_step,
            **({} if self.moe is None else {"moe": self.moe_counters()}),
            **({} if self.latent is None else {"latent": dict(self.latent)}),
            **({} if self.recurrent is None
               else {"recurrent": dict(self.recurrent)}),
        }


_ENGINES = []   # weakrefs; dead engines drop out of the global snapshot


def _register(m):
    _ENGINES.append(weakref.ref(m))


def live_metrics():
    """The ``EngineMetrics`` of every live engine, oldest first: the way
    to their rings (``.steps``, ``.launches``, ``.submits``:
    :class:`StepRecord`, :class:`LaunchRecord` and :class:`SubmitRecord`,
    oldest record first, every stamp a ``time.perf_counter()`` of this
    process) for whoever holds no engine."""
    live = [(ref, ref()) for ref in _ENGINES]
    _ENGINES[:] = [ref for ref, m in live if m is not None]
    return [m for _, m in live if m is not None]


def global_counters():
    """Summed snapshot across every live engine (profiler plumbing)."""
    total = {
        "engines": 0, "requests_submitted": 0, "requests_completed": 0,
        "requests_rejected": 0, "requests_timed_out": 0,
        "requests_cancelled": 0, "requests_shed": 0,
        "tokens_generated": 0, "prefills": 0,
        "decode_steps": 0, "peak_queue_depth": 0,
        "preemptions": 0, "chunked_prefills": 0, "chunk_steps": 0,
        "prefix_hit_tokens": 0, "prompt_tokens": 0, "cow_copies": 0,
        "peak_active": 0, "prefix_hit_rate": None,
        "pool_low_watermark": None, "tp_max": 1,
        "spec_steps": 0, "draft_steps": 0, "spec_proposed_tokens": 0,
        "spec_accepted_tokens": 0, "spec_emitted_tokens": 0,
        "spec_acceptance_rate": None,
        "step_phase_seconds": dict.fromkeys(PHASES, 0.0),
    }
    for m in live_metrics():
        s = m.snapshot()
        total["engines"] += 1
        for k, v in m.phase_seconds.items():
            total["step_phase_seconds"][k] += v
        for k in ("requests_submitted", "requests_completed",
                  "requests_rejected", "requests_timed_out",
                  "requests_cancelled", "requests_shed",
                  "tokens_generated", "prefills", "decode_steps",
                  "preemptions", "chunked_prefills", "chunk_steps",
                  "prefix_hit_tokens", "prompt_tokens", "cow_copies",
                  "spec_steps", "draft_steps", "spec_proposed_tokens",
                  "spec_accepted_tokens", "spec_emitted_tokens"):
            total[k] += s[k]
        total["peak_queue_depth"] = max(total["peak_queue_depth"],
                                        s["peak_queue_depth"])
        total["peak_active"] = max(total["peak_active"], s["peak_active"])
        total["tp_max"] = max(total["tp_max"], s.get("tp", 1))
        if s["pool_low_watermark"] is not None:
            lw = total["pool_low_watermark"]
            total["pool_low_watermark"] = (
                s["pool_low_watermark"] if lw is None
                else min(lw, s["pool_low_watermark"]))
    if total["prompt_tokens"]:
        total["prefix_hit_rate"] = round(
            total["prefix_hit_tokens"] / total["prompt_tokens"], 4)
    if total["spec_proposed_tokens"]:
        total["spec_acceptance_rate"] = round(
            total["spec_accepted_tokens"]
            / total["spec_proposed_tokens"], 4)
    return total


def ledger(handles):
    """Aggregate a finished workload's handles into one latency ledger
    (p50/p95 TTFT and inter-token latency in ms, total tokens/sec)."""
    done = [h for h in handles if h.metrics.finish_time is not None]
    ttfts = [h.metrics.ttft for h in done if h.metrics.ttft is not None]
    itls = [d for h in done for d in h.metrics.inter_token_latencies]
    total_tokens = sum(h.metrics.n_tokens for h in done)
    t0 = min((h.metrics.submit_time for h in done), default=0.0)
    t1 = max((h.metrics.finish_time for h in done), default=0.0)
    wall = max(t1 - t0, 1e-9)
    ms = 1e3
    return {
        "requests": len(done),
        "total_new_tokens": total_tokens,
        "tokens_per_sec": round(total_tokens / wall, 2),
        "ttft_ms_p50": round((_percentile(ttfts, 50) or 0) * ms, 3),
        "ttft_ms_p95": round((_percentile(ttfts, 95) or 0) * ms, 3),
        "itl_ms_p50": round((_percentile(itls, 50) or 0) * ms, 3),
        "itl_ms_p95": round((_percentile(itls, 95) or 0) * ms, 3),
    }
