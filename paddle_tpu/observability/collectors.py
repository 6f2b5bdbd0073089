"""Pull-time collectors: the pre-existing counter sources (eager
dispatch cache, serving engines + paged KV pool, train/serving
resilience ledgers, engine supervisors) exported through the metrics
registry without touching their hot paths.

Each collector imports its source lazily and tolerates the subsystem
being unused (empty families, never an import at module load — the
observability package must be importable before everything else).
"""
from __future__ import annotations

from .metrics import DEFAULT_LATENCY_BUCKETS, register_collector


def _fam(name, kind, help, samples):
    return {"name": name, "kind": kind, "help": help, "samples": samples}


def _dispatch_families():
    from ..framework import dispatch_cache

    s = dispatch_cache.dispatch_stats()
    yield _fam("paddle_dispatch_events_total", "counter",
               "eager dispatch-cache events by kind",
               [({"kind": k}, s[k]) for k in
                ("hits", "misses", "compiles", "bypasses",
                 "invalidations")])
    yield _fam("paddle_dispatch_entries", "gauge",
               "live compiled entries in the eager dispatch cache",
               [({}, s["entries"])])
    yield _fam("paddle_dispatch_enabled", "gauge",
               "1 when the eager dispatch cache is enabled",
               [({}, 1 if s["enabled"] else 0)])


def _serving_families():
    from ..serving import metrics as sm

    t = sm.global_counters()
    counter_keys = (
        "requests_submitted", "requests_completed", "requests_rejected",
        "requests_timed_out", "requests_cancelled", "requests_shed",
        "tokens_generated", "prefills", "decode_steps", "preemptions",
        "chunked_prefills", "chunk_steps", "prefix_hit_tokens",
        "prompt_tokens", "cow_copies", "spec_steps", "draft_steps",
        "spec_proposed_tokens", "spec_accepted_tokens",
        "spec_emitted_tokens")
    yield _fam("paddle_serving_events_total", "counter",
               "serving-engine counters summed across live engines",
               [({"kind": k}, t[k]) for k in counter_keys])
    yield _fam("paddle_serving_step_phase_seconds_total", "counter",
               "seconds of Engine.step() by phase (schedule, dispatch, "
               "fetch, emit) summed across live engines",
               [({"phase": k}, v)
                for k, v in t["step_phase_seconds"].items()])
    gauges = [("engines", t["engines"]),
              ("peak_queue_depth", t["peak_queue_depth"]),
              ("peak_active", t["peak_active"])]
    if t["prefix_hit_rate"] is not None:
        gauges.append(("prefix_hit_rate", t["prefix_hit_rate"]))
    if t.get("spec_acceptance_rate") is not None:
        gauges.append(("spec_acceptance_rate",
                       t["spec_acceptance_rate"]))
    if t["pool_low_watermark"] is not None:
        gauges.append(("pool_low_watermark", t["pool_low_watermark"]))
    yield _fam("paddle_serving_gauge", "gauge",
               "serving-engine point-in-time values",
               [({"kind": k}, v) for k, v in gauges])
    # merged ITL histogram across live engines (same bucket bounds)
    counts = [0] * (len(DEFAULT_LATENCY_BUCKETS) + 1)
    total_sum, total_count = 0.0, 0
    for m in sm.live_metrics():
        s, c = m.itl_hist.merge_counts(counts)
        total_sum += s
        total_count += c
    if total_count:
        cum, buckets = 0, []
        for b, c in zip(DEFAULT_LATENCY_BUCKETS, counts):
            cum += c
            buckets.append((b, cum))
        buckets.append((float("inf"), cum + counts[-1]))
        yield {"name": "paddle_serving_itl_seconds", "kind": "histogram",
               "help": "decode-step wall time (= inter-token latency) "
                       "across live engines",
               "buckets": buckets, "sum": total_sum,
               "count": total_count}


def _fleet_families():
    from ..serving import fleet as fl

    t = fl.global_counters()
    yield _fam("paddle_serving_fleets", "gauge",
               "live replica fleets", [({}, t["fleets"])])
    if not t["fleets"]:
        return
    counter_keys = ("routed", "prefix_routed", "migrations", "failovers",
                    "replica_kills", "route_flaps", "fleet_sheds",
                    "backoffs", "retries", "re_registers")
    yield _fam("paddle_serving_fleet_events_total", "counter",
               "fleet routing/failover/migration counters summed "
               "across live fleets",
               [({"kind": k}, t[k]) for k in counter_keys])
    # the replica health state machine, one gauge child per replica:
    # 0=healthy 1=degraded 2=draining 3=condemned (REPLICA_STATES order)
    samples = []
    for f in fl.live_fleets():
        for rid, state in f.replica_states().items():
            samples.append(({"fleet": f.name, "replica": rid},
                            fl.REPLICA_STATES.index(state)))
    if samples:
        yield _fam("paddle_serving_replica_state", "gauge",
                   "replica health state "
                   "(0=healthy 1=degraded 2=draining 3=condemned)",
                   samples)


def _resilience_families():
    from ..resilience import ledger

    for scope in ("train", "serving", "fleet"):
        t = ledger.global_counters(scope=scope)
        n = t.pop("ledgers", 0)
        yield _fam(f"paddle_resilience_{scope}_ledgers", "gauge",
                   f"live {scope}-scope flight ledgers", [({}, n)])
        if t:
            yield _fam(
                f"paddle_resilience_{scope}_events_total", "counter",
                f"{scope} flight-ledger events by kind",
                [({"event": k}, v) for k, v in sorted(t.items())])


def _serving_resilience_families():
    from ..serving import resilience as sr

    t = sr.global_counters()
    n = t.pop("supervisors", 0)
    yield _fam("paddle_serving_supervisors", "gauge",
               "live engine supervisors", [({}, n)])
    yield _fam("paddle_serving_resilience_events_total", "counter",
               "engine-supervisor counters summed across live "
               "supervisors",
               [({"kind": k}, v) for k, v in sorted(t.items())])


def _aot_families():
    from ..aot import get_service

    s = get_service().stats()
    yield _fam("paddle_aot_cache_enabled", "gauge",
               "1 when the persistent AOT executable cache is active",
               [({}, 1 if s["persistent"] else 0)])
    yield _fam("paddle_aot_cache_events_total", "counter",
               "AOT compile-service events by kind",
               [({"kind": k}, s[k]) for k in
                ("hits", "misses", "disk_exec_hits", "disk_hlo_hits",
                 "fingerprint_hits", "compiled", "corrupt_entries",
                 "persist_errors")])
    # store size: primary cache dir + read-only artifact sources
    yield _fam("paddle_aot_cache_bytes", "gauge",
               "bytes of serialized executables on disk by store",
               [({"dir": d["dir"]}, d["bytes"]) for d in s["disk"]])
    yield _fam("paddle_aot_cache_entries", "gauge",
               "serialized executable entries on disk by store",
               [({"dir": d["dir"]}, d["entries"]) for d in s["disk"]])


def _comm_families():
    from ..distributed.comm_opt import global_comm_stats

    s = global_comm_stats()
    if not s["steps"]:
        return
    yield _fam("paddle_comm_opt_steps", "gauge",
               "live comm-opt train steps", [({}, s["steps"])])
    # the byte COUNTERS live on the registry directly
    # (paddle_collective_bytes_total); the per-arm ratio is a pull-time
    # gauge because it is a static property of each live step's config
    yield _fam(
        "paddle_comm_compression_ratio", "gauge",
        "fp32 gradient-exchange bytes / actual wire bytes per live "
        "comm-opt step",
        [({"arm": str(i),
           "compress": a["grad_compress"] or "none",
           "zero1": "1" if a["zero1"] else "0",
           "tp": str(a["tp"])}, a["compression_ratio"])
         for i, a in enumerate(s["arms"])])


def install_default_collectors():
    """Attach the built-in sources to the default registry (idempotent:
    re-registration under the same name replaces)."""
    register_collector(_dispatch_families, "dispatch")
    register_collector(_serving_families, "serving")
    register_collector(_fleet_families, "fleet")
    register_collector(_resilience_families, "resilience")
    register_collector(_serving_resilience_families, "serving_resilience")
    register_collector(_aot_families, "aot")
    register_collector(_comm_families, "comm_opt")
