"""tpu_lint front ends: build a :class:`ProgramView` from whatever the
caller has — a jittable callable + example args, a Layer, raw StableHLO
text, a static-executor replay plan, a serving Engine, or the live
eager-dispatch cache — then run every registered program rule over it.
``selflint`` is the AST front end over python source files.
"""
from __future__ import annotations

import os

from . import rules_ast as _rules_ast  # noqa: F401  (registers rules)
from . import rules_program as _rules_prog  # noqa: F401  (registers rules)
from .findings import Report
from .hlo import parse_stablehlo
from .registry import iter_rules
from .rules_ast import SourceFile

# most recent reports, surfaced as one line in profiler.Profiler.summary()
_last_report = None


class ProgramView:
    """One audited program: lowered StableHLO text (parsed lazily),
    optionally the traced jaxpr, plus origin metadata the meta-level
    rules (plan/engine/dispatch) read."""

    def __init__(self, name, kind, stablehlo=None, jaxpr=None, meta=None):
        self.name = name
        self.kind = kind            # callable|stablehlo|plan|engine|dispatch
        self.stablehlo = stablehlo
        self.jaxpr = jaxpr          # ClosedJaxpr or None
        self.meta = dict(meta or {})
        self.metrics = {}
        self._module = None

    @property
    def module(self):
        if self._module is None and self.stablehlo:
            self._module = parse_stablehlo(self.stablehlo)
        return self._module

    def iter_eqns(self):
        """(eqn, path) over the jaxpr, recursing into sub-jaxprs
        (pjit/scan/cond bodies)."""
        if self.jaxpr is None:
            return
        yield from _walk_jaxpr(getattr(self.jaxpr, "jaxpr", self.jaxpr),
                               "")

    def run_rules(self, rules=None) -> Report:
        global _last_report
        report = Report(origin=f"{self.kind}:{self.name}")
        for r in iter_rules(kind="program", ids=rules):
            for f in r.run(self):
                report.add(f)
        report.metrics.update(self.metrics)
        _last_report = report
        return report


def _walk_jaxpr(jaxpr, prefix):
    for i, eqn in enumerate(jaxpr.eqns):
        path = f"{prefix}eqn[{i}]:{eqn.primitive.name}"
        yield eqn, path
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_jaxpr(sub, path + "/")


def _sub_jaxprs(params):
    for v in params.values():
        yield from _as_jaxprs(v)


def _as_jaxprs(v):
    # ClosedJaxpr / Jaxpr duck-typing: avoids importing private core
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _as_jaxprs(x)


# -- callable / model front end ---------------------------------------------

def _is_tensorish(fn, flat_args):
    from ..nn.layer_base import Layer
    from ..tensor import Tensor
    if isinstance(fn, Layer) or isinstance(getattr(fn, "__self__", None),
                                           Layer):
        return True
    return any(isinstance(a, Tensor) for a in flat_args)


def _unhashable_statics(args, kwargs):
    import jax
    import numpy as np
    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path((args, kwargs))
    for path, leaf in flat:
        if isinstance(leaf, (jax.Array, np.ndarray, np.generic)):
            continue
        try:
            hash(leaf)
        except TypeError:
            out.append((jax.tree_util.keystr(path),
                        type(leaf).__name__))
    return out


def _aliased_donations(args, donate_argnums):
    import jax
    if not donate_argnums:
        return []
    ids = {}
    out = []
    for i, a in enumerate(args):
        for leaf in jax.tree_util.tree_leaves(a):
            if not hasattr(leaf, "dtype"):
                continue
            j = ids.setdefault(id(leaf), i)
            if j != i and (i in donate_argnums or j in donate_argnums):
                out.append(f"args {j} and {i} share a buffer")
    return out


def audit(fn, *args, donate_argnums=(), name=None, rules=None,
          **kwargs) -> Report:
    """Trace + lower ``fn`` on the example arguments and run every
    program rule over the jaxpr and emitted StableHLO.

    Accepts plain jax-array callables (lowered directly, honoring
    ``donate_argnums``) and paddle Tensor/Layer callables (lowered
    through ``jit.to_static``'s StaticFunction, which hoists Layer
    parameters into jit arguments).
    """
    import jax

    flat_args = jax.tree_util.tree_leaves((args, kwargs))
    label = name or getattr(fn, "__name__", None) or type(fn).__name__
    meta = {"unhashable_statics": _unhashable_statics(args, kwargs),
            "aliased_donations": _aliased_donations(args, donate_argnums),
            "donate_argnums": tuple(donate_argnums)}

    text = None
    jaxpr = None
    try:
        if _is_tensorish(fn, flat_args):
            from ..nn.layer_base import Layer
            target = fn.forward if isinstance(fn, Layer) else fn
            from ..jit.api import StaticFunction
            sf = StaticFunction(target, convert_control_flow=False)
            text = sf.lower(*args, **kwargs).as_text()
        else:
            jfn = jax.jit(fn, donate_argnums=tuple(donate_argnums))
            text = jfn.lower(*args, **kwargs).as_text()
            try:
                jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
            except Exception as e:
                meta["jaxpr_error"] = f"{type(e).__name__}: {e}"
    except Exception as e:
        # un-lowerable example args (unhashable statics, non-array
        # leaves) are themselves a finding, not an audit crash: record
        # why and let retrace-risk report the offending leaves
        meta["lowering_error"] = f"{type(e).__name__}: {str(e)[:200]}"

    view = ProgramView(label, "callable", stablehlo=text, jaxpr=jaxpr,
                       meta=meta)
    return view.run_rules(rules)


def audit_model(model, *args, rules=None, **kwargs) -> Report:
    """Audit a Layer's jitted forward on example inputs (params hoisted
    as jit arguments, exactly what ``jit.to_static`` would compile)."""
    return audit(model, *args, rules=rules,
                 name=type(model).__name__, **kwargs)


def audit_stablehlo(text, name="stablehlo", rules=None) -> Report:
    """Audit an already-lowered StableHLO module (text form)."""
    return ProgramView(name, "stablehlo", stablehlo=text).run_rules(rules)


# -- plan / engine / dispatch front ends ------------------------------------

def _describe_entry(e):
    try:
        kind = e[0]
        if kind == "op":
            fn = e[1]
            label = getattr(fn, "__name__", type(fn).__name__)
            return f"op:{label}"
        return str(kind)
    except (AttributeError, IndexError, TypeError):
        return "host entry"


def audit_plan(plan_or_program, *batch, rules=None,
               name="replay_plan") -> Report:
    """Audit a static-executor replay plan (or every cached plan of a
    ``static.Program``): host splits, donation, fragmentation. A Fleet
    train step (anything exposing ``lower_hlo``) delegates to
    :func:`audit_train_step`, so the one entry point covers both
    compiled-training front ends."""
    from ..static.program import _ReplayPlan

    if hasattr(plan_or_program, "lower_hlo"):
        return audit_train_step(plan_or_program, *batch, rules=rules)
    if not isinstance(plan_or_program, _ReplayPlan):
        cache = getattr(plan_or_program, "_jit_cache", None) or {}
        plans = [p for p in cache.values() if p is not None]
        if not plans:
            raise ValueError(
                "program has no compiled replay plan yet — run the "
                "Executor at least twice so the plan builds")
        report = Report(origin=f"plan:{name}")
        for i, p in enumerate(plans):
            report.extend(audit_plan(p, rules=rules, name=f"{name}[{i}]"))
        global _last_report
        _last_report = report
        return report

    plan = plan_or_program
    host_entries = []
    segments = []
    for idx, (kind, payload) in enumerate(plan.steps):
        if kind == "host":
            host_entries.append((_describe_entry(payload), idx))
        else:
            segments.append({
                "index": idx, "donated": payload.donated,
                "n_state": len(payload.state_specs),
                "alias_count": payload.alias_count})
    meta = {"host_entries": host_entries, "segments": segments,
            "n_segments": len(segments), "n_host": plan.n_host,
            # segmented plans can't donate by design: don't double-count
            # the donation finding on top of the host-split finding
            "segmented": len(segments) > 1}
    return ProgramView(name, "plan", meta=meta).run_rules(rules)


def audit_train_step(step, *batch, rules=None) -> Report:
    """Audit a compiled Fleet train step (``CompiledTrainStep`` or
    ``distributed.comm_opt.CommOptTrainStep``) on an example batch: the
    REAL step program — forward, backward, gradient exchange and the
    optimizer update — is lowered and every program rule runs over its
    StableHLO. The ``unoverlapped-collective`` rule is the headline:
    a TP training matmul whose collective serializes after the dot
    (the GSPMD/serial form) is a high finding here, exactly like
    ``audit_engine`` gates the serving decode program."""
    meta = {"train_step": type(step).__name__}
    for attr in ("grad_compress", "zero1", "tp_overlap", "dp", "tp",
                 "stage", "accumulate_steps"):
        if hasattr(step, attr):
            meta[attr] = getattr(step, attr)
    try:
        from ..aot import aot_stats
        meta["aot"] = aot_stats()
    except Exception as e:
        meta["aot_error"] = f"{type(e).__name__}: {e}"
    text = None
    try:
        text = step.lower_hlo(*batch)
    except Exception as e:
        meta["lowering_error"] = f"{type(e).__name__}: {str(e)[:200]}"
    return ProgramView(type(step).__name__, "train_step", stablehlo=text,
                       meta=meta).run_rules(rules)


def audit_engine(engine, compile_budget=None, rules=None,
                 lower_decode=True) -> Report:
    """Audit a serving Engine: compile-count budget, bucket/KV geometry,
    donation policy — plus, when possible, the lowered decode program
    itself (dtype / padding rules see real HLO).

    Accepts a ``serving.resilience.EngineSupervisor`` too: the live
    engine incarnation is audited, and the compile budget accounts the
    UNION of prefill buckets across every rebuilt incarnation — an
    in-process rebuild re-traces nothing (module-level jit cache), but a
    fresh process pays the union, so that is the honest budget."""
    import jax

    from .engine_support import lower_decode_program

    supervisor = None
    if hasattr(engine, "buckets_seen_total") and hasattr(engine, "engine"):
        supervisor = engine
        engine = supervisor.engine
    buckets = set(engine.buckets_seen)
    chunk_used = bool(getattr(engine, "chunk_used", False))
    verify_used = bool(getattr(engine, "verify_used", False))
    draft_buckets = set(getattr(engine, "draft_buckets_seen", ()))
    draft_decode = bool(getattr(engine, "draft_decode_used", False))
    if supervisor is not None:
        buckets |= supervisor.buckets_seen_total
        chunk_used |= bool(getattr(supervisor, "chunk_used_total", False))
        verify_used |= bool(getattr(supervisor, "verify_used_total",
                                    False))
        draft_buckets |= set(getattr(supervisor, "draft_buckets_total",
                                     ()))
        draft_decode |= bool(getattr(supervisor,
                                     "draft_decode_used_total", False))
    meta = {
        "n_slots": engine.n_slots, "max_len": engine.max_len,
        "min_prompt_bucket": engine.min_prompt_bucket,
        "buckets_seen": sorted(buckets),
        "decode_used": engine.metrics.decode_steps > 0
        or bool(buckets),
        "compile_budget": (compile_budget if compile_budget is not None
                           else engine.compile_budget),
        "backend": jax.default_backend(),
        "donate": engine._donate,
        "kv_heads": engine.cache.kv_heads,
        "head_dim": engine.cache.head_dim,
        "block_size": engine.block_size,
        "n_blocks": engine.cache.pool.n_blocks,
        "prefill_chunk": engine.prefill_chunk,
        "chunk_used": chunk_used,
        "tp": getattr(engine, "tp", 1),
        "mesh": (engine.tp_geometry()
                 if hasattr(engine, "tp_geometry") else None),
    }
    spec = getattr(engine, "spec", None)
    if spec is not None:
        # speculative config + program usage (the compile-budget rule
        # counts the verify program and any draft-model lowerings) and
        # the acceptance ledger — across supervisor incarnations when
        # audited through one
        m = engine.metrics
        acc = {k: getattr(m, k, 0)
               for k in ("spec_steps", "draft_steps",
                         "spec_proposed_tokens", "spec_accepted_tokens",
                         "spec_emitted_tokens")}
        if supervisor is not None and hasattr(supervisor,
                                              "spec_counters"):
            acc = supervisor.spec_counters()
        rate = (acc["spec_accepted_tokens"] / acc["spec_proposed_tokens"]
                if acc["spec_proposed_tokens"] else None)
        meta["spec"] = {
            "k": spec.k, "draft": spec.draft_kind(),
            "verify_used": verify_used,
            "draft_buckets_seen": sorted(draft_buckets),
            "draft_decode_used": draft_decode,
            "acceptance": {**acc, "rate": rate}}
    # AOT warm-start visibility: programs restored from the executable
    # cache cost a fresh process zero backend compiles — the honest
    # warm-start compile count is programs minus disk-exec entries
    try:
        from ..aot import aot_stats
        sources = engine.aot_stats() if hasattr(engine, "aot_stats") \
            else {}
        # "live" programs have no persisted entry (a restart compiles
        # them); "compiled" ones were persisted at build and "disk-exec"
        # ones restored — both cost a warm restart nothing; "disk-hlo"
        # pays one recompile-from-StableHLO
        meta["aot"] = {**aot_stats(), "engine_programs": sources,
                       "warm_start_compiles": sum(
                           n for k, n in sources.items()
                           if k in ("live", "disk-hlo"))}
    except Exception as e:
        meta["aot_error"] = f"{type(e).__name__}: {e}"
    if supervisor is not None:
        meta["supervisor"] = {"rebuilds": supervisor.rebuilds,
                              "replayed": supervisor.replayed}
    text = None
    if lower_decode:
        try:
            text = lower_decode_program(engine)
        except Exception as e:
            meta["decode_lowering_error"] = f"{type(e).__name__}: {e}"
    return ProgramView(f"Engine[{type(engine).__name__}]", "engine",
                       stablehlo=text, meta=meta).run_rules(rules)


def audit_fleet(fleet, compile_budget=None, rules=None,
                lower_decode=False) -> Report:
    """Audit a ``serving.fleet.ReplicaFleet``: the compile budget is the
    UNION of prefill buckets (+ decode + chunk) across EVERY replica and
    every supervisor-rebuilt incarnation — in-process the replicas share
    the module-level jitted programs, so an N-replica fleet legitimately
    budgets as ONE engine (0 extra lowerings is the fleet contract,
    gated by ``tools/check_serving_compiles.py --fleet N``), and a fresh
    process pays exactly that union. Geometry/donation meta comes from
    replica 0 (fleet replicas share engine kwargs; tp degree may vary
    per replica and is reported per replica)."""
    import jax

    replicas = list(fleet.replicas.values())
    buckets: set = set()
    chunk_used = False
    decode_used = False
    per_replica = {}
    for rep in replicas:
        sup = rep.sup
        b = set(sup.engine.buckets_seen) | sup.buckets_seen_total
        buckets |= b
        chunk_used |= (bool(getattr(sup.engine, "chunk_used", False))
                       or bool(sup.chunk_used_total))
        decode_used |= sup.engine.metrics.decode_steps > 0 or bool(b)
        per_replica[rep.id] = {
            "state": rep.state, "tp": sup.engine.tp,
            "buckets_seen": sorted(b), "rebuilds": sup.rebuilds,
            "replayed": sup.replayed}
    first = replicas[0].engine
    if compile_budget is None:
        compile_budget = first.compile_budget
    meta = {
        "n_slots": first.n_slots, "max_len": first.max_len,
        "min_prompt_bucket": first.min_prompt_bucket,
        "buckets_seen": sorted(buckets),
        "decode_used": decode_used,
        "compile_budget": compile_budget,
        "backend": jax.default_backend(),
        "donate": first._donate,
        "kv_heads": first.cache.kv_heads,
        "head_dim": first.cache.head_dim,
        "block_size": first.block_size,
        "n_blocks": first.cache.pool.n_blocks,
        "prefill_chunk": first.prefill_chunk,
        "chunk_used": chunk_used,
        "fleet": {"name": fleet.name, "n_replicas": len(replicas),
                  "states": fleet.replica_states(),
                  "counters": fleet.counters(),
                  "per_replica": per_replica},
    }
    text = None
    if lower_decode:
        from .engine_support import lower_decode_program
        try:
            text = lower_decode_program(first)
        except Exception as e:
            meta["decode_lowering_error"] = f"{type(e).__name__}: {e}"
    report = ProgramView(f"ReplicaFleet[{len(replicas)}]", "engine",
                         stablehlo=text, meta=meta).run_rules(rules)
    # the fleet view rides in the report's measurements (Report carries
    # metrics, not meta) — tools embed it in their JSON ledgers
    report.metrics["fleet"] = meta["fleet"]
    return report


def audit_dispatch(rules=None) -> Report:
    """Audit the live eager-dispatch cache: blacklisted ops (with the
    recorded reason), megamorphic signatures, retrace pressure — plus
    the AOT compile-service view (warm-start compile counts with the
    executable cache enabled, key-instability findings)."""
    from ..aot import aot_stats
    from ..framework.dispatch_cache import dispatch_stats

    meta = {"dispatch_stats": dispatch_stats(), "aot": aot_stats()}
    return ProgramView("eager-dispatch", "dispatch",
                       meta=meta).run_rules(rules)


# -- AST self-lint front end -------------------------------------------------

def _iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def selflint(paths, rules=None) -> Report:
    """Run the AST rules over python source files/directories."""
    global _last_report
    report = Report(origin=f"selflint:{','.join(map(str, paths))}")
    n_files = 0
    for path in _iter_py_files(paths):
        n_files += 1
        sf = SourceFile.load(path)
        if sf.parse_error:
            from .findings import Finding
            report.add(Finding("parse-error", "info", sf.parse_error,
                               location=path))
            continue
        for r in iter_rules(kind="ast", ids=rules):
            for f in r.run(sf):
                report.add(f)
    report.metrics["selflint"] = {"files": n_files}
    _last_report = report
    return report


def findings_summary():
    """One-line summary of the most recent audit (None when nothing has
    been audited yet) — wired into profiler.Profiler.summary()."""
    if _last_report is None:
        return None
    return _last_report.summary_line()
