"""Program rules: static TPU perf/correctness hazards visible in a
traced jaxpr / lowered StableHLO program (or in the metadata of a
static-executor :class:`_ReplayPlan` / serving ``Engine``).

Every rule takes a :class:`~paddle_tpu.analysis.audit.ProgramView` and
yields findings; measurements land in ``view.metrics`` even when a rule
is clean, so thin CLIs (``tools/check_hlo_layout.py``) can report counts
without re-parsing.
"""
from __future__ import annotations

from .findings import Finding
from .hlo import classify_transposes
from .registry import rule

_BYTES = {"f64": 8, "i64": 8, "ui64": 8, "c64": 8, "c128": 16,
          "f32": 4, "i32": 4, "ui32": 4,
          "f16": 2, "bf16": 2, "i16": 2, "ui16": 2,
          "i8": 1, "ui8": 1, "i1": 1,
          "f8e4m3fn": 1, "f8e5m2": 1}

_FLOATS = {"f64": 64, "f32": 32, "f16": 16, "bf16": 16,
           "f8e4m3fn": 8, "f8e5m2": 8}


def _nbytes(t):
    return t.elems * _BYTES.get(t.dtype, 4)


def _mib(n):
    return n / (1 << 20)


# -- 1. interior layout transposes ------------------------------------------

@rule("interior-transpose", kind="program", severity="high",
      title="layout transpose between compute ops (not an entry/exit "
            "boundary) — per-op relayout, the NHWC planner's enemy")
def _interior_transpose(view):
    mod = view.module
    if mod is None:
        return
    interior, boundary = classify_transposes(mod)
    view.metrics["interior-transpose"] = {
        "interior": len(interior), "boundary": len(boundary),
        "total": len(interior) + len(boundary)}
    for op in interior[:8]:
        yield Finding(
            "interior-transpose", "high",
            f"interior layout transpose {op.types[0] if op.types else ''}"
            f" -> {op.types[-1] if op.types else ''} between compute ops",
            location=op.path,
            suggested_fix="make the surrounding ops layout-native "
            "(data_format / conv dimension numbers) or move the "
            "transpose to the region boundary "
            "(framework.to_channels_last)")
    if len(interior) > 8:
        yield Finding("interior-transpose", "high",
                      f"... and {len(interior) - 8} more interior "
                      "transposes", location=f"@{mod.main.name}")


# -- 2. silent dtype promotion ----------------------------------------------

@rule("dtype-promotion", kind="program", severity="high",
      title="fp64 leaking into traced code; bf16 dot/reduce without "
            "fp32 accumulation; implicit mixed-precision promotion")
def _dtype_promotion(view):
    found_f64 = []
    bf16_accum = []
    mixed = []
    mod = view.module
    if mod is not None:
        for op in mod.ops:
            if any(t.dtype == "f64" for t in op.types):
                found_f64.append(op.path)
            if op.name.endswith("dot_general") and op.types:
                if all(t.dtype == "bf16" for t in op.types):
                    bf16_accum.append(("dot", op.path))
            if op.name.endswith("reduce") and "applies" in op.raw:
                tys = [t for t in op.types if t.shape]
                if tys and all(t.dtype == "bf16" for t in tys):
                    bf16_accum.append(("reduce", op.path))
    jaxpr = view.jaxpr
    if jaxpr is not None:
        import numpy as np
        f64 = np.float64  # tpu_lint: allow(dtype-promotion) — the probe
        for c in getattr(jaxpr, "consts", ()):
            if getattr(c, "dtype", None) is not None and \
                    np.dtype(c.dtype) == f64:
                found_f64.append("closed-over constant")
        for eqn, path in view.iter_eqns():
            prim = eqn.primitive.name
            if prim == "convert_element_type" and \
                    str(eqn.params.get("new_dtype")) == "float64":
                found_f64.append(path)
            if prim in ("add", "sub", "mul", "div", "max", "min"):
                fl = [v.aval for v in eqn.invars
                      if hasattr(v.aval, "dtype")
                      and v.aval.dtype.kind == "f"]
                dts = {str(a.dtype) for a in fl}
                if len(dts) > 1:
                    mixed.append((path, sorted(dts)))
    view.metrics["dtype-promotion"] = {
        "f64_sites": len(found_f64), "bf16_accum_sites": len(bf16_accum),
        "mixed_precision_sites": len(mixed)}
    if found_f64:
        yield Finding(
            "dtype-promotion", "high",
            f"fp64 values in traced program at {len(found_f64)} site(s) "
            f"(first: {found_f64[0]}) — TPUs emulate f64 at ~1/10 "
            "throughput and jax x64 is off by policy",
            location=str(found_f64[0]),
            suggested_fix="keep constant math in numpy on the host and "
            "cast to the compute dtype before tracing")
    for kind, path in bf16_accum[:8]:
        yield Finding(
            "dtype-promotion", "medium",
            f"bf16 {kind} accumulates in bf16 (silent precision loss on "
            "long contractions)", location=path,
            suggested_fix="pass preferred_element_type=jnp.float32 (dot)"
            " or reduce in fp32 and cast the result")
    for path, dts in mixed[:4]:
        yield Finding(
            "dtype-promotion", "low",
            f"implicit mixed-precision promotion {'+'.join(dts)} — the "
            "narrower operand silently upcasts", location=path,
            suggested_fix="cast operands explicitly so the intended "
            "compute dtype is visible")


# -- 3. host round-trips -----------------------------------------------------

_CB_PRIMS = ("pure_callback", "io_callback", "debug_callback", "callback")


@rule("host-callback", kind="program", severity="high",
      title="host round-trip inside a compiled region (pure_callback / "
            "io_callback / py_func plan split)")
def _host_callback(view):
    n = 0
    jaxpr = view.jaxpr
    if jaxpr is not None:
        for eqn, path in view.iter_eqns():
            if any(eqn.primitive.name == p or "callback" in
                   eqn.primitive.name for p in _CB_PRIMS):
                n += 1
                cb = eqn.params.get("callback") or \
                    eqn.params.get("callback_func") or ""
                yield Finding(
                    "host-callback", "high",
                    f"{eqn.primitive.name} forces a device->host->device "
                    f"round-trip every execution ({str(cb)[:80]})",
                    location=path,
                    suggested_fix="move the python out of the hot path, "
                    "or precompute its result and pass it as an input")
    elif view.module is not None:
        for op in view.module.ops_named("stablehlo.custom_call",
                                        "custom_call"):
            tgt = op.custom_call_target or ""
            if "callback" in tgt or "py_func" in tgt:
                n += 1
                yield Finding(
                    "host-callback", "high",
                    f"custom_call @{tgt} is a host callback — device->"
                    "host->device round-trip every execution",
                    location=op.path,
                    suggested_fix="move the python out of the hot path")
    for desc, idx in view.meta.get("host_entries", ()):
        n += 1
        yield Finding(
            "host-callback", "high",
            f"host-only entry [{desc}] splits the compiled plan into "
            f"{view.meta.get('n_segments', '?')} segments — a device "
            "sync + eager python every step",
            location=f"plan step {idx}",
            suggested_fix="replace the host op with a traceable "
            "equivalent, or declare a pure `traced` form for it")
    view.metrics["host-callback"] = {"sites": n}


# -- 4. donation audit -------------------------------------------------------

_DONATION_MIN_BYTES = 1 << 20


@rule("donation", kind="program", severity="medium",
      title="large buffer returned with identical shape but not "
            "donated; donated buffer aliased to a live input")
def _donation(view):
    from .hlo import donated_arg_indices
    mod = view.module
    flagged = 0
    min_bytes = view.meta.get("donation_min_bytes", _DONATION_MIN_BYTES)
    if mod is not None and mod.main.args:
        donated = donated_arg_indices(mod)
        # each result buffer can absorb at most ONE input via aliasing:
        # consume matches greedily so an update fn (p, g) -> p' flags p
        # (the buffer that could alias) but not the gradient
        results = [(t.shape, t.dtype) for t in mod.main.result_types]
        for i, t, _attrs in mod.main.args:
            if t is None:
                continue
            if i in donated:
                if (t.shape, t.dtype) in results:
                    results.remove((t.shape, t.dtype))
                continue
            nb = _nbytes(t)
            if nb >= min_bytes and (t.shape, t.dtype) in results:
                results.remove((t.shape, t.dtype))
                flagged += 1
                if flagged <= 8:
                    yield Finding(
                        "donation", "medium",
                        f"arg {i} ({t}, {_mib(nb):.1f} MiB) is returned "
                        "with identical shape/dtype but not donated — "
                        "XLA must keep both buffers live (2x HBM for "
                        "the update)",
                        location=f"@{mod.main.name} %arg{i}",
                        suggested_fix="pass donate_argnums for the "
                        "updated state (params/moments/KV cache)")
        view.metrics["donation"] = {
            "args": len(mod.main.args), "donated": len(donated),
            "large_undonated": flagged}
    for where in view.meta.get("aliased_donations", ()):
        yield Finding(
            "donation", "high",
            f"donated buffer is aliased to another live input ({where}) "
            "— XLA may overwrite a buffer the other argument still "
            "reads", location=where,
            suggested_fix="copy the array before donating, or drop it "
            "from donate_argnums")
    for seg in view.meta.get("segments", ()):
        if seg.get("n_state", 0) > 0 and not seg.get("donated", False) \
                and not view.meta.get("segmented", False):
            yield Finding(
                "donation", "medium",
                f"plan segment {seg.get('index', '?')} threads "
                f"{seg['n_state']} state buffers without donation — "
                "every step copies the whole param/moment set",
                location=f"plan segment {seg.get('index', '?')}",
                suggested_fix="whole-program plans donate automatically;"
                " remove the host split that forced segmentation")
    if view.kind == "engine" and not view.meta.get("donate", True):
        backend = view.meta.get("backend", "cpu")
        sev = "medium" if backend != "cpu" else "info"
        yield Finding(
            "donation", sev,
            f"serving engine KV buffers not donated on backend="
            f"{backend}" + (" (expected on CPU: eager aliasing rules)"
                            if backend == "cpu" else
                            " — decode copies the full KV cache "
                            "every step"),
            location="serving.Engine",
            suggested_fix="construct Engine(donate=True) on TPU")


# -- 5. retrace risk ---------------------------------------------------------

@rule("retrace-risk", kind="program", severity="medium",
      title="unhashable statics reaching jit; ops blacklisted or "
            "megamorphic in the eager dispatch cache")
def _retrace_risk(view):
    unhashable = view.meta.get("unhashable_statics", ())
    for path, tname in unhashable:
        yield Finding(
            "retrace-risk", "medium",
            f"unhashable static argument ({tname}) at {path} reaches "
            "jit — the signature can't be cached, so every call "
            "re-traces or falls back to eager",
            location=path,
            suggested_fix="pass arrays for data, hashable values "
            "(tuples, not lists) for configuration")
    if view.meta.get("lowering_error") and not unhashable:
        yield Finding(
            "retrace-risk", "medium",
            "example arguments do not lower at all "
            f"({view.meta['lowering_error']}) — this callable falls "
            "back to eager on every invocation",
            location=view.name,
            suggested_fix="make every argument a pytree of arrays or "
            "hashable statics")
    stats = view.meta.get("dispatch_stats")
    if stats:
        view.metrics["retrace-risk"] = {
            "blacklisted": len(stats.get("blacklist", ())),
            "megamorphic": len(stats.get("megamorphic", ())),
            "compiles": stats.get("compiles", 0)}
        for item in stats.get("blacklist", ()):
            yield Finding(
                "retrace-risk", "medium",
                f"op {item['op']} blacklisted from the eager fast path: "
                f"{item['reason']}",
                location=item["op"],
                suggested_fix="remove data-dependent python (.item(), "
                "value branches) from the op body, or keep it off the "
                "hot path")
        for label in stats.get("megamorphic", ()):
            yield Finding(
                "retrace-risk", "medium",
                f"op {label} is megamorphic (hit the distinct-signature "
                "limit) — new shapes bypass the compile cache",
                location=label,
                suggested_fix="pad/bucket inputs to a bounded shape set "
                "(power-of-two buckets) so signatures repeat")


# -- 6. TPU padding waste ----------------------------------------------------

_LANE = 128
_SUBLANE = 8


def _pad_waste(shape):
    """(waste_factor, padded_shape) under 8x128 tiling of the two minor
    dims (f32 sublane; bf16/int8 need 16/32 — 8 is the optimistic
    floor, so flagged waste is a lower bound)."""
    if len(shape) < 1 or any(d <= 0 for d in shape):
        return 1.0, tuple(shape)
    padded = list(shape)
    padded[-1] = -(-shape[-1] // _LANE) * _LANE
    if len(shape) >= 2:
        padded[-2] = -(-shape[-2] // _SUBLANE) * _SUBLANE
    num = 1
    den = 1
    for p, d in zip(padded, shape):
        num *= p
        den *= d
    return num / den, tuple(padded)


@rule("padding-waste", kind="program", severity="low",
      title="dot/reduce dims far off the 8x128 TPU tile; non-power-of-"
            "two serving buckets; unaligned KV-cache geometry")
def _padding_waste(view):
    mod = view.module
    worst = {}
    if mod is not None:
        for op in mod.ops_named("stablehlo.dot_general", "dot_general",
                                "stablehlo.dot", "dot"):
            for t in op.types:
                if len(t.shape) < 2:
                    continue
                waste, padded = _pad_waste(t.shape)
                if waste >= 1.5:
                    key = (t.shape, t.dtype)
                    if key not in worst or worst[key][0] < waste:
                        worst[key] = (waste, padded, op.path)
        view.metrics["padding-waste"] = {
            "dot_sites_padded": len(worst),
            "worst_waste": max((w for w, _p, _l in worst.values()),
                               default=1.0)}
    ranked = sorted(worst.items(), key=lambda kv: -kv[1][0])
    for (shape, dtype), (waste, padded, path) in ranked[:6]:
        sev = "medium" if waste >= 4.0 else "low"
        yield Finding(
            "padding-waste", sev,
            f"dot operand/result {('x'.join(map(str, shape)))}x{dtype} "
            f"pads to {'x'.join(map(str, padded))} on TPU "
            f"({waste:.1f}x memory/compute waste)",
            location=path,
            suggested_fix="size contracting/output dims to multiples of "
            "128 (lane) and 8 (sublane), e.g. round hidden dims and "
            "vocab/class counts up")
    if view.kind == "engine":
        m = view.meta
        mb = m.get("min_prompt_bucket", 8)
        if mb & (mb - 1):
            yield Finding(
                "padding-waste", "medium",
                f"min_prompt_bucket={mb} is not a power of two — bucket "
                "ladder misaligns and multiplies distinct prefill "
                "shapes", location="serving.Engine",
                suggested_fix="use a power-of-two min_prompt_bucket")
        if m.get("max_len", 0) % _SUBLANE:
            yield Finding(
                "padding-waste", "low",
                f"KV cache max_len={m['max_len']} is not a multiple of "
                "8 — every KV line pads its sublane dim",
                location="serving.PagedKVCache",
                suggested_fix="round max_len up to a multiple of 8")
        lane = m.get("kv_heads", 0) * m.get("head_dim", 0)
        if lane and lane % _LANE:
            waste, _ = _pad_waste((1, lane))
            yield Finding(
                "padding-waste", "low",
                f"KV lane width kv_heads*head_dim={lane} pads to "
                f"{-(-lane // _LANE) * _LANE} ({waste:.1f}x KV HBM "
                "waste)", location="serving.PagedKVCache",
                suggested_fix="choose head_dim so kv_heads*head_dim is "
                "a multiple of 128, or pack heads before caching")
        bs = m.get("block_size")
        if bs:
            if bs % _SUBLANE:
                padded = -(-bs // _SUBLANE) * _SUBLANE
                yield Finding(
                    "padding-waste", "medium",
                    f"paged KV block_size={bs} is not a multiple of the "
                    f"{_SUBLANE}-line TPU sublane — every block "
                    f"scatter/gather tiles to {padded} lines "
                    f"({padded / bs:.2f}x pool HBM + DMA waste)",
                    location="serving.PagedKVCache",
                    suggested_fix="use a block_size that is a multiple "
                    "of 8 (16/32/64): KV lines then tile the sublane "
                    "dim exactly")
            if m.get("max_len", 0) % bs:
                mb = -(-m["max_len"] // bs)
                yield Finding(
                    "padding-waste", "low",
                    f"max_len={m['max_len']} is not a multiple of "
                    f"block_size={bs} — every slot's gathered view "
                    f"carries {mb * bs - m['max_len']} dead lines past "
                    "the causal bound",
                    location="serving.PagedKVCache",
                    suggested_fix="round max_len to a multiple of "
                    "block_size")


# -- 7. compile-count budget -------------------------------------------------

@rule("compile-budget", kind="program", severity="high",
      title="programs traced exceed the declared compile budget "
            "(serving bucket sprawl, plan fragmentation)")
def _compile_budget(view):
    if view.kind == "engine":
        m = view.meta
        buckets = sorted(m.get("buckets_seen", ()))
        chunk = 1 if m.get("chunk_used") else 0
        # paged budget: the block table is a plain RUNTIME operand, so
        # paging itself adds zero lowerings; chunked prefill adds
        # exactly ONE shared chunk program regardless of prompt length.
        # Speculative decoding adds ONE verify program (chunk-shaped,
        # per draft width k); a model draft additionally pays its own
        # prefill buckets + one fused draft decode (n-gram/custom
        # proposers are host-side: zero programs)
        spec = m.get("spec") or {}
        verify = 1 if spec.get("verify_used") else 0
        draft_buckets = sorted(spec.get("draft_buckets_seen", ()))
        draft = len(draft_buckets) \
            + (1 if spec.get("draft_decode_used") else 0)
        programs = len(buckets) + (1 if m.get("decode_used") else 0) \
            + chunk + verify + draft
        budget = m.get("compile_budget")
        view.metrics["compile-budget"] = {
            "programs": programs, "prefill_buckets": buckets,
            "chunk_program": bool(chunk), "budget": budget,
            "verify_program": bool(verify),
            "draft_programs": draft}
        pc = m.get("prefill_chunk")
        # a request of length <= prefill_chunk legitimately buckets to
        # the next power of two above it; anything beyond that should
        # have gone through the chunk program
        cap = None if pc is None else max(pc, 1 << (pc - 1).bit_length())
        sprawl = [b for b in buckets if cap is not None and b > cap]
        if sprawl:
            yield Finding(
                "compile-budget", "high",
                f"per-length prefill lowerings {sprawl} traced beyond "
                f"prefill_chunk={pc} — block-table operands must not "
                "add per-length programs; prompts above the chunk "
                "threshold must go through the single chunked-prefill "
                "program", location="serving.Engine",
                suggested_fix="route long prompts through chunked "
                "prefill (they bucket only up to prefill_chunk)")
        if budget is not None and programs > budget:
            yield Finding(
                "compile-budget", "high",
                f"{programs} XLA programs compiled ({len(buckets)} "
                f"prefill buckets {buckets} + decode"
                + (" + chunk" if chunk else "")
                + (" + verify" if verify else "")
                + (f" + {draft} draft" if draft else "")
                + ") exceeds the "
                f"declared budget of {budget}",
                location="serving.Engine",
                suggested_fix="cap prompt bucketing (raise "
                "min_prompt_bucket / clamp max prompt len, or enable "
                "chunked prefill so long prompts share one program) or "
                "raise compile_budget if the traffic mix justifies it")
        elif budget is None and programs:
            yield Finding(
                "compile-budget", "info",
                f"{programs} XLA programs in use ({len(buckets)} "
                "prefill buckets + decode"
                + (" + chunk" if chunk else "") + "); no compile "
                "budget declared",
                location="serving.Engine",
                suggested_fix="construct Engine(compile_budget=N) to "
                "gate compile-count regressions in CI")
    elif view.kind == "plan":
        n = view.meta.get("n_segments", 0)
        view.metrics["compile-budget"] = {"programs": n}
        if n > 1:
            yield Finding(
                "compile-budget", "low",
                f"replay plan fragments into {n} compiled programs "
                f"(+{view.meta.get('n_host', 0)} host entries) instead "
                "of one whole-program jit",
                location="static._ReplayPlan",
                suggested_fix="remove host-only entries from the "
                "program (see host-callback findings)")


# -- 8. unoverlapped collectives on the critical path ------------------------

_SERIAL_COLLECTIVES = {"all_reduce", "reduce_scatter"}
_GATHER_COLLECTIVES = {"all_gather", "all_to_all"}
_DOT_OPS = {"dot_general", "dot", "convolution"}
# ops a collective operand may transparently pass through while still
# being "the dot's result" (no compute to hide a hop behind)
_PASSTHROUGH_OPS = {"reshape", "transpose", "convert",
                    "bitcast_convert", "broadcast_in_dim"}


def _defining_dot(mod, var, defs, depth=0):
    op = defs.get(var)
    if op is None or depth > 4:
        return None
    base = op.name.split(".")[-1]
    if base in _DOT_OPS:
        return op
    if base in _PASSTHROUGH_OPS:
        for o in op.operands:
            hit = _defining_dot(mod, o, defs, depth + 1)
            if hit is not None:
                return hit
    return None


@rule("unoverlapped-collective", kind="program", severity="high",
      title="all_reduce/reduce_scatter/all_gather serializing directly "
            "after a dot — decompose into a ppermute-pipelined "
            "collective-matmul so the hops hide behind compute")
def _unoverlapped_collective(view):
    """The serial tensor-parallel form ``dot -> collective`` puts the
    collective's full latency on the critical path; fused
    computation-collectives (arXiv 2305.06942,
    ``distributed.collective_matmul``) split the dot into per-chunk
    partial dots pipelined over a ppermute ring so the wire time
    overlaps the math. A collective whose operand IS a dot result
    (through reshapes/converts only) is the serial form: high for the
    reducing collectives (all_reduce / reduce_scatter — the row-parallel
    matmul pattern), medium for a gather of dot output (the sharded-
    output pattern; sometimes terminal, still unoverlapped)."""
    mod = view.module
    if mod is None:
        return
    defs = {r: op for op in mod.ops for r in op.results}
    serial = []
    n_coll = 0
    n_ppermute = len(mod.ops_named("stablehlo.collective_permute",
                                   "collective_permute"))
    for op in mod.ops:
        base = op.name.split(".")[-1]
        if base not in _SERIAL_COLLECTIVES | _GATHER_COLLECTIVES:
            continue
        n_coll += 1
        for o in op.operands:
            dot = _defining_dot(mod, o, defs)
            if dot is not None:
                serial.append((op, dot, base))
                break
    view.metrics["unoverlapped-collective"] = {
        "collectives": n_coll, "serial_after_dot": len(serial),
        "collective_permutes": n_ppermute}
    for op, dot, base in serial[:8]:
        sev = "high" if base in _SERIAL_COLLECTIVES else "medium"
        yield Finding(
            "unoverlapped-collective", sev,
            f"{op.name} consumes the result of {dot.name} directly — "
            "the collective serializes after the matmul and its full "
            "latency lands on the decode/train critical path",
            location=op.path,
            suggested_fix="decompose into an overlapped collective-"
            "matmul (distributed.collective_matmul."
            "ring_rowparallel_matmul / matmul_allgather): per-chunk "
            "partial dots pipelined over a ppermute ring hide the hops "
            "behind compute")
    if len(serial) > 8:
        yield Finding(
            "unoverlapped-collective", "high",
            f"... and {len(serial) - 8} more serial collectives after "
            "dots", location=f"@{mod.main.name}")


# -- 9. AOT executable-cache key stability -----------------------------------

@rule("aot-key-instability", kind="program", severity="medium",
      title="identical program compiled under multiple AOT cache keys "
            "(warm starts will recompile instead of restoring)")
def _aot_key_instability(view):
    """The aot.CompileService signature key must uniquely name a
    program: when two different signatures both go through a FULL build
    and lower to the same StableHLO fingerprint in one process, the key
    is unstable (an unstable closure value, a per-process salt in the
    material, churned code tokens) and the on-disk cache degrades to
    one recompile per alias — exactly the cold start it exists to
    eliminate."""
    info = view.meta.get("aot")
    if not info:
        return
    unstable = info.get("instability") or []
    if unstable:
        view.metrics["aot-key-instability"] = {
            "programs": len(unstable),
            "extra_compiles": sum(u["n_keys"] - 1 for u in unstable)}
    for u in unstable:
        yield Finding(
            "aot-key-instability", "medium",
            f"program {u['fingerprint'][:12]}... was fully compiled "
            f"under {u['n_keys']} distinct cache keys ({', '.join(u['keys'][:4])}) "
            "in one process — the signature fails to unify identical "
            "programs, so a warm process recompiles instead of "
            "restoring the executable",
            location="aot.CompileService",
            suggested_fix="make the key material stable: drop "
            "process-local values (ids, unsalted reprs) from key_parts "
            "and derive code tokens from the functions the trace "
            "actually reaches")
