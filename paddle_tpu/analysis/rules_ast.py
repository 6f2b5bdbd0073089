"""AST self-lint rules: hazard patterns in paddle_tpu's own source.

Suppression is by inline annotation, never by config: a comment
``# tpu_lint: allow(rule-id[, rule-id...])`` on the flagged line, the
line above it, or the line directly above a ``def``/``class`` (which
then covers the whole body) marks a reviewed-and-intentional site.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field

from .findings import Finding
from .registry import rule

_ALLOW_RE = re.compile(r"#\s*tpu_lint:\s*allow\(([\w\-, ]+)\)")
_ALLOW_FILE_RE = re.compile(r"#\s*tpu_lint:\s*allow-file\(([\w\-, ]+)\)")


@dataclass
class SourceFile:
    """One parsed python source file plus its allow annotations."""

    path: str
    text: str
    tree: ast.AST = None
    lines: list = field(default_factory=list)
    allow_lines: dict = field(default_factory=dict)  # line -> {rule ids}
    allow_file: set = field(default_factory=set)
    parse_error: str = ""

    @classmethod
    def load(cls, path, text=None):
        if text is None:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        sf = cls(path=path, text=text, lines=text.splitlines())
        try:
            sf.tree = ast.parse(text, filename=path)
        except SyntaxError as e:
            sf.parse_error = f"SyntaxError: {e}"
            return sf
        sf._collect_allows()
        return sf

    def _collect_allows(self):
        for i, line in enumerate(self.lines, start=1):
            m = _ALLOW_FILE_RE.search(line)
            if m:
                self.allow_file.update(
                    x.strip() for x in m.group(1).split(","))
                continue
            m = _ALLOW_RE.search(line)
            if m:
                ids = {x.strip() for x in m.group(1).split(",")}
                # the annotation covers its own line and the next one
                self.allow_lines.setdefault(i, set()).update(ids)
                self.allow_lines.setdefault(i + 1, set()).update(ids)
        # an annotation on the line above a def/class (or its first
        # decorator) covers the whole body
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                first = min([node.lineno]
                            + [d.lineno for d in node.decorator_list])
                ids = self.allow_lines.get(first, set()) \
                    | self.allow_lines.get(first - 1, set())
                ids = {i for i in ids}
                if ids:
                    end = getattr(node, "end_lineno", node.lineno)
                    for ln in range(node.lineno, end + 1):
                        self.allow_lines.setdefault(ln, set()).update(ids)

    def allowed(self, rule_id, lineno):
        return rule_id in self.allow_file or \
            rule_id in self.allow_lines.get(lineno, ())

    def loc(self, node):
        return f"{self.path}:{getattr(node, 'lineno', '?')}"


def _finding(sf, rule_id, severity, node, message, fix):
    if sf.allowed(rule_id, getattr(node, "lineno", -1)):
        return None
    return Finding(rule_id, severity, message, location=sf.loc(node),
                   suggested_fix=fix, origin=sf.path)


# -- 1. id()-keyed caches ----------------------------------------------------

def _is_id_call(node):
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "id" and node.args)


def _contains_id_call(node):
    return any(_is_id_call(n) for n in ast.walk(node))


def _is_persistent_container(node):
    """Attribute-rooted (self._cache / obj._slots) or plain-Name
    containers can outlive the keyed object; calls/literals can't."""
    return isinstance(node, ast.Attribute)


@rule("id-keyed-cache", kind="ast", severity="high",
      title="id()-keyed entry in a persistent container — ids recycle "
            "after GC, resurrecting stale entries (ADVICE round-5 bug)")
def _id_keyed_cache(sf):
    if sf.tree is None:
        return
    for node in ast.walk(sf.tree):
        target = None
        if isinstance(node, ast.Subscript) and \
                _contains_id_call(node.slice):
            target = node.value
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("get", "setdefault", "pop") and \
                node.args and _contains_id_call(node.args[0]):
            target = node.func.value
        if target is None or not _is_persistent_container(target):
            continue
        f = _finding(
            sf, "id-keyed-cache", "high", node,
            "cache keyed by id(obj) on a persistent container — after "
            "the object dies its id can be reused, silently hitting the "
            "stale entry",
            "key by a stable monotonic token (static.program."
            "_stable_token idiom) or hold a reference to the keyed "
            "object; if the container provably outlives every key, "
            "annotate with  # tpu_lint: allow(id-keyed-cache)")
        if f:
            yield f


# -- 2. numpy calls inside traced bodies ------------------------------------

_TRACER_CALLS = {"jit", "vmap", "pmap", "grad", "value_and_grad", "vjp",
                 "jvp", "checkpoint", "remat", "scan", "while_loop",
                 "cond", "fori_loop", "switch", "map", "custom_vjp",
                 "custom_jvp", "to_static"}


def _call_name(node):
    """Trailing name of a call target: jax.jit -> 'jit'."""
    f = node.func if isinstance(node, ast.Call) else node
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _collect_traced_funcs(tree):
    """FunctionDef nodes whose body runs under a jax trace: decorated
    with jit/to_static, referenced in a jit(...) call, or passed to a
    lax control-flow / transform combinator."""
    funcs = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            funcs[node.name] = node
    traced = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _call_name(dec) in _TRACER_CALLS:
                    traced.add(node)
        if isinstance(node, ast.Call) and _call_name(node) in _TRACER_CALLS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name) and arg.id in funcs:
                    traced.add(funcs[arg.id])
                elif isinstance(arg, ast.Lambda):
                    traced.add(arg)
    return traced


@rule("numpy-in-traced", kind="ast", severity="medium",
      title="numpy call on a traced value inside a jitted/lax body — "
            "fails the trace or silently bakes a constant")
def _numpy_in_traced(sf):
    if sf.tree is None:
        return
    for fn in _collect_traced_funcs(sf.tree):
        if isinstance(fn, ast.Lambda):
            params = {a.arg for a in fn.args.args}
            body = [fn.body]
        else:
            params = {a.arg for a in fn.args.args
                      + fn.args.kwonlyargs + fn.args.posonlyargs}
            body = fn.body
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if not (isinstance(f, ast.Attribute)
                        and isinstance(f.value, ast.Name)
                        and f.value.id in ("np", "numpy")):
                    continue
                touches_param = any(
                    isinstance(a, ast.Name) and a.id in params
                    for a in ast.walk(node) if isinstance(a, ast.Name))
                if not touches_param:
                    continue  # np on python constants is host math: fine
                found = _finding(
                    sf, "numpy-in-traced", "medium", node,
                    f"np.{f.attr}() applied to a traced-function "
                    "argument — numpy can't consume tracers (trace "
                    "error) or, via __array__, bakes the first value "
                    "as a constant",
                    "use the jnp equivalent inside traced code; keep "
                    "numpy for host-side constant math only")
                if found:
                    yield found


# -- 3. blanket except that swallows the reason ------------------------------

_REPORTING_CALLS = {"warn", "warning", "error", "exception", "debug",
                    "info", "log", "print", "fail", "record", "append",
                    "add", "write"}


@rule("silent-except", kind="ast", severity="medium",
      title="blanket `except Exception` that neither re-raises nor "
            "records why — trace failures vanish without a reason")
def _silent_except(sf):
    if sf.tree is None:
        return
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        t = node.type
        blanket = t is None or (isinstance(t, ast.Name)
                                and t.id in ("Exception", "BaseException"))
        if not blanket:
            continue
        caught_used = False
        reports = False
        reraises = False
        for sub in ast.walk(ast.Module(body=node.body, type_ignores=[])):
            if isinstance(sub, ast.Raise):
                reraises = True
            if node.name and isinstance(sub, ast.Name) \
                    and sub.id == node.name:
                caught_used = True
            if isinstance(sub, ast.Call) and \
                    _call_name(sub) in _REPORTING_CALLS:
                reports = True
        if reraises or caught_used or reports:
            continue
        f = _finding(
            sf, "silent-except", "medium", node,
            "blanket except swallows the exception without recording "
            "type/message — when a trace fails here, nothing says why",
            "capture `as e` and record f'{type(e).__name__}: {e}' "
            "(blacklist reason, warning, or log) before falling back")
        if f:
            yield f


# -- 4. non-atomic writes in checkpoint-path modules -------------------------

# modules on a durability-critical path: a torn write here is a lost
# training run, so every publish must be tmp-write + rename
_DURABLE_PATH_HINTS = (
    "distributed/checkpoint", "distributed/elastic", "framework/io",
    "incubate/auto_checkpoint", "incubate/checkpoint", "resilience/",
)

_RENAME_CALLS = {"rename", "replace", "move", "renames"}


def _encl_funcs(tree):
    """node -> innermost enclosing FunctionDef (or None: module level)."""
    owner = {}

    def walk(node, fn):
        for child in ast.iter_child_nodes(node):
            nxt = fn
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nxt = child
            owner[child] = nxt
            walk(child, nxt)

    walk(tree, None)
    return owner


def _mentions_tmp(node):
    """The opened filename is visibly a temp (literal containing 'tmp',
    or a variable named like one) — the write IS the safe half of a
    tmp+rename pair or scratch output."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str) \
                and "tmp" in sub.value.lower():
            return True
        if isinstance(sub, ast.Name) and "tmp" in sub.id.lower():
            return True
    return False


@rule("non-atomic-write", kind="ast", severity="medium",
      title="open-write-close without tmp+rename in a checkpoint-path "
            "module — a kill mid-write leaves a torn file where durable "
            "state should be")
def _non_atomic_write(sf):
    if sf.tree is None:
        return
    path = sf.path.replace("\\", "/")
    if not any(h in path for h in _DURABLE_PATH_HINTS):
        return
    owner = _encl_funcs(sf.tree)
    renaming_funcs = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call) \
                and _call_name(node) in _RENAME_CALLS:
            fn = owner.get(node)
            if fn is not None:
                renaming_funcs.add(fn)
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "open" and len(node.args) >= 2):
            continue
        mode = node.args[1]
        if not (isinstance(mode, ast.Constant)
                and isinstance(mode.value, str)
                and mode.value.startswith("w")):
            continue        # reads and appends can't tear existing state
        if _mentions_tmp(node.args[0]):
            continue
        if owner.get(node) in renaming_funcs and owner.get(node) is not None:
            continue        # the function publishes via rename
        f = _finding(
            sf, "non-atomic-write", "medium", node,
            "checkpoint-path module writes a file in place "
            "(open('w')/close with no tmp+rename in the function) — a "
            "SIGKILL mid-write leaves a torn file that a restore may "
            "load",
            "write to '<path>.tmp' then os.replace(tmp, path); if the "
            "file is genuinely disposable (heartbeat, scratch), annotate "
            "with  # tpu_lint: allow(non-atomic-write)")
        if f:
            yield f


# -- 5. wall-clock durations (the observability span/latency contract) ------

def _is_walltime_call(node):
    """``time.time()`` — the NTP-steppable wall clock."""
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "time"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time")


@rule("wallclock-in-span", kind="ast", severity="high",
      title="time.time() used for a duration — wall clock steps under "
            "NTP/suspend; durations must use perf_counter()/monotonic()")
def _wallclock_in_span(sf):
    """Flag subtraction involving a ``time.time()`` result: the
    difference of two wall-clock reads is a DURATION, and wall clock is
    the wrong clock for one (NTP slew/step, DST, suspend). Plain
    ``time.time()`` reads (ledger timestamps, absolute deadlines that
    only get compared) are untouched. Legitimate wall-clock subtraction
    — cross-process liveness stamps, where monotonic clocks are not
    comparable — carries ``# tpu_lint: allow(wallclock-in-span)``."""
    if sf.tree is None:
        return
    # names assigned from time.time(), tracked PER enclosing function
    # (a `t0` in one function must not taint another's perf_counter
    # math); attribute targets (self._t0) are file-global because the
    # assignment and the subtraction usually live in different methods
    owner = _encl_funcs(sf.tree)
    wall_names, wall_attrs = set(), set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign) and _is_walltime_call(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    wall_names.add((owner.get(node), tgt.id))
                elif isinstance(tgt, ast.Attribute):
                    wall_attrs.add(tgt.attr)

    def is_wall_operand(op, fn):
        if _is_walltime_call(op):
            return True
        if isinstance(op, ast.Name) and (fn, op.id) in wall_names:
            return True
        return isinstance(op, ast.Attribute) and op.attr in wall_attrs

    seen_lines = set()
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)):
            continue
        fn = owner.get(node)
        if not (is_wall_operand(node.left, fn)
                or is_wall_operand(node.right, fn)):
            continue
        if node.lineno in seen_lines:
            continue
        seen_lines.add(node.lineno)
        f = _finding(
            sf, "wallclock-in-span", "high", node,
            "duration computed by subtracting wall-clock time.time() "
            "reads — NTP steps/suspend make the difference wrong, and "
            "spans/latency ledgers built on it lie",
            "use time.perf_counter() (sub-second durations) or "
            "time.monotonic() (deadlines/elapsed); if the subtraction "
            "genuinely needs wall clock (cross-process liveness "
            "stamps), annotate with "
            "# tpu_lint: allow(wallclock-in-span)")
        if f:
            yield f


# -- 6. fp64 constant math in library code (AST facet of dtype-promotion) ----

@rule("dtype-promotion", kind="ast", severity="medium",
      title="np.float64 constant math in library code — fp64 results "
            "must not leak into traced/compute paths (x64 is off)")
def _fp64_ast(sf):
    if sf.tree is None:
        return
    for node in ast.walk(sf.tree):
        is_f64_attr = (isinstance(node, ast.Attribute)
                       and node.attr in ("float64", "double")
                       and isinstance(node.value, ast.Name)
                       and node.value.id in ("np", "numpy", "jnp"))
        if not is_f64_attr:
            continue
        f = _finding(
            sf, "dtype-promotion", "medium", node,
            "explicit float64 in library code — jax x64 is off by "
            "policy, so fp64 here is host-side constant math that must "
            "be cast before reaching traced code",
            "cast the result to the compute dtype at the boundary; if "
            "the fp64 math is intentional (constant folding), annotate "
            "with  # tpu_lint: allow(dtype-promotion)")
        if f:
            yield f


# -- 7. literal tile/block sizes at pallas kernel call sites -----------------

#: public entry points of the tuner-registered pallas suite (plus raw
#: pallas_call): tile choices at these call sites belong to the tuner
_TUNED_KERNEL_CALLS = {
    "flash_attention", "int8_matmul_rescale", "int8_linear",
    "ragged_group_matmul", "ragged_dot",
    "fused_ce_stats", "fused_ce_loss", "sharded_vocab_ce", "pallas_call",
}
_TILE_KWARG_RE = re.compile(r"^block_[a-z0-9]+$")


def _is_int_literal(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return True
    return (isinstance(node, ast.Tuple)
            and node.elts
            and all(_is_int_literal(e) for e in node.elts))


@rule("untuned-kernel-config", kind="ast", severity="medium",
      title="literal tile/block size at a pallas kernel call site "
            "outside the tuner registry — hand-picked configs bypass "
            "the search (CUDA-L2: searched beats hand-picked)")
def _untuned_kernel_config(sf):
    """A ``block_*=128``-style integer literal passed to a
    tuner-registered kernel bakes one tiling for every shape; the call
    site should resolve its config through ``paddle_tpu.tuner``
    (``get_config``/``call``) so searched winners and persisted tuned
    configs apply. The tuner registry itself (``paddle_tpu/tuner/``)
    owns its literal spaces; other intentional literals — references,
    test fixtures, docs — annotate with
    ``# tpu_lint: allow(untuned-kernel-config)``."""
    if sf.tree is None:
        return
    path = sf.path.replace("\\", "/")
    if "/tuner/" in path or path.endswith("/tuner"):
        return        # the registry IS where literal spaces live
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) in _TUNED_KERNEL_CALLS):
            continue
        for kw in node.keywords:
            if kw.arg is None or not _TILE_KWARG_RE.match(kw.arg):
                continue
            if not _is_int_literal(kw.value):
                continue
            f = _finding(
                sf, "untuned-kernel-config", "medium", node,
                f"{_call_name(node)}({kw.arg}=<literal>) pins a "
                "hand-picked tile size at the call site — the tuner's "
                "searched/persisted config for the shape never applies",
                "resolve the config via paddle_tpu.tuner.get_config "
                "(or route the call through tuner.call); intentional "
                "literals annotate with  "
                "# tpu_lint: allow(untuned-kernel-config)")
            if f:
                yield f
            break     # one finding per call site is enough


# -- 8. serial collectives wrapping matmuls (AST facet) ----------------------

_COLLECTIVE_CALLS = {"psum", "all_gather", "reduce_scatter",
                     "psum_scatter", "all_to_all"}
_DOT_CALLS = {"dot", "matmul", "einsum", "dot_general"}


def _contains_matmul(node):
    for n in ast.walk(node):
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.MatMult):
            return True
        if isinstance(n, ast.Call) and _call_name(n) in _DOT_CALLS:
            return True
    return False


@rule("unoverlapped-collective", kind="ast", severity="high",
      title="lax.psum/all_gather/reduce_scatter wrapping a matmul "
            "expression — the serial collective-after-dot form")
def _unoverlapped_collective_ast(sf):
    """AST facet of the program rule: ``jax.lax.psum(x @ w, axis)`` (or
    a gather/scatter-reduce around a dot/matmul/einsum) writes the
    serial tensor-parallel form directly in source. The decomposed
    overlapped form lives in ``distributed.collective_matmul``; code
    that intentionally keeps the serial form (references, one-shot
    setup paths off the decode/train loop) annotates with
    ``# tpu_lint: allow(unoverlapped-collective)``."""
    if sf.tree is None:
        return
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) in _COLLECTIVE_CALLS
                and node.args and _contains_matmul(node.args[0])):
            continue
        f = _finding(
            sf, "unoverlapped-collective", "high", node,
            f"{_call_name(node)}() wraps a matmul expression — the "
            "collective serializes after the dot and its latency lands "
            "on the critical path",
            "use distributed.collective_matmul.ring_rowparallel_matmul"
            " / matmul_allgather (ppermute-pipelined partial dots); if "
            "the serial form is intentional, annotate with  "
            "# tpu_lint: allow(unoverlapped-collective)")
        if f:
            yield f
