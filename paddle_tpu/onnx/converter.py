"""jaxpr -> ONNX GraphProto converter.

Reference: python/paddle/onnx/export.py (which delegates to paddle2onnx,
a C++ program-desc -> ONNX translator). The TPU-native analog translates
the traced jaxpr of a layer's forward into an ONNX graph directly:
each lax primitive maps to one or a few ONNX ops (opset 13+), model
parameters become graph initializers, and constant subexpressions are
folded at export time.

Coverage targets inference graphs of the shipped model zoo: dense /
conv / norm / attention stacks (MatMul, Einsum, Conv, pooling,
reductions, elementwise, Gather embeddings, Where, Cast, shape ops) and
structured control flow — `lax.scan` -> Scan, `lax.cond` -> If,
`lax.while_loop` -> Loop with closure over outer-scope tensors — so
RNNs and scan-stacked models export too.
"""
from __future__ import annotations

import itertools

import numpy as np

from jax.extend.core import Literal

from .proto import onnx_pb2 as P

_ONNX_DTYPE = {
    "float32": 1, "uint8": 2, "int8": 3, "uint16": 4, "int16": 5,
    "int32": 6, "int64": 7, "bool": 9, "float16": 10, "float64": 11,
    "uint32": 12, "uint64": 13, "bfloat16": 16,
}

_INT64_MIN = -(2 ** 63)

# primitives that wrap a sub-jaxpr to inline (param key holding it varies)
_CALL_PRIMS = ("pjit", "jit", "closed_call", "core_call", "remat",
               "checkpoint", "custom_jvp_call", "custom_vjp_call",
               "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr")

_IDENTITY_PRIMS = ("stop_gradient", "copy", "device_put",
                   "sharding_constraint", "optimization_barrier",
                   "reduce_precision")

_UNARY = {
    "exp": "Exp", "log": "Log", "tanh": "Tanh", "sin": "Sin", "cos": "Cos",
    "tan": "Tan", "asin": "Asin", "acos": "Acos", "atan": "Atan",
    "sinh": "Sinh", "cosh": "Cosh", "asinh": "Asinh", "acosh": "Acosh",
    "atanh": "Atanh", "neg": "Neg", "abs": "Abs", "sign": "Sign",
    "floor": "Floor", "ceil": "Ceil", "round": "Round", "sqrt": "Sqrt",
    "logistic": "Sigmoid", "erf": "Erf",
}

_BINARY = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div", "pow": "Pow",
    "max": "Max", "min": "Min", "eq": "Equal", "lt": "Less",
    "le": "LessOrEqual", "gt": "Greater", "ge": "GreaterOrEqual",
}

_REDUCE_ATTR_AXES = {  # axes as attribute at opset 13
    "reduce_max": "ReduceMax", "reduce_min": "ReduceMin",
    "reduce_prod": "ReduceProd",
}


class OnnxExportError(NotImplementedError):
    pass


def _np_dtype_code(dt):
    name = np.dtype(dt).name
    if name not in _ONNX_DTYPE:
        raise OnnxExportError(f"dtype {name} has no ONNX mapping")
    return _ONNX_DTYPE[name]


def _tensor_proto(name, arr):
    arr = np.ascontiguousarray(arr)
    t = P.TensorProto(name=name, data_type=_np_dtype_code(arr.dtype))
    t.dims.extend(int(d) for d in arr.shape)
    t.raw_data = arr.tobytes()
    return t


def _value_info(name, shape, dtype):
    vi = P.ValueInfoProto(name=name)
    tt = vi.type.tensor_type
    tt.elem_type = _np_dtype_code(dtype)
    for d in shape:
        tt.shape.dim.add().dim_value = int(d)
    return vi


def _attr(name, v):
    a = P.AttributeProto(name=name)
    T = P.AttributeProto
    if isinstance(v, bool):
        a.type, a.i = T.INT, int(v)
    elif isinstance(v, (int, np.integer)):
        a.type, a.i = T.INT, int(v)
    elif isinstance(v, (float, np.floating)):
        a.type, a.f = T.FLOAT, float(v)
    elif isinstance(v, str):
        a.type, a.s = T.STRING, v.encode()
    elif isinstance(v, bytes):
        a.type, a.s = T.STRING, v
    elif isinstance(v, P.TensorProto):
        a.type = T.TENSOR
        a.t.CopyFrom(v)
    elif isinstance(v, P.GraphProto):
        a.type = T.GRAPH
        a.g.CopyFrom(v)
    elif isinstance(v, (list, tuple)):
        if all(isinstance(x, (int, np.integer)) for x in v):
            a.type = T.INTS
            a.ints.extend(int(x) for x in v)
        elif all(isinstance(x, (float, np.floating, int)) for x in v):
            a.type = T.FLOATS
            a.floats.extend(float(x) for x in v)
        else:
            raise OnnxExportError(f"attribute list {name}={v!r}")
    else:
        raise OnnxExportError(f"attribute {name}={v!r}")
    return a


class _Const:
    """A value known at export time (foldable, becomes an initializer
    only if a graph node consumes it)."""

    __slots__ = ("val",)

    def __init__(self, val):
        self.val = np.asarray(val)


class _Name:
    """A runtime graph tensor."""

    __slots__ = ("name",)

    def __init__(self, name):
        self.name = name


class _Ctx:
    def __init__(self, graph, opset, parent=None):
        self.graph = graph
        self.opset = opset
        if parent is None:
            # initializers always land in the ROOT graph: ONNX subgraph
            # nodes may reference outer-scope tensors by name
            self.root_graph = graph
            self._ids = itertools.count()
            self._taken = set()
            self._const_names = {}  # (dtype, shape, sha1) -> name
        else:
            self.root_graph = parent.root_graph
            self._ids = parent._ids
            self._taken = parent._taken
            self._const_names = parent._const_names

    def sub(self, graph):
        """Child context emitting nodes into `graph` (a control-flow
        body) while sharing names/initializers with the root."""
        return _Ctx(graph, self.opset, parent=self)

    def fresh(self, hint="t"):
        while True:
            name = f"{hint}_{next(self._ids)}"
            if name not in self._taken:
                self._taken.add(name)
                return name

    def claim(self, name):
        self._taken.add(name)
        return name

    def initializer(self, arr, hint="const"):
        import hashlib

        arr = np.ascontiguousarray(arr)
        key = (arr.dtype.str, arr.shape,
               hashlib.sha1(arr.tobytes()).hexdigest())
        if key in self._const_names:
            return self._const_names[key]
        name = self.fresh(hint)
        self.root_graph.initializer.append(_tensor_proto(name, arr))
        self._const_names[key] = name
        return name

    def read(self, val, hint="const"):
        """Graph-tensor name for a value, materializing consts."""
        if isinstance(val, _Name):
            return val.name
        return self.initializer(val.val, hint)

    def node(self, op_type, inputs, n_out=1, out=None, **attrs):
        """Append a node; returns its output name(s)."""
        outs = ([out] if out else
                [self.fresh(op_type.lower()) for _ in range(n_out)])
        n = P.NodeProto(op_type=op_type, name=self.fresh(f"n_{op_type}"))
        n.input.extend(inputs)
        n.output.extend(outs)
        for k, v in attrs.items():
            n.attribute.append(_attr(k, v))
        self.graph.node.append(n)
        return outs[0] if len(outs) == 1 else outs

    def i64(self, values, hint="axes"):
        return self.initializer(np.asarray(values, dtype=np.int64), hint)


def _sub_jaxpr(eqn):
    """(jaxpr, consts) for call-like primitives, else None."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        cj = eqn.params.get(key)
        if cj is None:
            continue
        if hasattr(cj, "jaxpr"):  # ClosedJaxpr
            return cj.jaxpr, list(cj.consts)
        return cj, []
    return None


def _try_fold(eqn, invals):
    """Evaluate an eqn whose inputs are all known, if cheap enough."""
    out_sz = sum(int(np.prod(v.aval.shape)) for v in eqn.outvars)
    if out_sz > 10_000_000:
        return None
    try:
        import jax

        with jax.default_device(jax.devices("cpu")[0]):
            vals = eqn.primitive.bind(
                *[np.asarray(v.val) for v in invals], **eqn.params)
    except Exception:
        return None
    if not eqn.primitive.multiple_results:
        vals = [vals]
    return [_Const(np.asarray(v)) for v in vals]


def _einsum_letters(dn, lhs_rank, rhs_rank):
    (lc, rc), (lb, rb) = dn
    letters = itertools.cycle("abcdefghijklmnopqrstuvwxyz")
    lhs = [None] * lhs_rank
    rhs = [None] * rhs_rank
    for i, j in zip(lb, rb):
        lhs[i] = rhs[j] = next(letters)
    for i, j in zip(lc, rc):
        lhs[i] = rhs[j] = next(letters)
    for spec in (lhs, rhs):
        for i, v in enumerate(spec):
            if v is None:
                spec[i] = next(letters)
    # XLA dot_general output: batch dims, then lhs free, then rhs free
    out = ([lhs[i] for i in lb]
           + [lhs[i] for i in range(lhs_rank) if i not in set(lb) | set(lc)]
           + [rhs[j] for j in range(rhs_rank) if j not in set(rb) | set(rc)])
    return f"{''.join(lhs)},{''.join(rhs)}->{''.join(out)}"


def _conv_transpose_node(ctx, eqn, ins):
    """conv_general_dilated with lhs_dilation is XLA's transposed conv:
    a unit-stride conv over the stride-dilated input with a spatially
    flipped, in/out-swapped kernel. Invert those kernel transforms in
    the graph and emit ONNX ConvTranspose."""
    p = eqn.params
    if any(s != 1 for s in p["window_strides"]):
        raise OnnxExportError("conv with both lhs_dilation and strides")
    if int(p["feature_group_count"]) != 1:
        raise OnnxExportError("grouped transposed conv export")
    k = list(eqn.invars[1].aval.shape[2:])
    d = list(p["rhs_dilation"])
    strides = [int(s) for s in p["lhs_dilation"]]
    plo, phi, opad = [], [], []
    for (lo, hi), ki, di in zip(p["padding"], k, d):
        eff = di * (ki - 1)
        if lo < 0 or hi < 0 or lo > eff:
            # negative jax pads (conv padding > effective kernel) crop
            # the output — not expressible as ConvTranspose pads
            raise OnnxExportError(
                "transposed conv pads outside the ONNX-representable "
                "range")
        plo.append(eff - lo)
        if hi <= eff:
            phi.append(eff - hi)
            opad.append(0)
        else:  # extra high-side output = ONNX output_padding
            phi.append(0)
            opad.append(hi - eff)
    nsp = len(k)
    # un-flip the spatial dims and un-swap (O,I)->(I,O)
    w = ctx.node("Slice", [ins[1],
                           ctx.i64([-1] * nsp, "starts"),
                           ctx.i64([_INT64_MIN + 1] * nsp, "ends"),
                           ctx.i64(list(range(2, 2 + nsp)), "axes"),
                           ctx.i64([-1] * nsp, "steps")])
    w = ctx.node("Transpose", [w],
                 perm=[1, 0] + list(range(2, 2 + nsp)))
    extra = {"output_padding": opad} if any(opad) else {}
    return ctx.node("ConvTranspose", [ins[0], w], kernel_shape=k,
                    strides=strides, pads=plo + phi, dilations=d,
                    group=1, **extra)


def _conv_node(ctx, eqn, ins):
    p = eqn.params
    dn = p["dimension_numbers"]
    ndim = len(eqn.invars[0].aval.shape)
    std = tuple(range(ndim))
    if (tuple(dn.lhs_spec) != std or tuple(dn.rhs_spec) != std
            or tuple(dn.out_spec) != std):
        raise OnnxExportError(
            f"conv layout {dn} is not NC{'HW'[:ndim-2]}/OIHW")
    if p.get("batch_group_count", 1) != 1:
        raise OnnxExportError("batch_group_count > 1")
    if any(s != 1 for s in p["lhs_dilation"]):
        return _conv_transpose_node(ctx, eqn, ins)
    pads_lo = [lo for lo, _ in p["padding"]]
    pads_hi = [hi for _, hi in p["padding"]]
    kernel = list(eqn.invars[1].aval.shape[2:])
    return ctx.node(
        "Conv", ins, kernel_shape=kernel,
        strides=list(p["window_strides"]),
        pads=pads_lo + pads_hi, dilations=list(p["rhs_dilation"]),
        group=int(p["feature_group_count"]))


def _pool_window(eqn):
    """Validate a reduce_window over trailing spatial dims; returns
    (kernel, strides, pads, dilations) or raises."""
    p = eqn.params
    wd = list(p["window_dimensions"])
    ws = list(p["window_strides"])
    pad = list(p["padding"])
    bd = list(p.get("base_dilation") or [1] * len(wd))
    wdil = list(p.get("window_dilation") or [1] * len(wd))
    if any(d != 1 for d in bd):
        raise OnnxExportError("reduce_window base_dilation")
    if wd[:2] != [1, 1] or ws[:2] != [1, 1] or pad[0] != (0, 0) \
            or pad[1] != (0, 0):
        raise OnnxExportError(f"reduce_window window {wd} not NCHW pooling")
    lo = [l for l, _ in pad[2:]]
    hi = [h for _, h in pad[2:]]
    return wd[2:], ws[2:], lo + hi, wdil[2:]


def _gather_node(ctx, eqn, invals):
    """jnp.take-along-axis-0-style gathers -> ONNX Gather."""
    p = eqn.params
    dn = p["dimension_numbers"]
    op_shape = eqn.invars[0].aval.shape
    idx_aval = eqn.invars[1].aval
    slice_sizes = tuple(p["slice_sizes"])
    if (len(dn.start_index_map) == 1
            and tuple(dn.collapsed_slice_dims) == tuple(dn.start_index_map)
            and not getattr(dn, "operand_batching_dims", ())
            and idx_aval.shape and idx_aval.shape[-1] == 1):
        axis = dn.start_index_map[0]
        want = tuple(1 if i == axis else d for i, d in enumerate(op_shape))
        if slice_sizes == want:
            data = ctx.read(invals[0], "gather_data")
            idx = ctx.read(invals[1], "gather_idx")
            if np.dtype(idx_aval.dtype) != np.int64:
                idx = ctx.node("Cast", [idx], to=_ONNX_DTYPE["int64"])
            # drop the trailing singleton index-vector dim
            sq = ctx.node("Reshape", [
                idx, ctx.i64(list(idx_aval.shape[:-1]), "idx_shape")])
            return ctx.node("Gather", [data, sq], axis=int(axis))
    raise OnnxExportError(f"gather pattern {dn} slice_sizes={slice_sizes}")


def _dynamic_slice(ctx, eqn, invals):
    sizes = [int(s) for s in eqn.params["slice_sizes"]]
    data = ctx.read(invals[0], "ds_data")
    starts = invals[1:]
    axes = list(range(len(sizes)))
    if all(isinstance(s, _Const) for s in starts):
        # jax clamps starts so the slice stays in bounds
        shape = eqn.invars[0].aval.shape
        st = [min(max(int(s.val), 0), int(d) - sz)
              for s, d, sz in zip(starts, shape, sizes)]
        return ctx.node("Slice", [
            data, ctx.i64(st, "starts"),
            ctx.i64([a + b for a, b in zip(st, sizes)], "ends"),
            ctx.i64(axes, "axes")])
    shape = eqn.invars[0].aval.shape
    parts = []
    for s, d, sz in zip(starts, shape, sizes):
        nm = ctx.read(s, "start")
        nm = ctx.node("Cast", [nm], to=_ONNX_DTYPE["int64"])
        # jax clamps starts into [0, dim - size]; ONNX Slice does not
        nm = ctx.node("Max", [nm, ctx.i64(0, "zero")])
        nm = ctx.node("Min", [nm, ctx.i64(int(d) - sz, "hi")])
        parts.append(ctx.node("Reshape", [nm, ctx.i64([1], "one")]))
    start_v = ctx.node("Concat", parts, axis=0)
    end_v = ctx.node("Add", [start_v, ctx.i64(sizes, "sizes")])
    return ctx.node("Slice", [data, start_v, end_v, ctx.i64(axes, "axes")])


def _dynamic_update_slice(ctx, eqn, invals):
    """lax.dynamic_update_slice -> ScatterND: a constant base grid of
    update-element coordinates shifted by the (clamped) start vector."""
    op_shape = [int(d) for d in eqn.invars[0].aval.shape]
    up_shape = [int(d) for d in eqn.invars[1].aval.shape]
    rank = len(op_shape)
    if rank == 0:  # scalar DUS is just the update value
        return ctx.node("Identity", [ctx.read(invals[1], "dus_update")])
    n_up = int(np.prod(up_shape))
    if n_up * rank > 5_000_000:
        raise OnnxExportError(
            "dynamic_update_slice with a very large update region")
    data = ctx.read(invals[0], "dus_data")
    update = ctx.read(invals[1], "dus_update")
    grid = np.stack(np.meshgrid(
        *[np.arange(d, dtype=np.int64) for d in up_shape],
        indexing="ij"), axis=-1)
    starts = invals[2:]
    if all(isinstance(s, _Const) for s in starts):
        st = [min(max(int(s.val), 0), d - u)
              for s, d, u in zip(starts, op_shape, up_shape)]
        idx = ctx.initializer(grid + np.asarray(st, np.int64),
                              "dus_idx")
    else:
        parts = []
        for s, d, u in zip(starts, op_shape, up_shape):
            nm = ctx.node("Cast", [ctx.read(s, "dus_start")],
                          to=_ONNX_DTYPE["int64"])
            nm = ctx.node("Max", [nm, ctx.i64(0, "zero")])
            nm = ctx.node("Min", [nm, ctx.i64(d - u, "hi")])
            parts.append(ctx.node("Reshape", [nm, ctx.i64([1], "one")]))
        start_v = ctx.node("Concat", parts, axis=0)
        idx = ctx.node("Add", [ctx.initializer(grid, "dus_grid"),
                               start_v])
    return ctx.node("ScatterND", [data, idx, update])


def _reduce_bool(ctx, eqn, ins, op):
    x = ctx.node("Cast", ins, to=_ONNX_DTYPE["int32"])
    r = ctx.node(op, [x], axes=[int(a) for a in eqn.params["axes"]],
                 keepdims=0)
    return ctx.node("Cast", [r], to=_ONNX_DTYPE["bool"])


def _outer_names(ctx, vals, hint):
    """Resolve values to names usable from a subgraph (ONNX subgraphs
    close over outer-scope tensors by name)."""
    return [_Name(ctx.read(v, hint)) for v in vals]


def _finish_subgraph(sub, outs, avals):
    """Set a subgraph's outputs, inserting Identity for values not
    produced by this graph's own nodes (consts / outer aliases)."""
    produced = {o for n in sub.graph.node for o in n.output}
    names = []
    seen = set()
    for val, aval in zip(outs, avals):
        if isinstance(val, _Const):
            name = sub.node("Identity", [sub.read(val, "out")])
        elif val.name not in produced or val.name in seen:
            # outer aliases AND repeated outvars (e.g. an RNN body
            # returning new_h twice) need a fresh SSA name
            name = sub.node("Identity", [val.name])
        else:
            name = val.name
        seen.add(name)
        sub.graph.output.append(_value_info(name, aval.shape, aval.dtype))
        names.append(name)
    return names


def _bool_name(ctx, val, hint):
    name = ctx.read(val, hint)
    dt = val.val.dtype if isinstance(val, _Const) else None
    if dt is None or np.dtype(dt) != np.bool_:
        name = ctx.node("Cast", [name], to=_ONNX_DTYPE["bool"])
    return name


def _scan_node(ctx, eqn, invals):
    """lax.scan -> ONNX Scan: carries map to state variables, xs to
    scan inputs (consts close over the outer scope)."""
    p = eqn.params
    closed = p["jaxpr"]
    nc, ncarry = p["num_consts"], p["num_carry"]
    reverse = bool(p.get("reverse", False))
    length = int(p["length"])
    inner = closed.jaxpr
    const_vals = _outer_names(ctx, invals[:nc], "scan_const")
    carries = invals[nc:nc + ncarry]
    xs = invals[nc + ncarry:]

    body = P.GraphProto(name=ctx.fresh("scan_body"))
    sub = ctx.sub(body)
    body_invals = list(const_vals)
    for var in inner.invars[nc:nc + ncarry]:
        nm = sub.fresh("scan_carry")
        body.input.append(_value_info(nm, var.aval.shape,
                                      var.aval.dtype))
        body_invals.append(_Name(nm))
    x_vars = inner.invars[nc + ncarry:]
    for var in x_vars:
        nm = sub.fresh("scan_x")
        body.input.append(_value_info(nm, var.aval.shape,
                                      var.aval.dtype))
        body_invals.append(_Name(nm))
    dummy = not x_vars  # Scan requires >= 1 scan input
    if dummy:
        nm = sub.fresh("scan_tick")
        body.input.append(_value_info(nm, (), "int32"))

    outs = _walk(sub, inner, closed.consts, body_invals)
    n_ys = len(outs) - ncarry
    _finish_subgraph(sub, outs, [v.aval for v in inner.outvars])

    scan_ins = [ctx.read(v, "scan_xs") for v in xs]
    if dummy:
        scan_ins = [ctx.initializer(
            np.zeros(length, np.int32), "scan_ticks")]
    n_scan = len(scan_ins)
    direction = [1 if reverse else 0] * n_scan
    node_outs = ctx.node(
        "Scan", [ctx.read(v, "scan_carry0") for v in carries] + scan_ins,
        n_out=ncarry + n_ys, body=body, num_scan_inputs=n_scan,
        scan_input_directions=direction,
        scan_output_directions=[1 if reverse else 0] * max(n_ys, 0)
        if n_ys else [])
    if isinstance(node_outs, str):
        node_outs = [node_outs]
    return [_Name(n) for n in node_outs]


def _cond_node(ctx, eqn, invals):
    """lax.cond -> ONNX If (two-branch; operands close over scope)."""
    branches = eqn.params["branches"]
    if len(branches) != 2:
        raise OnnxExportError(
            f"cond/switch with {len(branches)} branches")
    op_vals = _outer_names(ctx, invals[1:], "cond_arg")
    graphs = []
    for br in branches:
        g = P.GraphProto(name=ctx.fresh("branch"))
        sub = ctx.sub(g)
        outs = _walk(sub, br.jaxpr, br.consts, op_vals)
        _finish_subgraph(sub, outs, [v.aval for v in eqn.outvars])
        graphs.append(g)
    pred = _bool_name(ctx, invals[0], "cond_pred")
    node_outs = ctx.node("If", [pred], n_out=len(eqn.outvars),
                         then_branch=graphs[1], else_branch=graphs[0])
    if isinstance(node_outs, str):
        node_outs = [node_outs]
    return [_Name(n) for n in node_outs]


def _while_node(ctx, eqn, invals):
    """lax.while_loop -> ONNX Loop: body computes the next carry then
    re-evaluates the cond jaxpr for the loop condition."""
    p = eqn.params
    cj, bj = p["cond_jaxpr"], p["body_jaxpr"]
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    cconsts = _outer_names(ctx, invals[:cn], "while_ccost")
    bconsts = _outer_names(ctx, invals[cn:cn + bn], "while_bconst")
    init = invals[cn + bn:]
    init_names = [ctx.read(v, "loop_init") for v in init]

    # initial condition evaluated in the outer graph
    (cond0,) = _walk(ctx, cj.jaxpr, cj.consts,
                     cconsts + [_Name(n) for n in init_names])
    cond0_name = _bool_name(ctx, cond0, "loop_cond0")

    body = P.GraphProto(name=ctx.fresh("loop_body"))
    sub = ctx.sub(body)
    body.input.append(_value_info(sub.fresh("loop_iter"), (), "int64"))
    body.input.append(_value_info(sub.fresh("loop_cond_in"), (), "bool"))
    carry_vals = []
    for var in bj.jaxpr.invars[bn:]:
        nm = sub.fresh("loop_carry")
        body.input.append(_value_info(nm, var.aval.shape,
                                      var.aval.dtype))
        carry_vals.append(_Name(nm))
    new_carry = _walk(sub, bj.jaxpr, bj.consts, bconsts + carry_vals)
    (cond_out,) = _walk(sub, cj.jaxpr, cj.consts, cconsts + new_carry)
    cond_aval = cj.jaxpr.outvars[0].aval
    _finish_subgraph(sub, [cond_out] + new_carry,
                     [cond_aval] + [v.aval for v in eqn.outvars])

    node_outs = ctx.node("Loop", ["", cond0_name] + init_names,
                         n_out=len(eqn.outvars), body=body)
    if isinstance(node_outs, str):
        node_outs = [node_outs]
    return [_Name(n) for n in node_outs]


def _emit(ctx, eqn, invals):
    """Translate one eqn; returns a list of output values."""
    prim = eqn.primitive.name
    p = eqn.params

    def ins(*hints):
        return [ctx.read(v, h) for v, h in
                zip(invals, list(hints) + ["x"] * len(invals))]

    out_dt = eqn.outvars[0].aval.dtype if eqn.outvars else None

    if prim in _IDENTITY_PRIMS:
        return [invals[0]]

    if prim in _UNARY:
        return [_Name(ctx.node(_UNARY[prim], ins()))]

    if prim in _BINARY:
        if prim in ("add", "mul") and np.dtype(out_dt) == np.bool_:
            return [_Name(ctx.node(
                {"add": "Or", "mul": "And"}[prim], ins()))]
        return [_Name(ctx.node(_BINARY[prim], ins()))]

    if prim in ("and", "or", "xor"):
        boolean = np.dtype(out_dt) == np.bool_
        op = {"and": "And", "or": "Or", "xor": "Xor"}[prim] if boolean \
            else {"and": "BitwiseAnd", "or": "BitwiseOr",
                  "xor": "BitwiseXor"}[prim]
        return [_Name(ctx.node(op, ins()))]
    if prim == "not":
        boolean = np.dtype(out_dt) == np.bool_
        return [_Name(ctx.node("Not" if boolean else "BitwiseNot", ins()))]

    if prim == "ne":
        return [_Name(ctx.node("Not", [ctx.node("Equal", ins())]))]
    if prim == "rsqrt":
        return [_Name(ctx.node("Reciprocal", [ctx.node("Sqrt", ins())]))]
    if prim == "log1p":
        one = ctx.initializer(np.ones((), dtype=out_dt), "one")
        return [_Name(ctx.node("Log", [ctx.node("Add", ins() + [one])]))]
    if prim == "expm1":
        one = ctx.initializer(np.ones((), dtype=out_dt), "one")
        return [_Name(ctx.node("Sub", [ctx.node("Exp", ins()), one]))]
    if prim == "erfc":
        one = ctx.initializer(np.ones((), dtype=out_dt), "one")
        return [_Name(ctx.node("Sub", [one, ctx.node("Erf", ins())]))]
    if prim == "square":
        (x,) = ins()
        return [_Name(ctx.node("Mul", [x, x]))]
    if prim == "integer_pow":
        y = ctx.initializer(np.asarray(p["y"], dtype=out_dt), "exp")
        return [_Name(ctx.node("Pow", ins() + [y]))]
    if prim == "rem":
        # always fmod=1: lax.rem truncates (C semantics) for both ints
        # and floats; ONNX Mod with fmod=0 follows the divisor's sign
        return [_Name(ctx.node("Mod", ins(), fmod=1))]
    if prim == "clamp":
        lo, x, hi = invals
        r = ctx.node("Max", [ctx.read(x), ctx.read(lo, "clip_lo")])
        return [_Name(ctx.node("Min", [r, ctx.read(hi, "clip_hi")]))]
    if prim == "is_finite":
        (x,) = ins()
        bad = ctx.node("Or", [ctx.node("IsInf", [x]),
                              ctx.node("IsNaN", [x])])
        return [_Name(ctx.node("Not", [bad]))]
    if prim == "nextafter":
        raise OnnxExportError("nextafter")

    if prim == "convert_element_type":
        return [_Name(ctx.node("Cast", ins(),
                               to=_np_dtype_code(p["new_dtype"])))]

    if prim == "dot_general":
        dn = p["dimension_numbers"]
        (lc, rc), (lb, rb) = dn
        l_rank = len(eqn.invars[0].aval.shape)
        r_rank = len(eqn.invars[1].aval.shape)
        a, b = ins("matmul_a", "matmul_b")
        plain_mm = (not lb and not rb and l_rank >= 2 and r_rank == 2
                    and tuple(lc) == (l_rank - 1,) and tuple(rc) == (0,))
        batch_mm = (l_rank == r_rank and l_rank >= 3
                    and tuple(lb) == tuple(rb) == tuple(range(l_rank - 2))
                    and tuple(lc) == (l_rank - 1,)
                    and tuple(rc) == (l_rank - 2,))
        if plain_mm or batch_mm:
            return [_Name(ctx.node("MatMul", [a, b]))]
        eqn_str = _einsum_letters(dn, l_rank, r_rank)
        return [_Name(ctx.node("Einsum", [a, b], equation=eqn_str))]

    if prim == "conv_general_dilated":
        return [_Name(_conv_node(ctx, eqn, ins("conv_x", "conv_w")))]

    if prim == "reshape":
        if p.get("dimensions") is not None:
            raise OnnxExportError("reshape with dimension permutation")
        shape = ctx.i64(list(p["new_sizes"]), "shape")
        return [_Name(ctx.node("Reshape", ins() + [shape]))]
    if prim == "squeeze":
        shape = ctx.i64(list(eqn.outvars[0].aval.shape), "shape")
        return [_Name(ctx.node("Reshape", ins() + [shape]))]
    if prim == "expand_dims":
        shape = ctx.i64(list(eqn.outvars[0].aval.shape), "shape")
        return [_Name(ctx.node("Reshape", ins() + [shape]))]
    if prim == "transpose":
        return [_Name(ctx.node("Transpose", ins(),
                               perm=[int(x) for x in p["permutation"]]))]
    if prim in ("broadcast_in_dim", "broadcast"):
        out_shape = list(p["shape"])
        bdims = list(p["broadcast_dimensions"])
        in_shape = list(eqn.invars[0].aval.shape)
        mid = [1] * len(out_shape)
        for i, d in enumerate(bdims):
            mid[d] = in_shape[i]
        (x,) = ins("bcast")
        if mid != in_shape:
            x = ctx.node("Reshape", [x, ctx.i64(mid, "shape")])
        if mid != out_shape:
            x = ctx.node("Expand", [x, ctx.i64(out_shape, "shape")])
        return [_Name(x)]
    if prim == "concatenate":
        return [_Name(ctx.node("Concat", ins(),
                               axis=int(p["dimension"])))]
    if prim == "slice":
        if p.get("strides") is None:
            strides = [1] * len(p["start_indices"])
        else:
            strides = list(p["strides"])
        axes = list(range(len(strides)))
        return [_Name(ctx.node("Slice", ins() + [
            ctx.i64(list(p["start_indices"]), "starts"),
            ctx.i64(list(p["limit_indices"]), "ends"),
            ctx.i64(axes, "axes"), ctx.i64(strides, "steps")]))]
    if prim == "rev":
        dims = [int(d) for d in p["dimensions"]]
        return [_Name(ctx.node("Slice", ins() + [
            ctx.i64([-1] * len(dims), "starts"),
            ctx.i64([_INT64_MIN + 1] * len(dims), "ends"),
            ctx.i64(dims, "axes"),
            ctx.i64([-1] * len(dims), "steps")]))]
    if prim == "pad":
        cfg = list(p["padding_config"])
        if any(i != 0 for _, _, i in cfg):
            raise OnnxExportError("interior pad")
        if any(lo < 0 or hi < 0 for lo, hi, _ in cfg):
            raise OnnxExportError("negative pad")
        pads = [lo for lo, _, _ in cfg] + [hi for _, hi, _ in cfg]
        data, value = ins("pad_x", "pad_v")
        return [_Name(ctx.node("Pad", [
            data, ctx.i64(pads, "pads"), value]))]

    if prim == "select_n":
        if len(invals) != 3:
            raise OnnxExportError(f"select_n with {len(invals) - 1} cases")
        if np.dtype(eqn.invars[0].aval.dtype) != np.bool_:
            raise OnnxExportError("select_n with integer index")
        pred, on_false, on_true = ins("cond", "iffalse", "iftrue")
        return [_Name(ctx.node("Where", [pred, on_true, on_false]))]

    if prim == "reduce_sum":
        axes = ctx.i64([int(a) for a in p["axes"]], "axes")
        return [_Name(ctx.node("ReduceSum", ins() + [axes], keepdims=0))]
    if prim in _REDUCE_ATTR_AXES:
        return [_Name(ctx.node(
            _REDUCE_ATTR_AXES[prim], ins(),
            axes=[int(a) for a in p["axes"]], keepdims=0))]
    if prim == "reduce_and":
        return [_Name(_reduce_bool(ctx, eqn, ins(), "ReduceMin"))]
    if prim == "reduce_or":
        return [_Name(_reduce_bool(ctx, eqn, ins(), "ReduceMax"))]
    if prim in ("argmax", "argmin"):
        op = "ArgMax" if prim == "argmax" else "ArgMin"
        (axis,) = p["axes"]
        r = ctx.node(op, ins(), axis=int(axis), keepdims=0)
        code = _np_dtype_code(p["index_dtype"])
        if code != _ONNX_DTYPE["int64"]:
            r = ctx.node("Cast", [r], to=code)
        return [_Name(r)]
    if prim == "cumsum":
        axis = ctx.i64(int(p["axis"]), "axis")
        return [_Name(ctx.node("CumSum", ins() + [axis],
                               reverse=int(p.get("reverse", False))))]

    if prim == "top_k":
        k = ctx.i64([int(p["k"])], "k")
        vals, idx = ctx.node("TopK", ins() + [k], n_out=2, axis=-1,
                             largest=1, sorted=1)
        idx_dt = np.dtype(eqn.outvars[1].aval.dtype)
        if idx_dt != np.int64:
            idx = ctx.node("Cast", [idx], to=_np_dtype_code(idx_dt))
        return [_Name(vals), _Name(idx)]
    if prim == "sort":
        if p.get("num_keys", 1) != 1 or len(invals) != 1:
            raise OnnxExportError("multi-operand sort")
        axis = int(p["dimension"])
        size = int(eqn.invars[0].aval.shape[axis])
        vals, _ = ctx.node("TopK", ins() + [ctx.i64([size], "k")],
                           n_out=2, axis=axis, largest=0, sorted=1)
        return [_Name(vals)]

    if prim == "reduce_window_max":
        kernel, strides, pads, dil = _pool_window(eqn)
        return [_Name(ctx.node("MaxPool", ins(), kernel_shape=kernel,
                               strides=strides, pads=pads,
                               dilations=dil))]
    if prim == "reduce_window_sum":
        kernel, strides, pads, dil = _pool_window(eqn)
        if any(d != 1 for d in dil):
            raise OnnxExportError("dilated sum pooling")
        avg = ctx.node("AveragePool", ins(), kernel_shape=kernel,
                       strides=strides, pads=pads, count_include_pad=1)
        n = ctx.initializer(
            np.asarray(float(np.prod(kernel)), dtype=out_dt), "win")
        return [_Name(ctx.node("Mul", [avg, n]))]

    if prim == "gather":
        return [_Name(_gather_node(ctx, eqn, invals))]
    if prim == "dynamic_slice":
        return [_Name(_dynamic_slice(ctx, eqn, invals))]
    if prim == "dynamic_update_slice":
        return [_Name(_dynamic_update_slice(ctx, eqn, invals))]
    if prim in ("scatter", "scatter-add"):
        dn = p["dimension_numbers"]
        k = len(dn.scatter_dims_to_operand_dims)
        idx_depth = int(eqn.invars[1].aval.shape[-1]) \
            if eqn.invars[1].aval.shape else 0
        if (dn.update_window_dims
                or getattr(dn, "operand_batching_dims", ())
                or tuple(dn.inserted_window_dims) != tuple(range(k))
                or tuple(dn.scatter_dims_to_operand_dims)
                != tuple(range(k))
                or k != idx_depth):
            raise OnnxExportError(
                f"scatter pattern {dn} (only full-prefix scalar "
                "scatters export)")
        if prim == "scatter-add" and ctx.opset < 16:
            raise OnnxExportError(
                "scatter-add needs ScatterND reduction='add' (opset "
                ">= 16); pass opset_version=16 to export")
        data, idx, upd = ins("scat_data", "scat_idx", "scat_upd")
        if np.dtype(eqn.invars[1].aval.dtype) != np.int64:
            idx = ctx.node("Cast", [idx], to=_ONNX_DTYPE["int64"])
        # jax FILL_OR_DROP drops out-of-bounds updates; emulate by
        # clamping the index and neutralizing the dropped update
        dims = [int(d) for d in eqn.invars[0].aval.shape[:k]]
        limit = ctx.i64(dims, "scat_dims")
        nonneg = ctx.node("GreaterOrEqual", [idx, ctx.i64(0, "zero")])
        inb = ctx.node("Less", [idx, limit])
        both = ctx.node("Cast", [ctx.node("And", [nonneg, inb])],
                        to=_ONNX_DTYPE["int32"])
        valid = ctx.node("Cast", [ctx.node(
            "ReduceMin", [both], axes=[-1], keepdims=0)],
            to=_ONNX_DTYPE["bool"])
        safe = ctx.node("Max", [ctx.node(
            "Min", [idx, ctx.i64([d - 1 for d in dims], "scat_hi")]),
            ctx.i64(0, "zero")])
        if prim == "scatter-add":  # adding zero == dropped
            zero = ctx.initializer(
                np.zeros((), eqn.invars[2].aval.dtype), "scat_zero")
            upd2 = ctx.node("Where", [valid, upd, zero])
            return [_Name(ctx.node("ScatterND", [data, safe, upd2],
                                   reduction="add"))]
        # overwrite: dropped rows rewrite their current value
        current = ctx.node("GatherND", [data, safe])
        upd2 = ctx.node("Where", [valid, upd, current])
        return [_Name(ctx.node("ScatterND", [data, safe, upd2]))]

    if prim == "split":
        sizes = [int(s) for s in p["sizes"]]
        outs = ctx.node("Split", ins() + [ctx.i64(sizes, "split")],
                        n_out=len(sizes), axis=int(p["axis"]))
        if isinstance(outs, str):
            outs = [outs]
        return [_Name(n) for n in outs]

    if prim == "scan":
        return _scan_node(ctx, eqn, invals)
    if prim == "cond":
        return _cond_node(ctx, eqn, invals)
    if prim == "while":
        return _while_node(ctx, eqn, invals)

    raise OnnxExportError(f"primitive '{prim}' has no ONNX mapping")


def _walk(ctx, jaxpr, consts, invals, fold=True):
    env = {}

    def read(atom):
        if isinstance(atom, Literal):
            return _Const(np.asarray(atom.val))
        return env[atom]

    for var, const in zip(jaxpr.constvars, consts):
        env[var] = _Const(np.asarray(const))
    for var, val in zip(jaxpr.invars, invals):
        env[var] = val

    for eqn in jaxpr.eqns:
        vals = [read(a) for a in eqn.invars]
        sub = _sub_jaxpr(eqn) if eqn.primitive.name in _CALL_PRIMS else None
        if sub is not None:
            inner, inner_consts = sub
            if len(vals) != len(inner.invars):
                raise OnnxExportError(
                    f"{eqn.primitive.name}: {len(vals)} args for "
                    f"{len(inner.invars)}-input sub-jaxpr")
            outs = _walk(ctx, inner, inner_consts, vals, fold=fold)
        else:
            outs = None
            if fold and all(isinstance(v, _Const) for v in vals):
                outs = _try_fold(eqn, vals)
            if outs is None:
                outs = _emit(ctx, eqn, vals)
        if len(outs) != len(eqn.outvars):
            raise OnnxExportError(
                f"{eqn.primitive.name}: emitted {len(outs)} outputs for "
                f"{len(eqn.outvars)} outvars")
        for var, val in zip(eqn.outvars, outs):
            env[var] = val

    return [read(a) for a in jaxpr.outvars]


def jaxpr_to_onnx(closed_jaxpr, *, input_names, param_values=None,
                  graph_name="main", opset=13, producer="paddle_tpu",
                  fold_constants=True):
    """Convert a ClosedJaxpr to an ONNX ModelProto.

    The first `len(param_values)` jaxpr inputs become named initializers
    (weights); the rest become graph inputs named by `input_names`.
    """
    param_values = param_values or {}
    if not 13 <= opset <= 17:
        # ReduceSum takes axes as an input (>=13) while ReduceMax/Min/
        # Prod take them as an attribute (<18) — the emitted mix is only
        # valid in this window.
        raise OnnxExportError(
            f"opset {opset} unsupported (emitted ops target 13..17)")
    model = P.ModelProto(ir_version=8, producer_name=producer,
                         producer_version="1.0")
    op = model.opset_import.add()
    op.domain, op.version = "", opset
    g = model.graph
    g.name = graph_name

    ctx = _Ctx(g, opset)
    jaxpr = closed_jaxpr.jaxpr
    n_params = len(param_values)
    invals = []
    for name, value in param_values.items():
        ctx.claim(name)
        g.initializer.append(_tensor_proto(name, np.asarray(value)))
        invals.append(_Name(name))
    for var, name in zip(jaxpr.invars[n_params:], input_names):
        ctx.claim(name)
        g.input.append(_value_info(name, var.aval.shape, var.aval.dtype))
        invals.append(_Name(name))
    if len(invals) != len(jaxpr.invars):
        raise OnnxExportError(
            f"{len(jaxpr.invars)} jaxpr inputs vs {n_params} params + "
            f"{len(input_names)} input names")

    outs = _walk(ctx, jaxpr, closed_jaxpr.consts, invals,
                 fold=fold_constants)

    produced = {o for n in g.node for o in n.output}
    for i, (val, var) in enumerate(zip(outs, jaxpr.outvars)):
        if isinstance(val, _Const):
            name = ctx.read(val, f"output_{i}")
            name = ctx.node("Identity", [name], out=ctx.fresh("out"))
        elif val.name not in produced:
            name = ctx.node("Identity", [val.name], out=ctx.fresh("out"))
        else:
            name = val.name
        g.output.append(_value_info(name, var.aval.shape, var.aval.dtype))
    return model
