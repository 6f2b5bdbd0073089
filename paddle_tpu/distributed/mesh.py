"""Hybrid device mesh.

Replaces the reference's communicator-group plumbing
(python/paddle/distributed/fleet/base/topology.py HybridCommunicateGroup +
ProcessGroupNCCL ring ids) with one jax.sharding.Mesh whose named axes carry
the parallelism dimensions:

    ("pp", "dp", "sharding", "sep", "tp")

Collectives are never issued manually on the perf path — parameter/batch
PartitionSpecs over these axes tell XLA's SPMD partitioner where
all-reduce / all-gather / reduce-scatter / all-to-all belong, and it emits
them on ICI. Axis order puts tp innermost so tensor-parallel collectives ride
the fastest links (scaling-book layout).
"""
from __future__ import annotations

import warnings
from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (the package's one import site)
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXES = ("pp", "dp", "sharding", "sep", "tp")

_global_mesh: Optional[Mesh] = None


def build_mesh(dp: int = 1, tp: int = 1, pp: int = 1, sharding: int = 1,
               sep: int = 1, devices: Optional[Sequence] = None) -> Mesh:
    """The hybrid mesh over ``devices`` (default: every device jax has).

    No degrees at all means data parallel over all of them. Degrees that
    multiply to fewer than the devices present take the first few (parity
    testing on a virtual mesh, a tensor-parallel replica on part of a
    host; the reference requires product == world_size) and say so in a
    warning: a run that believes it spans a host must not sit on a part
    of it without a word."""
    devices = list(devices) if devices is not None else jax.devices()
    need = dp * tp * pp * sharding * sep
    if need == 1:
        dp = len(devices)
        need = dp
    if need > len(devices):
        raise ValueError(
            f"mesh degrees {dp}x{sharding}x{tp}x{pp}x{sep}={need} > "
            f"{len(devices)} devices")
    if need < len(devices):
        warnings.warn(
            f"mesh degrees dp={dp} sharding={sharding} tp={tp} pp={pp} "
            f"sep={sep} use the first {need} of {len(devices)} devices",
            stacklevel=2)
    arr = np.asarray(devices[:need]).reshape(pp, dp, sharding, sep, tp)
    return Mesh(arr, AXES)


def partial_manual(fn, mesh: Mesh, manual, in_specs, out_specs):
    """``fn`` under a shard_map that is manual over the axes ``manual``
    only: placement on the other axes stays with the GSPMD partitioner, so
    the call nests inside a pjit program. With ``check_vma=False`` jax
    0.9.0 breaks a partial-manual shard_map (its internal unmatch spec then
    names every mesh axis), so the check stays on whenever some axis stays
    automatic."""
    manual = frozenset(manual)
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     axis_names=manual,
                     check_vma=frozenset(mesh.axis_names) != manual)


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        _global_mesh = build_mesh()
    return _global_mesh


def mesh_axis_size(name: str) -> int:
    mesh = get_mesh()
    return mesh.shape[name] if name in mesh.shape else 1


def named_sharding(spec: PartitionSpec) -> NamedSharding:
    return NamedSharding(get_mesh(), spec)


def data_pspec(shape) -> PartitionSpec:
    """PartitionSpec for one batch leaf given its shape: batch dim over
    (dp, sharding); the seq dim (dim 1) over "sep" when divisible (sequence
    parallelism). Dims that don't divide stay replicated; scalars get P()."""
    shape = tuple(shape)
    if not shape:
        return PartitionSpec()
    dspan = mesh_axis_size("dp") * mesh_axis_size("sharding")
    first = ("dp", "sharding") if shape[0] % dspan == 0 else None
    rest = [None] * (len(shape) - 1)
    sep = mesh_axis_size("sep")
    if len(shape) >= 2 and sep > 1 and shape[1] % sep == 0:
        rest[0] = "sep"
    return PartitionSpec(first, *rest)


def infer_param_pspec(shape, tp_spec: Optional[PartitionSpec], stage: int,
                      min_shard_size: int = 1024) -> PartitionSpec:
    """Parameter placement policy.

    - tp_spec (from Column/RowParallelLinear etc.) is kept.
    - sharding stage 3 additionally shards the largest remaining dim over
      the "sharding" axis (ZeRO-3 == param pspec carries "sharding").
    - stages 0-2 leave params replicated (their ZeRO-ness lives in the
      optimizer-state/grad shardings chosen by the train-step builder).
    """
    ndim = len(shape)
    spec = list(tp_spec) if tp_spec is not None else [None] * ndim
    while len(spec) < ndim:
        spec.append(None)
    # drop declared axes the shape can't honor (e.g. an expert axis whose
    # count doesn't divide the mp degree falls back to replicated), and
    # normalize size-1 axes to None (a "tp" annotation on a tp=1 mesh is
    # no sharding at all — it must not block the stage-3 placement below)
    for d, ax in enumerate(spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh_axis_size(a)
        if size == 1 or (size > 1 and shape[d] % size != 0):
            spec[d] = None
    if stage >= 3 and int(np.prod(shape)) >= min_shard_size:
        ssize = mesh_axis_size("sharding")
        # Only tp-FREE params take the extra "sharding" dim. Mixing tp and
        # sharding axes on one weight (e.g. o_proj P("tp","sharding"))
        # forces GSPMD to reshard batch-sharded activations onto the
        # hidden dim for the weight-grad einsum — a transition the
        # partitioner can only do by full rematerialization ("[SPMD]
        # Involuntary full rematerialization" in the dryrun). tp params
        # stay tp-sharded; their fp32 moments still ZeRO-shard over
        # "sharding" (see train_step._opt_state_pspec), which is where
        # the memory actually is under Adam.
        if ssize > 1 and all(a is None for a in spec):
            cands = [(d, shape[d]) for d in range(ndim)
                     if shape[d] % ssize == 0]
            if cands:
                d = max(cands, key=lambda t: t[1])[0]
                spec[d] = "sharding"
    return PartitionSpec(*spec)
