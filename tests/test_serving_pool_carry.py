"""The paged programs carry the KV pool through their layer loop as a
scan CARRY, flat over layers (``serving.engine._scan_layers_over_pool``).

Parity: the helper against the loop it replaced — the pools handed to
``lax.scan`` as ``xs`` and stacked back as ``ys``, written out below —
over the per-layer bodies of ``text/generation.py``: activations and
both pools bitwise equal, at tiny shapes, several layers, some slots
inactive, some table entries on the trash block. Structure: no ``scan``
of a traced paged program moves an array of the pool's shape through its
``xs`` or ``ys`` (read off the jaxpr); the old loop does, which is what
shows the reader sees it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from paddle_tpu.serving import engine as E
from paddle_tpu.text import generation as G

_L, _NB, _BS, _HD, _MB, _S = 3, 9, 4, 8, 4, 4      # max_len = _MB * _BS = 16
_FF, _V = 48, 40
_ARCHS = {"llama-mha": ("llama", 4, 4), "llama-gqa4": ("llama", 8, 2),
          "gpt": ("gpt", 4, 4)}
_KINDS = ("decode", "chunk", "verify")


def _weights(arch, n_heads, n_kv, seed=0):
    """The stacked-weight tree of ``generation._stacked_weights`` /
    ``_gpt_stacked_weights``, random, float32."""
    h = n_heads * _HD
    if arch == "llama":
        shapes = {"wq": (_L, h, h), "wk": (_L, h, n_kv * _HD),
                  "wv": (_L, h, n_kv * _HD), "wo": (_L, h, h),
                  "wg": (_L, h, _FF), "wu": (_L, h, _FF), "wd": (_L, _FF, h),
                  "ln1": (_L, h), "ln2": (_L, h), "embed": (_V, h),
                  "norm": (h,), "head": (h, _V)}
    else:
        shapes = {"wqkv": (_L, h, 3 * h), "bqkv": (_L, 3 * h),
                  "wproj": (_L, h, h), "bproj": (_L, h), "ln1w": (_L, h),
                  "ln1b": (_L, h), "ln2w": (_L, h), "ln2b": (_L, h),
                  "wfc1": (_L, h, _FF), "bfc1": (_L, _FF),
                  "wfc2": (_L, _FF, h), "bfc2": (_L, h), "wte": (_V, h),
                  "wpe": (_MB * _BS, h), "lnfw": (h,), "lnfb": (h,),
                  "head": (h, _V)}
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32)
            for k, s in shapes.items()}


def _pools(n_kv, seed=1):
    rng = np.random.default_rng(seed)
    shape = (_L, _NB, _BS, n_kv, _HD)
    return (jnp.asarray(rng.standard_normal(shape), jnp.float32),
            jnp.asarray(rng.standard_normal(shape), jnp.float32))


def _statics(arch, n_heads, n_kv):
    return dict(arch=arch, n_heads=n_heads, n_kv=n_kv, eps=1e-6, theta=1e4,
                do_sample=False, top_k=0, top_p=1.0, block_size=_BS)


def _layer_statics(arch, n_heads, n_kv):
    """What of the program's statics its per-layer bodies take."""
    names = (("n_heads", "n_kv", "eps", "theta", "block_size")
             if arch == "llama" else ("n_heads", "block_size"))
    statics = _statics(arch, n_heads, n_kv)
    return {k: statics[k] for k in names}


# slot 1 is inactive (its row goes to the trash block); every table keeps
# its unused tail on the trash block (id 0)
_TABLES = np.array([[3, 5, 0, 0], [2, 0, 0, 0], [7, 1, 8, 0], [4, 0, 0, 0]],
                   np.int32)
_CUR = np.array([5, 3, 9, 0], np.int32)
_ACTIVE = np.array([True, False, True, True])
_TABLE_ROW = np.array([6, 2, 4, 0], np.int32)


def _loop_case(kind, arch, n_heads, n_kv):
    """(layer, x, block ids, row ids) of one program's layer loop, built
    as the program builds them."""
    h = n_heads * _HD
    rng = np.random.default_rng(2)
    st = _layer_statics(arch, n_heads, n_kv)
    if kind == "decode":
        cur = jnp.asarray(_CUR)
        blk = _TABLES[np.arange(_S), _CUR // _BS]
        dest = np.where(_ACTIVE, blk * _BS + _CUR % _BS, _CUR % _BS)
        x = jnp.asarray(rng.standard_normal((_S, 1, h)), jnp.float32)
        if arch == "llama":
            def layer(xc, lw, kp, vp, blocks, rows):
                return G._llama_decode_layer_paged(xc, lw, kp, vp, blocks,
                                                   rows, cur, cur, **st)
        else:
            def layer(xc, lw, kp, vp, blocks, rows):
                return G._gpt_decode_layer_paged(xc, lw, kp, vp, blocks,
                                                 rows, cur, **st)
        return layer, x, jnp.asarray(_TABLES), jnp.asarray(dest, jnp.int32)
    if kind == "chunk":
        # 8 positions from 4; below 6 a shared prefix, from 10 padding:
        # both go to the trash block
        gpos = 4 + np.arange(8)
        writable = (gpos >= 6) & (gpos < 10)
        body = {"llama": G._llama_chunk_layer, "gpt": G._gpt_chunk_layer}
    else:
        # a draft of 3 after position 5, the last one past the budget
        gpos = 5 + np.arange(4)
        writable = np.arange(4) < 3
        body = {"llama": G._llama_verify_layer, "gpt": G._gpt_verify_layer}
    wdest = np.where(writable, _TABLE_ROW[gpos // _BS] * _BS + gpos % _BS,
                     gpos % _BS)
    x = jnp.asarray(rng.standard_normal((1, len(gpos), h)), jnp.float32)
    gp = jnp.asarray(gpos, jnp.int32)

    def layer(xc, lw, kp, vp, blocks, rows):
        return body[arch](xc, lw, kp, vp, blocks, gp, rows, **st)

    return layer, x, jnp.asarray(_TABLE_ROW), jnp.asarray(wdest, jnp.int32)


def _xs_ys_loop(layer, stack, x, kc, vc, block_ids, row_ids):
    """The loop the helper replaced: each layer's pool slice cut out of
    the scan's ``xs`` and stacked back into its ``ys``."""
    def one(cx, lw_kv):
        x2, kc_l, vc_l = layer(cx["x"], lw_kv, lw_kv["kc"], lw_kv["vc"],
                               block_ids, row_ids)
        return {"x": x2}, (kc_l, vc_l)

    lw_kv = dict(stack)
    lw_kv["kc"] = kc
    lw_kv["vc"] = vc
    cx, (kc, vc) = jax.lax.scan(one, {"x": x}, lw_kv)
    return cx["x"], kc, vc


def _sub_jaxprs(value):
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _scans_with(jaxpr, shape, where):
    """Every ``scan`` below ``jaxpr`` (at any depth) that has an array of
    ``shape`` among its ``xs`` and ``ys`` (``where="moved"``) or in its
    carry (``where="carried"``). By shape and not by element count: at
    these sizes a weight stack has more elements than the pool."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            n_carry = eqn.params["num_carry"]
            if where == "carried":
                arrays = eqn.outvars[:n_carry]
            else:
                skip = eqn.params["num_consts"] + n_carry
                arrays = list(eqn.invars[skip:]) + list(eqn.outvars[n_carry:])
            if any(tuple(v.aval.shape) == tuple(shape) for v in arrays):
                found.append(eqn)
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                found += _scans_with(sub, shape, where)
    return found


_PROGRAMS = {"decode": E._paged_decode_impl, "chunk": E._paged_chunk_impl,
             "verify": E._spec_verify_impl}


def _program_args(kind, w, kc, vc):
    """The arguments of the whole program of ``kind`` (one device or tp:
    they take the same), as the engine passes them."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    keys = jnp.zeros((_S, 2), jnp.uint32)
    tok = i32(np.arange(_S) + 1)
    if kind == "decode":
        return (w, kc, vc, i32(_TABLES), tok, i32(_CUR),
                jnp.asarray(_ACTIVE), keys, jnp.ones((_S,), jnp.float32),
                jnp.ones((_S, _V), jnp.int8))
    vmask = jnp.ones((_V,), jnp.int8)
    temp = jnp.ones((1,), jnp.float32)
    if kind == "chunk":
        return (w, kc, vc, tok, i32(_CUR), keys,
                i32(np.arange(8)[None] % _V), i32(4), i32(10), i32(1),
                i32(_TABLE_ROW), i32(6), i32(1), i32(0), i32(0), temp, vmask)
    return (w, kc, vc, keys, i32(np.arange(4)[None] % _V), i32(5), i32(1),
            i32(_TABLE_ROW), i32(3), temp, vmask)


@pytest.mark.parametrize("kind", _KINDS)
@pytest.mark.parametrize("arch_name", sorted(_ARCHS))
def test_pool_as_carry_is_bitwise_the_xs_ys_loop(arch_name, kind):
    arch, n_heads, n_kv = _ARCHS[arch_name]
    w = _weights(arch, n_heads, n_kv)
    keys = G._LLAMA_STACK_KEYS if arch == "llama" else G._GPT_STACK_KEYS
    stack = {k: w[k] for k in keys}
    kc, vc = _pools(n_kv)
    layer, x, block_ids, row_ids = _loop_case(kind, arch, n_heads, n_kv)

    old = jax.jit(functools.partial(_xs_ys_loop, layer))
    new = jax.jit(functools.partial(E._scan_layers_over_pool, layer))
    want = old(stack, x, kc, vc, block_ids, row_ids)
    got = new(stack, x, kc, vc, block_ids, row_ids)
    for name, a, b in zip(("x", "kc", "vc"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    # the case is no empty one: every layer wrote rows, the activations
    # moved, and rows no id names are as they were
    assert not np.array_equal(np.asarray(got[0]), np.asarray(x))
    changed = np.any(np.asarray(got[1]) != np.asarray(kc), axis=(3, 4))
    changed = changed.reshape(_L, _NB * _BS)
    assert changed.any(axis=1).all()
    assert set(np.flatnonzero(changed.any(axis=0))) <= set(
        np.asarray(row_ids).tolist())

    # the reader below sees the old form
    old_jaxpr = jax.make_jaxpr(functools.partial(_xs_ys_loop, layer))(
        stack, x, kc, vc, block_ids, row_ids)
    assert _scans_with(old_jaxpr.jaxpr, kc.shape, "moved")
    # and none of it in the program the engine runs
    fn = functools.partial(_PROGRAMS[kind], **_statics(arch, n_heads, n_kv))
    jaxpr = jax.make_jaxpr(fn)(*_program_args(kind, w, kc, vc))
    flat = (_L * _NB,) + kc.shape[2:]
    assert len(_scans_with(jaxpr.jaxpr, flat, "carried")) == 1
    assert not _scans_with(jaxpr.jaxpr, kc.shape, "moved")
    # outward the pools keep their shape (decode returns the tokens first)
    first = 1 if kind == "decode" else 0
    assert [a.shape for a in jaxpr.out_avals[first:first + 2]] == [
        kc.shape, vc.shape]


@pytest.mark.parametrize("kind", ("decode", "chunk"))
def test_tp_programs_carry_the_pool_too(kind):
    """The same programs with ``tp=2``, traced inside their ``shard_map``
    over two (virtual) devices: the local pool shard rides the same
    carry."""
    from jax.sharding import Mesh
    tp = 2
    if len(jax.devices()) < tp:
        pytest.skip("needs two devices")
    arch, n_heads, n_kv = _ARCHS["llama-gqa4"]
    w = _weights(arch, n_heads, n_kv)
    kc, vc = _pools(n_kv)
    statics = dict(_statics(arch, n_heads, n_kv), tp=tp)
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
    fn = E._tp_jitted(mesh, kind, arch, False,
                      tuple(sorted(statics.items())))
    jaxpr = jax.make_jaxpr(fn)(*_program_args(kind, w, kc, vc))
    local = kc.shape[:3] + (n_kv // tp, _HD)
    assert len(_scans_with(jaxpr.jaxpr, (_L * _NB,) + local[2:],
                           "carried")) == 1
    assert not _scans_with(jaxpr.jaxpr, local, "moved")
