"""A routed feed-forward over its picks grouped by expert: each expert's
SwiGLU applied to the rows that picked it, and to no other.

The plain form (``nn/routed_ffn.py``) applies every held expert to every
row and weighs the unpicked by zero. Under a v5e's ridge (~240 rows) that
costs the banks' bytes, all of them, picked or not; over it, ``E / k``
times the picks' products. This form reads only the experts that some row
picked, each once a call, and multiplies only their rows.

1. **The layout** (XLA, a few small operations, no sort): a row picks an
   expert at most once, so its place in the expert's group is the number
   of rows before it that picked the expert. Each group starts on a tile
   of ``tm`` places; ``src`` and ``c`` give each place its row and its
   weight (0 and 0.0 past a group's end), and one int32 array the rest:
   the live tiles, each tile's expert, and the stream of weight blocks.
   Picks that are not here (``local == held``: absent experts, rows that
   are no tokens, weight 0) have no place.
2. **One kernel** (:func:`_kernel`), a grid step per row tile and block
   of ``tf`` of the expert's width. The call's rows ``[T, h]`` and its
   float32 result stay in VMEM throughout; a tile's rows are taken out of
   them, and its weighted results added back, by products with the
   tile's one-hot ``[tm, T]``, which move every number exactly (the
   result as three bf16 terms that sum to its float32 value). The weight
   blocks of the hit experts are a stream copied ``_DEPTH - 1`` blocks
   ahead of the one being multiplied, each block's copies started as soon
   as the block before it is in: left to the grid's own pipeline, which
   starts them a step ahead, they ran at 400-530 GB/s on a v5e, and at
   ~750 GB/s this way. ``silu(x Wg) * (x Wu)`` is float32, rounded to the
   rows' type once before ``Wd``; its product sums over the blocks in
   float32. Where one expert's three matrices fit whole (``tf == f``),
   its tiles share one block: each hit expert is read once a call.
   An expert nobody picked is neither copied nor multiplied, nor is a
   tile past a group's end.

Tiles come from the shapes: ``tm`` is the rows of a call rounded up to
whole sublanes, at most ``_ROWS``; ``tf`` the widest divisor of ``f`` in
whole lanes whose three blocks, ``_DEPTH`` times over, fit
``_WEIGHT_BYTES``.

The kernel takes the banks whole, as its first tensor operands after the
one int32 array, and has a single result: a device trace names it
``grouped_swiglu`` beside the banks' shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["blocks", "grouped_swiglu"]

#: rows a tile, at most: a whole pass of the MXU (64 measured the same on
#: a v5e, 256 slower)
_ROWS = 128
#: weight blocks in VMEM at once: the one being multiplied and two on
#: their way (two measured within 3 % of three on a v5e)
_DEPTH = 3
#: VMEM for the weight blocks, ``_DEPTH`` copies of each of the three
_WEIGHT_BYTES = 48 * 1024 * 1024
#: VMEM for the call's rows and its float32 result, held whole; more rows
#: than fit go the plain way
_ROW_BYTES = 24 * 1024 * 1024
#: VMEM one call may plan: the above, a tile's rows and float32 sum, and
#: the products in flight. A v5e has 128 MiB; the compiler's own default
#: is 16
_VMEM_BYTES = 100 * 1024 * 1024


def blocks(T, h, f, itemsize):
    """``(tm, tf)`` for ``T`` rows over experts of ``h x f``, or ``None``
    where no block in whole lanes fits ``_WEIGHT_BYTES`` or the rows do
    not fit ``_ROW_BYTES``."""
    if h % 128 or f % 128 or T * h * (itemsize + 4) > _ROW_BYTES:
        return None
    sublanes = 8 * 4 // itemsize
    tm = min(_ROWS, -(-T // sublanes) * sublanes)
    tf = next((b for b in range(f, 0, -128) if f % b == 0
               and _DEPTH * 3 * h * b * itemsize <= _WEIGHT_BYTES), None)
    return None if tf is None else (tm, tf)


def _exact_terms(c, dtype):
    """``c`` (float32) as terms of ``dtype`` whose sum is ``c`` exactly:
    three bf16 terms carry float32's 24 bits."""
    if dtype == jnp.float32:
        return [c]
    terms = []
    for _ in range(3):
        terms.append(c.astype(dtype))
        c = c - terms[-1].astype(jnp.float32)
    return terms


def _kernel(meta, wg_hbm, wu_hbm, wd_hbm, x_hbm, src_ref, c_ref, y_hbm,
            gbuf, ubuf, dbuf, x_vmem, y_vmem, sems, *tile, tiles, n_f):
    """Step ``q`` multiplies row tile ``q // n_f`` by block ``q % n_f`` of
    its expert's width. The weight blocks are a stream, block ``b`` in
    slot ``b % _DEPTH``: once block ``b`` is in, the copies of block
    ``b + _DEPTH - 1`` start, and block ``b`` is multiplied while they
    and those of the blocks between run. The call's rows and its result
    stay in VMEM
    throughout: a tile's rows are taken from them, and its results added
    back, by products with the tile's one-hot ``[tm, T]`` (one 1 a row),
    which move each number exactly."""
    q = pl.program_id(0)
    live_tiles, blocks_live = meta[0], meta[1]
    T, dtype = x_vmem.shape[0], x_vmem.dtype
    exact = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def block(q):
        return meta[2 + tiles + q] if n_f == 1 else q

    def copies(b):
        slot = b % _DEPTH
        if n_f == 1:
            e, cols = meta[2 + 2 * tiles + b], pl.ds(0, gbuf.shape[-1])
        else:
            e = meta[2 + b // n_f]
            cols = pl.ds(b % n_f * gbuf.shape[-1], gbuf.shape[-1])
        return [pltpu.make_async_copy(wg_hbm.at[e, :, cols], gbuf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(wu_hbm.at[e, :, cols], ubuf.at[slot],
                                      sems.at[1, slot]),
                pltpu.make_async_copy(wd_hbm.at[e, cols, :], dbuf.at[slot],
                                      sems.at[2, slot])]

    def start(b):
        @pl.when(b < blocks_live)
        def _():
            for c in copies(b):
                c.start()

    @pl.when(q == 0)
    def _prologue():
        for b in range(_DEPTH - 1):
            start(b)
        pltpu.sync_copy(x_hbm, x_vmem)
        y_vmem[...] = jnp.zeros_like(y_vmem)

    live = q < live_tiles * n_f
    b = block(q)

    @pl.when(live & ((q == 0) | (block(jnp.maximum(q - 1, 0)) != b)))
    def _next_block():
        for c in copies(b):
            c.wait()
        start(b + _DEPTH - 1)

    @pl.when(live)
    def _apply():
        slot, n = b % _DEPTH, q % n_f
        hot = (src_ref[...] == jax.lax.broadcasted_iota(
            jnp.int32, (src_ref.shape[0], T), 1)).astype(dtype)  # [tm, T]

        def rows():
            return jnp.dot(hot, x_vmem[...], precision=exact,
                           preferred_element_type=jnp.float32).astype(dtype)

        if n_f == 1:
            x = rows()
        else:
            xs_vmem, acc_ref = tile

            @pl.when(n == 0)
            def _take():
                xs_vmem[...] = rows()

            x = xs_vmem[...]
        g = jnp.dot(x, gbuf[slot], preferred_element_type=jnp.float32)
        u = jnp.dot(x, ubuf[slot], preferred_element_type=jnp.float32)
        a = (jax.nn.silu(g) * u).astype(dtype)
        part = jnp.dot(a, dbuf[slot], preferred_element_type=jnp.float32)

        def give_back(out):
            c = out.astype(dtype).astype(jnp.float32) * c_ref[...]
            for term in _exact_terms(c, dtype):
                y_vmem[...] += jax.lax.dot_general(
                    hot, term, (((0,), (0,)), ((), ())), precision=exact,
                    preferred_element_type=jnp.float32)

        if n_f == 1:
            give_back(part)
            return

        @pl.when(n == 0)
        def _first():
            acc_ref[...] = part

        @pl.when((n > 0) & (n < n_f - 1))
        def _more():
            acc_ref[...] += part

        @pl.when(n == n_f - 1)
        def _last():
            give_back(acc_ref[...] + part)

    @pl.when(q == pl.num_programs(0) - 1)
    def _epilogue():
        pltpu.sync_copy(y_vmem, y_hbm)


def _call(meta, wg, wu, wd, x, src, c, *, tm, tf, interpret):
    """The kernel over the rows ``x`` ``[T, h]`` as laid out by
    :func:`grouped_swiglu`: ``src`` and ``c`` ``[tiles * tm, 1]`` the row
    and the weight of each place of the tiles (0 and 0.0 where a group's
    last tile has no pick); ``meta``: the live tiles, the blocks of the
    weight stream, then each tile's expert (its index in the banks) and,
    where an expert's whole width is one block, each tile's place in the
    stream and each block's expert. Returns ``y`` ``[T, h]`` float32."""
    held, h, f = wg.shape
    n_f, tiles, T = f // tf, src.shape[0] // tm, x.shape[0]

    def rows(q, meta):
        return jnp.minimum(q // n_f, jnp.maximum(meta[0] - 1, 0)), 0

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(tiles * n_f,),
        in_specs=[hbm, hbm, hbm, hbm, pl.BlockSpec((tm, 1), rows),
                  pl.BlockSpec((tm, 1), rows)],
        out_specs=hbm,
        scratch_shapes=[pltpu.VMEM((_DEPTH, h, tf), wg.dtype),
                        pltpu.VMEM((_DEPTH, h, tf), wu.dtype),
                        pltpu.VMEM((_DEPTH, tf, h), wd.dtype),
                        pltpu.VMEM((T, h), x.dtype),
                        pltpu.VMEM((T, h), jnp.float32),
                        pltpu.SemaphoreType.DMA((3, _DEPTH))]
        + ([pltpu.VMEM((tm, h), x.dtype), pltpu.VMEM((tm, h), jnp.float32)]
           if n_f > 1 else []))
    return pl.pallas_call(
        functools.partial(_kernel, tiles=tiles, n_f=n_f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        name="grouped_swiglu", interpret=interpret,
    )(meta, wg, wu, wd, x, src, c)


def grouped_swiglu(x, local, w, wg, wu, wd, *, interpret=False):
    """``y[t] = sum_j w[t, j] * SwiGLU_{local[t, j]}(x[t])`` over the picks
    that are here, in float32, returned in ``x``'s type.

    ``x`` ``[T, h]``; ``local`` ``[T, k]`` int32, each row's picks as
    indices into the banks ``wg``, ``wu`` ``[held, h, f]`` and ``wd``
    ``[held, f, h]``, ``held`` for a pick that is not computed here; ``w``
    ``[T, k]`` float32 the picks' weights. A row's picks name distinct
    experts (they are a top-k), so no expert takes more than ``T`` rows.
    The shapes must tile (:func:`blocks` is not ``None``). ``interpret``
    (tests) runs the kernel through the interpreter."""
    T, h = x.shape
    held, _, f = wg.shape
    k = local.shape[1]
    tm, tf = blocks(T, h, f, x.dtype.itemsize)
    n_f = f // tf
    picks = T * min(k, held)
    tiles = min(pl.cdiv(picks, tm) + min(held, picks), held * pl.cdiv(T, tm))
    rows = tiles * tm

    # a row picks an expert at most once: its place in the expert's group
    # is the number of rows before it that picked the expert
    picked = jnp.any(local[:, :, None] == jnp.arange(held), axis=1)
    count = jnp.sum(picked, axis=0, dtype=jnp.int32)
    before = jnp.cumsum(picked, axis=0, dtype=jnp.int32) - picked
    need = (count + tm - 1) // tm
    first_tile = jnp.cumsum(need) - need
    expert = jnp.minimum(local, held - 1)
    dest = jnp.where(local < held, first_tile[expert] * tm
                     + jnp.take_along_axis(before, expert, axis=1),
                     rows).reshape(-1)
    src = jnp.zeros(rows, jnp.int32).at[dest].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), k), mode="drop")
    c = jnp.zeros(rows, jnp.float32).at[dest].set(w.reshape(-1),
                                                  mode="drop")

    hit = count > 0
    rank = jnp.cumsum(hit, dtype=jnp.int32) - 1        # among hit experts
    ids = jnp.zeros(held, jnp.int32).at[jnp.where(hit, rank, held)].set(
        jnp.arange(held, dtype=jnp.int32), mode="drop")
    tile_expert = jnp.minimum(jnp.sum(
        first_tile + need <= jnp.arange(tiles)[:, None], axis=1,
        dtype=jnp.int32), held - 1)
    live = jnp.sum(need, dtype=jnp.int32)
    stream = jnp.sum(hit, dtype=jnp.int32) if n_f == 1 else live * n_f
    meta = jnp.concatenate([jnp.stack([live, stream]), tile_expert,
                            rank[tile_expert], ids]).astype(jnp.int32)
    y = _call(meta, wg, wu, wd, x, src[:, None], c[:, None], tm=tm, tf=tf,
              interpret=interpret)
    return y.astype(x.dtype)
