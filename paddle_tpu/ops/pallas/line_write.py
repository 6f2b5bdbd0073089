"""Decode's write of one line a row into folded KV pools, in place.

A folded pool ``[blocks, bs * n, w]`` holds a block's ``bs`` lines of ``n``
KV pairs as one run of rows (``text/sambay.py`` says why it is held so);
decode writes one line a row of the batch: ``n`` rows at row ``dest * n``
of the flat ``[blocks * bs * n, w]`` view, ``dest`` being the line's place
(``block * bs + line in the block``).

Those ``n`` rows (10 at the hybrid cell's sizes) fill no whole tile (16
rows of bf16) and start inside one, and a copy moves whole tiles only. So
the kernel copies the ``span`` rows that hold a line (two tiles, inside
the line's own block) into VMEM, splices the line in at its offset, which
is one of a few known at trace time, and copies them back. Every read is
in flight before the first is waited on, and so is every write; a call
writes the lines of several pools (K and V) at once.

A row's tiles are read and written whole, so two rows that write lines of
one block would each put back the other's tiles as they found them. The
decode program never has them do so where it reads: a slot owns its
blocks (and its rings'), and every row that has no line to write writes
into a trash block (block 0 of the pool, of each window layer's rings),
whose bytes no one reads.

On one TPU v5e (``tools/time_pool_writes.py`` at the hybrid cell's 64
slots: a decode step's 18 writes as 9 calls of K and V) that takes
0.21 ms, against 0.91 ms for :func:`plain` and 4.45 ms for a scatter of
one ``[n, w]`` window a line, which the compiler makes a ``while`` loop of
one iteration a line.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["write_lines", "plain"]


def plain(pools, dest, lines):
    """The XLA form: each line as its ``n`` rows of the flat pool, a row an
    index of one scatter. The kernel's reference, and what runs off a
    TPU."""
    out = []
    for pool, new in zip(pools, lines):
        nb, rows, w = pool.shape
        n = new.shape[1]
        at = (dest * n)[:, None] + jnp.arange(n)
        out.append(pool.reshape(nb * rows, w).at[at.reshape(-1)].set(
            new.reshape(-1, w), mode="promise_in_bounds").reshape(pool.shape))
    return tuple(out)


def _tile(dtype):
    """Rows of one tile: 8 sublanes of 32 bits."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _geometry(shape, n, dtype):
    """``(tile, span, offsets)``: the rows of a tile, the rows copied for a
    line, and every offset of a line in them; None where a block fills no
    whole tiles or a line fits no span."""
    _, rows, w = shape
    tile = _tile(dtype)
    if w % 128 or rows % tile or rows % n:
        return None
    span = min(2 * tile, rows)
    offsets = sorted({n * i - min(n * i // tile * tile, rows - span)
                      for i in range(rows // n)})
    if offsets[-1] + n > span:
        return None
    return tile, span, offsets


def _kernel(dest_ref, *refs, n_pools, n, rows, tile, span, offsets):
    lines = refs[:n_pools]
    pools_out = refs[2 * n_pools:3 * n_pools]
    buf, sem = refs[3 * n_pools:]
    n_rows = lines[0].shape[0]

    def place(j):
        first = dest_ref[j] * n
        top = jnp.minimum(first // tile * tile,
                          first // rows * rows + rows - span)
        return first - top, pl.multiple_of(top, tile)

    def read(p, j):
        return pltpu.make_async_copy(
            pools_out[p].at[pl.ds(place(j)[1], span)], buf.at[p, j],
            sem.at[0])

    def write(p, j):
        return pltpu.make_async_copy(
            buf.at[p, j], pools_out[p].at[pl.ds(place(j)[1], span)],
            sem.at[1])

    def each(step):
        @pl.loop(0, n_rows)
        def _(j):
            for p in range(n_pools):
                step(p, j)

    each(lambda p, j: read(p, j).start())
    each(lambda p, j: read(p, j).wait())

    def splice(p, j):
        at = place(j)[0]
        for c in offsets:
            @pl.when(at == c)
            def _():
                buf[p, j, c:c + n, :] = lines[p][j]
        write(p, j).start()

    each(splice)
    each(lambda p, j: write(p, j).wait())


def _write_tiles(pools, dest, lines, *, interpret=False):
    nb, rows, w = pools[0].shape
    n_rows, n, _ = lines[0].shape
    tile, span, offsets = _geometry(pools[0].shape, n, pools[0].dtype)
    k = len(pools)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, n_pools=k, n=n, rows=rows, tile=tile,
                          span=span, offsets=tuple(offsets)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(),
            in_specs=[vmem] * k + [hbm] * k, out_specs=[hbm] * k,
            scratch_shapes=[pltpu.VMEM((k, n_rows, span, w), pools[0].dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((nb * rows, w), p.dtype)
                   for p in pools],
        input_output_aliases={1 + k + p: p for p in range(k)},
        interpret=interpret, name="write_lines",
    )(dest, *lines, *(p.reshape(nb * rows, w) for p in pools))
    return tuple(o.reshape(nb, rows, w) for o in out)


def write_lines(pools, dest, lines, *, interpret=False):
    """``lines[p]`` ``[R, n, w]`` into ``pools[p]`` ``[nb, bs * n, w]``
    (folded, all of one shape), row ``j``'s line at line place
    ``dest[j]``; the pools with the lines written. At most one row writes
    a line of any block whose bytes are read (see the module's
    docstring).

    The platform decides what runs: on a TPU the kernel, wherever a block
    fills whole tiles; anywhere else :func:`plain`. ``interpret=True``
    (tests) runs the kernel through the interpreter on whatever platform
    there is."""
    pools, lines = tuple(pools), tuple(lines)
    dest = dest.astype(jnp.int32)
    if interpret:
        return _write_tiles(pools, dest, lines, interpret=True)
    if _geometry(pools[0].shape, lines[0].shape[1], pools[0].dtype) is None:
        return plain(pools, dest, lines)
    return jax.lax.platform_dependent(
        pools, dest, lines, tpu=_write_tiles, default=plain)
