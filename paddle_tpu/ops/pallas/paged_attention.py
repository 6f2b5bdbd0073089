"""Paged decode attention: one query token a slot against the live blocks
of a block-paged KV pool, read in place.

The serving engine's decode program advances every slot one token against
the shared pool. The plain form (:func:`gathered`) builds each slot's
contiguous ``[max_len, kv, hd]`` view through its block table and scores
all of it; at serving lengths that view is the step's HBM bill, for keys
of which a fraction exist. The kernel walks only the blocks that can hold
a key the slot's token may see: from the block of its window's first
position (block 0 without a window) to the block of ``write_pos``. Table
entries outside that range are neither fetched nor computed; a slot whose
``write_pos`` is negative sees nothing, reads nothing and returns zeros.

One grid step a slot. The block table, the positions and the window are
scalar-prefetch operands; the pool stays in HBM and each block is copied
whole, ``[bs * n_kv, hd]`` contiguous bytes, into one of two VMEM buffers
of several blocks (``_blocks_a_step``), the next buffer filling while
this one is folded into a float32 online softmax. Any head grouping runs
the same code: the ``H`` query rows are multiplied against every line of
every KV head in the buffer at once (the MXU streams a K row once
whichever head it belongs to) and a row keeps the columns of its own KV
head, ``h // (H // n_kv)``, by mask.

Numerics: bf16 (or float32) ``q``, K and V into the MXU; scores, running
maximum, denominators and the accumulator in float32. The probabilities
go to the MXU as two bf16 terms, ``p = hi + lo``, so the products
``p . V`` carry sixteen bits of ``p`` instead of the eight a single
rounding against the running maximum would leave; one rounding, of the
output. The kernel is bound by its copies, so the second pass is free.

A window is data (an int32 operand, 0 for none): a model of layer kinds
compiles one kernel for both kinds wherever the shapes agree.

A latent cache has one pool and no V: a line is the latent beside the
shared rotary key, its values the first ``value_dim`` numbers of the same
line. The walk is the same with one buffer, each line read once and used
twice, and the scores times the model's own ``scale``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "gathered"]

_MASKED = -1e30
#: bytes of K (and as many of V) one step of the walk copies: a buffer of
#: each is held twice. Half a MiB is 4 blocks of 32 KV heads x 16 lines x
#: 128 and 32 blocks of 4 heads
_STEP_BYTES = 512 * 1024


def _blocks_a_step(rows, hd, itemsize, mb):
    """Blocks of ``rows`` lines-by-heads that one step of the walk copies
    and scores together: as many as ``_STEP_BYTES`` hold, at most the
    table's width."""
    return max(1, min(mb, _STEP_BYTES // (rows * hd * itemsize)))


def _tiles(pool_shape, dtype):
    """True where the pool's blocks can be copied and sliced under the
    TPU's (8, 128) rule: a block's ``bs * n_kv`` rows fill whole sublane
    tiles of the pool's type, and a row is whole lanes."""
    _, bs, n_kv, hd = pool_shape
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return (bs * n_kv) % sublanes == 0 and hd % 128 == 0


def _kernel(tables, pos, win, q_ref, *refs, nbk, n_kv, bs, mb, scale, vd):
    if vd is None:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems = refs
        pools = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))
    else:                    # one pool: a line's values are its first vd
        k_hbm, o_ref, kbuf, sems = refs
        pools = ((k_hbm, kbuf, 0),)
    s = pl.program_id(0)
    H, hd = q_ref.shape
    rows, group = bs * n_kv, H // n_kv
    C = nbk * rows

    @pl.when(s == 0)
    def _clean():
        # lines of a buffer that no copy has reached yet are multiplied by
        # a zero weight below: they have to be numbers
        for _, buf, _ in pools:
            buf[...] = jnp.zeros_like(buf)

    wp = pos[s]
    w = win[0]
    seen = jnp.minimum(jnp.maximum(wp, 0), mb * bs - 1)
    first_pos = jnp.where(w > 0, jnp.maximum(seen - w + 1, 0), 0)
    first = first_pos // bs
    last = seen // bs
    n_chunks = jnp.where(wp >= 0, (last - first) // nbk + 1, 0)

    def copies(c, slot, go):
        """Start (or wait for) the copies of chunk ``c``'s live blocks."""
        b0 = first + c * nbk

        def one(i, _):
            blk = tables[s, b0 + i]
            dst = pl.ds(pl.multiple_of(i * rows, rows), rows)
            for hbm, buf, which in pools:
                cp = pltpu.make_async_copy(hbm.at[blk], buf.at[slot, dst],
                                           sems.at[which, slot])
                cp.start() if go else cp.wait()
            return 0

        jax.lax.fori_loop(0, jnp.minimum(nbk, last - b0 + 1), one, 0)

    @pl.when(n_chunks > 0)
    def _first():
        copies(0, 0, True)

    q = q_ref[...]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    col = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    line, head = col // n_kv, col % n_kv
    mine = head == jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // group

    def chunk(c, carry):
        top, total, acc = carry
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            copies(c + 1, 1 - slot, True)

        copies(c, slot, False)
        k = kbuf[slot]
        v = vbuf[slot] if vd is None else k[:, :vd]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        at = (first + c * nbk) * bs + line
        ok = mine & (at <= wp) & (at >= first_pos)
        sc = jnp.where(ok, sc, _MASKED)
        top2 = jnp.maximum(top, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - top2), 0.0)
        shrink = jnp.exp(top - top2)
        if v.dtype == jnp.float32:
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
        else:
            hi = p.astype(v.dtype)
            lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
            pv = jnp.dot(hi, v, preferred_element_type=jnp.float32) \
                + jnp.dot(lo, v, preferred_element_type=jnp.float32)
        return (top2, total * shrink + jnp.sum(p, axis=1, keepdims=True),
                acc * shrink + pv)

    start = (jnp.full((H, 1), _MASKED, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, hd if vd is None else vd), jnp.float32))
    _, total, acc = jax.lax.fori_loop(0, n_chunks, chunk, start)
    o_ref[...] = (acc / jnp.where(total > 0.0, total, 1.0)).astype(
        o_ref.dtype)


def _walk(q, kc_pool, vc_pool, tables, write_pos, window, *, scale=None,
          value_dim=None, interpret=False):
    S, H, hd = q.shape
    nb, bs, n_kv, _ = kc_pool.shape
    mb = tables.shape[1]
    rows = bs * n_kv
    nbk = _blocks_a_step(rows, hd, kc_pool.dtype.itemsize, mb)
    kernel = functools.partial(_kernel, nbk=nbk, n_kv=n_kv, bs=bs, mb=mb,
                               scale=scale, vd=value_dim)
    # a block's lines and heads are one run of bytes in the pool as the
    # scatters write it: [nb, bs, kv, hd] read as [nb, bs * kv, hd]
    pools = [p.reshape(nb, rows, hd) for p in (kc_pool, vc_pool)
             if p is not None]
    row = pl.BlockSpec((None, H, hd), lambda s, *_: (s, 0, 0))
    out = row if value_dim is None else pl.BlockSpec(
        (None, H, value_dim), lambda s, *_: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(S,),
        in_specs=[row] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=out,
        scratch_shapes=[pltpu.VMEM((2, nbk * rows, hd), p.dtype)
                        for p in pools]
        + [pltpu.SemaphoreType.DMA((2, 2))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, value_dim or hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention" if value_dim is None
        else "paged_latent_attention", interpret=interpret,
    )(tables.astype(jnp.int32), write_pos.astype(jnp.int32),
      jnp.asarray(window, jnp.int32).reshape(1), q, *pools)


def gathered(q, kc_pool, vc_pool, tables, write_pos, window=0, *,
             scale=None, value_dim=None):
    """The plain form, and the kernel's parity oracle: every slot's view
    gathered through its whole table row (``generation._paged_view``),
    masked by position and scored as every other one-token attention of
    the program is (``generation._attend_rows``: float32 scores, the
    probabilities rounded to the pool's type before they meet V). A row
    with nothing to see returns the mean of its view (finite; nobody
    reads it)."""
    from ...text.generation import _attend_rows, _paged_view

    bs = kc_pool.shape[1]
    kview = _paged_view(kc_pool, tables, bs)
    vview = kview[..., :value_dim] if vc_pool is None \
        else _paged_view(vc_pool, tables, bs)
    at = jnp.arange(kview.shape[1])[None, :]
    window = jnp.asarray(window, jnp.int32)
    ok = (at <= write_pos[:, None]) & (
        (window <= 0) | (write_pos[:, None] - at < window))
    return _attend_rows(q, kview, vview, ok, q.dtype, scale)


@functools.lru_cache(maxsize=None)
def _form(f, has_v, scale, value_dim):
    """``f`` (:func:`_walk` or :func:`gathered`) over the operands that are
    arrays: a latent cache has no V pool. One function object a form, so
    that jax traces a branch once for all the layers that call it alike."""
    def call(q, kc_pool, *rest):
        *v, tables, write_pos, window = rest
        return f(q, kc_pool, v[0] if has_v else None, tables, write_pos,
                 window, scale=scale, value_dim=value_dim)
    return call


def paged_attention(q, kc_pool, vc_pool, tables, write_pos, window=0, *,
                    scale=None, value_dim=None, interpret=False):
    """One-token attention over a paged pool: ``q`` ``[S, H, hd]``
    against ``kc_pool`` / ``vc_pool`` ``[n_blocks, block_size, n_kv, hd]``
    through the slots' block tables ``[S, max_blocks]``. Slot ``s`` sees
    the positions ``<= write_pos[s]`` (none where that is negative) and,
    where ``window`` (an int32 scalar, traced or not) is positive, only
    the last ``window`` of them. Returns ``[S, H, hd]`` in ``q``'s type.
    The scores are over ``sqrt(hd)``, or times ``scale`` where the model
    states its own. A latent cache passes ``vc_pool`` None and
    ``value_dim``: a line's values are its first ``value_dim`` numbers,
    and ``[S, H, value_dim]`` comes back.

    The platform decides what runs: on a TPU the kernel, wherever a
    block of the pool fills whole tiles; anywhere else :func:`gathered`.
    ``interpret=True`` (tests) runs the kernel through the interpreter on
    whatever platform there is."""
    if q.shape[1] % kc_pool.shape[2]:
        raise ValueError(f"{q.shape[1]} query heads over "
                         f"{kc_pool.shape[2]} KV heads")
    if (vc_pool is None) != (value_dim is not None):
        raise ValueError("value_dim is given for a pool without V, and "
                         "for no other")
    args = (q, kc_pool, vc_pool, tables, write_pos, window)
    how = {"scale": scale, "value_dim": value_dim}
    if interpret:
        return _walk(*args, interpret=True, **how)
    if not _tiles(kc_pool.shape, kc_pool.dtype) or (value_dim or 0) % 128:
        return gathered(*args, **how)

    form = functools.partial(_form, has_v=vc_pool is not None, **how)
    return jax.lax.platform_dependent(
        *(a for a in args if a is not None),
        tpu=form(_walk), default=form(gathered))
