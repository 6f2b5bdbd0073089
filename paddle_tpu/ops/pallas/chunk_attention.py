"""Chunk attention over cached latents: the rows of one prefill chunk
against a tile of cached lines, folded into a running softmax without the
scores, or the lines' expansion, leaving the chip.

The serving engine's chunk program scores ``C`` fresh rows of ``H`` heads
against the slot's cached prefix a tile of ``T`` lines at a time. A cached
line is the latent ``c`` beside the one rotary key all heads share;
``kv_b_proj`` expands ``c`` to a head's keys and values. The plain form
(:func:`plain`) writes the expansion ``[T, H, dn + dv]`` and the float32
scores ``[H, C, T]`` to HBM, reads the scores back masked for their
maximum, again for the exponentials, and once more as the probabilities
that meet V: at 64 heads x 512 rows x 2048 lines (268 MB) those crossings
are most of what a tile costs. The kernel expands a block of ``tk`` lines
for one head in VMEM, and holds the ``[C, tk]`` block of that head's scores
there from the product that makes it to the product that consumes it; what
crosses HBM is the lines, the weights and the running ``(top, total,
acc)``.

One call folds one tile. The caller walks the tiles (and stops at the tile
of the chunk's last position); inside a tile the grid is heads by blocks
of ``tk`` lines, and a block past the chunk's last position is neither
fetched, expanded nor scored. A block that every row sees whole (all of
the cached prefix but the chunk's own lines) skips the mask.

``dn`` (a head's keys), ``dr`` (the shared key), ``dv`` (its values) and
the latent's rank are what the shapes say: the score is ``q . k_h +
q_shared . k_shared``, 192 wide against values of 128 in the model this
was written for.

Numerics are the plain form's: the operands go to the MXU as they are
(bf16), the expanded keys and values are rounded to the lines' type as an
array of them would be, scores, running maximum, denominators and the
accumulator are float32, the mask is ``line <= gpos[row]`` exactly, and
the probabilities are rounded to the values' type once, before they meet
V.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunk_attention", "plain", "start"]

_MASKED = -1e30
#: VMEM one grid step may plan: the operands' blocks twice (the next is
#: copied while this one is computed), the running state in and out, and
#: a head's expansion and scores in the few forms the softmax holds at
#: once. A v5e has 128 MiB; the compiler's own default is 16
_VMEM_BYTES = 64 * 1024 * 1024
_LINE_BLOCKS = (512, 256, 128)


def _line_block(T):
    """Lines a block: the largest of ``_LINE_BLOCKS`` that divides the
    tile, the tile itself where none does."""
    return next((b for b in _LINE_BLOCKS if T % b == 0), T)


def _blocks(H, C, dn, dr, dv, T, width, rank, itemsize):
    """``(hb, tk)``: heads a grid step and lines a block; ``hb`` the most
    heads, of 8, 4, 2, 1, that divide ``H`` and whose blocks fit
    ``_VMEM_BYTES``."""
    tk = _line_block(T)

    def need(hb):
        pad = lambda n: -(-n // 128) * 128                    # whole lanes
        operands = (tk * pad(width) + rank * hb * (dn + dv)
                    + hb * C * (pad(dn) + pad(dr))) * itemsize
        state = (hb * C * pad(dv) + 2 * C * 128) * 4
        return 2 * operands + 4 * state + 4 * tk * (dn + dv) \
            + 6 * C * tk * 4

    return next((hb for hb in (8, 4, 2) if H % hb == 0
                 and need(hb) <= _VMEM_BYTES), 1), tk


def _tiles(q, lines, w, dv):
    """True where the blocks obey the TPU's (8, 128) rule: the latent, a
    head's keys and its values are whole lanes, the rows whole sublane
    tiles, a block of lines whole lanes of the scores."""
    _, C, dn = q.shape
    sublanes = 8 * 4 // jnp.dtype(q.dtype).itemsize
    return (w.shape[0] % 128 == 0 and dn % 128 == 0 and dv % 128 == 0
            and C % sublanes == 0 and _line_block(lines.shape[0]) % 128 == 0)


def _kernel(at, q_ref, qs_ref, lines_ref, w_ref, gpos_ref, top_in, total_in,
            acc_in, top_ref, total_ref, acc_ref, *, hb, tk, dn, dv, scale):
    j = pl.program_id(1)
    first, lo, hi = at[0], at[1], at[2]
    base = first + j * tk
    rank, dr = w_ref.shape[0], qs_ref.shape[-1]

    @pl.when(j == 0)
    def _take():
        top_ref[...] = top_in[...]
        total_ref[...] = total_in[...]
        acc_ref[...] = acc_in[...]

    def fold(masked):
        c = lines_ref[:, :rank]
        ks = lines_ref[:, rank:rank + dr]
        if masked:
            line = base + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
            ok = line <= gpos_ref[...]                          # [C, tk]
        for i in range(hb):
            kv = jnp.dot(c, w_ref[:, i * (dn + dv):(i + 1) * (dn + dv)],
                         preferred_element_type=jnp.float32).astype(c.dtype)
            s = (jax.lax.dot_general(q_ref[i], kv[:, :dn],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qs_ref[i], ks,
                                       (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
                 ) * scale
            top = top_ref[:, i:i + 1]
            if masked:
                top2 = jnp.maximum(top, jnp.max(
                    jnp.where(ok, s, _MASKED), axis=1, keepdims=True))
                p = jnp.where(ok, jnp.exp(s - top2), 0.0)
            else:
                top2 = jnp.maximum(top, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - top2)
            shrink = jnp.exp(top - top2)
            acc_ref[i] = acc_ref[i] * shrink + jnp.dot(
                p.astype(c.dtype), kv[:, dn:],
                preferred_element_type=jnp.float32)
            total_ref[:, i:i + 1] = total_ref[:, i:i + 1] * shrink \
                + jnp.sum(p, axis=1, keepdims=True)
            top_ref[:, i:i + 1] = top2

    live = base <= hi                     # some row sees the block's first
    whole = base + tk - 1 <= lo           # every row sees its last

    @pl.when(live & whole)
    def _seen_whole():
        fold(False)

    @pl.when(live & jnp.logical_not(whole))
    def _seen_in_part():
        fold(True)


def _fold(q, q_shared, lines, w, gpos, first, top, total, acc, *, scale,
          interpret=False):
    H, C, dn = q.shape
    T, width = lines.shape
    rank, dr, dv = w.shape[0], q_shared.shape[-1], acc.shape[-1]
    hb, tk = _blocks(H, C, dn, dr, dv, T, width, rank, q.dtype.itemsize)
    G, J = H // hb, T // tk
    gpos = gpos.astype(jnp.int32)
    at = jnp.stack([jnp.asarray(first, jnp.int32), jnp.min(gpos),
                    jnp.max(gpos)])

    def block(g, j, at):
        """Block ``j`` of the tile, or the last one a row can see: a block
        past it is skipped, and asking for the same again copies
        nothing."""
        return jnp.minimum(j, jnp.clip((at[2] - at[0]) // tk, 0, J - 1)), 0

    # a head's statistics are a column beside its scores: [G, C, hb], the
    # rows down the sublanes
    def column(a):
        return jnp.swapaxes(a.reshape(G, hb, C), 1, 2)

    heads = lambda d: pl.BlockSpec((hb, C, d), lambda g, j, at: (g, 0, 0))
    stat = pl.BlockSpec((None, C, hb), lambda g, j, at: (g, 0, 0))
    state = [stat, stat, heads(dv)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(G, J),
        in_specs=[heads(dn), heads(dr), pl.BlockSpec((tk, width), block),
                  pl.BlockSpec((rank, hb * (dn + dv)),
                               lambda g, j, at: (0, g)),
                  pl.BlockSpec((C, 1), lambda g, j, at: (0, 0))] + state,
        out_specs=state)
    top, total, acc = pl.pallas_call(
        functools.partial(_kernel, hb=hb, tk=tk, dn=dn, dv=dv, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((G, C, hb), jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((H, C, dv), jnp.float32)],
        input_output_aliases={6: 0, 7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="chunk_attention", interpret=interpret,
    )(at, q, q_shared, lines, w.reshape(rank, H * (dn + dv)), gpos[:, None],
      column(top), column(total), acc)
    row = lambda a: jnp.swapaxes(a, 1, 2).reshape(H, C)
    return row(top), row(total), acc


def plain(q, q_shared, lines, w, gpos, first, top, total, acc, *, scale):
    """The plain form, and the kernel's parity oracle: the same fold as
    XLA's fusions, the expansion ``[T, H, dn + dv]`` and the scores ``[H,
    C, T]`` arrays."""
    dn, rank, dr = q.shape[-1], w.shape[0], q_shared.shape[-1]
    kv = jnp.einsum("kr,rhd->khd", lines[:, :rank], w)
    s = (jnp.einsum("hqd,khd->hqk", q, kv[..., :dn],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("hqd,kd->hqk", q_shared, lines[:, rank:rank + dr],
                      preferred_element_type=jnp.float32)) \
        * jnp.float32(scale)
    ok = (first + jnp.arange(lines.shape[0]))[None, :] <= gpos[:, None]
    # the mask is applied where the scores are read, twice, not to a copy
    # of them: a twentieth off the softmax fusion (0.104 -> 0.098 s of a
    # traced window, my chip runs, PR 32)
    top2 = jnp.maximum(top, jnp.max(jnp.where(ok, s, _MASKED), axis=-1))
    p = jnp.where(ok, jnp.exp(s - top2[..., None]), 0.0)
    shrink = jnp.exp(top - top2)
    acc = acc * shrink[..., None] + jnp.einsum(
        "hqk,khd->hqd", p.astype(kv.dtype), kv[..., dn:],
        preferred_element_type=jnp.float32)
    return top2, total * shrink + jnp.sum(p, axis=-1), acc


def start(H, C, dv):
    """The running state before any tile: ``(top, total, acc)``, nothing
    seen."""
    return (jnp.full((H, C), _MASKED, jnp.float32),
            jnp.zeros((H, C), jnp.float32),
            jnp.zeros((H, C, dv), jnp.float32))


@functools.lru_cache(maxsize=None)
def _form(f, scale):
    """``f`` (:func:`_fold` or :func:`plain`) at one ``scale``: one
    function object a form, so that jax traces a branch once for all the
    layers that call it alike."""
    return functools.partial(f, scale=scale)


def chunk_attention(q, q_shared, lines, w, gpos, first, carry, *, scale,
                    interpret=False):
    """One tile of cached lines folded into a chunk's running softmax.

    ``q`` ``[H, C, dn]`` and ``q_shared`` ``[H, C, dr]`` are the chunk's
    queries at positions ``gpos`` ``[C]``; ``lines`` ``[T, width]`` the
    tile's cached lines as they lie in the pool, the latent (``rank``
    numbers), then the key that all heads share (``dr``), then padding;
    ``w`` ``[rank, H, dn + dv]`` expands a latent to a head's keys beside
    its values. The tile's lines are ``first .. first + T - 1`` and row
    ``i`` sees those ``<= gpos[i]``. ``carry`` is the running ``(top [H,
    C], total [H, C], acc [H, C, dv])``, float32, from :func:`start` or
    the tile before; the new one comes back, and ``acc / total`` after the
    last tile is the attention's output.

    The platform decides what runs: on a TPU the kernel, wherever the
    blocks fill whole tiles; anywhere else :func:`plain`.
    ``interpret=True`` (tests) runs the kernel through the interpreter on
    whatever platform there is."""
    args = (q, q_shared, lines, w, gpos, first, *carry)
    if interpret:
        return _fold(*args, scale=scale, interpret=True)
    if not _tiles(q, lines, w, carry[2].shape[-1]):
        return plain(*args, scale=scale)
    return jax.lax.platform_dependent(
        *args, tpu=_form(_fold, scale), default=_form(plain, scale))
