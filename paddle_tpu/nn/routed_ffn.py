"""Routed feed-forward without a capacity: every pick is computed.

``nn/moe.py`` bounds each expert by a capacity and drops what overflows
(the GShard form its training path wants: static ``[E, C]`` shapes).
Serving a published top-k model may drop nothing, so this is the other
form: scores over the experts in float32 (a softmax, or a sigmoid with a
selection bias and a scale), the ``k`` largest, renormalised, and each
row's result the weighted sum of its picked experts' SwiGLUs. The banks
may be a share of the layer's experts (``routed_ffn``'s ``first``): the
router still scores all of them. A load that sends every token to one
expert is computed like any other.

Two forms compute it, and the platform chooses: on a TPU :func:`grouped`,
the picks grouped by expert through ``ops/pallas/grouped_swiglu.py``
(each expert some row picked is read once a call, and multiplies only
the rows that picked it); anywhere else, and on a TPU where the shapes do
not tile, :func:`plain`, every held expert applied to every row and the
unpicked weighted zero, which is also the kernel's parity oracle and its
backward.

Why grouped: the whole function at 16 / 128 / 512 rows of the layer's
own input and router, on one TPU v5e (``tools/time_routed_ffn.py``),
in ms:

================================  ====  ====  =====
64 experts of 2304 x 896, top 8    16   128    512
================================  ====  ====  =====
``plain``                          1.13  1.11   2.53
``jax.lax.ragged_dot``             3.08  5.53   6.34
megablox ``gmm``                   5.59  8.91  12.15
``grouped``                        0.79  1.13   1.22
================================  ====  ====  =====

================================  ====  ====  =====
12 held of 384, 7168 x 2048        16   128    512
================================  ====  ====  =====
``plain``                          1.45  1.48   3.42
``jax.lax.ragged_dot``             0.64  2.36   4.66
megablox ``gmm``                   3.58  6.58  12.76
``grouped``                        0.55  0.97   1.81
================================  ====  ====  =====

At 16 rows the first model's rows hit 45 of its 64 experts, which the
kernel reads at 706 GB/s (86 % of the chip's bandwidth) where the plain
form reads all 64; at 512 rows they hit all 64 and the plain form
multiplies eight times the picks' products. At 128 rows they hit all 64
and the products are still under a v5e's ridge (~240 rows), so both
forms read every bank and come within 3 % of each other; the serving
programs pass 16 or 32 rows (decode) and 512 (a chunk), so no row count
selects the plain form. ``gmm`` copies an expert's weights again for
every tile of its rows; ``ragged_dot`` is slower than the kernel at
every row count measured.

One function serves every program kind: the model's ``forward``, the
engine's prefill, chunk and decode bodies (``text/generation.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.pallas import grouped_swiglu as _gs

__all__ = ["grouped", "plain", "route", "routed_ffn"]


def route(m, wr, k, scoring="softmax", bias=None, scale=1.0):
    """``(experts [T, k] int32, weights [T, k] float32)`` of rows ``m``
    ``[T, h]`` under the router ``wr`` ``[h, E]``: float32 scores over all
    ``E`` (``scoring``: ``softmax``, or ``sigmoid``, each expert scored by
    itself), the ``k`` largest, their scores renormalised to sum to 1 and
    multiplied by ``scale``. ``bias`` ``[E]`` is added to the scores for
    the selection alone: it picks, it never weighs."""
    z = jnp.dot(m, wr, preferred_element_type=jnp.float32)
    if scoring == "softmax":
        g = jax.nn.softmax(z, axis=-1)
    elif scoring == "sigmoid":
        g = jax.nn.sigmoid(z)
    else:
        raise ValueError(f"scoring {scoring!r} is not implemented")
    if bias is None:
        # kept as it was written, not folded into the form below: equal
        # values, but another lowered text, and with it other compile-cache
        # keys for every program of a model routed this way
        vals, experts = jax.lax.top_k(g, k)
        weights = vals / jnp.sum(vals, -1, keepdims=True)
    else:
        _, experts = jax.lax.top_k(g + bias.astype(jnp.float32), k)
        vals = jnp.take_along_axis(g, experts, axis=-1)
        weights = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return experts.astype(jnp.int32), weights


def plain(m, local, w, wg, wu, wd):
    """Every held expert applied to every row, each row's result the
    weighted sum over its picks (weight zero elsewhere): the form off a
    TPU, the grouped kernel's parity oracle and its backward. Arguments as
    :func:`grouped`'s."""
    T, held = m.shape[0], wg.shape[0]
    c = jnp.zeros((T, held), jnp.float32).at[
        jnp.arange(T)[:, None], local].set(w, mode="drop")
    act = jax.nn.silu(jnp.einsum("th,ehf->etf", m, wg)) \
        * jnp.einsum("th,ehf->etf", m, wu)
    out = jnp.einsum("etf,efh->eth", act, wd)                 # [held, T, h]
    y = jnp.einsum("te,eth->th", c, out.astype(jnp.float32))
    return y.astype(m.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def grouped(m, local, w, wg, wu, wd, interpret=False):
    """The picks grouped by expert through ``ops/pallas/grouped_swiglu.py``
    (looked up at call time). ``local`` ``[T, k]`` indexes the banks,
    ``held`` where a pick is not computed here; ``w`` ``[T, k]`` float32,
    zero there. Its gradients are :func:`plain`'s."""
    return _gs.grouped_swiglu(m, local, w, wg, wu, wd, interpret=interpret)


def _grouped_fwd(m, local, w, wg, wu, wd, interpret):
    return grouped(m, local, w, wg, wu, wd, interpret), \
        (m, local, w, wg, wu, wd)


def _grouped_bwd(interpret, res, ct):
    m, local, w, wg, wu, wd = res
    _, vjp = jax.vjp(lambda m, w, wg, wu, wd: plain(m, local, w, wg, wu, wd),
                     m, w, wg, wu, wd)
    dm, dw, dg, du, dd = vjp(ct)
    return dm, None, dw, dg, du, dd


grouped.defvjp(_grouped_fwd, _grouped_bwd)


def routed_ffn(m, wr, wg, wu, wd, k, valid=None, first=0, **router):
    """``sum_e c_e * (silu(m Wg_e) * (m Wu_e)) Wd_e`` over those of each
    row's ``k`` routed experts that are held here. ``m`` ``[T, h]``;
    ``wr`` ``[h, E]`` scores all ``E`` experts (``router``: the further
    arguments of :func:`route`); the banks ``wg``, ``wu`` ``[held, h, f]``
    and ``wd`` ``[held, f, h]`` are those of the experts ``first`` to
    ``first + held - 1``, the share a chip holds of a layer divided over
    several (``held == E``, ``first == 0``: all of them). A pick keeps the
    weight it has among all ``k`` of its row; what a row's picks on
    absent experts would add is left out, so the shares of all the chips
    add up to the whole layer. ``valid`` ``[T]`` bool marks the rows that
    are tokens (a decode step's inactive slots and a bucket's padding are
    not): the others touch no expert's count and come back as zero rows.
    Every pick on a held expert is computed, whatever its expert's load.

    The platform decides the form: on a TPU, wherever the banks tile,
    :func:`grouped`; anywhere else :func:`plain`.

    Returns ``(y [T, h], picks [held] int32)``, ``picks`` the rows that
    picked each held expert."""
    experts, weights = route(m, wr, k, **router)
    held = wg.shape[0]
    local = experts - first
    here = (local >= 0) & (local < held) & (weights > 0)
    if valid is not None:
        here = here & valid[:, None]
    local = jnp.where(here, local, held)
    weights = jnp.where(here, weights, 0.0)
    picks = jnp.sum(local[..., None] == jnp.arange(held), axis=(0, 1),
                    dtype=jnp.int32)
    args = (m, local, weights, wg, wu, wd)
    if _gs.blocks(m.shape[0], *wg.shape[1:], m.dtype.itemsize) is None:
        return plain(*args), picks
    return jax.lax.platform_dependent(*args, tpu=grouped,
                                      default=plain), picks
