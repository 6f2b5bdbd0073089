"""Routed feed-forward without a capacity: every pick is computed.

``nn/moe.py`` bounds each expert by a capacity and drops what overflows
(the GShard form its training path wants: static ``[E, C]`` shapes).
Serving a published top-k model may drop nothing, so this is the other
form: scores over the experts in float32 (a softmax, or a sigmoid with a
selection bias and a scale), the ``k`` largest, renormalised, then every
expert held here applied to every row and each row's result the weighted
sum over its picks (weight zero elsewhere). The banks may be a share of
the layer's experts (``routed_ffn``'s ``first``): the router still scores
all of them. The
shapes depend on the rows and the banks alone: no factor pads anything,
and a load that sends every token to one expert is computed like any
other.

Why all experts, and not the picks grouped by expert: an expert's three
matrices take 3 h f 2 B / 819 GB/s to read and ``rows`` x 6 h f / 197
TFLOP/s to multiply, so under ~240 rows (a v5e's ridge) the read is the
longer and rows an expert was not picked for cost no time. Measured at
64 experts of 2304 x 896, top 8 (my chip run, PR 27), the whole function
at 16 / 128 / 512 rows: 1.13 / 1.11 / 2.51 ms (a decode step's layers
read their banks at 737 GB/s; at 512 rows the products run at 162
TFLOP/s), against 3.77 / 5.50 / 6.16 ms with the picks sorted by expert
through ``jax.lax.ragged_dot`` (the chip's grouped-matmul kernel: 1.26
ms a call at 128 picks, 1.96 ms at 4096, 4 % of the peak). No serving
program passes more rows than a prefill chunk (512 in the benchmark's
cell). Beyond the ridge this form multiplies ``E / k`` times the picks'
work: a grouped kernel that beats it there is worth writing when a
measured workload sends such batches (``PERF.md`` section 7).

One function serves every program kind: the model's ``forward``, the
engine's prefill, chunk and decode bodies (``text/generation.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route", "routed_ffn"]


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

    Returns ``(y [T, h], picks [held] int32)``, ``picks`` the rows that
    picked each held expert."""
    experts, weights = route(m, wr, k, **router)
    T, E, held = m.shape[0], wr.shape[-1], wg.shape[0]
    c = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], experts].set(weights)
    if held != E:
        c = jax.lax.slice_in_dim(c, first, first + held, axis=1)
    if valid is not None:
        c = jnp.where(valid[:, None], c, 0.0)
    act = jax.nn.silu(jnp.einsum("th,ehf->etf", m, wg)) \
        * jnp.einsum("th,ehf->etf", m, wu)
    out = jnp.einsum("etf,efh->eth", act, wd)                 # [held, T, h]
    y = jnp.einsum("te,eth->th", c, out.astype(jnp.float32))
    return y.astype(m.dtype), jnp.sum(c > 0, axis=0, dtype=jnp.int32)
