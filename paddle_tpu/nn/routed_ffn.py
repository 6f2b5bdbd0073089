"""Routed feed-forward without a capacity: every pick is computed.

``nn/moe.py`` bounds each expert by a capacity and drops what overflows
(the GShard form its training path wants: static ``[E, C]`` shapes).
Serving a published top-k model may drop nothing, so this is the other
form: softmax over the experts in float32, the ``k`` largest,
renormalised, then every expert applied to every row and each row's
result the weighted sum over its picks (weight zero elsewhere). The
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


def route(m, wr, k):
    """``(experts [T, k] int32, weights [T, k] float32)`` of rows ``m``
    ``[T, h]`` under the router ``wr`` ``[h, E]``: the ``k`` largest of a
    float32 softmax over all ``E``, renormalised to sum to 1."""
    g = jax.nn.softmax(jnp.dot(m, wr, preferred_element_type=jnp.float32),
                       axis=-1)
    vals, experts = jax.lax.top_k(g, k)
    return experts.astype(jnp.int32), vals / jnp.sum(vals, -1, keepdims=True)


def routed_ffn(m, wr, wg, wu, wd, k, valid=None):
    """``sum_e c_e * (silu(m Wg_e) * (m Wu_e)) Wd_e`` over each row's ``k``
    routed experts. ``m`` ``[T, h]``; ``wr`` ``[h, E]``; the banks ``wg``,
    ``wu`` ``[E, h, f]`` and ``wd`` ``[E, f, h]``. ``valid`` ``[T]`` bool
    marks the rows that are tokens (a decode step's inactive slots and a
    bucket's padding are not): the others touch no expert's count and
    come back as zero rows. Every pick is computed, whatever the load of
    its expert.

    Returns ``(y [T, h], picks [E] int32)``, ``picks`` the rows that
    picked each expert."""
    experts, weights = route(m, wr, k)
    T, E = m.shape[0], wg.shape[0]
    c = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], experts].set(weights)
    if valid is not None:
        c = jnp.where(valid[:, None], c, 0.0)
    act = jax.nn.silu(jnp.einsum("th,ehf->etf", m, wg)) \
        * jnp.einsum("th,ehf->etf", m, wu)
    out = jnp.einsum("etf,efh->eth", act, wd)                    # [E, T, h]
    y = jnp.einsum("te,eth->th", c, out.astype(jnp.float32))
    return y.astype(m.dtype), jnp.sum(c > 0, axis=0, dtype=jnp.int32)
