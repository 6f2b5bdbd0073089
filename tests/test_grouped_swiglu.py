"""The routed feed-forward's grouped kernel (``ops/pallas/grouped_swiglu.py``)
against its plain form, through the interpreter.

On a TPU ``nn.routed_ffn.routed_ffn`` applies each expert to the rows
that picked it (``grouped``); anywhere else every held expert to every
row (``plain``). These tests send ``routed_ffn`` down the TPU's branch
with the kernel interpreted, at the two routed models' shape families
cut to tiny widths (in whole lanes, as the kernel needs), and hold it to
the plain form in float32: outputs, ``picks`` and gradients.
"""
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import routed_ffn as R
from paddle_tpu.ops.pallas import grouped_swiglu as G


def _kernel_path():
    """``routed_ffn`` as it runs on a TPU, the kernel interpreted."""
    return mock.patch.object(jax.lax, "platform_dependent",
                             lambda *a, tpu, default: tpu(*a, True))


# mellum2's family: all experts held, softmax; kimi's: a share held under
# a sigmoid router with a selection bias and a scale, the width in blocks
_MELLUM = dict(h=256, f=128, E=16, held=16, first=0, k=4, router={})
_KIMI = dict(h=256, f=384, E=32, held=8, first=8, k=8,
             router=dict(scoring="sigmoid", scale=2.5), width_block=128)
_CASES = {
    "mellum-decode": dict(_MELLUM, T=16, grad=True),
    "mellum-decode_bf16": dict(_MELLUM, T=16, dtype=jnp.bfloat16),
    "mellum-one_row": dict(_MELLUM, T=1),
    "mellum-chunk": dict(_MELLUM, T=512),
    "mellum-chunk_rows_masked": dict(_MELLUM, T=512, valid=0.6),
    "mellum-one_expert_takes_every_pick": dict(_MELLUM, T=300, favour=3),
    "mellum-experts_with_no_pick": dict(_MELLUM, T=3, k=1),
    "mellum-weight_zero_pick": dict(_MELLUM, T=40, zero_last=True),
    "kimi-decode": dict(_KIMI, T=32, valid=0.8, grad=True),
    "kimi-chunk": dict(_KIMI, T=512),
    "kimi-chunk_bf16": dict(_KIMI, T=512, dtype=jnp.bfloat16),
    "kimi-every_row_to_one_held_expert": dict(_KIMI, T=200, favour=10),
    "kimi-nothing_held_picked": dict(_KIMI, T=4, favour=0, k=1),
}


def _narrow(c):
    """VMEM for weight blocks of ``c["width_block"]`` of the width: the
    published widths' blocks at tiny widths."""
    return G._DEPTH * 3 * c["h"] * c["width_block"] * 4


def _layer(c, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    T, h, f, E, held = c["T"], c["h"], c["f"], c["E"], c["held"]
    m = jax.random.normal(ks[0], (T, h))
    wr = jax.random.normal(ks[1], (h, E)) * 0.2
    router = dict(c["router"], first=c["first"])
    if "favour" in c:
        # a selection bias (or, under a softmax, the router's own column)
        # that gives one expert every row's first pick
        if "scoring" in router:
            router["bias"] = jnp.zeros(E).at[c["favour"]].set(100.0)
        else:
            wr = wr.at[:, c["favour"]].add(5.0)
            m = jnp.abs(m)
    wg, wu = (jax.random.normal(k, (held, h, f)) * 0.05 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (held, f, h)) * 0.05
    valid = jax.random.uniform(ks[5], (T,)) < c["valid"] \
        if "valid" in c else None
    m, wr, wg, wu, wd = (a.astype(c.get("dtype", jnp.float32))
                         for a in (m, wr, wg, wu, wd))
    return m, wr, wg, wu, wd, valid, router


def _route(c):
    """``route`` as the case has it: a weight-0 pick planted where asked."""
    true = R.route
    if not c.get("zero_last"):
        return true
    return lambda *a, **kw: (lambda e, w: (e, w.at[:, -1].set(0.0)))(
        *true(*a, **kw))


def _picks_as_before(route, m, wr, valid, router, c):
    """``picks`` as the one form had them: rows with a positive weight on
    each held expert, the rows that are no tokens left out."""
    experts, weights = route(m, wr, c["k"], **{
        a: b for a, b in router.items() if a != "first"})
    T = m.shape[0]
    w = jnp.zeros((T, c["E"])).at[jnp.arange(T)[:, None], experts].set(
        weights)[:, c["first"]:c["first"] + c["held"]]
    if valid is not None:
        w = jnp.where(valid[:, None], w, 0.0)
    return np.asarray(jnp.sum(w > 0, axis=0))


@pytest.mark.parametrize("case", sorted(_CASES))
def test_grouped_kernel_is_the_plain_form(case, monkeypatch):
    c = _CASES[case]
    m, wr, wg, wu, wd, valid, router = _layer(c)
    route = _route(c)
    monkeypatch.setattr(R, "route", route)
    itemsize = jnp.dtype(m.dtype).itemsize
    if "width_block" in c:
        monkeypatch.setattr(G, "_WEIGHT_BYTES", _narrow(c))
        assert G.blocks(c["T"], c["h"], c["f"], itemsize)[1] \
            <= c["width_block"]

    def layer(m, wr, wg, wu, wd):
        return R.routed_ffn(m, wr, wg, wu, wd, c["k"], valid, **router)

    want, picks = layer(m, wr, wg, wu, wd)
    with _kernel_path():
        got, got_picks = layer(m, wr, wg, wu, wd)
    assert np.array_equal(np.asarray(got_picks), np.asarray(picks))
    assert np.array_equal(np.asarray(picks),
                          _picks_as_before(route, m, wr, valid, router, c))
    if case == "mellum-one_expert_takes_every_pick":
        assert int(picks[c["favour"]]) == c["T"]          # four row tiles
    if case == "mellum-experts_with_no_pick":
        assert (np.asarray(picks) == 0).any()
    if valid is not None:
        assert not np.asarray(got)[~np.asarray(valid)].any()
    if "dtype" in c:
        # bf16 rows and banks: the kernel rounds ``silu(g) * u`` once where
        # the plain form rounds g, u and their product: two bf16 steps of
        # the largest output apart at most
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=2 ** -6 * np.abs(want).max())
        return
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    if c.get("grad"):
        r = jax.random.normal(jax.random.PRNGKey(1), want.shape)

        def loss(*a):
            return jnp.sum(layer(*a)[0] * r)

        ws = (m, wr, wg, wu, wd)
        want_g = jax.grad(loss, argnums=range(5))(*ws)
        with _kernel_path():
            got_g = jax.grad(loss, argnums=range(5))(*ws)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("fault", ["dropped_pick", "unnormalised",
                                   "no_selection_bias"])
def test_a_fault_planted_in_route_reaches_the_kernel_path(fault):
    """``benchmarks/controls.py`` and ``controls_latent.py`` plant their
    faults by patching ``routed_ffn.route``: on the kernel's path too the
    output moves, and to what the plain form gives under the same fault."""
    c = dict(_KIMI, T=48, router=dict(_KIMI["router"],
                                      bias=jnp.linspace(0, 1, 32)))
    m, wr, wg, wu, wd, valid, router = _layer(c, seed=3)
    true = R.route

    def dropped_pick(*a, **kw):
        e, w = true(*a, **kw)
        return e, w.at[:, -1].set(0.0)

    def unnormalised(m, wr, k, **kw):
        e, w = true(m, wr, k, **kw)
        return e, w * 3.0

    def no_selection_bias(*a, **kw):
        return true(*a, **{**kw, "bias": None})

    planted = {"dropped_pick": dropped_pick, "unnormalised": unnormalised,
               "no_selection_bias": no_selection_bias}[fault]

    def layer():
        return R.routed_ffn(m, wr, wg, wu, wd, c["k"], valid, **router)[0]

    with mock.patch.object(G, "_WEIGHT_BYTES", _narrow(c)):
        with _kernel_path():
            sound = layer()
        with mock.patch.object(R, "route", planted):
            plain = layer()
            with _kernel_path():
                faulty = layer()
    assert float(jnp.max(jnp.abs(faulty - sound))) > 1e-3
    np.testing.assert_allclose(np.asarray(faulty), np.asarray(plain),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,h,f,want", [
    (16, 2304, 896, (16, 896)),       # a routed decode step: whole experts
    (512, 2304, 896, (128, 896)),     # its chunk
    (32, 7168, 2048, (32, 256)),      # a share of 7168 x 2048 experts
    (512, 7168, 2048, (128, 256)),
    (4096, 7168, 2048, None),         # rows past VMEM: the plain form
    (16, 2304, 900, None),            # a width in no whole lanes
])
def test_tiles_come_from_the_shapes(rows, h, f, want):
    assert G.blocks(rows, h, f, 2) == want
