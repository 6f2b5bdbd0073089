"""The chunk-attention kernel (``ops/pallas/chunk_attention.py``) through
the Pallas interpreter on the CPU, against the plain XLA form it replaces
on a TPU and against a float32 evaluation in one piece, through the walk
over tiles that the latent chunk body makes
(``generation._latent_chunk_attention``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import chunk_attention as ca
from paddle_tpu.text import generation as G

_BS, _NB, _MB = 8, 40, 20          # 160 lines a table row
_TILE, _TK = 32, 16                # two blocks of lines a tile
_C = 16
_SCALE = 0.21


def _case(heads, dims, gpos, seed=0, blank_after=None):
    """A chunk's queries, a pool of lines and one slot's table row, in
    float32. ``blank_after``: what the pool holds in the blocks of ``_TK``
    lines wholly past it (a walk that reads them shows it)."""
    dn, dr, dv, rank = dims
    width = -(-(rank + dr) // 16) * 16
    rng = np.random.default_rng(seed)
    q_nope = rng.standard_normal((len(gpos), heads, dn))
    q_pe = rng.standard_normal((len(gpos), heads, dr))
    pool = rng.standard_normal((_NB, _BS, 1, width))
    wkv = rng.standard_normal((rank, heads, dn + dv)) * rank ** -0.5
    table = rng.permutation(_NB - 1)[:_MB] + 1
    if blank_after is not None:
        first = (max(gpos) // _TK + 1) * _TK // _BS
        pool[table[first:]] = blank_after
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(q_nope), f32(q_pe), f32(pool),
            jnp.asarray(table, jnp.int32), jnp.asarray(gpos, jnp.int32),
            f32(wkv))


def _in_one_piece(q_nope, q_pe, pool, table, gpos, wkv):
    """Float32, no tiles, no running state: every line of the row's table
    expanded, scored, masked and weighed at once."""
    hi = jax.lax.Precision.HIGHEST
    rank, dn, dr = wkv.shape[0], q_nope.shape[-1], q_pe.shape[-1]
    lines = pool[table].reshape(-1, pool.shape[-1])
    kv = jnp.einsum("kr,rhd->khd", lines[:, :rank], wkv, precision=hi)
    s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn], precision=hi)
         + jnp.einsum("qhd,kd->hqk", q_pe, lines[:, rank:rank + dr],
                      precision=hi)) * _SCALE
    ok = jnp.arange(lines.shape[0])[None, :] <= gpos[:, None]
    p = jnp.where(ok, jnp.exp(s - jnp.max(jnp.where(ok, s, -1e30), -1,
                                          keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("hqk,khd->qhd", p, kv[..., dn:], precision=hi)


@pytest.fixture
def tiled(monkeypatch):
    """Tiles of 32 lines in blocks of 16, and a way to send the walk
    through the interpreted kernel."""
    monkeypatch.setattr(G, "_LATENT_TILE", _TILE)
    monkeypatch.setattr(ca, "_LINE_BLOCKS", (_TK,))
    fold, calls = ca.chunk_attention, []

    def interpreted(*args, **how):
        calls.append(args[0].shape)
        return fold(*args, interpret=True, **how)

    def through_the_kernel():
        monkeypatch.setattr(ca, "chunk_attention", interpreted)
        return calls

    return through_the_kernel


_rows = np.arange(_C)
_CASES = {
    "no_cached_prefix": _rows,
    "a_prefix_ending_inside_the_first_tile": 8 + _rows,
    "a_prefix_ending_on_a_tiles_edge": _TILE + _rows,
    "three_tiles_and_a_part": 3 * _TILE + 8 + _rows,
    "positions_ending_inside_a_block": 40 + _rows[:12],
    # rows 0-7 see nothing of the second and third tile; row 8 nothing
    "rows_that_see_no_line_of_a_tile": np.concatenate(
        [3 + _rows[:8], [-1], 70 + _rows[:7]]),
}
_SHAPES = {                        # heads, (dn, dr, dv, rank)
    "4_heads_24_against_32": (4, (16, 8, 32, 16)),
    "6_heads_24_against_16": (6, (16, 8, 16, 24)),
    "64_heads_12_against_8": (64, (8, 4, 8, 8)),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(_SHAPES))
@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_kernel_against_the_plain_form_and_float32(case, shape, dtype,
                                                       tiled):
    """In float32 the walk through the kernel is the walk through the
    plain form, and the attention computed in one piece, to 2e-5 (the
    tolerances of ``paged_attention``'s suite); in bf16 it is no further
    from a float32 evaluation of the same bf16 inputs than the plain form
    is. A row that sees nothing returns zeros, and blocks of lines wholly
    past the chunk's last position, full of NaN, are never read."""
    heads, dims = _SHAPES[shape]
    gpos = _CASES[case]
    clean = _case(heads, dims, gpos, blank_after=0.0)
    dirty = _case(heads, dims, gpos, blank_after=np.nan)
    blind = np.asarray(gpos) < 0

    def cast(a, to=dtype):
        return tuple(x.astype(to) if jnp.issubdtype(x.dtype, jnp.floating)
                     else x for x in a)

    plain = np.asarray(G._latent_chunk_attention(
        *cast(clean), _SCALE, jnp.dtype(dtype)), np.float32)
    calls = tiled()
    got = np.asarray(G._latent_chunk_attention(
        *cast(dirty), _SCALE, jnp.dtype(dtype)), np.float32)
    assert len(calls) == 1 and calls[0] == (heads, len(gpos), dims[0])
    assert got.shape == (len(gpos), heads, dims[2])
    assert np.all(got[blind] == 0.0) and np.all(np.isfinite(got))
    exact = np.asarray(_in_one_piece(*cast(cast(clean), "float32")))
    if dtype == "float32":
        np.testing.assert_allclose(got, plain, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got[~blind], exact[~blind], atol=2e-5,
                                   rtol=2e-5)
        return
    err, plain_err = (np.abs(x[~blind] - exact[~blind])
                      for x in (got, plain))
    # the same roundings in another order of summation: as close in the
    # mean, and no outlier
    assert err.mean() <= plain_err.mean() * 1.02 + 1e-6
    assert err.max() <= plain_err.max() * 2 + 1e-6


def test_one_tile_returns_the_running_state_of_the_plain_form():
    """One call, from a state that has seen lines already: ``(top, total,
    acc)`` are the plain form's, ``acc`` laid out ``[H, C, dv]``."""
    H, C, dn, dr, dv, rank, T = 8, 16, 16, 8, 24, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    q, qs = (jax.random.normal(k, (H, C, d)) for k, d in
             zip(ks[:2], (dn, dr)))
    lines = jax.random.normal(ks[2], (T, 32))
    w = jax.random.normal(ks[3], (rank, H, dn + dv)) * 0.25
    gpos = 100 + jnp.arange(C)
    carry = (jax.random.normal(ks[4], (H, C)),
             jnp.exp(jax.random.normal(ks[5], (H, C))),
             jax.random.normal(ks[6], (H, C, dv)))
    want = ca.plain(q, qs, lines, w, gpos, 64, *carry, scale=_SCALE)
    got = ca.chunk_attention(q, qs, lines, w, gpos, 64, carry,
                             scale=_SCALE, interpret=True)
    assert [g.shape for g in got] == [(H, C), (H, C), (H, C, dv)]
    for g, x in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), atol=2e-5,
                                   rtol=2e-5)


def test_off_the_chip_the_plain_form_runs_and_small_shapes_never_tile():
    """Without ``interpret`` the CPU runs the plain form whatever the
    shapes; shapes that break the (8, 128) rule take it on any platform."""
    H, C, T = 4, 16, 32
    q, qs = jnp.ones((H, C, 16)), jnp.ones((H, C, 8))
    lines, w = jnp.ones((T, 32)), jnp.ones((16, H, 40))
    assert not ca._tiles(q, lines, w, 24)
    got = ca.chunk_attention(q, qs, lines, w, jnp.arange(C), 0,
                             ca.start(H, C, 24), scale=1.0)
    want = ca.plain(q, qs, lines, w, jnp.arange(C), 0, *ca.start(H, C, 24),
                    scale=1.0)
    for g, x in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
    bf = jnp.bfloat16
    assert ca._tiles(jnp.ones((64, 512, 128), bf), jnp.ones((2048, 640), bf),
                     jnp.ones((512, 64, 256), bf), 128)


def test_blocks_come_from_the_shapes():
    """At the latent cell's shapes a grid step takes 8 heads and 512
    lines; heads that 8 does not divide, or a tile that 512 does not,
    take the next size down."""
    assert ca._blocks(64, 512, 128, 64, 128, 2048, 640, 512, 2) == (8, 512)
    assert ca._blocks(12, 512, 128, 64, 128, 768, 640, 512, 2) == (4, 256)
    assert ca._blocks(7, 64, 128, 64, 128, 384, 640, 512, 2) == (1, 128)
