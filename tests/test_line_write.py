"""``ops/pallas/line_write.py``: decode's write of a line a row into folded
pools, through the interpreter on the CPU, against its XLA form and against
one scatter window a line (the form the hybrid class wrote before), at the
hybrid cell's geometry (16 lines of 10 KV pairs a block, 128 lanes) and
others: every line position of a block, K and V in one call, rows with no
line of their own in a trash block."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import line_write as L


def _window(pool, dest, lines):
    nb, rows, w = pool.shape
    return jax.lax.scatter(
        pool.reshape(nb * rows, w), (dest * lines.shape[1])[:, None], lines,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(),
            scatter_dims_to_operand_dims=(0,)),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS).reshape(pool.shape)


def _draw(shape, seed, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _case(bs, n, w, dtype, rows, trash, seed=0):
    """``rows`` rows over blocks of their own after the trash block
    ``trash``; every third row has no line and writes into the trash."""
    nb = 2 + rows
    dest = (1 + np.arange(rows)) * bs + np.arange(rows) % bs
    idle = np.arange(rows) % 3 == 2
    dest = np.where(idle, trash * bs + np.arange(rows) % bs, dest)
    pools = (_draw((nb, bs * n, w), seed, dtype),
             _draw((nb, bs * n, w), seed + 1, dtype))
    lines = (_draw((rows, n, w), seed + 2, dtype),
             _draw((rows, n, w), seed + 3, dtype))
    return pools, jnp.asarray(dest, jnp.int32), lines


@pytest.mark.parametrize("bs,n,w,dtype,rows,trash", [
    (16, 10, 128, jnp.bfloat16, 64, 0),      # the hybrid cell's pools
    (16, 10, 128, jnp.bfloat16, 20, 21),     # a ring's trash, not block 0
    (8, 2, 128, jnp.bfloat16, 9, 0),         # one tile a block: span = block
    (16, 5, 256, jnp.float32, 17, 0),        # 8 rows a tile
], ids=["cell", "ring_trash", "one_tile_blocks", "float32"])
def test_kernel_writes_the_bytes_of_the_scatter(bs, n, w, dtype, rows,
                                                trash):
    pools, dest, lines = _case(bs, n, w, dtype, rows, trash)
    assert L._geometry(pools[0].shape, n, dtype) is not None
    got = jax.jit(lambda p, d, x: L.write_lines(p, d, x, interpret=True))(
        pools, dest, lines)
    plain = jax.jit(L.plain)(pools, dest, lines)
    keep = np.arange(pools[0].shape[0]) != trash
    for g, p, pool, new in zip(got, plain, pools, lines):
        want = np.asarray(_window(pool, dest, new))[keep]
        np.testing.assert_array_equal(np.asarray(g)[keep], want)
        np.testing.assert_array_equal(np.asarray(p)[keep], want)
        assert not np.array_equal(want, np.asarray(pool)[keep])


def test_every_line_of_a_block_and_its_neighbours_untouched():
    """One row a block, line ``i`` of block ``i + 1`` for each of the 16:
    every offset of a line in the tiles the kernel copies, the last line's
    tiles held inside its block."""
    bs, n, w = 16, 10, 128
    pool = _draw((bs + 2, bs * n, w), 4, jnp.bfloat16)
    dest = jnp.asarray((1 + np.arange(bs)) * bs + np.arange(bs), jnp.int32)
    lines = _draw((bs, n, w), 5, jnp.bfloat16)
    got, = L.write_lines((pool,), dest, (lines,), interpret=True)
    want = np.asarray(pool).copy()
    for i in range(bs):
        want[1 + i, n * i:n * (i + 1)] = np.asarray(lines[i])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("shape,n", [((5, 12, 8), 3), ((5, 24, 128), 3),
                                     ((5, 20, 128), 10)],
                         ids=["narrow_lines", "block_off_tiles",
                              "block_under_two_tiles"])
def test_a_block_of_no_whole_tiles_takes_the_xla_form(shape, n):
    assert L._geometry(shape, n, jnp.bfloat16) is None
    pool = _draw(shape, 6, jnp.bfloat16)
    dest = jnp.asarray([4, 9, 14], jnp.int32)
    lines = _draw((3, n, shape[2]), 7, jnp.bfloat16)
    got, = jax.jit(L.write_lines)((pool,), dest, (lines,))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_window(pool, dest, lines)))
