"""Time the hybrid class's writes into its folded pools alone, in the form
``text/sambay.py`` writes them and in the window form it wrote them in
before, at the hybrid serving cell's shapes.

A folded pool is ``[blocks, bs * n, w]`` (``bs`` lines of ``n`` KV pairs a
block, a pair's line ``w`` wide). The cell (``phi4flash_reason_closed_8k``)
holds one full layer's pool, ``1 + slots * table`` blocks for K and again
for V (2.68 GB at 64 slots x 512 blocks), and the 8 window layers' rings
side by side, ``8 * (1 + slots * window / bs)`` blocks for K and for V
(1.34 GB). Two programs are timed:

* ``decode``: a decode step's 18 writes, a line a slot: K and V of the
  full layer into the pool, K and V of each window layer into its ring;
* ``chunk``: a chunk's 18 writes of whole blocks: a 512-line chunk as 32
  blocks into the pool (K and V), each window layer's ring written back
  whole (32 blocks, K and V).

The forms, a decode line's and a chunk's blocks:

* ``window``: one ``lax.scatter`` window a line or a block, at its first
  row of the flat ``[blocks * bs * n, w]`` pool (the compiler makes a
  ``while`` loop of one iteration a window): the form written before;
* ``rows`` (decode): a line as its ``n`` rows of the flat pool, a row an
  index of one scatter (``ops/pallas/line_write.py::plain``, the kernel's
  form off a TPU);
* ``kernel`` (decode): ``ops/pallas/line_write.py::write_lines``, K and V
  a call, what the decode program runs on a TPU;
* ``blocks`` (chunk): ``text/sambay.py::_put_blocks``, the block index an
  inserted dimension, what the chunk and prefill programs run.

A program runs its 18 writes ``--repeat`` times in one ``fori_loop``, each
time at the next line of every slot, with the pools donated, so the host's
dispatch is not timed. One JSON line a (program, form): the median
milliseconds of one pass of the 18 writes over ``--trials`` runs, the
bytes written, the ``while`` loops that carry a pool in the program of
one pass compiled alone,
and whether the pools it leaves equal the ``window`` form's, the trash
blocks (block 0 of the pool and of each ring, which rows with no line of
their own write) aside.

    python3 tools/time_pool_writes.py

Off a TPU pass smaller sizes (the times are then the CPU's):

    JAX_PLATFORMS=cpu python3 tools/time_pool_writes.py --slots 4 \\
        --table 8 --window 64 --chunk 128
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402

BS, PAIRS, W, WINDOW_LAYERS = 16, 10, 128, 8


def window_scatter(pool, dest, lines):
    """``lines`` ``[R, n, w]``, slab ``j`` at rows ``dest[j] * n`` of the
    flat pool: one scatter window a slab (the form written before)."""
    nb, rows, w = pool.shape
    return jax.lax.scatter(
        pool.reshape(nb * rows, w), (dest * lines.shape[1])[:, None], lines,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(),
            scatter_dims_to_operand_dims=(0,)),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS).reshape(pool.shape)


def _pairs(put):
    """A write of K and V lines by a write of one pool's."""
    return lambda pk, pv, dest, k, v: (put(pk, dest, k), put(pv, dest, v))


def _forms(interpret=False):
    """``{name: (write of K and V lines, write of whole blocks)}``."""
    from paddle_tpu.ops.pallas import line_write as L
    from paddle_tpu.text import sambay as S

    def blocks(pool, blocks, lines):
        return window_scatter(pool, blocks,
                              lines.reshape((-1,) + pool.shape[1:]))

    return {
        "window": (_pairs(window_scatter), blocks),
        "rows": (lambda pk, pv, dest, k, v: L.plain((pk, pv), dest, (k, v)),
                 None),
        "kernel": (lambda pk, pv, dest, k, v: L.write_lines(
            (pk, pv), dest, (k, v), interpret=interpret), None),
        "blocks": (None, S._put_blocks)}


def _program(kind, form, slots, table, window, repeat, interpret=False):
    """``(pk, pv, wk, wv, k, v) -> (pk, pv, wk, wv)``: ``repeat`` passes of
    a decode step's or a chunk's 18 writes in a ``fori_loop``, or one pass
    and no loop where ``repeat`` is None."""
    put_lines, put_blocks = _forms(interpret)[form]
    nbw = 1 + slots * window // BS
    per = window // BS
    s = jnp.arange(slots)

    def decode_pass(i, pools):
        pk, pv, wk, wv, k, v = pools
        line = i % (table * BS)
        dest = (1 + s * table) * BS + line
        pk, pv = put_lines(pk, pv, dest, k, v)
        rline = i % window
        for layer in range(WINDOW_LAYERS):
            rdest = (1 + s * per + layer * nbw) * BS + rline
            wk, wv = put_lines(wk, wv, rdest, k, v)
        return pk, pv, wk, wv, k, v

    def chunk_pass(i, pools):
        pk, pv, wk, wv, k, v = pools
        chunk = k.shape[0] // BS
        slot = i % slots
        blocks = 1 + slot * table + (i // slots) * chunk % table \
            + jnp.arange(chunk)
        pk, pv = put_blocks(pk, blocks, k), put_blocks(pv, blocks, v)
        ring = 1 + slot * per + jnp.arange(per)
        for layer in range(WINDOW_LAYERS):
            wk = put_blocks(wk, ring + layer * nbw, k[:window])
            wv = put_blocks(wv, ring + layer * nbw, v[:window])
        return pk, pv, wk, wv, k, v

    body = decode_pass if kind == "decode" else chunk_pass

    def run(pk, pv, wk, wv, k, v):
        if repeat is None:
            return body(0, (pk, pv, wk, wv, k, v))[:4]
        return jax.lax.fori_loop(0, repeat, body, (pk, pv, wk, wv, k, v))[:4]

    return jax.jit(run, donate_argnums=(0, 1, 2, 3))


def _pools(slots, table, window):
    nb = 1 + slots * table
    nbw = WINDOW_LAYERS * (1 + slots * window // BS)
    return jax.jit(lambda: tuple(
        jnp.zeros((n, BS * PAIRS, W), jnp.bfloat16)
        for n in (nb, nb, nbw, nbw)))()


def _lines(kind, slots, chunk, seed):
    rows = slots if kind == "decode" else chunk
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    draw = lambda key: jax.random.normal(                  # noqa: E731
        key, (rows, PAIRS, W), jnp.float32).astype(jnp.bfloat16)
    return draw(kk), draw(kv)


def _loops_over_pools(text, shapes):
    """``while`` loops of the compiled program whose state holds a pool."""
    sizes = {f"bf16[{s[0] * s[1]},{s[2]}]" for s in shapes} \
        | {"bf16[{}]".format(",".join(map(str, s))) for s in shapes}
    return sum(1 for m in re.finditer(r"= (\(.*\)) while\(", text)
               if any(sz in m.group(1) for sz in sizes))


@functools.partial(jax.jit, static_argnums=2)
def _same(a, b, pers):
    """Whether two sets of pools are equal but in their trash blocks, block
    0 of each run of ``per`` blocks (the pool's one, each window layer's
    ring's)."""
    out = True
    for x, y, per in zip(a, b, pers):
        trash = jnp.arange(x.shape[0]) % per == 0
        out &= jnp.all((x == y) | trash[:, None, None])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--table", type=int, default=512,
                    help="blocks of a slot's table row (max_len / 16)")
    ap.add_argument("--window", type=int, default=512)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--repeat", type=int, default=20)
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147483941)
    ap.add_argument("--interpret", action="store_true",
                    help="the kernel through the interpreter (off a TPU)")
    ap.add_argument("--out", help="a file to append the lines to too")
    a = ap.parse_args(argv)
    if a.chunk // BS > a.table or a.chunk < a.window:
        ap.error("--chunk must fit a table row and cover a window")
    device = jax.devices()[0]
    shape = (a.slots, a.table, a.window)
    pers = (1 + a.slots * a.table,) * 2 + (1 + a.slots * a.window // BS,) * 2
    with open(a.out or os.devnull, "a") as sink:
        for kind in ("decode", "chunk"):
            k, v = _lines(kind, a.slots, a.chunk, a.seed)
            rows = (a.slots * PAIRS * (2 + 2 * WINDOW_LAYERS)
                    if kind == "decode" else
                    2 * (a.chunk + WINDOW_LAYERS * a.window) * PAIRS)
            first = None
            for form in (("window", "rows", "kernel") if kind == "decode"
                         else ("window", "blocks")):
                pools = _pools(*shape)
                once = _program(kind, form, *shape, None, a.interpret)
                loops = _loops_over_pools(
                    once.lower(*pools, k, v).compile().as_text(),
                    [p.shape for p in pools])
                compiled = _program(kind, form, *shape, a.repeat,
                                    a.interpret).lower(
                    *pools, k, v).compile()
                pools = jax.block_until_ready(compiled(*pools, k, v))
                times = []
                for _ in range(a.trials):
                    t = time.perf_counter()
                    pools = jax.block_until_ready(compiled(*pools, k, v))
                    times.append((time.perf_counter() - t) / a.repeat * 1e3)
                del pools
                # one pass from nothing, to compare the forms' pools
                got = once(*_pools(*shape), k, v)
                if first is None:
                    first, same = got, True
                else:
                    same = bool(_same(first, got, pers))
                del got
                line = {"program": kind, "form": form,
                        "ms_per_pass": statistics.median(times),
                        "ms_each": times, "writes_per_pass":
                        2 + 2 * WINDOW_LAYERS,
                        "bytes_per_pass": rows * W * 2,
                        "while_loops_over_pools": loops,
                        "same_pools_as_window_form": same,
                        "slots": a.slots, "table": a.table,
                        "window": a.window, "chunk": a.chunk,
                        "device": device.device_kind}
                print(json.dumps(line), flush=True)
                sink.write(json.dumps(line) + "\n")
            del first


if __name__ == "__main__":
    main()
