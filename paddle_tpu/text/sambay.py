"""Per-layer bodies of a SambaY decoder (arXiv:2507.06607; the
``phi4flash`` family, ``text/models/phi4flash.py``): a self-decoder of
recurrent (Mamba-1) and window-attention layers, one full-attention layer
whose keys and values are the model's ONE KV cache, and a cross-decoder of
gated memory units and query-only attention layers that read that cache.

Like ``generation.py``'s bodies these are module-level and shared: the
model's ``forward`` and the serving engine's prefill, chunk and decode
programs (``serving/sambay_programs.py``) trace the same python. A body
comes in up to three forms, by what a program gives it:

- ``*_prefill``: a whole prompt from nothing, ``x`` ``[1, T, h]``;
- ``*_chunk``: ``C`` rows of one slot that continue what the slot keeps
  (the recurrent state and the convolution's last inputs; the window's
  lines; the pool);
- ``*_decode``: one token a slot, ``x`` ``[S, 1, h]``.

What a layer keeps is the program's to thread; a body takes and returns
it as values. No position encoding anywhere: order enters through the
recurrent layers, so a window layer's lines may sit in any order (a ring).

**Differential attention as grouped attention of twice the head size.**
Heads pair up: ``q = (q1, q2)``, ``k = (k1, k2)``, ``v = [v1 | v2]``, and
``a_j = softmax(q_j k_j^T / sqrt(hd)) v``. A cached line of a KV pair is
``[k1 | k2]`` beside ``[v1 | v2]``, ``2 hd`` numbers each: whole lanes at
``hd`` 64. With ``q1' = [q1 | 0]`` and ``q2' = [0 | q2]`` the two maps are
plain attention of ``2 hd``-wide heads over those lines (the zeros add
exact zeros), four query rows a KV pair where two query pairs share one.
So decode runs ``ops/pallas/paged_attention.py`` as it stands, and the
chunk walk is ``generation._attend``. The doubled score products are
noise beside the lines' bytes (decode) and the MLPs' products (chunks).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import generation as G

#: eps of the RMSNorm over the difference of the two maps (the
#: Differential Transformer's published 1e-5)
SUBLN_EPS = 1e-5


def lambda_init(layer):
    """``0.8 - 0.6 exp(-0.3 i)`` for layer ``i`` (0-based)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _mlp(x, lw, eps):
    """``x + W_down(silu(g) * u)``, ``[g, u] = W_gate_up LN'(x)``."""
    g, u = jnp.split(G._ln(x, lw["ln2w"], lw["ln2b"], eps) @ lw["wgu"], 2,
                     axis=-1)
    return x + (jax.nn.silu(g) * u) @ lw["wd"]


# ---------------------------------------------------------------------------
# Mamba-1
# ---------------------------------------------------------------------------


def _mamba_inputs(ext, z, lw, keep=None):
    """From the convolution's inputs ``ext`` ``[..., T + d_conv - 1, di]``
    (the carried ones, then the ``T`` new) to what the recurrence reads:
    ``(u [..., T, di], dt, B, C)`` in float32, ``dt`` zero where ``keep``
    (bool ``[T]``) is false, so that such a row leaves the state alone."""
    T = z.shape[-2]
    cw = lw["convw"].astype(jnp.float32)                    # [d_conv, di]
    u = sum(ext[..., j:j + T, :].astype(jnp.float32) * cw[j]
            for j in range(cw.shape[0])) + lw["convb"].astype(jnp.float32)
    u = jax.nn.silu(u).astype(z.dtype)
    ds = lw["alog"].shape[-1]
    r = lw["wdt"].shape[0]
    dbc = u @ lw["wx"]
    dt = jax.nn.softplus(
        jnp.matmul(dbc[..., :r], lw["wdt"],
                   preferred_element_type=jnp.float32)
        + lw["bdt"].astype(jnp.float32))
    if keep is not None:
        dt = jnp.where(keep[:, None], dt, 0.0)
    f32 = jnp.float32
    return (u.astype(f32), dt, dbc[..., r:r + ds].astype(f32),
            dbc[..., r + ds:].astype(f32))


def _mamba_step(h, u, dt, B, C, A):
    """One position of ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t u_t``,
    ``y_t = C_t . h_t``: ``h`` ``[..., ds, di]``, ``u`` / ``dt``
    ``[..., di]``, ``B`` / ``C`` ``[..., ds]``, ``A`` ``[ds, di]``."""
    h = jnp.exp(dt[..., None, :] * A) * h \
        + (dt * u)[..., None, :] * B[..., :, None]
    return h, jnp.sum(h * C[..., :, None], axis=-2)


def _mamba_out(x, y, u, z, lw, eps):
    """``(x + W_out(m * silu(z)), m)`` with ``m = y + D u`` (float32 in),
    then the layer's MLP; ``m`` in the model's type is the memory of the
    gated units below."""
    m = (y + lw["dskip"].astype(jnp.float32) * u).astype(z.dtype)
    return _mlp(x + (m * jax.nn.silu(z)) @ lw["wout"], lw, eps), m


def mamba_chunk(x, lw, ssm, conv, keep, n_keep, *, eps):
    """A Mamba layer over ``x`` ``[1, T, h]`` from the carried state
    ``ssm`` ``[ds, di]`` (float32) and the convolution's last inputs
    ``conv`` ``[d_conv - 1, di]``; the rows of ``keep`` (the first
    ``n_keep``) are tokens, the others padding that moves nothing.
    Returns ``(x, ssm, conv, m)``."""
    uz = G._ln(x, lw["ln1w"], lw["ln1b"], eps) @ lw["win"]
    u_in, z = jnp.split(uz[0], 2, axis=-1)                  # [T, di]
    ext = jnp.concatenate([conv.astype(u_in.dtype), u_in], axis=0)
    u, dt, B, C = _mamba_inputs(ext, z, lw, keep)
    A = -jnp.exp(lw["alog"].astype(jnp.float32)).T         # [ds, di]

    def one(h, t):
        h, y = _mamba_step(h, *t, A)
        return h, y

    ssm, y = jax.lax.scan(one, ssm, (u, dt, B, C))
    conv = jax.lax.dynamic_slice_in_dim(ext, n_keep, conv.shape[0], axis=0)
    x, m = _mamba_out(x, y[None], u[None], z[None], lw, eps)
    return x, ssm, conv.astype(x.dtype), m


def mamba_prefill(x, lw, keep, n_keep, *, eps):
    """:func:`mamba_chunk` from nothing (zeros)."""
    di, ds = lw["alog"].shape
    return mamba_chunk(x, lw, jnp.zeros((ds, di), jnp.float32),
                       jnp.zeros((lw["convw"].shape[0] - 1, di), x.dtype),
                       keep, n_keep, eps=eps)


def mamba_decode(x, lw, ssm, conv, active, *, eps):
    """One token a slot: ``x`` ``[S, 1, h]``, ``ssm`` ``[S, ds, di]``,
    ``conv`` ``[S, d_conv - 1, di]``; a slot that is not ``active`` keeps
    its state. Returns ``(x, ssm, conv, m)``."""
    uz = G._ln(x, lw["ln1w"], lw["ln1b"], eps) @ lw["win"]
    u_in, z = jnp.split(uz, 2, axis=-1)                     # [S, 1, di]
    ext = jnp.concatenate([conv.astype(u_in.dtype), u_in], axis=1)
    u, dt, B, C = _mamba_inputs(ext, z, lw)
    A = -jnp.exp(lw["alog"].astype(jnp.float32)).T
    h, y = _mamba_step(ssm, u[:, 0], dt[:, 0], B[:, 0], C[:, 0], A)
    on = active[:, None, None]
    x, m = _mamba_out(x, y[:, None], u, z, lw, eps)
    return (x, jnp.where(on, h, ssm),
            jnp.where(on, ext[:, 1:], conv).astype(conv.dtype), m)


def gmu(x, lw, m, *, eps):
    """Gated memory unit: ``x + W_out(silu(W_in LN(x)) * m)``, ``m`` the
    last recurrent layer's memory at the same positions; then the MLP."""
    g = jax.nn.silu(G._ln(x, lw["ln1w"], lw["ln1b"], eps) @ lw["gin"])
    return _mlp(x + (g * m) @ lw["gout"], lw, eps)


# ---------------------------------------------------------------------------
# differential attention
# ---------------------------------------------------------------------------


def _project(x, lw, n_heads, n_kv, eps):
    """``(q' [.., T, n_heads, 2 hd], k lines, v lines [.., T, n_kv / 2,
    2 hd])`` of normed ``x``; a query-only layer has ``wq`` and gives no
    lines."""
    h1 = G._ln(x, lw["ln1w"], lw["ln1b"], eps)
    lead = x.shape[:-1]
    if "wq" in lw:
        q, k, v = h1 @ lw["wq"], None, None
    else:
        qkv = h1 @ lw["wqkv"]
        hd = qkv.shape[-1] // (n_heads + 2 * n_kv)
        q, k, v = jnp.split(qkv, [n_heads * hd, (n_heads + n_kv) * hd], -1)
        k = k.reshape(lead + (n_kv // 2, 2 * hd))
        v = v.reshape(lead + (n_kv // 2, 2 * hd))
    hd = q.shape[-1] // n_heads
    q = q.reshape(lead + (n_heads // 2, 2, hd))
    zero = jnp.zeros_like(q[..., 0, :])
    q = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                   jnp.concatenate([zero, q[..., 1, :]], -1)], axis=-2)
    return q.reshape(lead + (n_heads, 2 * hd)), k, v


def _lambda(lw, layer):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``."""
    f32 = jnp.float32
    return jnp.exp(jnp.sum(lw["lq1"].astype(f32) * lw["lk1"].astype(f32))) \
        - jnp.exp(jnp.sum(lw["lq2"].astype(f32) * lw["lk2"].astype(f32))) \
        + lambda_init(layer)


def _combine(x, o, lw, layer, eps):
    """From the two maps' outputs ``o`` ``[..., n_heads, 2 hd]`` (rows
    ``2p`` and ``2p + 1`` of pair ``p``) to the layer's output: ``x +
    W_o((1 - lambda_init) RMSNorm(a1 - lambda a2))``, then the MLP."""
    li, lam, f32 = lambda_init(layer), _lambda(lw, layer), jnp.float32
    lead = o.shape[:-2]
    o = o.reshape(lead + (o.shape[-2] // 2, 2, o.shape[-1])).astype(f32)
    d = o[..., 0, :] - lam * o[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                          + SUBLN_EPS) * lw["subln"].astype(f32)
    d = ((1.0 - li) * d).astype(x.dtype).reshape(lead + (-1,))
    return _mlp(x + d @ lw["wo"], lw, eps)


def _scale(q):
    """The softmax scale of ``hd``-wide heads, ``q'`` being ``2 hd``."""
    return (q.shape[-1] // 2) ** -0.5


def _attend_seq(q, k, v, allowed, dt):
    """``q'`` ``[1, Q, H, w]`` over lines ``[1, T, n, w]`` under
    ``allowed`` ``[Q, T]`` -> ``[1, Q, H, w]``."""
    o = G._attend(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                  jnp.swapaxes(v, 1, 2), allowed[None, None], dt, _scale(q))
    return jnp.swapaxes(o, 1, 2)


def _sees(qpos, kpos, window=None):
    """``[Q, T]``: which keys at positions ``kpos`` (negative: no key) a
    row at ``qpos`` sees: the earlier ones and itself, under ``window``
    itself and the ``window - 1`` before it."""
    ok = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None])
    if window is not None:
        ok = ok & (qpos[:, None] - kpos[None, :] < window)
    return ok


def attention_prefill(x, lw, *, n_heads, n_kv, eps, layer, window=None,
                      shared=None):
    """A differential-attention layer over a whole prompt ``x`` ``[1, T,
    h]``, causal, under ``window`` where the layer has one; a query-only
    layer attends over ``shared``, the lines ``(k, v)`` of the layer that
    keeps them. Returns ``(x, (k, v))``, the lines being ``shared``'s for
    a query-only layer."""
    q, k, v = _project(x, lw, n_heads, n_kv, eps)
    if k is None:
        k, v = shared
    at = jnp.arange(x.shape[1])
    o = _attend_seq(q, k, v, _sees(at, at, window), x.dtype)
    return _combine(x, o, lw, layer, eps), (k, v)


def _scatter(pool, dest, lines):
    """``lines`` ``[R, n, w]`` into ``pool`` ``[nb, rows, w]``, slab ``j``
    at rows ``dest[j] * n`` on: with ``n`` KV pairs a line and ``dest`` a
    line's place (``block * bs + line in the block``) a line a slab
    (decode's write: a line a slot); with ``n == rows`` and ``dest`` block
    ids a whole block a slab. A pool is held folded like this, lines and
    KV pairs of a block one run of rows, because that is the form the
    decode kernel reads and the pairs (10 at the published sizes) fill no
    whole tile: held ``[nb, bs, n, w]`` the chip's compiler lays the
    lines out minor to the pairs and relays the whole pool, padded to 16
    pairs, around every scatter (4.9 GB of temporaries in the decode
    program, compiled for a described v5e)."""
    nb, rows, w = pool.shape
    return jax.lax.scatter(
        pool.reshape(nb * rows, w), (dest * lines.shape[1])[:, None], lines,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(),
            scatter_dims_to_operand_dims=(0,)),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS).reshape(pool.shape)


def _lines(pool, blocks, n):
    """The lines ``[1, len(blocks) * bs, n, w]`` of the blocks ``blocks``
    of a folded pool, in order."""
    got = pool[blocks]
    return got.reshape(1, -1, n, got.shape[-1])


def _put_blocks(pool, blocks, lines):
    """``lines`` ``[len(blocks) * bs, n, w]`` as whole blocks to the blocks
    ``blocks`` of a folded pool: one slab a block. A prompt's rows are
    written so (a chunk is 32 blocks where it is 512 lines): a scatter
    runs a slab at a time on the chip, 3.3 us each, and line by line the
    writes were 34 of a chunk's 69 ms (my chip run, PR 35)."""
    return _scatter(pool, blocks, lines.reshape((-1,) + pool.shape[1:]))


def pool_write(pool, table_row, lines, first, n_keep, block_size):
    """A prompt's ``lines`` ``[T, n, w]`` (positions ``first + j``,
    ``first`` at a block's start, the first ``n_keep`` tokens) into the
    slot's blocks, whole blocks at a time; a block with no token goes to
    the trash block. The last block's lines past the prompt are written
    too: a position is read only after the step that writes it."""
    pad = -lines.shape[0] % block_size
    lines = jnp.pad(lines, ((0, pad), (0, 0), (0, 0)))
    at = jnp.arange(lines.shape[0] // block_size)
    blocks = jnp.where(at * block_size < n_keep,
                       table_row[first // block_size + at], 0)
    return _put_blocks(pool, blocks, lines)


def ring_tables(n_slots, window, block_size):
    """The constant block tables of a window pool ``[1 + n_slots * window
    / block_size, block_size, ...]``: block 0 is trash, slot ``s`` owns
    the ``window / block_size`` blocks after ``1 + s * that``; position
    ``p`` of a slot lives at line ``p % window`` of its ring."""
    per = window // block_size
    return 1 + jnp.arange(n_slots, dtype=jnp.int32)[:, None] * per \
        + jnp.arange(per, dtype=jnp.int32)[None, :]


def ring_write(pool, ring_row, lines, first, n_keep, *, window, old=None):
    """Leave in a slot's ring (``pool`` folded) the newest lines of
    ``lines`` ``[T, n, w]`` (positions ``first + j``, the first ``n_keep``
    of them tokens): ring line ``i`` takes the last kept position that is
    ``i`` mod ``window``, where there is one, and keeps what it held
    (``old`` ``[window, n, w]``; nothing, for a prompt's first rows)
    where there is none. The ring is written back whole, a block a slab
    (:func:`_put_blocks`)."""
    at = jnp.arange(window)
    last = first + n_keep - 1
    p = last - jnp.mod(last - at, window)
    new = lines[jnp.clip(p - first, 0, lines.shape[0] - 1)]
    kept = jnp.zeros_like(new) if old is None else old
    return _put_blocks(pool, ring_row,
                       jnp.where((p >= first)[:, None, None], new, kept))


def window_chunk(x, lw, wk, wv, ring_row, gpos, n_keep, *, n_heads, n_kv,
                 eps, layer, window):
    """A window layer over ``C`` rows of one slot at positions ``gpos``:
    the rows see the ring's lines (the ``window`` positions before the
    chunk, wherever they sit) and one another, causally and through the
    window; then the ring takes the chunk's newest lines. ``wk`` / ``wv``
    ``[nb, bs, n, w]`` are this layer's pools. Returns ``(x, wk, wv)``."""
    q, k, v = _project(x, lw, n_heads, n_kv, eps)
    first = gpos[0]
    at = jnp.arange(window)
    rpos = (first - 1) - jnp.mod(first - 1 - at, window)
    kring = _lines(wk, ring_row, k.shape[2])
    vring = _lines(wv, ring_row, v.shape[2])
    kall = jnp.concatenate([kring, k], axis=1)
    vall = jnp.concatenate([vring, v], axis=1)
    cm = _sees(gpos, jnp.concatenate([rpos, gpos]), window)
    x = _combine(x, _attend_seq(q, kall, vall, cm, x.dtype), lw, layer, eps)
    return (x,
            ring_write(wk, ring_row, k[0], first, n_keep, window=window,
                       old=kring[0]),
            ring_write(wv, ring_row, v[0], first, n_keep, window=window,
                       old=vring[0]))


#: lines of the pool a chunk's rows are scored against at a time
POOL_TILE = 2048


def _attend_pool(q, kc, vc, table_row, gpos, n, dt):
    """``q'`` ``[1, C, H, w]`` at positions ``gpos`` over the lines of one
    slot in a folded pool, causally: the slot's blocks are walked a tile
    of ``POOL_TILE`` lines at a time with an online softmax (float32
    maximum, sum and weighted values, as ``generation._attend_tiled``),
    and only as far as the chunk's last position: a table row spans
    ``max_len``, of which a prompt fills a part, and lines past it are
    neither gathered nor scored. -> ``[1, C, H, w]``."""
    C, H, w = q.shape[1:]
    bs = kc.shape[1] // n
    per = max(1, min(POOL_TILE // bs, table_row.shape[0]))
    row = jnp.pad(table_row, (0, -table_row.shape[0] % per))
    g = H // n
    qg = jnp.swapaxes(q, 1, 2).reshape(1, n, g, C, w)
    scale = _scale(q)

    def tile(t, carry):
        top, total, acc = carry
        blocks = jax.lax.dynamic_slice_in_dim(row, t * per, per)
        k_t = jnp.swapaxes(_lines(kc, blocks, n), 1, 2)     # [1, n, T, w]
        v_t = jnp.swapaxes(_lines(vc, blocks, n), 1, 2)
        ok = _sees(gpos, t * per * bs + jnp.arange(per * bs))
        sc = jnp.einsum("bngqd,bnkd->bngqk", qg, k_t,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(ok, sc, -1e30)
        top2 = jnp.maximum(top, jnp.max(sc, axis=-1))
        p = jnp.where(ok, jnp.exp(sc - top2[..., None]), 0.0)
        shrink = jnp.exp(top - top2)
        acc = acc * shrink[..., None] + jnp.einsum(
            "bngqk,bnkd->bngqd", p.astype(dt), v_t,
            preferred_element_type=jnp.float32)
        return top2, total * shrink + jnp.sum(p, axis=-1), acc

    start = (jnp.full((1, n, g, C), -1e30, jnp.float32),
             jnp.zeros((1, n, g, C), jnp.float32),
             jnp.zeros((1, n, g, C, w), jnp.float32))
    _, total, acc = jax.lax.fori_loop(0, gpos[-1] // (per * bs) + 1, tile,
                                      start)
    o = (acc / jnp.maximum(total, 1e-30)[..., None]).astype(dt)
    return jnp.swapaxes(o.reshape(1, H, C, w), 1, 2)


def full_chunk(x, lw, kc, vc, table_row, gpos, n_keep, *, n_heads, n_kv,
               eps, layer, block_size):
    """The full-attention layer over ``C`` rows of one slot (its lines
    written to the slot's blocks first, :func:`pool_write`), or a
    query-only layer (which writes nothing): the rows attend over the
    slot's lines in the pool (:func:`_attend_pool`). Returns ``(x, kc,
    vc)``."""
    q, k, v = _project(x, lw, n_heads, n_kv, eps)
    if k is not None:
        kc = pool_write(kc, table_row, k[0], gpos[0], n_keep, block_size)
        vc = pool_write(vc, table_row, v[0], gpos[0], n_keep, block_size)
    o = _attend_pool(q, kc, vc, table_row, gpos, n_kv // 2, x.dtype)
    return _combine(x, o, lw, layer, eps), kc, vc


def attention_decode(x, lw, kc, vc, tables, dest, seen, *, n_heads, n_kv,
                     eps, layer, block_size):
    """One token a slot against a paged pool ``kc`` / ``vc`` ``[nb, bs *
    n, w]`` (folded): the layer's own line goes to line ``dest`` first (a
    query-only layer has none and writes nothing), then row ``s`` sees
    the lines ``<= seen[s]`` of its table row, none where that is
    negative. For a window layer the pool is the ring's, the tables
    :func:`ring_tables` and ``seen`` the ring's last filled line: the
    ring holds the window and nothing else. Returns ``(x, kc, vc)``."""
    q, k, v = _project(x, lw, n_heads, n_kv, eps)
    if k is not None:
        kc, vc = _scatter(kc, dest, k[:, 0]), _scatter(vc, dest, v[:, 0])
    from ..ops.pallas import paged_attention as kernel

    # the kernel's own form of a pool, which it folds again: no copy
    form = (kc.shape[0], block_size, n_kv // 2, kc.shape[-1])
    o = kernel.paged_attention(q[:, 0], kc.reshape(form), vc.reshape(form),
                               tables, seen, scale=_scale(q))
    return _combine(x, o[:, None], lw, layer, eps), kc, vc


# ---------------------------------------------------------------------------
# the layer loop of a whole prompt (the model's forward, the prefill program)
# ---------------------------------------------------------------------------


def layer_of(stack, i):
    """Layer ``i``'s leaves of a weight tree whose per-layer leaves are
    tuples of the layers' own arrays, None where a layer has no such leaf."""
    return {k: a[i] for k, a in stack.items() if a[i] is not None}


def stack_of(w):
    """The per-layer leaves of the serving weight tree."""
    return {k: a for k, a in w.items() if isinstance(a, tuple)}


def layers_prefill(stack, x, keep, n_keep, *, kinds, n_heads, n_kv, eps,
                   window):
    """Every layer over a whole prompt ``x`` ``[1, T, h]`` from nothing
    (``keep`` / ``n_keep``: the rows that are tokens). Returns ``(x, ssm
    [n_mamba, ds, di], conv [n_mamba, d_conv - 1, di], the window layers'
    lines [(k, v), ...], the full layer's lines (k, v))``: what a slot
    keeps of the prompt. The memory ``m`` and the shared lines are values
    of this loop, handed from the layer that makes them to those below."""
    mem = shared = None
    ssm, conv, wlines = [], [], []
    for i, kind in enumerate(kinds):
        lw = layer_of(stack, i)
        if kind == "mamba":
            x, s, c, mem = mamba_prefill(x, lw, keep, n_keep, eps=eps)
            ssm.append(s)
            conv.append(c)
        elif kind == "gmu":
            x = gmu(x, lw, mem, eps=eps)
        else:
            x, kv = attention_prefill(
                x, lw, n_heads=n_heads, n_kv=n_kv, eps=eps, layer=i,
                window=window if kind == "sliding_attention" else None,
                shared=shared)
            if kind == "sliding_attention":
                wlines.append(kv)
            elif kind == "full_attention":
                shared = kv
    return x, jnp.stack(ssm), jnp.stack(conv), wlines, shared
