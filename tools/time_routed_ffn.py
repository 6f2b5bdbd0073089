"""Time one routed feed-forward layer alone, four ways, at a configuration's
widths and under its own router.

The routed layer's rows and router are those a forward of the model
(built by ``benchmarks/models.py`` from the configuration file, cut to
``--layers`` layers, weights drawn from ``--seed``) hands its last routed
layer, so the picks are skewed as the served model's are, not uniform.
At each row count of ``--rows`` (the first ``T`` rows of that layer's
input) it times the whole function (router included) in these forms:

* ``plain``: every held expert applied to every row
  (``nn/routed_ffn.py``'s form off a TPU);
* ``ragged_dot``: the picks sorted by expert through
  ``jax.lax.ragged_dot``;
* ``megablox``: the same through
  ``jax.experimental.pallas.ops.tpu.megablox.gmm``;
* ``kernel``: ``ops/pallas/grouped_swiglu.py``, as ``routed_ffn`` runs it
  on a TPU (and ``kernel_rows<n>`` with ``n`` rows a tile at most).

One JSON line a (configuration, rows, form): the median milliseconds of a
call over ``--calls`` calls dispatched back to back, the experts its rows
hit, those experts' bytes over the time, and the largest difference from
``plain``'s output over ``plain``'s largest value.

    python3 tools/time_routed_ffn.py \\
        --config benchmarks/configs/mellum2-12b-a2.5b.json \\
        --config benchmarks/configs/kimi-k2.6-ep32.json

Off a TPU pass ``--interpret``: the kernels run through the interpreter
(a rehearsal of the code paths; its times are the CPU's).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402


def _cut(config, layers):
    """The configuration at ``layers`` layers: the first of each per-layer
    list, the last of them routed."""
    out = dict(config, num_hidden_layers=layers)
    for key in ("layer_types", "mlp_layer_types"):
        if key in out:
            out[key] = out[key][:layers]
    return out


def _layer_input(config, seed, rows):
    """``(m, wr, wg, wu, wd, k, router)`` of the last routed layer of a
    forward over ``rows`` tokens drawn from ``seed``."""
    from benchmarks import models
    from paddle_tpu.nn import routed_ffn as R
    import paddle_tpu as paddle

    model = models.build(config, seed)
    seen = []
    true = R.routed_ffn

    def capture(m, wr, wg, wu, wd, k, valid=None, **router):
        seen.append((m, wr, wg, wu, wd, k, router))
        return true(m, wr, wg, wu, wd, k, valid, **router)

    ids = jax.random.randint(jax.random.PRNGKey(seed), (1, rows), 0,
                             config["vocab_size"])
    with mock.patch.object(R, "routed_ffn", capture), paddle.no_grad():
        model(paddle.to_tensor(ids))
    return seen[-1]


def _prologue(m, wr, wg, k, router):
    """``routed_ffn``'s own: the picks as indices into the banks and their
    weights, zero where a pick is not computed here."""
    from paddle_tpu.nn import routed_ffn as R
    experts, weights = R.route(m, wr, k, **{
        a: b for a, b in router.items() if a != "first"})
    held = wg.shape[0]
    local = experts - router.get("first", 0)
    here = (local >= 0) & (local < held) & (weights > 0)
    return jnp.where(here, local, held), jnp.where(here, weights, 0.0)


def _sorted(m, local):
    key = local.reshape(-1)
    order = jnp.argsort(key, stable=True)
    return key, order, m[order // local.shape[1]]


def _unsort(out, key, order, local, w, held):
    back = jnp.zeros_like(out).at[order].set(out)
    got = back.reshape(*local.shape, -1).astype(jnp.float32)
    return jnp.sum(jnp.where((local < held)[..., None], w[..., None] * got,
                             0.0), axis=1)


def _grouped_by(dot, m, local, w, wg, wu, wd):
    held = wg.shape[0]
    key, order, xs = _sorted(m, local)
    sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    act = (jax.nn.silu(dot(xs, wg, sizes).astype(jnp.float32))
           * dot(xs, wu, sizes).astype(jnp.float32)).astype(m.dtype)
    out = dot(act, wd, sizes)
    live = jnp.arange(out.shape[0]) < jnp.sum(sizes)
    out = jnp.where(live[:, None], out, 0)
    return _unsort(out, key, order, local, w, held).astype(m.dtype)


def _forms(interpret):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from paddle_tpu.nn import routed_ffn as R

    def ragged(xs, bank, sizes):
        return jax.lax.ragged_dot(xs, bank, sizes)

    def megablox(xs, bank, sizes):
        pad = -xs.shape[0] % 128
        xp = jnp.pad(xs, ((0, pad), (0, 0)))
        return gmm(xp, bank, sizes, preferred_element_type=xs.dtype,
                   interpret=interpret)[:xs.shape[0]]

    return {"plain": R.plain,
            "ragged_dot": functools.partial(_grouped_by, ragged),
            "megablox": functools.partial(_grouped_by, megablox),
            "kernel": functools.partial(R.grouped, interpret=interpret)}


def _time(fn, args, calls):
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(calls):
            res = fn(*args)
        jax.block_until_ready(res)
        times.append((time.perf_counter() - t) / calls * 1e3)
    return statistics.median(times), out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", action="append", required=True)
    ap.add_argument("--rows", default="16,32,128,512")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2147483901)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--tile-rows", default="64,256",
                    help="further most rows a tile to time the kernel at")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", help="a file to append the lines to too")
    a = ap.parse_args(argv)
    from paddle_tpu.ops.pallas import grouped_swiglu as gs

    rows = [int(r) for r in a.rows.split(",")]
    device = jax.devices()[0]
    with open(a.out or os.devnull, "a") as sink:
        for path in a.config:
            with open(path) as f:
                config = _cut(json.load(f), a.layers)
            m, wr, wg, wu, wd, k, router = _layer_input(config, a.seed,
                                                        max(rows))
            held, h, ff = wg.shape
            forms = _forms(a.interpret)
            for T in rows:
                local, w = _prologue(m[:T], wr, wg, k, router)
                hit = int(jnp.sum(jnp.any(
                    local.reshape(-1)[:, None] == jnp.arange(held), 0)))
                picks = int(jnp.sum(local < held))
                variants = [(n, f, None) for n, f in forms.items()] + [
                    (f"kernel_rows{r}", forms["kernel"], r)
                    for r in map(int, a.tile_rows.split(",")) if r]
                base = None
                for name, form, tile_rows in variants:
                    def whole(m, wr, wg, wu, wd, form=form):
                        local, w = _prologue(m, wr, wg, k, router)
                        return form(m, local, w, wg, wu, wd)

                    patch = mock.patch.object(gs, "_ROWS", tile_rows) \
                        if tile_rows else mock.patch.object(gs, "_ROWS",
                                                            gs._ROWS)
                    with patch:
                        ms, y = _time(jax.jit(whole),
                                      (m[:T], wr, wg, wu, wd), a.calls)
                    y = y.astype(jnp.float32)
                    if base is None:
                        base = y
                    err = float(jnp.max(jnp.abs(y - base))
                                / jnp.maximum(jnp.max(jnp.abs(base)), 1e-30))
                    line = {"config": config["name"], "rows": T,
                            "form": name, "ms": ms, "picks_here": picks,
                            "experts_hit": hit, "held": held,
                            "hit_bytes_per_s": hit * 3 * h * ff
                            * wg.dtype.itemsize / (ms * 1e-3),
                            "max_rel_diff_from_plain": err,
                            "device": device.device_kind}
                    print(json.dumps(line), flush=True)
                    sink.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
