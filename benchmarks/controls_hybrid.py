#!/usr/bin/env python3
"""Controls of the ``correct`` of a serve cell whose model keeps three
kinds of state (recurrent layers, window layers, one full layer that
query-only layers read; gated memory units): ``controls.py``'s procedure
(the cell's model and engine as ``run.py`` builds them, ONE fault planted
in the PROGRAM, the driver's own reference check) with this model's faults.

    python3 benchmarks/controls_hybrid.py --workload <cell> --seed <n> --fault <name>

Every fault but ``none`` has to come out ``correct: false``; on the chip
at the published sizes all do but ``window_one_too_long`` (PERF.md section
6, PR 35, has each reading):

``state_not_carried``  a chunk's scan starts from zeros, not from the state
                       and the convolution inputs the last chunk left;
``window_one_too_long``  a row of a prefill or a chunk sees one line more
                       than the window (decode reads a ring of ``window``
                       lines and cannot). At a window of 512 that is two
                       thousandths of a row's weights: it flips a token or
                       none and reads ``correct: true`` on the chip; at the
                       tiny cell's window of 16 it fails. The ring's size
                       is what bounds the window;
``no_window``          such a row sees every earlier line it can reach;
``zero_memory``        the gated units multiply by a memory of zeros;
``stale_lines``        in decode the query-only layers read the pool
                       through the NEXT slot's block table: another
                       request's lines, as a previous tenant's would be;
``lambda_dropped``     the second map is not subtracted (lambda 0);
``eight_bit_activations``  the program in the nearest precision below the
                       bfloat16 it is served in: every LayerNorm's output,
                       which is what each projection reads, is rounded to
                       ``float8_e4m3fn`` and widened again. The reading
                       from above of the comparison's limit.
"""
from __future__ import annotations

import contextlib
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import controls  # noqa: E402


def _state_not_carried():
    import jax.numpy as jnp
    from paddle_tpu.text import sambay

    true = sambay.mamba_chunk
    return mock.patch.object(
        sambay, "mamba_chunk", lambda x, lw, ssm, conv, *a, **kw: true(
            x, lw, jnp.zeros_like(ssm), jnp.zeros_like(conv), *a, **kw))


def _window(change):
    from paddle_tpu.text import sambay

    true = sambay._sees
    return mock.patch.object(
        sambay, "_sees", lambda qpos, kpos, window=None: true(
            qpos, kpos, window and change(window)))


def _zero_memory():
    import jax.numpy as jnp
    from paddle_tpu.text import sambay

    true = sambay.gmu
    return mock.patch.object(
        sambay, "gmu", lambda x, lw, m, **kw: true(x, lw, jnp.zeros_like(m),
                                                   **kw))


def _stale_lines():
    import jax.numpy as jnp
    from paddle_tpu.text import sambay

    true = sambay.attention_decode

    def decode(x, lw, kc, vc, tables, *args, **kw):
        if "wq" in lw:                       # a query-only layer
            tables = jnp.roll(tables, 1, axis=0)
        return true(x, lw, kc, vc, tables, *args, **kw)

    return mock.patch.object(sambay, "attention_decode", decode)


def _lambda_dropped():
    import jax.numpy as jnp
    from paddle_tpu.text import sambay

    return mock.patch.object(sambay, "_lambda",
                             lambda lw, layer: jnp.float32(0.0))


def _eight_bit_activations():
    import jax.numpy as jnp
    from paddle_tpu.text import generation

    true = generation._ln

    def ln(x, w, b, eps=1e-5):
        y = true(x, w, b, eps)
        return y.astype(jnp.float8_e4m3fn).astype(y.dtype)

    return mock.patch.object(generation, "_ln", ln)


FAULTS = {"none": contextlib.nullcontext,
          "state_not_carried": _state_not_carried,
          "window_one_too_long": lambda: _window(lambda w: w + 1),
          "no_window": lambda: _window(lambda w: None),
          "zero_memory": _zero_memory,
          "stale_lines": _stale_lines,
          "lambda_dropped": _lambda_dropped,
          "eight_bit_activations": _eight_bit_activations}


def main(argv=None):
    with mock.patch.object(controls, "FAULTS", FAULTS):
        return controls.main(argv)


if __name__ == "__main__":
    sys.exit(main())
