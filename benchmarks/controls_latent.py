#!/usr/bin/env python3
"""Controls of a latent serve cell's ``correct``: ``controls.py``'s
procedure (the cell's model and engine as ``run.py`` builds them, ONE
fault planted in the PROGRAM, the driver's own reference check) with the
faults of a model with latent attention and a routed share beside a shared
expert.

    python3 benchmarks/controls_latent.py --workload <cell> --seed <n> --fault <name>

Every fault but ``none`` has to come out ``correct: false``:

``no_rotary_score``    the rotary part of the score is left out (``q_pe .
                       k_pe``: both come back zero from the rotation);
``no_mscale``          the softmax scale without ``m(mscale_all_dim)**2``;
``no_selection_bias``  the 8 largest of the scores, not of score + bias;
``no_routed_scale``    ``routed_scaling_factor`` left out of the weights;
``no_shared_expert``   the shared expert's part left out;
``no_latent_norm``     ``kv_a_layernorm`` skipped: the raw latent is cached
                       and expanded;
``eight_bit_activations``  ``controls.py``'s: the program in the nearest
                       precision below the one it is served in;
``altered_token``      ``controls.py``'s: one token altered as the engine
                       emits it, one row far under the best, which the
                       count of rows over the tolerance lets through and
                       the ceiling does not.
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


def _no_rotary_score():
    import jax.numpy as jnp
    from paddle_tpu.text import generation

    def rotate(q, k, *args, **kwargs):
        return jnp.zeros_like(q), jnp.zeros_like(k)

    return mock.patch.object(generation, "_rotate", rotate)


def _no_mscale():
    from paddle_tpu.text.models import kimi_k2

    true = kimi_k2.KimiK2Config.rope

    def rope(self):
        inv, factor, _ = true(self)
        return inv, factor, (self.qk_nope_head_dim
                             + self.qk_rope_head_dim) ** -0.5

    return mock.patch.object(kimi_k2.KimiK2Config, "rope", rope)


def _no_selection_bias():
    from paddle_tpu.nn import routed_ffn

    true = routed_ffn.route
    return mock.patch.object(
        routed_ffn, "route", lambda m, wr, k, **kw: true(
            m, wr, k, **{**kw, "bias": None}))


def _no_routed_scale():
    from paddle_tpu.nn import routed_ffn

    true = routed_ffn.route
    return mock.patch.object(
        routed_ffn, "route", lambda m, wr, k, **kw: true(
            m, wr, k, **{**kw, "scale": 1.0}))


def _no_shared_expert():
    from paddle_tpu.text import generation

    true = generation._feed_forward

    def feed_forward(h2, lw, *args, **kwargs):
        return true(h2, {k: a for k, a in lw.items()
                         if k not in ("sg", "su", "sd")}, *args, **kwargs)

    return mock.patch.object(generation, "_feed_forward", feed_forward)


def _no_latent_norm():
    from paddle_tpu.text import generation

    true = generation._latent_project

    def project(h1, lw, pos, **kwargs):
        q_nope, q_pe, c, k_pe = true(h1, lw, pos, **kwargs)
        return q_nope, q_pe, (h1 @ lw["wkva"])[..., :c.shape[-1]], k_pe

    return mock.patch.object(generation, "_latent_project", project)


FAULTS = {"none": contextlib.nullcontext,
          "no_rotary_score": _no_rotary_score,
          "no_mscale": _no_mscale,
          "no_selection_bias": _no_selection_bias,
          "no_routed_scale": _no_routed_scale,
          "no_shared_expert": _no_shared_expert,
          "no_latent_norm": _no_latent_norm,
          "eight_bit_activations": controls.FAULTS["eight_bit_activations"],
          "altered_token": controls.FAULTS["altered_token"]}


def main(argv=None):
    with mock.patch.object(controls, "FAULTS", FAULTS):
        return controls.main(argv)


if __name__ == "__main__":
    sys.exit(main())
