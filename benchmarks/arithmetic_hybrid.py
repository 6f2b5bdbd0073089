"""Bytes of a decoder of recurrent, window, one full and query-only layers
(a configuration with ``mb_per_layer``), from shapes: what a decode-only
step has to read and write whatever implements its layers. Kept with the
benchmark, beside ``arithmetic_decode.py``, so that no later PR can move a
share by recounting. Every size comes from the configuration's dict; the
recurrent sizes are the keys it lists under ``assumed``
(``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank``).
"""
from __future__ import annotations

from benchmarks.arithmetic_moe import ITEMSIZE


def layer_counts(config):
    """How many layers of each kind: recurrent, window, full, gated unit,
    query only (``mb_per_layer`` 2: every even layer is recurrent up to
    the middle one, a gated unit after it; the odd ones attend, the one
    after the middle over the whole context, later ones with queries
    alone)."""
    depth, mb = config["num_hidden_layers"], config["mb_per_layer"]
    half = depth // 2
    even_low = len([i for i in range(half + 1) if i % mb == 0])
    even_high = len([i for i in range(half + 2, depth) if i % mb == 0])
    return {"mamba": even_low, "window": half + 1 - even_low, "full": 1,
            "gmu": even_high, "cross": depth - half - 2 - even_high}


def d_inner(config):
    return config["mamba_expand"] * config["hidden_size"]


def mlp_params(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def attention_params(config, queries_only=False):
    h = config["hidden_size"]
    hd = h // config["num_attention_heads"]
    kv = 0 if queries_only else 2 * config["num_key_value_heads"] * hd
    return h * (h + kv) + h * h


def mamba_params(config):
    h, di = config["hidden_size"], d_inner(config)
    ds, r = config["mamba_d_state"], config["mamba_dt_rank"]
    return (h * 2 * di + config["mamba_d_conv"] * di + di
            + di * (r + 2 * ds) + r * di + di + di * ds + di + di * h)


def gmu_params(config):
    return 2 * config["hidden_size"] * d_inner(config)


def embedding_params(config):
    """The embedding, which is the head (tied)."""
    return config["vocab_size"] * config["hidden_size"]


def total_params(config):
    n = layer_counts(config)
    return (config["num_hidden_layers"] * mlp_params(config)
            + (n["window"] + n["full"]) * attention_params(config)
            + n["cross"] * attention_params(config, True)
            + n["mamba"] * mamba_params(config)
            + n["gmu"] * gmu_params(config) + embedding_params(config))


def weight_bytes(config):
    """Every weight a decode step multiplies by, once: the mixers, the
    MLPs and the head. Norms, lambdas and the embedding's looked-up rows
    are left out: under a thousandth."""
    return total_params(config) * ITEMSIZE[config["torch_dtype"]]


def line_bytes(config):
    """The keys and values of one position in one layer."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * hd \
        * ITEMSIZE[config["torch_dtype"]]


def state_bytes(config):
    """What ONE slot's recurrent layers carry: the float32 state and the
    convolution's last inputs, over the recurrent layers."""
    di = d_inner(config)
    return layer_counts(config)["mamba"] * (
        di * config["mamba_d_state"] * 4
        + (config["mamba_d_conv"] - 1) * di * ITEMSIZE[config["torch_dtype"]])


def cache_bytes(config, slots, max_len):
    """What an engine of ``slots`` slots of ``max_len`` positions holds,
    by kind: the pool's one layer, the window layers' rings, the states
    (trash blocks left out)."""
    n = layer_counts(config)
    return {"pool_bytes": n["full"] * slots * max_len * line_bytes(config),
            "window_bytes": n["window"] * slots * config["sliding_window"]
            * line_bytes(config),
            "state_bytes": slots * state_bytes(config)}


def decode_step_bytes(config, lines, lines_in_window, rows):
    """The bytes one decode-only step cannot avoid: every weight once; the
    ``lines`` its ``rows`` decode-active rows can see in the one KV layer,
    read by the full layer and by every query-only layer; the same cut to
    the window (``lines_in_window``) in every window layer; each row's
    recurrent states read and written."""
    n = layer_counts(config)
    return (weight_bytes(config)
            + (n["full"] + n["cross"]) * lines * line_bytes(config)
            + n["window"] * lines_in_window * line_bytes(config)
            + 2 * rows * state_bytes(config))
