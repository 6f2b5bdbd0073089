"""Bytes of a decode-only step from shapes: what the step has to read
whatever implements its layers. Kept with the benchmark, beside
``arithmetic_moe.py``, so that no later PR can move the share by
recounting. Every size comes from the configuration's dict; the lines of
K and V come from the engine's own count of what its decode-active rows
could see."""
from __future__ import annotations

from benchmarks import arithmetic_moe

ITEMSIZE = arithmetic_moe.ITEMSIZE


def _head_dim(config):
    return config.get("head_dim") or (config["hidden_size"]
                                      // config["num_attention_heads"])


def attention_bytes(config):
    """One layer's four projections, in the served type."""
    h, d = config["hidden_size"], _head_dim(config)
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    return (h * q + 2 * h * kv + q * h) * ITEMSIZE[config["torch_dtype"]]


def dense_ffn_bytes(config):
    """One layer's gate, up and down matrices."""
    return 3 * config["hidden_size"] * config["intermediate_size"] \
        * ITEMSIZE[config["torch_dtype"]]


def head_bytes(config):
    return config["hidden_size"] * config["vocab_size"] \
        * ITEMSIZE[config["torch_dtype"]]


def line_bytes(config):
    """One position's K and V in one layer."""
    return 2 * config["num_key_value_heads"] * _head_dim(config) \
        * ITEMSIZE[config["torch_dtype"]]


def weight_bytes(config, experts_hit=None):
    """Every weight a decode step multiplies by, once: each layer's
    projections and feed-forward and the head. A routed layer's
    feed-forward is the experts its rows picked and the router
    (``arithmetic_moe.routed_decode_bytes``; ``experts_hit`` the distinct
    experts of a step summed over the layers). Norms (two vectors a
    layer) and the embedding's rows (one a slot) are left out: under a
    thousandth."""
    layers = config["num_hidden_layers"]
    kinds = config.get("mlp_layer_types") or (
        ["sparse" if "num_experts" in config else "dense"] * layers)
    if len(set(kinds)) != 1:
        raise ValueError("dense and routed feed-forwards in one model are "
                         "not counted yet")
    if kinds[0] == "sparse":
        ffn = arithmetic_moe.routed_decode_bytes(config, experts_hit)
    else:
        ffn = layers * dense_ffn_bytes(config)
    return layers * attention_bytes(config) + ffn + head_bytes(config)


def kv_bytes(config, lines, lines_in_window):
    """The K and V lines a step's decode-active rows can see: ``lines``
    in a layer without a window, ``lines_in_window`` (each row cut to the
    window) in a ``sliding_attention`` layer; both summed over the rows
    (a mean over steps may be given)."""
    kinds = config.get("layer_types") \
        or ["full_attention"] * config["num_hidden_layers"]
    windowed = sum(k == "sliding_attention" for k in kinds)
    return ((len(kinds) - windowed) * lines + windowed * lines_in_window) \
        * line_bytes(config)


def decode_step_bytes(config, lines, lines_in_window, experts_hit=None):
    """The bytes one decode-only step cannot avoid reading."""
    return weight_bytes(config, experts_hit) \
        + kv_bytes(config, lines, lines_in_window)
