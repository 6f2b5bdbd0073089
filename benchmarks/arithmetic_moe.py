"""Bytes of a routed feed-forward from shapes: what a decode step has to
read whatever implements the layer. Kept with the benchmark so that no
later PR can move a roofline share by recounting. Every size comes from
the configuration's dict."""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def expert_bytes(config):
    """One expert's three matrices (gate, up, down) in the served type."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * ITEMSIZE[config["torch_dtype"]]


def router_bytes(config):
    return config["hidden_size"] * config["num_experts"] \
        * ITEMSIZE[config["torch_dtype"]]


def routed_decode_bytes(config, experts_hit):
    """The bytes one decode step's routed feed-forwards cannot avoid
    reading: in each layer every expert that at least one row picked,
    once, and the router. ``experts_hit`` is the distinct experts a step
    touched, summed over the layers (a mean over steps may be given).
    Activations are left out: 16 rows of them are a thousandth of one
    expert."""
    return experts_hit * expert_bytes(config) \
        + config["num_hidden_layers"] * router_bytes(config)


def expected_experts_hit(config, rows):
    """Distinct experts a layer's step touches when ``rows`` rows pick
    ``num_experts_per_tok`` experts each, uniformly and independently."""
    e, k = config["num_experts"], config["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)
