"""Operations and bytes from shapes: what the algorithm needs, whatever
the program does. Kept with the benchmark so that no later PR can move a
utilisation by recounting."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of one chip of ``device_kind``; a kind that
    the table lacks is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (it has {sorted(table)})")
    return table[device_kind]


def matmul_params(config):
    """Parameters that multiply every token: the layers' projections and
    the output head. The embedding is a lookup and the norms are
    elementwise: neither is counted."""
    h, ff = config["hidden_size"], config["intermediate_size"]
    d = config.get("head_dim") or h // config["num_attention_heads"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    layer = h * q + 2 * h * kv + q * h + 3 * h * ff
    return config["num_hidden_layers"] * layer + h * config["vocab_size"]


def total_params(config):
    h = config["hidden_size"]
    return (matmul_params(config) + h * config["vocab_size"]
            + (2 * config["num_hidden_layers"] + 1) * h)


def train_flops_per_token(config, seqlen):
    """Forward and backward operations a token of a ``seqlen`` sequence
    needs: 6 per matmul parameter, and causal attention counted as the
    half it is: QK^T and PV are 2 * 2 * L * d per head forward over the
    whole square, half of it under the causal mask, three times that with
    the backward pass. Recomputation is never counted."""
    d = config.get("head_dim") or (config["hidden_size"]
                                   // config["num_attention_heads"])
    attn = (3 * 2 * 2 * seqlen * d * config["num_attention_heads"]
            * config["num_hidden_layers"]) / 2
    return 6 * matmul_params(config) + attn
