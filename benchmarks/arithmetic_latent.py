"""Bytes and operations of a latent-attention (MLA) model with a routed
share, from shapes: what a decode-only step has to read whatever implements
its layers, and what the decode kernel reads and multiplies a line. Kept
with the benchmark, beside ``arithmetic_decode.py``, so that no later PR
can move a share by recounting. Every size comes from the configuration's
dict. A cache line is counted at its PUBLISHED size, ``kv_lora_rank +
qk_rope_head_dim`` numbers: a program that pads it reads more and shows
that as lost share."""
from __future__ import annotations

from benchmarks.arithmetic_moe import ITEMSIZE


def mla_params(config):
    """One layer's five attention matrices: q through its rank, kv through
    its rank with the shared rotary key beside it, and the output."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return (h * config["q_lora_rank"]
            + config["q_lora_rank"] * heads * (dn + dr)
            + h * (config["kv_lora_rank"] + dr)
            + config["kv_lora_rank"] * heads * (dn + config["v_head_dim"])
            + heads * config["v_head_dim"] * h)


def expert_params(config):
    """One routed expert's (and one shared expert's) three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_params(config):
    """The router over every expert it scores, and its selection bias."""
    scored = config.get("n_router_experts", config["n_routed_experts"])
    return config["hidden_size"] * scored + scored


def routed_layer_params(config, experts=0):
    """A routed layer with ``experts`` of its routed experts: attention,
    the shared experts, the router, and those."""
    return mla_params(config) + router_params(config) \
        + (config["n_shared_experts"] + experts) * expert_params(config)


def dense_layer_params(config):
    """A leading dense layer: attention and its feed-forward."""
    return mla_params(config) \
        + 3 * config["hidden_size"] * config["intermediate_size"]


def head_params(config):
    return config["hidden_size"] * config["vocab_size"]


def line_bytes(config):
    """All that is kept of one position in one layer, as published: the
    latent and the shared rotary key."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * ITEMSIZE[config["torch_dtype"]]


def routed_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def weight_bytes(config, experts_hit):
    """Every weight a decode step multiplies by, once: each layer's
    attention, the dense layers' feed-forward, each routed layer's shared
    expert and router, the held experts its rows picked (``experts_hit``:
    the distinct ones of a step, summed over the routed layers) and the
    head's slice. Norms and the embedding's rows are left out: under a
    thousandth."""
    dense = config["first_k_dense_replace"]
    params = dense * dense_layer_params(config) \
        + routed_layers(config) * routed_layer_params(config) \
        + experts_hit * expert_params(config) + head_params(config)
    return params * ITEMSIZE[config["torch_dtype"]]


def decode_step_bytes(config, lines, experts_hit):
    """The bytes one decode-only step cannot avoid reading: the weights
    and, in every layer, the ``lines`` its decode-active rows can see."""
    return weight_bytes(config, experts_hit) \
        + config["num_hidden_layers"] * lines * line_bytes(config)


def kernel_flops_a_line(config):
    """The absorbed decode attention's operations a cached line: every
    head scores the line (latent and rotary part) and sums its latent."""
    return 2 * config["num_attention_heads"] * (
        2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])


def kernel_seconds(config, lines, peaks):
    """The least time the decode kernels of one step can take for
    ``lines`` lines a layer: the larger of its bytes over the memory
    bandwidth and its operations over the bf16 peak."""
    n = config["num_hidden_layers"] * lines
    return max(n * line_bytes(config) / peaks["hbm_bytes_per_s"],
               n * kernel_flops_a_line(config) / peaks["bf16_flops_per_s"])
