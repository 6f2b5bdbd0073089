"""Mellum-2 family: a pre-norm decoder whose layers differ in kind.

What it has that ``llama.py`` has not, each as the published
``config.json`` names it:

- ``head_dim`` is a field (128 under a hidden size of 2304 and 32 heads:
  not ``hidden_size // num_attention_heads``);
- ``layer_types`` gives each layer its attention: ``sliding_attention``
  sees itself and the ``sliding_window - 1`` positions before it,
  ``full_attention`` everything before it;
- ``rope_parameters`` gives each kind its rotary table: the plain one, or
  a static YaRN table whose cos and sin carry an ``attention_factor``;
- the feed-forward is routed (``mlp_layer_types`` all ``sparse``):
  ``num_experts`` experts of width ``moe_intermediate_size``, the
  ``num_experts_per_tok`` largest of a float32 softmax, renormalised,
  every pick computed (``nn/routed_ffn.py``: no capacity, no drop).

The layer's mathematics is ``text/generation.py``'s llama bodies with a
window, a rotary table and a routed feed-forward given as data: the
model's ``forward`` and the serving engine's prefill, chunk and decode
programs trace the same python. Expert banks are single batched
parameters ``[E, h, f]`` / ``[E, f, h]``.

Not here, and refused or absent rather than approximated: q/k
normalisation, shared experts, router bias or score correction, the
multi-token-prediction head, a load-balancing loss (the published config
has no key for any of them), attention biases, dense feed-forward layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...nn import Embedding, Linear, RMSNorm
from ...nn import functional as F
from ...nn.initializer import XavierUniform
from ...nn.layer.container import LayerList
from ...nn.layer_base import Layer
from ...tensor import apply
from ...tensor_ops.manipulation import reshape

SLIDING, FULL = "sliding_attention", "full_attention"


def _default_rope():
    return {FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                   "original_max_position_embeddings": 8192,
                   "beta_fast": 32, "beta_slow": 1,
                   "attention_factor": 1.2772588722239782},
            SLIDING: {"rope_type": "default", "rope_theta": 500000}}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168      # published; no layer is dense
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    layer_types: list = field(default_factory=lambda: (
        [SLIDING] * 3 + [FULL]) * 7)
    mlp_layer_types: list = field(default_factory=lambda: ["sparse"] * 28)
    sliding_window: int = 1024
    use_sliding_window: bool = True
    rope_parameters: dict = field(default_factory=_default_rope)
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.layer_types) != n or len(self.mlp_layer_types) != n:
            raise ValueError(
                f"layer_types ({len(self.layer_types)}) and mlp_layer_types "
                f"({len(self.mlp_layer_types)}) need one entry for each of "
                f"the {n} layers")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"unknown layer_types {sorted(unknown)}")
        for what, ok in (
                ("mlp_layer_types other than 'sparse' (a dense layer)",
                 set(self.mlp_layer_types) <= {"sparse"}),
                ("attention_bias", not self.attention_bias),
                ("norm_topk_prob false", self.norm_topk_prob),
                ("tie_word_embeddings", not self.tie_word_embeddings),
                (f"hidden_act {self.hidden_act!r}", self.hidden_act == "silu"),
                ("use_sliding_window false with sliding layers",
                 self.use_sliding_window or SLIDING not in self.layer_types)):
            if not ok:
                raise ValueError(f"MellumConfig: {what} is not implemented")


MELLUM_TINY = MellumConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, num_experts=8, num_experts_per_tok=2,
    layer_types=[SLIDING] * 3 + [FULL], mlp_layer_types=["sparse"] * 4,
    sliding_window=8, max_position_embeddings=512,
    rope_parameters={
        FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
               "original_max_position_embeddings": 16, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.1386294361119891},
        SLIDING: {"rope_type": "default", "rope_theta": 10000}},
    dtype="float32")


def rope_table(params, head_dim):
    """``(inv_freq [head_dim / 2] float32, factor)`` of one layer kind's
    ``rope_parameters`` entry: the angle of pair ``i`` at position ``p``
    is ``p * inv_freq[i]``, and cos and sin are multiplied by ``factor``.

    ``default``: ``theta ** (-2i / d)``, factor 1. ``yarn`` (static, the
    same table at every position): pairs that turn more than
    ``beta_fast`` times within the original length keep their frequency,
    those that turn less than ``beta_slow`` times have it divided by
    ``factor``, and between the two pair indices (``low``, rounded down,
    and ``high``, rounded up) the two are mixed linearly."""
    d = head_dim
    base = float(params["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    kind = params.get("rope_type", "default")
    if kind == "default":
        return base.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not implemented")
    original = params["original_max_position_embeddings"]

    def pair_turning(n):
        return d * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(params["rope_theta"]))

    low = max(math.floor(pair_turning(params["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(params["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = (1 - ramp) * base + ramp * base / params["factor"]
    factor = params.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(params["factor"]) + 1.0
    return inv.astype(np.float32), float(factor)


def _layer_math(x, ln1, wq, wk, wv, wo, ln2, wr, wg, wu, wd, rope_inv,
                rope_scale, *, n_heads, n_kv, eps, window, moe_k):
    from .. import generation as G

    lw = {"ln1": ln1, "wq": wq, "wk": wk, "wv": wv, "wo": wo, "ln2": ln2,
          "wr": wr, "wg": wg, "wu": wu, "wd": wd, "rope_inv": rope_inv,
          "rope_scale": rope_scale}
    return G._llama_prefill_layer(
        x, lw, jnp.arange(x.shape[1]), n_heads=n_heads, n_kv=n_kv, eps=eps,
        theta=0.0, window=window, moe_k=moe_k)[0]


class MellumAttention(Layer):
    def __init__(self, c: MellumConfig):
        super().__init__()
        h, q = c.hidden_size, c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        self.q_proj = Linear(h, q, bias_attr=False)
        self.k_proj = Linear(h, kv, bias_attr=False)
        self.v_proj = Linear(h, kv, bias_attr=False)
        self.o_proj = Linear(q, h, bias_attr=False)
        self.q_proj.weight.pspec = P(None, "tp")
        self.k_proj.weight.pspec = P(None, "tp")
        self.v_proj.weight.pspec = P(None, "tp")
        self.o_proj.weight.pspec = P("tp", None)


class MellumExperts(Layer):
    """The expert banks, one batched parameter each."""

    def __init__(self, c: MellumConfig):
        super().__init__()
        e, h, f = c.num_experts, c.hidden_size, c.moe_intermediate_size
        # each expert drawn as a Linear of its own shape would be
        wide, narrow = XavierUniform(fan_in=h, fan_out=f), \
            XavierUniform(fan_in=f, fan_out=h)
        self.gate_proj = self.create_parameter(
            (e, h, f), default_initializer=wide)
        self.up_proj = self.create_parameter(
            (e, h, f), default_initializer=wide)
        self.down_proj = self.create_parameter(
            (e, f, h), default_initializer=narrow)


class MellumSparseMLP(Layer):
    def __init__(self, c: MellumConfig):
        super().__init__()
        self.gate = Linear(c.hidden_size, c.num_experts, bias_attr=False)
        self.experts = MellumExperts(c)


class MellumDecoderLayer(Layer):
    def __init__(self, c: MellumConfig, kind):
        super().__init__()
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = MellumAttention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                c.rms_norm_eps)
        self.mlp = MellumSparseMLP(c)
        # numpy, not jax: a model may be constructed under a trace
        inv, scale = rope_table(c.rope_parameters[kind], c.head_dim)
        self._rope = (inv, np.float32(scale))
        self._statics = dict(
            n_heads=c.num_attention_heads, n_kv=c.num_key_value_heads,
            eps=c.rms_norm_eps, moe_k=c.num_experts_per_tok,
            window=c.sliding_window if kind == SLIDING else None)

    def weights(self):
        """The layer's parameters in the order ``_layer_math`` takes."""
        a, m = self.self_attn, self.mlp
        return (self.input_layernorm.weight, a.q_proj.weight,
                a.k_proj.weight, a.v_proj.weight, a.o_proj.weight,
                self.post_attention_layernorm.weight, m.gate.weight,
                m.experts.gate_proj, m.experts.up_proj, m.experts.down_proj)

    def forward(self, x):
        return apply(_layer_math, x, *self.weights(), *self._rope,
                     **self._statics)


class MellumModel(Layer):
    def __init__(self, c: MellumConfig):
        super().__init__()
        self.config = c
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size)
        self.embed_tokens.weight.pspec = P("tp", None)
        self.layers = LayerList([MellumDecoderLayer(c, kind)
                                 for kind in c.layer_types])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class MellumForCausalLM(Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.mellum = MellumModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)
        self.lm_head.weight.pspec = P(None, "tp")
        if config.dtype == "bfloat16":
            self.to(dtype="bfloat16")

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.mellum(input_ids))
        if labels is None:
            return logits
        # next-token prediction: logits at t score labels at t+1
        return F.cross_entropy(
            reshape(logits[:, :-1],
                    (-1, self.config.vocab_size)).astype("float32"),
            reshape(labels[:, 1:], (-1,)))

    def stacked_weights(self):
        """The serving engine's weight tree (``generation._LLAMA_STACK_KEYS``
        plus the router, the expert banks and each layer's rotary table).
        Every layer's leaf is stacked ``[L, ...]`` but the expert banks:
        those stay the layers' own arrays, a tuple of ``L``, shared with
        the model: nine tenths of the weights are held once, and the
        engine's layer loop is unrolled over them."""
        names = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wr", "wg", "wu",
                 "wd")
        banks = ("wg", "wu", "wd")
        per_layer = [l.weights() for l in self.mellum.layers]
        w = {n: (tuple if n in banks else jnp.stack)(
            [ws[i]._data for ws in per_layer]) for i, n in enumerate(names)}
        w["rope_inv"] = jnp.asarray(
            np.stack([l._rope[0] for l in self.mellum.layers]))
        w["rope_scale"] = jnp.asarray(
            np.stack([l._rope[1] for l in self.mellum.layers]))
        w["embed"] = self.mellum.embed_tokens.weight._data
        w["norm"] = self.mellum.norm.weight._data
        w["head"] = self.lm_head.weight._data
        return w
