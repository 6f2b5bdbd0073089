"""Kimi-K2 family (``model_type`` ``kimi_k2``; the DeepSeek-V3 block): a
pre-norm decoder with latent attention and a routed feed-forward beside a
shared expert, after leading dense layers.

What it has that ``llama.py`` and ``mellum.py`` have not, each as the
published ``config.json`` names it:

- attention is latent (MLA): q through ``q_lora_rank`` and a norm, K and V
  through ``kv_lora_rank`` and a norm; a head's key is ``qk_nope_head_dim``
  numbers expanded from the latent beside ``qk_rope_head_dim`` rotary
  numbers that ALL heads share, its value ``v_head_dim`` numbers. What a
  cache keeps of a position is the normed latent and the rotated shared
  key, nothing per head;
- the rotary part is under static YaRN (``rope_scaling``), whose ``mscale``
  enters the softmax scale squared;
- the first ``first_k_dense_replace`` layers have a dense feed-forward of
  ``intermediate_size``, every later one a routed one: a sigmoid score for
  each of the router's experts, ``e_score_correction_bias`` added for the
  selection alone (``topk_method`` ``noaux_tc``), the ``num_experts_per_tok``
  largest, their scores renormalised and times ``routed_scaling_factor``;
  and ``n_shared_experts`` shared experts that every token passes.

**A share of the experts.** ``n_routed_experts`` counts the experts HELD:
the banks are ``[n_routed_experts, h, f]``. ``n_router_experts`` (None:
all are held) is how many the router scores, and ``first_routed_expert``
the index of the first held one: the layer routes over all, computes what
its own experts give (``nn/routed_ffn.py``) and leaves the rest out. With
every expert held it is the whole layer.

The layer's mathematics is ``text/generation.py``'s latent bodies: the
model's ``forward`` and the serving engine's prefill, chunk and decode
programs trace the same python (decode in the absorbed form, which exists
in decode alone).

Not here, and refused or absent rather than approximated: group-limited
routing (``n_group`` / ``topk_group`` above 1), a multi-token-prediction
layer, the auxiliary losses, the vision tower, attention biases. The
published checkpoints interleave the rotary pairs; this program rotates
split halves, so a loader permutes ``q_b_proj``'s and ``kv_a_proj``'s
rotary columns (``convert.py`` is where that belongs).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...nn import Embedding, Linear, RMSNorm
from ...nn import functional as F
from ...nn.initializer import Normal, XavierUniform
from ...nn.layer.container import LayerList
from ...nn.layer_base import Layer, ParamAttr
from ...tensor import apply
from ...tensor_ops.manipulation import reshape
from .mellum import rope_table


def _default_rope():
    return {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 384        # held here (see the module's text)
    n_router_experts: int | None = None     # scored; None: all are held
    first_routed_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.827
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 50000
    rope_scaling: dict = field(default_factory=_default_rope)
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_router_experts is None:
            self.n_router_experts = self.n_routed_experts
        last = self.first_routed_expert + self.n_routed_experts
        if self.first_routed_expert < 0 or last > self.n_router_experts:
            raise ValueError(
                f"experts {self.first_routed_expert}..{last - 1} are held "
                f"of the {self.n_router_experts} the router scores")
        for what, ok in (
                ("attention_bias", not self.attention_bias),
                ("norm_topk_prob false", self.norm_topk_prob),
                ("tie_word_embeddings", not self.tie_word_embeddings),
                (f"hidden_act {self.hidden_act!r}", self.hidden_act == "silu"),
                (f"scoring_func {self.scoring_func!r}",
                 self.scoring_func in ("sigmoid", "softmax")),
                (f"topk_method {self.topk_method!r}",
                 self.topk_method == "noaux_tc"),
                ("group-limited routing (n_group or topk_group above 1)",
                 self.n_group == 1 and self.topk_group == 1),
                ("moe_layer_freq other than 1", self.moe_layer_freq == 1),
                ("a multi-token-prediction layer",
                 not self.num_nextn_predict_layers),
                (f"rope_scaling type {self.rope_scaling.get('type')!r}",
                 self.rope_scaling.get("type") == "yarn")):
            if not ok:
                raise ValueError(f"KimiK2Config: {what} is not implemented")

    def layer_kinds(self):
        """``dense`` for the leading layers, ``routed`` for the others."""
        k = min(self.first_k_dense_replace, self.num_hidden_layers)
        return ("dense",) * k + ("routed",) * (self.num_hidden_layers - k)

    def router(self):
        """The further arguments of ``nn.routed_ffn.routed_ffn``, as pairs
        (a static of the serving programs)."""
        return (("first", self.first_routed_expert),
                ("scale", float(self.routed_scaling_factor)),
                ("scoring", self.scoring_func))

    def rope(self):
        """``(inv_freq [qk_rope_head_dim / 2], factor on cos and sin,
        softmax scale)``. With ``m(k) = 0.1 k ln(factor) + 1``: cos and
        sin times ``m(mscale) / m(mscale_all_dim)``, and the scores times
        ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5 *
        m(mscale_all_dim) ** 2``."""
        s = self.rope_scaling

        def m(k):
            return 0.1 * k * math.log(s["factor"]) + 1.0 \
                if s["factor"] > 1 else 1.0

        inv, factor = rope_table(
            dict(s, rope_type="yarn", rope_theta=self.rope_theta,
                 attention_factor=m(s.get("mscale", 1))
                 / m(s.get("mscale_all_dim", 0))), self.qk_rope_head_dim)
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if s.get("mscale_all_dim", 0):
            scale *= m(s["mscale_all_dim"]) ** 2
        return inv, factor, scale


KIMI_K2_TINY = KimiK2Config(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, max_position_embeddings=512,
    rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16},
    dtype="float32")


def _layer_math(x, rope_inv, rope_scale, *leaves, names, **statics):
    from .. import generation as G

    lw = dict(zip(names, leaves), rope_inv=rope_inv, rope_scale=rope_scale)
    return G._latent_prefill_layer(x, lw, np.arange(x.shape[1]),
                                   **statics)[0]


def _up_projection(rank, width):
    """A projection out of a low rank, drawn by its fan-in alone (variance
    ``1 / rank``): its outputs are as large as its normed inputs. Xavier's
    fan-out term would shrink ``kv_b_proj``'s 33-fold at the published
    sizes (512 into 16384), and q, keys and values a tenth of what the
    softmax scale assumes make attention three thousandths of the residual
    stream: no fault in it could be seen in any logit (my chip run, PR 32:
    three planted attention faults read ``correct``)."""
    return Linear(rank, width, bias_attr=False, weight_attr=ParamAttr(
        initializer=Normal(0.0, rank ** -0.5)))


class KimiK2Attention(Layer):
    def __init__(self, c: KimiK2Config):
        super().__init__()
        h, heads = c.hidden_size, c.num_attention_heads
        self.q_a_proj = Linear(h, c.q_lora_rank, bias_attr=False)
        self.q_a_layernorm = RMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = _up_projection(
            c.q_lora_rank, heads * (c.qk_nope_head_dim + c.qk_rope_head_dim))
        self.kv_a_proj_with_mqa = Linear(
            h, c.kv_lora_rank + c.qk_rope_head_dim, bias_attr=False)
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = _up_projection(
            c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = Linear(heads * c.v_head_dim, h, bias_attr=False)


class KimiK2MLP(Layer):
    """A dense SwiGLU: layer 0's feed-forward, and the shared expert."""

    def __init__(self, hidden, width):
        super().__init__()
        self.gate_proj = Linear(hidden, width, bias_attr=False)
        self.up_proj = Linear(hidden, width, bias_attr=False)
        self.down_proj = Linear(width, hidden, bias_attr=False)

    def weights(self):
        return (self.gate_proj.weight, self.up_proj.weight,
                self.down_proj.weight)


class KimiK2Experts(Layer):
    """The banks of the experts held here, one batched parameter each."""

    def __init__(self, c: KimiK2Config):
        super().__init__()
        e, h, f = c.n_routed_experts, c.hidden_size, c.moe_intermediate_size
        wide, narrow = XavierUniform(fan_in=h, fan_out=f), \
            XavierUniform(fan_in=f, fan_out=h)
        self.gate_proj = self.create_parameter(
            (e, h, f), default_initializer=wide)
        self.up_proj = self.create_parameter(
            (e, h, f), default_initializer=wide)
        self.down_proj = self.create_parameter(
            (e, f, h), default_initializer=narrow)

    def weights(self):
        return (self.gate_proj, self.up_proj, self.down_proj)


class KimiK2Gate(Layer):
    """The router over ALL the layer's experts, held here or not, and its
    selection bias (drawn, so that the term is live: a trained model's is
    whatever balancing left)."""

    def __init__(self, c: KimiK2Config):
        super().__init__()
        self.weight = self.create_parameter(
            (c.hidden_size, c.n_router_experts),
            default_initializer=XavierUniform())
        self.e_score_correction_bias = self.create_parameter(
            (c.n_router_experts,),
            default_initializer=Normal(0.0, c.initializer_range))


class KimiK2MoE(Layer):
    def __init__(self, c: KimiK2Config):
        super().__init__()
        self.gate = KimiK2Gate(c)
        self.experts = KimiK2Experts(c)
        self.shared_experts = KimiK2MLP(
            c.hidden_size, c.moe_intermediate_size * c.n_shared_experts)


class KimiK2DecoderLayer(Layer):
    def __init__(self, c: KimiK2Config, kind):
        super().__init__()
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = KimiK2Attention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                c.rms_norm_eps)
        self.mlp = KimiK2MoE(c) if kind == "routed" \
            else KimiK2MLP(c.hidden_size, c.intermediate_size)
        # numpy, not jax: a model may be constructed under a trace
        inv, factor, scale = c.rope()
        self._rope = (inv, np.float32(factor))
        self._statics = dict(
            n_heads=c.num_attention_heads, eps=c.rms_norm_eps,
            attn_scale=scale, moe_k=c.num_experts_per_tok,
            router=c.router())

    def weights(self):
        """``{name: parameter}`` under ``generation._LATENT_KEYS``' names."""
        a, m = self.self_attn, self.mlp
        w = {"ln1": self.input_layernorm.weight,
             "wqa": a.q_a_proj.weight, "qln": a.q_a_layernorm.weight,
             "wqb": a.q_b_proj.weight, "wkva": a.kv_a_proj_with_mqa.weight,
             "kvln": a.kv_a_layernorm.weight, "wkvb": a.kv_b_proj.weight,
             "wo": a.o_proj.weight,
             "ln2": self.post_attention_layernorm.weight}
        if isinstance(m, KimiK2MLP):
            return dict(w, **dict(zip(("wg", "wu", "wd"), m.weights())))
        return dict(
            w, wr=m.gate.weight, rb=m.gate.e_score_correction_bias,
            **dict(zip(("wg", "wu", "wd"), m.experts.weights())),
            **dict(zip(("sg", "su", "sd"), m.shared_experts.weights())))

    def forward(self, x):
        w = self.weights()
        return apply(_layer_math, x, *self._rope, *w.values(),
                     names=tuple(w), **self._statics)


class KimiK2Model(Layer):
    def __init__(self, c: KimiK2Config):
        super().__init__()
        self.config = c
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size)
        self.embed_tokens.weight.pspec = P("tp", None)
        self.layers = LayerList([KimiK2DecoderLayer(c, kind)
                                 for kind in c.layer_kinds()])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class KimiK2ForCausalLM(Layer):
    def __init__(self, config: KimiK2Config):
        super().__init__()
        self.config = config
        self.model = KimiK2Model(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)
        self.lm_head.weight.pspec = P(None, "tp")
        if config.dtype == "bfloat16":
            self.to(dtype="bfloat16")

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is None:
            return logits
        # next-token prediction: logits at t score labels at t+1
        return F.cross_entropy(
            reshape(logits[:, :-1],
                    (-1, self.config.vocab_size)).astype("float32"),
            reshape(labels[:, 1:], (-1,)))

    def stacked_weights(self):
        """The serving engine's weight tree (``generation._LATENT_KEYS``).
        Every layer's leaf is a tuple of the layers' OWN arrays, ``None``
        where a layer has no such leaf (the dense layer has no router, a
        routed one banks where the dense one has matrices): nothing is
        stacked, so every weight is held once, shared with the model, and
        the engine's layer loop is unrolled over the kinds."""
        per_layer = [l.weights() for l in self.model.layers]
        names = dict.fromkeys(n for ws in per_layer for n in ws)
        w = {n: tuple(ws[n]._data if n in ws else None for ws in per_layer)
             for n in names}
        w["rope_inv"] = tuple(jnp.asarray(l._rope[0])
                              for l in self.model.layers)
        w["rope_scale"] = tuple(jnp.asarray(l._rope[1])
                                for l in self.model.layers)
        w["embed"] = self.model.embed_tokens.weight._data
        w["norm"] = self.model.norm.weight._data
        w["head"] = self.lm_head.weight._data
        return w
