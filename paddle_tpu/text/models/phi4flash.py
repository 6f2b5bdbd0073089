"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``): the SambaY
decoder-hybrid-decoder of arXiv:2507.06607.

``num_hidden_layers`` pre-norm layers (``LayerNorm`` with weight and bias
before the mixer and before the SwiGLU MLP, a final ``LayerNorm``, the head
tied to the embedding, no position encoding: order enters through the
recurrent layers). ``mb_per_layer`` 2 makes every even layer recurrent;
the second half of the depth is the cross-decoder:

==================  =============================================  =========
layers (of 32)      mixer                                          keeps
==================  =============================================  =========
0, 2, .. 14         Mamba-1                                        state, 3 conv inputs
1, 3, .. 15         differential attention, window                 <= window lines
16                  Mamba-1; its scan output is the memory ``m``   state, 3 conv inputs
17                  differential attention, whole context          every line: the ONE KV cache
18, 20, .. 30       gated memory unit over ``m``                   nothing
19, 21, .. 31       differential attention, query only, over 17's  nothing
==================  =============================================  =========

The layers' mathematics is ``text/sambay.py``'s bodies: this model's
``forward`` and the serving engine's programs trace the same python.

Sizes the published ``config.json`` lacks are fields with the family's
conventional values (``mamba_d_state`` 16, ``mamba_d_conv`` 4,
``mamba_expand`` 2, ``mamba_dt_rank`` ceil(hidden / 16)); the
configuration file of the benchmark lists them under ``assumed``.

Initialisers are the published ones where the check needs them live:
``A_log = log(1..d_state)``, ``dt_proj.bias`` the inverse softplus of
values log-uniform in [1e-3, 1e-1], ``D = 1``, the four lambda vectors
normal(0, 0.1), the embedding (which is the head) normal(0,
``initializer_range``): so the state and the subtraction carry weight in
the output, and a fault in either shows in a logit.

Not here: training this model through Fleet (the train path has no
recurrent layer), dropout (``embd_pdrop`` / ``resid_pdrop`` are 0), biases
on the MLP or the head (``mlp_bias`` / ``lm_head_bias`` false).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...nn import Embedding, LayerNorm, Linear
from ...nn import functional as F
from ...nn.initializer import Constant, Initializer, Normal, Uniform
from ...nn.layer.container import LayerList
from ...nn.layer_base import Layer, ParamAttr
from ...tensor import apply
from ...tensor_ops.manipulation import reshape

@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    hidden_act: str = "silu"
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None        # None: ceil(hidden / 16)
    initializer_range: float = 0.02         # the embedding's (the head's)
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        for what, ok in (
                ("an untied head", self.tie_word_embeddings),
                ("mlp_bias", not self.mlp_bias),
                ("lm_head_bias", not self.lm_head_bias),
                (f"hidden_act {self.hidden_act!r}", self.hidden_act == "silu"),
                (f"mb_per_layer {self.mb_per_layer}", self.mb_per_layer == 2),
                ("a depth that is not a multiple of 4, or under 8",
                 self.num_hidden_layers % 4 == 0
                 and self.num_hidden_layers >= 8),
                ("heads that do not pair up two query pairs a KV pair",
                 self.num_attention_heads == 2 * self.num_key_value_heads
                 and self.num_key_value_heads % 2 == 0)):
            if not ok:
                raise ValueError(
                    f"Phi4FlashConfig: {what} is not implemented")

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    def layer_kinds(self):
        """Each layer's mixer, from the depth and ``mb_per_layer`` (the
        module's table)."""
        half = self.num_hidden_layers // 2
        out = []
        for i in range(self.num_hidden_layers):
            recurrent = i % self.mb_per_layer == 0
            if i <= half:
                out.append("mamba" if recurrent else "sliding_attention")
            elif i == half + 1:
                out.append("full_attention")
            else:
                out.append("gmu" if recurrent else "cross_attention")
        return tuple(out)


PHI4FLASH_TINY = Phi4FlashConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
    sliding_window=8, max_position_embeddings=512, mamba_d_state=4,
    dtype="float32")


class _ALog(Initializer):
    """``A_log[c, s] = log(s + 1)``: the published S4D-real start."""

    def __call__(self, shape, dtype, key):
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
            shape).astype(dtype)


class _DtBias(Initializer):
    """The inverse softplus of ``dt`` log-uniform in [1e-3, 1e-1]."""

    def __call__(self, shape, dtype, key):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class Phi4FlashMamba(Layer):
    def __init__(self, c: Phi4FlashConfig):
        super().__init__()
        h, di, ds, r = c.hidden_size, c.d_inner, c.mamba_d_state, \
            c.mamba_dt_rank
        self.in_proj = Linear(h, 2 * di, bias_attr=False)
        # a depthwise causal convolution: weight [d_conv, d_inner], tap
        # d_conv - 1 on the newest input; drawn as torch draws a Conv1d
        # (uniform within fan_in ** -0.5, fan_in = d_conv), like dt_proj
        # by its rank (the published Mamba initialisers)
        by = c.mamba_d_conv ** -0.5
        self.conv1d = Linear(
            c.mamba_d_conv, di,
            weight_attr=ParamAttr(initializer=Uniform(-by, by)),
            bias_attr=ParamAttr(initializer=Uniform(-by, by)))
        self.x_proj = Linear(di, r + 2 * ds, bias_attr=False)
        self.dt_proj = Linear(
            r, di,
            weight_attr=ParamAttr(initializer=Uniform(-r ** -0.5, r ** -0.5)),
            bias_attr=ParamAttr(initializer=_DtBias()))
        self.A_log = self.create_parameter(
            (di, ds), dtype="float32", default_initializer=_ALog())
        self.D = self.create_parameter(
            (di,), dtype="float32", default_initializer=Constant(1.0))
        self.out_proj = Linear(di, h, bias_attr=False)

    def weights(self):
        return {"win": self.in_proj.weight, "convw": self.conv1d.weight,
                "convb": self.conv1d.bias, "wx": self.x_proj.weight,
                "wdt": self.dt_proj.weight, "bdt": self.dt_proj.bias,
                "alog": self.A_log, "dskip": self.D,
                "wout": self.out_proj.weight}


class Phi4FlashAttention(Layer):
    """Differential attention; ``kind`` ``cross_attention`` has queries
    alone and reads the full layer's lines."""

    def __init__(self, c: Phi4FlashConfig, kind):
        super().__init__()
        h, nq, nkv = c.hidden_size, c.num_attention_heads, \
            c.num_key_value_heads
        hd = h // nq
        if kind == "cross_attention":
            self.q_proj = Linear(h, nq * hd, bias_attr=False)
        else:
            self.Wqkv = Linear(h, (nq + 2 * nkv) * hd, bias_attr=False)
        self.out_proj = Linear(nq * hd, h, bias_attr=False)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                (hd,), dtype="float32", default_initializer=Normal(0.0, 0.1)))
        self.subln = self.create_parameter(
            (2 * hd,), default_initializer=Constant(1.0))

    def weights(self):
        w = {"wq": self.q_proj.weight} if hasattr(self, "q_proj") \
            else {"wqkv": self.Wqkv.weight}
        return dict(w, wo=self.out_proj.weight, lq1=self.lambda_q1,
                    lk1=self.lambda_k1, lq2=self.lambda_q2,
                    lk2=self.lambda_k2, subln=self.subln)


class Phi4FlashGMU(Layer):
    def __init__(self, c: Phi4FlashConfig):
        super().__init__()
        self.in_proj = Linear(c.hidden_size, c.d_inner, bias_attr=False)
        self.out_proj = Linear(c.d_inner, c.hidden_size, bias_attr=False)

    def weights(self):
        return {"gin": self.in_proj.weight, "gout": self.out_proj.weight}


class Phi4FlashMLP(Layer):
    def __init__(self, c: Phi4FlashConfig):
        super().__init__()
        self.gate_up_proj = Linear(c.hidden_size, 2 * c.intermediate_size,
                                   bias_attr=False)
        self.down_proj = Linear(c.intermediate_size, c.hidden_size,
                                bias_attr=False)


class Phi4FlashDecoderLayer(Layer):
    def __init__(self, c: Phi4FlashConfig, kind):
        super().__init__()
        self.kind = kind
        self.input_layernorm = LayerNorm(c.hidden_size, c.layer_norm_eps)
        self.mixer = (Phi4FlashMamba(c) if kind == "mamba"
                      else Phi4FlashGMU(c) if kind == "gmu"
                      else Phi4FlashAttention(c, kind))
        self.post_attention_layernorm = LayerNorm(c.hidden_size,
                                                  c.layer_norm_eps)
        self.mlp = Phi4FlashMLP(c)

    def weights(self):
        """``{name: parameter}`` under the names ``text/sambay.py`` reads."""
        return dict(
            self.mixer.weights(),
            ln1w=self.input_layernorm.weight, ln1b=self.input_layernorm.bias,
            ln2w=self.post_attention_layernorm.weight,
            ln2b=self.post_attention_layernorm.bias,
            wgu=self.mlp.gate_up_proj.weight, wd=self.mlp.down_proj.weight)


class Phi4FlashModel(Layer):
    def __init__(self, c: Phi4FlashConfig):
        super().__init__()
        self.config = c
        # drawn small, as the family's ``initializer_range``: at the
        # layer's default N(0, 1) a position's own token, whose embedding
        # the residual stream carries, would outscore every other token
        # of the tied head by its squared norm, and no fault in any layer
        # could change the largest logit
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size, weight_attr=ParamAttr(
                initializer=Normal(0.0, c.initializer_range)))
        self.embed_tokens.weight.pspec = P("tp", None)
        self.layers = LayerList([Phi4FlashDecoderLayer(c, kind)
                                 for kind in c.layer_kinds()])
        self.final_layernorm = LayerNorm(c.hidden_size, c.layer_norm_eps)


def _forward_math(ids, *leaves, names, present, kinds, **statics):
    from .. import sambay as S

    *leaves, embed, normw, normb = leaves
    held = iter(leaves)
    w = {n: tuple(next(held) if there else None for there in has)
         for n, has in zip(names, present)}
    T = ids.shape[1]

    def one(row):
        x = jnp.take(embed, row, axis=0)[None]
        x = S.layers_prefill(w, x, jnp.ones((T,), bool), T, kinds=kinds,
                             **statics)[0]
        return x[0]

    from ..generation import _ln

    hidden = _ln(jax.vmap(one)(ids), normw, normb, statics["eps"])
    return jnp.einsum("blh,vh->blv", hidden, embed)


class Phi4FlashForCausalLM(Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.model = Phi4FlashModel(config)
        if config.dtype == "bfloat16":
            self.to(dtype="bfloat16")
            # the recurrence's own parameters stay float32, as the scan is
            for layer in self.model.layers:
                if layer.kind == "mamba":
                    m = layer.mixer
                    for p in (m.A_log, m.D, m.dt_proj.bias):
                        p._data = p._data.astype(jnp.float32)

    def serving_statics(self):
        c = self.config
        return dict(kinds=c.layer_kinds(), n_heads=c.num_attention_heads,
                    n_kv=c.num_key_value_heads, eps=c.layer_norm_eps,
                    window=c.sliding_window)

    def stacked_weights(self):
        """The serving engine's weight tree. Every per-layer leaf is a
        tuple of the layers' OWN arrays, None where a layer has no such
        leaf: nothing is stacked or copied, every weight is held once and
        shared with the model, and the engine's layer loop is unrolled
        over the kinds. The head is the embedding (tied): ``embed``
        ``[vocab, h]`` serves both."""
        per_layer = [l.weights() for l in self.model.layers]
        names = dict.fromkeys(n for ws in per_layer for n in ws)
        w = {n: tuple(ws[n]._data if n in ws else None for ws in per_layer)
             for n in names}
        w["embed"] = self.model.embed_tokens.weight._data
        w["normw"] = self.model.final_layernorm.weight._data
        w["normb"] = self.model.final_layernorm.bias._data
        return w

    def forward(self, input_ids, labels=None):
        w = self.stacked_weights()
        names = tuple(n for n, a in w.items() if isinstance(a, tuple))
        leaves = [a for n in names for a in w[n] if a is not None]
        logits = apply(
            _forward_math, input_ids, *leaves, w["embed"], w["normw"],
            w["normb"], names=names,
            present=tuple(tuple(a is not None for a in w[n]) for n in names),
            **self.serving_statics())
        if labels is None:
            return logits
        return F.cross_entropy(
            reshape(logits[:, :-1],
                    (-1, self.config.vocab_size)).astype("float32"),
            reshape(labels[:, 1:], (-1,)))
