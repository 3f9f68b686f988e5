"""LFM2-24B-A2B (LiquidAI/LFM2-24B-A2B, ``model_type: lfm2_moe``): a
decoder whose layers differ in kind by a list, ``layer_types``: three in
four mix tokens by a gated short convolution (``nn.ShortConv``), the
fourth by grouped-query attention with a per-head RMSNorm on q and k;
after the leading dense layers the feed-forward is a routed mixture of
experts with sigmoid scores and no shared expert; the output head is the
embedding's own rows. This module holds what one chip of an
expert-parallel job holds: its experts of every layer, its slice of the
vocabulary.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``; no biases.

* ``h_0 = E[ids]``; layer ``l``: ``a = h + Op_l(N_op(h))``, ``h' = a +
  F_l(N_ffn(a))``; ``Op_l`` is ``Conv`` or ``Attn`` by
  ``layer_types[l]``; ``F_l`` a dense SwiGLU for ``l <
  num_dense_layers``, ``nn.RoutedExperts`` after. Logits ``N_f(h_L)
  E^T`` (tied); loss: mean next-token cross-entropy, the log-sum-exp in
  float32.
* ``Conv(u)``: ``[b ; c ; x] = u W_in``; ``s_t = b_t * x_t``; ``y_t =
  sum_k w[:, k] * s_{t - (taps - 1) + k}``, zeros before the row's
  start; ``(c_t * y_t) W_out`` (``nn/functional/short_conv.py``).
* ``Attn(u)``: ``q = u W_q`` as ``H`` heads, ``k = u W_k`` and ``v = u
  W_v`` as ``H_kv`` heads; ``q`` and ``k`` through an RMSNorm over the
  head width with a learned scale (``q_layernorm``, ``k_layernorm``),
  then rotary (rotate-half pairing); query head ``h`` reads key/value
  head ``h // (H / H_kv)`` under the causal mask; ``W_out``.
* ``MoE``: ``nn.RoutedExperts(scoring="sigmoid", norm_eps=1e-6)``: the
  selection bias is the family's ``expert_bias``.

In a traced step a layer sits under ``layers/<i>`` (``layers/recompute/
<i>`` when recomputed), its operator under ``conv`` (``in_proj``, the op
``gated_short_conv``, ``out_proj``) or ``self_attn`` (the projections,
the two norms, ``rotary_embedding``, ``scaled_dot_product_attention``),
its feed-forward under ``mlp`` (``mlp/moe`` for an expert layer), the
final norm and the tied head under ``lm_head`` (``head_cross_entropy``).
With ``enable_recompute`` (``ParallelEngine(recompute=True)``) every
layer application and the head with its cross-entropy run again in the
backward pass, but for what carries a name of
``core/recompute_keeps.py``: the attention kernel's ``out`` and ``lse``,
an expert layer's picks, their scores, the sorted order and the sorted
rows' weights. **The stream after the operator carries no name here**:
Kanana-2's and SDAR's does, to spare a second ``o_proj`` that contracts
over 4096; both of this model's output projections contract over 2048,
which the rule prices at 0.010 ms a MB kept, half of what earns a name.
"""

from __future__ import annotations

import jax.numpy as jnp

from ...autograd.engine import apply
from ...framework.param_attr import ParamAttr
from ...nn import functional as F
from ...nn.initializer import Normal
from ...nn.layer_base import Layer
from ...nn.layer_common import Embedding, Linear
from ...nn.layer_conv_pool import ShortConv
from ...nn.layer_moe import RoutedExperts
from ...nn.layer_norm_act import RMSNorm
from ...nn.layer_transformer import GatedFeedForward
from .kanana2 import Kanana2PretrainingCriterion, Kanana2Stack
from .ouro import OuroForPretraining, _run, token_cross_entropy

__all__ = ["Lfm2Attention", "Lfm2DecoderLayer", "Lfm2Stack", "Lfm2Head",
           "Lfm2ForPretraining", "Lfm2PretrainingCriterion"]

CONV, ATTENTION = "conv", "full_attention"


class Lfm2Attention(Layer):
    """Causal grouped-query self-attention, q and k normed per head,
    rotary positions ``0..seq-1``, no biases."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 rope_theta=1e6, norm_eps=1e-5, weight_attr=None):
        super().__init__()
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.rope_theta = head_dim, rope_theta
        self.q_proj = Linear(hidden_size, num_heads * head_dim, weight_attr,
                             False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.q_layernorm = RMSNorm(head_dim, norm_eps)
        self.k_layernorm = RMSNorm(head_dim, norm_eps)
        self.out_proj = Linear(num_heads * head_dim, hidden_size, weight_attr,
                               False)

    def forward(self, x):
        from ...ops import manip_ops
        b, s, d = x.shape[0], x.shape[1], self.head_dim

        def heads(y, n):
            return manip_ops.reshape(y, [b, s, n, d])
        q = F.rotary_embedding(
            self.q_layernorm(heads(self.q_proj(x), self.num_heads)),
            self.rope_theta)
        k = F.rotary_embedding(
            self.k_layernorm(heads(self.k_proj(x), self.num_kv_heads)),
            self.rope_theta)
        out = F.scaled_dot_product_attention(
            q, k, heads(self.v_proj(x), self.num_kv_heads), is_causal=True)
        return self.out_proj(manip_ops.reshape(out, [b, s,
                                                     self.num_heads * d]))


class Lfm2DecoderLayer(Layer):
    """``a = x + Op(N_op(x))``, ``y = a + F(N_ffn(a))``. ``kind``:
    ``"conv"`` (``conv``: the keyword arguments of ``nn.ShortConv``) or
    ``"full_attention"`` (``attention``: those of
    :class:`Lfm2Attention`); ``F`` dense where ``experts`` is None, else
    routed: the keyword arguments of ``nn.RoutedExperts``."""

    def __init__(self, hidden_size, kind, conv=None, attention=None,
                 intermediate_size=None, experts=None, norm_eps=1e-5,
                 weight_attr=None):
        super().__init__()
        if kind not in (CONV, ATTENTION):
            raise ValueError(f"layer kind {kind!r}")
        self.operator_norm = RMSNorm(hidden_size, norm_eps)
        if kind == CONV:
            self.conv = ShortConv(hidden_size, weight_attr=weight_attr,
                                  **(conv or {}))
        else:
            self.self_attn = Lfm2Attention(hidden_size, norm_eps=norm_eps,
                                           weight_attr=weight_attr,
                                           **attention)
        self.ffn_norm = RMSNorm(hidden_size, norm_eps)
        self.mlp = (GatedFeedForward(hidden_size, intermediate_size,
                                     weight_attr) if experts is None else
                    RoutedExperts(hidden_size, weight_attr=weight_attr,
                                  scoring="sigmoid", norm_eps=1e-6,
                                  **experts))
        self.kind = kind

    def forward(self, x):
        op = self.conv if self.kind == CONV else self.self_attn
        a = x + op(self.operator_norm(x))
        return a + self.mlp(self.ffn_norm(a))


class Lfm2Stack(Kanana2Stack):
    """The layers in order, each of its own kind, each recomputed where
    ``enable_recompute``."""


class Lfm2Head(Layer):
    """The final norm (the family's ``embedding_norm``) and the head tied
    to the embedding: ``embedding`` [vocab, hidden] is the embedding's own
    parameter, handed in with every call, so the model has one leaf for
    both uses and its gradient is the sum of both. -> per-token
    cross-entropy against ``labels`` (float32, 0 where the label is
    ``-100``), or the logits where there are none."""

    def __init__(self, hidden_size, norm_eps=1e-5):
        super().__init__()
        self.norm = RMSNorm(hidden_size, norm_eps)

    def forward(self, h, embedding, labels=None):
        h = self.norm(h)
        if labels is None:
            return apply("tied_logits", lambda h, e: jnp.matmul(h, e.T),
                         (h, embedding))
        return apply("head_cross_entropy",
                     lambda h, e, y: token_cross_entropy(h, e.T, y),
                     (h, embedding, labels))


class Lfm2ForPretraining(Layer):
    """Embedding, the stack, the tied head. ``forward(input_ids,
    labels)`` -> per-token cross-entropy ``[batch, seq]`` for
    :class:`Lfm2PretrainingCriterion`; without labels, the logits.

    ``layer_types``: the kind of each of the ``len(layer_types)`` layers
    this model holds (a pipeline stage hands in its own stretch of the
    published list); the first ``num_dense_layers`` of them have a dense
    feed-forward. ``held_experts = (first, count)`` of ``num_experts`` and
    ``vocab_size`` are this chip's share; the router keeps ``num_experts``
    outputs and ``num_experts_per_tok`` picks."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 layer_types=(CONV, CONV, ATTENTION, CONV), num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=8, head_dim=None,
                 conv_L_cache=3, intermediate_size=11776,
                 moe_intermediate_size=1536, num_experts=64,
                 num_experts_per_tok=4, routed_scaling_factor=1.0,
                 held_experts=None, rope_theta=1e6, norm_eps=1e-5,
                 initializer_range=0.02):
        super().__init__()
        attr = ParamAttr(initializer=Normal(std=initializer_range))
        attention = dict(
            num_heads=num_attention_heads, num_kv_heads=num_key_value_heads,
            head_dim=head_dim or hidden_size // num_attention_heads,
            rope_theta=rope_theta)
        experts = dict(expert_width=moe_intermediate_size,
                       num_experts=num_experts, top_k=num_experts_per_tok,
                       held=held_experts,
                       routed_scaling_factor=routed_scaling_factor)
        self.embed_tokens = Embedding(vocab_size, hidden_size,
                                      weight_attr=attr)
        self.layers = Lfm2Stack(
            lambda i: Lfm2DecoderLayer(
                hidden_size, layer_types[i], dict(taps=conv_L_cache),
                attention, intermediate_size,
                None if i < num_dense_layers else experts, norm_eps, attr),
            len(layer_types))
        self.lm_head = Lfm2Head(hidden_size, norm_eps)

    next_token_labels = staticmethod(OuroForPretraining.next_token_labels)

    def forward(self, input_ids, labels=None):
        h = self.layers(self.embed_tokens(input_ids))
        remat = self.layers.enable_recompute and self.training
        return _run(self.lm_head, remat, h, self.embed_tokens.weight,
                    *(() if labels is None else (labels,)))


class Lfm2PretrainingCriterion(Kanana2PretrainingCriterion):
    """The mean of the labelled tokens' cross-entropy, float32."""
