"""SmallThinker-21BA3B-Instruct (PowerInfer/SmallThinker-21BA3B-Instruct,
``model_name: smallthinker_21b_instruct``): a decoder whose attention
layers differ in kind by two published lists, ``sliding_window_layout``
and ``rope_layout`` (one global layer without positions, then three that
see a window of keys and carry rotary positions), and whose every
feed-forward is a routed mixture of ReLU-gated experts **whose router
stands before the attention block**: it scores the block's normed input,
while the experts compute from the stream after attention. This module
holds what one chip of an expert-parallel job holds: its experts of every
layer, its slice of the vocabulary.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``; no biases; no norm on q or k.

* ``h_0 = E[ids]``; layer ``l``: ``u = N_in(h)``; the router reads ``u``
  (``nn.RoutedExperts(scoring="softmax", gate_activation="relu")``,
  ``forward(m, router_input=u)``: the six largest of ``u W_r`` in
  float32, their softmax among themselves);
* ``q = u W_q`` as ``H`` heads, ``k = u W_k`` and ``v = u W_v`` as
  ``H_kv`` heads; where ``rope_layout[l]`` rotary (rotate-half pairing)
  on q and k, else nothing; query head ``h`` reads key/value head ``h //
  (H / H_kv)``; query ``i`` sees key ``j`` iff ``j <= i`` and, where
  ``sliding_window_layout[l]``, ``i - j < sliding_window_size``
  (``ops.pallas.mask_rules.SlidingWindow``); ``a = h + softmax(q k^T /
  sqrt(d)) v W_o``;
* ``m = N_post(a)``; ``h' = a + sum_{e chosen and held} w_e (relu(m
  W_g^e) * (m W_u^e)) W_d^e``;
* logits ``N_f(h_L) W_head`` (untied); loss: mean next-token
  cross-entropy, the log-sum-exp in float32.

In a traced step a layer sits under ``layers/<i>`` (``layers/recompute/
<i>`` when recomputed), its attention under ``self_attn`` (``q_proj``,
``k_proj``, ``v_proj``, ``rotary_embedding`` where the layer has
positions, ``o_proj``) with **the attention op under a scope that names
the layer's kind, ``window`` or ``global``** (``self_attn/window/
scaled_dot_product_attention``), its experts under ``mlp/moe``, the head
under ``lm_head`` (``head_cross_entropy``). With ``enable_recompute``
(``ParallelEngine(recompute=True)``) every layer application and the head
with its cross-entropy run again in the backward pass, but for what
carries a name of ``core/recompute_keeps.py``: the attention kernel's
``out`` and ``lse``, the expert layer's picks, their scores, the sorted
order and the sorted rows' weights. **The stream after attention carries
no name here**: ``o_proj`` contracts over 28 x 128 = 3584, which the rule
prices at ``3584 x 5.08e-6`` = 0.018 ms a MB kept, under the 0.02 that
earns one (Kanana-2's and SDAR's, over 4096, reads 0.023 and has one).
"""

from __future__ import annotations

from ...autograd.engine import scope
from ...framework.param_attr import ParamAttr
from ...nn import functional as F
from ...nn.initializer import Normal
from ...nn.layer_base import Layer
from ...nn.layer_common import Embedding, Linear
from ...nn.layer_moe import RoutedExperts
from ...nn.layer_norm_act import RMSNorm
from ...ops.pallas.mask_rules import SlidingWindow
from .kanana2 import (Kanana2Head, Kanana2PretrainingCriterion,
                      Kanana2Stack)
from .ouro import OuroForPretraining, _run

__all__ = ["SmallThinkerAttention", "SmallThinkerDecoderLayer",
           "SmallThinkerForPretraining", "SmallThinkerPretrainingCriterion"]


class SmallThinkerAttention(Layer):
    """Causal grouped-query self-attention, no biases, no q / k norm.
    ``window``: the keys a query sees, itself included (None: every key
    up to itself). ``rotary``: positions ``0..seq-1`` turned into q and
    k, or no positions at all."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 window=None, rotary=True, rope_theta=1.5e6,
                 weight_attr=None):
        super().__init__()
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.rope_theta = head_dim, rope_theta
        self.window, self.rotary = window, rotary
        self.q_proj = Linear(hidden_size, num_heads * head_dim, weight_attr,
                             False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.o_proj = Linear(num_heads * head_dim, hidden_size, weight_attr,
                             False)

    def forward(self, x):
        from ...ops import manip_ops
        b, s, d = x.shape[0], x.shape[1], self.head_dim

        def heads(y, n):
            return manip_ops.reshape(y, [b, s, n, d])
        q = heads(self.q_proj(x), self.num_heads)
        k = heads(self.k_proj(x), self.num_kv_heads)
        if self.rotary:
            q = F.rotary_embedding(q, self.rope_theta)
            k = F.rotary_embedding(k, self.rope_theta)
        v = heads(self.v_proj(x), self.num_kv_heads)
        kind, mask = (("global", dict(is_causal=True))
                      if self.window is None else
                      ("window", dict(mask_rule=SlidingWindow(self.window))))
        with scope(kind):   # the two kinds of layer apart in a trace
            out = F.scaled_dot_product_attention(q, k, v, **mask)
        return self.o_proj(manip_ops.reshape(out, [b, s,
                                                   self.num_heads * d]))


class SmallThinkerDecoderLayer(Layer):
    """``u = N_in(x)``, ``a = x + Attn(u)``, ``y = a + MoE(N_post(a))``
    with the router reading ``u``. ``attention``: the keyword arguments
    of :class:`SmallThinkerAttention`; ``experts``: those of
    ``nn.RoutedExperts``."""

    def __init__(self, hidden_size, attention, experts, rms_norm_eps=1e-6,
                 weight_attr=None):
        super().__init__()
        self.input_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.self_attn = SmallThinkerAttention(
            hidden_size, weight_attr=weight_attr, **attention)
        self.post_attention_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.mlp = RoutedExperts(
            hidden_size, weight_attr=weight_attr, scoring="softmax",
            gate_activation="relu", **experts)

    def forward(self, x):
        u = self.input_layernorm(x)
        a = x + self.self_attn(u)
        return a + self.mlp(self.post_attention_layernorm(a), router_input=u)


class SmallThinkerForPretraining(Layer):
    """Embedding, the stack, the untied head. ``forward(input_ids,
    labels)`` -> per-token cross-entropy ``[batch, seq]`` for
    :class:`SmallThinkerPretrainingCriterion`; without labels, the
    logits.

    ``sliding_window_layout`` and ``rope_layout``: 1 where a layer sees
    ``sliding_window_size`` keys, and where it carries rotary positions,
    for each of the ``len(sliding_window_layout)`` layers this model holds
    (a pipeline stage hands in its own stretch of the published lists).
    ``held_experts = (first, count)`` of ``moe_num_primary_experts`` and
    ``vocab_size`` are this chip's share; the router keeps
    ``moe_num_primary_experts`` outputs and
    ``moe_num_active_primary_experts`` picks."""

    def __init__(self, vocab_size=151936, hidden_size=2560,
                 sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
                 sliding_window_size=4096, num_attention_heads=28,
                 num_key_value_heads=4, head_dim=128,
                 moe_ffn_hidden_size=768, moe_num_primary_experts=64,
                 moe_num_active_primary_experts=6, held_experts=None,
                 rope_theta=1.5e6, rms_norm_eps=1e-6,
                 initializer_range=0.02):
        super().__init__()
        if len(sliding_window_layout) != len(rope_layout):
            raise ValueError("sliding_window_layout and rope_layout name "
                             f"{len(sliding_window_layout)} and "
                             f"{len(rope_layout)} layers")
        attr = ParamAttr(initializer=Normal(std=initializer_range))
        experts = dict(expert_width=moe_ffn_hidden_size,
                       num_experts=moe_num_primary_experts,
                       top_k=moe_num_active_primary_experts,
                       held=held_experts)

        def attention(i):
            return dict(
                num_heads=num_attention_heads,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                window=sliding_window_size if sliding_window_layout[i]
                else None,
                rotary=bool(rope_layout[i]), rope_theta=rope_theta)
        self.embed_tokens = Embedding(vocab_size, hidden_size,
                                      weight_attr=attr)
        # the layers in order, each of its own kind, each recomputed where
        # ``enable_recompute``
        self.layers = Kanana2Stack(
            lambda i: SmallThinkerDecoderLayer(
                hidden_size, attention(i), experts, rms_norm_eps, attr),
            len(sliding_window_layout))
        self.lm_head = Kanana2Head(hidden_size, vocab_size, rms_norm_eps,
                                   attr)

    next_token_labels = staticmethod(OuroForPretraining.next_token_labels)

    def forward(self, input_ids, labels=None):
        h = self.layers(self.embed_tokens(input_ids))
        remat = self.layers.enable_recompute and self.training
        return _run(self.lm_head, remat, h,
                    *(() if labels is None else (labels,)))


class SmallThinkerPretrainingCriterion(Kanana2PretrainingCriterion):
    """The mean of the labelled tokens' cross-entropy, float32."""
