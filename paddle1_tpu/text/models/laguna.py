"""Laguna-XS.2 (poolside/Laguna-XS.2, ``model_type: laguna``): a decoder
whose attention layers differ in kind and in their number of query heads
by two published lists, ``layer_types`` and
``num_attention_heads_per_layer`` (one layer that sees every key with 48
query heads, then three that see a window of 512 with 64, over 8
key/value heads of 128 throughout), each under a gate of one value a
position a head; rotary positions over half a head with YaRN's
frequencies on the full layers and over the whole head on the window
layers; a dense SwiGLU first by ``mlp_layer_types``, then routed experts
under sigmoid scores beside one shared expert. This module holds what one
chip of an expert-parallel job holds: its experts of every layer, its
slice of the vocabulary.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``; no biases; no norm on q or k.
Layer ``l`` with ``H_l`` query heads:

* ``u = N_in(h)``; ``q = u W_q`` as ``H_l`` heads of ``d``, ``k = u W_k``
  and ``v = u W_v`` as ``H_kv`` heads; ``g = sigmoid(u W_gate)``, one
  value a position a head (``W_gate``: hidden x ``H_l``);
* rotary on q and k, rotate-half pairing inside the turned span
  (``F.rotary_embedding``). A sliding layer: every channel, pair ``i`` of
  ``d / 2`` by ``p * theta_s^(-2 i / d)``. A full layer: the first
  ``partial_rotary_factor * d`` channels alone, the others pass; pair
  ``i`` turns by ``p * f_i`` with YaRN's blend ``f_i = (1 - m_i) b_i /
  factor + m_i b_i`` (``F.yarn_frequencies``), and cos and sin are both
  multiplied by ``attention_factor``;
* query head ``n`` reads key/value head ``n // (H_l / H_kv)``; query ``i``
  sees key ``j`` iff ``j <= i`` and, in a sliding layer, ``i - j <
  sliding_window`` (``ops.pallas.mask_rules.SlidingWindow``); ``o_n =
  softmax(q_n k^T / sqrt(d)) v``; ``a = h + concat_n(g_n * o_n) W_o``;
* ``m = N_post(a)``. A dense layer: ``h' = a + (silu(m W_g) * (m W_u))
  W_d``. A sparse one: ``s = sigmoid(m W_r)`` over all the experts in
  float32, ``S`` its ``top_k`` largest, ``w_e = scaling * s_e / (sum_{j
  in S} s_j + 1e-20)``, ``h' = a + FF_shared(m) + sum_{e in S and held}
  w_e FF_e(m)``, every ``FF`` a SwiGLU (``nn.RoutedExperts``);
* logits ``N_f(h_L) W_head`` (untied); loss: mean next-token
  cross-entropy, the log-sum-exp in float32.

The gate's form, the router's scores and the rest of what ``config.json``
does not say are inferences, listed under ``assumed`` in
``benchmarks/configs/laguna_xs2_33b_a3b.json``.

In a traced step a layer sits under ``layers/<i>`` (``layers/recompute/
<i>`` when recomputed), its attention under ``self_attn`` (``q_proj``,
``k_proj``, ``v_proj``, ``rotary_embedding``, ``o_proj``) with the
attention op under a scope that names the layer's kind, ``window`` or
``global`` (``self_attn/global/scaled_dot_product_attention``), **the
gate's projection, its sigmoid and its product with the heads' outputs
under ``self_attn/gate``**, a dense feed-forward under ``mlp``
(``gate_proj``, ``up_proj``, ``down_proj``, ``swiglu``), experts under
``mlp/moe`` with the shared expert under ``mlp/moe/shared_experts``, the
head under ``lm_head`` (``head_cross_entropy``). With ``enable_recompute``
(``ParallelEngine(recompute=True)``) every layer application and the head
with its cross-entropy run again in the backward pass, but for what
carries a name of ``core/recompute_keeps.py``: the attention kernel's
``out`` and ``lse``, the stream after attention (``o_proj`` contracts
over 48 x 128 = 6144 or 64 x 128 = 8192: 0.031 and 0.042 ms a MB kept by
the rule's price, over the 0.02 that earns a name), an expert layer's
picks, their scores, the sorted order and the sorted rows' weights.
"""

from __future__ import annotations

from ...autograd.engine import scope
from ...core.recompute_keeps import keep_in_recompute
from ...framework.param_attr import ParamAttr
from ...nn import functional as F
from ...nn.initializer import Normal
from ...nn.layer_base import Layer
from ...nn.layer_common import Embedding, Linear
from ...nn.layer_moe import RoutedExperts
from ...nn.layer_norm_act import RMSNorm
from ...nn.layer_transformer import GatedFeedForward
from ...ops.pallas.mask_rules import SlidingWindow
from .kanana2 import (Kanana2Head, Kanana2PretrainingCriterion,
                      Kanana2Stack)
from .ouro import OuroForPretraining, _run

__all__ = ["LagunaAttention", "LagunaDecoderLayer", "LagunaForPretraining",
           "LagunaPretrainingCriterion"]


class LagunaAttention(Layer):
    """Causal grouped-query self-attention under a per-head output gate,
    no biases, no q / k norm. ``num_heads``: this layer's own. ``window``:
    the keys a query sees, itself included (None: every key up to
    itself). ``rotary``: the keyword arguments of ``F.rotary_embedding``
    that say how this layer's q and k turn (``theta``, or ``frequencies``
    and ``scale``)."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 window=None, rotary=None, weight_attr=None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over {num_kv_heads} "
                             "key/value heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.window = head_dim, window
        self.rotary = dict(rotary or {})
        self.q_proj = Linear(hidden_size, num_heads * head_dim, weight_attr,
                             False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.gate_proj = Linear(hidden_size, num_heads, weight_attr, False)
        self.o_proj = Linear(num_heads * head_dim, hidden_size, weight_attr,
                             False)

    def forward(self, x):
        from ...ops import manip_ops
        b, s, d = x.shape[0], x.shape[1], self.head_dim

        def heads(y, n):
            return manip_ops.reshape(y, [b, s, n, d])
        q = F.rotary_embedding(heads(self.q_proj(x), self.num_heads),
                               **self.rotary)
        k = F.rotary_embedding(heads(self.k_proj(x), self.num_kv_heads),
                               **self.rotary)
        v = heads(self.v_proj(x), self.num_kv_heads)
        kind, mask = (("global", dict(is_causal=True))
                      if self.window is None else
                      ("window", dict(mask_rule=SlidingWindow(self.window))))
        with scope(kind):   # the two kinds of layer apart in a trace
            out = F.scaled_dot_product_attention(q, k, v, **mask)
        with scope("gate"):     # one value a position a head
            gate = F.sigmoid(self.gate_proj(x))
            out = out * manip_ops.reshape(gate, [b, s, self.num_heads, 1])
        return self.o_proj(manip_ops.reshape(out, [b, s,
                                                   self.num_heads * d]))


class LagunaDecoderLayer(Layer):
    """``a = x + Attn(N_in(x))``, ``y = a + F(N_post(a))``; ``F`` a dense
    SwiGLU of ``intermediate_size`` where ``experts`` is None, else
    routed. ``attention``: the keyword arguments of
    :class:`LagunaAttention`; ``experts``: those of
    ``nn.RoutedExperts``."""

    def __init__(self, hidden_size, attention, intermediate_size=None,
                 experts=None, rms_norm_eps=1e-6, weight_attr=None):
        super().__init__()
        self.input_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.self_attn = LagunaAttention(hidden_size, weight_attr=weight_attr,
                                         **attention)
        self.post_attention_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.mlp = (GatedFeedForward(hidden_size, intermediate_size,
                                     weight_attr) if experts is None else
                    RoutedExperts(hidden_size, weight_attr=weight_attr,
                                  scoring="sigmoid", **experts))

    def forward(self, x):
        # ``N_post``'s backward reads ``a``: a recomputed segment keeps it
        # and does not run ``o_proj`` (K 6144 or 8192 -> 2048) again
        a = keep_in_recompute(
            x + self.self_attn(self.input_layernorm(x)), "stream_after_attn")
        return a + self.mlp(self.post_attention_layernorm(a))


class LagunaForPretraining(Layer):
    """Embedding, the stack, the untied head. ``forward(input_ids,
    labels)`` -> per-token cross-entropy ``[batch, seq]`` for
    :class:`LagunaPretrainingCriterion`; without labels, the logits.

    ``layer_types`` (``"full_attention"`` or ``"sliding_attention"``),
    ``num_attention_heads_per_layer`` and ``mlp_layer_types`` (``"dense"``
    or ``"sparse"``) say what each of the ``len(layer_types)`` layers this
    model holds is (a pipeline stage hands in its own stretch of the
    published lists); ``rope_parameters`` is the published group, how each
    type of layer turns. ``held_experts = (first, count)`` of
    ``num_experts`` and ``vocab_size`` are this chip's share; the router
    keeps ``num_experts`` outputs and ``num_experts_per_tok`` picks."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 layer_types=("full_attention",) + ("sliding_attention",) * 3,
                 num_attention_heads_per_layer=(48, 64, 64, 64),
                 mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
                 num_key_value_heads=8, head_dim=128, sliding_window=512,
                 rope_parameters=None, intermediate_size=8192,
                 moe_intermediate_size=512, num_experts=256,
                 num_experts_per_tok=8, shared_expert_intermediate_size=512,
                 moe_routed_scaling_factor=2.5, held_experts=None,
                 rms_norm_eps=1e-6, initializer_range=0.02):
        super().__init__()
        if not (len(layer_types) == len(num_attention_heads_per_layer)
                == len(mlp_layer_types)):
            raise ValueError(
                "layer_types, num_attention_heads_per_layer and "
                f"mlp_layer_types name {len(layer_types)}, "
                f"{len(num_attention_heads_per_layer)} and "
                f"{len(mlp_layer_types)} layers")
        attr = ParamAttr(initializer=Normal(std=initializer_range))
        turns = {kind: rotary_arguments(head_dim, group)
                 for kind, group in (rope_parameters or {}).items()
                 if isinstance(group, dict)}
        experts = dict(expert_width=moe_intermediate_size,
                       num_experts=num_experts, top_k=num_experts_per_tok,
                       held=held_experts,
                       shared_width=shared_expert_intermediate_size,
                       routed_scaling_factor=moe_routed_scaling_factor)

        def attention(i):
            kind = layer_types[i]
            if kind not in ("full_attention", "sliding_attention"):
                raise ValueError(f"layer_types[{i}]={kind!r}")
            return dict(
                num_heads=num_attention_heads_per_layer[i],
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                window=sliding_window if kind == "sliding_attention"
                else None, rotary=turns.get(kind))
        self.embed_tokens = Embedding(vocab_size, hidden_size,
                                      weight_attr=attr)
        # the layers in order, each of its own kind and head count, each
        # recomputed where ``enable_recompute``
        self.layers = Kanana2Stack(
            lambda i: LagunaDecoderLayer(
                hidden_size, attention(i), intermediate_size,
                None if mlp_layer_types[i] == "dense" else experts,
                rms_norm_eps, attr),
            len(layer_types))
        self.lm_head = Kanana2Head(hidden_size, vocab_size, rms_norm_eps,
                                   attr)

    next_token_labels = staticmethod(OuroForPretraining.next_token_labels)

    def forward(self, input_ids, labels=None):
        h = self.layers(self.embed_tokens(input_ids))
        remat = self.layers.enable_recompute and self.training
        return _run(self.lm_head, remat, h,
                    *(() if labels is None else (labels,)))


def rotary_arguments(head_dim, group):
    """One layer type's entry of the published ``rope_parameters`` as the
    keyword arguments of ``F.rotary_embedding``: ``theta`` alone for
    ``rope_type: default`` over the whole head; for ``yarn`` a table as
    long as half the turned span (``partial_rotary_factor``) with its
    ``attention_factor`` as the scale."""
    span = int(head_dim * group.get("partial_rotary_factor", 1))
    kind, theta = group.get("rope_type", "default"), group["rope_theta"]
    if kind == "default" and span == head_dim:
        return dict(theta=theta)
    if kind != "yarn":
        raise ValueError(f"rope_type={kind!r} over {span} of {head_dim} "
                         "channels")
    return dict(
        frequencies=F.yarn_frequencies(
            span, theta, group["factor"],
            group["original_max_position_embeddings"],
            group.get("beta_fast", 32.0), group.get("beta_slow", 1.0)),
        scale=group.get("attention_factor", 1.0))


class LagunaPretrainingCriterion(Kanana2PretrainingCriterion):
    """The mean of the labelled tokens' cross-entropy, float32."""
