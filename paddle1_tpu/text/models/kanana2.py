"""Kanana-2-30B-A3B (kakaocorp/kanana-2-30b-a3b-instruct-2601,
``model_type: deepseek_v3``): a decoder whose attention keeps one
compressed latent a token (MLA, DeepSeek-V2/V3) and whose feed-forward,
after a leading dense layer, is a routed mixture of experts beside shared
experts. This module holds what one chip of an expert-parallel job holds:
its experts of every layer, its slice of the vocabulary.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``; ``H`` heads; no biases.

* ``h_0 = E[ids]``; layer ``l``: ``a = x + Attn(N_1(x))``,
  ``y = a + F_l(N_2(a))``; ``F_l`` a dense SwiGLU for the first
  ``first_k_dense_replace`` layers, ``nn.RoutedExperts`` after them;
  logits ``N_f(h_L) W_out``; loss: mean next-token cross-entropy, the
  log-sum-exp in float32.
* ``Attn(u)``, latent attention: ``q = u W_q``, per head ``[q_nope;
  q_pe]``. ``[c; k_pe] = u W_kva``: one latent ``c`` of ``kv_lora_rank``
  and ONE rotary key ``k_pe`` a token, shared by all heads. ``N_kv(c)
  W_kvb`` gives per head ``[k_nope; v]``. Rotary on ``q_pe`` of every
  head and on ``k_pe``, pairs (2i, 2i+1) (``rope_interleave``). ``k =
  [k_nope; k_pe]``, ``softmax(q k^T / sqrt(qk_head_dim) + causal) v``,
  heads concatenated, ``W_o``. Keys are ``qk_nope + qk_rope`` wide, values
  ``v_head_dim``: the blockwise kernels take the two widths as they are.

In a traced step a layer sits under ``layers/<i>`` (``layers/recompute/
<i>`` when recomputed), its attention under ``self_attn`` (the
projections and the latent's norm: everything there but the op
``scaled_dot_product_attention`` and ``rotary_embedding``), its
feed-forward under ``mlp`` (and ``mlp/moe`` for an expert layer), the
head under ``lm_head``. With ``enable_recompute``
(``ParallelEngine(recompute=True)``) every layer application and the head
with its cross-entropy run again in the backward pass, but for what
carries a name of ``core/recompute_keeps.py``: the attention kernel's
``out`` and ``lse``, the stream after attention (so ``o_proj`` does not
run again), an expert layer's picks, their scores, the sorted order and
the sorted rows' weights (so neither ``top_k`` nor the sorts do); its
gathers of rows and its grouped products run again.
"""

from __future__ import annotations

import jax.numpy as jnp

from ...autograd.engine import apply
from ...core.recompute_keeps import keep_in_recompute
from ...framework.param_attr import ParamAttr
from ...nn import functional as F
from ...nn.initializer import Normal
from ...nn.layer_base import Layer
from ...nn.layer_common import Embedding, Linear
from ...nn.layer_moe import RoutedExperts
from ...nn.layer_norm_act import LayerList, RMSNorm
from ...nn.layer_transformer import GatedFeedForward
from .ouro import (IGNORE_INDEX, OuroForPretraining, _run,
                   token_cross_entropy)

__all__ = ["LatentAttention", "Kanana2DecoderLayer", "Kanana2Stack",
           "Kanana2Head", "Kanana2ForPretraining",
           "Kanana2PretrainingCriterion"]


class LatentAttention(Layer):
    """Multi-head latent attention (MLA) without a query latent
    (``q_lora_rank: null``), causal."""

    def __init__(self, hidden_size, num_heads, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, kv_lora_rank, rope_theta=1e6,
                 rope_interleave=True, rms_norm_eps=1e-6, weight_attr=None):
        super().__init__()
        self.num_heads = num_heads
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        self.kv_lora_rank = kv_lora_rank
        self.rope_theta, self.rope_interleave = rope_theta, rope_interleave
        self.q_proj = Linear(hidden_size, num_heads * (self.nope + self.rope),
                             weight_attr, False)
        self.kv_a_proj_with_mqa = Linear(hidden_size,
                                         kv_lora_rank + self.rope,
                                         weight_attr, False)
        self.kv_a_layernorm = RMSNorm(kv_lora_rank, rms_norm_eps)
        self.kv_b_proj = Linear(kv_lora_rank,
                                num_heads * (self.nope + v_head_dim),
                                weight_attr, False)
        self.o_proj = Linear(num_heads * v_head_dim, hidden_size, weight_attr,
                             False)

    def forward(self, x):
        from ...ops import manip_ops
        b, s, h = x.shape[0], x.shape[1], self.num_heads

        def turned(t):
            return F.rotary_embedding(t, self.rope_theta,
                                      interleaved=self.rope_interleave)
        q = manip_ops.reshape(self.q_proj(x), [b, s, h, self.nope + self.rope])
        q_nope, q_pe = manip_ops.split(q, [self.nope, self.rope], axis=-1)
        latent, k_pe = manip_ops.split(self.kv_a_proj_with_mqa(x),
                                       [self.kv_lora_rank, self.rope],
                                       axis=-1)
        kv = manip_ops.reshape(self.kv_b_proj(self.kv_a_layernorm(latent)),
                               [b, s, h, self.nope + self.v_dim])
        k_nope, v = manip_ops.split(kv, [self.nope, self.v_dim], axis=-1)
        k_pe = turned(manip_ops.reshape(k_pe, [b, s, 1, self.rope]))
        q = manip_ops.concat([q_nope, turned(q_pe)], axis=-1)
        k = manip_ops.concat(
            [k_nope, manip_ops.expand(k_pe, [b, s, h, self.rope])], axis=-1)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(manip_ops.reshape(out, [b, s, h * self.v_dim]))


class Kanana2DecoderLayer(Layer):
    """``a = x + Attn(N_1(x))``, ``y = a + F(N_2(a))``; ``F`` dense where
    ``experts`` is None, else routed: ``experts`` = dict(expert_width,
    num_experts, top_k, held, shared_width, routed_scaling_factor)."""

    def __init__(self, hidden_size, attention, intermediate_size=None,
                 experts=None, rms_norm_eps=1e-6, weight_attr=None):
        super().__init__()
        self.input_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.self_attn = LatentAttention(hidden_size, weight_attr=weight_attr,
                                         rms_norm_eps=rms_norm_eps,
                                         **attention)
        self.post_attention_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.mlp = (GatedFeedForward(hidden_size, intermediate_size,
                                     weight_attr) if experts is None else
                    RoutedExperts(hidden_size, weight_attr=weight_attr,
                                  **experts))

    def forward(self, x):
        # ``N_2``'s backward reads ``a``: a recomputed segment keeps it
        # and does not run ``o_proj`` (K 4096 -> 2048) again: 1.53 ms for
        # 67.1 MB, 0.023 ms a MB on a v5e (PERF.md, PR 37)
        a = keep_in_recompute(
            x + self.self_attn(self.input_layernorm(x)), "stream_after_attn")
        return a + self.mlp(self.post_attention_layernorm(a))


class Kanana2Stack(Layer):
    """The layers in order, the leading ones dense."""

    def __init__(self, layer_factory, num_layers):
        super().__init__()
        self.blocks = LayerList([layer_factory(i) for i in range(num_layers)])
        # declared, so that ParallelEngine(recompute=True) finds it
        self.enable_recompute = False

    def forward(self, h):
        remat = self.enable_recompute and self.training
        for block in self.blocks:
            h = _run(block, remat, h)
        return h


class Kanana2Head(Layer):
    """The final norm and the output head over this chip's slice of the
    vocabulary. -> per-token cross-entropy against ``labels`` (float32, 0
    where the label is ``-100``), or the logits where there are none."""

    def __init__(self, hidden_size, vocab_size, rms_norm_eps=1e-6,
                 weight_attr=None):
        super().__init__()
        self.norm = RMSNorm(hidden_size, rms_norm_eps)
        self.proj = Linear(hidden_size, vocab_size, weight_attr, False)

    def forward(self, h, labels=None):
        h = self.norm(h)
        if labels is None:
            return self.proj(h)
        return apply("head_cross_entropy", token_cross_entropy,
                     (h, self.proj.weight, labels))


class Kanana2ForPretraining(Layer):
    """Embedding, the stack, the head. ``forward(input_ids, labels)`` ->
    per-token cross-entropy ``[batch, seq]`` for
    :class:`Kanana2PretrainingCriterion`; without labels, the logits.

    ``held_experts = (first, count)`` of ``n_routed_experts`` and
    ``vocab_size`` are this chip's share; the router keeps
    ``n_routed_experts`` outputs and ``num_experts_per_tok`` picks."""

    def __init__(self, vocab_size=128256, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=32,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 kv_lora_rank=512, intermediate_size=6144,
                 moe_intermediate_size=768, n_routed_experts=128,
                 num_experts_per_tok=6, n_shared_experts=2,
                 first_k_dense_replace=1, routed_scaling_factor=2.448,
                 held_experts=None, rope_theta=1e6, rope_interleave=True,
                 rms_norm_eps=1e-6, initializer_range=0.02):
        super().__init__()
        attr = ParamAttr(initializer=Normal(std=initializer_range))
        attention = dict(
            num_heads=num_attention_heads, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            kv_lora_rank=kv_lora_rank, rope_theta=rope_theta,
            rope_interleave=rope_interleave)
        experts = dict(
            expert_width=moe_intermediate_size, num_experts=n_routed_experts,
            top_k=num_experts_per_tok, held=held_experts,
            shared_width=n_shared_experts * moe_intermediate_size,
            routed_scaling_factor=routed_scaling_factor)
        self.embed_tokens = Embedding(vocab_size, hidden_size,
                                      weight_attr=attr)
        self.layers = Kanana2Stack(
            lambda i: Kanana2DecoderLayer(
                hidden_size, attention, intermediate_size,
                None if i < first_k_dense_replace else experts,
                rms_norm_eps, attr),
            num_hidden_layers)
        self.lm_head = Kanana2Head(hidden_size, vocab_size, rms_norm_eps,
                                   attr)

    next_token_labels = staticmethod(OuroForPretraining.next_token_labels)

    def forward(self, input_ids, labels=None):
        h = self.layers(self.embed_tokens(input_ids))
        remat = self.layers.enable_recompute and self.training
        return _run(self.lm_head, remat, h,
                    *(() if labels is None else (labels,)))


class Kanana2PretrainingCriterion(Layer):
    """The mean of the labelled tokens' cross-entropy, float32."""

    def forward(self, token_losses, labels):
        def mean_loss(losses, y):
            w = (y != IGNORE_INDEX).astype(jnp.float32)
            return jnp.sum(losses.astype(jnp.float32) * w) \
                / jnp.maximum(jnp.sum(w), 1.0)
        return apply("next_token_loss", mean_loss, (token_losses, labels))
