"""SDAR-30B-A3B-Chat (JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``;
SDAR, arXiv:2510.06303): a Qwen3-MoE-shaped decoder (grouped-query
attention with a per-head RMSNorm on q and k, a softmax top-k router over
experts with no shared one) trained by diffusion over blocks (BD3-LMs,
arXiv:2503.09573). This module holds what one chip of an expert-parallel
job holds: its experts of every layer, its slice of the vocabulary.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``; no biases.

* One training row: ids ``x`` of length ``L``, blocks of ``B``
  (``block_length``) positions. Block ``k`` draws a level ``t_k = eps + (1
  - eps) u_k``; position ``i`` is masked, ``m_i``, iff ``u'_i <
  t_{i // B}``; ``xn_i = MASK if m_i else x_i``. ``u`` (``level``) and
  ``u'`` (``draw``) come with the batch, uniform on [0, 1).
* The step's input is ``z = [xn ; x]``, the noisy copy FIRST, ``2L`` long,
  at positions ``[0..L-1, 0..L-1]``. ``h_0 = E[z]``; layer ``l``: ``a = h
  + Attn(N_1(h))``, ``h' = a + MoE(N_2(a))``.
* ``Attn(u)``: ``q = u W_q`` as ``H`` heads, ``k = u W_k`` and ``v = u
  W_v`` as ``H_kv`` heads; ``q`` and ``k`` through an RMSNorm over the
  head width with a learned scale (``q_norm``, ``k_norm``), then rotary
  (rotate-half pairing) at position ``r mod L``; query head ``h`` reads
  key/value head ``h // (H / H_kv)`` under block diffusion's mask
  (``ops.pallas.mask_rules.BlockDiffusion``: a noisy position sees its
  own noisy block and the clean blocks before it, a clean one the clean
  blocks up to its own); ``W_o``.
* ``MoE``: ``nn.RoutedExperts(scoring="softmax")``, no shared expert.
* Logits at the ``L`` noisy rows only, no shift: the label of a masked
  position is its own clean id. Loss ``= (1 / (batch L)) sum_rows sum_i
  m_i / t_{i // B} * CE_i``, the cross-entropy in float32.

In a traced step: the op ``block_noise`` (``xn``, ``z``, positions,
labels, the weights ``m / t``), a layer under ``layers/<i>``
(``layers/recompute/<i>`` when recomputed) with ``self_attn`` (``q_proj``,
``k_proj``, ``v_proj``, ``q_norm``, ``k_norm``, ``o_proj``, the ops
``rotary_embedding`` and ``scaled_dot_product_attention``) and ``mlp/moe``,
the head under ``lm_head`` (``head_cross_entropy``), the weighting and the
mean as the op ``diffusion_loss``. With ``enable_recompute``
(``ParallelEngine(recompute=True)``) every layer application and the head
with its cross-entropy run again in the backward pass, but for what
carries a name of ``core/recompute_keeps.py``: the attention kernel's
``out`` and ``lse``, the stream after attention, the expert layer's picks,
their scores, the sorted order and the sorted rows' weights.
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
from ...ops.pallas.mask_rules import BlockDiffusion
from .kanana2 import Kanana2Head
from .ouro import IGNORE_INDEX, _run

__all__ = ["SdarAttention", "SdarDecoderLayer", "SdarStack",
           "SdarForBlockDiffusion", "SdarBlockDiffusionCriterion"]


class SdarAttention(Layer):
    """Grouped-query self-attention over a doubled row ``[noisy ; clean]``
    under block diffusion's mask, q and k normed per head, rotary at the
    positions given."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 block_length, rope_theta=1e6, rms_norm_eps=1e-6,
                 weight_attr=None):
        super().__init__()
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.block_length = head_dim, block_length
        self.rope_theta = rope_theta
        self.q_proj = Linear(hidden_size, num_heads * head_dim, weight_attr,
                             False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, False)
        self.q_norm = RMSNorm(head_dim, rms_norm_eps)
        self.k_norm = RMSNorm(head_dim, rms_norm_eps)
        self.o_proj = Linear(num_heads * head_dim, hidden_size, weight_attr,
                             False)

    def forward(self, x, positions):
        from ...ops import manip_ops
        b, s, d = x.shape[0], x.shape[1], self.head_dim

        def heads(y, n):
            return manip_ops.reshape(y, [b, s, n, d])
        # q and k carry no name for a recomputed segment: the norms'
        # backward reads ``q_proj``'s and ``k_proj``'s outputs, so the
        # products run again whatever is kept, and what a kept q would
        # spare (the normed value's float32 passes and rotary) is 1.59 ms
        # for 134 MB, 0.012 ms a MB on a v5e, k 0.010: under the rule's
        # 0.02 (``core/recompute_keeps.py``; PERF.md, PR 37)
        q = F.rotary_embedding(
            self.q_norm(heads(self.q_proj(x), self.num_heads)),
            self.rope_theta, positions)
        k = F.rotary_embedding(
            self.k_norm(heads(self.k_proj(x), self.num_kv_heads)),
            self.rope_theta, positions)
        out = F.scaled_dot_product_attention(
            q, k, heads(self.v_proj(x), self.num_kv_heads),
            mask_rule=BlockDiffusion(s // 2, self.block_length,
                                     noisy_first=True))
        return self.o_proj(manip_ops.reshape(out, [b, s, self.num_heads * d]))


class SdarDecoderLayer(Layer):
    """``a = x + Attn(N_1(x))``, ``y = a + MoE(N_2(a))``; ``attention``
    and ``experts``: the keyword arguments of :class:`SdarAttention` and
    ``nn.RoutedExperts``."""

    def __init__(self, hidden_size, attention, experts, rms_norm_eps=1e-6,
                 weight_attr=None):
        super().__init__()
        self.input_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.self_attn = SdarAttention(hidden_size, weight_attr=weight_attr,
                                       rms_norm_eps=rms_norm_eps, **attention)
        self.post_attention_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.mlp = RoutedExperts(hidden_size, weight_attr=weight_attr,
                                 scoring="softmax", **experts)

    def forward(self, x, positions):
        # ``N_2``'s backward reads ``a``: a recomputed segment keeps it
        # and does not run ``o_proj`` (K 4096 -> 2048) again: 1.62 ms for
        # 67.1 MB, 0.024 ms a MB on a v5e (PERF.md, PR 37)
        a = keep_in_recompute(
            x + self.self_attn(self.input_layernorm(x), positions),
            "stream_after_attn")
        return a + self.mlp(self.post_attention_layernorm(a))


class SdarStack(Layer):
    """The layers in order, each recomputed where ``enable_recompute``."""

    def __init__(self, layer_factory, num_layers):
        super().__init__()
        self.blocks = LayerList([layer_factory() for _ in range(num_layers)])
        # declared, so that ParallelEngine(recompute=True) finds it
        self.enable_recompute = False

    def forward(self, h, positions):
        remat = self.enable_recompute and self.training
        for block in self.blocks:
            h = _run(block, remat, h, positions)
        return h


class SdarForBlockDiffusion(Layer):
    """Embedding, the stack over the doubled row, the head over the noisy
    rows. ``forward(ids, level, draw)``: ids ``[batch, L]``, ``level``
    ``[batch, L / block_length]`` and ``draw`` ``[batch, L]`` uniform on
    [0, 1) -> (per-token cross-entropy ``[batch, L]``, 0 where a position
    is not masked; the weights ``m / t`` ``[batch, L]``), for
    :class:`SdarBlockDiffusionCriterion`.

    ``held_experts = (first, count)`` of ``num_experts`` and ``vocab_size``
    are this chip's share; the router keeps ``num_experts`` outputs and
    ``num_experts_per_tok`` picks. ``mask_token_id``: the id that stands
    for ``[MASK]``, the vocabulary's last where None. Half the noisy copy
    is that one row, whose picks are one token's: the rows a share holds
    move by thousands with them, and the step with the rows."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128,
                 moe_intermediate_size=768, num_experts=128,
                 num_experts_per_tok=8, held_experts=None, block_length=4,
                 mask_token_id=None, noise_eps=1e-3, rope_theta=1e6,
                 rms_norm_eps=1e-6, initializer_range=0.02):
        super().__init__()
        self.block_length, self.noise_eps = block_length, noise_eps
        self.mask_token_id = (vocab_size - 1 if mask_token_id is None
                              else mask_token_id)
        attr = ParamAttr(initializer=Normal(std=initializer_range))
        attention = dict(num_heads=num_attention_heads,
                         num_kv_heads=num_key_value_heads, head_dim=head_dim,
                         block_length=block_length, rope_theta=rope_theta)
        experts = dict(expert_width=moe_intermediate_size,
                       num_experts=num_experts, top_k=num_experts_per_tok,
                       held=held_experts)
        self.embed_tokens = Embedding(vocab_size, hidden_size,
                                      weight_attr=attr)
        self.layers = SdarStack(
            lambda: SdarDecoderLayer(hidden_size, attention, experts,
                                     rms_norm_eps, attr),
            num_hidden_layers)
        self.lm_head = Kanana2Head(hidden_size, vocab_size, rms_norm_eps,
                                   attr)

    def noise(self, ids, level, draw):
        """-> (``z`` [batch, 2L], positions [2L], labels [batch, L] (-100
        where a position is not masked), weights ``m / t`` [batch, L])."""
        block, eps, mask_id = (self.block_length, self.noise_eps,
                               self.mask_token_id)

        def f(ids, level, draw):
            t = jnp.repeat(eps + (1.0 - eps) * level.astype(jnp.float32),
                           block, axis=1)
            masked = draw.astype(jnp.float32) < t
            noisy = jnp.where(masked, jnp.asarray(mask_id, ids.dtype), ids)
            at = jnp.arange(ids.shape[1], dtype=jnp.int32)
            return (jnp.concatenate([noisy, ids], axis=1),
                    jnp.concatenate([at, at]),
                    jnp.where(masked, ids, IGNORE_INDEX),
                    masked.astype(jnp.float32) / t)
        return apply("block_noise", f, (ids, level, draw))

    def forward(self, ids, level, draw):
        z, positions, labels, weights = self.noise(ids, level, draw)
        h = self.layers(self.embed_tokens(z), positions)
        remat = self.layers.enable_recompute and self.training
        return _run(self.lm_head, remat, h[:, :ids.shape[1]], labels), weights


class SdarBlockDiffusionCriterion(Layer):
    """The masked positions' cross-entropy, each weighted by ``1 / t`` of
    its block, over every position of the batch; float32."""

    def forward(self, token_losses, weights):
        def weighted(losses, w):
            return jnp.sum(losses.astype(jnp.float32) * w) / losses.size
        return apply("diffusion_loss", weighted, (token_losses, weights))
