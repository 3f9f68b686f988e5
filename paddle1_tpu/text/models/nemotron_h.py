"""Nemotron 3 Nano 30B-A3B (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type: nemotron_h``): a decoder **whose layer is one mixer alone**,
its kind a letter of ``hybrid_override_pattern``: ``M`` a Mamba-2
state-space mixer, ``*`` grouped-query attention without any position
signal, ``E`` a routed mixture of non-gated relu² experts beside a shared
expert twice as wide. This module holds what one chip of an
expert-parallel job holds: its experts of every layer, its slice of the
vocabulary.

``N(x) = x * rsqrt(mean(x^2) + eps) * g``; no biases but the
convolution's. ``h_0 = E[ids]``; layer ``l``: ``h' = h + mixer_l(N_l(h))``;
logits ``N_f(h_L) W_head`` (untied); loss: mean next-token cross-entropy,
the log-sum-exp in float32.

* ``M`` (:class:`Mamba2Mixer`): ``[z | xBC | dt] = u W_in``; ``xBC =
  silu(conv(xBC) + b)``, a causal depthwise convolution of ``conv_kernel``
  taps a channel; ``xBC`` splits into ``x`` (``mamba_num_heads`` heads of
  ``mamba_head_dim``), ``B`` and ``C`` (``n_groups`` groups of
  ``ssm_state_size``); ``y = F.ssd_scan(x, dt, -exp(A_log), B, C, D,
  dt_bias)`` (``nn/functional/ssd.py`` has the recurrence); ``y =
  N_grouped(y * silu(z))``, the norm over each of ``n_groups`` groups of
  channels under one scale; ``y W_out``.
* ``*`` (``SmallThinkerAttention`` without a window and without rotary):
  ``q = u W_q`` as ``H`` heads, ``k``, ``v`` as ``H_kv``; query head ``n``
  reads key/value head ``n // (H / H_kv)``; causal; ``W_o``.
* ``E`` (``nn.RoutedExperts(scoring="sigmoid", gated=False,
  gate_activation="relu2")``): DeepSeek-V3's router (``noaux_tc``, one
  group) over experts ``relu(x W_up)^2 W_down`` and a shared expert of the
  same form.

In a traced step a layer sits under ``layers/<i>`` (``layers/recompute/
<i>`` when recomputed), its norm under ``norm``, its mixer under ``mamba``
(``in_proj``, ``conv``, the op ``ssd_scan``, ``gated_norm``,
``out_proj``), ``self_attn`` (the projections; the op under ``global``) or
``mlp`` (``mlp/moe``), the head under ``lm_head``. With
``enable_recompute`` (``ParallelEngine(recompute=True)``) every layer
application and the head with its cross-entropy run again in the backward
pass, but for what carries a name of ``core/recompute_keeps.py``: the
attention kernel's ``out`` and ``lse``, an expert layer's picks, their
scores, the sorted order and the sorted rows' weights. **Nothing of a
Mamba-2 layer carries a name**: its two projections contract over 2688
and 4096 into wider or equal outputs (0.014 and 0.021 ms a MB by the
rule's arithmetic, the second within a twentieth of the line and of a
layer that has no second product to spare), the scan's output and its
chunk states cost 0.005 ms a MB to make again
(``nn/functional/ssd.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...autograd.engine import apply, scope
from ...framework.param_attr import ParamAttr
from ...nn import functional as F
from ...nn.initializer import Assign, Constant, Normal
from ...nn.layer_base import Layer
from ...nn.layer_common import Embedding, Linear
from ...nn.layer_moe import RoutedExperts
from ...nn.layer_norm_act import RMSNorm
from .kanana2 import (Kanana2Head, Kanana2PretrainingCriterion,
                      Kanana2Stack)
from .ouro import OuroForPretraining, _run
from .smallthinker import SmallThinkerAttention

__all__ = ["Mamba2Mixer", "NemotronHLayer", "NemotronHForPretraining",
           "NemotronHPretrainingCriterion"]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the attribute, and so the scope, a layer's one mixer has by its kind
MIXER = {MAMBA: "mamba", ATTENTION: "self_attn", EXPERTS: "mlp"}


def causal_conv_silu(x, weight, bias):
    """``silu(conv(x) + bias)``: ``x`` [batch, seq, channels], ``weight``
    [channels, taps] (the last tap weighs the position itself), zeros
    before a row's start; float32 sums, ``x``'s dtype out. ``taps``
    shifted multiply-adds, which XLA fuses into one pass."""
    taps = weight.shape[1]
    xf, w = x.astype(jnp.float32), weight.astype(jnp.float32)
    total = bias.astype(jnp.float32) + w[:, taps - 1] * xf
    for k in range(taps - 1):
        by = taps - 1 - k
        total = total + w[:, k] * jnp.pad(
            xf, ((0, 0), (by, 0), (0, 0)))[:, :x.shape[1]]
    return jax.nn.silu(total).astype(x.dtype)


def gated_group_norm(y, z, weight, groups, eps):
    """``N(y * silu(z))`` with the mean square taken over each of
    ``groups`` equal groups of the channels, one scale over all of them:
    the gate multiplies before the norm."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = v.reshape(v.shape[:-1] + (groups, -1))
    ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    return (grouped * jax.lax.rsqrt(ms + eps)).reshape(v.shape).astype(
        y.dtype) * weight


def dt_bias_values(num_heads, low=1e-3, high=0.1, floor=1e-4):
    """The inverse softplus of ``num_heads`` time steps spread evenly in
    the logarithm over [low, high] and floored: the family's
    ``time_step_min / max / floor`` draw, made without a key."""
    dt = np.exp(np.linspace(math.log(low), math.log(high), num_heads))
    dt = np.maximum(dt, floor)
    return (dt + np.log(-np.expm1(-dt))).astype("float32")


class Mamba2Mixer(Layer):
    """See the module's docstring. ``forward``: [batch, seq, hidden] ->
    the same shape."""

    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, conv_kernel=4, chunk_size=128, norm_eps=1e-5,
                 weight_attr=None):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.n_groups, self.state_size = n_groups, state_size
        self.chunk_size, self.norm_eps = chunk_size, norm_eps
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * state_size
        self.in_proj = Linear(hidden_size,
                              self.inner + self.conv_dim + num_heads,
                              weight_attr, False)
        self.conv_weight = self.create_parameter(
            [self.conv_dim, conv_kernel], attr=weight_attr)
        self.conv_bias = self.create_parameter([self.conv_dim], is_bias=True)
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=Assign(
                np.log(np.arange(1, num_heads + 1, dtype="float32"))))
        self.D = self.create_parameter([num_heads],
                                       default_initializer=Constant(1.0))
        self.dt_bias = self.create_parameter(
            [num_heads], default_initializer=Assign(
                dt_bias_values(num_heads)))
        self.norm_weight = self.create_parameter(
            [self.inner], default_initializer=Constant(1.0))
        self.out_proj = Linear(self.inner, hidden_size, weight_attr, False)

    def forward(self, u):
        from ...ops import manip_ops
        b, s = u.shape[0], u.shape[1]
        groups, state = self.n_groups, self.state_size
        z, xbc, dt = manip_ops.split(
            self.in_proj(u), [self.inner, self.conv_dim, self.num_heads],
            axis=-1)
        with scope("conv"):
            xbc = apply("causal_conv_silu", causal_conv_silu,
                        (xbc, self.conv_weight, self.conv_bias))
        x, b_in, c_in = manip_ops.split(
            xbc, [self.inner, groups * state, groups * state], axis=-1)
        a_head = apply("ssd_decay_rate",
                       lambda a: -jnp.exp(a.astype(jnp.float32)),
                       (self.A_log,))
        y = F.ssd_scan(
            manip_ops.reshape(x, [b, s, self.num_heads, self.head_dim]), dt,
            a_head, manip_ops.reshape(b_in, [b, s, groups, state]),
            manip_ops.reshape(c_in, [b, s, groups, state]), self.D,
            self.dt_bias, chunk=self.chunk_size)
        with scope("gated_norm"):
            y = apply("gated_rms_norm", gated_group_norm,
                      (manip_ops.reshape(y, [b, s, self.inner]), z,
                       self.norm_weight), groups=groups, eps=self.norm_eps)
        return self.out_proj(y)


class NemotronHLayer(Layer):
    """``h + mixer(N(h))`` with the one mixer of ``kind``: ``"M"``
    (``mamba``: the keyword arguments of :class:`Mamba2Mixer`), ``"*"``
    (``attention``: those of ``SmallThinkerAttention``) or ``"E"``
    (``experts``: those of ``nn.RoutedExperts``)."""

    def __init__(self, hidden_size, kind, mamba=None, attention=None,
                 experts=None, norm_eps=1e-5, weight_attr=None):
        super().__init__()
        self.norm = RMSNorm(hidden_size, norm_eps)
        if kind == MAMBA:
            self.mamba = Mamba2Mixer(hidden_size, norm_eps=norm_eps,
                                     weight_attr=weight_attr, **mamba)
        elif kind == ATTENTION:
            self.self_attn = SmallThinkerAttention(
                hidden_size, window=None, rotary=False,
                weight_attr=weight_attr, **attention)
        elif kind == EXPERTS:
            self.mlp = RoutedExperts(
                hidden_size, weight_attr=weight_attr, scoring="sigmoid",
                gated=False, gate_activation="relu2", **experts)
        else:
            raise ValueError(f"layer kind {kind!r}")
        self.kind = kind

    def forward(self, h):
        return h + getattr(self, MIXER[self.kind])(self.norm(h))


class NemotronHForPretraining(Layer):
    """Embedding, the stack, the untied head. ``forward(input_ids,
    labels)`` -> per-token cross-entropy ``[batch, seq]`` for
    :class:`NemotronHPretrainingCriterion`; without labels, the logits.

    ``pattern``: the kind of each of the ``len(pattern)`` layers this model
    holds, a letter each (a pipeline stage hands in its own stretch of the
    published ``hybrid_override_pattern``). ``held_experts = (first,
    count)`` of ``n_routed_experts`` and ``vocab_size`` are this chip's
    share; the router keeps ``n_routed_experts`` outputs and
    ``num_experts_per_tok`` picks. Every matrix draws at
    ``initializer_range``; the family's ``rescale_prenorm_residual`` (the
    writers to the stream divided by the root of the depth) is the
    checkpoint's, or the benchmark's seeded weights'."""

    def __init__(self, vocab_size=131072, hidden_size=2688, pattern="MEMEM*E",
                 mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, chunk_size=128,
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                 moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_routed_experts=128, num_experts_per_tok=6,
                 routed_scaling_factor=2.5, held_experts=None,
                 layer_norm_epsilon=1e-5, initializer_range=0.02):
        super().__init__()
        attr = ParamAttr(initializer=Normal(std=initializer_range))
        mamba = dict(num_heads=mamba_num_heads, head_dim=mamba_head_dim,
                     n_groups=n_groups, state_size=ssm_state_size,
                     conv_kernel=conv_kernel, chunk_size=chunk_size)
        attention = dict(num_heads=num_attention_heads,
                         num_kv_heads=num_key_value_heads, head_dim=head_dim)
        experts = dict(expert_width=moe_intermediate_size,
                       num_experts=n_routed_experts,
                       top_k=num_experts_per_tok, held=held_experts,
                       shared_width=moe_shared_expert_intermediate_size,
                       routed_scaling_factor=routed_scaling_factor)
        self.embed_tokens = Embedding(vocab_size, hidden_size,
                                      weight_attr=attr)
        self.layers = Kanana2Stack(
            lambda i: NemotronHLayer(hidden_size, pattern[i], mamba,
                                     attention, experts, layer_norm_epsilon,
                                     attr),
            len(pattern))
        self.lm_head = Kanana2Head(hidden_size, vocab_size,
                                   layer_norm_epsilon, attr)

    next_token_labels = staticmethod(OuroForPretraining.next_token_labels)

    def forward(self, input_ids, labels=None):
        h = self.layers(self.embed_tokens(input_ids))
        remat = self.layers.enable_recompute and self.training
        return _run(self.lm_head, remat, h,
                    *(() if labels is None else (labels,)))


class NemotronHPretrainingCriterion(Kanana2PretrainingCriterion):
    """The mean of the labelled tokens' cross-entropy, float32."""
