"""Ouro, a looped language model (Zhu et al. 2025, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741; ByteDance/Ouro-2.6B,
``model_type: ouro``): one stack of decoder layers applied ``total_ut_steps``
times with the same weights, an exit head after every pass and a learned
gate that weighs the exits.

Tokens ``ids[b, s]``; ``N(x) = x * rsqrt(mean(x^2) + eps) * g`` with its own
``g`` at each site.

* ``h_0 = E[ids]``; for ``t = 1..T``: ``h_t = N_f(Stack(h_{t-1}))``, the
  same stack and the same final norm at every ``t``.
* One layer, sandwich norm: ``a = x + N_2(Attn(N_1(x)))``,
  ``y = a + N_4(MLP(N_3(a)))``. ``Attn``: multi-head causal attention with
  rotary positions on q and k (rotate-half pairing, positions ``0..s-1`` at
  every loop step), no biases. ``MLP(u) = down(silu(gate(u)) * up(u))``.
* Exit ``t``: ``l_t`` = cross-entropy of ``h_t W_out`` against the labels;
  gate ``lam_t = sigmoid(h_t . w_g + b_g)``, one gate for every ``t``.
* Exit distribution per token: ``p_t = lam_t * prod_{j<t}(1 - lam_j)`` for
  ``t < T``, ``p_T = prod_{j<T}(1 - lam_j)``. Loss (the paper's stage-I
  objective under a uniform prior): the mean over the labelled tokens of
  ``sum_t p_t l_t - beta * H(p)``.

The loop is a Python loop, so a step program holds ``T`` copies of the
stack's instructions over one copy of its weights, and autodiff sums a
shared weight's ``T`` gradients. (A ``lax.scan`` over the loop steps runs
too, and ran on the chip: PERF.md, PR 27. It compiles three times faster
and needs 0.7 GB more; the benchmark's trace reduction counts a ``while``
instruction's time and its body's both, so under it the regions of a step
no longer add up to the busy time.) In a traced step loop step ``t``'s
stack sits under the scope ``ut_step/<t>`` (``.../ut_step/<t>/layers/
<i>/...``) and the heads, the gate and the exit loss under ``exit_head``.
With ``enable_recompute`` (``ParallelEngine(...,
recompute=True)`` sets it) every layer application and every exit head with
its cross-entropy is re-run in the backward pass instead of kept: one
exit's float32 logits are ``tokens x vocab x 4`` bytes. All of a layer
application but its attention kernel, that is: where attention takes the
Pallas kernels (sequences >= 1024 on a TPU) ``fleet.utils.recompute``
keeps the kernel's ``out`` and ``lse`` beside the layer's input, one more
hidden-sized tensor an application, and the backward pass does not run the
forward kernel again (PERF.md, PR 30); and the feed-forward's output,
which the sandwich's last norm reads in the backward pass, one more, so
``down_proj`` does not run again either (``nn.GatedFeedForward``; PERF.md,
PR 37).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...autograd.engine import apply, scope
from ...framework.param_attr import ParamAttr
from ...nn import functional as F
from ...nn.initializer import Normal
from ...nn.layer_base import Layer
from ...nn.layer_common import Embedding, Linear
from ...nn.layer_norm_act import LayerList, RMSNorm
from ...nn.layer_transformer import GatedFeedForward

__all__ = ["OuroDecoderLayer", "OuroStack", "OuroExitHead",
           "OuroForPretraining", "OuroPretrainingCriterion"]

IGNORE_INDEX = -100


class OuroAttention(Layer):
    """Causal multi-head self-attention with rotary positions on q and k,
    no biases."""

    def __init__(self, hidden_size, num_heads, head_dim, rope_theta,
                 weight_attr=None):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.rope_theta = rope_theta
        width = num_heads * head_dim
        self.q_proj = Linear(hidden_size, width, weight_attr, False)
        self.k_proj = Linear(hidden_size, width, weight_attr, False)
        self.v_proj = Linear(hidden_size, width, weight_attr, False)
        self.o_proj = Linear(width, hidden_size, weight_attr, False)

    def forward(self, x):
        from ...ops import manip_ops
        b, s = x.shape[0], x.shape[1]

        def heads(y):
            return manip_ops.reshape(y, [b, s, self.num_heads, self.head_dim])
        q = F.rotary_embedding(heads(self.q_proj(x)), self.rope_theta)
        k = F.rotary_embedding(heads(self.k_proj(x)), self.rope_theta)
        out = F.scaled_dot_product_attention(q, k, heads(self.v_proj(x)),
                                             is_causal=True)
        return self.o_proj(manip_ops.reshape(
            out, [b, s, self.num_heads * self.head_dim]))


class OuroDecoderLayer(Layer):
    """``a = x + N_2(Attn(N_1(x)))``, ``y = a + N_4(MLP(N_3(a)))``."""

    def __init__(self, hidden_size, num_heads, head_dim, intermediate_size,
                 rope_theta=1e6, rms_norm_eps=1e-6, weight_attr=None):
        super().__init__()
        self.input_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.self_attn = OuroAttention(hidden_size, num_heads, head_dim,
                                       rope_theta, weight_attr)
        self.input_layernorm_2 = RMSNorm(hidden_size, rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(hidden_size, rms_norm_eps)
        self.mlp = GatedFeedForward(hidden_size, intermediate_size,
                                    weight_attr)
        self.post_attention_layernorm_2 = RMSNorm(hidden_size, rms_norm_eps)

    def forward(self, x):
        a = x + self.input_layernorm_2(self.self_attn(self.input_layernorm(x)))
        return a + self.post_attention_layernorm_2(
            self.mlp(self.post_attention_layernorm(a)))


class OuroStack(Layer):
    """One pass: the layers in order, then the final norm."""

    def __init__(self, layer_factory, num_layers, hidden_size, rms_norm_eps):
        super().__init__()
        self.blocks = LayerList([layer_factory() for _ in range(num_layers)])
        self.norm = RMSNorm(hidden_size, rms_norm_eps)
        # declared, so that ParallelEngine(recompute=True) finds it
        self.enable_recompute = False

    def forward(self, h):
        remat = self.enable_recompute and self.training
        for block in self.blocks:
            h = _run(block, remat, h)
        return self.norm(h)


class OuroExitHead(Layer):
    """The output head and the exit gate, shared by every loop step.
    -> (per-token cross-entropy against ``labels``, or the logits where
    there are none; the gate's logit per token, float32)."""

    def __init__(self, hidden_size, vocab_size, weight_attr=None):
        super().__init__()
        self.lm_head = Linear(hidden_size, vocab_size, weight_attr, False)
        self.gate = Linear(hidden_size, 1, weight_attr)

    def forward(self, h, labels=None):
        def gate(h, w, b):
            # float32 whatever an autocast made of the gate's weight
            return (jnp.einsum("bsh,h->bs", h.astype(jnp.float32),
                               w[:, 0].astype(jnp.float32))
                    + b[0].astype(jnp.float32))
        gate_logit = apply("exit_gate", gate,
                           (h, self.gate.weight, self.gate.bias))
        if labels is None:
            return self.lm_head(h), gate_logit

        return (apply("exit_cross_entropy", token_cross_entropy,
                      (h, self.lm_head.weight, labels)), gate_logit)


def token_cross_entropy(h, w, y):
    """Per-token cross-entropy of ``h @ w`` against ``y`` (0 where the
    label is ``-100``), logits and log-sum-exp in float32: ``F.linear`` +
    ``F.cross_entropy`` keep both in the autocast's dtype."""
    logits = jnp.matmul(h, w, preferred_element_type=jnp.float32)
    valid = y != IGNORE_INDEX
    at = jnp.where(valid, y, 0).astype(jnp.int32)
    picked = jnp.take_along_axis(logits, at[..., None], -1)[..., 0]
    return jnp.where(valid, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0)


def _run(layer, remat, *args):
    if remat:
        from ...distributed.fleet.utils.recompute import recompute
        return recompute(layer, *args)
    return layer(*args)


class OuroForPretraining(Layer):
    """Embedding, the looped stack and the exit heads.

    ``forward(input_ids, labels)`` -> (``token_losses``, ``gate_logits``),
    both ``[total_ut_steps, batch, seq]`` float32: exit ``t``'s
    cross-entropy per token (0 where the label is ``-100``) and the gate's
    logit, for :class:`OuroPretrainingCriterion`. Without labels the first
    is every exit's logits, ``[total_ut_steps, batch, seq, vocab]``.
    """

    def __init__(self, vocab_size=49152, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=16, head_dim=128,
                 intermediate_size=5632, total_ut_steps=4, rope_theta=1e6,
                 rms_norm_eps=1e-6, initializer_range=0.02):
        super().__init__()
        self.total_ut_steps = total_ut_steps
        attr = ParamAttr(initializer=Normal(std=initializer_range))
        self.embed_tokens = Embedding(vocab_size, hidden_size,
                                      weight_attr=attr)
        self.layers = OuroStack(
            lambda: OuroDecoderLayer(hidden_size, num_attention_heads,
                                     head_dim, intermediate_size, rope_theta,
                                     rms_norm_eps, attr),
            num_hidden_layers, hidden_size, rms_norm_eps)
        self.exit_head = OuroExitHead(hidden_size, vocab_size, attr)

    @staticmethod
    def next_token_labels(input_ids):
        """Position ``s``'s label is the id at ``s + 1``; the last
        position of a row has none."""
        def shift(ids):
            return jnp.concatenate(
                [ids[:, 1:], jnp.full_like(ids[:, :1], IGNORE_INDEX)], axis=1)
        return apply("next_token_labels", shift, (input_ids,))

    def forward(self, input_ids, labels=None):
        from ...ops import manip_ops
        h = self.embed_tokens(input_ids)
        remat = self.layers.enable_recompute and self.training
        heads = () if labels is None else (labels,)
        outs, gate_logits = [], []
        for t in range(self.total_ut_steps):
            with scope("ut_step"), scope(str(t)):
                h = self.layers(h)
            out, gate_logit = _run(self.exit_head, remat, h, *heads)
            outs.append(out)
            gate_logits.append(gate_logit)
        return manip_ops.stack(outs, 0), manip_ops.stack(gate_logits, 0)


class OuroPretrainingCriterion(Layer):
    """The exits' losses under the learned exit distribution, less
    ``beta`` times its entropy, averaged over the labelled tokens. All of
    it float32."""

    def __init__(self, beta=0.1):
        super().__init__()
        self.beta = beta

    def forward(self, token_losses, gate_logits, labels):
        beta = self.beta

        def exit_loss(losses, gates, y):
            losses, gates = (losses.astype(jnp.float32),
                             gates.astype(jnp.float32))
            # log p_t = log lam_t + sum_{j<t} log(1 - lam_j); the last
            # exit takes what is left
            stay = jax.nn.log_sigmoid(-gates)
            before = jnp.cumsum(stay, axis=0) - stay
            log_p = jnp.concatenate(
                [jax.nn.log_sigmoid(gates[:-1]) + before[:-1], before[-1:]])
            p = jnp.exp(log_p)
            per_token = jnp.sum(p * losses, 0) + beta * jnp.sum(p * log_p, 0)
            w = (y != IGNORE_INDEX).astype(jnp.float32)
            return jnp.sum(per_token * w) / jnp.maximum(jnp.sum(w), 1.0)
        with scope("exit_head"):
            return apply("exit_loss", exit_loss,
                         (token_losses, gate_logits, labels))
