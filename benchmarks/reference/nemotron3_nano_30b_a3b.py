"""Nemotron 3 Nano 30B-A3B (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
``config.json``, ``model_type: nemotron_h``) pre-training on one chip's
share of an expert-parallel job, written from these equations in plain
``jax.numpy``.

Tokens ``ids[b, s]``; ``E`` the vocab x hidden embedding, ``W_head`` the
hidden x vocab head (untied); ``N(x) = x * rsqrt(mean(x^2) + 1e-5) * g``
with its own ``g`` at each site; no bias but the convolution's. This stage
runs the published layers ``pipeline_first_layer .. pipeline_first_layer
+ num_hidden_layers - 1``; **a layer is one mixer alone**, ``h' = h +
mixer_l(N_l(h))``, its kind the layer's letter of
``hybrid_override_pattern``.

* ``M``, Mamba-2: ``[z | xBC | dt] = u W_in`` (widths 4096 | 6144 | 64);
  ``xBC_t = silu(b + sum_k w[:, k] xBC_{t - 3 + k})``, a causal depthwise
  convolution of 4 taps a channel, zeros before the row's start; ``xBC``
  splits into ``x`` (64 heads of 64), ``B`` and ``C`` (8 groups of 128;
  head ``n`` reads group ``n // 8``); ``d_t = softplus(dt_t + dt_bias)``,
  ``A = -exp(A_log)``; per head, with a state ``S`` [64, 128] from zero at
  the row's start, **the recurrence itself**, a position after the other::

      S_t = exp(d_t A) S_{t-1} + d_t x_t B_t^T
      y_t = S_t C_t + D x_t

  ``y = N_grouped(y * silu(z))``, the mean square over each of 8 groups of
  512 channels, one scale of 4096; ``mixer = y W_out``.
* ``*``, attention: ``q = u W_q`` as 32 heads of 128, ``k = u W_k``, ``v =
  u W_v`` as 2; query head ``n`` reads key/value head ``n // 16``; scores
  ``q . k / sqrt(128)``, query ``i`` sees key ``j`` iff ``j <= i``; softmax
  in float32; ``W_o``. **No rotary and no other position signal.**
* ``E``, experts: ``s = sigmoid(float32(u) W_r)`` over all 128; ``S`` = the
  6 largest of ``s + bias``; ``w_e = 2.5 * s_e / (sum_{j in S} s_j +
  1e-20)``; ``mixer = FF_shared(u) + sum_{e in S and held} w_e FF_e(u)``,
  ``FF(x) = relu(x W_up)^2 W_down`` without a gate, the routed ones 1856
  wide, the shared one 3712. **The share**: this chip holds
  ``n_routed_experts`` experts, those of rank ``expert_rank`` of
  ``expert_parallel`` chips; the router keeps all ``n_routed_experts *
  expert_parallel`` outputs. What the absent experts would add is left
  out, and that partial result goes on to the next layer. The vocabulary
  is a slice: ids, embedding, logits and loss over it.
* After the last layer ``N_f``, logits ``N_f(h_L) W_head``, loss = mean
  next-token cross-entropy (the last position of a row has no target),
  log-sum-exp in float32.

Departures and inferences, each under ``assumed`` in the configuration's
file: no rotary in the attention layers; the gate multiplies before the
grouped norm; no clamp on ``d_t`` beyond softplus; the selection bias
``e_bias`` a float32 state of zeros no gradient and no update reaches,
handed back unchanged as a state update, no auxiliary loss; the seeded
weights' ranges; the recipe of the job.

So that it fits: a row's positions go through the recurrence by
``lax.scan``, checkpointed by blocks of SCAN_BLOCK_POSITIONS (the 64
states at the blocks' starts are kept, 2 MB a head-set each, not one a
position); attention a block of one key/value head's queries at a time
against every key of the row (``smallthinker_21b_a3b``'s, told no window
and no rotary); the experts and the head ROW_BLOCK_POSITIONS and
EXIT_BLOCK_POSITIONS positions at a time; every held expert runs over
every token of its block under a mask of its picks, one expert after the
other. In a lower precision (the controls) the recurrence's operands are
rounded as an autocast program stores them (``x``, ``B``, ``C``, ``dt``
and ``y``: bfloat16) and its own arithmetic stays float32: it has no
matrix product to give float8 operands to.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from .kanana2_30b_a3b import _over_blocks, _over_rows, route
from .ouro_2p6b import EXIT_BLOCK_POSITIONS, _norm
from .smallthinker_21b_a3b import attention

# positions of a row whose recurrence is recomputed as one piece
SCAN_BLOCK_POSITIONS = 128
# positions of a row that go through an expert layer at a time
ROW_BLOCK_POSITIONS = 2048

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
MAMBA_KEYS = ("n", "w_in", "conv_w", "conv_b", "a_log", "d_skip", "dt_bias",
              "gn", "w_out")
ATTENTION_KEYS = ("n", "wq", "wk", "wv", "wo")
EXPERT_KEYS = ("n", "router", "e_bias", "e_up", "e_down", "s_up", "s_down")
KEYS = {MAMBA: MAMBA_KEYS, ATTENTION: ATTENTION_KEYS, EXPERTS: EXPERT_KEYS}
# the matrices that write to the residual stream
RESIDUAL_WRITERS = ("w_out", "wo", "e_down", "s_down")


def layer_kinds(cfg):
    """This stage's stretch of the published pattern, a letter a layer."""
    first = cfg.get("pipeline_first_layer", 0)
    return cfg["hybrid_override_pattern"][first:first
                                          + cfg["num_hidden_layers"]]


def routed_total(cfg):
    return cfg["n_routed_experts"] * cfg["expert_parallel"]


def mamba_sizes(cfg):
    """(heads, head width, groups, state, inner width, convolved width)."""
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    return (heads, width, groups, state, heads * width,
            heads * width + 2 * groups * state)


def init_params(cfg, key):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, _, _, _, inner, convolved = mamba_sizes(cfg)
    q_heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    held, width = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
    taps = cfg["conv_kernel"]
    shapes = {"embed": (v, h), "lm_head": (h, v)}
    for n, kind in enumerate(layer_kinds(cfg)):
        layer = {
            MAMBA: {"w_in": (h, inner + convolved + heads),
                    "w_out": (inner, h)},
            ATTENTION: {"wq": (h, q_heads * d), "wk": (h, kv * d),
                        "wv": (h, kv * d), "wo": (q_heads * d, h)},
            EXPERTS: {"router": (h, routed_total(cfg)),
                      "e_up": (held, h, width), "e_down": (held, width, h),
                      "s_up": (h, shared), "s_down": (shared, h)}}[kind]
        shapes.update({f"{k}.{n}": s for k, s in layer.items()})

    def spread(name):
        kind = name.split(".")[0]
        if kind == "embed":
            return cfg["embedding_initializer_range"]
        if kind in RESIDUAL_WRITERS:
            return cfg["residual_initializer_range"]
        return cfg["initializer_range"]
    keys = jax.random.split(key, len(shapes) + 1)
    out = {n: spread(n) * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    for n, kind in enumerate(layer_kinds(cfg)):
        out[f"n.{n}"] = jnp.ones((h,), jnp.float32)
        if kind == EXPERTS:
            out[f"e_bias.{n}"] = jnp.zeros((routed_total(cfg),), jnp.float32)
        if kind != MAMBA:
            continue
        # the family's own draws: the taps and their bias uniform in
        # +- 1 / sqrt(taps) (a depthwise Conv1d's default), the time step
        # log-uniform in [time_step_min, time_step_max] floored, through
        # the inverse of softplus
        k_w, k_b, k_dt = jax.random.split(jax.random.fold_in(keys[-1], n), 3)
        bound = 1.0 / math.sqrt(taps)
        out[f"conv_w.{n}"] = jax.random.uniform(
            k_w, (convolved, taps), jnp.float32, -bound, bound)
        out[f"conv_b.{n}"] = jax.random.uniform(
            k_b, (convolved,), jnp.float32, -bound, bound)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k_dt, (heads,), jnp.float32, math.log(cfg["time_step_min"]),
            math.log(cfg["time_step_max"]))), cfg["time_step_floor"])
        out[f"dt_bias.{n}"] = dt + jnp.log(-jnp.expm1(-dt))
        out[f"a_log.{n}"] = jnp.log(jnp.arange(1, heads + 1,
                                               dtype=jnp.float32))
        out[f"d_skip.{n}"] = jnp.ones((heads,), jnp.float32)
        out[f"gn.{n}"] = jnp.ones((inner,), jnp.float32)
    out["norm_f"] = jnp.ones((h,), jnp.float32)
    return out


def layer_weights(params, cfg, i):
    return {k: params[f"{k}.{i}"] for k in KEYS[layer_kinds(cfg)[i]]}


def recurrence(x, d, a, b, c):
    """One row. ``x`` [s, heads, width], ``d`` [s, heads], ``a`` [heads],
    ``b`` and ``c`` [s, heads, state] -> ``S_t C_t`` [s, heads, width],
    the state from zero, a position after the other."""
    s, heads, width = x.shape

    def step(state, now):
        x_t, d_t, b_t, c_t = now
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], -1)

    @jax.checkpoint
    def block(state, piece):
        return lax.scan(step, state, piece)

    rows = min(s, SCAN_BLOCK_POSITIONS)
    while s % rows:
        rows -= 1
    _, y = lax.scan(block, jnp.zeros((heads, width, b.shape[-1]),
                                     jnp.float32),
                    tuple(t.reshape((s // rows, rows) + t.shape[1:])
                          for t in (x, d, b, c)))
    return y.reshape(x.shape)


def mamba(u, lp, cfg, nm):
    """One row, [T, hidden] -> [T, hidden]."""
    heads, width, groups, state, inner, convolved = mamba_sizes(cfg)
    s, taps = u.shape[0], cfg["conv_kernel"]
    both = nm.dot(u, lp["w_in"])
    z, xbc, dt = (both[:, :inner], both[:, inner:inner + convolved],
                  both[:, inner + convolved:])
    total = lp["conv_b"] + sum(
        lp["conv_w"][:, k] * jnp.pad(xbc, ((taps - 1 - k, 0), (0, 0)))[:s]
        for k in range(taps))
    xbc = nm.act(jax.nn.silu(total))
    x = xbc[:, :inner].reshape(s, heads, width)
    b, c = (jnp.repeat(t.reshape(s, groups, state), heads // groups, 1)
            for t in (xbc[:, inner:inner + groups * state],
                      xbc[:, inner + groups * state:]))
    d = jax.nn.softplus(dt + lp["dt_bias"])
    y = nm.act(recurrence(x, d, -jnp.exp(lp["a_log"]), b, c)
               + lp["d_skip"][:, None] * x).reshape(s, inner)
    gated = (y * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    normed = gated * lax.rsqrt(jnp.mean(jnp.square(gated), -1, keepdims=True)
                               + cfg["layer_norm_epsilon"])
    return nm.dot(nm.act(normed.reshape(s, inner) * lp["gn"]), lp["w_out"])


def plain_ffn(u, up, down, nm):
    """``relu(u W_up)^2 W_down``: no gate."""
    return nm.dot(nm.act(jnp.square(jax.nn.relu(nm.dot(u, up)))), down)


def experts(u, lp, cfg, nm):
    """The held experts' part and the shared expert for the positions
    ``u`` [n, hidden]: a plain loop over the held experts, each over every
    position under its picks' weights."""
    weights, chosen = route(u, lp["router"], lp["e_bias"], cfg)
    held = cfg["n_routed_experts"]
    first = cfg["expert_rank"] * held
    out = plain_ffn(u, lp["s_up"], lp["s_down"], nm)
    for e in range(held):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = out + mine[:, None] * jax.checkpoint(
            lambda u, up, down: plain_ffn(u, up, down, nm))(
                u, lp["e_up"][e], lp["e_down"][e])
    return out


def layer_row(x, lp, cfg, nm, kind):
    """One row, [T, hidden], through one layer; ``lp``: its weights."""
    eps, s = cfg["layer_norm_epsilon"], x.shape[0]
    u = nm.act(_norm(x, lp["n"], eps))
    if kind == MAMBA:
        return nm.act(x + mamba(u, lp, cfg, nm))
    if kind == ATTENTION:
        return nm.act(x + attention(u, lp, cfg, nm, None, False))
    rows = min(s, ROW_BLOCK_POSITIONS)
    while s % rows:
        rows -= 1
    mixed = _over_blocks(
        jax.checkpoint(lambda u: experts(u, lp, cfg, nm)),
        u.reshape((s // rows, rows) + u.shape[1:])).reshape(x.shape)
    return nm.act(x + mixed)


def hidden(params, ids, cfg, nm):
    """[batch, T] ids -> the stream after the last layer, [batch, T,
    hidden], before the final norm."""
    h = nm.act(params["embed"][ids])
    for i, kind in enumerate(layer_kinds(cfg)):
        lp = layer_weights(params, cfg, i)
        h = _over_rows(lambda row: layer_row(row, lp, cfg, nm, kind),
                       ids.shape[1], h)
    return h


def head_logits(h, params, cfg, nm):
    """[..., hidden] -> the logits over this chip's slice of the
    vocabulary."""
    return nm.dot(nm.act(_norm(h, params["norm_f"],
                               cfg["layer_norm_epsilon"])),
                  params["lm_head"])


def loss(params, batch, cfg, nm):
    ids = batch["ids"]
    s = ids.shape[1]

    @jax.checkpoint
    def head_block(h, targets):
        """Cross-entropy per position of a block of one row's positions."""
        logits = head_logits(h, params, cfg, nm)
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked

    def head_row(h, row_ids):
        """[s - 1]: the last position of a row has no target."""
        cuts = list(range(0, s - 1, EXIT_BLOCK_POSITIONS)) + [s - 1]
        return jnp.concatenate([head_block(h[a:b], row_ids[a + 1:b + 1])
                                for a, b in zip(cuts, cuts[1:])])

    h = hidden(params, ids, cfg, nm)
    # the selection bias is a state, not a weight: handed back as it came
    state = {k: v for k, v in params.items() if k.startswith("e_bias.")}
    return jnp.mean(_over_rows(head_row, s, h, ids)), state
