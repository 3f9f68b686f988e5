"""Laguna-XS.2 (poolside/Laguna-XS.2 ``config.json``, ``model_type:
laguna``) pre-training on one chip's share of an expert-parallel job,
written from these equations in plain ``jax.numpy``.

Tokens ``ids[b, s]``; ``E`` the vocab x hidden embedding, ``W_head`` the
hidden x vocab head (untied); ``N(x) = x * rsqrt(mean(x^2) + 1e-6) * g``
with its own ``g`` at each site; positions ``p = 0..s-1``; no bias
anywhere, no norm on q or k. This stage runs the published layers
``pipeline_first_layer .. pipeline_first_layer + num_hidden_layers - 1``;
layer ``l`` has ``H_l = num_attention_heads_per_layer[l]`` query heads
(48 or 64) over 8 key/value heads of 128.

* ``h_0 = E[ids]``. ``u = N_in(h)``; ``q = u W_q`` as ``H_l`` heads,
  ``k = u W_k`` and ``v = u W_v`` as 8 heads; **the gate** ``g =
  sigmoid(u W_gate)``, one value a position a head (``W_gate``: 2048 x
  ``H_l``).
* **Rotary by halves** on q and k inside a turned span of ``r =
  partial_rotary_factor * 128`` channels: channel ``i < r / 2`` pairs
  with channel ``i + r / 2``, ``(a, b) -> (a cos - b sin, b cos + a
  sin)``, channels from ``r`` on pass. ``layer_types[l] ==
  "sliding_attention"``: ``r`` = 128, angle ``p * 10000^(-i / 64)``.
  ``"full_attention"``: ``r`` = 64, angle ``p * f_i`` with **YaRN's
  blend** ``f_i = (1 - m_i) b_i / 64 + m_i b_i``, ``b_i = 500000^(-i /
  32)``, ``m_i = 1 - clip((i - lo) / (hi - lo), 0, 1)``, ``lo =
  floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))`` clipped to ``[0, r
  - 1]``, ``c(t) = r ln(4096 / (2 pi t)) / (2 ln 500000)`` (``lo`` 5,
  ``hi`` 16: pairs 0-5 keep their frequency, pairs 16-31 are slowed 64
  times), and **cos and sin both multiplied by ``attention_factor``**
  1.4158883083359672 (the ``transformers`` convention for ``rope_type:
  yarn``).
* Query head ``n`` reads key/value head ``n // (H_l / 8)``; scores ``q_n
  . k / sqrt(128)``; query ``i`` sees key ``j`` iff ``j <= i`` and, in a
  sliding layer, ``i - j < sliding_window`` (512: the query's own
  position counts); softmax in float32; ``o_n = P_n v``; ``a = h +
  concat_n(g_n * o_n) W_o``.
* ``m = N_post(a)``. ``mlp_layer_types[l] == "dense"``: ``h' = a +
  (silu(m W_g) * (m W_u)) W_d``, width 8192. ``"sparse"``: ``s =
  sigmoid(float32(m) W_r)`` over all 256 experts; ``S`` = its 8 largest;
  ``w_e = 2.5 * s_e / (sum_{j in S} s_j + 1e-20)``; ``h' = a +
  FF_shared(m) + sum_{e in S and held} w_e FF_e(m)``, every ``FF`` a
  SwiGLU of width 512. **The share**: this chip holds ``num_experts``
  experts, those of rank ``expert_rank`` of ``expert_parallel`` chips; the
  router keeps all ``num_experts * expert_parallel`` outputs. What the
  absent experts would add is left out, and that partial result goes on
  to the next layer. The vocabulary is a slice: ids, embedding, logits
  and loss over it.
* After the last layer ``N_f``, logits ``N_f(h_L) W_head``, loss = mean
  next-token cross-entropy (the last position of a row has no target),
  log-sum-exp in float32.

Departures and inferences, each under ``assumed`` in the configuration's
file: the gate's form (per head, a sigmoid of ``u``, before ``W_o``); the
router's scores (sigmoid, normalised over the picks, times 2.5; a
selection bias ``e_bias`` that is a float32 state of zeros no gradient
and no update reaches, handed back unchanged as a state update; no
auxiliary loss); no q / k norm, no sandwich norm; rotate-half pairing;
the window counts the query's own position; the shared expert's weight 1;
the recipe of the job.

One row of 16,384 positions is taken ROW_BLOCK_POSITIONS at a time
wherever a position's work is its own (the feed-forward, the head);
attention one key/value head's group of query heads at a time from their
own columns of ``W_q`` to their own rows of ``W_o``, and inside a group a
block of its queries at a time against every key of the row under a dense
boolean mask made from the two positions (a window layer computes 32
times the products its band needs: a reference, not a kernel), each block
recomputed on its own in the backward pass and all blocks through one
``lax.map`` body. Every held expert runs over every
token under a mask of its picks (``model_flops`` counts the picks).
"""

import math

import jax
import jax.numpy as jnp

from . import kanana2_30b_a3b as sigmoid_experts
from .kanana2_30b_a3b import _over_blocks, _over_rows, swiglu
from .ouro_2p6b import EXIT_BLOCK_POSITIONS, _norm
from .smallthinker_21b_a3b import seen

# positions of a row that go through the feed-forward at a time
ROW_BLOCK_POSITIONS = 2048
# float32 attention scores alive at a time: one key/value head's query
# heads x a block of queries x every key of the row
SCORE_BLOCK_BYTES = 1 << 27

ATTENTION_KEYS = ("n1", "n2", "wq", "wk", "wv", "wgate", "wo")
DENSE_KEYS = sigmoid_experts.DENSE_KEYS
EXPERT_KEYS = sigmoid_experts.EXPERT_KEYS
# the matrices that write to the residual stream
RESIDUAL_WRITERS = ("wo", "w_down", "e_down", "s_down")


def stage(cfg, key):
    """This stage's stretch of a published list of a value a layer."""
    first = cfg.get("pipeline_first_layer", 0)
    return list(cfg[key][first:first + cfg["num_hidden_layers"]])


def layer_kinds(cfg):
    """(attention type, query heads, feed-forward type) of each layer of
    this stage."""
    return list(zip(stage(cfg, "layer_types"),
                    stage(cfg, "num_attention_heads_per_layer"),
                    stage(cfg, "mlp_layer_types")))


def routed_total(cfg):
    return cfg["num_experts"] * cfg["expert_parallel"]


def init_params(cfg, key):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared, wide = (cfg["shared_expert_intermediate_size"],
                    cfg["intermediate_size"])
    shapes = {"embed": (v, h), "lm_head": (h, v)}
    kinds = layer_kinds(cfg)
    for n, (_, heads, ffn) in enumerate(kinds):
        layer = {"wq": (h, heads * d), "wk": (h, kv * d), "wv": (h, kv * d),
                 "wgate": (h, heads), "wo": (heads * d, h)}
        if ffn == "dense":
            layer.update(w_gate=(h, wide), w_up=(h, wide), w_down=(wide, h))
        else:
            layer.update(router=(h, routed_total(cfg)),
                         e_gate_up=(held, h, 2 * width),
                         e_down=(held, width, h), s_gate=(h, shared),
                         s_up=(h, shared), s_down=(shared, h))
        shapes.update({f"{k}.{n}": s for k, s in layer.items()})

    def spread(name):
        """The embedding's rows and the matrices that write to the stream
        have ranges of their own (the configuration's file says why)."""
        kind = name.split(".")[0]
        if kind == "embed":
            return cfg["embedding_initializer_range"]
        if kind in RESIDUAL_WRITERS:
            return cfg["residual_initializer_range"]
        return cfg["initializer_range"]
    keys = jax.random.split(key, len(shapes))
    out = {n: spread(n) * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    for n, (_, _, ffn) in enumerate(kinds):
        out[f"n1.{n}"] = jnp.ones((h,), jnp.float32)
        out[f"n2.{n}"] = jnp.ones((h,), jnp.float32)
        if ffn != "dense":
            out[f"e_bias.{n}"] = jnp.zeros((routed_total(cfg),), jnp.float32)
    out["norm_f"] = jnp.ones((h,), jnp.float32)
    return out


def layer_weights(params, cfg, i):
    dense = layer_kinds(cfg)[i][2] == "dense"
    keys = ATTENTION_KEYS + (DENSE_KEYS if dense else EXPERT_KEYS)
    return {k: params[f"{k}.{i}"] for k in keys}


def turns(cfg, kind):
    """(frequencies [r / 2], the factor on cos and sin) of a layer type:
    the published ``rope_parameters[kind]`` by the formulas above."""
    group = cfg["rope_parameters"][kind]
    r = int(cfg["head_dim"] * group.get("partial_rotary_factor", 1))
    theta = float(group["rope_theta"])
    base = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    if group.get("rope_type", "default") == "default":
        return jnp.asarray(base, jnp.float32), 1.0
    factor = float(group["factor"])
    original = group["original_max_position_embeddings"]

    def c(t):
        return r * math.log(original / (2 * math.pi * t)) \
            / (2 * math.log(theta))
    lo = max(math.floor(c(group["beta_fast"])), 0)
    hi = min(math.ceil(c(group["beta_slow"])), r - 1)
    out = []
    for i, b in enumerate(base):
        m = 1.0 - min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        out.append((1.0 - m) * b / factor + m * b)
    return jnp.asarray(out, jnp.float32), float(group["attention_factor"])


def rotary_by_halves(x, frequencies, factor):
    """x: [s, heads, d]; the first ``2 * len(frequencies)`` channels turn,
    channel ``i`` with channel ``i + len(frequencies)``; the others
    pass."""
    half = frequencies.shape[0]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * frequencies
    cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(u, lp, cfg, nm, kind, heads):
    """One row, [T, hidden] -> [T, hidden]. k and v for the whole row;
    then **one key/value head's group of query heads at a time**: their
    columns of ``W_q`` and ``W_gate``, the turn, so many of their queries
    against every key of the row that a block's float32 scores are
    SCORE_BLOCK_BYTES at most, the gate, and their rows of ``W_o``; the
    groups' parts of ``W_o``'s product are summed. (Whole, the 64 heads'
    float32 q, its turned copy, the heads' outputs and the gated ones are
    0.5 GiB each and their cotangents as much again: my compile for a
    described v5e read 15.2 GiB of temporaries beside the reference's 5.5
    of weights and AdamW state.)"""
    kv, d, h = cfg["num_key_value_heads"], cfg["head_dim"], u.shape[1]
    group, s = heads // kv, u.shape[0]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    frequencies, factor = turns(cfg, kind)
    k = nm.act(rotary_by_halves(nm.dot(u, lp["wk"]).reshape(s, kv, d),
                                frequencies, factor))
    v = nm.dot(u, lp["wv"]).reshape(s, kv, d)

    @jax.checkpoint
    def block(q, at, k, v):
        """Queries [rows, group, d] at positions ``at`` [rows] against
        one head's keys [s, d]."""
        scores = nm.einsum("qgd,kd->gqk", q, k) / jnp.sqrt(jnp.float32(d))
        probs = nm.act(jax.nn.softmax(
            jnp.where(seen(at, s, window), scores, -jnp.inf), -1))
        return nm.einsum("gqk,kd->qgd", probs, v)

    rows = max(1, min(s, SCORE_BLOCK_BYTES // (4 * s * group)))
    while s % rows:
        rows -= 1

    @jax.checkpoint
    def one_group(w_q, w_gate, w_o, k, v):
        """[hidden, group * d], [hidden, group], [group * d, hidden] and
        the group's one key/value head [s, d] -> its part of the layer's
        output [s, hidden]."""
        q = nm.act(rotary_by_halves(nm.dot(u, w_q).reshape(s, group, d),
                                    frequencies, factor))
        gate = nm.act(jax.nn.sigmoid(nm.dot(u, w_gate)))     # [s, group]
        ctx = _over_blocks(lambda q, at: block(q, at, k, v),
                           q.reshape(s // rows, rows, group, d),
                           jnp.arange(s).reshape(-1, rows)).reshape(
                               s, group, d)
        return nm.dot(nm.act(ctx * gate[..., None]).reshape(s, group * d),
                      w_o)

    def by_group(w):
        """Columns [hidden, kv x n] -> [kv, hidden, n]."""
        return jnp.moveaxis(w.reshape(h, kv, -1), 1, 0)
    parts = _over_blocks(one_group, by_group(lp["wq"]), by_group(lp["wgate"]),
                         lp["wo"].reshape(kv, group * d, h),
                         jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0))
    return jnp.sum(parts, 0)


def experts(m, lp, cfg, nm):
    """The held experts' part and the shared expert for the positions
    ``m`` [n, hidden]: the sigmoid rule of ``kanana2_30b_a3b``'s
    reference under this configuration's keys."""
    return sigmoid_experts.experts(m, lp, {
        "n_routed_experts": cfg["num_experts"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "routed_scaling_factor": cfg["moe_routed_scaling_factor"],
        "expert_rank": cfg["expert_rank"]}, nm)


def layer_row(x, lp, cfg, nm, kind, heads):
    """One row, [T, hidden], through one layer; ``lp``: its weights."""
    eps, s = cfg["rms_norm_eps"], x.shape[0]
    u = nm.act(_norm(x, lp["n1"], eps))
    a = nm.act(x + jax.checkpoint(
        lambda u: attention(u, lp, cfg, nm, kind, heads))(u))

    @jax.checkpoint
    def feed_forward(a):
        m = nm.act(_norm(a, lp["n2"], eps))
        ff = (swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"], nm)
              if "w_gate" in lp else experts(m, lp, cfg, nm))
        return nm.act(a + ff)

    rows = min(s, ROW_BLOCK_POSITIONS)
    while s % rows:
        rows -= 1
    return _over_blocks(feed_forward, a.reshape(
        (s // rows, rows) + a.shape[1:])).reshape(x.shape)


def hidden(params, ids, cfg, nm):
    """[batch, T] ids -> the stream after the last layer, [batch, T,
    hidden], before the final norm."""
    h = nm.act(params["embed"][ids])
    for i, (kind, heads, _) in enumerate(layer_kinds(cfg)):
        lp = layer_weights(params, cfg, i)
        h = _over_rows(
            lambda row: layer_row(row, lp, cfg, nm, kind, heads),
            ids.shape[1], h)
    return h


def head_logits(h, params, cfg, nm):
    """[..., hidden] -> the logits over this chip's slice of the
    vocabulary."""
    return nm.dot(nm.act(_norm(h, params["norm_f"], cfg["rms_norm_eps"])),
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
