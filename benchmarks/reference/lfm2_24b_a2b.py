"""LFM2-24B-A2B (LiquidAI/LFM2-24B-A2B ``config.json``, ``model_type:
lfm2_moe``) pre-training on one chip's share of an expert-parallel job,
written from these equations in plain ``jax.numpy``.

Tokens ``ids[b, s]``; ``E`` the vocab x hidden embedding, which is the
output head too (tied); ``N(x) = x * rsqrt(mean(x^2) + 1e-5) * g`` with
its own ``g`` at each site; positions ``0..s-1``; no bias anywhere.

* ``h_0 = E[ids]``. Layer ``l``: ``a = h + Op_l(N_op(h))``, ``h' = a +
  F_l(N_ffn(a))``. ``Op_l`` is ``Conv`` or ``Attn`` by ``layer_types``
  (this stage runs the published layers ``pipeline_first_layer ..
  pipeline_first_layer + num_hidden_layers - 1``); ``F_l`` a dense SwiGLU
  2048 -> 11776 -> 2048 for the first ``num_dense_layers`` layers of the
  stage, ``MoE`` after. After the last layer ``N_f`` (the file's
  ``embedding_norm``), logits ``N_f(h_L) E^T``, loss = mean next-token
  cross-entropy (the last position of a row has no target), log-sum-exp
  in float32.
* ``Conv(u)``, ``u`` [T, 2048]: ``[B ; C ; x] = u W_in`` (``W_in`` 2048 x
  6144, three equal parts along the features in that order); ``s_t = B_t
  * x_t``; ``y_t[c] = sum_{k=0..2} w[c, k] * s_{t-2+k}[c]`` with ``s_t =
  0`` for ``t < 0`` (a depthwise causal cross-correlation, ``conv_L_cache``
  3 taps a channel, ``conv_bias`` false, no activation); ``Conv(u)_t =
  (C_t * y_t) W_out`` (``W_out`` 2048 x 2048).
* ``Attn(u)``: ``q = u W_q`` as 32 heads x 64, ``k = u W_k`` and ``v = u
  W_v`` as 8 heads x 64; ``q`` and ``k`` each through ``N`` over their 64
  with a learned scale per layer (``q_layernorm``, ``k_layernorm``);
  rotary over the whole 64, pairs ``(i, i + 32)``, theta 1e6, at position
  ``t``; scores ``q_h . k_{h // 4} / 8`` under the causal mask, softmax in
  float32, ``W_out concat_h(P_h v_{h // 4})``.
* ``MoE(u)``: ``s = sigmoid(float32(u) W_g)`` over all 64; chosen = top-4
  of ``s + b``; ``w = s[chosen] / (sum s[chosen] + 1e-6) *
  routed_scaling_factor`` (1); ``y = sum_{e chosen and held} w_e W_down_e
  (silu(W_gate_e u) * W_up_e u)``, width 1536. No shared expert. **The
  share**: this chip holds ``num_experts`` experts, those of rank
  ``expert_rank`` of ``expert_parallel`` chips; the router keeps all
  ``num_experts * expert_parallel`` outputs. What the absent experts would
  add is left out, and that partial result goes on to the next layer. The
  vocabulary is a slice: ids, embedding, logits and loss over it.

Departures, each under ``assumed`` in the configuration's file: the head
width (2048 / 32: the config has no ``head_dim``), the q / k norms, the
tied head and the 1e-6 (the ``lfm2_moe`` modeling file's, not
``config.json``'s); ``b`` (``expert_bias``) is a float32 state of zeros
that no gradient and no update reaches (``loss`` hands it back unchanged
as a state update, which keeps it from the update rule); no auxiliary
loss; the recipe of the job.

One row of 16,384 positions is taken ROW_BLOCK_POSITIONS at a time
wherever a position's work is its own (norms, feed-forwards, experts, the
queries of attention, the head), each block recomputed on its own in the
backward pass and all blocks through one ``lax.map`` body: whole, a row's
float32 scores are 34 GB ([32, 16384, 16384]) and its dense
feed-forward's 771 MB an activation, beside the reference's own 7.5 GB of
weights, gradients and AdamW state. A block of queries meets every key of
the row under the mask (twice the causal products: a reference, not a
kernel). The convolution and the keys and values need the whole row and
take it whole. Every held expert runs over every token under a mask of
its picks (``model_flops`` counts the picks).
"""

import jax
import jax.numpy as jnp
from jax import lax

from .kanana2_30b_a3b import _over_blocks, _over_rows, swiglu
from .ouro_2p6b import EXIT_BLOCK_POSITIONS, _norm, _rotary

# positions of a row that go through a feed-forward or meet the keys at a
# time
ROW_BLOCK_POSITIONS = 2048
# float32 attention scores alive at a time: a block of one key/value
# head's query heads x queries x every key of the row
SCORE_BLOCK_BYTES = 1 << 27
# float32 outputs [experts, positions of a block, hidden] of the held
# experts that run in one batched product: all 8 at the cell's size
EXPERTS_BLOCK_BYTES = 1 << 28

CONV_KEYS = ("w_in", "taps", "w_out")
ATTENTION_KEYS = ("wq", "wk", "wv", "wo", "qn", "kn")
DENSE_KEYS = ("w_gate", "w_up", "w_down")
EXPERT_KEYS = ("router", "e_bias", "e_gate_up", "e_down")


def layer_kinds(cfg):
    """The kind of each layer of this stage: a stretch of the published
    ``layer_types``."""
    first = cfg.get("pipeline_first_layer", 0)
    return list(cfg["layer_types"][first:first + cfg["num_hidden_layers"]])


def is_dense(cfg, layer):
    return layer < cfg["num_dense_layers"]


def routed_total(cfg):
    return cfg["num_experts"] * cfg["expert_parallel"]


def head_dim(cfg):
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def layer_keys(cfg, i):
    return (("n1", "n2")
            + (CONV_KEYS if layer_kinds(cfg)[i] == "conv" else ATTENTION_KEYS)
            + (DENSE_KEYS if is_dense(cfg, i) else EXPERT_KEYS))


def init_params(cfg, key):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    head_dim(cfg))
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, h)}
    for n, kind in enumerate(layer_kinds(cfg)):
        layer = ({"w_in": (h, 3 * h), "taps": (h, cfg["conv_L_cache"]),
                  "w_out": (h, h)} if kind == "conv" else
                 {"wq": (h, heads * d), "wk": (h, kv * d), "wv": (h, kv * d),
                  "wo": (heads * d, h)})
        if is_dense(cfg, n):
            i = cfg["intermediate_size"]
            layer.update(w_gate=(h, i), w_up=(h, i), w_down=(i, h))
        else:
            layer.update(router=(h, routed_total(cfg)),
                         e_gate_up=(held, h, 2 * width),
                         e_down=(held, width, h))
        shapes.update({f"{k}.{n}": s for k, s in layer.items()})
    keys = jax.random.split(key, len(shapes))
    out = {n: cfg["initializer_range"] * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    for n, kind in enumerate(layer_kinds(cfg)):
        out[f"n1.{n}"] = jnp.ones((h,), jnp.float32)
        out[f"n2.{n}"] = jnp.ones((h,), jnp.float32)
        if kind != "conv":
            out[f"qn.{n}"] = jnp.ones((d,), jnp.float32)
            out[f"kn.{n}"] = jnp.ones((d,), jnp.float32)
        if not is_dense(cfg, n):
            out[f"e_bias.{n}"] = jnp.zeros((routed_total(cfg),), jnp.float32)
    out["norm_f"] = jnp.ones((h,), jnp.float32)
    return out


def layer_weights(params, cfg, i):
    return {k: params[f"{k}.{i}"] for k in layer_keys(cfg, i)}


def short_conv(s, taps):
    """``s`` [T, channels], ``taps`` [channels, K] -> ``y_t = sum_k
    taps[:, k] * s_{t - (K - 1) + k}``, zeros before the start."""
    t, k = s.shape[0], taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, s.shape[1]), s.dtype), s])
    return sum(taps[:, j] * padded[j:j + t] for j in range(k))


def conv_operator(u, lp, cfg, nm):
    """One row, [T, hidden] -> [T, hidden], whole."""
    h = u.shape[1]
    both = nm.dot(u, lp["w_in"])
    b, c, x = both[:, :h], both[:, h:2 * h], both[:, 2 * h:]
    # the program holds its taps, like every weight, in the compute dtype
    return nm.dot(nm.act(c * short_conv(b * x, nm.act(lp["taps"]))),
                  lp["w_out"])


def attention_operator(u, lp, cfg, nm):
    """One row, [T, hidden] -> [T, hidden]: q, k and v for the whole row,
    then so many key/value heads at a time, or so many queries of one's
    group, against every key of the row that a block's float32 scores are
    SCORE_BLOCK_BYTES at most."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    head_dim(cfg))
    group, s = heads // kv, u.shape[0]
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    q = nm.act(_rotary(_norm(nm.dot(u, lp["wq"]).reshape(s, heads, d),
                             lp["qn"], eps), theta))
    k = nm.act(_rotary(_norm(nm.dot(u, lp["wk"]).reshape(s, kv, d),
                             lp["kn"], eps), theta))
    v = nm.dot(u, lp["wv"]).reshape(s, kv, d)

    @jax.checkpoint
    def block(q, at, k, v):
        """Queries [rows, m, group, d] at positions ``at`` [rows] against
        keys [s, m, d]."""
        scores = nm.einsum("qngd,knd->ngqk", q, k) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(s)[None, :] <= at[:, None]
        probs = nm.act(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1))
        return nm.einsum("ngqk,knd->qngd", probs, v)

    at_once = min(kv, max(1, SCORE_BLOCK_BYTES // (4 * s * s * group)))
    while kv % at_once:
        at_once -= 1
    rows = max(1, min(s, SCORE_BLOCK_BYTES // (4 * s * group * at_once)))
    while s % rows:
        rows -= 1

    def some_heads(q, k, v):
        """[s, at_once, group, d] against [s, at_once, d], ``rows``
        queries at a time."""
        ctx = _over_blocks(lambda q, at: block(q, at, k, v),
                           q.reshape((s // rows, rows) + q.shape[1:]),
                           jnp.arange(s).reshape(-1, rows))
        return ctx.reshape((s,) + ctx.shape[2:])

    def in_blocks(x):
        """[s, kv, ...] -> [blocks of key/value heads, s, at_once, ...]."""
        return jnp.moveaxis(x.reshape(
            (s, kv // at_once, at_once) + x.shape[2:]), 1, 0)

    ctx = _over_blocks(some_heads, in_blocks(q.reshape(s, kv, group, d)),
                       in_blocks(k), in_blocks(v))
    return nm.dot(jnp.moveaxis(ctx, 0, 1).reshape(s, heads * d), lp["wo"])


def route(u, w_gate, bias, cfg):
    """-> (weights [s, top_k], chosen experts [s, top_k]), float32."""
    scores = jax.nn.sigmoid(jnp.einsum("sh,he->se", u, w_gate,
                                       precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    return (picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
            * cfg["routed_scaling_factor"]), chosen


def experts(u, lp, cfg, nm):
    """The held experts' part for the positions ``u`` [n, hidden]."""
    weights, chosen = route(u, lp["router"], lp["e_bias"], cfg)
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    first = cfg["expert_rank"] * held

    @jax.checkpoint
    def some(u, mine, gate_up, down):
        """Experts [n, ...] over every token, summed under their masks
        ``mine`` [n, s]: each token's weight for the expert, or 0."""
        both = nm.einsum("sh,ehw->esw", u, gate_up)
        act = nm.act(jax.nn.silu(both[..., :width]) * both[..., width:])
        return jnp.sum(mine[..., None] * nm.einsum("esw,ewh->esh", act, down),
                       0)

    at_once = min(held, max(1, EXPERTS_BLOCK_BYTES
                            // (4 * u.shape[0] * u.shape[1])))
    while held % at_once:
        at_once -= 1
    ids = first + jnp.arange(held).reshape(-1, at_once)
    mine = jnp.sum(jnp.where(chosen[None, None] == ids[..., None, None],
                             weights[None, None], 0.0), -1)
    groups = _over_blocks(
        lambda *group: some(u, *group), mine,
        lp["e_gate_up"].reshape((-1, at_once) + lp["e_gate_up"].shape[1:]),
        lp["e_down"].reshape((-1, at_once) + lp["e_down"].shape[1:]))
    return jnp.sum(groups, 0)


def layer_row(x, lp, cfg, nm):
    """One row, [T, hidden], through one layer; ``lp``: its weights."""
    eps, s = cfg["norm_eps"], x.shape[0]
    operator = conv_operator if "w_in" in lp else attention_operator
    u = nm.act(_norm(x, lp["n1"], eps))
    a = nm.act(x + jax.checkpoint(
        lambda u: operator(u, lp, cfg, nm))(u))

    @jax.checkpoint
    def feed_forward(a):
        u = nm.act(_norm(a, lp["n2"], eps))
        return nm.act(a + (
            swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"], nm)
            if "w_gate" in lp else experts(u, lp, cfg, nm)))

    rows = min(s, ROW_BLOCK_POSITIONS)
    while s % rows:
        rows -= 1
    return _over_blocks(feed_forward,
                        a.reshape((s // rows, rows) + a.shape[1:])
                        ).reshape(x.shape)


def hidden(params, ids, cfg, nm):
    """[batch, T] ids -> the stream after the last layer, [batch, T,
    hidden], before the final norm."""
    h = nm.act(params["embed"][ids])
    for i in range(cfg["num_hidden_layers"]):
        lp = layer_weights(params, cfg, i)
        h = _over_rows(lambda row: layer_row(row, lp, cfg, nm), ids.shape[1],
                       h)
    return h


def head_logits(h, params, cfg, nm):
    """[..., hidden] -> the logits over this chip's rows of the embedding."""
    return nm.einsum("...k,vk->...v",
                     nm.act(_norm(h, params["norm_f"], cfg["norm_eps"])),
                     params["embed"])


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
