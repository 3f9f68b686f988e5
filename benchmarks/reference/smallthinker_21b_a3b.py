"""SmallThinker-21BA3B-Instruct (PowerInfer/SmallThinker-21BA3B-Instruct
``config.json``, ``model_name: smallthinker_21b_instruct``) pre-training
on one chip's share of an expert-parallel job, written from these
equations in plain ``jax.numpy``.

Tokens ``ids[b, s]``; ``E`` the vocab x hidden embedding, ``W_head`` the
hidden x vocab head (untied); ``N(x) = x * rsqrt(mean(x^2) + 1e-6) * g``
with its own ``g`` at each site; positions ``0..s-1``; no bias anywhere,
no norm on q or k.

* ``h_0 = E[ids]``. Layer ``l`` (this stage runs the published layers
  ``pipeline_first_layer .. pipeline_first_layer + num_hidden_layers -
  1``): ``u = N_in(h)``.
* **The router stands first and reads ``u``**: ``z = float32(u) W_r``
  over all 64 experts; ``S`` = the 6 largest of ``z``; ``w_e = exp(z_e) /
  sum_{j in S} exp(z_j)`` for ``e in S`` (``moe_primary_router_apply_
  softmax`` with ``norm_topk_prob``: the softmax over all 64 renormalised
  over the chosen is the softmax over the chosen).
* ``q = u W_q`` as 28 heads x 128, ``k = u W_k`` and ``v = u W_v`` as 4
  heads x 128. Where ``rope_layout[l] == 1``: rotary over the whole 128,
  pairs ``(i, i + 64)``, theta 1.5e6, at position ``t``, on q and k;
  where 0: no positions at all. Scores ``q_h . k_{h // 7} / sqrt(128)``;
  query ``i`` sees key ``j`` iff ``j <= i`` and, where
  ``sliding_window_layout[l] == 1``, ``i - j < sliding_window_size``
  (4096: the query's own position counts); softmax in float32; ``a = h +
  W_o concat_h(P_h v_{h // 7})``.
* ``m = N_post(a)``; ``h' = a + sum_{e in S and held} w_e W_down_e
  (relu(W_gate_e m) * W_up_e m)``, width 768: **the experts read the
  stream after attention, the choice and the weights come from before
  it**. No shared expert, no dense layer. **The share**: this chip holds
  ``moe_num_primary_experts`` experts, those of rank ``expert_rank`` of
  ``expert_parallel`` chips; the router keeps all
  ``moe_num_primary_experts * expert_parallel`` outputs. What the absent
  experts would add is left out, and that partial result goes on to the
  next layer. The vocabulary is a slice: ids, embedding, logits and loss
  over it.
* After the last layer ``N_f``, logits ``N_f(h_L) W_head``, loss = mean
  next-token cross-entropy (the last position of a row has no target),
  log-sum-exp in float32.

Departures, each under ``assumed`` in the configuration's file: the
router's input is the normed ``u`` and not ``h``; the window counts the
query's own position; rotate-half pairing; no q / k norm; nothing of the
"secondary experts" the family's description speaks of is built (the
config has the primary's keys alone); no auxiliary loss; the recipe of
the job.

One row of 16,384 positions is taken ROW_BLOCK_POSITIONS at a time
wherever a position's work is its own (norms, router, experts, the
queries of attention, the head), each block recomputed on its own in the
backward pass and all blocks through one ``lax.map`` body: whole, a row's
float32 scores are 30 GB ([28, 16384, 16384]), beside the reference's own
5.9 GB of weights, gradients and AdamW state. A block of queries meets
every key of the row under a dense boolean mask made from the two
positions (a window layer computes four times the products its band
needs: a reference, not a kernel). The keys and values need the whole row
and take it whole. Every held expert runs over every token under a mask
of its picks (``model_flops`` counts the picks).
"""

import jax
import jax.numpy as jnp
from jax import lax

from .kanana2_30b_a3b import _over_blocks, _over_rows
from .ouro_2p6b import EXIT_BLOCK_POSITIONS, _norm, _rotary

# positions of a row that go through the experts at a time
ROW_BLOCK_POSITIONS = 2048
# float32 attention scores alive at a time: a block of one key/value
# head's query heads x queries x every key of the row
SCORE_BLOCK_BYTES = 1 << 27
# float32 outputs [experts, positions of a block, hidden] of the held
# experts that run in one batched product: all 8 at the cell's size
EXPERTS_BLOCK_BYTES = 1 << 28

LAYER_KEYS = ("n1", "n2", "wq", "wk", "wv", "wo", "router", "e_gate_up",
              "e_down")


def layer_kinds(cfg):
    """(window or None, rotary) of each layer of this stage: a stretch of
    the two published lists."""
    first = cfg.get("pipeline_first_layer", 0)
    at = slice(first, first + cfg["num_hidden_layers"])
    return [(cfg["sliding_window_size"] if windowed else None, bool(turned))
            for windowed, turned in zip(cfg["sliding_window_layout"][at],
                                        cfg["rope_layout"][at])]


def routed_total(cfg):
    return cfg["moe_num_primary_experts"] * cfg["expert_parallel"]


def init_params(cfg, key):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    held, width = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    shapes = {"embed": (v, h), "lm_head": (h, v)}
    for n in range(cfg["num_hidden_layers"]):
        layer = {"wq": (h, heads * d), "wk": (h, kv * d), "wv": (h, kv * d),
                 "wo": (heads * d, h), "router": (h, routed_total(cfg)),
                 "e_gate_up": (held, h, 2 * width), "e_down": (held, width, h)}
        shapes.update({f"{k}.{n}": s for k, s in layer.items()})

    def spread(name):
        """The embedding's rows and the two matrices that write to the
        stream have ranges of their own (the configuration's file says
        why: one range for all collapses the routing)."""
        kind = name.split(".")[0]
        if kind == "embed":
            return cfg["embedding_initializer_range"]
        if kind in ("wo", "e_down"):
            return cfg["residual_initializer_range"]
        return cfg["initializer_range"]
    keys = jax.random.split(key, len(shapes))
    out = {n: spread(n) * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    for n in range(cfg["num_hidden_layers"]):
        out[f"n1.{n}"] = jnp.ones((h,), jnp.float32)
        out[f"n2.{n}"] = jnp.ones((h,), jnp.float32)
    out["norm_f"] = jnp.ones((h,), jnp.float32)
    return out


def layer_weights(params, i):
    return {k: params[f"{k}.{i}"] for k in LAYER_KEYS}


def seen(at, keys, window):
    """[queries, keys] bool: key ``j`` is up to query ``i`` and, under a
    window, fewer than ``window`` positions behind it."""
    behind = at[:, None] - jnp.arange(keys)[None, :]
    return (behind >= 0) & ((behind < window) if window else True)


def attention(u, lp, cfg, nm, window, rotary):
    """One row, [T, hidden] -> [T, hidden]: q, k and v for the whole row,
    then so many key/value heads at a time, or so many queries of one's
    group, against every key of the row that a block's float32 scores are
    SCORE_BLOCK_BYTES at most."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    group, s = heads // kv, u.shape[0]
    q = nm.dot(u, lp["wq"]).reshape(s, heads, d)
    k = nm.dot(u, lp["wk"]).reshape(s, kv, d)
    if rotary:
        theta = float(cfg["rope_theta"])
        q, k = nm.act(_rotary(q, theta)), nm.act(_rotary(k, theta))
    v = nm.dot(u, lp["wv"]).reshape(s, kv, d)

    @jax.checkpoint
    def block(q, at, k, v):
        """Queries [rows, m, group, d] at positions ``at`` [rows] against
        keys [s, m, d]."""
        scores = nm.einsum("qngd,knd->ngqk", q, k) / jnp.sqrt(jnp.float32(d))
        probs = nm.act(jax.nn.softmax(
            jnp.where(seen(at, s, window), scores, -jnp.inf), -1))
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


def route(u, w_router, cfg):
    """-> (weights [s, top_k], chosen experts [s, top_k]), float32: the
    largest logits and their softmax among themselves."""
    logits = jnp.einsum("sh,he->se", u, w_router,
                        precision=lax.Precision.HIGHEST)
    picked, chosen = lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    return jax.nn.softmax(picked, -1), chosen


def experts(m, u, lp, cfg, nm):
    """The held experts' part for the positions ``m`` [n, hidden], picked
    and weighted by the router from ``u`` [n, hidden]."""
    weights, chosen = route(u, lp["router"], cfg)
    held, width = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    first = cfg["expert_rank"] * held

    @jax.checkpoint
    def some(m, mine, gate_up, down):
        """Experts [n, ...] over every token, summed under their masks
        ``mine`` [n, s]: each token's weight for the expert, or 0."""
        both = nm.einsum("sh,ehw->esw", m, gate_up)
        act = nm.act(jax.nn.relu(both[..., :width]) * both[..., width:])
        return jnp.sum(mine[..., None] * nm.einsum("esw,ewh->esh", act, down),
                       0)

    at_once = min(held, max(1, EXPERTS_BLOCK_BYTES
                            // (4 * m.shape[0] * m.shape[1])))
    while held % at_once:
        at_once -= 1
    ids = first + jnp.arange(held).reshape(-1, at_once)
    mine = jnp.sum(jnp.where(chosen[None, None] == ids[..., None, None],
                             weights[None, None], 0.0), -1)
    groups = _over_blocks(
        lambda *group: some(m, *group), mine,
        lp["e_gate_up"].reshape((-1, at_once) + lp["e_gate_up"].shape[1:]),
        lp["e_down"].reshape((-1, at_once) + lp["e_down"].shape[1:]))
    return jnp.sum(groups, 0)


def layer_row(x, lp, cfg, nm, window, rotary):
    """One row, [T, hidden], through one layer; ``lp``: its weights."""
    eps, s = cfg["rms_norm_eps"], x.shape[0]
    u = nm.act(_norm(x, lp["n1"], eps))
    a = nm.act(x + jax.checkpoint(
        lambda u: attention(u, lp, cfg, nm, window, rotary))(u))

    @jax.checkpoint
    def feed_forward(a, u):
        m = nm.act(_norm(a, lp["n2"], eps))
        return nm.act(a + experts(m, u, lp, cfg, nm))

    rows = min(s, ROW_BLOCK_POSITIONS)
    while s % rows:
        rows -= 1

    def blocks(y):
        return y.reshape((s // rows, rows) + y.shape[1:])
    return _over_blocks(feed_forward, blocks(a), blocks(u)).reshape(x.shape)


def hidden(params, ids, cfg, nm):
    """[batch, T] ids -> the stream after the last layer, [batch, T,
    hidden], before the final norm."""
    h = nm.act(params["embed"][ids])
    for i, (window, rotary) in enumerate(layer_kinds(cfg)):
        lp = layer_weights(params, i)
        h = _over_rows(
            lambda row: layer_row(row, lp, cfg, nm, window, rotary),
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
    return jnp.mean(_over_rows(head_row, s, h, ids)), {}     # no state
