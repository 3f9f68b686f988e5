"""SDAR-30B-A3B-Chat (JetLM/SDAR-30B-A3B-Chat ``config.json``,
``model_type: sdar_moe``; SDAR, arXiv:2510.06303) trained by diffusion over
blocks (the mask and loss of BD3-LMs, arXiv:2503.09573) on one chip's
share of an expert-parallel job, written from these equations in plain
``jax.numpy``.

``N(x) = x * rsqrt(mean(x^2) + 1e-6) * g`` with its own ``g`` at each
site; ``H`` = 32 query heads and ``H_kv`` = 4 key/value heads of width
128; no biases.

* One training row: ids ``x`` of length ``L``, block length ``B``,
  ``blk(i) = i // B``. For block ``k`` a level ``t_k = eps + (1 - eps)
  u_k``; for position ``i`` a draw ``u'_i``; ``m_i = [u'_i < t_blk(i)]``;
  ``xn_i = MASK if m_i else x_i``. ``u`` (``level``), ``u'`` (``draw``)
  and ``x`` (``ids``) are the batch's three leaves, uniform on [0, 1).
* The input is ``z = [xn ; x]``, the noisy copy first, ``2L`` long, with
  positions ``p(r) = r mod L``. ``h_0 = E[z]``; layer ``l``: ``a = h +
  Attn(N_1(h))``, ``h' = a + MoE(N_2(a))``; after the last layer ``N_f``,
  then the untied head over this chip's slice of the vocabulary, at the
  ``L`` noisy rows.
* ``Attn(u)``: ``q = W_q u`` as 32 heads, ``k = W_k u`` and ``v = W_v u``
  as 4; ``q`` and ``k`` each through an RMSNorm over their 128 with a
  learned scale (``q_norm``, ``k_norm``: the ``sdar_moe`` / Qwen3-MoE
  modeling file's; ``config.json`` has no key for it); rotary over the
  whole 128, pairs (``i``, ``i + 64``), ``theta`` 1e6, at ``p(r)``;
  scores ``q_h . k_{h // 8} / sqrt(128)`` under the mask ``M``, softmax,
  ``W_o`` on the heads concatenated.
* ``M``: with ``noisy(r)`` the copy of ``r`` and ``b(r) = blk(r mod L)``,
  query ``r`` sees key ``c`` iff: both noisy and ``b(c) == b(r)``; ``r``
  noisy, ``c`` clean and ``b(c) < b(r)``; both clean and ``b(c) <=
  b(r)``; a clean query sees no noisy key.
* ``MoE(u)``: ``p = softmax(float32(u) W_g)`` over all 128 experts;
  chosen = top-8 of ``p``; ``w = p[chosen] / sum p[chosen]``; ``y =
  sum_{e chosen and held} w_e E_e(u)``, ``E_e`` = SwiGLU 2048 -> 768 ->
  2048. No shared expert, no selection bias. **The share**: this chip
  holds ``num_experts`` experts, those of rank ``expert_rank`` of
  ``expert_parallel`` chips; the router keeps all ``num_experts *
  expert_parallel`` outputs. What the absent experts would add is left
  out, and that partial result goes on to the next layer.
* The weights are drawn from the seed (``init_params``), but for eight
  columns of every router, which take the ``[MASK]`` row besides, one
  expert a share (``spread_mask_row``): every seed's share then holds
  the same number of that row's picks, and so the same work.
* Loss of a batch ``= (1 / (batch L)) sum_rows sum_i m_i / t_blk(i) *
  (-log softmax(logits_i)[x_i])``: no shift, the label of a masked
  position is its own clean id.

Every held expert runs over every token under a mask of its picks, as in
the Kanana-2 reference, whose helpers for blocks these are. A doubled
row's float32 scores are 1 GiB a head at the cell's size, so a row goes
through a layer ROW_BLOCK_POSITIONS at a time (only its keys and values
are made whole), and within such a block the key/value heads and blocks
of a head group's queries (against every key of the row, the mask by the
four cases above) go through ``lax.map``, one body compiled once; at a
small size each is one block and no loop.
"""

import jax
import jax.numpy as jnp
from jax import lax

from .kanana2_30b_a3b import _over_blocks, _over_rows
from .ouro_2p6b import EXIT_BLOCK_POSITIONS, _norm

# positions of a doubled row that go through a layer at a time
ROW_BLOCK_POSITIONS = 2048
# float32 attention scores alive at a time: a block of one key/value
# head's query heads x queries x every key of the doubled row
SCORE_BLOCK_BYTES = 1 << 27
# float32 outputs [experts, positions of a block, hidden] of the held
# experts that run in one batched product: all 16 at the cell's size
EXPERTS_BLOCK_BYTES = 1 << 28

LAYER_KEYS = ("wq", "wk", "wv", "wo", "qn", "kn", "n1", "n2", "router",
              "e_gate_up", "e_down")


def routed_total(cfg):
    return cfg["num_experts"] * cfg["expert_parallel"]


def init_params(cfg, key):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, h), "lm_head": (h, v)}
    for n in range(cfg["num_hidden_layers"]):
        shapes.update({
            f"wq.{n}": (h, heads * d), f"wk.{n}": (h, kv * d),
            f"wv.{n}": (h, kv * d), f"wo.{n}": (heads * d, h),
            f"router.{n}": (h, routed_total(cfg)),
            f"e_gate_up.{n}": (held, h, 2 * width),
            f"e_down.{n}": (held, width, h)})
    keys = jax.random.split(key, len(shapes))

    def std(name):
        """One range for all collapses the routing at the start: a
        layer's attention output (a mean of values, written through
        ``W_o``) is to the normed stream what 0.02 x sqrt(4096) is to
        0.02, so whatever the positions share grows sixfold a layer,
        every router sees much the same vector, and a share's picks come
        in lumps that follow the batch. So the embedding's rows take
        their own range (a token's own row outweighs what attention
        averages in) and the two matrices that write to the residual
        stream the narrower one (GPT-2's and Megatron-LM's scaled
        initialisation)."""
        if name == "embed":
            return cfg["embedding_initializer_range"]
        if name.startswith(("wo.", "e_down.")):
            return cfg["residual_initializer_range"]
        return cfg["initializer_range"]
    out = {n: std(n) * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    for n in range(cfg["num_hidden_layers"]):
        out[f"n1.{n}"] = jnp.ones((h,), jnp.float32)
        out[f"n2.{n}"] = jnp.ones((h,), jnp.float32)
        out[f"qn.{n}"] = jnp.ones((d,), jnp.float32)
        out[f"kn.{n}"] = jnp.ones((d,), jnp.float32)
    out["norm_f"] = jnp.ones((h,), jnp.float32)
    return spread_mask_row(out, cfg, jax.random.fold_in(key, 1))


def spread_mask_row(params, cfg, key):
    """Half the noisy copy is one row of the embedding, ``[MASK]``'s, so
    a quarter of a layer's positions make one token's picks. Drawn
    routers leave it to the seed how many of the eight a share holds (0
    to 3 of them, 4096 rows each at the cell's size), and the work of a
    step with it. A router that balances its load sends such a token to
    experts on different chips: in every layer ``top_k / expert_parallel``
    experts of each share, drawn from the seed, have ``mask_route_logit``
    along the row's direction added to their column of the router, so
    the row's picks are those, one a share at the cell's size, by a
    margin that no drawn score (0.9 a standard deviation at the
    cell's width) comes near."""
    shares, held = cfg["expert_parallel"], cfg["num_experts"]
    each = cfg["num_experts_per_tok"] // shares
    row = params["embed"][cfg["mask_token_id"]]
    along = cfg["mask_route_logit"] * row / (
        jnp.linalg.norm(row) * jnp.sqrt(jnp.float32(row.size)))
    out = dict(params)
    for n in range(cfg["num_hidden_layers"]):
        order = jnp.argsort(jax.random.uniform(jax.random.fold_in(key, n),
                                               (shares, held)), -1)
        chosen = (order[:, :each] + held * jnp.arange(shares)[:, None])
        out[f"router.{n}"] = params[f"router.{n}"].at[
            :, chosen.reshape(-1)].add(along[:, None])
    return out


def layer_weights(params, i):
    return {k: params[f"{k}.{i}"] for k in LAYER_KEYS}


def noise(ids, level, draw, cfg):
    """-> (``z`` [batch, 2L], masked [batch, L], ``t`` of each position's
    block [batch, L])."""
    eps = cfg["noise_eps"]
    t = jnp.repeat(eps + (1.0 - eps) * level.astype(jnp.float32),
                   cfg["block_length"], axis=1)
    masked = draw.astype(jnp.float32) < t
    noisy = jnp.where(masked, jnp.asarray(cfg["mask_token_id"], ids.dtype),
                      ids)
    return jnp.concatenate([noisy, ids], axis=1), masked, t


def visible(q_noisy, q_block, k_noisy, k_block):
    """The mask by its four cases; each argument broadcasts."""
    return jnp.where(
        q_noisy,
        jnp.where(k_noisy, k_block == q_block, k_block < q_block),
        ~k_noisy & (k_block <= q_block))


def _rotary_at(x, at, theta):
    """x: [s, heads, d] at positions ``at`` [s]; pair (i, i + d/2) turns
    by position * theta^(-2i/d)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = at.astype(jnp.float32)[:, None] * freq[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def places(s, cfg):
    """Of a doubled row of ``s`` positions: (position within its copy, in
    the noisy copy, block) of each."""
    at = jnp.arange(s) % (s // 2)
    return at, jnp.arange(s) < s // 2, at // cfg["block_length"]


def keys_values(u, lp, cfg, nm):
    """One doubled row, [2L, hidden] -> k (normed, turned) and v, [2L,
    key/value heads, head_dim] each."""
    kv, d, s = cfg["num_key_value_heads"], cfg["head_dim"], u.shape[0]
    k = nm.dot(u, lp["wk"]).reshape(s, kv, d)
    k = _rotary_at(_norm(k, lp["kn"], cfg["rms_norm_eps"]), places(s, cfg)[0],
                   float(cfg["rope_theta"]))
    return nm.act(k), nm.dot(u, lp["wv"]).reshape(s, kv, d)


def attend(u, at, q_noisy, q_blk, k, v, lp, cfg, nm):
    """Queries of some positions of a doubled row, ``u`` [n, hidden] at
    ``at`` [n] (their copy ``q_noisy``, their block ``q_blk``), against
    every key of the row -> [n, heads * head_dim]. So many key/value heads
    at a time, or so many queries of one's group, that a block's float32
    scores are SCORE_BLOCK_BYTES at most."""
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    group, n, s = heads // kv, u.shape[0], k.shape[0]
    _, noisy, blk = places(s, cfg)
    q = nm.dot(u, lp["wq"]).reshape(n, heads, d)
    q = nm.act(_rotary_at(_norm(q, lp["qn"], cfg["rms_norm_eps"]), at,
                          float(cfg["rope_theta"])))

    @jax.checkpoint
    def block(q, q_noisy, q_blk, k, v):
        """Queries [rows, m, group, d] against keys [s, m, d]."""
        scores = nm.einsum("qngd,knd->ngqk", q, k) / jnp.sqrt(jnp.float32(d))
        seen = visible(q_noisy[:, None], q_blk[:, None], noisy[None],
                       blk[None])
        probs = nm.act(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1))
        return nm.einsum("ngqk,knd->qngd", probs, v)

    at_once = min(kv, max(1, SCORE_BLOCK_BYTES // (4 * n * s * group)))
    while kv % at_once:
        at_once -= 1
    rows = max(1, min(n, SCORE_BLOCK_BYTES // (4 * s * group * at_once)))
    while n % rows:
        rows -= 1

    def some_heads(q, k, v):
        """[n, at_once, group, d] against [s, at_once, d], ``rows``
        queries at a time."""
        ctx = _over_blocks(
            lambda q, c, b: block(q, c, b, k, v),
            q.reshape((n // rows, rows) + q.shape[1:]),
            q_noisy.reshape(-1, rows), q_blk.reshape(-1, rows))
        return ctx.reshape((n,) + ctx.shape[2:])

    def in_blocks(x):
        """[., kv, ...] -> [blocks of key/value heads, ., at_once, ...]."""
        return jnp.moveaxis(x.reshape(
            (x.shape[0], kv // at_once, at_once) + x.shape[2:]), 1, 0)

    ctx = _over_blocks(some_heads, in_blocks(q.reshape(n, kv, group, d)),
                       in_blocks(k), in_blocks(v))
    return jnp.moveaxis(ctx, 0, 1).reshape(n, heads * d)


def route(u, w_gate, cfg):
    """-> (weights [s, top_k], chosen experts [s, top_k]), float32."""
    p = jax.nn.softmax(jnp.einsum("sh,he->se", u, w_gate,
                                  precision=lax.Precision.HIGHEST), -1)
    picked, chosen = lax.top_k(p, cfg["num_experts_per_tok"])
    return picked / jnp.sum(picked, -1, keepdims=True), chosen


def experts(u, lp, cfg, nm):
    """The held experts' part for the positions ``u`` [n, hidden]."""
    weights, chosen = route(u, lp["router"], cfg)
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
    """One doubled row, [2L, hidden], through one layer. Only the keys and
    values are made for the whole row; everything else is a position's
    own, and goes ROW_BLOCK_POSITIONS at a time, each block recomputed on
    its own in the backward pass: whole, a row of 16384 positions keeps
    256 MiB a float32 activation of the heads' width and the reference
    shares the chip with its own 8.8 GB of weights, gradients and AdamW
    state."""
    eps, s = cfg["rms_norm_eps"], x.shape[0]
    u = nm.act(_norm(x, lp["n1"], eps))
    k, v = keys_values(u, lp, cfg, nm)

    @jax.checkpoint
    def some(x, u, at, noisy, blk):
        ctx = attend(u, at, noisy, blk, k, v, lp, cfg, nm)
        a = nm.act(x + nm.dot(ctx, lp["wo"]))
        return nm.act(a + experts(nm.act(_norm(a, lp["n2"], eps)), lp, cfg,
                                  nm))

    rows = min(s, ROW_BLOCK_POSITIONS)
    out = _over_blocks(some, *(y.reshape((s // rows, rows) + y.shape[1:])
                               for y in (x, u) + places(s, cfg)))
    return out.reshape(x.shape)


def loss(params, batch, cfg, nm):
    ids = batch["ids"]
    length = ids.shape[1]
    z, masked, t = noise(ids, batch["level"], batch["draw"], cfg)

    @jax.checkpoint
    def head_block(h, targets):
        """Cross-entropy per position of a block of one row's positions."""
        logits = nm.dot(nm.act(_norm(h, params["norm_f"],
                                     cfg["rms_norm_eps"])), params["lm_head"])
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked

    def head_row(h, row_ids):
        """[L]: the noisy rows, each against its own clean id."""
        cuts = list(range(0, length, EXIT_BLOCK_POSITIONS)) + [length]
        return jnp.concatenate([head_block(h[a:b], row_ids[a:b])
                                for a, b in zip(cuts, cuts[1:])])

    h = nm.act(params["embed"][z])
    for i in range(cfg["num_hidden_layers"]):
        lp = layer_weights(params, i)
        h = _over_rows(lambda row: layer_row(row, lp, cfg, nm), 2 * length, h)
    ce = _over_rows(head_row, length, h[:, :length], ids)
    return jnp.sum(jnp.where(masked, ce / t, 0.0)) / ce.size, {}
