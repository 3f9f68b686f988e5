"""Kanana-2-30B-A3B (kakaocorp/kanana-2-30b-a3b-instruct-2601
``config.json``, ``model_type: deepseek_v3``) pre-training on one chip's
share of an expert-parallel job, written from these equations in plain
``jax.numpy``.

Tokens ``ids[b, s]``; ``E`` the vocab x hidden embedding, ``W_out`` the
hidden x vocab head (untied); ``N(x) = x * rsqrt(mean(x^2) + 1e-6) * g``
with its own ``g`` at each site; ``H`` = 32 heads; positions ``0..s-1``;
no biases (``attention_bias`` false).

* ``h_0 = E[ids]``; layer ``l`` = 0..L-1: ``a = x + Attn(N_1(x))``,
  ``y = a + F_l(N_2(a))``; ``F_0`` a dense SwiGLU 2048 -> 6144 -> 2048
  (``first_k_dense_replace`` 1), ``F_l = MoE`` for ``l >= 1``; logits
  ``N_f(h_L) W_out``; loss = mean next-token cross-entropy (the last
  position of a row has no target), log-sum-exp in float32.
* ``Attn(u)``: ``q = u W_q`` -> per head ``[q_nope(128); q_pe(64)]``.
  ``[c(512); k_pe(64)] = u W_kva``; ``c = N_kv(c)``; ``c W_kvb`` -> per
  head ``[k_nope(128); v(128)]``. Rotary, ``theta`` 1e6, on ``q_pe`` of
  every head and on the one ``k_pe``, which all heads share: pairs
  (2i, 2i+1) turn by ``pos * theta^(-2i/64)`` (``rope_interleave``; the
  public code de-interleaves q and k alike before a rotate-half, and a
  permutation common to both leaves every score as it was). ``k =
  [k_nope; k_pe]``. ``softmax(q k^T / sqrt(192) + causal) v``, heads
  concatenated to 4096, ``W_o``. ``rope_scaling`` null: no extra scale.
* ``MoE(u)`` per token: ``s = sigmoid(float32(u) W_g)`` in R^128; chosen
  = top-6 of ``s + b`` (``n_group`` = ``topk_group`` = 1: no group
  stage); ``w = s[chosen] / (sum s[chosen] + 1e-20) * 2.448``; ``y =
  sum_{e chosen and held} w_e E_e(u) + S(u)``; ``E_e`` = SwiGLU 2048 ->
  768 -> 2048, ``S`` = one SwiGLU 2048 -> 1536 -> 2048 (the 2 shared
  experts). **The share**: this chip holds ``n_routed_experts`` experts,
  those of rank ``expert_rank`` of ``expert_parallel`` chips; the router
  keeps all ``n_routed_experts * expert_parallel`` outputs. What the
  absent experts would add is left out, and that partial result goes on to
  the next layer. The vocabulary is a slice: ids, logits and loss over it.

Departures, each under ``assumed`` in the configuration's file: ``b``
(``e_score_correction_bias``) is a float32 state of zeros that no
gradient and no update reaches (``loss`` hands it back unchanged as a
state update, which keeps it from the update rule); no auxiliary loss;
the recipe of the job.

Every held expert runs over every token under a mask of its picks (16 /
0.75 times the routed work of the program, which sorts: fine for three
steps; ``model_flops`` counts the picks), EXPERTS_BLOCK_BYTES' worth of
experts in one batched product. Layers, blocks of queries and of
positions are Python loops under ``jax.checkpoint`` as in the Ouro
reference, whose helpers these are. Rows, blocks of heads and groups of
experts are not, where there is more than one: at the cell's size one
head's float32 scores of a row are 268 MB, taken 4096 queries at a time,
and the compiler writes 1 to 2 MB of code, in 6 to 11 s of one core, for
every float32 ``highest`` product that size that the program unrolls (at
depth 2 of 5 the unrolled step was 2.8 GB of code, 846 s and 31 GiB of
host memory to compile for a described v5e; on the chip's machine the
control's compile met its 40 GiB).
So they go through ``lax.map``, whose body is compiled once; at a small
size each is one block and no loop, which is what
``test_flops_against_xla_cost_analysis`` counts.
"""

import jax
import jax.numpy as jnp
from jax import lax

from .ouro_2p6b import BLOCK_TOKENS, EXIT_BLOCK_POSITIONS, _norm
from .ouro_2p6b import _over_rows as _rows_at_once

# float32 attention scores alive at a time: half of Ouro's, a block of
# heads at small sizes, of one head's queries at the cell's (8192 x 8192 x 4
# B is 256 MB a head, and the control one precision down keeps several
# copies of a block, rounded and not: with whole heads it needed 16.02 of
# the chip's 15.75 GB)
SCORE_BLOCK_BYTES = 1 << 27
# float32 outputs [experts, tokens of a row, hidden] of the held experts
# that run in one batched product: 4 of the 16 at the cell's size
EXPERTS_BLOCK_BYTES = 1 << 28

ATTENTION_KEYS = ("wq", "wkva", "nkv", "wkvb", "wo", "n1", "n2")
DENSE_KEYS = ("w_gate", "w_up", "w_down")
EXPERT_KEYS = ("router", "e_bias", "e_gate_up", "e_down", "s_gate", "s_up",
               "s_down")


def is_dense(cfg, layer):
    return layer < cfg["first_k_dense_replace"]


def routed_total(cfg):
    return cfg["n_routed_experts"] * cfg["expert_parallel"]


def init_params(cfg, key):
    h, v, heads = (cfg["hidden_size"], cfg["vocab_size"],
                   cfg["num_attention_heads"])
    nope, rope, vd, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    held, width = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * width
    shapes = {"embed": (v, h), "lm_head": (h, v)}
    for n in range(cfg["num_hidden_layers"]):
        layer = {"wq": (h, heads * (nope + rope)), "wkva": (h, rank + rope),
                 "wkvb": (rank, heads * (nope + vd)), "wo": (heads * vd, h)}
        if is_dense(cfg, n):
            i = cfg["intermediate_size"]
            layer.update(w_gate=(h, i), w_up=(h, i), w_down=(i, h))
        else:
            layer.update(router=(h, routed_total(cfg)),
                         e_gate_up=(held, h, 2 * width),
                         e_down=(held, width, h), s_gate=(h, shared),
                         s_up=(h, shared), s_down=(shared, h))
        shapes.update({f"{k}.{n}": s for k, s in layer.items()})
    keys = jax.random.split(key, len(shapes))
    out = {n: cfg["initializer_range"] * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    for n in range(cfg["num_hidden_layers"]):
        out[f"n1.{n}"] = jnp.ones((h,), jnp.float32)
        out[f"n2.{n}"] = jnp.ones((h,), jnp.float32)
        out[f"nkv.{n}"] = jnp.ones((rank,), jnp.float32)
        if not is_dense(cfg, n):
            out[f"e_bias.{n}"] = jnp.zeros((routed_total(cfg),), jnp.float32)
    out["norm_f"] = jnp.ones((h,), jnp.float32)
    return out


def layer_weights(params, cfg, i):
    keys = ATTENTION_KEYS + (DENSE_KEYS if is_dense(cfg, i) else EXPERT_KEYS)
    return {k: params[f"{k}.{i}"] for k in keys}


def _rotary_pairs(x, theta):
    """x: [s, heads, d]; pair (2i, 2i+1) turns by position *
    theta^(-2i/d)."""
    s, heads, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    pairs = x.reshape(s, heads, d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(s, heads, d)


def attend(u, lp, cfg, nm):
    """One batch row: [s, hidden] -> [s, heads * v_head_dim], so many heads
    at a time, or so many of one head's queries (against the keys up to
    the last of them), that a block's float32 scores are SCORE_BLOCK_BYTES
    at most."""
    heads, theta = cfg["num_attention_heads"], float(cfg["rope_theta"])
    nope, rope, vd, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"], cfg["kv_lora_rank"])
    s = u.shape[0]
    q = nm.dot(u, lp["wq"]).reshape(s, heads, nope + rope)
    both = nm.dot(u, lp["wkva"])
    latent = nm.act(_norm(both[:, :rank], lp["nkv"], cfg["rms_norm_eps"]))
    kv = nm.dot(latent, lp["wkvb"]).reshape(s, heads, nope + vd)
    k_pe = _rotary_pairs(both[:, None, rank:], theta)       # one a token
    q = nm.act(jnp.concatenate(
        [q[..., :nope], _rotary_pairs(q[..., nope:], theta)], -1))
    k = nm.act(jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s, heads, rope))], -1))
    v = kv[..., nope:]

    @jax.checkpoint
    def block(q, k, v):
        """Queries [rows, n, d] that end where the keys [upto, n, d] end."""
        rows, upto = q.shape[0], k.shape[0]
        scores = nm.einsum("qnd,knd->nqk", q, k) \
            / jnp.sqrt(jnp.float32(nope + rope))
        seen = jnp.tril(jnp.ones((rows, upto), bool), upto - rows)
        probs = nm.act(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1))
        return nm.einsum("nqk,knd->qnd", probs, v)

    at_once = min(heads, max(1, SCORE_BLOCK_BYTES // (4 * s * s)))
    while heads % at_once:
        at_once -= 1
    rows = min(s, SCORE_BLOCK_BYTES // (4 * s * at_once))

    def in_blocks(x):
        """[s, heads, d] -> [blocks of heads, s, at_once, d]."""
        return jnp.moveaxis(x.reshape(s, heads // at_once, at_once, -1), 1, 0)

    q, k, v = in_blocks(q), in_blocks(k), in_blocks(v)
    ctx = jnp.concatenate(
        [_over_blocks(block, q[:, r:r + rows], k[:, :r + rows],
                      v[:, :r + rows]) for r in range(0, s, rows)], axis=1)
    return jnp.moveaxis(ctx, 0, 1).reshape(s, heads * vd)


def _over_blocks(f, *xs):
    """``f`` over the leading axis of every ``xs``: a call where that is
    one block, ``lax.map`` (one body, compiled once) where it is more."""
    if xs[0].shape[0] == 1:
        return f(*(x[0] for x in xs))[None]
    return lax.map(lambda block: f(*block), xs)


def _over_rows(f, tokens_a_row, *xs):
    """``f`` maps one batch row (the leading axis of every ``xs``), each
    application recomputed in the backward pass: Ouro's helper where
    several rows make a block (a small size), one body for all the rows
    where a row is a block."""
    if 2 * tokens_a_row <= BLOCK_TOKENS:
        return _rows_at_once(f, tokens_a_row, *xs)
    return _over_blocks(jax.checkpoint(f), *xs)


def swiglu(u, gate, up, down, nm):
    return nm.dot(nm.act(jax.nn.silu(nm.dot(u, gate)) * nm.dot(u, up)), down)


def route(u, w_gate, bias, cfg):
    """-> (weights [s, top_k], chosen experts [s, top_k]), float32."""
    scores = jax.nn.sigmoid(jnp.einsum("sh,he->se", u, w_gate,
                                       precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    return (picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
            * cfg["routed_scaling_factor"]), chosen


def experts(u, lp, cfg, nm):
    """The held experts' part and the shared experts, one batch row."""
    weights, chosen = route(u, lp["router"], lp["e_bias"], cfg)
    held, width = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
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
    return swiglu(u, lp["s_gate"], lp["s_up"], lp["s_down"], nm) \
        + jnp.sum(groups, 0)


def layer_row(x, lp, cfg, nm):
    """One batch row, [s, hidden], through one layer; ``lp``: its weights."""
    eps = cfg["rms_norm_eps"]
    ctx = attend(nm.act(_norm(x, lp["n1"], eps)), lp, cfg, nm)
    a = nm.act(x + nm.dot(ctx, lp["wo"]))
    u = nm.act(_norm(a, lp["n2"], eps))
    ff = (swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"], nm)
          if "w_gate" in lp else experts(u, lp, cfg, nm))
    return nm.act(a + ff)


def loss(params, batch, cfg, nm):
    ids = batch["ids"]
    s = ids.shape[1]

    @jax.checkpoint
    def head_block(h, targets):
        """Cross-entropy per position of a block of one row's positions."""
        logits = nm.dot(nm.act(_norm(h, params["norm_f"],
                                     cfg["rms_norm_eps"])), params["lm_head"])
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked

    def head_row(h, row_ids):
        """[s - 1]: the last position of a row has no target."""
        cuts = list(range(0, s - 1, EXIT_BLOCK_POSITIONS)) + [s - 1]
        return jnp.concatenate([head_block(h[a:b], row_ids[a + 1:b + 1])
                                for a, b in zip(cuts, cuts[1:])])

    h = nm.act(params["embed"][ids])
    for i in range(cfg["num_hidden_layers"]):
        lp = layer_weights(params, cfg, i)
        h = _over_rows(lambda row: layer_row(row, lp, cfg, nm), s, h)
    # the selection bias is a state, not a weight: handed back as it came
    state = {k: v for k, v in params.items() if k.startswith("e_bias.")}
    return jnp.mean(_over_rows(head_row, s, h, ids)), state
