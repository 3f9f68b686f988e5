"""Ouro-2.6B (ByteDance/Ouro-2.6B ``config.json``, ``model_type: ouro``;
Zhu et al. 2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) pre-training, written from these equations in plain
``jax.numpy``.

Tokens ``ids[b, s]``; ``E`` the vocab x hidden embedding, ``W_out`` the
hidden x vocab head (untied); ``N(x) = x * rsqrt(mean(x^2) + eps) * g`` with
its own ``g`` at each site; ``L`` layers; ``T = total_ut_steps``.

* ``h_0 = E[ids]``. For ``t = 1..T``: ``h_t = N_f(Stack(h_{t-1}))``, the same
  ``Stack`` and the same final norm ``N_f`` at every ``t`` (the public
  implementation applies the final norm at the end of every pass and feeds
  its output to the next pass).
* ``Stack`` = layers ``1..L`` in order. One layer, sandwich norm:
  ``a = x + N_2(Attn(N_1(x)))``, ``y = a + N_4(MLP(N_3(a)))``.
* ``Attn(u)``: ``q, k, v = u W_q, u W_k, u W_v`` split into heads of
  ``head_dim``; rotary positions on ``q`` and ``k`` (rotate-half pairing,
  ``theta = rope_theta``, positions ``0..s-1``, the same at every loop
  step); ``softmax(q k^T / sqrt(head_dim) + causal mask) v``; ``W_o``.
* ``MLP(u) = (silu(u W_gate) * (u W_up)) W_down``.
* Exit ``t``: logits ``z_t = h_t W_out``; ``l_t[b, s]`` = cross-entropy of
  ``z_t[b, s]`` against ``ids[b, s+1]``; the last position of a row has no
  target and weight 0.
* Gate: ``lam_t = sigmoid(h_t . w_g + b_g)`` per token, one ``w_g, b_g`` for
  all ``t``. Exit distribution per token: ``p_1 = lam_1``,
  ``p_t = lam_t * prod_{j<t}(1 - lam_j)`` for ``t < T``,
  ``p_T = prod_{j<T}(1 - lam_j)``: the last step takes what is left.
* Loss (the paper's stage-I objective, uniform prior): the mean over the
  tokens that have a target of ``sum_t p_t * l_t - beta * H(p)``,
  ``H(p) = -sum_t p_t log p_t``.

Departures, each under ``assumed`` in the configuration's file: no biases in
attention or feed-forward (the config has no ``attention_bias`` key);
``beta``; the recipe of the job; ``early_exit_threshold`` is an inference
setting and unused.

Layers, loop steps, exits and blocks of rows, of heads and of positions are
Python loops, each application under ``jax.checkpoint``, so that XLA's count
of the forward pass counts every application (it counts a scan's body once)
and so that at the cell's size one row of one layer, a few heads' float32
attention scores (256 MB) and 2048 positions' logits (400 MB) are what the
backward pass holds at a time: the control one precision down keeps several
copies of each, rounded and not. At a small size the rows of a batch go
through ``vmap`` together.
"""

import jax
import jax.numpy as jnp
from jax import lax

LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
              "n1", "n2", "n3", "n4")


def init_params(cfg, key):
    """A layer's weights are leaves of their own, ``wq.0`` .. ``wq.<L-1>``:
    a stacked leaf would be sliced once a layer and the slices, used once a
    loop step, kept beside it for the whole step."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    a = cfg["num_attention_heads"] * cfg["head_dim"]
    per_layer = {"wq": (h, a), "wk": (h, a), "wv": (h, a), "wo": (a, h),
                 "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h)}
    shapes = {"embed": (v, h), "lm_head": (h, v), "gate_w": (h, 1)}
    for n in range(cfg["num_hidden_layers"]):
        shapes.update({f"{k}.{n}": s for k, s in per_layer.items()})
    keys = jax.random.split(key, len(shapes))
    out = {n: cfg["initializer_range"] * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    out.update({f"{k}.{n}": jnp.ones((h,), jnp.float32)
                for k in ("n1", "n2", "n3", "n4")
                for n in range(cfg["num_hidden_layers"])})
    out["norm_f"] = jnp.ones((h,), jnp.float32)
    out["gate_b"] = jnp.zeros((1,), jnp.float32)
    return out


def layer_weights(params, i):
    return {k: params[f"{k}.{i}"] for k in LAYER_KEYS}


def _norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rotary(x, theta):
    """x: [s, heads, d]; pair (i, i + d/2) turns by position * theta^(-2i/d)."""
    s, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


SCORE_BLOCK_BYTES = 1 << 28     # float32 attention scores alive at a time
BLOCK_TOKENS = 4096             # tokens a layer or an exit takes at a time
EXIT_BLOCK_POSITIONS = 2048     # positions of a row whose logits are alive


def _over_rows(f, tokens_a_row, *xs):
    """``f`` maps one batch row (the leading axis of every ``xs``), each
    application recomputed in the backward pass. At the cell's size a row
    is a block and the rows are a Python loop; at a small size BLOCK_TOKENS'
    worth of rows go through ``vmap`` at once."""
    rows, at_once = xs[0].shape[0], max(1, BLOCK_TOKENS // tokens_a_row)
    if at_once == 1:
        one = jax.checkpoint(f)
        return jnp.stack([one(*(x[r] for x in xs)) for r in range(rows)])
    many = jax.checkpoint(jax.vmap(f))
    return jnp.concatenate([many(*(x[r:r + at_once] for x in xs))
                            for r in range(0, rows, at_once)])


def attend(u, lp, cfg, nm):
    """One batch row: [s, hidden] -> [s, heads * head_dim], so many heads
    at a time that a block's float32 scores are SCORE_BLOCK_BYTES at most."""
    heads, d, theta = (cfg["num_attention_heads"], cfg["head_dim"],
                       float(cfg["rope_theta"]))
    s = u.shape[0]
    q = nm.act(_rotary(nm.dot(u, lp["wq"]).reshape(s, heads, d), theta))
    k = nm.act(_rotary(nm.dot(u, lp["wk"]).reshape(s, heads, d), theta))
    v = nm.dot(u, lp["wv"]).reshape(s, heads, d)

    @jax.checkpoint
    def block(q, k, v):
        scores = nm.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(jnp.float32(d))
        seen = jnp.tril(jnp.ones((s, s), bool))
        probs = nm.act(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1))
        return nm.einsum("nqk,knd->qnd", probs, v)

    at_once = max(1, SCORE_BLOCK_BYTES // (4 * s * s))
    return jnp.concatenate(
        [block(q[:, i:i + at_once], k[:, i:i + at_once], v[:, i:i + at_once])
         for i in range(0, heads, at_once)], axis=1).reshape(s, heads * d)


def layer_row(x, lp, cfg, nm):
    """One batch row, [s, hidden], through one layer; ``lp``: its weights."""
    eps = cfg["rms_norm_eps"]
    ctx = attend(nm.act(_norm(x, lp["n1"], eps)), lp, cfg, nm)
    a = nm.act(x + _norm(nm.dot(ctx, lp["wo"]), lp["n2"], eps))
    u = nm.act(_norm(a, lp["n3"], eps))
    ff = nm.dot(nm.act(jax.nn.silu(nm.dot(u, lp["w_gate"]))
                       * nm.dot(u, lp["w_up"])), lp["w_down"])
    return nm.act(a + _norm(ff, lp["n4"], eps))


def layer(x, lp, cfg, nm):
    """[rows, s, hidden] through one layer."""
    return _over_rows(lambda row: layer_row(row, lp, cfg, nm), x.shape[1], x)


def exit_distribution(gates):
    """``gates``: lam_1..lam_T -> p_1..p_T, which sum to 1."""
    left, out = jnp.ones_like(gates[0]), []
    for t, lam in enumerate(gates):
        out.append(left if t == len(gates) - 1 else lam * left)
        left = left * (1.0 - lam)
    return out


def loss(params, batch, cfg, nm):
    eps, beta = cfg["rms_norm_eps"], cfg["exit_entropy_beta"]
    ids = batch["ids"]
    s = ids.shape[1]

    @jax.checkpoint
    def exit_block(h, targets):
        """Cross-entropy per position of a block of one row's positions."""
        logits = nm.dot(h, params["lm_head"])
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jax.nn.logsumexp(logits, -1) - picked

    def exit_row(h, row_ids):
        """[s - 1]: the last position of a row has no target."""
        cuts = list(range(0, s - 1, EXIT_BLOCK_POSITIONS)) + [s - 1]
        return jnp.concatenate([exit_block(h[a:b], row_ids[a + 1:b + 1])
                                for a, b in zip(cuts, cuts[1:])])

    @jax.checkpoint
    def stack(h, layers, norm_f):
        """One pass; what it keeps for the backward pass is its input."""
        for lp in layers:
            h = layer(h, lp, cfg, nm)
        return nm.act(_norm(h, norm_f, eps))

    h = nm.act(params["embed"][ids])
    exits, gates = [], []
    for _ in range(cfg["total_ut_steps"]):
        h = stack(h, [layer_weights(params, i)
                      for i in range(cfg["num_hidden_layers"])],
                  params["norm_f"])
        exits.append(_over_rows(exit_row, s, h, ids))
        gates.append(jax.nn.sigmoid(
            jnp.einsum("bsh,h->bs", h[:, :-1], params["gate_w"][:, 0],
                       precision=lax.Precision.HIGHEST) + params["gate_b"][0]))
    total, entropy = 0.0, 0.0
    for l_t, p in zip(exits, exit_distribution(gates)):
        total = total + p * l_t
        entropy = entropy - p * jnp.log(p)
    return jnp.mean(total - beta * entropy), {}
