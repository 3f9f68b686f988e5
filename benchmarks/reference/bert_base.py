"""BERT (Devlin et al. 2018, arXiv:1810.04805) pre-training: the encoder of
Vaswani et al. 2017 with post-layer-norm blocks and GELU, the masked-LM head
tied to the word embedding, and the next-sentence head on the pooled first
token. Loss = mean masked-LM cross-entropy + mean next-sentence
cross-entropy. Plain ``jax.numpy``; the layers are a ``lax.scan`` over
stacked weights, each re-computed in the backward pass so that the float32
activations of a whole batch fit beside nothing else.

Every id of the traffic is a real token, so no attention mask is built.
The layer-norm epsilon is the configuration's.
"""

import jax
import jax.numpy as jnp
from jax import lax

LAYER_KEYS = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo", "ln1_g",
              "ln1_b", "w1", "b1", "w2", "b2", "ln2_g", "ln2_b")


def init_params(cfg, key):
    h, i, l = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    v, p, t = (cfg["vocab_size"], cfg["max_position_embeddings"],
               cfg["type_vocab_size"])
    std = cfg["initializer_range"]
    shapes = {"word_emb": (v, h), "pos_emb": (p, h), "type_emb": (t, h),
              "wq": (l, h, h), "wk": (l, h, h), "wv": (l, h, h),
              "wo": (l, h, h), "w1": (l, h, i), "w2": (l, i, h),
              "pool_w": (h, h), "mlm_w": (h, h), "nsp_w": (h, 2)}
    keys = jax.random.split(key, len(shapes))
    out = {n: std * jax.random.normal(k, s, jnp.float32)
           for (n, s), k in zip(sorted(shapes.items()), keys)}
    zeros = {"bq": (l, h), "bk": (l, h), "bv": (l, h), "bo": (l, h),
             "b1": (l, i), "b2": (l, h), "ln1_b": (l, h), "ln2_b": (l, h),
             "emb_ln_b": (h,), "pool_b": (h,), "mlm_b": (h,),
             "mlm_ln_b": (h,), "mlm_bias": (v,), "nsp_b": (2,)}
    ones = {"ln1_g": (l, h), "ln2_g": (l, h), "emb_ln_g": (h,),
            "mlm_ln_g": (h,)}
    out.update({n: jnp.zeros(s, jnp.float32) for n, s in zeros.items()})
    out.update({n: jnp.ones(s, jnp.float32) for n, s in ones.items()})
    return out


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def _xent(logits, labels):
    return (jax.nn.logsumexp(logits, -1)
            - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])


def loss(params, batch, cfg, nm):
    heads, eps = cfg["num_attention_heads"], cfg["layer_norm_eps"]
    ids = batch["ids"]
    b, s = ids.shape
    h = cfg["hidden_size"]
    x = (params["word_emb"][ids] + params["pos_emb"][:s][None]
         + params["type_emb"][0][None, None])
    x = nm.act(_layer_norm(x, params["emb_ln_g"], params["emb_ln_b"], eps))

    @jax.checkpoint
    def block(x, lp):
        def split(y):
            return y.reshape(b, s, heads, h // heads)
        q = split(nm.dot(x, lp["wq"]) + lp["bq"])
        k = split(nm.dot(x, lp["wk"]) + lp["bk"])
        v = split(nm.dot(x, lp["wv"]) + lp["bv"])
        att = nm.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(
            jnp.float32(h // heads))
        att = nm.act(jax.nn.softmax(att, -1))
        ctx = nm.einsum("bnqk,bknd->bqnd", att, v).reshape(b, s, h)
        x = nm.act(_layer_norm(x + nm.dot(ctx, lp["wo"]) + lp["bo"],
                               lp["ln1_g"], lp["ln1_b"], eps))
        ff = nm.dot(nm.act(_gelu(nm.dot(x, lp["w1"]) + lp["b1"])), lp["w2"])
        return nm.act(_layer_norm(x + ff + lp["b2"], lp["ln2_g"],
                                  lp["ln2_b"], eps)), None

    x, _ = lax.scan(block, x, {k: params[k] for k in LAYER_KEYS})

    pooled = nm.act(jnp.tanh(nm.dot(x[:, 0], params["pool_w"])
                             + params["pool_b"]))
    nsp_logits = nm.dot(pooled, params["nsp_w"]) + params["nsp_b"]
    tokens = x.reshape(b * s, h)
    if "mlm_pos" in batch:
        tokens = tokens[batch["mlm_pos"]]
    t = nm.act(_gelu(nm.dot(tokens, params["mlm_w"]) + params["mlm_b"]))
    t = nm.act(_layer_norm(t, params["mlm_ln_g"], params["mlm_ln_b"], eps))
    logits = nm.einsum("mh,vh->mv", t, params["word_emb"]) \
        + params["mlm_bias"]
    mlm = _xent(logits, batch["mlm_labels"].reshape(-1)).mean()
    nsp = _xent(nsp_logits, batch["nsp"]).mean()
    return mlm + nsp, {}
