"""AdamW (Loshchilov & Hutter 2019): the decoupled decay BERT's job uses."""

import jax.numpy as jnp


def init(params, opt):
    return {"m": {k: jnp.zeros_like(v) for k, v in params.items()},
            "v": {k: jnp.zeros_like(v) for k, v in params.items()},
            "t": jnp.zeros((), jnp.int32)}


def update(params, grads, state, lr, opt):
    b1, b2, eps, wd = (opt["beta1"], opt["beta2"], opt["epsilon"],
                       opt["weight_decay"])
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    m = {k: b1 * state["m"][k] + (1 - b1) * g for k, g in grads.items()}
    v = {k: b2 * state["v"][k] + (1 - b2) * g * g for k, g in grads.items()}
    new = {}
    for k, p in params.items():
        step = (m[k] / (1 - b1 ** tf)) / (
            jnp.sqrt(v[k] / (1 - b2 ** tf)) + eps)
        new[k] = p - lr * (step + wd * p)
    return new, {"m": m, "v": v, "t": t}, grads
