"""SGD with momentum and L2 decay (Goyal et al. 2017, eq. 9)."""

import jax.numpy as jnp


def init(params, opt):
    return {"u": {k: jnp.zeros_like(v) for k, v in params.items()}}


def update(params, grads, state, lr, opt):
    mu, wd = opt["momentum"], opt["weight_decay"]
    g_in = {k: g + wd * params[k] for k, g in grads.items()}
    u = {k: mu * state["u"][k] + g_in[k] for k in grads}
    return {k: p - lr * u[k] for k, p in params.items()}, {"u": u}, g_in
