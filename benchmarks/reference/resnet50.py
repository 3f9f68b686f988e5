"""ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1, 50-layer) in the
"v1.5" placement that ``paddle.vision.models.resnet50`` and torchvision
share: the stride of a down-sampling bottleneck sits on its 3x3 convolution.
Batch normalisation (Ioffe & Szegedy 2015) in training mode: the batch's own
biased statistics normalise, and the running statistics move towards them by
``1 - bn_momentum``. Loss = mean softmax cross-entropy. Plain ``jax.numpy``,
NCHW; every bottleneck is re-computed in the backward pass so that a whole
float32 batch fits (batch-norm statistics need the whole batch at once).
"""

import jax
import jax.numpy as jnp
from jax import lax


def _blocks(cfg):
    """(stage, block, in channels, width, stride) of every bottleneck."""
    inp = cfg["stem_channels"]
    for s, (n, width) in enumerate(zip(cfg["blocks"], cfg["widths"])):
        for b in range(n):
            yield s, b, inp, width, (2 if (b == 0 and s > 0) else 1)
            inp = width * cfg["expansion"]


def _conv_shapes(cfg):
    shapes = {"conv1_w": (cfg["stem_channels"], cfg["in_channels"], 7, 7)}
    bns = {"bn1": cfg["stem_channels"]}
    for s, b, inp, width, _ in _blocks(cfg):
        p, out = f"s{s}b{b}_", width * cfg["expansion"]
        shapes[p + "conv1_w"] = (width, inp, 1, 1)
        shapes[p + "conv2_w"] = (width, width, 3, 3)
        shapes[p + "conv3_w"] = (out, width, 1, 1)
        bns[p + "bn1"], bns[p + "bn2"], bns[p + "bn3"] = width, width, out
        if b == 0:
            shapes[p + "down_w"] = (out, inp, 1, 1)
            bns[p + "downbn"] = out
    return shapes, bns


def init_params(cfg, key):
    """He-normal convolutions (std = sqrt(2 / fan_in)), unit batch-norm
    scale, zero shift, N(0, 0.01) classifier: He et al. 2015b."""
    shapes, bns = _conv_shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 1)
    out = {}
    for (n, s), k in zip(sorted(shapes.items()), keys):
        fan_in = s[1] * s[2] * s[3]
        out[n] = jnp.sqrt(2.0 / fan_in) * jax.random.normal(
            k, s, jnp.float32)
    for n, c in bns.items():
        out[n + "_g"] = jnp.ones((c,), jnp.float32)
        out[n + "_b"] = jnp.zeros((c,), jnp.float32)
        out[n + "_mean"] = jnp.zeros((c,), jnp.float32)
        out[n + "_var"] = jnp.ones((c,), jnp.float32)
    feat = cfg["widths"][-1] * cfg["expansion"]
    out["fc_w"] = 0.01 * jax.random.normal(
        keys[-1], (feat, cfg["num_classes"]), jnp.float32)
    out["fc_b"] = jnp.zeros((cfg["num_classes"],), jnp.float32)
    return out


def loss(params, batch, cfg, nm):
    eps, mom = cfg["bn_epsilon"], cfg["bn_momentum"]
    updates = {}

    def bn(x, name, relu):
        mean = x.mean((0, 2, 3))
        var = jnp.square(x - mean[None, :, None, None]).mean((0, 2, 3))
        y = (x - mean[None, :, None, None]) * lax.rsqrt(
            var + eps)[None, :, None, None]
        y = y * params[name + "_g"][None, :, None, None] \
            + params[name + "_b"][None, :, None, None]
        return nm.act(jnp.maximum(y, 0.0) if relu else y), (mean, var)

    def note(name, stats):
        for which, stat in zip(("_mean", "_var"), stats):
            old = lax.stop_gradient(params[name + which])
            updates[name + which] = mom * old + (1 - mom) * \
                lax.stop_gradient(stat)

    x, st = bn(nm.conv(batch["x"], params["conv1_w"], 2, 3), "bn1", True)
    note("bn1", st)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, b, _, _, stride in _blocks(cfg):
        p = f"s{s}b{b}_"

        @jax.checkpoint
        def bottleneck(x, p=p, stride=stride, first=(b == 0)):
            y, s1 = bn(nm.conv(x, params[p + "conv1_w"], 1, 0),
                       p + "bn1", True)
            y, s2 = bn(nm.conv(y, params[p + "conv2_w"], stride, 1),
                       p + "bn2", True)
            y, s3 = bn(nm.conv(y, params[p + "conv3_w"], 1, 0),
                       p + "bn3", False)
            stats = {"bn1": s1, "bn2": s2, "bn3": s3}
            if first:
                x, stats["downbn"] = bn(
                    nm.conv(x, params[p + "down_w"], stride, 0),
                    p + "downbn", False)
            return nm.act(jnp.maximum(x + y, 0.0)), stats

        x, stats = bottleneck(x)
        for n, st in stats.items():
            note(p + n, st)
    logits = nm.dot(x.mean((2, 3)), params["fc_w"]) + params["fc_b"]
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, batch["y"][:, None], -1)[:, 0]
    return nll.mean(), updates
