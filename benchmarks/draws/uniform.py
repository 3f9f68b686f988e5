"""Real-valued leaves, uniform on [0, 1): the noise levels and the draws of
a diffusion batch."""

import numpy as np


def draw(rng, field, resolve):
    shape = tuple(resolve(s) for s in field["shape"])
    return rng.random(shape, dtype=np.float32).astype(field["dtype"])
