import numpy as np


def draw(rng, field, resolve):
    shape = tuple(resolve(s) for s in field["shape"])
    return rng.standard_normal(shape, dtype=np.float32).astype(
        field["dtype"])
