def draw(rng, field, resolve):
    shape = tuple(resolve(s) for s in field["shape"])
    return rng.integers(resolve(field["low"]), resolve(field["high"]),
                        shape).astype(field["dtype"])
