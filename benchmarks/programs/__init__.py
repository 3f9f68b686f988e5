"""The system under test, one module per configuration. This is the only
place where the benchmark touches ``paddle1_tpu``: it builds the Layer, the
optimizer and ``ParallelEngine`` as a trainer would, from the sizes in the
configuration's file, and says which of the reference's weights each of the
program's parameters is.

``leaves(cfg)`` -> [(program name, reference name, index in a stacked
reference weight or None)], and ``build(cfg, env, weights, devices)``, with
``weights`` keyed by program name, -> dict with
  ``engine``      the ``ParallelEngine`` (its ``step`` is what is timed),
  ``model``       the Layer, which keeps the initial weights,
  ``first_grad``  (slot name, factor): the optimizer slot that, after one
                  step from zero state, holds factor^-1 x the gradient the
                  update rule was given.
"""


def load_weights(model, weights):
    """Put the benchmark's weights, keyed by the program's own parameter
    names, into the Layer."""
    state = model.state_dict()
    if set(state) != set(weights):
        raise SystemExit("benchmarks: the Layer and the leaf map differ on "
                         f"{sorted(set(state) ^ set(weights))}")
    for name, value in weights.items():
        if tuple(value.shape) != tuple(state[name].shape):
            raise SystemExit(
                f"benchmarks: {name} is {tuple(state[name].shape)}, the "
                f"reference gives {tuple(value.shape)}")
        state[name].data = value


def make_optimizer(paddle, opt, parameters):
    """The optimizer module of the configuration's ``optimizer.kind``."""
    import importlib
    from .. import spec
    return importlib.import_module(
        f"{__name__}.optimizers.{opt['kind']}").make(
            paddle, opt, spec.lr_at(opt["lr_schedule"], 0), parameters)
