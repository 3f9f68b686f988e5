"""What decides ``correct`` for a training cell.

The program's first steps (driven through the window's own call and feed)
are followed by the configuration's plain reference from the same weights
and batches. Compared, each against a limit of its own:

  loss      each step's loss, relative gap;
  grad      the norm of the first gradient as the optimizer gets it, read
            from the optimizer's state after one step, by the worst leaf
            and by the median leaf;
  direction the first gradient itself, weight matrix by weight matrix
            (every leaf of two or more dimensions): the norm of the
            difference over the reference's norm, by the median matrix.
            Rounding noise has no mean, so it hardly moves a norm; it
            turns the gradient, and this is the number that a product in
            float8 moves. Compared where the configuration's limits hold
            ``grad_direction``: bfloat16 alone turns the gradient of a
            50-layer batch-normed net at seeded weights past all telling
            (PERF.md), so resnet50 has none;
  delta     the norm of the parameters' change after the steps, by the
            worst leaf.

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf (some gradients are
all but zero). The limits are in ``limits/<configuration>.json``, with
the readings they were set from in PERF.md.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import spec
from .reference.numerics import Numerics


def reference_record(cfg, weights, batches, lrs, precision="float32",
                     keep_matrices=True):
    """Follow ``len(batches)`` steps with the plain reference; the first
    gradient of the weight matrices is kept only where ``keep_matrices``.

    -> {"losses": [..], "grad": {ref name: norms}, "delta": {..},
    "matrices": {ref name: the first gradient of a weight matrix}} where a
    norm array has one entry per row of a stacked weight, or one entry.
    """
    ref = spec.module("reference", cfg)
    nm = Numerics(precision)
    opt = cfg["optimizer"]
    optim = spec.reference_optimizer(opt)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, batch, lr):
        def f(p):
            return ref.loss(p, batch, cfg, nm)
        (loss, updates), grads = jax.value_and_grad(f, has_aux=True)(params)
        trained = {k: v for k, v in params.items() if k not in updates}
        grads = {k: grads[k] for k in trained}
        new, state, g_in = optim.update(trained, grads, state, lr, opt)
        new.update(updates)
        return loss, new, state, _row_norms(g_in), {
            k: g for k, g in g_in.items() if keep_matrices and g.ndim >= 2}

    params = {k: jnp.array(v, jnp.float32) for k, v in weights.items()}
    start = {k: np.asarray(v, np.float32) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        shapes = jax.eval_shape(
            lambda p, b: ref.loss(p, b, cfg, nm)[1], params,
            {k: jnp.asarray(v) for k, v in batches[0].items()})
        state = optim.init({k: v for k, v in params.items()
                            if k not in shapes}, opt)
        losses, grad, matrices = [], None, None
        for i, (batch, lr) in enumerate(zip(batches, lrs)):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            loss, params, state, g, whole = step(params, state, batch,
                                                 jnp.float32(lr))
            losses.append(float(loss))
            if i == 0:
                grad = {k: np.asarray(v) for k, v in g.items()}
                matrices = jax.device_get(whole)
            del g, whole
        delta = _row_norms({k: params[k] - start[k] for k in params})
        delta = {k: np.asarray(v) for k, v in delta.items()}
    return {"losses": losses, "grad": grad, "delta": delta,
            "matrices": matrices}


def _row_norms(tree):
    """Norm of a leaf, or of each row of a stacked (3-d or [L, n]) leaf:
    one entry per leading index, so that a program whose layers are
    separate parameters can be matched row by row."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        whole = jnp.sqrt(jnp.sum(jnp.square(v)))
        rows = jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim)))) \
            if v.ndim > 1 else whole[None]
        out[k] = jnp.concatenate([whole[None], rows])
    return out


def in_programs_place(record, leaves):
    """A reference's record shaped as a program's, leaf by leaf of the
    program's own map. For the controls, which put a reference computed in
    a lower precision where the program stands."""
    def norms(what):
        return {p: pick(record[what], r, i) for p, r, i in leaves
                if r in record[what]}
    whole = {p: record["matrices"][r] if i is None
             else record["matrices"][r][i]
             for p, r, i in leaves if r in record["matrices"]}
    return {"losses": record["losses"], "grad": norms("grad"),
            "delta": norms("delta"),
            # a row of stacked biases is no matrix
            "matrices": {p: m for p, m in whole.items() if m.ndim >= 2}}


def pick(norms, ref_name, idx):
    """The reference's norm for one program leaf: row ``idx`` of a
    stacked weight, or the whole leaf."""
    arr = norms[ref_name]
    return float(arr[0] if idx is None else arr[1 + idx])


@jax.jit
def program_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def program_delta_norms(now, start):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        now[k].astype(jnp.float32) - start[k].astype(jnp.float32))))
        for k in now}


def program_matrices(tree, factor):
    """The program's first gradient of every weight matrix, on the host:
    ``tree`` is the optimizer's slot that holds it, ``factor`` times too
    small."""
    return {k: factor * np.asarray(v, np.float32)
            for k, v in jax.device_get(
                {k: v for k, v in tree.items() if v.ndim >= 2}).items()}


def direction_gaps(program, reference, leaves):
    """-> [(gap, program leaf)], largest first, over the weight matrices:
    |program gradient - reference gradient| over the larger of the
    reference's norm of that matrix and of the median matrix."""
    pairs = []
    for p, r, i in leaves:
        if p in program and r in reference:
            b = reference[r] if i is None else reference[r][i]
            a = np.asarray(program[p], np.float32).reshape(b.shape)
            pairs.append((p, float(np.linalg.norm((a - b).ravel())),
                          float(np.linalg.norm(b.ravel()))))
    if not pairs:
        return [(float("inf"), None)]
    floor = max(float(np.median([ref for _, _, ref in pairs])), 1e-30)
    return sorted(((d / max(b, floor), p) for p, d, b in pairs), reverse=True)


def leaf_gaps(program, reference, leaves):
    """-> [(gap, program leaf)], largest first. ``program``: {program
    name: norm}; ``reference``: row norms by reference name. A gap is
    |program norm - reference norm| over the larger of the reference's
    norm of that leaf and of the median leaf."""
    pairs = [(p, float(program[p]), pick(reference, r, i))
             for p, r, i in leaves if p in program and r in reference]
    if not pairs:
        return [(float("inf"), None)]
    floor = max(float(np.median([ref for _, _, ref in pairs])), 1e-30)
    return sorted(((abs(a - b) / max(b, floor), p) for p, a, b in pairs),
                  reverse=True)


def compare(program, reference, leaves, limits):
    """-> (correct, rows): each row is (what, value, limit, ok, note)."""
    rows = []
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        gap = abs(a - b) / max(abs(b), 1e-30)
        rows.append((f"loss[{i}] rel gap", gap, limits["loss"],
                     bool(np.isfinite(a)) and gap <= limits["loss"],
                     f"program {a:.6f} reference {b:.6f}"))
    for what in ("grad", "delta"):
        gaps = leaf_gaps(program[what], reference[what], leaves)
        only = [g for g, _ in gaps]
        rows.append((f"{what} norm worst-leaf gap", gaps[0][0],
                     limits[what + "_worst"],
                     gaps[0][0] <= limits[what + "_worst"],
                     f"at {gaps[0][1]}; {len(gaps)} leaves, 90th "
                     f"percentile {np.percentile(only, 90):.3g}"))
        if what == "grad":
            # a max swings by its nature; the median leaf is steady
            mid = float(np.median(only))
            rows.append(("grad norm median-leaf gap", mid,
                         limits["grad_median"], mid <= limits["grad_median"],
                         f"{len(gaps)} leaves"))
            if "grad_direction" not in limits:
                continue
            turned = direction_gaps(program["matrices"],
                                    reference["matrices"], leaves)
            mid = float(np.median([g for g, _ in turned]))
            rows.append(("grad direction median-matrix gap", mid,
                         limits["grad_direction"],
                         mid <= limits["grad_direction"],
                         f"{len(turned)} matrices, worst {turned[0][0]:.3g} "
                         f"at {turned[0][1]}"))
    return all(r[3] for r in rows), rows
