"""``F.ssd_scan`` (ISSUE 50): the chunked form and its two Pallas kernels
(interpreter mode) against the recurrence itself, a position after the
other, values and all seven gradients; rows that are no whole number of
chunks, a decay that lives across a chunk's edge; what the op counts where
it is traced; what ``supported`` admits. CPU, float32, tiny sizes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle1_tpu import obs  # noqa: E402
from paddle1_tpu.nn import functional as F  # noqa: E402
from paddle1_tpu.nn.functional import ssd  # noqa: E402
from paddle1_tpu.obs.registry import process_group  # noqa: E402
from paddle1_tpu.ops.pallas import ssd_scan as kernels  # noqa: E402

NAMES = ("x", "dt", "A", "B", "C", "D", "dt_bias")


def recurrence(x, dt, a, b, c, d_skip, dt_bias):
    """``S_t = exp(d_t A) S_{t-1} + d_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t``, by ``lax.scan`` over the positions: no chunk algebra."""
    n, _, heads, width = x.shape
    per = heads // b.shape[2]
    d = jax.nn.softplus(dt + dt_bias)
    bh, ch = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)

    def step(state, now):
        x_t, d_t, b_t, c_t = now
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("nhws,nhs->nhw", state, c_t)
    _, y = jax.lax.scan(
        step, jnp.zeros((n, heads, width, b.shape[3])),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, d, bh, ch)))
    return jnp.moveaxis(y, 0, 1) + d_skip[:, None] * x


def operands(seq, heads=4, width=8, groups=2, state=16, rows=2, seed=0,
             slow=False):
    """``slow``: decay rates and time steps so small that a position is
    still a tenth of itself several chunks later."""
    keys = jax.random.split(jax.random.key(seed), 7)
    low, high = (-7.0, -5.0) if slow else (-3.0, 1.5)
    return (jax.random.normal(keys[0], (rows, seq, heads, width)),
            jax.random.normal(keys[1], (rows, seq, heads)) - 1.0,
            -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=low,
                                        maxval=high)),
            0.3 * jax.random.normal(keys[3], (rows, seq, groups, state)),
            0.3 * jax.random.normal(keys[4], (rows, seq, groups, state)),
            jax.random.normal(keys[5], (heads,)),
            0.5 * jax.random.normal(keys[6], (heads,)))


def value_and_grads(fn, args):
    weights = jax.random.normal(jax.random.key(9), args[0].shape)
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * weights), argnums=tuple(range(7))))(*args)


def assert_follows(fn, args, rel=2e-5):
    want, want_grads = value_and_grads(recurrence, args)
    got, got_grads = value_and_grads(fn, args)
    assert float(got) == pytest.approx(float(want), rel=rel, abs=1e-4)
    for name, a, b in zip(NAMES, got_grads, want_grads):
        assert np.linalg.norm(a - b) <= rel * max(np.linalg.norm(b), 1e-3), \
            name


@pytest.mark.parametrize("seq,chunk,slow", [
    (40, 8, False),       # five chunks
    (44, 8, False),       # no whole number of chunks: padded with d = 0
    (320, 128, False),    # the cell's chunk, two and a half of it
    (96, 16, True),       # a position outlives its chunk's edge
])
def test_the_chunked_form_is_the_recurrence(seq, chunk, slow):
    args = operands(seq, slow=slow)
    if slow:    # what a position wrote is still there chunks later
        d = jax.nn.softplus(args[1] + args[6])
        assert float(jnp.exp(jnp.sum(d * args[2], 1)).min()) > 0.1
    assert_follows(lambda *a: ssd._scan(*a, chunk), args)


def test_the_values_follow_the_recurrence():
    args = operands(48)
    np.testing.assert_allclose(ssd._scan(*args, 16), recurrence(*args),
                               rtol=1e-4, atol=1e-5)


def by_kernels(x, dt, a, b, c, d_skip, dt_bias):
    """The two kernels behind the op's own softplus, whatever the
    backend."""
    d = jax.nn.softplus(dt + dt_bias)

    @jax.custom_vjp
    def scan(*operands):
        return kernels.forward(*operands)[0]

    def fwd(*operands):
        y, starts = kernels.forward(*operands, keep_states=True)
        return y, (operands, starts)
    scan.defvjp(fwd, lambda res, g: kernels.backward(*res[0], res[1], g))
    return scan(x, d, a, b, c, d_skip)


@pytest.mark.parametrize("heads,width,groups,slow", [
    (4, 64, 2, False),    # two heads a lane tile, as the cell's 64 of 64
    (4, 64, 1, True),     # the state carried across the chunks' edges
])
def test_the_kernels_are_the_recurrence(heads, width, groups, slow):
    args = operands(256, heads, width, groups, 128, rows=2, seed=1,
                    slow=slow)
    assert kernels.supported(args[0].shape, args[3].shape, 128)
    assert_follows(by_kernels, args)


def test_the_forward_kernel_keeps_the_state_at_every_chunks_start():
    x, dt, a, b, c, d_skip, bias = operands(256, 4, 64, 2, 128, rows=1)
    d = jax.nn.softplus(dt + bias)
    y, none = kernels.forward(x, d, a, b, c, d_skip)
    y_kept, starts = kernels.forward(x, d, a, b, c, d_skip, keep_states=True)
    assert none is None and starts.shape == (1, 2, 2, 128, 2 * 64)
    np.testing.assert_array_equal(y, y_kept)
    assert float(jnp.abs(starts[:, 0]).max()) == 0.0
    assert float(jnp.abs(starts[:, 1]).max()) > 0.0


@pytest.mark.parametrize("x_shape,b_shape,chunk,ok", [
    ((1, 8192, 64, 64), (1, 8192, 8, 128), 128, True),
    ((1, 8192, 64, 64), (1, 8192, 8, 128), 64, False),     # another chunk
    ((1, 8200, 64, 64), (1, 8200, 8, 128), 128, False),    # a ragged row
    ((1, 8192, 64, 32), (1, 8192, 8, 128), 128, False),    # a narrow head
    ((1, 8192, 64, 128), (1, 8192, 8, 128), 128, False),   # a head a tile
    ((1, 8192, 64, 64), (1, 8192, 8, 64), 128, False),     # a narrow state
    ((1, 8192, 8, 64), (1, 8192, 8, 128), 128, False),     # half a tile
])
def test_supported_admits_whole_tiles(x_shape, b_shape, chunk, ok):
    assert kernels.supported(x_shape, b_shape, chunk) is ok


def test_the_op_counts_its_bytes_chunks_and_path():
    obs.reset_process_registry()
    args = operands(40, heads=4, width=8, groups=2, state=16, rows=2)
    jax.jit(jax.grad(lambda *a: jnp.sum(ssd._scan(*a, 8))))(*args)
    want = ssd.traffic_bytes((2, 40, 4, 8), 2, 16, 4)
    assert want == {"forward": 4 * (2 * 2560 + 80 * 68),
                    "backward": 4 * (3 * 2560 + 2 * 80 * 68)}
    for which in ("forward", "backward"):
        group = process_group("pass").child(which)
        assert group.counter("ssd_scan_bytes_total").value == want[which]
        # by the kernels' chunk of 128, whatever the composition took
        assert group.counter("ssd_chunks_total").value == 2 * 4 * 1
    paths = process_group("path")
    assert paths.child("xla").counter("ssd_scan_calls_total").value == 1
    assert paths.child("kernel").counter("ssd_scan_calls_total").value == 0
    obs.reset_process_registry()


def test_the_public_op_takes_tensors_and_records_seven_gradients():
    import paddle1_tpu as paddle
    args = [paddle.to_tensor(np.asarray(a), stop_gradient=False)
            for a in operands(24)]
    y = F.ssd_scan(*args, chunk=8)
    assert y.shape == [2, 24, 4, 8]
    y.sum().backward()
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a)),
                    argnums=tuple(range(7)))(*operands(24))
    for name, t, w in zip(NAMES, args, want):
        np.testing.assert_allclose(t.grad.numpy(), w, rtol=2e-3, atol=2e-4,
                                   err_msg=name)
