"""OpTest-style harness.

Analog of the reference's op correctness harness
(/root/reference/python/paddle/fluid/tests/unittests/op_test.py:255):
``check_output`` compares an eager op against a numpy reference;
``check_grad`` compares tape-engine analytic gradients against central
finite differences (op_test.py:110 get_numeric_gradient).
"""

from __future__ import annotations

import unittest
from typing import Callable, Dict, Sequence

import jax.numpy as jnp
import numpy as np

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor


class OpTest(unittest.TestCase):
    rtol = 1e-5
    atol = 1e-6

    def check_output(self, op_fn: Callable, np_fn: Callable,
                     inputs: Sequence[np.ndarray], rtol=None, atol=None,
                     **attrs):
        tensors = [paddle.to_tensor(x) for x in inputs]
        got = op_fn(*tensors, **attrs)
        want = np_fn(*inputs, **attrs)
        got_list = got if isinstance(got, (tuple, list)) else [got]
        want_list = want if isinstance(want, (tuple, list)) else [want]
        for g, w in zip(got_list, want_list):
            np.testing.assert_allclose(
                np.asarray(g.numpy(), np.float64),
                np.asarray(w, np.float64),
                rtol=rtol or self.rtol, atol=atol or self.atol)
        return got

    def check_grad(self, op_fn: Callable, inputs: Sequence[np.ndarray],
                   grad_input_idx: Sequence[int] = (0,), delta=1e-3,
                   rtol=5e-3, atol=1e-4, reduce_fn=None, **attrs):
        """Compare tape gradients vs central finite differences."""
        inputs = [np.asarray(x, np.float64).astype(np.float32)
                  for x in inputs]

        def scalar_out(*arrs):
            ts = [paddle.to_tensor(a) for a in arrs]
            out = op_fn(*ts, **attrs)
            if isinstance(out, (tuple, list)):
                out = out[0]
            if reduce_fn is not None:
                return reduce_fn(out)
            return out.sum() if out.size > 1 else out

        # analytic via tape
        tensors = [paddle.to_tensor(a, stop_gradient=(i not in
                                                      grad_input_idx))
                   for i, a in enumerate(inputs)]
        out = op_fn(*tensors, **attrs)
        if isinstance(out, (tuple, list)):
            out = out[0]
        loss = (reduce_fn(out) if reduce_fn is not None else
                (out.sum() if out.size > 1 else out))
        loss.backward()

        for idx in grad_input_idx:
            analytic = tensors[idx].grad.numpy().astype(np.float64)
            numeric = self._numeric_grad(scalar_out, inputs, idx, delta)
            np.testing.assert_allclose(analytic, numeric, rtol=rtol,
                                       atol=atol,
                                       err_msg=f"grad mismatch input {idx}")

    @staticmethod
    def _numeric_grad(scalar_fn, inputs, idx, delta):
        base = [np.array(a, np.float32) for a in inputs]
        flat = base[idx].reshape(-1)
        grad = np.zeros_like(flat, np.float64)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + delta
            lo_hi = []
            f_hi = float(scalar_fn(*base).item())
            flat[i] = orig - delta
            f_lo = float(scalar_fn(*base).item())
            flat[i] = orig
            grad[i] = (f_hi - f_lo) / (2 * delta)
        return grad.reshape(base[idx].shape)


def rotary_by_halves(x, theta, at, interleaved, frequencies=None, scale=1.0):
    """The plain reference of ``F.rotary_embedding``: the op as it stood
    until ISSUE 45, a slice at half the head width (or a view in pairs)
    and a concatenation, in float32, differentiated by ``jax.grad``.
    ``x`` [batch, seq, heads, dim], ``at`` [seq] or [batch, seq].
    ``frequencies`` in ``theta``'s place turn the first ``2 *
    len(frequencies)`` channels, paired among themselves, and the others
    pass; ``scale`` multiplies cos and sin (ISSUE 47)."""
    if frequencies is None:
        half = x.shape[-1] // 2
        inv_freq = jnp.float32(theta) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = jnp.asarray(frequencies, jnp.float32)
        half = inv_freq.shape[0]
    angle = at.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if scale != 1.0:
        cos, sin = jnp.float32(scale) * cos, jnp.float32(scale) * sin
    xf = x.astype(jnp.float32)
    turned, rest = xf[..., :2 * half], xf[..., 2 * half:]
    if interleaved:
        pairs = turned.reshape(turned.shape[:-1] + (half, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        axis=-1).reshape(turned.shape)
    else:
        a, b = turned[..., :half], turned[..., half:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1)
    if rest.shape[-1]:
        out = jnp.concatenate([out, rest], axis=-1)
    return out.astype(x.dtype)
