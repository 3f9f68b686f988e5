"""The precision a reference computes in.

``float32`` is the reference proper: float32 everywhere, ``highest`` matmul
precision (on a TPU the default float32 product is a single bfloat16 pass).

The lower precisions put the reference in the place of a program that
autocasts: every operand of a matrix product or convolution and every
activation an autocast program would store (``act``) is rounded, forward
and backward, and everything is accumulated in float32.
``bfloat16`` is what the configurations state (bf16 AMP over f32 masters).
``float8_matmul`` is the control of ``correct``, the step below that would
tempt a later PR: bf16 AMP whose matrix products and convolutions alone
take float8 operands, forward (activation, weight) and backward (the
output's gradient); everything stored stays bfloat16.
``float8`` rounds every stored activation to float8 as well.
float8 is 4 exponent and 3 mantissa bits under a per-tensor scale (amax ->
240, the largest finite value), as an fp8 training recipe would scale it.

Rounding is ``lax.reduce_precision``: XLA may remove a float32 -> bfloat16
-> float32 convert pair (excess precision is allowed by default on the
TPU), which made a cast-based bfloat16 mode a no-op on the chip (PR 24).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

# precision -> (what a stored activation is rounded to, what an operand of
# a matrix product or convolution is rounded to)
PRECISIONS = {"float32": (None, None), "bfloat16": ("bfloat16", "bfloat16"),
              "float8_matmul": ("bfloat16", "float8"),
              "float8": ("float8", "float8")}
_E4M3_MAX = 240.0


def _round(x, to):
    if to is None:
        return x
    if to == "bfloat16":
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, _E4M3_MAX / amax, 1.0)
    return lax.reduce_precision(x * scale, exponent_bits=4,
                                mantissa_bits=3) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _rounded(x, forward, backward):
    """``x`` rounded to ``forward``; its gradient, on the way back, to
    ``backward``."""
    return _round(x, forward)


def _rounded_fwd(x, forward, backward):
    return _round(x, forward), None


def _rounded_bwd(forward, backward, _, g):
    return (_round(g, backward),)


_rounded.defvjp(_rounded_fwd, _rounded_bwd)


class Numerics:
    def __init__(self, precision="float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.stored, self.operand = PRECISIONS[precision]

    def act(self, x):
        """An activation as an autocast program would store it; its
        gradient is rounded the same way on the way back."""
        if self.stored is None:
            return x
        return _rounded(x, self.stored, self.stored)

    def _product(self, f, a, b):
        if self.stored is None:
            return f(a, b)
        # operands as the product reads them; the output as it is stored,
        # and its gradient as the backward products read it
        return _rounded(f(_rounded(a, self.operand, self.stored),
                          _rounded(b, self.operand, self.stored)),
                        self.stored, self.operand)

    def einsum(self, spec, a, b):
        return self._product(
            lambda x, y: jnp.einsum(spec, x, y,
                                    precision=lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32),
            a, b)

    def dot(self, a, b):
        """``a[..., k] @ b[k, n]``."""
        return self.einsum("...k,kn->...n", a, b)

    def conv(self, x, w, stride, padding):
        """NCHW input, OIHW kernel."""
        return self._product(
            lambda a, b: lax.conv_general_dilated(
                a, b, (stride, stride), [(padding, padding)] * 2,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32),
            x, w)
