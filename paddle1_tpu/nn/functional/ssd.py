"""The state-space scan of Mamba-2 (Dao and Gu 2024, "Transformers are
SSMs": the state-space dual, SSD), the token mixer of the ``nemotron_h``
family's ``M`` layers. No reference analog (the reference framework has
no recurrence over a state wider than an RNN cell's).

``x``: ``[batch, seq, heads, width]``; ``dt``: ``[batch, seq, heads]``;
``A``, ``D``, ``dt_bias``: ``[heads]`` (``A`` negative); ``B``, ``C``:
``[batch, seq, groups, state]``, head ``n`` reads group ``n // (heads /
groups)``. With ``d_t = softplus(dt_t + dt_bias)`` (float32) and a state
``S`` of ``[width, state]`` a head, from zero at a row's start::

    S_t = exp(d_t A) S_{t-1} + d_t x_t B_t^T
    y_t = S_t C_t + D x_t

**What runs is the chunked form** (``chunk`` positions, 128): with ``a_t =
d_t A`` and ``L_t`` the sum of ``a`` from the chunk's start up to ``t``,
inside a chunk::

    y_t = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) d_s x_s
          + exp(L_t) S_prev C_t + D x_t

and across chunks ``S_end = exp(L_Q) S_prev + sum_s exp(L_Q - L_s) d_s x_s
B_s^T``: three matrix products a chunk and one pass over the chunks,
where the recurrence is ``seq`` dependent steps. Every exponent is of a
number <= 0. State, decays and sums are float32 whatever the operands
arrive in (bfloat16 under AMP); the products take their operands in
``x``'s dtype and accumulate in float32; the result has ``x``'s dtype. A
row whose length is no multiple of ``chunk`` is padded with positions of
``d = 0``, which neither decay nor write the state.

**Two forms.** On a TPU, in a step that is one device's, at the shapes
``ops/pallas/ssd_scan.py::supported`` admits: its two kernels
(``p1t_ssd_fwd``, ``p1t_ssd_bwd``), which hold a chunk's decay matrix and
its products in VMEM and carry the state over the sequential grid axis;
the backward pass is written out there (the chunks in reverse, the
gradient of the state carried the same way). Anywhere else the
composition below in ``jax.numpy``, which writes the ``[chunks, heads,
chunk, chunk]`` float32 decays to HBM and is differentiated by
``jax.vjp``.

**What the backward keeps** is priced by ``core/recompute_keeps.py``'s
rule, not set: beside its operands it needs the state at every chunk's
start (``[chunks, heads, state, width]`` float32: 134 MB a layer at 8,192
positions of 64 heads of 64 x 128), which the forward kernel writes when
it is differentiated and not otherwise. Under a recomputed segment that
is the second forward's output, and neither it nor ``y`` carries a name:
the pass that makes both again costs under 1 ms for 201 MB, 0.005 ms a
MB, a quarter of what earns one.

**Counted where it is traced**, in the process registry:
``ssd_scan_bytes_total{pass}`` (the bytes a call's forward or backward
has to read and write by the closed form of its shapes,
:func:`traffic_bytes`, whichever form runs), ``ssd_chunks_total{pass}``
(chunks x heads a call) and ``ssd_scan_calls_total{path}`` (``kernel`` or
``xla``, a traced forward call). The benchmark's roofline share of the op
divides by a closed form of its own
(``benchmarks/model_flops/nemotron3_nano_30b_a3b.py::ssd_scan_bytes``)
and a test holds the two equal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...autograd.engine import apply
from ...core.flags import in_auto_partitioned_region
from ...core.tensor import Tensor, to_tensor
from ...ops.pallas import ssd_scan as kernels

__all__ = ["ssd_scan"]

F32 = jnp.float32


def traffic_bytes(x_shape, groups, state, itemsize) -> dict:
    """{"forward", "backward"}: the bytes one call has to move for ``x``
    of ``x_shape`` = [batch, seq, heads, width] with ``groups`` groups of
    ``state``. Forward: ``x``, ``B``, ``C`` and ``dt`` read, ``y``
    written. Backward: the four and ``y``'s gradient read, four gradients
    written. ``A``, ``D`` and ``dt_bias`` (a value a head) and the states
    at the chunks' starts (the implementation's, not the op's) are not
    counted."""
    batch, seq, heads, width = x_shape
    positions = batch * seq
    whole = positions * heads * width * itemsize
    small = positions * (2 * groups * state + heads) * itemsize
    return {"forward": 2 * whole + small,
            "backward": 3 * whole + 2 * small}


def _count(which, x, b):
    from ...obs.registry import process_group
    group = process_group("pass").child(which)
    group.counter("ssd_scan_bytes_total").inc(traffic_bytes(
        x.shape, b.shape[2], b.shape[3], x.dtype.itemsize)[which])
    group.counter("ssd_chunks_total").inc(
        x.shape[0] * x.shape[2] * -(-x.shape[1] // kernels.CHUNK))


def _use_kernels(x, b, chunk) -> bool:
    return (jax.default_backend() == "tpu"
            and not in_auto_partitioned_region()
            and kernels.supported(x.shape, b.shape, chunk))


def _dot(spec, a, b):
    """Operands as they arrive, float32 accumulation; a float32 product
    at the highest precision (one bfloat16 pass is the TPU's default)."""
    precision = (jax.lax.Precision.HIGHEST if a.dtype == F32
                 else jax.lax.Precision.DEFAULT)
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=F32)


def chunked(x, d, a_head, b, c, d_skip, chunk):
    """The chunked form in ``jax.numpy``: ``d`` [batch, seq, heads] is
    ``softplus(dt + dt_bias)`` in float32, ``seq`` a multiple of
    ``chunk``."""
    n, seq, heads, width = x.shape
    groups, state = b.shape[2], b.shape[3]
    per, q, chunks = heads // groups, chunk, seq // chunk
    xg = x.reshape(n, chunks, q, groups, per, width)
    bc, cc = (t.reshape(n, chunks, q, groups, state) for t in (b, c))
    dg = d.reshape(n, chunks, q, groups, per)
    total = jnp.cumsum(dg * a_head.reshape(groups, per), axis=2)  # L
    # [n, chunks, groups, per, q] x its own transpose: exp(L_t - L_s)
    lt = jnp.moveaxis(total, 2, -1)
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(seen, jnp.exp(jnp.minimum(
        lt[..., :, None] - lt[..., None, :], 0.0)), 0.0)
    scores = _dot("ncqgs,nckgs->ncgqk", cc, bc)
    w = (scores[:, :, :, None] * decay
         * jnp.moveaxis(dg, 2, -1)[..., None, :]).astype(x.dtype)
    y = _dot("ncgpqk,nckgpw->ncqgpw", w, xg)
    # a chunk's own writing to the state, and its decay of what came in
    last = total[:, :, -1:]
    written = _dot("nckgs,nckgpw->ncgpsw", bc,
                   (xg * (jnp.exp(last - total) * dg)[..., None]
                    ).astype(x.dtype))
    through = jnp.exp(last[:, :, 0])                 # [n, chunks, g, per]

    def carry(s, step):
        keep, add = step
        return keep[..., None, None] * s + add, s
    _, starts = jax.lax.scan(
        carry, jnp.zeros_like(written[:, 0]),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(written, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)       # [n, chunks, g, per, s, w]
    y = y + _dot("ncqgs,ncgpsw->ncqgpw", cc, starts.astype(x.dtype)) \
        * jnp.exp(total)[..., None]
    y = y + d_skip.reshape(groups, per)[:, :, None] * xg.astype(F32)
    return y.reshape(x.shape).astype(x.dtype)


def _forward(operands, chunk, keep_states):
    """-> (``y``, the states the kernels' backward reads, or None where
    the composition runs or nothing is kept)."""
    from ...obs.registry import process_group
    x, b = operands[0], operands[3]
    _count("forward", x, b)
    kernel = _use_kernels(x, b, chunk)
    process_group("path").child("kernel" if kernel else "xla") \
        .counter("ssd_scan_calls_total").inc()
    if kernel:
        return kernels.forward(*operands, keep_states=keep_states)
    return chunked(*operands, chunk), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, d, a_head, b, c, d_skip, chunk):
    return _forward((x, d, a_head, b, c, d_skip), chunk, False)[0]


def _ssd_fwd(x, d, a_head, b, c, d_skip, chunk):
    operands = (x, d, a_head, b, c, d_skip)
    y, starts = _forward(operands, chunk, True)
    return y, (operands, starts)


def _ssd_bwd(chunk, res, g):
    operands, starts = res
    _count("backward", operands[0], operands[3])
    if starts is not None:
        return kernels.backward(*operands, starts, g)
    return jax.vjp(lambda *o: chunked(*o, chunk), *operands)[1](g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _scan(x, dt, a_head, b, c, d_skip, dt_bias, chunk):
    seq = x.shape[1]
    chunk = min(chunk, -(-seq // 8) * 8)
    d = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    pad = -seq % chunk
    if pad:     # d = 0: the state neither decays nor is written
        x, d, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                      for t in (x, d, b, c))
    y = _ssd(x, d, a_head.astype(F32), b.astype(x.dtype), c.astype(x.dtype),
             d_skip.astype(F32), chunk)
    return y[:, :seq] if pad else y


def ssd_scan(x, dt, A, B, C, D, dt_bias, chunk=128, name=None):
    """See the module's docstring. -> ``[batch, seq, heads, width]`` in
    ``x``'s dtype."""
    args = tuple(t if isinstance(t, Tensor) else to_tensor(t)
                 for t in (x, dt, A, B, C, D, dt_bias))
    return apply("ssd_scan", _scan, args, chunk=chunk)
