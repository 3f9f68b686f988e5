"""The chunked state-space scan as two Pallas kernels (``nn/functional/
ssd.py`` has the equations and the XLA form); no reference analog.

A grid step is one chunk (``CHUNK`` = 128 positions) of one group of
heads: the group's heads share ``B`` and ``C``, so the chunk's ``C B^T``
is made once a step, and their ``x`` lies side by side in ``[batch, seq,
heads * width]`` as the projection wrote it, a window of ``per * width``
lanes (512 at 8 heads of 64). Heads narrower than a lane tile are taken a
tile (two heads of 64) at a time: a head's product runs on the whole tile
and a lane mask keeps its own half, which costs the 128-wide matrix unit
nothing. The state of the group, ``[state, per * width]`` float32 (every
head's ``S^T`` side by side), lives in VMEM scratch over the chunk axis,
the grid's last and sequential one, as the attention kernels carry their
running statistics.

What is a value a position a head (the decays' exponents ``L``, the
chunk's running sum of ``d A``, and ``d`` itself) is laid out by XLA both
ways, ``[.., seq, per]`` for the factors of rows and ``[.., per, seq]``
for the factors of columns: 4 bytes a position a head each, against a
transpose in the kernel. A chunk's decay matrix ``exp(L_t - L_s)`` is
made in VMEM from the two and never written.

Forward (``p1t_ssd_fwd``): ``x``, ``B``, ``C`` read once, ``y`` written
once; differentiated, it also writes the state at every chunk's start
(``[batch, chunks, groups, state, per * width]`` float32). Backward
(``p1t_ssd_bwd``): the chunks in reverse, the state's gradient carried
in scratch; ``x``, ``B``, ``C``, ``y``'s gradient and the kept states
read once, the gradients of ``x``, ``B`` and ``C`` written once, and
four small arrays a head a position from which XLA finishes the
gradients of ``d`` and ``A`` (a reverse running sum inside a chunk) and
of ``D``.

Exponents, decays, sums and the state are float32; the matrix unit takes
its operands in ``x``'s dtype (bfloat16 under AMP) and accumulates in
float32. Runs in interpreter mode off-TPU so tests exercise the same code
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common

__all__ = ["CHUNK", "supported", "forward", "backward"]

CHUNK = 128
_LANES = 128
_VMEM_LIMIT = 64 * 1024 * 1024
F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def supported(x_shape, b_shape, chunk) -> bool:
    """``x`` [batch, seq, heads, width] in whole chunks of 128, heads of
    half a lane tile (width 64: at a head a tile Mosaic folds the
    chunk's decay, one value, into a broadcast over rows and lanes at
    once, which it does not lower), a group's heads in whole tiles, a
    state of one lane tile."""
    if len(x_shape) != 4 or len(b_shape) != 4 or chunk != CHUNK:
        return False
    _, seq, heads, width = x_shape
    groups, state = b_shape[2], b_shape[3]
    return (seq % CHUNK == 0 and heads % groups == 0
            and 2 * width == _LANES and state == _LANES
            and (heads // groups * width) % _LANES == 0)


def _dot(a, b, dims):
    precision = None if a.dtype == F32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=F32)


def _pick(mine, new, old):
    """``new`` (a value a row, or one for all) on the lanes of the head
    that ``mine`` marks, ``old`` on the others: a tile's per-head factors
    side by side."""
    new = jnp.broadcast_to(new, (new.shape[0], _LANES))
    return new if old is None else jnp.where(mine, new, old)


def _sum_all(v):
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _fwd_kernel(x_ref, b_ref, c_ref, lc_ref, lr_ref, dc_ref, dr_ref,
                skip_ref, y_ref, *rest, width, keep_states):
    st_ref, s_ref = rest if keep_states else (None,) + rest
    q, lanes = x_ref.shape
    op = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if keep_states:
        st_ref[...] = s_ref[...]
    b, c = b_ref[...], c_ref[...]
    scores = _dot(c, b, _NT)                                  # C_t . B_s
    lc, lr, dc, dr = lc_ref[...], lr_ref[...], dc_ref[...], dr_ref[...]
    seen = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES),
                                            1) // width
    for j in range(lanes // _LANES):
        tile = slice(j * _LANES, (j + 1) * _LANES)
        x = x_ref[:, tile]
        state = s_ref[:, tile]
        y = e_rows = w_rows = None
        for i in range(_LANES // width):
            h = j * (_LANES // width) + i
            lt, ls = lc[:, h:h + 1], lr[h:h + 1, :]
            last = lc[q - 1:q, h:h + 1]
            decay = jnp.where(seen, jnp.exp(jnp.minimum(lt - ls, 0.0)), 0.0)
            inside = _dot((scores * decay * dr[h:h + 1, :]).astype(op), x,
                          _NN)
            mine = head_of_lane == i
            y = _pick(mine, inside, y)
            e_rows = _pick(mine, jnp.exp(lt), e_rows)
            w_rows = _pick(mine, jnp.exp(last - lt) * dc[:, h:h + 1], w_rows)
        through = e_rows[q - 1:q, :]       # exp(L_Q): the chunk's decay
        xf = x.astype(F32)
        y = (y + _dot(c, state.astype(op), _NN) * e_rows
             + skip_ref[:, tile] * xf)
        y_ref[:, tile] = y.astype(y_ref.dtype)
        s_ref[:, tile] = through * state + _dot(
            b, (xf * w_rows).astype(op), _TN)


def _bwd_kernel(x_ref, g_ref, b_ref, c_ref, lc_ref, lr_ref, dc_ref, dr_ref,
                skip_ref, st_ref, dx_ref, db_ref, dcc_ref, dl_col_ref,
                dl_row_ref, dd_col_ref, dd_row_ref, dskip_ref, ds_ref, *,
                width):
    q, lanes = x_ref.shape
    per = lc_ref.shape[1]
    op = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    b, c = b_ref[...], c_ref[...]
    scores = _dot(c, b, _NT)              # [t, s]: C_t . B_s
    scores_t = _dot(b, c, _NT)            # [s, t]
    lc, lr, dc, dr = lc_ref[...], lr_ref[...], dc_ref[...], dr_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower, upper = rows >= cols, rows <= cols
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES),
                                            1) // width
    head_of_col = jax.lax.broadcasted_iota(jnp.int32, (q, per), 1)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    d_scores = jnp.zeros((q, q), F32)
    db = jnp.zeros(db_ref.shape, F32)
    dcc = jnp.zeros(dcc_ref.shape, F32)
    dl_col = jnp.zeros((q, per), F32)
    dd_col = jnp.zeros((q, per), F32)
    for j in range(lanes // _LANES):
        tile = slice(j * _LANES, (j + 1) * _LANES)
        x, g = x_ref[:, tile], g_ref[:, tile]
        xf, gf = x.astype(F32), g.astype(F32)
        state, d_state = st_ref[:, tile], ds_ref[:, tile]
        read = _dot(c, state.astype(op), _NN)      # C_t S, before exp(L_t)
        sent = _dot(b, d_state.astype(op), _NN)    # B_s dS
        dx = e_rows = w_rows = None
        for i in range(_LANES // width):
            h = j * (_LANES // width) + i
            lt, ls = lc[:, h:h + 1], lr[h:h + 1, :]
            last = lc[q - 1:q, h:h + 1]
            d_row, d_col = dr[h:h + 1, :], dc[:, h:h + 1]
            mine = head_of_lane == i
            decay = jnp.where(lower, jnp.exp(jnp.minimum(lt - ls, 0.0)), 0.0)
            decay_t = jnp.where(upper, jnp.exp(jnp.minimum(ls - lt, 0.0)),
                                0.0)
            # dW[t, s] = dy_t . x_s over this head's lanes alone
            d_w = _dot(jnp.where(mine, g, jnp.zeros_like(g)), x, _NT)
            inside = _dot((scores_t * decay_t * d_col).astype(op), g, _NN)
            d_scores = d_scores + d_w * decay * d_row
            f = d_w * (scores * decay)
            e = f * d_row
            e_here = jnp.exp(lt)
            spread = jnp.exp(last - lt)
            w_here = spread * d_col
            got = jnp.sum(jnp.where(mine, read * gf, 0.0), axis=1,
                          keepdims=True) * e_here
            r = jnp.sum(jnp.where(mine, xf * sent, 0.0), axis=1,
                        keepdims=True)
            kept = _sum_all(jnp.where(mine, d_state * state, 0.0))
            to_l = (jnp.sum(e, axis=1, keepdims=True) + got - r * w_here
                    + jnp.where(last_row, jnp.sum(r * w_here, axis=0,
                                                  keepdims=True)
                                + jnp.exp(last) * kept, 0.0))
            dl_col = jnp.where(head_of_col == h, to_l, dl_col)
            dd_col = jnp.where(head_of_col == h, r * spread, dd_col)
            dl_row_ref[h:h + 1, :] = -jnp.sum(e, axis=0, keepdims=True)
            dd_row_ref[h:h + 1, :] = jnp.sum(f, axis=0, keepdims=True)
            dx = _pick(mine, inside, dx)
            e_rows = _pick(mine, e_here, e_rows)
            w_rows = _pick(mine, w_here, w_rows)
        through = e_rows[q - 1:q, :]       # exp(L_Q): the chunk's decay
        dx_ref[:, tile] = (dx + skip_ref[:, tile] * gf
                           + sent * w_rows).astype(dx_ref.dtype)
        ge = (gf * e_rows).astype(op)
        xw = (xf * w_rows).astype(op)
        dcc = dcc + _dot(ge, state.astype(op), _NT)
        db = db + _dot(xw, d_state.astype(op), _NT)
        ds_ref[:, tile] = through * d_state + _dot(c, ge, _TN)
        dskip_ref[:, tile] = jnp.sum(gf * xf, axis=0, keepdims=True)
    d_scores = d_scores.astype(op)
    dcc_ref[...] = (dcc + _dot(d_scores, b, _NN)).astype(dcc_ref.dtype)
    db_ref[...] = (db + _dot(d_scores, c, _TN)).astype(db_ref.dtype)
    dl_col_ref[...] = dl_col
    dd_col_ref[...] = dd_col


def _sizes(x, b):
    n, seq, heads, width = x.shape
    groups, state = b.shape[2], b.shape[3]
    return n, seq, heads, width, groups, state, heads // groups


def _by_head(d, a_head, groups):
    """``d`` [batch, seq, heads] float32 -> (L by rows, L by columns, d by
    rows, d by columns): ``[batch, groups, seq, per]`` and ``[batch,
    groups, per, seq]``, ``L`` the running sum of ``d A`` inside a
    chunk."""
    n, seq, heads = d.shape
    total = jnp.cumsum((d * a_head).reshape(n, seq // CHUNK, CHUNK, heads),
                       axis=2).reshape(n, seq, heads)

    def both(t):
        t = t.reshape(n, seq, groups, heads // groups)
        return t.transpose(0, 2, 1, 3), t.transpose(0, 2, 3, 1)
    (lc, lr), (dc, dr) = both(total), both(d)
    return lc, lr, dc, dr


def _specs(q, per, lanes, state, back):
    """Block specs of (x-like, B-like, by rows, by columns, skip, states)
    for grid (batch, group, chunk); ``back``: the chunks in reverse."""
    def at(k):
        return (pl.num_programs(2) - 1 - k) if back else k
    return (
        pl.BlockSpec((None, q, lanes), lambda n, g, k: (n, at(k), g)),
        pl.BlockSpec((None, q, state), lambda n, g, k: (n, at(k), g)),
        pl.BlockSpec((None, None, q, per), lambda n, g, k: (n, g, at(k), 0)),
        pl.BlockSpec((None, None, per, q), lambda n, g, k: (n, g, 0, at(k))),
        pl.BlockSpec((None, 1, lanes), lambda n, g, k: (g, 0, 0)),
        pl.BlockSpec((None, None, None, state, lanes),
                     lambda n, g, k: (n, at(k), g, 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _skip_rows(d_skip, groups, per, width):
    return jnp.broadcast_to(d_skip.astype(F32).reshape(groups, 1, per, 1),
                            (groups, 1, per, width)).reshape(
                                groups, 1, per * width)


def forward(x, d, a_head, b, c, d_skip, keep_states=False):
    """-> (``y`` as ``x``, the state at every chunk's start ``[batch,
    chunks, groups, state, per * width]`` float32 or None). ``d``
    [batch, seq, heads], ``a_head`` and ``d_skip`` [heads]: float32."""
    n, seq, heads, width, groups, state, per = _sizes(x, b)
    lanes, chunks = per * width, seq // CHUNK
    wide, narrow, by_rows, by_cols, skip, states = _specs(
        CHUNK, per, lanes, state, back=False)
    lc, lr, dc, dr = _by_head(d, a_head, groups)
    out_shape = [jax.ShapeDtypeStruct((n, seq, heads * width), x.dtype)]
    out_specs = [wide]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (n, chunks, groups, state, lanes), F32))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, keep_states=keep_states),
        grid=(n, groups, chunks),
        in_specs=[wide, narrow, narrow, by_rows, by_cols, by_rows, by_cols,
                  skip],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((state, lanes), F32)],
        compiler_params=_params(), name="p1t_ssd_fwd",
        interpret=_common.interpret(),
    )(x.reshape(n, seq, heads * width), b.reshape(n, seq, groups * state),
      c.reshape(n, seq, groups * state), lc, lr, dc, dr,
      _skip_rows(d_skip, groups, per, width))
    return (out[0].reshape(x.shape), out[1] if keep_states else None)


def backward(x, d, a_head, b, c, d_skip, starts, g):
    """-> the gradients of (``x``, ``d``, ``a_head``, ``b``, ``c``,
    ``d_skip``) for ``y``'s gradient ``g``; ``starts``: what
    :func:`forward` kept."""
    n, seq, heads, width, groups, state, per = _sizes(x, b)
    lanes, chunks = per * width, seq // CHUNK
    wide, narrow, by_rows, by_cols, skip, states = _specs(
        CHUNK, per, lanes, state, back=True)
    lc, lr, dc, dr = _by_head(d, a_head, groups)
    col = jax.ShapeDtypeStruct((n, groups, seq, per), F32)
    row = jax.ShapeDtypeStruct((n, groups, per, seq), F32)
    flat_b = jax.ShapeDtypeStruct((n, seq, groups * state), b.dtype)
    dx, db, dcc, dl_col, dl_row, dd_col, dd_row, dskip = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width),
        grid=(n, groups, chunks),
        in_specs=[wide, wide, narrow, narrow, by_rows, by_cols, by_rows,
                  by_cols, skip, states],
        out_specs=[wide, narrow, narrow, by_rows, by_cols, by_rows, by_cols,
                   pl.BlockSpec((None, None, None, 1, lanes),
                                lambda n, g, k: (n, g, k, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, seq, heads * width), x.dtype),
                   flat_b, flat_b, col, row, col, row,
                   jax.ShapeDtypeStruct((n, groups, chunks, 1, lanes), F32)],
        scratch_shapes=[pltpu.VMEM((state, lanes), F32)],
        compiler_params=_params(), name="p1t_ssd_bwd",
        interpret=_common.interpret(),
    )(x.reshape(n, seq, heads * width), g.reshape(n, seq, heads * width),
      b.reshape(n, seq, groups * state), c.reshape(n, seq, groups * state),
      lc, lr, dc, dr, _skip_rows(d_skip, groups, per, width), starts)

    def whole(by_rows, by_cols):
        """[batch, seq, heads] of a sum laid out both ways."""
        return (by_rows.transpose(0, 2, 1, 3)
                + by_cols.transpose(0, 3, 1, 2)).reshape(n, seq, heads)
    d_total = whole(dl_col, dl_row).reshape(n, chunks, CHUNK, heads)
    # L is a running sum inside a chunk: a position's d A reaches every
    # later L of its chunk
    d_a = jnp.flip(jnp.cumsum(jnp.flip(d_total, 2), axis=2), 2).reshape(
        n, seq, heads)
    return (dx.reshape(x.shape),
            whole(dd_col, dd_row) + d_a * a_head,
            jnp.sum(d_a * d, axis=(0, 1)),
            db.reshape(b.shape), dcc.reshape(c.shape),
            jnp.sum(dskip.reshape(n, groups, chunks, per, width),
                    axis=(0, 2, 4)).reshape(heads))
