"""The channels-last execution region (r5).

On the TPU the channel dim wants to be the minor (lane) dim: NHWC convs
with HWIO weights feed the MXU directly, while a channels-first conv or
``reduce_window`` pool leaves the layout change to the compiler. What
that costs on the v5e under XLA's own layout assignment is not measured
(``tools/tpu_conv_probe.py`` measures both layouts).
Under the ``conv_nhwc`` flag, every layout-sensitive channels-first-API
image op (conv, max/avg/adaptive pool, batch norm, transposed conv)
executes channels-last internally, transposing at its boundary;
adjacent ops' boundary transposes are inverse pairs that XLA's
algebraic simplifier cancels, so inside a jitted model only the stem
input and head output transposes survive.

This module is the single definition of the region's eligibility rule
and transpose pairs so the participating ops cannot drift apart.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["channels_last_region", "channels_last_region_for",
           "CONV_WEIGHT_PERM", "CONV_CL_SPEC"]

_identity = lambda t: t

# x_ndim -> (to channels-last, back to channels-first)
_PERMS = {
    3: ((0, 2, 1), (0, 2, 1)),                      # NCL  <-> NLC
    4: ((0, 2, 3, 1), (0, 3, 1, 2)),                # NCHW <-> NHWC
    5: ((0, 2, 3, 4, 1), (0, 4, 1, 2, 3)),          # NCDHW<->NDHWC
}

# spatial_rank -> permutation taking an [O, I, *k]-style weight to
# spatial-major [*k, I, O] (the HWIO family), and the matching
# channels-last conv_dimension_numbers spec — shared by _conv and
# _conv_transpose so the two flag paths cannot drift apart
CONV_WEIGHT_PERM = {1: (2, 1, 0), 2: (2, 3, 1, 0), 3: (2, 3, 4, 1, 0)}
CONV_CL_SPEC = {1: ("NWC", "WIO", "NWC"),
                2: ("NHWC", "HWIO", "NHWC"),
                3: ("NDHWC", "DHWIO", "NDHWC")}


def channels_last_region(x_ndim: int, channel_last: bool):
    """Resolve the channels-last region for one op application.

    Returns ``(active, to_internal, from_internal)``: when ``active``,
    the op should compute on ``to_internal(x)`` (channels-last) and
    return ``from_internal(y)``. Only channels-first tensors with a
    batch dim, a channel dim, and 1-3 spatial dims participate; callers
    gate ineligible cases by passing ``x_ndim=0``.
    """
    if channel_last or x_ndim not in _PERMS:
        return False, _identity, _identity
    from ...core.flags import conv_nhwc_active
    if not conv_nhwc_active():
        return False, _identity, _identity
    fwd, bwd = _PERMS[x_ndim]
    return (True,
            lambda t: jnp.transpose(t, fwd),
            lambda t: jnp.transpose(t, bwd))


def channels_last_region_for(x, spatial_rank: int, channel_last: bool):
    """Region resolution for an op with a known spatial rank: only a
    batched channels-first input of rank ``spatial_rank + 2``
    participates — a mis-ranked input stays on the normal (flag-off)
    path so its error message does not depend on a performance flag.
    ``x`` may be a Tensor, array, or tracer (anything with ``ndim``)."""
    rank = getattr(x, "ndim", 0)
    return channels_last_region(
        rank if rank == spatial_rank + 2 else 0, channel_last)
