"""Shared helpers for the Pallas kernel family (one definition — the
VMEM budget, row-block ladder, and backend check must not drift between
kernels)."""

from __future__ import annotations

import jax

_VMEM_BUDGET = 4 * 1024 * 1024  # input block + output block, f32


def interpret() -> bool:
    """Run the kernel in interpreter mode off-TPU so tests exercise the
    same code path the chip executes. Every kernel reads this one
    function at call time (``_common.interpret()``), so a test that
    compiles for a described chip patches it here."""
    return jax.default_backend() != "tpu"


def block_rows(rows: int, h: int) -> int:
    """Largest sublane-aligned row block whose [br, h] f32 in+out blocks
    fit the VMEM budget; 0 if none divides ``rows``."""
    if h <= 0:
        return 0
    for br in (256, 128, 64, 32, 16, 8):
        if rows % br == 0 and br * h * 4 * 2 <= _VMEM_BUDGET:
            return br
    return 0
