"""What the two blockwise attention kernels have to compute, from the
shapes alone: one definition, which every configuration's
``attention_kernel_flops`` calls with its own pairs, heads and widths.

A (query, key) pair that the mask lets through costs a head one
score-shaped product of ``2 x width`` FLOPs for each product the kernel
makes of it. The forward kernel makes two: the score ``q k^T`` over the key
width and ``p v`` over the value width. The one backward kernel makes
five: the score again, ``dS k`` (dQ) and ``dS^T q`` (dK) over the key
width, ``p^T dO`` (dV) and ``dO v^T`` (dP) over the value width. Pairs, not
tiles: what a kernel computes of a crossed tile's hidden part is not the
model's work, so a share of the peak made from this count cannot pass 100%
by counting it.

The keys are the kernels' names without what the program may write behind
them: ``reducers/kernel_mxu_pct.py`` takes an instruction named
``p1t_flash_attention_bwd``, ``p1t_flash_attention_bwd_dkv`` or
``p1t_flash_attention_bwd_dkv.17`` for the backward kernel, so the
benchmark does not decide how the program names it.
"""

FORWARD, BACKWARD = "p1t_flash_attention_fwd", "p1t_flash_attention_bwd"
# (products over the key width, products over the value width) a pair
PRODUCTS = {FORWARD: (1, 1), BACKWARD: (3, 2)}


def flops(pairs, heads, key_width, value_width=None):
    """{kernel: FLOPs} of ``pairs`` visible (query, key) pairs a head,
    over ``heads`` query heads."""
    value_width = key_width if value_width is None else value_width
    return {kernel: 2 * pairs * heads * (k * key_width + v * value_width)
            for kernel, (k, v) in PRODUCTS.items()}
