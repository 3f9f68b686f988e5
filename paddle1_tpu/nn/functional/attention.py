"""Attention functionals.

No direct reference analog (the reference's MultiHeadAttention is composed of
matmul/softmax ops in python/paddle/nn/layer/transformer.py:109); on TPU the
fused path matters, so this module is the single entry point that routes to
the Pallas flash-attention kernel when eligible (jit, TPU, aligned shapes)
and to the plain XLA composition otherwise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...autograd.engine import apply
from ...core.tensor import Tensor, to_tensor

__all__ = ["scaled_dot_product_attention", "attention_ref",
           "paged_attention", "rotary_embedding", "yarn_frequencies"]


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def attention_ref(q, k, v, mask=None, dropout_p=0.0, scale=None,
                  is_causal=False, dropout_key=None, mask_rule=None):
    """Pure-jax reference attention. q,k,v: [B, N, H, D] (paddle layout:
    batch, seq, heads, head_dim); v may have a head width of its own,
    which the result takes; k and v may have fewer heads than q
    (grouped-query attention), each then serves ``H / H_kv`` query heads.
    ``mask_rule``: a structured mask as ``ops.pallas.mask_rules``
    describes it, built dense here: for sizes the kernels do not take."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    # -> [B, H, N, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if is_causal:
        nq, nk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((nq, nk), bool), nk - nq)
        logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    if mask_rule is not None:
        from ...ops.pallas.mask_rules import dense_mask
        seen = dense_mask(mask_rule, logits.shape[-2], logits.shape[-1])
        logits = jnp.where(seen, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def _turn(x, at, theta, interleaved, frequencies, scale, back):
    """``x * C + (x @ P) * S`` over the last axis, or with ``back`` the
    same pass with ``S`` negated, which is its transpose. The turned span
    is twice the table's length (the whole head from ``theta``); on the
    channels behind it ``C`` is 1, ``S`` is 0 and ``P`` has no entry."""
    batch, seq, heads, d = x.shape
    if frequencies is None:
        half = d // 2
        inv_freq = jnp.float32(theta) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
    else:
        half = len(frequencies)
        inv_freq = jnp.asarray(frequencies, jnp.float32)
    span = 2 * half
    angle = at.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)   # [(batch,) seq, 1, half]
    if scale != 1.0:
        cos, sin = jnp.float32(scale) * cos, jnp.float32(scale) * sin
    if back:
        sin = -sin
    lane = jnp.arange(d)
    if interleaved:
        cos_d = jnp.repeat(cos, 2, axis=-1)
        sin_d = jnp.stack([-sin, sin], axis=-1).reshape(cos_d.shape)
        source = lane ^ 1
    else:
        cos_d = jnp.concatenate([cos, cos], axis=-1)
        sin_d = jnp.concatenate([-sin, sin], axis=-1)
        source = (lane + half) % span
    swap = lane[:, None] == source[None, :]
    if span < d:    # the channels that pass: a one, a zero, no partner
        rest = cos_d.shape[:-1] + (d - span,)
        cos_d = jnp.concatenate([cos_d, jnp.ones(rest, jnp.float32)], -1)
        sin_d = jnp.concatenate([sin_d, jnp.zeros(rest, jnp.float32)], -1)
        swap = swap & (lane < span)
    swap = swap.astype(x.dtype)
    # ones and zeros: exact in bf16 at the MXU's one bf16 precision, which
    # is named so that no process-wide default can ask for another; a
    # float32 x would be rounded to bf16 by a TPU's default
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    # every position a row of [heads, d], the batch folded into the
    # sequence: with a batch of one as an axis of its own XLA's layout
    # assignment on the v5e splits the pass round a float32 copy of q
    # (PERF.md section 6, PR 45: the forms tried, a cell each)
    rows = x.reshape(batch * seq, heads, d)
    cos_d, sin_d = (jnp.broadcast_to(t, (batch, seq, 1, d)).reshape(
        batch * seq, 1, d) for t in (cos_d, sin_d))
    partner = jax.lax.dot_general(
        rows, swap, (((2,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    return (rows.astype(jnp.float32) * cos_d
            + partner * sin_d).astype(x.dtype).reshape(x.shape)


# (theta, interleaved, frequencies, scale): static, so hashable
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rotary(x, at, how):
    return _turn(x, at, *how, back=False)


def _rotary_fwd(x, at, how):
    return _turn(x, at, *how, back=False), at


def _rotary_bwd(how, at, g):
    dat = (jnp.zeros_like(at) if jnp.issubdtype(at.dtype, jnp.floating)
           else np.zeros(at.shape, jax.dtypes.float0))
    return _turn(g, at, *how, back=True), dat


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def yarn_frequencies(dim, theta, factor, original_max_position_embeddings,
                     beta_fast=32.0, beta_slow=1.0):
    """YaRN's table (Peng et al. 2023, arXiv:2309.00071) for a turned span
    of ``dim`` channels, ``dim / 2`` frequencies for
    :func:`rotary_embedding`'s ``frequencies``: pair ``i`` keeps ``b_i =
    theta ** (-2 i / dim)`` where it turns more than ``beta_fast`` times
    over the original context, is slowed ``factor`` times where it turns
    less than ``beta_slow`` times, and is blended linearly in ``i``
    between: ``f_i = (1 - m_i) b_i / factor + m_i b_i``, ``m_i = 1 -
    clip((i - lo) / (hi - lo), 0, 1)``, ``lo = floor(c(beta_fast))``,
    ``hi = ceil(c(beta_slow))`` clipped to ``[0, dim - 1]``, ``c(r) = dim
    ln(original / (2 pi r)) / (2 ln theta)``. Host arithmetic in float64;
    the ``attention_factor`` a configuration publishes beside them goes
    in as ``scale``."""
    half = dim // 2
    base = float(theta) ** (-np.arange(half, dtype=np.float64) / half)

    def turns_at(r):
        return dim * np.log(original_max_position_embeddings
                            / (2 * np.pi * r)) / (2 * np.log(float(theta)))
    lo = max(int(np.floor(turns_at(beta_fast))), 0)
    hi = min(int(np.ceil(turns_at(beta_slow))), dim - 1)
    # a ramp of no length is a step
    keeps = 1 - np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (1 - keeps) * base / factor + keeps * base


def rotary_embedding(x, theta=10000.0, positions=None, interleaved=False,
                     frequencies=None, scale=1.0, name=None):
    """Rotary positions (Su et al. 2021, arXiv:2104.09864) on a
    [batch, seq, heads, dim] query or key. Pair ``i`` turns by the angle
    ``position * theta ** (-2 i / dim)``; rotate-half pairing makes it of
    channel ``i`` of the first half and channel ``i`` of the second,
    ``interleaved`` (the paper's own, ``rope_interleave`` of the
    DeepSeek-V3 family) of channels ``2i`` and ``2i + 1``. ``positions``:
    [seq] or [batch, seq] integers, ``0..seq-1`` when None; no gradient
    reaches them. The result has ``x``'s dtype.

    ``frequencies``: a table of its own in ``theta``'s place, one angle a
    position a pair (:func:`yarn_frequencies` makes YaRN's); **its length
    is the span that turns**, the first ``2 * len(frequencies)`` channels
    paired among themselves (halves of the span, or ``2i`` and ``2i +
    1``), and the channels behind it pass as they are
    (``partial_rotary_factor``). ``scale`` multiplies cos and sin alike
    (YaRN's ``attention_factor``), so a turned channel grows by it and a
    passing one does not.

    One pass at the full head width: ``y = x * C + (x @ P) * S``, with
    ``C`` the cosines laid under both channels of a pair, ``S`` the sines
    with the minus sign on the pair's first channel, and ``P`` the 0 / 1
    matrix that hands each channel its partner. A slice or a
    concatenation at half the width is no elementwise op on a TPU (64 of
    128 lanes) and left float32 halves of q and k in HBM; a product with
    ``P`` fuses. A span narrower than the head is the same pass: ``C`` 1
    and ``S`` 0 on the channels that pass, ``P`` pairing inside the span
    alone. Float32 whatever ``x`` is: the angles, ``C`` and ``S``,
    ``x @ P`` (a sum of one value and zeros, so exact), both products
    and their sum; the one rounding is the last cast. The backward is
    written by hand: ``P`` is its own inverse and swaps ``S``'s signs,
    so ``dx = g * C - (g @ P) * S`` is the same pass on ``g``, float32
    the same way. Autodiff of the forward would send the float32
    ``g * S`` through a bf16 product and round one term early. ``C`` and
    ``S`` are made again from the positions, which are all it keeps."""
    x = _t(x)
    at = (_t(positions) if positions is not None
          else jnp.arange(x.shape[1], dtype=jnp.int32))
    if frequencies is not None:
        frequencies = tuple(float(f) for f in frequencies)
        if not 0 < 2 * len(frequencies) <= x.shape[-1]:
            raise ValueError(f"{len(frequencies)} frequencies turn "
                             f"{2 * len(frequencies)} channels of a head "
                             f"of {x.shape[-1]}")
    how = (theta, interleaved, frequencies, float(scale))

    def f(x, at):
        return _rotary(x, at, how)
    return apply("rotary_embedding", f, (x, at))


# The shortest sequence at which the blockwise kernels ran a shorter
# forward + backward than XLA's dense composition on the v5e.
FLASH_MIN_SEQ = 1024


def use_flash_for(q, k) -> bool:
    """The dense-vs-flash dispatch policy, shared by every attention
    entry point (sdpa here, ulysses_attention in
    distributed/sequence_parallel.py). ``never`` → False, ``always`` →
    True (interpret mode off a TPU: the tests' switch). ``auto`` → the
    Pallas kernels where the chip showed them faster, from what the code
    can observe: the backend is a TPU, the step is one device's (inside
    ``core.flags.auto_partitioned_region`` GSPMD refuses a Mosaic
    kernel) and both sequences are at least ``FLASH_MIN_SEQ`` long. Tile
    alignment is ``flash_attention.supported``'s to say, which sees v's
    shape too. q/k are [batch, seq, heads, dim] arrays (or tracers); a
    value width of its own (latent attention: keys 192, values 128)
    moves no crossing in the sweep below, so the rule reads the
    sequences alone.

    The sweep (tools/tpu_flash_crossover.py on a TPU v5 lite, re-run
    2026-10-01 with PR 39's kernels, which walk the needed (query block,
    key block) pairs alone by a scalar-prefetched table: one call's
    forward + backward in isolation, bf16, 8192 tokens a call, ms dense /
    kernel; on the rectangular grid they had before, PR 35's table of
    2026-09-30, the kernels read 1.74, 2.29, 3.16, 4.91 and 8.41 in the
    first column, 1.75, 2.46, 4.01, 6.85 and 12.91 in the second, 6.89
    and 11.89 in the last, and dense the same to 0.1%):

    ====== ============= ============= ============= ============= ==================
    seq    d128 causal   d128 full     d64 causal    d64 full      d192 / v128 causal
    ====== ============= ============= ============= ============= ==================
    512    2.00 / 1.92   2.00 / 1.91   0.75 / 1.10   0.75 / 1.09   not measured
    1024   4.11 / 2.33   4.09 / 2.61   2.94 / 1.38   2.85 / 1.59   not measured
    2048   7.47 / 3.08   7.45 / 4.06   5.29 / 1.91   5.27 / 2.62   not measured
    4096   13.98 / 4.59  13.88 / 6.96  10.25 / 3.00  10.20 / 4.69  14.31 / 6.48
    8192   30.35 / 7.88  28.39 / 13.06 20.89 / 5.11  20.64 / 8.78  29.39 / 10.99
    ====== ============= ============= ============= ============= ==================

    What the table buys grows with the steps a mask lets it drop (6 to 8%
    of a causal call at 4096 and 8192); where it drops none it costs: a
    call without a mask reads 1 to 2% more from 2048 up and 6 to 9% more
    at 512 and 1024 at d 128, where a head has one or two grid steps and
    the table's trip to SMEM before each kernel has little to hide behind
    (no cell runs such a call: PERF.md section 7).

    The last column (16 heads): keys 192 and values 128 wide, latent
    attention's shape. 192 is no multiple of the 128-lane tile, so q, k,
    dq and dk take the kernels' transposed [B*H, N, D] layout and pay
    XLA transposes that the 128-wide column does not: 1.25 x its
    products, 1.4 x its time. The kernels still take under half of
    dense's time at 8192, where a 32-head row of dense scores (8.6 GB)
    does not fit at all.

    Dense wins at 512 at d 64 whatever the mask, and at BERT's shapes
    ([64,512,12,64] 5.46 / 5.50, level; [256,128,12,64] 1.61 / 6.69:
    one or no key block to skip, and at d 64 the kernels pay XLA
    transposes); at d 128 the kernels are ahead at 512 too (by 4%; by 13%
    on the rectangular grid), and from 1024 up, causal or not, by
    more the longer the sequence. **The rule stays where PR 28 set it**:
    no cell runs d 128 at 512, a crossing moves on a step's evidence, and
    ``bert_base.pretrain_s512`` forced onto the kernels gained 0.27%
    end to end (under the 1% that would have moved the rule) and failed
    the benchmark's comparison on the bf16 ``delta``. In a step:
    ``ouro_2p6b.pretrain_s4096`` 1257.3 -> 966.9 ms (PERF.md, PR 28)."""
    from ...core.flags import flag, flag_active
    if not flag_active("flash_attention"):
        return False
    if flag("flash_attention") == "always":
        return True
    return min(q.shape[1], k.shape[1]) >= FLASH_MIN_SEQ


def _count_arm(arm: str) -> None:
    """One increment a traced call of sdpa: which arm the step holds."""
    from ...obs.registry import process_group
    process_group("arm").child(arm).counter("attention_arm_total").inc()


def use_paged_kernel() -> bool:
    """Kernel-vs-ref dispatch for the paged decode gather, mirroring
    ``use_flash_for``'s flag grammar: ``pallas_paged_attention`` =
    ``never`` → XLA ``take`` composition, ``always`` → Pallas kernel
    (interpret mode off-TPU — the CI arm), ``auto`` → kernel on TPU
    only. No memory heuristic: at decode widths the dense gather
    materializes [slots, capacity, heads, dim] K/V per layer per step,
    which the kernel exists to avoid."""
    from ...core.flags import flag_active
    return flag_active("pallas_paged_attention")


def paged_attention(query, k_pool, v_pool, table, pos, name=None):
    """Decode attention over the block-paged KV pool.

    ``query``: [slots, window, heads, dim] — the decode window just
    written; ``k_pool``/``v_pool``: [pages, heads, page_size, dim]
    global pools; ``table``: [slots, max_pages_per_slot] int32 page
    table; ``pos``: [slots] int32 per-slot cursor AFTER the window
    write (the cache's advanced ``pos``), so query row ``i`` attends
    key positions ``<= pos - window + i``. Masking is positional —
    callers pass no attention mask, and pages past the cursor
    (including the parking page) never reach the softmax.
    """
    q, kp, vp, tb, ps_ = (_t(query), _t(k_pool), _t(v_pool), _t(table),
                          _t(pos))
    from ...ops.pallas import paged_attention as pa

    def f(q, kp, vp, tb, pos):
        base = pos.astype(jnp.int32) - jnp.int32(q.shape[1])
        if use_paged_kernel() and pa.supported(q.shape, kp.shape):
            return pa.paged_attention(q, kp, vp, tb, base)
        return pa.paged_attention_ref(q, kp, vp, tb, base)
    return apply("paged_attention", f, (q, kp, vp, tb, ps_))


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None,
                                 use_flash=True, mask_rule=None):
    """Fused attention entry. Takes the Pallas blockwise kernels where
    ``use_flash_for`` says they win and the shapes are tile-aligned, else
    the XLA composition. ``key`` and ``value`` may have fewer heads than
    ``query`` (grouped-query attention). ``mask_rule``: a structured mask
    as a description (``ops.pallas.mask_rules``: block diffusion's, or
    ``SlidingWindow(window)``, the causal mask cut to a band; given in
    ``is_causal``'s place, not beside it), not a dense array: the kernels
    skip its hidden tiles, the composition builds the dense mask from
    it."""
    q, k, v = _t(query), _t(key), _t(value)
    drop = dropout_p if training else 0.0
    dropout_key = None
    if drop > 0.0:
        from ...core.generator import next_key
        dropout_key = next_key()

    from ...ops.pallas import flash_attention as fa
    args = (q, k, v) + ((attn_mask,) if attn_mask is not None else ())

    def _as_padding_mask(mask, nk):
        """[B,1,1,Nk] bool/additive mask → [B, Nk] keep-mask, or None if
        not provably a pure padding mask (the flash kernel drops keys; it
        cannot represent finite soft biases)."""
        if mask is None or mask.ndim != 4 or mask.shape[-1] != nk:
            return None
        if mask.shape[1] != 1 or mask.shape[2] != 1:
            return None
        flat = mask[:, 0, 0, :]
        if mask.dtype == jnp.bool_:
            return flat.astype(jnp.float32)      # exact, trace-safe
        if isinstance(mask, jax.core.Tracer):
            # traced additive values are opaque — a finite bias would be
            # silently discarded; let attention_ref apply it instead
            return None
        import numpy as np
        fl = np.asarray(flat)
        if not bool(np.all((np.abs(fl) <= 1e-6) | (fl <= -1e4))):
            return None                          # soft bias → ref path
        return jnp.asarray(fl > -1e4, jnp.float32)

    def f(q, k, v, *m):
        mask = m[0] if m else None
        if (use_flash and drop == 0.0
                and use_flash_for(q, k)
                and fa.supported(q.shape, k.shape, causal=is_causal,
                                 v_shape=v.shape, mask=mask_rule)):
            pm = (None if mask is None
                  else _as_padding_mask(mask, k.shape[1]))
            if mask is None or pm is not None:
                _count_arm("flash")
                return fa.flash_attention(q, k, v, causal=is_causal,
                                          padding_mask=pm, mask=mask_rule)
        _count_arm("dense")
        return attention_ref(q, k, v, mask=mask, dropout_p=drop,
                             is_causal=is_causal, dropout_key=dropout_key,
                             mask_rule=mask_rule)
    return apply("scaled_dot_product_attention", f,
                 tuple(a if isinstance(a, Tensor) else _t(a) for a in args))
