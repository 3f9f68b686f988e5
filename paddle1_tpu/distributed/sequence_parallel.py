"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference snapshot has NO sequence parallelism (SURVEY §5 long-context:
absent) — this is the capability-extension target the TPU build adds as a
first-class mesh axis ('sp'). Two schemes, both pure-jax functions intended
to run under ``shard_map`` over the hybrid mesh (or inside a pjit with
explicit sp sharding):

* **ring_attention(q, k, v, axis_name)** — K/V shards rotate around the
  ICI ring via ``lax.ppermute`` while each device's queries accumulate
  online-softmax partials; peak memory is one K/V shard, comm fully
  overlaps compute on TPU (the ppermute for step i+1 is independent of the
  step-i matmuls, so XLA's latency-hiding scheduler pipelines them).
* **ulysses_attention(q, k, v, axis_name)** — all-to-all swaps the shard
  axis from sequence to heads, runs dense local attention (flash kernel
  when aligned), and swaps back. Cheaper for moderate sequence lengths;
  requires num_heads % sp == 0.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ulysses_attention"]

_NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask=None):
    """One q-shard x k-shard partial: returns (numerator, sumexp, rowmax).
    q,k,v: [B, N, H, D] shards; f32 math."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                         # [B,H,Nq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                         # [B,H,Nq]
    num = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return num, l, m


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """[B, N_local, H, D] per device; sequence sharded over ``axis_name``.

    Each of the sp steps computes local-q x rotating-KV partials and merges
    them with the running online-softmax state; ppermute advances the K/V
    ring one ICI neighbor per step.
    """
    d = q.shape[-1]
    sc = float(scale) if scale is not None else float(1.0 / (d ** 0.5))
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, nl, h, _ = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    q_pos = my * nl + jax.lax.broadcasted_iota(jnp.int32, (nl, 1), 0)

    def step(carry, i):
        k_cur, v_cur, m_run, l_run, acc = carry
        src = (my - i) % n  # whose shard we hold at step i
        mask = None
        if causal:
            k_pos = src * nl + jax.lax.broadcasted_iota(
                jnp.int32, (1, nl), 1)
            mask = (q_pos >= k_pos)[None, None]      # [1,1,Nq,Nk]
        num, l, m = _block_attn(q, k_cur, v_cur, sc, mask)
        m_new = jnp.maximum(m_run, m)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m - m_new)
        l_new = l_run * alpha + l * beta
        acc_new = acc * alpha[..., None] + num * beta[..., None]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, nl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, nl), jnp.float32)
    acc0 = jnp.zeros((b, h, nl, d), jnp.float32)
    # Mark the running-softmax carries device-varying so the scan carry
    # type matches (k/v rotate, so the whole carry is varying over sp).
    m0, l0, acc0 = (lax.pcast(x, (axis_name,), to="varying")
                    for x in (m0, l0, acc0))
    (_, _, m, l, acc), _ = lax.scan(step, (k, v, m0, l0, acc0),
                                    jnp.arange(n))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]                    # [B,H,Nq,D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): shard
    axis moves seq→heads, local attention runs over the FULL sequence with
    H/sp heads, then moves back. [B, N_local, H, D] in and out."""
    n = lax.axis_size(axis_name)
    b, nl, h, d = q.shape
    if h % n:
        raise ValueError(f"ulysses: num_heads {h} not divisible by sp={n}")

    def seq2head(x):
        # [B, Nl, H, D] -> [B, Nl*n(seq global), H/n, D]
        x = x.reshape(b, nl, n, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                           tiled=False)
        return x.reshape(b, nl * n, h // n, d)

    def head2seq(x):
        # [B, N_global, H/n, D] -> [B, n, Nl, H/n, D]; a2a removes the n
        # axis and re-inserts it before the head dim -> [B, Nl, n, H/n, D]
        x = x.reshape(b, n, nl, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                           tiled=False)
        return x.reshape(b, nl, h, d)

    qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)
    from ..ops.pallas import flash_attention as fa
    from ..nn.functional.attention import attention_ref, use_flash_for
    # same dense-vs-flash policy as scaled_dot_product_attention,
    # applied to the post-all-to-all GLOBAL sequence length
    if (fa.supported(qg.shape, kg.shape, causal=causal)
            and use_flash_for(qg, kg)):
        og = fa.flash_attention(qg, kg, vg, causal=causal, scale=scale)
    else:
        og = attention_ref(qg, kg, vg, is_causal=causal, scale=scale)
    return head2seq(og)
