"""The attention sweep behind ``use_flash_for`` and ``block_sizes``
(PR 28), on the chip it is run on: forward + backward of one attention
call in isolation, milliseconds a call, one table out.

Arms: ``dense`` (XLA's composition, ``attention_ref``), ``kernel`` (the
Pallas blockwise kernels of ops/pallas/flash_attention*.py, under each
candidate block triple), ``chunked`` (the same skip as an unrolled XLA
composition: query chunk i against keys [0, (i+1) * chunk)), ``jax``
(``jax.experimental.pallas.ops.tpu.flash_attention`` in its own
[B, H, N, D] layout, the yardstick).

    python tools/tpu_flash_crossover.py [--part blocks|lengths|latent|diffusion|all]

``blocks``: the Ouro shape [2, 4096, 16, 128] bf16 causal, the forward
kernel and the backward kernel alone under each block triple.
``lengths``: 8192 tokens at sequence
lengths 512 .. 8192 (d 128 and d 64, causal and not, and BERT's two
shapes), kernel against dense: the crossover. ``latent`` (PR 31): the
same at keys 192 and values 128 wide (latent attention), causal, at 4096
and 8192. ``diffusion`` (PR 33): block diffusion's mask rule with grouped
key/value heads at SDAR's size, a doubled row [1, 2 x 8192, 32 / 4, 128]
with blocks of 4: either kernel alone under a few block triples, and forward
+ backward beside the same shape under a causal mask over the 16384 (twice
the visible pairs, and not the model's mask: what the rule saves). Dense
does not fit there (1 GiB of float32 scores a head).
Writes chiprun_out/flash_sweep.json beside the table.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ms(f, *args, k=10, trials=3):
    import jax
    jax.block_until_ready(f(*args))
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        r = None
        for _ in range(k):
            r = f(*args)
        jax.block_until_ready(r)
        dt = (time.perf_counter() - t0) / k
        best = dt if best is None else min(best, dt)
    return best * 1e3


def chunked_causal(q, k, v, chunks):
    """Causal attention as ``chunks`` dense pieces, no ``while``: piece i
    takes queries [i*c, (i+1)*c) against keys [0, (i+1)*c). bf16
    operands, float32 scores and softmax, probabilities in q's dtype."""
    import jax
    import jax.numpy as jnp
    n, d = q.shape[1], q.shape[-1]
    c = n // chunks
    outs = []
    for i in range(chunks):
        hi = (i + 1) * c
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, i * c:hi], k[:, :hi],
                       preferred_element_type=jnp.float32) / (d ** 0.5)
        rows = i * c + jax.lax.broadcasted_iota(jnp.int32, (c, hi), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (c, hi), 1)
        s = jnp.where(rows >= cols, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :hi]))
    return jnp.concatenate(outs, axis=1)


def _inputs(b, s, h, d, dtype, layout="bnhd", dv=None):
    """q, k (``d`` wide), v, dout (``dv`` wide, ``d`` when None)."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.key(0), 4)
    return [jax.random.normal(
        kk, (b, s, h, w) if layout == "bnhd" else (b, h, s, w),
        jnp.float32).astype(dtype)
        for kk, w in zip(keys, (d, d, dv or d, dv or d))]


def _fwd_bwd(attn):
    """jitted (q, k, v, do) -> a scalar of every gradient: no download."""
    import jax
    import jax.numpy as jnp

    def f(q, k, v, do):
        out, vjp = jax.vjp(attn, q, k, v)
        return sum(jnp.sum(g.astype(jnp.float32)) for g in vjp(do))
    return jax.jit(f)


def _fwd(attn):
    import jax
    return jax.jit(lambda q, k, v, do: attn(q, k, v))


def arms(causal, blocks=None):
    from paddle1_tpu.nn.functional.attention import attention_ref
    from paddle1_tpu.ops.pallas import flash_attention as fa
    out = {
        "dense": lambda q, k, v: attention_ref(q, k, v, is_causal=causal),
        "kernel": lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, blocks=blocks),
    }
    if causal:
        out["chunked4"] = functools.partial(chunked_causal, chunks=4)
        out["chunked8"] = functools.partial(chunked_causal, chunks=8)
    return out


def jax_flash(causal, block):
    from jax.experimental.pallas.ops.tpu import flash_attention as jf

    def attn(q, k, v):
        bs = jf.BlockSizes(
            block_q=block, block_k_major=block, block_k=block, block_b=1,
            block_q_major_dkv=block, block_k_major_dkv=block,
            block_k_dkv=block, block_q_dkv=block,
            block_k_major_dq=block, block_k_dq=block, block_q_dq=block)
        return jf.flash_attention(q, k, v, causal=causal,
                                  sm_scale=q.shape[-1] ** -0.5,
                                  block_sizes=bs)
    return attn


TRIPLES = [(256, 512, 512), (512, 512, 512), (512, 1024, 512),
           (512, 1024, 1024), (1024, 512, 512), (1024, 1024, 512),
           (1024, 1024, 1024), (512, 2048, 512), (512, 512, 256),
           (256, 1024, 256), (1024, 2048, 1024), (1024, 2048, 512),
           (512, 2048, 1024), (512, 4096, 512), (1024, 4096, 512),
           (1024, 4096, 1024)]


def _one(rows, part, name, f, *a):
    try:
        ms = _ms(f, *a)
    except Exception as e:  # noqa: broad-except — a triple Mosaic
        # refuses is a row of the table, not the end of the sweep
        ms = None
        print(f"  {name}: refused: {str(e)[-300:]}", flush=True)
    rows.append({"part": part, "arm": name, "ms": ms})
    print(f"  {name}: {ms}", flush=True)


def _kernels_alone(rows, part, inputs, rule, triples):
    """The forward kernel and the one backward kernel (dQ, dK and dV
    together), each alone under each block triple."""
    import jax
    from paddle1_tpu.ops.pallas import flash_attention as fa
    from paddle1_tpu.ops.pallas.flash_attention_bwd import \
        flash_attention_bwd
    q, k, v, do = inputs
    scale = q.shape[-1] ** -0.5
    out, lse = jax.jit(lambda q, k, v: fa._flash_fwd(q, k, v, scale,
                                                     rule))(q, k, v)
    for t in triples:
        _one(rows, part, f"fwd {t}",
             jax.jit(lambda q, k, v, t=t: fa._flash_fwd(
                 q, k, v, scale, rule, blocks=t)[0]), q, k, v)
    for t in triples:
        _one(rows, part, f"bwd {t}",
             jax.jit(lambda *a, t=t: flash_attention_bwd(
                 *a, scale, rule, blocks=(None, t))), q, k, v, out, lse, do)


def part_diffusion(rows, length=8192, block=4, heads=32, kv_heads=4, dim=128,
                   triples=((512, 1024, 512), (512, 512, 512),
                            (1024, 1024, 512), (512, 2048, 512))):
    """Block diffusion's rule with grouped key/value heads at SDAR's
    size; beside it a causal mask over the doubled row."""
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.ops.pallas import flash_attention as fa
    from paddle1_tpu.ops.pallas.mask_rules import CAUSAL, BlockDiffusion
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 2 * length, h, dim),
                                     jnp.bfloat16)
                   for kk, h in zip(keys, (heads, kv_heads, kv_heads, heads)))
    rule = BlockDiffusion(length, block)
    _kernels_alone(rows, "diffusion", (q, k, v, do), rule, triples)
    for name, mask in (("block diffusion", rule), ("causal over 2L", CAUSAL)):
        attn = functools.partial(fa.flash_attention, mask=mask)
        _one(rows, "diffusion", f"{name} fwd", _fwd(attn), q, k, v, do)
        _one(rows, "diffusion", f"{name} fwd+bwd", _fwd_bwd(attn), q, k, v,
             do)


def part_blocks(rows, shape=(2, 4096, 16, 128), triples=TRIPLES,
                jax_blocks=(512, 1024)):
    """Each kernel alone at the Ouro shape, under each block triple."""
    import jax.numpy as jnp
    from paddle1_tpu.ops.pallas.mask_rules import CAUSAL
    q, k, v, do = _inputs(*shape, jnp.bfloat16)
    _kernels_alone(rows, "blocks", (q, k, v, do), CAUSAL, triples)
    one = functools.partial(_one, rows, "blocks")
    for name, attn in arms(True).items():
        one(f"{name} fwd", _fwd(attn), q, k, v, do)
        one(f"{name} fwd+bwd", _fwd_bwd(attn), q, k, v, do)
    qh, kh, vh, doh = _inputs(*shape, jnp.bfloat16, layout="bhnd")
    for block in jax_blocks:
        attn = jax_flash(True, block)
        one(f"jax{block} fwd", _fwd(attn), qh, kh, vh, doh)
        one(f"jax{block} fwd+bwd", _fwd_bwd(attn), qh, kh, vh, doh)


def part_lengths(rows, tokens=8192, lengths=(512, 1024, 2048, 4096, 8192),
                 extra=((64, 512, 12, 64, False), (256, 128, 12, 64, False)),
                 widths=((16, 128, 128, (True, False)),
                         (12, 64, 64, (True, False)))):
    """Kernel against dense by sequence length, 8192 tokens a call.
    ``widths``: (heads, key width, value width, masks)."""
    import jax.numpy as jnp
    cases = [(max(tokens // s, 1), s, h, d, dv, causal) for s in lengths
             for h, d, dv, masks in widths for causal in masks]
    cases += [(b, s, h, d, d, causal) for b, s, h, d, causal in extra]
    for b, s, h, d, dv, causal in cases:
        q, k, v, do = _inputs(b, s, h, d, jnp.bfloat16, dv=dv)
        line = {"part": "lengths", "shape": [b, s, h, d, dv],
                "causal": causal}
        for name, attn in arms(causal).items():
            if name.startswith("chunked"):
                continue      # lost at the Ouro shape (part blocks)
            try:
                line[name] = _ms(_fwd_bwd(attn), q, k, v, do)
            except Exception as e:  # noqa: broad-except — see part_blocks
                line[name] = None
                print(f"  {name} refused: {str(e)[-300:]}", flush=True)
        rows.append(line)
        print(" ", json.dumps(line), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--part", choices=("blocks", "lengths", "latent",
                                       "diffusion", "all"), default="all")
    ap.add_argument("--triples", default="",
                    help="'512,2048,512;1024,2048,512': only these")
    args = ap.parse_args()
    triples = [tuple(int(x) for x in t.split(","))
               for t in args.triples.split(";") if t] or TRIPLES
    import jax
    dev = jax.devices()[0]
    print("device:", dev.platform, dev.device_kind, flush=True)
    rows = []
    if args.part in ("blocks", "all"):
        part_blocks(rows, triples=triples)
    if args.part in ("lengths", "all"):
        part_lengths(rows)
    if args.part in ("latent", "all"):
        part_lengths(rows, lengths=(4096, 8192), extra=(),
                     widths=((16, 192, 128, (True,)),))
    if args.part in ("diffusion", "all"):
        part_diffusion(rows)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_sweep.json", "w") as f:
        json.dump({"device": [dev.platform, dev.device_kind],
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
