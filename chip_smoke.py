"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips   the hybrid-parallel path on four
                                        chips against one; no other phase

One chip: a full-width BERT-base trainer (12 layers, hidden 768, 12 heads,
FFN 3072, vocab 30522; random weights from a seed) takes 5 ``step`` calls
and one ``step_many`` of 2 through ``ParallelEngine``, each main-path
Pallas kernel runs once against its XLA reference at a real width, the
blockwise attention kernels run block diffusion's mask rule with grouped
key/value heads at SDAR's size against a float32 dense-mask reference in
blocks, the causal rule runs with 32 / 8 heads at head width 64 over LFM2's
16,384 positions and the two gated short-convolution kernels run at its
size against float32 shifted sums, the sliding-window rule (4,096 keys)
runs with 28 / 4 heads at SmallThinker's size against a float32 dense
band in blocks and again with a window of one query block (512 keys) and
64 / 8 heads beside the causal rule's 48 / 8 at Laguna-XS.2's, where
rotary positions over half a head under YaRN's table are held to their
slices in float32, the two state-space scan kernels run at Nemotron 3
Nano's size ([1, 8192, 64 heads of 64, 8 groups of state 128]) against
the chunked composition in float32 with each one's share of the HBM
roofline, beside the causal rule with 16 query heads a key/value head
([1, 8192, 32 / 2, 128]), the routed-expert layer takes more
held picks than its grouped products have rows and counts the late ones
on the device, eagerly and in two compiled steps
(``ParallelEngine.expert_load()``), and the grouped products' three
kernels run at Nemotron 3 Nano's two shapes ([9216, 2688] x [8, 2688,
1856] and back) against ``lax.ragged_dot`` and its transposes, each timed
beside XLA's own at a third of the rows held and at all of them. Every
check that fails raises: no phase may fail and the script still exit 0,
and no kernel gives way to its reference. One process, no child that
needs the chip.

The last line of standard output is the contract's JSON object. Every
time printed is a smoke reading on the host's clock, not a metric.
"""

import argparse
import functools
import gc
import json
import math
import os
import shutil
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
BATCH, SEQ = 32, 128
# MLM + NSP at chance: an untrained model should open near this
CHANCE_LOSS = math.log(30522) + math.log(2)
# The same seven optimizer steps (seed 0, the same batch, AdamW 1e-4) on
# the CPU in f32 with no kernel — the reference the chip's bf16-AMP run is
# held to. With no warm-up the loss first rises (15.1 at the third step)
# and is below its start only at the seventh. Regenerate, after a change
# to the model's init or the optimizer, with
# tests/test_chip_smoke.py::test_cpu_reference_losses (slow).
CPU_F32_LOSSES = (11.203659, 12.878224, 15.099131, 11.664064, 12.369730,
                  11.776111, 10.886946)


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"chip_smoke: ok: {what}", flush=True)


def tpu_devices(need):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: found no TPU: JAX's backend is "
            f"{devs[0].platform!r} ({devs[0].device_kind!r}). This script "
            "runs on the chip only; it has no CPU path.")
    if len(devs) < need:
        raise SystemExit(f"chip_smoke: need {need} TPU chips, JAX reports "
                         f"{len(devs)}")
    return devs


def build_trainer(devices, degrees, megatron=False, zero_stage=0,
                  amp_dtype="bfloat16"):
    """BERT-base + AdamW + ParallelEngine over ``devices``; the same
    seed gives the same weights and batch to every call."""
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (BertForPretraining,
                                         BertPretrainingCriterion,
                                         apply_megatron_sharding, bert_base)
    paddle.seed(0)
    model = BertForPretraining(bert_base(
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    cfg = model.bert
    check((cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads,
           cfg.intermediate_size, cfg.vocab_size)
          == (12, 768, 12, 3072, 30522),
          "BERT-base at full width: 12 layers, hidden 768, 12 heads, "
          "FFN 3072, vocab 30522")
    if megatron:
        apply_megatron_sharding(model)
    crit = BertPretrainingCriterion(cfg.vocab_size)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(m, b):
        scores, rel = m(Tensor(b["ids"]))
        return crit(scores, rel, Tensor(b["mlm"]), Tensor(b["nsp"]))

    engine = ParallelEngine(
        model, opt, loss_fn, mesh=build_mesh(devices=devices, **degrees),
        zero_stage=zero_stage, amp_dtype=amp_dtype)
    rng = np.random.default_rng(0)
    v = cfg.vocab_size
    batch = {"ids": rng.integers(1, v, (BATCH, SEQ)).astype(np.int32),
             "mlm": rng.integers(0, v, (BATCH, SEQ)).astype(np.int32),
             "nsp": rng.integers(0, 2, (BATCH,)).astype(np.int32)}
    return engine, batch


def timed_step(engine, batch):
    """One ``engine.step`` ended by ``block_until_ready``; (loss, s)."""
    import jax
    t0 = time.perf_counter()
    loss = engine.step(batch)
    jax.block_until_ready(loss.data)
    return float(loss), time.perf_counter() - t0


def compiled_step_text(engine, batch):
    """Optimized HLO of the engine's train step at this batch."""
    import jax
    import jax.numpy as jnp
    return engine.train_step_fn.lower(
        engine.params, engine.opt_state, engine.shard_batch(batch),
        jax.random.key(0), jnp.asarray(0.0, jnp.float32), engine.step_state
    ).compile().as_text()


def peak_bytes(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        check(stats is not None and stats.get("peak_bytes_in_use", 0) > 0,
              f"device {d.id} memory_stats() reports a peak")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


# -- one chip ---------------------------------------------------------------

def trainer_phase(dev):
    import jax
    t0 = time.perf_counter()
    engine, batch = build_trainer([dev], {"dp": 1})
    print(f"chip_smoke: built the model and the engine in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    check(all(leaf.devices() == {dev}
              for leaf in jax.tree_util.tree_leaves(engine.params)),
          f"every param leaf lives on {dev}")

    losses, secs = zip(*[timed_step(engine, batch) for _ in range(5)])
    print(f"chip_smoke: step losses {[round(x, 4) for x in losses]}")
    print(f"chip_smoke: first step (compile included) {secs[0]:.1f}s; "
          f"later steps {[round(s * 1e3, 1) for s in secs[1:]]} ms "
          "(smoke reading, not a metric)", flush=True)
    check(engine.cache_stats() == {"hits": 4, "misses": 1},
          f"the step compiled once over 5 calls: {engine.cache_stats()}")

    t0 = time.perf_counter()
    many = engine.step_many([batch, batch])
    jax.block_until_ready(many.data)
    many = np.asarray(many)
    print(f"chip_smoke: step_many(2) losses "
          f"{[round(float(x), 4) for x in many]} in "
          f"{time.perf_counter() - t0:.1f}s (its own compile included)")
    losses = list(losses) + many.tolist()
    check(engine.trace_count == 2,
          "one trace for step, one for step_many: trace_count == 2")
    check(bool(np.all(np.isfinite(losses))), "all 7 losses are finite")
    check(abs(losses[0] - CHANCE_LOSS) < 1.5,
          f"first loss {losses[0]:.3f} is within 1.5 of chance "
          f"{CHANCE_LOSS:.3f}")
    drift = float(np.max(np.abs(np.asarray(losses) / CPU_F32_LOSSES - 1)))
    check(drift <= 1e-2,
          f"all 7 losses agree with the CPU f32 reference run, rtol 1e-2 "
          f"(largest relative deviation {drift:.2e})")
    check(losses[6] < losses[0],
          f"the loss on the repeated batch ends below its start: "
          f"{losses[0]:.3f} -> {losses[6]:.3f}")
    check("tpu_custom_call" in compiled_step_text(engine, batch),
          "the compiled train step holds a tpu_custom_call (the fused "
          "layer-norm kernel, selected by fused_layer_norm=auto)")
    print(f"chip_smoke: peak bytes in use {peak_bytes([dev])[0]}")


def max_err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


def kernel_phase():
    """Each main-path kernel, compiled for the chip, against its XLA
    reference. The kernels are called directly — not through the
    functional layer that may choose the reference instead."""
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.nn.functional.attention import attention_ref
    from paddle1_tpu.ops.pallas import (flash_attention, layer_norm,
                                        paged_attention)
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.key(0), 16))

    def ref(fn, *args):
        # XLA's default f32 matmul on the TPU is one bf16 pass; the
        # reference has to be better than what it judges
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*[a.astype(f32) if a.dtype == bf16 else a
                                 for a in args])

    # flash attention, the forward and the backward kernel: BERT's width
    # (d 64: the transposed layout), Ouro's causal [2,4096,16,128]
    # (d 128: the packed layout, blocks above the diagonal skipped) and
    # latent attention's keys 192 / values 128 wide (one layout each)
    def loss_of(attn):
        return lambda q, k, v: (attn(q, k, v).astype(f32) ** 2).sum()

    def by_row(fn, *args):
        # the reference a batch row at a time: at s4096 its float32
        # scores are 1 GiB a row, and autodiff keeps several such
        rows = [fn(*[a[i:i + 1] for a in args])
                for i in range(args[0].shape[0])]
        return jax.tree_util.tree_map(lambda *r: jnp.concatenate(r), *rows)

    for shape, causal, v_width in (((8, 512, 12, 64), False, 64),
                                   ((2, 4096, 16, 128), True, 128),
                                   ((1, 4096, 8, 192), True, 128)):
        at = (f"{list(shape)}{f' v{v_width}' if v_width != shape[3] else ''}"
              f"{' causal' if causal else ''}")
        q, k, v = (jax.random.normal(next(keys), shape[:3] + (d,), bf16)
                   for d in (shape[3], shape[3], v_width))
        attn = functools.partial(flash_attention.flash_attention,
                                 causal=causal)
        plain = functools.partial(attention_ref, is_causal=causal)
        flash = jax.jit(attn)
        check("tpu_custom_call" in flash.lower(q, k, v).compile().as_text(),
              f"flash forward {at} compiled to a tpu_custom_call")
        err = max_err(flash(q, k, v),
                      by_row(lambda *a: ref(plain, *a), q, k, v))
        check(err <= 5e-2, f"flash forward {at} bf16 vs XLA reference: "
                           f"max abs err {err:.2e} <= 5e-2")
        grad = jax.jit(jax.grad(loss_of(attn), argnums=(0, 1, 2)))
        text = grad.lower(q, k, v).compile().as_text()
        n_calls = text.count('custom_call_target="tpu_custom_call"')
        check(n_calls == 2 and " while(" not in text,
              f"flash grad {at} compiled to {n_calls} tpu_custom_calls "
              "(forward, backward) and no while")
        want = by_row(lambda *a: ref(jax.grad(loss_of(plain),
                                              argnums=(0, 1, 2)), *a),
                      q, k, v)
        for name, g, w in zip(("dq", "dk", "dv"), grad(q, k, v), want):
            scale = float(np.max(np.abs(np.asarray(w))))
            err = max_err(g, w) / scale
            check(err <= 5e-2, f"flash backward {at} {name}: max abs err "
                               f"/ max |ref| = {err:.2e} <= 5e-2")

    # fused layer norm [4096, 768]
    x = jax.random.normal(next(keys), (4096, 768), bf16)
    w = jax.random.normal(next(keys), (768,), f32)
    b = jax.random.normal(next(keys), (768,), f32)
    ln = jax.jit(layer_norm.fused_layer_norm)
    check("tpu_custom_call" in ln.lower(x, w, b).compile().as_text(),
          "layer norm compiled to a tpu_custom_call")

    def ln_ref(x, w, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b
    err = max_err(ln(x, w, b), ref(ln_ref, x, w, b))
    check(err <= 5e-2, f"layer norm [4096,768] bf16 vs XLA reference: "
                       f"max abs err {err:.2e} <= 5e-2 (bf16 output)")

    # paged attention at one decode shape: 8 slots, window 1, h12 d64,
    # pages of 16, ragged lengths, page tables scattered over the pool
    slots, heads, dim, page, per_slot, pages = 8, 12, 64, 16, 8, 80
    qd = jax.random.normal(next(keys), (slots, 1, heads, dim), bf16)
    kp = jax.random.normal(next(keys), (pages, heads, page, dim), bf16)
    vp = jax.random.normal(next(keys), (pages, heads, page, dim), bf16)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.permutation(np.arange(1, pages))
                        [:slots * per_slot].reshape(slots, per_slot),
                        jnp.int32)
    base = jnp.asarray(rng.integers(0, page * per_slot - 1, slots),
                       jnp.int32)
    check(paged_attention.supported(qd.shape, kp.shape),
          "paged attention admits the decode shape")
    paged = jax.jit(paged_attention.paged_attention)
    check("tpu_custom_call" in paged.lower(
        qd, kp, vp, table, base).compile().as_text(),
        "paged attention compiled to a tpu_custom_call")
    err = max_err(paged(qd, kp, vp, table, base),
                  ref(paged_attention.paged_attention_ref,
                      qd, kp, vp, table, base))
    check(err <= 5e-2, f"paged attention [8,1,12,64] page 16 bf16 vs XLA "
                       f"gather reference: max abs err {err:.2e} <= 5e-2")


def block_diffusion_phase(length=8192, block=4, heads=32, kv_heads=4,
                          dim=128):
    """The blockwise kernels under block diffusion's mask with grouped
    key/value heads, at SDAR's size ([1, 2 x 8192, 32 / 4, 128] bf16),
    forward and backward against a float32 reference that holds the dense
    mask a block of queries at a time, written from the mask's four cases
    and not from ``mask_rules``; then each kernel's ms a call from a
    trace."""
    import jax
    import jax.numpy as jnp
    from benchmarks import trace_reduce
    from benchmarks.reducers.kernel_mxu_pct import seconds_a_step
    from paddle1_tpu.ops.pallas import flash_attention
    from paddle1_tpu.ops.pallas.mask_rules import BlockDiffusion
    bf16, f32 = jnp.bfloat16, jnp.float32
    group, s = heads // kv_heads, 2 * length
    kq, kk, kv_, kd = jax.random.split(jax.random.key(33), 4)
    q = jax.random.normal(kq, (1, s, heads, dim), bf16)
    k = jax.random.normal(kk, (1, s, kv_heads, dim), bf16)
    v = jax.random.normal(kv_, (1, s, kv_heads, dim), bf16)
    dout = jax.random.normal(kd, (1, s, heads, dim), bf16)
    rule = BlockDiffusion(length, block, noisy_first=True)
    at = f"[1, {s}, {heads}/{kv_heads}, {dim}] block diffusion B={block}"

    def kernels(q, k, v):
        return flash_attention.flash_attention(q, k, v, mask=rule)

    r = jnp.arange(s)
    noisy, blk = r < length, r % length // block
    rows = 512

    def plain(q, k, v):
        """float32, one key/value head and ``rows`` queries of its group
        at a time against every key."""
        qg = q[0].astype(f32).reshape(s // rows, rows, kv_heads, group, dim)
        kf, vf = k[0].astype(f32), v[0].astype(f32)

        @jax.checkpoint
        def some(args):
            qb, qn, qblk = args                 # [rows, kv, group, dim]
            scores = jnp.einsum("qngd,knd->ngqk", qb, kf) / dim ** 0.5
            seen = jnp.where(
                qn[:, None],
                jnp.where(noisy[None], blk[None] == qblk[:, None],
                          blk[None] < qblk[:, None]),
                ~noisy[None] & (blk[None] <= qblk[:, None]))
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("ngqk,knd->qngd", probs, vf)
        out = jax.lax.map(some, (qg, noisy.reshape(-1, rows),
                                 blk.reshape(-1, rows)))
        return out.reshape(1, s, heads, dim)

    got = vjp_of(kernels)
    text = got.lower(q, k, v, dout).compile().as_text()
    n_calls = text.count('custom_call_target="tpu_custom_call"')
    check(n_calls == 2 and " while(" not in text,
          f"flash grad {at} compiled to {n_calls} tpu_custom_calls and no "
          "while")
    with jax.default_matmul_precision("highest"):
        want = vjp_of(plain)(q, k, v, dout.astype(f32))
    for name, g, w in zip(("out", "dq", "dk", "dv"), got(q, k, v, dout),
                          want):
        scale = float(np.max(np.abs(np.asarray(w))))
        err = max_err(g, w) / scale
        check(err <= 5e-2, f"flash {at} {name}: max abs err / max |ref| = "
                           f"{err:.2e} <= 5e-2")
    trace_dir = os.path.join(REPO, ".bench_trace", "chip_smoke")
    shutil.rmtree(trace_dir, ignore_errors=True)
    calls = 5
    jax.profiler.start_trace(trace_dir)
    for _ in range(calls):
        out = got(q, k, v, dout)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    views = trace_reduce.views(trace)
    check(len(views) == 1 and len(views[0]["step_s"]) == calls,
          f"the trace holds the {calls} calls of one chip")
    # the backward kernel keeps the name dK/dV had (PERF.md section 7)
    for kernel, took in seconds_a_step(views[0], (
            "p1t_flash_attention_fwd",
            "p1t_flash_attention_bwd_dkv")).items():
        check(took > 0, f"{kernel} is in the trace")
        print(f"chip_smoke: {kernel} {at}: {1e3 * took:.3f} ms a call "
              "(smoke reading, not a metric)", flush=True)


def in_blocks_reference(window=None, rows=512):
    """-> ``plain(q, k, v)``: causal grouped-query attention on [1, s, H,
    d] in float32, one block of ``rows`` queries of every head at a time
    against every key under a dense mask made from the two positions
    (key ``j`` up to query ``i`` and, under a ``window``, fewer than
    ``window`` behind it); not from ``mask_rules``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def plain(q, k, v):
        (_, seq, heads, dim), kv_heads = q.shape, k.shape[2]
        qg = q[0].astype(f32).reshape(seq // rows, rows, kv_heads,
                                      heads // kv_heads, dim)
        kf, vf = k[0].astype(f32), v[0].astype(f32)

        @jax.checkpoint
        def some(args):
            qb, at = args
            scores = jnp.einsum("qngd,knd->ngqk", qb, kf) / dim ** 0.5
            behind = at[:, None] - jnp.arange(seq)[None]
            seen = (behind >= 0) & ((behind < window) if window else True)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("ngqk,knd->qngd", probs, vf)
        out = jax.lax.map(some, (qg, jnp.arange(seq).reshape(-1, rows)))
        return out.reshape(1, seq, heads, dim)
    return plain


def vjp_of(fn):
    """``fn(*args[:-1])`` and its pull-back of ``args[-1]``, jitted."""
    import jax

    def f(*args):
        out, pull = jax.vjp(fn, *args[:-1])
        return (out,) + pull(args[-1].astype(out.dtype))
    return jax.jit(f)


def kernels_against_blocks(at, q, k, v, dout, window=None):
    """The two blockwise kernels (causal, or the sliding window's rule)
    on bf16 operands against :func:`in_blocks_reference` in float32: out
    and the three gradients to 5e-2 of the reference's largest value.
    -> the jitted forward + backward of the kernels."""
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.ops.pallas import flash_attention
    from paddle1_tpu.ops.pallas.mask_rules import SlidingWindow
    how = (dict(causal=True) if window is None
           else dict(mask=SlidingWindow(window)))
    got = vjp_of(lambda q, k, v: flash_attention.flash_attention(
        q, k, v, **how))
    with jax.default_matmul_precision("highest"):
        want = vjp_of(in_blocks_reference(window))(
            q, k, v, dout.astype(jnp.float32))
    for name, g, w in zip(("out", "dq", "dk", "dv"), got(q, k, v, dout),
                          want):
        err = max_err(g, w) / float(np.max(np.abs(np.asarray(w))))
        check(err <= 5e-2, f"flash {at} {name}: max abs err / max |ref| = "
                           f"{err:.2e} <= 5e-2")
    return got


def lfm2_phase(seq=16384, heads=32, kv_heads=8, dim=64, channels=2048,
               taps=3):
    """LFM2-24B-A2B's two operators at its size: the blockwise kernels
    under the causal rule with 32 / 8 heads at head width 64 ([1, 16384,
    32 / 8, 64] bf16: no multiple of the 128-lane tile, so q, k, dq and dk
    take the transposed layout) against a float32 reference a block of
    queries at a time, and the two gated short-convolution kernels ([1,
    16384, 3 x 2048] bf16) against the shifted sums in float32."""
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.nn.functional import short_conv as op
    from paddle1_tpu.ops.pallas import short_conv as kernels
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.key(38), 7)
    q = jax.random.normal(keys[0], (1, seq, heads, dim), bf16)
    k = jax.random.normal(keys[1], (1, seq, kv_heads, dim), bf16)
    v = jax.random.normal(keys[2], (1, seq, kv_heads, dim), bf16)
    dout = jax.random.normal(keys[3], (1, seq, heads, dim), bf16)
    kernels_against_blocks(f"[1, {seq}, {heads}/{kv_heads}, {dim}] causal",
                           q, k, v, dout)

    bcx = jax.random.normal(keys[4], (1, seq, 3 * channels), bf16)
    w = (0.5 * jax.random.normal(keys[5], (channels, taps))).astype(bf16)
    g = jax.random.normal(keys[6], (1, seq, channels), bf16)
    at = f"[1, {seq}, 3 x {channels}] {taps} taps"
    check(kernels.supported(bcx.shape, taps) and op._use_kernels(bcx, w),
          f"gated_short_conv {at} takes the kernels")

    def shifted_sums(bcx, w, g):
        """The op's XLA form, in float32."""
        b, c, x = op._parts(bcx)
        y = op._taps_sum(b * x, w, op._earlier)
        dy = g.astype(f32) * c
        ds = op._taps_sum(dy, w, op._later)
        dw = jnp.stack([jnp.sum(dy * op._earlier(b * x, taps - 1 - j), (0, 1))
                        for j in range(taps)], 1)
        return c * y, jnp.concatenate([ds * x, g.astype(f32) * y, ds * b],
                                      -1), dw
    want = jax.jit(shifted_sums)(bcx, w, g)
    fwd, bwd = jax.jit(kernels.forward), jax.jit(kernels.backward)
    have = (fwd(bcx, w),) + bwd(bcx, w, g)
    for name, a, b in zip(("out", "d bcx", "d taps"), have, want):
        err = max_err(a, b) / float(np.max(np.abs(np.asarray(b))))
        check(err <= 2e-2, f"gated_short_conv {at} {name}: max abs err / "
                           f"max |ref| = {err:.2e} <= 2e-2")
    for name, fn, args in (("p1t_gated_short_conv_fwd", fwd, (bcx, w)),
                           ("p1t_gated_short_conv_bwd", bwd, (bcx, w, g))):
        t = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        print(f"chip_smoke: {name} {at}: "
              f"{100 * (time.perf_counter() - t):.3f} ms a call (smoke "
              "reading on the host's clock, not a metric)", flush=True)


def sliding_window_phase(seq=16384, heads=28, kv_heads=4, dim=128,
                         window=4096, causal_heads=None):
    """The blockwise kernels under the sliding window's rule, a band of
    ``window`` keys under the diagonal, with query heads in groups at a
    model's size against a float32 dense band in blocks, then the causal
    rule (with ``causal_heads`` query heads where a model's global layers
    have a count of their own); a call's forward + backward of each on
    the host's clock. The defaults are SmallThinker-21BA3B's ([1, 16384,
    28 / 4, 128] bf16, groups of 7, 4,096 keys)."""
    import jax
    import jax.numpy as jnp
    bf16 = jnp.bfloat16
    for n, w in ((heads, window), (causal_heads or heads, None)):
        keys = jax.random.split(jax.random.key(43), 4)
        q, k, v, dout = (jax.random.normal(kk, (1, seq, h, dim), bf16)
                         for kk, h in zip(keys, (n, kv_heads, kv_heads, n)))
        at = f"[1, {seq}, {n}/{kv_heads}, {dim}] " + (
            f"window {w}" if w else "causal")
        got = kernels_against_blocks(at, q, k, v, dout, w)
        t = time.perf_counter()
        for _ in range(5):
            out = got(q, k, v, dout)
        jax.block_until_ready(out)
        print(f"chip_smoke: flash forward + backward {at}: "
              f"{200 * (time.perf_counter() - t):.3f} ms a call (smoke "
              "reading on the host's clock, not a metric)", flush=True)


def partial_rotary_phase(seq=16384, heads=48, dim=128):
    """``F.rotary_embedding`` over half a head under YaRN's table and its
    factor at Laguna-XS.2's size ([1, 16384, 48, 128] bf16), result and
    gradient against the slices and the concatenation in float32."""
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.autograd import engine as ae
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.nn import functional as F
    table = F.yarn_frequencies(dim // 2, 500000.0, 64.0, 4096, 64.0, 1.0)
    scale = 1.4158883083359672
    x, g = (jax.random.normal(k, (1, seq, heads, dim), jnp.bfloat16)
            for k in jax.random.split(jax.random.key(47)))

    def op(x):
        with ae.no_grad():
            return F.rotary_embedding(Tensor(x), frequencies=table,
                                      scale=scale).data

    def plain(x):
        half = dim // 4
        angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] \
            * jnp.asarray(table, jnp.float32)
        cos, sin = scale * jnp.cos(angle), scale * jnp.sin(angle)
        a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                               -1)
    at = f"[1, {seq}, {heads}, {dim}] span {dim // 2}, YaRN"
    got, want = vjp_of(op)(x, g), vjp_of(plain)(
        x.astype(jnp.float32), g.astype(jnp.float32))
    for name, a, b in zip(("out", "dx"), got, want):
        err = max_err(a, b) / float(np.max(np.abs(np.asarray(b))))
        check(err <= 1e-2, f"rotary {at} {name}: max abs err / max |ref| = "
                           f"{err:.2e} <= 1e-2")


def experts_phase(tokens=4096, hidden=512, width=256):
    """``nn.RoutedExperts`` with more held picks than its grouped products
    have rows: every token picks the same six held experts, so five
    eighths of the picks take the path that runs each held expert over
    all tokens. Output and gradients against the plain sum over the held
    experts."""
    import jax
    import jax.numpy as jnp
    import paddle1_tpu as paddle
    from paddle1_tpu import nn
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.nn import layer_moe
    bf16, f32 = jnp.bfloat16, jnp.float32
    experts, held, top_k = 128, 16, 6

    paddle.seed(0)
    layer = nn.RoutedExperts(hidden, width, experts, top_k, held=(0, held),
                             routed_scaling_factor=2.448)
    for p in (layer.gate_up_proj, layer.down_proj):
        p.data = p.data.astype(bf16)
    # a selection bias that puts every token on the first six experts
    layer.e_score_correction_bias.data = jnp.zeros(
        experts, f32).at[:top_k].set(5.0)
    x = jax.random.normal(jax.random.key(1), (tokens, hidden), bf16)

    def plain(x, gate_up, down):
        weights, chosen = layer_moe.route(
            x, layer.router.data, layer.e_score_correction_bias.data,
            top_k, 2.448)
        y = jnp.zeros(x.shape, f32)
        for e in range(held):
            mine = jnp.sum(jnp.where(chosen == e, weights, 0.), -1)
            both = x.astype(f32) @ gate_up[e].astype(f32)
            half = both.shape[-1] // 2
            y = y + mine[:, None] * ((jax.nn.silu(both[:, :half])
                                      * both[:, half:]) @ down[e].astype(f32))
        return y

    rows = layer_moe.capacity_rows(tokens, top_k, held, experts)
    check(rows < tokens * top_k,
          f"routed experts [{tokens},{hidden}]: {tokens * top_k} held "
          f"picks against {rows} rows of the grouped products")
    with jax.default_matmul_precision("highest"):
        want_y = jax.jit(plain)(x, layer.gate_up_proj.data,
                                layer.down_proj.data)
        want_dx, want_dg = jax.jit(jax.grad(
            lambda *a: plain(*a).sum(), (0, 1)))(
                x, layer.gate_up_proj.data, layer.down_proj.data)
    xt = Tensor(x, stop_gradient=False)
    y = layer(xt)
    y.astype("float32").sum().backward()
    for name, g, w in (("output", y.data, want_y),
                       ("dx", xt.grad.data, want_dx),
                       ("d gate_up", layer.gate_up_proj.grad.data, want_dg)):
        scale = float(np.max(np.abs(np.asarray(w, np.float32))))
        err = max_err(g, w) / scale
        check(err <= 5e-2, f"routed experts, late picks, {name}: max abs "
                           f"err / max |ref| = {err:.2e} <= 5e-2")

    # what the layer counted of that forward, and what an engine over it
    # has after two compiled steps (it starts from the layer's own
    # counts): every pick is a held one, the grouped products have
    # ``rows`` rows and the rest are late
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    held_picks = tokens * top_k
    want = {"held_picks": held_picks, "late_picks": held_picks - rows,
            "late_steps": 1, "steps": 1}
    eager = layer.read_load(np.asarray(layer.expert_load.data))
    engine = ParallelEngine(
        layer, paddle.optimizer.SGD(learning_rate=0.0,
                                    parameters=layer.parameters()),
        lambda m, b: m(Tensor(b["x"])).astype("float32").sum(),
        mesh=build_mesh(devices=jax.devices()[:1]))
    for _ in range(2):
        engine.step({"x": x})
    compiled = engine.expert_load()[""]
    for name, load, n in (("eager", eager, 1),
                          ("and two compiled steps", compiled, 3)):
        got = {k: load[k] for k in want}
        check(got == {k: n * v for k, v in want.items()}
              and sum(load["rows"]) == n * rows
              and load["capacity_rows"] == rows,
              f"routed experts, expert_load(), {name}: {got}, "
              f"{sum(load['rows'])} rows within the capacity of {rows}")


# -- four chips -------------------------------------------------------------

def grouped_products_phase(rows=9216, groups=8, hidden=2688, width=1856):
    """The expert layer's grouped products at Nemotron 3 Nano's two
    shapes, where XLA's own grouped kernel tiles both widths at 128: the
    repo's three kernels (``ops/pallas/grouped_matmul.py``) against
    ``lax.ragged_dot`` and its transposes on the rows that hold a pick,
    ms a call of each at a third of the rows held and at all of them,
    and the arm ``layer_moe.grouped_matmul`` takes by the shape."""
    import jax
    import jax.numpy as jnp
    from paddle1_tpu.nn import layer_moe
    from paddle1_tpu.obs import registry
    from paddle1_tpu.ops.pallas import grouped_matmul as kernels
    bf16 = jnp.bfloat16
    share = np.random.default_rng(0).dirichlet(np.full(groups, 40.0))
    fills = {held: jnp.asarray(np.diff(np.floor(
        np.concatenate([[0], np.cumsum(share)]) * held)).astype(np.int32))
        for held in (rows // 3, rows)}

    def forms(product):
        def all_three(x, w, d, sizes):
            y, vjp = jax.vjp(lambda x, w: product(x, w, sizes), x, w)
            return (y,) + vjp(d)
        return [jax.jit(lambda *a, i=i: all_three(*a)[i]) for i in range(3)]
    for k, n in ((hidden, width), (width, hidden)):
        at = f"[{rows}, {k}] x [{groups}, {k}, {n}]"
        keys = jax.random.split(jax.random.key(k), 3)
        x = jax.random.normal(keys[0], (rows, k), bf16)
        w = (0.02 * jax.random.normal(keys[1], (groups, k, n))).astype(bf16)
        d = jax.random.normal(keys[2], (rows, n), bf16)
        check(kernels.supported(x, w), f"grouped product {at} takes the "
                                       "repo's kernels")
        ms, rows_held = {}, {}
        for arm, fns in (("xla", forms(jax.lax.ragged_dot)),
                         ("kernel", forms(kernels.grouped_matmul))):
            for held, sizes in fills.items():
                for name, fn in zip(("product", "dx", "dw"), fns):
                    out = jax.block_until_ready(fn(x, w, d, sizes))
                    t = time.perf_counter()
                    for _ in range(10):
                        out = fn(x, w, d, sizes)
                    jax.block_until_ready(out)
                    ms[arm, held, name] = 100 * (time.perf_counter() - t)
                    if held == rows // 3:
                        rows_held[arm, name] = np.asarray(
                            out if name == "dw" else out[:held], np.float32)
        for name in ("product", "dx", "dw"):
            ref = rows_held["xla", name]
            err = (max_err(rows_held["kernel", name], ref)
                   / float(np.abs(ref).max()))
            check(err <= 2e-2, f"grouped {name} {at}: max abs err / max "
                               f"|ragged_dot's| = {err:.2e} <= 2e-2")
            print(f"chip_smoke: grouped {name} {at}: " + ", ".join(
                f"{held} held rows {ms['kernel', held, name]:.3f} ms a call "
                f"(XLA's own {ms['xla', held, name]:.3f})"
                for held in fills) + " (smoke readings on the host's "
                "clock, not a metric)", flush=True)
        registry.reset_process_registry()
        jax.make_jaxpr(layer_moe.grouped_matmul)(x, w, fills[rows])
        check('moe_grouped_matmul_arm_total{arm="kernel"} 1'
              in registry.render_process_groups(),
              f"layer_moe.grouped_matmul {at} counts the kernel arm")


def ssd_scan_phase(seq=8192, heads=64, width=64, groups=8, state=128):
    """Nemotron 3 Nano's two mixers at its size: the two state-space scan
    kernels ([1, 8192, 64, 64] bf16 over 8 groups of state 128) against
    the chunked composition in float32, each kernel's ms a call and its
    share of the HBM roofline by the op's own closed form of its bytes;
    and the blockwise attention kernels under the causal rule with 16
    query heads a key/value head ([1, 8192, 32 / 2, 128])."""
    import jax
    import jax.numpy as jnp
    from benchmarks import peaks
    from paddle1_tpu.nn.functional import ssd as op
    from paddle1_tpu.ops.pallas import ssd_scan as kernels
    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.key(50), 10)
    x = (0.5 * jax.random.normal(keys[0], (1, seq, heads, width))).astype(bf16)
    d = jax.nn.softplus(jax.random.normal(keys[1], (1, seq, heads)) - 4.0)
    a = -jnp.arange(1, heads + 1, dtype=f32)
    b, c = ((0.3 * jax.random.normal(k, (1, seq, groups, state))).astype(bf16)
            for k in keys[2:4])
    skip = jnp.ones((heads,), f32)
    g = jax.random.normal(keys[4], x.shape, bf16)
    at = f"[1, {seq}, {heads}, {width}] x {groups} groups of {state}"
    check(kernels.supported(x.shape, b.shape, kernels.CHUNK)
          and op._use_kernels(x, b, kernels.CHUNK),
          f"ssd_scan {at} takes the kernels")
    fwd = jax.jit(lambda *o: kernels.forward(*o, keep_states=True))
    bwd = jax.jit(kernels.backward)
    y, starts = fwd(x, d, a, b, c, skip)
    have = (y,) + bwd(x, d, a, b, c, skip, starts, g)
    with jax.default_matmul_precision("highest"):
        want = vjp_of(lambda *o: op.chunked(*o, kernels.CHUNK))(
            x.astype(f32), d, a, b.astype(f32), c.astype(f32), skip,
            g.astype(f32))
    for name, got, ref in zip(("y", "dx", "dd", "dA", "dB", "dC", "dD"),
                              have, want):
        err = max_err(got, ref) / float(np.max(np.abs(np.asarray(ref))))
        check(err <= 5e-2, f"ssd_scan {at} {name}: max abs err / max |ref| "
                           f"= {err:.2e} <= 5e-2")
    peak = peaks.of(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    moved = op.traffic_bytes(x.shape, groups, state, 2)
    for name, which, fn, args in (
            ("p1t_ssd_fwd", "forward", fwd, (x, d, a, b, c, skip)),
            ("p1t_ssd_bwd", "backward", bwd,
             (x, d, a, b, c, skip, starts, g))):
        t = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        ms = 100 * (time.perf_counter() - t)
        print(f"chip_smoke: {name} {at}: {ms:.3f} ms a call with XLA's "
              f"layout of the values a head, {moved[which] / 1e6:.1f} MB "
              f"the op has to move, {100 * moved[which] / (1e-3 * ms) / peak:.1f}"
              "% of the HBM roofline (smoke reading on the host's clock, "
              "not a metric)", flush=True)

    q = jax.random.normal(keys[5], (1, seq, 32, 128), bf16)
    k, v = (jax.random.normal(key, (1, seq, 2, 128), bf16)
            for key in keys[6:8])
    dout = jax.random.normal(keys[8], (1, seq, 32, 128), bf16)
    kernels_against_blocks(f"[1, {seq}, 32/2, 128] causal", q, k, v, dout)


def four_chip_phase(devs):
    """dp=2 x mp=2 with Megatron sharding and zero_stage=2 over four
    chips, then the same model, seed and batch on one."""
    import jax
    four = devs[:4]
    engine, batch = build_trainer(four, {"dp": 2, "mp": 2}, megatron=True,
                                  zero_stage=2)
    sharded = [k for k, s in engine.param_specs.items()
               if any(ax is not None for ax in s)]
    check(len(sharded) > 0 and all(
        len(engine.params[k].sharding.device_set) == 4
        and not engine.params[k].sharding.is_fully_replicated
        for k in sharded),
        f"{len(sharded)} Megatron-sharded params each span four devices")
    losses4 = [timed_step(engine, batch)[0] for _ in range(3)]
    print(f"chip_smoke: four-chip losses {losses4}")
    check(engine.cache_stats()["misses"] == 1,
          "the four-chip step compiled once")
    text = compiled_step_text(engine, batch)
    check("all-reduce" in text,
          "the compiled four-chip step holds an all-reduce")
    print("chip_smoke: four-chip step holds a tpu_custom_call: "
          f"{'tpu_custom_call' in text}")
    peaks4 = peak_bytes(four)
    print(f"chip_smoke: four-chip peak bytes per device {peaks4}")
    del engine, text
    gc.collect()

    engine, batch = build_trainer(devs[:1], {"dp": 1})
    losses1 = [timed_step(engine, batch)[0] for _ in range(3)]
    print(f"chip_smoke: one-chip losses  {losses1}")
    peak1 = peak_bytes(devs[:1])[0]
    print(f"chip_smoke: one-chip peak bytes {peak1}")
    check(bool(np.all(np.isfinite(losses4 + losses1))),
          "all losses are finite")
    check(np.allclose(losses4, losses1, rtol=2e-2, atol=0),
          "per-step losses on four chips agree with one chip, rtol 2e-2")
    check(max(peaks4) < peak1,
          f"per-device peak on four chips {max(peaks4)} is below the "
          f"one-chip peak {peak1}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip hybrid-parallel path and its "
                         "one-chip comparison, and no other phase")
    args = ap.parse_args()

    devs = tpu_devices(4 if args.four_chips else 1)
    import jax
    from paddle1_tpu.core import flags as core_flags
    from paddle1_tpu.core import native
    core_flags.maybe_enable_compilation_cache(
        default_dir=os.path.join(REPO, ".jax_cache"))
    print(f"chip_smoke: device {devs[0].device_kind!r} x{len(devs)}, "
          f"jax {jax.__version__}")
    print(f"chip_smoke: compilation cache at "
          f"{jax.config.jax_compilation_cache_dir}")
    print(f"chip_smoke: g++ at {shutil.which('g++')}; native host runtime "
          f"{native.origin()}", flush=True)

    if args.four_chips:
        four_chip_phase(devs)
        count = 4
    else:
        trainer_phase(devs[0])
        kernel_phase()
        block_diffusion_phase()
        lfm2_phase()
        sliding_window_phase()
        # Laguna-XS.2: a window of one query block with heads in groups
        # of 8, the causal rule in groups of 6, rotary over half a head
        sliding_window_phase(heads=64, kv_heads=8, window=512,
                             causal_heads=48)
        partial_rotary_phase()
        experts_phase()
        grouped_products_phase()
        ssd_scan_phase()
        count = len(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
