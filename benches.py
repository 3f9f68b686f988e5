"""Benchmark configs 1/2/4/5 from BASELINE.md, invoked by
``bench.py --config <name>``; config 3 (bert_base, the driver default)
lives in bench.py itself.

Every config follows bench.py's timing: slope timing with a
host-readback barrier (not yet calibrated on the v5e — see bench.py),
median slope across trials, and ``mfu <= 1.0`` asserts wherever an MFU is
computed. The reference publishes no numeric
baselines (BASELINE.md), so ``vs_baseline`` is MFU/0.40 where an MFU
target applies and 1.0 (self-referential) for the throughput-only
configs.

Analog of the reference's config-driven op benchmark harness
(/root/reference/paddle/fluid/operators/benchmark/op_tester.cc — there a
config file picks the op; here --config picks the model-level workload).
"""

import numpy as np

from bench import (_assert_sane_mfu, _emit, _peak_flops, _read_back,
                   _timed_steps)

CONFIGS = {}


def config(name):
    def deco(fn):
        CONFIGS[name] = fn
        return fn
    return deco


def run_config(name: str, on_tpu: bool, batch=None) -> None:
    if name not in CONFIGS:
        raise SystemExit(
            f"unknown bench config {name!r}; available: "
            f"{['bert_base'] + sorted(CONFIGS)}")
    import inspect
    fn = CONFIGS[name]
    if batch is None:
        fn(on_tpu)
        return
    if "batch_override" not in inspect.signature(fn).parameters:
        raise SystemExit(
            f"config {name!r} does not support --batch; it would run at "
            f"its hardcoded batch while reporting yours (honesty "
            f"contract: refuse rather than mislead)")
    fn(on_tpu, batch_override=batch)


@config("mnist_lenet")
def bench_mnist_lenet(on_tpu):
    """BASELINE config 1: eager dygraph LeNet training — exercises the
    tape engine, nn, optimizer end-to-end (throughput, no MFU target)."""
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import to_tensor
    from paddle1_tpu.vision.models.lenet import LeNet

    batch = 64 if on_tpu else 16
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    rng = np.random.default_rng(0)
    # inputs staged to device once (eager mode already dispatches every
    # op by itself; re-uploading the pixels per step would add host->
    # device bandwidth on top — see bench_resnet50_dp)
    xt = to_tensor(rng.standard_normal(
        (batch, 1, 28, 28)).astype(np.float32))
    yt = to_tensor(rng.integers(0, 10, (batch,)).astype(np.int64))

    def step():
        out = model(xt)
        loss = paddle.nn.functional.cross_entropy(out, yt)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    _read_back(step())  # warmup, flushed to completion
    n_steps = 20 if on_tpu else 3
    times, loss = _timed_steps(step, n_steps)
    import statistics
    dt = statistics.median(times)
    _emit("mnist_lenet_eager_samples_per_sec", batch / dt, "samples/s", 1.0,
          {"batch": batch, "steps": n_steps,
           "step_ms_median": round(dt * 1e3, 2),
           "loss": float(loss.numpy()), "mode": "eager"})


@config("resnet50_dp")
def bench_resnet50_dp(on_tpu, batch_override=None):
    """BASELINE config 2: ResNet-50 data-parallel over all local devices
    (compiled engine; GSPMD inserts the grad all-reduce over ICI)."""
    import jax
    import statistics
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.vision.models.resnet import resnet50

    devs = jax.devices()
    img = 224 if on_tpu else 32
    # batch_override is the GLOBAL batch (same meaning as bert_base's
    # --batch); it must divide the device count
    if batch_override is not None and batch_override % len(devs):
        raise SystemExit(f"--batch {batch_override} not divisible by "
                         f"{len(devs)} devices")
    per_dev = (32 if on_tpu else 2) if batch_override is None \
        else batch_override // len(devs)
    batch = per_dev * len(devs)

    model = resnet50()
    # lr kept small: the bench replays ONE batch, where the ImageNet lr
    # schedule diverges; the timing is lr-independent
    opt = paddle.optimizer.Momentum(learning_rate=1e-3, momentum=0.9,
                                    parameters=model.parameters())

    def loss_fn(m, b):
        out = m(Tensor(b["x"]))
        return paddle.nn.functional.cross_entropy(out, Tensor(b["y"]))

    mesh = build_mesh(dp=len(devs), devices=devs)
    engine = ParallelEngine(model, opt, loss_fn, mesh=mesh,
                            amp_dtype="bfloat16" if on_tpu else None)
    rng = np.random.default_rng(0)

    # Batches are PRE-STAGED on device and cycled — measuring the
    # training step, not the host->device upload of a 19.3 MB float32
    # image batch (its cost on the v5e: not measured). Real training
    # feeds from the DataLoader's
    # device-prefetch path (io/dataloader.py), which overlaps uploads
    # with compute; cycling staged batches is the single-chip analog.
    def mk():
        return {"x": rng.standard_normal(
                    (batch, 3, img, img)).astype(np.float32),
                "y": rng.integers(0, 1000, (batch,)).astype(np.int64)}
    staged = [engine.shard_batch(mk()) for _ in range(2)]
    it = {"i": 0}

    def step():
        it["i"] += 1
        return engine.step(staged[it["i"] % len(staged)])

    _read_back(step())  # compile, flushed to completion
    times, loss = _timed_steps(step, 10 if on_tpu else 3)
    dt = statistics.median(times)

    # ResNet-50 @224 fwd ≈ 4.1e9 FLOPs/sample (2×MACs); bwd ≈ 2× fwd
    flops_sample = 4.1e9 * (img / 224.0) ** 2 * 3.0
    mfu = (flops_sample * batch / dt) / (_peak_flops(devs[0]) * len(devs))
    detail = {"batch": batch, "img": img, "devices": len(devs),
              "step_ms_median": round(dt * 1e3, 2), "mfu": round(mfu, 4),
              "amp": "bfloat16" if on_tpu else "none",
              "input": "device-staged (2-batch cycle; see docstring)",
              "loss": float(loss)}
    _assert_sane_mfu(mfu, detail, step_fn=step)
    _emit("resnet50_dp_samples_per_sec", batch / dt, "samples/s",
          mfu / 0.40, detail)


@config("ernie_sharded")
def bench_ernie_sharded(on_tpu):
    """BASELINE config 4: ERNIE-1.5B-class training with ZeRO-2 sharding
    (reduce-scatter over ICI). Published memory math
    (tools/memory_math.py): full depth needs ~28 GiB (f32 masters +
    Adam moments + grads + bf16 copy) — a single 16-GiB v5e cannot hold
    it; ZeRO-2 fits it from 4 chips (~14.4 GiB/chip). On one device
    this measures the LARGEST DEPTH THAT FITS: 10 of 24 layers at full
    width (~12.9 GiB peak) — per-layer compute identical to full scale,
    so full-depth throughput projects as value × (proxy step FLOPs /
    full step FLOPs) with the same MFU; the detail dict carries that
    projection. With >= 4 devices the full depth runs sharded; the
    full-scale sharded compile path is validated on the virtual
    8-device mesh by tests/test_parallel_engine.py, test_sharding_remat
    and __graft_entry__.py."""
    import jax
    import statistics
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (BertForPretraining,
                                         BertPretrainingCriterion,
                                         apply_megatron_sharding,
                                         ernie_1p5b)

    devs = jax.devices()
    n = len(devs)
    # memory math (tools/memory_math.py): 24 layers fit from 4 chips
    # under ZeRO-2; one chip holds at most 10 full-width layers
    layers = 24 if n >= 4 else (10 if on_tpu else 6)
    seq = 512 if on_tpu else 64
    per_dev = 4 if on_tpu else 1
    batch = per_dev * n

    enc = ernie_1p5b(num_hidden_layers=layers,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0,
                     **({} if on_tpu else
                        {"hidden_size": 256, "num_attention_heads": 4,
                         "intermediate_size": 1024, "vocab_size": 1024}))
    model = BertForPretraining(enc)
    crit = BertPretrainingCriterion(enc.vocab_size)
    if n > 1:
        apply_megatron_sharding(model)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(m, b):
        scores, rel = m(Tensor(b["ids"]))
        return crit(scores, rel, Tensor(b["mlm"]), Tensor(b["nsp"]))

    mesh = build_mesh(dp=1, sharding=n, devices=devs)
    engine = ParallelEngine(model, opt, loss_fn, mesh=mesh, zero_stage=2,
                            amp_dtype="bfloat16" if on_tpu else None)
    rng = np.random.default_rng(0)
    v = enc.vocab_size
    b = {"ids": rng.integers(1, v, (batch, seq)).astype(np.int32),
         "mlm": rng.integers(0, v, (batch, seq)).astype(np.int32),
         "nsp": rng.integers(0, 2, (batch,)).astype(np.int32)}

    _read_back(engine.step(b))  # compile, flushed to completion
    times, loss = _timed_steps(lambda: engine.step(b), 10 if on_tpu else 2)
    dt = statistics.median(times)

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    attn = 12 * layers * batch * seq * seq * enc.hidden_size
    flops_step = 6 * n_params * batch * seq + attn
    mfu = (flops_step / dt) / (_peak_flops(devs[0]) * n)
    detail = {"batch": batch, "seq": seq, "layers": layers,
              "params": n_params, "devices": n, "zero_stage": 2,
              "step_ms_median": round(dt * 1e3, 2), "mfu": round(mfu, 4),
              "proxy": layers != 24, "loss": float(loss)}
    if layers != 24 and on_tpu:
        # proxy basis (tools/memory_math.py): same width => same MFU;
        # full-depth samples/s = measured × FLOPs(proxy)/FLOPs(24L).
        # Per-layer param count inlined (NOT imported) so an import
        # problem can never eat the measurement before the JSON emits.
        H, I = enc.hidden_size, enc.intermediate_size
        per_layer = (4 * H * H + 4 * H) + (H * I + I + I * H + H) + 4 * H
        full_n = n_params + (24 - layers) * per_layer
        attn24 = 12 * 24 * batch * seq * seq * H
        flops_full = 6 * full_n * batch * seq + attn24
        detail["proxy_basis"] = ("largest depth fitting 16GiB "
                                 "(tools/memory_math.py)")
        detail["projected_full_depth_samples_per_sec"] = round(
            (batch / dt) * flops_step / flops_full, 2)
    _assert_sane_mfu(mfu, detail,
                     step_fn=lambda: engine.step(b))
    _emit("ernie_1p5b_zero2_samples_per_sec", batch / dt, "samples/s",
          mfu / 0.40, detail)


@config("yolov3_infer")
def bench_yolov3_infer(on_tpu):
    """BASELINE config 5: PP-YOLO-class detection inference — conv stack
    jitted on device; box decode + NMS measured separately (they run
    host-side at deploy time, matching the reference's split)."""
    import jax
    import statistics
    import time
    import paddle1_tpu as paddle
    from paddle1_tpu.autograd import engine as ag
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.vision.models.yolo import yolov3

    batch = 8 if on_tpu else 1
    img = 416 if on_tpu else 128
    model = yolov3(num_classes=80)
    model.eval()
    params = model.functional_state()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 3, img, img)).astype(np.float32)

    import jax.numpy as jnp

    @jax.jit
    def fwd(params, x):
        with ag.no_grad(), model.load_functional_state(params):
            return [o.data for o in model(Tensor(x))]

    # stage the input on device once (a 16.6 MB float32 batch; see
    # bench_resnet50_dp): deploy-time serving keeps a device-resident
    # input buffer the same way
    xd = jnp.asarray(x)
    _read_back(fwd(params, xd))  # compile, flushed
    times, outs = _timed_steps(lambda: fwd(params, xd),
                               20 if on_tpu else 3)
    dt = statistics.median(times)

    img_size = np.tile([[img, img]], (batch, 1)).astype(np.int32)  # [B,2]
    with ag.no_grad():
        # warm pass first: deploy-time serving is steady-state, and the
        # eager decode/NMS ops compile per shape on first touch
        model.postprocess([Tensor(o) for o in outs], Tensor(img_size))
        t0 = time.perf_counter()
        results = model.postprocess([Tensor(o) for o in outs],
                                    Tensor(img_size))
        post_ms = (time.perf_counter() - t0) * 1e3

    _emit("yolov3_infer_images_per_sec", batch / dt, "images/s", 1.0,
          {"batch": batch, "img": img,
           "step_ms_median": round(dt * 1e3, 2),
           "postprocess_ms_per_batch": round(post_ms, 2),
           "detections_img0": int(np.asarray(
               results[0][0].numpy()).shape[0]) if results else 0})


@config("allreduce_busbw")
def bench_allreduce_busbw(on_tpu, batch_override=None):
    """BASELINE primary metric's fleet half: allreduce bus bandwidth.

    Payload sweep of in-graph ``psum`` over every visible device
    (nccl-tests conventions: algbw = per-rank payload / time,
    busbw = algbw * 2(n-1)/n — the wire traffic of a ring). On one
    chip there is no ICI to measure: the run still executes (the
    numbers are the on-device reduction path) but is loudly marked
    ``blocked: single-chip``. On the virtual CPU mesh this smokes the
    full multi-device path; real numbers land whenever multi-chip
    hardware exists."""
    import statistics
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("x",))
    sizes_mb = [1, 4, 16, 64] if on_tpu else [1, 4]
    if batch_override:  # --batch reinterprets as max payload MB
        sizes_mb = [m for m in sizes_mb if m <= batch_override] \
            or [batch_override]
    sweep = []
    for mb in sizes_mb:
        elems = mb * (1 << 20) // 4
        x = jax.device_put(
            jnp.ones((n, elems), jnp.float32),
            NamedSharding(mesh, P("x", None)))

        @jax.jit
        def allreduce(v):
            return shard_map(
                lambda s: jax.lax.psum(s, "x") * (1.0 / n),
                mesh=mesh, in_specs=P("x", None),
                out_specs=P("x", None))(v)

        state = {"x": x}

        def step_fn():
            state["x"] = allreduce(state["x"])  # chained dependency
            return state["x"]

        _read_back(allreduce(x))  # compile outside the timing
        try:
            times, _ = _timed_steps(step_fn, 8 if on_tpu else 4)
            dt = statistics.median(times)
        except AssertionError:
            if n > 1:
                raise
            # single chip: psum over one device is (near) a no-op, so
            # the slope degenerates; time plain calls instead — the run
            # is marked `blocked: single-chip` below regardless
            import time as _time
            best = None
            for _ in range(10):
                t0 = _time.perf_counter()
                _read_back(step_fn())
                best = min(best or 1e9, _time.perf_counter() - t0)
            dt = best
        payload = elems * 4  # bytes per rank
        algbw = payload / dt
        busbw = algbw * (2 * (n - 1) / n)
        sweep.append({"payload_mb": mb,
                      "time_us": round(dt * 1e6, 1),
                      "algbw_gbps": round(algbw / 1e9, 3),
                      "busbw_gbps": round(busbw / 1e9, 3)})
    best = max(s["busbw_gbps"] for s in sweep)
    detail = {"device": str(devs[0].device_kind
                            if hasattr(devs[0], "device_kind")
                            else devs[0].platform),
              "n_devices": n, "sweep": sweep,
              "convention": "nccl-tests: busbw = algbw * 2(n-1)/n"}
    if n == 1:
        detail["blocked"] = ("single-chip: no ICI to measure — busbw "
                             "is 0 by the ring formula; sweep times "
                             "are the on-device reduction path only")
    _emit("fleet_allreduce_busbw", best, "GB/s", 1.0, detail)
