"""ResNet-50 training through ``ParallelEngine``, as ``benches.py`` builds
it: ``vision.models.resnet50``, softmax cross-entropy, ``Momentum``."""

from . import load_weights, make_optimizer

_BN = {"weight": "_g", "bias": "_b", "_mean": "_mean", "_variance": "_var"}


def leaves(cfg):
    out = [("conv1.weight", "conv1_w", None),
           ("fc.weight", "fc_w", None), ("fc.bias", "fc_b", None)]
    out += [(f"bn1.{p}", "bn1" + r, None) for p, r in _BN.items()]
    for s, n in enumerate(cfg["blocks"]):
        for b in range(n):
            prog, ref = f"layer{s + 1}.{b}.", f"s{s}b{b}_"
            for i in (1, 2, 3):
                out.append((f"{prog}conv{i}.weight", f"{ref}conv{i}_w", None))
                out += [(f"{prog}bn{i}.{p}", f"{ref}bn{i}{r}", None)
                        for p, r in _BN.items()]
            if b == 0:
                out.append((prog + "downsample.0.weight", ref + "down_w",
                            None))
                out += [(f"{prog}downsample.1.{p}", f"{ref}downbn{r}", None)
                        for p, r in _BN.items()]
    return out


def build(cfg, env, weights, devices):
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.vision.models.resnet import (BottleneckBlock, ResNet,
                                                  resnet50)
    if cfg["blocks"] == [3, 4, 6, 3]:
        model = resnet50(num_classes=cfg["num_classes"])
    else:
        model = _shallow(ResNet, BottleneckBlock, cfg)  # CPU rehearsal only
    load_weights(model, weights)
    opt, first_grad = make_optimizer(paddle, cfg["optimizer"],
                                     model.parameters())

    def loss_fn(m, b):
        return paddle.nn.functional.cross_entropy(m(Tensor(b["x"])),
                                                  Tensor(b["y"]))

    engine = ParallelEngine(
        model, opt, loss_fn,
        mesh=build_mesh(dp=len(devices), devices=list(devices)),
        amp_dtype=cfg["precision"]["compute"]
        if cfg["precision"]["compute"] != "float32" else None)
    return {"engine": engine, "model": model, "first_grad": first_grad}


def _shallow(ResNet, BottleneckBlock, cfg):
    """The same class with fewer bottlenecks a stage (CPU rehearsal)."""
    class Shallow(ResNet):
        _cfg = {50: (BottleneckBlock, list(cfg["blocks"]))}
    return Shallow(depth=50, num_classes=cfg["num_classes"])
