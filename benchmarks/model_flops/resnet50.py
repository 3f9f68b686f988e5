"""ResNet-50 training step from the layer shapes: each convolution costs
2 * K*K*Cin*Cout*Hout*Wout a sample forward, the same again for its weight
gradient and for its input gradient; the first convolution needs no input
gradient. At 224^2 and 1000 classes the forward pass is 4.09 GMACs =
8.18 GFLOPs a sample: benches.py's "4.1e9 FLOPs/sample (2xMACs)" was the
MAC count."""


def conv_layers(cfg):
    """(K, Cin, Cout, Hout) of every convolution, in forward order."""
    size = cfg["image"] // 2                    # 7x7 stride 2
    layers = [(7, cfg["in_channels"], cfg["stem_channels"], size)]
    size //= 2                                  # 3x3 max-pool stride 2
    inp = cfg["stem_channels"]
    for s, (n, width) in enumerate(zip(cfg["blocks"], cfg["widths"])):
        out = width * cfg["expansion"]
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            layers.append((1, inp, width, size))
            size //= stride                     # v1.5: stride on the 3x3
            layers.append((3, width, width, size))
            layers.append((1, width, out, size))
            if b == 0:
                layers.append((1, inp, out, size))
            inp = out
    return layers


def forward_macs(cfg, env):
    convs = conv_layers(cfg)
    per_sample = sum(k * k * ci * co * hw * hw for k, ci, co, hw in convs)
    per_sample += cfg["widths"][-1] * cfg["expansion"] * cfg["num_classes"]
    return env["batch"] * per_sample


def train_step_flops(cfg, env):
    k, ci, co, hw = conv_layers(cfg)[0]
    first = env["batch"] * k * k * ci * co * hw * hw
    return 2.0 * (3 * forward_macs(cfg, env) - first)
