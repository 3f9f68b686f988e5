"""BERT pre-training step: every matrix product of the forward pass, times
three (one product forward, two backward)."""


def forward_matmul_flops(cfg, env):
    b, s = env["batch"], env["seq"]
    h, i, l, v = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["vocab_size"])
    m = b * env.get("mlm_per_seq", s)          # rows the MLM head sees
    per_layer = (2 * b * s * (4 * h * h + 2 * h * i)   # q,k,v,out + FFN
                 + 2 * 2 * b * s * s * h)              # QK^T and PV
    heads = (2 * b * h * h + 2 * b * h * 2             # pooler, NSP
             + 2 * m * h * h + 2 * m * h * v)          # MLM transform, decoder
    return l * per_layer + heads


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
