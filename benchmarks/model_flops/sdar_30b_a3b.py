"""SDAR block-diffusion training step on one chip's share: every matrix
product of the forward pass, times three (one product forward, two
backward). The stack runs over the doubled row, ``2 * seq`` positions a
batch row; of the attention scores the pairs block diffusion's mask lets
through are counted, ``seq^2 + seq * block_length`` a row and head, once,
and pairs, not tiles: what a kernel computes of a crossed tile's hidden
part is not the model's work. The routed part is an expectation: under
even routing a position's ``num_experts_per_tok`` picks land on this
chip's ``num_experts`` of ``num_experts * expert_parallel`` experts that
often. The head runs over the ``seq`` noisy rows. What recomputation runs
again is not counted."""

from . import attention_kernels


def picks_here_a_token(cfg):
    return cfg["num_experts_per_tok"] / cfg["expert_parallel"]


def visible_pairs(cfg, env):
    """(query, key) pairs a batch row of ``seq`` ids shows: the noisy
    blocks among themselves, the noisy positions to the clean blocks
    before theirs, the clean ones to the clean blocks up to theirs."""
    return env["seq"] * (env["seq"] + cfg["block_length"])


def attention_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call a layer: the
    visible pairs' alone (``attention_kernels``: 2 products forward, 5 in
    the one backward kernel)."""
    calls = cfg["num_hidden_layers"] * env["batch"]
    return attention_kernels.flops(calls * visible_pairs(cfg, env),
                                   cfg["num_attention_heads"],
                                   cfg["head_dim"])


def forward_matmul_flops(cfg, env):
    rows = env["batch"] * env["seq"]
    positions = 2 * rows
    h, heads, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
    width = cfg["moe_intermediate_size"]
    routed_total = cfg["num_experts"] * cfg["expert_parallel"]
    layer = (
        2 * positions * (2 * h * heads * d + 2 * h * kv * d)
        + attention_kernel_flops(cfg, env)[attention_kernels.FORWARD]
        / cfg["num_hidden_layers"]
        + 2 * positions * (h * routed_total
                           + 3 * h * width * picks_here_a_token(cfg)))
    return cfg["num_hidden_layers"] * layer + 2 * rows * h * cfg["vocab_size"]


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
