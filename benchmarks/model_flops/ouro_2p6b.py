"""Ouro pre-training step: every matrix product of the forward pass, times
three (one product forward, two backward). A layer's weights are used
``total_ut_steps`` times a step and counted each time; of the attention
scores the causal half (a query with the keys up to itself) is counted, once;
what recomputation runs again is not counted."""

from . import attention_kernels


def attention_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call a layer a loop
    step: the causal half's pairs alone (``attention_kernels``)."""
    calls = (cfg["total_ut_steps"] * cfg["num_hidden_layers"]
             * env["batch"])
    return attention_kernels.flops(
        calls * (env["seq"] * (env["seq"] + 1) // 2),
        cfg["num_attention_heads"], cfg["head_dim"])


def forward_matmul_flops(cfg, env):
    tokens, s = env["batch"] * env["seq"], env["seq"]
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    a = cfg["num_attention_heads"] * cfg["head_dim"]
    layer = (2 * tokens * (4 * h * a + 3 * h * i)       # q,k,v,out + SwiGLU
             + 2 * 2 * env["batch"] * (s * (s + 1) // 2) * a)   # QK^T, PV
    exit_head = 2 * tokens * h * v + 2 * tokens * h     # head, gate
    return cfg["total_ut_steps"] * (cfg["num_hidden_layers"] * layer
                                    + exit_head)


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
