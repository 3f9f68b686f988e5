"""LFM2-24B-A2B pre-training step on one chip's share: every matrix
product of the forward pass, times three (one product forward, two
backward). Two kinds of layer: a gated short-convolution operator is its
two projections (the three taps a channel are elementwise work, left out
as every other such), an attention operator its four projections and, of
its scores, the causal half (a query with the keys up to itself), once.
The routed part is an expectation: under even routing a token's
``num_experts_per_tok`` picks land on this chip's ``num_experts`` of
``num_experts * expert_parallel`` experts that often. The router's product
over all the experts is counted, and the tied head's over this chip's
rows of the embedding; what recomputation runs again is not.

Beside the FLOPs, the bytes of the one op that is bound by memory:
:func:`short_conv_bytes`, what the ``gated_short_conv`` calls of a step
have to read and write: over the op's device time (scope
``gated_short_conv``) and the chip's HBM peak (``peaks.json``) they give
the op's share of its roofline (``short_conv_op_hbm_roofline``, reducer
``op_hbm_pct``). The program counts the same closed form where it traces
the op (``short_conv_bytes_total{pass}``,
``paddle1_tpu/nn/functional/short_conv.py::traffic_bytes``): written
twice, once on either side, and ``test_lfm2_yardstick.py`` holds the two
equal on a traced step."""

from . import attention_kernels


def layer_kinds(cfg):
    first = cfg.get("pipeline_first_layer", 0)
    return list(cfg["layer_types"][first:first + cfg["num_hidden_layers"]])


def head_dim(cfg):
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def picks_here_a_token(cfg):
    return cfg["num_experts_per_tok"] / cfg["expert_parallel"]


def causal_pairs(env):
    """(query, key) pairs a row of ``seq`` positions shows."""
    return env["seq"] * (env["seq"] + 1) // 2


def attention_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call an attention
    layer: the visible pairs' alone (``attention_kernels``)."""
    calls = layer_kinds(cfg).count("full_attention") * env["batch"]
    return attention_kernels.flops(calls * causal_pairs(env),
                                   cfg["num_attention_heads"], head_dim(cfg))


def short_conv_bytes(cfg, env, itemsize=2):
    """{"forward", "backward"}: bytes the ``gated_short_conv`` calls of
    one step must move, a call a convolution layer, operands ``itemsize``
    wide (bfloat16 under the configuration's AMP). Forward: the two gates
    and the convolution's input read, the output written, the taps read.
    Backward: the three and the output's gradient read, three gradients
    written, the taps read and their gradient written. The forward a
    recomputed segment runs again is no byte the op has to move."""
    whole = env["batch"] * env["seq"] * cfg["hidden_size"] * itemsize
    taps = cfg["hidden_size"] * cfg["conv_L_cache"] * itemsize
    calls = layer_kinds(cfg).count("conv")
    return {"forward": calls * (4 * whole + taps),
            "backward": calls * (7 * whole + 2 * taps)}


def forward_matmul_flops(cfg, env):
    tokens = env["batch"] * env["seq"]
    h, heads, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], head_dim(cfg))
    conv = 2 * tokens * (h * 3 * h + h * h)
    attention = (2 * tokens * (2 * h * heads * d + 2 * h * kv * d)
                 + 2 * env["batch"] * causal_pairs(env) * heads * 2 * d)
    dense = 2 * tokens * 3 * h * cfg["intermediate_size"]
    routed_total = cfg["num_experts"] * cfg["expert_parallel"]
    expert = 2 * tokens * (
        h * routed_total
        + 3 * h * cfg["moe_intermediate_size"] * picks_here_a_token(cfg))
    kinds = layer_kinds(cfg)
    n_dense = min(cfg["num_dense_layers"], len(kinds))
    return (kinds.count("conv") * conv
            + kinds.count("full_attention") * attention
            + n_dense * dense + (len(kinds) - n_dense) * expert
            + 2 * tokens * h * cfg["vocab_size"])


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
