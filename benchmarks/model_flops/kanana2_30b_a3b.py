"""Kanana-2 pre-training step on one chip's share: every matrix product of
the forward pass, times three (one product forward, two backward). Keys
and values have widths of their own in the attention term, of whose scores
the causal half (a query with the keys up to itself) is counted, once.
The routed part is an expectation: under even routing a token's
``num_experts_per_tok`` picks land on this chip's ``n_routed_experts`` of
``n_routed_experts * expert_parallel`` experts that often, and each pick
that lands costs one expert. The router's product over all the experts is
counted; what recomputation runs again is not."""

from . import attention_kernels


def picks_here_a_token(cfg):
    return cfg["num_experts_per_tok"] / cfg["expert_parallel"]


def attention_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call a layer: the
    causal half's pairs alone, keys ``qk_nope + qk_rope`` wide in the
    score, dQ and dK, values ``v_head_dim`` wide in PV, dV and dP
    (``attention_kernels``)."""
    calls = cfg["num_hidden_layers"] * env["batch"]
    return attention_kernels.flops(
        calls * (env["seq"] * (env["seq"] + 1) // 2),
        cfg["num_attention_heads"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def forward_matmul_flops(cfg, env):
    tokens, s = env["batch"] * env["seq"], env["seq"]
    h, v, heads = (cfg["hidden_size"], cfg["vocab_size"],
                   cfg["num_attention_heads"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    width = cfg["moe_intermediate_size"]
    attention = (
        2 * tokens * (h * heads * qk + h * (rank + cfg["qk_rope_head_dim"])
                      + rank * heads * (cfg["qk_nope_head_dim"] + vd)
                      + heads * vd * h)
        + 2 * env["batch"] * (s * (s + 1) // 2) * heads * (qk + vd))
    dense = 2 * tokens * 3 * h * cfg["intermediate_size"]
    routed_total = cfg["n_routed_experts"] * cfg["expert_parallel"]
    expert = 2 * tokens * (
        3 * h * width * (cfg["n_shared_experts"] + picks_here_a_token(cfg))
        + h * routed_total)
    n_dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return (cfg["num_hidden_layers"] * attention + n_dense * dense
            + (cfg["num_hidden_layers"] - n_dense) * expert
            + 2 * tokens * h * v)


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
