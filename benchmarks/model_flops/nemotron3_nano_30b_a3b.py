"""Nemotron 3 Nano 30B-A3B pre-training step on one chip's share: every
matrix product of the forward pass, times three (one product forward, two
backward). A layer is one mixer alone, by the published pattern: a
Mamba-2 mixer is its two projections and its scan, an attention layer its
four projections and, of its scores, the causal half, an expert layer its
router over all the experts, its shared expert and, as an expectation
under even routing, the ``num_experts_per_tok`` picks a token that land
on this chip's ``n_routed_experts`` of ``n_routed_experts *
expert_parallel``; the experts have no gate, so two products each. The
head's product is over this chip's slice of the vocabulary. The
convolution's four taps, the norms and the gates are elementwise work,
left out as every other such; what recomputation runs again is not
counted.

**The scan** is counted as its chunked form needs it
(:func:`scan_flops_a_token`), by pairs and not by tiles: of a chunk's ``C
B^T`` and of its product with ``x`` the causal half (a position with the
positions of its chunk up to itself), the state's update (``x_t B_t^T``)
and its read (``S_t C_t``); the backward pass has two products for each
of the forward's, and what it makes again of the forward's (``C B^T``,
the state's read) is no work of the model's. :func:`ssd_kernel_flops`
hands that to ``kernel_mxu_pct`` under the two kernels' names, so a share
of the matrix unit made from it cannot pass 100% by counting a masked half
or a tile's padding. By its shapes the op is bound by memory, not by the
matrix unit: :func:`ssd_scan_bytes` is what the ``ssd_scan`` calls of a
step have to read and write, over the op's device time (scope
``ssd_scan``) and the chip's HBM peak (``peaks.json``) the op's share of
its roofline (``ssd_scan_op_hbm_roofline``, reducer ``op_hbm_pct``),
whatever implements the op. The program counts the same closed form where
it traces the op (``ssd_scan_bytes_total{pass}``,
``paddle1_tpu/nn/functional/ssd.py::traffic_bytes``): written twice, once
on either side, and ``test_nemotron3_yardstick.py`` holds the two equal on
a traced step."""

from . import attention_kernels

SSD_FORWARD, SSD_BACKWARD = "p1t_ssd_fwd", "p1t_ssd_bwd"


def pattern(cfg):
    """This stage's stretch of the published pattern, a letter a layer."""
    first = cfg.get("pipeline_first_layer", 0)
    return cfg["hybrid_override_pattern"][first:first
                                          + cfg["num_hidden_layers"]]


def picks_here_a_token(cfg):
    return cfg["num_experts_per_tok"] / cfg["expert_parallel"]


def causal_pairs(env):
    """(query, key) pairs a row of ``seq`` positions shows."""
    return env["seq"] * (env["seq"] + 1) // 2


def attention_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call an attention
    layer: the visible pairs' alone (``attention_kernels``)."""
    calls = pattern(cfg).count("*") * env["batch"]
    return attention_kernels.flops(calls * causal_pairs(env),
                                   cfg["num_attention_heads"],
                                   cfg["head_dim"])


def scan_flops_a_token(cfg, env):
    """Forward FLOPs a position of one Mamba-2 layer's scan in its chunked
    form: a position meets ``(chunk + 1) / 2`` positions of its chunk."""
    heads, width = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    met = (min(cfg["chunk_size"], env["seq"]) + 1) / 2
    return (2 * met * groups * state          # C_t . B_s
            + 2 * met * heads * width         # its product with x
            + 2 * 2 * heads * width * state)  # the state's update and read


def ssd_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call a Mamba-2 layer."""
    forward = (pattern(cfg).count("M") * env["batch"] * env["seq"]
               * scan_flops_a_token(cfg, env))
    return {SSD_FORWARD: forward, SSD_BACKWARD: 2 * forward}


def ssd_scan_bytes(cfg, env, itemsize=2):
    """{"forward", "backward"}: bytes the ``ssd_scan`` calls of one step
    must move, a call a Mamba-2 layer, operands ``itemsize`` wide
    (bfloat16 under the configuration's AMP). Forward: ``x``, ``B``, ``C``
    and ``dt`` read, ``y`` written. Backward: the four and ``y``'s
    gradient read, four gradients written. A value a head (``A``, ``D``,
    ``dt_bias``) and the states at the chunks' starts, which are the
    implementation's and not the op's, are not counted; the forward a
    recomputed segment runs again is no byte the op has to move."""
    positions = env["batch"] * env["seq"]
    heads = cfg["mamba_num_heads"]
    whole = positions * heads * cfg["mamba_head_dim"] * itemsize
    small = positions * (2 * cfg["n_groups"] * cfg["ssm_state_size"]
                         + heads) * itemsize
    calls = pattern(cfg).count("M")
    return {"forward": calls * (2 * whole + small),
            "backward": calls * (3 * whole + 2 * small)}


def forward_matmul_flops(cfg, env):
    tokens = env["batch"] * env["seq"]
    h = cfg["hidden_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    m_heads = cfg["mamba_num_heads"]
    inner = m_heads * cfg["mamba_head_dim"]
    convolved = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = tokens * (2 * h * (inner + convolved + m_heads) + 2 * inner * h
                      + scan_flops_a_token(cfg, env))
    attention = (2 * tokens * (2 * h * heads * d + 2 * h * kv * d)
                 + 2 * env["batch"] * causal_pairs(env) * heads * 2 * d)
    routed_total = cfg["n_routed_experts"] * cfg["expert_parallel"]
    experts = 2 * tokens * (
        h * routed_total
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
        + 2 * h * cfg["moe_intermediate_size"] * picks_here_a_token(cfg))
    kinds = pattern(cfg)
    return (kinds.count("M") * mamba + kinds.count("*") * attention
            + kinds.count("E") * experts
            + 2 * tokens * h * cfg["vocab_size"])


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
