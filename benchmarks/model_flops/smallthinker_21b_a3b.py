"""SmallThinker-21BA3B-Instruct pre-training step on one chip's share:
every matrix product of the forward pass, times three (one product
forward, two backward). Two kinds of attention layer by
``sliding_window_layout``: of a layer's scores the visible pairs alone are
counted, once: the causal half where the layer is global, the band of
``sliding_window_size`` keys under the diagonal where it sees a window
(:func:`visible_pairs`, this module's own closed form; a test holds it
equal to the program's ``mask_rules`` count). The routed part is an
expectation: under even routing a token's
``moe_num_active_primary_experts`` picks land on this chip's
``moe_num_primary_experts`` of ``moe_num_primary_experts *
expert_parallel`` experts that often. The router's product over all the
experts is counted, and the head's over this chip's slice of the
vocabulary; what recomputation runs again is not."""

from . import attention_kernels


def windows(cfg):
    """The keys a query of each layer of this stage sees at most: the
    window's, or None where the layer is global."""
    first = cfg.get("pipeline_first_layer", 0)
    layout = cfg["sliding_window_layout"][first:first
                                          + cfg["num_hidden_layers"]]
    return [cfg["sliding_window_size"] if windowed else None
            for windowed in layout]


def visible_pairs(seq, window=None):
    """(query, key) pairs a row of ``seq`` positions shows: query ``i``
    sees keys ``max(0, i - window + 1) .. i``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def pairs_a_row(cfg, env):
    """The visible pairs of one row, summed over this stage's layers."""
    return sum(visible_pairs(env["seq"], w) for w in windows(cfg))


def picks_here_a_token(cfg):
    return cfg["moe_num_active_primary_experts"] / cfg["expert_parallel"]


def attention_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call a layer: the
    visible pairs' alone, by the layer's kind (``attention_kernels``)."""
    return attention_kernels.flops(env["batch"] * pairs_a_row(cfg, env),
                                   cfg["num_attention_heads"],
                                   cfg["head_dim"])


def forward_matmul_flops(cfg, env):
    tokens = env["batch"] * env["seq"]
    h, heads, kv, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    projections = 2 * tokens * (2 * h * heads * d + 2 * h * kv * d)
    scores = 2 * env["batch"] * pairs_a_row(cfg, env) * heads * 2 * d
    routed_total = cfg["moe_num_primary_experts"] * cfg["expert_parallel"]
    experts = 2 * tokens * (
        h * routed_total
        + 3 * h * cfg["moe_ffn_hidden_size"] * picks_here_a_token(cfg))
    return (layers * (projections + experts) + scores
            + 2 * tokens * h * cfg["vocab_size"])


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
