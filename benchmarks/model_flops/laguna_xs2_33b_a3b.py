"""Laguna-XS.2 pre-training step on one chip's share: every matrix product
of the forward pass, times three (one product forward, two backward). An
attention layer is counted by its own kind and its own number of query
heads (``layer_types``, ``num_attention_heads_per_layer``): of a layer's
scores the visible pairs alone, once, the causal half where the layer is
full, the band of ``sliding_window`` keys under the diagonal where it
slides (``smallthinker_21b_a3b.visible_pairs``, the benchmark's own closed
form; a test holds it equal to the program's ``mask_rules`` count); its
projections with the
gate's (hidden x heads). The routed part is an expectation: under even
routing a token's ``num_experts_per_tok`` picks land on this chip's
``num_experts`` of ``num_experts * expert_parallel`` experts that often;
the shared expert runs for every token. The router's product over all the
experts is counted, and the head's over this chip's slice of the
vocabulary; what recomputation runs again is not."""

from . import attention_kernels
from .smallthinker_21b_a3b import visible_pairs


def stage(cfg, key):
    first = cfg.get("pipeline_first_layer", 0)
    return list(cfg[key][first:first + cfg["num_hidden_layers"]])


def attention_layers(cfg):
    """(the keys a query sees at most, or None where the layer is full;
    its query heads) of each layer of this stage."""
    return [(cfg["sliding_window"] if kind == "sliding_attention" else None,
             heads)
            for kind, heads in zip(
                stage(cfg, "layer_types"),
                stage(cfg, "num_attention_heads_per_layer"))]


def head_pairs(cfg, env):
    """The visible pairs of one row, each as often as its layer has query
    heads, summed over this stage's layers."""
    return sum(heads * visible_pairs(env["seq"], window)
               for window, heads in attention_layers(cfg))


def picks_here_a_token(cfg):
    return cfg["num_experts_per_tok"] / cfg["expert_parallel"]


def attention_kernel_flops(cfg, env):
    """{kernel: FLOPs of its calls in one step}, a call a layer: the
    visible pairs' alone, by the layer's kind, over the layer's own query
    heads (``attention_kernels``)."""
    return attention_kernels.flops(env["batch"] * head_pairs(cfg, env), 1,
                                   cfg["head_dim"])


def forward_matmul_flops(cfg, env):
    tokens = env["batch"] * env["seq"]
    h, kv, d = (cfg["hidden_size"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    projections = sum(
        2 * tokens * (2 * h * heads * d + 2 * h * kv * d + h * heads)
        for _, heads in attention_layers(cfg))
    scores = 2 * env["batch"] * head_pairs(cfg, env) * 2 * d
    dense = 2 * tokens * 3 * h * cfg["intermediate_size"]
    routed_total = cfg["num_experts"] * cfg["expert_parallel"]
    sparse = 2 * tokens * (
        h * routed_total
        + 3 * h * cfg["shared_expert_intermediate_size"]
        + 3 * h * cfg["moe_intermediate_size"] * picks_here_a_token(cfg))
    n_dense = stage(cfg, "mlp_layer_types").count("dense")
    return (projections + scores + n_dense * dense
            + (cfg["num_hidden_layers"] - n_dense) * sparse
            + 2 * tokens * h * cfg["vocab_size"])


def train_step_flops(cfg, env):
    return 3.0 * forward_matmul_flops(cfg, env)
