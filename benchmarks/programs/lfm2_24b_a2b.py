"""LFM2-24B-A2B pre-training on one chip's share through ``ParallelEngine``
with per-block recomputation: ``Lfm2ForPretraining`` (gated
short-convolution layers and a grouped-query attention layer by
``layer_types``, a dense layer, then ``nn.RoutedExperts`` told which
experts it holds, the head tied to the embedding) under
``Lfm2PretrainingCriterion``, the label of a position the next id."""

from . import load_weights, make_optimizer

_CONV = {"conv.in_proj.weight": "w_in", "conv.conv_weight": "taps",
         "conv.out_proj.weight": "w_out"}
_ATTENTION = {"self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
              "self_attn.v_proj.weight": "wv",
              "self_attn.out_proj.weight": "wo",
              "self_attn.q_layernorm.weight": "qn",
              "self_attn.k_layernorm.weight": "kn"}
_NORMS = {"operator_norm.weight": "n1", "ffn_norm.weight": "n2"}
_DENSE = {"mlp.gate_proj.weight": "w_gate", "mlp.up_proj.weight": "w_up",
          "mlp.down_proj.weight": "w_down"}
_EXPERTS = {"mlp.router": "router", "mlp.e_score_correction_bias": "e_bias",
            "mlp.gate_up_proj": "e_gate_up", "mlp.down_proj": "e_down"}
# one leaf for the embedding and the head: the head is tied
_TOP = {"embed_tokens.weight": "embed", "lm_head.norm.weight": "norm_f"}


def layer_kinds(cfg):
    first = cfg.get("pipeline_first_layer", 0)
    return list(cfg["layer_types"][first:first + cfg["num_hidden_layers"]])


def leaves(cfg):
    out = [(p, r, None) for p, r in _TOP.items()]
    for i, kind in enumerate(layer_kinds(cfg)):
        op = _CONV if kind == "conv" else _ATTENTION
        ffn = _DENSE if i < cfg["num_dense_layers"] else _EXPERTS
        out += [(f"layers.blocks.{i}.{p}", f"{r}.{i}", None)
                for p, r in {**_NORMS, **op, **ffn}.items()]
    return out


def build(cfg, env, weights, devices):
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (Lfm2ForPretraining,
                                         Lfm2PretrainingCriterion)
    held = cfg["num_experts"]
    model = Lfm2ForPretraining(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=layer_kinds(cfg),
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], conv_L_cache=cfg["conv_L_cache"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=held * cfg["expert_parallel"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        held_experts=(cfg["expert_rank"] * held, held),
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        norm_eps=cfg["norm_eps"],
        initializer_range=cfg["initializer_range"])
    load_weights(model, weights)
    crit = Lfm2PretrainingCriterion()
    opt, first_grad = make_optimizer(paddle, cfg["optimizer"],
                                     model.parameters())

    def loss_fn(m, b):
        ids = Tensor(b["ids"])
        labels = m.next_token_labels(ids)
        return crit(m(ids, labels), labels)

    engine = ParallelEngine(
        model, opt, loss_fn,
        mesh=build_mesh(dp=len(devices), devices=list(devices)),
        amp_dtype=cfg["precision"]["compute"]
        if cfg["precision"]["compute"] != "float32" else None,
        recompute=True)
    return {"engine": engine, "model": model, "first_grad": first_grad}
