"""Laguna-XS.2 pre-training on one chip's share through ``ParallelEngine``
with per-block recomputation: ``LagunaForPretraining`` (full and sliding
attention layers of their own head counts under a per-head gate by the
published lists, a dense layer, then ``nn.RoutedExperts`` told which
experts it holds beside a shared expert, an untied head over the slice)
under ``LagunaPretrainingCriterion``, the label of a position the next
id."""

from . import load_weights, make_optimizer
from .smallthinker_21b_a3b import stage

_ATTENTION = {"input_layernorm.weight": "n1",
              "post_attention_layernorm.weight": "n2",
              "self_attn.q_proj.weight": "wq",
              "self_attn.k_proj.weight": "wk",
              "self_attn.v_proj.weight": "wv",
              "self_attn.gate_proj.weight": "wgate",
              "self_attn.o_proj.weight": "wo"}
_DENSE = {"mlp.gate_proj.weight": "w_gate", "mlp.up_proj.weight": "w_up",
          "mlp.down_proj.weight": "w_down"}
_EXPERTS = {"mlp.router": "router", "mlp.e_score_correction_bias": "e_bias",
            "mlp.gate_up_proj": "e_gate_up", "mlp.down_proj": "e_down",
            "mlp.shared_experts.gate_proj.weight": "s_gate",
            "mlp.shared_experts.up_proj.weight": "s_up",
            "mlp.shared_experts.down_proj.weight": "s_down"}
_TOP = {"embed_tokens.weight": "embed", "lm_head.norm.weight": "norm_f",
        "lm_head.proj.weight": "lm_head"}


def leaves(cfg):
    out = [(p, r, None) for p, r in _TOP.items()]
    for i, ffn in enumerate(stage(cfg, "mlp_layer_types")):
        out += [(f"layers.blocks.{i}.{p}", f"{r}.{i}", None) for p, r in {
            **_ATTENTION, **(_DENSE if ffn == "dense" else _EXPERTS)}.items()]
    return out


def build(cfg, env, weights, devices):
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (LagunaForPretraining,
                                         LagunaPretrainingCriterion)
    held = cfg["num_experts"]
    model = LagunaForPretraining(
        layer_types=stage(cfg, "layer_types"),
        num_attention_heads_per_layer=stage(
            cfg, "num_attention_heads_per_layer"),
        mlp_layer_types=stage(cfg, "mlp_layer_types"),
        num_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "num_key_value_heads", "head_dim",
            "sliding_window", "rope_parameters", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "shared_expert_intermediate_size", "moe_routed_scaling_factor",
            "rms_norm_eps", "initializer_range")})
    load_weights(model, weights)
    crit = LagunaPretrainingCriterion()
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
