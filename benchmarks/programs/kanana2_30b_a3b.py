"""Kanana-2 pre-training on one chip's share through ``ParallelEngine``
with per-block recomputation: ``Kanana2ForPretraining`` (latent attention,
a dense layer, then ``nn.RoutedExperts`` told which experts it holds)
under ``Kanana2PretrainingCriterion``, the label of a position the next
id."""

from . import load_weights, make_optimizer

_ATTENTION = {"self_attn.q_proj.weight": "wq",
              "self_attn.kv_a_proj_with_mqa.weight": "wkva",
              "self_attn.kv_a_layernorm.weight": "nkv",
              "self_attn.kv_b_proj.weight": "wkvb",
              "self_attn.o_proj.weight": "wo",
              "input_layernorm.weight": "n1",
              "post_attention_layernorm.weight": "n2"}
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
    for i in range(cfg["num_hidden_layers"]):
        ffn = _DENSE if i < cfg["first_k_dense_replace"] else _EXPERTS
        out += [(f"layers.blocks.{i}.{p}", f"{r}.{i}", None)
                for p, r in {**_ATTENTION, **ffn}.items()]
    return out


def build(cfg, env, weights, devices):
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (Kanana2ForPretraining,
                                         Kanana2PretrainingCriterion)
    held = cfg["n_routed_experts"]
    model = Kanana2ForPretraining(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=held * cfg["expert_parallel"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        held_experts=(cfg["expert_rank"] * held, held),
        rope_theta=cfg["rope_theta"], rope_interleave=cfg["rope_interleave"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"])
    load_weights(model, weights)
    crit = Kanana2PretrainingCriterion()
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
