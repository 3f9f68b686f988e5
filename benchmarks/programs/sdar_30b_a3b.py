"""SDAR block-diffusion training on one chip's share through
``ParallelEngine`` with per-block recomputation: ``SdarForBlockDiffusion``
(grouped-query attention under block diffusion's mask over a noisy and a
clean copy of every row, ``nn.RoutedExperts`` with the softmax rule, told
which experts it holds) under ``SdarBlockDiffusionCriterion``. The noise
levels and draws come with the batch; the noisy copy, the doubled row and
the loss weights are made inside the step."""

from . import load_weights, make_optimizer

_LAYER = {"self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
          "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
          "self_attn.q_norm.weight": "qn", "self_attn.k_norm.weight": "kn",
          "input_layernorm.weight": "n1",
          "post_attention_layernorm.weight": "n2",
          "mlp.router": "router", "mlp.gate_up_proj": "e_gate_up",
          "mlp.down_proj": "e_down"}
_TOP = {"embed_tokens.weight": "embed", "lm_head.norm.weight": "norm_f",
        "lm_head.proj.weight": "lm_head"}


def leaves(cfg):
    out = [(p, r, None) for p, r in _TOP.items()]
    for i in range(cfg["num_hidden_layers"]):
        out += [(f"layers.blocks.{i}.{p}", f"{r}.{i}", None)
                for p, r in _LAYER.items()]
    return out


def build(cfg, env, weights, devices):
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (SdarBlockDiffusionCriterion,
                                         SdarForBlockDiffusion)
    held = cfg["num_experts"]
    model = SdarForBlockDiffusion(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=held * cfg["expert_parallel"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=(cfg["expert_rank"] * held, held),
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"], noise_eps=cfg["noise_eps"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"])
    load_weights(model, weights)
    crit = SdarBlockDiffusionCriterion()
    opt, first_grad = make_optimizer(paddle, cfg["optimizer"],
                                     model.parameters())

    def loss_fn(m, b):
        return crit(*m(Tensor(b["ids"]), Tensor(b["level"]),
                       Tensor(b["draw"])))

    engine = ParallelEngine(
        model, opt, loss_fn,
        mesh=build_mesh(dp=len(devices), devices=list(devices)),
        amp_dtype=cfg["precision"]["compute"]
        if cfg["precision"]["compute"] != "float32" else None,
        recompute=True)
    return {"engine": engine, "model": model, "first_grad": first_grad}
