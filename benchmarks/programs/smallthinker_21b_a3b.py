"""SmallThinker-21BA3B-Instruct pre-training on one chip's share through
``ParallelEngine`` with per-block recomputation:
``SmallThinkerForPretraining`` (window and global attention layers by the
two published lists, ``nn.RoutedExperts`` told which experts it holds,
which router input it reads and that its gate is ReLU, an untied head
over the slice) under ``SmallThinkerPretrainingCriterion``, the label of a
position the next id."""

from . import load_weights, make_optimizer

_LAYER = {"input_layernorm.weight": "n1",
          "post_attention_layernorm.weight": "n2",
          "self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
          "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
          "mlp.router": "router", "mlp.gate_up_proj": "e_gate_up",
          "mlp.down_proj": "e_down"}
_TOP = {"embed_tokens.weight": "embed", "lm_head.norm.weight": "norm_f",
        "lm_head.proj.weight": "lm_head"}


def stage(cfg, key):
    """This stage's stretch of a published list of a value a layer."""
    first = cfg.get("pipeline_first_layer", 0)
    return list(cfg[key][first:first + cfg["num_hidden_layers"]])


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
    from paddle1_tpu.text.models import (SmallThinkerForPretraining,
                                         SmallThinkerPretrainingCriterion)
    held = cfg["moe_num_primary_experts"]
    model = SmallThinkerForPretraining(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        sliding_window_layout=stage(cfg, "sliding_window_layout"),
        rope_layout=stage(cfg, "rope_layout"),
        sliding_window_size=cfg["sliding_window_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_ffn_hidden_size=cfg["moe_ffn_hidden_size"],
        moe_num_primary_experts=held * cfg["expert_parallel"],
        moe_num_active_primary_experts=cfg[
            "moe_num_active_primary_experts"],
        held_experts=(cfg["expert_rank"] * held, held),
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"])
    load_weights(model, weights)
    crit = SmallThinkerPretrainingCriterion()
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
