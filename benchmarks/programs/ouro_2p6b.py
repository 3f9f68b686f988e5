"""Ouro pre-training through ``ParallelEngine`` with per-block
recomputation: ``OuroForPretraining`` (the looped stack and the exit heads)
under ``OuroPretrainingCriterion``, the label of a position the next id."""

from . import load_weights, make_optimizer

_LAYER = {"self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
          "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo",
          "mlp.gate_proj.weight": "w_gate", "mlp.up_proj.weight": "w_up",
          "mlp.down_proj.weight": "w_down",
          "input_layernorm.weight": "n1", "input_layernorm_2.weight": "n2",
          "post_attention_layernorm.weight": "n3",
          "post_attention_layernorm_2.weight": "n4"}
_TOP = {"embed_tokens.weight": "embed", "layers.norm.weight": "norm_f",
        "exit_head.lm_head.weight": "lm_head",
        "exit_head.gate.weight": "gate_w", "exit_head.gate.bias": "gate_b"}


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
    from paddle1_tpu.text.models import (OuroForPretraining,
                                         OuroPretrainingCriterion)
    model = OuroForPretraining(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        total_ut_steps=cfg["total_ut_steps"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"])
    load_weights(model, weights)
    crit = OuroPretrainingCriterion(cfg["exit_entropy_beta"])
    opt, first_grad = make_optimizer(paddle, cfg["optimizer"],
                                     model.parameters())

    def loss_fn(m, b):
        ids = Tensor(b["ids"])
        labels = m.next_token_labels(ids)
        return crit(*m(ids, labels), labels)

    engine = ParallelEngine(
        model, opt, loss_fn,
        mesh=build_mesh(dp=len(devices), devices=list(devices)),
        amp_dtype=cfg["precision"]["compute"]
        if cfg["precision"]["compute"] != "float32" else None,
        recompute=True)
    return {"engine": engine, "model": model, "first_grad": first_grad}
