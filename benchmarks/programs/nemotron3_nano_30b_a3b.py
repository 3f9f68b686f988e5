"""Nemotron 3 Nano 30B-A3B pre-training on one chip's share through
``ParallelEngine`` with per-block recomputation:
``NemotronHForPretraining`` (a layer one mixer alone by the published
pattern: Mamba-2 state-space mixers, a grouped-query attention layer
without positions, ``nn.RoutedExperts`` of non-gated relu² experts told
which it holds beside a shared expert, an untied head over the slice)
under ``NemotronHPretrainingCriterion``, the label of a position the next
id."""

from . import load_weights, make_optimizer
from ..model_flops.nemotron3_nano_30b_a3b import pattern

_MIXERS = {
    "M": {"mamba.in_proj.weight": "w_in", "mamba.conv_weight": "conv_w",
          "mamba.conv_bias": "conv_b", "mamba.A_log": "a_log",
          "mamba.D": "d_skip", "mamba.dt_bias": "dt_bias",
          "mamba.norm_weight": "gn", "mamba.out_proj.weight": "w_out"},
    "*": {"self_attn.q_proj.weight": "wq", "self_attn.k_proj.weight": "wk",
          "self_attn.v_proj.weight": "wv", "self_attn.o_proj.weight": "wo"},
    "E": {"mlp.router": "router", "mlp.e_score_correction_bias": "e_bias",
          "mlp.up_proj": "e_up", "mlp.down_proj": "e_down",
          "mlp.shared_experts.up_proj.weight": "s_up",
          "mlp.shared_experts.down_proj.weight": "s_down"}}
_TOP = {"embed_tokens.weight": "embed", "lm_head.norm.weight": "norm_f",
        "lm_head.proj.weight": "lm_head"}


def leaves(cfg):
    out = [(p, r, None) for p, r in _TOP.items()]
    for i, kind in enumerate(pattern(cfg)):
        out += [(f"layers.blocks.{i}.{p}", f"{r}.{i}", None)
                for p, r in {"norm.weight": "n", **_MIXERS[kind]}.items()]
    return out


def build(cfg, env, weights, devices):
    import paddle1_tpu as paddle
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed import ParallelEngine, build_mesh
    from paddle1_tpu.text.models import (NemotronHForPretraining,
                                         NemotronHPretrainingCriterion)
    held = cfg["n_routed_experts"]
    model = NemotronHForPretraining(
        pattern=pattern(cfg), n_routed_experts=held * cfg["expert_parallel"],
        held_experts=(cfg["expert_rank"] * held, held),
        **{k: cfg[k] for k in (
            "vocab_size", "hidden_size", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "num_experts_per_tok", "routed_scaling_factor",
            "layer_norm_epsilon", "initializer_range")})
    load_weights(model, weights)
    crit = NemotronHPretrainingCriterion()
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
