"""CPU tests of what PR 31 added to the yardstick: the FLOP count of a
model whose routed part is an expectation and whose keys and values differ
in width, the five scope metrics that read the expert layer's and latent
attention's names, and the new configuration's and cell's files. Nothing
here is a device metric."""

import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spec  # noqa: E402

FILES = spec.layer_metrics()
CELL = "kanana2_30b_a3b.pretrain_s8192"

# scopes as the Kanana-2 step compiled for a v5e carries them (PR 31)
J = "jit(counted_step)/"
FWD = J + "jvp(loss)/Kanana2ForPretraining/layers/recompute/"
BACK = (J + "transpose(jvp(loss))/Kanana2ForPretraining/layers/recompute/"
        "jvp(loss)/Kanana2ForPretraining/layers/recompute/checkpoint/")
AGAIN = BACK + "rematted_computation/"
SCOPES = {
    "fusion.1": FWD + "1/mlp/moe/moe_router/dot_general",
    "sort.2": FWD + "2/mlp/moe/moe_dispatch/sort",
    "fusion.3": AGAIN + "3/mlp/moe/moe_dispatch/gather",
    # XLA's own Mosaic kernel for jax.lax.ragged_dot carries the
    # compiler's name: parse_op_scopes places it by the scope the layer
    # hands the compiler (tests/test_step_names.py); the custom call that
    # makes its tile table (microseconds) has neither
    "ragged-dot-none.4": J + "transpose(jvp(loss))/moe/routed_experts",
    "ragged-dot-metadata.5": "ragged-dot-metadata",
    "fusion.21": FWD + "1/mlp/moe/routed_experts/mul",
    "fusion.6": BACK + "2/mlp/moe/moe_combine/mul",
    "conditional.7": FWD + "2/mlp/moe/moe_overflow/cond",
    "fusion.8": FWD + "1/mlp/moe/shared_experts/gate_proj/linear/"
    "dot_general",
    "fusion.9": AGAIN + "1/mlp/moe/shared_experts/swiglu/mul",
    "fusion.10": FWD + "1/mlp/moe/add",
    # the leading dense layer's feed-forward is no expert layer
    "fusion.11": FWD + "0/mlp/gate_proj/linear/dot_general",
    "fusion.12": FWD + "0/self_attn/q_proj/linear/dot_general",
    "fusion.13": AGAIN + "2/self_attn/kv_a_layernorm/rms_norm/mul",
    "fusion.14": FWD + "3/self_attn/rotary_embedding/mul",
    "fusion.15": BACK + "3/self_attn/kv_b_proj/linear/transpose",
    "p1t_flash_attention_fwd.16": FWD + "3/self_attn/"
    "scaled_dot_product_attention/jit(_fwd_call)/p1t_flash_attention_fwd/"
    "pallas_call",
    "fusion.17": BACK + "3/self_attn/scaled_dot_product_attention/"
    "jit(_bwd_call)/reduce_sum",
    "fusion.18": J + "jvp(loss)/Kanana2ForPretraining/recompute/lm_head/"
    "head_cross_entropy/dot_general",
    "fusion.19": J + "optimizer/add",
    # Ouro's attention layer has the same name: its cell does not list
    # the metric
    "fusion.20": J + "jvp(loss)/OuroForPretraining/ut_step/0/layers/"
    "recompute/0/self_attn/q_proj/linear/dot_general",
}
EXPECT = {
    "moe_ms": {"fusion.1", "sort.2", "fusion.3", "ragged-dot-none.4",
               "fusion.21", "fusion.6", "conditional.7", "fusion.8",
               "fusion.9", "fusion.10"},
    "moe_route_ms": {"fusion.1", "sort.2", "fusion.3", "fusion.6",
                     "conditional.7"},
    "routed_experts_ms": {"ragged-dot-none.4", "fusion.21"},
    "shared_experts_ms": {"fusion.8", "fusion.9"},
    "mla_proj_ms": {"fusion.12", "fusion.13", "fusion.14", "fusion.15",
                    "fusion.20"},
}


def _view(steps=4):
    ops = {f"%{n} = bf16[8,128]{{1,0}} fusion(%p.{i}), kind=kLoop":
           1e-3 * steps for i, n in enumerate(SCOPES)}
    return {"ops": ops, "step_s": [len(SCOPES) * 1e-3] * steps,
            "busy_s": len(SCOPES) * 1e-3 * steps}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_expert_and_latent_scope_metric_reads_its_scope(name):
    metric = FILES[name]
    value = spec.reducer(metric["reducer"])(
        {"views": [_view(), _view()]}, metric, maps=(SCOPES, {}))
    assert value == pytest.approx(len(EXPECT[name]))     # 1 ms each a step
    match = re.compile(metric["match"])
    exclude = re.compile(metric["exclude"]) if "exclude" in metric else None
    got = {n for n, path in SCOPES.items() if match.search(path)
           and not (exclude and exclude.search(path))}
    assert got == EXPECT[name]
    # no map, as on the parent of this PR, or no view: nothing, no raise
    for maps in ((None, None), ({}, {})):
        assert spec.reducer(metric["reducer"])(
            {"views": [_view()]}, metric, maps=maps) is None
    assert spec.reducer(metric["reducer"])(
        {"views": []}, metric, maps=(SCOPES, {})) is None


def test_the_expert_layers_parts_add_up_and_attention_stands_apart():
    parts = (EXPECT["moe_route_ms"] | EXPECT["routed_experts_ms"]
             | EXPECT["shared_experts_ms"])
    assert parts | {"fusion.10"} == EXPECT["moe_ms"]     # + the final add
    assert not EXPECT["moe_route_ms"] & EXPECT["routed_experts_ms"]
    assert not EXPECT["moe_ms"] & EXPECT["mla_proj_ms"]
    attention = re.compile(FILES["attention_ms"]["match"])
    kernel = {n for n, p in SCOPES.items() if attention.search(p)}
    assert kernel == {"p1t_flash_attention_fwd.16", "fusion.17"}
    assert not kernel & EXPECT["mla_proj_ms"]
    again = re.compile(FILES["recompute_ms"]["match"])
    assert {n for n, p in SCOPES.items() if again.search(p)} \
        == {"fusion.3", "fusion.9", "fusion.13"}


def test_kanana2_flops_hand_count():
    from benchmarks.model_flops import kanana2_30b_a3b as mf
    cfg = spec.config("kanana2_30b_a3b")
    env = {"batch": 2, "seq": 8192}
    tokens = 2 * 8192
    attention_params = (2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
                        + 32 * 128 * 2048)
    assert attention_params == 26345472                  # ISSUE 31: 26.35 M
    pairs = 8192 * 8193 // 2
    # keys 192 wide in QK^T, values 128 wide in PV
    attention = 2 * tokens * attention_params + 2 * 2 * pairs * 32 * (192 + 128)
    dense = 2 * tokens * 3 * 2048 * 6144
    assert mf.picks_here_a_token(cfg) == 0.75            # 6 x 16 / 128
    expert = 2 * tokens * (3 * 2048 * 768 * (2 + 0.75) + 2048 * 128)
    head = 2 * tokens * 2048 * 16032
    forward = 5 * attention + dense + 4 * expert + head
    assert mf.forward_matmul_flops(cfg, env) == forward
    assert mf.train_step_flops(cfg, env) == 3.0 * forward
    # ISSUE 31's arithmetic: 930 MFLOP a token forward, 45.7 TFLOP a step
    assert forward / tokens == pytest.approx(930e6, rel=2e-3)
    assert mf.train_step_flops(cfg, env) == pytest.approx(45.7e12, rel=2e-3)
    # the whole model's router and all its experts on one chip
    whole = {**cfg, "n_routed_experts": 128, "expert_parallel": 1}
    assert mf.picks_here_a_token(whole) == 6
    # no expert layer at depth 1: the leading layer is dense
    one = {**cfg, "num_hidden_layers": 1}
    assert mf.forward_matmul_flops(one, env) == attention + dense + head


def test_kanana2_attention_kernel_flops_hand_count():
    """The two attention kernels, 5 calls each: keys 192 wide in the
    score, dQ and dK, values 128 wide in PV, dV and dP (PERF.md section 5
    reads the kernels against 1.375 and 3.573 TFLOP a call)."""
    from benchmarks.model_flops import kanana2_30b_a3b as mf
    cfg = spec.config("kanana2_30b_a3b")
    pairs = 8192 * 8193 // 2
    kernels = mf.attention_kernel_flops(cfg, {"batch": 2, "seq": 8192})
    assert kernels == {
        "p1t_flash_attention_fwd": 5 * 2 * 2 * pairs * 32 * (192 + 128),
        "p1t_flash_attention_bwd": 5 * 2 * 2 * pairs * 32 * (3 * 192
                                                             + 2 * 128)}
    assert kernels["p1t_flash_attention_fwd"] / 5 \
        == pytest.approx(1.375e12, rel=1e-3)
    assert kernels["p1t_flash_attention_bwd"] / 5 \
        == pytest.approx(3.573e12, rel=1e-3)
    # a brute-force count of the causal pairs at a small size
    small = mf.attention_kernel_flops(cfg, {"batch": 3, "seq": 64})
    seen = sum(k <= q for q in range(64) for k in range(64))
    assert small["p1t_flash_attention_fwd"] \
        == 5 * 3 * 2 * seen * 32 * (192 + 128)


# kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, as the catalog
# beside the model-configs guide has it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


def test_kanana2_config_keeps_the_published_widths():
    cfg = spec.config("kanana2_30b_a3b")
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # inside the floors: 4 expert layers after the dense one, 8 routed
    # experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width and its picks
    assert cfg["n_routed_experts"] * cfg["expert_parallel"] == 128
    assert cfg["vocab_size"] * cfg["expert_parallel"] == 128256
    assert 0 <= cfg["expert_rank"] < cfg["expert_parallel"] == 8
    assert "Eight chips share each layer" in cfg["deployment"]
    assert "share 0 of 8" in cfg["deployment"]
    for key in ("weights", "selection_bias", "token_ids", "sequence",
                "optimizer", "lr_first_step", "recompute"):
        assert cfg["assumed"][key]
    # what the rehearsal changes are sizes, never the mechanism
    small = spec.config("kanana2_30b_a3b", rehearsal=True)
    assert small["n_routed_experts"] * small["expert_parallel"] == 8
    assert small["num_experts_per_tok"] == 6


def test_the_kanana2_cell():
    cell = spec.cell(CELL)
    loop = spec.loop(cell)
    assert (loop["readback_every"], loop["trace_first_step"],
            loop["trace_steps"]) == (5, 10, 10)
    assert cell["batch"] * cell["dims"]["seq"] == 16384
    assert cell["chips"] == 1
    listed = {m["name"] for m in spec.per_layer_for(CELL)}
    assert set(EXPECT) | {"attention_ms", "recompute_ms", "rms_norm_ms",
                          "forward_ms", "backward_ms", "optimizer_ms",
                          "unscoped_ms", "host_step_ms", "device_step_ms",
                          "step_mfu_pct", "attention_kernel_mxu_roofline",
                          "moe_held_picks_pct", "moe_expert_rows_max",
                          "moe_late_picks"} <= listed
