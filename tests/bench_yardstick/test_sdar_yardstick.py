"""CPU tests of what PR 33 added to the yardstick: the FLOP count of a step
whose attention is counted by the pairs block diffusion's mask lets
through, the scope metrics that read the new names (the expert layer's
three under the names kanana2's cell reads them by, since PR 41), the
uniform draw, and the new configuration's and cell's files. (The kernels'
share of the matrix unit: ``test_kernel_rooflines.py``, for every cell
that lists it.) (That the rehearsal passes ``correct`` in float32 and the
bfloat16 control fails it: ``test_bench_yardstick.py`` runs both for every
configuration there is.) Nothing here is a device metric."""

import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spec, traffic  # noqa: E402
from benchmarks.model_flops import sdar_30b_a3b as mf  # noqa: E402

FILES = spec.layer_metrics()
CELL = "sdar_30b_a3b.blockdiff_s8192"
NEW = ("attn_proj_ms", "diffusion_input_ms", "diffusion_head_ms",
       "attention_kernel_mxu_roofline", "moe_ms", "moe_route_ms",
       "routed_experts_ms")

# scopes as the SDAR step compiled for a v5e carries them (PR 33; one
# backward kernel since PR 35)
J = "jit(counted_step)/"
M = "SdarForBlockDiffusion/"
FWD = J + "jvp(loss)/" + M + "layers/recompute/"
BACK = (J + "transpose(jvp(loss))/" + M + "layers/recompute/jvp(loss)/" + M
        + "layers/recompute/checkpoint/")
AGAIN = BACK + "rematted_computation/"
SDPA = "self_attn/scaled_dot_product_attention/"
SCOPES = {
    "fusion.1": J + "jvp(loss)/" + M + "block_noise/select_n",
    "fusion.2": J + "jvp(loss)/" + M + "block_noise/concatenate",
    "fusion.3": FWD + "0/self_attn/q_proj/linear/dot_general",
    "fusion.4": AGAIN + "1/self_attn/k_proj/linear/dot_general",
    "fusion.5": BACK + "2/self_attn/v_proj/linear/transpose",
    "fusion.6": FWD + "3/self_attn/q_norm/rms_norm/mul",
    "fusion.7": AGAIN + "3/self_attn/k_norm/rms_norm/mul",
    "fusion.8": FWD + "4/self_attn/rotary_embedding/mul",
    "fusion.9": BACK + "4/self_attn/o_proj/linear/transpose",
    "p1t_flash_attention_fwd.10": FWD + "2/" + SDPA
    + "jit(_fwd_call)/p1t_flash_attention_fwd/pallas_call",
    "p1t_flash_attention_bwd_dkv.11": BACK + "2/" + SDPA
    + "jit(_bwd_call)/p1t_flash_attention_bwd_dkv/pallas_call",
    "fusion.13": BACK + "2/" + SDPA + "jit(_bwd_call)/reduce_sum",
    "fusion.14": FWD + "1/mlp/moe/moe_router/dot_general",
    "ragged-dot-none.15": J + "transpose(jvp(loss))/moe/routed_experts",
    "fusion.16": J + "jvp(loss)/" + M + "recompute/lm_head/norm/rms_norm/mul",
    "fusion.17": J + "jvp(loss)/" + M + "recompute/lm_head/"
    "head_cross_entropy/dot_general",
    "fusion.18": J + "transpose(jvp(loss))/" + M + "recompute/jvp(loss)/" + M
    + "recompute/checkpoint/rematted_computation/lm_head/"
    "head_cross_entropy/reduce_max",
    "fusion.19": J + "jvp(loss)/diffusion_loss/reduce_sum",
    "fusion.20": J + "transpose(jvp(loss))/diffusion_loss/mul",
    "fusion.21": J + "optimizer/add",
    # Kanana-2's attention layer has the same name and its head the same
    # scope
    "fusion.22": J + "jvp(loss)/Kanana2ForPretraining/layers/recompute/1/"
    "self_attn/kv_b_proj/linear/dot_general",
    # the expert layer under Kanana-2's names
    "sort.23": FWD + "1/mlp/moe/moe_dispatch/sort",
    "p1t_sum_picks_fwd.24": FWD + "1/mlp/moe/moe_combine/"
    "p1t_sum_picks_fwd/pallas_call",
    "conditional.25": AGAIN + "2/mlp/moe/moe_overflow/cond",
    "ragged-dot-none.26": J + "jvp(loss)/moe/routed_experts",
    "fusion.27": FWD + "1/mlp/moe/routed_experts/mul",
}
EXPECT = {
    "attn_proj_ms": {"fusion.3", "fusion.4", "fusion.5", "fusion.6",
                     "fusion.7", "fusion.8", "fusion.9", "fusion.22"},
    "diffusion_input_ms": {"fusion.1", "fusion.2"},
    "diffusion_head_ms": {"fusion.16", "fusion.17", "fusion.18", "fusion.19",
                          "fusion.20"},
    "moe_ms": {"fusion.14", "ragged-dot-none.15", "sort.23",
               "p1t_sum_picks_fwd.24", "conditional.25",
               "ragged-dot-none.26", "fusion.27"},
    "moe_route_ms": {"fusion.14", "sort.23", "p1t_sum_picks_fwd.24",
                     "conditional.25"},
    "routed_experts_ms": {"ragged-dot-none.15", "ragged-dot-none.26",
                          "fusion.27"},
}
KERNELS = {"p1t_flash_attention_fwd.10", "p1t_flash_attention_bwd_dkv.11"}


def _view(steps=4, ms=1.0):
    ops = {f"%{n} = bf16[8,128]{{1,0}} fusion(%p.{i}), kind=kLoop":
           1e-3 * ms * steps for i, n in enumerate(SCOPES)}
    return {"ops": ops, "step_s": [len(SCOPES) * 1e-3 * ms] * steps,
            "busy_s": len(SCOPES) * 1e-3 * ms * steps}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_new_scope_metric_reads_its_scope(name):
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


def test_the_attention_op_holds_the_kernels_and_stands_apart():
    attention = re.compile(FILES["attention_ms"]["match"])
    held = {n for n, p in SCOPES.items() if attention.search(p)}
    assert held == KERNELS | {"fusion.13"}
    assert not held & EXPECT["attn_proj_ms"]
    again = re.compile(FILES["recompute_ms"]["match"])
    assert {n for n, p in SCOPES.items() if again.search(p)} \
        == {"fusion.4", "fusion.7", "fusion.18", "conditional.25"}


def _brute_force_pairs(length, block):
    """Visible (query, key) pairs of a doubled row, pair by pair."""
    count = 0
    for r in range(2 * length):
        for c in range(2 * length):
            r_noisy, c_noisy = r < length, c < length
            br, bc = r % length // block, c % length // block
            count += ((r_noisy and c_noisy and bc == br)
                      or (r_noisy and not c_noisy and bc < br)
                      or (not r_noisy and not c_noisy and bc <= br))
    return count


@pytest.mark.parametrize("block", [1, 4, 16, 64])
def test_the_flop_count_is_a_brute_force_count_of_visible_pairs(block):
    cfg = {**spec.config("sdar_30b_a3b"), "block_length": block}
    env = {"batch": 3, "seq": 64, "blocks": 64 // block}
    pairs = _brute_force_pairs(64, block)
    assert mf.visible_pairs(cfg, env) == pairs == 64 * 64 + 64 * block
    per_pair = 2 * 128 * 32             # one score-shaped product, all heads
    # the forward kernel's scores and values; the one backward kernel's
    # scores again, dV, dP, dQ and dK (PR 35; 2 + 4 + 3 over three names
    # before it, which PR 41 took out as stale)
    assert mf.attention_kernel_flops(cfg, env) == {
        "p1t_flash_attention_fwd": 2 * per_pair * pairs * 3 * 5,
        "p1t_flash_attention_bwd": 5 * per_pair * pairs * 3 * 5}


def test_sdar_flops_hand_count():
    cfg = spec.config("sdar_30b_a3b")
    env = {"batch": 1, "seq": 8192, "blocks": 2048}
    positions, rows = 2 * 8192, 8192
    projections = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
    assert projections == 18874368                  # ISSUE 33: 19.1 M less the router
    assert mf.picks_here_a_token(cfg) == 1.0        # 8 x 16 / 128
    layer = (2 * positions * projections
             + 4 * (8192 * 8192 + 8192 * 4) * 32 * 128
             + 2 * positions * (2048 * 128 + 3 * 2048 * 768 * 1.0))
    head = 2 * rows * 2048 * 18992
    forward = 5 * layer + head
    assert mf.forward_matmul_flops(cfg, env) == forward
    assert mf.train_step_flops(cfg, env) == 3.0 * forward
    # ISSUE 33's arithmetic: 37.7 MFLOP a position in the projections, 9.4
    # in the held experts, 67 in scores and values; attention 55% of all
    assert 2 * projections == pytest.approx(37.7e6, rel=2e-3)
    assert 2 * 3 * 2048 * 768 == pytest.approx(9.4e6, rel=5e-3)
    scores = 4 * (8192 * 8192 + 8192 * 4) * 32 * 128
    assert scores / positions == pytest.approx(67e6, rel=5e-3)
    assert 5 * scores / forward == pytest.approx(0.55, abs=0.005)
    assert mf.train_step_flops(cfg, env) == pytest.approx(30.1e12, rel=2e-3)
    # a causal mask over the doubled row would count twice the pairs
    assert (16384 * 16385 // 2) / mf.visible_pairs(cfg, env) \
        == pytest.approx(2.0, rel=1e-3)


def test_the_uniform_draw():
    rng = np.random.default_rng(3)
    field = {"draw": "uniform", "shape": ["batch", "blocks"],
             "dtype": "float32"}
    x = spec.draw("uniform")(rng, field, {"batch": 4, "blocks": 512}.get)
    assert x.shape == (4, 512) and x.dtype == np.float32
    assert 0.0 <= x.min() and x.max() < 1.0 and 0.45 < x.mean() < 0.55


# JetLM/SDAR-30B-A3B-Chat config.json, as the catalog beside the
# model-configs guide has it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_sdar_config_keeps_the_published_widths():
    cfg = spec.config("sdar_30b_a3b")
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # inside the floors: 4 layers, 8 routed experts, an eighth of the
    # vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width and its picks
    assert cfg["num_experts"] * cfg["expert_parallel"] == 128
    assert cfg["vocab_size"] * cfg["expert_parallel"] == 151936
    assert 0 <= cfg["expert_rank"] < cfg["expert_parallel"] == 8
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    assert "Eight chips share each layer" in cfg["deployment"]
    assert "share 0 of 8" in cfg["deployment"]
    for key in ("block_length", "noise_schedule", "noise_source", "copies",
                "q_norm_k_norm", "mask_token", "weights", "router",
                "optimizer", "lr_first_step", "recompute", "mask_row_picks"):
        assert cfg["assumed"][key]
    # every seed's share holds one of the [MASK] row's eight picks a layer
    assert cfg["mask_route_logit"] > 0
    assert cfg["num_experts_per_tok"] == cfg["expert_parallel"]
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}[cfg["name"]]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # what the rehearsal changes are sizes, never the mechanism
    small = spec.config("sdar_30b_a3b", rehearsal=True)
    assert small["num_experts_per_tok"] == 8 and small["block_length"] == 4
    assert small["num_attention_heads"] > small["num_key_value_heads"] > 1
    assert small["mask_token_id"] == small["vocab_size"] - 1


def test_the_sdar_cell():
    cell = spec.cell(CELL)
    loop = spec.loop(cell)
    assert (loop["readback_every"], loop["trace_first_step"],
            loop["trace_steps"]) == (5, 10, 10)
    cfg = spec.config("sdar_30b_a3b")
    for src in (cell, cell["rehearsal"]):
        assert src["dims"]["seq"] == src["dims"]["blocks"] \
            * cfg["block_length"]
    assert 2 * cell["batch"] * cell["dims"]["seq"] == 16384
    assert cell["chips"] == 1
    assert sorted(cell["fields"]) == ["draw", "ids", "level"]
    listed = {m["name"] for m in spec.per_layer_for(CELL)}
    assert set(NEW) | {"attention_ms", "recompute_ms", "rms_norm_ms",
                       "forward_ms", "backward_ms", "optimizer_ms",
                       "unscoped_ms", "host_step_ms", "device_step_ms",
                       "step_mfu_pct", "moe_held_picks_pct",
                       "moe_expert_rows_max", "moe_late_picks"} <= listed
    # ids below the id that stands for [MASK]
    env = traffic.environment(cfg, cell)
    small = {**env, "batch": 2, "seq": 64, "blocks": 16}
    for batch in traffic.batches(cell, small, 2 ** 31 + 7, 2):
        assert batch["ids"].max() < cfg["mask_token_id"]
        assert batch["level"].shape == (2, 16)
        assert batch["draw"].shape == (2, 64)
