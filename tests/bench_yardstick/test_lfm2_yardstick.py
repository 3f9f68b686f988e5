"""CPU tests of what PR 38 added to the yardstick, and of the readers of
its scopes that PR 41 filed: the FLOP count of a step whose layers are of
two kinds, the bytes of the one op that memory bounds, the scopes of the
step under the scope metrics the cell is listed in (the accepted ones, the
expert layer's and the attention projections' under the names the other
cells read them by, and four of its own: the convolution operator, the op
inside it, the op's share of the HBM peak and the dense feed-forward), the
parameter count of the cut, and the configuration's and cell's files. (That
the rehearsal passes ``correct`` in float32 and the bfloat16 control fails
it: ``test_bench_yardstick.py`` runs both for every configuration there
is; the kernels' share of the matrix unit: ``test_kernel_rooflines.py``.)
Nothing here is a device metric."""

import os
import re
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import spec, traffic  # noqa: E402
from benchmarks.model_flops import lfm2_24b_a2b as mf  # noqa: E402
from benchmarks.reducers import op_hbm_pct  # noqa: E402

FILES = spec.layer_metrics()
CELL = "lfm2_24b_a2b.pretrain_s16384"
CONFIG = "lfm2_24b_a2b"
# the accepted metrics whose lists of cells gained this one (PR 38, and
# PR 41 from ``moe_ms`` on), and the four PR 41 filed for it
APPENDED = ("forward_ms", "backward_ms", "optimizer_ms", "attention_ms",
            "unscoped_ms", "host_step_ms", "recompute_ms", "rms_norm_ms",
            "moe_ms", "moe_route_ms", "routed_experts_ms", "attn_proj_ms",
            "moe_held_picks_pct", "moe_expert_rows_max", "moe_late_picks",
            "attention_kernel_mxu_roofline")
OWN = ("short_conv_ms", "short_conv_op_ms", "short_conv_op_hbm_roofline",
       "dense_ffn_ms")

# scopes as the LFM2 step compiled for a v5e carries them (PR 38)
J = "jit(counted_step)/"
M = "Lfm2ForPretraining/"
FWD = J + "jvp(loss)/" + M + "layers/recompute/"
BACK = (J + "transpose(jvp(loss))/" + M + "layers/recompute/jvp(loss)/" + M
        + "layers/recompute/checkpoint/")
AGAIN = BACK + "rematted_computation/"
SDPA = "self_attn/scaled_dot_product_attention/"
SCOPES = {
    "fusion.1": J + "jvp(loss)/" + M + "embed_tokens/embedding/jit(_take)/gather",
    "fusion.2": FWD + "0/operator_norm/rms_norm/mul",
    "fusion.3": FWD + "0/conv/in_proj/linear/dot_general",
    "p1t_gated_short_conv_fwd.4": FWD + "0/conv/gated_short_conv/"
    "p1t_gated_short_conv_fwd/pallas_call",
    "fusion.5": FWD + "0/conv/out_proj/linear/dot_general",
    "fusion.6": AGAIN + "2/conv/in_proj/linear/dot_general",
    "p1t_gated_short_conv_fwd.7": AGAIN + "2/conv/gated_short_conv/"
    "p1t_gated_short_conv_fwd/pallas_call",
    "p1t_gated_short_conv_bwd.8": BACK + "2/conv/gated_short_conv/"
    "p1t_gated_short_conv_bwd/pallas_call",
    "fusion.9": BACK + "3/conv/out_proj/linear/transpose",
    "fusion.10": BACK + "4/conv/gated_short_conv/transpose",
    "fusion.11": FWD + "0/mlp/gate_proj/linear/dot_general",
    "fusion.12": FWD + "1/self_attn/q_proj/linear/dot_general",
    "fusion.13": AGAIN + "1/self_attn/k_layernorm/rms_norm/mul",
    "fusion.14": FWD + "1/self_attn/rotary_embedding/mul",
    "fusion.15": BACK + "1/self_attn/out_proj/linear/transpose",
    "p1t_flash_attention_fwd.16": FWD + "1/" + SDPA
    + "jit(_fwd_call)/p1t_flash_attention_fwd/pallas_call",
    "p1t_flash_attention_bwd_dkv.17": BACK + "1/" + SDPA
    + "jit(_bwd_call)/p1t_flash_attention_bwd_dkv/pallas_call",
    "fusion.18": BACK + "1/" + SDPA + "jit(_bwd_call)/reduce_sum",
    "fusion.19": FWD + "1/mlp/moe/moe_router/dot_general",
    "sort.20": FWD + "2/mlp/moe/moe_dispatch/jit(argsort)/sort",
    "p1t_sum_picks_fwd.21": FWD + "3/mlp/moe/moe_combine/"
    "p1t_sum_picks_fwd/pallas_call",
    "conditional.22": AGAIN + "4/mlp/moe/moe_overflow/cond",
    "ragged-dot-none.23": J + "jvp(loss)/moe/routed_experts",
    "ragged-dot-none.24": J + "transpose(jvp(loss))/moe/routed_experts",
    "fusion.25": FWD + "1/mlp/moe/routed_experts/jit(silu)/mul",
    "fusion.26": J + "jvp(loss)/" + M + "recompute/lm_head/norm/rms_norm/mul",
    "fusion.27": J + "jvp(loss)/" + M + "recompute/lm_head/"
    "head_cross_entropy/dot_general",
    "fusion.28": J + "jvp(loss)/next_token_loss/reduce_sum",
    "fusion.29": J + "optimizer/add",
    # another model's convolution is no layer of this stack
    "fusion.30": J + "jvp(loss)/ResNet/conv/conv2d/conv_general_dilated",
    "fusion.31": FWD + "0/mlp/swiglu/mul",
    "fusion.32": BACK + "0/mlp/down_proj/linear/transpose",
}
KERNELS = {"p1t_flash_attention_fwd.16", "p1t_flash_attention_bwd_dkv.17"}
OP = {"p1t_gated_short_conv_fwd.4", "p1t_gated_short_conv_fwd.7",
      "p1t_gated_short_conv_bwd.8", "fusion.10"}
ROUTE = {"fusion.19", "sort.20", "p1t_sum_picks_fwd.21", "conditional.22"}
PRODUCTS = {"ragged-dot-none.23", "ragged-dot-none.24", "fusion.25"}
# what each scope metric of the cell holds of the scopes above
EXPECT = {
    "attention_ms": KERNELS | {"fusion.18"},
    "recompute_ms": {"fusion.6", "p1t_gated_short_conv_fwd.7", "fusion.13",
                     "conditional.22"},
    "rms_norm_ms": {"fusion.2", "fusion.13", "fusion.26"},
    "moe_ms": ROUTE | PRODUCTS,
    "moe_route_ms": ROUTE,
    "routed_experts_ms": PRODUCTS,
    "attn_proj_ms": {"fusion.12", "fusion.13", "fusion.14", "fusion.15"},
    "short_conv_ms": OP | {"fusion.3", "fusion.5", "fusion.6", "fusion.9"},
    "short_conv_op_ms": OP,
    # the leading dense layer's feed-forward; an expert layer's products
    # lie under ``mlp/moe``
    "dense_ffn_ms": {"fusion.11", "fusion.31", "fusion.32"},
}


def _view(steps=4, ms=1.0):
    ops = {f"%{n} = bf16[8,128]{{1,0}} fusion(%p.{i}), kind=kLoop":
           1e-3 * ms * steps for i, n in enumerate(SCOPES)}
    return {"ops": ops, "step_s": [len(SCOPES) * 1e-3 * ms] * steps,
            "busy_s": len(SCOPES) * 1e-3 * ms * steps}


def test_the_cell_is_in_the_lists_of_the_metrics_its_scopes_give():
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in APPENDED + OWN:
        assert CELL in entries[name]["workloads"], name
    for name in OWN:
        assert FILES[name]["moves"] == "samples_per_s"
        assert FILES[name]["source"] == "device_trace"
    assert CELL in [w["name"] for w in spec.benchmark()["workloads"]
                    if w["config"] == CONFIG]


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_scope_metric_reads_this_steps_scopes(name):
    metric = FILES[name]
    match = re.compile(metric["match"])
    exclude = re.compile(metric["exclude"]) if "exclude" in metric else None
    got = {n for n, path in SCOPES.items() if match.search(path)
           and not (exclude and exclude.search(path))}
    assert got == EXPECT[name]
    value = spec.reducer(metric["reducer"])(
        {"views": [_view(), _view()]}, metric, maps=(SCOPES, {}))
    assert value == pytest.approx(len(EXPECT[name]))     # 1 ms each a step


def test_the_operators_scopes_stand_apart():
    """The operator blocks share no instruction, the op lies inside its
    block, the attention op holds the kernels and no projection, and the
    expert layer's two parts make up its whole."""
    conv, proj = EXPECT["short_conv_ms"], EXPECT["attn_proj_ms"]
    assert EXPECT["short_conv_op_ms"] < conv
    assert not conv & proj and not EXPECT["attention_ms"] & (conv | proj)
    assert not EXPECT["dense_ffn_ms"] & (conv | proj | EXPECT["moe_ms"])
    assert EXPECT["moe_ms"] \
        == EXPECT["moe_route_ms"] | EXPECT["routed_experts_ms"]
    assert not EXPECT["moe_route_ms"] & EXPECT["routed_experts_ms"]


def test_the_ops_share_of_the_hbm_peak_from_a_hand_made_view():
    """``reducers/op_hbm_pct.py``: the bytes the op's calls of a step have
    to move (``model_flops.short_conv_bytes``, at the running cell's size)
    over the time under the op's scope and the chip's HBM peak."""
    metric = FILES["short_conv_op_hbm_roofline"]
    assert metric["reducer"] == "op_hbm_pct" and metric["unit"] == "%"
    assert metric["match"] == FILES["short_conv_op_ms"]["match"]
    cell, cfg = spec.cell(CELL), spec.config(CONFIG)
    moved = sum(mf.short_conv_bytes(
        cfg, traffic.environment(cfg, cell)).values())
    peak = 819e9
    # the time the op would take at half the peak, spread over the four
    # instructions under its scope
    view = _view(ms=1e3 * moved / (0.5 * peak) / len(OP))
    ctx = {"views": [view, view], "peaks": {"hbm_bytes_per_s": peak},
           "cell": cell, "config": cfg}
    maps = (SCOPES, {})
    assert op_hbm_pct.reduce(ctx, metric, maps=maps) == pytest.approx(50.0)
    # the bytes are the op's whatever the trace holds: with the forward
    # that a recomputed segment runs again out of the map, they go over
    # three quarters of the time
    once = {k: v for k, v in SCOPES.items()
            if k != "p1t_gated_short_conv_fwd.7"}
    assert op_hbm_pct.reduce(ctx, metric, maps=(once, {})) \
        == pytest.approx(50.0 * 4 / 3)
    # a rehearsal has no peaks, a run without a trace no view, the parent of
    # the PR that named the scopes no map, another configuration no such
    # function, a step without the op no instruction: nothing, no raise
    bert = {**ctx, "config": spec.config("bert_base"),
            "cell": spec.cell("bert_base.pretrain_s128")}
    bare = {k: v for k, v in SCOPES.items() if k not in OP}
    for other, given in ((({**ctx, "peaks": None}), maps),
                         ({**ctx, "views": []}, maps),
                         (ctx, (None, None)), (ctx, ({}, {})),
                         (bert, maps), (ctx, (bare, {}))):
        assert op_hbm_pct.reduce(other, metric, maps=given) is None


def test_lfm2_flops_hand_count():
    cfg = spec.config(CONFIG)
    env = {"batch": 1, "seq": 16384}
    tokens = 16384
    assert mf.layer_kinds(cfg) == ["conv", "full_attention", "conv", "conv",
                                   "conv"]
    assert mf.head_dim(cfg) == 64 and mf.picks_here_a_token(cfg) == 0.5
    conv = 2 * tokens * (2048 * 6144 + 2048 * 2048)
    pairs = 16384 * 16385 // 2
    attention = (2 * tokens * (2 * 2048 * 2048 + 2 * 2048 * 512)
                 + 2 * pairs * 32 * 2 * 64)
    dense = 2 * tokens * 3 * 2048 * 11776
    expert = 2 * tokens * (2048 * 64 + 3 * 2048 * 1536 * 0.5)
    head = 2 * tokens * 2048 * 8192
    forward = 4 * conv + attention + dense + 4 * expert + head
    assert mf.forward_matmul_flops(cfg, env) == forward
    assert mf.train_step_flops(cfg, env) == 3.0 * forward
    # ISSUE 38's arithmetic: 21.5 TFLOP a step; the four convolution
    # operators 6.6 (30%), the causal scores 3.3 (15%), the held experts
    # 9%, the dense layer 33%, the head 8%
    assert 3.0 * forward == pytest.approx(21.5e12, rel=5e-3)
    assert 3 * 4 * conv == pytest.approx(6.6e12, rel=5e-3)
    assert 3 * 2 * pairs * 32 * 2 * 64 == pytest.approx(3.3e12, rel=5e-3)
    assert 4 * expert / forward == pytest.approx(0.09, abs=0.005)
    assert dense / forward == pytest.approx(0.33, abs=0.005)
    assert head / forward == pytest.approx(0.08, abs=0.005)
    # the kernels: 2 and 5 score-shaped products a visible pair, one call
    per_pair = 2 * 64 * 32
    assert mf.attention_kernel_flops(cfg, env) == {
        "p1t_flash_attention_fwd": 2 * per_pair * pairs,
        "p1t_flash_attention_bwd": 5 * per_pair * pairs}
    assert mf.attention_kernel_flops(cfg, {"batch": 3, "seq": 64}) == {
        "p1t_flash_attention_fwd": 2 * per_pair * 64 * 65 // 2 * 3,
        "p1t_flash_attention_bwd": 5 * per_pair * 64 * 65 // 2 * 3}
    # a brute-force count of the causal pairs
    assert sum(k <= q for q in range(64) for k in range(64)) \
        == mf.causal_pairs({"seq": 64})


def test_the_short_convolutions_bytes_hand_count():
    cfg = spec.config(CONFIG)
    env = {"batch": 1, "seq": 16384}
    whole = 16384 * 2048 * 2            # one [1, 16384, 2048] bfloat16 array
    taps = 2048 * 3 * 2
    assert mf.short_conv_bytes(cfg, env) == {
        "forward": 4 * (4 * whole + taps),
        "backward": 4 * (7 * whole + 2 * taps)}
    # 16 KB a token forward, as ISSUE 38 has it
    assert 4 * whole / 16384 == 16384
    # at the v5e's 819 GB/s a step's calls take 3.6 ms at the least
    total = sum(mf.short_conv_bytes(cfg, env).values())
    assert 1e3 * total / 819e9 == pytest.approx(3.605, rel=1e-3)
    # the program's own closed form, where it traces the op, is the same
    from paddle1_tpu.nn.functional.short_conv import traffic_bytes
    one = traffic_bytes((1, 16384, 3 * 2048), 2, 3)
    assert {k: 4 * v for k, v in one.items()} == mf.short_conv_bytes(cfg, env)


def test_the_cut_has_the_parameters_the_issue_counted():
    """469,284,992 by ISSUE 38's equations: an eighth of the embedding
    (the head is tied to it), the final norm, and five layers with two
    norms each: a dense convolution layer, an attention expert layer,
    three convolution expert layers. The reference holds the expert bias
    besides (64 a layer, a state no optimizer touches); the program holds
    it as a buffer."""
    cfg = spec.config(CONFIG)
    reference, program = (spec.module(k, cfg) for k in ("reference",
                                                        "program"))
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.key(0))
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense = 3 * 2048 * 11776
    experts = 2048 * 64 + 8 * 3 * 2048 * 1536
    norms = 2 * 2048
    want = (8192 * 2048 + 2048 + (conv + dense + norms)
            + (attention + experts + norms) + 3 * (conv + experts + norms))
    assert want == 469_284_992
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    bias = {k: n for k, n in sizes.items() if k.startswith("e_bias.")}
    assert sum(bias.values()) == 4 * 64
    assert sum(sizes.values()) - sum(bias.values()) == want
    assert want * 16 == pytest.approx(7.51e9, rel=1e-3)
    # the program's map names every reference weight once
    leaves = program.leaves(cfg)
    assert sorted(r for _, r, _ in leaves) == sorted(sizes)
    assert len({p for p, _, _ in leaves}) == len(leaves)
    assert "lm_head.proj.weight" not in {p for p, _, _ in leaves}


# LiquidAI/LFM2-24B-A2B config.json, as the catalog beside the
# model-configs guide has it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": (["conv", "conv", "full_attention", "conv"] * 10),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_lfm2_config_keeps_the_published_widths():
    cfg = spec.config(CONFIG)
    assert len(PUBLISHED["layer_types"]) == 40
    assert [i for i, k in enumerate(PUBLISHED["layer_types"])
            if k == "full_attention"] == list(range(2, 40, 4))
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_dense_layers",
                       "num_experts", "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # inside the floors: a whole period and four layers after the leading
    # dense one, 8 routed experts, an eighth of the vocabulary
    kinds = mf.layer_kinds(cfg)
    assert kinds == PUBLISHED["layer_types"][1:6]
    after = kinds[cfg["num_dense_layers"]:]
    assert len(after) >= 4 and sorted(after) == ["conv"] * 3 \
        + ["full_attention"]
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width and its picks
    assert cfg["num_experts"] * cfg["expert_parallel"] == 64
    assert cfg["vocab_size"] * cfg["expert_parallel"] == 65536
    assert 0 <= cfg["expert_rank"] < cfg["expert_parallel"] == 8
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert "Eight chips share each layer" in cfg["deployment"]
    assert "share 0 of 8" in cfg["deployment"]
    assert "What the cut overstates" in cfg["deployment"]
    for key in ("head_dim", "q_norm_k_norm", "tied_head", "router",
                "expert_bias", "layer_pattern", "weights", "token_ids",
                "sequence", "optimizer", "lr_first_step", "recompute"):
        assert cfg["assumed"][key]
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}[cfg["name"]]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # what the rehearsal changes are sizes, never the mechanism
    small = spec.config(CONFIG, rehearsal=True)
    assert small["num_experts_per_tok"] == 4 and small["conv_L_cache"] == 3
    assert small["num_attention_heads"] > small["num_key_value_heads"] > 1
    assert sorted(set(mf.layer_kinds(small))) == ["conv", "full_attention"]
    assert small["num_dense_layers"] == 1 < small["num_hidden_layers"]


def test_the_lfm2_cell():
    cell = spec.cell(CELL)
    loop = spec.loop(cell)
    assert (loop["readback_every"], loop["trace_first_step"],
            loop["trace_steps"], loop["pool"]) == (5, 10, 10, 8)
    cfg = spec.config(CONFIG)
    assert (cell["batch"], cell["dims"]["seq"], cell["chips"]) \
        == (1, 16384, 1)
    assert sorted(cell["fields"]) == ["ids"]
    entry = {w["name"]: w for w in spec.benchmark()["workloads"]}[CELL]
    assert entry == {k: cell[k] for k in ("name", "config", "traffic",
                                          "chips", "why")}
    assert CONFIG in [c["name"] for c in spec.benchmark()["configs"]]
    listed = {m["name"] for m in spec.per_layer_for(CELL)}
    assert set(APPENDED + OWN) | {"device_step_ms", "step_mfu_pct",
                                  "peak_hbm_gib",
                                  "compiles_in_window"} <= listed
    # every id lies in the slice
    env = traffic.environment(cfg, cell)
    small = {**env, "batch": 2, "seq": 64}
    for batch in traffic.batches(cell, small, 2 ** 31 + 7, 2):
        assert batch["ids"].shape == (2, 64)
        assert 0 <= batch["ids"].min() and batch["ids"].max() < 8192
