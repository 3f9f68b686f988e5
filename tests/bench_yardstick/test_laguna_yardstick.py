"""CPU tests of what PR 47 added to the yardstick: the cell in the lists of
the accepted metrics its scopes give, the one scope metric of its own (the
per-head output gate) on a synthetic view, the FLOP count of a step whose
attention layers differ in kind and in their number of query heads
(visible pairs by kind, each as often as its layer has heads; the
benchmark's own closed form held equal to the program's), the kernels'
share of the matrix unit from that count, the parameter count of the cut,
and the configuration's and cell's files. Membership only: which entry
stands where in a list is nobody's to say
(``test_the_door_stays_open.py``). (That the rehearsal passes ``correct``
in float32 and the bfloat16 control fails it, and the count against XLA's
cost analysis: ``test_bench_yardstick.py`` runs them for every
configuration there is.) Nothing here is a device metric."""

import json
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
from benchmarks.model_flops import laguna_xs2_33b_a3b as mf  # noqa: E402
from benchmarks.reducers import kernel_mxu_pct  # noqa: E402

FILES = spec.layer_metrics()
CELL = "laguna_xs2_33b_a3b.pretrain_s16384"
CONFIG = "laguna_xs2_33b_a3b"
# the accepted metrics whose lists of cells gained this one, and its own
APPENDED = ("forward_ms", "backward_ms", "optimizer_ms", "unscoped_ms",
            "host_step_ms", "attention_ms", "window_attention_ms",
            "global_attention_ms", "attn_proj_ms", "recompute_ms",
            "rms_norm_ms", "moe_ms", "moe_route_ms", "routed_experts_ms",
            "shared_experts_ms", "dense_ffn_ms", "moe_held_picks_pct",
            "moe_expert_rows_max", "moe_late_picks",
            "attention_kernel_mxu_roofline")
OWN = ("attn_gate_ms",)

# scopes as the step carries them: layer 0 full attention and dense,
# layers 1-3 under the window, layer 4 full, all four sparse
J = "jit(counted_step)/"
M = "LagunaForPretraining/"
FWD = J + "jvp(loss)/" + M + "layers/recompute/"
BACK = (J + "transpose(jvp(loss))/" + M + "layers/recompute/jvp(loss)/" + M
        + "layers/recompute/checkpoint/")
AGAIN = BACK + "rematted_computation/"
GLOBAL = "self_attn/global/scaled_dot_product_attention/"
WINDOW = "self_attn/window/scaled_dot_product_attention/"
SCOPES = {
    "fusion.1": FWD + "0/input_layernorm/rms_norm/mul",
    "fusion.2": FWD + "0/self_attn/q_proj/linear/dot_general",
    "fusion.3": FWD + "0/self_attn/rotary_embedding/mul",
    "p1t_flash_attention_fwd.4": FWD + "0/" + GLOBAL
    + "jit(_fwd_call)/p1t_flash_attention_fwd/pallas_call",
    "fusion.5": FWD + "0/self_attn/gate/gate_proj/linear/dot_general",
    "fusion.6": FWD + "0/self_attn/gate/sigmoid/logistic",
    "fusion.7": FWD + "0/self_attn/gate/mul",
    "fusion.8": FWD + "0/self_attn/o_proj/linear/dot_general",
    "fusion.9": FWD + "0/mlp/gate_proj/linear/dot_general",
    "fusion.10": FWD + "0/mlp/swiglu/mul",
    "p1t_flash_attention_fwd.11": FWD + "2/" + WINDOW
    + "jit(_fwd_call)/p1t_flash_attention_fwd/pallas_call",
    "p1t_flash_attention_bwd_dkv.12": BACK + "2/" + WINDOW
    + "jit(_bwd_call)/p1t_flash_attention_bwd_dkv/pallas_call",
    "p1t_flash_attention_bwd_dkv.13": BACK + "4/" + GLOBAL
    + "jit(_bwd_call)/p1t_flash_attention_bwd_dkv/pallas_call",
    "fusion.14": BACK + "3/self_attn/gate/mul",
    "fusion.15": AGAIN + "3/self_attn/gate/gate_proj/linear/dot_general",
    "fusion.16": BACK + "1/self_attn/gate/gate_proj/linear/transpose",
    "fusion.17": FWD + "1/mlp/moe/moe_router/dot_general",
    "ragged-dot-none.18": FWD + "1/mlp/moe/routed_experts",
    "fusion.19": FWD + "1/mlp/moe/shared_experts/gate_proj/linear/"
    "dot_general",
    "fusion.20": AGAIN + "4/mlp/moe/shared_experts/swiglu/mul",
    "fusion.21": J + "optimizer/add",
    # a gate of another sort is no attention layer's
    "fusion.22": J + "jvp(loss)/Other/layers/0/mlp/gate_proj/linear/"
    "dot_general",
    "fusion.23": J + "jvp(loss)/Other/gate_unit/mul",
}
GATE = {"fusion.5", "fusion.6", "fusion.7", "fusion.14", "fusion.15",
        "fusion.16"}
KERNELS = {"p1t_flash_attention_fwd.4", "p1t_flash_attention_fwd.11",
           "p1t_flash_attention_bwd_dkv.12", "p1t_flash_attention_bwd_dkv.13"}
# what each scope metric of the cell holds of the scopes above
EXPECT = {
    "attn_gate_ms": GATE,
    "attention_ms": KERNELS,
    "window_attention_ms": {"p1t_flash_attention_fwd.11",
                            "p1t_flash_attention_bwd_dkv.12"},
    "global_attention_ms": {"p1t_flash_attention_fwd.4",
                            "p1t_flash_attention_bwd_dkv.13"},
    # the projections, rotary and the gate: the layer's scope less the op
    "attn_proj_ms": GATE | {"fusion.2", "fusion.3", "fusion.8"},
    "dense_ffn_ms": {"fusion.9", "fusion.10", "fusion.22"},
    "shared_experts_ms": {"fusion.19", "fusion.20"},
    "moe_ms": {"fusion.17", "ragged-dot-none.18", "fusion.19", "fusion.20"},
    "moe_route_ms": {"fusion.17"},
    "routed_experts_ms": {"ragged-dot-none.18"},
    "rms_norm_ms": {"fusion.1"},
    "recompute_ms": {"fusion.15", "fusion.20"},
}


def _view(names=SCOPES, steps=4, ms=1.0):
    ops = {f"%{n} = bf16[8,128]{{1,0}} fusion(%p.{i}), kind=kLoop":
           1e-3 * ms * steps for i, n in enumerate(names)}
    return {"ops": ops, "step_s": [len(names) * 1e-3 * ms] * steps,
            "busy_s": len(names) * 1e-3 * ms * steps}


def test_the_cell_is_in_the_lists_of_the_metrics_its_scopes_give():
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in APPENDED + OWN:
        assert CELL in entries[name]["workloads"], name
    metric, entry = FILES["attn_gate_ms"], entries["attn_gate_ms"]
    assert entry["workloads"] == [CELL]
    assert metric["reducer"] == "scope_ms"
    assert metric["match"] == "/self_attn/gate(/|$)"
    assert "exclude" not in metric
    for key in ("unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert (metric["unit"], metric["better"], metric["source"],
            metric["moves"]) == ("ms", "lower", "device_trace",
                                 "samples_per_s")
    assert metric["layer"] == FILES["attention_ms"]["layer"]
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


def test_the_gate_is_the_attention_layers_and_not_the_ops():
    """The gate's instructions lie in the attention layer's scope and
    outside the attention op's, so ``attn_proj_ms`` holds them and
    ``attention_ms`` does not; a feed-forward's ``gate_proj`` is no
    attention gate; a program without the scope (the parent of the PR that
    named it) gives nothing, and does not raise."""
    assert EXPECT["attn_gate_ms"] <= EXPECT["attn_proj_ms"]
    assert not EXPECT["attn_gate_ms"] & EXPECT["attention_ms"]
    assert not EXPECT["attn_gate_ms"] & EXPECT["dense_ffn_ms"]
    assert (EXPECT["window_attention_ms"] | EXPECT["global_attention_ms"]
            == EXPECT["attention_ms"])
    reduce = spec.reducer("scope_ms")
    unnamed = {n: s.replace("/self_attn/gate/", "/self_attn/")
               for n, s in SCOPES.items()}
    metric = FILES["attn_gate_ms"]
    assert reduce({"views": [_view()]}, metric, maps=(unnamed, {})) == 0.0
    assert reduce({"views": [_view()]}, metric, maps=(None, None)) is None
    assert reduce({"views": []}, metric, maps=(SCOPES, {})) is None


def test_laguna_flops_hand_count():
    cfg = spec.config(CONFIG)
    env = {"batch": 1, "seq": 16384}
    tokens = 16384
    assert mf.attention_layers(cfg) == [(None, 48), (512, 64), (512, 64),
                                        (512, 64), (None, 48)]
    assert mf.picks_here_a_token(cfg) == 0.5
    causal = 16384 * 16385 // 2
    band = 16384 * 512 - 512 * 511 // 2
    assert (causal, band) == (134_225_920, 8_257_792)
    assert mf.head_pairs(cfg, env) == 2 * 48 * causal + 3 * 64 * band

    def projections(heads):
        return 2 * tokens * (2 * 2048 * heads * 128 + 2 * 2048 * 1024
                             + 2048 * heads)
    gates = 2 * tokens * 2048 * (2 * 48 + 3 * 64)
    scores_global = 2 * 2 * 48 * causal * 2 * 128
    scores_window = 2 * 3 * 64 * band * 2 * 128
    dense = 2 * tokens * 3 * 2048 * 8192
    routers = 4 * 2 * tokens * 2048 * 256
    shared = 4 * 2 * tokens * 3 * 2048 * 512
    routed = 4 * 2 * tokens * 3 * 2048 * 512 * 0.5
    head = 2 * tokens * 2048 * 12544
    forward = (2 * projections(48) + 3 * projections(64) + scores_global
               + scores_window + dense + routers + shared + routed + head)
    assert mf.forward_matmul_flops(cfg, env) == forward
    assert mf.train_step_flops(cfg, env) == 3.0 * forward
    # ISSUE 47's arithmetic: 48.7 TFLOP a step; the two global layers'
    # scores 40.6%, the three window layers' 5.0%, the attention
    # projections 34.7%, the dense layer 10.2%, the head 5.2%, the shared
    # experts 2.5%, the held routed experts 1.3%, routers and gates 0.5%
    assert 3.0 * forward == pytest.approx(48.7e12, rel=1e-3)
    for part, share in ((scores_global, 0.406), (scores_window, 0.050),
                        (2 * projections(48) + 3 * projections(64) - gates,
                         0.347), (dense, 0.102), (head, 0.052),
                        (shared, 0.025), (routed, 0.013),
                        (routers + gates, 0.005)):
        assert part / forward == pytest.approx(share, abs=0.001)
    # attention of two head counts under the gate: four fifths
    assert (scores_global + scores_window + 2 * projections(48)
            + 3 * projections(64)) / forward == pytest.approx(0.80, abs=0.005)
    # a window layer shows 6% of a causal layer's pairs at 16,384 keys
    assert band / causal == pytest.approx(0.0615, abs=1e-3)
    # the kernels: 2 and 5 score-shaped products a visible pair, each
    # layer's pairs as often as it has query heads
    per_pair = 2 * 128
    pairs = 2 * 48 * causal + 3 * 64 * band
    assert mf.attention_kernel_flops(cfg, env) == {
        "p1t_flash_attention_fwd": 2 * per_pair * pairs,
        "p1t_flash_attention_bwd": 5 * per_pair * pairs}
    small = 64 * 65 // 2 * (2 * 48 + 3 * 64) * 3   # a row under the window
    assert mf.attention_kernel_flops(cfg, {"batch": 3, "seq": 64}) == {
        "p1t_flash_attention_fwd": 2 * per_pair * small,
        "p1t_flash_attention_bwd": 5 * per_pair * small}


@pytest.mark.parametrize("seq,window", [(64, 1), (64, 5), (64, 64),
                                        (64, 100), (16384, 512),
                                        (1024, 512), (512, 512)])
def test_the_benchmarks_pair_count_is_the_programs(seq, window):
    """Written twice, once on either side (neither imports the other):
    ``model_flops``' closed form, the program's ``mask_rules`` count, and
    at a small size a brute-force count of the band."""
    from paddle1_tpu.ops.pallas import mask_rules
    rule = mask_rules.SlidingWindow(window)
    assert mf.visible_pairs(seq, window) == rule.pairs(seq, seq)
    assert mf.visible_pairs(seq) == mask_rules.CAUSAL.pairs(seq, seq)
    if seq <= 64:
        assert mf.visible_pairs(seq, window) == sum(
            0 <= q - k < window for q in range(seq) for k in range(seq))


def test_the_kernels_share_of_the_matrix_unit_from_a_hand_made_view():
    """``attention_kernel_mxu_roofline`` in this cell: the two kernels'
    FLOPs on visible pairs by layer kind and head count over the time of
    their ten instructions a step (five layers forward, five backward)."""
    metric = FILES["attention_kernel_mxu_roofline"]
    cell, cfg = spec.cell(CELL), spec.config(CONFIG)
    flops = mf.attention_kernel_flops(cfg, traffic.environment(cfg, cell))
    peak = 197e12
    named = [f"p1t_flash_attention_fwd.{i}" for i in range(5)] \
        + [f"p1t_flash_attention_bwd_dkv.{i}" for i in range(5, 10)]
    seconds = sum(flops.values()) / (0.5 * peak)
    view = _view(named + ["fusion.1"], ms=1e3 * seconds / len(named))
    ctx = {"views": [view, view], "peak_flops_per_s": peak, "cell": cell,
           "config": cfg}
    assert kernel_mxu_pct.reduce(ctx, metric) == pytest.approx(50.0)
    # at the MXU's peak the kernels of a step would take 132 ms
    assert 1e3 * sum(flops.values()) / peak == pytest.approx(131.6, rel=1e-3)


def test_the_cut_has_the_parameters_the_issue_counted():
    """490,297,344 by ISSUE 47's equations, counted from the reference's
    shapes and from the built model."""
    cfg = spec.config(CONFIG)
    reference, program = (spec.module(k, cfg) for k in ("reference",
                                                        "program"))

    def attention(heads):
        return 2 * 2048 * heads * 128 + 2 * 2048 * 1024 + 2048 * heads
    expert = 3 * 2048 * 512
    dense_layer = attention(48) + 3 * 2048 * 8192 + 2 * 2048
    assert (attention(48), attention(64)) == (29_458_432, 37_879_808)

    def sparse_layer(heads):
        return attention(heads) + 2048 * 256 + 17 * expert + 2 * 2048
    assert dense_layer == pytest.approx(79.79e6, rel=1e-4)
    assert sparse_layer(64) == pytest.approx(91.88e6, rel=1e-4)
    assert sparse_layer(48) == pytest.approx(83.46e6, rel=1e-4)
    want = (dense_layer + 3 * sparse_layer(64) + sparse_layer(48)
            + 2 * 12544 * 2048 + 2048)
    assert want == 490_297_344 == cfg["parameters"]
    assert want * 16 == pytest.approx(7.84e9, rel=1e-3)
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.key(0))
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    biases = {k for k in sizes if k.startswith("e_bias.")}   # a state
    assert sum(v for k, v in sizes.items() if k not in biases) == want
    leaves = program.leaves(cfg)
    assert sorted(r for _, r, _ in leaves) == sorted(sizes)
    assert len({p for p, _, _ in leaves}) == len(leaves)


def test_laguna_config_keeps_the_published_widths():
    """Every key of the catalog's row (the ``model-configs`` guide's
    ``architectures.jsonl``, written out here) under its published value
    but the three under ``reduced``."""
    with open(os.path.join(HERE, "laguna_xs2_published.json")) as f:
        published = json.load(f)
    cfg = spec.config(CONFIG)
    assert len(published["layer_types"]) == 40 \
        == len(published["num_attention_heads_per_layer"]) \
        == len(published["mlp_layer_types"])
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(cfg["reduced"])
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    # inside the floors: the dense layer once and a whole period of the
    # four that follow, 16 routed experts, an eighth of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["pipeline_first_layer"]) == (5, 0)
    assert spec.module("reference", cfg).layer_kinds(cfg) == [
        ("full_attention", 48, "dense"), ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"), ("full_attention", 48, "sparse")]
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    # the router keeps its published width and its picks
    assert cfg["num_experts"] * cfg["expert_parallel"] == 256
    assert 0 <= cfg["expert_rank"] < cfg["expert_parallel"] == 16
    assert cfg["optimizer"]["lr_schedule"]["peak"] == 3e-5
    assert (cfg["initializer_range"], cfg["embedding_initializer_range"]) \
        == (0.02, 0.7)
    assert cfg["residual_initializer_range"] == pytest.approx(
        0.02 / (2 * published["num_hidden_layers"]) ** 0.5, rel=1e-5)
    assert "Sixteen chips share each layer" in cfg["deployment"]
    assert "share 0 of 16" in cfg["deployment"]
    assert "What the cut overstates" in cfg["deployment"]
    for key in ("gate", "router", "norms", "rotary", "window",
                "shared_expert", "weights", "token_ids", "sequence",
                "optimizer", "lr_first_step", "recompute"):
        assert cfg["assumed"][key], key
    assert "inference" in cfg["assumed"]["gate"]
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}[cfg["name"]]
    assert entry["source"] == "https://huggingface.co/poolside/" \
        "Laguna-XS.2/blob/main/config.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # what the rehearsal changes are sizes, never the mechanism
    small = spec.config(CONFIG, rehearsal=True)
    assert small["num_experts_per_tok"] == 8
    assert small["layer_types"] == published["layer_types"]
    assert small["mlp_layer_types"] == published["mlp_layer_types"]
    assert small["rope_parameters"] == published["rope_parameters"]
    assert set(small["num_attention_heads_per_layer"]) == {6, 8} \
        and small["num_key_value_heads"] == 1
    assert small["sliding_window"] < spec.cell(CELL)["rehearsal"]["dims"][
        "seq"]


def test_the_laguna_cell():
    cell = spec.cell(CELL)
    loop = spec.loop(cell)
    assert (loop["readback_every"], loop["trace_first_step"],
            loop["trace_steps"], loop["pool"]) == (5, 10, 10, 8)
    cfg = spec.config(CONFIG)
    assert (cell["batch"], cell["dims"]["seq"], cell["chips"]) \
        == (1, 16384, 1)
    # four times YaRN's original context, inside the declared positions
    full = cfg["rope_parameters"]["full_attention"]
    assert cell["dims"]["seq"] == 4 * full["original_max_position_embeddings"]
    assert cell["dims"]["seq"] <= cfg["max_position_embeddings"]
    assert sorted(cell["fields"]) == ["ids"]
    entry = {w["name"]: w for w in spec.benchmark()["workloads"]}[CELL]
    assert entry == {k: cell[k] for k in ("name", "config", "traffic",
                                          "chips", "why")}
    listed = {m["name"] for m in spec.per_layer_for(CELL)}
    assert set(APPENDED + OWN) | {"device_step_ms", "step_mfu_pct",
                                  "peak_hbm_gib",
                                  "compiles_in_window"} <= listed
    assert {m["name"] for m in spec.end_to_end_for(CELL)} \
        >= {"samples_per_s", "step_ms_p95", "setup_s"}
    # every id lies in the slice
    env = traffic.environment(cfg, cell)
    small = {**env, "batch": 2, "seq": 64}
    for batch in traffic.batches(cell, small, 2 ** 31 + 7, 2):
        assert batch["ids"].shape == (2, 64)
        assert 0 <= batch["ids"].min() and batch["ids"].max() < 12544
