"""CPU tests of what PR 50 added to the yardstick: the cell in the lists of
the accepted metrics its scopes give, its five own metrics (the Mamba-2
mixer, its two projections, the scan's scope, the scan's share of the HBM
roofline and its kernels' share of the matrix unit) on a synthetic view,
the FLOP count of a step whose layers are one mixer alone, the scan's
bytes held equal to what the program counts where it traces the op, the
parameter count of the cut, and the configuration's and cell's files
against the published keys (``nemotron3_nano_published.json`` beside this
file: the catalog's row). Membership only: which entry stands where in a
list is nobody's to say (``test_the_door_stays_open.py``). (That the
rehearsal passes ``correct`` in float32 and the bfloat16 control fails it,
and the count against XLA's cost analysis: ``test_bench_yardstick.py``
runs them for every configuration there is.) Nothing here is a device
metric."""

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
from benchmarks.model_flops import nemotron3_nano_30b_a3b as mf  # noqa: E402
from benchmarks.reducers import kernel_mxu_pct, op_hbm_pct  # noqa: E402

FILES = spec.layer_metrics()
CELL = "nemotron3_nano_30b_a3b.pretrain_s8192"
CONFIG = "nemotron3_nano_30b_a3b"
# the accepted metrics whose lists of cells gained this one, and its own
APPENDED = ("forward_ms", "backward_ms", "optimizer_ms", "attention_ms",
            "unscoped_ms", "host_step_ms", "recompute_ms", "rms_norm_ms",
            "moe_ms", "moe_route_ms", "routed_experts_ms",
            "shared_experts_ms", "attn_proj_ms", "global_attention_ms",
            "attention_kernel_mxu_roofline", "moe_held_picks_pct",
            "moe_expert_rows_max", "moe_late_picks")
OWN = ("mamba_ms", "mamba_proj_ms", "ssd_scan_ms",
       "ssd_scan_op_hbm_roofline", "ssd_kernel_mxu_roofline")
with open(os.path.join(HERE, "nemotron3_nano_published.json")) as f:
    PUBLISHED = json.load(f)

# scopes as the step carries them: layers 0, 2 Mamba-2, 1 experts, 5
# attention
J = "jit(counted_step)/"
M = "NemotronHForPretraining/"
FWD = J + "jvp(loss)/" + M + "layers/recompute/"
BACK = (J + "transpose(jvp(loss))/" + M + "layers/recompute/jvp(loss)/" + M
        + "layers/recompute/checkpoint/")
AGAIN = BACK + "rematted_computation/"
SDPA = "self_attn/global/scaled_dot_product_attention/"
SCOPES = {
    "fusion.1": FWD + "0/norm/rms_norm/mul",
    "fusion.2": FWD + "0/mamba/in_proj/linear/dot_general",
    "fusion.3": FWD + "0/mamba/conv/causal_conv_silu/mul",
    "p1t_ssd_fwd.4": FWD + "0/mamba/ssd_scan/p1t_ssd_fwd/pallas_call",
    "fusion.5": FWD + "0/mamba/ssd_scan/cumsum",
    "fusion.6": FWD + "0/mamba/gated_norm/gated_rms_norm/mul",
    "fusion.7": FWD + "0/mamba/out_proj/linear/dot_general",
    "p1t_ssd_fwd.8": AGAIN + "2/mamba/ssd_scan/p1t_ssd_fwd/pallas_call",
    "p1t_ssd_bwd.9": BACK + "2/mamba/ssd_scan/p1t_ssd_bwd/pallas_call",
    "fusion.10": BACK + "2/mamba/out_proj/linear/transpose",
    "fusion.11": AGAIN + "2/mamba/in_proj/linear/dot_general",
    "fusion.12": FWD + "5/self_attn/q_proj/linear/dot_general",
    "fusion.13": BACK + "5/self_attn/o_proj/linear/transpose",
    "p1t_flash_attention_fwd.14": FWD + "5/" + SDPA
    + "jit(_fwd_call)/p1t_flash_attention_fwd/pallas_call",
    "p1t_flash_attention_bwd_dkv.15": BACK + "5/" + SDPA
    + "jit(_bwd_call)/p1t_flash_attention_bwd_dkv/pallas_call",
    "fusion.16": FWD + "1/mlp/moe/moe_router/dot_general",
    "sort.17": FWD + "1/mlp/moe/moe_dispatch/jit(argsort)/sort",
    "ragged-dot-none.18": J + "jvp(loss)/moe/routed_experts",
    "fusion.19": FWD + "1/mlp/moe/routed_experts/square",
    "fusion.20": FWD + "1/mlp/moe/shared_experts/up_proj/linear/dot_general",
    "fusion.21": FWD + "1/mlp/moe/shared_experts/ffn_activation/square",
    "fusion.22": J + "jvp(loss)/" + M + "recompute/lm_head/norm/rms_norm/mul",
    "fusion.23": J + "optimizer/add",
    # another model's operator is no mixer of this stack
    "fusion.24": J + "jvp(loss)/Lfm2ForPretraining/layers/0/conv/"
    "gated_short_conv/mul",
}
SCAN = {"p1t_ssd_fwd.4", "fusion.5", "p1t_ssd_fwd.8", "p1t_ssd_bwd.9"}
PROJ = {"fusion.2", "fusion.7", "fusion.10", "fusion.11"}
KERNELS = {"p1t_flash_attention_fwd.14", "p1t_flash_attention_bwd_dkv.15"}
# what each scope metric of the cell holds of the scopes above
EXPECT = {
    "mamba_ms": SCAN | PROJ | {"fusion.3", "fusion.6"},
    "mamba_proj_ms": PROJ,
    "ssd_scan_ms": SCAN,
    "attention_ms": KERNELS,
    "global_attention_ms": KERNELS,
    "attn_proj_ms": {"fusion.12", "fusion.13"},
    "recompute_ms": {"p1t_ssd_fwd.8", "fusion.11"},
    "rms_norm_ms": {"fusion.1", "fusion.22"},
    "moe_ms": {"fusion.16", "sort.17", "ragged-dot-none.18", "fusion.19",
               "fusion.20", "fusion.21"},
    "moe_route_ms": {"fusion.16", "sort.17"},
    "routed_experts_ms": {"ragged-dot-none.18", "fusion.19"},
    "shared_experts_ms": {"fusion.20", "fusion.21"},
}


def _view(steps=4, ms=1.0):
    ops = {f"%{n} = bf16[8,128]{{1,0}} fusion(%p.{i}), kind=kLoop":
           1e-3 * ms * steps for i, n in enumerate(SCOPES)}
    return {"ops": ops, "step_s": [len(SCOPES) * 1e-3 * ms] * steps,
            "busy_s": len(SCOPES) * 1e-3 * ms * steps}


def _sizes():
    cell, cfg = spec.cell(CELL), spec.config(CONFIG)
    return cell, cfg, traffic.environment(cfg, cell)


def test_the_cell_is_in_the_lists_of_the_metrics_its_scopes_give():
    entries = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in APPENDED + OWN:
        assert CELL in entries[name]["workloads"], name
    for name in OWN:
        assert FILES[name]["moves"] == "samples_per_s"
        assert FILES[name]["source"] == "device_trace"
        assert {k: entries[name][k] for k in (
            "name", "unit", "better", "source", "layer", "moves")} \
            == {k: FILES[name][k] for k in (
                "name", "unit", "better", "source", "layer", "moves")}
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


def test_the_mixers_scopes_stand_apart():
    """A layer is one mixer alone: the three kinds share no instruction,
    the scan and the projections lie inside the Mamba-2 mixer and apart."""
    mamba = EXPECT["mamba_ms"]
    assert EXPECT["ssd_scan_ms"] < mamba and EXPECT["mamba_proj_ms"] < mamba
    assert not EXPECT["ssd_scan_ms"] & EXPECT["mamba_proj_ms"]
    others = (EXPECT["attention_ms"] | EXPECT["attn_proj_ms"]
              | EXPECT["moe_ms"])
    assert not mamba & others
    assert not EXPECT["moe_ms"] & (EXPECT["attention_ms"]
                                   | EXPECT["attn_proj_ms"])
    assert FILES["ssd_scan_op_hbm_roofline"]["match"] \
        == FILES["ssd_scan_ms"]["match"]


def test_the_scans_share_of_the_hbm_peak_from_a_hand_made_view():
    """``reducers/op_hbm_pct.py``: the bytes the op's calls of a step have
    to move (``model_flops.ssd_scan_bytes``, at the running cell's size)
    over the time under the op's scope and the chip's HBM peak, whatever
    implements the op."""
    metric = FILES["ssd_scan_op_hbm_roofline"]
    assert metric["reducer"] == "op_hbm_pct" and metric["unit"] == "%"
    cell, cfg, env = _sizes()
    moved = sum(mf.ssd_scan_bytes(cfg, env).values())
    peak = 819e9
    view = _view(ms=1e3 * moved / (0.5 * peak) / len(SCAN))
    ctx = {"views": [view, view], "peaks": {"hbm_bytes_per_s": peak},
           "cell": cell, "config": cfg}
    assert op_hbm_pct.reduce(ctx, metric, maps=(SCOPES, {})) \
        == pytest.approx(50.0)
    # a program without the op (the parent of this PR), a rehearsal, a
    # run without a trace, another configuration: nothing, no raise
    bare = {k: v for k, v in SCOPES.items() if k not in SCAN}
    bert = {**ctx, "config": spec.config("bert_base"),
            "cell": spec.cell("bert_base.pretrain_s128")}
    for other, given in ((ctx, (bare, {})), (ctx, (None, None)),
                         ({**ctx, "peaks": None}, (SCOPES, {})),
                         ({**ctx, "views": []}, (SCOPES, {})),
                         (bert, (SCOPES, {}))):
        assert op_hbm_pct.reduce(other, metric, maps=given) is None


def test_the_scan_kernels_share_of_the_matrix_unit_from_a_hand_made_view():
    """``reducers/kernel_mxu_pct.py`` over ``ssd_kernel_flops``: the two
    kernels by their names, the forward's two calls a layer (the
    recomputed one with it) in the time and once in the FLOPs."""
    metric = FILES["ssd_kernel_mxu_roofline"]
    assert metric["reducer"] == "kernel_mxu_pct" and metric["unit"] == "%"
    cell, cfg, env = _sizes()
    flops = mf.ssd_kernel_flops(cfg, env)
    assert set(flops) == {"p1t_ssd_fwd", "p1t_ssd_bwd"}
    assert flops["p1t_ssd_bwd"] == 2 * flops["p1t_ssd_fwd"]
    peak = 197e12
    seconds = sum(flops.values()) / (0.25 * peak)
    ops = {"%p1t_ssd_fwd.4 = bf16[1,8192,4096]{2,1,0} custom-call(%a)":
           0.25 * seconds * 2,
           "%p1t_ssd_fwd.8 = bf16[1,8192,4096]{2,1,0} custom-call(%a)":
           0.25 * seconds * 2,
           "%p1t_ssd_bwd.9 = (bf16[1,8192,4096]{2,1,0}) custom-call(%a)":
           0.5 * seconds * 2,
           "%p1t_flash_attention_fwd.14 = bf16[8]{0} custom-call(%a)": 1.0,
           "%fusion.2 = bf16[8]{0} fusion(%a)": 1.0}
    view = {"ops": ops, "step_s": [1.0, 1.0]}
    ctx = {"views": [view], "peak_flops_per_s": peak, "cell": cell,
           "config": cfg}
    assert kernel_mxu_pct.reduce(ctx, metric) == pytest.approx(25.0)
    # the attention kernels' share reads its own names in the same view
    both = kernel_mxu_pct.reduce(ctx, FILES["attention_kernel_mxu_roofline"])
    assert both == pytest.approx(
        100 * sum(mf.attention_kernel_flops(cfg, env).values()) / 0.5 / peak)
    # a trace without the kernels (the parent, or the composition): nothing
    none = {"ops": {k: v for k, v in ops.items() if "ssd" not in k},
            "step_s": [1.0, 1.0]}
    assert kernel_mxu_pct.reduce({**ctx, "views": [none]}, metric) is None


def test_nemotron3_flops_hand_count():
    _, cfg, env = _sizes()
    tokens = 8192
    scan = (2 * 64.5 * 8 * 128 + 2 * 64.5 * 64 * 64 + 4 * 64 * 64 * 128)
    assert mf.scan_flops_a_token(cfg, env) == scan
    assert scan == pytest.approx(2.76e6, rel=1e-2)      # ISSUE 50's 2.75 M
    mamba = tokens * (2 * 2688 * 10304 + 2 * 4096 * 2688 + scan)
    attention = (2 * tokens * (2 * 2688 * 4096 + 2 * 2688 * 256)
                 + 2 * (8192 * 8193 // 2) * 32 * 2 * 128)
    experts = 2 * tokens * (2688 * 128 + 2 * 2688 * 3712
                            + 2 * 2688 * 1856 * 6 / 16)
    head = 2 * tokens * 2688 * 16384
    forward = 4 * mamba + attention + 4 * experts + head
    assert mf.train_step_flops(cfg, env) == pytest.approx(3.0 * forward,
                                                          rel=1e-12)
    # ISSUE 50's shares of a token's 717 M forward: 45, 27, 16, 12%
    assert forward / tokens == pytest.approx(717e6, rel=5e-3)
    for part, share in ((4 * mamba, 0.45), (4 * experts, 0.27),
                        (attention, 0.16), (head, 0.12)):
        assert part / forward == pytest.approx(share, abs=0.006)
    assert mf.train_step_flops(cfg, env) == pytest.approx(17.6e12, rel=5e-3)
    assert mf.attention_kernel_flops(cfg, env) == {
        "p1t_flash_attention_fwd": 2 * (8192 * 8193 // 2) * 32 * 2 * 128,
        "p1t_flash_attention_bwd": 2 * (8192 * 8193 // 2) * 32 * 5 * 128}


def test_the_scans_bytes_hand_count_and_the_programs_own():
    _, cfg, env = _sizes()
    whole = 8192 * 4096 * 2             # one [1, 8192, 64, 64] bf16 array
    small = 8192 * (2 * 8 * 128 + 64) * 2
    assert mf.ssd_scan_bytes(cfg, env) == {
        "forward": 4 * (2 * whole + small),
        "backward": 4 * (3 * whole + 2 * small)}
    # about 20 KB a token a layer, as ISSUE 50 has it
    assert (2 * whole + small) / 8192 == pytest.approx(20.6e3, rel=1e-2)
    # the program's own closed form, where it traces the op, is the same
    from paddle1_tpu.nn.functional.ssd import traffic_bytes
    one = traffic_bytes((1, 8192, 64, 64), 8, 128, 2)
    assert {k: 4 * v for k, v in one.items()} == mf.ssd_scan_bytes(cfg, env)


@pytest.mark.drives_a_run
def test_the_scans_bytes_are_what_a_traced_rehearsal_step_counts():
    """One step of the program at the rehearsal's size (float32): what
    ``ssd_scan_bytes_total{pass}`` counted when the step was traced is the
    benchmark's closed form at that size, the backward's once and the
    forward's a whole number of times (jax traces a call's forward more
    than once under ``jax.checkpoint``)."""
    from paddle1_tpu import obs
    from paddle1_tpu.obs.registry import process_group
    cell = spec.cell(CELL)
    cfg = spec.config(CONFIG, rehearsal=True)
    env = traffic.environment(cfg, cell, rehearsal=True)
    program, reference = (spec.module(k, cfg) for k in ("program",
                                                        "reference"))
    w = jax.jit(lambda k: reference.init_params(cfg, k))(jax.random.key(0))
    obs.reset_process_registry()
    engine = program.build(
        cfg, env, {p: (w[r] if i is None else w[r][i])
                   for p, r, i in program.leaves(cfg)},
        jax.devices()[:1])["engine"]
    batch = traffic.batches(cell, env, 0, 1)[0]
    float(engine.step(engine.shard_batch(batch), lr=1e-5))
    want = mf.ssd_scan_bytes(cfg, env, itemsize=4)
    counted = {which: process_group("pass").child(which).counter(
        "ssd_scan_bytes_total").value for which in want}
    assert counted["backward"] == want["backward"] > 0
    assert counted["forward"] % want["forward"] == 0 < counted["forward"]
    obs.reset_process_registry()


def test_the_cut_has_the_parameters_the_issue_counted():
    """666,962,944 by ISSUE 50's equations (its 667.0 M): an eighth of the
    embedding and of the untied head, the final norm, and nine layers of a
    norm and one mixer each: four Mamba-2, four of experts, one of
    attention. The reference holds the selection bias besides (128 a
    layer, a state no optimizer touches); the program holds it as a
    buffer."""
    cfg = spec.config(CONFIG)
    reference, program = (spec.module(k, cfg) for k in ("reference",
                                                        "program"))
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.key(0))
    mamba = (2688 * (4096 + 6144 + 64) + 6144 * 4 + 6144 + 3 * 64 + 4096
             + 4096 * 2688)
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256
    experts = 2688 * 128 + 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712
    want = (2 * 16384 * 2688 + 2688 + 9 * 2688
            + 4 * mamba + attention + 4 * experts)
    assert want == 666_962_944 == cfg["parameters"]
    assert mamba + 2688 == 38_744_896 and attention + 2688 == 23_399_040
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    bias = {k: n for k, n in sizes.items() if k.startswith("e_bias.")}
    assert sum(bias.values()) == 4 * 128
    assert sum(sizes.values()) - sum(bias.values()) == want
    assert want * 16 == pytest.approx(10.67e9, rel=1e-3)
    assert want * 20 == pytest.approx(13.34e9, rel=1e-3)
    # the program's map names every reference weight once
    leaves = program.leaves(cfg)
    assert sorted(r for _, r, _ in leaves) == sorted(sizes)
    assert len({p for p, _, _ in leaves}) == len(leaves)


def test_nemotron3_config_keeps_the_published_widths():
    cfg = spec.config(CONFIG)
    assert len(PUBLISHED["hybrid_override_pattern"]) == 52 \
        == PUBLISHED["num_hidden_layers"]
    assert [PUBLISHED["hybrid_override_pattern"].count(k) for k in "ME*"] \
        == [23, 23, 6]
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}[cfg["name"]]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmarks/configs/" + CONFIG + ".json"
    assert cfg["source"].startswith(entry["source"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # inside the floors: the repeating unit whole in the driver's period
    # of nine, 8 routed experts, an eighth of the vocabulary
    kinds = mf.pattern(cfg)
    assert kinds == "MEMEM*EME" == PUBLISHED["hybrid_override_pattern"][:9]
    assert [kinds.count(k) for k in "ME*"] == [4, 4, 1]
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width and its picks
    assert cfg["n_routed_experts"] * cfg["expert_parallel"] == 128
    assert 0 <= cfg["expert_rank"] < cfg["expert_parallel"] == 16
    assert "Sixteen chips share each layer" in cfg["deployment"]
    assert "share 0 of 16" in cfg["deployment"]
    assert "What the cut overstates" in cfg["deployment"]
    for key in ("no_rotary", "gated_norm", "time_step", "convolution",
                "groups", "router", "experts", "weights", "token_ids",
                "sequence", "biases", "optimizer", "lr_first_step",
                "recompute"):
        assert cfg["assumed"][key]
    assert cfg["precision"] == {"compute": "bfloat16", "master": "float32",
                                "control": "float8_matmul"}
    # what the rehearsal changes are sizes, never the mechanism
    small = spec.config(CONFIG, rehearsal=True)
    assert mf.pattern(small) == kinds
    for key in ("num_experts_per_tok", "conv_kernel", "routed_scaling_factor",
                "mlp_hidden_act", "hybrid_override_pattern"):
        assert small[key] == PUBLISHED[key]
    assert small["mamba_num_heads"] > small["n_groups"] > 1
    assert small["num_attention_heads"] > small["num_key_value_heads"]
    assert small["moe_shared_expert_intermediate_size"] \
        == 2 * small["moe_intermediate_size"]


def test_the_nemotron3_cell():
    cell = spec.cell(CELL)
    loop = spec.loop(cell)
    assert (loop["readback_every"], loop["trace_first_step"],
            loop["trace_steps"], loop["pool"]) == (5, 10, 10, 8)
    assert (cell["batch"], cell["dims"]["seq"], cell["chips"]) \
        == (1, 8192, 1)
    assert cell["rehearsal"] == {"batch": 2, "dims": {"seq": 32}}
    assert sorted(cell["fields"]) == ["ids"]
    assert cell["fields"]["ids"]["high"] == "vocab_size"
    entry = {w["name"]: w for w in spec.benchmark()["workloads"]}[CELL]
    assert entry == {k: cell[k] for k in ("name", "config", "traffic",
                                          "chips", "why")}
    assert len(entry["why"]) <= 200
    assert CONFIG in [c["name"] for c in spec.benchmark()["configs"]]
    listed = {m["name"] for m in spec.per_layer_for(CELL)}
    assert set(APPENDED + OWN) | {"device_step_ms", "step_mfu_pct",
                                  "peak_hbm_gib", "device_idle_pct"} \
        <= listed
    # no metric of another model's mechanism reads this cell
    assert not listed & {"short_conv_ms", "window_attention_ms",
                         "attn_gate_ms", "mla_proj_ms", "dense_ffn_ms"}
    assert {m["name"] for m in spec.end_to_end_for(CELL)} \
        == {"samples_per_s", "step_ms_p95", "setup_s"}
    limits = spec.load_json("limits", CONFIG + ".json")
    assert set(limits["limits"]) == set(limits["rehearsal"]) == {
        "loss", "grad_worst", "grad_median", "grad_direction", "delta_worst"}
