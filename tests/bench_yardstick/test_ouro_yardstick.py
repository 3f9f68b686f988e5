"""CPU tests of what PR 27 added to the yardstick: the FLOP count of a
model whose layers run four times a step, the four scope metrics that read
the loop's and the exit heads' names, and the two new cells' files. Nothing
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

# scopes as the Ouro step compiled for a v5e carries them (PR 27)
J = "jit(counted_step)/"
FWD = J + "jvp(loss)/OuroForPretraining/"
BACK = J + "transpose(jvp(loss))/OuroForPretraining/"
SCOPES = {
    "fusion.1": FWD
    + "ut_step/2/layers/recompute/3/mlp/up_proj/linear/dot_general",
    "fusion.2": FWD + "ut_step/0/layers/recompute/0/input_layernorm/"
    "rms_norm/mul",
    "fusion.3": FWD + "ut_step/3/layers/norm/rms_norm/mul",
    "fusion.4": BACK + "ut_step/1/layers/recompute/ut_step/1/layers/"
    "recompute/checkpoint/rematted_computation/2/self_attn/"
    "scaled_dot_product_attention/dot_general",
    "fusion.5": BACK + "ut_step/1/layers/recompute/ut_step/1/layers/"
    "recompute/checkpoint/2/post_attention_layernorm_2/rms_norm/mul",
    "fusion.6": FWD + "recompute/exit_head/exit_cross_entropy/dot_general",
    "fusion.7": BACK + "recompute/recompute/checkpoint/rematted_computation/"
    "exit_head/exit_cross_entropy/reduce_max",
    "fusion.8": BACK + "recompute/recompute/checkpoint/exit_head/exit_gate/"
    "dot_general",
    "fusion.9": J + "jvp(loss)/OuroPretrainingCriterion/exit_head/exit_loss/"
    "exp",
    "fusion.10": FWD + "embed_tokens/embedding/gather",
    # a scan over the loop steps would name its one stack ut_steps
    "fusion.15": FWD + "ut_loop/while/body/ut_steps/layers/0/mlp/swiglu/mul",
    "fusion.11": J + "optimizer/add",
    "copy.12": "params['exit_head.lm_head.weight']",
    "copy.13": "opt_state[0]['layers.blocks.0.input_layernorm.weight']"
    "['moment1']",
    # bert's and resnet's names are not taken for the loop's
    "fusion.14": J + "jvp(loss)/BertForPretraining/bert/encoder/3/norm1/"
    "layer_norm/mul",
}
EXPECT = {
    "loop_layers_ms": {"fusion.1", "fusion.2", "fusion.3", "fusion.4",
                       "fusion.5", "fusion.15"},
    "exit_head_ms": {"fusion.6", "fusion.7", "fusion.8", "fusion.9"},
    "recompute_ms": {"fusion.4", "fusion.7"},
    "rms_norm_ms": {"fusion.2", "fusion.3", "fusion.5"},
}


def _view(steps=4):
    ops = {f"%{n} = bf16[8,128]{{1,0}} fusion(%p.{i}), kind=kLoop":
           1e-3 * steps for i, n in enumerate(SCOPES)}
    return {"ops": ops, "step_s": [len(SCOPES) * 1e-3] * steps,
            "busy_s": len(SCOPES) * 1e-3 * steps}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_later_scope_metric_reads_its_scope(name):
    metric = FILES[name]
    value = spec.reducer(metric["reducer"])(
        {"views": [_view(), _view()]}, metric, maps=(SCOPES, {}))
    assert value == pytest.approx(len(EXPECT[name]))     # 1 ms each a step
    match = re.compile(metric["match"])
    got = {n for n, path in SCOPES.items() if match.search(path)}
    assert got == EXPECT[name]
    # no map, as on the parent of the PR that added the scopes, or no view
    for maps in ((None, None), ({}, {})):
        assert spec.reducer(metric["reducer"])(
            {"views": [_view()]}, metric, maps=maps) is None
    assert spec.reducer(metric["reducer"])(
        {"views": []}, metric, maps=(SCOPES, {})) is None


def test_the_stack_and_the_heads_share_no_instruction():
    assert not EXPECT["loop_layers_ms"] & EXPECT["exit_head_ms"]
    # accepted metrics on the same names: attention by its op, and the
    # norm metric of PR 25 does not know rms_norm
    attention = re.compile(FILES["attention_ms"]["match"])
    assert {n for n, p in SCOPES.items() if attention.search(p)} \
        == {"fusion.4"}
    norm = re.compile(FILES["norm_ms"]["match"])
    assert {n for n, p in SCOPES.items() if norm.search(p)} == {"fusion.14"}


def test_ouro_flops_hand_count():
    from benchmarks.model_flops import ouro_2p6b as mf
    cfg = spec.config("ouro_2p6b")
    env = {"batch": 2, "seq": 4096}
    tokens = 2 * 4096
    layer_params = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer_params == 51380224
    pairs = 4096 * 4097 // 2                 # a query with the keys up to it
    layer = 2 * tokens * layer_params + 2 * 2 * 2 * pairs * 2048
    head = 2 * tokens * 2048 * 49152 + 2 * tokens * 2048
    # 6 layers kept, every one used 4 times a step; 4 exits
    assert mf.forward_matmul_flops(cfg, env) == 4 * (6 * layer + head)
    assert mf.train_step_flops(cfg, env) == 3.0 * 4 * (6 * layer + head)
    # ISSUE 27's arithmetic: 90.3 TFLOP a step
    assert mf.train_step_flops(cfg, env) == pytest.approx(90.3e12, rel=2e-3)
    # one loop step of one layer is a quarter of four
    one = {**cfg, "total_ut_steps": 1, "num_hidden_layers": 1}
    assert mf.forward_matmul_flops(one, env) == layer + head


def test_ouro_attention_kernel_flops_hand_count():
    """The two attention kernels, a call a layer a loop step (24 each a
    step), 128 wide throughout: 2 and 5 score-shaped products a causal
    pair (PERF.md section 5 reads the kernels against 0.1375 and 0.3437
    TFLOP a call)."""
    from benchmarks.model_flops import ouro_2p6b as mf
    cfg = spec.config("ouro_2p6b")
    pairs = 4096 * 4097 // 2
    per_pair = 2 * 128 * 16             # one score-shaped product, all heads
    kernels = mf.attention_kernel_flops(cfg, {"batch": 2, "seq": 4096})
    assert kernels == {
        "p1t_flash_attention_fwd": 24 * 2 * 2 * per_pair * pairs,
        "p1t_flash_attention_bwd": 24 * 2 * 5 * per_pair * pairs}
    assert kernels["p1t_flash_attention_fwd"] / 24 \
        == pytest.approx(0.1375e12, rel=1e-3)
    assert kernels["p1t_flash_attention_bwd"] / 24 \
        == pytest.approx(0.3437e12, rel=1e-3)
    # the forward kernel's two products are the model's scores and values
    one = {**cfg, "total_ut_steps": 1, "num_hidden_layers": 1}
    assert mf.attention_kernel_flops(one, {"batch": 2, "seq": 4096})[
        "p1t_flash_attention_fwd"] == 2 * 2 * 2 * pairs * 2048


# ByteDance/Ouro-2.6B config.json, the keys that say something of its shape
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def test_ouro_config_keeps_the_published_widths():
    cfg = spec.config("ouro_2p6b")
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 6 and cfg["total_ut_steps"] == 4
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert "pipeline stages" in cfg["deployment"]
    for key in ("weights", "token_ids", "sequence", "biases", "optimizer",
                "exit_entropy_beta", "lr_first_step", "recompute"):
        assert cfg["assumed"][key]


def test_the_s512_cell_is_the_s128_cell_at_phase_two():
    a = spec.cell("bert_base.pretrain_s128")
    b = spec.cell("bert_base.pretrain_s512")
    assert b["batch"] * b["dims"]["seq"] == a["batch"] * a["dims"]["seq"]
    assert b["dims"] == {"seq": 512, "mlm_per_seq": 80}
    same = set(a) - {"name", "traffic", "why", "batch", "dims"}
    assert all(a[k] == b[k] for k in same)
    assert spec.loop(b) == spec.LOOP


def test_the_ouro_cell_reads_back_every_fifth_step():
    cell = spec.cell("ouro_2p6b.pretrain_s4096")
    loop = spec.loop(cell)
    assert (loop["readback_every"], loop["trace_first_step"],
            loop["trace_steps"]) == (5, 10, 10)
    assert cell["batch"] * cell["dims"]["seq"] == 8192
    listed = {m["name"] for m in spec.per_layer_for(cell["name"])}
    assert set(EXPECT) | {"attention_ms", "forward_ms", "backward_ms",
                          "optimizer_ms", "unscoped_ms", "host_step_ms",
                          "device_step_ms", "step_mfu_pct",
                          "attention_kernel_mxu_roofline"} <= listed
