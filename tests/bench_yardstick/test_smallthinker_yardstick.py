"""CPU tests of what PR 43 added to the yardstick: the cell in the lists of
the accepted metrics its scopes give, the two scope metrics of its own
(the window layers' attention and the global layer's apart) on a synthetic
view and on the paths of a compiled step, the FLOP count of a step whose
attention layers are of two kinds (visible pairs by kind, the benchmark's
own closed form held equal to the program's), the kernels' share of the
matrix unit from that count, the parameter count of the cut, and the
configuration's and cell's files. Membership only: which entry stands
where in a list is nobody's to say (``test_the_door_stays_open.py``). (That
the rehearsal passes ``correct`` in float32 and the bfloat16 control fails
it, and the count against XLA's cost analysis: ``test_bench_yardstick.py``
runs them for every configuration there is.) Nothing here is a device
metric."""

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
from benchmarks.model_flops import smallthinker_21b_a3b as mf  # noqa: E402
from benchmarks.reducers import kernel_mxu_pct  # noqa: E402

FILES = spec.layer_metrics()
CELL = "smallthinker_21b_a3b.pretrain_s16384"
CONFIG = "smallthinker_21b_a3b"
# the accepted metrics whose lists of cells gained this one, and its own
APPENDED = ("forward_ms", "backward_ms", "optimizer_ms", "unscoped_ms",
            "host_step_ms", "attention_ms", "recompute_ms", "rms_norm_ms",
            "moe_ms", "moe_route_ms", "routed_experts_ms", "attn_proj_ms",
            "moe_held_picks_pct", "moe_expert_rows_max", "moe_late_picks",
            "attention_kernel_mxu_roofline")
OWN = ("window_attention_ms", "global_attention_ms")

# scopes as the step carries them: layer 0 global without positions,
# layers 1-3 under the window with rotary; the router first
J = "jit(counted_step)/"
M = "SmallThinkerForPretraining/"
FWD = J + "jvp(loss)/" + M + "layers/recompute/"
BACK = (J + "transpose(jvp(loss))/" + M + "layers/recompute/jvp(loss)/" + M
        + "layers/recompute/checkpoint/")
AGAIN = BACK + "rematted_computation/"
GLOBAL = "self_attn/global/scaled_dot_product_attention/"
WINDOW = "self_attn/window/scaled_dot_product_attention/"
SCOPES = {
    "fusion.1": J + "jvp(loss)/" + M + "embed_tokens/embedding/jit(_take)/gather",
    "fusion.2": FWD + "0/input_layernorm/rms_norm/mul",
    "fusion.3": FWD + "0/mlp/moe/moe_router/dot_general",
    "fusion.4": FWD + "0/self_attn/q_proj/linear/dot_general",
    "p1t_flash_attention_fwd.5": FWD + "0/" + GLOBAL
    + "jit(_fwd_call)/p1t_flash_attention_fwd/pallas_call",
    "p1t_flash_attention_bwd_dkv.6": BACK + "0/" + GLOBAL
    + "jit(_bwd_call)/p1t_flash_attention_bwd_dkv/pallas_call",
    "fusion.7": BACK + "0/" + GLOBAL + "jit(_bwd_call)/reduce_sum",
    "fusion.8": FWD + "1/self_attn/rotary_embedding/mul",
    "p1t_flash_attention_fwd.9": FWD + "1/" + WINDOW
    + "jit(_fwd_call)/p1t_flash_attention_fwd/pallas_call",
    "p1t_flash_attention_bwd_dkv.10": BACK + "2/" + WINDOW
    + "jit(_bwd_call)/p1t_flash_attention_bwd_dkv/pallas_call",
    "fusion.11": BACK + "3/" + WINDOW + "jit(_bwd_call)/reduce_sum",
    "fusion.12": AGAIN + "2/self_attn/k_proj/linear/dot_general",
    "fusion.13": BACK + "1/self_attn/o_proj/linear/transpose",
    "sort.14": FWD + "2/mlp/moe/moe_dispatch/jit(argsort)/sort",
    "p1t_sum_picks_fwd.15": FWD + "3/mlp/moe/moe_combine/"
    "p1t_sum_picks_fwd/pallas_call",
    "conditional.16": AGAIN + "3/mlp/moe/moe_overflow/cond",
    "ragged-dot-none.17": J + "jvp(loss)/moe/routed_experts",
    "ragged-dot-none.18": J + "transpose(jvp(loss))/moe/routed_experts",
    "fusion.19": FWD + "1/mlp/moe/routed_experts/jit(relu)/mul",
    "fusion.20": AGAIN + "1/post_attention_layernorm/rms_norm/mul",
    "fusion.21": J + "jvp(loss)/" + M + "recompute/lm_head/"
    "head_cross_entropy/dot_general",
    "fusion.22": J + "optimizer/add",
    # another model's attention carries no kind, a window of another sort
    # is no attention layer's
    "fusion.23": J + "jvp(loss)/Lfm2ForPretraining/layers/recompute/1/"
    "self_attn/scaled_dot_product_attention/mul",
    "fusion.24": J + "jvp(loss)/Other/window_partition/reshape",
}
GLOBAL_OPS = {"p1t_flash_attention_fwd.5", "p1t_flash_attention_bwd_dkv.6",
              "fusion.7"}
WINDOW_OPS = {"p1t_flash_attention_fwd.9", "p1t_flash_attention_bwd_dkv.10",
              "fusion.11"}
ROUTE = {"fusion.3", "sort.14", "p1t_sum_picks_fwd.15", "conditional.16"}
PRODUCTS = {"ragged-dot-none.17", "ragged-dot-none.18", "fusion.19"}
# what each scope metric of the cell holds of the scopes above
EXPECT = {
    "window_attention_ms": WINDOW_OPS,
    "global_attention_ms": GLOBAL_OPS,
    "attention_ms": WINDOW_OPS | GLOBAL_OPS | {"fusion.23"},
    "attn_proj_ms": {"fusion.4", "fusion.8", "fusion.12", "fusion.13"},
    "recompute_ms": {"fusion.12", "conditional.16", "fusion.20"},
    "rms_norm_ms": {"fusion.2", "fusion.20"},
    "moe_ms": ROUTE | PRODUCTS,
    "moe_route_ms": ROUTE,
    "routed_experts_ms": PRODUCTS,
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
    for name, kind in zip(OWN, ("window", "global")):
        metric, entry = FILES[name], entries[name]
        assert metric["reducer"] == "scope_ms"
        assert metric["match"] == f"/self_attn/{kind}(/|$)"
        assert "exclude" not in metric
        for key in ("unit", "better", "source", "layer", "moves"):
            assert metric[key] == entry[key], (name, key)
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


def test_the_two_kinds_of_layer_make_up_this_models_attention():
    """The window layers' and the global layer's attention share no
    instruction and together are what ``attention_ms`` reads of this
    model; the projections and rotary lie outside both; a program without
    the scopes (the parent of the PR that named them) gives nothing."""
    mine = {n for n, s in SCOPES.items() if M in s}
    assert not EXPECT["window_attention_ms"] & EXPECT["global_attention_ms"]
    assert (EXPECT["window_attention_ms"] | EXPECT["global_attention_ms"]
            == EXPECT["attention_ms"] & mine)
    assert not EXPECT["attn_proj_ms"] & EXPECT["attention_ms"]
    assert EXPECT["moe_ms"] \
        == EXPECT["moe_route_ms"] | EXPECT["routed_experts_ms"]
    reduce = spec.reducer("scope_ms")
    unnamed = {n: s.replace("/window/", "/").replace("/global/", "/")
               for n, s in SCOPES.items()}
    for name in OWN:
        assert reduce({"views": [_view()]}, FILES[name],
                      maps=(unnamed, {})) == 0.0
        assert reduce({"views": [_view()]}, FILES[name],
                      maps=(None, None)) is None
        assert reduce({"views": []}, FILES[name], maps=(SCOPES, {})) is None


@pytest.mark.drives_a_run
def test_the_patterns_read_the_paths_of_a_compiled_step():
    """The rehearsal's engine, stepped once on the CPU with the kernels in
    interpreter mode: the two patterns find the kernels of their kind of
    layer among the compiled step's own paths, three window layers and
    one global; every kernel lies under one or the other."""
    from paddle1_tpu import obs
    from paddle1_tpu.core.flags import flags_guard
    from paddle1_tpu.obs import costmodel
    obs.reset_process_registry()
    cell = spec.cell(CELL)
    cfg = spec.config(CONFIG, rehearsal=True)
    env = traffic.environment(cfg, {**cell, "rehearsal": {
        "batch": 1, "dims": {"seq": 128}}}, rehearsal=True)
    program, reference = (spec.module(k, cfg) for k in ("program",
                                                        "reference"))
    w = jax.jit(lambda k: reference.init_params(cfg, k))(jax.random.key(0))
    engine = program.build(cfg, env, {
        p: w[r] for p, r, _ in program.leaves(cfg)},
        jax.devices()[:1])["engine"]
    batch = traffic.batches(cell, env, 1, 1)[0]
    with flags_guard(flash_attention="always"):
        float(engine.step(engine.shard_batch(batch), lr=1e-4))
        scopes = costmodel.step_op_scopes()
    # (of an interpreted kernel's inner loops XLA's CPU backend keeps the
    # path from the kernel's name on: those say nothing of a layer)
    kernels = {n: s for n, s in scopes.items()
               if "p1t_flash_attention" in s and s.startswith("jit(")}
    found = {kind: {n for n, s in kernels.items()
                    if re.search(FILES[f"{kind}_attention_ms"]["match"], s)}
             for kind in ("window", "global")}
    assert found["window"] and found["global"]
    assert not found["window"] & found["global"]
    assert found["window"] | found["global"] == set(kernels), sorted(
        s for n, s in kernels.items()
        if n not in found["window"] | found["global"])[:4]
    layer = re.compile(r"/(\d)/self_attn/")
    assert {layer.search(scopes[n]).group(1) for n in found["global"]} \
        == {"0"}
    assert {layer.search(scopes[n]).group(1) for n in found["window"]} \
        == {"1", "2", "3"}
    # the attention op's whole time is the two kinds', nothing else
    op = re.compile(FILES["attention_ms"]["match"])
    assert {n for n, s in scopes.items() if op.search(s)} == {
        n for n, s in scopes.items() if any(re.search(
            FILES[m]["match"], s) for m in OWN)} >= set(kernels)
    del engine


def test_smallthinker_flops_hand_count():
    cfg = spec.config(CONFIG)
    env = {"batch": 1, "seq": 16384}
    tokens = 16384
    assert mf.windows(cfg) == [None, 4096, 4096, 4096]
    assert mf.picks_here_a_token(cfg) == 0.75
    causal = 16384 * 16385 // 2
    band = 4096 * 4097 // 2 + 12288 * 4096
    assert (causal, band) == (134_225_920, 58_722_304)
    assert mf.pairs_a_row(cfg, env) == causal + 3 * band
    projections = 2 * tokens * (2 * 2560 * 3584 + 2 * 2560 * 512)
    scores = 2 * (causal + 3 * band) * 28 * 2 * 128
    experts = 2 * tokens * (2560 * 64 + 3 * 2560 * 768 * 0.75)
    head = 2 * tokens * 2560 * 18992
    forward = 4 * (projections + experts) + scores + head
    assert mf.forward_matmul_flops(cfg, env) == forward
    assert mf.train_step_flops(cfg, env) == 3.0 * forward
    # ISSUE 43's arithmetic: 28.2 TFLOP a step, 573 MFLOP a token forward;
    # the kernels' visible pairs 47% (the three window layers 27, the
    # global one 20), the projections 29%, the head 17%, the experts 6%
    assert 3.0 * forward == pytest.approx(28.2e12, rel=2e-3)
    assert forward / tokens == pytest.approx(573.5e6, rel=1e-3)
    assert scores / forward == pytest.approx(0.47, abs=0.005)
    assert 2 * 3 * band * 28 * 2 * 128 / forward == pytest.approx(0.27,
                                                                  abs=0.005)
    assert 4 * projections / forward == pytest.approx(0.29, abs=0.005)
    assert head / forward == pytest.approx(0.17, abs=0.005)
    assert 4 * experts / forward == pytest.approx(0.06, abs=0.005)
    # a window layer runs 44% of a causal layer's pairs at 16,384 keys,
    # 75% at 8,192
    assert band / causal == pytest.approx(0.4375, abs=1e-3)
    assert mf.visible_pairs(8192, 4096) / mf.visible_pairs(8192) \
        == pytest.approx(0.75, abs=1e-3)
    # the kernels: 2 and 5 score-shaped products a visible pair
    per_pair = 2 * 128 * 28
    assert mf.attention_kernel_flops(cfg, env) == {
        "p1t_flash_attention_fwd": 2 * per_pair * (causal + 3 * band),
        "p1t_flash_attention_bwd": 5 * per_pair * (causal + 3 * band)}
    assert mf.attention_kernel_flops(cfg, {"batch": 3, "seq": 64}) == {
        "p1t_flash_attention_fwd": 2 * per_pair * 4 * (64 * 65 // 2) * 3,
        "p1t_flash_attention_bwd": 5 * per_pair * 4 * (64 * 65 // 2) * 3}


# rows a sequence, the window: shorter than, as long as, longer than it
@pytest.mark.parametrize("seq,window", [(64, 1), (64, 5), (64, 63), (64, 64),
                                        (64, 100), (16384, 4096),
                                        (8192, 4096), (4096, 4096)])
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
    FLOPs on visible pairs by layer kind over the time of their eight
    instructions a step (four layers forward, four backward)."""
    metric = FILES["attention_kernel_mxu_roofline"]
    cell, cfg = spec.cell(CELL), spec.config(CONFIG)
    flops = mf.attention_kernel_flops(cfg, traffic.environment(cfg, cell))
    peak = 197e12
    named = [f"p1t_flash_attention_fwd.{i}" for i in range(4)] \
        + [f"p1t_flash_attention_bwd_dkv.{i}" for i in range(4, 8)]
    seconds = sum(flops.values()) / (0.5 * peak)
    view = _view(named + ["fusion.1"], ms=1e3 * seconds / len(named))
    ctx = {"views": [view, view], "peak_flops_per_s": peak, "cell": cell,
           "config": cfg}
    assert kernel_mxu_pct.reduce(ctx, metric) == pytest.approx(50.0)
    # at the MXU's peak the kernels of a step would take 79 ms
    assert 1e3 * sum(flops.values()) / peak == pytest.approx(79.06, rel=1e-3)


def test_the_cut_has_the_parameters_the_issue_counted():
    """370,547,200 by ISSUE 43's equations, counted from the reference's
    shapes and from the built model: an eighth of the embedding and of
    the untied head, the final norm, and four layers of attention, a
    router over all 64 experts, two norms and 8 held experts."""
    cfg = spec.config(CONFIG)
    reference, program = (spec.module(k, cfg) for k in ("reference",
                                                        "program"))
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    layer = attention + 2560 * 64 + 2 * 2560 + 8 * 3 * 2560 * 768
    assert (attention, layer) == (20_971_520, 68_326_400)
    want = 4 * layer + 2 * 18992 * 2560 + 2560
    assert want == 370_547_200 == cfg["parameters"]
    assert want * 16 == pytest.approx(5.93e9, rel=1e-3)
    shapes = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                            jax.random.key(0))
    sizes = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert sum(sizes.values()) == want
    leaves = program.leaves(cfg)
    assert sorted(r for _, r, _ in leaves) == sorted(sizes)
    assert len({p for p, _, _ in leaves}) == len(leaves)
    # the built model: every parameter a leaf of the map, as large
    from paddle1_tpu.text.models import SmallThinkerForPretraining
    held = cfg["moe_num_primary_experts"]
    model = SmallThinkerForPretraining(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        sliding_window_layout=program.stage(cfg, "sliding_window_layout"),
        rope_layout=program.stage(cfg, "rope_layout"),
        sliding_window_size=cfg["sliding_window_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        moe_ffn_hidden_size=cfg["moe_ffn_hidden_size"],
        moe_num_primary_experts=held * cfg["expert_parallel"],
        moe_num_active_primary_experts=cfg[
            "moe_num_active_primary_experts"],
        held_experts=(cfg["expert_rank"] * held, held))
    built = {n: int(np.prod(p.shape)) for n, p in model.named_parameters()}
    assert sum(built.values()) == want
    assert built == {p: sizes[r] for p, r, _ in leaves}
    assert set(model.state_dict()) == set(built)        # no buffer at rest


# PowerInfer/SmallThinker-21BA3B-Instruct config.json, as the catalog
# beside the model-configs guide has it
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_smallthinker_config_keeps_the_published_widths():
    cfg = spec.config(CONFIG)
    assert len(PUBLISHED["rope_layout"]) == 52
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "moe_num_primary_experts",
                       "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    # inside the floors: a whole period of four layers, 8 routed experts,
    # an eighth of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["pipeline_first_layer"]) == (4, 0)
    assert mf.windows(cfg) == [None, 4096, 4096, 4096]
    assert spec.module("reference", cfg).layer_kinds(cfg) == [
        (None, False), (4096, True), (4096, True), (4096, True)]
    assert cfg["moe_num_primary_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width and its picks
    assert cfg["moe_num_primary_experts"] * cfg["expert_parallel"] == 64
    assert cfg["vocab_size"] * cfg["expert_parallel"] == 151936
    assert 0 <= cfg["expert_rank"] < cfg["expert_parallel"] == 8
    assert cfg["optimizer"]["lr_schedule"]["peak"] == 3e-5
    # the embedding's rows and the two writers to the stream have ranges
    # of their own (sdar's, for its reason: the file's ``assumed.weights``)
    assert (cfg["initializer_range"], cfg["embedding_initializer_range"]) \
        == (0.02, 0.7)
    assert cfg["residual_initializer_range"] == pytest.approx(
        0.02 / (2 * PUBLISHED["num_hidden_layers"]) ** 0.5, rel=1e-5)
    assert "Eight chips share each layer" in cfg["deployment"]
    assert "share 0 of 8" in cfg["deployment"]
    assert "What the cut overstates" in cfg["deployment"]
    for key in ("router_input", "window", "rotary", "q_norm_k_norm",
                "secondary_experts", "router", "expert", "weights",
                "token_ids", "sequence", "optimizer", "lr_first_step",
                "recompute"):
        assert cfg["assumed"][key]
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}[cfg["name"]]
    assert entry["source"] == "https://huggingface.co/PowerInfer/" \
        "SmallThinker-21BA3B-Instruct/blob/main/config.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    # what the rehearsal changes are sizes, never the mechanism
    small = spec.config(CONFIG, rehearsal=True)
    assert small["moe_num_active_primary_experts"] == 6
    assert small["num_attention_heads"] == 7 * small["num_key_value_heads"]
    assert small["num_hidden_layers"] == 4
    assert small["sliding_window_layout"] == PUBLISHED["sliding_window_layout"]
    assert small["sliding_window_size"] < spec.cell(CELL)["rehearsal"][
        "dims"]["seq"]


def test_the_smallthinker_cell():
    cell = spec.cell(CELL)
    loop = spec.loop(cell)
    assert (loop["readback_every"], loop["trace_first_step"],
            loop["trace_steps"], loop["pool"]) == (5, 10, 10, 8)
    cfg = spec.config(CONFIG)
    assert (cell["batch"], cell["dims"]["seq"], cell["chips"]) \
        == (1, 16384, 1)
    assert cell["dims"]["seq"] == cfg["max_position_embeddings"]
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
        assert 0 <= batch["ids"].min() and batch["ids"].max() < 18992
