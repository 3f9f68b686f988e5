"""Compile the main-path Pallas kernels for a described TPU v5e, at real
widths, without a chip: what Mosaic refuses here it refuses on the chip.
And the cells' whole blocks: one ResNet-50 stage-1 bottleneck, forward and
backward, whose text shows whether XLA fused batch norm into the
convolutions (ISSUE 26); one recomputed decoder block of Ouro, of SDAR and
two of SmallThinker, and Kanana-2's and SmallThinker's expert layers,
whose texts show which kernels run, how often and on what (ISSUE 30, 32,
33, 43, 44); rotary positions behind a projection (ISSUE 45); the grouped
products' scopes.

A cell's recomputed block is compiled through one helper
(``_recomputed``). No two cases share a compile: each of the five texts
has one reader. ``test_smallthinkers_expert_layer_gathers_no_row_it_does_
not_hold`` could read all but one of its lines out of the two blocks of
``test_recomputed_smallthinker_blocks_name_their_kind_and_route_first``
(the same 8 of 64 experts, top-6, hidden 2560, ``[1, 16384, 2560]``), but
it counts the layer's 8 grouped products, the blocks' text holds 16, and
the scope the TPU's compiler leaves a grouped product carries no block's
index to tell them apart by: it keeps the layer alone (ISSUE 46).

The only file that describes the chip. The topology is described inside
a module-scoped fixture — never at import, in a ``skipif`` or in
``parametrize`` arguments — because only one process may load the TPU's
library: under several test workers every worker imports this file, and
only the one that is handed it may make the call. (A second file of such
cases would go to a second worker, whose load the library refuses and
whose fixture then skips every case in silence: ISSUE 46 kept the file
whole.) Nothing runs: there is no device to hold an array, so every case
lowers ``ShapeDtypeStruct``s. A compile that passes is not a chip run.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle1_tpu.core.flags import flags_guard
from paddle1_tpu.ops.pallas import (_common, flash_attention, fused_bn,
                                    grouped_matmul, layer_norm, mask_rules,
                                    paged_attention, short_conv, softmax,
                                    ssd_scan, sum_picks)

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """Kernels out of interpret mode, and the persistent cache off: an
    executable compiled for a described chip is written to it but cannot
    be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(_common, "interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _flash(causal=False, masked=False, grad=False, dtype=BF16, mask=None,
           kv_heads=None):
    def build(b, s, h, d, dv=None):
        hk = kv_heads or h
        qkv = [((b, s, h, d), dtype), ((b, s, hk, d), dtype),
               ((b, s, hk, dv or d), dtype)]
        if masked:
            def fn(q, k, v, m):
                return flash_attention.flash_attention(
                    q, k, v, causal=causal, padding_mask=m, mask=mask)
            args = qkv + [((b, s), F32)]
        else:
            def fn(q, k, v):
                return flash_attention.flash_attention(q, k, v,
                                                       causal=causal,
                                                       mask=mask)
            args = qkv
        if grad:
            fwd = fn
            fn = jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                          argnums=(0, 1, 2))
        return fn, args
    return build


def _layer_norm():
    return (layer_norm.fused_layer_norm,
            [((4096, 768), BF16), ((768,), F32), ((768,), F32)])


def _softmax():
    return softmax.fused_softmax, [((32 * 12 * 128, 128), BF16)]


BN = [((128 * 14 * 14, 256), BF16), ((256,), F32), ((256,), F32),
      ((256,), F32), ((256,), F32)]           # x, mean, var, gamma, beta


def _bn_norm(act="identity", residual=False, grad=False):
    def fwd(x, m, v, g, b, *res):
        return fused_bn.fused_bn_norm(x, m, v, g, b, 1e-5, act=act,
                                      residual=res[0] if res else None)
    fn = fwd
    if grad:
        fn = jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                      argnums=(0, 1, 2, 3, 4))
    return fn, BN + BN[:1] * residual


def _bn_moments():
    return fused_bn.local_moments, BN[:1]


def _paged(window):
    slots, heads, dim, pages, page, per_slot = 8, 12, 64, 64, 16, 8
    pool = ((pages, heads, page, dim), BF16)
    return (paged_attention.paged_attention,
            [((slots, window, heads, dim), BF16), pool, pool,
             ((slots, per_slot), I32), ((slots,), I32)])


def _sum_picks(rows, hidden, tokens, fan, dtype=BF16):
    return (lambda o, where: sum_picks.sum_picks(o, where, fan),
            [((rows, hidden), dtype), ((tokens * fan,), I32)])


def _grouped(form, rows, k, n, groups, dtype=BF16):
    """One form of the grouped product: the other two kernels of the
    ``custom_vjp`` feed nothing that is returned and go."""
    def fn(x, w, d, sizes):
        y, vjp = jax.vjp(
            lambda x, w: grouped_matmul.grouped_matmul(x, w, sizes), x, w)
        return y if form == "product" else vjp(d)[form == "dw"]
    return fn, [((rows, k), dtype), ((groups, k, n), dtype),
                ((rows, n), dtype), ((groups,), I32)]


def _short_conv(grad=False, dtype=BF16):
    def build(batch, seq, channels, taps=3):
        bcx = ((batch, seq, 3 * channels), dtype)
        w = ((channels, taps), dtype)
        if grad:
            return short_conv.backward, [bcx, w,
                                         ((batch, seq, channels), dtype)]
        return short_conv.forward, [bcx, w]
    return build


def _ssd(grad=False, dtype=BF16, keep_states=True):
    def build(batch, seq, heads, width, groups, state=128):
        x = ((batch, seq, heads, width), dtype)
        per_head = ((batch, seq, heads), F32)
        group = ((batch, seq, groups, state), dtype)
        head = ((heads,), F32)
        operands = [x, per_head, head, group, group, head]
        if grad:
            starts = ((batch, seq // 128, groups, state,
                       heads // groups * width), F32)
            return ssd_scan.backward, operands + [starts, x]
        return (lambda *o: ssd_scan.forward(*o, keep_states=keep_states),
                operands)
    return build


B32_S128 = (32, 128, 12, 64)
B8_S512 = (8, 512, 12, 64)
OURO = (2, 4096, 16, 128)   # ouro_2p6b.pretrain_s4096's attention call
# kanana2_30b_a3b.pretrain_s8192's: keys 192 wide (no multiple of the
# 128-lane tile: the [B*H, N, D] layout), values 128 (the [B, N, H*D] one)
KANANA2 = (2, 8192, 32, 192, 128)

SDAR = (1, 16384, 32, 128)
SDAR_RULE = mask_rules.BlockDiffusion(8192, 4)

# lfm2_24b_a2b.pretrain_s16384's attention call: head width 64 (no
# multiple of the 128-lane tile: the [B*H, N, D] layout for q, k, dq, dk),
# 32 query heads over 8, twice the longest causal row of any other cell
LFM2 = (1, 16384, 32, 64)

# nemotron3_nano_30b_a3b.pretrain_s8192's: the one attention layer's 32
# query heads over 2 (groups of 16), and the four Mamba-2 layers' scan,
# 64 heads of 64 over 8 groups of state 128
NEMOTRON3 = (1, 8192, 32, 128)
NEMOTRON3_SCAN = (1, 8192, 64, 64, 8)
# its experts' two grouped products: [9216, k] x [8, k, n]
NEMOTRON3_EXPERTS = {"up": (2688, 1856), "down": (1856, 2688)}

# smallthinker_21b_a3b.pretrain_s16384's attention calls: 28 query heads
# over 4 (groups of 7, the first that is no power of two), under the
# window's rule on three layers of four and the causal one on the fourth
SMALLTHINKER = (1, 16384, 28, 128)
WINDOW = mask_rules.SlidingWindow(4096)

CASES = {
    "flash_fwd_b32_s128": lambda: _flash()(*B32_S128),
    "flash_fwd_b8_s512": lambda: _flash()(*B8_S512),
    "flash_causal_b8_s512": lambda: _flash(causal=True)(*B8_S512),
    "flash_masked_b32_s128": lambda: _flash(masked=True)(*B32_S128),
    "flash_grad_b32_s128": lambda: _flash(grad=True)(*B32_S128),
    "flash_grad_b8_s512": lambda: _flash(grad=True)(*B8_S512),
    "flash_masked_grad_b32_s128":
        lambda: _flash(masked=True, grad=True)(*B32_S128),
    "flash_causal_ouro_s4096_d128": lambda: _flash(causal=True)(*OURO),
    "flash_causal_grad_ouro_s4096_d128":
        lambda: _flash(causal=True, grad=True)(*OURO),
    "flash_causal_masked_grad_ouro_s4096_d128":
        lambda: _flash(causal=True, masked=True, grad=True)(*OURO),
    "flash_causal_kanana2_s8192_d192_v128":
        lambda: _flash(causal=True)(*KANANA2),
    "flash_causal_grad_kanana2_s8192_d192_v128":
        lambda: _flash(causal=True, grad=True)(*KANANA2),
    # SDAR's doubled row [1, 2 x 8192, 32 / 4, 128] under block
    # diffusion's rule, both orders of the copies; and grouped heads
    # under the rules there were
    "flash_block_diffusion_sdar_s16384_h32_kv4":
        lambda: _flash(mask=SDAR_RULE, kv_heads=4)(*SDAR),
    "flash_block_diffusion_grad_sdar_s16384_h32_kv4":
        lambda: _flash(mask=SDAR_RULE, kv_heads=4, grad=True)(*SDAR),
    "flash_block_diffusion_clean_first_grad_s4096_h8_kv2_d64":
        lambda: _flash(mask=mask_rules.BlockDiffusion(2048, 32, False),
                       kv_heads=2, grad=True)(1, 4096, 8, 64),
    "flash_causal_grad_s4096_h16_kv2_d128":
        lambda: _flash(causal=True, kv_heads=2, grad=True)(1, 4096, 16, 128),
    "flash_grad_s2048_h8_kv1_d192_v128":
        lambda: _flash(kv_heads=1, grad=True)(1, 2048, 8, 192, 128),
    # the widest operand block supported() admits: VMEM's worst case
    "flash_grad_f32_s4096_d256":
        lambda: _flash(grad=True, dtype=F32)(1, 4096, 2, 256),
    # more keys than one pass keeps resident: the backward kernel a key
    # range at a time (two ranges of 32,768; ISSUE 35)
    "flash_causal_grad_s65536_two_key_ranges":
        lambda: _flash(causal=True, grad=True)(1, 65536, 1, 128),
    "flash_causal_lfm2_s16384_h32_kv8_d64":
        lambda: _flash(causal=True, kv_heads=8)(*LFM2),
    "flash_causal_grad_lfm2_s16384_h32_kv8_d64":
        lambda: _flash(causal=True, kv_heads=8, grad=True)(*LFM2),
    "flash_window_smallthinker_s16384_h28_kv4":
        lambda: _flash(mask=WINDOW, kv_heads=4)(*SMALLTHINKER),
    "flash_window_grad_smallthinker_s16384_h28_kv4":
        lambda: _flash(mask=WINDOW, kv_heads=4, grad=True)(*SMALLTHINKER),
    # Laguna-XS.2's window layers: every tile crossed, the backward's run
    # by sub-tile in two layouts (ISSUE 48: static slices of rows and lanes)
    "flash_window_grad_laguna_s16384_h64_kv8_w512":
        lambda: _flash(mask=mask_rules.SlidingWindow(512), kv_heads=8,
                       grad=True)(1, 16384, 64, 128),
    # a window shorter than a block (both edges through one tile) with a
    # group of 7 and a padding mask
    "flash_window_masked_grad_s2048_h7_kv1_w300":
        lambda: _flash(mask=mask_rules.SlidingWindow(300), kv_heads=1,
                       masked=True, grad=True)(2, 2048, 7, 128),
    "flash_causal_nemotron3_s8192_h32_kv2":
        lambda: _flash(causal=True, kv_heads=2)(*NEMOTRON3),
    "flash_causal_grad_nemotron3_s8192_h32_kv2":
        lambda: _flash(causal=True, kv_heads=2, grad=True)(*NEMOTRON3),
    # Nemotron 3 Nano's scan: the forward with and without the states it
    # keeps for the backward, the backward; and float32 operands, two rows
    "ssd_fwd_nemotron3_8192x64x64_g8": lambda: _ssd()(*NEMOTRON3_SCAN),
    "ssd_fwd_alone_nemotron3_8192x64x64_g8":
        lambda: _ssd(keep_states=False)(*NEMOTRON3_SCAN),
    "ssd_bwd_nemotron3_8192x64x64_g8":
        lambda: _ssd(grad=True)(*NEMOTRON3_SCAN),
    "ssd_bwd_f32_2x1024x8x64_g2":
        lambda: _ssd(grad=True, dtype=F32)(2, 1024, 8, 64, 2),
    # LFM2's gated short convolution: one 16k row of 2048 channels, 3 taps
    "short_conv_fwd_lfm2_16384x2048": lambda: _short_conv()(1, 16384, 2048),
    "short_conv_bwd_lfm2_16384x2048":
        lambda: _short_conv(grad=True)(1, 16384, 2048),
    # float32 operands, several rows, four taps
    "short_conv_bwd_f32_4x4096x1024_4taps":
        lambda: _short_conv(grad=True, dtype=F32)(4, 4096, 1024, 4),
    "layer_norm_4096x768": _layer_norm,
    "softmax_49152x128": _softmax,
    "bn_norm_fwd_25088x256": _bn_norm,
    "bn_norm_res_relu_fwd_25088x256":
        lambda: _bn_norm(act="relu", residual=True),
    "bn_norm_grad_25088x256": lambda: _bn_norm(act="relu", grad=True),
    "bn_moments_25088x256": _bn_moments,
    # kanana2's combine: 16384 tokens x top-6 over 36864 rows of 2048
    "sum_picks_kanana2_36864x2048_t16384_top6":
        lambda: _sum_picks(36864, 2048, 16384, 6),
    "sum_picks_f32_4096x1024_t2048_top8":
        lambda: _sum_picks(4096, 1024, 2048, 8, F32),
    # smallthinker's: rows of 10 lane rows of 32 bits, [4, 640] a row
    "sum_picks_smallthinker_36864x2560_t16384_top6":
        lambda: _sum_picks(36864, 2560, 16384, 6),
    # chip_smoke.py's expert phase: two lane rows, [4, 128] a row
    "sum_picks_chip_smoke_9216x512_t4096_top6":
        lambda: _sum_picks(9216, 512, 4096, 6),
    # the shallowest rows: one lane row of 32 bits, bf16 and float32
    "sum_picks_4096x256_t2048_top6":
        lambda: _sum_picks(4096, 256, 2048, 6),
    "sum_picks_f32_4096x640_t2048_top6":
        lambda: _sum_picks(4096, 640, 2048, 6, F32),
    # the most picks supported() admits: SMEM's worst case
    "sum_picks_8192x2048_t32768_top6":
        lambda: _sum_picks(8192, 2048, 32768, 6),
    # Nemotron 3 Nano's two grouped products, each in its three forms
    # (ISSUE 51): 9216 rows, 8 held experts, 2688 -> 1856 -> 2688; and
    # float32 operands, whose widths go in tiles
    **{"grouped_%s_nemotron3_%s_9216x%dx%d" % (form, which, k, n):
       (lambda form=form, k=k, n=n: _grouped(form, 9216, k, n, 8))
       for form in ("product", "dx", "dw")
       for which, (k, n) in NEMOTRON3_EXPERTS.items()},
    "grouped_dw_f32_1024x2688x1856_g4":
        lambda: _grouped("dw", 1024, 2688, 1856, 4, F32),
    "paged_w1_h12_d64_p16": lambda: _paged(1),
    "paged_w4_h12_d64_p16": lambda: _paged(4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, for_the_chip):
    fn, args = CASES[case]()
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    # trace_reduce counts a `while` instruction's event and its body's
    # both: no kernel's wrapper may put one in the step
    assert not re.search(r"\bwhile\(", text)
    # the kernel's name reaches the chip's program twice: as the
    # instruction's own name, which a device trace shows, and in its
    # op_name, which obs.costmodel.step_op_scopes maps it to
    from paddle1_tpu.obs import costmodel
    scopes, _ = costmodel.parse_op_scopes(text)
    calls = re.findall(r"^\s+(?:ROOT\s+)?%(\S+) = .*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert calls
    for name in calls:       # p1t_layer_norm_fwd.3, jvp_p1t_fused_bn_..._.1
        kernel = re.search(r"p1t_[a-z0-9_]*[a-z0-9]", name)
        assert kernel, name
        assert kernel.group(0) in scopes[name], (name, scopes[name])


# one backward call of each cell that runs the kernels: (q's shape, key /
# value heads, value width, rule, whether the default 16 MiB of scoped
# VMEM would do)
BACKWARD_CALLS = {
    "ouro_2p6b": (OURO, 16, 128, mask_rules.CAUSAL, True),
    "kanana2_30b_a3b": (KANANA2[:4], 32, 128, mask_rules.CAUSAL, False),
    "sdar_30b_a3b": (SDAR, 4, 128, SDAR_RULE, False),
    "lfm2_24b_a2b": (LFM2, 8, 64, mask_rules.CAUSAL, False),
    "smallthinker_21b_a3b_window": (SMALLTHINKER, 4, 128, WINDOW, False),
    "smallthinker_21b_a3b_global": (SMALLTHINKER, 4, 128, mask_rules.CAUSAL,
                                    False),
    # Laguna-XS.2 (ISSUE 47): 64 query heads in groups of 8 under a window
    # of one query block (its 48 in groups of 6 under the causal rule walk
    # lfm2's and smallthinker's 272 steps)
    "laguna_xs2_33b_a3b_window": ((1, 16384, 64, 128), 8, 128,
                                  mask_rules.SlidingWindow(512), False),
}


# the steps of a head of each cell's table at the shipped (512, 1024): the
# parent's rectangles had 288, 128, 512 and 32
TABLE_STEPS = {"ouro_2p6b": 20, "kanana2_30b_a3b": 72, "sdar_30b_a3b": 160,
               "lfm2_24b_a2b": 272, "smallthinker_21b_a3b_window": 140,
               "smallthinker_21b_a3b_global": 272,
               "laguna_xs2_33b_a3b_window": 47}


@pytest.mark.parametrize("cell", sorted(BACKWARD_CALLS))
def test_a_cells_forward_call_walks_the_table_in_the_default_vmem(
        cell, forward_text, for_the_chip):
    """The forward kernel at each cell's shape compiles for the v5e with
    the table of needed pairs as its three scalar-prefetched columns, a
    grid of batch x heads by the table's steps, and keeps no more than a
    block of any operand: Mosaic uses under the default 16 MiB."""
    text = forward_text(cell)
    call, = re.findall(r"^.*%p1t_flash_attention_fwd\S* = .*$", text, re.M)
    assert call.count("s32[%d]{0}" % TABLE_STEPS[cell]) >= 3
    assert 0 < _scoped_vmem(call, "used_") <= 16 << 20


@pytest.fixture(scope="module")
def forward_text(one_chip):
    """cell -> the compiled text of one forward call at its shape, made
    once for the tests that read it."""
    import functools

    @functools.cache
    def text(cell):
        (b, s, h, d), h_kv, dv, rule, _ = BACKWARD_CALLS[cell]
        struct = lambda *dims: jax.ShapeDtypeStruct(dims, BF16,
                                                    sharding=one_chip)
        return jax.jit(functools.partial(
            flash_attention._fwd_call.__wrapped__, scale=d ** -0.5,
            rule=rule, blocks=None, interpret=False)).lower(
            struct(b, s, h, d), struct(b, s, h_kv, d),
            struct(b, s, h_kv, dv), None).compile().as_text()
    return text


def _replicated_statistics(text):
    """What a row statistic held across 128 lanes leaves in a compiled
    text when it is written out so (ISSUE 49; the parent's text holds
    both at every shape here): a float32 output of the forward attention
    kernel whose minor dimension is 128, beside ``out``, and XLA's
    ``copy`` of a float32 ``[heads, n, 128]`` array behind it."""
    hits = re.findall(r"= f32\[\d+,\d+,128\]\S* copy\(.*?\)", text)
    for name, call in _kernel_calls(text):
        if name == "p1t_flash_attention_fwd":
            hits += re.findall(r"f32\[[\d,]*\b128\]",
                               call.split(" custom-call(")[0])
    return hits


@pytest.mark.parametrize("cell", sorted(BACKWARD_CALLS))
def test_a_cells_forward_call_writes_its_lse_as_one_row_a_head(
        cell, forward_text, for_the_chip):
    """The forward kernel at each cell's shape writes ``out`` and one
    float32 ``[1, Nq]`` row a head, the backward kernel's ``stat`` block,
    and the ``[B*H, Nq]`` the wrapper returns is a bitcast of it: no
    128-lane copy of the LSE leaves VMEM, and XLA has nothing to relay
    out (ISSUE 49: 2.42 GB written and 7.1 ms of copies a laguna step)."""
    (b, s, h, _), _, _, _, _ = BACKWARD_CALLS[cell]
    text = forward_text(cell)
    assert _replicated_statistics(text) == []
    call, = re.findall(r"^.*%p1t_flash_attention_fwd\S* = .*$", text, re.M)
    written = call.split(" custom-call(")[0]
    assert re.findall(r"f32\[[\d,]*\]", written) == [
        "f32[%d,1,%d]" % (b * h, s)]
    assert not re.search(r"\bf32\[%d,%d,128\]" % (b * h, s), text)
    # what XLA still copies of it is the row itself, from the kernel's
    # rows of one sublane to the [B*H, Nq] array's tiles of eight: 4 bytes
    # a (head, query)
    assert set(re.findall(r"= (f32\[[\d,]*\])\S* copy\(", text)) <= {
        "f32[%d,1,%d]" % (b * h, s)}


def test_the_census_of_replicated_statistics_sees_the_parents_form():
    assert _replicated_statistics(
        "  %p1t_flash_attention_fwd.1 = (bf16[1,16384,8192]{2,1,0}, "
        "f32[64,16384,128]{2,1,0}) custom-call(%a), "
        'custom_call_target="tpu_custom_call"\n'
        "  %copy.1 = f32[64,16384,128]{1,0,2} copy(%get-tuple-element.2)"
    ) == ["= f32[64,16384,128]{1,0,2} copy(%get-tuple-element.2)",
          "f32[64,16384,128]"]


def _scoped_vmem(call, which="", offset="0"):
    """The scoped VMEM a compiled kernel instruction was allowed
    (``which`` ""; behind scalar-prefetched operands it starts at an
    ``offset`` of its own), or what Mosaic used of it ("used_")."""
    return int(re.search(
        r'"%sscoped_memory_configs":\[\{"memory_space":"1","offset":"%s",'
        r'"size":"(\d+)"' % (which, offset), call).group(1))


@pytest.mark.parametrize("cell", sorted(BACKWARD_CALLS))
def test_a_cells_backward_call_asks_for_less_vmem_than_the_limit_it_sets(
        cell, one_chip, for_the_chip, monkeypatch):
    """The one backward kernel keeps a key head's whole dK and dV resident
    (ISSUE 35), so it sets its own scoped-VMEM limit from its shapes:
    every key in one range at each cell's shape, under half the v5e's 128
    MiB, and what Mosaic uses (the compiled instruction says) is under
    it. At the default 16 MiB Mosaic refuses Kanana-2's and SDAR's (the
    limit is what lets them compile) and takes Ouro's."""
    import functools
    from paddle1_tpu.ops.pallas import flash_attention_bwd as fb
    (b, s, h, d), h_kv, dv, rule, default_does = BACKWARD_CALLS[cell]
    blocks = fb.block_sizes(*rule.sizes(s, s), max(d, dv), BF16)
    assert fb.key_span(s, *blocks, d, dv, BF16) == s
    limit = fb._vmem_bytes(s, *blocks, d, dv, BF16)
    assert 16 << 20 < limit < 64 << 20

    def compiled():
        struct = lambda *dims, dtype=BF16: jax.ShapeDtypeStruct(
            dims, dtype, sharding=one_chip)
        # a jit of its own: the wrapper's would hand back its first trace
        call = jax.jit(functools.partial(
            fb._bwd_call.__wrapped__, scale=d ** -0.5, rule=rule,
            blocks=None, interpret=False))
        return call.lower(
            struct(b, s, h, d), struct(b, s, h_kv, d), struct(b, s, h_kv, dv),
            struct(b, s, h, dv), struct(b * h, s, dtype=F32),
            struct(b, s, h, dv), None).compile().as_text()

    text = compiled()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the instruction says what it was allowed and what Mosaic used
    call, = re.findall(r"^.*%p1t_flash_attention_bwd_dkv\S* = .*$", text,
                       re.M)
    allowed, used = _scoped_vmem(call), _scoped_vmem(call, "used_")
    assert allowed == limit and 0 < used < allowed
    assert (used <= 16 << 20) == default_does
    # and it walks the table of needed pairs: three scalar-prefetched
    # columns, a step a pair (ISSUE 39)
    steps = mask_rules.pair_table(rule, s, s, *blocks[:2]).steps
    assert steps == TABLE_STEPS[cell]
    assert call.count("s32[%d]{0}" % steps) >= 3
    monkeypatch.setattr(fb, "_vmem_bytes", lambda *a: 16 << 20)
    if default_does:
        compiled()
    else:
        with pytest.raises(Exception, match="(?i)vmem"):
            compiled()


def test_every_kernel_in_the_tree_is_compiled_here():
    """The ``name="p1t_*"`` strings under ``ops/pallas/`` are the kernels
    that ``CASES`` traces to, no more and no fewer: a kernel that ``auto``
    can pick on a TPU has a compile for the v5e above, and a kernel that
    left the tree left its case."""
    import glob
    from paddle1_tpu.ops import pallas
    in_tree = set()
    for path in glob.glob(os.path.join(os.path.dirname(pallas.__file__),
                                       "*.py")):
        with open(path) as f:
            in_tree.update(re.findall(r'name="(p1t_\w+)"', f.read()))
    compiled = set()
    for case in CASES.values():
        fn, args = case()
        jaxpr = jax.make_jaxpr(fn)(
            *[jax.ShapeDtypeStruct(s, dt) for s, dt in args])
        compiled.update(re.findall(r"\bname=(p1t_\w+)", str(jaxpr)))
    assert in_tree == compiled


def _recomputed(one_chip, layers, shape, more=lambda: (), scope="loss",
                precision="default", f32=()):
    """Compiled text of loss and gradients of ``layers``, one after the
    other on a bf16 input of ``shape``, each under
    ``fleet.utils.recompute`` (and, where there are several, under a scope
    of its index), traced as ``make_train_step`` traces a model: tape
    off, ``jax.value_and_grad`` outside. The state is bf16
    ``ShapeDtypeStruct``s but for the names in ``f32``; ``more()`` makes a
    layer's inputs beside the stream inside the trace."""
    from paddle1_tpu.autograd import engine as ae
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.distributed.fleet.utils.recompute import recompute
    states = [{k: jax.ShapeDtypeStruct(v.shape, F32 if k in f32 else BF16,
                                       sharding=one_chip)
               for k, v in layer.state_dict().items()} for layer in layers]

    def loss(states, x):
        h = Tensor(x)
        with contextlib.ExitStack() as stack:
            if scope:
                stack.enter_context(jax.named_scope(scope))
            stack.enter_context(ae.no_grad())
            stack.enter_context(ae.traced_scopes())
            for i, (layer, state) in enumerate(zip(layers, states)):
                with layer.load_functional_state(state), (
                        jax.named_scope(str(i)) if len(layers) > 1
                        else contextlib.nullcontext()):
                    h = recompute(layer, h, *map(Tensor, more()))
        return (h.data.astype(F32) ** 2).mean()

    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            states, jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
        ).compile().as_text()


def _kernel_calls(text):
    """[(the kernel's name, its line)] of the text's ``tpu_custom_call``s
    that a ``p1t_*`` kernel of ours names, in the text's order."""
    return [(re.search(r"%\w*?(p1t_[a-z_]*[a-z])", c).group(1), c)
            for c in re.findall(
                r'^.*custom_call_target="tpu_custom_call".*$', text, re.M)
            if "p1t_" in c.split(" = ")[0]]


def _computations(text):
    """{computation name: its instruction lines} of an HLO module, the
    entry computation under ``"ENTRY"`` too."""
    out, body = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            body = out.setdefault(m.group(2), [])
            if m.group(1):
                out["ENTRY"] = body
        elif line.startswith("}"):
            body = None
        elif body is not None:
            body.append(line)
    return out


def _multiplies_by_zero(text, elements):
    """Instructions ``multiply(a, broadcast(constant(0)))`` of at least
    ``elements`` elements, in any computation."""
    hits = []
    for lines in _computations(text).values():
        zeros, zero_broadcasts = set(), set()
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                         r"(\w[\w\-]*)\((.*?)\)", line)
            if not m:
                continue
            name, dims, op, operands = m.groups()
            names = set(re.findall(r"%([\w.\-]+)", operands))
            if op == "constant" and operands.strip() in ("0", "-0"):
                zeros.add(name)
            elif op == "broadcast" and names & zeros:
                zero_broadcasts.add(name)
            elif op == "multiply" and names & zero_broadcasts:
                size = 1
                for d in dims.split(","):
                    size *= int(d or 1)
                if size >= elements:
                    hits.append(line.strip()[:160])
    return hits


def _stage1_bottleneck(one_chip, fused):
    """Compiled text of loss + gradients of one stage-1 bottleneck
    (b128, 56x56, 256 -> 64 -> 64 -> 256, bf16, ``relu(bn(conv(x)))``,
    residual add) traced as ``make_train_step`` traces a model: tape
    off, ``jax.grad`` outside, the batch statistics leaving as aux."""
    from paddle1_tpu.autograd import engine as ae
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.nn import functional as F
    from paddle1_tpu.nn.functional.norm import collect_stat_updates

    def conv_bn(x, p, i, pad, res=None):
        x = F.conv2d(x, Tensor(p[f"w{i}"]), padding=pad)
        c = p[f"g{i}"].shape[0]
        x = F.batch_norm(x, Tensor(jnp.zeros((c,), BF16)),
                         Tensor(jnp.ones((c,), BF16)), Tensor(p[f"g{i}"]),
                         Tensor(p[f"b{i}"]), training=True)
        return F.relu(x if res is None else x + res)

    def loss(p, x):
        with ae.no_grad(), collect_stat_updates() as sink:
            t = Tensor(x)
            out = conv_bn(t, p, 1, 0)
            out = conv_bn(out, p, 2, 1)
            out = conv_bn(out, p, 3, 0, res=t)
        return ((out.data.astype(F32) ** 2).mean(),
                [u.value for u in sink])

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)

    p = {"w1": s(64, 256, 1, 1), "w2": s(64, 64, 3, 3),
         "w3": s(256, 64, 1, 1), "g1": s(64), "b1": s(64), "g2": s(64),
         "b2": s(64), "g3": s(256), "b3": s(256)}
    with flags_guard(fused_bn=fused):
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)).lower(
                p, s(128, 256, 56, 56)).compile().as_text()


def test_bottleneck_batch_norm_fuses_into_the_convolutions(
        one_chip, for_the_chip, monkeypatch):
    """Training-mode batch norm is an XLA composition the compiler is
    free to fuse, whatever ``fused_bn`` says: no custom call, no pass
    over a zero cotangent, each norm's two sums out of a fusion that
    holds a convolution, at most two layout copies of an activation. A
    compile guards the fusion, not the time."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # one call site: the text carries its caller's line
    auto, always = [_stage1_bottleneck(one_chip, fused)
                    for fused in ("auto", "always")]
    assert always == auto
    full = 128 * 56 * 56 * 64
    assert "tpu_custom_call" not in auto
    assert _multiplies_by_zero(auto, full) == []
    by_zero = jax.jit(lambda x: x * jnp.zeros((), F32)).lower(
        jax.ShapeDtypeStruct((128, 56, 56, 64), F32, sharding=one_chip)
    ).compile().as_text()
    assert _multiplies_by_zero(by_zero, full)     # the census can see one

    # the forward of each of the three norms: a fusion that holds the
    # convolution and gives (sum[C], sum of squares[C], activation);
    # autodiff marks the backward's instructions transpose(jvp(..))
    bodies = _computations(auto)
    forward_sums = []
    for line in bodies["ENTRY"]:
        m = re.search(r"= \(f32\[(\d+)\]\S*, f32\[\1\]\S*, "
                      r"bf16\[128,56,56,\1\]\S*\) fusion\(.*"
                      r"calls=%([\w.\-]+)", line)
        if m and "transpose(jvp" not in line:
            body = "\n".join(bodies[m.group(2)])
            assert " convolution(" in body and " reduce(" in body, line
            forward_sums.append(int(m.group(1)))
    assert sorted(forward_sums) == [64, 64, 256]

    def activation_copies(entry):
        return len(re.findall(r"= bf16\[128,(?:56,56,\d+|\d+,56,56)\]\S* "
                              r"copy\(", "\n".join(entry)))
    assert activation_copies(bodies["ENTRY"]) <= 2


def test_a_recomputed_ouro_block_runs_the_forward_kernel_once(
        one_chip, for_the_chip, monkeypatch):
    """One decoder block at Ouro-2.6B's widths ([2, 4096, 2048] bf16, 16
    heads x 128) under ``fleet.utils.recompute``, loss and gradients: the
    recomputation keeps the attention kernel's ``out`` and ``lse``, so
    the backward pass holds the one backward kernel (it keeps dK/dV's
    name and writes dQ too: ISSUE 35) and no second forward kernel
    (ISSUE 30: the parent's text has two). Traced as
    ``make_train_step`` traces a model: tape off, ``jax.grad`` outside."""
    from paddle1_tpu.text.models import OuroDecoderLayer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _recomputed(one_chip, [OuroDecoderLayer(2048, 16, 128, 5632)],
                       (2, 4096, 2048), scope=None, precision=None)
    calls = re.findall(r'^.*custom_call_target="tpu_custom_call".*$', text,
                       re.M)
    ours = _kernel_calls(text)
    assert len(calls) == len(ours)
    kernels = sorted(name for name, _ in ours)
    # no more instances than the parent's two: a crossed tile's sub-tiles
    # are code inside the backward kernel, not kernels of their own
    # (ISSUE 48)
    assert kernels == ["p1t_flash_attention_bwd_dkv",
                       "p1t_flash_attention_fwd"]
    # the rest of the block is still run again in the backward pass
    assert "/rematted_computation/" in text
    assert not [c for c in calls if "/rematted_computation/" in c]
    # and the kept LSE is the row the kernel wrote (ISSUE 49)
    assert _replicated_statistics(text) == []


def _float32_arrays_of_q(text):
    """Instructions outside fusion bodies whose result is float32 and
    shaped like q at Ouro's size ([2, 4096, 16, 128]), like its
    projection ([2, 4096, 2048]) or half a head wide: arrays in HBM."""
    bodies = _computations(text)
    fused = set(re.findall(r"\bcalls=%([\w.\-]+)", text))
    return [line.strip()[:120]
            for name, lines in bodies.items() if name not in fused
            for line in lines
            if re.search(r" = f32\[2,4096,(16,128|2048|\d+,64)\]\S* "
                         r"(?!bitcast|get-tuple-element|parameter)\w", line)]


# how q turns: one theta over the whole head (ISSUE 45), YaRN's table and
# factor over half of it (ISSUE 47: Laguna-XS.2's full-attention layers)
TURNS = {"theta_whole_head": dict(theta=1e6),
         "yarn_half_a_head": dict(
             frequencies=[500000.0 ** (-i / 32) / (1 if i < 8 else 64)
                          for i in range(32)], scale=1.4158883083359672)}


@pytest.mark.parametrize("turn", sorted(TURNS))
def test_rotary_behind_a_projection_leaves_no_float32_array_of_q(
        turn, one_chip, for_the_chip):
    """``rotary_embedding`` behind a bf16 projection at Ouro's shape,
    loss and gradients (ISSUE 45): the pass at the full head width and
    its hand-written backward fuse, and no float32 array of q's shape, of
    the projection's or half a head wide is written; a span of 64 of the
    128 channels under a table of its own is the same pass and writes
    none either (ISSUE 47). Sliced at half the width and concatenated, as
    the op stood, the text holds such arrays (two of 67 MB, and the
    halves): the census can see them."""
    from paddle1_tpu.autograd import engine as ae
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.nn import functional as F
    from op_test import rotary_by_halves
    how = TURNS[turn]

    def compiled(rotary):
        def loss(w, x):
            q = rotary((x @ w).reshape(2, 4096, 16, 128))
            return (q.astype(F32) ** 2).mean()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            jax.ShapeDtypeStruct((2048, 2048), BF16, sharding=one_chip),
            jax.ShapeDtypeStruct((2, 4096, 2048), BF16, sharding=one_chip)
        ).compile().as_text()

    def op(q):
        with ae.no_grad():
            return F.rotary_embedding(Tensor(q), **how).data
    text = compiled(op)
    assert _float32_arrays_of_q(text) == []
    # nor an array of any type a quarter of a head wide (the halves of a
    # span of 64)
    assert not re.search(r"\[2,4096,16,32\]|\[8192,16,32\]", text)
    assert len(_float32_arrays_of_q(
        compiled(lambda q: rotary_by_halves(
            q, how.get("theta"), jnp.arange(4096), False,
            how.get("frequencies"), how.get("scale", 1.0))))) >= 2


def test_a_recomputed_sdar_block_holds_no_dense_mask_and_no_copy_of_k_or_v(
        one_chip, for_the_chip, monkeypatch):
    """One decoder block of SDAR's step at the cell's shape ([1, 2 x 8192,
    2048] bf16, 32 query heads over 4 key/value heads of 128, blocks of 4,
    16 of 128 experts, top-8) under ``fleet.utils.recompute``, loss and
    gradients (ISSUE 33): its attention is the two blockwise kernels
    under block diffusion's rule, the forward one not run again; the
    kernels read k and v 4 heads wide and the backward one writes dK and
    dV so, and dQ 32 heads wide (no copy per query head, no partial
    gradient); nothing in the text is shaped like the dense mask or
    the scores of the doubled row; the sum of a token's 8 picks is the
    kernel."""
    from paddle1_tpu.text.models import SdarDecoderLayer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    length = 8192
    layer = SdarDecoderLayer(
        2048, dict(num_heads=32, num_kv_heads=4, head_dim=128,
                   block_length=4),
        dict(expert_width=768, num_experts=128, top_k=8, held=(0, 16)))
    text = _recomputed(
        one_chip, [layer], (1, 2 * length, 2048),
        more=lambda: (jnp.tile(jnp.arange(length, dtype=I32), 2),))
    calls = dict(_kernel_calls(text))
    assert sorted(calls) == ["p1t_flash_attention_bwd_dkv",
                             "p1t_flash_attention_fwd", "p1t_sum_picks_fwd"]
    assert len(re.findall(r"%\w*p1t_flash_attention_fwd[.\d]* = ", text)) == 1
    narrow, wide = "bf16[1,16384,512]", "bf16[1,16384,4096]"
    one_range = "bf16[1,1,16384,4096]"      # dQ: a partial a key range

    def operands(call):
        return re.findall(r"(?:bf16|f32)\[[\d,]*\]", re.search(
            r"operand_layout_constraints=\{(.*?)\}\}", call).group(1))
    assert operands(calls["p1t_flash_attention_fwd"]) == [wide, narrow,
                                                          narrow]
    backward = calls["p1t_flash_attention_bwd_dkv"]
    assert operands(backward)[:4] == [wide, narrow, narrow, wide]
    # it writes dQ at 32 heads and dK and dV at 4, summed over each one's
    # 8 query heads inside
    written = backward.split(" custom-call(")[0]
    assert (written.count(one_range), written.count(narrow)) == (1, 2)
    # no dense mask, no scores of the doubled row, in any layout
    assert not re.search(r"\[(\d+,)*16384,16384\]", text)
    assert not re.search(r"\bwhile\(", text)
    assert "/rematted_computation/" in text
    assert _replicated_statistics(text) == []     # ISSUE 49


def test_recomputed_smallthinker_blocks_name_their_kind_and_route_first(
        one_chip, for_the_chip, monkeypatch):
    """A global and a window block of SmallThinker's step at the cell's
    shape ([1, 16384, 2560] bf16, 28 query heads over 4 of 128, a window
    of 4,096, 8 of 64 ReLU-gated experts, top-6) under
    ``fleet.utils.recompute``, loss and gradients (ISSUE 43): each
    block's attention is the two blockwise kernels under a scope that
    names its kind, the forward one not run again; Mosaic takes the
    backward's VMEM request with a key head's dK and dV resident while 7
    query heads pass; k, v, dK and dV stay 4 heads wide; the router's
    product reads the block's input and is float32; rotary is on the
    window block alone; every grouped product lies in a region under the
    expert layer's scope; nothing is shaped like a dense mask."""
    from paddle1_tpu.obs import costmodel
    from paddle1_tpu.text.models import SmallThinkerDecoderLayer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    experts = dict(expert_width=768, num_experts=64, top_k=6, held=(0, 8))
    text = _recomputed(one_chip, [SmallThinkerDecoderLayer(
        2560, dict(num_heads=28, num_kv_heads=4, head_dim=128, window=window,
                   rotary=window is not None), experts)
        for window in (None, 4096)], (1, 16384, 2560))
    assert not re.search(r"\bwhile\(", text)
    assert not re.search(r"\[(\d+,)*16384,16384\]", text)
    scopes, _ = costmodel.parse_op_scopes(text)
    kernels = sorted((re.sub(r"\.\d+$", "", n), costmodel.region_of(s),
                      "global" if "/self_attn/global/" in s else
                      "window" if "/self_attn/window/" in s else None,
                      "rematted_computation" in s)
                     for n, s in scopes.items()
                     if n.startswith("p1t_flash_attention"))
    assert kernels == [
        ("p1t_flash_attention_bwd_dkv", "backward", "global", False),
        ("p1t_flash_attention_bwd_dkv", "backward", "window", False),
        ("p1t_flash_attention_fwd", "forward", "global", False),
        ("p1t_flash_attention_fwd", "forward", "window", False)], kernels
    # groups of 7: k and v go in, and dK and dV come out, 4 heads wide;
    # what the instruction uses of VMEM is over the default 16 MiB (the
    # call's own limit is what lets it compile: the cell's backward call,
    # above) and, with what XLA adds round a kernel inside a larger
    # program, under what one call may ask
    from paddle1_tpu.ops.pallas import flash_attention_bwd as fb
    narrow = "bf16[1,16384,512]"
    for call in re.findall(r"^.*%p1t_flash_attention_bwd_dkv\S* = .*$", text,
                           re.M):
        assert call.split(" custom-call(")[0].count(narrow) == 2
        assert 16 << 20 < _scoped_vmem(call, "used_") < fb._VMEM_CAP
    assert _replicated_statistics(text) == []     # ISSUE 49
    # positions on the window block alone
    rotary = {block for s in scopes.values()
              if "/self_attn/rotary_embedding" in s
              for block in re.findall(r"/([01])/", s)}
    assert rotary == {"1"}
    # every grouped product in a region under the expert layer's scope
    products = [scopes[n] for n in scopes
                if re.match(r"ragged-dot-none(\.\d+)?$", n)]
    assert len(products) == 2 * 8 and all(
        w.endswith("/moe/routed_experts") and costmodel.region_of(w)
        for w in products), products
    # the router: a float32 product over the 64 experts, from the block's
    # input (2560 wide, not the experts' rows)
    router = [l for l in text.splitlines() if "/moe/moe_router/" in l
              and re.search(r"= f32\[16384,64\]\S* (fusion|convolution|dot)\(",
                            l)]
    assert router
    for op in ("moe_dispatch", "moe_combine", "moe_overflow"):
        assert any(f"/mlp/moe/{op}" in s for s in scopes.values()), op


def test_recomputed_laguna_attention_gates_its_heads_by_its_own_count(
        one_chip, for_the_chip, monkeypatch):
    """A full and a sliding attention layer of Laguna-XS.2's step at the
    cell's shape ([1, 16384, 2048] bf16; 48 query heads over 8 of 128
    under the causal rule with YaRN over half a head; 64 under a window of
    512 with one theta over the whole head) under
    ``fleet.utils.recompute``, loss and gradients (ISSUE 47): each layer's
    attention is the two blockwise kernels under a scope that names its
    kind, the forward one not run again, at its own head count (q 6,144
    and 8,192 wide; k, v, dK and dV stay 8 heads wide); the gate's
    product of one float a position a head lies under ``gate`` in both;
    rotary on both, and neither leaves a float32 array of q's shape or
    half a head wide; nothing is shaped like a dense mask. (The whole
    step with its expert layers: ``benchmarks/tools/aot_compile.py``.)"""
    from paddle1_tpu.obs import costmodel
    from paddle1_tpu.text.models import LagunaAttention
    from paddle1_tpu.text.models.laguna import rotary_arguments
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yarn = rotary_arguments(128, {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5})
    assert len(yarn["frequencies"]) == 32
    text = _recomputed(one_chip, [
        LagunaAttention(2048, 48, 8, 128, rotary=yarn),
        LagunaAttention(2048, 64, 8, 128, window=512,
                        rotary=dict(theta=10000))], (1, 16384, 2048))
    assert not re.search(r"\bwhile\(", text)
    assert not re.search(r"\[(\d+,)*16384,16384\]", text)
    scopes, _ = costmodel.parse_op_scopes(text)
    kernels = sorted((re.sub(r"\.\d+$", "", n), costmodel.region_of(s),
                      "global" if "/global/" in s else
                      "window" if "/window/" in s else None,
                      "rematted_computation" in s)
                     for n, s in scopes.items()
                     if n.startswith("p1t_flash_attention"))
    assert kernels == [
        ("p1t_flash_attention_bwd_dkv", "backward", "global", False),
        ("p1t_flash_attention_bwd_dkv", "backward", "window", False),
        ("p1t_flash_attention_fwd", "forward", "global", False),
        ("p1t_flash_attention_fwd", "forward", "window", False)], kernels
    # groups of 6 and of 8: q goes in 48 and 64 heads wide, k and v go in,
    # and dK and dV come out, 8 heads wide
    narrow = "bf16[1,16384,1024]"
    wide = {"bf16[1,16384,6144]", "bf16[1,16384,8192]"}
    calls = re.findall(r"^.*%p1t_flash_attention_bwd_dkv\S* = .*$", text,
                       re.M)
    assert len(calls) == 2
    for call in calls:
        assert call.split(" custom-call(")[0].count(narrow) == 2
    assert {w for call in calls for w in wide if w in call} == wide
    # the gate: under its scope in both layers, forward and backward, and
    # none of it the attention op's
    gate = [s for s in scopes.values() if "/gate/" in s]
    assert {costmodel.region_of(s) for s in gate} >= {"forward", "backward"}
    assert {kind for s in gate for kind in ("/0/", "/1/") if kind in s} \
        == {"/0/", "/1/"}
    assert not [s for s in gate if "scaled_dot_product_attention" in s]
    # positions on both layers; no float32 array of q's shape, and nothing
    # half or a quarter of a head wide
    assert {kind for s in scopes.values() if "/rotary_embedding" in s
            for kind in ("/0/", "/1/") if kind in s} == {"/0/", "/1/"}
    bodies = _computations(text)
    fused = set(re.findall(r"\bcalls=%([\w.\-]+)", text))
    written = [line.strip() for name, lines in bodies.items()
               if name not in fused for line in lines
               if re.search(r" = f32\[(1,)?16384,(48,128|64,128|6144|8192|"
                            r"\d+,64|\d+,32)\]\S* "
                            r"(?!bitcast|get-tuple-element|parameter)\w", line)]
    assert not [line[:120] for line in written if "rotary_embedding" in line]
    # (what is written at that width is the gate's, in this text alone:
    # behind the float32 loss of two lone layers its backward hands the
    # attention op's backward its dO as float32; in the cell's whole step
    # dO arrives in bf16 and ``delta``'s reduction fuses with it, on the
    # chip and compiled here: PERF.md section 7, "From PR 47" (1))
    assert all("/scaled_dot_product_attention/jit(_bwd_call)/" in line
               or "/gate/" in line for line in written), written
    # both layers' LSE leaves its kernel as one row a head (ISSUE 49)
    assert _replicated_statistics(text) == []


def test_the_expert_layer_gathers_no_row_for_a_pick_it_does_not_hold(
        one_chip, for_the_chip, monkeypatch):
    """Kanana-2's expert layer at the cell's shape ([2, 8192, 2048] bf16,
    16 of 128 experts, top-6: 98,304 picks over 36,864 rows) under
    ``fleet.utils.recompute``, loss and gradients (ISSUE 32). The sum of
    a token's picks is the kernel, once forward under ``moe_combine`` and
    once backward as the transpose of ``moe_dispatch``'s gather, and not
    again in the recomputed segment; the text holds none of what the
    gather over every pick made: no ``[36865, 2048]`` rows with a row of
    zeros behind them, no ``[98304, 2048]`` or ``[6, 16384, 2048]`` buffer
    of every pick's row (402 MB), no ``[16384, 6, 2048]`` relayout, in
    either layout of a row. The grouped products are all still under the
    layer's scope in their pass."""
    from paddle1_tpu import nn
    from paddle1_tpu.obs import costmodel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = nn.RoutedExperts(2048, 768, 128, 6, held=(0, 16),
                             shared_width=1536, routed_scaling_factor=2.448)
    text = _recomputed(one_chip, [layer], (2, 8192, 2048),
                       f32=("e_score_correction_bias",))
    for gone in (r"\[36865,2048\]", r"\[98304,2048\]", r"\[98304,16,128\]",
                 r"\[6,16384,2048\]", r"\[6,16384,16,128\]",
                 r"\[16384,6,2048\]", r"\[16384,6,16,128\]"):
        assert not re.search(gone, text), gone
    assert not re.search(r"\bwhile\(", text)
    scopes, _ = costmodel.parse_op_scopes(text)
    kernels = sorted(scopes[n] for n in scopes
                     if re.match(r"p1t_sum_picks_fwd(\.\d+)?$", n))
    assert [(costmodel.region_of(k), k.split("/moe/")[1].split("/")[0],
             "rematted_computation" in k) for k in kernels] == [
        ("forward", "moe_combine", False),
        ("backward", "moe_dispatch", False)], kernels
    products = sorted(scopes[n] for n in scopes
                      if re.match(r"ragged-dot-none(\.\d+)?$", n))
    assert len(products) == 8 and all(
        w.endswith("/moe/routed_experts") and costmodel.region_of(w)
        for w in products), products


def test_smallthinkers_expert_layer_gathers_no_row_it_does_not_hold(
        one_chip, for_the_chip, monkeypatch):
    """SmallThinker's expert layer at the cell's shape ([1, 16384, 2560]
    bf16, 8 of 64 ReLU-gated experts, top-6: 98,304 picks over 36,864
    rows of 10 lane rows of 32 bits, a packed tile and a quarter) under
    ``fleet.utils.recompute``, loss and gradients (ISSUE 44): the twin of
    Kanana-2's case above at a width that is no whole tile. The kernel
    once forward under ``moe_combine`` and once backward under
    ``moe_dispatch``, its rows ``[4, 640]`` deep as their lane rows
    allow, and nothing of what the gather over every pick made."""
    from paddle1_tpu import nn
    from paddle1_tpu.obs import costmodel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = nn.RoutedExperts(2560, 768, 64, 6, held=(0, 8),
                             scoring="softmax", gate_activation="relu")
    text = _recomputed(one_chip, [layer], (1, 16384, 2560))
    for gone in (r"\[36865,2560\]", r"\[98304,2560\]", r"\[98304,4,640\]",
                 r"\[6,16384,2560\]", r"\[6,16384,4,640\]",
                 r"\[16384,6,2560\]", r"\[16384,6,4,640\]"):
        assert not re.search(gone, text), gone
    assert not re.search(r"\bwhile\(", text)
    scopes, _ = costmodel.parse_op_scopes(text)
    kernels = sorted(scopes[n] for n in scopes
                     if re.match(r"p1t_sum_picks_fwd(\.\d+)?$", n))
    assert [(costmodel.region_of(k), k.split("/moe/")[1].split("/")[0],
             "rematted_computation" in k) for k in kernels] == [
        ("forward", "moe_combine", False),
        ("backward", "moe_dispatch", False)], kernels
    for call in re.findall(r"^.*%p1t_sum_picks_fwd\S* = .*$", text, re.M):
        assert call.split(" custom-call(")[0].count(
            "bf16[16384,4,640]{2,1,0:T(4,128)(2,1)") == 1, call
        assert "bf16[36864,4,640]" in call
    products = sorted(scopes[n] for n in scopes
                      if re.match(r"ragged-dot-none(\.\d+)?$", n))
    assert len(products) == 8 and all(
        w.endswith("/moe/routed_experts") and costmodel.region_of(w)
        for w in products), products


def test_the_grouped_products_keep_their_scope(one_chip, for_the_chip):
    """The TPU's compiler lowers ``lax.ragged_dot`` and its two transposes
    to grouped kernels it names ``ragged-dot-none`` itself, and drops the
    ``op_name``; the frontend attribute that ``grouped_matmul`` hands it
    survives, and ``parse_op_scopes`` puts each under the expert layer's
    scope in the pass its operands were made in: two products forward,
    one of them recomputed (the second's output is not needed again
    here) and four backward, named as a recomputed segment of
    ``make_train_step`` names its instructions."""
    from paddle1_tpu.nn import layer_moe
    from paddle1_tpu.obs import costmodel

    @jax.checkpoint
    def segment(xs, gate_up, down, sizes):
        with jax.named_scope("moe"):
            with jax.named_scope("moe_dispatch"):
                xs = xs * 2
            with jax.named_scope("routed_experts"):
                out = layer_moe.expert_ffn(xs, sizes, gate_up, down)
            with jax.named_scope("moe_combine"):
                return out * 3

    def loss(xs, gate_up, down, sizes):
        with jax.named_scope("loss"), jax.named_scope("Model"):
            return jnp.sum(segment(xs, gate_up, down, sizes).astype(F32))

    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    # the cell's: 36864 rows, 16 held experts, 2048 -> 2 x 768 -> 2048;
    # the chip's default precision (conftest asks float32 products of the
    # CPU, which the grouped kernel refuses of bf16 operands)
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            s((36864, 2048)), s((16, 2048, 1536)), s((16, 768, 2048)),
            s((16,), I32)).compile().as_text()
    assert not re.search(r"\bwhile\(", text)
    scopes, _ = costmodel.parse_op_scopes(text)
    where = sorted(scopes[n] for n in scopes
                   if re.match(r"ragged-dot-none(\.\d+)?$", n))
    assert all(w.endswith("/moe/routed_experts") for w in where), where
    passes = sorted((costmodel.region_of(w), "rematted_computation" in w)
                    for w in where)
    assert passes == ([("backward", False)] * 4 + [("backward", True)]
                      + [("forward", False)] * 2), where


@pytest.mark.parametrize("which", sorted(NEMOTRON3_EXPERTS))
@pytest.mark.parametrize("form", ["product", "dx", "dw"])
def test_a_grouped_product_stays_inside_the_vmem_it_asks_for(
        form, which, one_chip, for_the_chip):
    """Each form at Nemotron 3 Nano's two shapes asks for its blocks'
    bytes (``_block_bytes`` of the tiles ``_tiles`` reads off the shape)
    and the slack beside them, under half the v5e's 128 MiB, and Mosaic
    uses less; its table is four scalar-prefetched operands, a step for
    each of the ``9216 / 256 + 8 - 1`` pairs a fill can hold."""
    k, n = NEMOTRON3_EXPERTS[which]
    fn, args = _grouped(form, 9216, k, n, 8)
    text = jax.jit(fn).lower(*[jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                               for s, dt in args]).compile().as_text()
    (name, call), = _kernel_calls(text)
    assert name == {"product": "p1t_grouped_matmul_fwd",
                    "dx": "p1t_grouped_matmul_bwd_dx",
                    "dw": "p1t_grouped_matmul_bwd_dw"}[form]
    tiles = (grouped_matmul._tiles(9216, n, k, 2) if form == "dx" else
             grouped_matmul._tiles(9216, k, n, 2, dw=form == "dw"))
    assert tiles[0] == 256 and (form == "dw" or tiles[1:] == (
        (n, k) if form == "dx" else (k, n)))       # both widths whole
    allowed, used = _scoped_vmem(call, offset=r"\d+"), _scoped_vmem(
        call, "used_")
    assert allowed == (grouped_matmul._block_bytes(*tiles, 2, form == "dw")
                       + grouped_matmul._VMEM_SLACK) < 64 << 20
    assert 0 < used < allowed
    assert call.count("s32[43]{0}") >= 2 and "s32[9]{0}" in call


def test_nemotrons_expert_segment_runs_the_repos_grouped_kernels(
        one_chip, for_the_chip):
    """The twin of the case above at Nemotron 3 Nano's widths (9216 rows,
    8 held experts without a gate, 2688 -> 1856 -> 2688), where XLA's own
    kernel would tile both widths at 128 (ISSUE 51): the segment holds no
    ``ragged-dot-none``; its seven products are the repo's kernels, each
    under the expert layer's scope in its pass (two forward, the first
    again in the recomputed segment, ``dx`` and ``dw`` of each backward),
    so ``routed_experts_ms`` keeps reading them."""
    from paddle1_tpu.nn import layer_moe
    from paddle1_tpu.obs import costmodel

    @jax.checkpoint
    def segment(xs, up, down, sizes):
        with jax.named_scope("moe"):
            with jax.named_scope("moe_dispatch"):
                xs = xs * 2
            with jax.named_scope("routed_experts"):
                out = layer_moe.plain_expert_ffn(
                    xs, sizes, up, down, layer_moe.GATE_ACTIVATIONS["relu2"])
            with jax.named_scope("moe_combine"):
                return out * 3

    def loss(xs, up, down, sizes):
        with jax.named_scope("loss"), jax.named_scope("Model"):
            return jnp.sum(segment(xs, up, down, sizes).astype(F32))

    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            s((9216, 2688)), s((8, 2688, 1856)), s((8, 1856, 2688)),
            s((8,), I32)).compile().as_text()
    assert not re.search(r"\bwhile\(", text)
    assert "ragged-dot" not in text and "ragged_dot" not in text
    scopes, _ = costmodel.parse_op_scopes(text)
    calls = [(kernel, scopes[re.search(r"%(\S+) = ", line).group(1)])
             for kernel, line in _kernel_calls(text)]
    assert all("/moe/routed_experts/" in where for _, where in calls), calls
    assert sorted((kernel, costmodel.region_of(where),
                   "rematted_computation" in where)
                  for kernel, where in calls) == [
        ("p1t_grouped_matmul_bwd_dw", "backward", False),
        ("p1t_grouped_matmul_bwd_dw", "backward", False),
        ("p1t_grouped_matmul_bwd_dx", "backward", False),
        ("p1t_grouped_matmul_bwd_dx", "backward", False),
        ("p1t_grouped_matmul_fwd", "backward", True),
        ("p1t_grouped_matmul_fwd", "forward", False),
        ("p1t_grouped_matmul_fwd", "forward", False)], calls


# ``lax.ragged_dot``s [rows, k] x [groups, k, n]: each expert cell's two
# products, and ISSUE 51's two probes
RAGGED_DOTS = {
    "nemotron3_up": (9216, 2688, 1856, 8),
    "nemotron3_down": (9216, 1856, 2688, 8),
    "kanana2_up": (36864, 2048, 1536, 16),
    "kanana2_down": (36864, 768, 2048, 16),     # sdar's too, at 49152 rows
    "sdar_up": (49152, 2048, 1536, 16),
    "lfm2_up": (24576, 2048, 3072, 8),
    "lfm2_down": (24576, 1536, 2048, 8),
    "smallthinker_up": (36864, 2560, 1536, 8),
    "smallthinker_down": (36864, 768, 2560, 8),
    "laguna_up": (24576, 2048, 1024, 16),
    "laguna_down": (24576, 512, 2048, 16),
    "probe_2560x1792": (9216, 2560, 1792, 8),
    "probe_2688x2048": (9216, 2688, 2048, 8),
}


@pytest.mark.parametrize("product", sorted(RAGGED_DOTS))
def test_xla_tile_is_the_tile_xla_gives_a_width(product, one_chip):
    """``grouped_matmul.xla_tile`` decides which arm a cell's products
    take, so it is held to the compiler it speaks for: the TPU's grouped
    kernel says its tiles on its instruction (``ragged_dot_tiling="m,k,
    n"``), a row tile of 512 and each width at ``xla_tile`` of it. A JAX
    that changes the rule fails here and moves no cell in silence."""
    rows, k, n, groups = RAGGED_DOTS[product]
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.lax.ragged_dot).lower(
            jax.ShapeDtypeStruct((rows, k), BF16, sharding=one_chip),
            jax.ShapeDtypeStruct((groups, k, n), BF16, sharding=one_chip),
            jax.ShapeDtypeStruct((groups,), I32, sharding=one_chip)
        ).compile().as_text()
    assert set(re.findall(r'ragged_dot_tiling="([\d,]+)"', text)) == {
        "512,%d,%d" % (grouped_matmul.xla_tile(k), grouped_matmul.xla_tile(n))}
    up_or_down = jax.ShapeDtypeStruct((rows, k), BF16), \
        jax.ShapeDtypeStruct((groups, k, n), BF16)
    assert grouped_matmul.supported(*up_or_down) == (
        min(grouped_matmul.xla_tile(k), grouped_matmul.xla_tile(n)) < 256)


def test_sum_picks_supported_admits_only_what_fits():
    def ok(rows, hidden, tokens, fan, dtype=BF16):
        return sum_picks.supported(jax.ShapeDtypeStruct((rows, hidden), dtype),
                                   jax.ShapeDtypeStruct((tokens * fan,), I32),
                                   fan)
    assert ok(36864, 2048, 16384, 6) and ok(4096, 1024, 2048, 8, F32)
    assert ok(8192, 2048, 32768, 6)
    # any whole number of 32-bit lane rows (ISSUE 44): SmallThinker's 10,
    # bf16 rows of half a packed tile, one lane row
    assert ok(36864, 2560, 16384, 6) and ok(36864, 1024, 16384, 6)
    assert ok(4096, 256, 2048, 6) and ok(4096, 128, 2048, 6, F32)
    assert not ok(8192, 2048, 32768, 8)     # 1 MiB of picks: SMEM's whole
    assert not ok(36864, 2048 + 128, 16384, 6)  # half a lane row over
    assert not ok(36864, 128, 16384, 6) and not ok(4096, 64, 2048, 6, F32)
    assert not ok(1 << 20, 2048, 16384, 6)  # a row past its 20 bits
    assert not ok(4096, 2048, 2048, 9)      # a slot past its 3 bits
    assert not ok(4096, 2048, 2044, 6)      # tokens in no block of eight
    assert not sum_picks.supported(
        jax.ShapeDtypeStruct((36864,), F32),
        jax.ShapeDtypeStruct((98304,), I32), 1)     # the weights' vector


def test_paged_supported_admits_only_what_compiles(one_chip, for_the_chip):
    """Every shape ``supported()`` admits at the edges of its ranges
    (narrowest and widest head dim, window and page) must compile."""
    for w, d, page, dt in [(1, 8, 8, F32), (64, 256, 8, BF16),
                           (1, 128, 24, BF16), (4, 64, 128, F32)]:
        q = (8, w, 4, d)
        pool = (16, 4, page, d)
        assert paged_attention.supported(q, pool)
        shapes = [jax.ShapeDtypeStruct(s, t, sharding=one_chip)
                  for s, t in [(q, dt), (pool, dt), (pool, dt),
                               ((8, 4), I32), ((8,), I32)]]
        compiled = jax.jit(paged_attention.paged_attention).lower(
            *shapes).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_gspmd_step_takes_the_xla_composition(topo, for_the_chip,
                                              monkeypatch):
    """GSPMD cannot partition a Mosaic kernel: on a mesh of four chips
    an ``auto`` kernel flag must resolve to the XLA composition inside
    ``auto_partitioned_region`` (where ParallelEngine traces a
    multi-device step), and the bare kernel is refused."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle1_tpu.core.flags import auto_partitioned_region
    from paddle1_tpu.core.tensor import Tensor
    from paddle1_tpu.nn import functional as F
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("dp", "mp"))
    x = jax.ShapeDtypeStruct((4096, 768), BF16,
                             sharding=NamedSharding(mesh, P("dp", None)))
    wb = jax.ShapeDtypeStruct((768,), F32,
                              sharding=NamedSharding(mesh, P()))

    def ln(x, w, b):
        return F.layer_norm(Tensor(x), 768, Tensor(w), Tensor(b)).data

    def ln_gspmd(x, w, b):
        with auto_partitioned_region():
            return ln(x, w, b)

    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(ln).lower(x, wb, wb)
    text = jax.jit(ln_gspmd).lower(x, wb, wb).compile().as_text()
    assert "tpu_custom_call" not in text


def test_a_recomputed_mamba2_layer_runs_the_scan_kernels_and_writes_no_decays(
        one_chip, for_the_chip, monkeypatch):
    """A Mamba-2 layer of Nemotron 3 Nano's step at the cell's shape ([1,
    8192, 2688] bf16; 64 heads of 64 over 8 groups of state 128, 4 taps)
    under ``fleet.utils.recompute``, loss and gradients (ISSUE 50): the
    scan is the two kernels under the op's scope inside the mixer's, the
    forward one run again in the recomputed segment (nothing of the layer
    carries a name), no ``[chunks, heads, 128, 128]`` float32 decays are
    written, and the projections, the convolution and the gated norm lie
    under their scopes."""
    from paddle1_tpu.obs import costmodel
    from paddle1_tpu.text.models.nemotron_h import NemotronHLayer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = NemotronHLayer(2688, "M", dict(
        num_heads=64, head_dim=64, n_groups=8, state_size=128))
    text = _recomputed(one_chip, [layer], (1, 8192, 2688))
    assert not re.search(r"f32\[(\d+,)*64,(\d+,)?128,128\]", text)
    scopes, _ = costmodel.parse_op_scopes(text)
    kernels = sorted((re.sub(r"\.\d+$", "", n), costmodel.region_of(s),
                      "rematted_computation" in s)
                     for n, s in scopes.items() if n.startswith("p1t_ssd"))
    assert kernels == [("p1t_ssd_bwd", "backward", False),
                       ("p1t_ssd_fwd", "backward", True),
                       ("p1t_ssd_fwd", "forward", False)], kernels
    assert all("/mamba/ssd_scan/" in s for n, s in scopes.items()
               if n.startswith("p1t_ssd"))
    for part in ("norm/rms_norm", "mamba/in_proj/linear",
                 "mamba/conv/causal_conv_silu", "mamba/ssd_scan",
                 "mamba/gated_norm/gated_rms_norm", "mamba/out_proj/linear"):
        assert {costmodel.region_of(s) for s in scopes.values()
                if "/" + part in s} >= {"forward", "backward"}, part


@pytest.mark.slow
def test_the_nemotron3_cells_step_fits_a_described_v5e(one_chip, for_the_chip,
                                                       monkeypatch):
    """The whole step of ``nemotron3_nano_30b_a3b.pretrain_s8192`` as the
    benchmark builds it, compiled for a described v5e (a minute and a
    half: ``slow``; ``benchmarks/tools/aot_compile.py`` prints the same):
    arguments + temporaries + the harness's 4 bytes a parameter stay
    under the chip's 15.75 GiB, and both scan kernels are in the text."""
    from benchmarks import spec, traffic
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = spec.load_json("workloads",
                          "nemotron3_nano_30b_a3b.pretrain_s8192.json")
    cfg = spec.config(cell["config"])
    env = traffic.environment(cfg, cell)
    program, reference = (spec.module(k, cfg) for k in ("program",
                                                        "reference"))
    w = jax.jit(lambda k: reference.init_params(cfg, k))(jax.random.key(0))
    engine = program.build(
        cfg, env, {p: (w[r] if i is None else w[r][i])
                   for p, r, i in program.leaves(cfg)},
        jax.devices()[:1])["engine"]
    batch = traffic.batches(cell, env, 0, 1)[0]
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (engine.params, engine.opt_state,
         {k: jnp.asarray(v) for k, v in batch.items()},
         jax.random.key(0), jnp.float32(0)))
    compiled = jax.jit(engine._step_fn, donate_argnums=(0, 1)).lower(
        *args).compile()
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + 4 * cfg["parameters"])
    assert held < 15.75 * 2 ** 30, held / 2 ** 30
    text = compiled.as_text()
    assert "p1t_ssd_fwd" in text and "p1t_ssd_bwd" in text
