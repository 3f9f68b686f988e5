"""fluid.layers breadth tier 2: the mechanical
mappings from the reference's 36k-LoC layers surface
(/root/reference/python/paddle/fluid/layers/{nn,tensor,loss,ops,
sequence_lod,detection,learning_rate_scheduler,rnn}.py) onto the modern
functional API. Star-imported into :mod:`paddle1_tpu.fluid.layers`; the
teaching ``__getattr__`` there still covers everything not mapped.

Grouping and policy:
* pure elementwise/reduction/manipulation ops → direct delegation;
* parameter-bearing layer ops (layer_norm, group_norm, conv2d_transpose,
  ...) → implicit-parameter creation through ``_implicit_layer`` (same
  per-creation semantics as fc/conv2d);
* LoD sequence ops → the dense+lengths analogs in
  ``ops.sequence_ops`` (fluid spelling, ``length``/``lengths`` kwarg
  instead of LoD — MIGRATING.md "LoD" section);
* detection ops → ``vision.ops``;
* LR decay functions → ``optimizer.lr`` scheduler objects (fluid's
  decay "Variables" become scheduler instances every optimizer
  accepts);
* genuinely program-construction APIs (StaticRNN/While/Switch/
  DynamicRNN) stay teaching errors in layers.py — their with-block
  bodies build a static program the eager shim cannot re-execute;
  ``nn.RNN``/``static.nn.while_loop`` are the working migrations.
"""

from __future__ import annotations

import builtins as _bi  # several fluid names (range/abs/sum/...) shadow
                        # builtins at module scope

import numpy as np

import paddle1_tpu as _paddle
from ..core.tensor import Tensor, to_tensor
from ..nn import functional as F
from ..ops import manip_ops as _manip, math_ops as _math
from ..ops import sequence_ops as _seq
from .layers import _implicit_layer, _t

__all__ = [
    # elementwise / compare / logical
    "elementwise_max", "elementwise_min", "elementwise_mod",
    "elementwise_pow", "elementwise_floordiv", "equal", "not_equal",
    "less_than", "less_equal", "greater_than", "greater_equal",
    "logical_and", "logical_or", "logical_not", "logical_xor",
    # reductions / creation
    "reduce_min", "reduce_prod", "reduce_all", "reduce_any",
    "ones", "zeros", "ones_like", "zeros_like", "eye", "linspace",
    "range", "diag", "fill_constant_batch_size_like", "create_tensor",
    "create_global_var", "sums", "sum",
    # manipulation
    "argmax", "argmin", "argsort", "slice", "strided_slice", "split",
    "stack", "unstack", "unbind", "squeeze", "unsqueeze", "unique",
    "unique_with_counts", "where", "multiplex", "triu", "expand",
    "expand_as", "pad", "pad2d", "pad_constant_like", "crop",
    "crop_tensor", "flatten", "transpose", "gather", "gather_nd",
    "scatter", "scatter_nd_add", "size", "shard_index", "reverse",
    "rank", "increment", "is_empty", "has_inf", "has_nan", "isfinite",
    "space_to_depth", "shuffle_channel",
    # activations / math
    "relu6", "leaky_relu", "elu", "selu", "swish", "mish",
    "hard_sigmoid", "hard_swish", "brelu", "soft_relu", "stanh",
    "maxout", "prelu", "sign", "pow", "scale",
    "rsqrt", "abs", "floor", "ceil", "round",
    "erf", "sin", "cos", "clip_by_norm", "l2_normalize",
    "label_smooth", "cumsum",
    # losses / metrics
    "mse_loss", "huber_loss", "smooth_l1", "log_loss", "kldiv_loss",
    "bpr_loss", "rank_loss", "margin_rank_loss", "cos_sim",
    "sigmoid_cross_entropy_with_logits", "sigmoid_focal_loss",
    "npair_loss", "dice_loss", "square_error_cost", "warpctc",
    "edit_distance", "mean_iou",
    # norm / conv / pool / vision transforms (parameter-bearing use
    # implicit params)
    "layer_norm", "group_norm", "instance_norm", "lrn",
    "conv2d_transpose", "conv3d", "pool3d", "adaptive_pool2d",
    "image_resize", "resize_bilinear", "resize_nearest",
    "resize_trilinear", "resize_linear", "image_resize_short",
    "lod_reset", "lod_append", "pixel_shuffle", "grid_sampler", "affine_grid",
    "unfold", "temporal_shift",
    # detection (vision.ops)
    "yolo_box", "yolov3_loss", "multiclass_nms", "matrix_nms",
    "prior_box", "box_coder", "roi_align", "roi_pool", "box_clip",
    "iou_similarity", "distribute_fpn_proposals",
    # sequence (dense+lengths analogs, fluid spelling)
    "sequence_concat", "sequence_expand", "sequence_expand_as",
    "sequence_first_step", "sequence_last_step", "sequence_mask",
    "sequence_pad", "sequence_unpad", "sequence_pool",
    "sequence_reverse", "sequence_softmax", "sequence_enumerate",
    "sequence_conv", "sequence_erase", "sequence_reshape",
    "sequence_scatter", "sequence_slice", "sequence_topk_avg_pooling",
    "Print", "Assert", "case", "switch_case", "double_buffer",
    "beam_search", "beam_search_decode", "spectral_norm",
    "uniform_random_batch_size_like", "gaussian_random_batch_size_like",
    "lstm_unit", "hash", "target_assign", "continuous_value_model",
    "data_norm",
    "gather_tree", "add_position_encoding", "affine_channel",
    "autoincreased_step_counter", "get_tensor_from_selected_rows",
    "merge_selected_rows", "chunk_eval", "polygon_box_transform",
    "RNNCell",
    "hsigmoid", "bilinear_tensor_product", "fsp_matrix", "row_conv",
    "im2sequence", "center_loss", "sampling_id",
    "teacher_student_sigmoid_loss", "anchor_generator",
    "bipartite_match", "density_prior_box",
    "Normal", "Uniform", "Categorical", "MultivariateNormalDiag",
    "auc",
    # LR schedules (objects accepted by every optimizer)
    "exponential_decay", "natural_exp_decay", "inverse_time_decay",
    "polynomial_decay", "piecewise_decay", "cosine_decay",
    "noam_decay", "linear_lr_warmup",
    # rnn cells / runners
    "GRUCell", "LSTMCell", "rnn", "birnn",
    # seq2seq decode stack (nn.decode re-exports)
    "Decoder", "BeamSearchDecoder", "dynamic_decode", "DecodeHelper",
    "TrainingHelper", "GreedyEmbeddingHelper", "SampleEmbeddingHelper",
    "BasicDecoder",
    # fluid RNN-era recurrent ops (rnn_legacy)
    "dynamic_lstm", "dynamic_lstmp", "dynamic_gru", "gru_unit", "lstm",
    # sampled large-vocab losses
    "nce", "sampled_softmax_with_cross_entropy",
    # tier 7: user-op / crop / 3d long tail
    "py_func", "random_crop", "conv3d_transpose", "adaptive_pool3d",
    "scatter_nd",
    # detection training family
    "rpn_target_assign", "generate_proposals", "ssd_loss",
    "multi_box_head", "deformable_conv",
    # tier 8: decode/filter/io/detection-inference misc
    "ctc_greedy_decoder", "similarity_focus", "filter_by_instag",
    "reorder_lod_tensor_by_rank", "load", "read_file", "inplace_abn",
    "detection_output", "box_decoder_and_assign",
    "collect_fpn_proposals", "locality_aware_nms",
    # tier 9: roi pooling/warp + retinanet/rcnn label generators
    "psroi_pool", "prroi_pool", "deformable_roi_pooling",
    "roi_perspective_transform", "retinanet_target_assign",
    "retinanet_detection_output", "generate_proposal_labels",
    "generate_mask_labels",
    # tensor-array (eager lists)
    "create_array", "array_write", "array_read", "array_length",
    "tensor_array_to_tensor",
    # r5: queue-backed readers + the doc/codegen decorators (real
    # implementations — fluid/reader.py)
    "py_reader", "create_py_reader_by_data", "templatedoc", "autodoc",
    "generate_layer_fn", "generate_activation_fn",
    "generate_inplace_fn",
]


# -- elementwise / compare / logical -----------------------------------------

def _b(f):
    """Binary delegate with fluid's mid-axis broadcast semantics
    (reuses layers._ew_align: y of shape x.shape[axis:axis+y.ndim]
    broadcasts from ``axis``, the classic NCHW + [C] pattern)."""
    def impl(x, y, axis=-1, act=None, name=None):
        from .layers import _ew_align
        a, b = _ew_align(_t(x), _t(y), axis)
        out = f(a, b)
        return getattr(F, act)(out) if act else out
    return impl


elementwise_max = _b(_paddle.maximum)
elementwise_min = _b(_paddle.minimum)
elementwise_mod = _b(_paddle.mod)
elementwise_pow = _b(_paddle.pow)
elementwise_floordiv = _b(_paddle.floor_divide)


def _cmp(f):
    def impl(x, y, cond=None, name=None):
        return f(_t(x), _t(y))
    return impl


equal, not_equal = _cmp(_paddle.equal), _cmp(_paddle.not_equal)
less_than, less_equal = _cmp(_paddle.less_than), _cmp(_paddle.less_equal)
greater_than = _cmp(_paddle.greater_than)
greater_equal = _cmp(_paddle.greater_equal)
logical_and, logical_or = _cmp(_paddle.logical_and), _cmp(_paddle.logical_or)
logical_xor = _cmp(_paddle.logical_xor)


def logical_not(x, out=None, name=None):
    return _paddle.logical_not(_t(x))


# -- reductions / creation ---------------------------------------------------

def _red(f):
    def impl(input, dim=None, keep_dim=False, name=None):
        return f(_t(input), axis=dim, keepdim=keep_dim)
    return impl


reduce_min = _red(_paddle.min)
reduce_prod = _red(_paddle.prod)
reduce_all = _red(_paddle.all)
reduce_any = _red(_paddle.any)


def ones(shape, dtype="float32", force_cpu=False):
    return _paddle.ones(shape, dtype)


def zeros(shape, dtype="float32", force_cpu=False):
    return _paddle.zeros(shape, dtype)


def ones_like(x, out=None):
    return _paddle.ones_like(_t(x))


def zeros_like(x, out=None):
    return _paddle.zeros_like(_t(x))


def eye(num_rows, num_columns=None, batch_shape=None, dtype="float32"):
    out = _paddle.eye(num_rows, num_columns, dtype=dtype)
    if batch_shape:
        for n in reversed(batch_shape):
            out = _manip.tile(_manip.unsqueeze(out, axis=0),
                              [n] + [1] * out.ndim)
    return out


def linspace(start, stop, num, dtype="float32", name=None):
    return _paddle.linspace(start, stop, num, dtype)


def range(start, end, step, dtype, name=None):  # noqa: A001 (fluid name)
    return _paddle.arange(start, end, step, dtype)


def diag(diagonal):
    return _paddle.diag(_t(diagonal))


def fill_constant_batch_size_like(input, shape, dtype, value,
                                  input_dim_idx=0, output_dim_idx=0):
    shape = list(shape)
    shape[output_dim_idx] = _t(input).shape[input_dim_idx]
    return _paddle.full(shape, value, dtype)


def create_tensor(dtype, name=None, persistable=False):
    return _paddle.zeros([0], dtype)


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    from .layers import create_parameter
    p = create_parameter(shape, dtype=dtype)
    p._data = _paddle.full(shape, value, dtype).data
    return p


def sums(input, out=None):
    return _paddle.add_n([_t(x) for x in input])


def sum(x):  # noqa: A001 — fluid.layers.sum IS add_n over a list
    if isinstance(x, (list, tuple)):
        return _paddle.add_n([_t(v) for v in x])
    return _t(x)  # reference: a single input passes through unchanged


# -- manipulation ------------------------------------------------------------

def argmax(x, axis=0):
    return _paddle.argmax(_t(x), axis=axis)


def argmin(x, axis=0):
    return _paddle.argmin(_t(x), axis=axis)


def argsort(input, axis=-1, descending=False, name=None):
    x = _t(input)
    return (_paddle.sort(x, axis=axis, descending=descending),
            _paddle.argsort(x, axis=axis, descending=descending))


def slice(input, axes, starts, ends):  # noqa: A001
    return _paddle.slice(_t(input), axes, starts, ends)


def strided_slice(input, axes, starts, ends, strides):
    return _paddle.strided_slice(_t(input), axes, starts, ends, strides)


def split(input, num_or_sections, dim=-1, name=None):
    return _paddle.split(_t(input), num_or_sections, axis=dim)


def stack(x, axis=0, name=None):
    return _paddle.stack([_t(v) for v in x] if isinstance(x, (list, tuple))
                         else _t(x), axis=axis)


def unstack(x, axis=0, num=None):
    return _paddle.unstack(_t(x), axis=axis)


def unbind(input, axis=0):
    return _paddle.unbind(_t(input), axis=axis)


def squeeze(input, axes, name=None):
    return _manip.squeeze(_t(input), axis=axes)


def unsqueeze(input, axes, name=None):
    x = _t(input)
    for a in (axes if isinstance(axes, (list, tuple)) else [axes]):
        x = _manip.unsqueeze(x, axis=a)
    return x


def unique(x, dtype="int32"):
    # fluid returns (unique values, index mapping input->unique)
    u, inv = _paddle.unique(_t(x), return_inverse=True)
    return u, inv.astype(dtype)


def unique_with_counts(x, dtype="int32"):
    u, inv, counts = _paddle.unique(_t(x), return_inverse=True,
                                    return_counts=True)
    return u, inv.astype(dtype), counts


def where(condition):
    return _paddle.nonzero(_t(condition))


def multiplex(inputs, index):
    return _paddle.multiplex([_t(x) for x in inputs], _t(index))


def triu(input, diagonal=0, name=None):
    return _paddle.triu(_t(input), diagonal)


def expand(x, expand_times, name=None):
    return _paddle.tile(_t(x), expand_times)


def expand_as(x, target_tensor, name=None):
    return _paddle.expand_as(_t(x), _t(target_tensor))


def pad(x, paddings, pad_value=0.0, name=None):
    # fluid: flat [before0, after0, before1, after1, ...] over ALL dims
    return F.pad(_t(x), list(paddings), value=pad_value)


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    return F.pad(_t(input), list(paddings), mode=mode, value=pad_value,
                 data_format=data_format)


def pad_constant_like(x, y, pad_value=0.0, name=None):
    x, y = _t(x), _t(y)
    flat = []
    for i in _bi.range(x.ndim):
        flat += [0, x.shape[i] - y.shape[i]]
    return F.pad(y, flat, value=pad_value)


def crop(x, shape=None, offsets=None, name=None):
    return _paddle.crop(_t(x), shape, offsets)


def crop_tensor(x, shape=None, offsets=None, name=None):
    return _paddle.crop(_t(x), shape, offsets)


def flatten(x, axis=1, name=None):
    x = _t(x)
    lead = int(np.prod(x.shape[:axis])) if axis else 1
    return _manip.reshape(x, [lead, int(np.prod(x.shape[axis:]))])


def transpose(x, perm, name=None):
    return _paddle.transpose(_t(x), perm)


def gather(input, index, overwrite=True):
    return _paddle.gather(_t(input), _t(index))


def gather_nd(input, index, name=None):
    return _paddle.gather_nd(_t(input), _t(index))


def scatter(input, index, updates, overwrite=True, name=None):
    return _paddle.scatter(_t(input), _t(index), _t(updates),
                           overwrite=overwrite)


def scatter_nd_add(ref, index, updates, name=None):
    return _paddle.scatter_nd_add(_t(ref), _t(index), _t(updates))


def scatter_nd(index, updates, shape, name=None):
    return _paddle.scatter_nd(_t(index), _t(updates), shape)


def size(input):
    return _paddle.numel(_t(input))


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    return _paddle.shard_index(_t(input), index_num, nshards, shard_id,
                               ignore_value)


def reverse(x, axis):
    return _paddle.reverse(_t(x), axis)


def rank(input):
    return _paddle.rank(_t(input))


def increment(x, value=1.0, in_place=True):
    return _paddle.increment(_t(x), value)


def is_empty(x, cond=None):
    return _paddle.is_empty(_t(x))


def has_inf(x):
    return _math.any(_paddle.isinf(_t(x)))


def has_nan(x):
    return _math.any(_paddle.isnan(_t(x)))


def isfinite(x):
    return _math.all(_paddle.isfinite(_t(x)))


def space_to_depth(x, blocksize, name=None):
    import jax.numpy as jnp
    from ..autograd.engine import apply
    b = blocksize

    def f(a):
        n, c, h, w = a.shape
        a = a.reshape(n, c, h // b, b, w // b, b)
        a = a.transpose(0, 3, 5, 1, 2, 4)
        return a.reshape(n, c * b * b, h // b, w // b)
    return apply("space_to_depth", f, (_t(x),))


def shuffle_channel(x, group, name=None):
    from ..autograd.engine import apply

    def f(a):
        n, c, h, w = a.shape
        return a.reshape(n, group, c // group, h, w) \
                .transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)
    return apply("shuffle_channel", f, (_t(x),))


# -- activations / math ------------------------------------------------------

def _u(f, **fixed):
    def impl(x, name=None, **kw):
        kw.pop("act", None)
        return f(_t(x), **{**fixed, **kw})
    return impl


relu6 = _u(F.relu6)
elu = _u(F.elu)
selu = _u(F.selu)
mish = _u(F.mish)
hard_swish = _u(F.hardswish)
sign = _u(_paddle.sign)
# (sigmoid/tanh/square/sqrt/exp stay in layers.py — defining them here
# too would silently shadow those via the star import)
rsqrt = _u(_paddle.rsqrt)
abs = _u(_paddle.abs)  # noqa: A001
floor = _u(_paddle.floor)
ceil = _u(_paddle.ceil)
round = _u(_paddle.round)  # noqa: A001
erf = _u(_paddle.erf)
sin = _u(_paddle.sin)
cos = _u(_paddle.cos)


def leaky_relu(x, alpha=0.02, name=None):
    return F.leaky_relu(_t(x), negative_slope=alpha)


def swish(x, beta=1.0, name=None):
    return _t(x) * F.sigmoid(_t(x) * beta)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _math.clip(_t(x) * slope + offset, 0.0, 1.0)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _math.clip(_t(x), t_min, t_max)


def soft_relu(x, threshold=40.0, name=None):
    return _math.log(1 + _paddle.exp(_math.clip(_t(x), -threshold,
                                                threshold)))


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return scale_b * _paddle.tanh(_t(x) * scale_a)


def maxout(x, groups, name=None, axis=1):
    return F.maxout(_t(x), groups, axis=axis)


def prelu(x, mode="all", param_attr=None, name=None):
    x = _t(x)
    if mode == "element":
        from ..core.errors import UnimplementedError
        raise UnimplementedError(
            "prelu(mode='element') (one alpha per activation) is not "
            "mapped; use nn.PReLU with an explicit weight of the "
            "activation shape, or mode='channel'")
    num = 1 if mode == "all" else x.shape[1]
    lay = _implicit_layer(getattr(param_attr, "name", param_attr),
                          ("prelu", mode, num),
                          lambda: _paddle.nn.PReLU(num_parameters=num))
    return lay(x)


def pow(x, factor=1.0, name=None):  # noqa: A001
    return _paddle.pow(_t(x), factor)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None,
          name=None):
    x = _t(x)
    out = x * scale + bias if bias_after_scale else (x + bias) * scale
    return getattr(F, act)(out) if act else out


def clip_by_norm(x, max_norm, name=None):
    x = _t(x)
    norm = _math.sqrt(_math.sum(x * x))
    return x * _math.clip(max_norm / _paddle.maximum(norm,
                                                     to_tensor(1e-12)),
                          None, 1.0)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    return F.normalize(_t(x), p=2, axis=axis, epsilon=epsilon)


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    return F.label_smooth(_t(label), prior_dist=prior_dist,
                          epsilon=epsilon)


def cumsum(x, axis=None, exclusive=False, reverse=False, name=None):
    t = _t(x)
    ax = -1 if axis is None else axis
    if reverse:
        t = _manip.flip(t, axis=ax) if hasattr(_manip, "flip") \
            else _paddle.reverse(t, [ax])
    out = _paddle.cumsum(t, axis=ax)
    if exclusive:
        # shift right by one along ax, zero-filled (reference semantics)
        pads = [0] * (2 * out.ndim)
        pads[2 * (ax % out.ndim)] = 1
        shifted = F.pad(out, pads, value=0.0)
        sl = [__import__("builtins").slice(None)] * out.ndim
        sl[ax % out.ndim] = __import__("builtins").slice(0, out.shape[ax])
        from ..autograd.engine import apply as _apply
        out = _apply("exclusive_slice", lambda a: a[tuple(sl)], (shifted,))
    if reverse:
        out = _manip.flip(out, axis=ax) if hasattr(_manip, "flip") \
            else _paddle.reverse(out, [ax])
    return out


# -- losses ------------------------------------------------------------------

def mse_loss(input, label):
    return F.mse_loss(_t(input), _t(label))


def huber_loss(input, label, delta):
    d = _t(input) - _t(label)
    ad = _paddle.abs(d)
    quad = 0.5 * d * d
    lin = delta * ad - 0.5 * delta * delta
    return _paddle.where(ad <= delta, quad, lin)


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    sigma = 1.0 if sigma is None else sigma
    d = (_t(x) - _t(y)) * (_t(inside_weight) if inside_weight is not None
                           else 1.0)
    ad = _paddle.abs(d)
    s2 = sigma * sigma
    out = _paddle.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    if outside_weight is not None:
        out = out * _t(outside_weight)
    return _math.sum(out, axis=-1, keepdim=True)


def log_loss(input, label, epsilon=1e-4, name=None):
    return F.log_loss(_t(input), _t(label), epsilon)


def kldiv_loss(x, target, reduction="mean", name=None):
    return F.kl_div(_t(x), _t(target), reduction=reduction)


def bpr_loss(input, label, name=None):
    """Bayesian personalized ranking (reference loss.py bpr_loss):
    -mean(log(sigmoid(score_pos - score_others)))."""
    x, lab = _t(input), _t(label)
    if lab.ndim == x.ndim and lab.shape[-1] == 1:
        lab = _manip.squeeze(lab, axis=-1)
    pos = _manip.reshape(
        _paddle.index_sample(x, _manip.reshape(lab, [-1, 1]))
        if hasattr(_paddle, "index_sample")
        else _math.sum(x * F.one_hot(lab, x.shape[-1]), axis=-1,
                       keepdim=True), [-1, 1])
    diff = pos - x
    loss = -_math.log(F.sigmoid(diff) + 1e-12)
    n = x.shape[-1]
    # the sum includes the positive-vs-itself term (diff=0 ->
    # -log(sigmoid(0)) = log 2, gradient-free); subtract it exactly
    return (_math.sum(loss, axis=-1, keepdim=True)
            - float(np.log(2.0))) / max(n - 1, 1)


def rank_loss(label, left, right, name=None):
    lab, dl = _t(label), _t(left) - _t(right)
    return F.softplus(dl) - lab * dl


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    return F.relu(-_t(label) * (_t(left) - _t(right)) + margin)


def cos_sim(X, Y):
    return _manip.reshape(F.cosine_similarity(_t(X), _t(Y), axis=-1),
                          [-1, 1])


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      name=None, normalize=False):
    out = F.binary_cross_entropy_with_logits(_t(x), _t(label),
                                             reduction="none")
    mask = (_t(label) != ignore_index).astype(out.dtype)
    out = out * mask
    if normalize:
        out = out / _paddle.maximum(_math.sum(mask), to_tensor(1.0))
    return out


def sigmoid_focal_loss(x, label, fg_num, gamma=2.0, alpha=0.25):
    return F.sigmoid_focal_loss(_t(x), _t(label),
                                normalizer=_t(fg_num).astype("float32"),
                                gamma=gamma, alpha=alpha,
                                reduction="none")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    return F.npair_loss(_t(anchor), _t(positive), _t(labels), l2_reg)


def dice_loss(input, label, epsilon=1e-5):
    return F.dice_loss(_t(input), _t(label), epsilon)


def square_error_cost(input, label):
    return F.square_error_cost(_t(input), _t(label))


def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    return F.ctc_loss(_t(input), _t(label),
                      _t(input_length) if input_length is not None
                      else None,
                      _t(label_length) if label_length is not None
                      else None, blank=blank, reduction="none")


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None):
    """Levenshtein distance per pair (reference metric_op.py) — host
    computation (dynamic programming is not a TPU shape-stable op)."""
    import builtins
    a_all = np.asarray(_t(input).numpy())
    b_all = np.asarray(_t(label).numpy())
    la = (np.asarray(_t(input_length).numpy())
          if input_length is not None
          else np.full(a_all.shape[0], a_all.shape[1], np.int64))
    lb = (np.asarray(_t(label_length).numpy())
          if label_length is not None
          else np.full(b_all.shape[0], b_all.shape[1], np.int64))
    out = np.zeros((a_all.shape[0], 1), np.float32)
    seq_num = a_all.shape[0]
    ignored = set(ignored_tokens or [])
    for i in builtins.range(seq_num):
        a = [t for t in a_all[i][:la[i]].tolist() if t not in ignored]
        b = [t for t in b_all[i][:lb[i]].tolist() if t not in ignored]
        dp = list(builtins.range(len(b) + 1))
        for x_i, ca in enumerate(a, 1):
            prev, dp[0] = dp[0], x_i
            for y_i, cb in enumerate(b, 1):
                prev, dp[y_i] = dp[y_i], min(dp[y_i] + 1, dp[y_i - 1] + 1,
                                             prev + (ca != cb))
        d = float(dp[len(b)])
        out[i, 0] = d / max(len(b), 1) if normalized else d
    return to_tensor(out), to_tensor(np.asarray([seq_num], np.int64))


def mean_iou(input, label, num_classes):
    from ..metric import mean_iou as _miou
    return _miou(_t(input), _t(label), num_classes)


# -- tier 3: distributions / control-flow-lite / misc ------------------------

def Print(input, first_n=-1, message=None, summarize=20,  # noqa: N802
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """Eager print-passthrough (reference control_flow.py Print op)."""
    x = _t(input)
    n = None if summarize is None or summarize < 0 else summarize
    vals = np.asarray(x.numpy()).reshape(-1)[:n]
    print((message or "") + f" shape={list(x.shape)} "
          f"dtype={x.dtype} values={vals.tolist()}")
    return x


def Assert(cond, data=None, summarize=20, name=None):  # noqa: N802
    """Eager assert (reference control_flow.py Assert op)."""
    c = _t(cond)
    if not bool(np.asarray(c.numpy()).all()):
        extra = ""
        if data is not None:
            n = None if summarize is None or summarize < 0 else summarize
            extra = "; data=" + ", ".join(
                str(np.asarray(_t(d).numpy()).reshape(-1)[:n])
                for d in (data if isinstance(data, (list, tuple))
                          else [data]))
        raise AssertionError(f"fluid.layers.Assert failed{extra}")
    return c


def case(pred_fn_pairs, default=None, name=None):
    """Eager first-match dispatch (reference control_flow.py case):
    under trace, tensor predicates must be concrete — use
    static.nn.cond for traced branching."""
    for pred, fn in pred_fn_pairs:
        if bool(np.asarray(_t(pred).numpy())):
            return fn()
    if default is not None:
        return default()
    return pred_fn_pairs[-1][1]()


def switch_case(branch_index, branch_fns, default=None, name=None):
    idx = int(np.asarray(_t(branch_index).numpy()))
    fns = dict(branch_fns) if not isinstance(branch_fns, dict) \
        else branch_fns
    if idx in fns:
        return fns[idx]()
    if default is not None:
        return default()
    return fns[max(fns)]()


def double_buffer(reader, place=None, name=None):
    """Device prefetch is owned by io.DataLoader here; identity for
    API parity (reference io.py double_buffer)."""
    return reader


def Normal(loc, scale):  # noqa: N802
    from ..distribution import Normal as _N
    return _N(loc, scale)


def Uniform(low, high):  # noqa: N802
    from ..distribution import Uniform as _U
    return _U(low, high)


def Categorical(logits):  # noqa: N802
    from ..distribution import Categorical as _C
    return _C(logits)


class MultivariateNormalDiag:  # noqa: N801 — fluid class name
    """Multivariate normal with diagonal covariance (reference
    fluid/layers/distributions.py:528): ``loc`` [k], ``scale`` the
    [k, k] diagonal covariance matrix; entropy and KL per the
    reference's determinant/trace formulas."""

    def __init__(self, loc, scale):
        self.loc = _t(loc)
        self.scale = _t(scale)

    def _diag(self):
        import numpy as _np2
        return _np2.diag(_np2.asarray(self.scale.numpy()))

    def entropy(self):
        import math
        k = self.scale.shape[0]
        det = float(np.prod(self._diag()))
        return to_tensor(np.asarray(
            0.5 * (k * (1.0 + math.log(2 * math.pi))
                   + math.log(det)), np.float32))

    def kl_divergence(self, other):
        d_self = self._diag().astype(np.float64)
        d_other = other._diag().astype(np.float64)
        mu = (np.asarray(other.loc.numpy(), np.float64)
              - np.asarray(self.loc.numpy(), np.float64))
        k = self.scale.shape[0]
        tr = float((d_self / d_other).sum())
        quad = float((mu * (1.0 / d_other) * mu).sum())
        ln_cov = float(np.log(d_other.prod())
                       - np.log(d_self.prod()))
        return to_tensor(np.asarray(
            0.5 * (tr + quad - k + ln_cov), np.float32))


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1):
    """One-shot AUC over this batch (reference metric_op.py auc op; the
    stateful accumulation lives in metric.Auc). Returns (auc_value,
    [auc_value]) — the reference's (out, stat) pair collapses to the
    value."""
    if curve != "ROC":
        from ..core.errors import UnimplementedError
        raise UnimplementedError(
            f"auc(curve={curve!r}): only ROC is implemented "
            "(metric.Auc); PR-curve AUC is not mapped")
    from ..metric import Auc as _Auc
    m = _Auc(num_thresholds=num_thresholds)
    x = np.asarray(_t(input).numpy())
    y = np.asarray(_t(label).numpy()).reshape(-1, 1)
    m.update(x, y)
    v = float(m.accumulate())
    return to_tensor(np.float32(v)), [to_tensor(np.float32(v))]


# -- norm / conv / pool / vision transforms ----------------------------------

def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    x = _t(input)
    shape = list(x.shape[begin_norm_axis:])
    lay = _implicit_layer(name, ("layer_norm", tuple(shape)),
                          lambda: _paddle.nn.LayerNorm(shape,
                                                       epsilon=epsilon))
    out = lay(x)
    return getattr(F, act)(out) if act else out


def group_norm(input, groups, epsilon=1e-5, param_attr=None,
               bias_attr=None, act=None, data_layout="NCHW", name=None):
    x = _t(input)
    ch = x.shape[1 if data_layout == "NCHW" else -1]
    lay = _implicit_layer(name, ("group_norm", groups, ch),
                          lambda: _paddle.nn.GroupNorm(groups, ch,
                                                       epsilon=epsilon))
    out = lay(x)
    return getattr(F, act)(out) if act else out


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    x = _t(input)
    ch = x.shape[1]
    lay = _implicit_layer(name, ("instance_norm", ch),
                          lambda: _paddle.nn.InstanceNorm2D(
                              ch, epsilon=epsilon))
    return lay(x)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None,
        data_format="NCHW"):
    return F.local_response_norm(_t(input), size=n, alpha=alpha,
                                 beta=beta, k=k)


def conv2d_transpose(input, num_filters, output_size=None,
                     filter_size=None, padding=0, stride=1, dilation=1,
                     groups=1, param_attr=None, bias_attr=None,
                     act=None, name=None, data_format="NCHW"):
    x = _t(input)
    in_ch = x.shape[1 if data_format == "NCHW" else -1]
    if filter_size is None:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            "conv2d_transpose needs filter_size= (note the fluid "
            "argument order puts output_size BEFORE filter_size)")
    lay = _implicit_layer(
        name, ("conv2d_transpose", in_ch, num_filters, filter_size,
               stride, padding, dilation, groups),
        lambda: _paddle.nn.Conv2DTranspose(in_ch, num_filters,
                                           filter_size, stride=stride,
                                           padding=padding,
                                           dilation=dilation,
                                           groups=groups))
    out = lay(x, output_size=output_size) if output_size else lay(x)
    return getattr(F, act)(out) if act else out


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           act=None, name=None, data_format="NCDHW"):
    x = _t(input)
    in_ch = x.shape[1]
    lay = _implicit_layer(
        name, ("conv3d", in_ch, num_filters, filter_size, stride,
               padding, dilation, groups),
        lambda: _paddle.nn.Conv3D(in_ch, num_filters, filter_size,
                                  stride=stride, padding=padding,
                                  dilation=dilation, groups=groups))
    out = lay(x)
    return getattr(F, act)(out) if act else out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, name=None):
    x = _t(input)
    if global_pooling:
        pool_size = list(x.shape[2:])
        pool_stride, pool_padding = pool_size, 0
    f = F.max_pool3d if pool_type == "max" else F.avg_pool3d
    return f(x, kernel_size=pool_size, stride=pool_stride,
             padding=pool_padding)


def adaptive_pool2d(input, pool_size, pool_type="max",
                    require_index=False, name=None):
    f = (F.adaptive_max_pool2d if pool_type == "max"
         else F.adaptive_avg_pool2d)
    return f(_t(input), pool_size)


def adaptive_pool3d(input, pool_size, pool_type="max",
                    require_index=False, name=None):
    f = (F.adaptive_max_pool3d if pool_type == "max"
         else F.adaptive_avg_pool3d)
    return f(_t(input), pool_size)


def conv3d_transpose(input, num_filters, output_size=None,
                     filter_size=None, padding=0, stride=1, dilation=1,
                     groups=1, param_attr=None, bias_attr=None,
                     act=None, name=None, data_format="NCDHW"):
    x = _t(input)
    in_ch = x.shape[1 if data_format == "NCDHW" else -1]
    if filter_size is None:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            "conv3d_transpose needs filter_size= (the fluid argument "
            "order puts output_size BEFORE filter_size)")
    lay = _implicit_layer(
        name, ("conv3d_transpose", in_ch, num_filters, filter_size,
               stride, padding, dilation, groups),
        lambda: _paddle.nn.Conv3DTranspose(in_ch, num_filters,
                                           filter_size, stride=stride,
                                           padding=padding,
                                           dilation=dilation,
                                           groups=groups))
    out = lay(x, output_size=output_size) if output_size else lay(x)
    return getattr(F, act)(out) if act else out


def random_crop(x, shape, seed=None):
    """Per-instance random crop of the trailing dims to ``shape``
    (reference random_crop_op: dim 0 is the batch, every instance draws
    its own offsets)."""
    from ..autograd.engine import apply as _apply
    import jax
    import jax.numpy as jnp
    from ..core.generator import next_key
    xt = _t(x)
    shape = list(shape)
    if len(shape) != xt.ndim - 1:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            f"random_crop shape must cover the non-batch dims "
            f"({xt.ndim - 1}), got {shape}")
    key = (jax.random.key(int(seed)) if seed is not None
           else next_key())
    B = xt.shape[0]

    def f(a):
        maxs = jnp.asarray([a.shape[i + 1] - shape[i]
                            for i in _bi.range(len(shape))])
        offs = jax.vmap(
            lambda k: jax.random.randint(k, (len(shape),), 0,
                                         maxs + 1))(
            jax.random.split(key, B))

        def crop_one(ai, off):
            return jax.lax.dynamic_slice(ai, tuple(off), tuple(shape))
        return jax.vmap(crop_one)(a, offs)
    return _apply("random_crop", f, (xt,))


def py_func(func, x, out=None, backward_func=None,
            skip_vars_in_backward_input=None):
    """Run a user Python function as an op (reference layers/nn.py
    py_func, py_func_op.cc): ``func`` sees numpy arrays; with
    ``backward_func(*(inputs + outputs + out_grads)) -> input grads``
    the op is differentiable. ``skip_vars_in_backward_input`` removes
    specific input/output tensors from the backward call, matching the
    reference by object identity. ``out`` template tensors (if given)
    are updated in place and returned."""
    from ..autograd.py_layer import PyLayer
    xs = list(x) if isinstance(x, (list, tuple)) else [x]
    xs = [_t(v) for v in xs]
    outs_tpl = (list(out) if isinstance(out, (list, tuple))
                else ([out] if out is not None else None))
    skip = set(id(v) for v in (skip_vars_in_backward_input or []))

    class _PyFunc(PyLayer):
        @staticmethod
        def forward(ctx, *inputs):
            np_in = [np.asarray(t.numpy()) for t in inputs]
            res = func(*np_in)
            res_list = (list(res) if isinstance(res, (list, tuple))
                        else [res])
            outs = [to_tensor(np.asarray(r)) for r in res_list]
            ctx.save_for_backward(*inputs, *outs)
            ctx._n_in = len(inputs)
            return tuple(outs) if len(outs) > 1 else outs[0]

        @staticmethod
        def backward(ctx, *gouts):
            if backward_func is None:
                from ..core.errors import PreconditionNotMetError
                raise PreconditionNotMetError(
                    "py_func: backward reached but no backward_func= "
                    "was given")
            saved = ctx.saved_tensor
            ins, fouts = saved[:ctx._n_in], saved[ctx._n_in:]
            args = []
            for t in list(ins) + list(fouts):
                if id(t) in skip or \
                        any(t.data is s.data for s in _skip_tensors):
                    continue
                args.append(np.asarray(t.numpy()))
            args += [np.asarray(g.numpy()) for g in gouts]
            gres = backward_func(*args)
            gres = (list(gres) if isinstance(gres, (list, tuple))
                    else [gres])
            gts = [None if g is None else to_tensor(np.asarray(g))
                   for g in gres]
            diff_n = len([t for t in ins if not t.stop_gradient])
            if len(gts) == len(ins):
                gts = [g for g, t in zip(gts, ins)
                       if not t.stop_gradient]
            if len(gts) != diff_n:
                from ..core.errors import PreconditionNotMetError
                raise PreconditionNotMetError(
                    f"py_func backward_func returned {len(gts)} grads "
                    f"for {diff_n} differentiable inputs")
            return tuple(gts)

    _skip_tensors = [v for v in (skip_vars_in_backward_input or [])
                     if isinstance(v, Tensor)]
    result = _PyFunc.apply(*xs)
    res_list = (list(result) if isinstance(result, tuple)
                else [result])
    if outs_tpl is not None:
        for tpl, r in zip(outs_tpl, res_list):
            if isinstance(tpl, Tensor) and hasattr(tpl, "_replace_impl"):
                tpl._replace_impl(r)
    return result


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None,
                 align_corners=True, align_mode=1,
                 data_format="NCHW"):
    mode = {"BILINEAR": "bilinear", "NEAREST": "nearest",
            "TRILINEAR": "trilinear"}[resample]
    return F.interpolate(_t(input), size=out_shape, scale_factor=scale,
                         mode=mode,
                         align_corners=align_corners and mode != "nearest")


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    align_corners=True, align_mode=1,
                    data_format="NCHW"):
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        align_corners=align_corners)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   align_corners=True, data_format="NCHW"):
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        align_corners=False)


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     align_corners=True, align_mode=1,
                     data_format="NCDHW"):
    return image_resize(input, out_shape, scale, name, "TRILINEAR",
                        align_corners=align_corners)


def pixel_shuffle(x, upscale_factor):
    return F.pixel_shuffle(_t(x), upscale_factor)


def grid_sampler(x, grid, name=None):
    return F.grid_sample(_t(x), _t(grid))


def affine_grid(theta, out_shape, name=None):
    return F.affine_grid(_t(theta), out_shape)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1,
           name=None):
    return F.unfold(_t(x), kernel_sizes, strides=strides,
                    paddings=paddings, dilations=dilations)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return F.temporal_shift(_t(x), seg_num, shift_ratio)


# -- detection ---------------------------------------------------------------

def _v(fname):
    def impl(*args, **kwargs):
        from .. import vision
        kwargs.pop("name", None)
        args = tuple(_t(a) if isinstance(a, (np.ndarray, Tensor))
                     else a for a in args)
        return getattr(vision.ops, fname)(*args, **kwargs)
    return impl


yolo_box = _v("yolo_box")
multiclass_nms = _v("multiclass_nms")
matrix_nms = _v("matrix_nms")
prior_box = _v("prior_box")
box_coder = _v("box_coder")
roi_align = _v("roi_align")
roi_pool = _v("roi_pool")
distribute_fpn_proposals = _v("distribute_fpn_proposals")


def yolov3_loss(x, gt_box, gt_label, anchors, anchor_mask, class_num,
                ignore_thresh, downsample_ratio, gt_score=None,
                use_label_smooth=True, name=None):
    from ..vision.models.yolo import yolov3_loss as _impl
    return _impl(_t(x), _t(gt_box), _t(gt_label), anchors, anchor_mask,
                 class_num, ignore_thresh, downsample_ratio)


def box_clip(input, im_info, name=None):
    x, info = _t(input), _t(im_info)
    h = info[:, 0] / info[:, 2] - 1
    w = info[:, 1] / info[:, 2] - 1
    from ..autograd.engine import apply
    import jax.numpy as jnp

    def f(b, hh, ww):
        hh = hh.reshape(-1, *([1] * (b.ndim - 1)))
        ww = ww.reshape(-1, *([1] * (b.ndim - 1)))
        x1 = jnp.clip(b[..., 0::4], 0, ww)
        y1 = jnp.clip(b[..., 1::4], 0, hh)
        x2 = jnp.clip(b[..., 2::4], 0, ww)
        y2 = jnp.clip(b[..., 3::4], 0, hh)
        return jnp.stack([x1, y1, x2, y2], axis=-1).reshape(b.shape)
    return apply("box_clip", f, (x, w, h))


def iou_similarity(x, y, box_normalized=True, name=None):
    from ..autograd.engine import apply
    import jax.numpy as jnp

    def f(a, b):
        off = 0.0 if box_normalized else 1.0
        ax1, ay1, ax2, ay2 = a[:, 0], a[:, 1], a[:, 2], a[:, 3]
        bx1, by1, bx2, by2 = b[:, 0], b[:, 1], b[:, 2], b[:, 3]
        area_a = (ax2 - ax1 + off) * (ay2 - ay1 + off)
        area_b = (bx2 - bx1 + off) * (by2 - by1 + off)
        ix1 = jnp.maximum(ax1[:, None], bx1[None, :])
        iy1 = jnp.maximum(ay1[:, None], by1[None, :])
        ix2 = jnp.minimum(ax2[:, None], bx2[None, :])
        iy2 = jnp.minimum(ay2[:, None], by2[None, :])
        iw = jnp.clip(ix2 - ix1 + off, 0, None)
        ih = jnp.clip(iy2 - iy1 + off, 0, None)
        inter = iw * ih
        return inter / (area_a[:, None] + area_b[None, :] - inter)
    return apply("iou_similarity", f, (_t(x), _t(y)))


# -- sequence (dense + lengths analogs) --------------------------------------

sequence_concat = _seq.sequence_concat
sequence_expand = _seq.sequence_expand
sequence_first_step = _seq.sequence_first_step
sequence_last_step = _seq.sequence_last_step
sequence_mask = _seq.sequence_mask
sequence_pad = _seq.sequence_pad
sequence_unpad = _seq.sequence_unpad
sequence_pool = _seq.sequence_pool
sequence_reverse = _seq.sequence_reverse
sequence_softmax = _seq.sequence_softmax
sequence_erase = _seq.sequence_erase
sequence_reshape = _seq.sequence_reshape
sequence_scatter = _seq.sequence_scatter
sequence_slice = _seq.sequence_slice
sequence_topk_avg_pooling = _seq.sequence_topk_avg_pooling


def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=True, bias_attr=None, param_attr=None,
                  act=None, lengths=None, name=None):
    """fluid spelling of the dense+lengths sequence_conv: the context
    filter is an implicit parameter [filter_size*D, num_filters]
    (reference layers/nn.py sequence_conv creates it from param_attr);
    ``lengths`` is required (the LoD's replacement)."""
    if lengths is None:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            "sequence_conv needs lengths= in the dense+lengths world "
            "(the reference reads them from the input LoD)")
    if filter_stride != 1:
        from ..core.errors import UnimplementedError
        raise UnimplementedError(
            "sequence_conv supports filter_stride=1 only (the reference "
            "op has the same contract)")
    x = _t(input)
    D = x.shape[-1]
    lay = _implicit_layer(
        getattr(param_attr, "name", param_attr) or name,
        ("sequence_conv", D, filter_size, num_filters),
        lambda: _paddle.nn.Linear(filter_size * D, num_filters,
                                  bias_attr=bias_attr
                                  if bias_attr is not None else None))
    out = _seq.sequence_conv(x, lengths, lay.weight,
                             context_length=filter_size,
                             bias=getattr(lay, "bias", None))
    return getattr(F, act)(out) if act else out


def sequence_expand_as(x, y, lengths=None, name=None):
    if lengths is None:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            "sequence_expand_as needs lengths= in the dense+lengths "
            "world (the reference reads them from y's LoD): pass the "
            "per-row repeat counts, e.g. sequence_expand_as(x, y, "
            "lengths=row_lengths_of_y)")
    return _seq.sequence_expand(_t(x), lengths)


def sequence_enumerate(input, win_size, pad_value=0, name=None):
    from ..autograd.engine import apply
    import jax.numpy as jnp

    def f(a):
        T = a.shape[-1]
        idx = jnp.arange(T)[:, None] + jnp.arange(win_size)[None, :]
        win = jnp.where(idx < T, a[..., jnp.clip(idx, 0, T - 1)],
                        pad_value)
        return win
    return apply("sequence_enumerate", f, (_t(input),))


# -- LR schedules ------------------------------------------------------------

def exponential_decay(learning_rate, decay_steps, decay_rate,
                      staircase=False):
    from ..optimizer.lr import ExponentialDecay, StepDecay
    if staircase:
        return StepDecay(learning_rate, step_size=decay_steps,
                         gamma=decay_rate)
    return ExponentialDecay(learning_rate,
                            gamma=decay_rate ** (1.0 / decay_steps))


def natural_exp_decay(learning_rate, decay_steps, decay_rate,
                      staircase=False):
    from ..optimizer.lr import LambdaDecay, NaturalExpDecay
    if staircase:
        # reference: lr0 * exp(-rate * floor(step / decay_steps))
        return LambdaDecay(learning_rate,
                           lambda e: float(np.exp(
                               -decay_rate * (e // decay_steps))))
    return NaturalExpDecay(learning_rate,
                           gamma=decay_rate / decay_steps)


def inverse_time_decay(learning_rate, decay_steps, decay_rate,
                       staircase=False):
    from ..optimizer.lr import InverseTimeDecay, LambdaDecay
    if staircase:
        # reference: lr0 / (1 + rate * floor(step / decay_steps))
        return LambdaDecay(learning_rate,
                           lambda e: 1.0 / (1.0 + decay_rate *
                                            (e // decay_steps)))
    return InverseTimeDecay(learning_rate, gamma=decay_rate / decay_steps)


def polynomial_decay(learning_rate, decay_steps, end_learning_rate=1e-4,
                     power=1.0, cycle=False):
    from ..optimizer.lr import PolynomialDecay
    return PolynomialDecay(learning_rate, decay_steps,
                           end_lr=end_learning_rate, power=power,
                           cycle=cycle)


def piecewise_decay(boundaries, values):
    from ..optimizer.lr import PiecewiseDecay
    return PiecewiseDecay(boundaries, values)


def cosine_decay(learning_rate, step_each_epoch, epochs):
    from ..optimizer.lr import CosineAnnealingDecay
    return CosineAnnealingDecay(learning_rate,
                                T_max=step_each_epoch * epochs)


def noam_decay(d_model, warmup_steps, learning_rate=1.0):
    from ..optimizer.lr import NoamDecay
    return NoamDecay(d_model, warmup_steps, learning_rate=learning_rate)


def linear_lr_warmup(learning_rate, warmup_steps, start_lr, end_lr):
    from ..optimizer.lr import LinearWarmup
    return LinearWarmup(learning_rate, warmup_steps, start_lr, end_lr)


# -- rnn cells / runners -----------------------------------------------------

def GRUCell(hidden_size, **kw):  # noqa: N802 (fluid class-like factory)
    return _paddle.nn.GRUCell(hidden_size, hidden_size, **kw)


def LSTMCell(hidden_size, **kw):  # noqa: N802
    return _paddle.nn.LSTMCell(hidden_size, hidden_size, **kw)


def rnn(cell, inputs, initial_states=None, sequence_length=None,
        time_major=False, is_reverse=False, **kwargs):
    runner = _paddle.nn.RNN(cell, is_reverse=is_reverse,
                            time_major=time_major)
    return runner(_t(inputs), initial_states)


def birnn(cell_fw, cell_bw, inputs, initial_states=None,
          sequence_length=None, time_major=False, **kwargs):
    runner = _paddle.nn.BiRNN(cell_fw, cell_bw, time_major=time_major)
    return runner(_t(inputs), initial_states)


# seq2seq decode stack: the fluid spellings are the nn.decode objects
# (reference fluid/layers/rnn.py:753-2127 → paddle1_tpu/nn/decode.py)
from ..nn.decode import (  # noqa: E402,F401
    Decoder, BeamSearchDecoder, dynamic_decode, DecodeHelper,
    TrainingHelper, GreedyEmbeddingHelper, SampleEmbeddingHelper,
    BasicDecoder)
from .rnn_legacy import (  # noqa: E402,F401
    dynamic_lstm, dynamic_lstmp, dynamic_gru, gru_unit, lstm)
from .sampled_loss import (  # noqa: E402,F401
    nce, sampled_softmax_with_cross_entropy)
from .detection_train import (  # noqa: E402,F401
    rpn_target_assign, generate_proposals, ssd_loss, multi_box_head,
    deformable_conv, retinanet_target_assign,
    retinanet_detection_output, generate_proposal_labels,
    generate_mask_labels)
from .misc_tail import (  # noqa: E402,F401
    ctc_greedy_decoder, similarity_focus, filter_by_instag,
    reorder_lod_tensor_by_rank, load, read_file, inplace_abn,
    detection_output, box_decoder_and_assign, collect_fpn_proposals,
    locality_aware_nms)
from .roi_tail import (  # noqa: E402,F401
    psroi_pool, prroi_pool, deformable_roi_pooling,
    roi_perspective_transform)
from .reader import (  # noqa: E402,F401
    py_reader, create_py_reader_by_data, templatedoc, autodoc,
    generate_layer_fn, generate_activation_fn, generate_inplace_fn)


# -- tensor arrays (eager lists) ---------------------------------------------

def create_array(dtype):
    return []


def array_write(x, i, array=None):
    if array is None:
        array = []
    i = int(_t(i).numpy()) if not isinstance(i, int) else i
    while len(array) <= i:
        array.append(None)
    array[i] = _t(x)
    return array


def array_read(array, i):
    i = int(_t(i).numpy()) if not isinstance(i, int) else i
    return array[i]


def array_length(array):
    return to_tensor(np.asarray([len(array)], np.int64))


def tensor_array_to_tensor(input, axis=1, name=None, use_stack=False):
    ts = [_t(x) for x in input]
    out = (_paddle.stack(ts, axis=axis) if use_stack
           else _manip.concat(ts, axis=axis))
    sizes = to_tensor(np.asarray([t.shape[axis] for t in ts], np.int32))
    return out, sizes


# -- tier 4: remaining mappable nn/detection long-tail ------------------------

def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None,
             is_custom=False, is_sparse=False):
    """Hierarchical sigmoid (reference layers/nn.py hsigmoid): the
    [num_classes-1, D] inner-node weights are implicit parameters."""
    x = _t(input)
    D = x.shape[-1]
    lay = _implicit_layer(
        getattr(param_attr, "name", param_attr) or name,
        ("hsigmoid", D, num_classes),
        lambda: _paddle.nn.Linear(D, num_classes - 1))
    w = _manip.transpose(lay.weight, [1, 0])  # [C-1, D] like reference
    return F.hsigmoid_loss(x, _t(label), num_classes, w, lay.bias,
                           path_table=path_table, path_code=path_code)


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    """out_k = x^T W_k y + b_k (reference layers/nn.py
    bilinear_tensor_product); W [size, dx, dy] is implicit."""
    xt, yt = _t(x), _t(y)
    dx, dy = xt.shape[-1], yt.shape[-1]
    holder = _implicit_layer(
        getattr(param_attr, "name", param_attr) or name,
        ("bilinear_tp", dx, dy, size),
        lambda: _paddle.nn.Bilinear(dx, dy, size))
    out = holder(xt, yt)
    return getattr(F, act)(out) if act else out


def fsp_matrix(x, y):
    """Flow-of-solution-procedure matrix (reference layers/nn.py
    fsp_matrix, distillation): [N,C1,H,W] x [N,C2,H,W] →
    [N, C1, C2] = mean over H*W of outer products."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp

    def f(a, b):
        n, c1 = a.shape[0], a.shape[1]
        c2 = b.shape[1]
        hw = a.shape[2] * a.shape[3]
        af = a.reshape(n, c1, hw)
        bf = b.reshape(n, c2, hw)
        return jnp.einsum("ncx,ndx->ncd", af, bf) / hw
    return _apply("fsp_matrix", f, (_t(x), _t(y)))


def row_conv(input, future_context_size, param_attr=None, act=None,
             lengths=None, name=None):
    """Lookahead row convolution (reference row_conv_op, DeepSpeech):
    out[t] = sum_{k=0..K} w[k] * x[t+k], per feature channel. The
    [K+1, D] weight is implicit. Dense form: [B, T, D] (+ optional
    lengths masking)."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp
    x = _t(input)
    D = x.shape[-1]
    K = int(future_context_size)
    holder = _implicit_layer(
        getattr(param_attr, "name", param_attr) or name,
        ("row_conv", K, D),
        lambda: _paddle.nn.Linear(K + 1, D, bias_attr=False))
    w = holder.weight  # [K+1, D]

    def f(a, wv, *maybe_len):
        T = a.shape[1]
        bound = (maybe_len[0][:, None] if maybe_len
                 else jnp.full((a.shape[0], 1), T))
        out = jnp.zeros_like(a)
        for k in _bi.range(K + 1):
            shifted = jnp.roll(a, -k, axis=1)
            # context frame t+k must exist INSIDE the sequence (the
            # reference truncates at each sequence's end, not at T)
            ok = ((jnp.arange(T)[None, :] + k) < bound)[..., None]
            out = out + jnp.where(ok, shifted, 0.0) * wv[k][None, None, :]
        if maybe_len:
            valid = (jnp.arange(T)[None, :] < bound)[..., None]
            out = jnp.where(valid, out, 0.0)
        return out
    args = (x, w) + ((_t(lengths),) if lengths is not None else ())
    out = _apply("row_conv", f, args)
    return getattr(F, act)(out) if act else out


def im2sequence(input, filter_size=1, stride=1, padding=0,
                input_image_size=None, out_stride=1, name=None):
    """Image → patch sequence (reference im2sequence_op): [N,C,H,W] →
    [N, oh*ow, C*fh*fw] via unfold."""
    x = _t(input)
    cols = F.unfold(x, filter_size, strides=stride, paddings=padding)
    # unfold gives [N, C*fh*fw, L]; the reference sequence layout is
    # [N, L, C*fh*fw]
    return _manip.transpose(cols, [0, 2, 1])


def center_loss(input, label, num_classes, alpha, param_attr=None,
                update_center=True):
    """Center loss (reference center_loss_op): pulls features toward
    per-class centers; centers are an implicit parameter updated by a
    moving average when ``update_center``."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp
    x, lab = _t(input), _t(label)
    if lab.ndim > 1:
        lab = _manip.reshape(lab, [-1])
    D = x.shape[-1]
    holder = _implicit_layer(
        getattr(param_attr, "name", param_attr),
        ("center_loss", num_classes, D),
        lambda: _paddle.nn.Embedding(num_classes, D))
    centers = holder.weight
    # centers update ONLY by the moving average below (reference
    # center_loss_op grad maker emits d/dX alone) — enter the graph as
    # a stop-gradient value so an optimizer over implicit_parameters()
    # cannot double-update them
    centers_sg = to_tensor(centers.data)

    def f(feat, lb, c):
        sel = c[lb]
        diff = feat - sel
        return 0.5 * (diff * diff).sum(axis=-1, keepdims=True)
    loss = _apply("center_loss", f, (x, lab, centers_sg))
    if update_center:
        # reference updates centers OUTSIDE autodiff: c_j -= alpha *
        # mean_{i: y_i=j}(c_j - x_i)
        import numpy as _np
        feat = _np.asarray(x.numpy())
        lb = _np.asarray(lab.numpy())
        c = _np.array(centers.numpy())  # writable copy
        delta = _np.zeros_like(c)
        counts = _np.zeros(num_classes, _np.float32)
        _np.add.at(delta, lb, c[lb] - feat)
        _np.add.at(counts, lb, 1.0)
        c -= alpha * delta / (1.0 + counts)[:, None]
        centers._data = jnp.asarray(c)
    return loss


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="int32"):  # noqa: A002
    """Sample one index per row from row-probabilities (reference
    sampling_id_op; reproducible under a fixed seed like the repo's
    other RNG ops). Non-differentiable sample: no tape edge."""
    import jax
    import jax.numpy as jnp
    from ..core.generator import next_key
    xt = _t(x)
    key = (jax.random.fold_in(jax.random.key(seed), 0) if seed
           else next_key())
    out = jax.random.categorical(
        key, jnp.log(jnp.clip(xt.data, 1e-30, None)), axis=-1)
    return to_tensor(out.astype(jnp.dtype(dtype)))


def teacher_student_sigmoid_loss(input, label,
                                 soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    """Distillation loss (reference teacher_student_sigmoid_loss_op):
    label < 0 → teacher part -z*sigmoid(x); else standard logistic
    + teacher-weighted term (the reference's piecewise contract)."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp

    def f(x, y):
        # reference piecewise (teacher_student_sigmoid_loss_op.h:43-63;
        # the bounds clip only the GRADIENT there, forward is exact):
        #   y < -1        -> log(1+e^x)
        #   -1 <= y < 0   -> log(1+e^x) - x
        #   y >= 0        -> 2*log(1+e^x) - x*y
        log1pex = jnp.logaddexp(0.0, x)
        return jnp.where(y < -1.0, log1pex,
                         jnp.where(y < 0.0, log1pex - x,
                                   2.0 * log1pex - x * y))
    return _apply("teacher_student_sigmoid_loss", f,
                  (_t(input), _t(label)))


def anchor_generator(input, anchor_sizes=None, aspect_ratios=None,
                     variance=(0.1, 0.1, 0.2, 0.2), stride=None,
                     offset=0.5, name=None):
    """SSD/FasterRCNN anchors per feature-map cell (reference
    detection.py anchor_generator). Returns (anchors [H,W,A,4],
    variances [H,W,A,4]) in xyxy like the reference."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp
    x = _t(input)
    H, W = x.shape[-2], x.shape[-1]
    sizes = [float(s) for s in (anchor_sizes or [64., 128., 256., 512.])]
    ratios = [float(r) for r in (aspect_ratios or [0.5, 1.0, 2.0])]
    sx, sy = (float(stride[0]), float(stride[1])) if stride else (16., 16.)
    boxes = []
    # reference anchor_generator_op.h:75-94: per ratio, the base box is
    # round(sqrt(stride_area / ar)) x round(base_w * ar), scaled by
    # size/stride — NOT size*sqrt(ar) (which transposes w/h)
    for r in ratios:
        base_area = sx * sy
        base_w = round((base_area / r) ** 0.5)
        base_h = round(base_w * r)
        for s in sizes:
            boxes.append((base_w * s / sx, base_h * s / sy))
    A = len(boxes)

    def f(_):
        # centers at offset*(stride-1) + cell*stride; corners use the
        # (w-1)/2 pixel convention, both per the reference
        cx = offset * (sx - 1) + jnp.arange(W) * sx
        cy = offset * (sy - 1) + jnp.arange(H) * sy
        cxg, cyg = jnp.meshgrid(cx, cy)          # [H, W]
        wh = jnp.asarray(boxes)                   # [A, 2]
        x1 = cxg[..., None] - (wh[None, None, :, 0] - 1) / 2
        y1 = cyg[..., None] - (wh[None, None, :, 1] - 1) / 2
        x2 = cxg[..., None] + (wh[None, None, :, 0] - 1) / 2
        y2 = cyg[..., None] + (wh[None, None, :, 1] - 1) / 2
        anchors = jnp.stack([x1, y1, x2, y2], axis=-1)
        var = jnp.broadcast_to(jnp.asarray(variance), anchors.shape)
        return anchors, var
    return _apply("anchor_generator", f, (x,), n_outputs=2)


def bipartite_match(dist_matrix, match_type=None, dist_threshold=None,
                    name=None):
    """Greedy bipartite matching (reference bipartite_match_op, SSD
    target assignment). Host computation (argmax loops are not
    shape-stable); returns (match_indices [N,M], match_dist [N,M]) for
    a [N?, M, P]-less 2-D [M, P] or batched input list semantics
    reduced to the common [M, P] case."""
    d = np.asarray(_t(dist_matrix).numpy())
    if d.ndim != 2:
        raise ValueError("bipartite_match expects a [M, P] distance "
                         "matrix (per-image)")
    M, P = d.shape
    match_idx = -np.ones(P, np.int64)
    match_dist = np.zeros(P, np.float32)
    work = d.copy()
    # stage 1: mutual-best greedy assignment
    for _ in _bi.range(min(M, P)):
        i, j = np.unravel_index(np.argmax(work), work.shape)
        if work[i, j] <= 0:
            break
        match_idx[j] = i
        match_dist[j] = d[i, j]
        work[i, :] = -1.0
        work[:, j] = -1.0
    if match_type == "per_prediction":
        thr = dist_threshold if dist_threshold is not None else 0.5
        for j in np.where(match_idx < 0)[0]:
            i = int(np.argmax(d[:, j]))
            if d[i, j] >= thr:
                match_idx[j] = i
                match_dist[j] = d[i, j]
    return (to_tensor(match_idx.reshape(1, P)),
            to_tensor(match_dist.reshape(1, P)))


def density_prior_box(input, image=None, densities=None,
                      fixed_sizes=None, fixed_ratios=None,
                      variance=(0.1, 0.1, 0.2, 0.2), clip=False,
                      steps=(0.0, 0.0), offset=0.5, flatten_to_2d=False,
                      name=None):
    """Densified prior boxes (reference detection.py density_prior_box):
    each (density, fixed_size) pair lays density^2 shifted boxes per
    cell of every fixed_ratio."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp
    x = _t(input)
    H, W = x.shape[-2], x.shape[-1]
    img_h, img_w = (_t(image).shape[-2:] if image is not None
                    else (H * 16, W * 16))
    step_w = steps[0] or img_w / W
    step_h = steps[1] or img_h / H
    densities = [int(d) for d in (densities or [1])]
    fixed_sizes = [float(s) for s in (fixed_sizes or [step_w])]
    fixed_ratios = [float(r) for r in (fixed_ratios or [1.0])]
    # reference density_prior_box_op.h: sub-box shifts use the INTEGER
    # step_average; coordinates clamp to [0,1] in the assignment itself
    # (the clip arg is a no-op second pass there — kept for signature)
    step_avg = int((step_w + step_h) / 2)
    specs = []  # (w, h, shift_x, shift_y) per sub-box
    for density, size in zip(densities, fixed_sizes):
        for ratio in fixed_ratios:
            bw = size * (ratio ** 0.5)
            bh = size / (ratio ** 0.5)
            shift = step_avg / density
            for di in _bi.range(density):
                for dj in _bi.range(density):
                    specs.append((bw, bh,
                                  -step_avg / 2.0 + shift / 2.0
                                  + dj * shift,
                                  -step_avg / 2.0 + shift / 2.0
                                  + di * shift))
    A = len(specs)

    def f(_):
        cx = (jnp.arange(W) + offset) * step_w
        cy = (jnp.arange(H) + offset) * step_h
        cxg, cyg = jnp.meshgrid(cx, cy)
        sp = jnp.asarray(specs)                   # [A, 4]
        bx = cxg[..., None] + sp[None, None, :, 2]
        by = cyg[..., None] + sp[None, None, :, 3]
        x1 = (bx - sp[None, None, :, 0] / 2) / img_w
        y1 = (by - sp[None, None, :, 1] / 2) / img_h
        x2 = (bx + sp[None, None, :, 0] / 2) / img_w
        y2 = (by + sp[None, None, :, 1] / 2) / img_h
        out = jnp.clip(jnp.stack([x1, y1, x2, y2], axis=-1), 0.0, 1.0)
        var = jnp.broadcast_to(jnp.asarray(variance), out.shape)
        if flatten_to_2d:
            return out.reshape(-1, 4), var.reshape(-1, 4)
        return out, var
    return _apply("density_prior_box", f, (x,), n_outputs=2)


# -- tier 5: decode/misc long tail -------------------------------------------

def gather_tree(ids, parents):
    """Fluid spelling of paddle.nn.functional.gather_tree (the impl
    lives there — reference gather_tree_op)."""
    from ..nn.functional.common import gather_tree as _impl
    return _impl(_t(ids), _t(parents))


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    """Sinusoidal position encoding added to [B, T, D] (reference
    add_position_encoding_op): out = alpha*x + beta*PE."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp

    def f(x):
        B, T, D = x.shape
        pos = jnp.arange(T, dtype=jnp.float32)[:, None]
        half = D // 2
        # reference add_position_encoding_op.h: divisor exponent is
        # k/(half-1) (and pos/10000 for the degenerate half==1)
        if half > 1:
            div = jnp.power(10000.0,
                            jnp.arange(half, dtype=jnp.float32)
                            / (half - 1))
        else:
            div = jnp.full((half,), 10000.0, jnp.float32)
        ang = pos / div[None, :]
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        if pe.shape[-1] < D:
            pe = jnp.pad(pe, ((0, 0), (0, D - pe.shape[-1])))
        return alpha * x + beta * pe[None].astype(x.dtype)
    return _apply("add_position_encoding", f, (_t(input),))


def affine_channel(x, scale=None, bias=None, data_layout="NCHW",
                   act=None, name=None):
    """Per-channel affine with FIXED (non-learned) scale/bias (reference
    affine_channel_op — frozen-BN folding in detection models)."""
    xt = _t(x)
    c_axis = 1 if data_layout == "NCHW" else -1
    shape = [1] * xt.ndim
    shape[c_axis] = xt.shape[c_axis]
    out = xt
    if scale is not None:
        out = out * _manip.reshape(_t(scale), shape)
    if bias is not None:
        out = out + _manip.reshape(_t(bias), shape)
    return getattr(F, act)(out) if act else out


_step_counters = {}


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Per-name monotone counter (reference layers/nn.py
    autoincreased_step_counter — the global_step idiom)."""
    key = counter_name or "@STEP_COUNTER@"
    v = _step_counters.get(key, begin - step) + step
    _step_counters[key] = v
    return to_tensor(np.asarray([v], np.int64))


def get_tensor_from_selected_rows(x, name=None):
    """IndexedSlices (the SelectedRows analog) → its [n_rows, dim]
    VALUES tensor (reference get_tensor_from_selected_rows_op returns
    the rows' values as-is, NOT a zero-filled dense scatter)."""
    from ..core.indexed_slices import IndexedSlices
    if isinstance(x, IndexedSlices):
        return to_tensor(x.values)
    return _t(x)


def merge_selected_rows(x, name=None):
    """Merge duplicate rows of an IndexedSlices (reference
    merge_selected_rows_op — the grad-merge before an SGD sparse
    update)."""
    from ..core.indexed_slices import IndexedSlices
    if isinstance(x, IndexedSlices):
        return x.merge()
    return _t(x)


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    """Chunk-level precision/recall/F1 for sequence labeling (reference
    chunk_eval_op; IOB/IOE/IOBES schemes). Host computation — returns
    (precision, recall, f1, num_infer, num_label, num_correct) like the
    reference's six outputs."""
    schemes = {"IOB": 2, "IOE": 2, "IOBES": 4, "plain": 1}
    if chunk_scheme not in schemes:
        raise ValueError(f"chunk_scheme {chunk_scheme!r}; "
                         f"available {sorted(schemes)}")
    tag_num = schemes[chunk_scheme]
    excluded = set(excluded_chunk_types or [])

    def extract(seq):
        """tag id -> (chunk_type, position-in-scheme); chunks as
        (start, end, type) triples. Begin/end rules per reference
        chunk_eval_op.h ChunkBegin/ChunkEnd: IOB begins on B; IOE ends
        on E; IOBES begins on B|S and ends on E|S."""
        chunks, start, ctype = [], None, None
        for i, t in enumerate(seq):
            t = int(t)
            if t == tag_num * num_chunk_types:  # the O tag
                if start is not None:
                    chunks.append((start, i, ctype))
                    start = None
                continue
            typ, pos = divmod(t, tag_num)
            begin = ((chunk_scheme == "IOB" and pos == 0)
                     or (chunk_scheme == "IOBES" and pos in (0, 3)))
            if start is not None and (begin or typ != ctype):
                chunks.append((start, i, ctype))
                start = None
            if start is None:
                start, ctype = i, typ
            end = ((chunk_scheme == "IOE" and pos == 1)
                   or (chunk_scheme == "IOBES" and pos in (2, 3)))
            if end:
                chunks.append((start, i + 1, ctype))
                start = None
        if start is not None:
            chunks.append((start, len(seq), ctype))
        return {c for c in chunks if c[2] not in excluded}

    inf = np.atleast_2d(np.asarray(_t(input).numpy()))
    inf = inf.reshape(inf.shape[0], -1)
    lab = np.asarray(_t(label).numpy()).reshape(inf.shape)
    lens = (np.asarray(_t(seq_length).numpy()).reshape(-1)
            if seq_length is not None
            else np.full(inf.shape[0], inf.shape[1], np.int64))
    n_inf = n_lab = n_cor = 0
    for b in _bi.range(inf.shape[0]):
        ci = extract(inf[b][:lens[b]])
        cl = extract(lab[b][:lens[b]])
        n_inf += len(ci)
        n_lab += len(cl)
        n_cor += len(ci & cl)
    prec = n_cor / n_inf if n_inf else 0.0
    rec = n_cor / n_lab if n_lab else 0.0
    f1 = 2 * prec * rec / (prec + rec) if prec + rec else 0.0
    mk = lambda v, dt=np.float32: to_tensor(np.asarray([v], dt))
    return (mk(prec), mk(rec), mk(f1), mk(n_inf, np.int64),
            mk(n_lab, np.int64), mk(n_cor, np.int64))


def polygon_box_transform(input, name=None):
    """Quad-vertex offset map → absolute coordinates (reference
    polygon_box_transform_op, EAST-style text detection): channel 2k is
    an x-offset added to 4*col, channel 2k+1 a y-offset added to
    4*row."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp

    def f(x):
        N, C, H, W = x.shape
        xs = jnp.arange(W, dtype=x.dtype)[None, None, None, :] * 4
        ys = jnp.arange(H, dtype=x.dtype)[None, None, :, None] * 4
        is_x = (jnp.arange(C) % 2 == 0)[None, :, None, None]
        return jnp.where(is_x, xs - x, ys - x)
    return _apply("polygon_box_transform", f, (_t(input),))


class RNNCell:  # noqa: N801 — fluid name
    """Abstract cell base (reference rnn.py:62) — the working base here
    is paddle1_tpu.nn.RNNCellBase; both constructing AND subclassing
    this stub teach that."""

    _MSG = ("fluid.layers.RNNCell: subclass paddle1_tpu.nn.RNNCellBase "
            "instead (or use GRUCell/LSTMCell here)")

    def __init__(self, *a, **k):
        from ..core.errors import UnimplementedError
        raise UnimplementedError(self._MSG)

    def __init_subclass__(cls, **k):
        from ..core.errors import UnimplementedError
        raise UnimplementedError(RNNCell._MSG)


def resize_linear(input, out_shape=None, scale=None, name=None,
                  align_corners=True, align_mode=1,
                  data_format="NCW"):
    """1-D linear interpolation (reference resize_linear)."""
    return F.interpolate(_t(input), size=out_shape, scale_factor=scale,
                         mode="linear", align_corners=align_corners)


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """Resize keeping aspect ratio so the SHORT side equals
    out_short_len (reference image_resize_short)."""
    x = _t(input)
    if x.ndim != 4:
        raise ValueError("image_resize_short expects a 4-D NCHW tensor")
    h, w = x.shape[-2], x.shape[-1]
    short, long_ = (h, w) if h <= w else (w, h)
    new_long = int(out_short_len * long_ / short + 0.5)  # reference rounds
    out_shape = ([out_short_len, new_long] if h <= w
                 else [new_long, out_short_len])
    return image_resize(x, out_shape=out_shape, resample=resample)


def lod_reset(x, y=None, target_lod=None):
    """LoD carried as explicit lengths in this build: returns
    (x, new_lengths) — the lengths REPLACE the old partition (reference
    lod_reset_op semantics on the dense+lengths representation)."""
    if y is not None:
        if not isinstance(y, Tensor):
            y = to_tensor(np.asarray(y, np.int64))
        return _t(x), y
    if target_lod is None:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError("lod_reset needs y= or target_lod= "
                                   "(the new row lengths)")
    return _t(x), to_tensor(np.asarray(target_lod, np.int64))


def lod_append(x, level):
    """Append a deeper partition level. The dense+lengths world carries
    ONE level; the appended level is returned alongside for the caller
    to thread (reference lod_append on the LoD stack)."""
    return _t(x), to_tensor(np.asarray(level, np.int64))


def beam_search(pre_ids, pre_scores, ids, scores, beam_size, end_id,
                level=0, is_accumulated=True, name=None,
                return_parent_idx=False):
    """One beam-search step (reference beam_search_op) on the dense
    representation: ``pre_ids``/``pre_scores`` [B*beam, 1],
    ``scores`` [B*beam, V] (accumulated log-probs when
    ``is_accumulated``, else per-step log-probs added to pre_scores).
    Finished beams (pre_id == end_id) keep exactly one candidate — the
    end token at their frozen score. Returns (selected_ids,
    selected_scores[, parent_idx]) with [B*beam, 1] shapes."""
    from ..autograd.engine import apply as _apply
    import jax
    import jax.numpy as jnp
    pre_ids_t, pre_sc_t, sc_t = _t(pre_ids), _t(pre_scores), _t(scores)
    V = sc_t.shape[-1]
    total = sc_t.shape[0]
    B = total // beam_size
    pruned = ids is not None  # scores are topk-pruned: column j of row
    # r is the candidate whose VOCAB id is ids[r, j] (the reference's
    # canonical topk-then-beam_search usage)
    ids_t = _t(ids) if pruned else None

    def f(pid, psc, sc, *maybe_ids):
        pid = pid.reshape(B, beam_size)
        psc = psc.reshape(B, beam_size)
        sc = sc.reshape(B, beam_size, V)
        if not is_accumulated:
            sc = psc[..., None] + sc
        finished = pid == end_id
        neg = jnp.finfo(sc.dtype).min
        if pruned:
            # finished beams survive through their column-0 slot at the
            # frozen score (its token is forced to end_id below)
            only = jnp.full((B, beam_size, V), neg, sc.dtype)
            only = only.at[:, :, 0].set(psc)
        else:
            only = jnp.full((B, beam_size, V), neg, sc.dtype)
            only = only.at[:, :, end_id].set(psc)
        sc = jnp.where(finished[..., None], only, sc)
        flat = sc.reshape(B, beam_size * V)
        top_sc, top_ix = jax.lax.top_k(flat, beam_size)
        parent = (top_ix // V).astype(jnp.int64)
        col = (top_ix % V).astype(jnp.int64)
        if pruned:
            cand = maybe_ids[0].reshape(B, beam_size, V)
            token = jnp.take_along_axis(
                cand[jnp.arange(B)[:, None], parent], col[..., None],
                axis=-1)[..., 0].astype(jnp.int64)
        else:
            token = col
        parent_finished = jnp.take_along_axis(finished, parent, axis=-1)
        token = jnp.where(parent_finished, end_id, token)
        return (token.reshape(-1, 1), top_sc.reshape(-1, 1),
                parent.reshape(-1, 1))
    args = (pre_ids_t, pre_sc_t, sc_t) + ((ids_t,) if pruned else ())
    sel_ids, sel_sc, parent = _apply("beam_search", f, args,
                                     n_outputs=3)
    if return_parent_idx:
        return sel_ids, sel_sc, parent
    return sel_ids, sel_sc


def beam_search_decode(ids, scores, beam_size, end_id, name=None,
                       parents=None):
    """Back-trace the per-step beam selections into final sequences
    (reference beam_search_decode_op). Dense form: ``ids``/``parents``
    stacked [T, B, beam] (parents from beam_search's
    return_parent_idx); returns (sequences [T, B, beam],
    final scores passthrough) with positions after each beam's end_id
    filled with end_id."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp
    if parents is None:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            "beam_search_decode needs parents= (the stacked parent_idx "
            "from beam_search(..., return_parent_idx=True)) in the "
            "dense world — the reference read them from the LoD")
    seq = gather_tree(ids, parents)

    def f(s):
        # every position from the first end_id on becomes end_id
        # (replacing the end marker itself is a no-op)
        ended = jnp.cumsum((s == end_id).astype(jnp.int32), axis=0) >= 1
        return jnp.where(ended, end_id, s)
    return (_apply("beam_search_decode", f, (seq,)),
            _t(scores) if scores is not None else None)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    """Power-iteration spectral normalization (reference
    spectral_norm_op): the u/v vectors are implicit parameters of the
    call site."""
    w = _t(weight)
    lay = _implicit_layer(
        name, ("spectral_norm", tuple(w.shape), dim, power_iters),
        lambda: _paddle.nn.SpectralNorm(list(w.shape), dim=dim,
                                        power_iters=power_iters,
                                        eps=eps))
    return lay(w)


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):  # noqa: A002
    """uniform_random with one dim copied from a reference tensor
    (reference uniform_random_batch_size_like_op)."""
    shape = list(shape)
    shape[output_dim_idx] = _t(input).shape[input_dim_idx]
    from ..ops.manip_ops import uniform as _uniform
    return _uniform(shape, dtype=dtype, min=min, max=max, seed=seed)


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    shape = list(shape)
    shape[output_dim_idx] = _t(input).shape[input_dim_idx]
    if seed:
        import jax
        import jax.numpy as jnp
        key = jax.random.fold_in(jax.random.key(seed), 0)
        return to_tensor(mean + std * jax.random.normal(
            key, tuple(shape), jnp.dtype(dtype)))
    from .layers import gaussian_random
    return gaussian_random(shape, mean=mean, std=std, dtype=dtype)


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """One LSTM step with implicit gate weights (reference
    lstm_unit_op): gates = [x_t, h_prev] @ W + b with W
    [D_x + D_h, 4*D_h]; returns (hidden, cell)."""
    from ..autograd.engine import apply as _apply
    import jax
    import jax.numpy as jnp
    x, h, c = _t(x_t), _t(hidden_t_prev), _t(cell_t_prev)
    dx, dh = x.shape[-1], h.shape[-1]
    lay = _implicit_layer(
        getattr(param_attr, "name", param_attr) or name,
        ("lstm_unit", dx, dh, bias_attr is False),
        lambda: _paddle.nn.Linear(dx + dh, 4 * dh,
                                  bias_attr=bias_attr))
    gates = lay(_manip.concat([x, h], axis=-1))

    def f(g, c):
        # reference lstm_unit_op.h gate layout: (i, f, o, g)
        i, f_, o, ct = jnp.split(g, 4, axis=-1)
        f_ = jax.nn.sigmoid(f_ + forget_bias)
        i = jax.nn.sigmoid(i)
        o = jax.nn.sigmoid(o)
        new_c = f_ * c + i * jnp.tanh(ct)
        return jnp.tanh(new_c) * o, new_c
    hidden, cell = _apply("lstm_unit", f, (gates, c), n_outputs=2)
    return hidden, cell


def hash(input, hash_size, num_hash=1, name=None):  # noqa: A001
    """Bucket integer ids by ``num_hash`` deterministic hashes into
    [0, hash_size) (reference hash_op's xxhash-mod role — the exact
    hash family differs, the contract of stable well-mixed buckets is
    kept)."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp

    def f(ids):
        ids = ids.astype(jnp.uint32)
        outs = []
        for k in _bi.range(num_hash):
            salt = (0x9E3779B9 * (k + 1)) & 0xFFFFFFFF
            h = ids * jnp.uint32(2654435761) + jnp.uint32(salt)
            h ^= h >> 16
            h = h * jnp.uint32(0x85EBCA6B)
            h ^= h >> 13
            # the reference hashes the WHOLE last-dim row as one key
            # (n-gram windows); mix the per-element hashes into one
            acc = jnp.zeros(h.shape[:-1], jnp.uint32)
            for j in _bi.range(h.shape[-1]):
                acc = acc * jnp.uint32(1099087573) + h[..., j]
            outs.append((acc % jnp.uint32(hash_size)).astype(jnp.int64))
        # reference HashOutputSize: (..., num_hash, 1)
        return jnp.stack(outs, axis=-1)[..., None]
    return _apply("hash", f, (_t(input),))


def target_assign(input, matched_indices, negative_indices=None,
                  mismatch_value=0, name=None):
    """Assign per-prior targets from matched entity rows (reference
    target_assign_op, SSD training): out[i, j] = input[i,
    matched[i, j]] where matched >= 0, else mismatch_value; weights are
    1 for matched (and listed negatives), 0 otherwise. Returns (out,
    out_weight)."""
    from ..autograd.engine import apply as _apply
    import jax.numpy as jnp
    x, m = _t(input), _t(matched_indices)

    def f(x, m):
        B, P = m.shape
        safe = jnp.clip(m, 0, x.shape[1] - 1)
        gathered = jnp.take_along_axis(
            x, safe[..., None].repeat(x.shape[-1], -1), axis=1)
        ok = (m >= 0)[..., None]
        out = jnp.where(ok, gathered, mismatch_value)
        w = ok.astype(x.dtype)
        return out, w
    out, w = _apply("target_assign", f, (x, m), n_outputs=2)
    if negative_indices is not None:
        # reference NegTargetAssignFunctor: negatives are PER ROW (the
        # LoD partition) — out forced to mismatch_value, weight to 1
        import numpy as _np
        wv = _np.array(w.numpy())   # writable copies
        ov = _np.array(out.numpy())
        neg = _np.asarray(_t(negative_indices).numpy())
        if neg.ndim == 1:
            neg = _np.tile(neg[None, :], (wv.shape[0], 1))
        for b in _bi.range(wv.shape[0]):
            for j in neg[b].reshape(-1):
                j = int(j)
                if j >= 0:
                    wv[b, j] = 1.0
                    ov[b, j] = mismatch_value
        return to_tensor(ov), to_tensor(wv)
    return out, w


def continuous_value_model(input, show_click, use_cvm=True):
    """CTR show/click feature transform (reference cvm_op): with
    ``use_cvm`` the first two embedding columns become log(show+1) and
    log(click+1)-log(show+1); without it they are dropped. The BACKWARD
    matches the reference grad kernel: dX's first two columns receive
    the CVM show/click values themselves (cvm_op grad), not autodiff
    zeros."""
    from ..autograd.engine import apply as _apply
    import jax
    import jax.numpy as jnp
    x, sc = _t(input), _t(show_click)

    @jax.custom_vjp
    def cvm(x, sc):
        show = jnp.log(sc[:, 0:1] + 1.0)
        click = jnp.log(sc[:, 1:2] + 1.0) - show
        if use_cvm:
            return jnp.concatenate([show, click, x[:, 2:]], axis=-1)
        return x[:, 2:]

    def fwd(x, sc):
        show = jnp.log(sc[:, 0:1] + 1.0)
        click = jnp.log(sc[:, 1:2] + 1.0) - show
        out = (jnp.concatenate([show, click, x[:, 2:]], axis=-1)
               if use_cvm else x[:, 2:])
        return out, (show, click)

    def bwd(res, g):
        show, click = res
        tail = g[:, 2:] if use_cvm else g
        dx = jnp.concatenate([show, click, tail], axis=-1)
        return dx, None
    cvm.defvjp(fwd, bwd)
    return _apply("cvm", cvm, (x, sc))


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=True, slot_dim=-1,
              summary_decay=0.9999999, update=True):
    """Global data normalization by ACCUMULATED batch statistics
    (reference data_norm_op — the CTR-model alternative to batch_norm:
    no per-batch recomputation at serving time; the summary stats
    batch_size/batch_sum/batch_square_sum are persistent and updated
    OUTSIDE autograd)."""
    import jax.numpy as jnp
    if slot_dim not in (-1, 0):
        from ..core.errors import UnimplementedError
        raise UnimplementedError(
            "data_norm slot_dim (per-slot zero-show special casing) is "
            "not mapped; use slot_dim=-1 or normalize slots separately")
    x = _t(input)
    D = x.shape[-1]

    holder = _implicit_layer(
        getattr(param_attr, "name", param_attr) or name,
        ("data_norm", D),
        lambda: _make_data_norm_stats(D))
    bsize, bsum, bsq = holder.batch_size, holder.batch_sum, \
        holder.batch_square_sum
    # stop-gradient stats (the reference's summaries update by decay,
    # not by autodiff)
    means = to_tensor(bsum.data) / to_tensor(bsize.data)
    scales = _math.sqrt(to_tensor(bsize.data)
                        / to_tensor(bsq.data))
    out = (x - means) * scales
    if update:
        # the reference updates the summaries in the GRAD op — once per
        # backward — so stage a PENDING update (on-device sums) that the
        # backward-end callback commits; eval-only forwards never touch
        # the stats, and multiple forwards before one backward count
        # once (latest wins, like one grad-op run)
        holder._pending = (x.shape[0],
                           jnp.sum(x.data, axis=0),
                           jnp.sum(x.data * x.data, axis=0),
                           summary_decay)
        _data_norm_pending.add(holder)
    return getattr(F, act)(out) if act else out


_data_norm_pending = set()


def _commit_data_norm_updates():
    for holder in list(_data_norm_pending):
        pend = getattr(holder, "_pending", None)
        if pend is None:
            continue
        n, ssum, ssq, decay = pend
        holder.batch_size._data = holder.batch_size.data * decay + n
        holder.batch_sum._data = holder.batch_sum.data * decay + ssum
        holder.batch_square_sum._data = (holder.batch_square_sum.data
                                         * decay + ssq)
        holder._pending = None
    _data_norm_pending.clear()


from .layers import _ag_engine as _ag  # noqa: E402

_ag.register_backward_end_callback(_commit_data_norm_updates)


def _make_data_norm_stats(D):
    lay = _paddle.nn.Layer()
    lay.batch_size = lay.create_parameter(
        [D], default_initializer=_paddle.nn.initializer.Constant(1e4))
    lay.batch_sum = lay.create_parameter(
        [D], default_initializer=_paddle.nn.initializer.Constant(0.0))
    lay.batch_square_sum = lay.create_parameter(
        [D], default_initializer=_paddle.nn.initializer.Constant(1e4))
    for p in (lay.batch_size, lay.batch_sum, lay.batch_square_sum):
        p.stop_gradient = True
    return lay
