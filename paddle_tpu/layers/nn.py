"""Layer DSL: functions that append ops to the default Program.

Reference: python/paddle/v2/fluid/layers/nn.py (fc :63, embedding :184,
conv2d :772, …) and the Gen-1 DSL python/paddle/trainer_config_helpers/
layers.py (fc_layer, img_conv_layer, …). Each function builds params via
LayerHelper and appends ops; shapes use -1 for the batch dim.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from ..core.program import Variable, default_main_program
from ..initializer import ConstantInitializer, NormalInitializer
from .helper import LayerHelper

__all__ = [
    "data",
    "fc",
    "embedding",
    "conv2d",
    "conv2d_transpose",
    "pool2d",
    "batch_norm",
    "fused_conv_bn",
    "bn_stats",
    "bn_apply",
    "RawConvBN",
    "layer_norm",
    "rms_norm",
    "rotary_embedding",
    "silu_gate",
    "dropout",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "square_error_cost",
    "accuracy",
    "mean",
    "concat",
    "reshape",
    "transpose",
    "softmax",
    "relu",
    "sigmoid",
    "tanh",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "scale",
    "cast",
    "fill_constant",
    "increment",
    "clip",
    "less_than",
    "less_equal",
    "greater_than",
    "greater_equal",
    "equal",
    "not_equal",
    "logical_and",
    "logical_not",
    "topk",
    "argmax",
    "lrn",
    "matmul",
    "reduce_sum",
    "reduce_mean",
    "split",
    "expand",
]


def data(
    name: str,
    shape: Sequence[int],
    dtype=np.float32,
    lod_level: int = 0,
    append_batch_size: bool = True,
    sparse_format: Optional[str] = None,
) -> Variable:
    """Reference: fluid layers/io.py `data` — declares a feed variable.

    shape excludes the batch dim when append_batch_size=True.
    sparse_format="binary"/"float" declares a sparse slot (reference v2
    data_type.sparse_binary_vector / sparse_float_vector backed by
    CpuSparseMatrix); the runtime value is a core/sparse.py SparseArray
    and shape must be [dim]."""
    block = default_main_program().current_block()
    full_shape = ((-1,) + tuple(shape)) if append_batch_size else tuple(shape)
    if sparse_format not in (None, "binary", "float"):
        raise ValueError(f"sparse_format must be 'binary'/'float', got {sparse_format!r}")
    return block.create_var(name, full_shape, dtype, lod_level=lod_level,
                            sparse_format=sparse_format)


def fc(
    input,
    size: int,
    act: Optional[str] = None,
    num_flatten_dims: int = 1,
    param_attr=None,
    bias_attr=None,
    name=None,
) -> Variable:
    """Reference: fluid layers/nn.py:63 `fc`; Gen-1 fc_layer

    (trainer_config_helpers/layers.py) / FullyConnectedLayer.cpp:27.
    Multiple inputs are summed after their own W (MixedLayer semantics)."""
    helper = LayerHelper("fc", name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_outs = []
    for i, inp in enumerate(inputs):
        in_dim = int(np.prod(inp.shape[num_flatten_dims:]))
        w = helper.create_parameter(
            param_attr if not isinstance(param_attr, (list, tuple)) else param_attr[i],
            shape=(in_dim, size),
            dtype=inp.dtype,
        )
        out = helper.create_tmp_variable(inp.dtype, inp.shape[:num_flatten_dims] + (size,), inp.lod_level)
        helper.append_op(
            type="mul",
            inputs={"X": [inp], "Y": [w]},
            outputs={"Out": [out]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_outs.append(out)
    if len(mul_outs) == 1:
        pre_bias = mul_outs[0]
    else:
        pre_bias = helper.create_tmp_variable(inputs[0].dtype, mul_outs[0].shape)
        helper.append_op(type="sum", inputs={"X": mul_outs}, outputs={"Out": [pre_bias]})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=(size,), is_bias=True)
        pre_act = helper.create_tmp_variable(pre_bias.dtype, pre_bias.shape, pre_bias.lod_level)
        helper.append_op(
            type="elementwise_add",
            inputs={"X": [pre_bias], "Y": [b]},
            outputs={"Out": [pre_act]},
            attrs={"axis": -1},
        )
    else:
        pre_act = pre_bias
    return helper.append_activation(pre_act, act)


def embedding(
    input,
    size: Sequence[int],
    is_sparse: bool = False,
    padding_idx: Optional[int] = None,
    param_attr=None,
    dtype=np.float32,
    name=None,
) -> Variable:
    """Reference: fluid layers/nn.py:184 `embedding` / lookup_table_op.cc.

    is_sparse=True gives the table SelectedRows (row-wise) gradients
    (reference: framework/selected_rows.h + SparseRowMatrix.h): the autodiff
    lowering never materializes a dense [vocab, dim] grad — it takes grads
    w.r.t. the gathered rows only (core/executor.py) — and optimizer ops
    apply lazy row-wise updates via scatter (ops/optimizer_ops.py)."""
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(
        param_attr,
        shape=tuple(size),
        dtype=dtype,
        default_initializer=NormalInitializer(0.0, 0.01),
    )
    if is_sparse:
        w.sparse_update = True
    out = helper.create_tmp_variable(dtype, input.shape + (size[1],), input.lod_level)
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"is_sparse": is_sparse, "padding_idx": padding_idx},
    )
    return out


def _pair_(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


def _conv_out_hw(hw, ksize, stride, padding, dilation=1):
    """Static NCHW output spatial dims; -1 propagates unknowns."""
    k, s, p, d = _pair_(ksize), _pair_(stride), _pair_(padding), _pair_(dilation)
    out = []
    for i in range(2):
        if hw[i] == -1:
            out.append(-1)
        else:
            eff = d[i] * (k[i] - 1) + 1
            out.append((hw[i] + 2 * p[i] - eff) // s[i] + 1)
    return tuple(out)


def conv2d(
    input,
    num_filters: int,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups: int = 1,
    act: Optional[str] = None,
    param_attr=None,
    bias_attr=None,
    name=None,
    data_format: str = "NCHW",
) -> Variable:
    """Reference: fluid layers/nn.py:772 `conv2d`; Gen-1 img_conv_layer.

    data_format="NHWC" runs channels-minor — the TPU-native layout (channel
    dim lands on the 128-wide lane register dimension; no relayout before
    the MXU). The weight parameter keeps OIHW shape either way."""
    helper = LayerHelper("conv2d", name=name)
    in_c = input.shape[1] if data_format == "NCHW" else input.shape[3]
    fh, fw = _pair_(filter_size)
    w_shape = (num_filters, in_c // groups, fh, fw)
    fan_in = (in_c // groups) * fh * fw
    std = (2.0 / fan_in) ** 0.5
    w = helper.create_parameter(
        param_attr, w_shape, default_initializer=NormalInitializer(0.0, std)
    )
    inputs = {"Input": [input], "Filter": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, (num_filters,), is_bias=True)
        inputs["Bias"] = [b]
    hw_in = input.shape[2:4] if data_format == "NCHW" else input.shape[1:3]
    out_hw = _conv_out_hw(hw_in, (fh, fw), stride, padding, dilation)
    out_shape = ((-1, num_filters) + out_hw if data_format == "NCHW"
                 else (-1,) + out_hw + (num_filters,))
    out = helper.create_tmp_variable(input.dtype, out_shape)
    helper.append_op(
        type="conv2d",
        inputs=inputs,
        outputs={"Output": [out]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "data_format": data_format,
        },
    )
    return helper.append_activation(out, act)


def conv2d_transpose(
    input, num_filters, filter_size, stride=1, padding=0, param_attr=None,
    bias_attr=None, act: Optional[str] = None, name=None
) -> Variable:
    helper = LayerHelper("conv2d_transpose", name=name)
    in_c = input.shape[1]
    fh, fw = _pair_(filter_size)
    w = helper.create_parameter(param_attr, (in_c, num_filters, fh, fw))
    s, p = _pair_(stride), _pair_(padding)
    inputs = {"Input": [input], "Filter": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, (num_filters,), is_bias=True)
        inputs["Bias"] = [b]
    out_hw = tuple(
        -1 if input.shape[2 + i] == -1
        else (input.shape[2 + i] - 1) * s[i] - 2 * p[i] + (fh, fw)[i]
        for i in range(2)
    )
    out = helper.create_tmp_variable(input.dtype, (-1, num_filters) + out_hw)
    helper.append_op(
        type="conv2d_transpose",
        inputs=inputs,
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding},
    )
    return helper.append_activation(out, act)


def pool2d(
    input,
    pool_size=2,
    pool_type: str = "max",
    pool_stride=None,
    pool_padding=0,
    global_pooling: bool = False,
    exclusive: bool = True,
    name=None,
    data_format: str = "NCHW",
) -> Variable:
    """Reference: fluid layers/nn.py `pool2d` / pool_op.cc."""
    helper = LayerHelper("pool2d", name=name)
    hw_in = input.shape[2:4] if data_format == "NCHW" else input.shape[1:3]
    c = input.shape[1] if data_format == "NCHW" else input.shape[3]
    if global_pooling:
        out_hw = (1, 1)
    else:
        out_hw = _conv_out_hw(
            hw_in,
            pool_size,
            pool_stride if pool_stride is not None else pool_size,
            pool_padding,
        )
    out_shape = ((-1, c) + out_hw if data_format == "NCHW"
                 else (-1,) + out_hw + (c,))
    out = helper.create_tmp_variable(input.dtype, out_shape)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": pool_size,
            "strides": pool_stride if pool_stride is not None else pool_size,
            "paddings": pool_padding,
            "global_pooling": global_pooling,
            "exclusive": exclusive,
            "data_format": data_format,
        },
    )
    return out


def _create_bn_params(helper, c, param_attr=None, bias_attr=None):
    """scale/bias trainables + running mean/variance persistables, in the
    exact creation order batch_norm uses (shared with the fused conv path
    so the two formulations produce identical checkpoint names)."""
    scale = helper.create_parameter(
        param_attr, (c,), default_initializer=ConstantInitializer(1.0)
    )
    bias = helper.create_parameter(bias_attr, (c,), is_bias=True)
    from ..param_attr import ParamAttr as _PA

    mean = helper.create_parameter(
        _PA(name=f"{helper.name}.mean"), (c,),
        default_initializer=ConstantInitializer(0.0),
    )
    var = helper.create_parameter(
        _PA(name=f"{helper.name}.variance"), (c,),
        default_initializer=ConstantInitializer(1.0),
    )
    # running stats are state, not trainable weights
    for v in (mean, var):
        v.trainable = False
        v.is_parameter = False
        v.persistable = True
    return scale, bias, mean, var


def batch_norm(
    input,
    act: Optional[str] = None,
    momentum: float = 0.9,
    epsilon: float = 1e-5,
    is_test: bool = False,
    param_attr=None,
    bias_attr=None,
    name=None,
    data_format: str = "NCHW",
) -> Variable:
    """Reference: fluid layers/nn.py `batch_norm` / batch_norm_op.cc."""
    helper = LayerHelper("batch_norm", name=name)
    c = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    scale, bias, mean, var = _create_bn_params(helper, c, param_attr,
                                               bias_attr)
    out = helper.create_tmp_variable(input.dtype, input.shape)
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [out]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_format": data_format},
    )
    return helper.append_activation(out, act)


class RawConvBN:
    """A raw (pre-BatchNorm) activation plus the stats/params needed to
    normalize it — the currency of the fused conv+BN protocol
    (ops/fused_conv_ops.py). Consumers either materialize the normalized
    tensor (bn_apply: one fused elementwise pass) or hand the pair to the
    next fused_conv_bn, which applies the normalize to its operand as
    it reads it (the activation is then never written normalized at all)."""

    __slots__ = ("out", "mean", "inv", "scale", "bias")

    def __init__(self, out, mean, inv, scale, bias):
        self.out = out
        self.mean = mean
        self.inv = inv
        self.scale = scale
        self.bias = bias


def fused_conv_bn(
    input,
    num_filters: int,
    stride: int = 1,
    prologue_act: Optional[str] = "relu",
    momentum: float = 0.9,
    epsilon: float = 1e-5,
    param_attr=None,
    bn_param_attr=None,
    bn_bias_attr=None,
    name=None,
) -> RawConvBN:
    """1x1 conv + BatchNorm through the fused raw-stats protocol (NHWC,
    train mode). `input` is a Variable (normalized activation — no
    prologue) or a RawConvBN (the previous BN's apply+act runs inside this
    conv's kernel prologue). Returns this conv's RawConvBN.

    Reference: the cuDNN fused conv machinery the reference's conv hot
    path always runs through (gserver/layers/CudnnConvBaseLayer.cpp,
    cuda/src/hl_cuda_cudnn.cc); parameter names match the unfused
    conv2d+batch_norm sequence exactly so checkpoints interchange (the
    eval-mode graph is built unfused)."""
    prologue = isinstance(input, RawConvBN)
    x = input.out if prologue else input
    in_c = x.shape[3]
    conv_helper = LayerHelper("conv2d")
    std = (2.0 / in_c) ** 0.5
    w = conv_helper.create_parameter(
        param_attr, (num_filters, in_c, 1, 1),
        default_initializer=NormalInitializer(0.0, std),
    )
    # `name` names the BN half (its helper owns the running mean/variance
    # persistable names, which must match an unfused batch_norm's)
    bn_helper = LayerHelper("batch_norm", name=name)
    scale, bias, mean, var = _create_bn_params(
        bn_helper, num_filters, bn_param_attr, bn_bias_attr)
    out_hw = tuple(
        -1 if d == -1 else (d + stride - 1) // stride for d in x.shape[1:3]
    )
    out = conv_helper.create_tmp_variable(
        x.dtype, (-1,) + out_hw + (num_filters,))
    bmean = conv_helper.create_tmp_variable(np.float32, (num_filters,))
    binv = conv_helper.create_tmp_variable(np.float32, (num_filters,))
    inputs = {"X": [x], "Filter": [w], "Mean": [mean], "Variance": [var]}
    if prologue:
        inputs.update({"XMean": [input.mean], "XInv": [input.inv],
                       "XScale": [input.scale], "XBias": [input.bias]})
    conv_helper.append_op(
        type="fused_conv_bn",
        inputs=inputs,
        outputs={"Out": [out], "BatchMean": [bmean], "BatchInv": [binv]},
        attrs={"stride": stride, "epsilon": epsilon, "momentum": momentum,
               "prologue_act": prologue_act},
    )
    return RawConvBN(out, bmean, binv, scale, bias)


def bn_stats(
    input,
    momentum: float = 0.9,
    epsilon: float = 1e-5,
    param_attr=None,
    bias_attr=None,
    name=None,
) -> RawConvBN:
    """Stats-only BatchNorm over a raw NHWC activation (one reduce pass);
    pairs with bn_apply / a fused_conv_bn prologue for the normalize.
    Parameter names match an unfused batch_norm at the same position."""
    helper = LayerHelper("batch_norm", name=name)
    c = input.shape[-1]
    scale, bias, mean, var = _create_bn_params(helper, c, param_attr,
                                               bias_attr)
    bmean = helper.create_tmp_variable(np.float32, (c,))
    binv = helper.create_tmp_variable(np.float32, (c,))
    helper.append_op(
        type="bn_stats",
        inputs={"X": [input], "Mean": [mean], "Variance": [var]},
        outputs={"BatchMean": [bmean], "BatchInv": [binv]},
        attrs={"epsilon": epsilon, "momentum": momentum},
    )
    return RawConvBN(input, bmean, binv, scale, bias)


def bn_apply(raw: RawConvBN, act: Optional[str] = None, name=None) -> Variable:
    """Materialize the normalized activation of a RawConvBN (one XLA
    elementwise pass, fused with adjacent adds/relus by the compiler)."""
    helper = LayerHelper("bn_apply", name=name)
    out = helper.create_tmp_variable(raw.out.dtype, raw.out.shape)
    helper.append_op(
        type="bn_apply",
        inputs={"X": [raw.out], "Mean": [raw.mean], "Inv": [raw.inv],
                "Scale": [raw.scale], "Bias": [raw.bias]},
        outputs={"Out": [out]},
        attrs={"act": act},
    )
    return out


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5, name=None):
    helper = LayerHelper("layer_norm", name=name)
    dim = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input]}
    if scale:
        inputs["Scale"] = [
            helper.create_parameter(None, (dim,), default_initializer=ConstantInitializer(1.0))
        ]
    if shift:
        inputs["Bias"] = [helper.create_parameter(None, (dim,), is_bias=True)]
    out = helper.create_tmp_variable(input.dtype, input.shape)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out]},
        attrs={"begin_norm_axis": begin_norm_axis, "epsilon": epsilon},
    )
    return out


def rms_norm(input, epsilon=1e-5, param_attr=None, name=None, group=None):
    """RMSNorm over the last axis with a learned scale (ones at the
    start): x * rsqrt(mean(x^2) + epsilon) * w. Beyond the 2017
    reference's layer set; the norm of the Llama / OLMo families. The
    output is float32 under amp too (ops/nn_ops.py:rms_norm): a router may
    read it. The one exception is not a caller's to ask for: the two norms
    `multi_head_attention` puts on Q and K, which it marks itself as read by
    nothing but its rotary or its kernel (layers/attention.py).
    group G: the last axis is G-lane groups side by side (the heads of a
    packed projection), each normed on its own over its G lanes, and ONE
    scale [G] serves them all. None: the whole axis, and the op appended is
    the one it always was."""
    helper = LayerHelper("rms_norm", name=name)
    width = int(input.shape[-1])
    if group is not None and (group <= 0 or width % group):
        raise ValueError(f"groups of {group} lanes do not divide {width}")
    w = helper.create_parameter(
        param_attr, (width if group is None else int(group),),
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(np.float32, input.shape)
    attrs = {"epsilon": epsilon}
    if group is not None:
        attrs["group"] = int(group)
    helper.append_op(
        type="rms_norm",
        inputs={"X": [input], "Scale": [w]},
        outputs={"Y": [out]},
        attrs=attrs,
    )
    return out


def rotary_embedding(x, num_heads: int, theta: float = 10000.0, name=None,
                     rotary_dim: Optional[int] = None, positions=None,
                     sections=None):
    """Rotary position embedding (Su et al. 2021, rotate-half convention)
    on a packed multi-head projection [B, T, E]: position t rotates each
    head's (i, i + D/2) lane pair by t * theta^(-2i/D). No parameters.
    rotary_dim R: only the last R lanes of each head turn (pairs (i, i +
    R/2) of those R, frequencies theta^(-2i/R)) and the D - R in front pass
    through: latent attention's `[nope | rope]` head. None: the whole head,
    and the op appended is the one it always was.
    positions: an int32 Variable [B, A, T], the positions as FED DATA, A
    axes a token (a data layer is batch-major), with `sections`: A counts of
    frequency pairs that sum to half the turned lanes; pair i turns by the
    position on the axis whose section holds it (a three-axis rotary's
    `mrope_section` [16, 24, 24] over temporal, height and width: the
    Qwen2-VL form, contiguous sections). `sections` None with A = 1: a plain
    fed position. A token whose axes are equal turns as position t = that
    value does without the input, bit for bit. None: positions 0..T-1, and
    the op appended is the one it always was."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_tmp_variable(x.dtype, x.shape)
    attrs = {"num_heads": num_heads, "theta": float(theta)}
    if rotary_dim is not None:
        attrs["rotary_dim"] = int(rotary_dim)
    inputs = {"X": [x]}
    if positions is not None:
        turned = int(x.shape[-1]) // num_heads if rotary_dim is None \
            else int(rotary_dim)
        sections = [turned // 2] if sections is None else [
            int(n) for n in sections]
        if len(positions.shape) != 3 or int(positions.shape[1]) \
                != len(sections) or sum(sections) != turned // 2:
            raise ValueError(
                f"positions {tuple(positions.shape)} with sections "
                f"{sections}: [B, {len(sections)}, T] and sections that sum "
                f"to {turned // 2} pairs")
        inputs["Positions"] = [positions]
        attrs["sections"] = sections
    elif sections is not None:
        raise ValueError("sections without positions")
    helper.append_op(
        type="rotary_embedding",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs=attrs,
    )
    return out


def dropout(x, dropout_prob: float, is_test: bool = False, name=None) -> Variable:
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(x.dtype, x.shape, x.lod_level)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test},
    )
    return out


# ------------------------------------------------------------- losses ------
def cross_entropy(input, label, soft_label: bool = False) -> Variable:
    helper = LayerHelper("cross_entropy")
    out = helper.create_tmp_variable(input.dtype, (input.shape[0], 1))
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label: bool = False):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_tmp_variable(
        logits.dtype, logits.shape, lod_level=logits.lod_level
    )
    loss = helper.create_tmp_variable(
        logits.dtype, (logits.shape[0], 1), lod_level=logits.lod_level
    )
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={"soft_label": soft_label},
    )
    return loss


def square_error_cost(input, label) -> Variable:
    helper = LayerHelper("square_error_cost")
    out = helper.create_tmp_variable(input.dtype, input.shape)
    helper.append_op(
        type="square_error_cost",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out]},
    )
    return out


def accuracy(input, label, k: int = 1) -> Variable:
    """Reference: fluid layers accuracy — topk + accuracy op."""
    helper = LayerHelper("accuracy")
    vals = helper.create_tmp_variable(input.dtype, input.shape[:-1] + (k,))
    idxs = helper.create_tmp_variable(np.int32, input.shape[:-1] + (k,))
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [vals], "Indices": [idxs]},
        attrs={"k": k},
    )
    acc = helper.create_tmp_variable(np.float32, ())
    helper.append_op(
        type="accuracy",
        inputs={"Indices": [idxs], "Label": [label]},
        outputs={"Accuracy": [acc]},
    )
    return acc


# ------------------------------------------------- elementwise / shape ------
def _unary(op_type, x, attrs=None, out_shape=None):
    helper = LayerHelper(op_type)
    out = helper.create_tmp_variable(x.dtype, out_shape if out_shape is not None else x.shape, x.lod_level)
    helper.append_op(type=op_type, inputs={"X": [x]}, outputs={"Out": [out]}, attrs=attrs or {})
    return out


def _binary(op_type, x, y, attrs=None):
    helper = LayerHelper(op_type)
    out = helper.create_tmp_variable(x.dtype, x.shape, x.lod_level)
    helper.append_op(
        type=op_type, inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]}, attrs=attrs or {}
    )
    return out


def mean(x):
    return _unary("mean", x, out_shape=())


def softmax(x):
    return _unary("softmax", x)


def relu(x):
    return _unary("relu", x)


def sigmoid(x):
    return _unary("sigmoid", x)


def tanh(x):
    return _unary("tanh", x)


def elementwise_add(x, y, axis=-1):
    return _binary("elementwise_add", x, y, {"axis": axis})


def elementwise_sub(x, y, axis=-1):
    return _binary("elementwise_sub", x, y, {"axis": axis})


def elementwise_mul(x, y, axis=-1):
    return _binary("elementwise_mul", x, y, {"axis": axis})


def elementwise_div(x, y, axis=-1):
    return _binary("elementwise_div", x, y, {"axis": axis})


def silu_gate(x, gate=None, name=None):
    """x * silu(gate), shaped like x; with no `gate`, x is [gate | value]
    side by side along its last axis (a fused up-projection's output) and
    the result is value * silu(gate), half as wide. Float32 inside, x's
    dtype out; the backward keeps the operands alone
    (ops/ssm_ops.py:silu_gate)."""
    helper = LayerHelper("silu_gate", name=name)
    inputs = {"X": [x]}
    shape = tuple(x.shape)
    if gate is None:
        if shape[-1] % 2:
            raise ValueError(f"a fused [gate | value] of {shape[-1]} lanes")
        shape = shape[:-1] + (shape[-1] // 2,)
    else:
        inputs["Gate"] = [gate]
    out = helper.create_tmp_variable(x.dtype, shape)
    helper.append_op(type="silu_gate", inputs=inputs, outputs={"Out": [out]})
    return out


def scale(x, scale=1.0, bias=0.0):
    return _unary("scale", x, {"scale": scale, "bias": bias})


def cast(x, dtype):
    helper = LayerHelper("cast")
    out = helper.create_tmp_variable(np.dtype(dtype), x.shape, x.lod_level)
    helper.append_op(
        type="cast", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"dtype": np.dtype(dtype).name},
    )
    return out


def fill_constant(shape, dtype, value):
    """Reference: fluid layers fill_constant (operators/fill_constant_op.cc)."""
    helper = LayerHelper("fill_constant")
    out = helper.create_tmp_variable(np.dtype(dtype), tuple(shape))
    helper.append_op(
        type="fill_constant",
        inputs={},
        outputs={"Out": [out]},
        attrs={
            "shape": list(shape),
            "dtype": np.dtype(dtype).name,
            "value": value,
        },
    )
    return out


def increment(x, value=1.0):
    """Reference: operators/increment_op.cc."""
    helper = LayerHelper("increment")
    out = helper.create_tmp_variable(x.dtype, x.shape, x.lod_level)
    helper.append_op(
        type="increment", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"step": value},
    )
    return out


def concat(input, axis=0):
    helper = LayerHelper("concat")
    shape = list(input[0].shape)
    ax = axis if axis >= 0 else len(shape) + axis
    if all(v.shape[ax] != -1 for v in input):
        shape[ax] = sum(v.shape[ax] for v in input)
    else:
        shape[ax] = -1
    out = helper.create_tmp_variable(input[0].dtype, tuple(shape))
    helper.append_op(
        type="concat", inputs={"X": list(input)}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def reshape(x, shape):
    return _unary("reshape", x, {"shape": list(shape)}, out_shape=tuple(shape))


def transpose(x, perm):
    return _unary("transpose", x, {"axis": list(perm)},
                  out_shape=tuple(x.shape[i] for i in perm))


def matmul(x, y, transpose_x=False, transpose_y=False):
    helper = LayerHelper("matmul")
    shape = tuple(x.shape)
    if len(x.shape) >= 2 and len(y.shape) >= 2:
        shape = tuple(x.shape[:-2]) + (
            x.shape[-1] if transpose_x else x.shape[-2],
            y.shape[-2] if transpose_y else y.shape[-1])
    out = helper.create_tmp_variable(x.dtype, shape, x.lod_level)
    helper.append_op(
        type="matmul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y})
    return out


def clip(x, min, max):  # noqa: A002 — fluid layers.clip signature
    """Reference: fluid layers clip / operators/clip_op.cc."""
    return _unary("clip", x, {"min": float(min), "max": float(max)})


def _reduced_shape(shape, dim, keep_dim):
    if dim is None:
        # keepdims over all axes preserves rank
        return (1,) * len(shape) if keep_dim else ()
    dims = (dim,) if isinstance(dim, int) else tuple(dim)
    dims = tuple(d % len(shape) for d in dims)
    if keep_dim:
        return tuple(1 if i in dims else s for i, s in enumerate(shape))
    return tuple(s for i, s in enumerate(shape) if i not in dims)


def reduce_sum(x, dim=None, keep_dim=False):
    return _unary(
        "reduce_sum", x,
        {"dim": dim, "keep_dim": keep_dim, "reduce_all": dim is None},
        out_shape=_reduced_shape(x.shape, dim, keep_dim),
    )


def reduce_mean(x, dim=None, keep_dim=False):
    return _unary(
        "reduce_mean", x,
        {"dim": dim, "keep_dim": keep_dim, "reduce_all": dim is None},
        out_shape=_reduced_shape(x.shape, dim, keep_dim),
    )


def split(x, num_or_sections, dim=0):
    helper = LayerHelper("split")
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "axis": dim}
        sizes = [x.shape[dim] // n if x.shape[dim] != -1 else -1] * n
    else:
        n = len(num_or_sections)
        attrs = {"sections": list(num_or_sections), "axis": dim}
        sizes = list(num_or_sections)
    ax = dim if dim >= 0 else len(x.shape) + dim
    outs = [helper.create_tmp_variable(
        x.dtype, tuple(x.shape[:ax]) + (size,) + tuple(x.shape[ax + 1:]))
        for size in sizes]
    helper.append_op(type="split", inputs={"X": [x]}, outputs={"Out": outs}, attrs=attrs)
    return outs


def expand(x, expand_times):
    return _unary("expand", x, {"expand_times": list(expand_times)})


def topk(input, k=1):
    helper = LayerHelper("top_k")
    vals = helper.create_tmp_variable(input.dtype, input.shape[:-1] + (k,))
    idxs = helper.create_tmp_variable(np.int32, input.shape[:-1] + (k,))
    helper.append_op(
        type="top_k", inputs={"X": [input]},
        outputs={"Out": [vals], "Indices": [idxs]}, attrs={"k": k},
    )
    return vals, idxs


def argmax(x, axis=-1):
    helper = LayerHelper("argmax")
    out = helper.create_tmp_variable(np.int32, x.shape[:-1])
    helper.append_op(
        type="argmax", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def lrn(input, n=5, k=2.0, alpha=1e-4, beta=0.75):
    return _unary("lrn", input, {"n": n, "k": k, "alpha": alpha, "beta": beta})


def _broadcast_static_shape(a, b):
    """numpy broadcast over static shapes where -1 is an unknown dim."""
    a, b = tuple(a), tuple(b)
    n = max(len(a), len(b))
    a = (1,) * (n - len(a)) + a
    b = (1,) * (n - len(b)) + b
    out = []
    for da, db in zip(a, b):
        if da == -1 or db == -1:
            out.append(-1 if max(da, db) in (-1, 1) else max(da, db))
        else:
            out.append(max(da, db))
    return tuple(out)


def _compare_layer(op_type, x, y):
    helper = LayerHelper(op_type)
    out = helper.create_tmp_variable(
        np.bool_, _broadcast_static_shape(x.shape, y.shape), x.lod_level
    )
    helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def less_than(x, y):
    """Reference: operators/compare_op.cc (fluid layers.less_than)."""
    return _compare_layer("less_than", x, y)


def less_equal(x, y):
    return _compare_layer("less_equal", x, y)


def greater_than(x, y):
    return _compare_layer("greater_than", x, y)


def greater_equal(x, y):
    return _compare_layer("greater_equal", x, y)


def equal(x, y):
    return _compare_layer("equal", x, y)


def not_equal(x, y):
    return _compare_layer("not_equal", x, y)


def logical_and(x, y):
    return _compare_layer("logical_and", x, y)


def logical_not(x):
    helper = LayerHelper("logical_not")
    out = helper.create_tmp_variable(np.bool_, x.shape, x.lod_level)
    helper.append_op(type="logical_not", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out
