"""High-level NN layers that build graph ops.

Parity: python/paddle/fluid/layers/nn.py — same function names, argument
names, and op-emission behavior (fc emits mul+sum+bias+act, conv2d creates
its filter parameter, batch_norm creates scale/bias/moving stats, ...).
"""
import numpy as np

from ..core.framework import Variable
from ..core.layer_helper import LayerHelper
from ..core.initializer import ConstantInitializer, NormalInitializer
from ..core.param_attr import ParamAttr
from ..core import unique_name
from ..core.utils import pair as _pair

__all__ = [
    "fc", "embedding", "conv2d", "conv2d_transpose", "pool2d", "batch_norm",
    "layer_norm", "dropout", "softmax", "softmax_with_cross_entropy",
    "cross_entropy", "square_error_cost", "accuracy", "topk", "matmul",
    "one_hot", "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "split", "l2_normalize", "cos_sim", "dropout",
    "smooth_l1", "autoincreased_step_counter", "transpose", "im2sequence",
    "multiplex", "label_smooth", "nce", "lrn", "maxout", "relu", "log",
    "expand", "sequence_mask", "linear_chain_crf", "crf_decoding",
    "chunk_eval", "warpctc", "ctc_greedy_decoder", "sequence_erase",
    "edit_distance", "fused_attention", "rms_norm", "rotary_embedding",
    "causal_conv1d", "gated_delta_rule", "kda_delta_rule", "selective_scan",
    "ssd_scan",
    "mhc_pre",
    "mhc_post", "mhc_expand",
    "mhc_reduce",
]


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None, use_mkldnn=False):
    """Fully connected. Parity: fluid.layers.fc (nn.py:88 in reference).

    Emits one mul op per input + sum (if multiple) + bias + activation.
    """
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()

    mul_results = []
    for input_var, param_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        flatten = num_flatten_dims
        if input_var.lod_level > 0 and num_flatten_dims == 1:
            # sequence input in padded [B, T, D] layout: the reference's flat
            # [total_tokens, D] fc is a per-timestep projection here
            flatten = len(input_shape) - 1
        param_shape = [
            int(np.prod(input_shape[flatten:]))
        ] + [size]
        w = helper.create_parameter(
            attr=param_attr, shape=param_shape, dtype=dtype, is_bias=False)
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": flatten, "y_num_col_dims": 1})
        mul_results.append(tmp)

    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_activation = helper.append_bias_op(pre_bias, dim_start=flatten)
    return helper.append_activation(pre_activation)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """Parity: fluid.layers.embedding → lookup_table op. `is_sparse` selects
    the reference's SelectedRows grad path; here it is accepted and ignored:
    the table's gradient is always a dense [V, D] in the table's dtype, the
    rows of a repeated id summed in float32. On one TPU a Pallas kernel
    writes a table of rows of 8 KiB and more (ops/embedding_grad.py),
    elsewhere XLA's scatter-add does, which on a TPU is no sparse-efficient
    operation (PERF.md section 6, PR 41)."""
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False)
    tmp = helper.create_variable_for_type_inference(dtype)
    padding_idx = -1 if padding_idx is None else \
        padding_idx if padding_idx >= 0 else (size[0] + padding_idx)
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [tmp]},
        attrs={"is_sparse": is_sparse, "is_distributed": is_distributed,
               "padding_idx": padding_idx})
    return tmp


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           use_mkldnn=False, act=None, name=None):
    """Parity: fluid.layers.conv2d (cuDNN kernel → XLA conv on MXU)."""
    helper = LayerHelper("conv2d", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    if num_channels % groups != 0:
        raise ValueError("num_channels must be divisible by groups")
    num_filter_channels = num_channels // groups

    filter_size = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)

    filter_shape = [num_filters, num_filter_channels] + list(filter_size)

    def _get_default_param_initializer():
        std = (2.0 / (filter_size[0] ** 2 * num_channels)) ** 0.5
        return NormalInitializer(0.0, std, 0)

    filter_param = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=_get_default_param_initializer())

    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [filter_param]},
        outputs={"Output": [pre_bias]},
        attrs={"strides": list(stride), "paddings": list(padding),
               "dilations": list(dilation), "groups": groups,
               "use_cudnn": use_cudnn})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, use_cudnn=True, act=None, name=None):
    """Parity: fluid.layers.conv2d_transpose."""
    helper = LayerHelper("conv2d_transpose", **locals())
    dtype = helper.input_dtype()
    input_channel = input.shape[1]
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    if filter_size is None:
        if output_size is None:
            raise ValueError("output_size must be set when filter_size is None")
        output_size = _pair(output_size)
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size_h = (output_size[0] - (h_in - 1) * stride[0] +
                         2 * padding[0] - 1) // dilation[0] + 1
        filter_size_w = (output_size[1] - (w_in - 1) * stride[1] +
                         2 * padding[1] - 1) // dilation[1] + 1
        filter_size = [filter_size_h, filter_size_w]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [input_channel, num_filters] + list(filter_size)
    img_filter = helper.create_parameter(
        dtype=dtype, shape=filter_shape, attr=helper.param_attr)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [img_filter]},
        outputs={"Output": [pre_bias]},
        attrs={"strides": list(stride), "paddings": list(padding),
               "dilations": list(dilation)})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, use_mkldnn=False, name=None):
    """Parity: fluid.layers.pool2d."""
    if pool_type not in ("max", "avg"):
        raise ValueError("pool_type must be 'max' or 'avg'")
    helper = LayerHelper("pool2d", **locals())
    dtype = helper.input_dtype()
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": list(_pair(pool_size)),
               "global_pooling": global_pooling,
               "strides": list(_pair(pool_stride)),
               "paddings": list(_pair(pool_padding)),
               "ceil_mode": ceil_mode})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, use_mkldnn=False, name=None,
               moving_mean_name=None, moving_variance_name=None,
               do_model_average_for_mean_and_var=False):
    """Parity: fluid.layers.batch_norm — creates scale/bias params and
    persistable moving mean/variance updated in-place each step."""
    helper = LayerHelper("batch_norm", **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    if data_layout == "NCHW":
        channel_num = input_shape[1] if len(input_shape) > 2 else input_shape[-1]
    else:
        channel_num = input_shape[-1]
    param_shape = [channel_num]

    scale = helper.create_parameter(
        attr=helper.param_attr, shape=param_shape, dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True)

    mean = helper.create_parameter(
        attr=ParamAttr(name=moving_mean_name,
                       initializer=ConstantInitializer(0.0), trainable=False),
        shape=param_shape, dtype=dtype)
    mean.stop_gradient = True
    variance = helper.create_parameter(
        attr=ParamAttr(name=moving_variance_name,
                       initializer=ConstantInitializer(1.0), trainable=False),
        shape=param_shape, dtype=dtype)
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(
        dtype=dtype, stop_gradient=True)
    saved_variance = helper.create_variable_for_type_inference(
        dtype=dtype, stop_gradient=True)
    # in_place is accepted for API parity but always materializes a fresh
    # var: aliasing Y onto X would make the vjp backward replay read the
    # normalized output as its input (XLA buffer reuse makes the "in place"
    # memory saving moot anyway).
    batch_norm_out = helper.create_variable_for_type_inference(dtype)

    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [batch_norm_out], "MeanOut": [mean],
                 "VarianceOut": [variance], "SavedMean": [saved_mean],
                 "SavedVariance": [saved_variance]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout})
    return helper.append_activation(batch_norm_out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    """Parity: fluid.layers.layer_norm."""
    helper = LayerHelper("layer_norm", **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    param_shape = [int(np.prod(input_shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr, shape=param_shape, dtype=dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="layer_norm", inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [var_out]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def rms_norm(input, begin_norm_axis=-1, epsilon=1e-5, param_attr=None,
             name=None, zero_centered=False, gate=None,
             begin_scale_axis=None):
    """Root-mean-square norm over the axes from begin_norm_axis on, with a
    learned scale (initialised to 1) and no shift: scale * x /
    sqrt(mean(x^2) + epsilon), accumulated in float32 (ops/nn_ops.py).
    zero_centered: the weight is stored around 0 (initialised to 0) and the
    result is (1 + scale) * x_hat. gate: a Variable of input's shape, the
    result is scale * x_hat * silu(gate) (a gated norm). begin_scale_axis:
    an axis before begin_norm_axis from which the scale has an element of
    its own (a norm a group under one weight over all groups: input [..,
    G, C / G], begin_norm_axis -1, begin_scale_axis -2); None: the axes
    normed over."""
    helper = LayerHelper("rms_norm", **locals())
    dtype = helper.input_dtype()
    begin = begin_norm_axis % len(input.shape)
    scaled = begin if begin_scale_axis is None \
        else begin_scale_axis % len(input.shape)
    if scaled > begin:
        raise ValueError("rms_norm: begin_scale_axis %d lies behind "
                         "begin_norm_axis %d" % (scaled, begin))
    scale = helper.create_parameter(
        attr=helper.param_attr,
        shape=[int(np.prod(input.shape[scaled:]))], dtype=dtype,
        default_initializer=ConstantInitializer(
            0.0 if zero_centered else 1.0))
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [input], "Scale": [scale]}
    attrs = {"epsilon": float(epsilon), "begin_norm_axis": begin}
    # what the defaults leave as it was is not written
    if zero_centered:
        attrs["zero_centered"] = True
    if scaled != begin:
        attrs["begin_scale_axis"] = scaled
    if gate is not None:
        inputs["Gate"] = [gate]
    helper.append_op(type="rms_norm", inputs=inputs, outputs={"Y": [out]},
                     attrs=attrs)
    return out


def rotary_embedding(x, pos, base=10000.0, name=None, rotary_dim=None,
                     inv_freq=None, table_scale=1.0, layout="half"):
    """Rotary position embedding of x [B, T, H, D] at the integer positions
    pos [B, T], a Variable (fed or computed), so that a decode step can pass
    its own. Half-split pairs (i, i + R/2) over the first R = rotary_dim
    channels of every head (None: all D), angle pos * base^(-2i/R); the
    channels from R on pass unchanged. inv_freq: R/2 floats, a frequency
    table made outside (a scaled one: YaRN's), which replaces base^(-2i/R);
    table_scale multiplies cos and sin. layout "interleaved": the pairs are
    (2i, 2i + 1); "half", the default, is what every caller before the
    latent form uses."""
    if layout not in ("half", "interleaved"):
        raise ValueError("rotary_embedding layout must be 'half' or "
                         "'interleaved', got %r" % (layout,))
    helper = LayerHelper("rotary_embedding", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"base": float(base)}
    if rotary_dim is not None and int(rotary_dim) != int(x.shape[-1]):
        attrs["rotary_dim"] = int(rotary_dim)
    # what the defaults leave as it was is not written
    if inv_freq is not None:
        attrs["inv_freq"] = [float(f) for f in inv_freq]
    if float(table_scale) != 1.0:
        attrs["table_scale"] = float(table_scale)
    if layout != "half":
        attrs["layout"] = layout
    helper.append_op(
        type="rotary_embedding", inputs={"X": [x], "Pos": [pos]},
        outputs={"Out": [out]}, attrs=attrs)
    return out


def causal_conv1d(input, kernel_size, act=None, param_attr=None, name=None):
    """A causal depthwise convolution over time without bias: input [B, T,
    C], one filter of `kernel_size` taps a channel (the parameter is [C,
    kernel_size]), y_t[c] = sum_m w[c, m] x_(t - kernel_size + 1 + m)[c]
    with zeros before the sequence; act None or "silu", applied in the same
    op (ops/linear_attention_ops.py); any other act is refused. The op takes
    no gate: a gated short convolution (models/causal_lm.py:short_conv, C *
    conv(B * u)) is two elementwise multiplies around it, which on the v5e
    cost 1.15 times one fused pass over the operands at [1, 8192, 2048]
    (PERF.md section 6, PR 39), so the kernels were left as they are."""
    if act not in (None, "silu"):
        raise ValueError("causal_conv1d act must be None or 'silu', got %r"
                         % (act,))
    helper = LayerHelper("causal_conv1d", input=input, param_attr=param_attr,
                         name=name)
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[int(input.shape[-1]), int(kernel_size)], dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="causal_conv1d", inputs={"X": [input], "Filter": [w]},
        outputs={"Out": [out]},
        attrs={"activation": act} if act else {})
    return out


def gated_delta_rule(q, k, v, g, beta, name=None):
    """The gated delta rule, a linear-attention mixer with a [dk, dv] state
    a value head (ops/gated_delta_kernels.py): q, k [B, T, Hk, dk], v [B,
    T, Hv, dv] with Hk dividing Hv (key head j serves value heads j * Hv /
    Hk on), g [B, T, Hv] the log decay (<= 0) and beta [B, T, Hv] the write
    strength -> [B, T, Hv, dv]. Per head, from S_0 = 0:
    S' = exp(g_t) S_(t-1); S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S_t^T q_t, computed in chunks. q and k are l2-normalised over dk
    first (1e-6 inside the root) and q multiplied by dk^-0.5."""
    helper = LayerHelper("gated_delta_rule", name=name)
    out = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op(
        type="gated_delta_rule",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
        outputs={"Out": [out]}, attrs={})
    return out


def kda_delta_rule(q, k, v, g, beta, name=None):
    """The delta rule whose decay is a key CHANNEL's (Kimi Delta Attention,
    arXiv:2510.26692; ops/kda_kernels.py): q, k [B, T, H, dk], v [B, T, H,
    dv], g [B, T, H, dk] the log decay a channel (in (-5.9, 0] a token: the
    chunked form's exponentials are finite under that bound and no other)
    and beta [B, T, H] the write strength -> [B, T, H, dv]. Per head, from
    S_0 = 0: S' = Diag(exp(g_t)) S_(t-1); S_t = S' + beta_t k_t (v_t - S'^T
    k_t)^T; o_t = S_t^T q_t, computed in chunks. q and k are l2-normalised
    over dk first (1e-6 inside the root) and q multiplied by dk^-0.5."""
    helper = LayerHelper("kda_delta_rule", name=name)
    out = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op(
        type="kda_delta_rule",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
        outputs={"Out": [out]}, attrs={})
    return out


def selective_scan(x, delta, a, b, c, d, name=None):
    """A Mamba mixer's selective scan (ops/selective_scan_kernels.py): x,
    delta [B, T, C] (delta > 0, after its softplus), a [C, N] (negative), b,
    c [B, T, N] and d [C] -> [B, T, C] in x's dtype. A channel from s = 0:
    s_t[n] = exp(delta_t a[n]) s_(t-1)[n] + delta_t b_t[n] x_t; y_t = sum_n
    c_t[n] s_t[n] + d x_t, in float32."""
    helper = LayerHelper("selective_scan", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="selective_scan",
        inputs={"X": [x], "Delta": [delta], "A": [a], "B": [b], "C": [c],
                "D": [d]},
        outputs={"Out": [out]}, attrs={})
    return out


def ssd_scan(x, delta, a, b, c, d, name=None):
    """A Mamba-2 mixer's state-space-dual scan (ops/ssd_kernels.py): x [B,
    T, H, P], delta [B, T, H] (> 0, after its softplus), a (negative) and d
    [H], and b, c [B, T, N], one group that every head reads, or [B, T, G,
    N], head h reading group h // (H / G) -> [B, T, H, P]
    in x's dtype. A head from s = 0, s [N, P]: s_t = exp(delta_t a) s_(t-1)
    + b_t^T (delta_t x_t); y_t = c_t s_t + d x_t, computed a chunk of
    tokens at a time as matmuls."""
    helper = LayerHelper("ssd_scan", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="ssd_scan",
        inputs={"X": [x], "Delta": [delta], "A": [a], "B": [b], "C": [c],
                "D": [d]},
        outputs={"Out": [out]}, attrs={})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None):
    """Parity: fluid.layers.dropout (Mask output, downgrade_in_infer)."""
    helper = LayerHelper("dropout", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "seed": seed if seed is not None else 0})
    return out


def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper("softmax", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [out]})
    return out


def relu(x, name=None):
    helper = LayerHelper("relu", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="relu", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def log(x, name=None):
    helper = LayerHelper("log", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="log", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def cross_entropy(input, label, soft_label=False):
    """Parity: fluid.layers.cross_entropy (input = probabilities)."""
    helper = LayerHelper("cross_entropy", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False):
    """Parity: fluid.layers.softmax_with_cross_entropy (fused, numerically
    stable; single XLA fusion on TPU)."""
    helper = LayerHelper("softmax_with_cross_entropy", **locals())
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={"soft_label": soft_label})
    return loss


def square_error_cost(input, label):
    """Parity: fluid.layers.square_error_cost."""
    helper = LayerHelper("square_error_cost", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="square_error_cost",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out]})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1", **locals())
    diff = helper.create_variable_for_type_inference(x.dtype)
    loss = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        type="smooth_l1_loss", inputs=inputs,
        outputs={"Diff": [diff], "Out": [loss]},
        attrs={"sigma": sigma if sigma is not None else 1.0})
    return loss


def accuracy(input, label, k=1, correct=None, total=None):
    """Parity: fluid.layers.accuracy (emits topk + accuracy ops)."""
    helper = LayerHelper("accuracy", **locals())
    topk_out = helper.create_variable_for_type_inference(input.dtype)
    topk_indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="topk", inputs={"X": [input]},
        outputs={"Out": [topk_out], "Indices": [topk_indices]},
        attrs={"k": k})
    acc_out = helper.create_variable_for_type_inference("float32")
    if correct is None:
        correct = helper.create_variable_for_type_inference("int32")
    if total is None:
        total = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="accuracy",
        inputs={"Out": [topk_out], "Indices": [topk_indices],
                "Label": [label]},
        outputs={"Accuracy": [acc_out], "Correct": [correct],
                 "Total": [total]})
    return acc_out


def topk(input, k):
    helper = LayerHelper("topk", **locals())
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="topk", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    return values, indices


def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    helper = LayerHelper("matmul", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="matmul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y})
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot", **locals())
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="one_hot", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"depth": depth})
    return out


def _reduce_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, **locals())
        out = helper.create_variable_for_type_inference(input.dtype)
        attrs = {"keep_dim": keep_dim,
                 "reduce_all": dim is None,
                 "dim": dim if dim is not None else 0}
        helper.append_op(type=op_type, inputs={"X": [input]},
                         outputs={"Out": [out]}, attrs=attrs)
        return out
    layer.__name__ = op_type
    return layer


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")
reduce_prod = _reduce_layer("reduce_prod")


def split(input, num_or_sections, dim=-1, name=None):
    """Parity: fluid.layers.split."""
    helper = LayerHelper("split", **locals())
    input_shape = input.shape
    dim = dim if dim >= 0 else dim + len(input_shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        attrs = {"num": num, "sections": [], "axis": dim}
    else:
        num = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": dim}
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(num)]
    helper.append_op(type="split", inputs={"X": [input]},
                     outputs={"Out": outs}, attrs=attrs)
    return outs


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="l2_normalize", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim", **locals())
    out = helper.create_variable_for_type_inference(X.dtype)
    xnorm = helper.create_variable_for_type_inference(X.dtype)
    ynorm = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xnorm],
                              "YNorm": [ynorm]})
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="transpose", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"axis": list(perm)})
    return out


def multiplex(inputs, index):
    helper = LayerHelper("multiplex", **locals())
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(type="multiplex",
                     inputs={"X": inputs, "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"epsilon": float(epsilon)})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="maxout", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"groups": groups})
    return out


def nce(input, label, num_total_classes, sample_weight=None, param_attr=None,
        bias_attr=None, num_neg_samples=None, name=None):
    """Parity: fluid.layers.nce."""
    helper = LayerHelper("nce", **locals())
    dim = input.shape[1]
    num_neg_samples = 10 if num_neg_samples is None else int(num_neg_samples)
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[num_total_classes, dim],
        dtype=input.dtype, is_bias=False)
    b = helper.create_parameter(
        attr=helper.bias_attr, shape=[num_total_classes, 1],
        dtype=input.dtype, is_bias=True)
    cost = helper.create_variable_for_type_inference(input.dtype)
    sample_logits = helper.create_variable_for_type_inference(input.dtype)
    sample_labels = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="nce",
        inputs={"Input": [input], "Label": [label], "Weight": [w],
                "Bias": [b]},
        outputs={"Cost": [cost], "SampleLogits": [sample_logits],
                 "SampleLabels": [sample_labels]},
        attrs={"num_total_classes": int(num_total_classes),
               "num_neg_samples": num_neg_samples})
    return cost / (num_neg_samples + 1)


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    """Parity: fluid.layers.im2sequence (OCR path). Output is a sequence:
    one timestep per output pixel, feature = C*kh*kw patch."""
    helper = LayerHelper("im2sequence", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    out_len = helper.block.create_var(
        name=out.name + "@SEQLEN", shape=[-1], dtype="int32",
        stop_gradient=True)
    helper.append_op(
        type="im2sequence", inputs={"X": [input]},
        outputs={"Out": [out], "OutLen": [out_len]},
        attrs={"kernels": list(_pair(filter_size)),
               "strides": list(_pair(stride)),
               "paddings": list(_pair(padding)) * 2})
    out.lod_level = 1
    out.seq_len_var = out_len.name
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Parity: fluid.layers.autoincreased_step_counter — a persistable int64
    counter incremented once per run; drives LR schedules."""
    helper = LayerHelper("global_step_counter")
    counter_name = counter_name or "@STEP_COUNTER@"
    counter = helper.create_or_get_global_variable(
        name=counter_name, dtype="int64", shape=[1], persistable=True)
    if counter.op is None:
        helper.set_variable_initializer(
            counter, initializer=ConstantInitializer(value=begin - 1))
        counter.op = helper.main_program.global_block().prepend_op(
            type="increment",
            inputs={"X": [counter]},
            outputs={"Out": [counter]},
            attrs={"step": float(step)},
            infer_shape=False)
        counter.stop_gradient = True
    return counter




def fused_attention(q, k, v, causal=False, scale=None, kv_len=None,
                    sp_impl="ring", name=None, window=None, q_rope=None,
                    k_rope=None, block_diffusion=None):
    """Flash attention over q [B, T, Hq, D] and k, v [B, T, Hkv, D]
    (TPU-native addition — the reference era built attention from
    matmul+softmax ops; this is the fused pallas path, see
    ops/pallas_kernels.py). Grouped queries come from the shapes: Hkv
    divides Hq and query head h reads key/value head h // (Hq // Hkv).
    window: None or an int, query i sees key j only where i - j < window
    (with causal, the `window` newest keys up to itself). kv_len: optional
    [B] int32 Variable of true key lengths (padded-batch masking + block
    skipping); defaults to k's sequence-lengths companion when k is a
    lod_level>0 sequence. The sequence-parallel paths take neither a
    window nor grouped queries and raise NotImplementedError. Under a ParallelExecutor mesh with an 'sp'
    axis the op runs sequence-parallel; sp_impl chooses the algorithm:
    "ring" (K/V rotation over ICI, any head count) or "ulysses"
    (all-to-all head sharding, needs heads % sp == 0).

    The latent form: q_rope [B, T, Hq, dr] and k_rope [B, T, 1, dr], given
    together: a head's score is q . k + q_rope . k_rope, so its keys are D
    + dr wide, dr of them one rotary key that all heads read, and its
    values D (latent attention's head of 192 on values of 128) or, v [B, T,
    Hkv, dv], another width (192 + 64 on values of 256: the result is then
    [B, T, Hq, dv]; ops/pallas_kernels.py latent_form has the form the
    kernels run it in). `scale` defaults to 1 / sqrt(D + dr).

    block_diffusion: None, or (block_length, L): the T = 2 L rows are two
    copies of one sequence of L tokens, the NOISED copy in rows 0 .. L - 1
    and the CLEAN copy in rows L .. 2 L - 1 (block diffusion's training
    mask, BD3-LM, arXiv:2503.09573), block_length dividing L. Row r is
    (copy, position i, block b = i // block_length) and sees row s iff:
    both are noised and b_s = b_r (block-diagonal, both directions inside a
    block); or r is noised, s clean and b_s < b_r (offset block-causal); or
    both are clean and b_s <= b_r (block-causal); a clean row never sees a
    noised one. It is the whole mask: causal, window, kv_len, the latent
    form and the sequence-parallel paths are refused beside it. The attr is
    written only where it is asked for."""
    if (q_rope is None) != (k_rope is None):
        raise ValueError("fused_attention takes q_rope and k_rope together")
    if sp_impl not in ("ring", "ulysses"):
        raise ValueError(
            "fused_attention sp_impl must be 'ring' or 'ulysses', got %r"
            % (sp_impl,))
    if window is not None and int(window) < 1:
        raise ValueError("fused_attention window must be None or >= 1, got "
                         "%r" % (window,))
    if block_diffusion is not None:
        block_diffusion = [int(n) for n in block_diffusion]
        length, copy = block_diffusion
        if causal or window is not None or kv_len is not None \
                or q_rope is not None:
            raise ValueError(
                "fused_attention block_diffusion is the whole mask: causal, "
                "window, kv_len and the latent form are refused beside it")
        if length < 1 or copy % length or (
                q.shape[1] not in (-1, None) and q.shape[1] != 2 * copy):
            raise ValueError(
                "fused_attention block_diffusion (block_length, L) takes T "
                "= 2 L rows in whole blocks, got %r on q %r"
                % (block_diffusion, tuple(q.shape)))
    helper = LayerHelper("fused_attention", **locals())
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if q_rope is not None:
        inputs.update(QRope=[q_rope], KRope=[k_rope])
    if kv_len is None and getattr(k, "seq_len_var", None):
        kv_len = k.block.var_recursive(k.seq_len_var)
    if kv_len is not None:
        inputs["KVLen"] = [kv_len]
    attrs = {"causal": bool(causal),
             "scale": None if scale is None else float(scale),
             "sp_impl": str(sp_impl)}
    if window is not None:
        attrs["window"] = int(window)
    if block_diffusion is not None:
        attrs["block_diffusion"] = block_diffusion
    helper.append_op(
        type="fused_attention", inputs=inputs,
        outputs={"Out": [out]}, attrs=attrs)
    return out


def mhc_pre(x, streams, sinkhorn_iters=20, epsilon=1e-6, clamp=(-30.0, 30.0),
            phi_attr=None, bias_attr=None, alpha_attr=None, name=None):
    """A sub-layer's read from `streams` residual streams x [B, T, streams *
    C], a token's vectors side by side (manifold-constrained
    hyper-connections, ops/mhc_kernels.py): returns (h [B, T, C] =
    sum_i H_pre[i] x[i], coef [B, T, 128] float32, the stream for `mhc_post`
    to read). The coefficients are made from the token's own streams: x' =
    x / sqrt(mean(x^2) + epsilon) over all streams * C, Ht = alpha * (x'
    Phi) + b; H_pre = sigmoid, H_post = 2 sigmoid, H_res = `sinkhorn_iters`
    Sinkhorn steps (columns, then rows, epsilon in both divisors) on
    exp(clip(Ht, *clamp)). Three parameters, float32: Phi [streams * C, K],
    b [K] and alpha [3] (pre, post, res), K = streams^2 + 2 streams, columns
    [pre | post | res by rows]. Defaults: Phi normal(0, 0.02), alpha 0.01,
    b_pre = -log(streams - 1) (H_pre = 1 / streams), b_post = 0 (H_post =
    1), b_res = 0."""
    helper = LayerHelper("mhc_pre", **locals())
    n = int(streams)
    k = n * n + 2 * n
    width = int(x.shape[-1])
    if width % n:
        raise ValueError("mhc_pre: a stream %d wide is not %d vectors"
                         % (width, n))
    bias0 = np.zeros([k], "float32")
    bias0[:n] = -np.log(max(n - 1, 1))
    from ..core.initializer import NumpyArrayInitializer
    phi = helper.create_parameter(
        attr=ParamAttr.to_attr(phi_attr), shape=[width, k], dtype="float32",
        default_initializer=NormalInitializer(0.0, 0.02))
    bias = helper.create_parameter(
        attr=ParamAttr.to_attr(bias_attr), shape=[k], dtype="float32",
        default_initializer=NumpyArrayInitializer(bias0))
    alpha = helper.create_parameter(
        attr=ParamAttr.to_attr(alpha_attr), shape=[3], dtype="float32",
        default_initializer=ConstantInitializer(0.01))
    out = helper.create_variable_for_type_inference(x.dtype)
    coef = helper.create_variable_for_type_inference("float32")
    stream = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="mhc_pre",
        inputs={"X": [x], "Phi": [phi], "Bias": [bias], "Alpha": [alpha]},
        outputs={"Out": [out], "Coef": [coef], "Stream": [stream]},
        attrs={"streams": n, "sinkhorn_iters": int(sinkhorn_iters),
               "epsilon": float(epsilon), "clamp_min": float(clamp[0]),
               "clamp_max": float(clamp[1])})
    return out, coef, stream


def mhc_post(x, y, coef, streams, name=None):
    """out[i] = sum_j H_res[i, j] x[j] + H_post[i] y: the streams x [B, T,
    streams * C] (mhc_pre's third result) mixed and the sub-layer's output y
    [B, T, C] written into them, by mhc_pre's `coef`."""
    helper = LayerHelper("mhc_post", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mhc_post",
                     inputs={"X": [x], "Y": [y], "Coef": [coef]},
                     outputs={"Out": [out]}, attrs={"streams": int(streams)})
    return out


def mhc_expand(x, streams, name=None):
    """x [B, T, C] -> [B, T, streams * C]: `streams` copies side by side,
    the residual streams' start (bfloat16 under AMP)."""
    helper = LayerHelper("mhc_expand", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mhc_expand", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"streams": int(streams)})
    return out


def mhc_reduce(x, streams, name=None):
    """x [B, T, streams * C] -> [B, T, C], the streams' sum: the readout."""
    helper = LayerHelper("mhc_reduce", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mhc_reduce", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"streams": int(streams)})
    return out


def expand(x, expand_times, name=None):
    """Tile x along each dim. Parity: fluid.layers.expand / expand_op.cc."""
    helper = LayerHelper("expand", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="expand", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"expand_times": list(expand_times)})
    return out


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    """[N] lengths -> [N, maxlen] 0/1 mask. Parity: fluid.layers.sequence_mask
    / sequence_mask_op.h. `maxlen` may be an int or a Variable whose dim 1
    supplies the static length (TPU needs a static bound)."""
    helper = LayerHelper("sequence_mask", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [x]}
    attrs = {"out_dtype": dtype}
    if isinstance(maxlen, Variable):
        inputs["MaxLenRef"] = [maxlen]
    elif maxlen is not None:
        attrs["maxlen"] = int(maxlen)
    else:
        raise ValueError("TPU sequence_mask needs a static maxlen (int or a "
                         "Variable whose second dim provides it)")
    helper.append_op(type="sequence_mask", inputs=inputs,
                     outputs={"Y": [out]}, attrs=attrs, infer_shape=False)
    if isinstance(maxlen, Variable):
        m = maxlen.shape[1] if maxlen.shape is not None else -1
    else:
        m = int(maxlen)
    if x.shape is not None:
        out.shape = (x.shape[0], m)
    out.stop_gradient = True
    return out


def _crf_seq_len(helper, x):
    from .sequence import _seq_len
    return _seq_len(helper, x)


def linear_chain_crf(input, label, param_attr=None):
    """Linear-chain CRF negative log-likelihood, one cost per sequence.

    Parity: fluid.layers.linear_chain_crf (reference nn.py:786) over
    linear_chain_crf_op.h. Creates the [size+2, size] transition parameter
    (row 0 start, row 1 end, rows 2.. tag->tag); returns LogLikelihood
    [num_seqs, 1]. The reference's Alpha/EmissionExps/TransitionExps
    outputs existed only to feed the hand-written grad kernel and have no
    equivalent here (jax.vjp re-derives the backward pass).
    """
    helper = LayerHelper("linear_chain_crf", **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size],
        dtype=helper.input_dtype())
    ll = helper.create_variable_for_type_inference(helper.input_dtype())
    helper.append_op(
        type="linear_chain_crf",
        inputs={"Emission": [input], "Transition": [transition],
                "Label": [label], "XLen": [_crf_seq_len(helper, input)]},
        outputs={"LogLikelihood": [ll]})
    ll.lod_level = 0
    ll.seq_len_var = None
    ll.shape = (-1, 1)
    return ll


def crf_decoding(input, param_attr, label=None):
    """Viterbi decode with the trained CRF transitions.

    Parity: fluid.layers.crf_decoding (reference nn.py:812) over
    crf_decoding_op.h. Without label: the best tag path (sequence, int64).
    With label: per-token 1/0 correctness indicators.
    """
    helper = LayerHelper("crf_decoding", **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size],
        dtype=helper.input_dtype())
    path = helper.create_variable_for_type_inference("int64")
    inputs = {"Emission": [input], "Transition": [transition],
              "XLen": [_crf_seq_len(helper, input)]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [path]})
    path.stop_gradient = True
    return path


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    """Chunk-level precision/recall/F1 (IOB/IOE/IOBES/plain schemes).

    Parity: fluid.layers.chunk_eval (reference nn.py:1014) over
    chunk_eval_op.h; label encodes (chunk_type, tag) as
    chunk_type * num_tag_types + tag.
    """
    helper = LayerHelper("chunk_eval", **locals())
    precision = helper.create_variable_for_type_inference("float32")
    recall = helper.create_variable_for_type_inference("float32")
    f1_score = helper.create_variable_for_type_inference("float32")
    num_infer = helper.create_variable_for_type_inference("int64")
    num_label = helper.create_variable_for_type_inference("int64")
    num_correct = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="chunk_eval",
        inputs={"Inference": [input], "Label": [label],
                "XLen": [_crf_seq_len(helper, input)]},
        outputs={"Precision": [precision], "Recall": [recall],
                 "F1-Score": [f1_score], "NumInferChunks": [num_infer],
                 "NumLabelChunks": [num_label],
                 "NumCorrectChunks": [num_correct]},
        attrs={"num_chunk_types": num_chunk_types,
               "chunk_scheme": chunk_scheme,
               "excluded_chunk_types": excluded_chunk_types or []})
    for v in (precision, recall, f1_score, num_infer, num_label, num_correct):
        v.lod_level = 0
        v.seq_len_var = None
        v.shape = (1,)
        v.stop_gradient = True
    return (precision, recall, f1_score, num_infer, num_label, num_correct)


def warpctc(input, label, blank=0, norm_by_times=False):
    """CTC loss on unnormalized logit sequences, one loss per sequence.

    Parity: fluid.layers.warpctc (reference nn.py:2620) over warpctc_op;
    the warp-ctc library's internal softmax is part of the op. Returns
    Loss [num_seqs, 1].
    """
    helper = LayerHelper("warpctc", **locals())
    loss_out = helper.create_variable_for_type_inference(input.dtype)
    grad_out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="warpctc",
        inputs={"Logits": [input], "Label": [label],
                "XLen": [_crf_seq_len(helper, input)],
                "LabelLen": [_crf_seq_len(helper, label)]},
        outputs={"Loss": [loss_out], "WarpCTCGrad": [grad_out]},
        attrs={"blank": blank, "norm_by_times": norm_by_times})
    loss_out.lod_level = 0
    loss_out.seq_len_var = None
    loss_out.shape = (-1, 1)
    return loss_out


def _erase_or_align_out(helper, op_type, inputs, attrs, dtype="int64"):
    """Emit an op that compacts sequences (new data + new lengths)."""
    out = helper.create_variable_for_type_inference(dtype)
    out_len = helper.block.create_var(
        name=out.name + "@SEQLEN", shape=[-1], dtype="int32",
        stop_gradient=True)
    out_slot = "Output" if op_type == "ctc_align" else "Out"
    helper.append_op(
        type=op_type, inputs=inputs,
        outputs={out_slot: [out], "OutLen": [out_len]}, attrs=attrs,
        infer_shape=False)
    out.lod_level = 1
    out.seq_len_var = out_len.name
    out.stop_gradient = True
    return out


def ctc_greedy_decoder(input, blank, name=None):
    """Greedy CTC decode: argmax per step, merge repeats, drop blanks.

    Parity: fluid.layers.ctc_greedy_decoder (reference nn.py:2478):
    top_k(k=1) + ctc_align(merge_repeated=True).
    """
    helper = LayerHelper("ctc_greedy_decoder", **locals())
    topk_out = helper.create_variable_for_type_inference(input.dtype)
    topk_indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="topk", inputs={"X": [input]},
        outputs={"Out": [topk_out], "Indices": [topk_indices]},
        attrs={"k": 1})
    out = _erase_or_align_out(
        helper, "ctc_align",
        {"Input": [topk_indices], "XLen": [_crf_seq_len(helper, input)]},
        {"merge_repeated": True, "blank": blank})
    if input.shape is not None:
        out.shape = (input.shape[0], input.shape[1])
    return out


def sequence_erase(input, tokens):
    """Remove the given token ids from each sequence (compacting it).

    Parity: sequence_erase_op (used by edit_distance's ignored_tokens)."""
    helper = LayerHelper("sequence_erase", **locals())
    out = _erase_or_align_out(
        helper, "sequence_erase",
        {"X": [input], "XLen": [_crf_seq_len(helper, input)]},
        {"tokens": list(tokens)})
    if input.shape is not None:
        out.shape = tuple(input.shape[:2])
    return out


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  name=None):
    """Levenshtein distance between hypothesis and reference sequences.

    Parity: fluid.layers.edit_distance (reference nn.py:2532). Returns
    (distances [num_seqs, 1] float32, sequence_num [1] int64).
    """
    helper = LayerHelper("edit_distance", **locals())
    if ignored_tokens:
        input = sequence_erase(input, ignored_tokens)
        label = sequence_erase(label, ignored_tokens)
    out = helper.create_variable_for_type_inference("float32")
    seq_num = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="edit_distance",
        inputs={"Hyps": [input], "Refs": [label],
                "HypsLen": [_crf_seq_len(helper, input)],
                "RefsLen": [_crf_seq_len(helper, label)]},
        outputs={"Out": [out], "SequenceNum": [seq_num]},
        attrs={"normalized": normalized})
    for v in (out, seq_num):
        v.lod_level = 0
        v.seq_len_var = None
        v.stop_gradient = True
    out.shape = (-1, 1)
    seq_num.shape = (1,)
    return out, seq_num
