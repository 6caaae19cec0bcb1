"""NN op lowerings: conv, pool, norms, losses, embedding, dropout.

Parity: paddle/fluid/operators/{conv_op,conv_cudnn_op,conv_transpose_op,
pool_op,batch_norm_op,layer_norm_op,dropout_op,softmax_op,cross_entropy_op,
softmax_with_cross_entropy_op,sigmoid_cross_entropy_with_logits_op,
lookup_table_op,accuracy_op,smooth_l1_loss_op,log_loss_op,huber_loss_op,
lrn_op,maxout_op,label_smooth_op,nce_op}.{cc,cu,h}.

TPU notes: convs/matmuls keep fluid's NCHW layout at the IR level — XLA's TPU
layout assignment transposes to the MXU-friendly layout internally, so parity
of semantics costs nothing. bf16 convs run bf16-in/bf16-out and rely on the
TPU MXU's internal f32 accumulate (an explicit preferred_element_type breaks
conv's grad rule); mul/matmul request f32 accumulation explicitly.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import (counts, register, set_like, shapes_from,
                             single)
from ..core.utils import pair as _pair
from ..observability.registry import REGISTRY
from .kernel_config import flash_at, pallas_on


def _out(x):
    return {"Out": [x]}


def _conv_layout():
    """FLAGS_conv_layout=NHWC runs the conv/pool family in channels-last
    compute layout (boundary transposes around each op; XLA folds
    adjacent pairs). The fluid-facing contract stays NCHW — this is the
    internal MXU layout knob the perf sweep probes (round-2 verdict
    missing #4). Read at trace time: set it before the first run of a
    program (the jit cache keys on the program, not the flag)."""
    import os
    layout = os.environ.get("FLAGS_conv_layout", "NCHW").upper()
    if layout not in ("NCHW", "NHWC"):
        raise ValueError(
            "FLAGS_conv_layout=%r: expected NCHW or NHWC (a typo here "
            "would otherwise silently run the NCHW path)" % layout)
    return layout


# ---------------------------------------------------------------------------
# convolution family (MXU)
# ---------------------------------------------------------------------------

@register("conv2d")
def _conv2d(ctx, ins, attrs):
    x = single(ins, "Input")    # NCHW
    w = single(ins, "Filter")   # OIHW
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    pad2 = [(pads[0], pads[0]), (pads[1], pads[1])]
    # bf16 operands stay bf16 end-to-end: the TPU MXU accumulates in f32
    # internally, and conv's transpose (grad) rule rejects the
    # preferred_element_type + downcast pattern (f32 cotangent meets bf16
    # filter), so an explicit f32 accumulate would break training.
    if _conv_layout() == "NHWC":
        out = lax.conv_general_dilated(
            jnp.transpose(x, (0, 2, 3, 1)),
            jnp.transpose(w, (2, 3, 1, 0)),
            window_strides=strides, padding=pad2, rhs_dilation=dil,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)
        out = jnp.transpose(out, (0, 3, 1, 2))
    else:
        out = lax.conv_general_dilated(
            x, w,
            window_strides=strides, padding=pad2, rhs_dilation=dil,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups)
    return {"Output": [out.astype(x.dtype)]}


@register("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    return _conv2d(ctx, ins, attrs)


@register("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x = single(ins, "Input")    # NCHW
    w = single(ins, "Filter")   # IOHW in fluid transpose conv
    if int(attrs.get("groups", 1) or 1) != 1:
        # era parity: conv_transpose_op.cc:101 "We enforce groups number
        # == 1" — silently ignoring the attr would compute wrong results
        raise ValueError(
            "conv2d_transpose: groups != 1 is not supported (the "
            "reference enforces groups == 1 for transposed convolution)")
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    # Fluid's filter layout [C_in, C_out, kh, kw] is exactly the OIHW layout
    # of the FORWARD conv this op is the input-gradient of (the transpose
    # maps the forward conv's O channels back to its I channels), so declare
    # it "OIHW" and let transpose_kernel swap I/O + flip the taps. And
    # fluid's `paddings` attr is the FORWARD conv's padding: on the
    # stride-dilated input the gradient conv pads (effective_k - 1 - pad)
    # per side, giving the reference output size (H-1)*stride + k - 2*pad.
    eff = [(w.shape[2] - 1) * dil[0] + 1, (w.shape[3] - 1) * dil[1] + 1]
    out = lax.conv_transpose(
        x, w,
        strides=strides,
        padding=[(eff[0] - 1 - pads[0], eff[0] - 1 - pads[0]),
                 (eff[1] - 1 - pads[1], eff[1] - 1 - pads[1])],
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        transpose_kernel=True)
    return {"Output": [out.astype(x.dtype)]}


# ---------------------------------------------------------------------------
# pooling (reference: pool_op.cc; cuDNN pooling → lax.reduce_window)
# ---------------------------------------------------------------------------

@register("pool2d")
def _pool2d(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    if attrs.get("global_pooling"):
        ksize = (x.shape[2], x.shape[3])
        pads = (0, 0)
        strides = (1, 1)
    # ceil_mode rounds the output size UP; realized as extra trailing
    # padding so reduce_window emits ceil((H - k + 2p)/s) + 1 positions
    # (pool_op.cc ceil_mode attr; the extra rows never enter an avg count)
    extra = [0, 0]
    if attrs.get("ceil_mode", False):
        for d, hw in enumerate((x.shape[2], x.shape[3])):
            span = hw - ksize[d] + 2 * pads[d]
            out_ceil = -(-span // strides[d]) + 1
            extra[d] = max(0, (out_ceil - 1) * strides[d] - span)
    nhwc = _conv_layout() == "NHWC"
    if nhwc:  # channels-last compute layout, same knob as conv2d
        x = jnp.transpose(x, (0, 2, 3, 1))
        window = (1,) + ksize + (1,)
        strides4 = (1,) + strides + (1,)
        padding = ((0, 0), (pads[0], pads[0] + extra[0]),
                   (pads[1], pads[1] + extra[1]), (0, 0))
    else:
        window = (1, 1) + ksize
        strides4 = (1, 1) + strides
        padding = ((0, 0), (0, 0), (pads[0], pads[0] + extra[0]),
                   (pads[1], pads[1] + extra[1]))
    if ptype == "max":
        init = -jnp.inf
        out = lax.reduce_window(x, init, lax.max, window, strides4, padding)
    else:
        s = lax.reduce_window(x, 0.0, lax.add, window, strides4, padding)
        if attrs.get("exclusive", True) and (pads[0] or pads[1] or
                                             extra[0] or extra[1]):
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides4, padding)
            # a ceil-mode window can sit fully inside padding (count 0);
            # emit 0 there, not 0/0
            out = s / jnp.maximum(cnt, 1.0)
        else:
            out = s / float(ksize[0] * ksize[1])
    if nhwc:
        out = jnp.transpose(out, (0, 3, 1, 2))
    return _out(out.astype(x.dtype))


@register("maxout")
def _maxout(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW
    g = attrs["groups"]
    n, c, h, w = x.shape
    return _out(jnp.max(x.reshape(n, c // g, g, h, w), axis=2))


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

# Mean and Variance are the running statistics, [C] float32 as the layer
# makes them; the batch's own (train mode) have their shape and dtype
@register("batch_norm", infer=shapes_from(
    Y="X", MeanOut="Mean", VarianceOut="Variance", SavedMean="Mean",
    SavedVariance="Variance"))
def _batch_norm(ctx, ins, attrs):
    x = single(ins, "X")          # NCHW or NC
    scale = single(ins, "Scale")  # [C]
    bias = single(ins, "Bias")
    mean = single(ins, "Mean")      # moving mean (persistable)
    var = single(ins, "Variance")   # moving variance (persistable)
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    layout = attrs.get("data_layout", "NCHW")

    axes = tuple(i for i in range(x.ndim)
                 if i != (1 if layout == "NCHW" and x.ndim > 2 else x.ndim - 1))
    caxis = 1 if (layout == "NCHW" and x.ndim > 2) else x.ndim - 1
    bshape = [1] * x.ndim
    bshape[caxis] = x.shape[caxis]

    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = mean
        saved_var = var
    else:
        xf = x.astype(jnp.float32)
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.var(xf, axis=axes)
        # moving averages updated OUTSIDE the grad path
        use_mean_s = lax.stop_gradient(use_mean)
        use_var_s = lax.stop_gradient(use_var)
        mean_out = momentum * mean + (1 - momentum) * use_mean_s
        var_out = momentum * var + (1 - momentum) * use_var_s
        saved_mean = use_mean
        saved_var = use_var

    inv = lax.rsqrt(use_var.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - use_mean.reshape(bshape)) * inv.reshape(bshape)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    return {"Y": [y.astype(x.dtype)],
            "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [saved_mean], "SavedVariance": [saved_var]}


def _layer_norm_rows(shape, attrs):
    return (int(np.prod(shape[:attrs.get("begin_norm_axis", 1)])),)


@register("layer_norm", calls_pallas=True, infer=shapes_from(
    Y="X", Mean=("X", _layer_norm_rows, "float32"),
    Variance=("X", _layer_norm_rows, "float32")))
def _layer_norm(ctx, ins, attrs):
    x = single(ins, "X")
    scale = single(ins, "Scale")
    bias = single(ins, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    lead = int(np.prod(x.shape[:begin]))
    if scale is not None and bias is not None and pallas_on("ln"):
        from . import pallas_kernels as pk
        y, mean, var = pk.layer_norm(x.reshape(lead, -1), scale.reshape(-1),
                                     bias.reshape(-1), eps=eps)
        return {"Y": [y.reshape(x.shape).astype(x.dtype)],
                "Mean": [mean], "Variance": [var]}
    x2 = x.reshape(lead, -1).astype(jnp.float32)
    mean = jnp.mean(x2, axis=1, keepdims=True)
    var = jnp.var(x2, axis=1, keepdims=True)
    y = (x2 - mean) * lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.reshape(1, -1)
    if bias is not None:
        y = y + bias.reshape(1, -1)
    return {"Y": [y.reshape(x.shape).astype(x.dtype)],
            "Mean": [mean.reshape(lead)], "Variance": [var.reshape(lead)]}


def _rms_norm_math(x, scale, gate, begin, eps, zero_centered, scaled=None):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=tuple(range(begin, x.ndim)),
                  keepdims=True)
    scale = scale.astype(jnp.float32)
    if zero_centered:
        scale = 1.0 + scale
    y = x32 * lax.rsqrt(ms + eps) * scale.reshape(
        x.shape[begin if scaled is None else scaled:])
    if gate is not None:
        y = y * jax.nn.silu(gate.astype(jnp.float32))
    return y.astype(x.dtype)


def _head_lines(x, scale, eps):
    """The norm over the last axis of x under a float32 scale [D]:
    _rms_norm_math's lines, as rms_norm_kernels.rms_norm takes them."""
    return _rms_norm_math(x, scale, None, x.ndim - 1, eps, False)


# the rule can hold a kernel: building a Program does not trace it
@register("rms_norm", infer=shapes_from(Y="X"))
def _rms_norm(ctx, ins, attrs):
    """y = scale * x / sqrt(mean(x^2) + eps) over the axes from
    begin_norm_axis on; the statistics and the product accumulate in
    float32 whatever x's dtype is, and y comes back in it. zero_centered:
    the weight is stored around 0, y = (1 + scale) * x_hat.
    begin_scale_axis: the scale has an element for every position from that
    axis on (an axis before begin_norm_axis: a norm a group under one
    weight over all groups). Gate, where
    given (x's shape): y = scale * x_hat * silu(gate), under jax.checkpoint
    so that the backward pass keeps x and the gate as they came and not
    their float32 copies (192 MiB a layer at [2, 4096, 32, 128]; AOT
    compile, PR 33). The forward pass is `_rms_norm_math`'s jax.numpy lines
    everywhere; where `rms_norm_path` says so (the norm over a head of a 4-D
    x: q's and k's, an `o_norm`, a `subln`) their transpose is one Pallas
    pass over x's and dy's rows (ops/rms_norm_kernels.py), elsewhere the one
    jax derives: the block norms, the gated and the grouped ones, heads of
    64."""
    x = single(ins, "X")
    args = (attrs.get("begin_norm_axis", 1) % x.ndim,
            attrs.get("epsilon", 1e-5), attrs.get("zero_centered", False),
            attrs.get("begin_scale_axis"))
    if rms_norm_path(ctx, x, ins, attrs) == "kernel":
        from .rms_norm_kernels import rms_norm
        _, eps, zero_centered, _ = args
        scale = single(ins, "Scale").astype(jnp.float32)
        return {"Y": [rms_norm(_head_lines, x,
                               1.0 + scale if zero_centered else scale, eps)]}
    if ins.get("Gate"):
        y = jax.checkpoint(lambda x, s, g: _rms_norm_math(x, s, g, *args))(
            x, single(ins, "Scale"), single(ins, "Gate"))
    else:
        y = _rms_norm_math(x, single(ins, "Scale"), None, *args)
    return {"Y": [y]}


def rms_norm_path(ctx, x, ins, attrs):
    """"kernel" where rms_norm_kernels' one pass is the norm's transpose for
    X: kernel_config.pallas_on("rms_head") (a TPU, or PADDLE_TPU_PALLAS), no
    mesh, X [B, T, H, D] normed over its last axis alone, D whole lane
    tiles, a scale of exactly [D] (no begin_scale_axis), no Gate, and rows
    that whole blocks divide (rms_norm_kernels.applies); else "xla", the
    jax.numpy lines: the block norms (3-D), the gated and the grouped norms,
    heads of 64. The one place that decides; the counter reads it too."""
    from .rms_norm_kernels import applies
    fits = getattr(ctx, "mesh", None) is None and x.ndim == 4 \
        and attrs.get("begin_norm_axis", 1) % x.ndim == 3 \
        and attrs.get("begin_scale_axis") is None and not ins.get("Gate") \
        and tuple(ins["Scale"][0].shape) == tuple(x.shape[3:]) \
        and applies(x.shape)
    return "kernel" if fits and pallas_on("rms_head") else "xla"


@counts("rms_norm")
def _count_rms_norm_call(ctx, attrs, ins):
    x = ins["X"][0]
    if x.ndim != 4:
        return      # a block norm: no head to count
    REGISTRY.counter(
        "ptpu_rms_norm_calls_total",
        "rms_norm ops over a 4-D x lowered (forward ops, not a grad op's "
        "replay), by who runs the norm's transpose (kernel: the one Pallas "
        "pass of ops/rms_norm_kernels.py, where the norm is over a head of "
        "whole lane tiles under a weight [D], ungated; xla: the transpose "
        "jax derives from the jax.numpy lines), the heads and a head's width"
    ).inc(path=rms_norm_path(ctx, x, ins, attrs), heads=str(x.shape[2]),
          head_dim=str(x.shape[3]))


def rotary_path(ctx, x, pos, attrs):
    """"kernel" where rotary_kernels' one pass runs for X [B, T, H, D]:
    kernel_config.pallas_on("rope") (a TPU, or PADDLE_TPU_PALLAS), no mesh,
    integer positions, and a rotation the kernel computes
    (rotary_kernels.applies: the whole head turns, half-split, D whole lane
    tiles, rows that whole blocks divide); else "xla", the jax.numpy lines:
    heads of 64, the interleaved layout, a head that turns in part. The one
    place that decides; the counter reads it too."""
    from .rotary_kernels import applies
    fits = getattr(ctx, "mesh", None) is None \
        and not jnp.issubdtype(pos.dtype, jnp.floating) and applies(
            x.shape, x.dtype.itemsize,
            attrs.get("rotary_dim") or x.shape[-1],
            attrs.get("layout", "half"))
    return "kernel" if fits and pallas_on("rope") else "xla"


@register("rotary_embedding", calls_pallas=True, infer=shapes_from(Out="X"))
def _rotary_embedding(ctx, ins, attrs):
    """Rotary position embedding of x [B, T, H, D] at the positions Pos
    [B, T] (an input, not a constant: a decode step feeds its own). The
    half-split convention over the first R = rotary_dim channels (all D by
    default): the pair (i, i + R/2) of every head turns by pos *
    base^(-2i/R), the channels from R on pass. With the attr `inv_freq`
    (R/2 floats, a table made outside: a scaled one) pair i turns by pos *
    inv_freq[i] instead, and cos and sin are multiplied by `table_scale`
    (1). layout "interleaved": the pairs are (2i, 2i + 1). Angles, cos
    and sin and the rotation are float32; the result comes back in x's
    dtype. Where `rotary_path` says so the rotation is one Pallas pass over
    x that computes the same float32 products from the same cos and sin;
    else the lines below, which XLA compiles to three passes."""
    x = single(ins, "X")
    pos = single(ins, "Pos")
    d = attrs.get("rotary_dim") or x.shape[-1]
    if d % 2 or d > x.shape[-1]:
        raise ValueError("rotary_embedding needs an even width up to the "
                         "head's %d, got %d" % (x.shape[-1], d))
    if attrs.get("inv_freq") is not None:
        inv_freq = jnp.asarray(attrs["inv_freq"], jnp.float32)
        if inv_freq.shape != (d // 2,):
            raise ValueError("rotary_embedding: inv_freq has %d entries for "
                             "%d pairs" % (inv_freq.size, d // 2))
    else:
        inv_freq = attrs.get("base", 10000.0) ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.reshape(x.shape[:2]).astype(jnp.float32)[:, :, None, None] \
        * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if attrs.get("table_scale", 1.0) != 1.0:
        cos, sin = cos * attrs["table_scale"], sin * attrs["table_scale"]
    if rotary_path(ctx, x, pos, attrs) == "kernel":
        from .rotary_kernels import rotary, tables
        return _out(rotary(x, *tables(cos, sin)))
    x32 = x.astype(jnp.float32)
    whole = d == x.shape[-1]
    turned = x32 if whole else x32[..., :d]
    if attrs.get("layout", "half") == "interleaved":
        pairs = turned.reshape(turned.shape[:-1] + (d // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        parts = [jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).reshape(turned.shape)]
    else:
        x1, x2 = jnp.split(turned, 2, axis=-1)
        parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if not whole:
        parts.append(x32[..., d:])
    return _out(jnp.concatenate(parts, -1).astype(x.dtype))


@counts("rotary_embedding")
def _count_rotary_call(ctx, attrs, ins):
    x = ins["X"][0]
    REGISTRY.counter(
        "ptpu_rotary_calls_total",
        "rotary_embedding ops lowered (forward ops, not a grad op's replay), "
        "by the path taken (kernel: the one Pallas pass of "
        "ops/rotary_kernels.py, where the whole head turns, half-split, and "
        "a head is whole lane tiles; xla: the jax.numpy lines), the heads, "
        "a head's width and the channels that turn"
    ).inc(path=rotary_path(ctx, x, ins["Pos"][0], attrs),
          heads=str(x.shape[2]), head_dim=str(x.shape[3]),
          rotary_dim=str(attrs.get("rotary_dim") or x.shape[3]))


@register("lrn")
def _lrn(ctx, ins, attrs):
    x = single(ins, "X")  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


@register("l2_normalize")
def _l2_norm_op(ctx, ins, attrs):
    x = single(ins, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


# ---------------------------------------------------------------------------
# dropout (reference: dropout_op.cc — Mask output keeps fwd/bwd consistent)
# ---------------------------------------------------------------------------

@register("dropout", uses_rng=True)
def _dropout(ctx, ins, attrs):
    x = single(ins, "X")
    p = attrs.get("dropout_prob", 0.5)
    if attrs.get("is_test", False):
        # fluid's default "downgrade_in_infer": scale at inference
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    keep = jax.random.bernoulli(ctx.rng(seed=attrs.get("seed", 0)), 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    return {"Out": [x * mask], "Mask": [mask]}


# ---------------------------------------------------------------------------
# softmax & losses
# ---------------------------------------------------------------------------

@register("softmax")
def _softmax(ctx, ins, attrs):
    return _out(jax.nn.softmax(single(ins, "X"), axis=-1))


@register("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return _out(jax.nn.log_softmax(single(ins, "X"), axis=-1))


def _gather_label_logits(logp, label):
    # [..., C] logits + [..., 1] (or [...]) labels -> [...] picked values
    lead = logp.shape[:-1]
    flat = logp.reshape(-1, logp.shape[-1])
    lab = label.reshape(-1).astype(jnp.int32)
    rows = jnp.arange(flat.shape[0])
    return flat[rows, lab].reshape(lead)


@register("cross_entropy")
def _cross_entropy(ctx, ins, attrs):
    x = single(ins, "X")        # probabilities [N, C]
    label = single(ins, "Label")
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), axis=-1,
                        keepdims=True)
    else:
        picked = _gather_label_logits(jnp.log(jnp.maximum(x, 1e-20)), label)
        loss = -picked[..., None]
    return {"Y": [loss]}


def softmax_xent_form(ctx, logits, attrs):
    """(path, dense) of one softmax_with_cross_entropy op, from what its rule
    sees. path: `kernel` for hard labels on 2-D logits where the kernel is
    on (a TPU), else `xla`. dense: whether the rule builds the `Softmax`
    output; always on the XLA path, on the kernel path only where something
    reads it. Kernel and not dense, the op touches [N, V] once, in the dtype
    the logits come in (AMP does not upcast them: core/lowering._apply_amp),
    and its backward has no result but dlogits."""
    if attrs.get("soft_label", False) or logits.ndim != 2 \
            or not pallas_on("xent"):
        return "xla", True
    # a ctx that says nothing of its op's readers has every slot built
    return "kernel", "Softmax" not in getattr(ctx, "unread_outputs", ())


@register("softmax_with_cross_entropy", calls_pallas=True,
          optional_outputs=("Softmax",), infer=shapes_from(
              Softmax="Logits",
              Loss=("Logits", lambda shape, attrs: shape[:-1] + (1,))))
def _softmax_xent(ctx, ins, attrs):
    logits = single(ins, "Logits")
    label = single(ins, "Label")
    path, dense = softmax_xent_form(ctx, logits, attrs)
    if path == "kernel":
        # loss + logsumexp in one VMEM pass; the softmax itself is only
        # formed in the backward, where it is the gradient
        from . import pallas_kernels as pk
        loss = pk.softmax_xent(logits, label.reshape(-1))
        if not dense:
            # nothing reads Softmax, so none is built: a slot built here is
            # a result of the differentiated function (the op lowers under
            # jax.vjp), and XLA keeps its exponentials and their transpose,
            # two passes over [N, V], for a cotangent of zeros. Under AMP
            # the logits come as they are (bf16) and the loss stays float32,
            # as the upcast gave it.
            return {"Loss": [loss.astype(
                jnp.float32 if ctx.amp else logits.dtype)]}
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return {"Softmax": [jnp.exp(logp).astype(logits.dtype)],
                "Loss": [loss.astype(logits.dtype)]}
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        loss = -_gather_label_logits(logp, label)[..., None]
    return {"Softmax": [jnp.exp(logp).astype(logits.dtype)],
            "Loss": [loss.astype(logits.dtype)]}


@counts("softmax_with_cross_entropy")
def _count_softmax_xent_layer(ctx, attrs, ins):
    logits = ins["Logits"][0]
    form = softmax_xent_form(ctx, logits, attrs)
    seen = logits.dtype
    if ctx.amp and form != ("kernel", False) and seen == jnp.bfloat16:
        seen = jnp.dtype(jnp.float32)       # _apply_amp's upcast
    REGISTRY.counter(
        "ptpu_softmax_xent_layers_total",
        "softmax_with_cross_entropy ops lowered (forward ops, not a grad "
        "op's replay), by who computes the loss (the Pallas kernel, or "
        "XLA), the dtype the rule reads the logits in (under AMP bfloat16 "
        "where the kernel runs and builds no Softmax, float32 elsewhere) "
        "and whether anything reads the dense Softmax output (unread, the "
        "kernel path builds none; the XLA path builds it either way)"
    ).inc(path=form[0], logits=str(seen),
          softmax="unread" if "Softmax" in ctx.unread_outputs else "read")


def _fused_attention_infer(block, op, out_vars):
    """Out is Q's [B, T, Hq] on V's last dim (the latent forms' value may be
    another width than the part without position), in V's dtype off the
    dense path and Q's off the flash kernels. Traced to learn that, the
    flash kernels' bodies cost 2.7 s of an 18-layer program's build (PERF.md
    section 6, PR 53). A latent head at widths the flash kernels refuse is
    refused here, while the program is built, by the kernels' own check."""
    q, v = (block.var_recursive(op.inputs[s][0]) for s in ("Q", "V"))
    if q.shape is None or v.shape is None:
        return
    flash = q.shape[1] != -1 and flash_at(q.shape[1])
    if flash and op.inputs.get("QRope") and q.shape[-1] != v.shape[-1]:
        # the one latent form that needs asking; the module is a kernel's
        from .pallas_kernels import latent_form
        rope = block.var_recursive(op.inputs["QRope"][0])
        latent_form(q.shape[-1], rope.shape[-1], v.shape[-1])
    for out in out_vars.get("Out", ()):
        set_like(out, q, lambda shape: shape[:-1] + (v.shape[-1],),
                 q.dtype if flash else v.dtype)


@register("fused_attention", calls_pallas=True, infer=_fused_attention_infer)
def _fused_attention(ctx, ins, attrs):
    """flash attention over [B, T, H, D] q/k/v (TPU-native addition; see
    ops/pallas_kernels.py). Differentiable via the kernel's custom_vjp.

    Sequence parallelism is Program-reachable here: under a
    ParallelExecutor mesh with an 'sp' axis, the same op dispatches to
    parallel/ring_attention.py — the sequence dim shards over sp, K/V
    blocks rotate the ring via lax.ppermute, and the online softmax
    matches the single-chip kernel exactly (incl. causal + kv_len).

    The latent form: QRope [B, T, Hq, dr] and KRope [B, T, 1, dr] beside Q
    and K of one width D and V of D or of another width (192 + 64 on 256):
    a head's score is q . k + q_rope . k_rope, the rotary key one that all
    heads share (pallas_kernels.flash_attention). The dense path
    concatenates the two parts and repeats the shared key a head; the flash
    kernels do neither at equal widths, and at unequal ones what
    pallas_kernels.latent_form says.

    `block_diffusion` [block_length, L], an attr a program has only where
    it was asked for: the T = 2 L rows are a noised and a clean copy of one
    sequence under the block-diffusion mask (pallas_kernels.flash_attention
    has the rule; the dense path writes it out, ring_attention.
    block_diffusion_mask). Refused beside causal, a window, KVLen, the
    latent form and an 'sp' mesh axis."""
    q = single(ins, "Q")
    k = single(ins, "K")
    v = single(ins, "V")
    q_rope = single(ins, "QRope") if ins.get("QRope") else None
    k_rope = single(ins, "KRope") if ins.get("KRope") else None
    kv_len = single(ins, "KVLen") if ins.get("KVLen") else None
    causal = attrs.get("causal", False)
    scale = attrs.get("scale", None)
    # grouped queries come from the shapes (K and V with fewer heads than Q,
    # a divisor of them); `window` is an attr: query i sees key j only where
    # i - j < window
    window = attrs.get("window", None)
    bd = attrs.get("block_diffusion", None)
    if bd is not None:
        bd = tuple(int(n) for n in bd)
        if causal or window is not None or kv_len is not None \
                or q_rope is not None:
            raise ValueError(
                "fused_attention: block_diffusion %r is the whole mask: "
                "causal, window, KVLen and the latent form are refused "
                "beside it" % (bd,))
    mesh = ctx.mesh
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        if window is not None or k.shape[2] != q.shape[2] \
                or q_rope is not None or bd is not None:
            raise NotImplementedError(
                "fused_attention under an 'sp' mesh axis has neither a "
                "window nor grouped queries nor the latent form nor the "
                "block-diffusion mask: the ring "
                "and Ulysses paths would ignore window=%r, %d key/value "
                "heads for %d query heads, the rotary parts and "
                "block_diffusion=%r" % (window, k.shape[2], q.shape[2], bd))
        # sp_impl picks the sequence-parallel algorithm: "ring" (default;
        # K/V blocks rotate over ICI, O(T/sp) memory, any head count) or
        # "ulysses" (all-to-all head sharding — one collective round
        # instead of sp-1 ppermute hops when heads % sp == 0)
        if attrs.get("sp_impl", "ring") == "ulysses":
            from ..parallel.ulysses import ulysses_attention_sharded
            return _out(ulysses_attention_sharded(
                q, k, v, mesh, causal=causal, scale=scale, kv_len=kv_len))
        from ..parallel.ring_attention import ring_attention_sharded
        return _out(ring_attention_sharded(
            q, k, v, mesh, causal=causal, scale=scale, kv_len=kv_len))
    # kernel_config.flash_at owns the flash-or-dense decision: dense
    # below the crossover, at one query row (decode), and when
    # PADDLE_TPU_PALLAS opts 'attn' out; the Pallas kernel otherwise, at
    # the tile kernel_config.DEFAULT_TILES holds.
    if not flash_at(q.shape[1]):
        from ..parallel.ring_attention import attention_reference
        if q_rope is not None:
            if scale is None:
                scale = (q.shape[3] + q_rope.shape[3]) ** -0.5
            q = jnp.concatenate([q, q_rope], -1)
            k = jnp.concatenate(
                [k, jnp.broadcast_to(k_rope, q_rope.shape)], -1)
        return _out(attention_reference(
            q, k, v, causal=causal, scale=scale, kv_len=kv_len,
            window=window, block_diffusion=bd).astype(v.dtype))
    from . import pallas_kernels as pk
    out = pk.flash_attention(
        q, k, v, causal=causal, scale=scale, kv_len=kv_len, window=window,
        q_rope=q_rope, k_rope=k_rope, block_diffusion=bd)
    return _out(out)


@counts("fused_attention")
def _count_attention_layer(ctx, attrs, ins):
    from .pallas_kernels import heads_a_block, latent_form
    q, k = ins["Q"][0], ins["K"][0]
    window, bd = attrs.get("window"), attrs.get("block_diffusion")
    if ctx.mesh is not None and ctx.mesh.shape.get("sp", 1) > 1:
        path = str(attrs.get("sp_impl", "ring"))
    else:
        path = "flash" if flash_at(q.shape[1]) else "dense"
    # how the flash kernels index a head of [B, T, H*D]: in place, so many
    # heads a lane block, or after a transpose to a row a head
    heads = heads_a_block(q.shape[2], k.shape[2], q.shape[3]) \
        or "transposed" if path == "flash" else "none"
    # the latent form's labels are its own: an op without QRope counts
    # under the labels it always had
    latent = {}
    if ins.get("QRope"):
        q_rope, k_rope = ins["QRope"][0], ins["KRope"][0]
        v_dim = ins["V"][0].shape[3]
        latent = dict(form="latent", v_dim=str(v_dim),
                      rope_dim=str(q_rope.shape[3]),
                      rope_key_group=str(q_rope.shape[2] // k_rope.shape[2]))
        if v_dim != q.shape[3]:
            # a part without position that is not the value's width: the
            # form the flash kernels run the head in is one more label, and
            # the lane blocks are the whole head's where it is joined
            core = latent_form(q.shape[3], q_rope.shape[3], v_dim) \
                if path == "flash" else "dense"
            latent["core"] = core
            if core == "whole":
                heads = heads_a_block(q.shape[2], k.shape[2], v_dim) \
                    or "transposed"
    REGISTRY.counter(
        "ptpu_attention_layers_total",
        "fused_attention ops lowered (forward ops, not a grad op's replay), "
        "by kind (full, or window with its size), query and key/value "
        "heads, the path taken (flash, dense, or the sequence-parallel one), "
        "the head's width and, on the flash path, the heads the kernels "
        "index in one lane block (or transposed); the latent form besides "
        "by form=latent, the value's width, the rotary part's width "
        "(head_dim is then the part without position) and the query heads "
        "that read one rotary key, and, where the value is not as wide as "
        "the part without position (192 + 64 on 256), by core, the form the "
        "head runs in: whole (the two parts joined, the plain kernels at "
        "v_dim) or dense; an op under the block-diffusion mask alone counts "
        "as kind block_diffusion with its block_length and copy_length (its "
        "T rows are two copies of copy_length tokens)"
    ).inc(kind="block_diffusion" if bd else "full" if window is None
          else "window", **({} if not bd else dict(
              block_length=str(bd[0]), copy_length=str(bd[1]))),
          window=str(window or 0), q_heads=str(q.shape[2]),
          kv_heads=str(k.shape[2]), path=path, head_dim=str(q.shape[3]),
          heads_a_block=str(heads), **latent)


@register("sigmoid_cross_entropy_with_logits")
def _sigmoid_xent(ctx, ins, attrs):
    x = single(ins, "X")
    label = single(ins, "Label")
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return _out(loss)


@register("square_error_cost")
def _square_error(ctx, ins, attrs):
    x, y = single(ins, "X"), single(ins, "Y")
    return _out(jnp.square(x - y))


@register("smooth_l1_loss")
def _smooth_l1(ctx, ins, attrs):
    x, y = single(ins, "X"), single(ins, "Y")
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    iw = single(ins, "InsideWeight")
    ow = single(ins, "OutsideWeight")
    if iw is not None:
        diff = diff * iw
    ad = jnp.abs(diff)
    elem = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if ow is not None:
        elem = elem * ow
    loss = jnp.sum(elem.reshape(elem.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [loss], "Diff": [diff]}


@register("log_loss")
def _log_loss(ctx, ins, attrs):
    p = single(ins, "Predicted")
    label = single(ins, "Labels")
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {"Loss": [loss]}


@register("huber_loss")
def _huber_loss(ctx, ins, attrs):
    x, y = single(ins, "X"), single(ins, "Y")
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register("hinge_loss")
def _hinge_loss(ctx, ins, attrs):
    logits = single(ins, "Logits")
    labels = single(ins, "Labels")
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)]}


@register("rank_loss")
def _rank_loss(ctx, ins, attrs):
    label = single(ins, "Label")
    left = single(ins, "Left")
    right = single(ins, "Right")
    d = left - right
    return _out(jnp.log1p(jnp.exp(d)) - label * d)


@register("margin_rank_loss")
def _margin_rank_loss(ctx, ins, attrs):
    label = single(ins, "Label")
    x1, x2 = single(ins, "X1"), single(ins, "X2")
    margin = attrs.get("margin", 0.0)
    act = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [act], "Activated": [(act > 0).astype(x1.dtype)]}


@register("label_smooth")
def _label_smooth(ctx, ins, attrs):
    x = single(ins, "X")
    eps = attrs.get("epsilon", 0.0)
    dist = single(ins, "PriorDist")
    if dist is not None:
        out = (1 - eps) * x + eps * dist
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    return _out(out)


# ---------------------------------------------------------------------------
# embedding (reference: lookup_table_op — the pserver sparse path's hot op)
# ---------------------------------------------------------------------------

@register("lookup_table")
def _lookup_table(ctx, ins, attrs):
    w = single(ins, "W")        # [V, D]
    ids = single(ins, "Ids")    # [N, 1] int64
    flat = ids.reshape(-1).astype(jnp.int32)
    padding_idx = attrs.get("padding_idx", -1)
    from .embedding_grad import grad_form, take_rows
    out = take_rows(w, flat, grad_form(flat.size, w.shape[1], ctx.mesh))
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((flat == padding_idx)[:, None], 0.0, out)
    out_shape = tuple(ids.shape[:-1]) + (w.shape[-1],) \
        if ids.shape and ids.shape[-1] == 1 else tuple(ids.shape) + (w.shape[-1],)
    return _out(out.reshape(out_shape))


@counts("lookup_table")
def _count_embedding_layer(ctx, attrs, ins):
    from .embedding_grad import grad_form
    w, ids = ins["W"][0], ins["Ids"][0]
    REGISTRY.counter(
        "ptpu_embedding_layers_total",
        "lookup_table ops lowered (forward ops, not a grad op's replay), by "
        "the rows looked up, the table's rows and width, and who builds the "
        "table's dense gradient in the backward pass (XLA's scatter, or the "
        "kernel that writes the table block by block)"
    ).inc(rows=str(ids.size), vocab=str(w.shape[0]), width=str(w.shape[1]),
          grad=grad_form(ids.size, w.shape[1], ctx.mesh))


# ---------------------------------------------------------------------------
# metrics (reference: accuracy_op.cc, auc_op.cc)
# ---------------------------------------------------------------------------

@register("accuracy")
def _accuracy(ctx, ins, attrs):
    pred_idx = single(ins, "Indices")   # [N, k] from topk
    label = single(ins, "Label")        # [N, 1]
    n = pred_idx.shape[0]
    correct = jnp.any(pred_idx.astype(jnp.int64) ==
                      label.astype(jnp.int64).reshape(-1, 1), axis=1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    return {"Accuracy": [(num_correct / n).reshape(1)],
            "Correct": [num_correct.astype(jnp.int32).reshape(1)],
            "Total": [jnp.full((1,), n, jnp.int32)]}


@register("auc")
def _auc(ctx, ins, attrs):
    # streaming AUC state lives in persistable vars updated here
    pred = single(ins, "Predict")
    label = single(ins, "Label").reshape(-1)
    tp_in = single(ins, "TP")  # stat buckets [num_thresholds]
    fp_in = single(ins, "FP")
    num_t = attrs.get("num_thresholds", 200)
    pos_score = pred[:, 1] if pred.ndim == 2 and pred.shape[1] == 2 else pred.reshape(-1)
    bucket = jnp.clip((pos_score * num_t).astype(jnp.int32), 0, num_t - 1)
    is_pos = (label > 0).astype(jnp.int64)
    tp = tp_in + jnp.zeros_like(tp_in).at[bucket].add(is_pos)
    fp = fp_in + jnp.zeros_like(fp_in).at[bucket].add(1 - is_pos)
    # integrate over thresholds (cumulative from high score to low)
    tp_c = jnp.cumsum(tp[::-1])[::-1].astype(jnp.float64)
    fp_c = jnp.cumsum(fp[::-1])[::-1].astype(jnp.float64)
    tot_pos = jnp.maximum(tp_c[0], 1)
    tot_neg = jnp.maximum(fp_c[0], 1)
    tpr = tp_c / tot_pos
    fpr = fp_c / tot_neg
    auc = -jnp.trapezoid(tpr, fpr)
    return {"AUC": [auc.astype(jnp.float32).reshape(1)],
            "TPOut": [tp], "FPOut": [fp]}


# ---------------------------------------------------------------------------
# nce (reference: nce_op.cc) — negative sampling loss
# ---------------------------------------------------------------------------

@register("nce", uses_rng=True)
def _nce(ctx, ins, attrs):
    x = single(ins, "Input")          # [N, D]
    label = single(ins, "Label")      # [N, num_true]
    w = single(ins, "Weight")         # [V, D]
    b = single(ins, "Bias")           # [V]
    num_neg = attrs.get("num_neg_samples", 10)
    num_total = attrs.get("num_total_classes")
    n = x.shape[0]
    label = label.reshape(n, -1).astype(jnp.int32)
    num_true = label.shape[1]
    neg = jax.random.randint(ctx.rng(seed=attrs.get("seed", 0)), (n, num_neg), 0, num_total)
    samples = jnp.concatenate([label, neg], axis=1)      # [N, T+S]
    sw = jnp.take(w, samples.reshape(-1), axis=0).reshape(n, -1, w.shape[1])
    logits = jnp.einsum("nd,nsd->ns", x, sw)
    if b is not None:
        logits = logits + jnp.take(b.reshape(-1), samples.reshape(-1)).reshape(n, -1)
    labels01 = jnp.concatenate(
        [jnp.ones((n, num_true)), jnp.zeros((n, num_neg))], axis=1)
    ce = jnp.maximum(logits, 0) - logits * labels01 + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    cost = jnp.sum(ce, axis=1, keepdims=True)
    return {"Cost": [cost], "SampleLogits": [logits], "SampleLabels": [samples]}


@register("im2sequence")
def _im2sequence(ctx, ins, attrs):
    """Patches -> per-image sequence (reference im2sequence_op.h Im2Col).

    Input [B, C, H, W] -> Out [B, oh*ow, C*kh*kw] + OutLen (= oh*ow for
    every image; static shapes make it a constant vector). Feature order is
    channel-major (c, kh, kw) like the reference's im2col."""
    x = single(ins, "X")
    kh, kw = attrs["kernels"]
    sh, sw = attrs.get("strides", [1, 1])
    pads = attrs.get("paddings", [0, 0, 0, 0])
    up, left, down, right = (pads if len(pads) == 4 else
                             [pads[0], pads[1], pads[0], pads[1]])
    b, c, h, w = x.shape
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=(sh, sw),
        padding=((up, down), (left, right)))    # [B, C*kh*kw, oh, ow]
    f = patches.shape[1]
    oh, ow = patches.shape[2], patches.shape[3]
    out = patches.reshape(b, f, oh * ow).transpose(0, 2, 1)
    out_len = jnp.full((b,), oh * ow, jnp.int32)
    return {"Out": [out], "OutLen": [out_len]}
