"""Elementwise / math / tensor op lowerings.

Parity: paddle/fluid/operators/{activation_op,elementwise_*,mul_op,matmul_op,
mean_op,scale_op,sum_op,cast_op,concat_op,reshape_op,transpose_op,split_op,
reduce_op,fill_*,uniform_random_op,gaussian_random_op,clip_op,compare_op,
logical_op,cumsum_op,scatter_op,gather_op,topk_op,one_hot_op,...}.{cc,cu}.
Each CUDA kernel there becomes one jnp/lax expression here; gradients are
derived automatically via jax.vjp of these rules (no *_grad lowerings).
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..core.registry import register, single


def _out(x):
    return {"Out": [x]}


# ---------------------------------------------------------------------------
# activations (reference: activation_op.cc ~27 kernels)
# ---------------------------------------------------------------------------

def _act(name, fn):
    register(name)(lambda ctx, ins, attrs, fn=fn: _out(fn(single(ins, "X"), attrs)))


_act("sigmoid", lambda x, a: jax.nn.sigmoid(x))
_act("logsigmoid", lambda x, a: jax.nn.log_sigmoid(x))
_act("exp", lambda x, a: jnp.exp(x))
_act("relu", lambda x, a: jax.nn.relu(x))
_act("tanh", lambda x, a: jnp.tanh(x))
_act("tanh_shrink", lambda x, a: x - jnp.tanh(x))
_act("softshrink", lambda x, a: jnp.where(x > a.get("lambda", 0.5), x - a.get("lambda", 0.5),
                                          jnp.where(x < -a.get("lambda", 0.5), x + a.get("lambda", 0.5), 0.0)))
_act("sqrt", lambda x, a: jnp.sqrt(x))
_act("abs", lambda x, a: jnp.abs(x))
_act("ceil", lambda x, a: jnp.ceil(x))
_act("floor", lambda x, a: jnp.floor(x))
_act("cos", lambda x, a: jnp.cos(x))
_act("sin", lambda x, a: jnp.sin(x))
_act("round", lambda x, a: jnp.round(x))
_act("reciprocal", lambda x, a: 1.0 / x)
_act("log", lambda x, a: jnp.log(x))
_act("square", lambda x, a: jnp.square(x))
_act("softplus", lambda x, a: jax.nn.softplus(x))
_act("softsign", lambda x, a: x / (1 + jnp.abs(x)))
_act("brelu", lambda x, a: jnp.clip(x, a.get("t_min", 0.0), a.get("t_max", 24.0)))
_act("leaky_relu", lambda x, a: jax.nn.leaky_relu(x, a.get("alpha", 0.02)))
_act("soft_relu", lambda x, a: jnp.log1p(jnp.exp(jnp.clip(x, -a.get("threshold", 40.0), a.get("threshold", 40.0)))))
_act("elu", lambda x, a: jax.nn.elu(x, a.get("alpha", 1.0)))
_act("relu6", lambda x, a: jnp.clip(x, 0.0, a.get("threshold", 6.0)))
_act("pow", lambda x, a: jnp.power(x, a.get("factor", 1.0)))
_act("stanh", lambda x, a: a.get("scale_b", 1.7159) * jnp.tanh(a.get("scale_a", 2.0 / 3.0) * x))
_act("hard_shrink", lambda x, a: jnp.where(jnp.abs(x) > a.get("threshold", 0.5), x, 0.0))
_act("thresholded_relu", lambda x, a: jnp.where(x > a.get("threshold", 1.0), x, 0.0))
_act("hard_sigmoid", lambda x, a: jnp.clip(a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0))
_act("swish", lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x))


# ---------------------------------------------------------------------------
# elementwise binary ops with fluid's axis-broadcast semantics
# (reference: elementwise_op_function.h)
# ---------------------------------------------------------------------------

def _bcast_y(x, y, axis):
    """Fluid broadcast: Y's shape must match a contiguous run of X's dims
    starting at `axis` (axis=-1 => trailing alignment, numpy-style)."""
    if x.ndim == y.ndim:
        return y
    if axis == -1 or axis is None:
        axis = x.ndim - y.ndim
    new_shape = (1,) * axis + y.shape + (1,) * (x.ndim - axis - y.ndim)
    return y.reshape(new_shape)


def _elementwise(name, fn):
    def lower(ctx, ins, attrs):
        x, y = single(ins, "X"), single(ins, "Y")
        y = _bcast_y(x, y, attrs.get("axis", -1))
        return _out(fn(x, y))
    register(name)(lower)


_elementwise("elementwise_add", lambda x, y: x + y)
_elementwise("elementwise_sub", lambda x, y: x - y)
_elementwise("elementwise_mul", lambda x, y: x * y)
_elementwise("elementwise_div", lambda x, y: x / y)
_elementwise("elementwise_max", jnp.maximum)
_elementwise("elementwise_min", jnp.minimum)
_elementwise("elementwise_pow", jnp.power)


@register("minus")
def _minus(ctx, ins, attrs):
    return _out(single(ins, "X") - single(ins, "Y"))


# ---------------------------------------------------------------------------
# mul / matmul (reference: mul_op.cc, matmul_op.cc) — MXU path
# ---------------------------------------------------------------------------

# The name a `mul` gives its result inside the body of a loop that recomputes
# (ctx.in_recomputing_loop: no other program traces it) where the contraction
# is wider than the result's columns (x2.shape[1] > y2.shape[1]): the whole
# of the shape rule `narrow_matmul`, stated here because only here are the
# 2-D shapes and the cast product both in view. The name is an identity that
# lowers to no operation; its one reader is that loop's policy
# (ops/control_ops.py _kept_by), which keeps a value of this name.
NARROW_MATMUL = "ptpu_narrow_matmul"


def _flatten2d(x, num_col_dims):
    lead = int(np.prod(x.shape[:num_col_dims])) if num_col_dims > 0 else 1
    return x.reshape(lead, -1)


@register("mul")
def _mul(ctx, ins, attrs):
    x, y = single(ins, "X"), single(ins, "Y")
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    x2 = _flatten2d(x, xn)
    y2 = y.reshape(int(np.prod(y.shape[:yn])), -1)
    out = jnp.matmul(x2, y2, preferred_element_type=jnp.float32).astype(x.dtype) \
        if x.dtype == jnp.bfloat16 else x2 @ y2
    if getattr(ctx, "in_recomputing_loop", False) \
            and x2.shape[1] > y2.shape[1]:
        # NARROW_MATMUL's rule (a LowerCtx's attribute: shape inference's
        # stand-in has none); what reads the product must read this value
        out = checkpoint_name(out, NARROW_MATMUL)
    out_shape = x.shape[:xn] + y.shape[yn:]
    return _out(out.reshape(out_shape))


@register("matmul")
def _matmul(ctx, ins, attrs):
    x, y = single(ins, "X"), single(ins, "Y")
    if attrs.get("transpose_X"):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if attrs.get("transpose_Y"):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    if x.dtype == jnp.bfloat16 or y.dtype == jnp.bfloat16:
        out = jnp.matmul(x, y, preferred_element_type=jnp.float32) \
            .astype(jnp.promote_types(x.dtype, y.dtype))
    else:
        out = jnp.matmul(x, y)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return _out(out)


# ---------------------------------------------------------------------------
# shape / dtype manipulation
# ---------------------------------------------------------------------------

@register("mean")
def _mean(ctx, ins, attrs):
    return _out(jnp.mean(single(ins, "X")).reshape(1))


@register("scale")
def _scale(ctx, ins, attrs):
    x = single(ins, "X")
    out = x * attrs.get("scale", 1.0)
    bias = attrs.get("bias", 0.0)
    if bias:
        if attrs.get("bias_after_scale", True):
            out = out + bias
        else:
            out = (x + bias) * attrs.get("scale", 1.0)
    return _out(out)


@register("cast")
def _cast(ctx, ins, attrs):
    return _out(single(ins, "X").astype(np.dtype(attrs["out_dtype"])))


@register("sum")
def _sum(ctx, ins, attrs):
    xs = ins.get("X", [])
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return _out(out)


@register("concat")
def _concat(ctx, ins, attrs):
    return _out(jnp.concatenate(ins["X"], axis=attrs.get("axis", 0)))


@register("split")
def _split(ctx, ins, attrs):
    x = single(ins, "X")
    axis = attrs.get("axis", 0)
    sections = attrs.get("sections")
    if sections:
        idx = np.cumsum(sections)[:-1].tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, attrs.get("num", 1), axis=axis)
    return {"Out": list(outs)}


@register("reshape")
def _reshape(ctx, ins, attrs):
    x = single(ins, "X")
    shape = list(attrs["shape"])
    # fluid semantics: 0 means copy dim from input, -1 infers
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape)] \
        if any(s == 0 for s in shape) else shape
    return _out(x.reshape(shape))


@register("squeeze")
def _squeeze(ctx, ins, attrs):
    x = single(ins, "X")
    axes = attrs.get("axes") or [i for i, d in enumerate(x.shape) if d == 1]
    return _out(jnp.squeeze(x, axis=tuple(axes)))


@register("unsqueeze")
def _unsqueeze(ctx, ins, attrs):
    x = single(ins, "X")
    for a in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, a)
    return _out(x)


@register("transpose")
def _transpose(ctx, ins, attrs):
    return _out(jnp.transpose(single(ins, "X"), attrs["axis"]))


@register("expand")
def _expand(ctx, ins, attrs):
    x = single(ins, "X")
    times = attrs["expand_times"]
    return _out(jnp.tile(x, times))


@register("assign")
def _assign(ctx, ins, attrs):
    return _out(single(ins, "X"))


@register("print")
def _print(ctx, ins, attrs):
    """Identity + debug callback print (reference print_op.cc). Works under
    jit and inside lax control flow; the runtime prints when the step runs.
    first_n is honored per compiled entry via a host-side counter in the
    callback closure (a re-trace starts a fresh count)."""
    x = single(ins, "In")
    msg = attrs.get("message") or ""
    parts = []
    if attrs.get("print_tensor_name", True):
        parts.append(attrs.get("var_name", ""))
    if attrs.get("print_tensor_type", True):
        parts.append(str(x.dtype))
    if attrs.get("print_tensor_shape", True):
        parts.append(str(tuple(x.shape)))
    header = " ".join(p for p in [msg] + parts if p)
    summarize = attrs.get("summarize", -1)
    first_n = attrs.get("first_n", -1)
    shown = x.reshape(-1)
    if summarize and summarize > 0:
        shown = shown[:summarize]
    state = {"n": 0}

    def _emit(v):
        if first_n < 0 or state["n"] < first_n:
            state["n"] += 1
            print(header, np.asarray(v))

    jax.debug.callback(_emit, shown)
    return _out(x)


@register("clip")
def _clip(ctx, ins, attrs):
    return _out(jnp.clip(single(ins, "X"), attrs["min"], attrs["max"]))


@register("clip_by_norm")
def _clip_by_norm(ctx, ins, attrs):
    x = single(ins, "X")
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return _out(x * scale)


# ---------------------------------------------------------------------------
# reductions (reference: reduce_op.cc family)
# ---------------------------------------------------------------------------

def _reduce(name, fn):
    def lower(ctx, ins, attrs):
        x = single(ins, "X")
        if attrs.get("reduce_all"):
            dim = None
        else:
            dim = attrs.get("dim", 0)
            if isinstance(dim, (list, tuple)):
                dim = tuple(dim)
        keep = attrs.get("keep_dim", False)
        out = fn(x, axis=dim, keepdims=keep)
        if dim is None and not keep:
            out = out.reshape(1)
        return _out(out)
    register(name)(lower)


_reduce("reduce_sum", jnp.sum)
_reduce("reduce_mean", jnp.mean)
_reduce("reduce_max", jnp.max)
_reduce("reduce_min", jnp.min)
_reduce("reduce_prod", jnp.prod)


# ---------------------------------------------------------------------------
# fills / random (reference: fill_constant_op.cc, uniform_random_op.cc, ...)
# ---------------------------------------------------------------------------

def _resolve_bsl_shape(ref, attrs):
    """*_batch_size_like shape: copy batch dim from a reference input."""
    shape = list(attrs["shape"])
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    return shape


@register("fill_constant")
def _fill_constant(ctx, ins, attrs):
    dtype = np.dtype(attrs.get("dtype", "float32"))
    shape = [1 if s == -1 else s for s in attrs.get("shape", [1])]
    return _out(jnp.full(shape, attrs.get("value", 0.0), dtype=dtype))


@register("fill_constant_batch_size_like")
def _fill_cbsl(ctx, ins, attrs):
    ref = single(ins, "Input")
    shape = _resolve_bsl_shape(ref, attrs)
    return _out(jnp.full(shape, attrs.get("value", 0.0),
                         dtype=np.dtype(attrs.get("dtype", "float32"))))


@register("fill_zeros_like")
def _fill_zeros_like(ctx, ins, attrs):
    return _out(jnp.zeros_like(single(ins, "X")))


@register("fill")
def _fill(ctx, ins, attrs):
    """fill_op.cc: fill Out with the row-major `value` float list, reshaped
    to `shape`, cast to `dtype` (force_cpu is a placement no-op here)."""
    arr = np.asarray(attrs["value"], dtype=np.float32)
    arr = arr.reshape(attrs["shape"]).astype(
        np.dtype(attrs.get("dtype", "float32")))
    return _out(jnp.asarray(arr))


def _attr_np_dtype(attrs, default="float32"):
    """Resolve a "dtype" attr that may be a numpy-style string (our
    layers) OR the era framework.proto VarType enum int (era descs and
    reference OpTest configs encode dtype as e.g. 5=FP32, 2=INT32)."""
    v = attrs.get("dtype", default)
    if isinstance(v, (int, np.integer)):
        table = {0: "bool", 1: "int16", 2: "int32", 3: "int64",
                 4: "float16", 5: "float32", 6: "float64"}
        v = table.get(int(v), default)
    return np.dtype(v)


@register("assign_value")
def _assign_value(ctx, ins, attrs):
    """assign_value_op.cc:55 stores the payload in a dtype-SUFFIXED attr
    (fp32_values / int32_values, selected in assign_value_op.h:34) —
    accept those wire names (era descs / OpTest configs, where dtype is
    the VarType enum int) alongside the layer's own "values"."""
    dtype = _attr_np_dtype(attrs)
    if "values" in attrs:
        vals = attrs["values"]
    elif dtype == np.int32 and "int32_values" in attrs:
        vals = attrs["int32_values"]
    elif "fp32_values" in attrs:
        vals = attrs["fp32_values"]
    else:
        raise KeyError(
            "assign_value: none of values/fp32_values/int32_values in "
            "attrs %r" % sorted(attrs))
    arr = np.asarray(vals, dtype=dtype)
    return _out(jnp.asarray(arr.reshape(attrs["shape"])))


@register("shape")
def _shape(ctx, ins, attrs):
    x = single(ins, "Input")
    return _out(jnp.asarray(x.shape, dtype=jnp.int32))


@register("uniform_random", uses_rng=True)
def _uniform_random(ctx, ins, attrs):
    dtype = np.dtype(attrs.get("dtype", "float32"))
    shape = [1 if s == -1 else s for s in attrs["shape"]]
    out = jax.random.uniform(ctx.rng(seed=attrs.get("seed", 0)), shape, dtype=dtype,
                             minval=attrs.get("min", -1.0),
                             maxval=attrs.get("max", 1.0))
    return _out(out)


@register("uniform_random_batch_size_like", uses_rng=True)
def _uniform_random_bsl(ctx, ins, attrs):
    ref = single(ins, "Input")
    shape = _resolve_bsl_shape(ref, attrs)
    return _out(jax.random.uniform(ctx.rng(seed=attrs.get("seed", 0)), shape,
                                   dtype=np.dtype(attrs.get("dtype", "float32")),
                                   minval=attrs.get("min", -1.0),
                                   maxval=attrs.get("max", 1.0)))


@register("gaussian_random", uses_rng=True)
def _gaussian_random(ctx, ins, attrs):
    dtype = np.dtype(attrs.get("dtype", "float32"))
    shape = [1 if s == -1 else s for s in attrs["shape"]]
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * \
        jax.random.normal(ctx.rng(seed=attrs.get("seed", 0)), shape, dtype=dtype)
    return _out(out)


@register("gaussian_random_batch_size_like", uses_rng=True)
def _gaussian_random_bsl(ctx, ins, attrs):
    ref = single(ins, "Input")
    shape = _resolve_bsl_shape(ref, attrs)
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * \
        jax.random.normal(ctx.rng(seed=attrs.get("seed", 0)), shape,
                          dtype=np.dtype(attrs.get("dtype", "float32")))
    return _out(out)


@register("truncated_gaussian_random", uses_rng=True)
def _truncated_gaussian_random(ctx, ins, attrs):
    dtype = np.dtype(attrs.get("dtype", "float32"))
    shape = [1 if s == -1 else s for s in attrs["shape"]]
    std = attrs.get("std", 1.0)
    out = attrs.get("mean", 0.0) + std * jax.random.truncated_normal(
        ctx.rng(seed=attrs.get("seed", 0)), -2.0, 2.0, shape, dtype=dtype)
    return _out(out)


# ---------------------------------------------------------------------------
# comparison / logical (reference: compare_op.cc, logical_op.cc)
# ---------------------------------------------------------------------------

def _compare(name, fn):
    def lower(ctx, ins, attrs):
        return _out(fn(single(ins, "X"), single(ins, "Y")))
    register(name)(lower)


_compare("less_than", lambda x, y: x < y)
_compare("less_equal", lambda x, y: x <= y)
_compare("greater_than", lambda x, y: x > y)
_compare("greater_equal", lambda x, y: x >= y)
_compare("equal", lambda x, y: x == y)
_compare("not_equal", lambda x, y: x != y)
_compare("logical_and", jnp.logical_and)
_compare("logical_or", jnp.logical_or)
_compare("logical_xor", jnp.logical_xor)


@register("logical_not")
def _logical_not(ctx, ins, attrs):
    return _out(jnp.logical_not(single(ins, "X")))


# ---------------------------------------------------------------------------
# indexing / misc
# ---------------------------------------------------------------------------

@register("sign")
def _sign(ctx, ins, attrs):
    return _out(jnp.sign(single(ins, "X")))


@register("reduce_sum_square")
def _reduce_sum_square(ctx, ins, attrs):
    return _out(jnp.sum(jnp.square(single(ins, "X"))).reshape(1))


@register("global_norm_scale")
def _global_norm_scale(ctx, ins, attrs):
    total_sq = single(ins, "X").reshape(())
    clip = attrs["clip_norm"]
    norm = jnp.sqrt(total_sq)
    return _out(jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12)).reshape(1))


@register("cumsum")
def _cumsum(ctx, ins, attrs):
    x = single(ins, "X")
    axis = attrs.get("axis", -1)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive"):
        out = out - x
    if attrs.get("reverse"):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
        if attrs.get("exclusive"):
            out = out - x
    return _out(out)


@register("gather")
def _gather(ctx, ins, attrs):
    x, idx = single(ins, "X"), single(ins, "Index")
    return _out(jnp.take(x, idx.reshape(-1).astype(jnp.int32), axis=0))


@register("scatter")
def _scatter(ctx, ins, attrs):
    x, idx, upd = single(ins, "X"), single(ins, "Ids"), single(ins, "Updates")
    idx = idx.reshape(-1).astype(jnp.int32)
    return _out(x.at[idx].set(upd))


@register("topk")
def _topk(ctx, ins, attrs):
    x = single(ins, "X")
    k = attrs.get("k", 1)
    vals, idx = lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int64)]}


@register("arg_max")
def _arg_max(ctx, ins, attrs):
    return _out(jnp.argmax(single(ins, "X"), axis=attrs.get("axis", -1))
                .astype(jnp.int64))


@register("one_hot")
def _one_hot(ctx, ins, attrs):
    x = single(ins, "X")
    depth = attrs["depth"]
    idx = x.reshape(x.shape[:-1] if x.shape and x.shape[-1] == 1 else x.shape)
    return _out(jax.nn.one_hot(idx.astype(jnp.int32), depth, dtype=jnp.float32))


@register("increment")
def _increment(ctx, ins, attrs):
    x = single(ins, "X")
    return _out(x + jnp.asarray(attrs.get("step", 1.0), dtype=x.dtype))


@register("is_empty")
def _is_empty(ctx, ins, attrs):
    x = single(ins, "X")
    return _out(jnp.asarray(x.size == 0))


@register("multiplex")
def _multiplex(ctx, ins, attrs):
    ids = single(ins, "Ids").reshape(-1).astype(jnp.int32)
    xs = jnp.stack(ins["X"], axis=0)  # [n_candidates, batch, ...]
    rows = jnp.arange(ids.shape[0])
    return _out(xs[ids, rows])


@register("cos_sim")
def _cos_sim(ctx, ins, attrs):
    x, y = single(ins, "X"), single(ins, "Y")
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / jnp.maximum(xn * yn, 1e-12)
    return {"Out": [out], "XNorm": [xn], "YNorm": [yn]}


@register("l2_normalize_raw")
def _l2_normalize(ctx, ins, attrs):
    x = single(ins, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


def _wn_axes(x, dim):
    return tuple(i for i in range(x.ndim) if i != dim) if dim is not None \
        else tuple(range(x.ndim))


@register("wn_norm")
def _wn_norm(ctx, ins, attrs):
    """||X|| over every axis except attr dim (weight-norm g init)."""
    x = single(ins, "X")
    dim = attrs.get("dim")
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=_wn_axes(x, dim)))
    return _out(n.reshape(-1))


@register("weight_norm")
def _weight_norm(ctx, ins, attrs):
    """W = G * V / ||V|| (parity: layer_helper.py __weight_normalize —
    there a 9-op sub-graph; here one op whose jax.vjp yields the G and V
    gradients)."""
    g = single(ins, "G")
    v = single(ins, "V")
    dim = attrs.get("dim")
    axes = _wn_axes(v, dim)
    norm = jnp.sqrt(jnp.sum(jnp.square(v), axis=axes, keepdims=True))
    scale = g.reshape([v.shape[dim] if (dim is not None and i == dim) else 1
                       for i in range(v.ndim)]) if dim is not None \
        else g.reshape((1,) * v.ndim)
    return _out(v * (scale / jnp.maximum(norm, 1e-12)))
