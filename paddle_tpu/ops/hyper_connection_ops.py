"""Lowerings of the hyper-connection ops (ops/mhc_kernels.py has the
mathematics): a residual path of `streams` vectors a token, kept side by
side as [..., streams * C], around a sub-layer. No reference-era op has
more than one residual stream."""
import numpy as np

import jax.numpy as jnp

from ..core.registry import counts, register, shapes_from, single
from ..observability.registry import REGISTRY
from .kernel_config import pallas_on


def mhc_path(mesh, shape, streams):
    """"kernel" where mhc_kernels' passes run for a stream of `shape` [...,
    streams * C]: kernel_config.pallas_on("mhc") (a TPU, or PADDLE_TPU_PALLAS), no
    mesh, and blocks that divide the shape; else "xla", the jax.numpy
    passes. The one place that decides; the layer counter reads it too."""
    from .mhc_kernels import applies
    rows = int(np.prod(shape[:-1]))
    fits = shape[-1] % streams == 0 and applies(
        rows, streams, shape[-1] // streams)
    return "kernel" if fits and mesh is None and pallas_on("mhc") else "xla"


def _kernels(ctx, shape, attrs):
    return mhc_path(ctx.mesh, shape, attrs["streams"]) == "kernel"


def _one_stream(shape, attrs):
    return shape[:-1] + (shape[-1] // attrs["streams"],)


def _all_streams(shape, attrs):
    return shape[:-1] + (shape[-1] * attrs["streams"],)


@register("mhc_pre", calls_pallas=True, infer=shapes_from(
    Out=("X", _one_stream), Stream="X",
    Coef=("X", lambda shape, attrs: shape[:-1] + (128,), "float32")))
def _mhc_pre(ctx, ins, attrs):
    """Out [..., C], the streams X [..., n*C] read into a sub-layer by H_pre;
    Coef [..., 128] float32, the token's H_pre, H_post and H_res (columns
    [0, n), [n, 2n), [2n, 2n + n*n), H_res by rows); Stream, X itself, for
    mhc_post to read: X then has one consumer, and its gradient's two parts
    meet in this op's backward kernel. Phi [n*C, n*n + 2n], Bias [n*n +
    2n] and Alpha [3] are float32 and so is everything between X and Coef,
    whatever X's dtype."""
    from . import mhc_kernels
    x = single(ins, "X")
    h, coef, stream = mhc_kernels.pre(
        x, single(ins, "Phi"), single(ins, "Alpha"), single(ins, "Bias"),
        attrs["streams"], attrs["sinkhorn_iters"], attrs["epsilon"],
        (attrs["clamp_min"], attrs["clamp_max"]),
        _kernels(ctx, x.shape, attrs))
    return {"Out": [h], "Coef": [coef], "Stream": [stream]}


@counts("mhc_pre")
def _count_hyper_connection_layer(ctx, attrs, ins):
    x = ins["X"][0]
    REGISTRY.counter(
        "ptpu_hyper_connection_layers_total",
        "mhc_pre ops lowered (forward ops, not a grad op's replay): the "
        "sub-layers that read from and write to several residual streams, "
        "by the streams, a stream's width, the Sinkhorn steps and the path "
        "of the passes over the streams (the Pallas kernels, or XLA)"
    ).inc(streams=str(attrs["streams"]),
          width=str(x.shape[-1] // attrs["streams"]),
          sinkhorn_iters=str(attrs["sinkhorn_iters"]),
          path=mhc_path(ctx.mesh, x.shape, attrs["streams"]))


@register("mhc_post", calls_pallas=True, infer=shapes_from(Out="X"))
def _mhc_post(ctx, ins, attrs):
    """Out[i] = sum_j H_res[i, j] X[j] + H_post[i] Y: the streams mixed and
    the sub-layer's output Y [..., C] written into them, by mhc_pre's
    Coef."""
    from . import mhc_kernels
    x = single(ins, "X")
    y = single(ins, "Y").astype(x.dtype)
    return {"Out": [mhc_kernels.post(x, y, single(ins, "Coef"),
                                     attrs["streams"],
                                     _kernels(ctx, x.shape, attrs))]}


@register("mhc_expand", calls_pallas=True,
          infer=shapes_from(Out=("X", _all_streams)))
def _mhc_expand(ctx, ins, attrs):
    """X [..., C] -> `streams` copies side by side, the streams' start.
    Under AMP the streams are bfloat16 from here on: a layer's passes over
    them are bound by their bytes."""
    from . import mhc_kernels
    x = single(ins, "X")
    if getattr(ctx, "amp", False) and x.dtype == jnp.float32:
        x = x.astype(jnp.bfloat16)
    n = attrs["streams"]
    wide = x.shape[:-1] + (x.shape[-1] * n,)
    return {"Out": [mhc_kernels.expand(x, n, _kernels(ctx, wide, attrs))]}


@register("mhc_reduce", calls_pallas=True,
          infer=shapes_from(Out=("X", _one_stream)))
def _mhc_reduce(ctx, ins, attrs):
    """X [..., streams * C] -> the streams' sum [..., C], the readout."""
    from . import mhc_kernels
    x = single(ins, "X")
    return {"Out": [mhc_kernels.reduce(x, attrs["streams"],
                                       _kernels(ctx, x.shape, attrs))]}
