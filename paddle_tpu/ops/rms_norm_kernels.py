"""The transpose of rms_norm over a head as ONE pass: the Pallas kernel
`ptpu_rms_norm_bwd`, which reads q's (k's) rows and their cotangent in the
projection's own layout and writes dx once.

The norm over the last axis of x [B, T, H, D] under a weight [D] is, a head,

    y = x32 * rsqrt(mean_D(x32^2) + eps) * scale          (float32, rounded
                                                           once to x's dtype)

(`ops/nn_ops.py _rms_norm_math`). The FORWARD pass stays those jax.numpy
lines, and XLA's to fuse: in a compiled step it hands them the projection's
float32 accumulator and the rotary's lines behind them their float32
result, so q and k are rounded to bf16 once, where the attention core reads
them. A forward kernel (built and measured, PR 72: SDAR +8.9 to +9.2 %
where this form gives +3.9 to +5.1) rounds at its own boundaries, once or
twice more, and Laguna's `reference` verdict then failed on 2 of 7 and 2 of 8
seeds (a token whose routing the reference calls decided went to another
expert), 0 of 26 without: PERF.md section 6.

The transpose of those lines, as XLA compiles it over rows that
`ptpu_rotary` pins to [B*T, H*D], lays the float32 image of x out
heads-outside-tokens for the reductions and back and relays the [B*T, H]
statistics four times (2,152 MiB accessed for 256 MiB of traffic at
[1, 8192, 32, 128] bf16, forward and transpose; described-v5e compile, PR
70). Here, one pass over x's float32 image and dy, both [B*T, H*D] (the
image is `x.astype(float32)` written OUTSIDE the kernel, `_rms_norm_bwd`
says why: a Mosaic call that read the bf16 x would make the projection's
rounding real for the forward pass too, and Laguna's verdict failed so, 1
of 7 seeds). A grid step takes a block of rows
(`rotary_kernels.block_rows` at no table: whole sublane tiles that DIVIDE
the rows) of a GROUP of heads (`_group`: up
to eight, a divisor of H; the groups are the grid's second axis); inside, a
loop over chunks of 32 rows and a static loop over the group's heads, D
lanes each, so what the body holds as values is a few vector registers
however large the block is, and what jax traces and lowers is eight heads'
lines whatever H is. rstd is computed again from x (nothing is kept across
the passes but the op's own inputs: no float32 image, no statistics), and

    g = dy32 * scale    xh = x32 * rstd
    dx = rstd * (g - xh * mean_D(g * xh))
    dscale = sum over rows and heads of dy32 * xh

A head's two sums are lane reductions (across D / 128 registers first where
D is wider than a tile). dscale accumulates in float32 in one resident
[8, D] block over the whole grid, which is sequential (eight sublanes of
partial sums, which XLA adds). The tests and `chip_smoke.py --phases R` hold
dx and dscale to the transpose jax derives from the lines.

`zero_centered` stays outside: 1 + scale is a [D] array XLA makes, and the
weight's gradient passes through it unchanged.

In a module of its own: jax keeps source locations inside a Mosaic call's
serialized kernel, so an edit above a kernel in pallas_kernels.py re-keys
every executable that holds one (PERF.md section 6, PR 27).
"""
import functools

import jax
import jax.numpy as jnp
from .pallas_import import kernel_entry, pl

from . import kernel_config
from .rotary_kernels import block_rows as _rotary_block_rows

__all__ = ["rms_norm", "applies", "block_rows"]

_F32 = jnp.float32
_LANES = 128
# the rows the kernel's body holds as values at a time: two sublane tiles of
# a 2-byte dtype, four of float32
_CHUNK = 32
# the sublanes of the resident block that holds dscale's partial sums
_PARTIALS = 8
# the most heads a grid step takes (_group)
_GROUP = 8


def _tile_bytes():
    return kernel_config.DEFAULT_TILES["rms_head"]["tile_bytes"]


def _group(heads):
    """The heads a grid step takes: the most, up to _GROUP, that divide
    `heads`."""
    return max(g for g in range(1, _GROUP + 1) if heads % g == 0)


def block_rows(n, width, itemsize):
    """Rows of a block [rows, width] of x [n, H*D], `width` the lanes of a
    group of heads, or None where the kernel has none to offer:
    rotary_kernels.block_rows' rule (whole sublane tiles that DIVIDE n, or
    all n; its docstring says why no block may be ragged) at
    DEFAULT_TILES["rms_head"] and no table beside x."""
    return _rotary_block_rows(n, width, itemsize, 0, _tile_bytes())


def applies(shape):
    """Does the kernel compute the transpose of the norm over the last axis
    of x [B, T, H, D]? A head is whole lane tiles and the rows of x's
    float32 image have a block."""
    if len(shape) != 4:
        return False
    b, t, h, d = shape
    return d % _LANES == 0 and block_rows(b * t, _group(h) * d, 4) is not None


def _chunks(rows, body):
    """body(slice of rows) over a block's rows, _CHUNK at a time and then
    what is left."""
    def chunk(i, carry):
        body(pl.ds(pl.multiple_of(i * _CHUNK, _CHUNK), _CHUNK))
        return carry

    whole = rows // _CHUNK
    if whole:
        jax.lax.fori_loop(0, whole, chunk, None)
    if rows % _CHUNK:
        body(slice(whole * _CHUNK, rows))


def _rstd(x32, eps):
    ms = jnp.sum(x32 * x32, axis=-1, keepdims=True) / x32.shape[-1]
    return jax.lax.rsqrt(ms + eps)


def _bwd_kernel(x_ref, dy_ref, scale_ref, dx_ref, dscale_ref, *, heads,
                head_dim, eps):
    scale = scale_ref[...]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def transpose(at):
        partial = None
        for h in range(heads):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            x32 = x_ref[at, lanes].astype(_F32)
            dy32 = dy_ref[at, lanes].astype(_F32)
            rstd = _rstd(x32, eps)
            xh = x32 * rstd
            g = dy32 * scale
            mean = jnp.sum(g * xh, axis=-1, keepdims=True) / head_dim
            dx_ref[at, lanes] = (rstd * (g - xh * mean)).astype(dx_ref.dtype)
            p = dy32 * xh
            partial = p if partial is None else partial + p
        n = partial.shape[0]
        if n % _PARTIALS == 0:
            # whole sublane tiles of float32: the sum is vector adds
            dscale_ref[...] += jnp.sum(
                partial.reshape(n // _PARTIALS, _PARTIALS, head_dim), axis=0)
        else:
            dscale_ref[0:1, :] += jnp.sum(partial, axis=0, keepdims=True)

    _chunks(x_ref.shape[0], transpose)


def _block(i, j):
    return (i, j)


def _whole(i, j):
    return (0, 0)


# A jax.jit of its own, everything but the arrays static
# (ops/pallas_import.py has the rule): q's and k's calls of every layer trace
# the body once a shape.
@kernel_entry("ptpu_rms_norm_bwd",
              static_argnames=("rows", "eps", "interpret"))
def _bwd_call(x, dy, scale, *, rows, eps, interpret):
    n, width = x.shape
    head_dim = scale.shape[1]
    heads = _group(width // head_dim)
    lanes = heads * head_dim
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, head_dim=head_dim,
                          eps=eps),
        grid=(n // rows, width // lanes),
        in_specs=[pl.BlockSpec((rows, lanes), _block),
                  pl.BlockSpec((rows, lanes), _block),
                  pl.BlockSpec((1, head_dim), _whole)],
        out_specs=[pl.BlockSpec((rows, lanes), _block),
                   pl.BlockSpec((_PARTIALS, head_dim), _whole)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, dy.dtype),
                   jax.ShapeDtypeStruct((_PARTIALS, head_dim), _F32)],
        interpret=interpret,
        name="ptpu_rms_norm_bwd",
    )(x, dy, scale)


def _static(x, head_dim):
    lanes = _group(x.shape[1] // head_dim) * head_dim
    return dict(rows=block_rows(x.shape[0], lanes, x.dtype.itemsize),
                interpret=kernel_config.dispatch_platform() != "tpu")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def _rms_norm(lines, x, scale, eps):
    return lines(x, scale, eps)


def _rms_norm_fwd(lines, x, scale, eps):
    # the op's own inputs and nothing else cross to the backward pass
    return lines(x, scale, eps), (x, scale)


def _rms_norm_bwd(lines, eps, kept, dy):
    x, scale = kept
    b, t, h, d = x.shape
    # x's float32 image, written here and not in the kernel so that it is
    # XLA's to place: where x is a projection's result, every reader of its
    # bf16 rounding is then a convert, XLA keeps the product's float32
    # accumulator for both passes and the forward lines norm THAT, as they
    # do with jax's own transpose behind them. A Mosaic call that reads the
    # bf16 x makes the rounding real, and the forward pass reads it too
    # (module docstring: Laguna's verdict).
    x32 = x.astype(_F32).reshape(b * t, h * d)
    dx, dscale = _bwd_call(x32, dy.reshape(b * t, h * d),
                           scale.reshape(1, d), eps=eps, **_static(x32, d))
    return dx.reshape(b, t, h, d), jnp.sum(dscale, axis=0)


_rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def rms_norm(lines, x, scale, eps):
    """`lines(x, scale, eps)`, y [B, T, H, D] in x's dtype (the jax.numpy
    lines of the norm over every head's D lanes under `scale` [D] float32),
    with the kernel as its transpose (module docstring). Differentiable in
    x and scale. `lines` is a module-level function (jax keeps it as a
    static argument); x is one `applies` says yes to."""
    if x.ndim != 4 or x.shape[3] % _LANES or scale.shape != x.shape[3:] \
            or scale.dtype != _F32:
        raise ValueError(
            "rms_norm kernel: x [B, T, H, D] with D a multiple of %d and a "
            "float32 scale [D]; got x %s, scale %s %s"
            % (_LANES, x.shape, scale.shape, scale.dtype))
    return _rms_norm(lines, x, scale, float(eps))
