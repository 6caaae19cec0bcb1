"""causal_conv1d as two streaming Pallas kernels: the short causal depthwise
convolution over time that feeds a linear-attention mixer (three layers in
four of Qwen3-Next, 4 taps over 8192 channels), forward and backward, each
reading and writing every element once.

    y_t[c] = act(sum_m w[m, c] x_(t-K+1+m)[c])      x_s = 0 for s < 0

The op is bound by bytes and by nothing else: 2 K multiply-adds an element.
`ptpu_causal_conv1d_fwd` walks a (sequence, channel block)'s T tiles from
the first to the last; a tile's float32 copy lands in VMEM scratch behind
the last rows of the tile before it (zeros at a sequence's first tile: the
grid is sequential on the chip), and an inner loop takes _ROWS rows of
that scratch at a time with the _HALO rows before them and rolls the
window once a tap. `ptpu_causal_conv1d_bwd` walks the tiles from the last to
the first: it recomputes the pre-activation from the x tile and the rows
before it (a second, _HALO-row view of x that ends where the tile starts:
the reverse walk has not been there, so no carry can bring them), forms dz
= dy act'(pre), leaves the tile's first rows of dz in scratch for the tile
before it, and adds a tile's part of dw into an accumulator written once a
(sequence, channel block). From the forward to the backward pass only x
and the filter live.

Precision: products, sums, SiLU, its derivative and dw are float32
whatever x's dtype is; y and dx are rounded once, to x's dtype.

In a module of its own: jax keeps source locations inside a Mosaic call's
serialized kernel, so an edit above a kernel in pallas_kernels.py re-keys
every executable that holds one (PERF.md section 6, PR 27).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from .pallas_import import kernel_entry, pl, pltpu

from . import kernel_config

__all__ = ["causal_conv1d", "applies", "blocks"]

_F32 = jnp.float32
# rows of scratch ahead of (forward) or behind (backward) a tile, and of the
# backward pass's second view of x: one sublane tile of a 2-byte dtype, so
# every block and every copy of whole halos stays aligned
_HALO = 16
_LANES = 128
# channels a grid step at most: rows of 1 KiB in bf16 are long enough for the
# DMA, and past 512 the backward kernel's registers spill (kernel_config has
# the sweep)
_BLOCK_C = 512
# rows the kernels' inner loop takes at a time: its operands stay in vector
# registers from the K shifted loads to the store
_ROWS = 32


def applies(t, c, width):
    """Do the kernels' blocks divide x [B, t, c] under a filter of `width`
    taps? Whole sublane tiles of rows, whole lanes of channels, and a halo
    that holds the width - 1 rows a tile needs of its neighbour."""
    return t % _HALO == 0 and c % _LANES == 0 and 1 <= width <= _HALO + 1


def blocks(t, c, tile_bytes):
    """(block_t, block_c) of one grid step for x [B, t, c]: block_c the
    largest multiple of 128 lanes up to _BLOCK_C that divides c, block_t the
    largest multiple of _ROWS (of _HALO where t has no such divisor)
    dividing t whose float32 tile [block_t, block_c] stays inside
    `tile_bytes`: a tile that is long in T pays the halo less often."""
    block_c = max(n for n in range(_LANES, min(c, _BLOCK_C) + 1, _LANES)
                  if c % n == 0)
    most = max(_HALO, tile_bytes // (block_c * 4))
    fits = [n for n in range(_HALO, min(t, most) + 1, _HALO) if t % n == 0]
    whole = [n for n in fits if n % _ROWS == 0]
    return (whole or fits)[-1], block_c


def _rows(block_t):
    return _ROWS if block_t % _ROWS == 0 else _HALO


def _sigmoid(z):
    """1 / (1 + exp(-z)) in float32: the EUP's approximate reciprocal and
    one Newton step, which squares its error (to 1e-7 of the float32 path's
    logistic on the v5e, where the logistic's own divide costs the forward
    kernel 4 % and the backward 6 %; my chip run, PR 34). exp stays finite:
    past z = -80 the result is 2e-35 either way."""
    d = 1.0 + jnp.exp(jnp.minimum(-z, 80.0))
    r = pl.reciprocal(d, approx=True)
    return r * (2.0 - d * r)


def _earlier(window, width):
    """[x_(t-K+1+m) for m in 0 .. K - 1] over some rows t, from a window of
    _HALO rows before them and the rows themselves. A roll of the whole
    window and an aligned slice: the sublane rotates run on the XLU, where
    an unaligned slice costs the VPU a shift and a select a register (the
    backward kernel 0.57 -> 0.46 ms at [1, 4096, 8192] bf16; my chip run,
    PR 34)."""
    return [pltpu.roll(window, width - 1 - m, 0)[_HALO:] if m < width - 1
            else window[_HALO:] for m in range(width)]


def _later(window, width, rows):
    """[dz_(t+K-1-m) for m in 0 .. K - 1] over `rows` rows t, from a window
    of the rows themselves and _HALO rows after them."""
    return [pltpu.roll(window, rows + _HALO - (width - 1 - m), 0)[:rows]
            if m < width - 1 else window[:rows] for m in range(width)]


def _taps(w, xs):
    z = w[0] * xs[0]
    for w_m, x_m in zip(w[1:], xs[1:]):
        z += w_m * x_m
    return z


def _fwd_kernel(x_ref, w_ref, y_ref, xe_ref, *, width, silu, block_t):
    """One grid step: tile j of a (sequence, channel block). xe_ref [_HALO +
    block_t, block_c] float32 holds the rows before the tile, then the
    tile."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        xe_ref[:_HALO] = jnp.zeros((_HALO,) + xe_ref.shape[1:], _F32)

    @pl.when(pl.program_id(2) > 0)
    def _():
        xe_ref[:_HALO] = xe_ref[block_t:]
    xe_ref[_HALO:] = x_ref[0].astype(_F32)
    rows = _rows(block_t)
    w = [w_ref[m:m + 1] for m in range(width)]

    def step(i, carry):
        r = pl.multiple_of(i * rows, rows)
        z = _taps(w, _earlier(xe_ref[pl.ds(r, _HALO + rows)], width))
        if silu:
            z = z * _sigmoid(z)
        y_ref[0, pl.ds(r, rows)] = z.astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, block_t // rows, step, 0)


def _bwd_kernel(x_ref, xh_ref, dy_ref, w_ref, dx_ref, dw_ref, xe_ref,
                dze_ref, *, width, silu, block_t):
    """One grid step of the reverse walk: tile j = T / block_t - 1 - step.
    xe_ref as the forward kernel's; dze_ref [block_t + _HALO, block_c]
    float32 holds the tile's dz, then the first rows of the later tile's."""
    first = pl.program_id(2) == 0                   # the sequence's LAST tile

    @pl.when(first)
    def _():
        dze_ref[block_t:] = jnp.zeros((_HALO,) + dze_ref.shape[1:], _F32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    @pl.when(jnp.logical_not(first))
    def _():
        dze_ref[block_t:] = dze_ref[:_HALO]
    head = xh_ref[0].astype(_F32)
    # the sequence's first tile: the view is clamped onto the tile's own
    # first rows, and what lies before a sequence is zeros
    xe_ref[:_HALO] = jnp.where(
        pl.program_id(2) == pl.num_programs(2) - 1, 0.0, head)
    xe_ref[_HALO:] = x_ref[0].astype(_F32)
    rows = _rows(block_t)
    n = block_t // rows
    w = [w_ref[m:m + 1] for m in range(width)]
    block_c = xe_ref.shape[1]

    def step(i, dw):
        r = pl.multiple_of((n - 1 - i) * rows, rows)
        xs = _earlier(xe_ref[pl.ds(r, _HALO + rows)], width)
        dz = dy_ref[0, pl.ds(r, rows)].astype(_F32)
        if silu:                        # d silu / dz = s (1 + z (1 - s))
            z = _taps(w, xs)
            s = _sigmoid(z)
            dz = dz * (s * (1.0 + z * (1.0 - s)))
        dze_ref[pl.ds(r, rows)] = dz
        dx = _taps(w, _later(dze_ref[pl.ds(r, rows + _HALO)], width, rows))
        dx_ref[0, pl.ds(r, rows)] = dx.astype(dx_ref.dtype)
        # a sublane tile of partial sums a tap: whole-register adds here,
        # one reduction over the 8 sublanes a grid step
        return tuple(
            acc + (x * dz).reshape(rows // 8, 8, block_c).sum(0)
            for acc, x in zip(dw, xs))

    dw = lax.fori_loop(0, n, step,
                       (jnp.zeros((8, block_c), _F32),) * width)
    dw_ref[0] += jnp.concatenate(
        [acc.sum(0, keepdims=True) for acc in dw], 0)


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _interpret():
    return kernel_config.dispatch_platform() != "tpu"


def _tile(x, tile):
    if tile is not None:
        return tile
    return blocks(x.shape[1], x.shape[2],
                  kernel_config.DEFAULT_TILES["conv"]["tile_bytes"])


# The two calls are jax.jits of their own, everything but the arrays static
# (ops/pallas_import.py has the rule): a model's layers call them at one
# shape, and a step traces each kernel's body once and not once a layer.
@kernel_entry("ptpu_causal_conv1d_fwd",
              static_argnames=("silu", "tile", "interpret"))
def _fwd_call(x, wt, *, silu, tile, interpret):
    b, t, c = x.shape
    width = wt.shape[0]
    block_t, block_c = tile
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, silu=silu,
                          block_t=block_t),
        # T innermost: a (sequence, channel block) walks all its tiles
        # before the next one reuses the scratch
        grid=(b, c // block_c, t // block_t),
        in_specs=[_vmem((1, block_t, block_c), lambda i, j, k: (i, k, j)),
                  _vmem((width, block_c), lambda i, j, k: (0, j))],
        out_specs=_vmem((1, block_t, block_c), lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + block_t, block_c), _F32)],
        interpret=interpret,
        name="ptpu_causal_conv1d_fwd",
    )(x, wt)


@kernel_entry("ptpu_causal_conv1d_bwd",
              static_argnames=("silu", "tile", "interpret"))
def _bwd_call(x, wt, dy, *, silu, tile, interpret):
    b, t, c = x.shape
    width = wt.shape[0]
    block_t, block_c = tile
    nt, per = t // block_t, block_t // _HALO

    def tile_at(i, j, k):               # tiles from the last to the first
        return (i, nt - 1 - k, j)

    def halo_at(i, j, k):               # the _HALO rows that end at the tile
        return (i, jnp.maximum((nt - 1 - k) * per - 1, 0), j)

    return pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, silu=silu,
                          block_t=block_t),
        grid=(b, c // block_c, nt),
        in_specs=[_vmem((1, block_t, block_c), tile_at),
                  _vmem((1, _HALO, block_c), halo_at),
                  _vmem((1, block_t, block_c), tile_at),
                  _vmem((width, block_c), lambda i, j, k: (0, j))],
        out_specs=[_vmem((1, block_t, block_c), tile_at),
                   _vmem((1, width, block_c), lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, width, c), _F32)],
        scratch_shapes=[pltpu.VMEM((_HALO + block_t, block_c), _F32),
                        pltpu.VMEM((block_t + _HALO, block_c), _F32)],
        interpret=interpret,
        name="ptpu_causal_conv1d_bwd",
    )(x, x, dy, wt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(x, w, silu, tile):
    return _fwd_call(x, w.T.astype(_F32), silu=silu, tile=_tile(x, tile),
                     interpret=_interpret())


def _conv_fwd(x, w, silu, tile):
    return _conv(x, w, silu, tile), (x, w)


def _conv_bwd(silu, tile, res, dy):
    x, w = res
    dx, dw = _bwd_call(x, w.T.astype(_F32), dy.astype(x.dtype), silu=silu,
                       tile=_tile(x, tile), interpret=_interpret())
    return dx, dw.sum(0).T.astype(w.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv1d(x, w, silu=False, tile=None):
    """y [B, T, C] in x's dtype for x [B, T, C] and the filter w [C, K]
    (module docstring), differentiable in both. T a multiple of 16, C of
    128, K <= 17 (`applies`). tile: (block_t, block_c) of a sweep or a
    test, else `blocks` of kernel_config.DEFAULT_TILES["conv"]."""
    if x.ndim != 3 or w.ndim != 2 or w.shape[0] != x.shape[2] \
            or not applies(x.shape[1], x.shape[2], w.shape[1]):
        raise ValueError(
            "causal_conv1d kernels: x [B, T, C] with T a multiple of %d and "
            "C of %d, w [C, K] with K <= %d; got x %s, w %s"
            % (_HALO, _LANES, _HALO + 1, x.shape, w.shape))
    return _conv(x, w, bool(silu), None if tile is None else tuple(tile))
