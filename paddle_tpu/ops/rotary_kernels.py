"""rotary_embedding over whole heads as ONE pass: the Pallas kernel
`ptpu_rotary`, which reads every element of x once and writes it once.

The half-split rotation of a head x = [x1, x2] (D lanes, D a multiple of
128) by the angles of a token is

    y = [x1 cos - x2 sin, x2 cos + x1 sin]
      = x * cf + swap(x) * sf      cf = [cos, cos], sf = [-sin, sin]

with swap the exchange of a head's halves: a lane rotate by D / 2, one XLU
operation a vector register. As jax.numpy lines (`jnp.split` and
`jnp.concatenate` at lane 64 of a 128-lane head) XLA compiles it to three
passes: a float32 image of x written to HBM, the two rotated halves as two
half-empty arrays, and their join (784 MiB accessed for 128 MiB of traffic
at [1, 8192, 32, 128] bf16; described-v5e compile, PR 70).

x comes as the projection wrote it, rows of [B*T, H*D]; a grid step takes a
block of rows and one block of the two tables, [B*T, D] float32, that all
heads of a row share (`block_rows`: the three blocks together inside
DEFAULT_TILES["rope"], whatever H is: at one head the tables are four times
x's bytes). Inside, a loop over chunks of 32 rows: the chunk's angles are
read once, then a static loop over the H lane tiles of a row, so what the
body holds as values is a few vector registers however large the block is.
The products and the sum are float32, the very ones the jax.numpy lines
compute (x2 * (-sin) is -(x2 * sin) to the bit), rounded once to x's dtype:
the kernel's results are the rule's, element for element, and the tests
and `chip_smoke.py --phases P` hold them to that.

The rotation is linear in x and its transpose is the same rotation at the
negated angle, dx = dy * cf + swap(dy) * (-sf): the same kernel. Nothing
of x is kept for the backward pass, only the two small tables.

In a module of its own: jax keeps source locations inside a Mosaic call's
serialized kernel, so an edit above a kernel in pallas_kernels.py re-keys
every executable that holds one (PERF.md section 6, PR 27).
"""
import functools

import jax
import jax.numpy as jnp
from .pallas_import import kernel_entry, pl, pltpu

from . import kernel_config

__all__ = ["rotary", "applies", "block_rows", "tables"]

_F32 = jnp.float32
_LANES = 128
# rows of a block come in whole sublane tiles of a 2-byte dtype
_SUBLANES = 16
# the rows the kernel's body holds as values at a time: two such tiles, four
# of float32
_CHUNK = 32


def block_rows(n, width, itemsize, head_dim, tile_bytes):
    """Rows of x [n, width] a grid step takes, or None where the kernel has
    none to offer: the most whole sublane tiles that DIVIDE n and whose
    bytes, x's block and the block of each float32 table [rows, head_dim]
    with it, stay inside `tile_bytes`; all n where that is fewer. The step
    writes a block of x's size besides, and Mosaic holds every block twice.

    A block never reaches past the last row. In a compiled step XLA keeps
    arrays of a few MiB in VMEM and hands a Mosaic call its operands and
    result there; SmallThinker's step (k is [8192, 128] bf16, 2 MiB) with a
    last block that reached past the array's end in it did not come back
    from the chip: q's ragged blocks beside k's on the kernel, or k's
    ragged blocks alone, every run; the same step with blocks that divide
    8192 runs (my chip runs, PR 70: PERF.md section 6 has the variants).
    Alone, operands in HBM, the same ragged calls finish and are equal
    (`chip_smoke.py --phases P`): a kernel is read in the step it enters."""
    most = tile_bytes // (width * itemsize + 2 * head_dim * 4)
    if n <= most:
        return n
    for rows in range(most // _SUBLANES * _SUBLANES, 0, -_SUBLANES):
        if n % rows == 0:
            return rows
    return None


def applies(shape, itemsize, rotary_dim, layout):
    """Does the kernel compute this rotation of x [B, T, H, D]? The whole
    head turns (one swap of halves), in the half-split layout, a head is
    whole lane tiles, and the rows have a block (`block_rows`)."""
    if len(shape) != 4:
        return False
    b, t, h, d = shape
    return (layout == "half" and rotary_dim == d and d % _LANES == 0
            and block_rows(b * t, h * d, itemsize, d,
                           _tile_bytes()) is not None)


def _tile_bytes():
    return kernel_config.DEFAULT_TILES["rope"]["tile_bytes"]


def tables(cos, sin):
    """(cf, sf), [rows, D] float32 each, from the rule's cos and sin [B, T,
    1, D / 2]: cf = [cos, cos], sf = [-sin, sin]."""
    rows = cos.shape[0] * cos.shape[1]
    return (jnp.concatenate([cos, cos], -1).reshape(rows, -1),
            jnp.concatenate([-sin, sin], -1).reshape(rows, -1))


def _kernel(x_ref, cf_ref, sf_ref, y_ref, *, heads, head_dim):
    rows = x_ref.shape[0]

    def turn(at):
        # one chunk of rows: its angles once, then every head's lane tiles
        cf, sf = cf_ref[at, :], sf_ref[at, :]
        for h in range(heads):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            x32 = x_ref[at, lanes].astype(_F32)
            y = x32 * cf + pltpu.roll(x32, head_dim // 2, 1) * sf
            y_ref[at, lanes] = y.astype(y_ref.dtype)

    def chunk(i, carry):
        turn(pl.ds(pl.multiple_of(i * _CHUNK, _CHUNK), _CHUNK))
        return carry

    whole = rows // _CHUNK
    if whole:
        jax.lax.fori_loop(0, whole, chunk, None)
    if rows % _CHUNK:
        turn(slice(whole * _CHUNK, rows))


# A jax.jit of its own, everything but the arrays static
# (ops/pallas_import.py has the rule): q's and k's calls of every layer, forward
# and backward, trace the body once a shape. One read and one write of x: a
# recomputing loop replays it like a pass of XLA's own and does not keep its
# result (costs_its_bytes; ops/control_ops.py _kept_by).
@kernel_entry("ptpu_rotary", costs_its_bytes=True,
              static_argnames=("rows", "interpret"))
def _call(x, cf, sf, *, rows, interpret):
    n, width = x.shape
    head_dim = cf.shape[1]

    def block(i):
        return (i, 0)

    return pl.pallas_call(
        functools.partial(_kernel, heads=width // head_dim,
                          head_dim=head_dim),
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, width), block),
                  pl.BlockSpec((rows, head_dim), block),
                  pl.BlockSpec((rows, head_dim), block)],
        out_specs=pl.BlockSpec((rows, width), block),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="ptpu_rotary",
    )(x, cf, sf)


def _turn(x, cf, sf):
    rows = block_rows(x.shape[0], x.shape[1], x.dtype.itemsize, cf.shape[1],
                      _tile_bytes())
    return _call(x, cf, sf, rows=rows,
                 interpret=kernel_config.dispatch_platform() != "tpu")


@jax.custom_vjp
def _rotary(x, cf, sf):
    return _turn(x, cf, sf)


def _rotary_fwd(x, cf, sf):
    return _turn(x, cf, sf), (cf, sf)


def _rotary_bwd(tables_kept, dy):
    cf, sf = tables_kept
    # no cotangent for the tables: the rule takes this path for integer
    # positions only
    return _turn(dy, cf, -sf), None, None


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def rotary(x, cf, sf):
    """y [B, T, H, D] in x's dtype: every head of x turned by its token's
    angles, cf and sf [B*T, D] float32 of `tables` (module docstring).
    Differentiable in x. The rows of a grid step are block_rows' at
    kernel_config.DEFAULT_TILES["rope"]; x is one `applies` says yes to."""
    if x.ndim != 4 or x.shape[3] % _LANES or cf.shape != (
            x.shape[0] * x.shape[1], x.shape[3]) or sf.shape != cf.shape \
            or cf.dtype != _F32 or sf.dtype != _F32:
        raise ValueError(
            "rotary kernel: x [B, T, H, D] with D a multiple of %d and "
            "float32 tables [B*T, D]; got x %s, cf %s, sf %s"
            % (_LANES, x.shape, cf.shape, sf.shape))
    b, t, h, d = x.shape
    return _rotary(x.reshape(b * t, h * d), cf, sf).reshape(x.shape)
