"""lookup_table's gather, with a backward of its own.

The forward is `jnp.take`. The backward returns what jax's own transpose of
that gather returns: a dense `[V, D]` gradient in the table's dtype, every
repeated id's rows summed in float32 in the order they came, no row dropped.
What differs is who builds it. `zeros([V, D]).at[ids].add(g)` compiles on
XLA:TPU to a sort of the ids and one `fusion(kind=kCustom)` around the
scatter, and on the v5e that fusion costs two to five passes over the
table's bytes where it is fast (1.5 ms for 8192 rows into `[16384, 2048]`
float32, 3.8 for 16384 into `[50304, 2048]`) and up to 1.9 us a row where it
is not: 15.5 ms into `[37984, 2560]`, the longest device operation of the
SmallThinker cell's step, 59 into `[32768, 5120]`, and 6 to 15 ms by how
often ids repeat; which it is follows a row's lane tiles, the table's rows
and the number of ids in no order one could write a rule on (my chip runs,
PR 41). The same scatter over pieces of a row (`[V * k, D / k]`, ids
`id * k + j`) is fast, 3.2 ms, but its result is another layout of the same
bytes and the copy back holds two tables at the step's fullest moment (+0.44
GiB compiled, AOT compile, PR 41). So on one TPU `ptpu_embedding_grad`
writes the table of rows of 8 KiB and more: XLA sorts the ids and gathers g's rows in that order; the
kernel walks the vocabulary in blocks of rows, zero-fills a block in VMEM,
adds the block's share of the sorted rows one by one (read from HBM a chunk
ahead) and lets the pipeline write the block. Every element of the table is
written once and every row of g read once, whatever the ids: a block's work
is its rows, and the rows add up to N. 1.4 ms at SmallThinker's shape and
0.4 to 2.3 at the other decoder cells', each under XLA's own (PERF.md
section 6, PR 41). The sum of a run of equal ids is XLA's, bit for bit: the sort is
stable and the rows are added in its order.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from .pallas_import import kernel_entry, pl, pltpu

from . import kernel_config

__all__ = ["take_rows", "grad_form", "dense_grad"]

_LANES = 128
# sorted rows a DMA brings from HBM: a sublane tile of float32 twice over
_CHUNK = 16
# the narrowest row the kernel takes, 8 KiB of float32. A row costs the
# kernel some 30 ns whatever its width and XLA's scatter a share of its two
# to five passes over the table: in the transformer cells (16384 ids into
# [32000, 512]) the two come out even, 0.56 against 0.51 ms a lookup in the
# step's trace, and every shape at which XLA's scatter was slow had wider
# rows than this (my chip runs, PR 41)
_MIN_WIDTH = 2048
# ids the kernel takes: Mosaic prefetches them into the v5e's 1 MiB of SMEM
# and refuses 2**18 (AOT compile, PR 41)
_MAX_ROWS = 1 << 17


def grad_form(rows, width, mesh=None):
    """Who builds the gradient of a `width`-wide table `rows` ids look into:
    `kernel` where the kernels are on (a TPU), the step is one device's (a
    Mosaic call does not partition), a row is whole lane tiles and at least
    _MIN_WIDTH wide, and the sorted ids fit the scalar memory they are
    prefetched into; else `scatter`, XLA's, what jax's own transpose of the
    gather gives."""
    if width % _LANES or width < _MIN_WIDTH or rows > _MAX_ROWS \
            or mesh is not None or not kernel_config.pallas_on("emb"):
        return "scatter"
    return "kernel"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def take_rows(w, ids, form):
    """w [V, D] at ids [N] (int32): [N, D]. `form` is grad_form's answer
    for the backward."""
    return jnp.take(w, ids, axis=0)


def _take_rows_fwd(w, ids, form):
    # the table is its own residual: a parameter, live anyway, and the
    # backward reads nothing of it but its shape and dtype
    return jnp.take(w, ids, axis=0), (w, ids)


def _take_rows_bwd(form, res, g):
    w, ids = res
    acc = jnp.promote_types(w.dtype, jnp.float32)
    if form == "kernel":
        dw = dense_grad(ids, g.astype(acc), w.shape[0])
    else:
        dw = jnp.zeros(w.shape, acc).at[ids].add(g.astype(acc))
    return dw.astype(w.dtype), None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _block_rows(vocab, width, itemsize):
    """Vocabulary rows a grid step writes: the table's budget of bytes
    (kernel_config.DEFAULT_TILES["emb"]) in whole sublane tiles, and no
    more than the table has."""
    budget = kernel_config.DEFAULT_TILES["emb"]["tile_bytes"]
    rows = max(8, budget // (width * itemsize) // 8 * 8)
    return min(rows, -(-vocab // 8) * 8)


def _kernel(start_ref, ids_ref, g_hbm, out_ref, buf, sem, *, block_rows):
    b = pl.program_id(0)
    s, e = start_ref[b], start_ref[b + 1]
    first = s // _CHUNK
    last = jnp.where(e > s, (e + _CHUNK - 1) // _CHUNK, first)
    out_ref[...] = jnp.zeros_like(out_ref)

    def copy(c, slot):
        return pltpu.make_async_copy(
            g_hbm.at[pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)],
            buf.at[slot], sem.at[slot])

    @pl.when(last > first)
    def _():
        copy(first, 0).start()

    def chunk(c, carry):
        slot = (c - first) % 2
        copy(c, slot).wait()

        @pl.when(c + 1 < last)
        def _():
            copy(c + 1, 1 - slot).start()

        def row(p, carry):
            r = ids_ref[p] - b * block_rows
            out_ref[pl.ds(r, 1), :] += buf[slot, pl.ds(p - c * _CHUNK, 1), :]
            return carry

        return lax.fori_loop(jnp.maximum(s, c * _CHUNK),
                             jnp.minimum(e, (c + 1) * _CHUNK), row, carry)

    lax.fori_loop(first, last, chunk, 0)


@kernel_entry("ptpu_embedding_grad",
              static_argnames=("vocab", "block_rows", "interpret"))
def _dense_grad(ids, g, vocab, block_rows, interpret):
    n, d = g.shape
    blocks = -(-vocab // block_rows)
    # as jnp.take reads them: a negative id counts from the end, an id
    # outside the table has no row (it sorts behind the last block)
    ids = jnp.where(ids < 0, ids + vocab, ids)
    ids = jnp.where((ids < 0) | (ids >= vocab), blocks * block_rows, ids)
    pad = -n % _CHUNK
    ids = jnp.pad(ids, (0, pad), constant_values=blocks * block_rows)
    ids, order = lax.sort_key_val(ids, jnp.arange(n + pad, dtype=jnp.int32))
    rows = jnp.take(jnp.pad(g, ((0, pad), (0, 0))), order, axis=0)
    start = jnp.searchsorted(
        ids, jnp.arange(blocks + 1, dtype=jnp.int32) * block_rows,
        side="left", method="compare_all").astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        out_shape=jax.ShapeDtypeStruct((vocab, d), g.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_rows, d),
                                   lambda b, start, ids: (b, 0)),
            scratch_shapes=[pltpu.VMEM((2, _CHUNK, d), g.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        interpret=interpret, name="ptpu_embedding_grad")(start, ids, rows)


def dense_grad(ids, g, vocab, block_rows=None, interpret=None):
    """The dense [vocab, D] sum of g's rows [N, D] by ids [N], through the
    kernel, in g's dtype (float32, or float64 for a table that is)."""
    if interpret is None:
        interpret = kernel_config.dispatch_platform() != "tpu"
    if block_rows is None:
        block_rows = _block_rows(vocab, g.shape[1], g.dtype.itemsize)
    return _dense_grad(ids, g, vocab, int(block_rows), bool(interpret))
