"""The routed experts' grouped matmuls as Pallas kernels (`moe_ffn`:
parallel/moe.py `_grouped_matmul` on one TPU).

`[M, K] x [G, K, N] -> [M, N]`, the rows sorted by group and `sizes [G]` a
run-time array, is what `jax.lax.ragged_dot` computes. XLA's own kernel for
it on the v5e runs a layer's nine matmuls at 14-92 TFLOP/s at the 71 to 2048
rows a group the benchmark's cells have, and at 107 in the OLMoE cell's
trace; the three kernels here, one for each pass, at 21-154, and 160 there
(my chip runs, PR 50: PERF.md section 6):

  ptpu_expert_gmm_fwd       rows x the group's matrix
  ptpu_expert_gmm_drows     the same walk, d out x the matrix transposed
  ptpu_expert_gmm_dweights  [G, K, N]: a group's rows^T x its d out

All three walk one list of VISITS (`plan`): a visit is one tile of
`block_m` rows met by one group, the visits ordered by group and, within a
group, by tile. A tile that spans a group boundary is visited once a group;
an empty group has one visit, in which no row is its own (so its d weights
are written, as zeros); the tiles wholly past `sizes.sum()` have no visit and
are neither read nor written, so the rows from there on hold whatever the
buffer held (what `ragged_dot` does on the v5e: moe.routed_ffn's docstring).
The list is as long as it could be at most (tiles + G - 1, a static
number); the visits past the last real one name the last one's blocks
again, so that nothing is copied for them, and do nothing. The plan is
computed once a layer from `sizes` and handed to all nine calls.

A group's whole [K, N] matrix is one block (2-7 MB in bf16 at the cells'
widths; parallel/moe.py `matmul_route` is the rule), so a visit streams
`block_m` rows past it and the matrix is read from HBM once a group. Inside
a visit the rows are taken `_SUB` at a time, and only the sub-tiles that
hold a row of the group: a tile mostly another group's costs its own rows'
products. Operands go to the MXU in the dtype they come in, the accumulation
is float32, the result is in the dtype `ragged_dot` returns
(`preferred_element_type=lhs.dtype`).

d rows is the slowest pass where an expert is narrow (93 TFLOP/s beside
the forward's 133 at SmallThinker's [2560 x 768], 146 beside 152 at LFM2's
[2048 x 1792]; `ragged_dot`'s own d rows is its slowest too). Mosaic's
schedule shows a transposing push of a weight tile for every product there
where the forward has a plain one; computing `matrix @ rows^T` instead (the
rows latched, a twentieth of the pushes, the result transposed on the XLU)
read the same to 2 % and was taken out again (my chip runs, PR 50: PERF.md
section 6), so what holds that pass back is not known.

The three entry points are `jax.jit`s with their tiles static, as
moe.py's held-rows loops are: gate and up share a trace, a model's layers
share all, so a step is traced and lowered with six kernel instances (three
kernels at two shapes) however deep the model.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from .pallas_import import kernel_entry, pl, pltpu

from . import kernel_config

__all__ = ["KERNELS", "GroupPlan", "plan", "gmm", "gmm_drows",
           "gmm_dweights", "gmm_unit", "unwritten"]

# as pallas_kernels.KERNEL_NAMES and EXPERT_MATMUL_KERNELS list them
KERNELS = ("ptpu_expert_gmm_fwd", "ptpu_expert_gmm_drows",
           "ptpu_expert_gmm_dweights", "ptpu_expert_gmm_unit_fwd")

# Rows a trip of a visit's inner loop sends to the MXU: what decides how
# much of a tile shared by several groups is computed for nothing. Trips of
# 256 were 2-5 % slower at every cell's shape and whole tiles of 512 8-20 %
# (my chip run, PR 50; kernel_config.DEFAULT_TILES has the sweep).
_SUB = 128
# Mosaic's default scoped VMEM is 16 MiB of the v5e's 128; a resident matrix,
# its second buffer and d weights' float32 accumulator want up to 60
_VMEM_LIMIT = 100 << 20


@jax.tree_util.register_pytree_node_class
class GroupPlan(object):
    """The visits of `plan`: int32 arrays, and the row tile they were
    counted at as static data (a `jax.jit` or a `custom_vjp` carries the
    plan as an argument)."""

    def __init__(self, block_m, group_of, tile_of, offsets, visits):
        self.block_m = block_m
        self.group_of, self.tile_of = group_of, tile_of
        self.offsets, self.visits = offsets, visits

    def tree_flatten(self):
        return (self.group_of, self.tile_of, self.offsets,
                self.visits), self.block_m

    @classmethod
    def tree_unflatten(cls, block_m, arrays):
        return cls(block_m, *arrays)


def plan(sizes, rows, block_m=None):
    """The visits of the groups `sizes [G]` over `rows` sorted rows in tiles
    of `block_m`: on [G] and [tiles + G - 1] integers, no row is touched.

    -> GroupPlan(group_of [V], tile_of [V]: the group and the row tile of
    visit v; offsets [G + 1]: the groups' first rows and the last one's end;
    visits [1]: how many of the V = tiles + G - 1 are real). `block_m`
    where given (a sweep, a kernel test), else DEFAULT_TILES["gmm"]'s and no
    more than the rows there are, in whole sublane tiles of bfloat16."""
    if block_m is None:
        block_m = min(kernel_config.DEFAULT_TILES["gmm"]["block_m"],
                      -(-rows // 16) * 16)
    groups, tiles = sizes.shape[0], -(-rows // block_m)
    sizes = sizes.astype(jnp.int32)
    end = jnp.cumsum(sizes)
    start = end - sizes
    first_tile = jnp.minimum(start // block_m, tiles - 1)
    met = jnp.where(sizes > 0, -(-end // block_m) - start // block_m, 1)
    visit_end = jnp.cumsum(met)
    v = jnp.minimum(jnp.arange(tiles + groups - 1, dtype=jnp.int32),
                    visit_end[-1] - 1)
    group_of = jnp.sum(visit_end[None, :] <= v[:, None], axis=1,
                       dtype=jnp.int32)
    tile_of = first_tile[group_of] + v - (visit_end - met)[group_of]
    return GroupPlan(block_m, group_of, tile_of.astype(jnp.int32),
                     jnp.concatenate([jnp.zeros((1,), jnp.int32), end]),
                     visit_end[-1:])


def _visit(group_of, tile_of, offsets, block_m):
    """(group, [lo, hi): the rows of this visit's tile that are its group's,
    counted from the tile's first)."""
    v = pl.program_id(0)
    group, first = group_of[v], tile_of[v] * block_m
    return (group, jnp.maximum(offsets[group] - first, 0),
            jnp.minimum(offsets[group + 1] - first, block_m))


def _own_rows(at, sub, lo, hi):
    """[sub, 1]: which of the `sub` rows from `at` of the tile lie in
    [lo, hi)."""
    row = at + lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    return (row >= lo) & (row < hi)


def _rows_kernel(group_of, tile_of, offsets, visits, lhs_ref, rhs_ref,
                 out_ref, *, sub, transposed):
    """One visit of the forward matmul (or, `transposed`, of d rows: the
    group's matrix [K, N] contracted over N): the products of the sub-tiles
    that hold a row of the group, a row written where it is the group's."""
    block_m = lhs_ref.shape[0]
    _, lo, hi = _visit(group_of, tile_of, offsets, block_m)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    @pl.when((pl.program_id(0) < visits[0]) & (hi > lo))
    def _():
        def trip(s, carry):
            at = pl.multiple_of(s * sub, sub)
            rows = pl.ds(at, sub)
            acc = lax.dot_general(lhs_ref[rows, :], rhs_ref[0], dims,
                                  preferred_element_type=jnp.float32)
            out_ref[rows, :] = jnp.where(
                _own_rows(at, sub, lo, hi), acc.astype(out_ref.dtype),
                out_ref[rows, :])
            return carry

        lax.fori_loop(lo // sub, (hi + sub - 1) // sub, trip, 0)


def _weights_kernel(group_of, tile_of, offsets, visits, lhs_ref, dout_ref,
                    out_ref, acc_ref, *, sub):
    """One visit of d weights: the group's rows^T x its d out, summed in
    float32 over the group's visits (which are consecutive) and written
    with the last. Both operands are masked: a row that is not the group's
    may hold anything."""
    block_m = lhs_ref.shape[0]
    v, last = pl.program_id(0), visits[0] - 1
    group, lo, hi = _visit(group_of, tile_of, offsets, block_m)

    @pl.when((v == 0) | (group_of[jnp.maximum(v - 1, 0)] != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((v <= last) & (hi > lo))
    def _():
        def trip(s, carry):
            at = pl.multiple_of(s * sub, sub)
            rows = pl.ds(at, sub)
            own = _own_rows(at, sub, lo, hi)
            lhs = lhs_ref[rows, :]
            dout = dout_ref[rows, :]
            acc_ref[...] += lax.dot_general(
                jnp.where(own, lhs, jnp.zeros_like(lhs)),
                jnp.where(own, dout, jnp.zeros_like(dout)),
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(lo // sub, (hi + sub - 1) // sub, trip, 0)

    @pl.when((v == last) | ((v < last) & (
        group_of[jnp.minimum(v + 1, group_of.shape[0] - 1)] != group)))
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _sub_tile(block_m):
    """_SUB rows where the tile is whole sub-tiles, else the tile (a toy
    size)."""
    return _SUB if block_m % _SUB == 0 else block_m


_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=_VMEM_LIMIT)


def _rows_call(lhs, name, rhs, group_plan, transposed, interpret):
    m, width = lhs.shape
    block_m = group_plan.block_m
    out_width = rhs.shape[1 if transposed else 2]
    return pl.pallas_call(
        functools.partial(_rows_kernel, sub=_sub_tile(block_m),
                          transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, out_width), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(group_plan.group_of.shape[0],),
            in_specs=[
                pl.BlockSpec((block_m, width),
                             lambda v, g, t, o, n: (t[v], 0)),
                pl.BlockSpec((1,) + rhs.shape[1:],
                             lambda v, g, t, o, n: (g[v], 0, 0))],
            out_specs=pl.BlockSpec((block_m, out_width),
                                   lambda v, g, t, o, n: (t[v], 0))),
        compiler_params=_PARAMS, interpret=interpret, name=name,
    )(group_plan.group_of, group_plan.tile_of, group_plan.offsets,
      group_plan.visits, lhs, rhs)


@kernel_entry("ptpu_expert_gmm_fwd", static_argnames=("interpret",))
def _gmm(lhs, rhs, group_plan, *, interpret):
    return _rows_call(lhs, "ptpu_expert_gmm_fwd", rhs, group_plan, False,
                      interpret)


@kernel_entry("ptpu_expert_gmm_drows", static_argnames=("interpret",))
def _gmm_drows(dout, rhs, group_plan, *, interpret):
    return _rows_call(dout, "ptpu_expert_gmm_drows", rhs, group_plan, True,
                      interpret)


@kernel_entry("ptpu_expert_gmm_dweights",
              static_argnames=("dtype", "interpret"))
def _gmm_dweights(lhs, dout, group_plan, *, dtype, interpret):
    block_m = group_plan.block_m
    groups = group_plan.offsets.shape[0] - 1
    k, n = lhs.shape[1], dout.shape[1]
    return pl.pallas_call(
        functools.partial(_weights_kernel, sub=_sub_tile(block_m)),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(group_plan.group_of.shape[0],),
            in_specs=[
                pl.BlockSpec((block_m, k), lambda v, g, t, o, n: (t[v], 0)),
                pl.BlockSpec((block_m, n), lambda v, g, t, o, n: (t[v], 0))],
            out_specs=pl.BlockSpec((1, k, n),
                                   lambda v, g, t, o, n: (g[v], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)]),
        compiler_params=_PARAMS, interpret=interpret,
        name="ptpu_expert_gmm_dweights",
    )(group_plan.group_of, group_plan.tile_of, group_plan.offsets,
      group_plan.visits, lhs, dout)


def _interpret(interpret):
    if interpret is None:
        interpret = kernel_config.dispatch_platform() != "tpu"
    return bool(interpret)


def gmm(lhs, rhs, group_plan, interpret=None):
    """lhs [M, K] x rhs [G, K, N] by `group_plan` -> [M, N] in lhs's dtype;
    the rows past the groups' sum are not written."""
    return _gmm(lhs, rhs, group_plan, interpret=_interpret(interpret))


def gmm_drows(dout, rhs, group_plan, interpret=None):
    """d lhs of `gmm`: dout [M, N] x rhs [G, K, N]^T -> [M, K] in dout's
    dtype; the rows past the groups' sum are not written."""
    return _gmm_drows(dout, rhs, group_plan, interpret=_interpret(interpret))


def gmm_dweights(lhs, dout, group_plan, dtype=None, interpret=None):
    """d rhs of `gmm`: [G, K, N] in `dtype` (lhs's where None), group g's
    the float32 sum over its rows of lhs[m]^T dout[m]; zeros for an empty
    group. No row past the groups' sum is read into it."""
    return _gmm_dweights(lhs, dout, group_plan,
                         dtype=jnp.dtype(lhs.dtype if dtype is None
                                         else dtype),
                         interpret=_interpret(interpret))


# --- PR 65: below the three kernels above, so that no line of theirs moves ---
# (a Mosaic payload carries file and line). Two more calls, which between them
# leave no pass of a layer outside a tile below the groups' sum:
#
#   ptpu_expert_gmm_unit_fwd  the forward walk with the experts' unit as its
#                             epilogue: a row tile is read ONCE, met by the
#                             group's `w_gate` and `w_up` blocks (one matrix
#                             for an expert of two), and `gate`, `up` and
#                             hidden = unit(gate, up) are written for the
#                             group's own rows. The float32 accumulators are
#                             rounded to the output dtype FIRST and the unit
#                             is computed from the rounded values, as
#                             moe.py's units compute it from the stored
#                             arrays: the three outputs are two `gmm` calls'
#                             and the jax.numpy unit's to the bit (on the
#                             interpreter; Mosaic's logistic is its own).
#   ptpu_expert_rows_unwritten  a call that writes nothing: its output is a
#                             buffer of sorted rows as the allocator left it,
#                             what the loops over the held tiles start their
#                             carry from where a fill of zeros was a pass over
#                             all the rows for the sake of tiles nothing reads
#                             (moe.py `_sorted_rows_start`).


def _unit_kernel(group_of, tile_of, offsets, visits, lhs_ref, *refs, sub,
                 unit):
    """One visit of `gmm_unit`: refs are (w_gate, w_up, gate, up, hidden),
    or (w_up, up, hidden) for experts of two matrices."""
    gated = len(refs) == 5
    if gated:
        gate_w, up_w, gate_ref, up_ref, hidden_ref = refs
    else:
        up_w, up_ref, hidden_ref = refs
    block_m = lhs_ref.shape[0]
    _, lo, hi = _visit(group_of, tile_of, offsets, block_m)
    dims = (((1,), (0,)), ((), ()))

    @pl.when((pl.program_id(0) < visits[0]) & (hi > lo))
    def _():
        def trip(s, carry):
            at = pl.multiple_of(s * sub, sub)
            rows = pl.ds(at, sub)
            own = _own_rows(at, sub, lo, hi)
            lhs = lhs_ref[rows, :]

            def product(w_ref, out_ref):
                out = lax.dot_general(
                    lhs, w_ref[0], dims, preferred_element_type=jnp.float32
                ).astype(out_ref.dtype)
                out_ref[rows, :] = jnp.where(own, out, out_ref[rows, :])
                return out

            up = product(up_w, up_ref)
            hidden = unit(product(gate_w, gate_ref), up) if gated \
                else unit(up)
            hidden_ref[rows, :] = jnp.where(own, hidden, hidden_ref[rows, :])
            return carry

        lax.fori_loop(lo // sub, (hi + sub - 1) // sub, trip, 0)


@kernel_entry("ptpu_expert_gmm_unit_fwd",
              static_argnames=("unit", "interpret"))
def _gmm_unit(lhs, weights, group_plan, *, unit, interpret):
    m, width = lhs.shape
    block_m = group_plan.block_m
    out_width = weights[0].shape[2]

    def tile(w):
        return pl.BlockSpec((block_m, w), lambda v, g, t, o, n: (t[v], 0))

    return pl.pallas_call(
        functools.partial(_unit_kernel, sub=_sub_tile(block_m), unit=unit),
        out_shape=[jax.ShapeDtypeStruct((m, out_width), lhs.dtype)]
        * (len(weights) + 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(group_plan.group_of.shape[0],),
            in_specs=[tile(width)] + [
                pl.BlockSpec((1,) + w.shape[1:],
                             lambda v, g, t, o, n: (g[v], 0, 0))
                for w in weights],
            out_specs=[tile(out_width)] * (len(weights) + 1)),
        compiler_params=_PARAMS, interpret=interpret,
        name="ptpu_expert_gmm_unit_fwd",
    )(group_plan.group_of, group_plan.tile_of, group_plan.offsets,
      group_plan.visits, lhs, *weights)


def gmm_unit(lhs, w_gate, w_up, group_plan, unit, interpret=None):
    """(gate, up, hidden) = (lhs x w_gate, lhs x w_up, unit(gate, up)) by
    `group_plan`, each [M, F] in lhs's dtype and each as `gmm` and `unit`
    (a function of arrays in that dtype, hashable: it is a static argument)
    would give it; `w_gate` None: (up, unit(up)). The rows past the groups'
    sum are not written."""
    weights = (w_up,) if w_gate is None else (w_gate, w_up)
    return _gmm_unit(lhs, weights, group_plan, unit=unit,
                     interpret=_interpret(interpret))


def _unwritten_kernel(like_ref, out_ref):
    del like_ref, out_ref


@kernel_entry("ptpu_expert_rows_unwritten",
              static_argnames=("shape", "dtype", "interpret"))
def _unwritten(like, *, shape, dtype, interpret):
    whole = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _unwritten_kernel, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[whole], out_specs=whole, interpret=interpret,
        name="ptpu_expert_rows_unwritten")(like)


def unwritten(shape, dtype, like, interpret=None):
    """An array of `shape` and `dtype` that nothing wrote: on the chip
    whatever its buffer held (no pass, no bytes), NaN on the interpreter.
    `like` is any array the caller has at hand, left where it is and not
    read: an operand makes two such calls of one computation ONE to XLA
    (it merges no instruction without operands), which is what a grad op's
    replay of a forward rule needs (moe.routed_ffn's docstring)."""
    return _unwritten(like, shape=tuple(shape), dtype=jnp.dtype(dtype),
                      interpret=_interpret(interpret))
