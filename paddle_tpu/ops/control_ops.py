"""Control-flow op lowerings: loops, conditionals, tensor arrays, rank tables.

Parity: paddle/fluid/operators/{while_op,conditional_block_op,array_operator,
tensor_array_read_write_op,lod_rank_table_op,max_sequence_len_op,
shrink_rnn_memory_op,lod_tensor_to_array_op,array_to_lod_tensor_op,
reorder_lod_tensor_by_rank_op,compare_op,increment_op,beam_search_op,
beam_search_decode_op}.{cc,cu,h} and the reference's recurrent_op.cc.

TPU-first design (SURVEY.md §6.4):
- `while` lowers to one `lax.while_loop` whose carry is (iter, cond, written
  outer vars incl. tensor arrays) — the reference re-enters the op-by-op
  interpreter per iteration with fresh step-Scopes.
- `rnn_scan` (the lowering target of Dynamic/StaticRNN) is a single
  `lax.scan` over time with per-row length masking: memories freeze and
  outputs zero once t >= seqlen. This replaces the reference's
  lod_tensor_to_array + shrink_memory + while machinery (sorted shrinking
  batches) with fixed-shape masked compute — what XLA wants. Because it is a
  registered pure rule, `grad_of` differentiates it with jax.vjp and BPTT
  falls out of lax.scan's transpose; the reference needs while_grad_op and
  hand-maintained step-scope stacks.
- LoDTensorArray = fixed-capacity stacked buffer + current length
  (dynamic_update_slice writes). Capacity is static (XLA) — taken from the
  array var's declared capacity, default 256.
- conditional_block evaluates the sub-block and `where`-selects against the
  out vars' previous values (scalar-cond form used by Switch / LR schedules);
  the non-scalar form (IfElse) runs the block unconditionally and lets
  merge_lod_tensor's row mask do the select — compute-both-and-mask instead
  of the reference's split/merge of ragged sub-batches.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core import registry
from ..core.registry import register, single
from ..core import lowering
from ..core.lowering import (register_special, Env, lower_block,
                             PROGRAM_ERR, accumulate_error)
from .basic import NARROW_MATMUL

DEFAULT_ARRAY_CAPACITY = 256


# ---------------------------------------------------------------------------
# pytree value types threaded through the env / loop carries
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class TensorArray(object):
    """LoDTensorArray value: stacked buffer [capacity, ...] + length scalar.

    Parity: paddle/fluid/framework/lod_tensor_array.h (a std::vector of
    LoDTensors on host). Fixed capacity makes it a legal XLA loop carry.
    """

    def __init__(self, buffer, length, overflow=None):
        self.buffer = buffer
        self.length = length
        # sticky error flag: set by any traced write at index >= capacity.
        # It rides the pytree through loop carries and is surfaced as an
        # in-graph error output (lowering.build_program_fn collect_errors);
        # the Executor raises host-side after the step — the TPU-native
        # stand-in for checkify inside lax control flow.
        self.overflow = jnp.zeros((), bool) if overflow is None else overflow

    def tree_flatten(self):
        return (self.buffer, self.length, self.overflow), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def write(self, i, x):
        # Out-of-capacity writes with a concrete index fail at trace time.
        # A traced index (inside lax loops) is checked in-graph via the
        # sticky overflow flag (XLA clamps the store itself) — size
        # create_array(capacity=...) to the loop bound (layers like
        # decoder_decode use max_length + 1).
        cap = self.buffer.shape[0]
        try:
            if int(i) >= cap:
                raise IndexError(
                    "tensor array write at index %d exceeds capacity %d; "
                    "pass a larger capacity to create_array()" % (int(i), cap))
        except (TypeError, jax.errors.TracerIntegerConversionError,
                jax.errors.ConcretizationTypeError):
            pass
        i = jnp.asarray(i, jnp.int32).reshape(())
        buf = lax.dynamic_update_index_in_dim(
            self.buffer, jnp.asarray(x, self.buffer.dtype), i, axis=0)
        over = self.overflow | (i >= cap) | (i < 0)
        return TensorArray(buf, jnp.maximum(self.length, i + 1), over)

    def read(self, i):
        i = jnp.asarray(i, jnp.int32).reshape(())
        return lax.dynamic_index_in_dim(self.buffer, i, axis=0,
                                        keepdims=False)

    @staticmethod
    def empty(shape, dtype, capacity=DEFAULT_ARRAY_CAPACITY):
        return TensorArray(jnp.zeros((capacity,) + tuple(shape), dtype),
                           jnp.zeros((), jnp.int32))


@jax.tree_util.register_pytree_node_class
class RankTable(object):
    """lod_rank_table value: sequence lengths sorted descending + the
    permutation that sorts them (reference: framework/lod_rank_table.h)."""

    def __init__(self, lengths, index):
        self.lengths = lengths  # int32 [num_seqs], descending
        self.index = index      # int32 [num_seqs], original positions

    def tree_flatten(self):
        return (self.lengths, self.index), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


# increment / compare / is_empty lowerings live in ops/basic.py


def _sweep_overflow(benv, incoming):
    """OR of `incoming`, the sub-env's accumulated error, and every
    TensorArray overflow flag visible in the sub-env — how a flag raised on
    an array that never escapes its sub-block still reaches the top level
    (threaded through the enclosing loop's carry)."""
    err = incoming
    sub = benv.read_opt(PROGRAM_ERR)
    if sub is not None:
        err = err | sub
    for v in benv.values.values():
        if isinstance(v, TensorArray):
            err = err | v.overflow
    return err

# ---------------------------------------------------------------------------
# tensor arrays (special: they produce/consume TensorArray env values)
# ---------------------------------------------------------------------------

def _env_array(ctx, op, env, name, like=None):
    """Fetch the TensorArray for `name`, creating an empty one on first
    write (capacity from the array var's attr, element shape from `like`)."""
    arr = env.read_opt(name)
    if arr is not None:
        return arr
    if like is None:
        raise ValueError("tensor array %r read before any write" % name)
    var = lowering._find_var(ctx.program, name)
    cap = getattr(var, "capacity", None) or DEFAULT_ARRAY_CAPACITY
    return TensorArray.empty(np.shape(like), jnp.result_type(like), cap)


@register_special("write_to_array")
def _write_to_array(ctx, op, env):
    x = env.read(op.inputs["X"][0])
    i = env.read(op.inputs["I"][0])
    out = op.outputs["Out"][0]
    arr = _env_array(ctx, op, env, out, like=x)
    env.write(out, arr.write(i, x))


@register_special("read_from_array")
def _read_from_array(ctx, op, env):
    arr = env.read(op.inputs["X"][0])
    i = env.read(op.inputs["I"][0])
    env.write(op.outputs["Out"][0], arr.read(i))


@register_special("lod_array_length")
def _lod_array_length(ctx, op, env):
    arr = env.read(op.inputs["X"][0])
    env.write(op.outputs["Out"][0], arr.length.reshape((1,)))


@register_special("lod_rank_table")
def _lod_rank_table(ctx, op, env):
    xlen = env.read(op.inputs["XLen"][0]).astype(jnp.int32)
    # stable descending sort (matches reference LoDRankTable ordering)
    order = jnp.argsort(-xlen, stable=True).astype(jnp.int32)
    env.write(op.outputs["Out"][0], RankTable(xlen[order], order))


@register_special("max_sequence_len")
def _max_sequence_len(ctx, op, env):
    rt = env.read(op.inputs["RankTable"][0])
    env.write(op.outputs["Out"][0], rt.lengths[0].reshape((1,)))


@register_special("reorder_lod_tensor_by_rank")
def _reorder_by_rank(ctx, op, env):
    x = env.read(op.inputs["X"][0])
    rt = env.read(op.inputs["RankTable"][0])
    env.write(op.outputs["Out"][0], jnp.take(x, rt.index, axis=0))
    if op.inputs.get("XLen") and op.outputs.get("OutLen"):
        xl = env.read(op.inputs["XLen"][0])
        env.write(op.outputs["OutLen"][0], jnp.take(xl, rt.index, axis=0))


@register_special("shrink_rnn_memory")
def _shrink_rnn_memory(ctx, op, env):
    # The reference shrinks the batch to sequences still alive at step I
    # (sorted-by-length layout). The padded-dense design keeps shapes static
    # and masks updates inside rnn_scan instead, so this is identity.
    env.write(op.outputs["Out"][0], env.read(op.inputs["X"][0]))


@register_special("lod_tensor_to_array")
def _lod_tensor_to_array(ctx, op, env):
    # [B, T, ...] padded sequence -> time-major array of [B, ...] steps.
    # With a RankTable input, rows are permuted into rank (descending-length)
    # order first, matching reorder_lod_tensor_by_rank on companion tensors
    # (the reference idiom pairs the two; array_to_lod_tensor undoes it).
    x = env.read(op.inputs["X"][0])
    if op.inputs.get("RankTable"):
        rt = env.read(op.inputs["RankTable"][0])
        x = jnp.take(x, rt.index, axis=0)
    xt = jnp.moveaxis(x, 1, 0)
    env.write(op.outputs["Out"][0],
              TensorArray(xt, jnp.asarray(x.shape[1], jnp.int32)))


@register_special("array_to_lod_tensor")
def _array_to_lod_tensor(ctx, op, env):
    # Output is [B, capacity, ...]: XLA cannot produce a data-dependent time
    # dim, so the written length goes out as a per-row lengths companion
    # (OutLen) and downstream sequence ops mask the zero tail.
    arr = env.read(op.inputs["X"][0])
    out = jnp.moveaxis(arr.buffer, 0, 1)
    if op.inputs.get("RankTable"):
        # undo the rank permutation applied by lod_tensor_to_array
        rt = env.read(op.inputs["RankTable"][0])
        inv = jnp.argsort(rt.index)
        out = jnp.take(out, inv, axis=0)
    env.write(op.outputs["Out"][0], out)
    if op.outputs.get("OutLen"):
        env.write(op.outputs["OutLen"][0],
                  jnp.full((out.shape[0],), arr.length, jnp.int32))


# ---------------------------------------------------------------------------
# while
# ---------------------------------------------------------------------------

@register_special("while")
def _while(ctx, op, env):
    """lax.while_loop over the sub-block.

    carry = (iter_counter, cond, *carry_vars). carry_names (computed at build
    time by layers.control_flow.While.complete) are the vars written inside
    the sub-block that live in an ancestor block. Tensor arrays in the carry
    must be written at least once before the loop so their buffers exist
    (the usual fluid idiom: array_write(init, i=0, array) precedes While).
    """
    sub = ctx.program.blocks[op.attrs["sub_block"]]
    cond_name = op.inputs["Condition"][0]
    carry_names = list(op.attrs["carry_names"])
    missing = [n for n in carry_names if n not in env]
    if missing:
        raise ValueError(
            "While loop carries %r, but they have no value before the loop. "
            "XLA loop carries need an initial value: assign / array_write / "
            "fill_constant each of them before `with while_op.block():`."
            % missing)

    err0 = env.read_opt(PROGRAM_ERR)
    init = (jnp.zeros((), jnp.int32),
            jnp.reshape(env.read(cond_name), ()).astype(bool),
            tuple(env.read(n) for n in carry_names),
            jnp.zeros((), bool) if err0 is None else err0)

    def cond_fn(carry):
        return carry[1]

    def body_fn(carry):
        it, _, vals, err = carry
        benv = Env()
        benv.values = dict(env.values)
        benv.write(PROGRAM_ERR, err)
        for n, v in zip(carry_names, vals):
            benv.write(n, v)
        ctx._loop_iters.append(it)
        try:
            lower_block(ctx, sub, benv)
        finally:
            ctx._loop_iters.pop()
        new_vals = tuple(
            jnp.asarray(benv.read(n), jnp.result_type(v))
            if not isinstance(v, (TensorArray, RankTable)) else benv.read(n)
            for n, v in zip(carry_names, vals))
        return (it + 1,
                jnp.reshape(benv.read(cond_name), ()).astype(bool), new_vals,
                _sweep_overflow(benv, err))

    _, _, final, final_err = lax.while_loop(cond_fn, body_fn, init)
    for n, v in zip(carry_names, final):
        env.write(n, v)
    env.write(cond_name, jnp.zeros((1,), bool))
    accumulate_error(env, final_err)


# ---------------------------------------------------------------------------
# conditional_block (Switch / IfElse)
# ---------------------------------------------------------------------------

@register_special("conditional_block")
def _conditional_block(ctx, op, env):
    sub = ctx.program.blocks[op.attrs["sub_block"]]
    out_names = list(op.attrs["out_names"])

    zero_err = jnp.zeros((), bool)

    def run_block():
        benv = Env()
        benv.values = dict(env.values)
        benv.write(PROGRAM_ERR, zero_err)  # block-local error contribution
        lower_block(ctx, sub, benv)
        return ([benv.read(n) for n in out_names],
                _sweep_overflow(benv, zero_err))

    if not op.attrs.get("is_scalar_condition", True):
        # IfElse form: merge_lod_tensor's row mask does the select; the
        # block itself runs unconditionally on the full batch.
        outs, berr = run_block()
        for n, v in zip(out_names, outs):
            env.write(n, v)
        accumulate_error(env, berr)
        return

    cond = jnp.reshape(env.read(op.inputs["Cond"][0]), ()).astype(bool)
    # Blocks are pure, so compute the block unconditionally and where-select
    # against each out var's previous value (zeros if first write) — Switch
    # cases each overwrite the same out vars, last-where with exclusive
    # conditions reproduces first-match-wins. XLA dedupes the shared work.
    outs, berr = run_block()
    accumulate_error(env, berr & cond)  # untaken branch can't overflow
    for n, o in zip(out_names, outs):
        p = env.read_opt(n)
        if p is None:
            p = jnp.zeros_like(o)
        else:
            p = jnp.broadcast_to(jnp.asarray(p, o.dtype), o.shape)
        env.write(n, jnp.where(cond, o, p))


@register("split_lod_tensor")
def _split_lod_tensor(ctx, ins, attrs):
    # compute-both-and-mask: both branches see the full batch (see module doc)
    x = single(ins, "X")
    return {"OutTrue": [x], "OutFalse": [x]}


@register("merge_lod_tensor")
def _merge_lod_tensor(ctx, ins, attrs):
    x_true = single(ins, "InTrue")
    x_false = single(ins, "InFalse")
    mask = single(ins, "Mask")  # [B, 1] bool/float
    m = jnp.reshape(mask, (-1,) + (1,) * (x_true.ndim - 1)).astype(bool)
    return {"Out": [jnp.where(m, x_true,
                              jnp.asarray(x_false, x_true.dtype))]}


# ---------------------------------------------------------------------------
# rnn_scan — the lowering target of DynamicRNN / StaticRNN
# ---------------------------------------------------------------------------

def _rnn_scan_lower(ctx, ins, attrs):
    sub = ctx.program.blocks[attrs["sub_block"]]
    xs = ins.get("X", [])                 # step inputs [B, T, feat...]
    boots = ins.get("Boot", [])           # memory boot values [B, h]
    statics = ins.get("Static", [])       # closed-over reads
    seqlen = single(ins, "SeqLen")        # [B] int32 or None (StaticRNN)

    in_names = attrs["in_names"]          # placeholders inside sub-block
    static_names = attrs["static_names"]
    pre_names = attrs["pre_names"]        # memory placeholders
    update_names = attrs["update_names"]  # vars holding the new memory value
    out_names = attrs["out_names"]        # per-step outputs to stack

    T = int(attrs["max_len"]) if attrs.get("max_len") else xs[0].shape[1]
    xs_t = [jnp.moveaxis(x, 1, 0) for x in xs]  # [T, B, ...]

    def step(carry, xt):
        t, mems, err = carry
        benv = Env()
        benv.write(PROGRAM_ERR, err)
        for n, v in zip(static_names, statics):
            benv.write(n, v)
        for n, v in zip(pre_names, mems):
            benv.write(n, v)
        for n, v in zip(in_names, xt):
            benv.write(n, v)
        ctx._loop_iters.append(t)
        try:
            lower_block(ctx, sub, benv)
        finally:
            ctx._loop_iters.pop()
        new_mems = [jnp.asarray(benv.read(n), jnp.result_type(m))
                    for n, m in zip(update_names, mems)]
        outs = [benv.read(n) for n in out_names]
        if seqlen is not None:
            alive = t < seqlen.astype(jnp.int32)  # [B]

            def sel(new, old):
                m = alive.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(m, new, jnp.asarray(old, new.dtype))

            new_mems = [sel(nm, pm) for nm, pm in zip(new_mems, mems)]
            outs = [sel(o, jnp.zeros_like(o)) for o in outs]
        return (t + 1, tuple(new_mems), _sweep_overflow(benv, err)), \
            tuple(outs)

    carry = (jnp.zeros((), jnp.int32), tuple(boots), jnp.zeros((), bool))
    if attrs.get("recompute"):
        step = _recomputing(ctx, attrs, step, carry, tuple(
            jax.ShapeDtypeStruct(x.shape[1:], x.dtype) for x in xs_t), T)
    (_, final_mems, final_err), stacked = lax.scan(
        step, carry, tuple(xs_t), length=T)
    outs = [jnp.moveaxis(o, 0, 1) for o in stacked]  # [B, T, ...]
    # "__errors__" is accumulated into the enclosing env by lower_op
    return {"Out": outs, "LastMem": list(final_mems),
            "__errors__": final_err}


def _kept_by(prim, avals, params):
    """The rule by which a recomputing loop keeps an equation's outputs
    across the forward/backward boundary, or None: what is cheap to keep
    and dear to replay, by what the equation itself shows.

    kernel_output: a Pallas forward kernel's. XLA merges nothing into a
    Mosaic call's replay, so the replay costs the kernel whole (why
    _linearizations exists outside loops). No test of bytes: the one such
    kernel a looped body holds today (flash attention) leaves the op's own
    result and a row of statistics; a kernel with larger outputs would be
    kept whole (ROADMAP A15). Not a kernel whose entry says that it costs
    its bytes (`kernel_entry(.., costs_its_bytes=True)`, one read and one
    write of its operand: ops/rotary_kernels.py): its replay is the traffic
    that reading a kept result back would be, and keeping costs the memory
    besides (Ouro: 16 results of 16 MiB a trip, 15.209 GiB compiled for the
    described v5e against 13.769 replayed; PR 70).
    narrow_matmul: a value named ops/basic.py NARROW_MATMUL. The shape rule
    is `mul`'s and is stated there, once (contraction wider than the
    result's columns; the cast product, not the dot_general's float32
    one; named only while _recomputing traces a body): of all matmuls the
    fewest bytes for the replay it saves.
    row_reduction: a reduction over 128 elements (a lane row) or more, a
    128th of what it read or less. Kept for the compiled step's memory,
    not for its time: without the statistics the replay reads every kept
    value once more for them and XLA holds float32 copies to do it, 14.886
    GiB compiled against 13.787 with them (PERF.md section 6, PR 58). The
    threshold is not measured: every reduction a cell's loop meets is over
    2048, so any threshold from 2 to 2048 gives the same step."""
    if prim.name == "pallas_call":
        # an equation of this primitive exists: pallas is imported
        from .pallas_import import costs_its_bytes
        return None if costs_its_bytes(params.get("name")) \
            else "kernel_output"
    if prim.name == "name":
        return "narrow_matmul" if params["name"] == NARROW_MATMUL else None
    if prim.name.startswith("reduce_") and "axes" in params and np.prod(
            [avals[0].shape[a] for a in params["axes"]]) >= 128:
        return "row_reduction"
    return None


def keeps_across_passes(prim, *avals, **params):
    """The policy of a recomputing loop's jax.checkpoint: a trip keeps its
    carry and the outputs of the equations _kept_by names; the backward
    pass replays everything else."""
    return _kept_by(prim, avals, params) is not None


def _kept_values(jaxpr, scope=None):
    """(fluid op (type, instance), rule, bytes) for every equation of a
    loop's body whose outputs the loop keeps, as jax asks the policy: an
    equation nested in another (a custom_vjp's, a jit's) under the outer
    one's fluid scope ("-" where it has none), a kept equation's own body
    not at all. Read off the body BEFORE jax differentiates it, a
    custom_vjp's by its primal, where jax asks the policy about the forward
    rule's equations: equal for a kernel op whose primal is its forward
    rule's first result (flash attention's), and held to jax's own account
    of the saved residuals by tests/unittests/test_loop_keep_policy.py."""
    for eqn in jaxpr.eqns:
        at = scope or lowering.parse_op_scope(
            str(eqn.source_info.name_stack)) or ("-", "-")
        rule = _kept_by(eqn.primitive, [v.aval for v in eqn.invars],
                        eqn.params)
        if rule:
            yield (at, rule, sum(v.aval.size * v.aval.dtype.itemsize
                                 for v in eqn.outvars))
        else:
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from _kept_values(inner, at)


def _recomputing(ctx, attrs, step, carry, xt, trips):
    """`step` under jax.checkpoint: a trip keeps its carry and what
    keeps_across_passes names, and the backward pass replays the rest of
    its body. The body is traced here, once, so that the kept values can be
    read off its equations and booked: the ops they belong to are not
    counted as replayed (count_loop_ops), ptpu_remat_kept_values_total and
    ptpu_remat_kept_bytes say what was kept."""
    from ..observability.registry import REGISTRY
    # in the body, and nowhere else, a `mul` names its narrow product
    outer, ctx.in_recomputing_loop = ctx.in_recomputing_loop, True
    try:
        body, out = jax.make_jaxpr(step, return_shape=True)(carry, xt)
    finally:
        ctx.in_recomputing_loop = outer
    out_tree = jax.tree.structure(out)
    loop = str(attrs["sub_block"])
    kept = list(_kept_values(body.jaxpr))
    # an op whose statistics alone are kept still runs again
    ctx.recomputing_loops[attrs["sub_block"]] = (
        trips, {at for at, rule, _ in kept if rule != "row_reduction"})
    count_loop_ops(ctx, "forward", attrs)
    values = REGISTRY.counter(
        "ptpu_remat_kept_values_total",
        "values a recomputing loop op keeps across the forward/backward "
        "boundary beside its carry, a trip, by the loop's sub-block, the "
        "fluid op of the body that computes the value and the rule that "
        "kept it: kernel_output (a Pallas forward kernel's outputs), "
        "narrow_matmul (a `mul` whose contraction is wider than its "
        "result) or row_reduction (a reduction over 128 elements or more); "
        "an op whose kernel or matmul is kept is not counted as replayed "
        "in ptpu_remat_ops_total")
    for (op_type, _), rule, _ in kept:
        values.inc(trips, loop=loop, op=op_type, rule=rule)
    REGISTRY.gauge(
        "ptpu_remat_kept_bytes",
        "bytes a recomputing loop op keeps across the forward/backward "
        "boundary beside its carry: a trip's kept values times the trips"
    ).set(trips * sum(size for _, _, size in kept), loop=loop)

    def run(carry, xt):
        return jax.tree.unflatten(out_tree, jax.core.eval_jaxpr(
            body.jaxpr, body.consts, *jax.tree.leaves((carry, xt))))
    return jax.checkpoint(run, policy=keeps_across_passes)


def count_loop_ops(ctx, kind, attrs):
    """The ops of a recomputing loop's body in ptpu_remat_ops_total, once a
    trip: `forward` where the loop is lowered, `replayed` where its grad op
    is, less the ops whose values the loop keeps (_recomputing): the
    compiled step does not run their kernel or matmul again."""
    sub = ctx.program.blocks[attrs["sub_block"]]
    trips, kept = ctx.recomputing_loops[attrs["sub_block"]]
    lowering._count_remat_ops(
        kind, [op for op in sub.ops if kind == "forward"
               or lowering.parse_op_scope(lowering.op_scope(op)) not in kept],
        times=trips)


def _rnn_scan_infer(block, op, out_vars):
    sub = block.program.blocks[op.attrs["sub_block"]]
    T = op.attrs.get("max_len")
    if not T and op.inputs.get("X"):
        x0 = block.var_recursive(op.inputs["X"][0])
        T = x0.shape[1] if x0.shape is not None else None
    for name, inner in zip(op.outputs.get("Out", ()),
                           op.attrs["out_names"]):
        iv = sub.var_recursive(inner)
        ov = block.var_recursive(name)
        if iv.shape is not None:
            ov.shape = (iv.shape[0], T if T else -1) + tuple(iv.shape[1:])
        ov.dtype = iv.dtype
    for name, inner in zip(op.outputs.get("LastMem", ()),
                           op.attrs["update_names"]):
        iv = sub.var_recursive(inner)
        ov = block.var_recursive(name)
        ov.shape, ov.dtype = iv.shape, iv.dtype


registry.register("rnn_scan", _rnn_scan_lower, infer=_rnn_scan_infer)


# ---------------------------------------------------------------------------
# beam search (dense [batch, beam] layout)
# ---------------------------------------------------------------------------

@register_special("beam_search")
def _beam_search(ctx, op, env):
    """One step of beam search in dense [batch, beam] layout.

    Parity: paddle/fluid/operators/beam_search_op.cc, which grows/prunes
    LoD-encoded candidate lists on the host. Here each batch row always
    keeps exactly `beam_size` beams (finished beams are frozen: their only
    legal expansion is end_id at zero added cost), so shapes stay static
    for XLA and the whole decode loop lives in one lax.while_loop.

    inputs:  pre_ids [B,K] int, pre_scores [B,K] f32 (cumulative log-prob),
             scores [B,K,V] f32 (log-probs of the next token per beam)
    outputs: selected_ids [B,K], selected_scores [B,K],
             parent_idx [B,K] int32 (which source beam each came from)
    """
    pre_ids = env.read(op.inputs["pre_ids"][0])
    pre_scores = env.read(op.inputs["pre_scores"][0])
    scores = env.read(op.inputs["scores"][0])
    beam_size = int(op.attrs["beam_size"])
    end_id = int(op.attrs["end_id"])

    B, K, V = scores.shape
    finished = (pre_ids == end_id)  # [B,K]

    # expansion scores: live beams add token log-prob; finished beams can
    # only "extend" with end_id at zero cost (keeps their total fixed).
    total = pre_scores[:, :, None] + scores            # [B,K,V]
    only_end = jnp.full((K, V), -1e9, scores.dtype).at[:, end_id].set(0.0)
    total = jnp.where(finished[:, :, None],
                      pre_scores[:, :, None] + only_end[None], total)

    flat = total.reshape(B, K * V)
    top_scores, top_idx = lax.top_k(flat, beam_size)   # [B,K]
    parent = (top_idx // V).astype(jnp.int32)
    token = (top_idx % V).astype(pre_ids.dtype)
    env.write(op.outputs["selected_ids"][0], token)
    env.write(op.outputs["selected_scores"][0], top_scores)
    if op.outputs.get("parent_idx"):
        env.write(op.outputs["parent_idx"][0], parent)


@register_special("beam_search_decode")
def _beam_search_decode(ctx, op, env):
    """Backtrack beam-search step arrays into full sequences.

    Parity: paddle/fluid/operators/beam_search_decode_op.cc (host-side LoD
    backtrace). Here: reverse lax.scan over the (ids, parents) TensorArrays.

    inputs:  Ids (TensorArray of [B,K] tokens), ParentIdx (TensorArray of
             [B,K] parent beam indices), Scores (TensorArray of cumulative
             [B,K] scores — the last written entry is the final total)
    outputs: SentenceIds [B,K,C] (end_id-padded), SentenceScores [B,K]
    """
    ids_arr = env.read(op.inputs["Ids"][0])
    par_arr = env.read(op.inputs["ParentIdx"][0])
    scores_arr = env.read(op.inputs["Scores"][0])
    scores = scores_arr.read(scores_arr.length - 1)
    end_id = int(op.attrs["end_id"])

    buf_ids = ids_arr.buffer      # [C, B, K]
    buf_par = par_arr.buffer      # [C, B, K]
    C, B, K = buf_ids.shape
    n = ids_arr.length            # actual steps written

    binx = jnp.arange(B)[:, None]                      # [B,1]
    init_beam = jnp.tile(jnp.arange(K)[None], (B, 1))  # [B,K]

    def back(beam, t):
        valid = t < n
        tok = jnp.where(valid, buf_ids[t][binx, beam],
                        jnp.asarray(end_id, buf_ids.dtype))
        prev = jnp.where(valid, buf_par[t][binx, beam], beam)
        return prev.astype(jnp.int32), tok

    _, toks = lax.scan(back, init_beam.astype(jnp.int32),
                       jnp.arange(C - 1, -1, -1))
    sentences = jnp.moveaxis(toks[::-1], 0, 2)         # [B,K,C]
    env.write(op.outputs["SentenceIds"][0], sentences)
    env.write(op.outputs["SentenceScores"][0], scores)
