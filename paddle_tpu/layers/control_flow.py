"""Control-flow layers: While, Switch, IfElse, StaticRNN, DynamicRNN, arrays.

Parity: python/paddle/fluid/layers/control_flow.py. The graph-building API is
preserved (sub-blocks, BlockGuards, tensor arrays, rank tables); lowering is
TPU-native — see ops/control_ops.py: While -> lax.while_loop,
Dynamic/StaticRNN -> one masked lax.scan (`rnn_scan` op), conditional blocks
-> lax.cond / row-mask select.
"""
from ..core import unique_name
from ..core.framework import Variable, default_main_program
from ..core.layer_helper import LayerHelper

__all__ = [
    "While", "Switch", "IfElse", "StaticRNN", "DynamicRNN",
    "increment", "array_write", "array_read", "array_length", "create_array",
    "less_than", "less_equal", "greater_than", "greater_equal", "equal",
    "not_equal", "is_empty", "lod_rank_table", "max_sequence_len",
    "reorder_lod_tensor_by_rank", "shrink_memory", "lod_tensor_to_array",
    "array_to_lod_tensor", "split_lod_tensor", "merge_lod_tensor",
    "Print", "ParallelDo", "get_places", "StaticRNNMemoryLink",
    "BlockGuardWithCompletion", "BlockGuard", "WhileGuard",
    "ConditionalBlock", "Select",
]


class BlockGuard(object):
    """Enter a new sub-block of `program`; pop back on exit.

    Parity: control_flow.py BlockGuard."""

    def __init__(self, program):
        self.program = program

    def __enter__(self):
        self.block = self.program.create_block()
        return self.block

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.program.rollback()
        return False


def _written_names(block):
    """Names written by `block`'s ops (including nested sub-blocks)."""
    names = set()
    blocks = [block]
    for b in block.program.blocks:
        if any(p is not None and b.parent_idx == p.idx for p in blocks):
            blocks.append(b)
    for b in blocks:
        for op in b.ops:
            names.update(n for n in op.all_output_vars() if n)
    return names


def _read_names(block):
    """Names read (in order, deduped) by `block`'s ops incl. nested blocks."""
    seen, order = set(), []
    blocks = [block]
    for b in block.program.blocks:
        if any(b.parent_idx == p.idx for p in blocks):
            blocks.append(b)
    for b in blocks:
        for op in b.ops:
            for n in op.all_input_vars():
                if n and n not in seen:
                    seen.add(n)
                    order.append(n)
    return order


# ---------------------------------------------------------------------------
# scalar helpers
# ---------------------------------------------------------------------------

def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment", **locals())
    out = x if in_place else helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="increment", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"step": float(value)},
                     infer_shape=False)
    return out


def _compare(op_type):
    def fn(x, y, cond=None, **ignored):
        helper = LayerHelper(op_type, x=x, y=y)
        if cond is None:
            cond = helper.create_variable_for_type_inference("bool")
            cond.stop_gradient = True
        helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                         outputs={"Out": [cond]})
        return cond
    fn.__name__ = op_type
    return fn


less_than = _compare("less_than")
less_equal = _compare("less_equal")
greater_than = _compare("greater_than")
greater_equal = _compare("greater_equal")
equal = _compare("equal")
not_equal = _compare("not_equal")


def is_empty(x, cond=None, **ignored):
    helper = LayerHelper("is_empty", x=x)
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool")
        cond.stop_gradient = True
    helper.append_op(type="is_empty", inputs={"X": [x]},
                     outputs={"Out": [cond]}, infer_shape=False)
    return cond


# ---------------------------------------------------------------------------
# tensor arrays / rank tables
# ---------------------------------------------------------------------------

def create_array(dtype, capacity=None):
    """Create a LoDTensorArray var. `capacity` (TPU extension) fixes the
    stacked-buffer length; default ops/control_ops.DEFAULT_ARRAY_CAPACITY."""
    helper = LayerHelper("array")
    arr = helper.block.create_var(
        name=unique_name.generate("array"), dtype=dtype)
    arr.is_tensor_array = True
    arr.capacity = capacity
    return arr


def array_write(x, i, array=None):
    helper = LayerHelper("array_write", **locals())
    if array is None:
        array = create_array(x.dtype)
    helper.append_op(type="write_to_array",
                     inputs={"X": [x], "I": [i]},
                     outputs={"Out": [array]}, infer_shape=False)
    if array.shape is None:
        array.shape = x.shape  # element shape, used by array_read infer
        array.dtype = x.dtype
    return array


def array_read(array, i):
    helper = LayerHelper("array_read", **locals())
    out = helper.create_variable_for_type_inference(array.dtype)
    helper.append_op(type="read_from_array",
                     inputs={"X": [array], "I": [i]},
                     outputs={"Out": [out]}, infer_shape=False)
    out.shape = array.shape
    return out


def array_length(array):
    helper = LayerHelper("array_length", **locals())
    out = helper.create_variable_for_type_inference("int32")
    out.stop_gradient = True
    out.shape = (1,)
    helper.append_op(type="lod_array_length", inputs={"X": [array]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def lod_rank_table(x, level=0):
    helper = LayerHelper("lod_rank_table", **locals())
    if x.seq_len_var is None:
        raise ValueError("lod_rank_table needs a sequence input")
    table = helper.block.create_var(
        name=unique_name.generate("lod_rank_table"), dtype="int32")
    helper.append_op(
        type="lod_rank_table",
        inputs={"XLen": [helper.block.var_recursive(x.seq_len_var)]},
        outputs={"Out": [table]}, attrs={"level": level}, infer_shape=False)
    return table


def max_sequence_len(rank_table):
    helper = LayerHelper("max_seqence_len", **locals())
    out = helper.create_variable_for_type_inference("int32")
    out.stop_gradient = True
    out.shape = (1,)
    helper.append_op(type="max_sequence_len",
                     inputs={"RankTable": [rank_table]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def reorder_lod_tensor_by_rank(x, rank_table):
    helper = LayerHelper("reorder_lod_tensor_by_rank", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    inputs = {"X": [x], "RankTable": [rank_table]}
    outputs = {"Out": [out]}
    if x.seq_len_var is not None:
        out_len = helper.block.create_var(
            name=out.name + "@SEQLEN", shape=[-1], dtype="int32",
            stop_gradient=True)
        inputs["XLen"] = [helper.block.var_recursive(x.seq_len_var)]
        outputs["OutLen"] = [out_len]
        out.lod_level = x.lod_level
        out.seq_len_var = out_len.name
    helper.append_op(type="reorder_lod_tensor_by_rank", inputs=inputs,
                     outputs=outputs, infer_shape=False)
    return out


def shrink_memory(x, i, table):
    helper = LayerHelper("shrink_memory", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = x.shape
    helper.append_op(type="shrink_rnn_memory",
                     inputs={"X": [x], "I": [i], "RankTable": [table]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def lod_tensor_to_array(x, table=None):
    helper = LayerHelper("lod_tensor_to_array", **locals())
    arr = create_array(x.dtype)
    inputs = {"X": [x]}
    if table is not None:
        inputs["RankTable"] = [table]
        arr.rank_table_var = table.name
    helper.append_op(type="lod_tensor_to_array",
                     inputs=inputs, outputs={"Out": [arr]},
                     infer_shape=False)
    if x.shape is not None:
        arr.shape = (x.shape[0],) + tuple(x.shape[2:])
    return arr


def array_to_lod_tensor(x, table=None):
    helper = LayerHelper("array_to_lod_tensor", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    out_len = helper.block.create_var(
        name=out.name + "@SEQLEN", shape=[-1], dtype="int32",
        stop_gradient=True)
    inputs = {"X": [x]}
    if table is not None:
        inputs["RankTable"] = [table]
    elif getattr(x, "rank_table_var", None):
        inputs["RankTable"] = [x.rank_table_var]
    helper.append_op(type="array_to_lod_tensor",
                     inputs=inputs,
                     outputs={"Out": [out], "OutLen": [out_len]},
                     infer_shape=False)
    # time dim is the array capacity; the written length rides the lengths
    # companion so sequence ops mask the zero tail
    out.lod_level = 1
    out.seq_len_var = out_len.name
    return out


def split_lod_tensor(input, mask, level=0):
    helper = LayerHelper("split_lod_tensor", **locals())
    out_true = helper.create_variable_for_type_inference(input.dtype)
    out_false = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="split_lod_tensor",
                     inputs={"X": [input], "Mask": [mask]},
                     outputs={"OutTrue": [out_true], "OutFalse": [out_false]},
                     attrs={"level": level})
    return out_true, out_false


def merge_lod_tensor(in_true, in_false, x, mask, level=0):
    helper = LayerHelper("merge_lod_tensor", **locals())
    out = helper.create_variable_for_type_inference(in_true.dtype)
    helper.append_op(type="merge_lod_tensor",
                     inputs={"InTrue": [in_true], "InFalse": [in_false],
                             "X": [x], "Mask": [mask]},
                     outputs={"Out": [out]}, attrs={"level": level})
    return out


# ---------------------------------------------------------------------------
# While
# ---------------------------------------------------------------------------

class While(object):
    """while cond: run block. Lowered to one lax.while_loop.

    Parity: control_flow.py `While` (while_op.cc). Vars written in the block
    that live in an enclosing block form the loop carry; tensor arrays
    carried through the loop must be written once before it (the standard
    fluid decoder idiom already does this).
    """
    BEFORE_WHILE_BLOCK = 0
    IN_WHILE_BLOCK = 1
    AFTER_WHILE_BLOCK = 2

    def __init__(self, cond, name=None):
        self.helper = LayerHelper("while", name=name)
        self.status = While.BEFORE_WHILE_BLOCK
        if not isinstance(cond, Variable):
            raise TypeError("condition should be a Variable")
        self.cond_var = cond

    def block(self):
        return WhileGuard(self)

    def complete(self):
        program = self.helper.main_program
        while_block = program.current_block()
        parent_block = program.blocks[while_block.parent_idx]

        carry = []
        for name in sorted(_written_names(while_block)):
            if not while_block.has_var(name) and name != self.cond_var.name:
                carry.append(name)
        out_vars = [parent_block.var_recursive(n) for n in carry
                    if parent_block.has_var_recursive(n)]

        # carried vars are listed as inputs too ("X") so state analysis loads
        # persistable carries from the Scope before marking them written
        parent_block.append_op(
            type="while",
            inputs={"Condition": [self.cond_var], "X": out_vars},
            outputs={"Out": out_vars},
            attrs={"sub_block": while_block.idx,
                   "carry_names": [v.name for v in out_vars]},
            infer_shape=False)


class WhileGuard(BlockGuard):
    def __init__(self, while_op):
        super(WhileGuard, self).__init__(while_op.helper.main_program)
        self.while_op = while_op

    def __enter__(self):
        self.while_op.status = While.IN_WHILE_BLOCK
        return super(WhileGuard, self).__enter__()

    def __exit__(self, exc_type, exc_val, exc_tb):
        if exc_type is not None:
            return False
        self.while_op.status = While.AFTER_WHILE_BLOCK
        self.while_op.complete()
        return super(WhileGuard, self).__exit__(exc_type, exc_val, exc_tb)


# ---------------------------------------------------------------------------
# Switch
# ---------------------------------------------------------------------------

class ConditionalBlock(object):
    """Scalar-condition conditional block (building block of Switch).

    Parity: control_flow.py ConditionalBlock / conditional_block_op.cc."""

    def __init__(self, inputs, is_scalar_condition=True, name=None):
        self.inputs = inputs
        self.is_scalar_condition = is_scalar_condition
        self.helper = LayerHelper("conditional_block", name=name)

    def block(self):
        return ConditionalBlockGuard(self)

    def complete(self):
        program = self.helper.main_program
        inside_block = program.current_block()
        parent_block = program.blocks[inside_block.parent_idx]
        out_names = [n for n in sorted(_written_names(inside_block))
                     if not inside_block.has_var(n)
                     and parent_block.has_var_recursive(n)]
        # OutPrev: the out vars' previous values are read by the not-taken
        # branch, so they must appear as inputs for state analysis to load
        # scope-initialized (persistable) values
        parent_block.append_op(
            type="conditional_block",
            inputs={"Cond": [v.name for v in self.inputs],
                    "OutPrev": out_names},
            outputs={"Out": out_names},
            attrs={"sub_block": inside_block.idx,
                   "out_names": out_names,
                   "is_scalar_condition": self.is_scalar_condition},
            infer_shape=False)


class ConditionalBlockGuard(BlockGuard):
    def __init__(self, cond_block):
        super(ConditionalBlockGuard, self).__init__(
            cond_block.helper.main_program)
        self.cond_block = cond_block

    def __exit__(self, exc_type, exc_val, exc_tb):
        if exc_type is None:
            self.cond_block.complete()
        return super(ConditionalBlockGuard, self).__exit__(
            exc_type, exc_val, exc_tb)


class Switch(object):
    """switch { case(cond): ... default: ... } — first matching case wins.

    Parity: control_flow.py `Switch` (used by learning-rate schedules).
    Each case lowers to a conditional_block guarded by
    cond_i AND NOT(any earlier cond).
    """

    def __init__(self, name=None):
        self.helper = LayerHelper("switch", name=name)
        self.inside_scope = False
        self.pre_not_taken = None  # Variable: no earlier case matched

    def case(self, condition):
        if not self.inside_scope:
            raise ValueError("case should be called inside with")
        from . import tensor, ops
        if self.pre_not_taken is None:
            eff = condition
            not_cond = ops.logical_not(x=condition)
            self.pre_not_taken = not_cond
        else:
            eff = ops.logical_and(x=self.pre_not_taken, y=condition)
            self.pre_not_taken = ops.logical_and(
                x=self.pre_not_taken, y=ops.logical_not(x=condition))
        cb = ConditionalBlock([eff], is_scalar_condition=True)
        return cb.block()

    def default(self):
        if self.pre_not_taken is None:
            raise ValueError("there should be at least one case before default")
        cb = ConditionalBlock([self.pre_not_taken], is_scalar_condition=True)
        return cb.block()

    def __enter__(self):
        self.inside_scope = True
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.inside_scope = False
        return False


# ---------------------------------------------------------------------------
# IfElse
# ---------------------------------------------------------------------------

class IfElse(object):
    """Row-wise conditional: rows of the batch where `cond` holds flow
    through the true block, the rest through the false block.

    Parity: control_flow.py `IfElse` (split_lod_tensor/merge_lod_tensor +
    conditional_block). TPU lowering computes BOTH branches on the full
    batch and selects per row with the mask — static shapes, no ragged
    sub-batches (see ops/control_ops.py).
    """
    OUT_IF_ELSE_BLOCKS = 0
    IN_IF_ELSE_TRUE_BLOCKS = 1
    IN_IF_ELSE_FALSE_BLOCKS = 2

    def __init__(self, cond, name=None):
        self.helper = LayerHelper("ifelse", name=name)
        self.cond = cond
        self.input_table = {}
        self.status = IfElse.OUT_IF_ELSE_BLOCKS
        self.conditional_true_block = ConditionalBlock(
            [cond], is_scalar_condition=False)
        self.conditional_false_block = ConditionalBlock(
            [cond], is_scalar_condition=False)
        self.output_table = [[], []]  # (true_out, false_out)

    def input(self, x):
        if self.status == IfElse.OUT_IF_ELSE_BLOCKS:
            raise ValueError("input must be called inside a block")
        # both branches see the full batch; mask select happens at merge
        return x

    def _block(self, status):
        ie = self

        class _Guard(BlockGuard):
            def __init__(self):
                super(_Guard, self).__init__(ie.helper.main_program)

            def __enter__(self):
                ie.status = status
                return super(_Guard, self).__enter__()

            def __exit__(self, t, v, tb):
                if t is None:
                    cb = (ie.conditional_true_block
                          if status == IfElse.IN_IF_ELSE_TRUE_BLOCKS
                          else ie.conditional_false_block)
                    cb.complete()
                ie.status = IfElse.OUT_IF_ELSE_BLOCKS
                return super(_Guard, self).__exit__(t, v, tb)

        return _Guard()

    def true_block(self):
        return self._block(IfElse.IN_IF_ELSE_TRUE_BLOCKS)

    def false_block(self):
        return self._block(IfElse.IN_IF_ELSE_FALSE_BLOCKS)

    def output(self, *outs):
        if self.status == IfElse.OUT_IF_ELSE_BLOCKS:
            raise ValueError("output can only be invoked in an if/else block")
        false_side = self.status == IfElse.IN_IF_ELSE_FALSE_BLOCKS
        table = self.output_table[1 if false_side else 0]
        from . import tensor
        parent_block = self.helper.main_program.blocks[
            self.helper.main_program.current_block().parent_idx]
        for o in outs:
            outside = parent_block.create_var(
                name=unique_name.generate("ifelse_out"),
                dtype=o.dtype, shape=o.shape)
            tensor.assign(o, outside)
            table.append(outside)

    def __call__(self):
        if self.status != IfElse.OUT_IF_ELSE_BLOCKS:
            raise ValueError("__call__ only at out-block status")
        if len(self.output_table[0]) != len(self.output_table[1]):
            raise ValueError("true/false blocks must produce the same number "
                             "of outputs")
        rlist = []
        for t, f in zip(*self.output_table):
            rlist.append(merge_lod_tensor(t, f, t, self.cond))
        return rlist


# ---------------------------------------------------------------------------
# rnn_scan builders (StaticRNN / DynamicRNN)
# ---------------------------------------------------------------------------

class _RNNBase(object):
    """Shared machinery: records a step sub-block + links, then emits one
    `rnn_scan` op (masked lax.scan) in the parent block."""

    BEFORE_RNN_BLOCK = 0
    IN_RNN_BLOCK = 1
    AFTER_RNN_BLOCK = 2

    def __init__(self, layer_type, name=None):
        self.helper = LayerHelper(layer_type, name=name)
        self.status = self.BEFORE_RNN_BLOCK
        self._step_inputs = []    # (outer Variable, inner placeholder)
        self._memories = []       # dict(boot, pre, update)
        self._outputs = []        # (inner Variable, outer Variable)
        self._step_block = None
        self._seq_var = None      # first sequence step input (for SeqLen)
        self._masked = True
        self._steps = None        # trip count of a loop with no step input
        self._recompute = False

    # -- block guard --------------------------------------------------------
    def _assert_in_rnn_block(self, method):
        if self.status != self.IN_RNN_BLOCK:
            raise ValueError("you must invoke %s inside rnn block" % method)

    def step(self):
        return _RNNGuard(self)

    block = step  # DynamicRNN spells it block()

    # -- step API -----------------------------------------------------------
    def step_input(self, x, level=0):
        self._assert_in_rnn_block("step_input")
        if not isinstance(x, Variable):
            raise TypeError("step_input takes a Variable")
        if x.shape is None or len(x.shape) < 2:
            raise ValueError("step input must be a [batch, time, ...] tensor")
        if self._seq_var is None and x.seq_len_var is not None:
            self._seq_var = x
        inner = self._step_block.create_var(
            name=unique_name.generate(self.helper.name + ".in"),
            shape=(x.shape[0],) + tuple(x.shape[2:]), dtype=x.dtype)
        self._step_inputs.append((x, inner))
        return inner

    def static_input(self, x):
        self._assert_in_rnn_block("static_input")
        # statics are closed over by name — the sub-block reads the outer var
        return x

    def memory(self, init=None, shape=None, value=0.0, init_value=0.0,
               batch_ref=None, need_reorder=False, dtype="float32",
               init_batch_dim_idx=0, ref_batch_dim_idx=1):
        self._assert_in_rnn_block("memory")
        program = self.helper.main_program
        parent_block = program.blocks[self._step_block.parent_idx]
        if init is None:
            ref = batch_ref if batch_ref is not None else (
                self._step_inputs[0][0] if self._step_inputs else None)
            if shape is None or ref is None:
                raise ValueError("memory without init needs shape and a "
                                 "step_input (or batch_ref) for the batch dim")
            boot = parent_block.create_var(
                name=unique_name.generate(self.helper.name + ".mem_boot"),
                shape=[-1] + list(shape), dtype=dtype)
            parent_block.append_op(
                type="fill_constant_batch_size_like",
                inputs={"Input": [ref]},
                outputs={"Out": [boot]},
                attrs={"value": float(value or init_value),
                       "shape": [-1] + list(shape), "dtype": dtype,
                       "input_dim_idx": 0, "output_dim_idx": 0},
                infer_shape=False)
            return self.memory(init=boot)
        pre = self._step_block.create_var(
            name=unique_name.generate(self.helper.name + ".mem"),
            shape=init.shape, dtype=init.dtype)
        self._memories.append({"boot": init, "pre": pre, "update": None})
        return pre

    def update_memory(self, ex_mem, new_mem):
        self._assert_in_rnn_block("update_memory")
        for m in self._memories:
            if m["pre"] is ex_mem or m["pre"].name == ex_mem.name:
                m["update"] = new_mem
                return
        raise ValueError("update_memory: %r is not a memory of this RNN"
                         % ex_mem.name)

    def output(self, *outputs):
        self._assert_in_rnn_block("output")
        program = self.helper.main_program
        parent_block = program.blocks[self._step_block.parent_idx]
        for o in outputs:
            outer = parent_block.create_var(
                name=unique_name.generate(self.helper.name + ".out"),
                dtype=o.dtype)
            if self._seq_var is not None:
                outer.lod_level = max(self._seq_var.lod_level, 1)
                outer.seq_len_var = self._seq_var.seq_len_var
            self._outputs.append((o, outer))

    step_output = output

    def __call__(self, *args, **kwargs):
        if self.status != self.AFTER_RNN_BLOCK:
            raise ValueError("rnn output accessible only after the rnn block")
        outs = [outer for _, outer in self._outputs]
        return outs[0] if len(outs) == 1 else outs

    # -- completion ---------------------------------------------------------
    def _complete(self):
        program = self.helper.main_program
        step_block = self._step_block
        parent_block = program.blocks[step_block.parent_idx]
        if not self._step_inputs and not self._steps:
            raise ValueError("RNN needs at least one step_input")
        for m in self._memories:
            if m["update"] is None:
                raise ValueError("memory %r never update_memory'd"
                                 % m["pre"].name)

        in_names = [inner.name for _, inner in self._step_inputs]
        pre_names = [m["pre"].name for m in self._memories]
        written = _written_names(step_block)
        placeholder = set(in_names) | set(pre_names)
        static_names = [
            n for n in _read_names(step_block)
            if n not in written and n not in placeholder
            and not step_block.has_var(n)
            and parent_block.has_var_recursive(n)]

        inputs = {"X": [x.name for x, _ in self._step_inputs],
                  "Boot": [m["boot"].name for m in self._memories],
                  "Static": static_names}
        if self._masked and self._seq_var is not None:
            inputs["SeqLen"] = [self._seq_var.seq_len_var]

        last_mems = []
        for m in self._memories:
            lm = parent_block.create_var(
                name=unique_name.generate(self.helper.name + ".last_mem"),
                dtype=m["boot"].dtype)
            last_mems.append(lm)
        self.final_memories = last_mems

        parent_block.append_op(
            type="rnn_scan",
            inputs=inputs,
            outputs={"Out": [outer for _, outer in self._outputs],
                     "LastMem": last_mems},
            attrs={"sub_block": step_block.idx,
                   "in_names": in_names,
                   "static_names": static_names,
                   "pre_names": pre_names,
                   "update_names": [m["update"].name for m in self._memories],
                   "out_names": [o.name for o, _ in self._outputs],
                   "max_len": self._steps})
        if self._recompute:     # what the default leaves is not written
            parent_block.ops[-1].attrs["recompute"] = True


class _RNNGuard(BlockGuard):
    def __init__(self, rnn):
        super(_RNNGuard, self).__init__(rnn.helper.main_program)
        self.rnn = rnn

    def __enter__(self):
        self.rnn.status = self.rnn.IN_RNN_BLOCK
        blk = super(_RNNGuard, self).__enter__()
        self.rnn._step_block = blk
        return blk

    def __exit__(self, exc_type, exc_val, exc_tb):
        if exc_type is not None:
            return False
        self.rnn.status = self.rnn.AFTER_RNN_BLOCK
        self.rnn._complete()
        return super(_RNNGuard, self).__exit__(exc_type, exc_val, exc_tb)


class StaticRNN(_RNNBase):
    """Fixed-length RNN over [batch, time, ...] inputs (no length masking).

    Parity: control_flow.py StaticRNN / recurrent_op.cc. Lowered to one
    lax.scan; BPTT comes from jax.vjp of the scan. `steps`: the trip count
    of a loop with no step input, memories and stacked outputs alone (a
    stack of layers run `steps` times over weights the block closes over,
    models/causal_lm.py). `recompute`: the body runs under jax.checkpoint,
    so a trip keeps its memories and the backward pass replays it, but for
    what is cheap to keep and dear to replay: a Pallas forward kernel's
    outputs, the result of a `mul` whose contraction is wider than its
    result and row statistics (ops/control_ops.py keeps_across_passes
    decides by the traced equation; there is no knob). The forward op then keeps its
    linearization for its grad op."""

    def __init__(self, name=None, steps=None, recompute=False):
        super(StaticRNN, self).__init__("static_rnn", name)
        self._masked = False
        self._steps, self._recompute = steps, recompute


class DynamicRNN(_RNNBase):
    """Variable-length RNN over padded sequences: memories freeze and
    outputs zero past each row's true length.

    Parity: control_flow.py DynamicRNN (which expands to lod_rank_table +
    lod_tensor_to_array + While + shrink_memory). Here it is ONE masked
    lax.scan — same math, static shapes, MXU-batched gate matmuls."""

    def __init__(self, name=None):
        super(DynamicRNN, self).__init__("dynamic_rnn", name)

    def step_input(self, x, level=0):
        if x.seq_len_var is None:
            raise ValueError(
                "DynamicRNN.step_input needs a sequence (lod_level>0) input")
        return super(DynamicRNN, self).step_input(x, level)


def Print(input, first_n=-1, message=None, summarize=-1,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """Parity: fluid.layers.Print (reference control_flow.py:150,
    print_op.cc). Wraps the tensor so each execution prints `message` and
    the value; lowered to jax.debug.print, which works inside jit and on
    device. The op is identity, so gradients pass through unchanged
    (print_phase is accepted; values print whenever the op executes,
    including its recompute inside the backward's vjp). Returns the
    identity output so the print stays live in the graph."""
    helper = LayerHelper("print", name=None)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="print", inputs={"In": [input]}, outputs={"Out": [out]},
        attrs={"first_n": int(first_n), "message": message or "",
               "summarize": int(summarize),
               "print_tensor_name": bool(print_tensor_name),
               "print_tensor_type": bool(print_tensor_type),
               "print_tensor_shape": bool(print_tensor_shape),
               "print_phase": str(print_phase),
               "var_name": input.name})
    if input.shape is not None:
        out.shape = tuple(input.shape)
    return out


def get_places(device_count=None, device_type=None):
    """Parity: fluid.layers.get_places — the reference returned a places
    variable for ParallelDo. Device placement is mesh-declarative here, so
    this returns the device list for inspection (filtered to device_type
    when given, e.g. 'CPU')."""
    import jax
    devices = jax.devices(device_type.lower()) if device_type \
        else jax.devices()
    if device_count is not None:
        devices = devices[:device_count]
    return devices


class ParallelDo(object):
    """Parity shim: reference control_flow.py ParallelDo replicated a
    sub-block over GPUs with gradient all-reduce (parallel_do_op.cc). The
    TPU-native equivalent is GSPMD data parallelism (ParallelExecutor), so
    this shim runs the body INLINE on the full batch — numerically the
    behavior ParallelDo produced, with the device distribution delegated to
    the mesh. Kept so reference scripts run unchanged."""

    def __init__(self, places, use_nccl=False, name=None):
        self._outputs = []

    def do(self):
        import contextlib

        @contextlib.contextmanager
        def guard():
            yield
        return guard()

    def read_input(self, var):
        return var

    def write_output(self, var):
        self._outputs.append(var)

    def __call__(self):
        if not self._outputs:
            raise ValueError("ParallelDo: no outputs written; call "
                             "write_output inside the do() block")
        return self._outputs[0] if len(self._outputs) == 1 \
            else list(self._outputs)


class StaticRNNMemoryLink(object):
    """Parity: reference control_flow.py StaticRNNMemoryLink — the
    (init, pre_mem, mem) record linking a memory across steps. The scan
    lowering tracks this inside _RNNBase; the class is kept for scripts
    that introspect it."""

    def __init__(self, init, pre_mem, mem=None):
        self.init = init
        self.pre_mem = pre_mem
        self.mem = mem


class BlockGuardWithCompletion(_RNNGuard):
    """Parity: reference control_flow.py BlockGuardWithCompletion — the
    with-block helper that completes the RNN on exit. Functionally the same
    guard rnn.block()/step() return (_RNNGuard: sets IN_RNN_BLOCK, opens
    the step sub-block, emits the rnn_scan op on exit), kept under the
    reference name for scripts that construct it directly."""


class Select(object):
    """Parity placeholder: fluid.Select (the CSP-style channel select from
    fluid.concurrency). The concurrency surface is an explicit scope cut —
    see SURVEY.md §2: its blocking-channel semantics contradict whole-
    program XLA execution; the TPU-native equivalents are the async reader
    layers (double_buffer) and collective-based parallelism."""

    def __init__(self, *args, **kwargs):
        raise NotImplementedError(
            "fluid.concurrency channels/Select are not rebuilt in "
            "paddle_tpu (explicit scope cut, SURVEY.md §2); use the reader "
            "layers (double_buffer) for async input or ParallelExecutor "
            "collectives for parallelism")
