"""`core/lowering.py` names no model op (PR 73): what an op reports of a
forward lowering is `OpDef.counts`, registered beside the rule in the op's
own module, and `_lower_op_inner` calls it without knowing whose it is.

The two lists below are the ledger of what the core still names (ROADMAP
C15): a PR that shortens one edits this file, a PR that lengthens one has to
say why."""
import ast
import os

import paddle_tpu  # noqa: F401  (registers every op)
from paddle_tpu.core import registry

LOWERING = os.path.join(os.path.dirname(registry.__file__), "lowering.py")

# name imported from paddle_tpu.ops / paddle_tpu.parallel -> the function of
# core/lowering.py that imports it
IMPORTED_FROM_ABOVE = {
    ("ops.kernel_config", "flash_min_seq"): "trace_env_key",
    ("ops.nn_ops", "_conv_layout"): "trace_env_key",
    ("ops.nn_ops", "softmax_xent_form"): "_apply_amp",
    ("ops.control_ops", "count_loop_ops"): "_lower_grad_of",
    ("ops.control_ops", "TensorArray"): "build_program_fn",
}
# the functions that may compare an op's type with a string, and the one
# string any other may: `grad_of` is the IR's own op, not a model's
MAY_NAME_OPS = {"_apply_amp"}
IR_OPS = {"grad_of"}


def _functions(tree):
    """(top-level function name or None, node) of every node of the file."""
    for top in tree.body:
        name = top.name if isinstance(top, ast.FunctionDef) else None
        for node in ast.walk(top):
            yield name, node


def _is_op_type(node):
    return isinstance(node, ast.Name) and node.id in ("op_type", "fwd_type") \
        or isinstance(node, ast.Attribute) and node.attr == "type"


def _strings(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return [s for e in node.elts for s in _strings(e)]
    return []


def test_the_core_imports_five_names_from_above_and_compares_no_op():
    with open(LOWERING) as f:
        tree = ast.parse(f.read())
    imported, named = {}, []
    for function, node in _functions(tree):
        if isinstance(node, ast.ImportFrom) and node.level == 2 \
                and (node.module or "").split(".")[0] in ("ops", "parallel"):
            for alias in node.names:
                imported[node.module, alias.name] = function
        if isinstance(node, ast.Compare) and function not in MAY_NAME_OPS:
            sides = [node.left] + node.comparators
            if any(_is_op_type(s) for s in sides):
                named += [(function, s) for side in sides
                          for s in _strings(side) if s not in IR_OPS]
    assert imported == IMPORTED_FROM_ABOVE
    assert not named, "core/lowering.py compares an op's type with %r: " \
        "register what the op reports beside its rule (registry.counts)" \
        % named


def test_an_op_counts_itself_from_the_module_of_its_rule():
    counted = {t: od for t, od in registry._OPS.items()
               if od.counts is not None}
    assert sorted(counted) == [
        "causal_conv1d", "fused_attention", "gated_delta_rule",
        "kda_delta_rule", "lookup_table", "mhc_pre", "moe_ffn", "rms_norm",
        "rotary_embedding", "selective_scan", "softmax_with_cross_entropy",
        "ssd_scan"]
    for op_type, od in counted.items():
        assert registry.get(op_type) is od
        assert od.counts.__module__ == od.lower.__module__, op_type
        assert od.counts.__module__.startswith("paddle_tpu.ops."), op_type
