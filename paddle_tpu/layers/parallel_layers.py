"""Fluid layers for the parallel subsystems: pipelined_stack (PP),
switch_moe (EP, top-1 with capacity) and moe_ffn (dropless top-k routed
experts, one chip).

These are the Program-path entries to parallel/pipeline.py and
parallel/moe.py: build the model with them like any other layer, train it
with Executor on one chip (sequential / dense semantics), and hand the
same Program to ParallelExecutor with a mesh carrying a 'pp' / 'ep' axis
to get the GPipe looped-pipeline schedule / the GShard-style expert
all-to-all — no model rewrite. The reference era (mozga-intel/Paddle,
2018) predates both; its only partitioning is the pserver parameter split
(python/paddle/fluid/distribute_transpiler.py).
"""
from ..core.framework import Variable
from ..core.layer_helper import LayerHelper
from ..core.initializer import ConstantInitializer
from ..core.param_attr import ParamAttr
from ..core import unique_name

__all__ = ["pipelined_stack", "switch_moe", "moe_ffn"]


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


def _block_sig(program, block, canon=None):
    """Structural signature of a stage sub-block: op types, attrs, AND
    dataflow wiring (recursing into nested sub-blocks, whose indices
    differ per stage even when their contents match). Execution always
    uses stage 0's template, so ANY divergence across stages — attrs
    (fc(act='relu') vs 'tanh') or topology (fc(fc(x)) vs fc(x)) — must be
    a build error, not silent stage-0 math. Wiring is compared through
    first-seen canonical ids, so the generated var names themselves may
    legitimately differ per stage."""
    canon = {} if canon is None else canon

    def cid(n):
        if n not in canon:
            canon[n] = len(canon)
        return canon[n]

    sig = []
    for op in block.ops:
        attrs = []
        for k in sorted(op.attrs):
            if k == "sub_block":
                idx = op.attrs[k]
                attrs.append((k, _block_sig(program, program.blocks[idx],
                                            canon)))
            elif k.endswith(("_name", "_names")):
                # binding metadata holds per-stage generated var names
                # (rnn_scan in_names, conditional out_names, ...); their
                # wiring is canonicalized like op input/output names
                v = op.attrs[k]
                names = v if isinstance(v, (list, tuple)) else [v]
                attrs.append((k, tuple(cid(x) for x in names
                                       if isinstance(x, str))))
            else:
                attrs.append((k, _freeze(op.attrs[k])))
        wiring = tuple(
            (kind, slot, tuple(cid(n) for n in names if n))
            for kind, slots in (("in", op.inputs), ("out", op.outputs))
            for slot, names in sorted(slots.items()))
        sig.append((op.type, tuple(attrs), wiring))
    return tuple(sig)


def _check_stage_block(program, blk, avail, s):
    """Validate one stage sub-block (recursively): every read resolves
    inside the stage, and nothing writes persistable state. Nested
    sub-block lowerings bind their own placeholder names via *_name(s)
    attrs (rnn_scan in_names/pre_names/..., conditional out_names);
    those count as available inside the nested block."""
    for op in blk.ops:
        for n in op.all_input_vars():
            if n and n not in avail:
                raise ValueError(
                    "pipeline stage %d op %r reads %r from outside the "
                    "stage; stages must be self-contained (only their "
                    "own parameters and the stage input)" % (s, op.type, n))
        bound = set()
        for k, v in op.attrs.items():
            if k.endswith("_names") and isinstance(v, (list, tuple)):
                bound.update(x for x in v if isinstance(x, str))
            elif k.endswith("_name") and isinstance(v, str):
                bound.add(v)
        idx = op.attrs.get("sub_block")
        if isinstance(idx, int):
            _check_stage_block(program, program.blocks[idx],
                               avail | bound, s)
        for n in op.all_output_vars():
            if not n:
                continue
            v = blk.var_recursive(n) if blk.has_var_recursive(n) else None
            if v is not None and getattr(v, "persistable", False):
                raise ValueError(
                    "pipeline stage %d op %r writes persistable %r; "
                    "stages must be stateless (no in-stage batch_norm "
                    "stat updates)" % (s, op.type, n))
            avail.add(n)


def pipelined_stack(input, num_stages, build_stage, num_microbatches=None,
                    name=None):
    """Run `input` through `num_stages` copies of a builder-defined stage,
    as ONE `pipeline` op (lowering: ops/parallel_ops.py).

    build_stage(x) -> y is called once per stage inside its own sub-block;
    parameters it creates become that stage's private weights (stage s
    gets an independent init draw). Stages must be homogeneous — same op
    sequence and parameter shapes — and shape-preserving (y.shape ==
    x.shape), the classic pipeline regime (e.g. a transformer encoder
    layer, a resnet block stack at fixed width).

    Execution:
      * Executor / mesh without a 'pp' axis: the stages run sequentially
        in one XLA program (identical math, zero overhead).
      * ParallelExecutor with mesh {'pp': num_stages, ...}: the GPipe
        looped pipeline of parallel/pipeline.py — stage s's weights live
        on pipeline rank s, microbatches stream over the ring via
        lax.ppermute, dp (if present) shards the microbatch dim.
        num_microbatches defaults to num_stages; more shrinks the bubble.
    Fully differentiable (grad_of takes jax.vjp of the whole schedule).

    Constraints (checked at build time): stages may not write persistable
    state (no batch_norm stat updates inside a stage), may not read
    variables from outside the stage other than their own parameters, and
    must consume/produce plain dense tensors. Random ops inside a stage
    draw per-stage (not per-microbatch) keys.
    """
    if not isinstance(input, Variable):
        raise TypeError("pipelined_stack input must be a Variable")
    if int(num_stages) < 1:
        raise ValueError("pipelined_stack needs num_stages >= 1, got %r"
                         % (num_stages,))
    helper = LayerHelper("pipeline", name=name)
    main = helper.main_program
    gb = main.global_block()

    stage_params = []      # [ [param names] per stage ]
    stage_sigs = []        # op-type sequences, for the homogeneity check
    sub0 = None
    in_name = out_name = None

    for s in range(num_stages):
        before = [p.name for p in gb.all_parameters()]
        blk = main.create_block()
        try:
            ph = blk.create_var(
                name=unique_name.generate("pipeline_stage_in"),
                dtype=input.dtype, shape=input.shape)
            out_v = build_stage(ph)
        finally:
            main.rollback()
        if not isinstance(out_v, Variable):
            raise TypeError("build_stage must return a Variable")
        if out_v.dtype != input.dtype or (
                out_v.shape is not None and input.shape is not None
                and tuple(out_v.shape) != tuple(input.shape)):
            raise ValueError(
                "pipeline stages must be shape-preserving: stage %d maps "
                "%s %s -> %s %s" % (s, input.shape, input.dtype,
                                    out_v.shape, out_v.dtype))
        seen = set(before)
        new_params = [p.name for p in gb.all_parameters()
                      if p.name not in seen]
        # self-containment: reads resolve to the placeholder, the stage's
        # own params, or values produced earlier in the stage (recursing
        # into nested control-flow sub-blocks)
        _check_stage_block(main, blk, {ph.name} | set(new_params), s)
        if not new_params:
            raise ValueError(
                "pipeline stage %d creates no parameters; per-stage "
                "weights are what pipeline parallelism distributes — a "
                "parameterless transform belongs inline, not in "
                "pipelined_stack" % s)
        stage_params.append(new_params)
        stage_sigs.append(_block_sig(main, blk))
        if s == 0:
            sub0, in_name, out_name = blk, ph.name, out_v.name
        else:
            if stage_sigs[s] != stage_sigs[0]:
                raise ValueError(
                    "pipeline stages are not homogeneous (op types/attrs "
                    "differ between stage %d and stage 0; every stage "
                    "executes stage 0's template, so divergence would be "
                    "silently ignored): %s vs %s"
                    % (s, stage_sigs[s], stage_sigs[0]))
            if len(new_params) != len(stage_params[0]):
                raise ValueError(
                    "pipeline stage %d created %d parameters but stage 0 "
                    "created %d" % (s, len(new_params),
                                    len(stage_params[0])))
            for a, b in zip(stage_params[0], new_params):
                sa, sb = gb.var(a).shape, gb.var(b).shape
                if tuple(sa or ()) != tuple(sb or ()):
                    raise ValueError(
                        "pipeline stage %d param %r shape %s != stage 0 "
                        "param %r shape %s" % (s, b, sb, a, sa))

    M = int(num_microbatches) if num_microbatches else 0
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pipeline",
        inputs={"X": [input],
                "StageParams": [n for ps in stage_params for n in ps]},
        outputs={"Out": [out]},
        attrs={"sub_block": sub0.idx, "num_stages": int(num_stages),
               "params_per_stage": len(stage_params[0]),
               "param_names": list(stage_params[0]),
               "in_name": in_name, "out_name": out_name,
               "num_microbatches": M})
    return out


def _suffixed(base, suffix):
    """`base`'s settings for one of a layer's several parameters, named
    <base name>.<suffix> where the base has a name."""
    return ParamAttr(
        name=(base.name + "." + suffix) if base.name else None,
        initializer=base.initializer, learning_rate=base.learning_rate,
        regularizer=base.regularizer, trainable=base.trainable,
        gradient_clip=base.gradient_clip)


def switch_moe(input, num_experts, d_hidden, capacity_factor=1.25,
               param_attr=None, name=None):
    """Top-1 switch mixture-of-experts FFN (lowering: ops/parallel_ops.py
    -> parallel/moe.py moe_layer). input [..., D] -> (out [..., D],
    aux_loss [1]).

    Each token routes to its argmax expert (fixed capacity
    ceil(N/E * capacity_factor); overflow tokens pass through with zero
    expert output). aux_loss is the GShard load-balance term — add a small
    multiple to the training loss. Under ParallelExecutor with a mesh
    carrying an 'ep' axis the expert dim is sharded P('ep') and XLA lowers
    the dispatch/combine einsums to the all-to-all over ICI; on one chip
    the same op runs dense.
    """
    helper = LayerHelper("moe", name=name)
    dtype = input.dtype
    d = int(input.shape[-1])
    e, h = int(num_experts), int(d_hidden)
    base = ParamAttr.to_attr(param_attr)
    if base is False:
        raise ValueError("switch_moe requires parameters")

    def attr(suffix, shape, is_bias=False):
        return helper.create_parameter(attr=_suffixed(base, suffix),
                                       shape=shape, dtype=dtype,
                                       is_bias=is_bias)

    gate = attr("gate", [d, e])
    w1 = attr("w1", [e, d, h])
    b1 = attr("b1", [e, h], is_bias=True)
    w2 = attr("w2", [e, h, d])
    b2 = attr("b2", [e, d], is_bias=True)
    out = helper.create_variable_for_type_inference(dtype)
    aux = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="moe",
        inputs={"X": [input], "Gate": [gate], "W1": [w1], "B1": [b1],
                "W2": [w2], "B2": [b2]},
        outputs={"Out": [out], "AuxLoss": [aux]},
        attrs={"capacity_factor": float(capacity_factor)})
    return out, aux


def moe_ffn(input, num_experts, d_expert, top_k, norm_topk_prob=False,
            param_attr=None, name=None, router_input=None, activation="silu",
            experts_held=None, first_expert=0, scoring="softmax",
            expert_bias_attr=None, routed_scaling_factor=1.0,
            norm_epsilon=None, gated=True, n_group=1, topk_group=1):
    """Dropless top-k routed experts, each a gated FFN without bias
    (lowering: ops/parallel_ops.py -> parallel/moe.py routed_ffn). input
    [..., D] -> (out [..., D], balance_loss [1], z_loss [1], expert_load
    [num_experts] int32).

    A token's softmax router probabilities pick its top_k experts and weigh
    their outputs (renormalised to sum 1 only with norm_topk_prob). Every
    one of the top_k * N assignments is computed, whatever the imbalance:
    there is no capacity. balance_loss is num_experts * sum_e (c_e / N) *
    mean_n p[n, e] and z_loss mean_n logsumexp(router logits)^2: add small
    multiples of both to the training loss. expert_load is c_e, the
    assignments an expert received; it sums to top_k * N.

    router_input: the tensor the router reads, [..., Dr] (another width
    than input's where the experts work in a latent space: the router's
    parameter is then [Dr, num_experts]); None: input itself. activation:
    "silu" or "relu", the gate branch's. gated False: an expert is two
    matrices, act(x w_up) w_down with activation "relu2" (relu(.)^2), and
    the layer has no w_gate parameter. A layer
    that is one chip's share of an expert-parallel one gives experts_held
    (None: all) and first_expert: the router keeps num_experts columns and
    the top_k is over all of them, the expert weights are [experts_held, ..],
    only assignments to those experts are computed and `out` is their
    partial sum; expert_load still counts all assignments.

    scoring: "softmax" (the above) or "sigmoid": a token's scores are s =
    sigmoid(router logits), an expert each on its own; with norm_topk_prob
    the chosen scores are divided by their sum + 1e-6; balance_loss and
    z_loss are zeros, both being defined on a softmax. expert_bias_attr: a
    ParamAttr (or True) makes a float32 parameter [num_experts], named
    <param_attr's name>.expert_bias and created after the router, that is
    persistable and not trainable: it has no gradient variable, the
    optimizer holds no state for it and a gradient clip does not see it. It
    is added to the scores for the choice of the top_k and to nothing else:
    the weights come from the scores without it. Its initializer is the
    attr's (zeros by default); None or False: no bias. It is refused with
    softmax scoring, where no model defines it. routed_scaling_factor
    multiplies the weights after the renormalisation. norm_epsilon: what a
    sigmoid router's renormalisation adds to the chosen scores' sum instead
    of 1e-6 (None: 1e-6). n_group > 1 with topk_group < n_group: DeepSeek-V3's
    group-limited choice (arXiv:2412.19437, `noaux_tc`): the experts are
    n_group runs of neighbours, a group's score the sum of its two largest
    scores (with the bias), and the top_k is taken inside the topk_group
    best groups, every score outside them out of the choice; the weights
    are the chosen experts' as without it. A share's held experts lie in
    few groups: a token that keeps none of them gets zeros here.
    """
    helper = LayerHelper("moe_ffn", name=name)
    dtype = input.dtype
    d, e, f = int(input.shape[-1]), int(num_experts), int(d_expert)
    held = e if experts_held is None else int(experts_held)
    if not 1 <= int(top_k) <= e:
        raise ValueError("moe_ffn top_k must be in [1, num_experts=%d], got "
                         "%r" % (e, top_k))
    if not (1 <= held and 0 <= int(first_expert) <= e - held):
        raise ValueError("moe_ffn cannot hold experts %d..%d of %d"
                         % (first_expert, int(first_expert) + held - 1, e))
    if activation not in (("silu", "relu") if gated else ("relu2",)):
        raise ValueError(
            "moe_ffn activation must be 'silu' or 'relu' in gated experts "
            "and 'relu2' in ungated ones, got %r with gated=%r"
            % (activation, gated))
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError("moe_ffn scoring must be 'softmax' or 'sigmoid', "
                         "got %r" % (scoring,))
    n_group, topk_group = int(n_group), int(topk_group)
    limited = n_group > 1 and topk_group < n_group
    if n_group < 1 or e % n_group or not 1 <= topk_group <= n_group or (
            limited and (e // n_group < 2
                         or topk_group * (e // n_group) < int(top_k))):
        raise ValueError(
            "moe_ffn cannot choose top_k=%d inside topk_group=%d of n_group="
            "%d groups of %d experts (groups of two or more that divide the "
            "experts, and the kept ones hold top_k)" % (
                top_k, topk_group, n_group, e))
    biased = expert_bias_attr not in (None, False)
    if biased and scoring != "sigmoid":
        raise ValueError("moe_ffn adds an expert bias to sigmoid scores "
                         "only, scoring is %r" % (scoring,))
    base = ParamAttr.to_attr(param_attr)
    if base is False:
        raise ValueError("moe_ffn requires parameters")

    def param(suffix, shape):
        return helper.create_parameter(attr=_suffixed(base, suffix),
                                       shape=shape, dtype=dtype)

    inputs = {"X": [input], "Router": [param("router", [
        d if router_input is None else int(router_input.shape[-1]), e])]}
    if biased:
        given = ParamAttr() if expert_bias_attr is True \
            else ParamAttr.to_attr(expert_bias_attr)
        bias = helper.create_parameter(
            attr=ParamAttr(
                name=(base.name + ".expert_bias") if base.name else None,
                initializer=given.initializer or ConstantInitializer(0.0),
                trainable=False),
            shape=[e], dtype="float32")
        bias.stop_gradient = True
        inputs["ExpertBias"] = [bias]
    if gated:
        inputs["WGate"] = [param("w_gate", [held, d, f])]
    inputs.update({
              "WUp": [param("w_up", [held, d, f])],
              "WDown": [param("w_down", [held, f, d])]})
    # what the defaults leave as it was is not written: a layer that holds
    # every expert, routes from its input and gates with SiLU is the op it
    # always was
    attrs = {"top_k": int(top_k), "norm_topk_prob": bool(norm_topk_prob)}
    if router_input is not None:
        inputs["RouterX"] = [router_input]
    if activation != "silu":
        attrs["activation"] = str(activation)
    if first_expert:
        attrs["first_expert"] = int(first_expert)
    if scoring != "softmax":
        attrs["scoring"] = str(scoring)
    if float(routed_scaling_factor) != 1.0:
        attrs["scale"] = float(routed_scaling_factor)
    if norm_epsilon is not None:
        attrs["norm_epsilon"] = float(norm_epsilon)
    if limited:
        attrs["n_group"], attrs["topk_group"] = n_group, topk_group
    out = helper.create_variable_for_type_inference(dtype)
    balance = helper.create_variable_for_type_inference("float32")
    z = helper.create_variable_for_type_inference("float32")
    load = helper.create_variable_for_type_inference("int32",
                                                     stop_gradient=True)
    helper.append_op(
        type="moe_ffn", inputs=inputs,
        outputs={"Out": [out], "BalanceLoss": [balance], "ZLoss": [z],
                 "ExpertLoad": [load]},
        attrs=attrs)
    return out, balance, z, load
