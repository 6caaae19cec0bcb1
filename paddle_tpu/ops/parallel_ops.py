"""Program-level lowerings of the parallel subsystems: the `pipeline` op
(GPipe looped pipeline, parallel/pipeline.py), the `moe` op (top-1
switch expert parallelism, parallel/moe.py) and the `moe_ffn` op (dropless
top-k routed experts, parallel/moe.py routed_ffn).

These make PP and EP reachable from the fluid Program path
(layers.pipelined_stack / layers.switch_moe build the ops; Executor runs
them sequentially / densely on one chip; ParallelExecutor with a mesh
carrying a 'pp' / 'ep' axis runs the real collective schedules). The
reference era had neither — its only model-partitioning story is the
pserver parameter split (python/paddle/fluid/distribute_transpiler.py) —
but SURVEY §2 commits to DP/TP/PP/SP/EP composable on one Mesh *for
Programs*, which is exactly what these two ops close.

Both lower through pure-jax library code, so `grad_of` (core/backward.py)
differentiates them with jax.vjp like any other registered op: the
backward pipeline falls out of lax.scan/ppermute transposition, the MoE
backward out of the einsum transposes. No hand-written grad machinery.
"""
import jax.numpy as jnp
from jax import lax

from ..core import registry
from ..core.registry import single
from ..core.lowering import Env, lower_block, PROGRAM_ERR
from ..observability.registry import REGISTRY


def _stage_runner(ctx, attrs):
    """Build stage_fn(param_values, x) -> y that lowers the template
    sub-block with the stage's parameter values bound to the template
    names. `marker` (a python int or traced int32) is folded into the rng
    stream so random ops vary per stage, and suppresses in-graph
    assertion escapes while tracing inside shard_map/scan."""
    sub = ctx.program.blocks[attrs["sub_block"]]
    pnames = list(attrs["param_names"])
    in_name = attrs["in_name"]
    out_name = attrs["out_name"]

    def stage_fn(plist, xin, marker, traced):
        """traced=True while inside shard_map/scan (pp path): assertion
        flags can't escape the trace, so add_error must be suppressed via
        _loop_iters. The sequential path is at top trace level — only the
        rng stream needs the per-stage fold, assertions still escape.
        Returns (out, err): err sweeps the stage env's PROGRAM_ERR and
        TensorArray overflow flags (like control_ops' sub-blocks do) so
        in-stage overflows reach the host on the sequential path."""
        from .control_ops import _sweep_overflow
        benv = Env()
        benv.write(PROGRAM_ERR, jnp.zeros((), bool))
        for n, v in zip(pnames, plist):
            benv.write(n, v)
        benv.write(in_name, xin)
        stack = ctx._loop_iters if traced else ctx._rng_extra
        stack.append(marker)
        try:
            lower_block(ctx, sub, benv)
        finally:
            stack.pop()
        return benv.read(out_name), _sweep_overflow(
            benv, jnp.zeros((), bool))

    return stage_fn


def _pipeline_lower(ctx, ins, attrs):
    x = single(ins, "X")
    flat = list(ins.get("StageParams", []))
    S = int(attrs["num_stages"])
    Pn = int(attrs["params_per_stage"])
    stage_fn = _stage_runner(ctx, attrs)

    mesh = ctx.mesh
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    if pp > 1:
        if pp != S:
            raise ValueError(
                "pipeline op has %d stages but the mesh 'pp' axis is %d — "
                "stage count and pipeline ranks must match" % (S, pp))
        from ..parallel.pipeline import pipeline_apply
        # stack each template param across stages -> [S, ...] leaves; the
        # shard_map in_spec P('pp') places stage s's slice on rank s
        stacked = [jnp.stack([flat[s * Pn + j] for s in range(S)])
                   for j in range(Pn)]
        M = int(attrs.get("num_microbatches") or 0) or None
        batch_axis = "dp" if mesh.shape.get("dp", 1) > 1 else None
        out = pipeline_apply(
            # error flags minted inside shard_map/scan can't escape the
            # trace — dropped here, mirroring add_error's loop rule
            lambda plist, xin: stage_fn(plist, xin,
                                        lax.axis_index("pp"), True)[0],
            stacked, x, mesh, num_microbatches=M, axis="pp",
            batch_axis=batch_axis)
        return {"Out": [out]}
    # single-chip / no-pp-axis: run the stages sequentially (the exact
    # math the pipeline schedule computes, minus the ring); stage error
    # flags escape via the "__errors__" channel like rnn_scan's
    out = x
    err = jnp.zeros((), bool)
    for s in range(S):
        out, serr = stage_fn(flat[s * Pn:(s + 1) * Pn], out, s, False)
        err = err | serr
    return {"Out": [out], "__errors__": err}


def _pipeline_infer(block, op, out_vars):
    xv = block.var_recursive(op.inputs["X"][0])
    ov = block.var_recursive(op.outputs["Out"][0])
    ov.shape, ov.dtype = xv.shape, xv.dtype


registry.register("pipeline", _pipeline_lower, infer=_pipeline_infer)


def _moe_lower(ctx, ins, attrs):
    from ..parallel.moe import moe_layer
    x = single(ins, "X")
    params = {"gate": single(ins, "Gate"),
              "w1": single(ins, "W1"), "b1": single(ins, "B1"),
              "w2": single(ins, "W2"), "b2": single(ins, "B2")}
    mesh = ctx.mesh
    ep = mesh.shape.get("ep", 1) if mesh is not None else 1
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    y, aux = moe_layer(params, x2,
                       capacity_factor=float(attrs["capacity_factor"]),
                       mesh=mesh if ep > 1 else None, axis="ep")
    return {"Out": [y.reshape(x.shape)], "AuxLoss": [aux.reshape(1)]}


_scalar = ("X", lambda shape, attrs: (1,), "float32")
registry.register("moe", _moe_lower, infer=registry.shapes_from(
    Out="X", AuxLoss=_scalar))


def _moe_ffn_lower(ctx, ins, attrs):
    """Dropless top-k routed experts (parallel/moe.py routed_ffn), gated
    (WGate, WUp, WDown) or, without WGate, of two matrices.
    Under AMP the router stays float32 and the experts compute in bfloat16
    from the float32 master weights; the op decides that here because one
    input, X, may feed both. RouterX, where the model gives it, is what the
    router reads instead of X, at a width of its own where the Router's
    rows say so; the weights' leading dimension is the experts
    held, `first_expert` the index of the first. `scoring` (softmax where
    absent, or sigmoid), ExpertBias [E] (added to the scores for the choice
    of the top_k alone; an input without a gradient variable), `scale` and
    `norm_epsilon` (routed_ffn's norm_eps) are routed_ffn's, and `n_group`
    with `topk_group` its `groups`, the group limit on the choice (absent:
    none)."""
    from ..parallel.moe import routed_ffn
    x = single(ins, "X")
    router_x = single(ins, "RouterX") if ins.get("RouterX") else None
    out, balance, z, load = routed_ffn(
        x.reshape(-1, x.shape[-1]), single(ins, "Router"),
        single(ins, "WGate") if ins.get("WGate") else None,
        single(ins, "WUp"), single(ins, "WDown"),
        top_k=int(attrs["top_k"]),
        norm_topk_prob=bool(attrs.get("norm_topk_prob", False)),
        expert_dtype=jnp.bfloat16 if getattr(ctx, "amp", False) else None,
        router_x=None if router_x is None
        else router_x.reshape(-1, router_x.shape[-1]),
        activation=str(attrs.get("activation", "silu")),
        first_expert=int(attrs.get("first_expert", 0)),
        scoring=str(attrs.get("scoring", "softmax")),
        expert_bias=single(ins, "ExpertBias") if ins.get("ExpertBias")
        else None,
        scale=float(attrs.get("scale", 1.0)),
        norm_eps=attrs.get("norm_epsilon"), mesh=ctx.mesh,
        groups=(int(attrs["n_group"]), int(attrs["topk_group"]))
        if attrs.get("n_group") else None)
    return {"Out": [out.reshape(x.shape)], "BalanceLoss": [balance],
            "ZLoss": [z], "ExpertLoad": [load]}


# The outputs' shapes, written down: Out is X's, the two loss terms [1]
# float32, ExpertLoad [E] int32. Inferred by tracing the rule, as an op
# without this is, the whole layer (the router, the sort, the loops over the
# held rows and, on a TPU, the grouped-matmul kernels) was traced twice a
# layer when the program was built, for these four shapes.
registry.register("moe_ffn", _moe_ffn_lower, infer=registry.shapes_from(
    Out="X", BalanceLoss=_scalar, ZLoss=_scalar,
    ExpertLoad=("Router", lambda shape, attrs: shape[1:2], "int32")))


@registry.counts("moe_ffn")
def _count_moe_layer(ctx, attrs, ins):
    from ..parallel.moe import (KERNEL_MATMUL, matmul_route, numbered_by,
                                rows_moved)
    router, w_up = ins["Router"][0], ins["WUp"][0]
    experts, held = router.shape[1], w_up.shape[0]
    path = matmul_route(
        w_up.shape[1], w_up.shape[2],
        jnp.bfloat16 if ctx.amp else ins["X"][0].dtype, ctx.mesh)
    # what the defaults leave as it was counts under the labels it always
    # had: an ungated layer says so, and a router that reads another width
    # than the experts' input says which, and a share narrower than top_k
    # that its assignments are numbered by held expert, and a layer on the
    # kernels' route that its unit is the gate/up kernel's epilogue
    own = {}
    if path == KERNEL_MATMUL:
        own["unit"] = "kernel"
    if not ins.get("WGate"):
        own["gated"] = "false"
    if numbered_by(experts, held, attrs["top_k"]) == "expert":
        own["numbered"] = "expert"
    if attrs.get("n_group"):
        own["groups"] = str(attrs["n_group"])
        own["kept_groups"] = str(attrs["topk_group"])
    router_input = "pre_attention" if ins.get("RouterX") else "own"
    if router.shape[0] != ins["X"][0].shape[-1]:
        router_input = str(router.shape[0])
    REGISTRY.counter(
        "ptpu_moe_layers_total",
        "moe_ffn ops lowered (forward ops, not a grad op's replay), by "
        "experts a token, experts routed over, experts held, the gate's "
        "activation, what the router reads (the experts' own input or "
        "another tensor, pre_attention), the grouped-matmul route "
        "(ragged_dot, or expert_gmm: the kernels of ops/expert_gmm.py), the "
        "rows of the sorted buffer that every pass between the router "
        "and the layer's output touches (all, or the tiles of the held "
        "assignments: the four permutations, the two d rows' sum, the "
        "unit's transpose, and the unit itself and every buffer's first "
        "value where `unit` is kernel), how the router scores "
        "(softmax or sigmoid), whether an expert bias enters the choice of "
        "the top_k and the factor that scales the weights; `gated` false "
        "where an expert is two matrices (activation relu2), and under "
        "router_input the router's own input width where it is not the "
        "experts'; `numbered` expert where the share held is narrower than "
        "top_k and the assignments are numbered by held expert (held * N of "
        "them, a group's rows by token and a token's sum in expert order) "
        "and not by top-k slot (top_k * N, by slot then token, in score "
        "order), as they are wherever the label is absent "
        "(moe.numbered_by); `unit` kernel where the experts' unit runs as "
        "the epilogue of the one kernel that multiplies a row tile by the "
        "gate and up matrices and the buffers of sorted rows start as a "
        "call's output that nothing filled (path expert_gmm: "
        "expert_gmm.gmm_unit, moe._sorted_rows_start), absent where it is a "
        "pass of XLA's over all the buffer's rows (path ragged_dot); "
        "`groups` and `kept_groups` where the choice is limited to a "
        "token's kept_groups best of `groups` runs of neighbouring experts "
        "(moe._group_limited), absent where it is over all experts"
    ).inc(top_k=str(attrs["top_k"]), experts=str(experts), held=str(held),
          activation=str(attrs.get("activation", "silu")),
          router_input=router_input,
          path=path, rows=rows_moved(experts, held),
          scoring=str(attrs.get("scoring", "softmax")),
          bias=str(bool(ins.get("ExpertBias"))).lower(),
          scale="%g" % attrs.get("scale", 1.0), **own)
