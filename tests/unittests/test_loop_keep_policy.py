"""What a recomputing loop keeps across the forward/backward boundary
(ops/control_ops.py keeps_across_passes): the tiny Ouro program of
test_causal_lm_ouro.py, 2 layers run 4 times, with the flash kernels in the
interpreter (FLAGS_flash_min_seq=0 takes them at T=16). The loop's
jax.checkpoint keeps a Pallas forward kernel's outputs and the product a
`mul` names because its contraction (the SwiGLU's 48) is wider than its
result (32); the step is the same step, the backward scan runs neither a
second time, and the program's counters say so.
"""
import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
# public only as print_saved_residuals, which prints this list
from jax._src.ad_checkpoint import saved_residuals

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import lowering
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import control_ops

import test_causal_lm_ouro as ouro

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAYERS, TRIPS = 2, 4
FORMS = ("kept", "replayed", "no_recompute")


def _keep_nothing(prim, avals, params):
    return None


def _step(recompute=True, cfg=ouro.CFG, amp=False, grads=True):
    """(the step's function as the executors build it, its arguments, the
    parameters' names): the loss and, with `grads`, every parameter's
    gradient fetched."""
    main, startup, fetch = ouro._build(cfg, amp, recompute)
    params = [p.name for p in main.global_block().all_parameters()]
    feed = ouro._feed()
    fetches = [fetch["loss"].name] + [p + "@GRAD" for p in params] * grads
    rw, ro, out = lowering.analyze_state(main, sorted(feed), fetches)
    fn = lowering.build_program_fn(main, sorted(feed), fetches, rw, ro, out)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        args = ([jnp.asarray(feed[n]) for n in sorted(feed)],
                [scope.get(n) for n in rw], [scope.get(n) for n in ro],
                jnp.uint32(0))
    return fn, args, params


# the loop's sub-block in the tiny program, as the kept values are labelled
LOOP = "1"
COUNTED = {
    "attention_forward": ("ptpu_remat_ops_total", dict(
        kind="forward", op="fused_attention")),
    "attention_replayed": ("ptpu_remat_ops_total", dict(
        kind="replayed", op="fused_attention")),
    "mul_replayed": ("ptpu_remat_ops_total", dict(kind="replayed", op="mul")),
    "kept_kernels": ("ptpu_remat_kept_values_total", dict(
        loop=LOOP, op="fused_attention", rule="kernel_output")),
    "kept_products": ("ptpu_remat_kept_values_total", dict(
        loop=LOOP, op="mul", rule="narrow_matmul"))}


def _counted():
    return {key: ouro._count(name, **labels)
            for key, (name, labels) in COUNTED.items()}


def _primitives(jaxpr, name, inside=()):
    """The equations of `name` in `jaxpr`, each as the primitives it is
    nested in."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append((inside, eqn))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives(inner, name, inside + (eqn.primitive.name,))
    return found


def _spy(recomputing, bodies):
    def spied(ctx, attrs, step, carry, xt, trips):
        body = recomputing(ctx, attrs, step, carry, xt, trips)
        bodies.append((body, (carry, xt)))
        return body
    return spied


@pytest.fixture(scope="module")
def steps():
    """{form: (results, jaxpr, counters booked by its lowering)} of the one
    training step, its loop lowered in three ways; "saved_<form>": what
    jax's own partial evaluation of the checkpointed body saves, [(aval,
    where from)]; "kept_bytes" and "report" of the shipped form."""
    out = {}
    for form in FORMS:
        with pytest.MonkeyPatch.context() as patch:
            patch.setenv("FLAGS_flash_min_seq", "0")
            # since PR 60 the kernel's call is a jax.jit of its own, and jax
            # keeps its partial evaluation of that jit's jaxpr under the
            # policy FUNCTION: a form that patches what the one function
            # answers starts from no cache, or it is handed the form
            # before's split of the kernel (its outputs saved)
            jax.clear_caches()
            if form == "replayed":
                # as the parent lowered the loop: a policy that keeps nothing
                patch.setattr(control_ops, "_kept_by", _keep_nothing)
            # the loop's body under its checkpoint, and what it was given
            bodies = []
            patch.setattr(control_ops, "_recomputing", _spy(
                control_ops._recomputing, bodies))
            before = _counted()
            fn, args, params = _step(recompute=form != "no_recompute")
            jaxpr = jax.make_jaxpr(fn)(*args)
            if form != "no_recompute":
                (body, body_args), = bodies
                out["saved_" + form] = saved_residuals(body, *body_args)
            counted = {k: v - before[k] for k, v in _counted().items()}
            fetched = jax.jit(fn)(*args)[0]
            if form == "kept":
                # a gauge: the next lowering of the loop sets it again
                out["kept_bytes"] = REGISTRY.gauge(
                    "ptpu_remat_kept_bytes").value(loop=LOOP)
                out["report"] = "\n".join(profiler._recompute_lines())
        out[form] = (dict(zip(["loss"] + params, fetched)), jaxpr, counted)
    return out


@pytest.mark.parametrize("other", FORMS[1:])
@pytest.mark.parametrize("name", ["loss"] + ouro.NAMES)
def test_the_step_is_the_same_step(steps, name, other):
    """The loss and every parameter's gradient: kept values against a
    policy that keeps nothing (the parent's loop) and against no
    recomputation at all."""
    got, want = steps["kept"][0][name], steps[other][0][name]
    assert np.abs(np.asarray(want)).max() > 0
    assert ouro._error(got, want) < 1e-5


def _backward_scan(jaxpr, name):
    """Equations of `name` in the loop's backward scan: those of a scan that
    lie under a remat, a kernel's own body apart."""
    return [eqn for inside, eqn in _primitives(jaxpr.jaxpr, name)
            if "scan" in inside and "pallas_call" not in inside
            and any("remat" in p or "checkpoint" in p for p in inside)]


def test_the_backward_scan_runs_no_forward_kernel(steps):
    """A layer's flash forward kernel, once a body of the backward scan
    where the parent's ran it again beside the two backward kernels."""
    def kernels(form):
        return sorted(eqn.params["name"] for eqn in _backward_scan(
            steps[form][1], "pallas_call"))
    backward = ["ptpu_flash_bwd_dkdv", "ptpu_flash_bwd_dq"] * LAYERS
    assert kernels("kept") == sorted(backward)
    assert kernels("replayed") == sorted(
        backward + ["ptpu_flash_fwd"] * LAYERS)
    # the forward scan runs it once a layer either way
    for form in ("kept", "replayed"):
        assert len(_primitives(steps[form][1].jaxpr, "pallas_call")) \
            == LAYERS + len(kernels(form))


def test_the_backward_scan_runs_no_down_projection_again(steps):
    """The products of contraction 48 into 32 columns, [B T, 48] x [48, 32]:
    one a layer less in the backward scan's body, which still holds the two
    products of its gradient."""
    def down(form):
        return sum(
            1 for eqn in _backward_scan(steps[form][1], "dot_general")
            if eqn.invars[0].aval.shape[-1] == 48
            and eqn.outvars[0].aval.shape[-1] == 32
            and eqn.params["dimension_numbers"][0] in (((1,), (0,)),
                                                       ((2,), (0,))))
    assert down("replayed") - down("kept") == LAYERS
    every = {form: len(_backward_scan(steps[form][1], "dot_general"))
             for form in ("kept", "replayed")}
    assert every["replayed"] - every["kept"] == LAYERS


def test_a_kept_op_is_not_counted_as_replayed(steps):
    """ptpu_remat_ops_total counts fused_attention and the narrow `mul`
    forward as before and replayed only under the policy that keeps
    nothing; ptpu_remat_kept_values_total counts them, once a trip."""
    uses = LAYERS * TRIPS
    assert steps["kept"][2] == {
        "attention_forward": uses, "attention_replayed": 0,
        "mul_replayed": 6 * uses, "kept_kernels": uses,
        "kept_products": uses}
    assert steps["replayed"][2] == {
        "attention_forward": uses, "attention_replayed": uses,
        "mul_replayed": 7 * uses, "kept_kernels": 0, "kept_products": 0}
    assert set(steps["no_recompute"][2].values()) == {0}


def test_the_benchmark_reads_one_forward_run(steps, monkeypatch):
    """benchmark/configs/ouro.py flash_forward_runs divides replayed by
    forward lowerings of fused_attention: 1.0 where the kernel's outputs
    are kept, 2.0 under the parent's loop."""
    monkeypatch.syspath_prepend(REPO)
    from benchmark import manifest
    module = manifest.load_module(os.path.join(
        REPO, "benchmark", "configs", "ouro.py"))

    def runs(form):
        counted = steps[form][2]
        family = {"samples": [
            ({"kind": "forward", "op": "fused_attention"},
             counted["attention_forward"]),
            ({"kind": "replayed", "op": "fused_attention"},
             counted["attention_replayed"])]}
        monkeypatch.setattr(
            REGISTRY, "snapshot", lambda: {"ptpu_remat_ops_total": family})
        return module.flash_forward_runs()
    assert runs("kept") == 1.0
    assert runs("replayed") == 2.0


def test_kept_bytes_are_the_kept_values(steps):
    """A trip keeps, a layer, the kernel's output rows [B T, H D] and its
    logsumexp (float32 here) and the down projection's result [B, T, 32];
    the gauge holds that times the trips, under the loop's sub-block."""
    kernels = [eqn for _, eqn in _primitives(
        steps["kept"][1].jaxpr, "pallas_call")
        if eqn.params["name"] == "ptpu_flash_fwd"]
    assert len(kernels) == LAYERS
    a_layer = sum(v.aval.size * v.aval.dtype.itemsize
                  for v in kernels[0].outvars) \
        + ouro.B * ouro.T * 32 * 4
    assert a_layer > 2 * ouro.B * ouro.T * 32 * 4
    assert steps["kept_bytes"] == TRIPS * LAYERS * a_layer


def _saved_by_policy(saved):
    """Of a checkpointed body's residuals, those the policy saved: not the
    body's arguments (the carry) and not what it closes over (weights)."""
    return [(aval, where) for aval, where in saved
            if not where.startswith(("from the argument", "from a constant"))]


def test_the_booked_values_are_what_the_checkpoint_saves(steps):
    """_kept_values reads the kept set off the body's equations BEFORE jax
    differentiates it (a custom_vjp's by its primal, where jax asks the
    policy about the forward rule's equations): the bytes and the number of
    values it booked are those of jax.ad_checkpoint's own account of what
    the checkpoint saves, so a kernel whose forward rule differs from its
    primal, or a change of jax's partial evaluation, fails here."""
    saved = _saved_by_policy(steps["saved_kept"])
    a_trip = sum(aval.size * aval.dtype.itemsize for aval, _ in saved)
    assert TRIPS * a_trip == steps["kept_bytes"] > 0
    # a kernel's two outputs and a product's one, a layer: each booked once
    # a trip as ONE kept value of its fluid op
    counted = steps["kept"][2]
    assert TRIPS * len(saved) \
        == 2 * counted["kept_kernels"] + counted["kept_products"]
    # the logsumexp rows as the kernel's jit leaves them (PR 60: the call
    # behind a `jit` equation, which jax's partial evaluation splits under
    # the same policy); the output rows pass a reshape first, as before
    assert sum("jitted function '_flash_fwd_call'" in where
               for _, where in saved) == LAYERS
    # under the policy that keeps nothing, the carry and the weights alone
    assert _saved_by_policy(steps["saved_replayed"]) == []
    assert len(steps["saved_replayed"]) \
        == len(steps["saved_kept"]) - len(saved)


def test_kept_values_of_a_body_with_every_rule_but_a_kernel():
    """The same account for what the toy loop does not reach: a row
    statistic over 128 columns, a named product under a jit, a product that
    is not named and a reduction that is too short."""
    from jax.ad_checkpoint import checkpoint_name

    @jax.jit
    def narrow(h, w):
        return checkpoint_name(h @ w, control_ops.NARROW_MATMUL)

    def body(x, w_up, w_down):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
        h = jnp.tanh(x @ w_up)
        return jnp.sin(narrow(h, w_down)) + jnp.max(x[:, :64], axis=-1,
                                                    keepdims=True)
    args = [jax.ShapeDtypeStruct(shape, jnp.float32)
            for shape in ((8, 128), (128, 256), (256, 128))]
    kept = list(control_ops._kept_values(jax.make_jaxpr(body)(*args).jaxpr))
    assert sorted((rule, size) for _, rule, size in kept) == [
        ("narrow_matmul", 8 * 128 * 4), ("row_reduction", 8 * 4)]
    saved = _saved_by_policy(saved_residuals(jax.checkpoint(
        body, policy=control_ops.keeps_across_passes), *args))
    assert sorted(aval.size * aval.dtype.itemsize for aval, _ in saved) \
        == sorted(size for _, _, size in kept)


@pytest.mark.parametrize("shape,axes,rule", [
    ((4, 2048), (1,), "row_reduction"), ((4, 128), (1,), "row_reduction"),
    ((4, 8, 16), (1, 2), "row_reduction"), ((4, 127), (1,), None),
    ((2048, 4), (1,), None)])
def test_a_reduction_over_a_lane_row_is_kept(shape, axes, rule):
    """The third rule, which the toy model's norms over 32 columns do not
    reach: a reduce_* over 128 elements or more (the real model's row
    statistics, a 2048th of what they read); jax.lax.reduce_precision, a
    `reduce_` by name alone, never."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    for fn in (jnp.sum, jnp.max):
        eqn, = jax.make_jaxpr(lambda a: fn(a, axis=axes))(x).eqns
        assert control_ops._kept_by(
            eqn.primitive, [v.aval for v in eqn.invars], eqn.params) == rule
        assert control_ops.keeps_across_passes(
            eqn.primitive, *[v.aval for v in eqn.invars], **eqn.params) \
            == (rule is not None)
    eqn, = jax.make_jaxpr(
        lambda a: jax.lax.reduce_precision(a, 8, 7))(x).eqns
    assert eqn.primitive.name == "reduce_precision"
    assert control_ops._kept_by(eqn.primitive, [x], eqn.params) is None


@pytest.mark.parametrize("kernel,module,kept", [
    ("ptpu_rotary", "rotary_kernels", None),
    ("ptpu_rms_norm_bwd", "rms_norm_kernels", "kernel_output"),
    ("ptpu_kda_fwd", "kda_kernels", "kernel_output")])
def test_a_kernel_that_costs_its_bytes_is_replayed(kernel, module, kept):
    """The first rule keeps a Pallas kernel's outputs, but not those of a
    kernel whose entry says it is one read and one write of its operand
    (`kernel_entry(.., costs_its_bytes=True)`, where it is defined): the
    rotary's one pass. The policy holds no kernel's name."""
    import importlib
    import types
    importlib.import_module("paddle_tpu.ops." + module)
    prim = types.SimpleNamespace(name="pallas_call")
    assert control_ops._kept_by(prim, [], {"name": kernel}) == kept
    assert control_ops.keeps_across_passes(prim, name=kernel) \
        == (kept is not None)
    assert kernel not in open(control_ops.__file__).read()


def test_a_loop_that_does_not_recompute_books_nothing(steps):
    """StaticRNN without `recompute` (the book's models): no checkpoint, no
    counter, no product named, and its body is not traced apart from the
    scan's own trace. The recomputing loop's body names a layer's down
    projection, and nothing outside the loop is named."""
    jaxpr = steps["no_recompute"][1]
    assert not _primitives(jaxpr.jaxpr, "name")
    named = _primitives(steps["kept"][1].jaxpr, "name")
    assert named and all("scan" in inside for inside, _ in named)
    assert {eqn.params["name"] for _, eqn in named} \
        == {control_ops.NARROW_MATMUL}
    assert not [p for inside, _ in _primitives(jaxpr.jaxpr, "dot_general")
                for p in inside if "remat" in p or "checkpoint" in p]


def test_profile_report_lists_the_kept_values(steps):
    """profile_report()'s lines on recomputation, as they read after the
    loop's lowering: a kept op is not replayed, and what loop 1 keeps."""
    report = steps["report"]
    assert "a kept op is not replayed" in report
    assert "loop 1 keeps" in report and "kernel_output of fused_attention" \
        in report and "narrow_matmul of mul" in report
    assert "ptpu_remat_kept_values_total" in report
    assert "ptpu_remat_kept_bytes" in report


# The lowered step (StableHLO, no locations) of a model with no loop, as the
# parent commit (PR 57) lowered it: sha256 of the text, first 16 digits. Its
# down projections are narrow `mul`s too, and are not named: outside a
# recomputing loop's body the `mul` rule traces what it traced.
# Since PR 70 `rotary_embedding` keeps its linearization (`calls_pallas`: its
# rule can reach `ptpu_rotary`), so the forward op lowers under jax.vjp and
# the grad op replays nothing: other text, and with the field off the text of
# PR 57 to the digit ("9a730a94c876afc8" / "f49ffd8c5ce85187").
_PARENT_STEP = {("f32", True): "70cedc0731f00ce4",
                ("amp", True): "f2d4b2c4d23cb0f8",
                ("f32", False): "9a730a94c876afc8",
                ("amp", False): "f49ffd8c5ce85187"}


@pytest.mark.parametrize("precision,rotary_kept", sorted(_PARENT_STEP))
def test_a_program_without_a_recomputing_loop_lowers_as_the_parent_did(
        precision, rotary_kept, monkeypatch):
    from paddle_tpu.core import registry
    monkeypatch.delenv("FLAGS_flash_min_seq", raising=False)
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.setattr(registry.get("rotary_embedding"), "calls_pallas",
                        rotary_kept)
    fn, args, _ = _step(cfg=ouro.DENSE, amp=precision == "amp", grads=False)
    assert not _primitives(jax.make_jaxpr(fn)(*args).jaxpr, "name")
    text = jax.jit(fn).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_STEP[precision, rotary_kept]
