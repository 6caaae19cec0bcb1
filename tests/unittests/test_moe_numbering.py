"""parallel/moe.py `routed_ffn` where the share held is narrower than top_k
(Nemotron-3-Super's 8 of 512 at top-22): the assignments are numbered by
held expert (held * N of them) and not by top-k slot (top_k * N).
`numbered_by` decides from the shapes alone; the layer is held to plain jax
over every expert (output, `ExpertLoad`, and the gradient of every input) for
each unit, router and option, through `ragged_dot` and through the kernels'
interpreter; the shares of all chips add up to the layer with every expert
held; a token none of whose choices is held gets zeros; a share as wide as
top_k or wider lowers as it did (no compare over [held, top_k, N]); and
`ptpu_moe_layers_total` says `numbered="expert"` for a narrow share alone.

And what moves a scalar an assignment between the router's top-k and the
rows' passes (PR 63): the chosen scores are read by a compare (`_chosen`),
`rank` and the weights' two permutations by sorts (`_sorted_by`), under every
numbering. The forms they replaced (`take_along_axis`, `.at[order].set`,
`[order]`, `[rank]`) are kept here as plain jax.numpy oracles and `_route`
and the layer are held to them to the bit at the cells' shapes; the
jaxpr of a layer forward and backward holds no gather or scatter of one
scalar an assignment; and the compiled step of one layer has no forward pass
under its grad op's scope.

And the rows of the sorted buffer that belong to no group (PR 65): on the
kernels' route the experts' unit is the gate/up kernel's epilogue and the
buffers of sorted rows start as a call's output that nothing filled. The
layer is held to the forms they replaced (two forward kernels and the
jax.numpy unit over the stored arrays; a fill of zeros), stood in their
place, to the bit at the eight cells' shapes, and the compiled step of one
layer has no instruction with all the buffer's rows under the op's scopes
outside the kernels."""
import itertools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.parallel import moe

# float32 against float32, every matmul at full precision: another order of
# the same sums
TOLERANCE = 2e-5

# (experts, held, top_k) of the benchmark's expert cells
CELLS = {"olmoe": (64, 64, 8), "smallthinker": (64, 16, 6),
         "lfm2": (32, 8, 4), "qwen3_next": (512, 32, 10),
         "xing4_0": (64, 8, 4), "glm_4_7_flash": (64, 8, 4),
         "nemotron_3_super": (512, 8, 22), "laguna_s_2_1": (256, 8, 10)}
# the cells whose share is narrower than top_k
NARROW = ("laguna_s_2_1", "nemotron_3_super")


@pytest.mark.parametrize("experts,held,top_k,want", [
    (512, 8, 22, "expert"), (32, 4, 5, "expert"), (16, 1, 2, "expert"),
    (32, 4, 4, "slot"), (64, 16, 6, "slot"), (512, 32, 10, "slot"),
    (64, 8, 4, "slot"), (64, 64, 8, None), (8, 8, 8, None), (4, 4, 1, None)])
def test_the_rule_reads_the_shapes_alone(experts, held, top_k, want):
    assert moe.numbered_by(experts, held, top_k) == want


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_keep_their_numbering(cell):
    experts, held, top_k = CELLS[cell]
    assert moe.numbered_by(experts, held, top_k) == (
        "expert" if cell in NARROW else None if cell == "olmoe" else "slot")


# --- against plain jax over every expert ------------------------------------

N, D, DR, E, F = 40, 24, 36, 32, 16


def _weights(held, gated, router_x, seed=0, d=D, f=F, n=N):
    rng = np.random.RandomState(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    w = {"x": draw(n, d),
         "router": draw(DR if router_x else d, E, scale=0.4),
         "w_up": draw(held, d, f, scale=d ** -0.5),
         "w_down": draw(held, f, d, scale=f ** -0.5)}
    if gated:
        w["w_gate"] = draw(held, d, f, scale=d ** -0.5)
    if router_x:
        w["router_x"] = draw(n, DR)
    return w, draw(n, d), draw(E, scale=0.2)


def _plain(w, top_k, first, held, activation, scoring, bias, norm, scale):
    """The share's output with no sort and no buffer: every held expert on
    every token, weighted by the token's choice of it (0 where it made
    none)."""
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.dot(w.get("router_x", w["x"]), w["router"], precision=hi)
    s = jax.nn.softmax(logits, -1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s if bias is None else s + bias, top_k)
    weight = jnp.take_along_axis(s, chosen, -1)
    if norm:
        weight = weight / (weight.sum(-1, keepdims=True) + (
            moe.SIGMOID_NORM_EPS if scoring == "sigmoid" else 0.0))
    weight = weight * scale
    by_expert = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].add(weight)
    up = jnp.einsum("nd,hdf->nhf", w["x"], w["w_up"], precision=hi)
    if "w_gate" in w:
        gate = jnp.einsum("nd,hdf->nhf", w["x"], w["w_gate"], precision=hi)
        hidden = (jax.nn.silu if activation == "silu" else jax.nn.relu)(
            gate) * up
    else:
        hidden = jnp.square(jax.nn.relu(up))
    y = jnp.einsum("nhf,hfd->nhd", hidden, w["w_down"], precision=hi)
    load = jnp.sum(chosen.reshape(-1)[:, None] == jnp.arange(s.shape[1]),
                   axis=0)
    return jnp.einsum("nhd,nh->nd", y, by_expert[:, first:first + held],
                      precision=hi), load


def _routed(w, top_k, first, activation, scoring, bias, norm, scale):
    out, _, _, load = moe.routed_ffn(
        w["x"], w["router"], w.get("w_gate"), w["w_up"], w["w_down"], top_k,
        norm, router_x=w.get("router_x"), activation=activation,
        first_expert=first, scoring=scoring, expert_bias=bias, scale=scale)
    return out, load


def _value_and_grads(fn, w, g):
    def loss(w):
        out, load = fn(w)
        return jnp.sum(out * g), (out, load)
    (_, (out, load)), grads = jax.value_and_grad(loss, has_aux=True)(w)
    return out, load, grads


def _error(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


CASES = {
    # unit, scoring, bias, norm_topk_prob, scale, router_x, first, held, top_k
    "relu2_sigmoid_bias_norm_scale_latent": (
        "relu2", "sigmoid", True, True, 5.0, True, 8, 4, 9),
    "silu_softmax": ("silu", "softmax", False, False, 1.0, False, 0, 4, 6),
    "silu_softmax_norm_last_share": (
        "silu", "softmax", False, True, 1.0, False, 29, 3, 8),
    "relu_softmax_norm_router_x": (
        "relu", "softmax", False, True, 1.0, True, 12, 4, 5),
    "relu_sigmoid_bias_scale": (
        "relu", "sigmoid", True, False, 2.5, False, 16, 2, 3),
    "relu2_softmax_one_held": (
        "relu2", "softmax", False, True, 1.0, False, 5, 1, 4),
    "silu_sigmoid_norm_every_choice": (
        "silu", "sigmoid", False, True, 1.0, True, 24, 8, 32)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_narrow_share_against_plain_jax(case, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    activation, scoring, biased, norm, scale, router_x, first, held, top_k \
        = CASES[case]
    assert moe.numbered_by(E, held, top_k) == "expert"
    w, g, bias = _weights(held, activation != "relu2", router_x)
    bias = bias if biased else None
    args = (top_k, first, activation, scoring, bias, norm, scale)
    got = _value_and_grads(lambda w: _routed(w, *args), w, g)
    want = _value_and_grads(
        lambda w: _plain(w, top_k, first, held, *args[2:]), w, g)
    assert _error(got[0], want[0]) < TOLERANCE
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert int(got[1].sum()) == top_k * N
    assert sorted(got[2]) == sorted(w)
    for name in w:                  # x, the router and its input, each matrix
        assert _error(got[2][name], want[2][name]) < TOLERANCE, name


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_kernels_interpreter_agrees_with_ragged_dot(gated, monkeypatch):
    d = f = 128                     # whole lane tiles: `matmul_route`
    held, top_k, first = 4, 7, 8
    activation = "silu" if gated else "relu2"
    w, g, bias = _weights(held, gated, True, seed=3, d=d, f=f)
    args = (top_k, first, activation, "sigmoid", bias, True, 2.0)
    results = {}
    for route, env in ((moe.GROUPED_MATMUL, None), (moe.KERNEL_MATMUL, "gmm")):
        if env is None:
            monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("PADDLE_TPU_PALLAS", env)
        assert moe.matmul_route(d, f, jnp.float32) == route
        results[route] = _value_and_grads(lambda w: _routed(w, *args), w, g)
    want = _value_and_grads(
        lambda w: _plain(w, top_k, first, held, *args[2:]), w, g)
    for route, got in results.items():
        assert _error(got[0], want[0]) < TOLERANCE, route
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))
        for name in w:
            assert _error(got[2][name], want[2][name]) < TOLERANCE, (
                route, name)


@pytest.mark.parametrize("activation", ["silu", "relu2"])
def test_the_shares_add_up_to_the_layer(activation, monkeypatch):
    """E / held chips, each its own experts: the partial sums are the layer
    with every expert held, and so are the gradients (x's and the router's
    summed over the chips, a chip's matrices its own)."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    held, top_k = 4, 6
    w, g, bias = _weights(E, activation != "relu2", True, seed=5)
    args = (top_k, 0, activation, "sigmoid", bias, True, 2.5)
    assert moe.numbered_by(E, E, top_k) is None
    whole = _value_and_grads(lambda w: _routed(w, *args), w, g)
    matrices = [k for k in w if k.startswith("w_")]
    out, grads = 0.0, {}
    for first in range(0, E, held):
        share = dict(w, **{k: w[k][first:first + held] for k in matrices})
        part = _value_and_grads(
            lambda w: _routed(w, top_k, first, *args[2:]), share, g)
        np.testing.assert_array_equal(np.asarray(part[1]),
                                      np.asarray(whole[1]))
        out = out + part[0]
        for name in w:
            if name in matrices:
                assert _error(part[2][name],
                              whole[2][name][first:first + held]) \
                    < TOLERANCE, (first, name)
            else:
                grads[name] = grads.get(name, 0.0) + part[2][name]
    assert _error(out, whole[0]) < TOLERANCE
    for name, got in grads.items():
        assert _error(got, whole[2][name]) < TOLERANCE, name


def test_a_token_with_no_held_choice_gets_zeros(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    held, top_k, first = 2, 5, 20
    w, g, _ = _weights(held, False, False, seed=7)
    args = (top_k, first, "relu2", "softmax", None, True, 1.0)
    out, load, grads = _value_and_grads(lambda w: _routed(w, *args), w, g)
    logits = np.asarray(w["x"]) @ np.asarray(w["router"])
    chosen = np.argsort(-logits, axis=1)[:, :top_k]
    without = ~((chosen >= first) & (chosen < first + held)).any(1)
    assert 0 < without.sum() < N    # both kinds of token
    assert not np.asarray(out)[without].any()
    assert np.abs(np.asarray(out)[~without]).min(axis=1).max() > 0
    # such a token's row reaches no expert and its weights no output: the
    # router's softmax alone ties its logits to the other tokens' nothing
    assert not np.asarray(grads["x"])[without].any()
    want = _value_and_grads(
        lambda w: _plain(w, top_k, first, held, *args[2:]), w, g)
    assert _error(grads["router"], want[2]["router"]) < TOLERANCE


# --- a share as wide as top_k, or wider, lowers as it did -------------------

def _all_eqns(jaxpr):
    """Every equation of the jaxpr and of the jaxprs in their parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def _compares_over(jaxpr, dims):
    """The shapes of the `eq`s over the axes `dims`, in that order (the
    chosen scores' compare, `moe._chosen`, is over [top_k, E, N] under every
    numbering), anywhere in the jaxpr."""
    return [eqn.outvars[0].aval.shape for eqn in _all_eqns(jaxpr)
            if eqn.primitive.name == "eq"
            and tuple(eqn.outvars[0].aval.shape) == tuple(dims)]


@pytest.mark.parametrize("held,top_k,numbered", [
    (4, 4, "slot"), (8, 4, "slot"), (E, 4, None), (4, 5, "expert"),
    (4, 22, "expert")])
def test_only_a_narrow_share_compares_over_held_experts(held, top_k,
                                                        numbered):
    assert moe.numbered_by(E, held, top_k) == numbered
    w, g, _ = _weights(held, True, False)
    jaxpr = jax.make_jaxpr(lambda w: _value_and_grads(
        lambda w: _routed(w, top_k, 0, "silu", "softmax", None, True, 1.0),
        w, g))(w).jaxpr
    found = _compares_over(jaxpr, (held, top_k, N))
    assert bool(found) == (numbered == "expert")
    # and the integers are as many as the numbering says: by slot one
    # argsort of the keys and `rank` sorted out of `order`; by expert `order`
    # sorted out of `rank`; under either the weights sorted to their rows
    # and their gradients sorted back (`moe._sorted_by`)
    assert _sorted_lengths(jaxpr) == (
        [N * held] * 3 if numbered == "expert" else [N * top_k] * 4)


def _sorted_lengths(jaxpr):
    return [eqn.outvars[0].aval.shape[0] for eqn in _all_eqns(jaxpr)
            if eqn.primitive.name == "sort"]


# --- the counter ------------------------------------------------------------

def _lower_one_layer(experts, held, top_k, gated=True):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        out, _, _, load = fluid.layers.moe_ffn(
            x, experts, 8, top_k, experts_held=held, gated=gated,
            activation="silu" if gated else "relu2")
        loss = fluid.layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed={"x": np.random.RandomState(0).randn(
            12, 16).astype("float32")}, fetch_list=[loss, load])
    assert np.isfinite(got[0]).all() and int(got[1].sum()) == 12 * top_k


def _moe_samples(snapshot, **where):
    return {tuple(sorted(labels.items())): value for labels, value
            in snapshot.get("ptpu_moe_layers_total", {"samples": []})[
                "samples"]
            if all(labels.get(k) == v for k, v in where.items())}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_counter_names_the_numbering_of_a_narrow_share_alone(cell):
    experts, held, top_k = CELLS[cell]
    where = dict(experts=str(experts), held=str(held), top_k=str(top_k))
    before = _moe_samples(REGISTRY.snapshot(), **where)
    _lower_one_layer(experts, held, top_k, gated=cell != "nemotron_3_super")
    after = _moe_samples(REGISTRY.snapshot(), **where)
    moved = {labels: value - before.get(labels, 0)
             for labels, value in after.items()
             if value != before.get(labels, 0)}
    assert list(moved.values()) == [1]          # the forward op, once
    labels, = (dict(k) for k in moved)
    assert "unit" not in labels                 # `ragged_dot`'s route
    if cell in NARROW:
        assert labels["numbered"] == "expert" and labels["rows"] == "held"
    else:
        assert "numbered" not in labels
        assert labels["rows"] == ("all" if cell == "olmoe" else "held")


# --- no scalar moves by gather or scatter (PR 63) ---------------------------

# scoring, expert bias, norm_topk_prob, scale, norm_eps, activation of the
# eight cells' routers (benchmark/configs/*.json)
ROUTERS = {"olmoe": ("softmax", False, False, 1.0, None, "silu"),
           "smallthinker": ("softmax", False, True, 1.0, None, "relu"),
           "qwen3_next": ("softmax", False, True, 1.0, None, "silu"),
           "lfm2": ("sigmoid", True, True, 1.0, None, "silu"),
           "xing4_0": ("sigmoid", True, True, 2.0, 1e-20, "silu"),
           "glm_4_7_flash": ("sigmoid", True, True, 1.8, 1e-20, "silu"),
           "nemotron_3_super": ("sigmoid", True, True, 5.0, 1e-20, "relu2"),
           "laguna_s_2_1": ("softmax", False, True, 2.5, None, "silu")}


def _route_of_pr_62(logits, top_k, norm_topk_prob, scoring, expert_bias,
                    scale, norm_eps=None):
    """`moe._route` as it stood up to PR 62: the chosen scores are `top_k`'s
    own values, and a gather where a bias enters the choice."""
    if scoring == "softmax":
        lse = jax.nn.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
    else:
        lse, probs = None, jax.nn.sigmoid(logits)
    if expert_bias is None:
        gate, expert = jax.lax.top_k(probs, top_k)
    else:
        _, expert = jax.lax.top_k(
            probs + expert_bias.astype(jnp.float32), top_k)
        gate = jnp.take_along_axis(probs, expert, axis=-1)
    if norm_topk_prob:
        total = gate.sum(-1, keepdims=True)
        if scoring == "sigmoid":
            total = total + (moe.SIGMOID_NORM_EPS if norm_eps is None
                             else norm_eps)
        gate = gate / total
    if scale != 1.0:
        gate = gate * scale
    return probs, lse, gate, expert


def _moved_as_up_to_pr_62(keys, *values):
    """`moe._sorted_by` for keys a permutation, by XLA's scatter and gather:
    the inverse as `zeros.at[keys].set(arange)` (how `rank` came out of
    `order`) and each value read at it (`gate.reshape(-1)[order]` keyed on
    `rank`, `dweight[rank]` keyed on `order`)."""
    inverse = jnp.zeros_like(keys).at[keys].set(
        jnp.arange(keys.shape[0], dtype=keys.dtype))
    return tuple(v[inverse] for v in values)


def _the_parents_forms(monkeypatch):
    monkeypatch.setattr(moe, "_route", _route_of_pr_62)
    monkeypatch.setattr(moe, "_sorted_by", _moved_as_up_to_pr_62)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_router_is_the_parents_to_the_bit(cell):
    """`_route`'s four results, and the gradient into the logits of a loss
    that reads all of them, under the cell's scoring, bias,
    renormalisation and scale."""
    experts, _, top_k = CELLS[cell]
    scoring, biased, norm, scale, norm_eps, _ = ROUTERS[cell]
    rng = np.random.RandomState(11)
    n = 96
    logits = jnp.asarray(rng.randn(n, experts) * 2.0, jnp.float32)
    bias = jnp.asarray(rng.randn(experts) * 0.1, jnp.float32) if biased \
        else None
    g_gate = jnp.asarray(rng.randn(n, top_k), jnp.float32)
    g_probs = jnp.asarray(rng.randn(n, experts), jnp.float32)
    eps = {} if norm_eps is None else {"norm_eps": norm_eps}

    def both(route, logits):
        def loss(logits):
            probs, lse, gate, expert = route(logits, top_k, norm, scoring,
                                             bias, scale, **eps)
            total = jnp.sum(gate * g_gate) + jnp.sum(probs * g_probs)
            if lse is not None:
                total = total + jnp.sum(jnp.square(lse))
            return total, (probs, lse, gate, expert)
        (_, results), grad = jax.value_and_grad(loss, has_aux=True)(logits)
        return results + (grad,)

    for run in (both, lambda route, logits: jax.jit(
            lambda logits: both(route, logits))(logits)):
        got = run(moe._route, logits)
        want = run(_route_of_pr_62, logits)
        assert (got[1] is None) == (want[1] is None) == (scoring == "sigmoid")
        for name, a, b in zip(("scores", "lse", "weights", "experts",
                               "d logits"), got, want):
            if a is not None:
                assert a.shape == b.shape and a.dtype == b.dtype, name
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)
        assert np.abs(np.asarray(got[4])).max() > 0


def _cell_layer(cell, dtype, n=48, d=128, f=128, seed=13):
    """One layer at the cell's (experts, held, top_k) and router, the held
    experts in the middle of the range, widths of whole lane tiles (so that
    the kernels' interpreter takes them): (the inputs, run(inputs) -> (out,
    load), the output's cotangent)."""
    experts, held, top_k = CELLS[cell]
    scoring, biased, norm, scale, norm_eps, activation = ROUTERS[cell]
    rng = np.random.RandomState(seed)

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)

    w = {"x": draw(n, d), "router": draw(d, experts, scale=0.4),
         "w_up": draw(held, d, f, scale=d ** -0.5),
         "w_down": draw(held, f, d, scale=f ** -0.5)}
    if activation != "relu2":
        w["w_gate"] = draw(held, d, f, scale=d ** -0.5)
    bias = draw(experts, scale=0.1) if biased else None
    first = (experts - held) // 2

    def run(w):
        out, _, _, load = moe.routed_ffn(
            w["x"], w["router"], w.get("w_gate"), w["w_up"], w["w_down"],
            top_k, norm, expert_dtype=dtype, activation=activation,
            first_expert=first, scoring=scoring, expert_bias=bias,
            scale=scale, norm_eps=norm_eps)
        return out, load

    return w, run, draw(n, d).astype(dtype or jnp.float32)


def _routes():
    for cell in sorted(CELLS):
        for route, dtype in ((moe.GROUPED_MATMUL, None),
                             (moe.GROUPED_MATMUL, "bfloat16"),
                             (moe.KERNEL_MATMUL, None)):
            yield pytest.param(cell, route, dtype, id="%s-%s-%s" % (
                cell, route, dtype or "float32"))


def _take(route, monkeypatch):
    if route == moe.KERNEL_MATMUL:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "gmm")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)


@pytest.mark.parametrize("cell,route,dtype", _routes())
def test_the_layer_is_the_parents_to_the_bit(cell, route, dtype,
                                             monkeypatch):
    """The output, `ExpertLoad` and every input's gradient with the scalars
    moved by compare and sort, against the same layer with them moved by
    `take_along_axis`, a scatter and two gathers: through `ragged_dot` in
    float32 and with bfloat16 experts (AMP), and through the kernels'
    interpreter."""
    _take(route, monkeypatch)
    w, run, g = _cell_layer(cell, dtype and jnp.dtype(dtype))
    assert moe.matmul_route(128, 128, jnp.dtype(dtype or "float32")) == route
    got = _value_and_grads(run, w, g)
    with monkeypatch.context() as parent:
        _the_parents_forms(parent)
        want = _value_and_grads(run, w, g)
    assert int(got[1].sum()) == CELLS[cell][2] * w["x"].shape[0]
    assert np.abs(np.asarray(got[0], np.float32)).max() > 0
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert sorted(got[2]) == sorted(w)
    for name in w:
        assert np.abs(np.asarray(got[2][name])).max() > 0, name
        np.testing.assert_array_equal(np.asarray(got[2][name]),
                                      np.asarray(want[2][name]), err_msg=name)


def _scalar_moves(jaxpr, counts):
    """(primitive, indices) of every gather, scatter and scatter-add,
    anywhere in the jaxpr, that moves single elements (a slice, or an
    update window, of one element an index) at one of `counts` indices: a
    scalar an assignment or a token. The rows' gathers (a whole row a
    slice), the loops' one-index updates and what the kernels' plan reads
    a visit are not such."""
    found = []
    for eqn in _all_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "gather":
            single = all(size == 1 for size in eqn.params["slice_sizes"])
        elif name in ("scatter", "scatter-add"):
            single = not eqn.params["dimension_numbers"].update_window_dims
        else:
            continue
        indices = int(np.prod(eqn.invars[1].aval.shape[:-1]))
        if single and indices in counts:
            found.append((name, indices))
    return found


def _row_moves(jaxpr, width):
    return [eqn for eqn in _all_eqns(jaxpr) if eqn.primitive.name == "gather"
            and tuple(eqn.params["slice_sizes"]) == (1, width)]


@pytest.mark.parametrize("cell,route", itertools.product(
    sorted(CELLS), (moe.GROUPED_MATMUL, moe.KERNEL_MATMUL)))
def test_no_scalar_moves_by_gather_or_scatter(cell, route, monkeypatch):
    """One layer forward and backward at the cell's shapes: no gather,
    scatter or scatter-add of one scalar an assignment (top_k * N, held * N
    or [N, top_k] indices) or a token; the parent's forms, stood in their
    place, are seen (so the walk is not blind), and the rows' gathers
    stay."""
    _take(route, monkeypatch)
    n = 52                          # no multiple of it counts experts or visits
    w, run, g = _cell_layer(cell, None, n=n)
    experts, held, top_k = CELLS[cell]
    counts = (n, n * top_k, n * held)

    def lowered():
        return jax.make_jaxpr(lambda w: _value_and_grads(run, w, g))(w).jaxpr

    jaxpr = lowered()
    assert _scalar_moves(jaxpr, counts) == []
    assert _row_moves(jaxpr, 128)
    biased = ROUTERS[cell][1]
    with monkeypatch.context() as parent:
        _the_parents_forms(parent)
        seen = sorted(_scalar_moves(lowered(), counts))
    a = n * (held if moe.numbered_by(experts, held, top_k) == "expert"
             else top_k)
    # the chosen scores and their scatter-add (`top_k`'s own transpose where
    # no bias enters), then a scatter and a gather a call of `_sorted_by`
    want = ([("gather", n * top_k)] if biased else []) \
        + [("scatter-add", n * top_k)] + [("gather", a), ("scatter", a)] * 3
    assert seen == sorted(want)


# --- the grad op replays no forward pass ("Left by PR 62" (b)) --------------

def _one_layer_step(experts, held, top_k, width=128, gated=True):
    """The compiled text (this backend's) of one training step of a layer:
    a projection, `moe_ffn` under a sigmoid router with a bias, SGD."""
    from paddle_tpu.core import lowering
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[width], dtype="float32")
        hidden = fluid.layers.fc(input=x, size=width, bias_attr=False)
        out, _, _, _ = fluid.layers.moe_ffn(
            hidden, experts, width, top_k, norm_topk_prob=True,
            experts_held=held, scoring="sigmoid", expert_bias_attr=True,
            gated=gated, activation="silu" if gated else "relu2")
        loss = fluid.layers.mean(out)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rw, ro, outs = lowering.analyze_state(main, ["x"], [loss.name])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        state = [[np.asarray(scope.find_var(name).get_tensor())
                  for name in names] for names in (rw, ro)]
    step = lowering.build_program_fn(main, ["x"], [loss.name], rw, ro, outs)
    return jax.jit(lambda feed, rw, ro: step(feed, rw, ro, 0)).lower(
        [np.zeros((64, width), "float32")], *state).compile().as_text()


def _under(text, scope, *marks):
    """The instructions of the compiled step whose `op_name` lies under the
    fluid op `scope` and holds every one of `marks`."""
    return [name for name in re.findall(r'op_name="([^"]*)"', text)
            if re.search(r"op:%s/" % scope, name)
            and all(mark in name for mark in marks)]


def _forward_passes(text, scope, route, held_share=True):
    """The rows' forward passes under `scope`: where a share is held
    `_held_rows`' loop, and the forward grouped matmuls (the kernels by
    their names: the down matmul's;
    `ragged_dot`, which this backend lowers to a dot, by a dot that is no
    transpose's and no `_token_sum`'s, the router's own among them)."""
    found = [_under(text, scope, "jit(_held_rows)/while")] if held_share \
        else []
    if route == moe.KERNEL_MATMUL:
        # the kernel with the unit in it is counted on a described v5e
        # (test_device_names.py: a Mosaic call is whole to XLA): of the
        # interpreter's loop XLA keeps the outputs a consumer reads, the
        # hidden rows alone in the forward op and all three in the replay,
        # and merges no two loops that differ
        return found + [_under(text, scope, "ptpu_expert_gmm_fwd")]
    return found + [[name for name in _under(text, scope, "dot_general")
                     if "transpose(" not in name
                     and "_token_sum" not in name]]


# one cell a numbering: by slot, by held expert, and every expert held
@pytest.mark.parametrize("cell,route", itertools.product(
    ("lfm2", "nemotron_3_super", "olmoe"),
    (moe.GROUPED_MATMUL, moe.KERNEL_MATMUL)))
def test_the_grad_op_replays_no_forward_pass(cell, route, monkeypatch):
    """`moe_ffn`'s grad op replays the forward rule and counts on XLA to
    merge the replay with the forward op's operations. Whatever the rows'
    passes read has to stay apart from anything XLA will not merge, or the
    passes run twice a step (PR 62: a forward kernel, the unit's pass and
    `_held_rows` under `op:moe_ffn_grad`, +4.5 ms a step). The compiled
    step of one layer has them under the forward op's scope and none under
    the grad op's; and a router whose chosen experts the replay computes
    differently (a stand-in for an operation XLA does not merge) has them
    under both, so the count sees."""
    _take(route, monkeypatch)
    held_share = moe.numbered_by(*CELLS[cell]) is not None
    text = _one_layer_step(*CELLS[cell])
    for found in _forward_passes(text, "moe_ffn", route, held_share):
        assert found
    for found in _forward_passes(text, "moe_ffn_grad", route, held_share):
        assert found == []
    if held_share:
        assert _under(text, "moe_ffn_grad", "jit(_held_weighted)/while")
    else:
        assert _under(text, "moe_ffn_grad", "transpose(")

    calls = itertools.count(1)
    route_of_the_tree = moe._route

    def never_the_same_twice(*args, **kwargs):
        probs, lse, gate, expert = route_of_the_tree(*args, **kwargs)
        return probs, lse, gate, jnp.where(expert >= 0, expert, -next(calls))

    monkeypatch.setattr(moe, "_route", never_the_same_twice)
    text = _one_layer_step(*CELLS[cell])
    for found in _forward_passes(text, "moe_ffn_grad", route, held_share):
        assert found


# --- the rows that belong to no group (PR 65) -------------------------------

def _apart(rows, w_gate, w_up, plan, unit):
    """`expert_gmm.gmm_unit` as the layer made it up to PR 64: a forward
    kernel a matrix and the unit over the stored arrays, all their rows."""
    from paddle_tpu.ops import expert_gmm
    made = [expert_gmm.gmm(rows, w, plan) for w in (w_gate, w_up)
            if w is not None]
    return made + [unit(*made)]


def _the_forms_up_to_pr_64(monkeypatch):
    from paddle_tpu.ops import expert_gmm
    monkeypatch.setattr(expert_gmm, "gmm_unit", _apart)
    monkeypatch.setattr(expert_gmm, "unwritten",
                        lambda shape, dtype, like: jnp.zeros(shape, dtype))


@pytest.mark.parametrize("dtype", [None, "bfloat16"],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_kernels_layer_is_the_parents_to_the_bit(cell, dtype,
                                                     monkeypatch):
    """The output, `ExpertLoad` and every input's gradient through the
    kernels' interpreter, with the unit in the gate/up kernel and the
    buffers of sorted rows started from a call's output that nothing wrote
    (NaN, on the interpreter: none may reach a result), against the same
    layer with two forward kernels, the unit as a pass over the stored
    arrays and a fill of zeros, at the eight cells' (top_k, E, H,
    activation), with float32 and with bfloat16 experts. The tiles are
    cut so that the loops make several trips and leave whole tiles
    unmet."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "gmm")
    monkeypatch.setattr(moe, "ROW_TILE", 32)
    monkeypatch.setattr(moe, "SUM_TILE", 32)
    w, run, g = _cell_layer(cell, dtype and jnp.dtype(dtype))
    assert moe.matmul_route(128, 128, jnp.dtype(dtype or "float32")) \
        == moe.KERNEL_MATMUL
    got = _value_and_grads(run, w, g)
    with monkeypatch.context() as parent:
        _the_forms_up_to_pr_64(parent)
        want = _value_and_grads(run, w, g)
    assert int(got[1].sum()) == CELLS[cell][2] * w["x"].shape[0]
    assert np.abs(np.asarray(got[0], np.float32)).max() > 0
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert sorted(got[2]) == sorted(w)
    for name in w:
        assert np.abs(np.asarray(got[2][name])).max() > 0, name
        np.testing.assert_array_equal(np.asarray(got[2][name]),
                                      np.asarray(want[2][name]), err_msg=name)


# what may have all the buffer's rows in its result and be no pass over
# them: a loop's carry and the tile written into it, in place
_NO_PASS = ("parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "dynamic-update-slice", "copy")


def _whole_buffer_passes(text, rows, widths):
    """(scope, opcode, the end of its op_name) of every instruction of the
    compiled step under `op:moe_ffn` or `op:moe_ffn_grad`, outside the
    kernels' interpreters (their instructions carry the kernel's name),
    whose result has all `rows` rows at one of `widths` and is no loop's
    carry: an elementwise pass, a fill, a gather of the whole buffer."""
    found = []
    for line in text.splitlines():
        met = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(?[^=]*?\)?) "
                       r"(\w[\w\-]*)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not met or not name or "op:moe_ffn" not in name.group(1) \
                or "ptpu_" in name.group(1):
            continue
        shape, opcode = met.groups()
        if opcode in _NO_PASS or not any(
                "[%d,%d]" % (rows, width) in shape for width in widths):
            continue
        if opcode == "fusion" and name.group(1).endswith(
                "dynamic_update_slice"):
            continue
        found.append(("moe_ffn_grad" if "op:moe_ffn_grad" in name.group(1)
                      else "moe_ffn", opcode, name.group(1)[-40:]))
    return found


@pytest.mark.parametrize("cell,gated", [
    ("lfm2", True), ("lfm2", False), ("nemotron_3_super", True),
    ("nemotron_3_super", False)])
def test_no_pass_has_all_the_rows_of_a_held_share(cell, gated, monkeypatch):
    """One layer's step through the kernels' interpreter, a share held,
    numbered by slot and by held expert, gated and not: under the op's two
    scopes nothing outside the kernels has all A rows of an [A, D] or [A,
    F] array but the loops' carries: no fill, no elementwise pass. With the
    forms up to PR 64 stood in their place the unit's pass is there, so the
    walk sees."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "gmm")
    monkeypatch.setattr(moe, "ROW_TILE", 16)
    monkeypatch.setattr(moe, "SUM_TILE", 16)
    experts, held, top_k = CELLS[cell]
    rows = 64 * (held if moe.numbered_by(*CELLS[cell]) == "expert"
                 else top_k)
    text = _one_layer_step(experts, held, top_k, gated=gated)
    assert _under(text, "moe_ffn", "ptpu_expert_gmm_unit_fwd")
    assert _whole_buffer_passes(text, rows, (128,)) == []
    _the_forms_up_to_pr_64(monkeypatch)
    seen = _whole_buffer_passes(_one_layer_step(experts, held, top_k,
                                                gated=gated), rows, (128,))
    assert {scope for scope, _, _ in seen} == {"moe_ffn"}
    assert any(opcode in ("multiply", "maximum") for _, opcode, _ in seen)
