"""parallel/moe.py `routed_ffn` where the share held is narrower than top_k
(Nemotron-3-Super's 8 of 512 at top-22): the assignments are numbered by
held expert (held * N of them) and not by top-k slot (top_k * N).
`numbered_by` decides from the shapes alone; the layer is held to plain jax
over every expert (output, `ExpertLoad`, and the gradient of every input) for
each unit, router and option, through `ragged_dot` and through the kernels'
interpreter; the shares of all chips add up to the layer with every expert
held; a token none of whose choices is held gets zeros; a share as wide as
top_k or wider lowers as it did (no compare over [held, top_k, N]); and
`ptpu_moe_layers_total` says `numbered="expert"` for a narrow share alone."""
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
         "nemotron_3_super": (512, 8, 22)}


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
        "expert" if cell == "nemotron_3_super" else
        None if cell == "olmoe" else "slot")


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

def _compares_over(jaxpr, dims):
    """The shapes of the `eq`s over three axes of the sizes `dims`, in any
    order, anywhere in the jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "eq" and sorted(
                eqn.outvars[0].aval.shape) == sorted(dims):
            found.append(eqn.outvars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _compares_over(sub, dims)
    return found


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
    # argsort of the keys; by expert `order` sorted out of `rank`, the
    # weights sorted to their rows and their gradients sorted back
    # (`moe._sorted_by`)
    assert _sorted_lengths(jaxpr) == (
        [N * held] * 3 if numbered == "expert" else [N * top_k])


def _sorted_lengths(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            found.append(eqn.outvars[0].aval.shape[0])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _sorted_lengths(sub)
    return found


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
    if cell == "nemotron_3_super":
        assert labels["numbered"] == "expert" and labels["rows"] == "held"
    else:
        assert "numbered" not in labels
        assert labels["rows"] == ("all" if cell == "olmoe" else "held")
