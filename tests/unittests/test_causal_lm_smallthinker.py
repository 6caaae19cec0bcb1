"""SmallThinker-21BA3B's layer through models/causal_lm.py at a tiny size on
the CPU: grouped queries, a window and rotary positions by layer (period 4),
the router read before attention, ReGLU experts of which one chip holds a
share. The Program against models/causal_lm_reference.py (loss, logits,
every gradient) at T > window; `routed_ffn` told which experts it holds; and
the test that ties the share to the model: the shares of all chips sum to
the whole layer.
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm, causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.parallel import moe

# chip 1 of the 2 that share a layer: 4 of 8 query heads on 2 of 4 key/value
# heads of 8, experts 8..15 of 16, half a vocabulary of 128; four layers, one
# period: full attention without rotary, then three windowed (16) with
CFG = dict(
    hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=4, vocab_size=64, moe_ffn_hidden_size=16,
    moe_num_primary_experts=8, moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1.5e6, rope_scaling=None,
    rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2,
    sliding_window_size=16, tie_word_embeddings=False, hidden_act="relu",
    router_input="pre_attention", router_aux_loss_coef=0.0,
    router_z_loss_coef=0.0,
    share=dict(chips=2, chip=1, published=dict(
        num_attention_heads=8, num_key_value_heads=4,
        moe_num_primary_experts=16, vocab_size=128)))
B, T = 2, 40                    # T > the window
TOLERANCE = 2e-5                # float32 against float32, other summation order


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _feed(seed=0):
    tok = np.random.RandomState(seed).randint(0, CFG["vocab_size"],
                                              (B, T + 1))
    return {"ids": tok[:, :-1],
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy(),
            "labels": tok[:, 1:, None]}


def _counter(name, **labels):
    return REGISTRY.counter(name, "").value(**labels)


def _counts():
    from paddle_tpu.parallel.moe import GROUPED_MATMUL
    return {
        "moe": _counter("ptpu_moe_layers_total", top_k="3", experts="16",
                        held="8", activation="relu",
                        router_input="pre_attention", path=GROUPED_MATMUL,
                        rows="held", scoring="softmax", bias="false",
                        scale="1"),
        "full": _counter("ptpu_attention_layers_total", kind="full",
                         window="0", q_heads="4", kv_heads="2", path="dense",
                         head_dim="8", heads_a_block="none"),
        "window": _counter("ptpu_attention_layers_total", kind="window",
                           window="16", q_heads="4", kv_heads="2",
                           path="dense", head_dim="8", heads_a_block="none")}


def _run_program(amp):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        if amp:
            main.enable_mixed_precision()
        loss, logits, load = causal_lm.build_train(CFG, T)
    params = main.global_block().all_parameters()
    scope = fluid.Scope()
    before = _counts()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        out = exe.run(main, feed=_feed(), fetch_list=[loss, logits, load]
                      + [p.name + "@GRAD" for p in params])
    after = _counts()
    got = {"loss": out[0], "logits": out[1], "expert_load": out[2],
           "grads": dict(zip((p.name for p in params), out[3:])),
           "counted": {k: after[k] - before[k] for k in after},
           "ops": [op.type for op in main.global_block().ops]}
    return params, weights, got


@pytest.fixture(scope="module")
def program():
    return _run_program(amp=False)


@pytest.fixture(scope="module")
def want(program):
    params, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    (loss, (logits, load)), grads = jax.jit(
        lambda p: reference.loss_and_grads(CFG, p, feed["ids"], feed["pos"],
                                           feed["labels"]))(weights)
    return {"loss": loss, "logits": logits, "expert_load": load,
            "grads": dict(zip((p.name for p in params), grads))}


def test_resolve_maps_smallthinkers_keys():
    c = causal_lm.resolve(CFG)
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (16, 8, 8)
    assert (c["intermediate_size"], c["num_experts_per_tok"]) == (16, 3)
    assert c["rope_layers"] == [False, True, True, True]
    assert c["window_layers"] == [None, 16, 16, 16]
    assert causal_lm._layer(c, 0)["rope_theta"] is None
    assert causal_lm._layer(c, 1)["window"] == 16
    # a model whose layers are all alike sees its own config in every layer,
    # and which layer it is (its parameters are named by it)
    plain = causal_lm.resolve(dict(
        vocab_size=8, hidden_size=8, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=4))
    assert causal_lm._layer(plain, 1) == dict(plain, layer=1, ffn="dense",
                                              reads="own", lambda_init=None)
    assert plain["head_dim"] == 4 and plain["experts_held"] == 0


@pytest.mark.parametrize("edit,error,match", [
    (dict(rope_layout=[0, 1]), ValueError, "rope_layout"),
    (dict(sliding_window_layout=[1]), ValueError, "sliding_window_layout"),
    (dict(moe_primary_router_apply_softmax=False), NotImplementedError,
     "moe_primary_router_apply_softmax"),
    (dict(hidden_act="gelu"), NotImplementedError, "hidden_act"),
    (dict(router_input="post_mlp"), NotImplementedError, "router_input"),
    (dict(share=dict(chips=2, chip=2, published=dict(
        moe_num_primary_experts=16))), ValueError, "cannot hold")])
def test_resolve_refuses_what_the_builder_cannot_build(edit, error, match):
    with pytest.raises(error, match=match):
        causal_lm.resolve(dict(CFG, **edit))


def test_program_has_the_shares_shapes(program):
    params, weights, got = program
    shapes = [w.shape for w in weights]
    assert len(params) == 1 + 4 * 10 + 2
    assert shapes[0] == (64, 32)                        # half the vocabulary
    assert shapes[1:11] == [
        (32,), (32, 32), (32, 16), (32, 16), (32, 32), (32,),
        (32, 16), (8, 32, 16), (8, 32, 16), (8, 16, 32)]
    assert shapes[-1] == (32, 64)
    assert got["ops"].count("rotary_embedding") == 2 * 3    # not on layer 0
    assert got["ops"].count("fused_attention") == 4
    assert got["ops"].count("moe_ffn") == 4


def test_program_agrees_with_the_reference(program, want):
    _, _, got = program
    assert _error(got["loss"], want["loss"]) < TOLERANCE
    assert _error(got["logits"], want["logits"]) < TOLERANCE
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])
    # every assignment is counted, over all 16 experts, held or not
    assert got["expert_load"].shape == (16,)
    assert got["expert_load"].sum() == 4 * 3 * B * T
    assert 0 < got["expert_load"][8:].sum() < got["expert_load"].sum()


def test_every_gradient_agrees_with_the_reference(program, want):
    params, _, got = program
    errors = {p.name: _error(got["grads"][p.name], want["grads"][p.name])
              for p in params}
    assert max(errors.values()) < TOLERANCE, errors
    assert all(np.abs(want["grads"][p.name]).max() > 0 for p in params)


def test_amp_program_agrees_with_the_reference(want):
    _, _, got = _run_program(amp=True)
    assert got["logits"].dtype == jnp.bfloat16
    assert _error(got["logits"], want["logits"]) < 5e-2
    assert _error(got["loss"], want["loss"]) < 1e-3
    assert got["expert_load"].sum() == 4 * 3 * B * T


def test_the_new_counters_and_labels(program):
    """ptpu_moe_layers_total says what is held, the activation, what the
    router reads and that the held rows alone are moved (rows="held", PR
    32); ptpu_attention_layers_total counts forward fused_attention
    ops by kind, window, heads and path, not a grad op's replay."""
    assert program[2]["counted"] == {"moe": 4, "full": 1, "window": 3}


@pytest.mark.parametrize("mutant", ["window_off", "rope_on_global",
                                    "silu_for_relu", "router_after_attention",
                                    "wrong_kv_head", "top5"])
def test_reference_tells_a_broken_model(program, want, mutant):
    """The reference with one mechanism changed is further from the Program
    than the tolerance: the comparison sees each of them."""
    _, weights, got = program
    cfg = dict(CFG)
    if mutant == "window_off":
        cfg["sliding_window_layout"] = [0] * 8
    elif mutant == "rope_on_global":
        cfg["rope_layout"] = [1] * 8
    elif mutant == "silu_for_relu":
        cfg["hidden_act"] = "silu"
    elif mutant == "router_after_attention":
        cfg["router_input"] = "own"
    elif mutant == "top5":
        cfg["moe_num_active_primary_experts"] = 2
    elif mutant == "wrong_kv_head":     # query head h reads head 1 - h // 2
        weights = [np.concatenate([w[:, 8:], w[:, :8]], 1)
                   if w.shape == (32, 16) and i % 10 in (3, 4) else w
                   for i, w in enumerate(weights)]
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    logits = reference.forward(cfg, weights, feed["ids"], feed["pos"])[0]
    assert _error(got["logits"], logits) > 10 * TOLERANCE


# --- routed_ffn told which experts it holds ----------------------------------

def _layer_inputs(seed, n=48, d=16, e=16, f=8):
    rng = np.random.RandomState(seed)
    x, a = (jnp.asarray(rng.randn(n, d), jnp.float32) for _ in range(2))
    router = jnp.asarray(rng.randn(d, e), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(e, d, f) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d) * 0.3, jnp.float32)
    return x, a, router, wg, wu, wd


REF_C = {"num_experts": 16, "num_experts_per_tok": 6, "norm_topk_prob": True,
         "hidden_act": "relu"}


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_routed_ffn_reads_the_router_elsewhere(activation):
    x, a, router, wg, wu, wd = _layer_inputs(1)
    c = dict(REF_C, hidden_act=activation)
    with jax.default_matmul_precision("highest"):
        got = moe.routed_ffn(x, router, wg, wu, wd, top_k=6,
                             norm_topk_prob=True, router_x=a,
                             activation=activation)
        want = reference.routed_experts(x, router, wg, wu, wd, c, router_x=a)
        own = reference.routed_experts(x, router, wg, wu, wd, c)
    assert _error(got[0], want[0]) < TOLERANCE
    np.testing.assert_array_equal(got[3], want[3])
    assert _error(got[0], own[0]) > 0.1     # the router's input matters


@pytest.mark.parametrize("routing", ["softmax", "sigmoid_with_bias"])
def test_four_shares_of_four_experts_sum_to_the_layer(routing):
    """Forward and every gradient: each share routes over all 16, computes
    its 4, and the four partial sums (and the four gradients of x and of the
    router's input) add up to what the uncut layer gives. Under a softmax
    router, and under a sigmoid one whose top 6 is chosen with an expert
    bias (PR 39): the bias, like the router, keeps all 16 columns in every
    share."""
    x, a, router, wg, wu, wd = _layer_inputs(2)
    more, c = {}, REF_C
    if routing == "sigmoid_with_bias":
        bias = jnp.asarray(np.random.RandomState(4).randn(16) * 0.3,
                           jnp.float32)
        more = dict(scoring="sigmoid", expert_bias=bias, scale=1.5)
        c = dict(REF_C, router_scoring="sigmoid", routed_scaling_factor=1.5)

    def share(i, x, a, router, wg, wu, wd):
        sl = slice(4 * i, 4 * i + 4)
        return moe.routed_ffn(x, router, wg[sl], wu[sl], wd[sl], top_k=6,
                              norm_topk_prob=True, router_x=a,
                              activation="relu", first_expert=4 * i, **more)

    def whole(x, a, router, wg, wu, wd):
        return reference.routed_experts(x, router, wg, wu, wd, c, router_x=a,
                                        expert_bias=more.get("expert_bias"))

    args = (x, a, router, wg, wu, wd)
    g = jnp.asarray(np.random.RandomState(3).randn(*x.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(lambda *p: whole(*p)[0], *args)
        want_grads = vjp(g)
        outs, grads, rows = [], [], 0
        for i in range(4):
            out, _, _, load = share(i, *args)
            np.testing.assert_array_equal(load, whole(*args)[3])
            rows += int(load[4 * i:4 * i + 4].sum())
            outs.append(out)
            grads.append(jax.vjp(lambda *p: share(i, *p)[0], *args)[1](g))
    assert rows == 6 * x.shape[0]       # every assignment, once
    assert _error(sum(outs), want) < TOLERANCE
    assert all(float(jnp.abs(o).max()) > 0 for o in outs)
    for j, name in enumerate(("x", "router_x", "router", "w_gate", "w_up",
                              "w_down")):
        total = sum(gr[j] for gr in grads)
        assert _error(total, want_grads[j]) < 5 * TOLERANCE, name


@pytest.mark.parametrize("tile", [32, 2048])
def test_rows_past_the_groups_sum_reach_nothing(monkeypatch, tile):
    """On the CPU ragged_dot writes zeros past the groups' sum; on the v5e
    it leaves those rows unwritten (PR 31's chip run). Here they are filled
    with NaN, forward and in the transposes, and neither the output nor a
    gradient sees it, with the held rows in one tile or in several."""
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    x, a, router, wg, wu, wd = _layer_inputs(4)
    grouped = moe._grouped_matmul

    @jax.custom_vjp
    def poison(y, total):
        return jnp.where(jnp.arange(y.shape[0])[:, None] < total, y, jnp.nan)

    poison.defvjp(lambda y, total: (poison(y, total), total),
                  lambda total, g: (poison(g, total), None))

    def unwritten(lhs, rhs, sizes, plan=None):
        total = sizes.sum()
        return poison(grouped(poison(lhs, total), rhs, sizes, plan), total)

    def run(x, a, wg, wu, wd):
        return moe.routed_ffn(x, router, wg[:4], wu[:4], wd[:4], top_k=6,
                              norm_topk_prob=True, router_x=a,
                              activation="relu")[0]

    g = jnp.ones_like(x)
    want, vjp = jax.vjp(run, x, a, wg, wu, wd)
    want_grads = vjp(g)
    monkeypatch.setattr(moe, "_grouped_matmul", unwritten)
    got, vjp = jax.vjp(run, x, a, wg, wu, wd)
    got_grads = vjp(g)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for a_, b_ in zip(got_grads, want_grads):
        assert bool(jnp.isfinite(a_).all())
        np.testing.assert_allclose(a_, b_, rtol=1e-5, atol=1e-6)


# --- the rows routed_ffn moves: slot-major, held tiles only (PR 32) ------------

def _token_major_routed_ffn(x, router, w_gate, w_up, w_down, top_k, router_x,
                            first_expert):
    """routed_ffn as PR 31 had it, ReLU and renormalised gates: assignment
    a = n * top_k + j, every gather and mask over all top_k * N rows."""
    n, d = x.shape
    e, held = router.shape[1], w_gate.shape[0]
    probs = jax.nn.softmax(jnp.dot(router_x, router), -1)
    gate, expert = jax.lax.top_k(probs, top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    expert = expert.reshape(-1)
    local = expert - first_expert
    here = (local >= 0) & (local < held)
    order = jnp.argsort(jnp.where(here, local, held), stable=True)
    rank = jnp.argsort(order)
    load = jnp.sum(expert[:, None] == jnp.arange(e), axis=0, dtype=jnp.int32)
    sizes = load[first_expert:first_expert + held]
    rows = jnp.where((jnp.arange(top_k * n) < sizes.sum())[:, None],
                     x[order // top_k], 0)
    hidden = jax.nn.relu(moe._grouped_matmul(rows, w_gate, sizes)) \
        * moe._grouped_matmul(rows, w_up, sizes)
    y = moe._grouped_matmul(hidden, w_down, sizes)[rank].reshape(n, top_k, d)
    y = jnp.where(here.reshape(n, top_k, 1), y, 0.0)
    return jnp.sum(y * gate[:, :, None], axis=1)


HELD = {"all": (16, 0), "share": (4, 8), "last": (4, 12)}


@pytest.mark.parametrize("tile", [32, 2048])
@pytest.mark.parametrize("held", sorted(HELD))
def test_routed_ffn_keeps_the_values_of_the_token_major_order(
        monkeypatch, held, tile):
    """Value, dx, d router_x, dw_gate, dw_up, dw_down: against the plain
    reference and against PR 31's algorithm, with every expert held (no
    loop), a share in the middle and the last share; the loop in one trip
    (tile 2048 > 288 rows) and in several."""
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    x, a, router, wg, wu, wd = _layer_inputs(7)
    count, first = HELD[held]
    sl = slice(first, first + count)

    def new(x, a, wg, wu, wd):
        return moe.routed_ffn(x, router, wg, wu, wd, top_k=6,
                              norm_topk_prob=True, router_x=a,
                              activation="relu", first_expert=first)[0]

    def old(x, a, wg, wu, wd):
        return _token_major_routed_ffn(x, router, wg, wu, wd, 6, a, first)

    def plain(x, a, wg, wu, wd):
        return reference.routed_experts(x, router, wg, wu, wd, REF_C,
                                        router_x=a, first_expert=first)[0]

    args = (x, a, wg[sl], wu[sl], wd[sl])
    g = jnp.asarray(np.random.RandomState(8).randn(*x.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, want, ref = (
            (out,) + vjp(g) for out, vjp in
            (jax.vjp(f, *args) for f in (new, old, plain)))
    for name, u, v, w in zip(("out", "dx", "drouter_x", "dw_gate", "dw_up",
                              "dw_down"), got, want, ref):
        assert _error(u, v) < 2e-6, name
        assert _error(u, w) < 5 * TOLERANCE, name


def _steered(held_logit):
    """Inputs whose router sends every token's six choices to experts 4..11
    (held_logit > 0) or away from them (< 0): the router's input carries a
    constant feature that only those eight columns read."""
    x, a, router, wg, wu, wd = _layer_inputs(9)
    a = a.at[:, 0].set(10.0)
    router = router.at[0].set(0.0).at[0, 4:12].set(held_logit)
    return x, a, router, wg[4:12], wu[4:12], wd[4:12]


@pytest.mark.parametrize("tile", [32, 100])
@pytest.mark.parametrize("extreme", ["none_held", "every_one_held"])
def test_routed_ffn_at_the_extremes_of_imbalance(monkeypatch, extreme, tile):
    """No assignment on the held experts: the row loops make no trip, zeros
    and zero gradients. Every assignment on them: sizes.sum() == top_k * N,
    the loops run every tile, and the share is the whole layer; at a tile of
    100 the last of three trips over the 288 rows meets 12 rows a second
    time, also where it writes over what it reads (PR 40)."""
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    x, a, router, wg, wu, wd = _steered(-5.0 if extreme == "none_held"
                                        else 5.0)

    def run(x, a, wg, wu, wd):
        return moe.routed_ffn(x, router, wg, wu, wd, top_k=6,
                              norm_topk_prob=True, router_x=a,
                              activation="relu", first_expert=4)

    g = jnp.ones_like(x)
    with jax.default_matmul_precision("highest"):
        load = run(x, a, wg, wu, wd)[3]
        got, vjp = jax.vjp(lambda *p: run(*p)[0], x, a, wg, wu, wd)
        grads = vjp(g)
    held_rows = int(load[4:12].sum())
    if extreme == "none_held":
        assert held_rows == 0
        assert not np.asarray(got).any()
        assert all(not np.asarray(gr).any() for gr in grads)
        return
    assert held_rows == 6 * x.shape[0]

    def plain(x, a, wg, wu, wd):
        return reference.routed_experts(x, router, wg, wu, wd, REF_C,
                                        router_x=a, first_expert=4)[0]

    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(plain, x, a, wg, wu, wd)
        want_grads = vjp(g)
    assert _error(got, want) < TOLERANCE
    for u, v in zip(grads, want_grads):
        assert _error(u, v) < 5 * TOLERANCE


def _assignments(seed, n, top_k, experts, first, held):
    """(order, rank, total) as routed_ffn builds them for random choices of
    which experts first .. first + held - 1 are held."""
    expert = jnp.asarray(np.random.RandomState(seed).randint(
        0, experts, top_k * n))
    local = expert - first
    here = (local >= 0) & (local < held)
    order = jnp.argsort(jnp.where(here, local, held), stable=True)
    return order, jnp.argsort(order), jnp.sum(here)


@pytest.mark.parametrize("tile", [32, 100, 2048])
def test_the_last_tiles_tail_is_zero_in_the_rows_and_in_their_gradient(
        monkeypatch, tile):
    """sizes.sum() is no multiple of the tile (nor the buffer, at 100, where
    the last trip meets rows a second time): `_held_rows` gives x[token]
    below the sum and zeros from there on (in a buffer of zeros; in the
    rest of the last tile it met, from any other); `_combine`'s backward the
    weighted g[token] below it and, since PR 40 writes dy over y, what y
    held from there on, which no group reads; and no NaN past the sum
    reaches a weight's or a token's gradient."""
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    n, k, d = 48, 6, 16
    order, rank, total = _assignments(10, n, k, 16, 0, 4)
    assert 0 < int(total) < k * n and int(total) % min(tile, k * n)
    rng = np.random.RandomState(11)
    x, g = (jnp.asarray(rng.randn(n, d), jnp.float32) for _ in range(2))
    gate = jnp.asarray(rng.rand(k, n), jnp.float32)
    live = (np.arange(k * n) < int(total))[:, None]
    places = moe._token_places(rank, total, k)

    rows = moe._held_rows(x, order, total, jnp.zeros((k * n, d), x.dtype),
                          tile=tile)
    np.testing.assert_array_equal(
        rows, np.where(live, np.asarray(x)[np.asarray(order) % n], 0))
    # from a buffer nothing wrote (PR 65: the kernels' route; NaN on the
    # interpreter) the tiles past the last one met keep what they held
    unfilled = moe._held_rows(x, order, total,
                              jnp.full((k * n, d), jnp.nan, x.dtype),
                              tile=tile)
    t = min(tile, k * n)
    # the last trip starts no later than where it ends with the buffer
    met = min((-(-int(total) // t) - 1) * t, k * n - t) + t
    np.testing.assert_array_equal(unfilled[:met], rows[:met])
    assert bool(jnp.isnan(unfilled[met:]).all())

    y = jnp.where(live, jnp.asarray(rng.randn(k * n, d), jnp.float32),
                  jnp.nan)
    out, vjp = jax.vjp(lambda y, gate: moe._combine(y, gate, order, rank,
                                                    total, places), y, gate)
    assert bool(jnp.isfinite(out).all())
    dy, dgate = vjp(g)
    weight = np.asarray(gate).reshape(-1)[np.asarray(order)][:, None]
    g_rows = np.asarray(g)[np.asarray(order) % n]
    np.testing.assert_allclose(np.asarray(dy)[:int(total)],
                               (g_rows * weight)[:int(total)], rtol=1e-6)
    tail = np.asarray(dy)[int(total):]      # zero in the last tile met
    assert ((tail == 0) | np.isnan(tail)).all()
    want = np.where(live, g_rows * np.nan_to_num(np.asarray(y)), 0).sum(-1)
    assert bool(jnp.isfinite(dgate).all())
    np.testing.assert_allclose(np.asarray(dgate).reshape(-1),
                               want[np.asarray(rank)], rtol=1e-5, atol=1e-6)
    dx = moe._token_sum((jnp.where(live, rows, jnp.nan),) * 2, rank, places,
                        tile=moe.SUM_TILE)
    assert bool(jnp.isfinite(dx).all())
    np.testing.assert_allclose(
        dx, 2 * _add_at(rows, None, order, total, n), rtol=1e-6, atol=1e-6)


# --- the token side: the held rows only (PR 40) --------------------------------

def _slot_sum_of_pr_32(rows, rank, total, slots, gate=None):
    """`_slot_sum` as PR 32 left it: the gather of all top_k * N rows by
    `rank`, the unheld ones selected away, a float32 sum slot by slot."""
    by_slot = rows[rank].reshape(slots, -1, rows.shape[1])
    held = (rank < total).reshape(slots, -1, 1)
    acc = 0.0
    for j in range(slots):
        term = jnp.where(held[j], by_slot[j], 0).astype(jnp.float32)
        acc = acc + (term if gate is None else term * gate[j][:, None])
    return acc.astype(rows.dtype)


def _token_sum_inputs(seed, order, total, n, k, d, dtype, poison):
    rng = np.random.RandomState(seed)
    rows = jnp.asarray(rng.randn(k * n, d), dtype)
    if poison:
        rows = jnp.where((jnp.arange(k * n) < total)[:, None], rows, jnp.nan)
    return rows, jnp.asarray(rng.rand(k, n) + 0.1, jnp.float32)


def _add_at(rows, gate, order, total, n):
    """The sum in float64, `np.add.at` over the held sorted rows."""
    total, order = int(total), np.asarray(order)
    r64 = np.asarray(rows.astype(jnp.float32), np.float64)[:total]
    want = np.zeros((n, rows.shape[1]))
    if gate is not None:
        r64 = r64 * np.asarray(gate, np.float64).reshape(-1)[
            order[:total], None]
    np.add.at(want, order[:total] % n, r64)
    return want


def _tokens_without(rank, total, n, k):
    return int((np.asarray(rank < total).reshape(k, n).sum(0) == 0).sum())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("sum_tile", [8, 32])
@pytest.mark.parametrize("tile", [32, 1024])
@pytest.mark.parametrize("held", sorted(HELD))
def test_the_token_side_sum_touches_the_held_rows_only(
        monkeypatch, held, tile, sum_tile, dtype):
    """`_token_sum`, weighted (`_combine` forward) and plain (`_dispatch`
    backward), against np.add.at in float64 and against PR 32's gather of
    all top_k * N rows: every assignment held (total == A), a share in the
    middle, the last share; trips of 8 and of 32 places, which tokens
    straddle (the 300 places are a multiple of neither: the arrays are
    padded). NaN in every row past `total` reaches no token. bfloat16
    rows meet the float32 weight as three bfloat16 terms: the sum is the
    float32 one, not a sum of rounded products."""
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    monkeypatch.setattr(moe, "SUM_TILE", sum_tile)
    n, k, d = 50, 6, 16
    count, first = HELD[held]
    order, rank, total = _assignments(13, n, k, 16, first, count)
    assert int(total) == k * n if held == "all" else 0 < int(total) < k * n
    rows, gate = _token_sum_inputs(14, order, total, n, k, d, dtype, True)
    places = moe._token_places(rank, total, k)
    held_slots, begin, count = (np.asarray(v) for v in places)
    assert int(count) == int(total) + _tokens_without(rank, total, n, k)
    assert held_slots.sum() == int(total) and begin[0] == 0
    assert (np.diff(begin) == np.maximum(held_slots.sum(0), 1)[:-1]).all()
    for weighted in (True, False):
        got = moe._token_sum((rows,), rank, places,
                             gate if weighted else None,
                             tile=moe.SUM_TILE)
        assert got.dtype == rows.dtype and got.shape == (n, d)
        assert bool(jnp.isfinite(got).all())
        want = _add_at(rows, gate if weighted else None, order, total, n)
        old = _slot_sum_of_pr_32(rows, rank, total, k,
                                 gate if weighted else None)
        if dtype == "float32":
            assert _error(got, want) < 1e-6
            assert _error(got, old) < 1e-6
        else:
            # the float64 sum rounded once to bfloat16, within an ulp
            assert _error(got, want) < 2.0 ** -8
            assert _error(got, old) < 2.0 ** -8


def test_a_bfloat16_weighted_sum_is_not_a_sum_of_rounded_products():
    """Weight times row in float32, summed in float32, rounded once: as PR
    32's sum. With the weight rounded to bfloat16 first (one term of the
    three) the result is a bfloat16 ulp off in many places."""
    n, k, d = 64, 2, 32
    order, rank, total = _assignments(15, n, k, 4, 0, 2)
    rows, gate = _token_sum_inputs(16, order, total, n, k, d, "bfloat16",
                                   False)
    places = moe._token_places(rank, total, k)
    got = moe._token_sum((rows,), rank, places, gate, tile=moe.SUM_TILE)
    want = _slot_sum_of_pr_32(rows, rank, total, k, gate)
    # three exact partial products summed in float32 against one rounded
    # product: a float32 ulp apart at most, which moves a bfloat16 tie
    assert (np.asarray(got, np.float32)
            != np.asarray(want, np.float32)).mean() < 0.005
    rounded = _slot_sum_of_pr_32(
        rows, rank, total, k,
        gate.astype(jnp.bfloat16).astype(jnp.float32))
    assert (np.asarray(rounded, np.float32)
            != np.asarray(want, np.float32)).mean() > 0.05


@pytest.mark.parametrize("extreme", ["none_held", "a_run_of_tokens_without"])
def test_the_token_side_sum_where_no_row_is_held(monkeypatch, extreme):
    """No assignment held: every token takes its one empty place, exactly
    zero. Twenty consecutive tokens none of whose choices is held, more than
    two trips of 8 places: exactly zero there, the float64 sum elsewhere."""
    monkeypatch.setattr(moe, "ROW_TILE", 32)
    monkeypatch.setattr(moe, "SUM_TILE", 8)
    n, k, d = 48, 6, 16
    expert = np.random.RandomState(17).randint(0, 16, (k, n))
    if extreme == "none_held":
        expert = 4 + expert % 12
    else:
        expert[:, 14:34] = 4 + expert[:, 14:34] % 12
    here = jnp.asarray(expert.reshape(-1) < 4)
    order = jnp.argsort(jnp.where(here, jnp.asarray(expert.reshape(-1)), 4),
                        stable=True)
    rank, total = jnp.argsort(order), jnp.sum(here)
    rows, gate = _token_sum_inputs(18, order, total, n, k, d, "float32", True)
    places = moe._token_places(rank, total, k)
    got = np.asarray(moe._token_sum((rows,), rank, places, gate,
                                    tile=moe.SUM_TILE))
    if extreme == "none_held":
        assert int(total) == 0 and int(places[2]) == n and not got.any()
        return
    assert _tokens_without(rank, total, n, k) >= 20
    assert not got[14:34].any()
    assert _error(got, _add_at(rows, gate, order, total, n)) < 1e-6


@pytest.mark.parametrize("tile", [100, 1024])
@pytest.mark.parametrize("held", ["share", "last"])
def test_a_share_has_the_gradients_of_the_layer_with_the_rest_zeroed(
        monkeypatch, held, tile):
    """routed_ffn holding a share (the held rows only, every pass) against
    routed_ffn holding every expert (rows="all": no loop, no one-hot sum)
    with the other experts' weights zero, on the same inputs: the value and
    the gradients of x, the router's input, the router and the three
    weights, at 1e-6 in float32. At a tile of 100 the 288 rows are no
    multiple of it: the last trip of each loop meets rows a second time,
    also where it writes d gate over d hidden and dy over y."""
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    monkeypatch.setattr(moe, "SUM_TILE", 64)
    x, a, router, wg, wu, wd = _layer_inputs(19)
    count, first = HELD[held]
    sl = slice(first, first + count)
    mask = jnp.zeros((16, 1, 1)).at[sl].set(1.0)

    def share(x, a, router, wg, wu, wd):
        return moe.routed_ffn(x, router, wg[sl], wu[sl], wd[sl], top_k=6,
                              norm_topk_prob=True, router_x=a,
                              activation="relu", first_expert=first)[0]

    def whole(x, a, router, wg, wu, wd):
        return moe.routed_ffn(x, router, wg * mask, wu * mask, wd * mask,
                              top_k=6, norm_topk_prob=True, router_x=a,
                              activation="relu")[0]

    g = jnp.asarray(np.random.RandomState(20).randn(*x.shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, want = ((out,) + vjp(g) for out, vjp in
                     (jax.vjp(f, x, a, router, wg, wu, wd)
                      for f in (share, whole)))
    for name, u, v in zip(("out", "dx", "drouter_x", "drouter", "dw_gate",
                           "dw_up", "dw_down"), got, want):
        assert _error(u, v) < 1e-6, name
        assert np.asarray(v).any(), name


def test_the_share_of_the_buffer_gathered_reads_from_expert_load():
    """What the held-rows loops touch is ExpertLoad[first : first + held]
    .sum() / (top_k * N), a value every step fetches: a quarter of the
    buffer when 4 of 16 experts are held and the router is even."""
    x, a, router, wg, wu, wd = _layer_inputs(12, n=256)
    load = moe.routed_ffn(x, router, wg[8:12], wu[8:12], wd[8:12], top_k=6,
                          norm_topk_prob=True, router_x=a, activation="relu",
                          first_expert=8)[3]
    assert int(load.sum()) == 6 * 256
    share = float(load[8:12].sum()) / (6 * 256)
    assert 0.15 < share < 0.35
    assert moe.rows_moved(16, 4) == "held" and moe.rows_moved(16, 16) == "all"


def test_the_op_takes_the_share_through_the_layer():
    with pytest.raises(ValueError, match="cannot hold"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data("x", [4, 8], dtype="float32")
            fluid.layers.moe_ffn(x, num_experts=8, d_expert=4, top_k=2,
                                 experts_held=4, first_expert=6)
    with pytest.raises(ValueError, match="activation"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.layers.data("x", [4, 8], dtype="float32")
            fluid.layers.moe_ffn(x, num_experts=8, d_expert=4, top_k=2,
                                 activation="gelu")


# --- the share tied to the model ---------------------------------------------

def test_the_shares_of_a_layer_sum_to_the_whole_layer():
    """One layer on one input, 4 chips: chip i holds query heads 2i, 2i + 1
    on key/value head i, and experts 4i .. 4i + 3. The four attention
    outputs sum to the whole layer's (8 heads on 4), and the four expert
    outputs, computed by the Program's routed_ffn from that same h1, sum to
    the uncut reference routed_experts."""
    rng = np.random.RandomState(6)
    d, hd, t = 32, 8, 24
    c = causal_lm.resolve(dict(
        CFG, num_attention_heads=8, num_key_value_heads=4,
        moe_num_primary_experts=16, share=None, num_hidden_layers=1,
        sliding_window_layout=[1], rope_layout=[1], sliding_window_size=10))
    cl = causal_lm._layer(c, 0)
    x = jnp.asarray(rng.randn(1, t, d), jnp.float32)
    pos = jnp.arange(t)[None]
    w_in, w_post = (jnp.asarray(rng.rand(d) + 0.5, jnp.float32)
                    for _ in range(2))
    wq = jnp.asarray(rng.randn(d, 8 * hd) * 0.2, jnp.float32)
    wk, wv = (jnp.asarray(rng.randn(d, 4 * hd) * 0.2, jnp.float32)
              for _ in range(2))
    wo = jnp.asarray(rng.randn(8 * hd, d) * 0.2, jnp.float32)
    router = jnp.asarray(rng.randn(d, 16), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(16, d, 16) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(16, 16, d) * 0.2, jnp.float32)
    with jax.default_matmul_precision("highest"):
        a = reference.rms_norm(x, w_in, 1e-6)
        whole = reference.attention(a, pos, wq, wk, wv, None, None, wo, cl)
        parts = [reference.attention(
            a, pos, wq[:, 16 * i:16 * i + 16], wk[:, 8 * i:8 * i + 8],
            wv[:, 8 * i:8 * i + 8], None, None, wo[16 * i:16 * i + 16], cl)
            for i in range(4)]
        assert _error(sum(parts), whole) < TOLERANCE
        assert _error(parts[0], whole) > 0.1
        h1 = x + whole
        m = reference.rms_norm(h1, w_post, 1e-6).reshape(t, d)
        uncut = reference.routed_experts(m, router, wg, wu, wd, c,
                                         router_x=a.reshape(t, d))[0]
        shares = [moe.routed_ffn(
            m, router, wg[4 * i:4 * i + 4], wu[4 * i:4 * i + 4],
            wd[4 * i:4 * i + 4], top_k=3, norm_topk_prob=True,
            router_x=a.reshape(t, d), activation="relu",
            first_expert=4 * i)[0] for i in range(4)]
    assert _error(sum(shares), uncut) < TOLERANCE


# --- fused_attention: the attr, the shapes, the paths that refuse -------------

def _attention_op(q, k, v, mesh=None, **attrs):
    from paddle_tpu.core import registry
    ctx = types.SimpleNamespace(mesh=mesh, amp=False)
    return registry.get("fused_attention").lower(
        ctx, {"Q": [q], "K": [k], "V": [v]}, dict(causal=True, **attrs))


def test_fused_attention_takes_a_window_and_grouped_queries():
    from paddle_tpu.parallel.ring_attention import attention_reference
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(2, 24, 4, 8), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, 24, 2, 8), jnp.float32)
            for _ in range(2))
    out = _attention_op(q, k, v, window=5)["Out"][0]
    np.testing.assert_allclose(
        out, attention_reference(q, k, v, causal=True, window=5), rtol=1e-6)
    full = _attention_op(q, k, v)["Out"][0]
    assert _error(out, full) > 1e-2


@pytest.mark.parametrize("sp_impl", ["ring", "ulysses"])
@pytest.mark.parametrize("what", ["window", "grouped"])
def test_sequence_parallel_paths_refuse_rather_than_ignore(what, sp_impl):
    from paddle_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"sp": 2}, jax.devices()[:2])
    q = jnp.zeros((2, 16, 4, 8), jnp.float32)
    kv = q if what == "window" else q[:, :, :2]
    attrs = dict(sp_impl=sp_impl, **({"window": 4} if what == "window"
                                     else {}))
    with pytest.raises(NotImplementedError, match="window nor grouped"):
        _attention_op(q, kv, kv, mesh=mesh, **attrs)


def test_layer_writes_the_window_only_where_there_is_one():
    """A program without a window is, attr for attr, the program it was."""
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = fluid.layers.data("q", [16, 4, 8], dtype="float32")
        kv = fluid.layers.data("kv", [16, 2, 8], dtype="float32")
        fluid.layers.fused_attention(q, q, q, causal=True)
        fluid.layers.fused_attention(q, kv, kv, causal=True, window=4)
        ops = fluid.default_main_program().global_block().ops
    assert "window" not in ops[0].attrs and ops[1].attrs["window"] == 4
    with pytest.raises(ValueError, match="window"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            q = fluid.layers.data("q", [16, 4, 8], dtype="float32")
            fluid.layers.fused_attention(q, q, q, window=0)
