"""The routed experts' grouped-matmul kernels (ops/expert_gmm.py) in the
interpreter: each pass against `jax.lax.ragged_dot` and its `jax.vjp`,
`routed_ffn` through them against the `ragged_dot` route, and the two guards
of what the kernels may cost a restart (no import moves; a kernel is traced
once a shape, not once a layer).
"""
import functools
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.parallel import moe

# rows, K, N, the groups' sizes, the row tile
CASES = {
    # an empty group, a group of 1, sizes that are no multiple of 8 or of
    # the tile, a share held: 41 of 64 rows in a group
    "ragged": (64, 32, 48, (10, 0, 1, 23, 7), 16),
    "all_rows_grouped": (64, 32, 48, (10, 0, 1, 23, 30), 16),
    "one_group_has_every_row": (64, 32, 48, (0, 0, 64, 0), 32),
    "rows_no_multiple_of_the_tile": (70, 32, 48, (3, 5, 0, 13), 16),
    "empty_groups_first_and_last": (48, 32, 32, (0, 17, 0, 0, 9, 0), 16),
    "a_tile_of_sub_tiles": (300, 128, 128, (130, 0, 3, 125), 256),
    # SmallThinker's and OLMoE's widths
    "smallthinker_widths": (32, 2560, 768, (9, 0, 14), 16),
    "olmoe_widths": (32, 2048, 1024, (20, 12), 32),
}
DTYPES = {"float32": (jnp.float32, 1e-5), "bfloat16": (jnp.bfloat16, 2e-2)}


@functools.lru_cache(maxsize=None)
def _both(case, dtype):
    """{pass: (the kernel's, ragged_dot's)} over the rows in a group."""
    from paddle_tpu.ops import expert_gmm
    m, k, n, sizes, block_m = CASES[case]
    rng = np.random.RandomState(len(case))
    lhs, dout = (jnp.asarray(rng.randn(m, w), dtype) for w in (k, n))
    rhs = jnp.asarray(rng.randn(len(sizes), k, n) * k ** -0.5, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    total = int(sizes.sum())
    want, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=a.dtype), lhs, rhs)
    d_rows, d_weights = vjp(dout.at[total:].set(0))
    plan = expert_gmm.plan(sizes, m, block_m)
    # what lies past the groups may be anything: no result reads it
    nan = jnp.asarray(np.nan, dtype)
    return {
        "forward": (expert_gmm.gmm(lhs.at[total:].set(nan), rhs, plan,
                                   interpret=True)[:total], want[:total]),
        "d_rows": (expert_gmm.gmm_drows(dout.at[total:].set(nan), rhs, plan,
                                        interpret=True)[:total],
                   d_rows[:total]),
        "d_weights": (expert_gmm.gmm_dweights(
            lhs.at[total:].set(nan), dout.at[total:].set(nan), plan,
            interpret=True), d_weights)}


@pytest.mark.parametrize("which", ["forward", "d_rows", "d_weights"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_against_ragged_dot(case, dtype, which):
    kind, tol = DTYPES[dtype]
    got, want = _both(case, kind)[which]
    assert got.dtype == want.dtype == kind and got.shape == want.shape
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)
    if which == "d_weights":
        for group, size in enumerate(CASES[case][3]):
            if size == 0:
                assert not got[group].any(), "an empty group's d weights"


def test_plan_visits_every_tile_of_every_group_once():
    from paddle_tpu.ops import expert_gmm
    sizes = jnp.asarray((10, 0, 1, 23, 7), jnp.int32)
    plan = expert_gmm.plan(sizes, 64, 16)
    visits = int(plan.visits[0])
    pairs = list(zip(np.asarray(plan.group_of)[:visits].tolist(),
                     np.asarray(plan.tile_of)[:visits].tolist()))
    # group 3 holds rows 11..33: tiles 0, 1, 2; group 4 rows 34..40: tile 2
    assert pairs == [(0, 0), (1, 0), (2, 0), (3, 0), (3, 1), (3, 2), (4, 2)]
    assert plan.group_of.shape == (64 // 16 + 5 - 1,)
    # the visits past the last name its blocks again: nothing is copied
    assert set(zip(np.asarray(plan.group_of)[visits:].tolist(),
                   np.asarray(plan.tile_of)[visits:].tolist())) == {(4, 2)}
    assert np.asarray(plan.offsets).tolist() == [0, 10, 10, 11, 34, 41]


# --- the unit as the gate/up kernel's epilogue (PR 65) ----------------------

# rows, K, F, the groups' sizes, the row tile
UNIT_CASES = {
    # group 3 ends in tile 2, which group 4 shares; one tile past the sum
    "a_tile_shared_by_two_groups": (64, 32, 48, (10, 0, 1, 23, 7), 16),
    "an_empty_group_between_two": (48, 32, 32, (16, 0, 9), 16),
    # two whole tiles and part of a third belong to no group
    "rows_past_the_groups_sum": (96, 32, 128, (20, 7, 11), 16),
    "a_tile_above_the_rows": (40, 128, 128, (3, 5, 0, 13), 256),
}


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("activation", ["silu", "relu", "relu2"])
@pytest.mark.parametrize("case", sorted(UNIT_CASES))
def test_the_unit_in_the_kernel_is_two_matmuls_and_the_unit_to_the_bit(
        case, activation, dtype):
    """`gmm_unit`'s gate, up and hidden rows against two `gmm` calls and the
    jax.numpy unit of moe.py on the stored arrays, on the groups' rows to
    the bit (the accumulators are rounded before the unit, as the stored
    arrays are); a tile wholly past the groups' sum is not written: it
    holds the interpreter's value of a buffer nothing wrote, NaN, the
    sentinel every output starts from; and no NaN in a row past the sum
    (which may hold anything) reaches a row below it."""
    from paddle_tpu.ops import expert_gmm
    m, k, f, sizes, block_m = UNIT_CASES[case]
    kind, _ = DTYPES[dtype]
    gated = activation != "relu2"
    unit = moe._gated_unit(activation) if gated \
        else moe._ungated_unit(activation)
    rng = np.random.RandomState(len(case))
    total = sum(sizes)
    lhs = jnp.asarray(rng.randn(m, k), kind).at[total:].set(np.nan)
    w_gate, w_up = (jnp.asarray(rng.randn(len(sizes), k, f) * k ** -0.5, kind)
                    for _ in range(2))
    plan = expert_gmm.plan(jnp.asarray(sizes, jnp.int32), m, block_m)
    want = [expert_gmm.gmm(lhs, w, plan, interpret=True)
            for w in ((w_gate, w_up) if gated else (w_up,))]
    want.append(unit(*want))
    got = expert_gmm.gmm_unit(lhs, w_gate if gated else None, w_up, plan,
                              unit, interpret=True)
    assert len(got) == len(want) == (3 if gated else 2)
    written = min(-(-total // block_m) * block_m, m)
    for name, a, b in zip(("gate", "up", "hidden")[-len(got):], got, want):
        assert a.dtype == kind and a.shape == (m, f), name
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        assert np.isfinite(a[:total]).all() and np.abs(a[:total]).max() > 0
        np.testing.assert_array_equal(a[:total], b[:total], err_msg=name)
        assert np.isnan(a[written:]).all(), name + ": a tile past the sum"
    if case == "rows_past_the_groups_sum":
        assert m - written >= 2 * block_m


def test_a_buffer_nothing_wrote_has_an_operand_and_no_value():
    """`unwritten` is a call with one operand (XLA merges no instruction
    without operands, and a grad op's replay has to be merged) that reads
    nothing and writes nothing: the interpreter's NaN everywhere."""
    from paddle_tpu.ops import expert_gmm
    like = jnp.arange(7, dtype=jnp.int32)
    for dtype in (jnp.float32, jnp.bfloat16):
        got = expert_gmm.unwritten((24, 128), dtype, like, interpret=True)
        assert got.shape == (24, 128) and got.dtype == dtype
        assert np.isnan(np.asarray(got, np.float32)).all()
    jaxpr = jax.make_jaxpr(lambda like: expert_gmm.unwritten(
        (24, 128), jnp.float32, like, interpret=True))(like)
    call, = (eqn for eqn in jaxpr.jaxpr.eqns)
    assert [v.aval.shape for v in call.invars] == [(7,)]


# --- routed_ffn through the kernels ----------------------------------------

N, D, E, F, TOP_K = 48, 128, 8, 128, 3


def _routed(held, monkeypatch, kernels):
    if kernels:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "gmm")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert moe.matmul_route(D, F, jnp.float32) == (
        moe.KERNEL_MATMUL if kernels else moe.GROUPED_MATMUL)
    rng = np.random.RandomState(1)
    x, g = (jnp.asarray(rng.randn(N, D), jnp.float32) for _ in range(2))
    router = jnp.asarray(rng.randn(D, E), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.randn(held, D, F) * D ** -0.5,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.randn(held, F, D) * F ** -0.5, jnp.float32)

    def loss(*weights):
        out, balance, z, _ = moe.routed_ffn(
            *weights, top_k=TOP_K, first_expert=0 if held == E else 2)
        return jnp.sum(out * g) + balance[0] + z[0], out

    (_, out), grads = jax.value_and_grad(
        loss, argnums=range(5), has_aux=True)(x, router, w_gate, w_up, w_down)
    return (out,) + grads


@pytest.mark.parametrize("held", [E, 3], ids=["all_held", "a_share_held"])
def test_routed_ffn_through_the_kernels(held, monkeypatch):
    want = _routed(held, monkeypatch, kernels=False)
    got = _routed(held, monkeypatch, kernels=True)
    for name, a, b in zip(("out", "dx", "drouter", "dw_gate", "dw_up",
                           "dw_down"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= 2e-5 * np.abs(b).max(), name


def test_the_route_is_ragged_dot_where_the_kernels_cannot_run(monkeypatch):
    """Off a TPU with nothing set (every other tier-1 test), under a mesh,
    at widths that are no whole lane tiles, and where an expert's matrix
    would not fit."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    assert moe.matmul_route(2560, 768, jnp.bfloat16) == "ragged_dot"
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "gmm")
    assert moe.matmul_route(2560, 768, jnp.bfloat16) == "expert_gmm"
    assert moe.matmul_route(3584, 1024, jnp.bfloat16) == "expert_gmm"
    assert moe.matmul_route(2560, 768, jnp.bfloat16, mesh=object()) \
        == "ragged_dot"
    assert moe.matmul_route(32, 16, jnp.float32) == "ragged_dot"
    assert moe.matmul_route(8192, 2048, jnp.float32) == "ragged_dot"


# --- what the kernels may cost a restart -----------------------------------

def test_import_paddle_tpu_leaves_pallas_out():
    """`jax.experimental.pallas` costs 1.3 s to import (PERF.md section 6,
    PR 48 / PR 50): a cell that runs no kernel never pays it, and one that
    does pays it where the first kernel is traced."""
    code = ("import sys, paddle_tpu, paddle_tpu.parallel.moe; "
            "bad = [m for m in sys.modules if 'pallas' in m]; "
            "assert not bad, bad")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]


# SmallThinker's layer at toy size (tests/unittests/test_causal_lm_
# smallthinker.py), at widths the kernels take and no other test has
CFG = dict(
    hidden_size=256, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=4, vocab_size=64, moe_ffn_hidden_size=128,
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
B, T = 2, 24


def _step(cfg):
    from paddle_tpu.models import causal_lm
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, _, _ = causal_lm.build_train(cfg, T)
    params = main.global_block().all_parameters()
    tok = np.random.RandomState(0).randint(0, cfg["vocab_size"], (B, T + 1))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return exe.run(main, feed={
            "ids": tok[:, :-1], "labels": tok[:, 1:, None],
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy()},
            fetch_list=[loss] + [p.name + "@GRAD" for p in params])


def test_a_kernel_is_traced_once_a_shape_not_once_a_layer(monkeypatch):
    """Four expert layers, nine grouped matmuls each: the step is traced
    with six matmul kernel instances (gate and up as ONE forward kernel
    with the unit in it, down; two shapes of each transpose) and the two
    shapes of the buffer nothing wrote, the counter names the kernels'
    route and the unit in the kernel, and the step's loss and gradients are
    the `ragged_dot` route's."""
    from paddle_tpu.ops import expert_gmm
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    want = _step(CFG)
    traced = []
    pallas_call = expert_gmm.pl.pallas_call

    def counting(*args, **kwargs):
        traced.append(kwargs.get("name"))
        return pallas_call(*args, **kwargs)

    monkeypatch.setattr(expert_gmm.pl, "pallas_call", counting)
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "gmm")
    counter = REGISTRY.counter("ptpu_moe_layers_total", "")
    labels = dict(top_k="3", experts="16", held="8", activation="relu",
                  router_input="pre_attention", rows="held",
                  scoring="softmax", bias="false", scale="1")
    routes = {"expert_gmm": dict(path="expert_gmm", unit="kernel"),
              "ragged_dot": dict(path="ragged_dot")}
    before = {path: counter.value(**labels, **own)
              for path, own in routes.items()}
    got = _step(CFG)
    assert {path: counter.value(**labels, **own) - before[path]
            for path, own in routes.items()} \
        == {"expert_gmm": 4, "ragged_dot": 0}
    fwd, drows, dweights, unit_fwd = expert_gmm.KERNELS
    assert sorted(traced) == sorted(
        [fwd, unit_fwd] + 2 * [drows, dweights]
        + 2 * ["ptpu_expert_rows_unwritten"])
    for a, b in zip(got, want):
        assert np.abs(a - b).max() <= 2e-5 * max(np.abs(b).max(), 1e-3)
