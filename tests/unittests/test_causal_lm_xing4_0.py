"""models/causal_lm.py at Xing4.0's shape (tiny widths, seeded weights): the
Program against models/causal_lm_reference.py for loss, logits and every
parameter's gradient, with four residual streams and with one, whole and
as one chip's share; the shares add up; the Sinkhorn normalisation and its
gradient; YaRN's table and scale against hand-computed values; the flash
kernels' latent form (a head of 192 on values of 128, one rotary key for all
heads) and the `ptpu_mhc_*` kernels in the interpreter against the plain
ops; what `resolve()` refuses; and that the builder emits the programs the
parent emitted for every configuration the benchmark had."""
import hashlib
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import mhc_kernels, pallas_kernels
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.ring_attention import attention_reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
# the published keys at toy widths: a leading dense layer, two expert
# layers; heads of 128 + 64 on values of 128 as published (the kernels'
# latent form wants whole lane blocks)
CFG = dict(
    vocab_size=64, hidden_size=128, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=256,
    moe_intermediate_size=64, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, first_k_dense_replace=1, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc", routed_scaling_factor=2,
    n_group=1, topk_group=1, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(YARN, original_max_position_embeddings=16),
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, rope_interleaved=True, router_renorm_epsilon=1e-20,
    router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
    expert_bias_initializer_range=0.1, tie_word_embeddings=False,
    hidden_act="silu", attention_bias=False, num_nextn_predict_layers=0,
    ep_size=1, moe_layer_freq=1, model_type="xing4_0")
HELD = dict(n_routed_experts=2, share=dict(
    chips=4, chip=1, published=dict(n_routed_experts=8)))
B, T = 2, 32
TOLERANCE = 2e-4        # float32 against float32: another order of sums


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _feed(seed=0):
    tok = np.random.RandomState(seed).randint(0, CFG["vocab_size"],
                                              (B, T + 1))
    return {"ids": tok[:, :-1],
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy(),
            "labels": tok[:, 1:, None]}


def _run_program(cfg):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, load = causal_lm.build_train(cfg, T)
    block = main.global_block()
    params = block.all_parameters()
    trained = [p for p in params if p.name + "@GRAD" in block.vars]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        out = exe.run(main, feed=_feed(), fetch_list=[loss, logits, load]
                      + [p.name + "@GRAD" for p in trained])
    got = {"loss": out[0], "logits": out[1], "expert_load": out[2],
           "grads": dict(zip((p.name for p in trained), out[3:])),
           "ops": [op.type for op in block.ops]}
    feed = _feed()
    (loss, (logits, load)), grads = reference.loss_and_grads(
        cfg, weights, *(jnp.asarray(feed[k]) for k in ("ids", "pos",
                                                        "labels")))
    want = {"loss": loss, "logits": logits, "expert_load": load,
            "grads": {p.name: g for p, g in zip(params, grads)}}
    return params, got, want


# the hyper-connections start where the builder's defaults put them (alpha
# 0.5, b_res = I); `paper_start` is arXiv:2512.24880's: alpha 0.01 and H_res
# near the identity
CASES = {"streams_4": {}, "streams_1": dict(hc_mult=1),
         "streams_4_share": HELD, "streams_1_share": dict(HELD, hc_mult=1),
         "streams_4_paper_start": dict(hc_alpha_init=0.01,
                                       hc_res_diag_init=4.0)}
_RUNS = {}


@pytest.fixture(params=sorted(CASES))
def run(request):
    if request.param not in _RUNS:
        _RUNS[request.param] = _run_program(dict(CFG,
                                                 **CASES[request.param]))
    return (request.param,) + _RUNS[request.param]


def test_program_agrees_with_the_reference(run):
    name, params, got, want = run
    assert _error(got["loss"], want["loss"]) < 1e-5
    assert _error(got["logits"], want["logits"]) < TOLERANCE
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])
    assert int(got["expert_load"].sum()) == 2 * 2 * B * T   # 2 layers, top 2
    streams = name.startswith("streams_4")
    assert ("mhc_pre" in got["ops"]) == streams
    assert got["ops"].count("mhc_post") == (6 if streams else 0)
    assert got["ops"].count("fused_attention") == 3


def test_every_gradient_agrees_with_the_reference(run):
    name, params, got, want = run
    trained = {p.name for p in params if p.trainable}
    assert set(got["grads"]) == trained
    assert not any(n.endswith("expert_bias") for n in trained)
    worst = max((_error(got["grads"][n], want["grads"][n]), n)
                for n in trained)
    # at the paper's start the first sub-layer's d b is a sum over tokens
    # that all but cancels (its largest entry is 8e-6): rounding shows
    assert worst[0] < (5e-4 if name.endswith("paper_start")
                       else TOLERANCE), worst


def test_parameters_are_named_by_layer_and_role():
    params, _, _ = _RUNS.get("streams_4") or _run_program(CFG)
    names = [p.name for p in params]
    hc = ["%s.%s" % (role, part) for role in ("attn_hc",)
          for part in ("phi", "b", "alpha")]
    assert names[:12] == ["embedding"] + ["layer_0." + n for n in hc] + [
        "layer_0." + n for n in ("input_norm", "wq_a", "q_a_norm", "wq_b",
                                 "wkv_a", "kv_a_norm", "wkv_b", "wo")]
    assert names[12:19] == ["layer_0.ffn_hc.phi", "layer_0.ffn_hc.b",
                            "layer_0.ffn_hc.alpha",
                            "layer_0.post_attention_norm", "layer_0.w_gate",
                            "layer_0.w_up", "layer_0.w_down"]
    assert names[-2:] == ["final_norm", "head"]
    shapes = {p.name: tuple(p.shape) for p in params}
    assert shapes["layer_0.attn_hc.phi"] == (4 * 128, 24)
    assert shapes["layer_0.wq_b"] == (48, 4 * 192)
    assert shapes["layer_0.wkv_a"] == (128, 32 + 64)
    assert shapes["layer_0.wkv_b"] == (32, 4 * 256)
    assert shapes["layer_1.experts.expert_bias"] == (8,)
    # the shared expert is ungated: gate, up, down and no fourth weight
    assert [n for n in names if n.startswith("layer_1.shared_expert")] == [
        "layer_1.shared_expert.w_gate", "layer_1.shared_expert.w_up",
        "layer_1.shared_expert.w_down"]


# --- the shares add up ------------------------------------------------------

def test_the_chips_shares_add_up_to_the_uncut_layer():
    """Four chips hold 2 of 8 experts each: their routed parts (the
    program's routed_ffn, given a share) plus the shared expert, which
    every chip computes alike, counted once equal the uncut reference's
    layer."""
    c = causal_lm.resolve(CFG)
    rng = np.random.RandomState(3)
    d, f, e = 128, 64, 8
    x = jnp.asarray(rng.randn(B * T, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, e) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.randn(e) * 0.1, jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(e, d, f) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, f, d) * 0.1, jnp.float32)
    shared = [jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
              for s in ((d, f), (d, f), (f, d))]
    with jax.default_matmul_precision("highest"):
        whole, _, _, load = reference.routed_experts(
            x, router, wg, wu, wd, c, expert_bias=bias)
        whole = whole + reference.shared_expert(x, *shared)
        parts = reference.shared_expert(x, *shared)
        for chip in range(4):
            held = slice(2 * chip, 2 * chip + 2)
            out, _, _, chip_load = moe.routed_ffn(
                x, router, wg[held], wu[held], wd[held], top_k=2,
                norm_topk_prob=True, first_expert=2 * chip,
                scoring="sigmoid", expert_bias=bias, scale=2.0,
                norm_eps=1e-20)
            np.testing.assert_array_equal(chip_load, load)
            parts = parts + out
    assert _error(parts, whole) < 1e-5


# --- the Sinkhorn normalisation ---------------------------------------------

def _coefficient_inputs(seed=5, tokens=64, n=4):
    rng = np.random.RandomState(seed)
    k = mhc_kernels.columns(n)
    z = jnp.asarray(rng.randn(tokens, 128) * 2.0, jnp.float32)
    alpha = jnp.asarray([0.4, 0.6, 0.5], jnp.float32)
    bias = jnp.asarray(rng.randn(k) * 0.5, jnp.float32)
    g = jnp.asarray(rng.randn(tokens, 128), jnp.float32).at[:, k:].set(0.0)
    return z, alpha, bias, g, k


def test_h_res_is_doubly_stochastic_to_the_steps_error():
    z, alpha, bias, _, k = _coefficient_inputs()
    coef = mhc_kernels.coefficients(z, alpha, bias, 4, 20, 1e-6,
                                    (-30.0, 30.0), True)
    res = np.asarray(coef[:, 8:k]).reshape(-1, 4, 4)
    assert np.abs(res.sum(2) - 1).max() < 1e-5          # rows: the last step
    assert np.abs(res.sum(1) - 1).max() < 5e-2          # columns: converging
    once = np.asarray(mhc_kernels.coefficients(
        z, alpha, bias, 4, 1, 1e-6, (-30.0, 30.0), True)[:, 8:k]
    ).reshape(-1, 4, 4)
    assert np.abs(res.sum(1) - 1).max() < np.abs(once.sum(1) - 1).max()
    assert (res > 0).all()
    plain = mhc_kernels.coefficients_plain(z, alpha, bias, 4, 20, 1e-6,
                                           (-30.0, 30.0))
    assert _error(coef, plain) < 1e-5


@pytest.mark.parametrize("what", ["dz", "dalpha", "dbias"])
def test_sinkhorns_gradient_is_jax_grad_of_the_loop(what):
    """ptpu_mhc_coeffs_bwd replays the 20 steps and differentiates them as
    written: the same as jax.grad of the reference's Python loop."""
    z, alpha, bias, g, k = _coefficient_inputs()
    c = dict(hc_eps=1e-6, hc_sinkhorn_iters=20, mhc_h_res_clamp_min=-30.0,
             mhc_h_res_clamp_max=30.0)

    def loop(z, alpha, bias):       # the reference, given z in place of x'Phi
        x = jnp.zeros((z.shape[0], 4, 6), jnp.float32).at[:, 0, 0].set(1.0)
        norm = jax.lax.rsqrt(1.0 / 24 + 1e-6)
        # Phi picks x' = norm in the one nonzero channel: x' Phi = z
        pre, post, res = jax.vmap(
            lambda zt: reference.hyper_connection(
                x[0], jnp.zeros((24, k)).at[0].set(zt / norm), bias, alpha,
                c))(z[:, :k])
        coef = jnp.concatenate([pre, post, res.reshape(-1, 16)], -1)
        return jnp.sum(coef * g[:, :k])

    want = dict(zip(("dz", "dalpha", "dbias"),
                    jax.grad(loop, argnums=(0, 1, 2))(z, alpha, bias)))
    got = dict(zip(("dz", "dalpha", "dbias"), mhc_kernels.coefficients_bwd(
        z, alpha, bias, g, 4, 20, 1e-6, (-30.0, 30.0), True)))
    if what == "dz":
        assert _error(got["dz"][:, :k], want["dz"][:, :k]) < 1e-4
    else:
        assert _error(got[what], want[what]) < 1e-4


# --- YaRN ---------------------------------------------------------------------

def test_yarn_table_and_scale_against_hand_computed_values():
    """factor 64 over 4096 at theta 10000 on 64 rotary channels: the ramp
    runs from pair 10 to pair 23 and m = 0.1 ln 64 + 1 = 1.41589."""
    table, table_scale, scale = causal_lm.yarn_table(YARN, 10000, 64, 192)
    f = [10000 ** (-2 * i / 64) for i in range(32)]
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                    / (2 * math.log(10000)))
    hi = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                   / (2 * math.log(10000)))
    assert (lo, hi) == (10, 23)
    np.testing.assert_allclose(table[:11], f[:11], rtol=1e-5)
    np.testing.assert_allclose(table[23:], [x / 64 for x in f[23:]],
                               rtol=1e-5)
    r = (16 - 10) / 13
    assert table[16] == pytest.approx(f[16] * (1 - r) + f[16] / 64 * r,
                                      rel=1e-5)
    m = 0.1 * math.log(64) + 1
    assert m == pytest.approx(1.41589, abs=1e-5)
    assert scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-6)
    assert scale == pytest.approx(0.14468, abs=1e-5)
    assert table_scale == 1.0
    # the reference reads rope_scaling by itself and finds the same table
    np.testing.assert_allclose(
        reference.yarn_inv_freq(YARN, 10000, 64), table, rtol=1e-5)
    assert float(reference.yarn_mscale(64, 1)) == pytest.approx(m, rel=1e-6)


@pytest.mark.parametrize("layout", ["interleaved", "half"])
def test_rotary_embedding_takes_a_table_and_a_layout(layout):
    """The op with YaRN's table against the reference's rope; the default
    layout is the half-split one every other configuration uses."""
    table, _, _ = causal_lm.yarn_table(YARN, 10000, 64, 192)
    rng = np.random.RandomState(2)
    x = rng.randn(2, 8, 3, 64).astype("float32")
    pos = np.broadcast_to(np.arange(8) * 500, (2, 8)).copy()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", [8, 3, 64], dtype="float32")
        pv = fluid.layers.data("pos", [8], dtype="int64")
        out = fluid.layers.rotary_embedding(xv, pv, inv_freq=table,
                                            layout=layout)
        plain = fluid.layers.rotary_embedding(xv, pv)
    assert "layout" not in plain.op.attrs and "inv_freq" not in plain.op.attrs
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x, "pos": pos}, fetch_list=[out])
    want = reference.rope(jnp.asarray(x), jnp.asarray(pos), 10000,
                          inv_freq=jnp.asarray(table),
                          interleaved=layout == "interleaved")
    assert _error(got, want) < 1e-5
    other = reference.rope(jnp.asarray(x), jnp.asarray(pos), 10000,
                           inv_freq=jnp.asarray(table),
                           interleaved=layout != "interleaved")
    assert _error(got, other) > 0.1


# --- the flash kernels' latent form ---------------------------------------------

def _latent_operands(t=96, h=4, d=128, dr=64):
    ks = jax.random.split(jax.random.key(1), 6)
    q, k, v, w = (jax.random.normal(ks[i], (2, t, h, d)) for i in (0, 1, 2, 5))
    return (q, k, v, jax.random.normal(ks[3], (2, t, h, dr)),
            jax.random.normal(ks[4], (2, t, 1, dr))), w


@pytest.fixture(scope="module")
def latent():
    ops, w = _latent_operands()
    scale = 0.1

    def flash(q, k, v, qr, kr):
        return jnp.sum(w * pallas_kernels.flash_attention(
            q, k, v, causal=True, scale=scale, q_rope=qr, k_rope=kr,
            block_q=32, block_k=32, interpret=True))

    def dense(q, k, v, qr, kr):     # concatenated and broadcast, the naive way
        return jnp.sum(w * attention_reference(
            jnp.concatenate([q, qr], -1),
            jnp.concatenate([k, jnp.broadcast_to(kr, qr.shape)], -1), v,
            causal=True, scale=scale))

    got = jax.value_and_grad(flash, argnums=(0, 1, 2, 3, 4))(*ops)
    want = jax.value_and_grad(dense, argnums=(0, 1, 2, 3, 4))(*ops)
    names = ("forward", "dq", "dk", "dv", "dq_rope", "dk_rope")
    return (dict(zip(names, (got[0],) + got[1])),
            dict(zip(names, (want[0],) + want[1])))


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv", "dq_rope",
                                  "dk_rope"])
def test_flash_kernels_at_192_on_128_with_a_shared_rotary_key(latent, what):
    got, want = latent
    assert got[what].shape == want[what].shape
    assert _error(got[what], want[what]) < 2e-5


def test_flash_kernels_are_unchanged_at_one_width():
    """D_qk = D_v without the rotary operands: the kernels' results against
    the dense attention as before, and the wrapper refuses a value of
    another width by name."""
    (q, k, v, qr, kr), w = _latent_operands()

    def flash(q, k, v):
        return jnp.sum(w * pallas_kernels.flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, interpret=True))
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(w * attention_reference(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    assert max(_error(a, b) for a, b in zip(got, want)) < 2e-5
    with pytest.raises(ValueError, match="q_rope and k_rope"):
        pallas_kernels.flash_attention(q, k, v[..., :64], interpret=True)
    with pytest.raises(ValueError, match="latent form takes q_rope"):
        pallas_kernels.flash_attention(q, k, v, q_rope=qr, interpret=True)
    with pytest.raises(ValueError, match="whole lane blocks"):
        pallas_kernels.flash_attention(
            q[..., :64], k[..., :64], v[..., :64], q_rope=qr, k_rope=kr,
            interpret=True)
    assert pallas_kernels.heads_a_block(32, 32, 192) is None
    assert pallas_kernels.heads_a_block(32, 32, 128) == 1


# --- the hyper-connections' kernels -------------------------------------------

def _mhc_layer(kernels, dtype, seed=0, rows=32, n=4, c=128):
    ks = jax.random.split(jax.random.key(seed), 8)
    k = mhc_kernels.columns(n)
    x = jax.random.normal(ks[0], (rows, n * c), jnp.float32).astype(dtype)
    phi = jax.random.normal(ks[1], (n * c, k)) * 0.05
    alpha = jnp.array([0.3, 0.5, 0.7])
    bias = jax.random.normal(ks[2], (k,)) * 0.5
    w = jax.random.normal(ks[3], (c, c)) * 0.1
    args = (n, 20, 1e-6, (-30.0, 30.0))

    def f(x, phi, alpha, bias, w):
        h, coef, stream = mhc_kernels.pre(x, phi, alpha, bias, *args, kernels)
        y = jnp.tanh(h.astype(jnp.float32) @ w).astype(x.dtype)
        out = mhc_kernels.post(stream, y, coef, n, kernels)
        wide = mhc_kernels.expand(mhc_kernels.reduce(out, n, kernels), n,
                                  kernels)
        return jnp.sum(jnp.sin(wide.astype(jnp.float32))), (h, coef, out)

    (loss, aux), grads = jax.value_and_grad(
        f, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, phi, alpha, bias, w)
    names = ("h", "coef", "x_out", "dx", "dphi", "dalpha", "dbias", "dw")
    return dict(zip(names, aux + grads), loss=loss)


@pytest.mark.parametrize("dtype,tolerance", [("float32", 2e-5),
                                             ("bfloat16", 1e-2)])
@pytest.mark.parametrize("what", ["loss", "h", "coef", "x_out", "dx", "dphi",
                                  "dalpha", "dbias", "dw"])
def test_mhc_kernels_against_the_plain_ops(dtype, tolerance, what):
    """Every ptpu_mhc_* kernel in the interpreter, forward and backward,
    against the jax.numpy passes jax differentiates by itself. In bfloat16
    the coefficients still agree to float32's rounding: x is bfloat16
    exactly and Phi enters as hi + lo."""
    key = (dtype,)
    if key not in _RUNS:
        _RUNS[key] = (_mhc_layer(True, jnp.dtype(dtype)),
                      _mhc_layer(False, jnp.dtype(dtype)))
    got, want = _RUNS[key]
    assert _error(got[what], want[what]) < (
        2e-5 if what == "coef" else tolerance)


def test_mhc_kernels_take_their_names_from_kernel_names():
    assert set(mhc_kernels.KERNELS) <= set(pallas_kernels.KERNEL_NAMES)
    assert mhc_kernels.applies(4096, 4, 3584)
    assert not mhc_kernels.applies(4096, 4, 100)


# --- what resolve() refuses, and what it reads ----------------------------------

@pytest.mark.parametrize("edit,match", [
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(n_group=3), "n_group"),
    (dict(topk_group=2), "topk_group"),
    (dict(rope_scaling=dict(YARN, type="linear")), "rope_scaling"),
    (dict(total_ut_steps=2, n_routed_experts=0), "hc_mult"),
    (dict(topk_method="group_limited_greedy"), "topk_method"),
    (dict(kv_lora_rank=None), "kv_lora_rank"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(num_key_value_heads=2), "latent attention"),
])
def test_resolve_refuses_by_name_what_is_not_built(edit, match):
    with pytest.raises(NotImplementedError, match=match):
        causal_lm.resolve(dict(CFG, **edit))


def test_resolve_reads_the_published_keys():
    c = causal_lm.resolve(dict(CFG, **HELD))
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (8, 2, 2)
    assert c["ffn_layers"] == ["dense", "experts", "experts"]
    assert (c["router_scoring"], c["use_expert_bias"]) == ("sigmoid", True)
    assert (c["shared_expert_intermediate_size"], c["shared_expert_gate"],
            c["intermediate_size"], c["dense_intermediate_size"]) \
        == (64, False, 64, 256)
    assert c["latent"] and c["rotary_dim"] == 64 and c["head_dim"] == 192
    assert len(c["rope_inv_freq"]) == 32
    plain = causal_lm.resolve(dict(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=4, intermediate_size=48))
    assert not plain["latent"] and plain["hc_mult"] == 1
    assert plain["rope_inv_freq"] is None \
        and plain["attention_scale"] is None


def test_the_new_ops_are_counted():
    hc = REGISTRY.counter("ptpu_hyper_connection_layers_total", "")
    attention = REGISTRY.counter("ptpu_attention_layers_total", "")
    labels = dict(streams="4", width="128", sinkhorn_iters="20", path="xla")
    latent = dict(kind="full", window="0", q_heads="4", kv_heads="4",
                  path="dense", head_dim="128", heads_a_block="none",
                  form="latent", v_dim="128", rope_dim="64",
                  rope_key_group="4")
    before = hc.value(**labels), attention.value(**latent)
    _run_program(CFG)
    assert hc.value(**labels) - before[0] == 6          # two a layer
    assert attention.value(**latent) - before[1] == 3


# --- the builder emits the parent's programs --------------------------------------

# (sha256 of every op's type, attrs and slots and of every parameter's name
# and shape, the number of those lines) of the training program each of the
# benchmark's five causal_lm configurations built at the parent commit
# (944866a), at T = 32 and a vocabulary of 64: none of them has a key this
# PR added, so the builder emits for them what it emitted then, op for op
PARENT_PROGRAMS = {
    "olmoe_1b_7b": ("783709248e34da44", 127),
    "smallthinker_21b_a3b": ("7b129097247f711a", 315),
    "qwen3_next_80b_a3b": ("8c52377528e76a47", 623),
    "ouro_2_6b": ("4279248872769230", 679),
    "lfm2_8b_a1b": ("fdb2554623e38c46", 351)}


def _program_digest(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, vocab_size=64)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        causal_lm.build_train(cfg, 32, learning_rate=cfg["learning_rate"])
    lines = []
    for block in main.blocks:
        for op in block.ops:
            attrs = {k: v for k, v in sorted(op.attrs.items())
                     if isinstance(v, (bool, int, float, str, list, tuple,
                                       type(None)))
                     and k not in ("fwd_uid",)}
            lines.append("%s %s %s %s" % (
                op.type, json.dumps(attrs, sort_keys=True, default=str),
                sorted((s, len(n)) for s, n in op.inputs.items()),
                sorted((s, len(n)) for s, n in op.outputs.items())))
    for p in main.global_block().all_parameters():
        lines.append("%s %s %s" % (p.name, tuple(p.shape), p.trainable))
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16], \
        len(lines)


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_the_builder_emits_the_parents_program(name):
    assert _program_digest(name) == PARENT_PROGRAMS[name]
