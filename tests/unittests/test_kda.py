"""ops/kda_kernels.py, the delta rule whose decay is a key channel's (Kimi
Delta Attention): both paths (lax.scan over chunks, and the two Pallas
kernels in the interpreter) against the token-by-token recurrence of
models/causal_lm_reference.py, forward and the gradients of q, k, v, g and
beta, under decays at Ling-3.0-flash's bound, near 0 and mixed inside one
chunk, at chunks of one and of four 16-row blocks and at a T the chunk does
not divide; a scalar decay broadcast to the channels is gated_delta_rule;
the op through a Program with its counter; what the function refuses."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import kda_kernels, pallas_kernels
from paddle_tpu.ops.gated_delta_kernels import gated_delta_rule

B, T, H, DK, DV = 2, 100, 2, 16, 8
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def _inputs(decay, seed=0, t=T):
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(rng.randn(B, t, H, DK), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(B, t, H, DV), jnp.float32)
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(B, t, H), jnp.float32))
    a = jnp.asarray(rng.randn(B, t, H, DK), jnp.float32)
    g = {"bound": jnp.full(a.shape, -5.0),           # every channel at it
         "near_zero": -1e-3 * jax.nn.sigmoid(a),
         # sigmoid(3 a): from e^-9 to 1 - e^-9 inside every chunk
         "mixed": -5.0 * jax.nn.sigmoid(3.0 * a)}[decay]
    return q, k, v, g, beta, jnp.asarray(rng.randn(B, t, H, DV), jnp.float32)


def _recurrence(q, k, v, g, beta):
    return reference.kda_rule(reference.l2norm(q) * DK ** -0.5,
                              reference.l2norm(k), v, g, beta)


def _with_grads(rule, args, weight):
    out, vjp = jax.vjp(rule, *args)
    return (out,) + vjp(weight)


_WANT = {}


def _want(decay, t):
    if (decay, t) not in _WANT:
        *args, weight = _inputs(decay, t=t)
        with jax.default_matmul_precision("highest"):
            _WANT[decay, t] = _with_grads(_recurrence, args, weight)
    return _WANT[decay, t]


@pytest.mark.parametrize("decay", ["bound", "near_zero", "mixed"])
@pytest.mark.parametrize("path,chunk,t", [
    ("scan", 16, T), ("scan", 64, T), ("kernel", 16, T), ("kernel", 64, T),
    ("scan", 32, 96), ("kernel", 32, 96)])
def test_both_paths_agree_with_the_recurrence(decay, path, chunk, t):
    """T = 100 is padded to a whole chunk (beta = g = 0 there: nothing
    written, nothing decayed), T = 96 is three chunks of 32. An error is
    taken over its reference's largest value, and over 0.1 where that is
    smaller: at the bound the decay's own gradient is e^-5 of the others',
    and float32's rounding of the sums around it does not shrink with it."""
    *args, weight = _inputs(decay, t=t)
    want = _want(decay, t)
    with jax.default_matmul_precision("highest"):
        got = _with_grads(lambda *a: kda_kernels.kda_delta_rule(
            *a, path=path, chunk=chunk), args, weight)
    for name, one, ref in zip(NAMES, got, want):
        assert one.shape == ref.shape
        scale = max(float(jnp.abs(ref).max()), 0.1)
        assert float(jnp.abs(one - ref).max()) / scale < 1e-4, name


def test_a_scalar_decay_broadcast_to_channels_is_the_gated_delta_rule():
    q, k, v, _, beta, _ = _inputs("mixed")
    g = -jax.nn.softplus(jnp.asarray(
        np.random.RandomState(4).randn(B, T, H), jnp.float32))
    with jax.default_matmul_precision("highest"):
        for path in ("scan", "kernel"):
            scalar = gated_delta_rule(q, k, v, g, beta, path=path, chunk=64)
            channel = kda_kernels.kda_delta_rule(
                q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta,
                path=path, chunk=64)
            assert float(jnp.abs(scalar - channel).max()) < 1e-5, path


def test_bf16_operands_keep_the_state_and_the_decay_float32():
    """Under AMP the matmuls' operands are bf16: the result is within bf16's
    rounding of the float32 one and is not the float32 one."""
    q, k, v, g, beta, _ = _inputs("mixed")
    exact = kda_kernels.kda_delta_rule(q, k, v, g, beta, path="scan")
    for path in ("scan", "kernel"):
        rounded = kda_kernels.kda_delta_rule(
            q, k, v, g, beta, path=path, operand_dtype=jnp.bfloat16)
        assert rounded.dtype == v.dtype
        error = float(jnp.abs(rounded - exact).max() / jnp.abs(exact).max())
        assert 1e-4 < error < 5e-2, (path, error)


def test_a_decay_past_the_bound_is_loud_and_not_wrong():
    """Sixteen rows at -8 a channel: exp(15 x 8) is past float32, and the
    chunked form says so with a NaN, never with a finite wrong number."""
    q, k, v, g, beta, _ = _inputs("bound", t=32)
    out = kda_kernels.kda_delta_rule(q, k, v, g * 1.6, beta, path="scan",
                                     chunk=16)
    assert not bool(jnp.isfinite(out).all())
    fine = kda_kernels.kda_delta_rule(q, k, v, g * 1.15, beta, path="scan",
                                      chunk=16)
    assert bool(jnp.isfinite(fine).all())


@pytest.mark.parametrize("edit,match", [
    (dict(g=lambda a: a[3][..., 0]), "q, k and g"),
    (dict(beta=lambda a: a[4][..., None]), "beta"),
    (dict(path="flash"), "path"), (dict(chunk=48), "chunk")])
def test_what_the_function_refuses(edit, match):
    q, k, v, g, beta, _ = _inputs("mixed", t=32)
    args = [q, k, v, g, beta]
    kwargs = {}
    for key, value in edit.items():
        if key == "g":
            args[3] = value(args)
        elif key == "beta":
            args[4] = value(args)
        else:
            kwargs[key] = value
    with pytest.raises(ValueError, match=match):
        kda_kernels.kda_delta_rule(*args, **kwargs)


def test_the_kernels_are_named():
    assert pallas_kernels.KDA_KERNELS == ("ptpu_kda_fwd", "ptpu_kda_bwd")
    assert set(pallas_kernels.KDA_KERNELS) <= set(pallas_kernels.KERNEL_NAMES)
    assert kda_kernels.SUB_BLOCK == 16


def test_the_op_through_a_program_and_its_counter(monkeypatch):
    """fluid.layers.kda_delta_rule with its grad op, on the scan path and
    (PADDLE_TPU_PALLAS=kda) on the kernels in the interpreter; the counter's
    kind="kda" sample says which."""
    counter = REGISTRY.counter("ptpu_linear_attention_layers_total", "")
    *args, weight = _inputs("mixed", t=64)
    want = _want("mixed", 64)
    for flag, path in (("", "scan"), ("kda", "kernel")):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", flag)
        labels = dict(kind="kda", k_heads=str(H), v_heads=str(H),
                      d_k=str(DK), d_v=str(DV), chunk="64", sub_block="16",
                      path=path)
        before = counter.value(**labels)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            feeds = [fluid.layers.data(name, list(x.shape[1:]), "float32")
                     for name, x in zip("qkvgb", args)]
            for var in feeds:
                var.stop_gradient = False
            out = fluid.layers.kda_delta_rule(*feeds)
            w = fluid.layers.data("w", list(weight.shape[1:]), "float32")
            loss = fluid.layers.reduce_sum(out * w)
            fluid.backward.append_backward(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(
            main, feed=dict(zip("qkvgb", (np.asarray(x) for x in args)),
                            w=np.asarray(weight)),
            fetch_list=[out] + [name + "@GRAD" for name in "qkvgb"])
        assert counter.value(**labels) == before + 1
        for name, one, ref in zip(NAMES, got, want):
            scale = max(float(jnp.abs(ref).max()), 0.1)
            assert float(np.abs(one - np.asarray(ref)).max()) / scale \
                < 2e-4, (path, name)
