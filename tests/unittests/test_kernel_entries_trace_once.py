"""A function that holds a pallas_call is a jax.jit of its own, everything
but its arrays static (ops/pallas_import.py `kernel_entry`, PR 60): a step
traces a kernel's Python body once a shape and not once a call site, and the
traced jaxpr is one object, which jax lowers as one function.

A family of kernels at a time, on the CPU in the interpreter: `jax.vjp`
through three call sites at one shape and one at another counts two real
traces a kernel in `ptpu_kernel_body_traces_total{kernel}` (four where the
backward pass runs the forward kernel in a second variant); the values and
every gradient are, to the bit, those of the same function traced with jit
off (`jax.disable_jit`: the entries' bodies inline, the operations are the
same; both compiled without XLA's fusion passes, whose choices on the CPU
follow the instructions' order and round a sum one way or the other); and
the three-site function's jaxpr holds one inner jaxpr an entry.
Then a three-layer transformer at toy widths through the Executor: nine
attention call sites, the counter at three a flash kernel and under."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import causal_conv_kernels, gated_delta_kernels, \
    kda_kernels
from paddle_tpu.ops import mhc_kernels, pallas_kernels
from paddle_tpu.ops import rms_norm_kernels, rotary_kernels
from paddle_tpu.ops import selective_scan_kernels
from paddle_tpu.ops.nn_ops import _head_lines

F32 = jnp.float32
FLASH = {"ptpu_flash_fwd": "_flash_fwd_call",
         "ptpu_flash_bwd_dq": "_flash_bwd_dq_call",
         "ptpu_flash_bwd_dkdv": "_flash_bwd_dkdv_call"}


def _normal(seed, *shapes, dtype=F32):
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return tuple((jax.random.normal(k, s, F32) * 0.5).astype(dtype)
                 for k, s in zip(keys, shapes))


def _flash(hq, hkv, d, **kw):
    """q, k, v [1, T, H, D] at T = 32 and, the other shape, 16."""
    def args(other, seed):
        t = 16 if other else 32
        return _normal(seed, (1, t, hq, d), (1, t, hkv, d), (1, t, hkv, d))
    return (lambda q, k, v: pallas_kernels.flash_attention(
        q, k, v, causal=True, interpret=True, **kw)), args


def _two_part():
    def args(other, seed):
        t = 16 if other else 32
        return _normal(seed, (1, t, 2, 128), (1, t, 2, 128), (1, t, 2, 128),
                       (1, t, 2, 64), (1, t, 1, 64))
    return (lambda q, k, v, qr, kr: pallas_kernels.flash_attention(
        q, k, v, causal=True, q_rope=qr, k_rope=kr, interpret=True)), args


def _layer_norm():
    def args(other, seed):
        return _normal(seed, (16 if other else 32, 128), (128,), (128,))
    return (lambda x, s, b: pallas_kernels.layer_norm(
        x, s, b, interpret=True)[0]), args


def _xent():
    def args(other, seed):
        n = 8 if other else 16
        return _normal(seed, (n, 128)) + (jnp.arange(n) % 128,)
    return (lambda logits, labels: pallas_kernels.softmax_xent(
        logits, labels, interpret=True)), args


def _delta_rule():
    def args(other, seed):
        t = 16 if other else 32
        q, k, v, g, beta = _normal(seed, (1, t, 1, 16), (1, t, 1, 16),
                                   (1, t, 2, 16), (1, t, 2), (1, t, 2))
        return q, k, v, -jax.nn.softplus(g), jax.nn.sigmoid(beta)
    return (lambda *a: gated_delta_kernels.gated_delta_rule(
        *a, path="kernel", chunk=16)), args


def _kda_rule():
    def args(other, seed):
        t = 16 if other else 32
        q, k, v, g, beta = _normal(seed, (1, t, 2, 16), (1, t, 2, 16),
                                   (1, t, 2, 16), (1, t, 2, 16), (1, t, 2))
        return q, k, v, -5.0 * jax.nn.sigmoid(g), jax.nn.sigmoid(beta)
    return (lambda *a: kda_kernels.kda_delta_rule(
        *a, path="kernel", chunk=16)), args


def _selective_scan():
    def args(other, seed):
        t = 8 if other else 16
        x, dt, a, b, c, d = _normal(seed, (1, t, 1024), (1, t, 1024),
                                    (1024, 4), (1, t, 4), (1, t, 4), (1024,))
        return x, jax.nn.softplus(dt), -jnp.exp(a), b, c, d
    return (lambda *a: selective_scan_kernels.selective_scan(
        *a, path="kernel", chunk=8)), args


def _causal_conv():
    def args(other, seed):
        return _normal(seed, (1, 16 if other else 32, 128), (128, 4))
    return (lambda x, w: causal_conv_kernels.causal_conv1d(
        x, w, silu=True)), args


_MHC = (4, 2, 1e-6, (-30.0, 30.0))      # streams, Sinkhorn steps, eps, clamp


def _mhc_pre():
    k = mhc_kernels.columns(4)

    def args(other, seed):
        x, phi, bias = _normal(seed, (8 if other else 16, 4 * 128),
                               (4 * 128, k), (k,))
        return x, phi * 0.1, jnp.array([0.3, 0.5, 0.7]), bias

    def call(x, phi, alpha, bias):
        h, coef, stream = mhc_kernels.pre(x, phi, alpha, bias, *_MHC, True)
        return h.sum() + coef.sum() + stream.sum()
    return call, args


def _mhc_post():
    def args(other, seed):
        rows = 8 if other else 16
        x, y, coef = _normal(seed, (rows, 4 * 128), (rows, 128), (rows, 128))
        return x, y, coef
    return (lambda x, y, coef: mhc_kernels.post(x, y, coef, 4, True)), args


def _mhc_stream(pass_, width):
    def args(other, seed):
        return _normal(seed, (8 if other else 16, width))
    return (lambda x: pass_(x, 4, True)), args


# family: ((call, args), {kernel: real traces of the four-site vjp}, {kernel:
# the entry's name, where the three-site jaxpr holds it in ONE variant}).
# The delta rule's backward pass runs its forward kernel again for the
# states (`emit`): a second variant, so four traces and two inner jaxprs.
def _rotary():
    """x [1, T, 2, 128] and its tokens' integer positions; the tables are
    made from them as the rule makes them."""
    def args(other, seed):
        t = 16 if other else 32
        return _normal(seed, (1, t, 2, 128)) + (
            (jnp.arange(t) * (seed + 1)).reshape(1, t),)

    def call(x, pos):
        angle = pos.astype(F32)[:, :, None, None] * 1e4 ** (
            -jnp.arange(0, 128, 2, dtype=F32) / 128)
        return rotary_kernels.rotary(
            x, *rotary_kernels.tables(jnp.cos(angle), jnp.sin(angle)))
    return call, args


def _rms_norm():
    """x [1, T, 2, 128] and the weight of a head, float32 as the rule hands
    it over."""
    def args(other, seed):
        t = 16 if other else 32
        x, scale = _normal(seed, (1, t, 2, 128), (128,))
        return x, 1.0 + scale
    return (lambda x, scale: rms_norm_kernels.rms_norm(
        _head_lines, x, scale, 1e-6)), args


FAMILIES = {
    "flash_plain": (_flash(2, 2, 64), dict.fromkeys(FLASH, 2), FLASH),
    "flash_grouped": (_flash(4, 2, 128), dict.fromkeys(FLASH, 2), FLASH),
    "flash_windowed": (_flash(2, 2, 64, window=8), dict.fromkeys(FLASH, 2),
                       FLASH),
    "flash_two_part": (_two_part(), dict.fromkeys(FLASH, 2), FLASH),
    "layer_norm": (_layer_norm(), {"ptpu_layer_norm_fwd": 2},
                   {"ptpu_layer_norm_fwd": "_ln_call"}),
    "softmax_xent": (_xent(), {"ptpu_softmax_xent_fwd": 2},
                     {"ptpu_softmax_xent_fwd": "_xent_call"}),
    "gated_delta_rule": (
        _delta_rule(),
        {"ptpu_gated_delta_fwd": 4, "ptpu_gated_delta_bwd": 2},
        {"ptpu_gated_delta_bwd": "_bwd_call"}),
    "kda_delta_rule": (
        _kda_rule(), {"ptpu_kda_fwd": 4, "ptpu_kda_bwd": 2},
        {"ptpu_kda_bwd": "_bwd_call"}),
    "selective_scan": (
        _selective_scan(),
        {"ptpu_selective_scan_fwd": 2, "ptpu_selective_scan_bwd": 2},
        {"ptpu_selective_scan_fwd": "_fwd_call",
         "ptpu_selective_scan_bwd": "_bwd_call"}),
    "causal_conv1d": (
        _causal_conv(),
        {"ptpu_causal_conv1d_fwd": 2, "ptpu_causal_conv1d_bwd": 2},
        {"ptpu_causal_conv1d_fwd": "_fwd_call",
         "ptpu_causal_conv1d_bwd": "_bwd_call"}),
    "mhc_pre": (
        _mhc_pre(),
        {"ptpu_mhc_pre_fwd": 2, "ptpu_mhc_pre_bwd": 2,
         "ptpu_mhc_coeffs_fwd": 2, "ptpu_mhc_coeffs_bwd": 2},
        {"ptpu_mhc_pre_fwd": "_pre_fwd_call",
         "ptpu_mhc_pre_bwd": "_pre_bwd_call",
         "ptpu_mhc_coeffs_fwd": "coefficients",
         "ptpu_mhc_coeffs_bwd": "coefficients_bwd"}),
    "mhc_post": (
        _mhc_post(), {"ptpu_mhc_post_fwd": 2, "ptpu_mhc_post_bwd": 2},
        {"ptpu_mhc_post_fwd": "_post_fwd_call",
         "ptpu_mhc_post_bwd": "_post_bwd_call"}),
    "mhc_expand": (
        _mhc_stream(mhc_kernels.expand, 128),
        {"ptpu_mhc_expand": 2, "ptpu_mhc_reduce": 2},
        {"ptpu_mhc_expand": "_expand_call",
         "ptpu_mhc_reduce": "_reduce_call"}),
    "mhc_reduce": (
        _mhc_stream(mhc_kernels.reduce, 4 * 128),
        {"ptpu_mhc_reduce": 2, "ptpu_mhc_expand": 2},
        {"ptpu_mhc_reduce": "_reduce_call",
         "ptpu_mhc_expand": "_expand_call"}),
    # one entry for both passes, the transpose the same kernel at the same
    # shape: a site calls it twice, and a shape traces it twice (jax runs a
    # backward rule under an abstract mesh of its own, and jit keeps a trace
    # under its context)
    "rotary": (_rotary(), {"ptpu_rotary": 4}, {"ptpu_rotary": "_call"}, 2),
    "rms_norm_head": (
        _rms_norm(), {"ptpu_rms_norm_bwd": 2},
        {"ptpu_rms_norm_bwd": "_bwd_call"}),
}


def _traces():
    return {dict(key)["kernel"]: n for key, n in REGISTRY.counter(
        "ptpu_kernel_body_traces_total").samples()}


def _gained(before):
    return {k: n - before.get(k, 0) for k, n in _traces().items()
            if n != before.get(k, 0)}


def _equations(jaxpr, name):
    """Every equation of the primitive `name` in `jaxpr`, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner, name)


def _step(call):
    """sites -> (a result a site, the gradient of their sum to every array
    of every site), by jax.vjp."""
    def results(sites):
        return [jnp.sum(jnp.sin(call(*site).astype(F32))) for site in sites]

    def step(sites):
        outs, vjp = jax.vjp(results, sites)
        return outs, vjp([jnp.ones_like(out) for out in outs])[0]
    return step


def _compiled(fn, sites):
    """fn, lowered for `sites` here (which traces it) and compiled with
    XLA's fusion passes off."""
    return jax.jit(fn).lower(sites).compile(compiler_options={
        "xla_disable_hlo_passes":
            "fusion,cpu-instruction-fusion,multi_output_fusion"})


def _float_arrays(tree):
    """Integer operands (labels) have no gradient to compare."""
    return [np.asarray(a, np.float32) for a in jax.tree.leaves(tree)
            if jnp.issubdtype(a.dtype, jnp.floating)]


# The families are two files' (this one and
# test_kernel_entries_trace_once_b.py, each with sorted(FAMILIES)[i::FILES]):
# under `--dist loadfile` a file is one worker's from start to end, and the
# files of the fewest cases are handed out last (ROADMAP C8, PR 73).
FILES = 2


def a_kernel_entry_traces_once_a_shape(family):
    (call, args), traces, entries = FAMILIES[family][:3]
    # the calls of an entry a site makes, each pass's the one jaxpr: one,
    # but where the backward pass is the forward's own entry
    calls_a_site = (FAMILIES[family][3:] or (1,))[0]
    sites = tuple(args(False, seed) for seed in (1, 2, 3)) + (args(True, 4),)
    step = _step(call)
    jax.clear_caches()          # whatever earlier tests traced at these shapes
    before = _traces()
    outs, grads = _compiled(step, sites)(sites)
    assert _gained(before) == traces

    # three more call sites at the first shape trace nothing, and every site
    # of an entry holds the one jaxpr jit's cache gave it
    before = _traces()
    three = jax.make_jaxpr(step)(sites[:3])
    assert _gained(before) == {}
    by_entry = {}
    for eqn in _equations(three.jaxpr, "jit"):
        by_entry.setdefault(eqn.params["name"], []).append(
            eqn.params["jaxpr"])
    for kernel, entry in entries.items():
        assert len(by_entry[entry]) == 3 * calls_a_site, (kernel, entry)
        assert len({id(j) for j in by_entry[entry]}) == calls_a_site, (
            kernel, entry)
    kernels_a_site = sum(traces.values()) // 2
    assert len(list(_equations(three.jaxpr, "pallas_call"))) \
        == 3 * kernels_a_site

    # a site with jit off, once a shape: the entries' bodies inline, the
    # kernels' equations are the same, and so is every bit of every site
    plain = {}
    for other, site in ((False, sites[0]), (True, sites[3])):
        with jax.disable_jit():
            flat = jax.make_jaxpr(step)((site,))
        assert not set(entries.values()) & {
            eqn.params["name"] for eqn in _equations(flat.jaxpr, "jit")}
        assert len(list(_equations(flat.jaxpr, "pallas_call"))) \
            == kernels_a_site
        plain[other] = _compiled(
            lambda one, flat=flat: jax.core.eval_jaxpr(
                flat.jaxpr, flat.consts, *jax.tree.leaves(one)), (site,))
    for i, site in enumerate(sites):
        want = _float_arrays(plain[i == 3]((site,)))
        got = _float_arrays((outs[i], grads[i]))
        assert len(got) == len(want) > 1
        for a, b in zip(got, want):
            assert np.abs(b).max() > 0
            assert np.array_equal(a, b)


@pytest.mark.parametrize("family", sorted(FAMILIES)[0::FILES])
def test_a_kernel_entry_traces_once_a_shape(family):
    a_kernel_entry_traces_once_a_shape(family)


def test_a_three_layer_transformer_traces_a_kernel_a_variant(monkeypatch):
    """transformer_base's Program at toy widths, three layers, through the
    Executor with every kernel in the interpreter: nine fused_attention ops
    take the flash path (three encoder, three causal, three cross), and a
    flash kernel's body is traced at most three times (causal or not, by
    lengths of keys), layer_norm's at most twice, the loss kernel's once."""
    from paddle_tpu.models import transformer
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "1")
    monkeypatch.setenv("FLAGS_flash_min_seq", "0")
    t, heads, layers = 16, 2, 3
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        _, avg_cost, _ = transformer.build_train(
            src_vocab_size=64, trg_vocab_size=64, max_length=t,
            n_layer=layers, n_head=heads, d_key=16, d_value=16, d_model=32,
            d_inner_hid=64, dropout_rate=0.0, use_fused_attention=True)
    rng = np.random.RandomState(0)
    seqs = [list(rng.randint(3, 64, t)) for _ in range(4)]
    feed = transformer.prepare_batch(seqs[:2], seqs[2:], t, heads,
                                     fused=True)

    def sites():
        return sum(n for key, n in REGISTRY.counter(
            "ptpu_attention_layers_total").samples()
            if dict(key)["path"] == "flash")

    jax.clear_caches()
    before, sites_before = _traces(), sites()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        loss, = exe.run(main, feed=feed, fetch_list=[avg_cost])
    assert np.isfinite(np.asarray(loss)).all()
    assert sites() - sites_before == 3 * layers
    gained = _gained(before)
    for kernel in FLASH:
        assert 1 <= gained[kernel] <= 3, gained
    assert 1 <= gained["ptpu_layer_norm_fwd"] <= 2, gained
    assert gained["ptpu_softmax_xent_fwd"] == 1, gained
