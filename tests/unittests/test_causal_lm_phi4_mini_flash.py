"""models/causal_lm.py at Phi-4-mini-flash-reasoning's shape (tiny widths,
seeded weights): the Program against models/causal_lm_reference.py for loss,
logits and every trained parameter's gradient; the selective scan op alone
against `lax.scan` (both paths, forward and the six gradients) at a T that
is no multiple of the chunk; the window's edge; the gradients that reach the
memory and the shared keys and values as the sum over their readers; the
tied table's gradient; what `resolve()` reads of the new keys and what it
still refuses."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import selective_scan_kernels as scan

# published layers 0, 1, 16, 17, 18, 19 of 32: a Mamba mixer, a windowed
# differential attention, the Mamba mixer that hands on its scan output, the
# full attention that hands on its keys and values, a gated memory unit and a
# cross attention; 4 query pairs on 2 key pairs of 8; 4 states, R = 4
CFG = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=6,
    num_attention_heads=8, num_key_value_heads=4, intermediate_size=96,
    layer_norm_eps=1e-5, mb_per_layer=2, sliding_window=8,
    tie_word_embeddings=True, hidden_act="silu", attention_bias=True,
    conv_bias=True, differential_attention=True, mlp_gate_up_fused=True,
    mamba_d_state=4, layer_indices=[0, 1, 16, 17, 18, 19], embd_pdrop=0,
    resid_pdrop=0, mlp_bias=False, lm_head_bias=False, initializer_range=0.2,
    share=dict(chips=1, chip=0, published=dict(num_hidden_layers=32)))
B, T = 2, 24
TOLERANCE = 2e-4                # float32 against float32: another order of
#                                 sums
# parameters that start at an identity (a bias of 0, a weight of 1): drawn
# off it before the comparison, or a rule that drops one would pass
OFF_IDENTITY = (".bias", ".d", ".subln", "final_norm", "_norm")


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


def _run_program(cfg=CFG):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, _ = causal_lm.build_train(cfg, T)
    block = main.global_block()
    params = block.all_parameters()
    scope = fluid.Scope()
    rng = np.random.RandomState(5)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for p in params:
            if p.name.endswith(OFF_IDENTITY):
                w = np.asarray(scope.get(p.name))
                scope.set(p.name, jnp.asarray(
                    w + 0.2 * rng.standard_normal(w.shape).astype("f")))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        names = [p.name + "@GRAD" for p in params]
        out = exe.run(main, feed=_feed(), fetch_list=[loss, logits] + names)
    return main, params, weights, {
        "loss": out[0], "logits": out[1],
        "grads": dict(zip(names, out[2:]))}


@pytest.fixture(scope="module")
def program():
    return _run_program()


@pytest.fixture(scope="module")
def want(program):
    _, params, weights, _ = program
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    (loss, (logits, _)), grads = jax.jit(
        lambda p: reference.loss_and_grads(CFG, p, feed["ids"], feed["pos"],
                                           feed["labels"]))(weights)
    return {"loss": loss, "logits": logits,
            "grads": dict(zip((p.name for p in params), grads))}


def test_resolve_reads_the_decoder_hybrid_decoders_keys():
    c = causal_lm.resolve(CFG)
    assert c["mixer_layers"] == ["mamba", "attention", "mamba", "attention",
                                 "gmu", "attention"]
    assert c["reads_layers"] == ["own"] * 4 + ["shared"] * 2
    assert c["window_layers"] == [None, 8, None, None, None, None]
    assert (c["memory_layer"], c["kv_layer"]) == (2, 3)
    # lambda_init at the PUBLISHED index, not at the cut's
    assert c["lambda_init_layers"][1] == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3))
    assert c["lambda_init_layers"][5] == pytest.approx(
        0.8 - 0.6 * np.exp(-0.3 * 19))
    assert c["lambda_init_layers"][0] is None
    assert c["rope_theta"] is None and c["norm_type"] == "layer_norm"
    assert c["mamba_dt_rank"] == 4 and c["head_dim"] == 8
    # the whole model, by its own indices
    whole = causal_lm.resolve(dict(
        {k: v for k, v in CFG.items() if k not in ("layer_indices",
                                                   "share")},
        num_hidden_layers=32))
    kinds = list(zip(whole["mixer_layers"], whole["reads_layers"]))
    assert kinds.count(("mamba", "own")) == 9
    assert kinds.count(("attention", "own")) == 9
    assert kinds.count(("gmu", "shared")) == 7
    assert kinds.count(("attention", "shared")) == 7
    assert whole["window_layers"].count(8) == 8


@pytest.mark.parametrize("change, error", [
    (dict(attention_bias=True, differential_attention=False,
          mb_per_layer=0), NotImplementedError),
    (dict(conv_bias=True, mb_per_layer=0, differential_attention=False,
          attention_bias=False), NotImplementedError),
    (dict(layer_indices=[0, 1, 16, 18, 19, 21]), ValueError),   # no layer 17
    (dict(layer_indices=[0, 1, 15, 17, 18, 19]), ValueError),   # no layer 16
    (dict(layer_indices=[0, 1, 2]), ValueError),
    (dict(mb_per_layer=3), NotImplementedError),
    (dict(total_ut_steps=2), NotImplementedError),
    (dict(num_nextn_predict_layers=1), NotImplementedError),
    (dict(mlp_bias=True), NotImplementedError),
    (dict(resid_pdrop=0.1), NotImplementedError),
    (dict(num_attention_heads=6, num_key_value_heads=3), ValueError)])
def test_resolve_refuses(change, error):
    with pytest.raises(error):
        causal_lm.resolve(dict(CFG, **change))


def test_loss_and_logits_match_the_reference(program, want):
    got = program[3]
    assert _error(got["loss"], want["loss"]) < TOLERANCE
    assert _error(got["logits"], want["logits"]) < TOLERANCE


def test_every_trained_parameter_has_the_references_gradient(program, want):
    _, params, _, got = program
    assert len(params) == 94
    worst = {p.name: _error(got["grads"][p.name + "@GRAD"],
                            want["grads"][p.name]) for p in params
             if not p.name.endswith("wk.bias")}
    # a bias on the keys moves every score of a query alike, and a softmax
    # does not see that: its gradient is rounding in both, held to the
    # scale of the value bias's
    for p in params:
        if p.name.endswith("wk.bias"):
            scale = np.abs(want["grads"][p.name.replace("wk", "wv")]).max()
            for grad in (got["grads"][p.name + "@GRAD"],
                         want["grads"][p.name]):
                assert np.abs(grad).max() < 1e-5 * scale
    assert max(worst.values()) < 5 * TOLERANCE, sorted(
        worst.items(), key=lambda kv: -kv[1])[:5]
    # the tied table: the lookup's scatter-add plus the head's matmul
    assert np.abs(want["grads"]["embedding"]).max() > 0


def test_what_is_handed_on_gets_the_sum_of_its_readers_gradients():
    """The memory m (layer 2's scan output: its own gate and layer 4's
    unit read it) and the shared keys and values (layer 3's core and layer
    5's read them): the gradient the program accumulates into each is the
    reference's jax.grad with respect to that array, which is the sum over
    the readers by construction."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, _, _ = causal_lm.build_train(CFG, T)
    block = main.global_block()
    scans = [op for op in block.ops if op.type == "selective_scan"]
    cores = [op for op in block.ops if op.type == "fused_attention"]
    memory = scans[1].output("Out")[0]
    key, value = cores[1].input("K")[0], cores[1].input("V")[0]
    assert cores[2].input("K")[0] == key and cores[2].input("V")[0] == value
    readers = [op.type for op in block.ops if memory in op.all_input_vars()
               and op.type != "grad_of"]
    assert len(readers) >= 2
    params = block.all_parameters()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = [np.asarray(scope.get(p.name)) for p in params]
        got = exe.run(main, feed=_feed(), fetch_list=[
            memory + "@GRAD", key + "@GRAD", value + "@GRAD"])
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    c = causal_lm.resolve(CFG)

    def loss_with(offsets):
        """The reference's loss with `offsets` added to what is handed
        on: its gradient at 0 is the gradient that reaches each array."""
        original_mamba = reference.mamba
        original_attention = reference.differential_attention
        seen = {"scan": 0, "core": 0}

        def mamba(*args, **kw):
            out, y = original_mamba(*args, **kw)
            seen["scan"] += 1
            if seen["scan"] == 2:
                # the gate of the layer itself reads the moved memory too
                z = jnp.split(args[0] @ args[1], 2, axis=-1)[1]
                y = y + offsets["memory"]
                out = (y * jax.nn.silu(z)) @ args[9]
            return out, y

        def attention(a, wq, bq, kv, *rest):
            seen["core"] += 1
            if seen["core"] == 2:       # the layer that hands on
                _, (k, v) = original_attention(a, wq, bq, kv, *rest)
                kv = (k + offsets["k"], v + offsets["v"])
            return original_attention(a, wq, bq, kv, *rest)

        reference.mamba, reference.differential_attention = mamba, attention
        try:
            return reference.loss_fn(CFG, weights, feed["ids"], feed["pos"],
                                     feed["labels"])[0]
        finally:
            reference.mamba = original_mamba
            reference.differential_attention = original_attention

    di, hd = 2 * c["hidden_size"], c["head_dim"]
    zeros = {"memory": jnp.zeros((B, T, di)),
             "k": jnp.zeros((B, T, 2, 2, hd)),
             "v": jnp.zeros((B, T, 2, 2 * hd))}
    want = jax.grad(loss_with)(zeros)
    assert _error(got[0], want["memory"]) < 5 * TOLERANCE
    # the program's keys are [key pair, map] heads padded to 2 hd with
    # zeros, its values the pair's repeated a map: the gradient of the
    # padding is not the reference's, and the two maps' values' sum is
    dk = np.asarray(got[1]).reshape(B, T, 2, 2, 2 * hd)[..., :hd]
    assert _error(dk, want["k"]) < 5 * TOLERANCE
    dv = np.asarray(got[2]).reshape(B, T, 2, 2, 2 * hd).sum(3)
    assert _error(dv, want["v"]) < 5 * TOLERANCE


def test_the_windows_edge():
    """Under sliding_window w query i sees key i - w + 1 and not key i - w:
    the reference's mask, and the program's through fused_attention."""
    c = reference.layer_config(causal_lm.resolve(CFG), 1)
    assert c["window"] == 8
    rng = np.random.RandomState(3)
    d, hd = CFG["hidden_size"], 8
    a = jnp.asarray(rng.standard_normal((1, T, d)), jnp.float32)
    wq, wo = (jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)
              for _ in range(2))
    kv = [jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
          for s in ((d, d // 2), (d // 2,), (d, d // 2), (d // 2,))]
    lambdas = [jnp.asarray(rng.standard_normal(hd) * 0.1, jnp.float32)
               for _ in range(4)]

    def out(a):
        return reference.differential_attention(
            a, wq, None, kv, lambdas, jnp.ones(2 * hd), wo, None, c)[0]

    # d out[i] / d a[j]: zero for j <= i - 8 and for j > i
    jac = jax.jacobian(lambda a: out(a)[0, 20].sum())(a)[0]     # [T, D]
    moved = np.abs(np.asarray(jac)).max(-1) > 0
    assert moved[13:21].all() and not moved[:13].any() \
        and not moved[21:].any()


@pytest.mark.parametrize("t", [24, 40])
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_selective_scan_matches_the_token_by_token_recurrence(path, t):
    """Forward and the gradients of x, Delta, A, B, C and D against the
    reference's lax.scan, the kernels in the interpreter under chunks of 16
    (T = 24 and 40: neither a whole number of chunks)."""
    b, ch, n = 2, 1024, 4
    keys = jax.random.split(jax.random.key(t), 7)
    x = jax.random.normal(keys[0], (b, t, ch))
    delta = jax.nn.softplus(jax.random.normal(keys[1], (b, t, ch)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(keys[2], (ch, n)))
    bm, cm = (jax.random.normal(k, (b, t, n)) for k in keys[3:5])
    d = jax.random.normal(keys[5], (ch,))
    w = jax.random.normal(keys[6], (b, t, ch))
    args = (x, delta, a, bm, cm, d)

    def op(*args):
        return scan.selective_scan(*args, path=path, chunk=16)

    want = reference.selective_scan(*args)
    assert _error(op(*args), want) < 1e-5
    grads = jax.grad(lambda *v: (op(*v) * w).sum(), argnums=range(6))(*args)
    wants = jax.grad(lambda *v: (reference.selective_scan(*v) * w).sum(),
                     argnums=range(6))(*args)
    for name, got, ref in zip("x delta a b c d".split(), grads, wants):
        assert _error(got, ref) < 1e-5, name


def test_selective_scan_refuses_what_its_kernels_cannot_take():
    assert scan.applies(5120, 16) and not scan.applies(5000, 16) \
        and not scan.applies(1024, 32)
    x = jnp.zeros((1, 8, 64))
    with pytest.raises(ValueError):
        scan.selective_scan(x, x, jnp.zeros((64, 4)), jnp.zeros((1, 8, 4)),
                            jnp.zeros((1, 8, 4)), jnp.zeros((64,)))
    with pytest.raises(ValueError):
        scan.selective_scan(x, x, jnp.zeros((64, 4)), jnp.zeros((1, 8, 3)),
                            jnp.zeros((1, 8, 4)), jnp.zeros((64,)),
                            path="xla")


def test_counters_say_what_was_built_and_lowered():
    layers = REGISTRY.counter("ptpu_causal_lm_layers_total", "")
    scans = REGISTRY.counter("ptpu_selective_scan_layers_total", "")
    common = dict(rotary_dim="0", gate="false", ffn="dense", shared="0",
                  sandwich="false", module="trunk")
    keys = {
        "mamba": dict(common, mixer="mamba", conv="4", reads="own",
                      differential="false"),
        "gmu": dict(common, mixer="gmu", conv="0", reads="shared",
                    differential="false"),
        "own": dict(common, mixer="attention", conv="0", reads="own",
                    differential="true"),
        "cross": dict(common, mixer="attention", conv="0", reads="shared",
                      differential="true")}
    lowered = dict(channels="128", states="4", chunk="64", path="xla")
    before = {k: layers.value(**v) for k, v in keys.items()}
    before_scans = scans.value(**lowered)
    _run_program()
    assert {k: layers.value(**v) - before[k] for k, v in keys.items()} == {
        "mamba": 2, "gmu": 1, "own": 2, "cross": 1}
    assert scans.value(**lowered) - before_scans == 2
