"""models/causal_lm.py at GLM-4.7-Flash's shape (tiny widths, seeded
weights): a multi-token-prediction module that shares the embedding and the
head with the trunk, and latent attention whose part without position (48)
is not as wide as its value (64). The Program against
models/causal_lm_reference.py for both losses, both logits and every
parameter's gradient (the embedding's and the head's each the sum of two
uses), whole and as one chip's share; the attention core at 48 + 16 on 64 in
the form `pallas_kernels.latent_form` gives it in the interpreter and on the
dense path; the shares of a trunk layer and of the module's layer add up;
what `resolve()` refuses; a module broken on purpose is told from the
healthy one; the counters and the module's role on its ops' scopes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import lowering
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.ops import pallas_kernels
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.ring_attention import attention_reference

# the published keys at toy widths: a leading dense layer, an expert layer,
# and the module's layer behind them
CFG = dict(
    vocab_size=64, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, first_k_dense_replace=1, norm_topk_prob=True,
    topk_method="noaux_tc", router_scoring="sigmoid",
    routed_scaling_factor=1.8, n_group=1, topk_group=1, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=48, qk_rope_head_dim=16,
    v_head_dim=64, rms_norm_eps=1e-5, rope_theta=1000000, rope_scaling=None,
    partial_rotary_factor=1, rope_interleaved=True,
    router_renorm_epsilon=1e-20, router_aux_loss_coef=0.0,
    router_z_loss_coef=0.0, expert_bias_initializer_range=0.1,
    tie_word_embeddings=False,
    hidden_act="silu", attention_bias=False, num_nextn_predict_layers=1,
    mtp_loss_weight=0.3, model_type="glm4_moe_lite")
HELD = dict(n_routed_experts=2, share=dict(
    chips=4, chip=1, published=dict(n_routed_experts=8)))
B, T = 2, 32
TOLERANCE = 2e-4        # float32 against float32: another order of sums
SHARED = ("embedding", "head", "layer_2.eh_proj", "layer_1.wo")


def _error(got, want):
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32).reshape(want.shape)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _feed(seed=0):
    tok = np.random.RandomState(seed).randint(0, CFG["vocab_size"],
                                              (B, T + 2))
    return {"ids": tok[:, :-2],
            "pos": np.broadcast_to(np.arange(T), (B, T)).copy(),
            "labels": tok[:, 1:-1, None], "labels_next": tok[:, 2:, None]}


def _reference(cfg, weights, feed, **broken):
    """The reference's loss, its parts and every gradient; `broken` swaps a
    feed (labels_next=..., next_ids through labels=...)."""
    feed = dict({k: jnp.asarray(v) for k, v in feed.items()}, **broken)

    def loss(p, found=None):
        return reference.loss_fn(cfg, p, feed["ids"], feed["pos"],
                                 feed["labels"],
                                 labels_next=feed["labels_next"], found=found)
    weights = [jnp.asarray(w, jnp.float32) for w in weights]
    (total, (logits, load)), grads = jax.value_and_grad(
        loss, has_aux=True)(weights)
    found = {}
    loss(weights, found)
    return dict(found, loss=total, logits=logits, expert_load=load), grads


def _run_program(cfg):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    extras = {}
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, load = causal_lm.build_train(cfg, T, extras=extras)
    block = main.global_block()
    params = block.all_parameters()
    trained = [p for p in params if p.name + "@GRAD" in block.vars]
    names = ("main_loss", "mtp_loss", "mtp_logits", "mtp_input")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # every norm's weight off the 1 it starts from: at 1 a weight left
        # unread shows nowhere, and the module's N_h(N_f(s)) is the identity
        draw = np.random.RandomState(3)
        for p in params:
            if len(p.shape) == 1 and "norm" in p.name:
                scope.set(p.name, jnp.asarray(
                    draw.normal(1.0, 0.1, p.shape), jnp.float32))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        out = exe.run(main, feed=_feed(),
                      fetch_list=[loss, logits, load]
                      + [extras[n] for n in names]
                      + [p.name + "@GRAD" for p in trained])
    got = dict(zip(("loss", "logits", "expert_load") + names, out[:7]),
               grads=dict(zip((p.name for p in trained), out[7:])),
               block=block)
    want, grads = _reference(cfg, weights, _feed())
    want["grads"] = {p.name: g for p, g in zip(params, grads)}
    return params, weights, got, want


CASES = {"whole": {}, "share": HELD}
_RUNS = {}


def _case(name):
    if name not in _RUNS:
        _RUNS[name] = _run_program(dict(CFG, **CASES[name]))
    return _RUNS[name]


@pytest.fixture(params=sorted(CASES))
def run(request):
    return (request.param,) + _case(request.param)


@pytest.mark.parametrize("what", ["loss", "main_loss", "mtp_loss", "logits",
                                  "mtp_logits", "mtp_input"])
def test_program_agrees_with_the_reference(run, what):
    _, _, _, got, want = run
    assert _error(got[what], want[what]) < (
        1e-5 if what.endswith("loss") else TOLERANCE)
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])
    # an expert layer and the module's layer, top 2
    assert int(got["expert_load"].sum()) == 2 * 2 * B * T
    total = float(np.ravel(want["main_loss"])[0]) \
        + 0.3 * float(np.ravel(want["mtp_loss"])[0])
    assert abs(float(np.ravel(got["loss"])[0]) - total) < 1e-5


@pytest.mark.parametrize("name", SHARED + ("every other",))
def test_gradients_agree_with_the_reference(run, name):
    """The embedding's gradient is two lookups' scatter-adds summed, the
    head's two matmuls', outside any loop op; the last trunk layer's `wo`
    is reached by both losses."""
    _, params, _, got, want = run
    trained = {p.name for p in params if p.trainable}
    assert set(got["grads"]) == trained
    assert not any(n.endswith("expert_bias") for n in trained)
    names = [name] if name in SHARED else sorted(trained - set(SHARED))
    worst = max((_error(got["grads"][n], want["grads"][n]), n)
                for n in names)
    assert worst[0] < TOLERANCE, worst


def test_each_use_alone_is_not_the_gradient():
    """The embedding's and the head's gradients need both uses: the
    reference with the module's loss weighed 0 (the trunk's use alone)
    gives another gradient than the program's."""
    params, weights, got, _ = _case("whole")
    _, grads = _reference(dict(CFG, mtp_loss_weight=0.0), weights, _feed())
    alone = {p.name: g for p, g in zip(params, grads)}
    for name in ("embedding", "head"):
        assert _error(got["grads"][name], alone[name]) > 0.05, name


def test_the_module_shares_two_parameters_with_the_trunk():
    params, _, got, _ = _case("whole")
    names = [p.name for p in params]
    assert names.count("embedding") == names.count("head") == 1
    module = names.index("final_norm")
    assert names[module:module + 4] == [
        "final_norm", "layer_2.enorm", "layer_2.hnorm", "layer_2.eh_proj"]
    assert names[-2:] == ["layer_2.shared_head.norm", "head"]
    assert [n for n in names if n.startswith("layer_2.")][3:11] == [
        "layer_2." + n for n in ("input_norm", "wq_a", "q_a_norm", "wq_b",
                                 "wkv_a", "kv_a_norm", "wkv_b", "wo")]
    shapes = {p.name: tuple(p.shape) for p in params}
    assert shapes["layer_2.eh_proj"] == (128, 64)
    assert shapes["layer_2.wkv_b"] == (16, 4 * (48 + 64))
    assert shapes["layer_2.experts.expert_bias"] == (8,)
    ops = got["block"].ops
    uses = {name: [op.type for op in ops if op.type != "grad_of"
                   and name in [n for ns in op.inputs.values() for n in ns]]
            for name in ("embedding", "head")}
    assert uses["embedding"][:2] == ["lookup_table", "lookup_table"]
    assert uses["head"][:2] == ["mul", "mul"]
    feeds = {v for v in ("ids", "pos", "labels", "labels_next")
             if v in got["block"].vars}
    assert len(feeds) == 4


def test_the_modules_ops_carry_its_role():
    """Every op from the module's lookup to its loss, and each of their
    grad ops, lowers under "op:<type>/mtp.0.<instance>"; the trunk's ops
    and the final norm do not."""
    _, _, got, _ = _case("whole")
    ops = got["block"].ops
    forward = [op for op in ops if op.type != "grad_of"]
    role = [op.attrs.get(lowering.ROLE_ATTR) for op in forward]
    first = role.index("mtp.0")
    assert forward[first].type == "reshape"         # labels -> [B, T]
    assert forward[first + 1].type == "lookup_table"
    assert all(r is None for r in role[:first])
    assert forward[first - 1].type == "rms_norm"    # the trunk's final norm
    marked = [op for op in forward if op.attrs.get(lowering.ROLE_ATTR)]
    types = [op.type for op in marked]
    assert types.count("fused_attention") == 1
    assert types.count("moe_ffn") == 1
    assert types.count("softmax_with_cross_entropy") == 1
    assert types.count("lookup_table") == 1
    for op in marked:
        kind, instance = lowering.parse_op_scope(
            "jit(fn)/" + lowering.op_scope(op) + "/dot_general")
        assert instance.startswith("mtp.0."), lowering.op_scope(op)
    grads = [op for op in ops if op.type == "grad_of"
             and op.attrs["fwd_attrs"].get(lowering.ROLE_ATTR)]
    assert len(grads) >= len(marked) - 4            # all but the loss's tail
    assert all("/mtp.0." in lowering.op_scope(op) for op in grads)
    trunk = next(op for op in forward if op.type == "fused_attention")
    assert "mtp.0" not in lowering.op_scope(trunk)


# --- the counters -----------------------------------------------------------

def test_the_module_is_counted():
    layers = REGISTRY.counter("ptpu_causal_lm_layers_total", "")
    modules = REGISTRY.counter("ptpu_causal_lm_mtp_modules_total", "")
    attention = REGISTRY.counter("ptpu_attention_layers_total", "")
    built = dict(mixer="attention", rotary_dim="16", gate="false", conv="0",
                 shared="32", sandwich="false", reads="own",
                 differential="false")
    keys = [dict(built, ffn="experts", module="mtp"),
            dict(built, ffn="experts", module="trunk"),
            dict(built, ffn="dense", shared="0", module="trunk")]
    module = dict(depth="1", shared_embedding="true", shared_head="true",
                  loss_weight="0.3")
    core = dict(kind="full", window="0", q_heads="4", kv_heads="4",
                path="dense", head_dim="48", heads_a_block="none",
                form="latent", v_dim="64", rope_dim="16",
                rope_key_group="4", core="dense")
    before = [layers.value(**k) for k in keys] + [
        modules.value(**module), attention.value(**core)]
    _run_program(dict(CFG))
    after = [layers.value(**k) for k in keys] + [
        modules.value(**module), attention.value(**core)]
    assert [b - a for a, b in zip(before, after)] == [1, 1, 1, 1, 3]
    assert "may run more than once" in REGISTRY.counter(
        "ptpu_causal_lm_heads_total", "").help


# --- the shares add up ------------------------------------------------------

@pytest.mark.parametrize("layer", ["layer_1", "layer_2"],
                         ids=["trunk", "module"])
def test_the_chips_shares_add_up_to_the_uncut_layer(layer):
    """Four chips hold 2 of 8 experts each: their routed parts (the
    program's routed_ffn, given a share) plus the shared expert, which
    every chip computes alike, counted once equal the uncut reference's
    layer, on a trunk layer's weights and on the module's layer's."""
    params, weights, _, _ = _case("whole")
    c = causal_lm.resolve(CFG)
    w = {p.name: jnp.asarray(v) for p, v in zip(params, weights)}
    router, bias, wg, wu, wd = (w["%s.experts.%s" % (layer, n)] for n in (
        "router", "expert_bias", "w_gate", "w_up", "w_down"))
    shared = [w["%s.shared_expert.%s" % (layer, n)]
              for n in ("w_gate", "w_up", "w_down")]
    x = jnp.asarray(np.random.RandomState(3).randn(B * T, 64), jnp.float32)
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
                scoring="sigmoid", expert_bias=bias, scale=1.8,
                norm_eps=1e-20)
            np.testing.assert_array_equal(chip_load, load)
            parts = parts + out
    assert _error(parts, whole) < 1e-5


# --- the attention core where key part and value differ ----------------------

def _core_inputs(dn, dr, dv, h=4, t=64, seed=1):
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(rng.randn(2, t, h, dn), jnp.float32)
            for _ in range(2))
    v, g = (jnp.asarray(rng.randn(2, t, h, dv), jnp.float32)
            for _ in range(2))
    return q, k, v, jnp.asarray(rng.randn(2, t, h, dr), jnp.float32), \
        jnp.asarray(rng.randn(2, t, 1, dr), jnp.float32), g


def _dense(q, k, v, qr, kr, g):
    qq = jnp.concatenate([q, qr], -1)
    kk = jnp.concatenate([k, jnp.broadcast_to(kr, qr.shape)], -1)
    return (attention_reference(qq, kk, v, causal=True,
                                scale=qq.shape[-1] ** -0.5) * g).sum()


_CORES = {}


WIDTHS = {"whole": (48, 16, 64, 4), "dense": (48, 16, 64, 4),
          "whole_published": (192, 64, 256, 2)}


def _core(path):
    """(out and the five gradients) of the core by `path` and by the dense
    float32 formula, at 48 + 16 on 64 or, `whole_published`, at 192 + 64 on
    256."""
    if path not in _CORES:
        q, k, v, qr, kr, g = _core_inputs(*WIDTHS[path])

        def flash(q, k, v, qr, kr):
            return pallas_kernels.flash_attention(
                q, k, v, causal=True, q_rope=qr, k_rope=kr, block_q=32,
                block_k=32, interpret=True)

        def op(q, k, v, qr, kr):        # the op's rule, under the crossover
            from paddle_tpu.core import registry

            class Ctx(object):
                mesh, amp = None, False
            return registry.get("fused_attention").lower(
                Ctx(), {"Q": [q], "K": [k], "V": [v], "QRope": [qr],
                        "KRope": [kr]}, {"causal": True})["Out"][0]

        fn = op if path == "dense" else flash
        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(
                lambda *a: (fn(*a) * g).sum(), argnums=(0, 1, 2, 3, 4))(
                    q, k, v, qr, kr)
            want = jax.value_and_grad(
                lambda *a: _dense(*a, g), argnums=(0, 1, 2, 3, 4))(
                    q, k, v, qr, kr)
        _CORES[path] = ((got[0],) + got[1], (want[0],) + want[1],
                        fn(q, k, v, qr, kr).shape)
    return _CORES[path]


@pytest.mark.parametrize("path", ["whole", "whole_published", "dense"])
@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv", "dq_rope",
                                  "dk_rope"])
def test_the_core_where_key_part_and_value_differ(path, what):
    """A part without position of 48 beside a value of 64, one rotary key
    for the four heads: the whole head joined (the plain kernels at 64 on
    64, and at the published 192 + 64 on 256) in the interpreter, and the
    dense path; dk_rope is summed over the heads."""
    got, want, shape = _core(path)
    i = ["forward", "dq", "dk", "dv", "dq_rope", "dk_rope"].index(what)
    dn, dr, dv, h = WIDTHS[path]
    assert shape == (2, 64, h, dv)
    assert got[i].shape == want[i].shape
    assert _error(got[i], want[i]) < 2e-5


def test_flash_attention_says_which_widths_it_takes():
    q, k, v, qr, kr, _ = _core_inputs(48, 16, 32)
    with pytest.raises(ValueError, match=r"192 \+ 64 on 256.*48 \+ 16 on 32"):
        pallas_kernels.flash_attention(q, k, v, causal=True, q_rope=qr,
                                       k_rope=kr, interpret=True)
    with pytest.raises(ValueError, match="192 \\+ 64 on 256"):
        pallas_kernels.flash_attention(q, k, v, causal=True, interpret=True)
    assert pallas_kernels.latent_form(128, 64, 128) == "two_part"
    assert pallas_kernels.latent_form(192, 64, 256) == "whole"
    with pytest.raises(ValueError, match=r"got 192 \+ 64 on 128"):
        pallas_kernels.latent_form(192, 64, 128)
    assert pallas_kernels.heads_a_block(20, 20, 192) is None
    assert pallas_kernels.heads_a_block(20, 20, 256) == 1
    assert "192 beside a value of 256" in " ".join(
        pallas_kernels.heads_a_block.__doc__.split())


# --- what resolve refuses, and what a broken module reads --------------------

@pytest.mark.parametrize("edit,match", [
    (dict(num_nextn_predict_layers=2), "num_nextn_predict_layers 0 or 1"),
    (dict(hc_mult=4), "hc_mult=1"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings=False"),
    (dict(layer_types=["full_attention"] * 2), "layer_types=None"),
    (dict(rope_layout=[1, 1]), "rope_layout=None"),
    (dict(sliding_window_layout=[0, 0], sliding_window_size=8),
     "sliding_window_layout=None"),
    (dict(full_attention_interval=2), "full_attention_interval=1"),
    (dict(topk_method="group_limited_greedy"), "topk_method"),
    (dict(n_group=3), "n_group"),
    (dict(v_head_dim=None), "lacks"),
])
def test_resolve_refuses_by_name_what_is_not_built(edit, match):
    with pytest.raises(NotImplementedError, match=match):
        causal_lm.resolve(dict(CFG, **edit))


def test_resolve_reads_the_published_keys():
    c = causal_lm.resolve(dict(CFG, **HELD))
    assert (c["mtp_layers"], c["num_hidden_layers"]) == (1, 2)
    assert c["mixer_layers"] == ["attention"] * 3
    assert c["ffn_layers"] == ["dense", "experts", "experts"]
    assert c["rope_layers"] == [True] * 3 and c["window_layers"] == [None] * 3
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (8, 2, 2)
    assert c["latent"] and c["rotary_dim"] == 16 and c["head_dim"] == 64
    assert c["attention_scale"] is None and c["mtp_loss_weight"] == 0.3
    assert c["use_expert_bias"] and not c["shared_expert_gate"]
    # without the module the patterns are the trunk's alone
    c = causal_lm.resolve(dict(CFG, num_nextn_predict_layers=0))
    assert c["mtp_layers"] == 0 and len(c["ffn_layers"]) == 2


@pytest.mark.parametrize("broken,sees", [
    ("embeds_t_i", "mtp_input"), ("second_labels_off_by_one", "mtp_loss"),
    ("concat_swapped", "mtp_input"), ("lambda_1", "loss")])
def test_a_broken_module_is_told_from_the_healthy_one(broken, sees):
    """The reference broken on purpose, against the healthy program: the
    module's lookup on t_i, its targets t_(i+1), [state; embedding] under
    the same W_eh, lambda 1."""
    params, weights, got, want = _case("whole")
    feed = {k: jnp.asarray(v) for k, v in _feed().items()}
    cfg, weights, swaps = dict(CFG), list(weights), {}
    if broken == "second_labels_off_by_one":
        swaps["labels_next"] = feed["labels"]
    elif broken == "lambda_1":
        cfg["mtp_loss_weight"] = 1.0
    elif broken == "concat_swapped":
        i = [p.name for p in params].index("layer_2.eh_proj")
        weights[i] = np.concatenate([weights[i][64:], weights[i][:64]])
    if broken == "embeds_t_i":
        found = {}
        reference.passes(cfg, weights, feed["ids"], feed["pos"],
                         next_ids=feed["ids"], found=found)
        wrong = found
    else:
        wrong, _ = _reference(cfg, weights, _feed(), **swaps)
    assert _error(got[sees], want[sees]) < TOLERANCE
    # uniform tokens: either target costs about ln 64 at initialisation
    assert _error(got[sees], wrong[sees]) > 20 * TOLERANCE
