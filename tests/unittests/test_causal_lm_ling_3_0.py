"""models/causal_lm.py at Ling-3.0-flash's shape (`model_type:
bailing_hybrid`; tiny widths, seeded weights): KDA mixers (a delta rule
whose decay is a key channel's) on five layers in six and latent attention
without a query rank and with a gate a head on the sixth, a dense layer
before the expert layers, a sigmoid router that chooses groups before
experts. The Program against models/causal_lm_reference.py for the loss,
the logits, every layer's state and every parameter's gradient, whole and
as one chip's share; the group limit against a written-out choice on ties
and on a token whose best experts lie in a dropped group; the shares of an
expert layer add up to the uncut layer; what `resolve()` refuses and reads;
the counters; a broken mixer is told from the healthy one."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import lowering
from paddle_tpu.models import causal_lm
from paddle_tpu.models import causal_lm_reference as reference
from paddle_tpu.observability.registry import REGISTRY
from paddle_tpu.parallel import moe

# the published keys at toy widths: published layers 1-7 of 42 (one leading
# dense layer, then a whole period of six: the latent layer is index 5)
CFG = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=7,
    layer_indices=[1, 2, 3, 4, 5, 6, 7], num_attention_heads=2,
    num_key_value_heads=2, head_dim=16, intermediate_size=48,
    moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
    num_shared_experts=1, num_experts=16, num_experts_per_tok=4, n_group=4,
    topk_group=2, first_k_dense_replace=1, norm_topk_prob=True,
    routed_scaling_factor=2.5, score_function="sigmoid",
    scoring_func="sigmoid", topk_method="noaux_tc",
    moe_router_enable_expert_bias=True, router_renorm_epsilon=1e-20,
    router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
    expert_bias_initializer_range=0.1, layer_group_size=6,
    kda_lower_bound=-5, kda_safe_gate=True, no_kda_lora=True,
    use_kda_lora=False, mtp_use_kda=False, short_conv_kernel_size=4,
    linear_silu=True, use_qk_norm=True, num_kv_heads_for_linear_attn=0,
    q_lora_rank=None, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, qk_head_dim=24, rotary_dim=8,
    partial_rotary_factor=0.5, rope_theta=6000000, rope_scaling=None,
    rope_interleave=True,
    gated_attention_proj_granularity_type="head_wise", rms_norm_eps=1e-6,
    hidden_act="silu", tie_word_embeddings=False, use_bias=False,
    use_qkv_bias=False, group_norm_size=1, use_nGPT=False,
    up_proj_norm=False, value_norm=False, use_mla_nope=False,
    scale_router_input=False, seq_aux=True, mtp_loss_scaling_factor=0,
    num_nextn_predict_layers=0, expert_swiglu_limit_list=[0] * 35 + [4] * 7,
    share_expert_swiglu_limit_list=[0] * 34 + [5] * 6 + [7] * 2,
    initializer_range=0.2, model_type="bailing_hybrid",
    share=dict(chips=1, chip=0, published=dict(num_hidden_layers=42)))
HELD = dict(num_experts=4, share=dict(
    chips=4, chip=1, published=dict(num_hidden_layers=42, num_experts=16)))
B, T = 2, 32
TOLERANCE = 1e-3        # float32 against float32: another order of sums (a
# head's a_log gradient is one sum over every token and channel: 4e-4)
KDA_ROLES = ("wq", "conv_q", "wk", "conv_k", "wv", "conv_v", "wf", "dt_bias",
             "a_log", "wbeta", "o_norm", "wg", "wo")
LATENT_ROLES = ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wg", "wo")


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


def _reference(cfg, weights, feed):
    feed = {k: jnp.asarray(v) for k, v in feed.items()}

    def loss(p, found=None):
        return reference.loss_fn(cfg, p, feed["ids"], feed["pos"],
                                 feed["labels"], found=found)
    weights = [jnp.asarray(w, jnp.float32) for w in weights]
    (total, (logits, load)), grads = jax.value_and_grad(
        loss, has_aux=True)(weights)
    found = {}
    loss(weights, found)
    return dict(found, loss=total, logits=logits, expert_load=load), grads


def _layer_states(block, layers):
    """The residual stream after each layer: what the next layer's input
    norm reads, and the final norm after the last."""
    read = {op.input("Scale")[0]: op.input("X")[0] for op in block.ops
            if op.type == "rms_norm"}
    return [read["layer_%d.input_norm" % i] for i in range(1, layers)] \
        + [read["final_norm"]]


def _run_program(cfg, break_mixer=None):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, logits, load = causal_lm.build_train(cfg, T)
    block = main.global_block()
    params = block.all_parameters()
    trained = [p for p in params if p.name + "@GRAD" in block.vars]
    states = _layer_states(block, cfg["num_hidden_layers"])
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        # every norm's weight off the 1 it starts from: at 1 a weight left
        # unread shows nowhere
        draw = np.random.RandomState(3)
        for p in params:
            if len(p.shape) == 1 and "norm" in p.name:
                scope.set(p.name, jnp.asarray(
                    draw.normal(1.0, 0.1, p.shape), jnp.float32))
        weights = [np.asarray(scope.get(p.name)) for p in params]
        out = exe.run(main, feed=_feed(),
                      fetch_list=[loss, logits, load] + states
                      + [p.name + "@GRAD" for p in trained])
    n = len(states)
    got = dict(zip(("loss", "logits", "expert_load"), out[:3]),
               states=out[3:3 + n],
               grads=dict(zip((p.name for p in trained), out[3 + n:])),
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


@pytest.mark.parametrize("what", ["loss", "logits"])
def test_program_agrees_with_the_reference(run, what):
    _, _, _, got, want = run
    assert _error(got[what], want[what]) < (
        1e-5 if what == "loss" else TOLERANCE)
    np.testing.assert_array_equal(got["expert_load"], want["expert_load"])
    # six expert layers, top 4
    assert int(got["expert_load"].sum()) == 6 * 4 * B * T


@pytest.mark.parametrize("layer", range(7))
def test_every_layers_state_agrees_with_the_reference(run, layer):
    _, _, _, got, want = run
    assert len(want["states"]) == 7
    assert _error(got["states"][layer], want["states"][layer]) < TOLERANCE


@pytest.mark.parametrize("group", ["kda", "latent", "experts", "rest"])
def test_gradients_agree_with_the_reference(run, group):
    _, params, _, got, want = run
    trained = {p.name for p in params if p.trainable}
    assert set(got["grads"]) == trained
    assert not any(n.endswith("expert_bias") for n in trained)
    kda = {n for n in trained if n.split(".")[0] in (
        "layer_0", "layer_1", "layer_2", "layer_3", "layer_5", "layer_6")
        and n.split(".", 1)[1] in KDA_ROLES}
    latent = {n for n in trained if n.startswith("layer_4.")
              and n.split(".", 1)[1] in LATENT_ROLES}
    experts = {n for n in trained if ".experts." in n
               or ".shared_expert." in n}
    assert len(kda) == 6 * 13 and len(latent) == 6
    names = {"kda": kda, "latent": latent, "experts": experts,
             "rest": trained - kda - latent - experts}[group]
    worst = max((_error(got["grads"][n], want["grads"][n]), n)
                for n in sorted(names))
    assert worst[0] < TOLERANCE, worst


def test_the_parameters_names_and_order():
    params, _, got, _ = _case("whole")
    names = [p.name for p in params]
    assert names[:1] == ["embedding"] and names[-2:] == ["final_norm", "head"]
    first = [n for n in names if n.startswith("layer_0.")]
    assert first == ["layer_0." + r for r in (
        ("input_norm",) + KDA_ROLES + ("post_attention_norm", "w_gate",
                                       "w_up", "w_down"))]
    fifth = [n for n in names if n.startswith("layer_4.")]
    assert fifth == ["layer_4." + r for r in (
        ("input_norm",) + LATENT_ROLES + (
            "post_attention_norm", "experts.router", "experts.expert_bias",
            "experts.w_gate", "experts.w_up", "experts.w_down",
            "shared_expert.w_gate", "shared_expert.w_up",
            "shared_expert.w_down"))]
    shapes = {p.name: tuple(p.shape) for p in params}
    assert shapes["layer_0.wf"] == (32, 32)          # full rank, a channel
    assert shapes["layer_0.dt_bias"] == (32,) and shapes["layer_0.a_log"] \
        == (2,)
    assert shapes["layer_0.wbeta"] == (32, 2) and shapes["layer_0.o_norm"] \
        == (16,)
    assert shapes["layer_0.conv_q"] == (32, 4)
    assert shapes["layer_4.wq"] == (32, 2 * 24)      # no query rank
    assert shapes["layer_4.wkv_a"] == (32, 24 + 8)
    assert shapes["layer_4.wg"] == (32, 2)           # a gate a head
    assert shapes["layer_1.experts.router"] == (32, 16)
    types = [op.type for op in got["block"].ops]
    assert types.count("kda_delta_rule") == 6
    assert types.count("fused_attention") == 1
    assert types.count("causal_conv1d") == 18
    moe_ops = [op for op in got["block"].ops if op.type == "moe_ffn"]
    assert len(moe_ops) == 6
    assert all(op.attrs["n_group"] == 4 and op.attrs["topk_group"] == 2
               for op in moe_ops)


def test_the_decay_starts_inside_its_bound_and_spread():
    """The start `assumed` in the configuration: a token's log decay a
    channel lies in (-5, 0) and, over channels, on both sides of the
    middle."""
    _, _, _, want = _case("whole")
    g = np.asarray(want["kda_g"])
    assert g.shape == (B, T, 2, 16)
    assert -5 <= g.min() < -3.5 and -0.5 < g.max() < 0
    assert np.asarray(want["kda_state"]).shape == (B, 2, 16, 16)


# --- what resolve() refuses, and what it reads ------------------------------

@pytest.mark.parametrize("edit,match", [
    (dict(use_kda_lora=True), "use_kda_lora"),
    (dict(no_kda_lora=False), "no_kda_lora"),
    (dict(kda_safe_gate=False), "kda_safe_gate"),
    (dict(mtp_use_kda=True), "mtp_use_kda"),
    (dict(expert_swiglu_limit_list=[0, 0, 4] + [0] * 39),
     "expert_swiglu_limit_list"),
    (dict(share_expert_swiglu_limit_list=[0] * 7 + [5] + [0] * 34),
     "share_expert_swiglu_limit_list"),
    (dict(group_norm_size=4), "group_norm_size"),
    (dict(use_nGPT=True), "use_nGPT"),
    (dict(up_proj_norm=True), "up_proj_norm"),
    (dict(value_norm=True), "value_norm"),
    (dict(use_mla_nope=True), "use_mla_nope"),
    (dict(scale_router_input=True), "scale_router_input"),
    (dict(linear_silu=False), "linear_silu"),
    (dict(use_qk_norm=False), "use_qk_norm"),
    (dict(num_kv_heads_for_linear_attn=1), "num_kv_heads_for_linear_attn"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(kda_lower_bound=-8), "kda_lower_bound"),
    (dict(gated_attention_proj_granularity_type="element_wise"),
     "gated_attention_proj_granularity_type"),
    (dict(kv_lora_rank=None), "lacks"),
    (dict(n_group=3), "n_group"),
    (dict(topk_group=5), "topk_group"),
    (dict(n_group=16, topk_group=8), "n_group"),
    (dict(topk_method="greedy", moe_router_enable_expert_bias=False),
     "group limit"),
    (dict(total_ut_steps=2), "total_ut_steps"),
    (dict(hc_mult=2), "hc_mult"),
])
def test_resolve_refuses_by_name_what_is_not_built(edit, match):
    with pytest.raises(NotImplementedError, match=match):
        causal_lm.resolve(dict(CFG, **edit))


@pytest.mark.parametrize("edit,match", [
    (dict(layer_indices=[1, 2, 3]), "layer_indices"),
    (dict(layer_indices=[1, 2, 3, 4, 5, 6, 42]), "layer_indices"),
    (dict(qk_head_dim=32), "qk_head_dim")])
def test_resolve_holds_the_keys_to_each_other(edit, match):
    with pytest.raises(ValueError, match=match):
        causal_lm.resolve(dict(CFG, **edit))


def test_resolve_reads_the_published_keys():
    c = causal_lm.resolve(dict(CFG, **HELD))
    assert c["mixer_layers"] == ["kda"] * 4 + ["attention"] + ["kda"] * 2
    assert c["ffn_layers"] == ["dense"] + ["experts"] * 6
    assert c["latent"] and c["q_lora_rank"] is None
    assert c["attention_gate"] == "per_head" and c["rope_interleaved"]
    assert (c["num_experts"], c["experts_held"], c["first_expert"]) \
        == (16, 4, 4)
    assert c["group_limited"] and (c["n_group"], c["topk_group"]) == (4, 2)
    assert c["use_expert_bias"] and c["router_scoring"] == "sigmoid"
    assert c["shared_expert_intermediate_size"] == 16
    assert not c["shared_expert_gate"] and c["rotary_dim"] == 8
    # the whole model's pattern: 35 KDA layers to 7 latent ones
    # (its last seven layers clamp their experts' SwiGLU, which is refused:
    # the pattern is read with the clamps taken off)
    whole = causal_lm.resolve(dict(
        CFG, num_hidden_layers=42, layer_indices=list(range(42)),
        first_k_dense_replace=2, expert_swiglu_limit_list=[0] * 42,
        share_expert_swiglu_limit_list=[0] * 42))
    assert whole["mixer_layers"].count("kda") == 35
    assert [i for i, m in enumerate(whole["mixer_layers"])
            if m == "attention"] == [5, 11, 17, 23, 29, 35, 41]
    assert whole["ffn_layers"][:3] == ["dense", "dense", "experts"]
    # no limit where every group is kept
    assert not causal_lm.resolve(dict(CFG, topk_group=4))["group_limited"]


# --- the group limit ----------------------------------------------------------

def _written_out(scores, bias, n_group, topk_group, k):
    """The choice, one token at a time in float64 Python: a group's score
    the sum of its two largest s + b, the best groups by a stable sort (the
    lower index where two are level), every score outside them out, the top
    k of what is left by a stable sort."""
    chosen = []
    for s in np.asarray(scores, np.float64) + np.asarray(bias, np.float64):
        groups = s.reshape(n_group, -1)
        by_group = [np.sort(g)[-2:].sum() for g in groups]
        kept = np.argsort(-np.asarray(by_group), kind="stable")[:topk_group]
        left = np.full(s.shape, -np.inf)
        for g in kept:
            lo = g * groups.shape[1]
            left[lo:lo + groups.shape[1]] = groups[g]
        chosen.append(sorted(np.argsort(-left, kind="stable")[:k]))
    return chosen


def _logits_of(scores):
    scores = np.asarray(scores, np.float64)
    return jnp.asarray(np.log(scores) - np.log1p(-scores), jnp.float32)


@pytest.mark.parametrize("case", ["random", "ties", "dropped_group"])
def test_the_group_limit_against_a_written_out_choice(case):
    n, e, n_group, topk_group, k = 48, 16, 4, 2, 4
    rng = np.random.RandomState(5)
    scores = rng.uniform(0.05, 0.95, (n, e))
    bias = 0.1 * rng.randn(e)
    if case == "ties":
        # scores of eighths (exact in float32, as their sigmoid's logit is
        # not: the scores are handed in as they are), no bias: level groups
        # and level experts in every token
        scores = rng.randint(1, 8, (n, e)) / 8.0
        bias = np.zeros(e)
    if case == "dropped_group":
        # the single best expert of every token lies in group 3, whose
        # second best is its worst: the group is dropped, and the best
        # expert with it
        scores = rng.uniform(0.5, 0.6, (n, e))
        scores[:, 12:] = 0.01
        scores[:, 13] = 0.95
        bias = np.zeros(e)
    want = _written_out(scores, bias, n_group, topk_group, k)
    probs = jnp.asarray(scores, jnp.float32)
    limited = moe._group_limited(probs + jnp.asarray(bias, jnp.float32),
                                 (n_group, topk_group))
    got = np.sort(np.asarray(jax.lax.top_k(limited, k)[1]), -1)
    assert [list(row) for row in got] == [list(row) for row in want]
    plain = reference.group_limited(
        probs + jnp.asarray(bias, jnp.float32), n_group, topk_group)
    np.testing.assert_array_equal(np.asarray(limited), np.asarray(plain))
    if case == "dropped_group":
        assert not (got == 13).any()
        free = np.asarray(jax.lax.top_k(probs, k)[1])
        assert (free == 13).any(-1).all()
    if case != "ties":
        # through _route, from logits: the weights are the chosen scores
        # without the bias, renormalised and scaled
        _, _, gate, expert = moe._route(
            _logits_of(scores), k, True, "sigmoid",
            jnp.asarray(bias, jnp.float32), 2.5, norm_eps=1e-20,
            groups=(n_group, topk_group))
        order = np.argsort(np.asarray(expert), -1)
        assert [list(r) for r in np.take_along_axis(
            np.asarray(expert), order, -1)] == [list(r) for r in want]
        weights = np.take_along_axis(scores, np.asarray(want), -1)
        weights = 2.5 * weights / weights.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(gate), order, -1), weights,
            rtol=2e-5)


# --- the share ----------------------------------------------------------------

@pytest.mark.parametrize("layer", ["layer_1", "layer_4"])
def test_the_chips_shares_add_up_to_the_uncut_layer(layer):
    """Four chips hold 4 of 16 experts each, a whole group of the four: a
    chip's routed part (the program's routed_ffn, given a share) is zeros
    for every token whose two kept groups exclude its own. The four parts
    plus the shared expert, which every chip computes alike, counted once
    equal the uncut reference's layer."""
    params, weights, _, _ = _case("whole")
    c = causal_lm.resolve(CFG)
    w = {p.name: jnp.asarray(v) for p, v in zip(params, weights)}
    router, bias, wg, wu, wd = (w["%s.experts.%s" % (layer, n)] for n in (
        "router", "expert_bias", "w_gate", "w_up", "w_down"))
    shared = [w["%s.shared_expert.%s" % (layer, n)]
              for n in ("w_gate", "w_up", "w_down")]
    x = jnp.asarray(np.random.RandomState(3).randn(B * T, 32), jnp.float32)
    silent = 0
    with jax.default_matmul_precision("highest"):
        whole, _, _, load = reference.routed_experts(
            x, router, wg, wu, wd, c, expert_bias=bias)
        whole = whole + reference.shared_expert(x, *shared)
        parts = reference.shared_expert(x, *shared)
        for chip in range(4):
            held = slice(4 * chip, 4 * chip + 4)
            out, _, _, chip_load = moe.routed_ffn(
                x, router, wg[held], wu[held], wd[held], top_k=4,
                norm_topk_prob=True, first_expert=4 * chip,
                scoring="sigmoid", expert_bias=bias, scale=2.5,
                norm_eps=1e-20, groups=(4, 2))
            np.testing.assert_array_equal(chip_load, load)
            silent += int((np.abs(np.asarray(out)).max(-1) == 0).sum())
            parts = parts + out
    assert _error(parts, whole) < 1e-5
    # two of four groups kept: a token is silent on two chips at least
    assert silent >= 2 * B * T
    # and without the limit the layer is another one
    free, _, _, _ = reference.routed_experts(
        x, router, wg, wu, wd, dict(c, group_limited=False),
        expert_bias=bias)
    assert _error(free + reference.shared_expert(x, *shared), whole) > 0.05


# --- the counters -------------------------------------------------------------

def test_the_layers_are_counted():
    layers = REGISTRY.counter("ptpu_causal_lm_layers_total", "")
    linear = REGISTRY.counter("ptpu_linear_attention_layers_total", "")
    moes = REGISTRY.counter("ptpu_moe_layers_total", "")

    def kda(ffn, **more):
        return dict(mixer="kda", module="trunk", reads="own",
                    differential="false", rotary_dim="0", gate="false",
                    conv="4", ffn=ffn, shared="16" if ffn == "experts"
                    else "0", sandwich="false", **more)

    grouped = dict(groups="4", kept_groups="2")
    samples = (
        kda("dense"), kda("experts", **grouped),
        dict(mixer="attention", module="trunk", reads="own",
             differential="false", rotary_dim="8", gate="per_head",
             conv="0", ffn="experts", shared="16", sandwich="false",
             **grouped))
    before = [layers.value(**s) for s in samples]
    op_before = linear.value(kind="kda", k_heads="2", v_heads="2", d_k="16",
                             d_v="16", chunk="64", sub_block="16",
                             path="scan")
    _run_program(CFG)
    assert [layers.value(**s) - b for s, b in zip(samples, before)] \
        == [1, 5, 1]
    assert linear.value(kind="kda", k_heads="2", v_heads="2", d_k="16",
                        d_v="16", chunk="64", sub_block="16",
                        path="scan") == op_before + 6
    assert any(dict(labels).get("groups") == "4"
               and dict(labels).get("kept_groups") == "2"
               for labels, _ in moes.samples())


def test_the_new_op_and_its_grad_lower_under_their_own_scopes():
    _, _, got, _ = _case("whole")
    ops = got["block"].ops
    op = next(op for op in ops if op.type == "kda_delta_rule")
    assert lowering.parse_op_scope(
        "jit(fn)/" + lowering.op_scope(op) + "/dot_general")[0] \
        == "kda_delta_rule"
    grad = next(op for op in ops if op.type == "grad_of"
                and op.attrs["fwd_type"] == "kda_delta_rule")
    assert lowering.parse_op_scope(
        "jit(fn)/" + lowering.op_scope(grad) + "/dot_general")[0] \
        == "kda_delta_rule_grad"


# --- a broken mixer is told from the healthy one --------------------------------

@pytest.mark.parametrize("broken", ["scalar_decay", "beta_off", "no_limit"])
def test_a_broken_layer_is_told_from_the_healthy_one(broken, monkeypatch):
    """The comparison above is no tautology: a head's mean decay on every
    channel (Qwen3-Next's rule under Ling's name), beta = 1, or a router
    without its group limit each leave the tolerance by far."""
    from paddle_tpu.ops import kda_kernels
    real_rule, real_route = kda_kernels.kda_delta_rule, moe._route
    if broken == "scalar_decay":
        monkeypatch.setattr(
            kda_kernels, "kda_delta_rule",
            lambda q, k, v, g, beta, **kw: real_rule(
                q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True),
                                          g.shape), beta, **kw))
    elif broken == "beta_off":
        monkeypatch.setattr(
            kda_kernels, "kda_delta_rule",
            lambda q, k, v, g, beta, **kw: real_rule(
                q, k, v, g, jnp.ones_like(beta), **kw))
    else:
        monkeypatch.setattr(
            moe, "_route", lambda *a, groups=None, **kw: real_route(*a, **kw))
    _, _, got, want = _run_program(CFG)
    assert _error(got["logits"], want["logits"]) > 20 * TOLERANCE
