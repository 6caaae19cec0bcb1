"""Laguna-S-2.1 (paddle_tpu/models/causal_lm.py, `model_type: laguna`) as
the benchmark trains it: one of 32 chips' share of published layers 0-4 (a
leading dense layer, then four expert layers: sliding, sliding, sliding,
full), attention whose geometry is a LAYER's (12 query heads on 2, half a
head turned under YaRN, on the full layers; 18 on 2, the whole head turned
at theta 1e4, behind a window of 512 on the sliding ones; a sigmoid gate a
head), top-10 of 256 softmax-routed experts times 2.5, 8 held, beside a
gated shared expert, a quarter of the vocabulary. `make_batch` and
`samples_per_step` are configs/causal_lm.py's; this file adds the
operations a token, the counts the kernels' readers divide by (the flash
kernels' a layer, the held experts' three matmuls, the embedding gradient's
table), the benchmark's copy of the plain float32 reference, blocked so
that it fits beside the training state (attention a head at a time as a
dense masked softmax, the mask and both rotary tables written out HERE from
the configuration's own keys and not taken from the builder's lists or its
`yarn_table`; the held experts one at a time; the head in blocks of rows),
and the cell's check, which also holds four gradients of the last two
layers to the reference's jax.grad from layer 3 on.
"""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, manifest

_HERE = os.path.dirname(os.path.abspath(__file__))
base = manifest.load_module(os.path.join(_HERE, "causal_lm.py"))
shared = manifest.load_module(os.path.join(_HERE, "smallthinker.py"))

SAMPLE = base.SAMPLE
PROBE_COLUMNS = base.PROBE_COLUMNS
HEAD_ROWS = shared.HEAD_ROWS
samples_per_step = base.samples_per_step
# full sequences of uniform ids in [0, vocab_size): the configuration's
# vocab_size is chip 0's slice of the published vocabulary (words 0 ..
# 25087), so ids and labels are drawn from the slice
make_batch = base.make_batch
# every assignment a held expert computed is a row through three matrices
# of hidden_size x moe_intermediate_size (3072 x 1024), by the sizes
# `resolve` gives a share
expert_matmul_ops = base.expert_matmul_ops
_router_margin = shared._router_margin      # the held-set margin on softmax
visible_pairs = shared.visible_pairs
SLIDING = "sliding_attention"
# rows and columns of a weight's gradient that are fetched: a corner, every
# element of which sums over every token
CORNER = 128
# the gradients the cell holds: fetch -> (layer, the parameter's role, its
# place among the layer's parameters as `reference` takes them)
GRADIENTS = {"wg_4_grad": (4, "wg", "wg"), "wq_4_grad": (4, "wq", "wq"),
             "wg_3_grad": (3, "wg", "wg"),
             "expert_gate_4_grad": (4, "experts.w_gate", "w_gate")}
FIRST_GRADIENT_LAYER = 3
_ATTENTION = ("input_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wg", "wo")
_EXPERTS = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down",
            "s_weight")
_DENSE = ("w_gate", "w_up", "w_down")


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def _kinds(cfg):
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def _heads(cfg):
    return [int(n) for n in cfg["num_attention_heads_per_layer"]][
        :cfg["num_hidden_layers"]]


def _windows(cfg):
    return [cfg["sliding_window"] if kind == SLIDING else None
            for kind in _kinds(cfg)]


def build(fluid, cfg, traffic):
    """Builds the training program in the current guard, after asking the
    program for the geometry by layer: a program from before it refuses the
    configuration's keys one by one, this names the cause. Fetches: the
    loss; the logits of the first PROBE_COLUMNS words at every position;
    `expert_load`; `q_0`, `k_0`, `q_1`, `k_1`, head 0's query and key as the
    core of layer 0 (full) and of layer 1 (sliding) reads them, normed and
    turned, at every position; `gate_1`, layer 1's gate [T, 18];
    `attention_1` and `attention_4`, PROBE_COLUMNS channels of the
    attention's output behind W_o on layers 1 and 4; `routed` and `shared`,
    as many of what the held experts and the gated shared expert of the
    first expert layer add to a token, apart; `state`, of the residual
    state after layer 4; and, of the backward pass, before the clip, a
    corner of the gradients of layer 4's W_g and W_q, of layer 3's W_g and
    of the held experts' gate matrices in layer 4."""
    from paddle_tpu.models import causal_lm
    if not hasattr(causal_lm, "_geometry_by_layer"):
        raise NotImplementedError(
            "this program's causal_lm has no geometry by layer (a head "
            "count, rotary parameters and a window that are a layer's, a "
            "gate a head): it cannot build %s" % (cfg["name"],))
    fluid.default_main_program().enable_mixed_precision()
    loss, logits, load = causal_lm.build_train(
        cfg, traffic["seq_len"], learning_rate=cfg["learning_rate"],
        beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
        epsilon=cfg["adam_epsilon"], clip_norm=cfg["clip_norm"])
    block = fluid.default_main_program().global_block()
    layers = fluid.layers
    hd = cfg["head_dim"]

    def behind(name):       # what the op that reads parameter `name` gives
        return next(op for op in block.ops
                    if name in op.input_arg_names).output("Out")[0]

    def columns(name):
        var = block.var(name)
        return layers.crop(var, shape=[-1, -1, min(PROBE_COLUMNS,
                                                   int(var.shape[-1]))])

    cores = [op for op in block.ops if op.type == "fused_attention"]
    routed = next(op for op in block.ops if op.type == "moe_ffn")
    # the shared part as it is added: behind its sigmoid gate
    beside = next(op for op in block.ops if op.type == "elementwise_add"
                  and op.input("X")[0] == routed.output("Out")[0])
    # the gate a head: the sigmoid of what W_g gives
    gate = next(op for op in block.ops if op.type == "sigmoid"
                and op.input("X")[0] == behind("layer_1.wg"))
    state = next(op for op in block.ops if op.type == "rms_norm"
                 and op.input("Scale")[0] == "final_norm").input("X")[0]
    fetches = {
        "loss": loss,
        "logits": layers.crop(logits, shape=[-1, -1, min(
            PROBE_COLUMNS, cfg["vocab_size"])]),
        "expert_load": load,
        "gate_1": block.var(gate.output("Out")[0]),
        "attention_1": columns(behind("layer_1.wo")),
        "attention_4": columns(behind("layer_4.wo")),
        "routed": columns(routed.output("Out")[0]),
        "shared": columns(beside.input("Y")[0]),
        "state": columns(state)}
    for i in (0, 1):
        for name, slot in (("q_%d" % i, "Q"), ("k_%d" % i, "K")):
            fetches[name] = layers.crop(block.var(cores[i].input(slot)[0]),
                                        shape=[-1, -1, 1, hd])
    for fetch, (i, role, _) in GRADIENTS.items():
        grad = block.var("layer_%d.%s@GRAD" % (i, role))
        fetches[fetch] = layers.crop(
            grad, shape=[int(n) for n in grad.shape[:-2]]
            + [min(CORNER, int(n)) for n in grad.shape[-2:]])
    return fetches


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part, of the
    ARITHMETIC, whatever form is built: layer 0's dense SwiGLU; the
    attention's five projections (W_q, W_k, W_v, W_g, W_o) at the heads a
    layer holds and its core over the pairs the layer's mask leaves (scores
    and weighted sum, a held query head); an expert layer's router at its
    published 256 columns, its shared expert whole with its gate, and the
    held experts a token is expected to reach (10 x 8 / 256 of them, three
    matrices of 3072 x 1024 each); the head over the held words. Norms,
    rotary, the routing's sort and the optimizer are not counted. At the
    cell's five layers and T = 4096: 331.6e6, the dense FFN 34.1 %, the head
    23.2 %, attention's projections 20.9 %, its core 5.8 %, the shared
    experts 11.4 %, the held routed experts 3.6 %, the router 0.9 %."""
    c = _resolved(cfg)
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    hkv, t = c["num_key_value_heads"], traffic["seq_len"]
    heads, windows = _heads(cfg), _windows(cfg)
    expert_layers = c["ffn_layers"].count("experts")
    return {
        "dense_ffn": c["ffn_layers"].count("dense") * 3 * d
        * c["dense_intermediate_size"],
        "attention_projections": sum(d * (2 * h * hd + 2 * hkv * hd + h)
                                     for h in heads),
        "attention": sum(visible_pairs(t, w) / t * h * 2 * hd
                         for h, w in zip(heads, windows)),
        "router": expert_layers * d * c["num_experts"],
        "shared_expert": expert_layers
        * (3 * d * c["shared_expert_intermediate_size"] + d),
        "routed_experts": expert_layers * shared.held_share(c) * 3 * d * f,
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes; windowed layers count the pairs they see.
    At the cell's five layers and T = 4096: 3 x 2 x 331.6e6 = 1990e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step that the three flash kernels are given, a
    LAYER at a time: 12 query heads over all causal pairs on the full
    layers (0 and 4), 18 over the VISIBLE pairs of a window of 512 on the
    sliding ones (1-3), counting only the pairs inside the mask: 4, 8 and 6
    x 128 a pair and query head for the forward, dK/dV and dQ kernels
    (configs/smallthinker.py has why). A 512-key window under 512 x 512
    tiles visits two key blocks a query block and half of what it computes
    is masked, so a share of the peak from this says what the mask costs
    and cannot pass 100 %."""
    pairs = sum(h * visible_pairs(traffic["seq_len"], w)
                for h, w in zip(_heads(cfg), _windows(cfg))) \
        * traffic["batch"]
    hd = cfg["head_dim"]
    return {"ptpu_flash_fwd": 4 * hd * pairs,
            "ptpu_flash_bwd_dkdv": 8 * hd * pairs,
            "ptpu_flash_bwd_dq": 6 * hd * pairs}


def embedding_grad_bytes(cfg, traffic):
    """Bytes a step that the embedding's gradient has to move THROUGH HBM:
    the dense [25088, 3072] float32 table written once (308.3e6). The
    [tokens, D] float32 rows of the output's gradient are left out, as
    configs/granite_4_0_h_micro.py leaves them out: a compiled step may hand
    them to the kernel in VMEM, and a count that holds them to the HBM rate
    then reads over 100 %."""
    return 4 * cfg["hidden_size"] * cfg["vocab_size"]


def rotary_table(rope, rotary, pos):
    """(cos, sin) [T, rotary / 2] float32 of one kind of layer's
    `rope_parameters` at the positions pos [T], from the equations and in
    float64 on the host's side of the trace: f_i = theta^(-2i/R) over the R
    / 2 pairs; rope_type yarn (arXiv:2309.00071): the pair that turns n
    times over the original length L is number R ln(L / (2 pi n)) / (2 ln
    theta); pairs up to lo = floor(that at beta_fast) keep f_i, pairs from
    hi = ceil(that at beta_slow) get f_i / factor, a linear ramp between;
    cos and sin times attention_factor (0.1 ln(factor) + 1 where the set
    does not give it)."""
    theta = float(rope["rope_theta"])
    i = np.arange(rotary // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rotary)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor = float(rope["factor"])
        length = float(rope["original_max_position_embeddings"])

        def pair(turns):
            return rotary * math.log(length / (2 * math.pi * turns)) \
                / (2 * math.log(theta))

        lo = max(math.floor(pair(rope.get("beta_fast", 32))), 0)
        hi = min(math.ceil(pair(rope.get("beta_slow", 1))), rotary - 1)
        ramp = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
        f = f * (1 - ramp) + f / factor * ramp
        scale = rope.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(factor) + 1.0
    angle = pos.astype(jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def turned(x, cos, sin):
    """x [T, H, hd] with the first R = 2 x cos.shape[-1] channels of every
    head turned, half-split pairs (i, i + R / 2) inside them; the channels
    from R on pass."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from a plain float32 forward on the program's
    weights, given the same share, cut into blocks (module docstring); a
    test holds it equal to the unblocked reference of
    paddle_tpu/models/causal_lm_reference.py. The layer's kind
    (`layer_types[i]`) names its mask (key j is visible to query i iff 0 <=
    i - j, and < sliding_window on a sliding layer) and its rotary table
    (`rotary_table` on `rope_parameters[kind]`); a layer's heads are its
    W_q's columns over head_dim. `router_margin` [B, T] is the least, over
    the expert layers, of a token's held-set margin
    (configs/smallthinker.py's, on softmax scores), `experts_margin` the
    first expert layer's own. Of the backward pass: jax.grad of the stack
    from layer 3 on (layers 3 and 4, the final norm and the head's mean
    loss) with respect to four parameters, on the reference's own state
    entering layer 3 (the harness computes the reference before the
    program's first step, so the program's state is not there to start
    from)."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    params = iter(params)

    def take(names):
        return {name: jnp.asarray(next(params), jnp.float32)
                for name in names}

    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    layers, kinds = cfg["num_hidden_layers"], _kinds(cfg)
    dense = len(cfg["mlp_only_layers"])
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d = cfg["hidden_size"]
    labels = batch["labels"].reshape(b, t)

    embedding = take(["embedding"])["embedding"]
    weights = []
    for i in range(layers):
        own = take(_ATTENTION)
        own["post_norm"] = take(["post_norm"])["post_norm"]
        weights.append((own, take(_DENSE if i < dense else _EXPERTS)))
    w_f, w_lm = take(["final_norm"])["final_norm"], take(["head"])["head"]
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")

    age = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]        # i - j
    masks = {kind: (age >= 0) & (age < cfg["sliding_window"])
             if kind == SLIDING else age >= 0 for kind in set(kinds)}
    ropes = {kind: (cfg["rope_parameters"][kind], int(
        hd * cfg["rope_parameters"][kind].get("partial_rotary_factor", 1)))
        for kind in set(kinds)}

    def attention(kind, a, pos, w):
        """(the layer's output behind W_o [B, T, D], head 0's query and key
        as the core reads them [B, T, hd], the gate [B, T, H])."""
        h, hkv = w["wq"].shape[1] // hd, w["wk"].shape[1] // hd
        visible = masks[kind]

        def sequence(xs):                   # [T, D], [T]
            a, pos_row = xs
            cos, sin = rotary_table(*ropes[kind], pos_row)
            q = turned(plain.rms_norm((a @ w["wq"]).reshape(t, h, hd),
                                      w["q_norm"], eps), cos, sin)
            k = turned(plain.rms_norm((a @ w["wk"]).reshape(t, hkv, hd),
                                      w["k_norm"], eps), cos, sin)
            v = (a @ w["wv"]).reshape(t, hkv, hd)
            k_heads, v_heads = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

            @jax.checkpoint
            def one_head(args):
                qh, head = args
                kh, vh = k_heads[head // (h // hkv)], \
                    v_heads[head // (h // hkv)]
                s = jnp.where(visible, (qh @ kh.T) * hd ** -0.5, -jnp.inf)
                return jax.nn.softmax(s, -1) @ vh

            ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                         jnp.arange(h)))
            gate = jax.nn.sigmoid(a @ w["wg"])                  # [T, H]
            ctx = ctx.transpose(1, 0, 2) * gate[:, :, None]
            return ctx.reshape(t, h * hd) @ w["wo"], q[:, 0], k[:, 0], gate
        return jax.lax.map(sequence, (a, pos))

    def experts(m, w):
        """(routed, shared, load, margin) of one expert layer on m [B, T,
        D]: the held experts one at a time over the tokens whose float32
        choice names them (every token, masked); the shared expert behind
        its sigmoid gate."""
        flat = m.reshape(b * t, d)
        routed, _, _, load = plain.routed_experts(
            flat, w["router"], w["w_gate"], w["w_up"], w["w_down"], c)
        beside = jax.nn.sigmoid(flat @ w["s_weight"]) * (
            (jax.nn.silu(flat @ w["s_gate"]) * (flat @ w["s_up"]))
            @ w["s_down"])
        return routed.reshape(b, t, d), beside.reshape(b, t, d), load, \
            _router_margin(flat @ w["router"], c)

    def layer(i, x, replaced=None):
        """(the state after layer i, what the layer found)."""
        own, ffn = weights[i]
        if replaced:
            own, ffn = dict(own), dict(ffn)
            for (at, group, name), value in replaced.items():
                if at == i:
                    (own if group == "attention" else ffn)[name] = value
        out, q, k, gate = attention(
            kinds[i], plain.rms_norm(x, own["input_norm"], eps), pos, own)
        found = {"attention": out, "q": q, "k": k, "gate": gate}
        x = x + out
        m = plain.rms_norm(x, own["post_norm"], eps)
        if i < dense:
            return x + (jax.nn.silu(m @ ffn["w_gate"]) * (m @ ffn["w_up"])) \
                @ ffn["w_down"], found
        found["routed"], found["shared"], found["load"], found["margin"] = \
            experts(m, ffn)
        return x + found["routed"] + found["shared"], found

    held = {fetch: (i, "attention" if place in _ATTENTION else "ffn", place)
            for fetch, (i, _, place) in GRADIENTS.items()}
    start = min(FIRST_GRADIENT_LAYER, layers - 1)
    if min(i for i, _, _ in held.values()) < start:
        raise ValueError("the cell's gradients are of layers from %d on: %r"
                         % (start, held))

    def tail(theta, x):
        """The stack from layer `start` on under `theta` for four of its
        parameters, the final norm and the head: (mean loss, (logits probe,
        state, what these layers found))."""
        replaced = {held[fetch]: value for fetch, value in theta.items()}
        behind = []
        for i in range(start, layers):
            x, found = layer(i, x, replaced)
            behind.append(found)

        def head(xs):
            logits = plain.rms_norm(xs[0], w_f, eps) @ w_lm
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                       axis=-1)
            return nll.sum(), logits[:, :PROBE_COLUMNS]

        n = min(HEAD_ROWS, b * t)
        nll, probe = jax.lax.map(head, (x.reshape(-1, n, d),
                                        labels.reshape(-1, n, 1)))
        return nll.sum() / (b * t), (probe.reshape(b, t, -1), x, behind)

    with jax.default_matmul_precision("highest"):
        x, by_layer = embedding[ids], []
        for i in range(start):
            x, found = layer(i, x)
            by_layer.append(found)
        theta = {fetch: (weights[i][0] if group == "attention"
                         else weights[i][1])[name]
                 for fetch, (i, group, name) in held.items()}
        (loss, (probe, state, behind)), grads = jax.value_and_grad(
            tail, has_aux=True)(theta, x)
    by_layer += behind
    routed_layers = [found for found in by_layer if "load" in found]
    out = {"loss": loss, "logits": probe,
           "expert_load": sum(found["load"] for found in routed_layers),
           "state": state[..., :PROBE_COLUMNS],
           "gate_1": by_layer[1]["gate"],
           "routed": routed_layers[0]["routed"][..., :PROBE_COLUMNS],
           "shared": routed_layers[0]["shared"][..., :PROBE_COLUMNS],
           "router_margin": jnp.stack(
               [found["margin"] for found in routed_layers]).min(0)
           .reshape(b, t),
           "experts_margin": routed_layers[0]["margin"].reshape(b, t)}
    for i in (1, layers - 1):
        out["attention_%d" % i] = by_layer[i]["attention"][
            ..., :PROBE_COLUMNS]
    for i in (0, 1):
        out["q_%d" % i] = by_layer[i]["q"][:, :, None]
        out["k_%d" % i] = by_layer[i]["k"][:, :, None]
    for fetch, grad in grads.items():
        out[fetch] = grad[..., :CORNER, :CORNER]
    return out


# what is compared at the tokens whose routing is decided: in every expert
# layer, or in the first one alone; the rest at every token (layers 0 and 1
# lie before any router) or as they are (the gradients, sums over tokens)
_DECIDED = ("logits", "attention_4", "state")
_FIRST_DECIDED = ("routed",)
_MARGINS = ("router_margin", "experts_margin")


def check(cfg, first, want, scalars):
    """checks.training on every fetch, each by its largest error over the
    reference's largest value: the loss, `q_0`, `k_0`, `q_1`, `k_1`,
    `gate_1`, `attention_1` and `shared` at every token (no router lies
    before the first five, and the shared expert reads what the router
    reads); `routed` at the tokens the FIRST expert layer decides (the
    reference's held-set margin, configs/smallthinker.py's, at least
    `reference.router_margin`: under it bf16 activations may turn an
    assignment to or from a held expert, which moves the token as far as a
    dropped expert would; such tokens are left out and counted, never the
    tolerance widened to let them in); `logits`, `attention_4` and `state`
    at the tokens decided in EVERY expert layer; the four gradients as they
    are (sums over tokens, so a token whose assignment turned is in them
    whole). The logits are also held by their mean error over their mean
    size, and so is `attention_1` (`attention_1_mean`): a sliding layer's
    output at a position past the window is the gated mean of 512 values,
    a 22nd of the largest output (position 0's, one value), so one key
    more or fewer at the window's edge moves every such row by a 22nd of
    its own size and the largest error over the largest value by nothing
    that rounding does not. `dropless`: every one of the 10 assignments of every token in
    every expert layer was counted, and the rows the held experts computed
    differ from the reference's by no more than the assignments that went
    to another expert."""
    c = _resolved(cfg)
    tolerance = cfg["reference"]["tolerance"]
    margin = np.asarray(want["router_margin"])
    decided = margin >= cfg["reference"]["router_margin"]
    first_decided = np.asarray(want["experts_margin"]) \
        >= cfg["reference"]["router_margin"]

    def compared(x):
        out = {}
        for name in want:
            if name == "expert_load" or name in _MARGINS:
                continue
            value = np.asarray(x[name], np.float32).reshape(
                np.asarray(want[name]).shape)
            out[name] = value[decided] if name in _DECIDED \
                else value[first_decided] if name in _FIRST_DECIDED \
                else value
        return out

    got, ref = compared(first), compared(want)
    verdicts, found = checks.training(cfg, got, ref, scalars)
    mean_error, window_error = (
        float(np.abs(got[name] - ref[name]).mean()
              / np.abs(ref[name]).mean())
        for name in ("logits", "attention_1"))
    verdicts["reference"] = verdicts["reference"] \
        and mean_error <= tolerance["logits_mean"] \
        and window_error <= tolerance["attention_1_mean"]
    load = np.asarray(first["expert_load"], np.int64)
    want_load = np.asarray(want["expert_load"], np.int64)
    tokens = decided.size
    assignments = tokens * c["num_experts_per_tok"] \
        * c["ffn_layers"].count("experts")
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    moved = int(np.abs(load - want_load).sum()) // 2
    verdicts["dropless"] = int(load.sum()) == assignments and abs(
        int(load[held].sum()) - int(want_load[held].sum())) <= moved
    found += "; logits_mean off by %.3e (tolerance %g), " \
        "attention_1_mean off by %.3e (tolerance %g); logits, " \
        "attention_4 and state of %d of %d tokens compared (router margin " \
        ">= %g in every expert layer; over all tokens the logits are off " \
        "by %.2e), routed of %d (that margin in the first expert layer); " \
        "%d of %d assignments counted, the %d held experts computed %d " \
        "rows (reference %d; %d..%d an expert), at least %d assignments " \
        "went to another expert than in the reference" % (
            mean_error, tolerance["logits_mean"], window_error,
            tolerance["attention_1_mean"], decided.sum(), tokens,
            cfg["reference"]["router_margin"],
            checks.normalised_error(first["logits"], want["logits"]),
            first_decided.sum(), load.sum(), assignments, c["experts_held"],
            load[held].sum(), want_load[held].sum(), load[held].min(),
            load[held].max(), moved)
    return verdicts, found
