"""NVIDIA-Nemotron-3-Super-120B-A12B (paddle_tpu/models/causal_lm.py) as the
benchmark trains it: one of 64 chips' share of published layers 0-10
(`MEMEMEM*EME`: five Mamba-2 mixers of one held group, five LatentMoE layers
of 8 held experts beside a whole shared expert, one attention layer of 4
query heads on 1 key/value head: one whole period) and an eighth of the
vocabulary. A layer is ONE branch, h + f(N(h)). `make_batch` and
`samples_per_step` are configs/causal_lm.py's; this file adds the operations
a token, the counts the kernels' readers divide by (the routed experts' TWO
matmuls an assignment, the scan's least at 16 heads, the flash kernels' at 4
heads on 1, the embedding gradient's table), the benchmark's copy of the
plain float32 reference, blocked so that it fits beside the training state
(the scan token by token with a state kept a segment of 64 tokens, attention
a head at a time, the held experts one at a time, the head in blocks of
rows), and the cell's check, which also holds five gradients of the last
two layers (the last `E` layer's W_dn and the held experts' W1, the last `M`
layer's A_log, dt_bias and D) to the reference's jax.grad from that `M`
layer on.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, manifest

_HERE = os.path.dirname(os.path.abspath(__file__))
base = manifest.load_module(os.path.join(_HERE, "causal_lm.py"))
shared = manifest.load_module(os.path.join(_HERE, "smallthinker.py"))
lfm2 = manifest.load_module(os.path.join(_HERE, "lfm2.py"))
granite = manifest.load_module(os.path.join(_HERE, "granite_4_0_h_micro.py"))

SAMPLE = base.SAMPLE
PROBE_COLUMNS = base.PROBE_COLUMNS
HEAD_ROWS = shared.HEAD_ROWS
samples_per_step = base.samples_per_step
# full sequences of uniform ids in [0, vocab_size): the configuration's
# vocab_size is chip 0's slice of the published vocabulary (words 0 ..
# 16383), so ids and labels are drawn from the slice
make_batch = base.make_batch
MARGINS = lfm2.MARGINS
_router_margin = lfm2._router_margin        # the held-set margin on s + b
SSD_KERNELS = granite.SSD_KERNELS
# the matmuls of one UNGATED expert (up, down): configs/causal_lm.py's three
# would read 1.5 times the work here
EXPERT_MATMULS = 2
PASSES = base.PASSES
# the convolution's bias starts at 0, an identity that would hide a rule
# that drops it: drawn normal(0, .) on the benchmark's side, as
# configs/granite_4_0_h_micro.py does
IDENTITY_RANGE = granite.IDENTITY_RANGE
SEGMENT = granite.SEGMENT
# rows and columns of a weight's gradient that are fetched: a corner, every
# element of which sums over every token
CORNER = 128
# the gradients the cell holds: fetch -> (the layer's place among the kinds
# counted from the END of the stack, the parameter's role)
GRADIENTS = {"latent_down_grad": ("experts", "latent_down"),
             "w_up_grad": ("experts", "experts.w_up"),
             "a_log_grad": ("mamba2", "a_log"),
             "dt_bias_grad": ("mamba2", "dt_bias"),
             "d_grad": ("mamba2", "d")}
# where each sits among its layer's parameters behind the norm: a mixer's
# (w_in, conv, conv.bias, dt_bias, a_log, d, gated_norm, w_out), an expert
# layer's (latent_down, router, expert_bias, w_up, w_down, latent_up, the
# shared expert's w_up, w_down)
_PLACE = {"dt_bias": 3, "a_log": 4, "d": 5, "latent_down": 0,
          "experts.w_up": 3}


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def _kinds(c):
    """A layer's one branch, by layer: mamba2, attention, experts or
    dense."""
    return [mixer if ffn == "none" else ffn
            for mixer, ffn in zip(c["mixer_layers"], c["ffn_layers"])]


def _last(kinds, kind):
    return len(kinds) - 1 - kinds[::-1].index(kind)


def build(fluid, cfg, traffic):
    """Builds the training program in the current guard, after asking the
    program for the one-branch layers: a program from before them refuses
    the configuration's keys one by one, this names the cause. Fetches: the
    loss; the logits of the first PROBE_COLUMNS words at every position;
    `expert_load`; `scan`, the first PROBE_COLUMNS channels (two heads) of
    layer 0's scan output before the gate, and `delta`, its Delta (all 16
    heads); of the first `E` layer `latent` (u = x W_dn, PROBE_COLUMNS of
    its 1024 columns), `routed` (what the held experts add to a token,
    BEFORE W_up, PROBE_COLUMNS of 1024), `routed_out` (behind W_up) and
    `shared` (the shared expert's part), each PROBE_COLUMNS channels;
    `attention`, as many of the attention layer's output behind W_o;
    `state`, of the residual state after the last layer; and, of the
    backward pass, before the clip: a CORNER x CORNER corner of the
    gradients of the last `E` layer's W_dn and of every held expert's W1
    (an expert that no token chose has a gradient of 0 in program and
    reference alike, and an error of 0 / 0), the gradients of the last
    `M` layer's A_log, dt_bias and D, and `latent_grad`, PROBE_COLUMNS
    columns of the gradient that reaches the last `E` layer's u (a token's
    own row, where the weights' gradients are sums over tokens)."""
    from paddle_tpu.models import causal_lm
    if not hasattr(causal_lm, "PATTERN"):
        raise NotImplementedError(
            "this program's causal_lm has no hybrid_override_pattern (a "
            "layer of ONE branch, ungated experts in a latent space): it "
            "cannot build %s" % (cfg["name"],))
    fluid.default_main_program().enable_mixed_precision()
    loss, logits, load = causal_lm.build_train(
        cfg, traffic["seq_len"], learning_rate=cfg["learning_rate"],
        beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
        epsilon=cfg["adam_epsilon"], clip_norm=cfg["clip_norm"])
    startup = fluid.default_startup_program().global_block()
    block = fluid.default_main_program().global_block()
    for p in block.all_parameters():
        if p.name.endswith("conv.bias"):
            fluid.initializer.Normal(0.0, IDENTITY_RANGE)(
                startup.var(p.name), startup)
    layers = fluid.layers
    kinds = _kinds(_resolved(cfg))

    def behind(name):           # what the op that reads parameter `name` gives
        return next(op for op in block.ops
                    if name in op.input_arg_names).output("Out")[0]

    def columns(name):
        var = block.var(name)
        return layers.crop(var, shape=[-1, -1, min(PROBE_COLUMNS,
                                                   int(var.shape[-1]))])

    scan = next(op for op in block.ops if op.type == "ssd_scan")
    routed = next(op for op in block.ops if op.type == "moe_ffn")
    first = kinds.index("experts")
    state = next(op for op in block.ops if op.type == "rms_norm"
                 and op.input("Scale")[0] == "final_norm").input("X")[0]
    fetches = {
        "loss": loss,
        "logits": layers.crop(logits, shape=[-1, -1, min(
            PROBE_COLUMNS, cfg["vocab_size"])]),
        "expert_load": load,
        "scan": layers.crop(
            block.var(scan.output("Out")[0]),
            shape=[-1, -1, PROBE_COLUMNS // cfg["mamba_head_dim"],
                   cfg["mamba_head_dim"]]),
        "delta": block.var(scan.input("Delta")[0]),
        "latent": columns(routed.input("X")[0]),
        "routed": columns(routed.output("Out")[0]),
        "routed_out": columns(behind("layer_%d.latent_up" % first)),
        "shared": columns(behind("layer_%d.shared_expert.w_down" % first)),
        "attention": columns(behind("layer_%d.wo"
                                    % kinds.index("attention"))),
        "state": columns(state)}
    # what reaches the LAST `E` layer's experts' input going back: a token's
    # own, which a check can hold at the tokens whose routing is decided
    last_routed = [op for op in block.ops if op.type == "moe_ffn"][-1]
    fetches["latent_grad"] = columns(last_routed.input("X")[0] + "@GRAD")
    for fetch, (kind, role) in GRADIENTS.items():
        grad = block.var("layer_%d.%s@GRAD" % (_last(kinds, kind), role))
        if len(grad.shape) > 1:         # [latent, width], a held expert each
            grad = layers.crop(grad, shape=[int(n) for n in grad.shape[:-2]]
                               + [min(CORNER, int(n))
                                  for n in grad.shape[-2:]])
        fetches[fetch] = grad
    return fetches


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part, of the
    ARITHMETIC, whatever form is built. A Mamba-2 mixer's two projections
    (d -> d_i + (d_i + 2 N) + H, d_i -> d) at the 16 heads held and the
    recurrence's own 2 N P a head; the attention layer's four projections at
    4 query heads on 1 and its core, causal over the whole sequence; an `E`
    layer's router at its published 512 columns, its two latent projections,
    its shared expert whole, and the held experts a token is expected to
    reach (22 x 8 / 512 of them, two matrices of 1024 x 2688 each); the head
    over the held words. Convolutions, norms, the routing's sort and the
    optimizer are not counted. At the cell's eleven layers and T = 4096:
    425.9e6, the shared experts 51.7 %, the head 15.8 %, the mixers 16.4 %,
    router and latent projections 12.3 %, the held routed experts 2.2 %."""
    c = _resolved(cfg)
    kinds = _kinds(c)
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    heads, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    di, t, latent = heads * p, traffic["seq_len"], c["moe_latent_size"]
    mixers, cores = kinds.count("mamba2"), kinds.count("attention")
    routed = kinds.count("experts")
    reached = c["num_experts_per_tok"] * c["experts_held"] \
        / float(c["num_experts"])
    return {
        "scan_projections": mixers * (d * (2 * di + 2 * n + heads) + di * d),
        "scan": mixers * heads * 2 * n * p,
        "attention_projections": cores * 2 * d * (h + hkv) * hd,
        "attention": cores * shared.visible_pairs(t, None) / t * h * 2 * hd,
        "router_and_latent": routed * (d * c["num_experts"]
                                       + 2 * d * latent),
        "shared_expert": routed * 2 * d
        * c["shared_expert_intermediate_size"],
        "routed_experts": routed * reached * EXPERT_MATMULS * latent * f,
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes; nothing recomputed counts. At the cell's
    eleven layers and T=4096: 3 x 2 x 425.9e6 = 2555e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def expert_matmul_ops(cfg, traffic, load):
    """configs/causal_lm.py's count at what an expert is HERE: every
    assignment that a held expert computed is one row through the expert's
    EXPERT_MATMULS = 2 matrices of moe_latent_size x the expert's width
    (1024 x 2688: the experts read the latent, not the hidden state), two
    operations a multiply-add, PASSES passes. `load` is the `expert_load`
    fetch, [E] of one step or [steps, E] of several, over all of the
    router's columns: the held experts' are taken out here."""
    c = _resolved(cfg)
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    assignments = int(np.asarray(load, np.int64).reshape(
        -1, c["num_experts"])[:, held].sum())
    return PASSES * EXPERT_MATMULS * 2 * c["moe_latent_size"] \
        * c["intermediate_size"] * assignments


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step that the three flash kernels are given over
    the one attention core (4 query heads of 128 on 1, causal over the whole
    sequence), counting only the pairs inside the mask: 4, 8 and 6 x 128 a
    pair and query head for the forward, dK/dV and dQ kernels
    (configs/smallthinker.py has why). Edge blocks compute masked pairs too,
    so a share of the peak from this cannot pass 100 %."""
    c = _resolved(cfg)
    pairs = shared.visible_pairs(traffic["seq_len"], None) \
        * _kinds(c).count("attention") * traffic["batch"] \
        * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 4 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dkdv": 8 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dq": 6 * c["head_dim"] * pairs}


def embedding_grad_bytes(cfg, traffic):
    """Bytes a step that the embedding's gradient has to move THROUGH HBM:
    the dense [16384, 4096] float32 table written once (268.4e6). The
    [tokens, D] float32 rows of the output's gradient are left out, as
    configs/granite_4_0_h_micro.py leaves them out: a compiled step may hand
    them to the kernel in VMEM, and a count that holds them to the HBM rate
    then reads over 100 %."""
    return 4 * cfg["hidden_size"] * cfg["vocab_size"]


def ssd_kernel_ops(cfg, traffic, chunk):
    """configs/granite_4_0_h_micro.py's count of the LEAST the chunked
    state-space-dual scan needs, at what is held here: 16 heads of 64 on 128
    states, one group, five layers (a call a layer forward, once more in the
    backward pass, and the reverse kernel)."""
    c = _resolved(cfg)
    layers = _kinds(c).count("mamba2")
    tokens = traffic["batch"] * traffic["seq_len"]
    h, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    q = (chunk + 1) / 2.0
    forward = (2 * tokens * (q * n + h * (q * p + 2 * n * p)),
               tokens * (2 * 2 * h * p + 2 * 2 * n + 4 * h))
    reverse = (2 * tokens * (2 * q * n + h * (2 * q * p + 4 * n * p)),
               tokens * (3 * 2 * h * p + 4 * 2 * n + 2 * 4 * h))
    return {"ptpu_ssd_fwd": [forward, forward] * layers,
            "ptpu_ssd_bwd": [reverse] * layers}


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from the plain float32 forward of
    paddle_tpu/models/causal_lm_reference.py on the program's weights, with
    the same arithmetic cut into blocks (module docstring); a test holds it
    equal to the unblocked reference. `router_margin` [B, T] is the least,
    over the `E` layers, of a token's held-set margin on s + b, and
    `experts_margin` the first `E` layer's own. Of the backward pass:
    jax.grad of the stack from the last `M` layer on (with the `E` layer
    behind it, the final norm and the head's mean loss) with respect to
    five parameters, on the reference's own state entering that layer (the
    harness computes the reference before the program's first step, so the
    program's state is not there to start from)."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    kinds = _kinds(c)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, hd = c["rms_norm_eps"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    ids = batch["ids"]
    b, t = ids.shape
    d = c["hidden_size"]
    labels = batch["labels"].reshape(b, t)

    embedding = take(1)[0]
    weights = [(take(1)[0], take({"mamba2": 8, "attention": 4, "experts": 8,
                                  "dense": 2}[kind])) for kind in kinds]
    w_f, w_lm = take(2)
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")

    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):                      # [T, hd] each
        q, k, v = qkv
        s = jnp.where(causal, (q @ k.T) * hd ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    def attention(a, wq, wk, wv, wo):       # no positional term
        def sequence(a):                    # [T, D]
            q = (a @ wq).reshape(t, h, hd).transpose(1, 0, 2)
            k, v = (jnp.repeat((a @ w).reshape(t, hkv, hd), h // hkv, axis=1)
                    .transpose(1, 0, 2) for w in (wk, wv))
            return jax.lax.map(one_head, (q, k, v)).transpose(
                1, 0, 2).reshape(t, h * hd) @ wo
        return jax.lax.map(sequence, a)

    def experts(a, own, found=None, moved=None):
        """A LatentMoE on a [B, T, D]: the held experts one at a time over
        the tokens whose float32 choice names them (every token, masked),
        on u = a W_dn (+ `moved`, zeros whose gradient is u's); their sum
        through W_up; the shared expert beside it."""
        down, router, bias, wu, wd, up, s_up, s_down = own
        flat = a.reshape(b * t, d)
        u = flat @ down if moved is None else flat @ down + moved
        r, _, _, load = plain.routed_experts(
            u, router, None, wu, wd, c, router_x=flat, expert_bias=bias)
        out, beside = r @ up, plain.relu2_mlp(flat, s_up, s_down)
        if found is not None:
            margin = _router_margin(jax.nn.sigmoid(flat @ router) + bias, c)
            found["load"] = found.get("load", 0) + load
            found["router_margin"] = jnp.minimum(
                found.get("router_margin", jnp.inf), margin)
            found["last_margin"] = margin
            for name, value in (("latent", u), ("routed", r),
                                ("routed_out", out), ("shared", beside),
                                ("experts_margin", margin)):
                found.setdefault(name, value)
        return (out + beside).reshape(b, t, d)

    def layer(j, x, own=None, found=None, moved=None):
        norm, kept = weights[j]
        a, own = plain.rms_norm(x, norm, eps), own or kept
        if kinds[j] == "mamba2":
            out = plain.mamba2(a, *own, eps, found=found, segment=SEGMENT,
                               groups=c["mamba_n_groups"])
        elif kinds[j] == "attention":
            out = attention(a, *own)
            if found is not None:
                found["attention"] = out
        elif kinds[j] == "experts":
            out = experts(a, own, found, moved)
        else:
            out = plain.relu2_mlp(a, *own)
        return x + out

    # the gradients' layers: the last `M` layer and everything behind it
    start, last_routed = _last(kinds, "mamba2"), _last(kinds, "experts")
    held = {fetch: (_last(kinds, kind), _PLACE[role])
            for fetch, (kind, role) in GRADIENTS.items()}
    if min(j for j, _ in held.values()) < start:
        raise ValueError("the cell's gradients are of layers from the last "
                         "Mamba-2 mixer on: %r" % (held,))

    def tail(theta, x):
        """The stack from layer `start` on under `theta` for five of its
        parameters, the final norm and the head: (mean loss, (logits probe,
        state, what these layers add to `found`)). A mixer runs under
        jax.checkpoint (its scan keeps a state a segment); the others do
        not, so that what they leave in `found` is this trace's own."""
        found = {}
        for j in range(start, len(kinds)):
            own = list(weights[j][1])
            for fetch, (at, place) in held.items():
                if at == j:
                    own[place] = theta[fetch]
            x = jax.checkpoint(functools.partial(layer, j))(x, own) \
                if kinds[j] == "mamba2" else layer(
                    j, x, own, found,
                    theta["latent_grad"] if j == last_routed else None)

        def head(xs):
            logits = plain.rms_norm(xs[0], w_f, eps) @ w_lm
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                       axis=-1)
            return nll.sum(), logits[:, :PROBE_COLUMNS]

        n = min(HEAD_ROWS, b * t)
        nll, probe = jax.lax.map(head, (x.reshape(-1, n, d),
                                        labels.reshape(-1, n, 1)))
        return nll.sum() / (b * t), (probe.reshape(b, t, -1), x, found)

    with jax.default_matmul_precision("highest"):
        x, found = embedding[ids], {}
        for j in range(start):
            x = layer(j, x, found=found)
        theta = {fetch: weights[j][1][place]
                 for fetch, (j, place) in held.items()}
        theta["latent_grad"] = jnp.zeros((b * t, c["moe_latent_size"]
                                          or d), jnp.float32)
        (loss, (probe, state, behind)), grads = jax.value_and_grad(
            tail, has_aux=True)(theta, x)
    for name, value in behind.items():
        if name == "load":
            found["load"] = found.get("load", 0) + value
        elif name == "router_margin":
            found[name] = jnp.minimum(found.get(name, jnp.inf), value)
        elif name == "last_margin":
            found[name] = value
        else:
            found.setdefault(name, value)
    out = {"loss": loss, "logits": probe, "expert_load": found["load"],
           "scan": found["scan"][..., :PROBE_COLUMNS].reshape(
               b, t, -1, c["mamba_d_head"]),
           "delta": found["delta"],
           "attention": found["attention"][..., :PROBE_COLUMNS],
           "state": state[..., :PROBE_COLUMNS],
           "router_margin": found["router_margin"].reshape(b, t),
           "experts_margin": found["experts_margin"].reshape(b, t),
           "last_margin": found["last_margin"].reshape(b, t)}
    for name in ("latent", "routed", "routed_out", "shared"):
        out[name] = found[name].reshape(b, t, -1)[..., :PROBE_COLUMNS]
    out["latent_grad"] = grads.pop("latent_grad").reshape(
        b, t, -1)[..., :PROBE_COLUMNS]
    for fetch, grad in grads.items():
        out[fetch] = grad[..., :CORNER, :CORNER] if grad.ndim > 1 else grad
    return out


# what is compared at the tokens whose routing is decided: in every `E`
# layer, or in the first one alone
_DECIDED = ("logits", "attention", "state")
_FIRST_DECIDED = ("routed", "routed_out")
_LAST_DECIDED = ("latent_grad",)
_MARGINS = ("router_margin", "experts_margin", "last_margin")


def check(cfg, first, want, scalars):
    """checks.training on every fetch, each by its largest error over the
    reference's largest value: the loss, `scan`, `delta`, `latent`, `shared`
    and the five gradients as they are (no router lies before the first
    four, and a gradient sums over every token); `logits`, `attention` and
    `state` at the tokens whose routing is decided in EVERY `E` layer (the
    reference's held-set margin on s + b, configs/lfm2.py's, at least
    `reference.router_margin`: under it bf16 activations may turn an
    assignment to or from a held expert, which moves the token as far as a
    dropped expert would; such tokens are left out and counted, never the
    tolerance widened to let them in); `routed` and `routed_out` at the
    tokens the FIRST `E` layer decides, `latent_grad` at those the LAST
    decides. The gradients of W_dn and of the held experts' W1 are sums
    over tokens, so a token whose assignment turned is in them whole: their
    error is of as low a rank as tokens turned (one to five an expert, 45
    over the 8, on the chip) over a floor of a hundredth, and their limits
    are wide for that; `latent_grad` is what holds the experts' backward
    pass tightly. The logits are also held by their
    mean error over their mean size. `dropless`: every one of the 22
    assignments of every token in every `E` layer was counted, and the rows
    the held experts computed differ from the reference's by no more than
    the assignments that went to another expert."""
    c = _resolved(cfg)
    tolerance = cfg["reference"]["tolerance"]
    margin = np.asarray(want["router_margin"])
    decided = margin >= cfg["reference"]["router_margin"]
    first_decided, last_decided = (
        np.asarray(want[name]) >= cfg["reference"]["router_margin"]
        for name in ("experts_margin", "last_margin"))

    def compared(x):
        out = {}
        for name in want:
            if name == "expert_load" or name in _MARGINS:
                continue
            value = np.asarray(x[name], np.float32).reshape(
                np.asarray(want[name]).shape)
            out[name] = value[decided] if name in _DECIDED \
                else value[first_decided] if name in _FIRST_DECIDED \
                else value[last_decided] if name in _LAST_DECIDED \
                else value
        return out

    got, ref = compared(first), compared(want)
    verdicts, found = checks.training(cfg, got, ref, scalars)
    mean_error = float(np.abs(got["logits"] - ref["logits"]).mean()
                       / np.abs(ref["logits"]).mean())
    verdicts["reference"] = verdicts["reference"] \
        and mean_error <= tolerance["logits_mean"]
    load = np.asarray(first["expert_load"], np.int64)
    want_load = np.asarray(want["expert_load"], np.int64)
    tokens = decided.size
    assignments = tokens * c["num_experts_per_tok"] \
        * _kinds(c).count("experts")
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    moved = int(np.abs(load - want_load).sum()) // 2
    verdicts["dropless"] = int(load.sum()) == assignments and abs(
        int(load[held].sum()) - int(want_load[held].sum())) <= moved
    by_margin = []
    for m in MARGINS:
        keep = margin >= m
        by_margin.append("%g: %d tokens %.2e" % (
            m, keep.sum(), checks.normalised_error(
                first["logits"][keep], want["logits"][keep])
            if keep.any() else float("nan")))
    found += "; logits_mean off by %.3e (tolerance %g); logits, attention " \
        "and state of %d of %d tokens compared (router margin >= %g in " \
        "every expert layer; over all tokens the logits are off by %.2e), " \
        "routed and routed_out of %d (that margin in the first expert " \
        "layer); %d of %d assignments counted, the %d held experts " \
        "computed %d rows (reference %d; %d..%d an expert), at least %d " \
        "assignments went to another expert than in the reference; logits " \
        "by margin >= %s" % (
            mean_error, tolerance["logits_mean"], decided.sum(), tokens,
            cfg["reference"]["router_margin"],
            checks.normalised_error(first["logits"], want["logits"]),
            first_decided.sum(), load.sum(), assignments, c["experts_held"],
            load[held].sum(), want_load[held].sum(), load[held].min(),
            load[held].max(), moved, ", ".join(by_margin))
    return verdicts, found
