"""granite-4.0-h-micro (paddle_tpu/models/causal_lm.py) as the benchmark
trains it: one chip's share of published layers 0-9 (five Mamba-2 mixers, the
attention layer without positional term at index 5, four more mixers: one
whole period) and an eighth of the vocabulary. `make_batch` and
`samples_per_step` are configs/causal_lm.py's; this file adds the operations
a token, the operations the three flash kernels are given over the one
attention core, the least the state-space-dual scan's two kernels have to
compute and move, the bytes of the embedding's gradient, the benchmark's copy
of the plain float32 reference, blocked so that it fits beside the training
state (attention a head at a time, the MLPs and the tied head in blocks of
rows, the scan token by token with a state kept a segment of 64 tokens), and
the cell's check, which also holds what the STATE gives layer 0's scan (a
fetch of the benchmark's own: the scan again over two heads without its skip
term) and five gradients of published layer 9's
mixer (A_log, dt_bias, D, the convolution's bias, the gated norm's weight:
each a sum over every token, which no forward fetch sees dropped) to the
reference's jax.grad of that layer, the final norm and the head.

`ssd_kernel_ops` counts the LEAST the chunked form needs, so that the share
cannot pass 100 % whatever the kernels do inside: C B^T once a chunk and not
once a head or a block of heads, a chunk's products under L by the pairs a
token sees ((Q + 1) / 2 of Q), every operand once and in two bytes. The
kernels compute a head's products 128 lanes wide for a result of 64
(ops/ssd_kernels.py), so against the bf16 peak they stand under 50 % by
construction; at this shape the bytes bind the count and not the products.
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

SAMPLE = base.SAMPLE
PROBE_COLUMNS = base.PROBE_COLUMNS
HEAD_ROWS = shared.HEAD_ROWS
samples_per_step = base.samples_per_step
make_batch = base.make_batch
# the two Pallas passes over chunks of layers.ssd_scan (the backward pass
# runs the first once more, for the state that enters every chunk)
SSD_KERNELS = ("ptpu_ssd_fwd", "ptpu_ssd_bwd")
# the convolution's bias starts at 0, an identity that would hide a rule
# that drops it: drawn normal(0, .) on the benchmark's side (the .json's
# `assumed.identities`)
IDENTITY_RANGE = 0.1
# tokens between two states the reference's scan keeps for its backward pass
SEGMENT = 64
# the mixer parameters of the last layer whose gradients the cell holds:
# fetch -> the parameter's role
GRADIENTS = {"a_log_grad": "a_log", "dt_bias_grad": "dt_bias", "d_grad": "d",
             "conv_bias_grad": "conv.bias", "gated_norm_grad": "gated_norm"}
# where each sits among a mixer's parameters (w_in, conv, conv.bias, dt_bias,
# a_log, d, gated_norm, w_out)
_PLACE = {"conv.bias": 2, "dt_bias": 3, "a_log": 4, "d": 5, "gated_norm": 6}


def build(fluid, cfg, traffic):
    """Builds the training program in the current guard, after asking the
    program for the mixer: a program from before it refuses the
    configuration's keys one by one, this names the cause. Fetches: the
    loss; the logits of the first PROBE_COLUMNS words at every position;
    `scan`, the first PROBE_COLUMNS channels of layer 0's scan output before
    the gate; `carried`, what the STATE gives those channels: the scan
    again over layer 0's first two heads with D = 0, an op of the
    benchmark's own behind the step's (the skip term D x is a thousand times
    the state's part at initialisation and y comes in bf16, so y - D x holds
    nothing of it); `delta`, its Delta (all heads); `attention`, PROBE_COLUMNS
    channels of the attention layer's output (behind W_o); `state`,
    PROBE_COLUMNS channels of the residual state after the last layer; and,
    of the backward pass, the gradients of the last layer's A_log, dt_bias,
    D, convolution bias and gated norm's weight, before the clip."""
    from paddle_tpu.models import causal_lm
    if not hasattr(causal_lm, "mamba2"):
        raise NotImplementedError(
            "this program's causal_lm has no Mamba-2 mixer (layer_types "
            "mamba with mamba_n_heads): it cannot build %s" % (cfg["name"],))
    fluid.default_main_program().enable_mixed_precision()
    loss, logits, _ = causal_lm.build_train(
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
    c = causal_lm.resolve(cfg)
    last = c["num_hidden_layers"] - 1
    scan = next(op for op in block.ops if op.type == "ssd_scan")
    # what W_o gives and what the final norm reads
    behind = "layer_%d.wo" % c["mixer_layers"].index("attention")
    attention = next(op for op in block.ops
                     if behind in op.input_arg_names).output("Out")[0]
    state = next(op for op in block.ops if op.type == "rms_norm"
                 and op.input("Scale")[0] == "final_norm").input("X")[0]
    heads = PROBE_COLUMNS // c["mamba_d_head"]

    def columns(name, n=PROBE_COLUMNS):
        return layers.crop(block.var(name), shape=[-1, -1, n])

    x0, delta0, a0, d0 = (block.var(scan.input(slot)[0])
                          for slot in ("X", "Delta", "A", "D"))
    # B and C by their place behind the convolution ([x; B; C]), not by the
    # slot the mixer gave them to its scan in
    b0, c0 = (block.var(name) for name in next(
        op for op in block.ops if op.type == "split"
        and scan.input("B")[0] in op.output("Out")).output("Out")[1:])
    carried = layers.ssd_scan(
        layers.crop(x0, shape=[-1, -1, heads, c["mamba_d_head"]]),
        layers.crop(delta0, shape=[-1, -1, heads]),
        layers.crop(a0, shape=[heads]), b0, c0,
        layers.scale(layers.crop(d0, shape=[heads]), scale=0.0))
    fetches = {
        "loss": loss,
        "logits": layers.crop(logits, shape=[-1, -1, min(
            PROBE_COLUMNS, cfg["vocab_size"])]),
        "scan": layers.crop(block.var(scan.output("Out")[0]),
                            shape=[-1, -1, heads, c["mamba_d_head"]]),
        "carried": carried,
        "delta": delta0,
        "attention": columns(attention),
        "state": columns(state)}
    for fetch, role in GRADIENTS.items():
        fetches[fetch] = block.var("layer_%d.%s@GRAD" % (last, role))
    return fetches


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part, of the
    ARITHMETIC, whatever form is built. A Mamba-2 mixer's two projections (d
    -> d_i + (d_i + 2 N) + H, d_i -> d); the recurrence's own: a head's state
    [N, P] is updated and read out once a token, 2 N P a head (what any chunk
    length computes beside that under L is the form's, not the model's); the
    attention layer's four projections and its core, causal over the whole
    sequence, 32 heads x (64 + 64) a visible key; the ten gated MLPs; the
    tied head over the held words. The convolution's taps, norms, gates and
    the optimizer are not counted."""
    c = _resolved(cfg)
    d, hd, f = c["hidden_size"], c["head_dim"], c["dense_intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    heads, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    di, t = heads * p, traffic["seq_len"]
    mixers = c["mixer_layers"].count("mamba2")
    cores = c["mixer_layers"].count("attention")
    return {
        "scan_projections": mixers * (d * (2 * di + 2 * n + heads) + di * d),
        "scan": mixers * heads * 2 * n * p,
        "attention_projections": cores * 2 * d * (h + hkv) * hd,
        "attention": cores * shared.visible_pairs(t, None) / t * h * 2 * hd,
        "mlp": c["num_hidden_layers"] * 3 * d * f,
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes; nothing recomputed counts. At the cell's ten
    layers and T=2048: 3 x 2 x 785.5e6 = 4713e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step that the three flash kernels are given over
    the one attention core (32 query heads of 64 on 8, causal over the whole
    sequence), counting only the pairs inside the mask: 4, 8 and 6 x 64 a
    pair and query head for the forward, dK/dV and dQ kernels
    (configs/smallthinker.py has why). Edge blocks compute masked pairs too,
    so a share of the peak from this cannot pass 100 %."""
    c = _resolved(cfg)
    pairs = shared.visible_pairs(traffic["seq_len"], None) \
        * c["mixer_layers"].count("attention") * traffic["batch"] \
        * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 4 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dkdv": 8 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dq": 6 * c["head_dim"] * pairs}


def embedding_grad_bytes(cfg, traffic):
    """Bytes a step that the embedding's gradient has to move THROUGH HBM:
    the dense [V, D] float32 table written once. The [tokens, D] float32
    rows of the output's gradient (16.8 MB here) are left out, as
    configs/phi4_mini_flash.py leaves them out: a compiled step may hand
    them to the kernel in VMEM, and a count that holds them to the HBM rate
    then reads over 100 %. A step that holds the rows in HBM moves more than
    this and reads under its true share, never over."""
    return 4 * cfg["hidden_size"] * cfg["vocab_size"]


def ssd_kernel_ops(cfg, traffic, chunk):
    """{kernel: [(matmul operations, bytes), ...] a step}, a pair a call, of
    the LEAST the chunked state-space-dual scan needs at chunks of `chunk`
    tokens (module docstring). A layer, T tokens, H heads of P on N states,
    q = (chunk + 1) / 2 the pairs a token sees in its chunk. Forward, and
    once more in the backward pass for the states: C B^T (q N a token) and a
    head's products under L (q P), C S and B^T X (N P each). Reverse: dX
    from its chunk (q P) and through the state (N P), dS (N P), the products
    that dB and dC are made of (q P a head for dG, 2 q N a token for dG B
    and dG^T C, 2 N P a head through the state). Bytes: x and y (forward), x,
    dy and dx (reverse) [T, H P] and B, C and their gradients [T, N] at two
    bytes, Delta and its gradient [T, H] at four; the states between the
    chunks and the float32 copies the kernels write are the implementation's
    and not counted."""
    c = _resolved(cfg)
    layers = c["mixer_layers"].count("mamba2")
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
    equal to the unblocked reference. Of the backward pass: jax.grad of the
    last layer, the final norm and the head's mean loss with respect to five
    of that layer's parameters, on the reference's own state entering it
    (the harness computes the reference before the program's first step,
    so the program's state is not there to start from)."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, hd = c["rms_norm_eps"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    ids = batch["ids"]
    b, t = ids.shape
    d = c["hidden_size"]
    labels = batch["labels"].reshape(b, t)
    branch = c["residual_multiplier"]

    embedding = take(1)[0]
    weights = []
    for kind in c["mixer_layers"]:
        n1 = take(1)[0]
        mixer = take(8 if kind == "mamba2" else 4)
        weights.append((n1, mixer, take(1)[0], take(2)))
    w_f = take(1)[0]
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")

    def rows(fn, x):            # fn over x [B, T, ...] HEAD_ROWS rows a time
        n = min(HEAD_ROWS, b * t)
        flat = x.reshape((-1, n) + x.shape[2:])
        return jax.lax.map(fn, flat).reshape((b, t, -1))

    def mlp(x, norm, ffn):
        def block(m):
            gate, up = jnp.split(plain.rms_norm(m, norm, eps) @ ffn[0], 2,
                                 axis=-1)
            return (jax.nn.silu(gate) * up) @ ffn[1]
        return x + branch * rows(block, x)

    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):                      # [T, hd] each
        q, k, v = qkv
        s = jnp.where(causal, (q @ k.T) * c["attention_multiplier"],
                      -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    def attention(a, wq, wk, wv, wo):       # no positional term
        def sequence(a):                    # [T, D]
            q = (a @ wq).reshape(t, h, hd).transpose(1, 0, 2)
            k, v = (jnp.repeat((a @ w).reshape(t, hkv, hd), h // hkv, axis=1)
                    .transpose(1, 0, 2) for w in (wk, wv))
            return jax.lax.map(one_head, (q, k, v)).transpose(
                1, 0, 2).reshape(t, h * hd) @ wo
        return jax.lax.map(sequence, a)

    def layer(j, x, mixer=None, found=None):
        n1, own, n3, ffn = weights[j]
        a = plain.rms_norm(x, n1, eps)
        if c["mixer_layers"][j] == "mamba2":
            mixed = plain.mamba2(a, *(mixer or own), eps, found=found,
                                 segment=SEGMENT)
        else:
            mixed = attention(a, *own)
            if found is not None:
                found["attention"] = mixed
        return mlp(x + branch * mixed, n3, ffn)

    last = c["num_hidden_layers"] - 1

    def tail(theta, x):
        """The last layer under `theta` for five of its mixer's parameters,
        the final norm and the head: (mean loss, (logits probe, state))."""
        mixer = list(weights[last][1])
        for role, value in theta.items():
            mixer[_PLACE[role]] = value
        x = jax.checkpoint(functools.partial(layer, last))(x, mixer)

        def head(xs):
            logits = plain.rms_norm(xs[0], w_f, eps) @ embedding.T \
                / c["logits_scaling"]
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                       axis=-1)
            return nll.sum(), logits[:, :PROBE_COLUMNS]

        n = min(HEAD_ROWS, b * t)
        nll, probe = jax.lax.map(head, (x.reshape(-1, n, d),
                                        labels.reshape(-1, n, 1)))
        return nll.sum() / (b * t), (probe.reshape(b, t, -1), x)

    with jax.default_matmul_precision("highest"):
        x, found = embedding[ids] * c["embedding_multiplier"], {}
        for j in range(last):
            x = layer(j, x, found=found)
        theta = {role: weights[last][1][place]
                 for role, place in _PLACE.items()}
        (loss, (probe, state)), grads = jax.value_and_grad(
            tail, has_aux=True)(theta, x)
    out = {"loss": loss, "logits": probe,
           "scan": found["scan"][..., :PROBE_COLUMNS].reshape(
               b, t, -1, c["mamba_d_head"]),
           "carried": found["carried"][..., :PROBE_COLUMNS].reshape(
               b, t, -1, c["mamba_d_head"]),
           "delta": found["delta"],
           "attention": found["attention"][..., :PROBE_COLUMNS],
           "state": state[..., :PROBE_COLUMNS]}
    out.update((fetch, grads[role]) for fetch, role in GRADIENTS.items())
    return out


def check(cfg, first, want, scalars):
    """checks.training on every fetch, at EVERY position (the model has no
    router): each by its largest error over the reference's largest value,
    the five gradients among them, each a vector of 64 (A_log, dt_bias, D),
    4352 (the convolution's bias) or 4096 (the gated norm's weight) that
    sums over every token. The gradients of A_log and dt_bias are sums of
    terms of either sign that all but cancel (a head's pairs of tokens, each
    weighed by its decay), so one head's entry swings with the bf16 noise of
    the state that enters the layer: they are also held by their MEAN error
    over their mean size (`_mean`), which 64 heads steady (the .json's
    `reference.why` has both readings over the seeds). So is `attention`: at
    initialisation the scores are small at a scale of 1 / 64 and every map
    is nearly flat, so a positional term that should not be there moves
    every position a little and no position much."""
    tolerance = cfg["reference"]["tolerance"]
    verdicts, found = checks.training(cfg, first, want, scalars)
    held = {}
    for name in ("a_log_grad", "dt_bias_grad", "attention"):
        b = np.asarray(want[name], np.float32)
        error = np.abs(np.asarray(first[name], np.float32).reshape(b.shape)
                       - b)
        held[name + "_mean"] = float(error.mean() / np.abs(b).mean())
    verdicts["reference"] = verdicts["reference"] and all(
        held[name] <= tolerance[name] for name in held)
    found += "; " + ", ".join(
        "%s off by %.3e (tolerance %g)" % (name, held[name], tolerance[name])
        for name in sorted(held))
    return verdicts, found
