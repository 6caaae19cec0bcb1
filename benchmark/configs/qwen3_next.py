"""Qwen3-Next-80B-A3B (paddle_tpu/models/causal_lm.py) as the benchmark
trains it: one chip's share of a layer that sixteen chips divide, one period
of the layer pattern (three gated delta nets, one gated full-attention
layer). `build`, `make_batch` and `samples_per_step` are configs/causal_lm.py's
and the check (logits where the held-set router margin clears a threshold
in every layer, plus `dropless`) configs/smallthinker.py's; this file adds
the operations a token, the operations and bytes of the kernels the cell's
metrics read (the three flash kernels at D=256; the two gated delta
kernels, by what the pass over chunks is given) and the benchmark's copy of
the plain float32 reference, blocked
so that it fits beside the training state: the delta rule as the
token-by-token recurrence over all 64 (sequence, value head) states at
once (4 MiB), attention one (sequence, query head) at a time, the held
experts one at a time, the head in blocks of rows. Sizes are in the
configuration's .json under the keys of the model's `config.json`; the
counts of experts and words there are what this chip holds (`share`).
"""
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
# vocab_size is this chip's slice of the published vocabulary (chip 0's,
# words 0 .. 18991), so ids and labels are drawn from the slice
make_batch = base.make_batch
expert_matmul_ops = base.expert_matmul_ops
embedding_grad_bytes = base.embedding_grad_bytes
# the held-set margin of configs/smallthinker.py
_router_margin = shared._router_margin
MARGINS = (0.0, 0.02, 0.05, 0.1, 0.2)


def build(fluid, cfg, traffic):
    """configs/causal_lm.py's `build`, after asking the program for the op
    three of the four layers are made of: a program from before the gated
    delta rule would build four softmax-attention layers from this
    configuration, and fail only when the reference reads other parameters
    than it made."""
    if not hasattr(fluid.layers, "gated_delta_rule"):
        raise NotImplementedError(
            "this program has no fluid.layers.gated_delta_rule: it cannot "
            "build %s" % (cfg["name"],))
    return base.build(fluid, cfg, traffic)


def check(cfg, first, want, scalars):
    """configs/smallthinker.py's check (the loss, the logits of the tokens
    whose held-set router margin clears `reference.router_margin` in all
    four layers, `dropless`), and, in the line it prints, what the logits
    are off by at other thresholds: a delta net's state carries a moved
    assignment's effect to every later token of its sequence, and the
    readings by threshold are how the threshold was chosen (the
    configuration's .json has them)."""
    verdicts, found = shared.check(cfg, first, want, scalars)
    margin = np.asarray(want["router_margin"])
    by_margin = []
    for m in MARGINS:
        keep = margin >= m
        by_margin.append("%g: %d tokens %.2e" % (
            m, keep.sum(), checks.normalised_error(
                first["logits"][keep], want["logits"][keep])
            if keep.any() else float("nan")))
    return verdicts, found + "; logits by margin >= " + ", ".join(by_margin)


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def _mixers(c):
    delta = c["mixer_layers"].count("gated_delta")
    return delta, c["num_hidden_layers"] - delta


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part. A gated delta
    net: its two input projections and the output projection, the four taps
    of its convolution, and the recurrence's own three [dk, dv] products a
    value head (S^T k, the rank-one write, S^T q), whatever the chunk the
    op computes it in. A full-attention layer: the query-and-gate, key,
    value and output projections and the core over the causal pairs. Every
    layer: the router at its published width, the held experts a token is
    expected to reach (10 x 32 / 512 of them), the shared expert and its
    gate. The head over the held words."""
    c = _resolved(cfg)
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    fs = c["shared_expert_intermediate_size"]
    delta, full = _mixers(c)
    layers, t = c["num_hidden_layers"], traffic["seq_len"]
    return {
        "delta_projections": delta * d * (2 * hk * dk + 2 * hv * dv + 2 * hv
                                          + hv * dv),
        "delta_convolution": delta * c["linear_conv_kernel_dim"]
        * (2 * hk * dk + hv * dv),
        "delta_rule": delta * hv * 3 * dk * dv,
        "attention_projections": full * d * hd * (
            (2 if c["attention_gate"] else 1) * h + 2 * hkv + h),
        "attention": full * shared.visible_pairs(t, None) / t * 2 * hd * h,
        "router": layers * d * c["num_experts"],
        "experts": layers * shared.held_share(c) * 3 * d * f,
        "shared_expert": layers * (3 * d * fs + d),
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes. Embedding lookup, norms, rotary, softmax,
    the gates' exponentials, routing and the optimizer are not counted. At
    4 layers and T=4096: 3 x 426.9e6 = 1280.8e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels in the one
    full-attention layer, counting only the causal pairs: 4, 8 and 6 x D a
    pair and query head (configs/smallthinker.py has why); edge blocks
    compute masked pairs too, so a share of the peak from this cannot pass
    100 %."""
    c = _resolved(cfg)
    pairs = _mixers(c)[1] * shared.visible_pairs(traffic["seq_len"], None) \
        * traffic["batch"] * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 4 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dkdv": 8 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dq": 6 * c["head_dim"] * pairs}


GATED_DELTA_KERNELS = ("ptpu_gated_delta_fwd", "ptpu_gated_delta_bwd")


def gated_delta_kernel_ops(cfg, traffic, chunk):
    """{kernel: [(operations, bytes), ...]} a step, one pair a call and
    layer pattern, of what the two kernels of the pass over chunks are GIVEN
    to do at chunks of `chunk` tokens: their own matmuls, and their operands
    and results moved once between HBM and VMEM. Not the recurrence's least
    (6 x dk x dv a token and value head, which `ops_per_sample` counts) and
    not what prepares the chunks, which XLA runs outside the kernels and
    the kernels' traced time does not hold: the share this feeds compares
    the kernels' time with the kernels' work.

    A tile is one (sequence, value head, chunk); its operands, bf16 under
    the configuration's AMP: qe, kd, w [C, dk], u [C, dv], m [C, C], and
    erow [dv] float32; the state is [dk, dv].
    `ptpu_gated_delta_fwd` runs twice a layer: for o (reads the six, writes
    o [C, dv]; W S, Qe S, K~^T V' at C x dk x dv multiply-adds each and M V'
    at C x C x dv) and, in the backward pass, for the state that enters
    every chunk (needs kd, u, w, erow; writes [dk, dv]; W S and K~^T V').
    `ptpu_gated_delta_bwd` reads the six, a state and dO [C, dv] and writes
    the six's gradients: seven products of C x dk x dv and two of C x C x
    dv."""
    c = _resolved(cfg)
    hv = c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    tiles = _mixers(c)[0] * traffic["batch"] * hv \
        * -(-traffic["seq_len"] // chunk)
    big, small = 2 * chunk * dk * dv, 2 * chunk * chunk * dv    # operations
    six = 2 * chunk * (3 * dk + dv + chunk) + 4 * dv            # bytes
    state, rows = 2 * dk * dv, 2 * chunk * dv
    return {
        "ptpu_gated_delta_fwd": [
            (tiles * (3 * big + small), tiles * (six + rows)),
            (tiles * 2 * big,
             tiles * (2 * chunk * (2 * dk + dv) + 4 * dv + state))],
        "ptpu_gated_delta_bwd": [
            (tiles * (7 * big + 2 * small),
             tiles * (2 * six + state + rows))]}


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from the plain float32 forward of
    paddle_tpu/models/causal_lm_reference.py on the program's weights, with
    the same arithmetic cut into blocks (module docstring). A test holds it
    equal to the unblocked reference."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, hd, centred = c["rms_norm_eps"], c["head_dim"], \
        c["norm_zero_centered"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d = c["hidden_size"]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def attention(a, pos_row, wq, wk, wv, q_norm, k_norm, wo):   # a [T, D]
        q = (a @ wq).reshape(1, t, h, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:]
        k, v = ((a @ w).reshape(1, t, hkv, hd) for w in (wk, wv))
        q = plain.rms_norm(q, q_norm, eps, centred)
        k = plain.rms_norm(k, k_norm, eps, centred)
        q, k = (plain.rope(x, pos_row[None], c["rope_theta"],
                           c["rotary_dim"]) for x in (q, k))
        q, k, v = (x[0].transpose(1, 0, 2) for x in (q, k, v))   # [H, T, hd]

        def one_head(args):
            qh, head = args
            kh, vh = k[head // (h // hkv)], v[head // (h // hkv)]
            s = jnp.where(causal, (qh @ kh.T) * hd ** -0.5, -jnp.inf)
            return jax.nn.softmax(s, -1) @ vh

        ctx = jax.lax.map(one_head, (q, jnp.arange(h)))
        ctx = ctx.transpose(1, 0, 2) * jax.nn.sigmoid(gate[0])
        return ctx.reshape(t, h * hd) @ wo

    load = jnp.zeros((c["num_experts"],), jnp.int32)
    margin = jnp.full((b * t,), jnp.inf)
    with jax.default_matmul_precision("highest"):
        x = take(1)[0][ids]
        for i in range(c["num_hidden_layers"]):
            a = plain.rms_norm(x, take(1)[0], eps, centred)
            if c["mixer_layers"][i] == "gated_delta":
                x = x + plain.gated_delta_net(a, *take(7), c)
            else:
                weights = take(6)
                x = x + jax.lax.map(
                    lambda xs: attention(xs[0], xs[1], *weights), (a, pos))
            m = plain.rms_norm(x, take(1)[0], eps, centred).reshape(b * t, d)
            router, wg, wu, wd = take(4)
            out, _, _, ld = plain.routed_experts(m, router, wg, wu, wd, c)
            margin = jnp.minimum(margin, _router_margin(m @ router, c))
            out = out + plain.shared_expert(m, *take(4))
            x = x + out.reshape(b, t, d)
            load = load + ld
        w_f, w_lm = take(2)

        def head(xs):                       # HEAD_ROWS rows: [R, D], [R, 1]
            logits = plain.rms_norm(xs[0], w_f, eps, centred) @ w_lm
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                       axis=-1)
            return nll.sum(), logits[:, :PROBE_COLUMNS]

        rows = min(HEAD_ROWS, b * t)
        nll, probe = jax.lax.map(head, (
            x.reshape(-1, rows, d), batch["labels"].reshape(-1, rows, 1)))
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")
    return {"loss": nll.sum() / (b * t),
            "logits": probe.reshape(b, t, -1), "expert_load": load,
            "router_margin": margin.reshape(b, t)}
