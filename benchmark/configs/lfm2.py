"""LFM2-8B-A1B (paddle_tpu/models/causal_lm.py) as the benchmark trains it:
one chip's share of a layer that four chips divide, a leading dense layer
and one period of the layer pattern (an attention layer to three gated short
convolutions). `make_batch` and `samples_per_step` are configs/causal_lm.py's;
this file adds the operations a token, the operations of the three flash
kernels at 32 query heads on 8 key/value heads of 64, the bytes the two
causal_conv1d kernels move through HBM, the benchmark's copy of the plain
float32 reference, blocked so that it fits beside the training state
(attention one (sequence, query head) at a time, the held experts one at a
time, the tied head in blocks of rows), and a check that counts the
assignments of the layers that have experts. Sizes are in the
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
# words 0 .. 16383), so ids and labels are drawn from the slice
make_batch = base.make_batch
expert_matmul_ops = base.expert_matmul_ops
embedding_grad_bytes = base.embedding_grad_bytes
MARGINS = (0.0, 0.02, 0.05, 0.1, 0.2)
# the two Pallas passes of layers.causal_conv1d, which a short_conv mixer's
# convolution runs as (between the two gate multiplies, which XLA runs)
SHORT_CONV_KERNELS = ("ptpu_causal_conv1d_fwd", "ptpu_causal_conv1d_bwd")


def build(fluid, cfg, traffic):
    """configs/causal_lm.py's `build`, after asking the program for the
    mixer four of the five layers are made of: a program from before it
    refuses the configuration's keys one by one, this names the cause. Two
    fetches more: the first query head and the first key head as the one
    attention layer's core reads them (normed a head, turned by rotary),
    [B, T, 1, 64] each: the logits at initialisation, where attention is
    near uniform, hardly see what happens to q and k before the scores."""
    from paddle_tpu.models import causal_lm
    if not hasattr(causal_lm, "short_conv"):
        raise NotImplementedError(
            "this program's causal_lm has no short_conv mixer: it cannot "
            "build %s" % (cfg["name"],))
    fetches = base.build(fluid, cfg, traffic)
    block = fluid.default_main_program().global_block()
    core, = [op for op in block.ops if op.type == "fused_attention"]
    for name, slot in (("queries", "Q"), ("keys", "K")):
        fetches[name] = fluid.layers.crop(
            block.var(core.input(slot)[0]), shape=[-1, -1, 1, -1])
    return fetches


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def _count(c, kind):
    return c["mixer_layers"].count(kind)


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part. A short_conv
    mixer: its input projection to [B, C, u] and its output projection, and
    the taps of its convolution (the two gates are a multiply each, not
    counted). An attention mixer: the four projections and the core over
    the causal pairs, a query head. The leading dense FFN at its own width;
    in the layers after it the router at its published width and the held
    experts a token is expected to reach (4 x 8 / 32 of them). The tied
    head over the held words."""
    c = _resolved(cfg)
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    conv, full = _count(c, "short_conv"), _count(c, "attention")
    routed = c["ffn_layers"].count("experts")
    t = traffic["seq_len"]
    return {
        "conv_projections": conv * 4 * d * d,
        "conv_taps": conv * c["conv_L_cache"] * d,
        "attention_projections": full * d * hd * (2 * h + 2 * hkv),
        "attention": full * shared.visible_pairs(t, None) / t * 2 * hd * h,
        "dense_ffn": c["ffn_layers"].count("dense") * 3 * d
        * c["dense_intermediate_size"],
        "router": routed * d * c["num_experts"],
        "experts": routed * shared.held_share(c) * 3 * d * f,
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes. Embedding lookup, norms, rotary, softmax,
    the gates, routing and the optimizer are not counted. At 5 layers and
    T=8192: 3 x 432.6e6 = 1297.8e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels in the one
    attention layer, counting only the causal pairs: 4, 8 and 6 x D a pair
    and query head (configs/smallthinker.py has why); edge blocks compute
    masked pairs too, so a share of the peak from this cannot pass 100 %."""
    c = _resolved(cfg)
    pairs = _count(c, "attention") * shared.visible_pairs(
        traffic["seq_len"], None) * traffic["batch"] \
        * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 4 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dkdv": 8 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dq": 6 * c["head_dim"] * pairs}


def short_conv_kernel_bytes(cfg, traffic):
    """{kernel: bytes a step} that the two kernels of SHORT_CONV_KERNELS
    move to and from HBM in the short_conv mixers' convolutions, in the
    step as XLA compiles it; an array [T, D] is 2 bytes an element under
    the configuration's AMP. Not every operand of these calls is in HBM:
    the compiled step (AOT compile for a described v5e, PR 39: `S(1)` in an
    operand's layout is VMEM) has the `B * u` fusion write the forward
    kernel's input into VMEM, from where a copy of its own takes it to HBM
    for the backward pass, and keeps the first layer's gradient of v, the
    last the backward pass makes, in VMEM too. So the forward kernel moves
    its result alone, and the backward kernel reads v and the output's
    gradient and writes v's gradient, less that one array. Counted with
    every operand in HBM (5 arrays a layer) the forward kernels read 134 %
    of the HBM rate in the trace; counted so, every call of either kernel
    reads 65 to 68 % of it (PERF.md section 5). The halo rows, the filter
    and its gradient (K x D float32 a layer) are not counted. A change
    that moves an operand between the memories (the gates inside the
    kernels, another XLA) changes this count: read the layouts again."""
    c = _resolved(cfg)
    layers = _count(c, "short_conv")
    array = traffic["batch"] * traffic["seq_len"] * c["hidden_size"] * 2
    return {"ptpu_causal_conv1d_fwd": layers * array,
            "ptpu_causal_conv1d_bwd": (3 * layers - 1) * array}


def _router_margin(scores, c):
    """configs/smallthinker.py's held-set margin, on the scores the choice
    is made from, s + b: that rule reads a softmax's probabilities p and
    only their ratios ((p_i - p_j) / p_i), so it is given log(s + b), whose
    softmax is (s + b) over its sum. A score at or under 0 (a small s under
    a negative bias) is as far from the top 4 as a score can be."""
    return shared._router_margin(jnp.log(jnp.maximum(scores, 1e-30)), c)


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

    eps, hd = c["rms_norm_eps"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d = c["hidden_size"]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def attention(a, pos_row, wq, wk, wv, q_norm, k_norm, wo):   # a [T, D]
        q = plain.rms_norm((a @ wq).reshape(1, t, h, hd), q_norm, eps)
        k = plain.rms_norm((a @ wk).reshape(1, t, hkv, hd), k_norm, eps)
        v = (a @ wv).reshape(1, t, hkv, hd)
        q, k = (plain.rope(x, pos_row[None], c["rope_theta"])
                for x in (q, k))
        probe = q[0, :, :1], k[0, :, :1]                     # [T, 1, hd]
        q, k, v = (x[0].transpose(1, 0, 2) for x in (q, k, v))   # [H, T, hd]

        def one_head(args):
            qh, head = args
            kh, vh = k[head // (h // hkv)], v[head // (h // hkv)]
            s = jnp.where(causal, (qh @ kh.T) * hd ** -0.5, -jnp.inf)
            return jax.nn.softmax(s, -1) @ vh

        ctx = jax.lax.map(one_head, (q, jnp.arange(h)))
        return ctx.transpose(1, 0, 2).reshape(t, h * hd) @ wo, probe

    load = jnp.zeros((c["num_experts"],), jnp.int32)
    margin = jnp.full((b * t,), jnp.inf)
    with jax.default_matmul_precision("highest"):
        embedding = take(1)[0]
        x = embedding[ids]
        for i in range(c["num_hidden_layers"]):
            a = plain.rms_norm(x, take(1)[0], eps)
            if c["mixer_layers"][i] == "short_conv":
                x = x + plain.short_conv(a, *take(3))
            else:
                weights = take(6)
                out, (queries, keys) = jax.lax.map(
                    lambda xs: attention(xs[0], xs[1], *weights), (a, pos))
                x = x + out
            m = plain.rms_norm(x, take(1)[0], eps)
            if c["ffn_layers"][i] == "dense":
                wg, wu, wd = take(3)
                x = x + (jax.nn.silu(m @ wg) * (m @ wu)) @ wd
                continue
            m = m.reshape(b * t, d)
            router, bias, wg, wu, wd = take(5)
            out, _, _, ld = plain.routed_experts(m, router, wg, wu, wd, c,
                                                 expert_bias=bias)
            margin = jnp.minimum(margin, _router_margin(
                jax.nn.sigmoid(m @ router) + bias, c))
            x = x + out.reshape(b, t, d)
            load = load + ld
        w_f = take(1)[0]

        def head(xs):                       # HEAD_ROWS rows: [R, D], [R, 1]
            logits = plain.rms_norm(xs[0], w_f, eps) @ embedding.T
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
            "queries": queries, "keys": keys,
            "router_margin": margin.reshape(b, t)}


def check(cfg, first, want, scalars):
    """checks.training on the loss and on the logits of the tokens whose
    routing is decided in every layer that has experts (the smallest of
    their held-set margins on s + b is at least `reference.router_margin`),
    and `dropless`: in the first step every one of the top_k assignments of
    every token, in each of those layers, was counted, and the rows the
    held experts computed are the assignments that fell on them
    (configs/smallthinker.py's check, over the layers that route: the
    leading dense layer has no assignments). One limit more on the same
    logits, `logits_mean`: their mean absolute error over the reference's
    mean absolute value. The largest error of 110,000 logits is set by the
    few furthest off and moves by half from seed to seed; the mean is steady
    to a few per cent, so it sees a change that moves every token a little
    (the bias in the weights) where the largest error cannot. And
    `queries_keys`: the first query head and the first key head as the
    attention core reads them, at every position (no expert layer lies
    before them): a norm a head left out changes the scores' temperature by
    a fifth and the logits by under twice their floor. The line it prints
    says what the logits are off by at other thresholds too, as
    configs/qwen3_next.py's does: the convolutions and the attention layer
    carry a moved assignment's effect to later tokens."""
    c = _resolved(cfg)
    load = np.asarray(first["expert_load"], np.int64)
    margin = np.asarray(want["router_margin"])
    decided = margin >= cfg["reference"]["router_margin"]

    def compared(x):
        return {"loss": x["loss"], "logits": x["logits"][decided],
                "queries_keys": np.concatenate(
                    [np.asarray(x[name], np.float32)
                     for name in ("queries", "keys")], -1)}

    verdicts, found = checks.training(cfg, compared(first), compared(want),
                                      scalars)
    got, ref = (np.asarray(x["logits"], np.float32)[decided]
                for x in (first, want))
    mean_error = float(np.abs(got - ref).mean() / np.abs(ref).mean())
    limit = cfg["reference"]["tolerance"]["logits_mean"]
    verdicts["reference"] = verdicts["reference"] and mean_error <= limit
    tokens = decided.size
    assignments = tokens * c["num_experts_per_tok"] \
        * c["ffn_layers"].count("experts")
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    want_load = np.asarray(want["expert_load"], np.int64)
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
    found += "; logits_mean off by %.3e (tolerance %g); logits of %d of %d " \
        "tokens compared (router margin >= %g in " \
        "every expert layer; over all tokens they are off by %.2e); %d of " \
        "%d assignments counted, the %d held experts computed %d rows " \
        "(reference %d; %d..%d an expert), at least %d assignments went to " \
        "another expert than in the reference; logits by margin >= %s" % (
            mean_error, limit, decided.sum(), tokens,
            cfg["reference"]["router_margin"],
            checks.normalised_error(first["logits"], want["logits"]),
            load.sum(), assignments, c["experts_held"], load[held].sum(),
            want_load[held].sum(), load[held].min(), load[held].max(), moved,
            ", ".join(by_margin))
    return verdicts, found
