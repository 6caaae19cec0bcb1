"""Xing4.0-29B-A4B (paddle_tpu/models/causal_lm.py) as the benchmark trains
it: one chip's share of a layer that eight chips divide, the second leading
dense layer and four expert layers. `make_batch` and `samples_per_step` are
configs/causal_lm.py's; this file adds the operations a token, the
operations of the three flash kernels at a head of 192 on keys of 192 (64
of them one rotary key all heads share) and values of 128, the bytes the
hyper-connections' kernels move through HBM, the benchmark's copy of the
plain float32 reference, blocked so that it fits beside the training state
(attention one query head at a time, the held experts one at a time, the
head in blocks of rows), and the cell's check. Sizes are in the
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
lfm2 = manifest.load_module(os.path.join(_HERE, "lfm2.py"))

SAMPLE = base.SAMPLE
PROBE_COLUMNS = base.PROBE_COLUMNS
HEAD_ROWS = shared.HEAD_ROWS
samples_per_step = base.samples_per_step
# full sequences of uniform ids in [0, vocab_size): the configuration's
# vocab_size is chip 0's slice of the published vocabulary (words 0 ..
# 16383), so ids and labels are drawn from the slice
make_batch = base.make_batch
expert_matmul_ops = base.expert_matmul_ops
embedding_grad_bytes = base.embedding_grad_bytes
MARGINS = lfm2.MARGINS
_router_margin = lfm2._router_margin        # the held-set margin on s + b
# the Pallas passes over the residual streams (ops/mhc_kernels.py)
MHC_KERNELS = ("ptpu_mhc_pre_fwd", "ptpu_mhc_pre_bwd", "ptpu_mhc_post_fwd",
               "ptpu_mhc_post_bwd", "ptpu_mhc_expand", "ptpu_mhc_reduce",
               "ptpu_mhc_coeffs_fwd", "ptpu_mhc_coeffs_bwd")


def build(fluid, cfg, traffic):
    """configs/causal_lm.py's `build`, after asking the program for the
    attention every layer is made of: a program from before it refuses the
    configuration's keys one by one, this names the cause. Fetches more.
    Of the leading dense layer, before which no router lies: head 0's query
    and key as its attention core reads them, the part without position
    then the rotary part, 192 each ([B, T, 1, 192]); the first
    PROBE_COLUMNS channels of each of the four streams after the layer
    ([B, T, 4 x 128]); the coefficients of the layer's second
    hyper-connection ([B, T, 128] float32, H_res in columns 8 .. 23). Of
    the first expert layer, what its held experts add to a token before the
    shared expert's part (the first `moe_ffn`'s output, [B, T, 128]): the
    one place where the weights the chosen experts carry are not diluted by
    the shared expert and four streams. Of the last layer, the four
    streams it leaves ([B, T, 4 x 128]: their sum is the same under an
    H_res and under its transpose, the streams are not) and their readout
    (the sum) before the final norm ([B, T, 128])."""
    from paddle_tpu.models import causal_lm
    if not hasattr(causal_lm, "latent_attention"):
        raise NotImplementedError(
            "this program's causal_lm has neither latent attention nor "
            "hyper-connections: it cannot build %s" % (cfg["name"],))
    fetches = base.build(fluid, cfg, traffic)
    layers = fluid.layers
    block = fluid.default_main_program().global_block()
    core = next(op for op in block.ops if op.type == "fused_attention")

    def head0(slot):
        return layers.crop(block.var(core.input(slot)[0]),
                           shape=[-1, -1, 1, -1])

    fetches["queries"] = layers.concat([head0("Q"), head0("QRope")], axis=3)
    fetches["keys"] = layers.concat([head0("K"), head0("KRope")], axis=3)
    post = [op for op in block.ops if op.type == "mhc_post"][1]
    n, d = cfg["hc_mult"], cfg["hidden_size"]

    def probe(stream):          # [B, T, n x D] -> [B, T, n x PROBE_COLUMNS]
        return layers.concat(
            [layers.crop(stream, shape=[-1, -1, PROBE_COLUMNS],
                         offsets=[0, 0, i * d]) for i in range(n)], axis=2)

    fetches["streams"] = probe(block.var(post.output("Out")[0]))
    fetches["coefficients"] = block.var(post.input("Coef")[0])
    routed = next(op for op in block.ops if op.type == "moe_ffn")
    fetches["experts"] = layers.crop(block.var(routed.output("Out")[0]),
                                     shape=[-1, -1, PROBE_COLUMNS])
    # the streams' readout as the final norm reads it: the norm divides a
    # scale out again, so neither the logits nor the loss see it
    readout = next(op for op in block.ops if op.type == "mhc_reduce")
    fetches["streams_out"] = probe(block.var(readout.input("X")[0]))
    fetches["readout"] = layers.crop(block.var(readout.output("Out")[0]),
                                     shape=[-1, -1, PROBE_COLUMNS])
    return fetches


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part. Latent attention:
    its seven projections (q down and up, kv down and up with the rotary
    key, out) and the core over the causal pairs, a query head 192 wide on
    the scores and 128 on the values. The hyper-connections' projections
    ([n D] x [n^2 + 2n], two a layer) and their mixing (n^2 + 2n
    multiply-adds a channel). The leading dense FFN at its own width; in
    the layers after it the router at its published width, the held experts
    a token is expected to reach (4 x 8 / 64 of them) and the shared
    expert. The head over the held words."""
    c = _resolved(cfg)
    d, f, h = c["hidden_size"], c["intermediate_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    layers, n = c["num_hidden_layers"], c["hc_mult"]
    routed = c["ffn_layers"].count("experts")
    t = traffic["seq_len"]
    k = n * n + 2 * n
    return {
        "attention_projections": layers * (
            d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d),
        "attention": layers * shared.visible_pairs(t, None) / t
        * h * (dn + dr + dv),
        "hyper_connection_projections": 2 * layers * n * d * k,
        "hyper_connection_mixing": 2 * layers * d * k,
        "dense_ffn": c["ffn_layers"].count("dense") * 3 * d
        * c["dense_intermediate_size"],
        "router": routed * d * c["num_experts"],
        "experts": routed * shared.held_share(c) * 3 * d * f,
        "shared_expert": routed * 3 * d
        * c["shared_expert_intermediate_size"],
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes. Embedding lookup, norms, rotary, softmax,
    the Sinkhorn steps, routing and the optimizer are not counted."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels, counting only
    the causal pairs, a pair and query head: the forward kernel's scores at
    192 and weighted sum at 128, 2 x (192 + 128); dK/dV's scores, dV (128),
    dP (128) and dK (192), 2 x (192 + 128 + 128 + 192); dQ's scores, dP and
    dQ, 2 x (192 + 128 + 192). The rotary part's 64 is a contraction the
    MXU pads to 128 and edge blocks compute masked pairs too, so a share of
    the peak from this cannot pass 100 %."""
    c = _resolved(cfg)
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    v = c["v_head_dim"]
    pairs = c["num_hidden_layers"] * shared.visible_pairs(
        traffic["seq_len"], None) * traffic["batch"] \
        * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 2 * (qk + v) * pairs,
            "ptpu_flash_bwd_dkdv": 2 * (qk + v + v + qk) * pairs,
            "ptpu_flash_bwd_dq": 2 * (qk + v + qk) * pairs}


def mhc_kernel_bytes(cfg, traffic):
    """{kernel: bytes a step} that the kernels of MHC_KERNELS must move to
    and from HBM: each stream array [T, n D] and each [T, D] array a kernel
    reads or writes, once, at 2 bytes an element under the configuration's
    AMP, and the [T, 128] float32 arrays of coefficients, as the compiled
    step's layouts hold them (AOT compile for a described v5e, PR 43: no
    operand of these calls of a stream's size lies in VMEM; the [T, 128]
    float32 arrays, 2 MiB, sometimes do, and are not a hundredth of a
    stream). pre_fwd reads X and writes h and z; post_fwd reads X, y and the
    coefficients and writes X'; post_bwd reads dX', X, y and the
    coefficients and writes dX, dy and the dots; pre_bwd reads X, dX, dh, z
    and dz and writes dX and dz; expand and reduce run once forward and
    once backward a step each; the two coefficient kernels read and write
    [24, T] float32 arrays (z and the coefficients; z, their gradient, dHt
    and a result nothing reads), which the VPU and not the bytes bound. Phi
    (2 x 3.5 MiB a call) is not counted."""
    c = _resolved(cfg)
    n, rows = c["hc_mult"], traffic["batch"] * traffic["seq_len"]
    one = rows * c["hidden_size"] * 2
    stream, coef = n * one, rows * 128 * 4
    sub = 2 * c["num_hidden_layers"]
    small = rows * (n * n + 2 * n) * 4
    return {"ptpu_mhc_coeffs_fwd": sub * 2 * small,
            "ptpu_mhc_coeffs_bwd": sub * 4 * small,
            "ptpu_mhc_pre_fwd": sub * (stream + one + coef),
            "ptpu_mhc_post_fwd": sub * (2 * stream + one + coef),
            "ptpu_mhc_post_bwd": sub * (3 * stream + 2 * one + 2 * coef),
            "ptpu_mhc_pre_bwd": sub * (3 * stream + one + 3 * coef),
            "ptpu_mhc_expand": 2 * (stream + one),
            "ptpu_mhc_reduce": 2 * (stream + one)}


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

    eps, n = c["rms_norm_eps"], c["hc_mult"]
    h = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rkv, scaling = c["kv_lora_rank"], c["rope_scaling"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d = c["hidden_size"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    m = plain.yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * m * m
    table = plain.yarn_inv_freq(scaling, c["rope_theta"], dr)
    table_scale = plain.yarn_mscale(scaling["factor"], scaling["mscale"]) / m

    def turn(x, pos_row):
        return plain.rope(x, pos_row[None], c["rope_theta"], inv_freq=table,
                          interleaved=c["rope_interleaved"],
                          table_scale=table_scale)

    def attention(a, pos_row, wq_a, q_a_norm, wq_b, wkv_a, kv_a_norm, wkv_b,
                  wo):                                           # a [T, D]
        q = (plain.rms_norm(a @ wq_a, q_a_norm, eps) @ wq_b).reshape(
            1, t, h, dn + dr)
        ckv = a @ wkv_a
        kv = (plain.rms_norm(ckv[:, :rkv], kv_a_norm, eps) @ wkv_b).reshape(
            t, h, dn + dv)
        k_r = turn(ckv[:, rkv:].reshape(1, t, 1, dr), pos_row)[0, :, 0]
        q = jnp.concatenate([q[..., :dn], turn(q[..., dn:], pos_row)], -1)[0]
        probe = q[:, :1], jnp.concatenate([kv[:, :1, :dn], k_r[:, None]], -1)

        def one_head(args):                 # [T, 192], [T, 256]
            qh, kvh = args
            kh = jnp.concatenate([kvh[:, :dn], k_r], -1)
            s = jnp.where(causal, (qh @ kh.T) * scale, -jnp.inf)
            return jax.nn.softmax(s, -1) @ kvh[:, dn:]

        ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                     kv.transpose(1, 0, 2)))
        return ctx.transpose(1, 0, 2).reshape(t, h * dv) @ wo, probe

    def read(x, hc):
        """plain.passes' `read` on x [B, T, n, D]; the coefficients too."""
        pre, post, res = plain.hyper_connection(x, *hc, c)
        return jnp.einsum("bti,btid->btd", pre, x), lambda y: \
            jnp.einsum("btij,btjd->btid", res, x) \
            + post[..., None] * y[:, :, None], res

    load = jnp.zeros((c["num_experts"],), jnp.int32)
    margin = jnp.full((b * t,), jnp.inf)
    experts = None
    with jax.default_matmul_precision("highest"):
        x = take(1)[0][ids]
        x = jnp.broadcast_to(x[:, :, None], (b, t, n, d))
        for i in range(c["num_hidden_layers"]):
            hc = take(3)
            got, write, _ = read(x, hc)
            a = plain.rms_norm(got, take(1)[0], eps)
            weights = take(7)
            out, probe = jax.lax.map(
                lambda xs: attention(xs[0], xs[1], *weights), (a, pos))
            x = write(out)
            hc = take(3)
            got, write, res = read(x, hc)
            mid = plain.rms_norm(got, take(1)[0], eps)
            if c["ffn_layers"][i] == "dense":
                wg, wu, wd = take(3)
                x = write((jax.nn.silu(mid @ wg) * (mid @ wu)) @ wd)
                queries, keys = probe
                streams, h_res = x[..., :PROBE_COLUMNS], res
                continue
            mid = mid.reshape(b * t, d)
            router, bias, wg, wu, wd = take(5)
            out, _, _, ld = plain.routed_experts(mid, router, wg, wu, wd, c,
                                                 expert_bias=bias)
            scores = jax.nn.sigmoid(mid @ router) + bias
            here = _router_margin(scores, c)
            if experts is None:
                # the first expert layer's own, with the margin between the
                # fourth and the fifth of ALL 64: a trade among experts held
                # elsewhere moves the sum the chosen scores are divided by,
                # and a held expert's weight with it
                experts = out[:, :PROBE_COLUMNS]
                experts_margin = _router_margin(scores, dict(
                    c, first_expert=0, experts_held=c["num_experts"]))
            out = out + plain.shared_expert(mid, *take(3))
            margin = jnp.minimum(margin, here)
            x = write(out.reshape(b, t, d))
            load = load + ld
        streams_out = x[..., :PROBE_COLUMNS]
        x = x.sum(2)
        readout = x[..., :PROBE_COLUMNS]
        w_f, w_lm = take(2)

        def head(xs):                       # HEAD_ROWS rows: [R, D], [R, 1]
            logits = plain.rms_norm(xs[0], w_f, eps) @ w_lm
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
            "streams": streams.reshape(b, t, -1),
            "coefficients": h_res.reshape(b, t, n * n), "readout": readout,
            "streams_out": streams_out.reshape(b, t, -1),
            "experts": experts.reshape(b, t, -1),
            "experts_margin": experts_margin.reshape(b, t),
            "router_margin": margin.reshape(b, t)}


def check(cfg, first, want, scalars):
    """configs/lfm2.py's check (the loss; the logits of the tokens whose
    routing is decided in every expert layer, by their largest and by their
    mean error; `queries_keys`, head 0's query and key of the dense layer's
    attention, 192 each after rotary; `dropless`) and more. At the tokens
    the logits are compared at (over all tokens a moved assignment's token
    is off by a fifth): `readout`, the streams' sum as the final norm reads
    it (the norm divides a wrong scale out again, so nothing after it sees
    one), and `streams_out`, the four streams the last layer leaves (H_res
    transposed leaves their sum as it is, rows and columns both summing to
    1, and the streams not). `experts`, what the first expert layer's held
    experts add to a token, at the tokens whose top 4 of ALL 64 that layer
    decides by the margin: under the held-set margin a trade among experts
    held elsewhere still moves the renormalising sum, and a held expert's
    weight by up to a tenth (6.4e-2 healthy); the weights the chosen
    experts carry show here at their full size, where the logits have them
    diluted by a shared expert and four streams. And three limits on the
    dense layer, before which no router lies: `streams`, the four residual
    streams after it (their first PROBE_COLUMNS channels, at every
    position); `h_res`, its second hyper-connection's H_res against the
    reference's; and `h_res_sums`, how far that H_res's rows and columns
    are from summing to 1 in the program (the 20 Sinkhorn steps leave the
    rows at float32's rounding and the columns at the iteration's error)."""
    c = _resolved(cfg)
    n = c["hc_mult"]
    tolerance = cfg["reference"]["tolerance"]
    h_res = np.asarray(first["coefficients"],
                       np.float32)[..., 2 * n:2 * n + n * n]
    load = np.asarray(first["expert_load"], np.int64)
    margin = np.asarray(want["router_margin"])
    decided = margin >= cfg["reference"]["router_margin"]
    first_decided = np.asarray(want["experts_margin"]) \
        >= cfg["reference"]["router_margin"]

    def compared(x, res):
        return {"loss": x["loss"], "logits": x["logits"][decided],
                "queries_keys": np.concatenate(
                    [np.asarray(x[name], np.float32)
                     for name in ("queries", "keys")], -1),
                "streams": np.asarray(x["streams"], np.float32),
                "readout": np.asarray(x["readout"], np.float32)[decided],
                "streams_out": np.asarray(x["streams_out"],
                                          np.float32)[decided],
                "experts": np.asarray(x["experts"],
                                      np.float32)[first_decided],
                "h_res": res}

    verdicts, found = checks.training(
        cfg, compared(first, h_res),
        compared(want, np.asarray(want["coefficients"], np.float32)),
        scalars)
    got, ref = (np.asarray(x["logits"], np.float32)[decided]
                for x in (first, want))
    mean_error = float(np.abs(got - ref).mean() / np.abs(ref).mean())
    square = h_res.reshape(h_res.shape[:-1] + (n, n))
    sums = float(max(np.abs(square.sum(-1) - 1).max(),
                     np.abs(square.sum(-2) - 1).max()))
    verdicts["reference"] = verdicts["reference"] \
        and mean_error <= tolerance["logits_mean"] \
        and sums <= tolerance["h_res_sums"]
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
        "tokens compared (router margin >= %g in every expert layer; over " \
        "all tokens they are off by %.2e), experts of %d (that margin " \
        "over all experts in the first expert layer); %d of %d " \
        "assignments counted, " \
        "the %d held experts computed %d rows (reference %d; %d..%d an " \
        "expert), at least %d assignments went to another expert than in " \
        "the reference; logits by margin >= %s; H_res rows and columns off " \
        "1 by %.3e (tolerance %g)" % (
            mean_error, tolerance["logits_mean"], decided.sum(), tokens,
            cfg["reference"]["router_margin"],
            checks.normalised_error(first["logits"], want["logits"]),
            first_decided.sum(), load.sum(), assignments, c["experts_held"],
            load[held].sum(), want_load[held].sum(), load[held].min(), load[held].max(), moved,
            ", ".join(by_margin), sums, tolerance["h_res_sums"])
    return verdicts, found
