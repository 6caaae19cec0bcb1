"""GLM-4.7-Flash (paddle_tpu/models/causal_lm.py) as the benchmark trains it:
one chip's share of a layer that eight chips divide: the leading dense
layer, four expert layers and the multi-token-prediction module, which
shares the embedding and the head with the trunk. `samples_per_step` is
configs/causal_lm.py's; this file adds the batch (T + 2 ids a sequence: the
inputs, the next tokens and the tokens after those), the operations a token
with the module's counted, the operations of the three flash kernels at a
head of 192 + 64 on a value of 256 over six cores, the bytes of the
embedding's gradient for two lookups of one table, the benchmark's copy of
the plain float32 reference, blocked so that it fits beside the training
state (attention one query head at a time, the held experts one at a time,
the two passes of the head in blocks of rows), and the cell's check, which
also holds the gradients of the two parameters the module shares with the
trunk to the sum of their two uses. Sizes are in the configuration's .json
under the keys of the model's `config.json`; the counts of experts and
words there are what this chip holds (`share`).
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
# tokens a step are target positions, counted once: a position has two
# losses and is one token
samples_per_step = base.samples_per_step
# every assignment a held expert computed, the module's layer's among them:
# the `expert_load` fetch sums the five expert layers
expert_matmul_ops = base.expert_matmul_ops
MARGINS = lfm2.MARGINS
# the spread the module's hnorm weight is drawn with around 1: at exactly 1
# N_h(N_f(s)) is the identity and no check can see it dropped (`build`; the
# .json's `assumed.norm_weights`)
HNORM_RANGE = 0.1
_router_margin = lfm2._router_margin        # the held-set margin on s + b


def build(fluid, cfg, traffic):
    """Builds the training program in the current guard, after asking the
    program for the module: a program from before it refuses
    `num_nextn_predict_layers` 1 in `resolve`, this names the cause.
    Fetches: the loss L = L_main + lambda L_mtp, its two terms, the logits
    of the first PROBE_COLUMNS words at every position of the trunk's pass
    of the head and of the module's, the experts' assignment counts over
    the five expert layers; of the leading dense layer, before which no
    router lies, head 0's query and key as its attention core reads them,
    the part without position then the rotary part, 256 each ([B, T, 1,
    256]); the first PROBE_COLUMNS channels of what the module's layer
    reads, W_eh [N_e(Emb(t_(i+1))); N_h(N_f(s_i))]; and, of the backward
    pass, the two parameters with two uses, before the clip: the head's
    gradient at the first PROBE_COLUMNS words (`head_grad` [D, 128]: two
    matmuls summed), and the first PROBE_COLUMNS channels of the
    embedding's (`embedding_grad` [V, 128]: two scatter-adds summed)
    beside the same channels of what each lookup's scatter-add read
    (`rows_grad` [2 B, T, 128]: the inputs' rows, then the next tokens')."""
    from paddle_tpu.models import causal_lm
    if "mtp_loss_weight" not in causal_lm.DEFAULTS:
        raise NotImplementedError(
            "this program's causal_lm builds no multi-token-prediction "
            "module (num_nextn_predict_layers): it cannot build %s"
            % (cfg["name"],))
    fluid.default_main_program().enable_mixed_precision()
    extras = {}
    loss, logits, load = causal_lm.build_train(
        cfg, traffic["seq_len"], learning_rate=cfg["learning_rate"],
        beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
        epsilon=cfg["adam_epsilon"], clip_norm=cfg["clip_norm"],
        extras=extras)
    # the configuration's own start for ONE weight (`assumed.norm_weights`):
    # the module's hnorm is drawn around the 1 every norm starts from, by a
    # second initialiser behind the builder's in the startup program
    startup = fluid.default_startup_program().global_block()
    fluid.initializer.Normal(1.0, HNORM_RANGE)(
        startup.var("layer_%d.hnorm" % cfg["num_hidden_layers"]), startup)
    layers = fluid.layers
    columns = min(PROBE_COLUMNS, cfg["vocab_size"])
    block = fluid.default_main_program().global_block()
    core = next(op for op in block.ops if op.type == "fused_attention")

    def head0(slot):
        return layers.crop(block.var(core.input(slot)[0]),
                           shape=[-1, -1, 1, -1])

    channels = min(PROBE_COLUMNS, cfg["hidden_size"])
    rows_grad = [layers.crop(block.var(op.output("Out")[0] + "@GRAD"),
                             shape=[-1, -1, channels])
                 for op in block.ops if op.type == "lookup_table"]

    return {
        "loss": loss, "main_loss": extras["main_loss"],
        "mtp_loss": extras["mtp_loss"],
        "logits": layers.crop(logits, shape=[-1, -1, columns]),
        "mtp_logits": layers.crop(extras["mtp_logits"],
                                  shape=[-1, -1, columns]),
        "expert_load": load,
        "queries": layers.concat([head0("Q"), head0("QRope")], axis=3),
        "keys": layers.concat([head0("K"), head0("KRope")], axis=3),
        "mtp_input": layers.crop(extras["mtp_input"],
                                 shape=[-1, -1, channels]),
        "head_grad": layers.crop(block.var("head@GRAD"),
                                 shape=[-1, columns]),
        "embedding_grad": layers.crop(block.var("embedding@GRAD"),
                                      shape=[-1, channels]),
        "rows_grad": layers.concat(rows_grad, axis=0)}


def make_batch(cfg, traffic, key):
    """Every sequence at full length, cut from ONE draw of seq_len + 2
    uniform token ids in [0, vocab_size) (the configuration's vocab_size is
    chip 0's slice of the published vocabulary): the first seq_len are the
    inputs t_i, the next window the labels t_(i+1), which the module also
    embeds, and the last the second labels t_(i+2)."""
    b, t = traffic["batch"], traffic["seq_len"]
    tok = jax.random.randint(key, (b, t + 2), 0, cfg["vocab_size"], jnp.int32)
    return {"ids": tok[:, :-2],
            "pos": jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t)),
            "labels": tok[:, 1:-1, None], "labels_next": tok[:, 2:, None]}


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part, the module's
    among them (training runs it at every position). Latent attention in
    the five trunk layers and the module's: its seven projections (q down
    and up, kv down and up with the rotary key, out) and the core over the
    causal pairs, a query head 256 wide on the scores and 256 on the
    values. The leading dense FFN at its own width; in the four expert
    layers and the module's the router at its published width, the held
    experts a token is expected to reach (4 x 8 / 64 of them) and the
    shared expert. `eh_proj` [2 D, D]. The head over the held words, twice:
    the trunk's pass and the module's."""
    c = _resolved(cfg)
    d, f, h = c["hidden_size"], c["intermediate_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    layers = c["num_hidden_layers"] + c["mtp_layers"]
    routed = c["ffn_layers"].count("experts")
    t = traffic["seq_len"]
    return {
        "attention_projections": layers * (
            d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d),
        "attention": layers * shared.visible_pairs(t, None) / t
        * h * (dn + dr + dv),
        "dense_ffn": c["ffn_layers"].count("dense") * 3 * d
        * c["dense_intermediate_size"],
        "router": routed * d * c["num_experts"],
        "experts": routed * shared.held_share(c) * 3 * d * f,
        "shared_expert": routed * 3 * d
        * c["shared_expert_intermediate_size"],
        "eh_proj": c["mtp_layers"] * 2 * d * d,
        "head": (1 + c["mtp_layers"]) * d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token (a target position, counted once, with both of its losses),
    by configs/transformer_base.py's convention: two a multiply-add, three
    passes. Embedding lookups, norms, rotary, softmax, routing and the
    optimizer are not counted. 3 x 2 x 478.5e6 = 2871e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels over the six
    cores (five trunk layers and the module's), counting only the causal
    pairs, a pair and query head, of the WORK: scores over the whole head
    of 192 + 64 = 256 and a weighted sum over values of 256, 2 x (256 +
    256) forward; dK/dV's scores, dV, dP and dK, 2 x 4 x 256; dQ's scores,
    dP and dQ, 2 x 3 x 256; whichever form the core takes
    (ops/pallas_kernels.py latent_form). Edge blocks compute masked
    pairs too, so a share of the peak from this cannot pass 100 %."""
    c = _resolved(cfg)
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    v = c["v_head_dim"]
    pairs = (c["num_hidden_layers"] + c["mtp_layers"]) * shared.visible_pairs(
        traffic["seq_len"], None) * traffic["batch"] \
        * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 2 * (qk + v) * pairs,
            "ptpu_flash_bwd_dkdv": 2 * (qk + v + v + qk) * pairs,
            "ptpu_flash_bwd_dq": 2 * (qk + v + qk) * pairs}


def embedding_grad_bytes(cfg, traffic):
    """Bytes a step that the embedding's gradient has to move, counted from
    the work, for TWO lookups of one table (the inputs, and the next tokens
    the module embeds): the dense [V, D] float32 gradient written once and
    the [tokens, D] rows of each lookup's output gradient read once, 4
    bytes an element. An implementation that runs a kernel a lookup writes
    the table twice and adds the two; that is its own, and not counted.
    19360 x 2048 x 4 = 158.6e6 written + 2 x 8192 x 2048 x 4 = 134.2e6
    read at two sequences."""
    c = _resolved(cfg)
    tokens = traffic["batch"] * traffic["seq_len"]
    return 4 * cfg["hidden_size"] * (
        cfg["vocab_size"] + (1 + c["mtp_layers"]) * tokens)


def reference(cfg, traffic, params, batch):
    """What `build` fetches of the forward pass, from the plain float32
    forward of paddle_tpu/models/causal_lm_reference.py on the program's
    weights, with the same arithmetic cut into blocks (module docstring); a
    test holds it equal to the unblocked reference. Of the backward pass,
    `head_grad`: dL/dW_out at the first PROBE_COLUMNS words in closed form
    from the two passes of the head, (N_f(s)^T (softmax - onehot) + lambda
    N_s(y)^T (softmax' - onehot')) / tokens, which a test holds equal to
    jax.grad of the unblocked reference; and `lookups` [2 B, T], the ids
    each of the two lookups read (the inputs, then the next tokens), for
    `check` to scatter the program's own row gradients by."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, h = c["rms_norm_eps"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rkv = c["kv_lora_rank"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d = c["hidden_size"]
    trunk = c["num_hidden_layers"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = (dn + dr) ** -0.5

    def turn(x, pos_row):
        return plain.rope(x, pos_row[None], c["rope_theta"],
                          interleaved=c["rope_interleaved"])

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

        def one_head(args):                 # [T, 256], [T, 192 + 256]
            qh, kvh = args
            kh = jnp.concatenate([kvh[:, :dn], k_r], -1)
            s = jnp.where(causal, (qh @ kh.T) * scale, -jnp.inf)
            return jax.nn.softmax(s, -1) @ kvh[:, dn:]

        ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                     kv.transpose(1, 0, 2)))
        return ctx.transpose(1, 0, 2).reshape(t, h * dv) @ wo, probe

    load = jnp.zeros((c["num_experts"],), jnp.int32)
    margin = jnp.full((b * t,), jnp.inf)
    with jax.default_matmul_precision("highest"):
        embedding = take(1)[0]
        x = embedding[ids]
        for i in range(trunk + c["mtp_layers"]):
            if i == trunk:
                # the module: behind the final norm, on the next tokens'
                # embeddings and the normed state
                w_f = take(1)[0]
                state = plain.rms_norm(x, w_f, eps)
                x = mtp_input = plain.mtp_input(
                    embedding[batch["labels"][..., 0]], state, *take(3), eps)
            a = plain.rms_norm(x, take(1)[0], eps)
            weights = take(7)
            out, probe = jax.lax.map(
                lambda xs: attention(xs[0], xs[1], *weights), (a, pos))
            x = x + out
            mid = plain.rms_norm(x, take(1)[0], eps)
            if c["ffn_layers"][i] == "dense":
                wg, wu, wd = take(3)
                x = x + (jax.nn.silu(mid @ wg) * (mid @ wu)) @ wd
                queries, keys = probe
                continue
            mid = mid.reshape(b * t, d)
            router, bias, wg, wu, wd = take(5)
            out, _, _, ld = plain.routed_experts(mid, router, wg, wu, wd, c,
                                                 expert_bias=bias)
            margin = jnp.minimum(margin, _router_margin(
                jax.nn.sigmoid(mid @ router) + bias, c))
            out = out + plain.shared_expert(mid, *take(3))
            x = x + out.reshape(b, t, d)
            load = load + ld
        w_s, w_lm = take(2)

        def head(w_norm):
            def rows(xs):                   # HEAD_ROWS rows: [R, D], [R, 1]
                state = xs[0] if w_norm is None \
                    else plain.rms_norm(xs[0], w_norm, eps)
                logits = state @ w_lm
                logp = jax.nn.log_softmax(logits, -1)
                nll = -jnp.take_along_axis(logp, xs[1], axis=-1)
                # d nll.sum() / d w_lm at the probed words, in closed form:
                # state^T (softmax - onehot)
                dlogits = jnp.exp(logp[:, :PROBE_COLUMNS]) - (
                    xs[1] == jnp.arange(min(PROBE_COLUMNS, logp.shape[1])))
                return nll.sum(), logits[:, :PROBE_COLUMNS], \
                    state.T @ dlogits
            return rows

        n = min(HEAD_ROWS, b * t)
        # the trunk's state is normed already (the module read it so): its
        # pass of the head norms nothing more
        nll, probe, grad = jax.lax.map(head(None), (
            state.reshape(-1, n, d), batch["labels"].reshape(-1, n, 1)))
        nll_mtp, probe_mtp, grad_mtp = jax.lax.map(head(w_s), (
            x.reshape(-1, n, d), batch["labels_next"].reshape(-1, n, 1)))
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")
    main_loss, mtp_loss = nll.sum() / (b * t), nll_mtp.sum() / (b * t)
    return {"loss": main_loss + c["mtp_loss_weight"] * mtp_loss,
            "main_loss": main_loss, "mtp_loss": mtp_loss,
            "logits": probe.reshape(b, t, -1),
            "mtp_logits": probe_mtp.reshape(b, t, -1), "expert_load": load,
            "queries": queries, "keys": keys,
            "mtp_input": mtp_input[..., :PROBE_COLUMNS],
            "head_grad": (grad.sum(0) + c["mtp_loss_weight"]
                          * grad_mtp.sum(0)) / (b * t),
            "lookups": jnp.concatenate([ids, batch["labels"][..., 0]]),
            "router_margin": margin.reshape(b, t)}


def check(cfg, first, want, scalars):
    """checks.training on the loss L and its two terms (`main_loss`,
    `mtp_loss`), on the trunk's and the module's logits of the tokens whose
    routing is decided in all five expert layers (the held-set margin on s
    + b at least `reference.router_margin`), by their largest error and
    (`logits_mean`, `mtp_logits_mean`) by their mean error over their mean
    size; on `queries_keys`, head 0's query and key of the dense layer's
    core, 256 each after rotary, at every position (no router lies before
    them); and on `mtp_input`, what the module's layer reads, at the
    decided tokens (the trunk's four expert layers lie before it).
    Of the backward pass, the two parameters the module shares with the
    trunk: `head_grad_mean`, the head's gradient before the clip at the
    first 128 words against the reference's closed form (two matmuls
    summed), by its mean error over its mean size (its largest values are
    single tokens' states, and a token routed otherwise than in the
    reference would decide a largest-error reading alone); and
    `embedding_grad`, 128 channels of the table's gradient against the
    float64 sums of the rows the program's own two scatter-adds read, each
    scattered by its lookup's ids: what the two summed have to be, whatever
    the layers behind them gave. `dropless`: every one of the top-4
    assignments of every token in the five expert layers was counted, and
    the rows the held experts computed are the assignments that fell on
    them."""
    c = _resolved(cfg)
    tolerance = cfg["reference"]["tolerance"]
    load = np.asarray(first["expert_load"], np.int64)
    margin = np.asarray(want["router_margin"])
    decided = margin >= cfg["reference"]["router_margin"]

    def compared(x):
        return {"loss": x["loss"], "main_loss": x["main_loss"],
                "mtp_loss": x["mtp_loss"],
                "logits": np.asarray(x["logits"], np.float32)[decided],
                "mtp_logits": np.asarray(x["mtp_logits"],
                                         np.float32)[decided],
                "queries_keys": np.concatenate(
                    [np.asarray(x[name], np.float32)
                     for name in ("queries", "keys")], -1),
                "mtp_input": np.asarray(x["mtp_input"],
                                        np.float32)[decided]}

    got, ref = compared(first), compared(want)
    got["embedding_grad"] = np.asarray(first["embedding_grad"], np.float32)
    rows = np.asarray(first["rows_grad"], np.float64)
    summed = np.zeros(got["embedding_grad"].shape, np.float64)
    np.add.at(summed, np.asarray(want["lookups"]).ravel(),
              rows.reshape(-1, rows.shape[-1]))
    ref["embedding_grad"] = summed
    verdicts, found = checks.training(cfg, got, ref, scalars)
    got["head_grad"], ref["head_grad"] = (
        np.asarray(x["head_grad"], np.float32) for x in (first, want))
    means = {name + "_mean": float(np.abs(got[name] - ref[name]).mean()
                                   / np.abs(ref[name]).mean())
             for name in ("logits", "mtp_logits", "head_grad")}
    verdicts["reference"] = verdicts["reference"] and all(
        means[name] <= tolerance[name] for name in means)
    tokens = decided.size
    routed = c["ffn_layers"].count("experts")
    assignments = tokens * c["num_experts_per_tok"] * routed
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
    found += "; %s; logits of %d of %d tokens compared (router margin >= " \
        "%g in all %d expert layers; over all tokens the trunk's are off by " \
        "%.2e and the module's by %.2e); %d of %d assignments counted, the " \
        "%d held experts computed %d rows (reference %d; %d..%d an " \
        "expert), at least %d assignments went to another expert than in " \
        "the reference; logits by margin >= %s" % (
            ", ".join("%s off by %.3e (tolerance %g)"
                      % (name, means[name], tolerance[name])
                      for name in sorted(means)),
            decided.sum(), tokens, cfg["reference"]["router_margin"], routed,
            checks.normalised_error(first["logits"], want["logits"]),
            checks.normalised_error(first["mtp_logits"], want["mtp_logits"]),
            load.sum(), assignments, c["experts_held"], load[held].sum(),
            want_load[held].sum(), load[held].min(), load[held].max(), moved,
            ", ".join(by_margin))
    return verdicts, found
