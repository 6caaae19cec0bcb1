"""Ouro-2.6B (paddle_tpu/models/causal_lm.py; `model_type: ouro`,
arXiv:2510.25741) as the benchmark trains it: one stack of dense layers run
`total_ut_steps` times over the same weights, sandwich norms, an exit gate
that weighs the passes' cross-entropies. `make_batch` and `samples_per_step`
are configs/causal_lm.py's; this file adds the build (it fetches every pass's
logits and the exit distribution), the operations a token over all passes
and heads, the flash kernels' operations by what the program's counters say
was lowered, the benchmark's copy of the plain float32 reference, blocked so
that it runs in set-up beside the training state (attention one (sequence,
head) at a time, the heads in blocks of rows), and the check: a dense model
routes nothing, so every position is compared.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks, manifest

base = manifest.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "causal_lm.py"))

SAMPLE = base.SAMPLE
PROBE_COLUMNS = base.PROBE_COLUMNS
HEAD_ROWS = 1024        # rows of the heads' logits alive at a time
samples_per_step = base.samples_per_step
make_batch = base.make_batch
embedding_grad_bytes = base.embedding_grad_bytes


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def build(fluid, cfg, traffic):
    """The training program in the current guard; every step fetches the
    loss, the logits of the first PROBE_COLUMNS words of every pass at every
    position ([B, P x T, 128], pass t's rows (t - 1) T .. t T - 1) and the
    exit distribution [B, P, T]. A program from before the looped stack
    would build eight plain layers from this configuration: it is asked
    first."""
    from paddle_tpu.models import causal_lm
    if "total_ut_steps" not in causal_lm.DEFAULTS:
        raise NotImplementedError(
            "this program's causal_lm has no total_ut_steps: it cannot "
            "build %s" % (cfg["name"],))
    fluid.default_main_program().enable_mixed_precision()
    extras = {}
    loss, _, _ = causal_lm.build_train(
        cfg, traffic["seq_len"], learning_rate=cfg["learning_rate"],
        beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
        epsilon=cfg["adam_epsilon"], clip_norm=cfg["clip_norm"],
        extras=extras)
    probe = fluid.layers.concat([
        fluid.layers.crop(logits, shape=[
            -1, -1, min(PROBE_COLUMNS, cfg["vocab_size"])])
        for logits in extras["pass_logits"]], axis=1)
    return {"loss": loss, "logits": probe, "exit_p": extras["exit_p"]}


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part, over all passes:
    a layer application is the four attention projections, the SwiGLU's
    three matrices and the attention core over the causal pairs (scores and
    weighted sum, half of T keys on average); the head once a pass. The
    exit gate (hidden_size a pass), norms, rotary and the loss are not
    counted."""
    c = _resolved(cfg)
    d, f, t = c["hidden_size"], c["intermediate_size"], traffic["seq_len"]
    h, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    applications = c["total_ut_steps"] * c["num_hidden_layers"]
    return {
        "projections": applications * d * hd * (2 * h + 2 * hkv),
        "feed_forward": applications * 3 * d * f,
        "attention": applications * 2 * h * hd * (t + 1) / 2,
        "heads": c["total_ut_steps"] * d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes. The model's own operations: four passes and
    four heads; forward ops that recomputation runs again are not counted,
    so recomputation lowers `mfu`. At 8 layers and T=4096: 3 x 2 x 2315.1e6
    = 13.89e9."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def _lowered(counter, **labels):
    """Sum of the program's counter `counter` over the samples that carry
    `labels`, or None where the program has no such counter."""
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get(counter)
    if not family:
        return None
    return sum(value for have, value in family["samples"]
               if all(have.get(k) == v for k, v in labels.items()))


def flash_forward_runs():
    """How many times the program runs a layer's flash forward kernel a
    step, by its own counters: once in the forward pass; once more where
    recomputation replays the op's segment (`ptpu_remat_ops_total`); once
    more where the grad op replays the forward rule and keeps no
    linearization (`ptpu_lowering_grad_ops_total{path="replayed"}`).
    Ratios of lowerings, so that a program lowered twice reads the same."""
    forward = _lowered("ptpu_remat_ops_total", kind="forward",
                       op="fused_attention")
    if not forward:
        return 1.0
    again = _lowered("ptpu_remat_ops_total", kind="replayed",
                     op="fused_attention") \
        + (_lowered("ptpu_lowering_grad_ops_total", path="replayed",
                    op="fused_attention") or 0.0)
    return 1.0 + again / forward


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels, counting only
    the causal pairs: 4, 8 and 6 x D a pair and query head
    (configs/smallthinker.py has why), a layer application each, and the
    forward kernel as many times as the program's counters say it runs
    (`flash_forward_runs`), never more. Edge blocks compute masked pairs
    too, so a share of the peak from this cannot pass 100 %."""
    c = _resolved(cfg)
    t = traffic["seq_len"]
    pairs = c["total_ut_steps"] * c["num_hidden_layers"] * t * (t + 1) // 2 \
        * traffic["batch"] * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 4 * c["head_dim"] * pairs
            * flash_forward_runs(),
            "ptpu_flash_bwd_dkdv": 8 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dq": 6 * c["head_dim"] * pairs}


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from the plain float32 forward of
    paddle_tpu/models/causal_lm_reference.py (`passes` and `loss_fn`) on
    the program's weights, with the same arithmetic cut into blocks:
    attention one (sequence, head) at a time, the head, its loss and the
    gate HEAD_ROWS rows at a time. A test holds it equal to the unblocked
    reference."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, hd = c["rms_norm_eps"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d, passes = c["hidden_size"], c["total_ut_steps"]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def attention(a, pos_row, wq, wk, wv, wo):           # a [T, D]
        q = (a @ wq).reshape(1, t, h, hd)
        k, v = ((a @ w).reshape(1, t, hkv, hd) for w in (wk, wv))
        q, k = (plain.rope(x, pos_row[None], c["rope_theta"])
                for x in (q, k))
        q, k, v = (x[0].transpose(1, 0, 2) for x in (q, k, v))   # [H, T, hd]

        def one_head(args):
            qh, head = args
            kh, vh = k[head // (h // hkv)], v[head // (h // hkv)]
            s = jnp.where(causal, (qh @ kh.T) * hd ** -0.5, -jnp.inf)
            return jax.nn.softmax(s, -1) @ vh

        ctx = jax.lax.map(one_head, (q, jnp.arange(h)))
        return ctx.transpose(1, 0, 2).reshape(t, h * hd) @ wo

    embedding = take(1)[0]
    weights = [take(11) for _ in range(c["num_hidden_layers"])]
    w_f, w_g, b_g, w_lm = take(4)
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")

    def head(xs):                       # HEAD_ROWS rows: [R, D], [R, 1]
        logits = xs[0] @ w_lm
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                   axis=-1)
        return nll[:, 0], logits[:, :PROBE_COLUMNS], \
            jax.nn.sigmoid(xs[0] @ w_g + b_g)

    rows = min(HEAD_ROWS, b * t)
    labels = batch["labels"].reshape(-1, rows, 1)
    nll, probe, lam = [], [], []
    with jax.default_matmul_precision("highest"):
        x = embedding[ids]
        for _ in range(passes):
            for n1, wq, wk, wv, wo, n2, n3, wg, wu, wd, n4 in weights:
                a = plain.rms_norm(x, n1, eps)
                mixed = jax.lax.map(
                    lambda xs: attention(xs[0], xs[1], wq, wk, wv, wo),
                    (a, pos))
                x = x + plain.rms_norm(mixed, n2, eps)
                m = plain.rms_norm(x, n3, eps)
                x = x + plain.rms_norm(
                    (jax.nn.silu(m @ wg) * (m @ wu)) @ wd, n4, eps)
            x = plain.rms_norm(x, w_f, eps)
            one = jax.lax.map(head, (x.reshape(-1, rows, d), labels))
            nll.append(one[0].reshape(b, t))
            probe.append(one[1].reshape(b, t, -1))
            lam.append(one[2].reshape(b, t))
    p = plain.exit_distribution(jnp.stack(lam))                 # [P, B, T]
    entropy = -jax.scipy.special.xlogy(p, p).sum(0)
    loss = ((p * jnp.stack(nll)).sum(0)
            - c["exit_entropy_coef"] * entropy).mean()
    return {"loss": loss, "logits": jnp.concatenate(probe, axis=1),
            "exit_p": jnp.moveaxis(p, 0, 1)}


def check(cfg, first, want, scalars):
    """checks.training on the loss, on the first PROBE_COLUMNS logits of
    every pass at every position and on the exit distribution at every
    position: nothing is routed, so nothing is left out."""
    verdicts, found = checks.training(cfg, first, want, scalars)
    passes = first["exit_p"].shape[1]
    by_pass = ", ".join("%.2e" % checks.normalised_error(got, ref)
                        for got, ref in zip(
                            np.split(first["logits"], passes, axis=1),
                            np.split(want["logits"], passes, axis=1)))

    def shares(p):
        return " ".join("%.4f" % x for x in p[0, :, 0])
    return verdicts, found + "; logits by pass off by %s; exit_p of the " \
        "first position %s (reference %s)" % (
            by_pass, shares(first["exit_p"]), shares(want["exit_p"]))
