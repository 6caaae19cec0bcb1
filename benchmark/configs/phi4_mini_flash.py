"""Phi-4-mini-flash-reasoning (paddle_tpu/models/causal_lm.py) as the
benchmark trains it: one chip's share of six published layers (0, 1, 16, 17,
18, 19: a Mamba mixer, a windowed differential attention, the Mamba mixer
that hands on its scan output, the full attention that hands on its keys and
values, a gated memory unit and a cross attention that read them) and an
eighth of the vocabulary. `make_batch` and `samples_per_step` are
configs/causal_lm.py's; this file adds the operations a token, the
operations the three flash kernels are given over the three cores, the bytes
the selective scan and the embedding's gradient move through HBM, the
benchmark's copy of
the plain float32 reference, blocked so that it fits beside the training
state (a sequence's attention one pair of heads at a time, the MLPs and the
tied head in blocks of rows), and the cell's check, which also holds the
gradients that REACH the memory and the shared keys (each the sum over its
readers) to the reference's jax.grad of everything behind the scan that
made the memory.

`selective_scan_kernel_bytes` is the only roof the recurrence has a peak
for: it has no matmul, and benchmark/peaks.json no vector peak. Where the
VPU binds (16 states a channel are 16 exponentials and some 100 vector
operations a token and register of channels) the share of the HBM rate
reads low, and that is the finding, not a fault of the count.
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
# the two Pallas passes of layers.selective_scan (the backward pass replays
# a chunk's states inside its own kernel)
SELECTIVE_SCAN_KERNELS = ("ptpu_selective_scan_fwd",
                          "ptpu_selective_scan_bwd")
# what the parameters that start at an identity are drawn with around it,
# on the benchmark's side (the .json's `assumed.identities`): every bias
# normal(0, .) and a scan's skip D normal(1, .); at exactly 0 and 1 a rule
# that drops one reads healthy
IDENTITY_RANGE = 0.1


def build(fluid, cfg, traffic):
    """Builds the training program in the current guard, after asking the
    program for the cross-decoder: a program from before it refuses the
    configuration's keys one by one, this names the cause. Fetches: the
    loss; the logits of the first PROBE_COLUMNS words at every position;
    `memory`, the first PROBE_COLUMNS channels of the scan output the gated
    memory unit reads; `shared_k` and `shared_v`, the first key pair's two
    keys ([B, T, 2, hd]) and its value ([B, T, 1, 2 hd]) as the cross
    attention's core reads them; `delta`, PROBE_COLUMNS channels of the
    first scan's Delta; and, of the backward pass, `memory_grad` and
    `shared_k_grad`: the same crops of the gradients accumulated into the
    memory and the shared keys, each the sum over its readers."""
    from paddle_tpu.models import causal_lm
    if not hasattr(causal_lm, "gated_memory_unit"):
        raise NotImplementedError(
            "this program's causal_lm has no layer that reads another "
            "layer's state (mb_per_layer): it cannot build %s"
            % (cfg["name"],))
    fluid.default_main_program().enable_mixed_precision()
    loss, logits, _ = causal_lm.build_train(
        cfg, traffic["seq_len"], learning_rate=cfg["learning_rate"],
        beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
        epsilon=cfg["adam_epsilon"], clip_norm=cfg["clip_norm"])
    # the configuration's own start for the parameters at an identity: a
    # second initialiser behind the builder's in the startup program
    startup = fluid.default_startup_program().global_block()
    init = fluid.initializer.Normal
    for p in fluid.default_main_program().global_block().all_parameters():
        if p.name.endswith(".bias") and "_norm" not in p.name:
            init(0.0, IDENTITY_RANGE)(startup.var(p.name), startup)
        elif p.name.endswith(".d"):
            init(1.0, IDENTITY_RANGE)(startup.var(p.name), startup)
    layers = fluid.layers
    block = fluid.default_main_program().global_block()
    c = causal_lm.resolve(cfg)
    hd = c["head_dim"]
    scans = [op for op in block.ops if op.type == "selective_scan"]
    cores = [op for op in block.ops if op.type == "fused_attention"]
    cross = cores[-1]
    memory = block.var(scans[-1].output("Out")[0])
    key, value = (block.var(cross.input(slot)[0]) for slot in ("K", "V"))
    columns = min(PROBE_COLUMNS, cfg["vocab_size"])
    channels = min(PROBE_COLUMNS, int(memory.shape[-1]))

    def keys(var):              # [B, T, (key pair, map), 2 hd] -> pair 0
        return layers.crop(var, shape=[-1, -1, 2, hd])

    return {
        "loss": loss,
        "logits": layers.crop(logits, shape=[-1, -1, columns]),
        "memory": layers.crop(memory, shape=[-1, -1, channels]),
        "shared_k": keys(key),
        "shared_v": layers.crop(value, shape=[-1, -1, 1, 2 * hd]),
        "delta": layers.crop(block.var(scans[0].input("Delta")[0]),
                             shape=[-1, -1, channels]),
        "memory_grad": layers.crop(block.var(memory.name + "@GRAD"),
                                   shape=[-1, -1, channels]),
        "shared_k_grad": keys(block.var(key.name + "@GRAD"))}


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part, of the
    ARITHMETIC, whatever form is built. A Mamba mixer's four projections
    (in, the scan's [r; B; C], Delta's, out); a gated memory unit's two; a
    differential attention's projections (q, k, v and out where it makes
    its own keys and values, q and out where it reads another layer's) and
    its core: two score maps a pair of heads over keys of hd, each on the
    pair's value of 2 hd, 20 x 2 x (64 + 128) = 7,680 a visible key; the
    gated MLPs; the tied head over the held words. The convolution's taps,
    the recurrence (16 states a channel on the VPU), norms, lambda and the
    optimizer are not counted."""
    c = _resolved(cfg)
    d, hd, f = c["hidden_size"], c["head_dim"], c["dense_intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    di = c["mamba_expand"] * d
    n, rank = c["mamba_d_state"], c["mamba_dt_rank"]
    t = traffic["seq_len"]
    kinds = list(zip(c["mixer_layers"], c["reads_layers"]))
    own, cross = kinds.count(("attention", "own")), \
        kinds.count(("attention", "shared"))
    keys = sum(shared.visible_pairs(t, w) / t for (kind, _), w in zip(
        kinds, c["window_layers"]) if kind == "attention")
    return {
        "scan_projections": c["mixer_layers"].count("mamba") * (
            d * 2 * di + di * (rank + 2 * n) + rank * di + di * d),
        "memory_unit": c["mixer_layers"].count("gmu") * 2 * d * di,
        "attention_projections": (own + cross) * 2 * d * h * hd
        + own * 2 * d * hkv * hd,
        "attention": keys * (h // 2) * 2 * (hd + 2 * hd),
        "mlp": c["num_hidden_layers"] * 3 * d * f,
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes; nothing a recomputation replays. At the
    cell's six layers and T=8192: 3 x 2 x 763.5e6 = 4581e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step that the three flash kernels are GIVEN
    over the three cores, counting only the pairs inside each layer's mask:
    the builder runs a pair's two maps as two heads of 2 hd = 128 (a map's
    query and key padded from 64 with zeros, models/causal_lm.py
    differential_attention), so a visible pair costs a head of 128 on a
    value of 128, 4, 8 and 6 x 128 for the forward, dK/dV and dQ kernels,
    over 40 heads: 4 / 3 of the arithmetic `forward_macs` counts (the
    zeros are multiplied). Edge blocks compute masked pairs too, so a share
    of the peak from this cannot pass 100 %."""
    c = _resolved(cfg)
    width = 2 * c["head_dim"]
    pairs = sum(shared.visible_pairs(traffic["seq_len"], w)
                for kind, w in zip(c["mixer_layers"], c["window_layers"])
                if kind == "attention") \
        * traffic["batch"] * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 4 * width * pairs,
            "ptpu_flash_bwd_dkdv": 8 * width * pairs,
            "ptpu_flash_bwd_dq": 6 * width * pairs}


def embedding_grad_bytes(cfg, traffic):
    """Bytes a step that the embedding's gradient has to move THROUGH HBM:
    the dense [V, D] float32 table written once, and nothing else. The
    [tokens, D] float32 rows of the output's gradient, which
    configs/causal_lm.py counts beside it, do not cross HBM in this cell:
    the compiled step keeps them in VMEM (the fusion that gathers them into
    the ids' order writes f32[8192, 2560]{..S(1)}, 80 MiB of the v5e's 128,
    and the kernel reads it there; AOT compile for a described v5e, PR 54;
    the count that holds them to the HBM rate read 105.1 % on the chip, and
    configs/lfm2.py short_conv_kernel_bytes leaves out what the step holds
    in VMEM for the same reason). A step that holds the rows in HBM moves
    more than this and reads under its true share, never over 100 %."""
    return 4 * cfg["hidden_size"] * cfg["vocab_size"]


def selective_scan_kernel_bytes(cfg, traffic):
    """{kernel: bytes a step} that the recurrence of the Mamba mixers' scans
    has to move to and from HBM, whatever computes it, 4 bytes an element
    (float32). Forward, a layer: x and Delta read and y written, [T, d_i]
    each, B and C read ([T, 2 N]). Backward: x, Delta and dy read, dx and
    dDelta written, B and C read and their gradients written. The states a
    kernel keeps between its chunks for the backward pass are an
    implementation's (how many follows the program's chunk, and the
    compiled step hands them to the backward kernel in VMEM), so they are
    not counted; nor are A, D and their gradients."""
    c = _resolved(cfg)
    layers = c["mixer_layers"].count("mamba")
    tokens = traffic["batch"] * traffic["seq_len"]
    array = 4 * tokens * c["mamba_expand"] * c["hidden_size"]
    small = 4 * tokens * 2 * c["mamba_d_state"]
    return {"ptpu_selective_scan_fwd": layers * (3 * array + small),
            "ptpu_selective_scan_bwd": layers * (5 * array + 2 * small)}


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from the plain float32 forward of
    paddle_tpu/models/causal_lm_reference.py on the program's weights, with
    the same arithmetic cut into blocks (module docstring); a test holds it
    equal to the unblocked reference. Of the backward pass: jax.grad of
    everything behind the scan that made the memory (that layer's gate,
    output projection and MLP, the layers after it and the head, each
    under jax.checkpoint) with respect to the memory and to the shared keys,
    on the reference's own state: the sum over their readers by
    construction."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, hd = c["rms_norm_eps"], c["head_dim"]
    ids = batch["ids"]
    b, t = ids.shape
    d = c["hidden_size"]
    labels = batch["labels"].reshape(b, t)

    embedding = take(1)[0]
    weights = []
    for i in range(c["num_hidden_layers"]):
        n1 = take(2)
        kind, reads = c["mixer_layers"][i], c["reads_layers"][i]
        mixer = take(9) if kind == "mamba" else take(2) if kind == "gmu" \
            else take(13 if reads == "own" else 9)
        weights.append((n1, mixer, take(2), take(2)))
    w_f = take(2)
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")

    def rows(fn, x):            # fn over x [B, T, ...] HEAD_ROWS rows a time
        n = min(HEAD_ROWS, b * t)
        flat = x.reshape((-1, n) + x.shape[2:])
        return jax.lax.map(fn, flat).reshape((b, t, -1))

    def mlp(x, norm, ffn):
        def block(m):
            gate, up = jnp.split(plain.layer_norm(m, *norm, eps) @ ffn[0], 2,
                                 axis=-1)
            return (jax.nn.silu(gate) * up) @ ffn[1]
        return x + rows(block, x)

    def attention(a, mixer, i, kv, offset=None):
        """plain.differential_attention, a sequence and a query pair at a
        time: (out [B, T, D], (k, v)). `kv` None: the layer's own keys and
        values, with `offset` (two arrays shaped like them) added."""
        lc = plain.layer_config(c, i)
        wq, bq = mixer[:2]
        lambdas, subln, wo, bo = mixer[-7:-3], mixer[-3], mixer[-2], mixer[-1]
        pairs, kv_pairs = c["num_attention_heads"] // 2, \
            c["num_key_value_heads"] // 2
        group = pairs // kv_pairs
        if kv is None:
            wk, bk, wv, bv = mixer[2:6]
            k = (a @ wk + bk).reshape(b, t, kv_pairs, 2, hd)
            v = (a @ wv + bv).reshape(b, t, kv_pairs, 2 * hd)
            if offset is not None:
                k, v = k + offset[0], v + offset[1]
        else:
            k, v = kv
        q = (a @ wq + bq).reshape(b, t, pairs, 2, hd)
        age = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        visible = age >= 0
        if lc["window"] is not None:
            visible = visible & (age < lc["window"])
        lam = jnp.exp(lambdas[0] @ lambdas[1]) \
            - jnp.exp(lambdas[2] @ lambdas[3]) + lc["lambda_init"]

        @jax.checkpoint
        def one(args):          # q, k [T, 2, hd], v [T, 2 hd]
            q, k, v = args
            s = jnp.einsum("qjd,kjd->jqk", q, k) * hd ** -0.5
            maps = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), -1)
            ctx = jnp.einsum("jqk,kd->jqd", maps, v)
            return plain.rms_norm(ctx[0] - lam * ctx[1], subln, eps) \
                * (1.0 - lc["lambda_init"])

        def sequence(args):     # pairs on the leading axis
            q, k, v = args
            out = jax.lax.map(one, (
                q.transpose(1, 0, 2, 3),
                jnp.repeat(k, group, axis=1).transpose(1, 0, 2, 3),
                jnp.repeat(v, group, axis=1).transpose(1, 0, 2)))
            return out.transpose(1, 0, 2).reshape(t, -1)

        out = jax.lax.map(sequence, (q, k, v))
        return out @ wo + bo, (k, v)

    def layer(j, gate, x, memory, kv):
        """Layer j on x: (its output, the keys and values handed on). `gate`
        not None: the layer that made `memory`, from behind its scan, `gate`
        its z. At `kv_layer`, `kv` arrives as the offsets on what it
        makes."""
        n1, mixer, n3, ffn = weights[j]
        if gate is not None:
            mixed = (memory * jax.nn.silu(gate)) @ mixer[8]
        else:
            a = plain.layer_norm(x, *n1, eps)
            if c["mixer_layers"][j] == "gmu":
                mixed = plain.gated_memory_unit(a, memory, *mixer)
            elif j == c["kv_layer"]:
                mixed, kv = attention(a, mixer, j, None, offset=kv)
            else:
                mixed, _ = attention(a, mixer, j, kv)
        return mlp(x + mixed, n3, ffn), kv

    def behind(first, gate, x, memory, kv):
        """Layers `first` on (that one from behind its scan) and the head's
        mean loss: (loss, (logits probe, the keys and values handed on))."""
        for j in range(first, c["num_hidden_layers"]):
            x, kv = jax.checkpoint(functools.partial(
                layer, j, gate if j == first else None))(x, memory, kv)

        def head(xs):
            logits = plain.layer_norm(xs[0], *w_f, eps) @ embedding.T
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                       axis=-1)
            return nll.sum(), logits[:, :PROBE_COLUMNS]

        n = min(HEAD_ROWS, b * t)
        nll, probe = jax.lax.map(head, (x.reshape(-1, n, d),
                                        labels.reshape(-1, n, 1)))
        return nll.sum() / (b * t), (probe.reshape(b, t, -1), kv)

    found = {}
    with jax.default_matmul_precision("highest"):
        x = embedding[ids]
        first = c["memory_layer"]
        for i in range(first):
            n1, mixer, n3, ffn = weights[i]
            a = plain.layer_norm(x, *n1, eps)
            mixed = plain.mamba(a, *mixer, found=found)[0] \
                if c["mixer_layers"][i] == "mamba" \
                else attention(a, mixer, i, None)[0]
            x = mlp(x + mixed, n3, ffn)
        # the layer that hands on its scan output: up to the scan here, the
        # rest under jax.grad
        n1, mixer = weights[first][:2]
        a = plain.layer_norm(x, *n1, eps)
        gate = jnp.split(a @ mixer[0], 2, axis=-1)[1]
        _, memory = plain.mamba(a, *mixer, found=found)
        pairs = c["num_key_value_heads"] // 2
        zeros = (jnp.zeros((b, t, pairs, 2, hd)),
                 jnp.zeros((b, t, pairs, 2 * hd)))
        # kv enters as offsets on what layer `kv_layer` makes: its gradient
        # at 0 is the gradient that reaches the shared keys and values
        (loss, (probe, (k, v))), grads = jax.value_and_grad(
            functools.partial(behind, first, gate, x), argnums=(0, 1),
            has_aux=True)(memory, zeros)
    return {"loss": loss, "logits": probe,
            "memory": memory[..., :PROBE_COLUMNS],
            "shared_k": k[:, :, 0], "shared_v": v[:, :, :1],
            "delta": found["delta"][..., :PROBE_COLUMNS],
            "memory_grad": grads[0][..., :PROBE_COLUMNS],
            "shared_k_grad": grads[1][0][:, :, 0]}


def check(cfg, first, want, scalars):
    """checks.training on every forward fetch at EVERY position (the model
    has no router): the loss; the logits by their largest error and
    (`logits_mean`) by their mean error over their mean size; `memory`,
    `shared_k`, `shared_v` and `delta` as the layers that read them do.
    Of the backward pass `memory_grad` and `shared_k_grad`, each against
    the reference's sum over its readers (no forward fetch sees a reader's
    gradient dropped), by two numbers: `_mean`, the mean error over the mean
    size, and `_p999`, the error that one element in a thousand passes, over
    the reference's largest value. The largest error itself is printed with
    its place ([sequence, token, ..]) and holds no limit: it is one
    element's at the sequence's first tokens, and over healthy seeds it
    swung from 2.6e-2 to 9.85e-1 (the .json's `reference.why`)."""
    tolerance = cfg["reference"]["tolerance"]
    forward = ("loss", "logits", "memory", "shared_k", "shared_v", "delta")
    got, ref = ({n: np.asarray(x[n], np.float32) for n in forward}
                for x in (first, want))
    verdicts, found = checks.training(cfg, got, ref, scalars)
    held, largest = {}, []
    for name in ("logits", "memory_grad", "shared_k_grad"):
        b = np.asarray(want[name], np.float32)
        error = np.abs(np.asarray(first[name], np.float32).reshape(b.shape)
                       - b)
        held[name + "_mean"] = float(error.mean() / np.abs(b).mean())
        if name != "logits":
            held[name + "_p999"] = float(np.quantile(error, 0.999)
                                         / np.abs(b).max())
            largest.append("%s %.2e at %s" % (
                name, error.max() / np.abs(b).max(),
                list(map(int, np.unravel_index(error.argmax(), b.shape)))))
    verdicts["reference"] = verdicts["reference"] and all(
        held[name] <= tolerance[name] for name in held)
    found += "; " + ", ".join(
        "%s off by %.3e (tolerance %g)" % (name, held[name], tolerance[name])
        for name in sorted(held)) + "; largest errors of the gradients: " \
        + ", ".join(largest)
    return verdicts, found
