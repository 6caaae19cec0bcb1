"""Ling-3.0-flash (paddle_tpu/models/causal_lm.py) as the benchmark trains
it: chip 0's share of a layer that 64 chips divide, published layers 1-7
(one of the two leading dense layers, then a whole period of six expert
layers: KDA, KDA, KDA, latent attention, KDA, KDA). `make_batch` and
`samples_per_step` are configs/causal_lm.py's; this file adds the fetches of
the first KDA layer's decay and recurrence (before any router), the
operations a token, the operations and bytes of the kernels the cell's
metrics read (the two KDA kernels, by what the pass over chunks is given;
the three flash kernels at a head of 128 + 64 on a value of 128), the
held-set margin of a router that chooses groups before experts, the
benchmark's copy of the plain float32 reference, blocked so that it fits
beside the training state (the delta rule as the token-by-token recurrence
under a checkpoint every 64 tokens, attention one query head at a time, the
held experts one at a time, the head in blocks of rows), and the cell's
check. Sizes are in the configuration's .json under the keys of the model's
`config.json`; the counts of heads, experts and words there are what this
chip holds (`share`).
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
SCAN_BLOCK = 64         # tokens of the reference's recurrence a checkpoint
samples_per_step = base.samples_per_step
# full sequences of uniform ids in [0, vocab_size): the configuration's
# vocab_size is chip 0's slice of the published vocabulary (words 0 ..
# 39295), so ids and labels are drawn from the slice
make_batch = base.make_batch
expert_matmul_ops = base.expert_matmul_ops
embedding_grad_bytes = base.embedding_grad_bytes
MARGINS = (0.0, 0.02, 0.05, 0.1)         # the held set's
GROUP_MARGINS = (0.0, 0.002, 0.005, 0.01, 0.02)     # the held group's
KDA_KERNELS = ("ptpu_kda_fwd", "ptpu_kda_bwd")


def build(fluid, cfg, traffic):
    """configs/causal_lm.py's `build`, after asking the program for the op
    six of the seven layers are made of (a program from before it fails
    here, at once, and names the cause), with fetches more. Of the FIRST
    layer's mixer, before which no router lies: `decay`, head 0's log decay
    a channel as the delta rule reads it ([B, T, 1, 128] float32),
    `kda_out`, head 0 of what the rule gives, and `kda_ctx`, head 0's 128
    channels of what the layer's W_o reads (normed, then gated). Of the
    latent layer: head 0's
    query and key as its core reads them, the part without position then
    the rotary part ([B, T, 1, 192] each), and `latent_ctx`, head 0's 128
    channels of what its W_o reads (the core's output behind the gate a
    head). `state`: 128 channels of what the final norm reads."""
    if not hasattr(fluid.layers, "kda_delta_rule"):
        raise NotImplementedError(
            "this program has no fluid.layers.kda_delta_rule (a delta rule "
            "whose decay is a key channel's): it cannot build %s"
            % (cfg["name"],))
    fetches = base.build(fluid, cfg, traffic)
    layers = fluid.layers
    block = fluid.default_main_program().global_block()
    rule = next(op for op in block.ops if op.type == "kda_delta_rule")

    def head0(name):
        return layers.crop(block.var(name), shape=[-1, -1, 1, -1])

    final = next(op for op in block.ops if op.type == "rms_norm"
                 and op.input("Scale")[0] == "final_norm")
    core = next(op for op in block.ops if op.type == "fused_attention")

    def read_by(w_o):                   # 128 channels of what a W_o reads
        op = next(op for op in block.ops if op.type == "mul"
                  and op.input("Y")[0] == w_o)
        return layers.crop(block.var(op.input("X")[0]),
                           shape=[-1, -1, cfg["v_head_dim"]])

    return dict(fetches, decay=head0(rule.input("G")[0]),
                kda_out=head0(rule.output("Out")[0]),
                kda_ctx=read_by("layer_0.wo"),
                queries=layers.concat([head0(core.input("Q")[0]),
                                       head0(core.input("QRope")[0])],
                                      axis=3),
                keys=layers.concat([head0(core.input("K")[0]),
                                    head0(core.input("KRope")[0])], axis=3),
                latent_ctx=read_by("layer_%d.wo" % _latent_layers(cfg)[0]),
                state=layers.crop(
                    block.var(final.input("X")[0]),
                    shape=[-1, -1, min(PROBE_COLUMNS, cfg["hidden_size"])]))


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def _latent_layers(cfg):
    """The built layers that are latent attention, by the configuration's
    own keys and not the builder's reading of them: published index i where
    (i + 1) % layer_group_size == 0."""
    return [k for k, i in enumerate(cfg["layer_indices"])
            if (i + 1) % cfg["layer_group_size"] == 0]


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part. A KDA mixer: its
    five wide projections (q, k, v, the decay a channel, the gate a
    channel), the write strength a head and the output projection, the
    taps of its three convolutions, and the recurrence's own three [dk, dv]
    products a head (S^T k, the rank-one write, S^T q), whatever the chunk
    the op computes it in. The latent layer: q, the kv down-projection with
    the rotary key, the kv up-projection, the gate a head and the output
    projection, and the core over the causal pairs (a head 192 wide on the
    scores and 128 on the values). The leading dense FFN at its own width;
    in the expert layers the router at its published width, the held
    experts a token is expected to reach (8 x 8 / 512 of them) and the
    shared expert. The head over the held words."""
    c = _resolved(cfg)
    d, f, h = c["hidden_size"], c["intermediate_size"], \
        c["num_attention_heads"]
    hd = c["head_dim"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rkv = c["kv_lora_rank"]
    kda, latent = (c["mixer_layers"].count(m) for m in ("kda", "attention"))
    routed = c["ffn_layers"].count("experts")
    t = traffic["seq_len"]
    return {
        "kda_projections": kda * d * (5 * h * hd + h + h * hd),
        "kda_convolutions": kda * c["short_conv_kernel_size"] * 3 * h * hd,
        "kda_rule": kda * h * 3 * hd * hd,
        "latent_projections": latent * (
            d * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + d * h
            + h * dv * d),
        "latent_attention": latent * shared.visible_pairs(t, None) / t
        * h * (dn + dr + dv),
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
    the gates' exponentials, routing and the optimizer are not counted."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels in the one
    latent layer, counting only the causal pairs, a pair and query head, of
    the WORK (configs/glm_4_7_flash.py has the rule): scores over the whole
    head of 128 + 64 = 192 and a weighted sum over values of 128. Edge
    blocks compute masked pairs too, so a share of the peak from this cannot
    pass 100 %."""
    c = _resolved(cfg)
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    v = c["v_head_dim"]
    pairs = c["mixer_layers"].count("attention") * shared.visible_pairs(
        traffic["seq_len"], None) * traffic["batch"] \
        * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 2 * (qk + v) * pairs,
            "ptpu_flash_bwd_dkdv": 2 * (qk + v + v + qk) * pairs,
            "ptpu_flash_bwd_dq": 2 * (qk + v + qk) * pairs}


def kda_kernel_ops(cfg, traffic, chunk):
    """{kernel: [(operations, bytes), ...]} a step, one pair a call and
    layer pattern, of what the two kernels of the pass over chunks are GIVEN
    to do at chunks of `chunk` tokens: their own matmuls, and their operands
    and results moved once between HBM and VMEM
    (configs/qwen3_next.py:gated_delta_kernel_ops has the argument; the
    tile is the same but for the state's decay, a row of dk float32 and not
    of dv). Not the recurrence's least (6 x dk x dv a token and head, which
    `ops_per_sample` counts) and not what prepares the chunks (`_prepare`:
    the decayed products a channel, (I + L)^-1), which XLA runs outside the
    kernels and the kernels' traced time does not hold.

    A tile is one (sequence, head, chunk); its operands, bf16 under the
    configuration's AMP: qe, kd, w [C, dk], u [C, dv], m [C, C], and erow
    [dk] float32; the state is [dv, dk]. `ptpu_kda_fwd` runs twice a layer:
    for o (reads the six, writes o [C, dv]; W S, Qe S, K~^T V' at C x dk x
    dv multiply-adds each and M V' at C x C x dv) and, in the backward pass,
    for the state that enters every chunk (needs kd, u, w, erow; writes [dv,
    dk]; W S and K~^T V'). `ptpu_kda_bwd` reads the six, a state and dO [C,
    dv] and writes the six's gradients: seven products of C x dk x dv and
    two of C x C x dv."""
    c = _resolved(cfg)
    h, dk = c["num_attention_heads"], c["head_dim"]
    dv = dk
    tiles = c["mixer_layers"].count("kda") * traffic["batch"] * h \
        * -(-traffic["seq_len"] // chunk)
    big, small = 2 * chunk * dk * dv, 2 * chunk * chunk * dv    # operations
    six = 2 * chunk * (3 * dk + dv + chunk) + 4 * dk            # bytes
    state, rows = 2 * dk * dv, 2 * chunk * dv
    return {
        "ptpu_kda_fwd": [
            (tiles * (3 * big + small), tiles * (six + rows)),
            (tiles * 2 * big,
             tiles * (2 * chunk * (2 * dk + dv) + 4 * dk + state))],
        "ptpu_kda_bwd": [
            (tiles * (7 * big + 2 * small),
             tiles * (2 * six + state + rows))]}


def _router_margin(scores, c):
    """How far a token is from another set of HELD experts under a router
    that chooses groups before experts, from the scores the choice is made
    from, s + b [N, E]: two margins, each [N]. The held GROUP's: the held
    experts are neighbours and lie in one group (0-7 of 512 in group 0 of
    8); with the groups' scores (the sum of a group's two largest) sorted
    down, g_1 >= g_2 >= ..., and k = topk_group, it is (g_h - g_(k+1)) /
    g_h where the held group h is kept (how far it is from falling out)
    and (g_k - g_h) / g_k where it is not (from coming in): under it the
    chip's whole share of the token may appear or vanish. The held SET's:
    configs/lfm2.py's held-set margin on the scores that are left, those
    outside the kept groups at minus infinity (a token that does not keep
    the held group has no held assignment to gain or lose: 1, as far as a
    margin goes). Two
    margins because they are of two sizes: a group's score is a sum of two
    scores near 1 and moves by a part in a thousand under bf16, a single
    score's distance to the eighth best by far more of itself."""
    from paddle_tpu.models import causal_lm_reference as plain
    n_group, kept = c["n_group"], c["topk_group"]
    by_group = scores.reshape(scores.shape[0], n_group, -1)
    group = jax.lax.top_k(by_group, 2)[0].sum(-1)           # [N, n_group]
    ranked = jnp.sort(group, axis=-1)[:, ::-1]
    held = group[:, c["first_expert"] // by_group.shape[-1]]
    inside = held >= ranked[:, kept - 1]
    groups = jnp.where(inside, (held - ranked[:, kept]) / held,
                       (ranked[:, kept - 1] - held) / ranked[:, kept - 1])
    left = plain.group_limited(scores, n_group, kept)
    return groups, lfm2._router_margin(left, c)


def _scan_in_blocks(q, k, v, g, beta, found=None):
    """causal_lm_reference.kda_rule, the recurrence token by token
    (`kda_step`), as an outer scan over blocks of SCAN_BLOCK tokens whose
    inner scan stands under jax.checkpoint: a gradient through it would
    keep a state a block and not a state a token. The same steps in the
    same order."""
    from paddle_tpu.models.causal_lm_reference import kda_step
    b, t, h, dk = q.shape
    block = SCAN_BLOCK if t % SCAN_BLOCK == 0 else t

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(kda_step, state, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((t // block, block)
                                             + x.shape[:1] + x.shape[2:])
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(tokens, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
                        xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


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

    eps, h = c["rms_norm_eps"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rkv = c["kv_lora_rank"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d = c["hidden_size"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = (dn + dr) ** -0.5

    def turn(x, pos_row):
        return plain.rope(x, pos_row[None], c["rope_theta"],
                          interleaved=c["rope_interleaved"])

    def attention(a, pos_row, wq, wkv_a, kv_a_norm, wkv_b, wg, wo):
        q = (a @ wq).reshape(1, t, h, dn + dr)                  # a [T, D]
        ckv = a @ wkv_a
        kv = (plain.rms_norm(ckv[:, :rkv], kv_a_norm, eps) @ wkv_b).reshape(
            t, h, dn + dv)
        k_r = turn(ckv[:, rkv:].reshape(1, t, 1, dr), pos_row)[0, :, 0]
        q = jnp.concatenate([q[..., :dn], turn(q[..., dn:], pos_row)], -1)[0]

        def one_head(args):                 # [T, 192], [T, 128 + 128]
            qh, kvh = args
            kh = jnp.concatenate([kvh[:, :dn], k_r], -1)
            s = jnp.where(causal, (qh @ kh.T) * scale, -jnp.inf)
            return jax.nn.softmax(s, -1) @ kvh[:, dn:]

        ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                     kv.transpose(1, 0, 2)))
        ctx = (ctx.transpose(1, 0, 2) * jax.nn.sigmoid(a @ wg)[..., None]
               ).reshape(t, h * dv)
        return ctx @ wo, (q[:, :1], jnp.concatenate(
            [kv[:, :1, :dn], k_r[:, None]], -1), ctx[:, :dv])

    load = jnp.zeros((c["num_experts"],), jnp.int32)
    margin = group_margin = jnp.full((b * t,), jnp.inf)
    found, latent = {}, _latent_layers(cfg)
    with jax.default_matmul_precision("highest"):
        x = take(1)[0][ids]
        for i in range(c["num_hidden_layers"]):
            a = plain.rms_norm(x, take(1)[0], eps)
            if i not in latent:
                x = x + plain.kda(a, *take(13), c, found=found,
                                  rule=_scan_in_blocks)
            else:
                weights = take(6)
                out, probe = jax.lax.map(
                    lambda xs: attention(xs[0], xs[1], *weights), (a, pos))
                x = x + out
                if i == latent[0]:
                    queries, keys, latent_ctx = probe
            mid = plain.rms_norm(x, take(1)[0], eps)
            if c["ffn_layers"][i] == "dense":
                wg, wu, wd = take(3)
                x = x + (jax.nn.silu(mid @ wg) * (mid @ wu)) @ wd
                continue
            mid = mid.reshape(b * t, d)
            router, bias, wg, wu, wd = take(5)
            out, _, _, ld = plain.routed_experts(mid, router, wg, wu, wd, c,
                                                 expert_bias=bias)
            groups, held = _router_margin(
                jax.nn.sigmoid(mid @ router) + bias, c)
            group_margin = jnp.minimum(group_margin, groups)
            margin = jnp.minimum(margin, held)
            out = out + plain.shared_expert(mid, *take(3))
            x = x + out.reshape(b, t, d)
            load = load + ld
        w_f, w_lm = take(2)

        def head(xs):                       # HEAD_ROWS rows: [R, D], [R, 1]
            logits = plain.rms_norm(xs[0], w_f, eps) @ w_lm
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                       axis=-1)
            return nll.sum(), logits[:, :PROBE_COLUMNS]

        rows = min(HEAD_ROWS, b * t)
        nll, logits = jax.lax.map(head, (
            x.reshape(-1, rows, d), batch["labels"].reshape(-1, rows, 1)))
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")
    return {"loss": nll.sum() / (b * t),
            "logits": logits.reshape(b, t, -1), "expert_load": load,
            "decay": found["kda_g"][:, :, :1], "kda_out":
            found["kda_out"][:, :, :1],
            "kda_ctx": found["kda_ctx"][:, :, 0], "queries": queries,
            "keys": keys,
            "latent_ctx": latent_ctx, "state": x[..., :PROBE_COLUMNS],
            "router_margin": margin.reshape(b, t),
            "group_margin": group_margin.reshape(b, t)}


def check(cfg, first, want, scalars):
    """checks.training on the loss; on `decay`, `kda_out` and `kda_ctx`,
    head 0 of the first layer's log decay, of its recurrence's output and of
    what its W_o reads (normed, then gated), at every position (no router
    lies before them); on the logits, on `state` (128
    channels of what the final norm reads), on `queries_keys` (head 0's
    query and key of the latent layer's core, 192 each after rotary) and on
    `latent_ctx` (head 0 of what that layer's W_o reads) of the tokens whose
    routing is decided in all six expert layers (`_router_margin`: the held
    group's margin at least `reference.group_margin` and the held set's at
    least `reference.router_margin`), by their largest error and
    (`logits_mean`) by their mean error over their mean size. `dropless`: every one of the
    top-8 assignments of every token in the six expert layers was counted,
    and the rows the held experts computed are the assignments that fell on
    them. The line it prints has what the logits are off by at other
    thresholds: a KDA layer's state carries a moved assignment's effect to
    every later token of its sequence, and the readings by threshold are
    how the threshold was chosen."""
    c = _resolved(cfg)
    tolerance = cfg["reference"]["tolerance"]
    load = np.asarray(first["expert_load"], np.int64)
    margin = np.asarray(want["router_margin"])
    group_margin = np.asarray(want["group_margin"])
    decided = (margin >= cfg["reference"]["router_margin"]) \
        & (group_margin >= cfg["reference"]["group_margin"])

    def compared(x):
        return {"loss": x["loss"],
                "decay": np.asarray(x["decay"], np.float32),
                "kda_out": np.asarray(x["kda_out"], np.float32),
                "kda_ctx": np.asarray(x["kda_ctx"], np.float32),
                "logits": np.asarray(x["logits"], np.float32)[decided],
                "queries_keys": np.concatenate(
                    [np.asarray(x[name], np.float32)[decided]
                     for name in ("queries", "keys")], -1),
                "latent_ctx": np.asarray(x["latent_ctx"],
                                         np.float32)[decided],
                "state": np.asarray(x["state"], np.float32)[decided]}

    got, ref = compared(first), compared(want)
    verdicts, found = checks.training(cfg, got, ref, scalars)
    mean = float(np.abs(got["logits"] - ref["logits"]).mean()
                 / np.abs(ref["logits"]).mean()) if decided.any() \
        else float("nan")
    verdicts["reference"] = bool(verdicts["reference"]
                                 and mean <= tolerance["logits_mean"])
    tokens = decided.size
    routed = c["ffn_layers"].count("experts")
    assignments = tokens * c["num_experts_per_tok"] * routed
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    want_load = np.asarray(want["expert_load"], np.int64)
    moved = int(np.abs(load - want_load).sum()) // 2
    verdicts["dropless"] = int(load.sum()) == assignments and abs(
        int(load[held].sum()) - int(want_load[held].sum())) <= moved
    by_margin = []
    for g in GROUP_MARGINS:
        for m in MARGINS:
            keep = (margin >= m) & (group_margin >= g)
            by_margin.append("%g/%g: %d tokens %.2e" % (
                g, m, keep.sum(), checks.normalised_error(
                    first["logits"][keep], want["logits"][keep])
                if keep.any() else float("nan")))
    found += "; logits_mean off by %.3e (tolerance %g); logits of %d of %d " \
        "tokens compared (the held group's margin >= %g and the held set's " \
        ">= %g in all %d expert layers; over all tokens they are off by " \
        "%.2e); %d of %d " \
        "assignments counted, the %d held experts computed %d rows " \
        "(reference %d; %d..%d an expert), at least %d assignments went to " \
        "another expert than in the reference; logits by the group's / the " \
        "set's margin >= %s" % (
            mean, tolerance["logits_mean"], decided.sum(), tokens,
            cfg["reference"]["group_margin"],
            cfg["reference"]["router_margin"], routed,
            checks.normalised_error(first["logits"], want["logits"]),
            load.sum(), assignments, c["experts_held"], load[held].sum(),
            want_load[held].sum(), load[held].min(), load[held].max(), moved,
            ", ".join(by_margin))
    return verdicts, found
