"""SmallThinker-21BA3B (paddle_tpu/models/causal_lm.py) as the benchmark
trains it: one chip's share of a layer that four chips divide. What differs
from configs/causal_lm.py (OLMoE's), which this file takes `build`,
`make_batch` and `samples_per_step` from: the operations a token (grouped
queries, a window on three layers of four, the expected share of a token's
experts that is held), the operations of the three flash kernels at the
cell's shapes, the blocked float32 reference (a [T, T] mask from i - j, one
query head at a time on its key/value head, the held experts one at a time,
the router read before attention, the head in blocks of rows) and a check
that still decides at depth 4. Sizes are in the configuration's .json under
the keys of the model's `config.json`; the counts of heads, experts and
words there are what this chip holds (`share`).
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
HEAD_ROWS = 1024        # rows of the head's logits alive at a time
build = base.build
samples_per_step = base.samples_per_step
# full sequences of uniform ids in [0, vocab_size): the configuration's
# vocab_size is this chip's slice of the published vocabulary (chip 0's,
# words 0 .. 37983), so ids and labels are drawn from the slice
make_batch = base.make_batch
# counted from the work, by the sizes `resolve` gives a share too: the rows
# the 16 held experts computed, and this chip's slice of the vocabulary
expert_matmul_ops = base.expert_matmul_ops
embedding_grad_bytes = base.embedding_grad_bytes


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def visible_pairs(t, window):
    """(query, key) pairs a causal layer attends at length t: key j <= i,
    and i - j < window where there is one."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_pairs(cfg, traffic):
    """visible_pairs of every layer, in order."""
    c = _resolved(cfg)
    return [visible_pairs(traffic["seq_len"], w) for w in c["window_layers"]]


def held_share(c):
    """The expected number of a token's top-k experts that are held here,
    under uniform routing: k x held / routed over (1.5 of 6 at 16 of 64).
    The operations count takes this expectation, not a run's draw."""
    return c["num_experts_per_tok"] * c["experts_held"] / c["num_experts"]


def forward_macs(cfg, traffic):
    """Multiply-adds of one token's forward pass, by part: the four
    attention projections of the held heads, the router at its published
    width, the held experts a token is expected to reach, the attention core
    over the visible pairs of every layer (scores and weighted sum, per held
    query head), the head over the held words."""
    c = _resolved(cfg)
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    layers, t = c["num_hidden_layers"], traffic["seq_len"]
    return {
        "projections": layers * d * hd * (2 * h + 2 * hkv),
        "router": layers * d * c["num_experts"],
        "experts": layers * held_share(c) * 3 * d * f,
        "attention": sum(layer_pairs(cfg, traffic)) / t * 2 * hd * h,
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes. Windowed layers count the pairs they see
    (25.2 M of 33.6 M at T=8192, window 4096), experts the 1.5 of a token's
    6 that are expected on the 16 held of 64. Embedding lookup, norms,
    rotary, softmax, routing and the optimizer are not counted. At 4 layers
    and T=8192: 3 x 356.2e6 = 1068.7e6."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels at the cell's
    shapes, counting only the pairs inside the mask: a pair costs a query
    head 4 x D in the forward kernel (q k^T, p v), 8 x D in dK/dV (k q^T,
    p^T dO, v dO^T, ds^T q) and 6 x D in dQ (q k^T, dO v^T, ds k). Blocks on
    the band's edges compute masked pairs too, so the kernels do more than
    this and a share of the peak from it cannot pass 100 %."""
    c = _resolved(cfg)
    pairs = sum(layer_pairs(cfg, traffic)) * traffic["batch"] \
        * c["num_attention_heads"]
    return {"ptpu_flash_fwd": 4 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dkdv": 8 * c["head_dim"] * pairs,
            "ptpu_flash_bwd_dq": 6 * c["head_dim"] * pairs}


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from the plain float32 forward of
    paddle_tpu/models/causal_lm_reference.py on the program's weights, with
    the same arithmetic cut into blocks so that it fits beside the training
    state: attention one (sequence, query head) at a time ([8192, 8192]
    float32 scores are 256 MiB), the held experts one at a time over all
    tokens, the head and its loss HEAD_ROWS rows at a time. A test holds it
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
    age = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]        # i - j

    def attention(a, pos_row, wq, wk, wv, wo, cl):               # a [T, D]
        visible = age >= 0
        if cl["window"] is not None:
            visible = visible & (age < cl["window"])
        q = (a @ wq).reshape(1, t, h, hd)
        k, v = ((a @ w).reshape(1, t, hkv, hd) for w in (wk, wv))
        if cl["rope_theta"] is not None:
            q = plain.rope(q, pos_row[None], cl["rope_theta"])
            k = plain.rope(k, pos_row[None], cl["rope_theta"])
        q, k, v = (x[0].transpose(1, 0, 2) for x in (q, k, v))   # [H, T, hd]

        def one_head(args):
            qh, head = args
            kh, vh = k[head // (h // hkv)], v[head // (h // hkv)]
            s = jnp.where(visible, (qh @ kh.T) * hd ** -0.5, -jnp.inf)
            return jax.nn.softmax(s, -1) @ vh

        ctx = jax.lax.map(one_head, (q, jnp.arange(h)))
        return ctx.transpose(1, 0, 2).reshape(t, h * hd) @ wo

    load = jnp.zeros((c["num_experts"],), jnp.int32)
    margin = jnp.full((b * t,), jnp.inf)
    with jax.default_matmul_precision("highest"):
        x = take(1)[0][ids]
        for i in range(c["num_hidden_layers"]):
            cl = plain.layer_config(c, i)
            w_in, wq, wk, wv, wo, w_post = take(6)
            a = plain.rms_norm(x, w_in, eps)
            x = x + jax.lax.map(
                lambda xs: attention(xs[0], xs[1], wq, wk, wv, wo, cl),
                (a, pos))
            m = plain.rms_norm(x, w_post, eps).reshape(b * t, d)
            router, wg, wu, wd = take(4)
            a = a.reshape(b * t, d)
            router_x = a if c["router_input"] == "pre_attention" else m
            out, _, _, ld = plain.routed_experts(m, router, wg, wu, wd, c,
                                                 router_x=router_x)
            margin = jnp.minimum(margin, _router_margin(router_x @ router, c))
            x = x + out.reshape(b, t, d)
            load = load + ld
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
            "router_margin": margin.reshape(b, t)}


def _router_margin(router_logits, c):
    """How far a token is from another set of HELD experts among its top k,
    [N], from its router probabilities sorted down, p_1 >= p_2 >= ...: the
    smaller of (p_i - p_(k+1)) / p_i over the held experts i inside the top
    k (the nearest to falling out) and (p_k - p_j) / p_k over the held
    experts j outside it (the nearest to coming in); infinite where no
    expert is held on either side. Trades among experts that are not held
    change nothing this chip computes, so they do not count; three experts
    level at the cut do (the held one may be the third). With every expert
    held it is (p_k - p_(k+1)) / p_k. A token whose margin is under what
    bf16 activations move the probabilities by may have an assignment here
    in the program and not in the float32 reference, or the other way
    round, and both are right."""
    k = c["num_experts_per_tok"]
    probs = jax.nn.softmax(router_logits, -1)
    order = jnp.argsort(-probs, axis=-1)
    p = jnp.take_along_axis(probs, order, -1)
    local = order - c["first_expert"]
    held = (local >= 0) & (local < c["experts_held"])
    leave = jnp.where(held[:, :k], 1.0 - p[:, k:k + 1] / p[:, :k], jnp.inf)
    enter = jnp.where(held[:, k:], 1.0 - p[:, k:] / p[:, k - 1:k], jnp.inf)
    return jnp.minimum(leave.min(-1), enter.min(-1))


def check(cfg, first, want, scalars):
    """checks.training on the loss and on the logits of the tokens whose
    routing is decided in every layer, and `dropless`: in the first step
    every one of the top_k assignments of every token, in every layer, was
    counted, and the rows the held experts computed are those assignments
    that fell on them.

    A token is decided where the smallest of its layers' router margins
    (_router_margin) is at least `reference.router_margin`. At depth 4 an
    undecided token's moved assignment also reaches later positions, through
    the attention of the layers above it; what that does to the decided
    tokens' logits was measured on the chip and is within the tolerance (the
    configuration's .json has the numbers), so they are compared and the
    undecided ones counted and left out, never the tolerance widened to let
    them in."""
    c = _resolved(cfg)
    load = np.asarray(first["expert_load"], np.int64)
    decided = np.asarray(want["router_margin"]) \
        >= cfg["reference"]["router_margin"]
    verdicts, found = checks.training(
        cfg, {"loss": first["loss"], "logits": first["logits"][decided]},
        {"loss": want["loss"], "logits": want["logits"][decided]}, scalars)
    tokens = decided.size
    assignments = tokens * c["num_experts_per_tok"] * c["num_hidden_layers"]
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    want_load = np.asarray(want["expert_load"], np.int64)
    moved = int(np.abs(load - want_load).sum()) // 2
    # rows computed = the held experts' counts (they are the grouped
    # matmuls' group sizes); a count that leaves the reference's by more
    # than the assignments that moved is rows lost or made up
    verdicts["dropless"] = int(load.sum()) == assignments and abs(
        int(load[held].sum()) - int(want_load[held].sum())) <= moved
    found += "; logits of %d of %d tokens compared (router margin >= %g in " \
        "every layer; over all tokens they are off by %.2e); %d of %d " \
        "assignments counted, the %d held experts computed %d rows " \
        "(reference %d; %d..%d an expert), at least %d assignments went to " \
        "another expert than in the reference" % (
            decided.sum(), tokens, cfg["reference"]["router_margin"],
            checks.normalised_error(first["logits"], want["logits"]),
            load.sum(), assignments, c["experts_held"], load[held].sum(),
            want_load[held].sum(), load[held].min(), load[held].max(), moved)
    return verdicts, found
