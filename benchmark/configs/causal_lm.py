"""A decoder-only language model (paddle_tpu/models/causal_lm.py) as the
benchmark trains it: program build, batch from a key, operations a token,
the plain float32 reference forward blocked so that it runs in set-up beside
the training state, and the cell's check. Sizes are in the configuration's
.json under the keys of the model's Hugging Face `config.json`; any
configuration of this family (OLMoE-1B-7B, a tiny one in a test) names this
file as its `module`.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark import checks

SAMPLE = "token"
PROBE_COLUMNS = 128


def build(fluid, cfg, traffic):
    """Build the training program in the current program guard; returns
    what every step fetches: the loss (mean next-token cross-entropy plus
    the routers' auxiliary terms), the logits of the first PROBE_COLUMNS
    words at every position, and the experts' assignment counts."""
    from paddle_tpu.models import causal_lm
    fluid.default_main_program().enable_mixed_precision()
    loss, logits, load = causal_lm.build_train(
        cfg, traffic["seq_len"], learning_rate=cfg["learning_rate"],
        beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
        epsilon=cfg["adam_epsilon"], clip_norm=cfg["clip_norm"])
    probe = fluid.layers.crop(
        logits, shape=[-1, -1, min(PROBE_COLUMNS, cfg["vocab_size"])])
    return {"loss": loss, "logits": probe, "expert_load": load}


def samples_per_step(cfg, traffic):
    """Positions trained a step: every one carries a next-token loss."""
    return traffic["batch"] * traffic["seq_len"]


def make_batch(cfg, traffic, key):
    """Every sequence at full length: seq_len + 1 uniform token ids, the
    first seq_len as input, the last seq_len as the next-token labels."""
    b, t = traffic["batch"], traffic["seq_len"]
    tok = jax.random.randint(key, (b, t + 1), 0, cfg["vocab_size"], jnp.int32)
    return {"ids": tok[:, :-1],
            "pos": jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t)),
            "labels": tok[:, 1:, None]}


def active_weights(cfg):
    """Weights a token multiplies: four attention projections, the router,
    the gate, up and down matrices of the experts it is routed to (of the
    dense FFN where there are no experts), a layer; the output head once."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    experts = cfg.get("num_experts", 0)
    ffn = 3 * d * f * (cfg["num_experts_per_tok"] if experts else 1)
    return cfg["num_hidden_layers"] * (4 * d * d + d * experts + ffn) \
        + d * cfg["vocab_size"]


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one token, by configs/transformer_base.py's convention: two a
    multiply-add and three passes over the active weights (the experts a
    token is routed to, not the stored ones); attention's scores and
    weighted sum 2 x 2 x keys x width forward, no weight gradient, so 3 x
    that, and causal, so half. Embedding lookup, norms, rotary, softmax,
    routing and the optimizer are not counted. OLMoE-1B-7B at one layer and
    T=4096: 6 x 170.3e6 + 50.3e6 = 1071.9e6."""
    attn_core = 2 * traffic["seq_len"] * 2 * cfg["hidden_size"]
    return 3 * 2 * active_weights(cfg) \
        + 3 * attn_core * 0.5 * cfg["num_hidden_layers"]


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels, every layer
    causal over the whole sequence (OLMoE: 16 heads of 128, T=4096, batch
    4), counting only the pairs inside the mask: 4, 8 and 6 x D a pair and
    query head for the forward, dK/dV and dQ kernels
    (configs/smallthinker.py has why). Edge blocks compute masked pairs
    too, so a share of the peak from this cannot pass 100 %."""
    t, heads = traffic["seq_len"], cfg["num_attention_heads"]
    head_dim = cfg["hidden_size"] // heads
    pairs = cfg["num_hidden_layers"] * t * (t + 1) // 2 * traffic["batch"] \
        * heads
    return {"ptpu_flash_fwd": 4 * head_dim * pairs,
            "ptpu_flash_bwd_dkdv": 8 * head_dim * pairs,
            "ptpu_flash_bwd_dq": 6 * head_dim * pairs}


# the matmuls of one gated expert (gate, up, down) and the passes over each
# (forward, gradient to the rows, gradient to the weights)
EXPERT_MATMULS = 3
PASSES = 3


def expert_matmul_ops(cfg, traffic, load):
    """Matmul operations a step of the routed experts held here, counted
    from the work and not from what implements it: every assignment that a
    held expert computed is one row through the expert's EXPERT_MATMULS
    matrices of hidden_size x the expert's width, two operations a
    multiply-add, PASSES passes. `load` is the `expert_load` fetch, the
    assignments by expert summed over the routed layers, [E] of one step or
    [steps, E] of several (then the count is all those steps'; all of the
    router's columns: the held experts' are taken out
    here, as `check` does for `dropless`). Rows that a grouped matmul pads
    a group or a tile with are not counted, nor rows of experts held
    elsewhere that an implementation walks over, so a share of the peak
    from this cannot pass 100 %. The router, the gated unit between the
    matmuls and the permutations around them are not the matmuls' and are
    not counted.
    OLMoE holds every expert: 16384 tokens x 8 x 1 layer = 131072
    assignments x 3 x 3 x 2 x 2048 x 1024 = 4.948e12 whatever the load's
    shape is."""
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    assignments = int(np.asarray(load, np.int64).reshape(
        -1, c["num_experts"])[:, held].sum())
    return PASSES * EXPERT_MATMULS * 2 * c["hidden_size"] \
        * c["intermediate_size"] * assignments


def embedding_grad_bytes(cfg, traffic):
    """Bytes a step that the embedding's gradient has to move, counted from
    the work: lookup_table's backward hands the optimizer a dense [V, D]
    gradient in the table's dtype (float32: the master weight's), so every
    element of it is written once, and every one of the [tokens, D] rows of
    the output's gradient is read once, at the same 4 bytes (the sum of a
    repeated id's rows is float32's). One lookup a step. What sorts the ids
    and brings the rows into their order (XLA's, around the kernel) moves
    bytes of its own that no form of the gradient needs; they are not
    counted. SmallThinker: 37984 x 2560 x 4 = 389.0e6 written + 8192 x 2560
    x 4 = 83.9e6 read."""
    tokens = traffic["batch"] * traffic["seq_len"]
    return 4 * cfg["hidden_size"] * (cfg["vocab_size"] + tokens)


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from the plain float32 forward of
    paddle_tpu/models/causal_lm_reference.py on the program's weights, with
    the same arithmetic cut into blocks so that it fits beside the training
    state: attention one (sequence, head) at a time, the experts one at a
    time over all tokens, the head and its loss one sequence at a time. A
    test holds it equal to the unblocked reference."""
    from paddle_tpu.models import causal_lm_reference as plain
    from paddle_tpu.models.causal_lm import resolve
    c = resolve(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, heads = c["rms_norm_eps"], c["num_attention_heads"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    d = c["hidden_size"]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(qkv):                      # [T, D/H] each
        q, k, v = qkv
        s = jnp.where(causal, (q @ k.T) * (d // heads) ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    def attention(a, pos_row, wq, wk, wv, q_norm, k_norm, wo):   # a [T, D]
        q, k, v = a @ wq, a @ wk, a @ wv
        if c["qk_norm"]:
            q, k = plain.rms_norm(q, q_norm, eps), plain.rms_norm(k, k_norm,
                                                                  eps)
        q, k, v = (x.reshape(1, t, heads, d // heads) for x in (q, k, v))
        if c["rope_theta"] is not None:
            q = plain.rope(q, pos_row[None], c["rope_theta"])
            k = plain.rope(k, pos_row[None], c["rope_theta"])
        ctx = jax.lax.map(one_head, tuple(
            x[0].transpose(1, 0, 2) for x in (q, k, v)))         # [H, T, D/H]
        return ctx.transpose(1, 0, 2).reshape(t, d) @ wo

    balance = z = 0.0
    load = jnp.zeros((max(c["num_experts"], 1),), jnp.int32)
    margin = jnp.full((b * t,), jnp.inf)
    layers = c["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        h = take(1)[0][ids]
        for _ in range(layers):
            w_in, wq, wk, wv = take(4)
            q_norm, k_norm = take(2) if c["qk_norm"] else (None, None)
            wo, w_post = take(2)
            h = h + jax.lax.map(
                lambda xs: attention(plain.rms_norm(xs[0], w_in, eps), xs[1],
                                     wq, wk, wv, q_norm, k_norm, wo),
                (h, pos))
            m = plain.rms_norm(h, w_post, eps)
            if c["num_experts"]:
                router, wg, wu, wd = take(4)
                m = m.reshape(b * t, d)
                out, lb, lz, ld = plain.routed_experts(m, router, wg, wu, wd,
                                                       c)
                margin = jnp.minimum(margin, _router_margin(m @ router, c))
                h = h + out.reshape(b, t, d)
                balance, z, load = balance + lb / layers, z + lz / layers, \
                    load + ld
            else:
                wg, wu, wd = take(3)
                h = h + (jax.nn.silu(m @ wg) * (m @ wu)) @ wd
        w_f, w_lm = take(2)

        def head(xs):                       # one sequence: [T, D], [T, 1]
            logits = plain.rms_norm(xs[0], w_f, eps) @ w_lm
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1), xs[1],
                                       axis=-1)
            return nll.sum(), logits[:, :PROBE_COLUMNS]

        nll, probe = jax.lax.map(head, (h, batch["labels"]))
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")
    loss = nll.sum() / (b * t) + c["router_aux_loss_coef"] * balance \
        + c["router_z_loss_coef"] * z
    return {"loss": loss, "logits": probe, "expert_load": load,
            "router_margin": margin.reshape(b, t)}


def _router_margin(router_logits, c):
    """How far a token's choice of experts is from another one: (p_k -
    p_(k+1)) / p_k of its sorted router probabilities, [N]. A token whose
    margin is under what bf16 activations move the probabilities by may
    route its k-th assignment to another expert in the program than in the
    float32 reference, and both are right."""
    k = c["num_experts_per_tok"]
    if k >= c["num_experts"]:
        return jnp.full(router_logits.shape[:1], jnp.inf)
    top = jax.lax.top_k(jax.nn.softmax(router_logits, -1), k + 1)[0]
    return (top[:, k - 1] - top[:, k]) / top[:, k - 1]


def check(cfg, first, want, scalars):
    """checks.training on the loss and on the logits of the tokens whose
    routing is decided, and one verdict more, `dropless`: in the first step
    every one of the top_k assignments of every token, in every layer, was
    computed by an expert.

    A token is decided where the reference's router margin (_router_margin)
    is at least `reference.router_margin` of the configuration. Under it,
    bf16 activations may turn the token's k-th choice to the next expert,
    which replaces an eighth of its experts' output and moves its logits as
    far as a dropped expert would: such tokens are left out of the logits'
    comparison (and counted), never the tolerance widened to let them in.
    At depth 1 a token's routing moves no other token's logits."""
    load = np.asarray(first["expert_load"], np.int64)
    decided = np.asarray(want["router_margin"]) \
        >= cfg["reference"]["router_margin"]
    verdicts, found = checks.training(
        cfg, {"loss": first["loss"], "logits": first["logits"][decided]},
        {"loss": want["loss"], "logits": want["logits"][decided]}, scalars)
    tokens = decided.size
    assignments = tokens * cfg["num_experts_per_tok"] \
        * cfg["num_hidden_layers"]
    verdicts["dropless"] = int(load.sum()) == assignments
    moved = int(np.abs(load - np.asarray(want["expert_load"], np.int64))
                .sum()) // 2
    found += "; logits of %d of %d tokens compared (router margin >= %g; " \
        "over all tokens they are off by %.2e); experts computed %d of %d " \
        "assignments (load %d..%d an expert), at least %d of them by " \
        "another expert than in the reference" % (
            decided.sum(), tokens, cfg["reference"]["router_margin"],
            checks.normalised_error(first["logits"], want["logits"]),
            load.sum(), assignments, load.min(), load.max(), moved)
    return verdicts, found
