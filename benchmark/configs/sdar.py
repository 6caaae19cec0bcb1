"""SDAR-30B-A3B-Chat (paddle_tpu/models/causal_lm.py, `model_type:
sdar_moe`, `objective: block_diffusion`) as the benchmark trains it: one of
8 chips' share of published layers 0-3 under the block-diffusion objective
(SDAR, arXiv:2510.06303; BD3-LM, arXiv:2503.09573): a noised and a clean
copy of every sequence side by side (2 T rows through the trunk, T through
the last layer's W_o and FFN and the head), attention under the
block-diffusion mask (blocks of 4), a 1/t-weighted loss on the masked
positions alone, over a Qwen3-MoE-shaped layer (32 query heads on 4, a
QK-norm a head, top-8 of 128 softmax-routed experts renormalised, 16 held),
an eighth of the vocabulary. This file has the batch (clean ids, a noise
level a block, the mask draws, the noised ids and the weights, all from the
key), the operations a token, the counts the kernels' readers divide by (the
flash kernels' visible pairs, the held experts' three matmuls, the embedding
gradient's table), the benchmark's copy of the plain float32 reference,
blocked so that it fits beside the training state (the [2 T, 2 T] mask
written out HERE from the (copy, position, block) rule and not taken from
the kernels' helper or the builder's; attention a head at a time as a dense
masked softmax; the held experts one at a time; the head in blocks of rows;
every layer under jax.checkpoint), and the cell's check, which also holds
five gradients to the reference's jax.grad.
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
# every assignment a held expert computed is a row through three matrices
# of hidden_size x moe_intermediate_size (2048 x 768), by the sizes
# `resolve` gives a share
expert_matmul_ops = base.expert_matmul_ops
_router_margin = shared._router_margin      # the held-set margin on softmax
# rows and columns of a weight's gradient that are fetched: a corner, every
# element of which sums over every row
CORNER = 128
# the gradients the cell holds: fetch -> (layer, the parameter's role, its
# place among the layer's parameters as `reference` takes them)
GRADIENTS = {"wq_3_grad": (3, "wq", "wq"), "wk_3_grad": (3, "wk", "wk"),
             "wv_0_grad": (0, "wv", "wv"),
             "expert_gate_2_grad": (2, "experts.w_gate", "w_gate")}
_LAYER = ("input_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
          "post_norm", "router", "w_gate", "w_up", "w_down")


def _resolved(cfg):
    from paddle_tpu.models.causal_lm import resolve
    return resolve(cfg)


def build(fluid, cfg, traffic):
    """Builds the training program in the current guard, after asking the
    program for the objective: a program from before it would build the
    next-token model under this configuration's name, this names the cause.
    Fetches: the loss; the logits of the first PROBE_COLUMNS words on the
    noised rows; `expert_load`; `q_0` and `k_0`, head 0's query and key as
    the core of layer 0 reads them, normed and turned, on both copies [B, 2
    T, 1, 128]; `attention_0` (both copies) and `attention_3` (the noised
    rows), PROBE_COLUMNS channels of the attention's output behind W_o;
    `routed`, as many of what the held experts of layer 0 add to a row, both
    copies; `state`, of what the final norm reads; and, of the backward
    pass, before the clip, a corner of the gradients of layer 3's W_q and
    W_k, of layer 0's W_v, of the held experts' gate matrices in layer 2
    (with `load_2`, that layer's own assignments by expert), and the
    embedding's mask row whole."""
    from paddle_tpu.models import causal_lm
    if not hasattr(causal_lm, "_objective"):
        raise NotImplementedError(
            "this program's causal_lm has no training objective but "
            "next-token (no two copies of a sequence, no block-diffusion "
            "mask in the attention, no weighted loss): it cannot build %s"
            % (cfg["name"],))
    fluid.default_main_program().enable_mixed_precision()
    loss, logits, load = causal_lm.build_train(
        cfg, traffic["seq_len"], learning_rate=cfg["learning_rate"],
        beta1=cfg["adam_beta1"], beta2=cfg["adam_beta2"],
        epsilon=cfg["adam_epsilon"], clip_norm=cfg["clip_norm"])
    block = fluid.default_main_program().global_block()
    layers = fluid.layers
    hd, last = cfg["head_dim"], cfg["num_hidden_layers"] - 1

    def behind(name):       # what the op that reads parameter `name` gives
        return next(op for op in block.ops
                    if name in op.input_arg_names).output("Out")[0]

    def columns(name):
        var = block.var(name)
        return layers.crop(var, shape=[-1, -1, min(PROBE_COLUMNS,
                                                   int(var.shape[-1]))])

    core = next(op for op in block.ops if op.type == "fused_attention")
    routed = next(op for op in block.ops if op.type == "moe_ffn")
    state = next(op for op in block.ops if op.type == "rms_norm"
                 and op.input("Scale")[0] == "final_norm").input("X")[0]
    fetches = {
        "loss": loss,
        "logits": layers.crop(logits, shape=[-1, -1, min(
            PROBE_COLUMNS, cfg["vocab_size"])]),
        "expert_load": load,
        "q_0": layers.crop(block.var(core.input("Q")[0]),
                           shape=[-1, -1, 1, hd]),
        "k_0": layers.crop(block.var(core.input("K")[0]),
                           shape=[-1, -1, 1, hd]),
        "attention_0": columns(behind("layer_0.wo")),
        "attention_3": columns(behind("layer_%d.wo" % last)),
        "routed": columns(routed.output("Out")[0]),
        "state": columns(state)}
    for fetch, (i, role, _) in GRADIENTS.items():
        grad = block.var("layer_%d.%s@GRAD" % (min(i, last), role))
        fetches[fetch] = layers.crop(
            grad, shape=[int(n) for n in grad.shape[:-2]]
            + [min(CORNER, int(n)) for n in grad.shape[-2:]])
    # the assignments by expert of the layer whose gate matrices are held
    fetches["load_2"] = block.var([op for op in block.ops
                                   if op.type == "moe_ffn"][
        min(GRADIENTS["expert_gate_2_grad"][0], last)].output(
            "ExpertLoad")[0])
    fetches["mask_row_grad"] = layers.crop(
        block.var("embedding@GRAD"), shape=[1, cfg["hidden_size"]],
        offsets=[cfg["mask_token_id"], 0])
    return fetches


def samples_per_step(cfg, traffic):
    """The DATA tokens a step: each is two rows through the trunk and one
    through the head, and a token of the corpus all the same."""
    return traffic["batch"] * traffic["seq_len"]


def make_batch(cfg, traffic, key):
    """One step's feed from the key, cut as a user of the library cuts it:
    `ids` [B, T] uniform over the data words 0 .. mask_token_id - 1 (the
    mask id is the last held word), and `noisy_ids` and `loss_weight` of
    `causal_lm_reference.block_diffusion_batch` (a noise level a block of
    block_length positions on the linear schedule, t_b = eps + (1 - eps)
    u_b; m_i Bernoulli(t_b(i)); the mask id where m_i; m_i / t_b(i)); `pos`
    0 .. T - 1."""
    from paddle_tpu.models.causal_lm_reference import block_diffusion_batch
    b, t = traffic["batch"], traffic["seq_len"]
    k_ids, k_noise = jax.random.split(key)
    ids = jax.random.randint(k_ids, (b, t), 0, cfg["mask_token_id"],
                             jnp.int32)
    noisy_ids, loss_weight = block_diffusion_batch(
        k_noise, ids, cfg["block_length"], cfg["mask_token_id"],
        cfg["noise_eps"])
    return {"ids": ids, "noisy_ids": noisy_ids,
            "pos": jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t)),
            "loss_weight": loss_weight}


def visible_pairs(t, block_length):
    """(query, key) pairs of the block-diffusion mask over two copies of t
    tokens, by copy of the query: a noised row of block b sees its own
    block_length noised rows and the b x block_length clean rows before its
    block, a clean one the (b + 1) x block_length clean rows up to its
    block's end: t^2 / 2 + t x block_length / 2 each (t (t + 4) / 2 at
    blocks of 4), t^2 + 4 t together."""
    half = t * (t + block_length) // 2
    return {"noised": half, "clean": half}


def forward_macs(cfg, traffic):
    """Multiply-adds of one DATA token's forward pass, by part, of what the
    LOSS needs: two rows a token through every layer's W_q, W_k and W_v and
    through layers 0-2's W_o, router and experts, ONE through the last
    layer's W_o, router and experts and the head (nothing reads the clean
    copy's rows behind the last layer's core); the core over the visible
    pairs of both copies on layers 0-2 and of the NOISED queries alone on
    the last (the kernel computes the last layer's clean queries too: they
    are counted for the kernel, `flash_kernel_ops`, and not here); the
    router at its published 128 columns; the held experts a row is expected
    to reach (8 x 16 / 128 = 1 of them, three matrices of 2048 x 768). Norms,
    rotary, the routing's sort and the optimizer are not counted. At the
    cell's four layers and T = 4096: 333.9e6, attention's projections 42.7
    %, its core 35.2 %, the held experts 9.9 %, the head 11.6 %, the router
    0.5 %."""
    c = _resolved(cfg)
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    layers, t = c["num_hidden_layers"], traffic["seq_len"]
    pairs = visible_pairs(t, cfg["block_length"])
    behind = 2 * (layers - 1) + 1       # rows a token behind the cores
    return {
        "attention_projections": 2 * layers * d * hd * (h + 2 * hkv)
        + behind * h * hd * d,
        "attention": ((layers - 1) * sum(pairs.values()) + pairs["noised"])
        / t * h * 2 * hd,
        "router": behind * d * c["num_experts"],
        "routed_experts": behind * shared.held_share(c) * 3 * d * f,
        "head": d * c["vocab_size"]}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one data token, by configs/transformer_base.py's convention: two a
    multiply-add, three passes. At the cell's four layers and T = 4096: 3 x
    2 x 333.9e6 = 2003e6, 8.2e12 a step."""
    return 3 * 2 * sum(forward_macs(cfg, traffic).values())


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step that the three flash kernels are asked for:
    every layer's 32 query heads over the visible pairs of BOTH copies (t^2
    + 4 t a head: the last layer's clean queries are computed too), 4, 8
    and 6 x 128 a pair and query head for the forward, dK/dV and dQ kernels
    (configs/smallthinker.py has why). At tiles of 512 x 512 and T = 4096 a
    head's 16 query blocks visit 80 key blocks for 64.06 blocks' worth of
    visible pairs, and the 8 noised diagonal blocks hold 4 visible keys a
    row of 512, so a share of the peak from this says what the mask costs
    and cannot pass 100 %."""
    pairs = sum(visible_pairs(traffic["seq_len"],
                              cfg["block_length"]).values()) \
        * cfg["num_attention_heads"] * cfg["num_hidden_layers"] \
        * traffic["batch"]
    hd = cfg["head_dim"]
    return {"ptpu_flash_fwd": 4 * hd * pairs,
            "ptpu_flash_bwd_dkdv": 8 * hd * pairs,
            "ptpu_flash_bwd_dq": 6 * hd * pairs}


def embedding_grad_bytes(cfg, traffic):
    """Bytes a step that the embedding's gradient has to move through HBM,
    counted from the work (configs/causal_lm.py's convention): the dense
    [18992, 2048] float32 table written once (155.6e6) and the [8192, 2048]
    float32 rows of the output's gradient read once (67.1e6: both copies'
    lookups are ONE op of 2 T ids). The rows are counted here, where
    configs/granite_4_0_h_micro.py leaves its own out: in this cell's
    compiled step the kernel's rows operand is defined in HBM (`{1,0:T(8,
    128)}` with no `S(1)`; AOT compile, PR 66: 64 MiB do not fit VMEM)."""
    rows = 2 * traffic["batch"] * traffic["seq_len"]
    return 4 * cfg["hidden_size"] * (cfg["vocab_size"] + rows)


def mask(t, block_length):
    """[2 T, 2 T] bool, row r sees row s, from the rule by (copy, position,
    block): rows 0 .. T - 1 are the noised copy and T .. 2 T - 1 the clean
    one; b = position // block_length; r sees s iff both are noised and b_s
    = b_r, or r is noised, s clean and b_s < b_r, or both are clean and b_s
    <= b_r."""
    noised = jnp.concatenate([jnp.ones(t, bool), jnp.zeros(t, bool)])
    block = jnp.concatenate([jnp.arange(t), jnp.arange(t)]) // block_length
    r_noised, s_noised = noised[:, None], noised[None, :]
    b_r, b_s = block[:, None], block[None, :]
    return (r_noised & s_noised & (b_s == b_r)) \
        | (r_noised & ~s_noised & (b_s < b_r)) \
        | (~r_noised & ~s_noised & (b_s <= b_r))


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from a plain float32 forward on the program's
    weights and the batch as fed, given the same share, cut into blocks
    (module docstring); a test holds it equal to the unblocked reference of
    paddle_tpu/models/causal_lm_reference.py. `router_margin` [B, T] is the
    least, over the four layers, of a NOISED row's held-set margin
    (configs/smallthinker.py's, on softmax scores), `experts_margin` [B, 2
    T] layer 0's own on every row. Of the backward pass: jax.grad of the
    whole stack's loss with respect to five arrays (three attention
    matrices, layer 2's held gate matrices, the embedding's mask row), every
    layer under jax.checkpoint: layer 0's W_v and the mask row reach the
    loss through every layer, so no later start would do."""
    from paddle_tpu.models import causal_lm_reference as plain
    c = _resolved(cfg)
    params = iter(params)

    def take(names):
        return {name: jnp.asarray(next(params), jnp.float32)
                for name in names}

    eps, hd, d = cfg["rms_norm_eps"], cfg["head_dim"], cfg["hidden_size"]
    layers, mask_id = cfg["num_hidden_layers"], cfg["mask_token_id"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ids, pos = batch["ids"], batch["pos"]
    b, t = ids.shape
    embedding = take(["embedding"])["embedding"]
    weights = [take(_LAYER) for _ in range(layers)]
    w_f, w_lm = take(["final_norm"])["final_norm"], take(["head"])["head"]
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")
    tokens = jnp.concatenate([batch["noisy_ids"], ids], 1)      # [B, 2 T]
    pos = jnp.concatenate([pos, pos], 1)
    visible = mask(t, cfg["block_length"])

    def attention(a, w, last):
        """(the layer's output behind W_o [B, 2 T or T, D], head 0's query
        and key as the core reads them [B, 2 T, hd])."""
        def sequence(xs):                   # [2 T, D], [2 T]
            a, pos_row = xs

            def heads(x, n, weight):
                x = plain.rms_norm(x.reshape(2 * t, n, hd), weight, eps)
                return plain.rope(x[None], pos_row[None],
                                  cfg["rope_theta"])[0]

            q = heads(a @ w["wq"], h, w["q_norm"])
            k = heads(a @ w["wk"], hkv, w["k_norm"])
            v = (a @ w["wv"]).reshape(2 * t, hkv, hd)
            k_heads, v_heads = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
            # the last layer's clean queries are read by nothing
            rows = t if last else 2 * t

            @jax.checkpoint
            def one_head(args):
                qh, head = args
                kh, vh = k_heads[head // (h // hkv)], \
                    v_heads[head // (h // hkv)]
                s = jnp.where(visible[:rows], (qh @ kh.T) * hd ** -0.5,
                              -jnp.inf)
                return jax.nn.softmax(s, -1) @ vh

            ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2)[:, :rows],
                                         jnp.arange(h)))
            return ctx.transpose(1, 0, 2).reshape(rows, h * hd) @ w["wo"], \
                q[:, 0], k[:, 0]
        return jax.lax.map(sequence, (a, pos))

    def experts(m, w):
        """(routed, load, margin) of one layer's held experts on m [N, D]:
        softmax over all 128 columns in float32, the 8 largest, weights
        over their sum; the held experts one at a time over the rows whose
        choice names them (every row, masked)."""
        logits = m @ w["router"]
        gate, idx = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                  c["num_experts_per_tok"])
        if c["norm_topk_prob"]:
            gate = gate / gate.sum(-1, keepdims=True)

        @jax.checkpoint
        def one(total, args):
            i, wg, wu, wd = args
            weight = jnp.sum(jnp.where(idx == i, gate, 0.0), -1)
            return total + weight[:, None] * (
                (jax.nn.silu(m @ wg) * (m @ wu)) @ wd), None

        held = c["first_expert"] + jnp.arange(w["w_gate"].shape[0])
        out, _ = jax.lax.scan(one, jnp.zeros_like(m), (
            held, w["w_gate"], w["w_up"], w["w_down"]))
        load = jnp.sum(idx[:, :, None] == jnp.arange(c["num_experts"]),
                       axis=(0, 1), dtype=jnp.int32)
        return out, load, _router_margin(logits, c)

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(x, w, last):
        """(the state after the layer, what the layer found)."""
        out, q, k = attention(plain.rms_norm(x, w["input_norm"], eps), w,
                              last)
        x = (x[:, :t] if last else x) + out
        m = plain.rms_norm(x, w["post_norm"], eps)
        routed, load, margin = experts(m.reshape(-1, d), w)
        routed = routed.reshape(x.shape)
        return x + routed, {
            "attention": out[..., :PROBE_COLUMNS], "q": q, "k": k,
            "routed": routed[..., :PROBE_COLUMNS], "load": load,
            "margin": margin.reshape(b, -1)}

    held = {fetch: (min(i, layers - 1), place)
            for fetch, (i, _, place) in GRADIENTS.items()}

    def loss_of(theta):
        """(the loss, what was found) under `theta` for the five arrays."""
        x = jnp.where((tokens == mask_id)[..., None], theta["mask_row_grad"],
                      embedding[tokens])
        found = []
        for i in range(layers):
            w = dict(weights[i], **{place: theta[fetch] for fetch, (at, place)
                                    in held.items() if at == i})
            x, one = layer(x, w, i == layers - 1)
            found.append(one)

        @jax.checkpoint
        def head(xs):
            rows, labels, weight = xs
            logits = plain.rms_norm(rows, w_f, eps) @ w_lm
            nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                       labels[:, None], axis=-1)[:, 0]
            return (weight * nll).sum(), logits[:, :PROBE_COLUMNS]

        n = min(HEAD_ROWS, b * t)
        nll, probe = jax.lax.map(head, (
            x.reshape(-1, n, d), ids.reshape(-1, n),
            batch["loss_weight"].reshape(-1, n)))
        return nll.sum() / (b * t), (probe.reshape(b, t, -1), x, found)

    with jax.default_matmul_precision("highest"):
        theta = {fetch: weights[at][place]
                 for fetch, (at, place) in held.items()}
        theta["mask_row_grad"] = embedding[mask_id]
        (loss, (probe, state, found)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(theta)
    out = {"loss": loss, "logits": probe,
           "expert_load": sum(one["load"] for one in found),
           "q_0": found[0]["q"][:, :, None], "k_0": found[0]["k"][:, :, None],
           "attention_0": found[0]["attention"],
           "attention_3": found[-1]["attention"],
           "routed": found[0]["routed"],
           "state": state[..., :PROBE_COLUMNS],
           "router_margin": jnp.stack(
               [one["margin"][:, :t] for one in found]).min(0),
           "experts_margin": found[0]["margin"],
           "load_2": found[held["expert_gate_2_grad"][0]]["load"]}
    for fetch, grad in grads.items():
        out[fetch] = grad[None] if fetch == "mask_row_grad" \
            else grad[..., :CORNER, :CORNER]
    return out


# what is compared at the noised rows whose routing is decided in every
# layer, and at the rows of both copies that layer 0 decides; the rest at
# every row (layer 0's attention lies before any router) or as they are (the
# gradients, sums over rows)
_DECIDED = ("logits", "attention_3", "state")
_FIRST_DECIDED = ("routed",)
_MARGINS = ("router_margin", "experts_margin")
# an expert of layer 2 whose count of rows differs from the reference's by
# more than this is left out of `expert_gate_2_grad`: the masked positions
# are one word and move as ONE cluster of rows, thousands at once
SAME_ROWS = 64


def check(cfg, first, want, scalars):
    """checks.training on every fetch, each by its largest error over the
    reference's largest value: the loss, `q_0`, `k_0` and `attention_0` on
    every row of both copies (no router lies before them); `routed` at the
    rows of both copies that layer 0 decides (the reference's held-set
    margin, configs/smallthinker.py's, at least `reference.router_margin`:
    under it bf16 activations may turn an assignment to or from a held
    expert, which moves the row as far as a dropped expert would; such rows
    are left out and counted, never the tolerance widened to let them in);
    `logits`, `attention_3` and `state` at the noised rows decided in EVERY
    layer; the gradients of W_q, W_k, W_v and the mask row as they are (sums
    over rows, so a row whose assignment turned is in them whole).
    `expert_gate_2_grad` is held by the MEDIAN over the held experts of an
    expert's largest error (over the largest reference value of them all):
    a row whose assignment turns between bf16 and float32 is in ONE
    expert's sum whole, a masked row weighs 1 / t, up to a thousand times
    another, and the masked positions, ONE word, may turn together; by the
    largest error over all sixteen the fetch read 5.5e-3 to 0.10 on
    fourteen seeds and 0.30 and 0.42 on two (my chip runs, PR 66), with
    every expert's rows within 64 of the reference's. An expert of layer 2
    whose rows (`load_2`) differ from the reference's by more than
    SAME_ROWS is left out and counted. The logits are also held by their
    mean error over their mean size. `dropless`: every one of the 8
    assignments of every row in every layer was counted (two copies in
    layers 0-2, the noised rows in the last: 229,376 at T = 4096), and the
    rows the held experts computed differ from the reference's by no more
    than the assignments that went to another expert."""
    c = _resolved(cfg)
    tolerance = cfg["reference"]["tolerance"]
    decided = np.asarray(want["router_margin"]) \
        >= cfg["reference"]["router_margin"]
    first_decided = np.asarray(want["experts_margin"]) \
        >= cfg["reference"]["router_margin"]
    held = slice(c["first_expert"], c["first_expert"] + c["experts_held"])
    same_rows = np.abs(
        np.asarray(first["load_2"], np.int64)[held]
        - np.asarray(want["load_2"], np.int64)[held]) <= SAME_ROWS

    def compared(x):
        out = {}
        for name in want:
            if name in ("expert_load", "load_2") or name in _MARGINS:
                continue
            value = np.asarray(x[name], np.float32).reshape(
                np.asarray(want[name]).shape)
            out[name] = value[decided] if name in _DECIDED \
                else value[first_decided] if name in _FIRST_DECIDED \
                else value[same_rows] if name == "expert_gate_2_grad" \
                else value
        return out

    got, ref = compared(first), compared(want)
    # the experts' gate matrices: an expert's largest error over the largest
    # reference value of all compared experts, and the MEDIAN of that over
    # the experts (a row that turns is in ONE expert's sum whole, and a
    # masked row weighs up to 1 / noise_eps)
    gate_got, gate_ref = got.pop("expert_gate_2_grad"), \
        ref.pop("expert_gate_2_grad")
    by_expert = np.abs(gate_got - gate_ref).reshape(len(gate_ref), -1).max(
        -1) / np.abs(gate_ref).max() if same_rows.any() else np.zeros(1)
    gate_error = float(np.median(by_expert))
    verdicts, found = checks.training(cfg, got, ref, scalars)
    verdicts["reference"] = verdicts["reference"] \
        and gate_error <= tolerance["expert_gate_2_grad"]
    mean_error = float(np.abs(got["logits"] - ref["logits"]).mean()
                       / np.abs(ref["logits"]).mean())
    verdicts["reference"] = verdicts["reference"] \
        and mean_error <= tolerance["logits_mean"]
    load = np.asarray(first["expert_load"], np.int64)
    want_load = np.asarray(want["expert_load"], np.int64)
    rows = first_decided.size * (c["num_hidden_layers"] - 1) + decided.size
    assignments = rows * c["num_experts_per_tok"]
    moved = int(np.abs(load - want_load).sum()) // 2
    verdicts["dropless"] = int(load.sum()) == assignments and abs(
        int(load[held].sum()) - int(want_load[held].sum())) <= moved
    found += "; expert_gate_2_grad off by %.2e (tolerance %g; the median " \
        "over the compared experts, the largest of them %.2e)" % (
            gate_error, tolerance["expert_gate_2_grad"], by_expert.max())
    found += "; logits_mean off by %.3e (tolerance %g); logits, " \
        "attention_3 and state of %d of %d noised rows compared (router " \
        "margin >= %g in every layer; over all rows the logits are off by " \
        "%.2e), routed of %d of %d rows (that margin in layer 0); %d of %d " \
        "assignments counted, the %d held experts computed %d rows " \
        "(reference %d; %d..%d an expert), at least %d assignments went " \
        "to another expert than in the reference; expert_gate_2_grad of %d " \
        "of the %d held experts (layer 2's rows within %d of the " \
        "reference's)" % (
            mean_error, tolerance["logits_mean"], decided.sum(),
            decided.size, cfg["reference"]["router_margin"],
            checks.normalised_error(first["logits"], want["logits"]),
            first_decided.sum(), first_decided.size, load.sum(),
            assignments, c["experts_held"], load[held].sum(),
            want_load[held].sum(), load[held].min(), load[held].max(), moved,
            same_rows.sum(), same_rows.size, SAME_ROWS)
    return verdicts, found
