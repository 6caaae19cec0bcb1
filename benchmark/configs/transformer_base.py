"""The encoder-decoder transformer (Vaswani et al. 2017) as the benchmark
runs it: program build, batch from a key, operations a token, and the plain
float32 reference forward. Sizes are in the configuration's .json; any
configuration of this architecture (base, big, a tiny one in a test) names
this file as its `module`.
"""
import jax
import jax.numpy as jnp

from benchmark import checks

SAMPLE = "token"
check = checks.training
PROBE_COLUMNS = 128


def build(fluid, cfg, traffic):
    """Build the training program in the current program guard; returns
    what every step fetches: the loss (mean label-smoothed cross-entropy a
    target token) and the logits of the first PROBE_COLUMNS words at every
    position, a crop of the vocabulary projection's output that costs the
    step one small copy. The loss at initialisation is about ln(V) whatever
    the forward does; the logits are what attention, the feed-forward and
    the norms made."""
    from paddle_tpu.models import transformer
    fluid.default_main_program().enable_mixed_precision()
    _, avg_cost, predict = transformer.build_train(
        src_vocab_size=cfg["src_vocab_size"],
        trg_vocab_size=cfg["trg_vocab_size"],
        max_length=traffic["seq_len"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], d_key=cfg["d_key"], d_value=cfg["d_value"],
        d_model=cfg["d_model"], d_inner_hid=cfg["d_inner_hid"],
        dropout_rate=cfg["dropout_rate"],
        label_smooth_eps=cfg["label_smooth_eps"],
        warmup_steps=cfg["warmup_steps"], use_fused_attention=True)
    probe = fluid.layers.crop(
        predict, shape=[-1, -1, min(PROBE_COLUMNS, cfg["trg_vocab_size"])])
    return {"loss": avg_cost, "logits": probe}


def samples_per_step(cfg, traffic):
    """Target positions trained a step; each has a source position beside
    it."""
    return traffic["batch"] * traffic["seq_len"]


def make_batch(cfg, traffic, key):
    """Every sequence at full length: source ids, target ids shifted right
    behind <s> (id 1) as the decoder input, the target ids as labels."""
    b, t = traffic["batch"], traffic["seq_len"]
    k_src, k_trg = jax.random.split(key)
    src = jax.random.randint(k_src, (b, t), 3, cfg["src_vocab_size"],
                             jnp.int32)
    trg = jax.random.randint(k_trg, (b, t), 3, cfg["trg_vocab_size"],
                             jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    full = jnp.full((b, 1), t, jnp.int32)
    return {
        "src_word": src, "src_pos": pos,
        "trg_word": jnp.concatenate(
            [jnp.ones((b, 1), jnp.int32), trg[:, :-1]], axis=1),
        "trg_pos": pos, "src_len": full, "trg_len": full,
        "lbl_word": trg[:, :, None],
        "lbl_weight": jnp.ones((b, t, 1), jnp.float32)}


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one target token with its source token beside it. Two a multiply-add,
    three passes (forward, gradient to the input, gradient to the weights).

    Weights: an encoder layer has four attention projections and the two
    feed-forward matrices, a decoder layer eight projections (self and
    cross) and the feed-forward; the vocabulary projection once. Attention
    scores and the weighted sum are 2 x 2 x keys x width forward for a query
    and have no weight gradient pass of their own, so 3 x that; the
    decoder's self-attention is causal and counts half. Embedding lookups,
    layer norm, softmax and the optimizer are not counted. At the base
    sizes: 386.1e6 at T=256, 551.3e6 at T=2048."""
    d, dff, t = cfg["d_model"], cfg["d_inner_hid"], traffic["seq_len"]
    dk, dv = cfg["n_head"] * cfg["d_key"], cfg["n_head"] * cfg["d_value"]
    attn_w = d * (2 * dk + dv) + dv * d          # q, k, v and output
    ffn_w = 2 * d * dff
    enc_w = attn_w + ffn_w
    dec_w = 2 * attn_w + ffn_w
    weights = cfg["n_layer"] * (enc_w + dec_w) + d * cfg["trg_vocab_size"]
    attn_core = 2 * t * (dk + dv)                # one full attention, forward
    cores = cfg["n_layer"] * (1 + 0.5 + 1)       # encoder, causal, cross
    return 3 * 2 * weights + 3 * attn_core * cores


def flash_kernel_ops(cfg, traffic):
    """Matmul operations a step of the three flash kernels, counting only
    the pairs inside the mask, every sequence at full length T: a layer of
    the encoder attends T x T pairs, a layer of the decoder T (T + 1) / 2 in
    its causal self-attention and T x T in its cross-attention. A pair costs
    a head two operations a multiply-add over d_key for each product with q
    or k and over d_value for each with v or dO: the forward kernel q k^T
    and p v; dK/dV k q^T, p^T dO, v dO^T and ds^T q; dQ q k^T, dO v^T and
    ds k (4, 8 and 6 x D at d_key = d_value = D, the count of
    configs/smallthinker.py). Edge blocks of the causal layers compute
    masked pairs too, so a share of the peak from this cannot pass 100 %.
    Below the program's flash crossover (the t256 cell) no such kernel runs
    and the reader that divides by their time finds nothing."""
    t, dk, dv = traffic["seq_len"], cfg["d_key"], cfg["d_value"]
    pairs = cfg["n_layer"] * (t * t + t * (t + 1) // 2 + t * t) \
        * traffic["batch"] * cfg["n_head"]
    return {"ptpu_flash_fwd": 2 * (dk + dv) * pairs,
            "ptpu_flash_bwd_dkdv": 4 * (dk + dv) * pairs,
            "ptpu_flash_bwd_dq": 2 * (2 * dk + dv) * pairs}


def reference(cfg, traffic, params, batch):
    """What `build` fetches, from the plain forward pass in float32: dense
    attention, no AMP, no kernel. Every sequence is full
    length, so the only mask is the causal one. `params` are the program's
    parameters in the order it created them (see the `take` calls)."""
    params = list(params)
    pos = [0]
    h, d = cfg["n_head"], cfg["d_model"]

    def take(n):
        got = params[pos[0]:pos[0] + n]
        pos[0] += n
        return got if n > 1 else got[0]

    def norm(x):
        g, b = take(2)
        mean = x.mean(-1, keepdims=True)
        var = jnp.square(x - mean).mean(-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b

    def attention(q_in, kv_in, causal):
        wq, wk, wv, wo = take(4)
        b, tq, _ = q_in.shape
        tk = kv_in.shape[1]
        q = (q_in @ wq).reshape(b, tq, h, -1)
        k = (kv_in @ wk).reshape(b, tk, h, -1)
        v = (kv_in @ wv).reshape(b, tk, h, -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["d_key"] ** -0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((tq, tk), bool)), s, -jnp.inf)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return ctx.reshape(b, tq, -1) @ wo

    def ffn(x):
        w1, b1, w2, b2 = take(4)
        return jax.nn.relu(x @ w1 + b1) @ w2 + b2

    def embed(word, position):
        table, pos_table = take(2)
        return table[word] * d ** 0.5 + pos_table[position]

    with jax.default_matmul_precision("highest"):
        x = embed(batch["src_word"], batch["src_pos"])
        for _ in range(cfg["n_layer"]):
            n = norm(x)
            x = x + attention(n, n, causal=False)
            x = x + ffn(norm(x))
        enc = norm(x)
        y = embed(batch["trg_word"], batch["trg_pos"])
        for _ in range(cfg["n_layer"]):
            n = norm(y)
            y = y + attention(n, n, causal=True)
            y = y + attention(norm(y), enc, causal=False)
            y = y + ffn(norm(y))
        logits = norm(y) @ take(1)
    if pos[0] != len(params):
        raise ValueError("the reference read %d parameters, the program has "
                         "%d: the two are not the same architecture"
                         % (pos[0], len(params)))
    v = cfg["trg_vocab_size"]
    probe = logits[:, :, :PROBE_COLUMNS]
    logits = logits.reshape(-1, v)
    label = batch["lbl_word"].reshape(-1, 1)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, label, axis=1)
    # uniform label smoothing: (1 - eps) on the label, eps / V everywhere
    eps = cfg["label_smooth_eps"]
    cost = (1 - eps) * nll - eps * logp.mean(-1, keepdims=True)
    weight = batch["lbl_weight"].reshape(-1, 1)
    return {"loss": (cost * weight).sum() / weight.sum(), "logits": probe}

