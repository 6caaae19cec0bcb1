"""The plain reference of models/causal_lm.py: the forward pass, its loss
and, through jax.grad, its gradients, in jax.numpy and float32 under
jax.default_matmul_precision("highest"). No kernel, no cache, no sorting:
attention is the dense softmax(q k^T) v and every expert is computed over
all tokens, one expert at a time, and masked. The tests hold the Program to
it (tests/unittests/test_causal_lm.py).

It follows Hugging Face's `modeling_olmoe.py` and, for the keys
SmallThinker-21BA3B adds (grouped queries, a window and rotary positions by
layer, the router read before attention, ReGLU experts), the layer as
PowerInfer's `config.json` and model card give it; for Qwen3-Next's (gated
delta nets on three layers of four, a gated full-attention layer with
QK-norm a head and partial rotary, zero-centred norm weights, a gated shared
expert) Hugging Face's `modeling_qwen3_next.py`, the delta rule as its
token-by-token recurrence (`torch_recurrent_gated_delta_rule`). Each
departure is marked "Departure:" below. For a looped model (Ouro,
`model_type: ouro`; arXiv:2510.25741: total_ut_steps passes over the same
layers, sandwich norms, an exit gate over the passes' losses) the layer is
Hugging Face's `modeling_ouro.py` and the loss the paper's. For LFM2
(`model_type: lfm2_moe`: gated short convolutions and attention layers by
`layer_types`, leading dense layers, a sigmoid router whose top-k is chosen
with an expert bias and weighed without it, a tied head) Hugging Face's
`modeling_lfm2_moe.py`; it has no multi-token prediction. For Xing4.0
(`model_type: xing4_0`: DeepSeek-V3's latent attention, sigmoid router with
a correction bias and ungated shared expert, behind `hc_mult` residual
streams mixed by manifold-constrained hyper-connections) DeepSeek-V3's
released `modeling_deepseek.py` for what its keys mean and arXiv:2512.24880
(over arXiv:2409.19606) for the `hc_*` keys; its multi-token-prediction
module does not fit beside its cut and is left out of it. For GLM-4.7-Flash
(`model_type: glm4_moe_lite`: DeepSeek-V3's block without the streams, a
head of 192 + 64 on a value of 256, and `num_nextn_predict_layers` 1)
DeepSeek-V3's report, arXiv:2412.19437 section 2.2, for the
multi-token-prediction module and its loss (`mtp_input`, `loss_fn`).
For Phi-4-mini-flash-reasoning (`model_type: phi4flash`; SambaY,
arXiv:2507.06607: Mamba mixers, windowed and full differential attention,
arXiv:2410.05258, and a cross-decoder whose gated memory units read ONE
layer's scan output and whose attention layers read ONE layer's keys and
values; LayerNorm, a gated MLP whose first matrix holds gate and value, a
tied head, no positional term) the equations of ISSUE 54 as
`modeling_phi4flash.py` has them: `selective_scan` is Mamba-1's recurrence
token by token (`lax.scan`), `differential_attention` two dense masked
softmax maps a pair of heads.
For granite-4.0-h-micro (`model_type: granitemoehybrid`: nine Mamba-2
mixers, arXiv:2405.21060, to one attention layer without positional term;
embedding_multiplier, residual_multiplier, attention_multiplier and
logits_scaling on the main path; a tied head) the equations of ISSUE 57 as
`modeling_granitemoehybrid.py` has them: `ssd_scan` is Mamba-2's recurrence
token by token (`lax.scan`; the Program computes it a chunk at a time as
matmuls, and shares no algebra with this), `mamba2` gates BEFORE its norm.
`params` is the list of the Program's parameters in the order
models/causal_lm.py creates them.

One chip's share of a layer comes as arguments: `attention` computes the
heads whose weights it is given (Wq's, Wk's and Wv's columns and Wo's rows
of those heads), `routed_experts` the experts whose weights it is given,
`first_expert` saying which of the router's columns they are, and the
vocabulary is the embedding's and the head's rows and columns. What the
absent heads and experts would add is left out; the shares of all chips sum
to the whole layer (tests/unittests/test_causal_lm_smallthinker.py).
"""
import jax
import jax.numpy as jnp

from .causal_lm import resolve


def layer_config(c, i):
    """The resolved config as layer i reads it: `rope_theta` None where the
    pattern gives the layer no rotary (NoPE), `window` its sliding window or
    None. The reference's own reading of the two patterns, not the
    builder's."""
    cl = dict(c, rope_theta=c["rope_theta"] if c["rope_layers"][i]
              else None, window=c["window_layers"][i],
              lambda_init=c["lambda_init_layers"][i])
    if c["geometry_by_layer"]:
        # laguna's: the layer's kind names its window and its rotary
        # parameters, read here from the config's own keys
        kind = c["layer_types"][i]
        cl["window"] = c["sliding_window"] if kind == "sliding_attention" \
            else None
        rope = c.get("rope_parameters", {}).get(kind)
        if rope is not None:
            cl.update(rope_theta=rope.get("rope_theta", c["rope_theta"]),
                      rope_scaling=rope if rope.get("rope_type") == "yarn"
                      else None,
                      rotary_dim=int(c["head_dim"] * rope.get(
                          "partial_rotary_factor",
                          c["partial_rotary_factor"])))
    return cl


def rms_norm(x, w, eps, zero_centered=False):
    """w * x_hat, or (1 + w) * x_hat where the weight is stored around 0
    (Qwen3NextRMSNorm)."""
    # Departure: HF rounds the normalised value to the input dtype before
    # the weight multiplies it; in float32 the two are the same
    if zero_centered:
        w = 1.0 + w
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, pos, theta, rotary_dim=None, inv_freq=None, interleaved=False,
         table_scale=1.0):
    """x [B, T, H, D], pos [B, T]: HF's rotate_half convention over the
    first R = rotary_dim channels (all by default), the pair (i, i + R/2)
    turns by pos * theta^(-2i/R); the channels from R on pass. inv_freq [R /
    2]: a table that replaces theta^(-2i/R) (`yarn_inv_freq`); interleaved:
    the pairs are (2i, 2i + 1) (DeepSeek-V3's stored layout); cos and sin
    are multiplied by table_scale."""
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle) * table_scale, jnp.sin(angle) * table_scale
    x, rest = x[..., :d], x[..., d:]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(x, 2, axis=-1)
        turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                 -1)
    return jnp.concatenate([turned, rest], -1)


def yarn_mscale(factor, mscale):
    """YaRN's m: 0.1 mscale ln(factor) + 1 past factor 1."""
    return 0.1 * mscale * jnp.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(scaling, theta, d):
    """YaRN's frequency table over the d / 2 rotary pairs (arXiv:2309.00071,
    as DeepSeek-V3's `DeepseekV3YarnRotaryEmbedding` computes it): f_i =
    theta^(-2i/d); the pair that turns `turns` times over the original
    length is number d ln(original / (2 pi turns)) / (2 ln theta); pairs up
    to lo = floor(that at beta_fast) keep f_i, pairs from hi = ceil(that at
    beta_slow) get f_i / factor, a linear ramp between. The reference's own
    reading of `rope_scaling`, not the builder's table."""
    factor, original = scaling["factor"], \
        scaling["original_max_position_embeddings"]

    def pair(turns):
        return d * jnp.log(original / (turns * 2 * jnp.pi)) \
            / (2 * jnp.log(float(theta)))

    lo = jnp.maximum(jnp.floor(pair(scaling.get("beta_fast", 32))), 0)
    hi = jnp.minimum(jnp.ceil(pair(scaling.get("beta_slow", 1))), d - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = float(theta) ** (-2 * i / d)
    ramp = jnp.clip((i - lo) / jnp.maximum(hi - lo, 1e-3), 0, 1)
    return f * (1 - ramp) + f / factor * ramp


def latent_attention(a, pos, wq_a, q_a_norm, wq_b, wkv_a, kv_a_norm, wkv_b,
                     wo, c, w_gate=None):
    """DeepseekV3Attention on a [B, T, D], the naive way: q = N(a W_qa)
    W_qb, a head [q_nope; q_rope]; [c_kv; k_r] = a W_kva; kv = N(c_kv)
    W_kvb, a head [k_nope; v]; rotary turns q_rope of every head and k_r,
    which is then repeated to every head and concatenated behind its
    k_nope; a dense causal softmax over scale x q . k with scale =
    (dn + dr)^(-1/2) m^2, m = yarn_mscale(factor, mscale_all_dim); P v; W_o.

    With wq_a None (q_lora_rank null, DeepSeek-V2-Lite's form) q = a W_qb,
    one matrix; with w_gate [D, H] (bailing_hybrid's head_wise gate) the
    core's output is multiplied by sigmoid(a w_gate), one scalar a head,
    before W_o.

    Departure: HF permutes q_rope's and k_r's channels from interleaved
    pairs to halves and then applies rotate_half; the scores are the same
    and the channels of q and k are those of the stored layout here."""
    b, t, _ = a.shape
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps = c["rms_norm_eps"]
    h = wq_b.shape[1] // (dn + dr)
    scaling = c["rope_scaling"]
    q = ((a if wq_a is None else rms_norm(a @ wq_a, q_a_norm, eps))
         @ wq_b).reshape(b, t, h, dn + dr)
    ckv = a @ wkv_a
    kv = (rms_norm(ckv[..., :c["kv_lora_rank"]], kv_a_norm, eps)
          @ wkv_b).reshape(b, t, h, dn + dv)
    k_r = ckv[..., c["kv_lora_rank"]:].reshape(b, t, 1, dr)
    scale, table, table_scale = (dn + dr) ** -0.5, None, 1.0
    if scaling is not None:
        m = yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))
        scale = scale * m * m
        table = yarn_inv_freq(scaling, c["rope_theta"], dr)
        table_scale = yarn_mscale(scaling["factor"],
                                  scaling.get("mscale", 1)) / m
    q_rope = q[..., dn:]
    if c["rope_theta"] is not None:
        q_rope, k_r = (rope(x, pos, c["rope_theta"], inv_freq=table,
                            interleaved=c["rope_interleaved"],
                            table_scale=table_scale) for x in (q_rope, k_r))
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (b, t, h, dr))],
                        -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., dn:])
    if w_gate is not None:
        ctx = ctx * jax.nn.sigmoid(a @ w_gate)[..., None]
    return ctx.reshape(b, t, h * dv) @ wo, (q, k)


def hyper_connection(x, phi, bias, alpha, c):
    """(H_pre [.., n], H_post [.., n], H_res [.., n, n]) of the streams x
    [.., n, D], a token at a time (arXiv:2512.24880, section 3): x' =
    vec(x) / sqrt(mean(vec(x)^2) + hc_eps), Ht = alpha (x' Phi) + b with
    columns [pre (n) | post (n) | res (n x n by rows)] and alpha one scalar
    a group; H_pre = sigmoid, H_post = 2 sigmoid, H_res = the Sinkhorn
    normalisation of exp(clip(Ht_res)): hc_sinkhorn_iters times columns
    then rows, each divided by its sum + hc_eps.

    Departure (the paper leaves them open, the config names no key): no
    learned weight on the flat RMS norm (it folds into Phi); hc_eps in the
    norm and in both divisors; the clip before the exp."""
    n, eps = x.shape[-2], c["hc_eps"]
    flat = x.reshape(x.shape[:-2] + (-1,))
    z = (flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                              + eps)) @ phi
    ht = jnp.concatenate([jnp.full((n,), alpha[0]), jnp.full((n,), alpha[1]),
                          jnp.full((n * n,), alpha[2])]) * z + bias
    m = jnp.exp(jnp.clip(ht[..., 2 * n:], c["mhc_h_res_clamp_min"],
                         c["mhc_h_res_clamp_max"])).reshape(
                             ht.shape[:-1] + (n, n))
    for _ in range(c["hc_sinkhorn_iters"]):
        m = m / (m.sum(-2, keepdims=True) + eps)        # columns
        m = m / (m.sum(-1, keepdims=True) + eps)        # rows
    return jax.nn.sigmoid(ht[..., :n]), \
        2.0 * jax.nn.sigmoid(ht[..., n:2 * n]), m


def attention(a, pos, wq, wk, wv, q_norm, k_norm, wo, c, w_gate=None,
              found=None, visible=None, rows=None):
    """Causal attention of the heads whose weights are given: wq [D, Hq x
    hd], wk and wv [D, Hkv x hd], wo [Hq x hd, D]; query head h reads
    key/value head h // (Hq / Hkv). c["rope_theta"] None: no position
    enters (NoPE). c["window"] w: key j is visible to query i iff j <= i and
    i - j < w (the Hugging Face sliding-window mask's convention). With
    attention_gate wq is [D, Hq x 2 hd], a head [q, gate], and the context
    is multiplied by sigmoid(gate) before wo; qk_norm "head" is over each
    head's hd after the split, with one weight [hd]. w_gate [D, Hq]
    (laguna's `gating: per-head`): the context of head h is multiplied by
    sigmoid(a w_gate)[h] before wo. Under rope_scaling (a layer's own
    rope_parameters of rope_type yarn) cos and sin are multiplied by
    `attention_factor` where the set gives it, else by m(mscale) /
    m(mscale_all_dim). A dict given as `found` gets, a call, `core_q` and
    `core_k` (q and k as the core reads them: normed and turned) and
    `head_gate` (the per-head gate's sigmoid). `visible` [T, T] bool: the
    mask itself, in place of the causal one and the window (block
    diffusion's, `block_diffusion_mask`); `rows`: the core's output is cut
    to its first so many rows before the gate a head and wo."""
    b, t, _ = a.shape
    hd, eps = c["head_dim"], c["rms_norm_eps"]
    gated, centred = c["attention_gate"] is True, c["norm_zero_centered"]
    h, hkv = wq.shape[1] // (2 * hd if gated else hd), wk.shape[1] // hd
    q, k, v = a @ wq, a @ wk, a @ wv
    if c["qk_norm"] is True:     # over all channels, before the head split
        q = rms_norm(q, q_norm, eps, centred)
        k = rms_norm(k, k_norm, eps, centred)
    q = q.reshape(b, t, h, -1)
    if gated:
        q, gate = q[..., :hd], q[..., hd:]
    k, v = (x.reshape(b, t, hkv, hd) for x in (k, v))
    if c["qk_norm"] == "head":
        q = rms_norm(q, q_norm, eps, centred)
        k = rms_norm(k, k_norm, eps, centred)
    scale, table, table_scale = hd ** -0.5, None, 1.0
    if c["attention_multiplier"] is not None:   # granite's: the scale itself
        scale = c["attention_multiplier"]
    if c["rope_scaling"] is not None:           # YaRN, as latent_attention
        factor = c["rope_scaling"]["factor"]
        m = yarn_mscale(factor, c["rope_scaling"].get("mscale_all_dim", 0))
        scale, table = scale * m * m, yarn_inv_freq(
            c["rope_scaling"], c["rope_theta"], c["rotary_dim"])
        table_scale = yarn_mscale(factor,
                                  c["rope_scaling"].get("mscale", 1)) / m
        if c["rope_scaling"].get("attention_factor") is not None:
            table_scale = c["rope_scaling"]["attention_factor"]
    if c["rope_theta"] is not None:
        q, k = (rope(x, pos, c["rope_theta"], c["rotary_dim"], table,
                     c["rope_interleaved"], table_scale) for x in (q, k))
    if found is not None:
        found.setdefault("core_q", []).append(q)
        found.setdefault("core_k", []).append(k)
    k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if visible is None:
        age = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]   # i - j
        visible = age >= 0
        if c["window"] is not None:
            visible = visible & (age < c["window"])
    s = jnp.where(visible, s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    if gated:
        ctx = ctx * jax.nn.sigmoid(gate)
    if w_gate is not None:
        gate = jax.nn.sigmoid(a @ w_gate)
        ctx = ctx * gate[..., None]
        if found is not None:
            found.setdefault("head_gate", []).append(gate)
    if rows is not None:
        ctx = ctx[:, :rows]
    return ctx.reshape(b, -1, h * hd) @ wo


def layer_norm(x, w, b, eps):
    """LayerNorm over the last axis with weight and bias."""
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def selective_scan(x, delta, a, b, c, d):
    """Mamba-1's recurrence token by token on x, delta [B, T, C], a [C, N],
    b, c [B, T, N], d [C]: s_t[c, n] = exp(delta_t[c] a[c, n]) s_(t-1)[c, n]
    + delta_t[c] b_t[n] x_t[c] from s = 0; y_t[c] = sum_n c_t[n] s_t[c, n]
    + d[c] x_t[c]."""
    def step(s, xs):
        x, dt, b, c = xs
        s = jnp.exp(dt[..., None] * a) * s + (dt * x)[..., None] * b[:, None]
        return s, (s * c[:, None]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros(x.shape[:1] + a.shape, x.dtype),
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1) + d * x


def mamba(a, w_in, w_conv, b_conv, w_x, w_dt, b_dt, a_log, d, w_out,
          found=None):
    """(the mixer's output, its scan output y before the gate) of a Mamba-1
    mixer on a [B, T, D]: [u; z] = a w_in; c = SiLU(conv(u) + b_conv)
    (b_conv None: no bias); [r; B; C] = c w_x; Delta = softplus(r w_dt +
    b_dt); A = -exp(a_log); y = selective_scan(c, Delta, A, B, C, d); (y *
    SiLU(z)) w_out. A dict given as `found` gets `delta`."""
    n, rank = a_log.shape[1], w_dt.shape[0]
    u, z = jnp.split(a @ w_in, 2, axis=-1)
    u = causal_conv(u, w_conv)
    u = jax.nn.silu(u if b_conv is None else u + b_conv)
    r, b, c = jnp.split(u @ w_x, [rank, rank + n], axis=-1)
    delta = jax.nn.softplus(r @ w_dt + b_dt)
    if found is not None:
        found.setdefault("delta", delta)
    y = selective_scan(u, delta, -jnp.exp(a_log), b, c, d)
    return (y * jax.nn.silu(z)) @ w_out, y


def ssd_scan(x, delta, a, b, c, d, found=None, segment=None):
    """Mamba-2's recurrence token by token on x [B, T, H, P], delta [B, T,
    H], a and d [H], b, c [B, T, N] (one group: every head reads the same b
    and c) or [B, T, G, N] (head h reads group h // (H / G)): a head's state
    s [N, P] from 0, s_t = exp(delta_t a) s_(t-1) +
    b_t^T (delta_t x_t); y_t = c_t s_t + d x_t. A dict given as `found`
    gets `state`, the state after the last token [B, H, N, P]. `segment`
    (a divisor of T) changes no result: so many tokens run under one
    jax.checkpoint, and a backward pass keeps a state a segment and not one
    a token (2 MB a token at 64 heads of 64 on 128 states)."""
    def step(s, xs):
        x, dt, b, c = xs                # b, c [B, H, N]: a head's own group's
        s = jnp.exp(dt * a)[..., None, None] * s \
            + b[..., None] * (dt[..., None] * x)[:, :, None, :]
        return s, jnp.einsum("bhn,bhnp->bhp", c, s)

    def tokens(s, xs):
        return jax.lax.scan(step, s, xs)

    def by_head(v):                     # [B, T, (G,) N] -> [B, T, H, N]
        v = v[:, :, None] if v.ndim == 3 else v
        return jnp.repeat(v, x.shape[2] // v.shape[2], axis=2)

    t = x.shape[1]
    b, c = by_head(b), by_head(c)
    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c))
    s = jnp.zeros(x.shape[:1] + (x.shape[2], b.shape[-1], x.shape[3]),
                  x.dtype)
    if segment is None:
        last, y = tokens(s, xs)
    else:
        last, y = jax.lax.scan(jax.checkpoint(tokens), s, tuple(
            v.reshape((t // segment, segment) + v.shape[1:]) for v in xs))
    if found is not None:
        found["state"] = last
    return jnp.moveaxis(y.reshape((t,) + y.shape[-3:]), 0, 1) + d[:, None] * x


def mamba2(a, w_in, w_conv, b_conv, dt_bias, a_log, d, w_norm, w_out, eps,
           found=None, segment=None, groups=1):
    """A Mamba-2 mixer (GraniteMoeHybridMambaLayer; NemotronHMamba2Mixer
    with `groups` G > 1) on a [B, T, D], H heads
    of P with N states, G groups of B and C: [z; xBC; dt] = a w_in, d_i +
    (d_i + 2 G N)
    + H columns; xBC' = SiLU(conv(xBC) + b_conv) (b_conv None: no bias); [x;
    B; C] = xBC', B and C [G, N] a token; Delta = softplus(dt + dt_bias); A
    = -exp(a_log); y =
    ssd_scan(x, Delta, A, B, C, d); RMSNorm(y * SiLU(z)) over each group's
    d_i / G channels under w_norm [d_i] (one group: all d_i at once), the
    gate first; that w_out. A dict given as
    `found` gets the first mixer's `delta`, `scan` (y before the gate) and
    `carried` (y less its skip term D x: what the state gave) and every
    mixer's `state` (the last one's stays)."""
    h = dt_bias.shape[0]
    di = w_out.shape[0]
    n = (w_in.shape[1] - 2 * di - h) // (2 * groups)
    z, xbc, dt = jnp.split(a @ w_in, [di, 2 * di + 2 * groups * n], axis=-1)
    xbc = causal_conv(xbc, w_conv)
    xbc = jax.nn.silu(xbc if b_conv is None else xbc + b_conv)
    x, b, c = jnp.split(xbc, [di, di + groups * n], axis=-1)
    b, c = (v.reshape(v.shape[:2] + (groups, n)) for v in (b, c))
    delta = jax.nn.softplus(dt + dt_bias)
    heads = x.reshape(x.shape[:2] + (h, di // h))
    y = ssd_scan(heads, delta, -jnp.exp(a_log), b, c, d, found=found,
                 segment=segment)
    if found is not None:
        found.setdefault("delta", delta)
        found.setdefault("scan", y.reshape(x.shape))
        found.setdefault("carried",
                         (y - d[:, None] * heads).reshape(x.shape))
    gated = (y.reshape(x.shape) * jax.nn.silu(z)).reshape(
        x.shape[:2] + (groups, di // groups))
    return rms_norm(gated, w_norm.reshape(groups, -1), eps).reshape(
        x.shape) @ w_out


def gated_memory_unit(a, memory, w_in, w_out):
    """(SiLU(a w_in) * memory) w_out: `memory` another layer's scan
    output."""
    return (jax.nn.silu(a @ w_in) * memory) @ w_out


def differential_attention(a, wq, bq, kv, lambdas, subln, wo, bo, c):
    """(output, (k, v)) of differential attention on a [B, T, D]: q = a wq
    + bq as [B, T, H / 2, 2, hd] -> q1, q2; `kv` either this layer's (wk,
    bk, wv, bv), k and v as [B, T, Hkv / 2, 2, hd] -> k1, k2 and V = [v1;
    v2], or another layer's (k, V) as this returns them; a query pair p
    reads key pair p // (pairs / key pairs); P_j = softmax(q_j k_j^T /
    sqrt(hd) + mask), causal and under c["window"]; o = P_1 V - lambda P_2
    V, lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + c["lambda_init"];
    RMSNorm over the 2 hd under `subln`, times (1 - lambda_init); wo + bo.
    A bias of None is no bias."""
    b, t, _ = a.shape
    hd, eps = c["head_dim"], c["rms_norm_eps"]

    def project(w, bias):
        y = a @ w
        return (y if bias is None else y + bias).reshape(b, t, -1, 2, hd)

    q = project(wq, bq)
    if len(kv) == 4:
        k, v = project(*kv[:2]), project(*kv[2:])
        v = v.reshape(b, t, -1, 2 * hd)
    else:
        k, v = kv
    group = q.shape[2] // k.shape[2]
    kr, vr = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqpjd,bkpjd->bpjqk", q, kr) * hd ** -0.5
    age = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    visible = age >= 0
    if c["window"] is not None:
        visible = visible & (age < c["window"])
    maps = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), -1)
    ctx = jnp.einsum("bpjqk,bkpd->bqpjd", maps, vr)
    lq1, lk1, lq2, lk2 = lambdas
    lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + c["lambda_init"]
    out = rms_norm(ctx[..., 0, :] - lam * ctx[..., 1, :], subln, eps) \
        * (1.0 - c["lambda_init"])
    out = out.reshape(b, t, -1) @ wo
    return (out if bo is None else out + bo), (k, v)


def causal_conv(x, w):
    """x [B, T, C], w [C, K]: y_t[c] = sum_m w[c, m] x_(t-K+1+m)[c], zeros
    before the sequence: K shifted adds."""
    width, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (width - 1, 0), (0, 0)])
    return sum(xp[:, m:m + t] * w[:, m] for m in range(width))


def short_conv(a, w_in, w_conv, w_out):
    """Lfm2MoeShortConv on a [B, T, D]: [B, C, u] = a w_in, chunked in that
    order; v = B * u; c = the causal convolution of v under w_conv [D, K],
    no bias, no activation, as K shifted multiply-adds; (C * c) w_out."""
    b, gate, u = jnp.split(a @ w_in, 3, axis=-1)
    return (gate * causal_conv(b * u, w_conv)) @ w_out


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token: q, k [B, T, H, dk] (already
    normalised and scaled), v [B, T, H, dv], g and beta [B, T, H] -> o [B,
    T, H, dv]. A head's state S [dk, dv] starts at zero; S' = exp(g_t) S;
    S = S' + beta_t k_t (v_t - S'^T k_t)^T; o_t = S^T q_t."""
    def step(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None, None]
        written = vt - jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + kt[..., :, None] * (bt[..., None] * written)[
            ..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    b, _, h, dk = q.shape
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
                        tuple(jnp.moveaxis(x, 1, 0)
                              for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def gated_delta_net(a, w_qkvz, w_ba, w_conv, dt_bias, a_log, w_norm, w_out,
                    c):
    """Qwen3NextGatedDeltaNet on a [B, T, D]: w_qkvz's columns are a key
    head's [q dk, k dk, v rep x dv, z rep x dv] and w_ba's its [b rep, a
    rep] (fix_query_key_value_ordering), rep value heads a key head; key
    head j serves value heads j rep .. j rep + rep - 1
    (repeat_interleave)."""
    b, t, _ = a.shape
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    rep = hv // hk
    qkvz = (a @ w_qkvz).reshape(b, t, hk, -1)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
    ba = (a @ w_ba).reshape(b, t, hk, 2 * rep)
    beta = jax.nn.sigmoid(ba[..., :rep].reshape(b, t, hv))
    g = -jnp.exp(a_log) * jax.nn.softplus(
        ba[..., rep:].reshape(b, t, hv) + dt_bias)
    mixed = jax.nn.silu(causal_conv(jnp.concatenate(
        [x.reshape(b, t, -1) for x in (q, k, v)], -1), w_conv))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    q, k = (jnp.repeat(l2norm(x.reshape(b, t, hk, dk)), rep, axis=2)
            for x in (q, k))
    o = delta_rule(q * dk ** -0.5, k, v.reshape(b, t, hv, dv), g, beta)
    # Qwen3NextRMSNormGated: the weight, initialised to 1, is not
    # zero-centred
    o = rms_norm(o, w_norm, c["rms_norm_eps"]) * jax.nn.silu(
        z.reshape(b, t, hv, dv))
    return o.reshape(b, t, hv * dv) @ w_out


def kda_step(state, xs):
    """One token of the delta rule with a decay a key channel on a head's
    state [B, H, dk, dv]: S' = Diag(exp(g_t)) S; S = S' + beta_t k_t (v_t -
    S'^T k_t)^T; o_t = S^T q_t. -> (S, o_t)."""
    qt, kt, vt, gt, bt = xs
    state = state * jnp.exp(gt)[..., None]
    written = vt - jnp.einsum("bhkv,bhk->bhv", state, kt)
    state = state + kt[..., :, None] * (bt[..., None] * written)[..., None, :]
    return state, jnp.einsum("bhkv,bhk->bhv", state, qt)


def kda_rule(q, k, v, g, beta, found=None):
    """The delta rule with a decay a key channel, token by token
    (`kda_step`): q, k [B, T, H, dk] (already normalised and scaled), v [B,
    T, H, dv], g [B, T, H, dk] the log decay, beta [B, T, H] -> o [B, T, H,
    dv], a head's state S [dk, dv] from zero. The recurrence itself: it
    shares no algebra with the chunked form the Program computes. A dict
    given as `found` gets `kda_state`, the state after the last token."""
    b, _, h, dk = q.shape
    last, o = jax.lax.scan(
        kda_step, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    if found is not None:
        found.setdefault("kda_state", last)
    return jnp.moveaxis(o, 0, 1)


def kda_gates(a, wf, dt_bias, a_log, wbeta, c):
    """(g [B, T, H, dk], beta [B, T, H]) of a KDA layer on a [B, T, D]: one
    decay a key channel from a full-rank projection, g = kda_lower_bound x
    sigmoid(exp(A_log_h) (a W_f + dt_bias)) (kda_safe_gate: bounded below,
    so that a chunked form's exponentials stay finite), and one write
    strength a head, beta = sigmoid(a W_beta)."""
    b, t, _ = a.shape
    h = a_log.shape[0]
    g = c["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (a @ wf + dt_bias).reshape(b, t, h, -1))
    return g, jax.nn.sigmoid(a @ wbeta)


def kda(a, wq, conv_q, wk, conv_k, wv, conv_v, wf, dt_bias, a_log, wbeta,
        o_norm, wg, wo, c, found=None, rule=kda_rule):
    """Kimi Delta Attention (arXiv:2510.26692) on a [B, T, D] as
    bailing_hybrid's config switches it: q~, k~, v = SiLU(conv(a W)), three
    causal depthwise convolutions; q = l2(q~) / sqrt(dk), k = l2(k~)
    (use_qk_norm); the gates of `kda_gates`; the recurrence (`rule`: the
    benchmark's copy hands in the same with checkpoints); y = [N_head(o) *
    sigmoid(a W_g)] W_o, the norm over a head's dv under one weight [dv].
    A dict given as `found` gets the first such layer's `kda_g`, `kda_out`
    (the recurrence's output) and `kda_ctx` (what W_o reads: normed, then
    gated)."""
    b, t, _ = a.shape
    h = a_log.shape[0]
    q, k, v = (jax.nn.silu(causal_conv(a @ w, conv)).reshape(b, t, h, -1)
               for w, conv in ((wq, conv_q), (wk, conv_k), (wv, conv_v)))
    dk = q.shape[-1]
    g, beta = kda_gates(a, wf, dt_bias, a_log, wbeta, c)
    o = rule(l2norm(q) * dk ** -0.5, l2norm(k), v, g, beta, found=found)
    if found is not None:
        found.setdefault("kda_g", g)
        found.setdefault("kda_out", o)
    o = rms_norm(o, o_norm, c["rms_norm_eps"]) \
        * jax.nn.sigmoid(a @ wg).reshape(o.shape)
    if found is not None:
        found.setdefault("kda_ctx", o)
    return o.reshape(b, t, -1) @ wo


def group_limited(choice, n_group, topk_group):
    """DeepSeek-V3's group limit on a router's choice (`noaux_tc`,
    arXiv:2412.19437), written out: choice [N, E], the scores with their
    bias; the E experts are n_group runs of neighbours; a group's score is
    the sum of its two largest entries; the topk_group best groups stay and
    every entry outside them is minus infinity (out of the choice, as the
    family's inference code has it: not 0, which a negative s + b would lose
    to)."""
    n, e = choice.shape
    by_group = choice.reshape(n, n_group, e // n_group)
    score = jax.lax.top_k(by_group, 2)[0].sum(-1)           # [N, n_group]
    best = jax.lax.top_k(score, topk_group)[1]
    kept = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], best].set(True)
    return jnp.where(jnp.repeat(kept, e // n_group, axis=1), choice, -jnp.inf)


def shared_expert(m, wg, wu, wd, ws=None):
    """sigmoid(m w_s) * SwiGLU(m): every token passes it; without w_s
    (DeepSeek-V3's shared experts) the SwiGLU as it is."""
    out = (jax.nn.silu(m @ wg) * (m @ wu)) @ wd
    return out if ws is None else jax.nn.sigmoid(m @ ws) * out


def relu2_mlp(m, wu, wd):
    """nemotron_h's MLP, shared expert or one routed expert: two matrices,
    relu(m w_u)^2 w_d."""
    return jnp.square(jax.nn.relu(m @ wu)) @ wd


def gated_unit(m, wg, wu, c):
    """act(m @ wg) * (m @ wu): SiLU (SwiGLU) or, hidden_act relu, ReLU on
    the gate branch (ReGLU)."""
    act = jax.nn.relu if c.get("hidden_act") == "relu" else jax.nn.silu
    return act(m @ wg) * (m @ wu)


def routed_experts(m, router, w_gate, w_up, w_down, c, router_x=None,
                   first_expert=None, expert_bias=None):
    """m [N, D] -> (out [N, D], balance term, z term, load [E] int32). The
    router reads router_x where it is given (of any width: router [Dr, E]),
    m otherwise, and routes over
    all E columns; w_gate None: experts of two matrices, relu(m w_up)^2
    w_down; the experts computed are those whose weights are given,
    first_expert .. first_expert + len(w_gate) - 1 (c's own by default), and
    `out` is their part of the sum. With router_scoring sigmoid
    (Lfm2MoeSparseMoeBlock) the scores are s = sigmoid(logits), the top k is
    chosen over s + expert_bias [E] where there is one and weighed by s
    itself, renormalised over the chosen with 1e-6 (router_renorm_epsilon
    where given: DeepSeek-V3's is 1e-20) added to their sum, and scaled by
    routed_scaling_factor; the two auxiliary terms are 0. With n_group and
    topk_group (c["group_limited"]) the top k is chosen inside a token's
    best groups (`group_limited`).

    Departure: the expert bias is a buffer in `modeling_lfm2_moe.py`, moved
    during pre-training by a rule the config does not give; here it is an
    input that training does not move, and it has no gradient (lax.top_k's
    indices carry none)."""
    n, e, k = m.shape[0], c["num_experts"], c["num_experts_per_tok"]
    first = c.get("first_expert", 0) if first_expert is None \
        else first_expert
    logits = (m if router_x is None else router_x) @ router
    sigmoid = c.get("router_scoring", "softmax") == "sigmoid"
    probs = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, -1)
    if c.get("group_limited"):
        # the top k inside the kept groups; the weights are the scores'
        _, idx = jax.lax.top_k(group_limited(
            probs + expert_bias, c["n_group"], c["topk_group"]), k)
        gate = jnp.take_along_axis(probs, idx, axis=-1)
    elif expert_bias is None:
        gate, idx = jax.lax.top_k(probs, k)
    else:
        _, idx = jax.lax.top_k(probs + expert_bias, k)
        gate = jnp.take_along_axis(probs, idx, axis=-1)
    if c["norm_topk_prob"]:
        # a softmax over all E renormalised over the chosen k is the softmax
        # over the k chosen logits
        eps = c.get("router_renorm_epsilon")
        gate = gate / (gate.sum(-1, keepdims=True)
                       + ((1e-6 if eps is None else eps) if sigmoid
                          else 0.0))
    gate = gate * c.get("routed_scaling_factor", 1)

    # Departure: HF gathers an expert's tokens and index_adds its outputs;
    # here every expert sees every token and a token's weight for an expert
    # it did not choose is 0. lax.map keeps one expert's activations alive
    def one(args):
        i, wg, wu, wd = args
        weight = jnp.sum(jnp.where(idx == i, gate, 0.0), -1)
        return weight[:, None] * (relu2_mlp(m, wu, wd) if wg is None
                                  else gated_unit(m, wg, wu, c) @ wd)

    out = jax.lax.map(one, (first + jnp.arange(w_up.shape[0]), w_gate,
                            w_up, w_down)).sum(0)
    load = jnp.sum(idx[:, :, None] == jnp.arange(e), axis=(0, 1),
                   dtype=jnp.int32)
    if sigmoid:
        return out, 0.0, 0.0, load
    # Departure: HF pools the router probabilities of all layers before the
    # product and has no z term; these are the per-layer terms of
    # arXiv:2409.02060 (the same at depth 1)
    balance = e * jnp.sum(load / n * probs.mean(0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return out, balance, z, load


def exit_distribution(lam):
    """lam [P, B, T], the gates' sigmoids -> p [P, B, T]: p_1 = lam_1, p_t =
    lam_t prod_(j<t) (1 - lam_j), and the last pass takes the remainder
    prod_(j<P) (1 - lam_j), whatever its own gate says: the shares sum to 1
    (arXiv:2510.25741, section 3)."""
    left, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def passes(cfg, params, ids, pos, next_ids=None, found=None):
    """([logits [B, T, V] of pass 1 .. P], p [P, B, T] or None, balance
    term, z term, expert_load) of the model on ids, pos [B, T]. With a
    multi-token-prediction module (num_nextn_predict_layers 1; `next_ids`
    [B, T], the token after each position, which the module embeds) the
    module's logits are one entry more at the end of the list (without
    `next_ids` the trunk alone is computed), its layer's
    assignments are in expert_load, and a dict given as `found` gets
    `mtp_input`, what the module's layer reads. A model
    with total_ut_steps = P > 1 runs the same layers and the same final
    norm P times, each pass on the normed state of the pass before: a
    Python loop over one set of weights, each read from `params` once. With
    sandwich_norm a layer is a = x + N2(mixer(N1(x))), y = a + N4(FFN(N3(
    a))). A dict given as `found` gets `states`, the state after each
    two-branch layer. With exit_gate, p is the exit distribution of lambda_t =
    sigmoid(h_t w_g + b_g); without it only the last pass has logits that
    count and p is None.

    Departure: the paper composes the passes as head(M^L(...M^L(emb))) with
    the final norm inside the head; the released `modeling_ouro.py` norms the
    state at the end of every pass and starts the next pass from the normed
    state, which is what this does."""
    c = resolve(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    e, eps = c["num_experts"], c["rms_norm_eps"]
    layers, centred = c["num_hidden_layers"], c["norm_zero_centered"]
    plain_norm = c["norm_type"] == "layer_norm"     # weight and bias

    def take_norm():
        return take(2) if plain_norm else take(1)[0]

    def norm(x, w):
        return layer_norm(x, *w, eps) if plain_norm \
            else rms_norm(x, w, eps, centred)

    def with_bias(n, has):              # n matrices, each with its bias
        return [w for _ in range(n)
                for w in (take(2) if has else take(1) + [None])]

    def ungated_experts():
        """nemotron_h's experts: ([latent_down], (router, expert bias or
        None, None for the gate there is not, w_up, w_down), [latent_up],
        the shared expert's two or None)."""
        latent = 1 if c["moe_latent_size"] else 0
        return (take(latent), take(1) + (
            take(1) if c["use_expert_bias"] else [None]) + [None] + take(2),
            take(latent),
            take(2) if c["shared_expert_intermediate_size"] else None)

    sandwich = c["sandwich_norm"]
    routed = c["ffn_layers"].count("experts")   # the terms' mean is theirs
    embedding = take(1)[0]
    weights = []                # a layer: (N1, mixer, N2, N3, ffn, N4), or
    one_branch_kind = {}        # (N, its one branch's own): layer -> kind
    streams, mtp = c["hc_mult"], c["mtp_layers"]
    hcs = []                    # a layer: (attention's, the FFN's) (phi, b,
    w_f = module = None         # alpha), or (None, None)
    for i in range(layers + mtp):
        if i == layers:
            # the module lies behind the final norm: enorm, hnorm, eh_proj,
            # then a layer's own, then shared_head.norm
            w_f, module = take(1)[0], take(3)
        kind = c["mixer_layers"][i] if c["ffn_layers"][i] == "none" \
            else c["ffn_layers"][i] if c["mixer_layers"][i] == "none" \
            else None
        if kind is not None:
            # one branch behind one norm (hybrid_override_pattern): a
            # mixer's own (w_in .. w_out, or wq, wk, wv, wo), the experts'
            # (`ungated_experts`) or a dense MLP's two
            hcs.append((None, None))
            one_branch_kind[i] = kind
            weights.append((take_norm(), (
                take(2) + (take(1) if c["mamba_conv_bias"] else [None])
                + take(5) if kind == "mamba2"
                else take(3) + [None, None] + take(1) if kind == "attention"
                else ungated_experts() if kind == "experts" else take(2))))
            continue
        hc_a = take(3) if streams > 1 else None
        n1 = take_norm()
        if c["mixer_layers"][i] == "mamba":
            mixer = take(2) + (take(1) if c["conv_bias"] else [None]) \
                + take(6)
        elif c["mixer_layers"][i] == "gmu":
            mixer = take(2)
        elif c["mixer_layers"][i] == "mamba2":
            mixer = take(2) + (take(1) if c["mamba_conv_bias"] else [None]) \
                + take(5)
        elif c["differential_attention"]:
            # wq, bq, [wk, bk, wv, bv], the four lambda vectors, the
            # norm's weight, wo, bo
            has = c["attention_bias"]
            own = c["reads_layers"][i] == "own"
            mixer = with_bias(1, has) + [with_bias(2, has) if own else None,
                                         take(4)] + take(1) \
                + with_bias(1, has)
        elif c["mixer_layers"][i] == "gated_delta":
            mixer = take(7)
        elif c["mixer_layers"][i] == "kda":
            mixer = take(13)
        elif c["mixer_layers"][i] == "short_conv":
            mixer = take(3)
        elif c["latent"]:
            # [wq_a, q_a_norm], wq_b (wq where q has no low rank), wkv_a,
            # kv_a_norm, wkv_b, [the gate a head, made before wo], wo
            mixer = (take(2) if c["q_lora_rank"] is not None
                     else [None, None]) + take(4)
            gate = take(1) if c["attention_gate"] == "per_head" else [None]
            mixer = mixer + take(1) + gate
        else:
            # wq, wk, wv, [q_norm, k_norm], wo, then [the per-head gate],
            # which the program creates before wo
            mixer = take(3) + (take(2) if c["qk_norm"] else [None, None])
            gate = take(1) if c["attention_gate"] == "per_head" else []
            mixer = mixer + take(1) + gate
        n2 = take(1)[0] if sandwich else None
        hcs.append((hc_a, take(3) if streams > 1 else None))
        n3 = take_norm()
        # experts: router, [expert bias], gate, up, down, [shared expert's 3
        # and its gate's weight]
        shared = 0 if not c["shared_expert_intermediate_size"] \
            else 4 if c["shared_expert_gate"] else 3
        if not c["ffn_gated"]:
            # a module's layer under hybrid_override_pattern: its experts
            # as a one-branch layer's
            ffn = ungated_experts()
        else:
            ffn = take(2) if c["mlp_gate_up_fused"] \
                else take(3) if c["ffn_layers"][i] == "dense" else (
                take(1) + (take(1) if c["use_expert_bias"] else [None])
                + take(3 + shared))
        weights.append((n1, mixer, n2, n3, ffn,
                        take(1)[0] if sandwich else None))
    if mtp:
        module = module + take(1)
    else:
        w_f = take_norm()
    w_g, b_g = take(2) if c["exit_gate"] else (None, None)
    # a tied head is the embedding read again, transposed
    w_lm = embedding.T if c["tie_word_embeddings"] else take(1)[0]
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")

    balance = z = 0.0
    load = jnp.zeros((max(e, 1),), jnp.int32)
    logits, lam = [], []
    with jax.default_matmul_precision("highest"):
        h = embedding[ids] * c["embedding_multiplier"]
        b, t, d = h.shape
        branch = c["residual_multiplier"]   # on what each branch adds
        if streams > 1:
            # Departure (arXiv:2409.19606's convention; the config has no key
            # for either end): the streams start as `streams` copies of the
            # embedding and are read out as their sum
            h = jnp.broadcast_to(h[:, :, None], (b, t, streams, d))

        def read(x, hc):
            """(what the sub-layer reads, how its output is written back)
            through a hyper-connection, or the plain residual."""
            if hc is None:
                return x, lambda y: x + y
            pre, post, res = hyper_connection(x, *hc, c)
            return jnp.einsum("bti,btid->btd", pre, x), lambda y: \
                jnp.einsum("btij,btjd->btid", res, x) \
                + post[..., None] * y[:, :, None]

        handed_on = {}          # what one layer leaves for later ones

        def latent_experts(m, ffn, terms):
            """(nemotron_h's LatentMoE on m [B, T, D], terms with its own):
            u = m W_dn; the routed experts on u, the router on m; that W_up;
            + the shared expert on m. A dict given as `found` gets the first
            such layer's `latent` (u), `routed` (the experts' sum before
            W_up), `routed_out` (behind it) and `shared`."""
            down, (router, bias, _, wu, wd), up, shared = ffn
            flat = m.reshape(b * t, d)
            u = flat @ down[0] if down else flat
            r, lb, lz, ld = routed_experts(
                u, router, None, wu, wd, c, router_x=flat, expert_bias=bias)
            out = (r @ up[0] if up else r).reshape(b, t, d)
            kept = {"latent": u, "routed": r, "routed_out": out}
            if shared is not None:
                kept["shared"] = relu2_mlp(m, *shared)
                out = out + kept["shared"]
            if found is not None:
                for name, value in kept.items():
                    found.setdefault(name, value)
            return out, (terms[0] + lb / routed, terms[1] + lz / routed,
                         terms[2] + ld)

        def one_branch(h, i, terms):
            """hybrid_override_pattern's layer i: h + f(N(h)), f a mixer or
            an FFN. A dict given as `found` gets `layers`, the state after
            each."""
            (n, own), kind = weights[i], one_branch_kind[i]
            a = norm(h, n)
            if kind == "mamba2":
                out = mamba2(a, *own, eps, found=found,
                             groups=c["mamba_n_groups"])
            elif kind == "attention":
                out = attention(a, pos, *own, layer_config(c, i))
                if found is not None:
                    found.setdefault("attention", out)
            elif kind == "experts":
                out, terms = latent_experts(a, own, terms)
            else:
                out = relu2_mlp(a, *own)
            if found is not None:
                found.setdefault("layers", []).append(h + out)
            return h + out, terms

        def layer(h, i, terms):
            """Layer i on h; terms = (balance, z, load) with the layer's."""
            if i in one_branch_kind:
                return one_branch(h, i, terms)
            n1, mixer, n2, n3, ffn, n4 = weights[i]
            x, write = read(h, hcs[i][0])
            a = norm(x, n1)
            if c["mixer_layers"][i] == "mamba":
                mixed, y = mamba(a, *mixer, found=found)
                if i == c["memory_layer"]:
                    handed_on["memory"] = y
                    if found is not None:
                        found["memory"] = y
            elif c["mixer_layers"][i] == "gmu":
                mixed = gated_memory_unit(a, handed_on["memory"], *mixer)
            elif c["mixer_layers"][i] == "mamba2":
                mixed = mamba2(a, *mixer, eps, found=found,
                               groups=c["mamba_n_groups"])
            elif c["differential_attention"]:
                wq, bq, own, lambdas, subln, wo, bo = mixer
                mixed, kv = differential_attention(
                    a, wq, bq, handed_on["kv"] if own is None else own,
                    lambdas, subln, wo, bo, layer_config(c, i))
                if i == c["kv_layer"]:
                    handed_on["kv"] = kv
                    if found is not None:
                        found["shared_k"], found["shared_v"] = kv
            elif c["mixer_layers"][i] == "gated_delta":
                mixed = gated_delta_net(a, *mixer, c)
            elif c["mixer_layers"][i] == "kda":
                mixed = kda(a, *mixer, c, found=found)
            elif c["mixer_layers"][i] == "short_conv":
                mixed = short_conv(a, *mixer)
            elif c["latent"]:
                mixed, _ = latent_attention(a, pos, *mixer[:7],
                                            layer_config(c, i),
                                            w_gate=mixer[7])
            else:
                mixed = attention(a, pos, *mixer[:6], layer_config(c, i),
                                  *mixer[6:], found=found)
                if found is not None:
                    found.setdefault("attention_layers", []).append(mixed)
            if sandwich:
                mixed = rms_norm(mixed, n2, eps, centred)
            if found is not None and c["mixer_layers"][i] == "attention":
                found.setdefault("attention", mixed)
            h = write(branch * mixed)
            x, write = read(h, hcs[i][1])
            m = norm(x, n3)
            if not c["ffn_gated"]:
                out, terms = latent_experts(m, ffn, terms)
            elif c["ffn_layers"][i] == "experts":
                out, lb, lz, ld = routed_experts(
                    m.reshape(b * t, d), ffn[0], *ffn[2:5], c,
                    router_x=a.reshape(b * t, d)
                    if c["router_input"] == "pre_attention" else None,
                    expert_bias=ffn[1])
                out = out.reshape(b, t, d)
                if c["shared_expert_intermediate_size"]:
                    out = out + shared_expert(m, *ffn[5:])
                terms = (terms[0] + lb / routed, terms[1] + lz / routed,
                         terms[2] + ld)
            elif c["mlp_gate_up_fused"]:
                gate, up = jnp.split(m @ ffn[0], 2, axis=-1)
                out = (jax.nn.silu(gate) * up) @ ffn[1]
            else:
                wg, wu, wd = ffn
                out = (jax.nn.silu(m @ wg) * (m @ wu)) @ wd
            if sandwich:
                out = rms_norm(out, n4, eps, centred)
            if found is not None:
                found.setdefault("states", []).append(write(branch * out))
            return write(branch * out), terms

        terms = (balance, z, load)
        for _ in range(c["total_ut_steps"]):
            for i in range(layers):
                h, terms = layer(h, i, terms)
            if streams > 1:
                h = h.sum(2)
            h = norm(h, w_f)
            logits.append(h @ w_lm / c["logits_scaling"])
            if c["exit_gate"]:
                lam.append(jax.nn.sigmoid(h @ w_g + b_g))
        if mtp and next_ids is not None:
            x = mtp_input(embedding[next_ids], h, *module[:3], eps)
            if found is not None:
                found["mtp_input"] = x
            y, terms = layer(x, layers, terms)
            logits.append(rms_norm(y, module[3], eps, centred) @ w_lm
                          / c["logits_scaling"])
        balance, z, load = terms
    p = exit_distribution(jnp.stack(lam)) if c["exit_gate"] else None
    return logits, p, balance, z, load


def mtp_input(e, s, enorm, hnorm, eh_proj, eps):
    """What the multi-token-prediction module's layer reads (arXiv:
    2412.19437, equation 21): W_eh [N_e(e); N_h(s)] on e [B, T, D], the
    embedding of the token after each position, and s, the trunk's state.

    Departure: the report writes [N(h); N(Emb)], the state first, on the
    state before the final norm; the family's public inference code
    concatenates the embedding first and takes the state after the trunk's
    final norm, which is what a checkpoint's `eh_proj` was trained under,
    and what this does (s is the normed state)."""
    return jnp.concatenate([rms_norm(e, enorm, eps), rms_norm(s, hnorm, eps)],
                           -1) @ eh_proj


def forward(cfg, params, ids, pos):
    """(logits [B, T, V], balance term, z term, expert_load) of the model
    on ids, pos [B, T]: the last pass's logits, which is what a user reads
    out; the two terms are means over the layers and the load is their sum
    (zeros without experts)."""
    logits, _, balance, z, load = passes(cfg, params, ids, pos)
    return logits[-1], balance, z, load


def loss_fn(cfg, params, ids, pos, labels, with_passes=False,
            labels_next=None, found=None):
    """(loss, (logits, expert_load)), or with_passes (loss, (logits of every
    pass, p, expert_load)). With a multi-token-prediction module the loss
    is L_main + mtp_loss_weight x L_mtp (arXiv:2412.19437, equations 24 and
    25 at depth 1), L_mtp the mean cross-entropy of the module's logits
    against `labels_next`, the token two after each position; `logits` are
    the trunk's, and a dict given as `found` gets `main_loss`, `mtp_loss`,
    `mtp_logits` and `mtp_input`. Departure: the report's L_mtp runs over
    positions 2 .. T of a sequence of T tokens, dropping the position whose
    target lies past the end; here every position has both targets, the
    sequence being cut from T + 2 tokens. With exit_gate the loss is the mean a position
    of sum_t p_t CE(z_t, y) - exit_entropy_coef x H(p), H(p) = -sum_t p_t
    log p_t (0 log 0 = 0): the entropy-regularised objective of
    arXiv:2510.25741 with a uniform prior over the exit step.

    Departure: HF shifts `labels` by one inside the model and drops the last
    position; here `labels[b, t]` is already the token after position t, so
    every position carries a loss. Departure: the paper's beta is a
    hyper-parameter of its stage I; `exit_entropy_coef` is an assumed
    value."""
    c = resolve(cfg)
    mtp = c["mtp_layers"]
    logits, p, balance, z, load = passes(
        cfg, params, ids, pos,
        next_ids=labels.reshape(ids.shape) if mtp else None, found=found)
    mtp_logits = logits.pop() if mtp else None

    def nll(one, labels=labels):
        return -jnp.take_along_axis(
            jax.nn.log_softmax(one, -1),
            labels.reshape(one.shape[:2] + (1,)), axis=-1)[..., 0]

    if p is None:
        loss = nll(logits[-1]).mean()
    else:
        ce = jnp.stack([nll(one) for one in logits])            # [P, B, T]
        entropy = -jax.scipy.special.xlogy(p, p).sum(0)
        loss = ((p * ce).sum(0) - c["exit_entropy_coef"] * entropy).mean()
    if mtp:
        mtp_loss = nll(mtp_logits, labels_next).mean()
        if found is not None:
            found.update(main_loss=loss, mtp_loss=mtp_loss,
                         mtp_logits=mtp_logits)
        loss = loss + c["mtp_loss_weight"] * mtp_loss
    loss = loss + c["router_aux_loss_coef"] * balance \
        + c["router_z_loss_coef"] * z
    if with_passes:
        return loss, (logits, p, load)
    return loss, (logits[-1], load)


def loss_and_grads(cfg, params, ids, pos, labels, labels_next=None):
    """((loss, (logits, expert_load)), [d loss / d parameter])."""
    return jax.value_and_grad(
        lambda p: loss_fn(cfg, p, ids, pos, labels,
                          labels_next=labels_next), has_aux=True)(
            [jnp.asarray(p, jnp.float32) for p in params])


def block_diffusion_mask(t, block_length):
    """[2 T, 2 T] bool, row r sees row s, of block diffusion's training mask
    (BD3-LM, arXiv:2503.09573, section 3.1 and its appendix on the
    vectorised objective), written from the rule by (copy, position, block):
    the rows are a noised copy of the T tokens, then a clean copy; with b =
    position // block_length, r sees s iff both are noised and b_s = b_r
    (inside a block, both directions), or r is noised, s clean and b_s <
    b_r (the clean blocks before its own), or both are clean and b_s <= b_r
    (block-causal). A clean row never sees a noised one."""
    copy = jnp.concatenate([jnp.ones(t, bool), jnp.zeros(t, bool)])  # noised?
    block = jnp.tile(jnp.arange(t) // block_length, 2)
    r_noised, s_noised = copy[:, None], copy[None, :]
    b_r, b_s = block[:, None], block[None, :]
    return jnp.where(
        r_noised, jnp.where(s_noised, b_s == b_r, b_s < b_r),
        jnp.where(s_noised, False, b_s <= b_r))


def block_diffusion_loss(cfg, params, ids, noisy_ids, pos, loss_weight,
                         found=None):
    """(loss, (logits [B, T, V] on the noised copy's rows, expert_load)) of
    a config with `objective: block_diffusion` (SDAR, arXiv:2510.06303,
    whose objective is BD3-LM's): the rows are E[noisy_ids] then E[ids], 2
    T of them, both copies of token i at position pos[i]; every layer is a
    = h + Attn(N1(h)) under `block_diffusion_mask`, h = a + FFN(N2(a)) (a
    dense SwiGLU or the routed experts whose weights are given, with the
    shared expert where there is one); nothing reads the clean copy's rows
    behind the last layer's attention core, so that layer's W_o, residual
    and FFN, the final norm and the head run on the noised copy's T rows;
    the loss is (1 / (B T)) sum_i loss_weight_i CE(logits_i, ids_i): the
    label is the clean id at the SAME position, the weight 1 / t at a
    masked position and 0 elsewhere, as fed. A dict given as `found` gets
    `attention_layers` (each layer's attention output behind W_o: 2 T rows,
    T in the last), `core_q` and `core_k` (`attention`'s), `routed` (the
    first expert layer's routed output), `router_logits` (a layer's, [rows,
    E]) and `state` (what the final norm reads)."""
    c = resolve(cfg)
    if c["block_diffusion"] is None:
        raise ValueError("the config has no objective block_diffusion")
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    eps, layers = c["rms_norm_eps"], c["num_hidden_layers"]
    centred = c["norm_zero_centered"]
    embedding = take(1)[0]
    weights = []
    for i in range(layers):
        n1 = take(1)[0]
        mixer = take(3) + (take(2) if c["qk_norm"] else [None, None])
        gate = take(1) if c["attention_gate"] == "per_head" else []
        mixer = mixer + take(1) + gate
        n3 = take(1)[0]
        if c["ffn_layers"][i] == "dense":
            ffn = take(3)
        else:
            if c["use_expert_bias"] or not c["ffn_gated"]:
                raise NotImplementedError(
                    "the block-diffusion reference has softmax-routed gated "
                    "experts and dense SwiGLUs")
            shared = 0 if not c["shared_expert_intermediate_size"] \
                else 4 if c["shared_expert_gate"] else 3
            ffn = take(4 + shared)
        weights.append((n1, mixer, n3, ffn))
    w_f, w_lm = take(2)
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")
    b, t = ids.shape
    d = c["hidden_size"]
    visible = block_diffusion_mask(t, c["block_diffusion"]["block_length"])
    load = jnp.zeros((max(c["num_experts"], 1),), jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = embedding[jnp.concatenate([noisy_ids, ids], 1)] \
            * c["embedding_multiplier"]
        pos2 = jnp.concatenate([pos, pos], 1)
        for i, (n1, mixer, n3, ffn) in enumerate(weights):
            last = i == layers - 1
            a = rms_norm(h, n1, eps, centred)
            mixed = attention(a, pos2, *mixer[:6], layer_config(c, i),
                              *mixer[6:], found=found, visible=visible,
                              rows=t if last else None)
            if found is not None:
                found.setdefault("attention_layers", []).append(mixed)
            h = (h[:, :t] if last else h) + mixed
            m = rms_norm(h, n3, eps, centred)
            if c["ffn_layers"][i] == "dense":
                wg, wu, wd = ffn
                out = (jax.nn.silu(m @ wg) * (m @ wu)) @ wd
            else:
                flat = m.reshape(-1, d)
                out, _, _, ld = routed_experts(flat, ffn[0], *ffn[1:4], c)
                out = out.reshape(m.shape)
                if found is not None:
                    found.setdefault("routed", out)
                    found.setdefault("router_logits", []).append(
                        flat @ ffn[0])
                if len(ffn) > 4:
                    out = out + shared_expert(m, *ffn[4:])
                load = load + ld
            h = h + out
        if found is not None:
            found["state"] = h
        logits = rms_norm(h, w_f, eps, centred) @ w_lm / c["logits_scaling"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                   ids[..., None], axis=-1)[..., 0]
        loss = (loss_weight * nll).sum() / (b * t)
    return loss, (logits, load)


def block_diffusion_batch(key, ids, block_length, mask_token_id,
                          noise_eps=1e-3):
    """(noisy_ids, loss_weight) of clean ids [B, T] from a jax key: a noise
    level a block of block_length positions on the linear schedule, t_b =
    eps + (1 - eps) u_b, u_b uniform on [0, 1) (alpha_t = 1 - t; BD3-LM's and
    LLaDA's form); m_i Bernoulli(t_b(i)); noisy_i = mask_token_id where m_i
    else ids_i; loss_weight_i = m_i / t_b(i), float32."""
    b, t = ids.shape
    k_level, k_mask = jax.random.split(key)
    level = noise_eps + (1.0 - noise_eps) * jax.random.uniform(
        k_level, (b, t // block_length), jnp.float32)
    level = jnp.repeat(level, block_length, axis=1)
    masked = jax.random.uniform(k_mask, (b, t), jnp.float32) < level
    return jnp.where(masked, mask_token_id, ids).astype(ids.dtype), \
        jnp.where(masked, 1.0 / level, 0.0).astype(jnp.float32)
