"""The plain reference of models/causal_lm.py: the forward pass, its loss
and, through jax.grad, its gradients, in jax.numpy and float32 under
jax.default_matmul_precision("highest"). No kernel, no cache, no sorting:
attention is the dense softmax(q k^T) v and every expert is computed over
all tokens, one expert at a time, and masked. The tests hold the Program to
it (tests/unittests/test_causal_lm.py).

It follows Hugging Face's `modeling_olmoe.py` and, for the keys
SmallThinker-21BA3B adds (grouped queries, a window and rotary positions by
layer, the router read before attention, ReGLU experts), the layer as
PowerInfer's `config.json` and model card give it; each departure is marked
"Departure:" below. `params` is the list of the Program's parameters in the
order models/causal_lm.py creates them.

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
    return dict(c, rope_theta=c["rope_theta"] if c["rope_layers"][i]
                else None, window=c["window_layers"][i])


def rms_norm(x, w, eps):
    # Departure: HF rounds the normalised value to the input dtype before
    # the weight multiplies it; in float32 the two are the same
    return w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, pos, theta):
    """x [B, T, H, D], pos [B, T]: HF's rotate_half convention, the pair
    (i, i + D/2) turns by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, :, None, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


def attention(a, pos, wq, wk, wv, q_norm, k_norm, wo, c):
    """Causal attention of the heads whose weights are given: wq [D, Hq x
    hd], wk and wv [D, Hkv x hd], wo [Hq x hd, D]; query head h reads
    key/value head h // (Hq / Hkv). c["rope_theta"] None: no position
    enters (NoPE). c["window"] w: key j is visible to query i iff j <= i and
    i - j < w (the Hugging Face sliding-window mask's convention)."""
    b, t, _ = a.shape
    hd = c["head_dim"]
    h, hkv = wq.shape[1] // hd, wk.shape[1] // hd
    q, k, v = a @ wq, a @ wk, a @ wv
    if c["qk_norm"]:     # over all channels, before the head split
        q = rms_norm(q, q_norm, c["rms_norm_eps"])
        k = rms_norm(k, k_norm, c["rms_norm_eps"])
    q = q.reshape(b, t, h, hd)
    k, v = (x.reshape(b, t, hkv, hd) for x in (k, v))
    if c["rope_theta"] is not None:
        q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    age = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]       # i - j
    visible = age >= 0
    if c["window"] is not None:
        visible = visible & (age < c["window"])
    s = jnp.where(visible, s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return ctx.reshape(b, t, h * hd) @ wo


def gated_unit(m, wg, wu, c):
    """act(m @ wg) * (m @ wu): SiLU (SwiGLU) or, hidden_act relu, ReLU on
    the gate branch (ReGLU)."""
    act = jax.nn.relu if c.get("hidden_act") == "relu" else jax.nn.silu
    return act(m @ wg) * (m @ wu)


def routed_experts(m, router, w_gate, w_up, w_down, c, router_x=None,
                   first_expert=None):
    """m [N, D] -> (out [N, D], balance term, z term, load [E] int32). The
    router reads router_x where it is given, m otherwise, and routes over
    all E columns; the experts computed are those whose weights are given,
    first_expert .. first_expert + len(w_gate) - 1 (c's own by default), and
    `out` is their part of the sum."""
    n, e, k = m.shape[0], c["num_experts"], c["num_experts_per_tok"]
    first = c.get("first_expert", 0) if first_expert is None \
        else first_expert
    logits = (m if router_x is None else router_x) @ router
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        # a softmax over all E renormalised over the chosen k is the softmax
        # over the k chosen logits
        gate = gate / gate.sum(-1, keepdims=True)

    # Departure: HF gathers an expert's tokens and index_adds its outputs;
    # here every expert sees every token and a token's weight for an expert
    # it did not choose is 0. lax.map keeps one expert's activations alive
    def one(args):
        i, wg, wu, wd = args
        weight = jnp.sum(jnp.where(idx == i, gate, 0.0), -1)
        return weight[:, None] * (gated_unit(m, wg, wu, c) @ wd)

    out = jax.lax.map(one, (first + jnp.arange(w_gate.shape[0]), w_gate,
                            w_up, w_down)).sum(0)
    load = jnp.sum(idx[:, :, None] == jnp.arange(e), axis=(0, 1),
                   dtype=jnp.int32)
    # Departure: HF pools the router probabilities of all layers before the
    # product and has no z term; these are the per-layer terms of
    # arXiv:2409.02060 (the same at depth 1)
    balance = e * jnp.sum(load / n * probs.mean(0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return out, balance, z, load


def forward(cfg, params, ids, pos):
    """(logits [B, T, V], balance term, z term, expert_load) of the model
    on ids, pos [B, T]; the two terms are means over the layers and the
    load is their sum (zeros without experts)."""
    c = resolve(cfg)
    params = iter(params)

    def take(n):
        return [jnp.asarray(next(params), jnp.float32) for _ in range(n)]

    e, eps = c["num_experts"], c["rms_norm_eps"]
    layers = c["num_hidden_layers"]
    balance = z = 0.0
    load = jnp.zeros((max(e, 1),), jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = take(1)[0][ids]
        b, t, d = h.shape
        for i in range(layers):
            w_in, wq, wk, wv = take(4)
            q_norm, k_norm = take(2) if c["qk_norm"] else (None, None)
            wo, w_post = take(2)
            a = rms_norm(h, w_in, eps)
            h = h + attention(a, pos, wq, wk, wv, q_norm, k_norm, wo,
                              layer_config(c, i))
            m = rms_norm(h, w_post, eps)
            if e:
                out, lb, lz, ld = routed_experts(
                    m.reshape(b * t, d), *take(4), c,
                    router_x=a.reshape(b * t, d)
                    if c["router_input"] == "pre_attention" else None)
                h = h + out.reshape(b, t, d)
                balance, z, load = balance + lb / layers, z + lz / layers, \
                    load + ld
            else:
                wg, wu, wd = take(3)
                h = h + (jax.nn.silu(m @ wg) * (m @ wu)) @ wd
        w_f, w_lm = take(2)
        logits = rms_norm(h, w_f, eps) @ w_lm
    if next(params, None) is not None:
        raise ValueError("the reference read fewer parameters than the "
                         "program has: the two are not the same architecture")
    return logits, balance, z, load


def loss_fn(cfg, params, ids, pos, labels):
    """(loss, (logits, expert_load)).

    Departure: HF shifts `labels` by one inside the model and drops the last
    position; here `labels[b, t]` is already the token after position t, so
    every position carries a loss."""
    c = resolve(cfg)
    logits, balance, z, load = forward(cfg, params, ids, pos)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(
        logp, labels.reshape(logits.shape[:2] + (1,)), axis=-1)
    loss = nll.mean() + c["router_aux_loss_coef"] * balance \
        + c["router_z_loss_coef"] * z
    return loss, (logits, load)


def loss_and_grads(cfg, params, ids, pos, labels):
    """((loss, (logits, expert_load)), [d loss / d parameter])."""
    return jax.value_and_grad(
        lambda p: loss_fn(cfg, p, ids, pos, labels), has_aux=True)(
            [jnp.asarray(p, jnp.float32) for p in params])
