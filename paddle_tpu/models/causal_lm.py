"""Decoder-only language model built from fluid layers, driven by a config
dict whose keys are those of a Hugging Face `config.json`.

One builder for the family: token embedding (no scale, no position table),
N x (RMS norm, self-attention with grouped queries, optional QK-norm, rotary
positions on the layers whose pattern says so and a sliding window on the
layers whose pattern says so, RMS norm, a dense SwiGLU FFN or dropless top-k
routed experts), final RMS norm, an untied output head, next-token
cross-entropy plus the routers' auxiliary losses. A new decoder-only
architecture is a config plus the ops it lacks, not a model file. Users:
OLMoE-1B-7B (`model_type: olmoe`; Muennighoff et al. 2024, arXiv:2409.02060)
and SmallThinker-21BA3B (PowerInfer; window and full attention mixed with
period 4, no rotary on the full layers, the router read from the attention's
normed input, ReGLU experts), whose equations the module follows;
`causal_lm_reference.py` is the same forward in plain jax.numpy.

Config keys read (HF names): vocab_size, hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads (a divisor of the heads: query
head h reads key/value head h // group), head_dim (absent: hidden_size /
heads), intermediate_size (the dense FFN's width, or one expert's),
num_experts (0 or absent: dense SwiGLU), num_experts_per_tok,
norm_topk_prob, rms_norm_eps, rope_theta (None: no rotary), hidden_act
(silu; relu for experts), attention_bias (false), clip_qkv (null),
rope_scaling (null), tie_word_embeddings (false), initializer_range, embedding_initializer_range (absent: the
same), router_aux_loss_coef, router_z_loss_coef; `qk_norm`, which `config.json`
does not carry because `modeling_olmoe.py` always applies it; and
`router_input` ("own", or "pre_attention": the router reads the
attention's normed input). SmallThinker's own names are mapped onto these:
moe_ffn_hidden_size (intermediate_size), moe_num_primary_experts
(num_experts), moe_num_active_primary_experts (num_experts_per_tok),
moe_primary_router_apply_softmax (true), rope_layout and
sliding_window_layout (one 0/1 a layer: rotary on, window on) and
sliding_window_size.

A configuration that is one chip's share of a layer divided over several
says so under `share`: {"chips": n, "chip": i, "published": {key: the whole
model's count}}. The counts the config gives for heads, experts and
vocabulary are then what this chip holds; the router keeps the published
number of experts as its columns and top-k is over all of them, the chip
computes experts chip * held .. chip * held + held - 1, and attention's and
the experts' outputs are the partial sums of what is held.

Parameters are created in the order the reference reads them: embedding;
a layer's input norm, Wq, Wk, Wv, q norm, k norm, Wo, post-attention norm,
then router, gate, up, down (experts) or gate, up, down (dense); final
norm; head.
"""
import paddle_tpu as fluid

DEFAULTS = {
    "num_experts": 0, "num_experts_per_tok": 0, "norm_topk_prob": False,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "hidden_act": "silu",
    "attention_bias": False, "clip_qkv": None, "tie_word_embeddings": False,
    "initializer_range": 0.02, "router_aux_loss_coef": 0.01,
    "router_z_loss_coef": 0.001, "qk_norm": False, "rope_scaling": None,
    "router_input": "own", "window": None,
    "moe_primary_router_apply_softmax": True}
# SmallThinker's key -> the key the builder reads
ALIASES = {"moe_ffn_hidden_size": "intermediate_size",
           "moe_num_primary_experts": "num_experts",
           "moe_num_active_primary_experts": "num_experts_per_tok"}


def resolve(cfg):
    """`cfg` over DEFAULTS, refusing what the builder cannot build rather
    than building something else under the model's name. Adds what the
    builder derives: head_dim, experts_held and first_expert (the share),
    and the two per-layer patterns `rope_layers` and `window_layers`."""
    c = dict(DEFAULTS, **cfg)
    for theirs, ours in ALIASES.items():
        if theirs in c:
            c[ours] = c[theirs]
    c.setdefault("num_key_value_heads", c["num_attention_heads"])
    for key, want in (("attention_bias", False), ("clip_qkv", None),
                      ("tie_word_embeddings", False), ("rope_scaling", None),
                      ("moe_primary_router_apply_softmax", True)):
        if c[key] != want:
            raise NotImplementedError(
                "causal_lm builds %s=%r only, the config has %r"
                % (key, want, c[key]))
    if c["hidden_act"] not in (("silu", "relu") if c["num_experts"]
                               else ("silu",)):
        raise NotImplementedError(
            "causal_lm builds hidden_act silu, and relu in routed experts; "
            "the config has %r" % (c["hidden_act"],))
    if c["router_input"] not in ("own", "pre_attention"):
        raise NotImplementedError("causal_lm builds router_input own or "
                                  "pre_attention, the config has %r"
                                  % (c["router_input"],))
    if "head_dim" not in c:
        if c["hidden_size"] % c["num_attention_heads"]:
            raise ValueError("hidden_size %d is not a multiple of %d heads"
                             % (c["hidden_size"], c["num_attention_heads"]))
        c["head_dim"] = c["hidden_size"] // c["num_attention_heads"]
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise ValueError("%d query heads are no multiple of %d key/value "
                         "heads" % (c["num_attention_heads"],
                                    c["num_key_value_heads"]))
    # the share: the counts above are what is held here; the router's width
    # is the published number of experts
    share = c.get("share") or {}
    c["experts_held"] = c["num_experts"]
    c["num_experts"] = share.get("published", {}).get(
        "moe_num_primary_experts", c["num_experts"])
    c["first_expert"] = share.get("chip", 0) * c["experts_held"] \
        if c["experts_held"] != c["num_experts"] else 0
    if c["first_expert"] + c["experts_held"] > c["num_experts"]:
        raise ValueError("chip %d cannot hold %d of %d experts"
                         % (share.get("chip", 0), c["experts_held"],
                            c["num_experts"]))
    layers = c["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        if key in c and len(c[key]) < layers:
            raise ValueError("%s has %d entries for %d layers"
                             % (key, len(c[key]), layers))
    c["rope_layers"] = [c["rope_theta"] is not None
                        and bool(c.get("rope_layout", [1] * layers)[i])
                        for i in range(layers)]
    c["window_layers"] = [
        c["sliding_window_size"]
        if c.get("sliding_window_layout", [0] * layers)[i] else None
        for i in range(layers)]
    return c


def _layer(c, i):
    """The config as layer i sees it: `rope_theta` None where the pattern
    gives the layer no rotary, `window` its sliding window or None. Where
    every layer is alike it is `c` itself."""
    theta = c["rope_theta"] if c["rope_layers"][i] else None
    window = c["window_layers"][i]
    if theta == c["rope_theta"] and window == c["window"]:
        return c
    return dict(c, rope_theta=theta, window=window)


def _linear(x, size, c):
    return fluid.layers.fc(
        input=x, size=size, bias_attr=False, num_flatten_dims=2,
        param_attr=fluid.ParamAttr(initializer=fluid.initializer.Normal(
            0.0, c["initializer_range"])))


def _norm(x, c):
    return fluid.layers.rms_norm(x, epsilon=c["rms_norm_eps"])


def attention(x, pos, c):
    """Causal self-attention over x [B, T, D], with the window c["window"]
    where the layer has one. QK-norm, where the config has it, is over all
    channels before the head split; rotary positions, where the layer has
    them, turn every head of q and k; key/value heads may be fewer than
    query heads; the core is layers.fused_attention."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    q, k, v = (_linear(x, n * hd, c) for n in (h, hkv, hkv))
    if c["qk_norm"]:
        q, k = _norm(q, c), _norm(k, c)
    q, k, v = (fluid.layers.reshape(t, shape=[0, -1, n, hd])
               for t, n in ((q, h), (k, hkv), (v, hkv)))
    if c["rope_theta"] is not None:
        q, k = (fluid.layers.rotary_embedding(t, pos, base=c["rope_theta"])
                for t in (q, k))
    ctx = fluid.layers.fused_attention(q, k, v, causal=True,
                                       window=c["window"])
    return _linear(fluid.layers.reshape(ctx, shape=[0, -1, h * hd]), d, c)


def feed_forward(x, c, router_input=None):
    """(out, aux) of one layer's FFN on x [B, T, D]: routed experts give
    aux = (balance_loss, z_loss, expert_load), the dense SwiGLU None.
    `router_input` is what the router reads where it is not x."""
    if c["num_experts"]:
        out, balance, z, load = fluid.layers.moe_ffn(
            x, num_experts=c["num_experts"], d_expert=c["intermediate_size"],
            top_k=c["num_experts_per_tok"],
            norm_topk_prob=c["norm_topk_prob"],
            param_attr=fluid.ParamAttr(initializer=fluid.initializer.Normal(
                0.0, c["initializer_range"])),
            router_input=router_input, activation=c["hidden_act"],
            experts_held=c["experts_held"], first_expert=c["first_expert"])
        return out, (balance, z, load)
    gate = fluid.layers.swish(_linear(x, c["intermediate_size"], c))
    up = _linear(x, c["intermediate_size"], c)
    return _linear(gate * up, c["hidden_size"], c), None


def causal_lm(cfg, seq_len):
    """Build the training graph in the current program guard. Feeds: `ids`
    [B, T] token ids, `pos` [B, T] their positions, `labels` [B, T, 1] the
    next token at every position. Returns (loss, logits [B, T, V],
    expert_load): the loss is the mean cross-entropy a position plus
    router_aux_loss_coef x the layers' mean balance loss plus
    router_z_loss_coef x their mean z loss (neither term is built where
    both coefficients are 0); expert_load [E] int32 sums the layers'
    assignment counts (None without experts)."""
    c = resolve(cfg)
    ids = fluid.layers.data("ids", [seq_len], dtype="int64")
    pos = fluid.layers.data("pos", [seq_len], dtype="int64")
    labels = fluid.layers.data("labels", [seq_len, 1], dtype="int64")
    h = fluid.layers.embedding(
        ids, size=[c["vocab_size"], c["hidden_size"]],
        param_attr=fluid.ParamAttr(initializer=fluid.initializer.Normal(
            0.0, c.get("embedding_initializer_range",
                       c["initializer_range"]))))
    aux = []
    for i in range(c["num_hidden_layers"]):
        cl = _layer(c, i)
        a = _norm(h, cl)
        h = h + attention(a, pos, cl)
        out, layer_aux = feed_forward(
            _norm(h, cl), cl,
            router_input=a if c["router_input"] == "pre_attention" else None)
        h = h + out
        if layer_aux is not None:
            aux.append(layer_aux)
    logits = _linear(_norm(h, c), c["vocab_size"], c)
    cost = fluid.layers.softmax_with_cross_entropy(
        logits=fluid.layers.reshape(logits, shape=[-1, c["vocab_size"]]),
        label=fluid.layers.reshape(labels, shape=[-1, 1]))
    loss = fluid.layers.mean(cost)
    load = None
    if aux and (c["router_aux_loss_coef"] or c["router_z_loss_coef"]):
        balance, z, load = (fluid.layers.sums(list(terms))
                            for terms in zip(*aux))
        loss = loss + balance * (c["router_aux_loss_coef"] / len(aux)) \
            + z * (c["router_z_loss_coef"] / len(aux))
    elif aux:
        load = fluid.layers.sums([terms[2] for terms in aux])
    return loss, logits, load


def build_train(cfg, seq_len, learning_rate=4e-4, beta1=0.9, beta2=0.95,
                epsilon=1e-8, clip_norm=1.0):
    """causal_lm + Adam under global-norm gradient clipping (clip_norm None:
    no clipping). Returns (loss, logits, expert_load)."""
    loss, logits, load = causal_lm(cfg, seq_len)
    if clip_norm is not None:
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(clip_norm=clip_norm))
    fluid.optimizer.Adam(learning_rate=learning_rate, beta1=beta1,
                         beta2=beta2, epsilon=epsilon).minimize(loss)
    return loss, logits, load
