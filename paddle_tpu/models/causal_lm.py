"""Decoder-only language model built from fluid layers, driven by a config
dict whose keys are those of a Hugging Face `config.json`.

One builder for the family: token embedding (no scale, no position table),
N x (RMS norm, self-attention with optional QK-norm and rotary positions,
RMS norm, a dense SwiGLU FFN or dropless top-k routed experts), final RMS
norm, an untied output head, next-token cross-entropy plus the routers'
auxiliary losses. A new decoder-only architecture is a config plus the ops
it lacks, not a model file. First user: OLMoE-1B-7B (`model_type: olmoe`;
Muennighoff et al. 2024, arXiv:2409.02060), whose equations the module
follows; `causal_lm_reference.py` is the same forward in plain jax.numpy.

Config keys read (HF names): vocab_size, hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads (must equal the heads: no grouped
queries yet), intermediate_size (the dense FFN's width, or one expert's),
num_experts (0 or absent: dense SwiGLU), num_experts_per_tok,
norm_topk_prob, rms_norm_eps, rope_theta (None: no rotary), hidden_act
(silu), attention_bias (false), clip_qkv (null), tie_word_embeddings
(false), initializer_range, router_aux_loss_coef, router_z_loss_coef; and
`qk_norm`, which `config.json` does not carry because `modeling_olmoe.py`
always applies it.

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
    "router_z_loss_coef": 0.001, "qk_norm": False}


def resolve(cfg):
    """`cfg` over DEFAULTS, refusing what the builder cannot build rather
    than building something else under the model's name."""
    c = dict(DEFAULTS, **cfg)
    c.setdefault("num_key_value_heads", c["num_attention_heads"])
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("clip_qkv", None), ("tie_word_embeddings", False),
                      ("num_key_value_heads", c["num_attention_heads"])):
        if c[key] != want:
            raise NotImplementedError(
                "causal_lm builds %s=%r only, the config has %r"
                % (key, want, c[key]))
    if c["hidden_size"] % c["num_attention_heads"]:
        raise ValueError("hidden_size %d is not a multiple of %d heads"
                         % (c["hidden_size"], c["num_attention_heads"]))
    return c


def _linear(x, size, c):
    return fluid.layers.fc(
        input=x, size=size, bias_attr=False, num_flatten_dims=2,
        param_attr=fluid.ParamAttr(initializer=fluid.initializer.Normal(
            0.0, c["initializer_range"])))


def _norm(x, c):
    return fluid.layers.rms_norm(x, epsilon=c["rms_norm_eps"])


def attention(x, pos, c):
    """Causal self-attention over x [B, T, D]. QK-norm, where the config has
    it, is over all D channels before the head split; rotary positions turn
    every head of q and k; the core is layers.fused_attention."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    q, k, v = (_linear(x, d, c) for _ in range(3))
    if c["qk_norm"]:
        q, k = _norm(q, c), _norm(k, c)
    q, k, v = (fluid.layers.reshape(t, shape=[0, -1, h, d // h])
               for t in (q, k, v))
    if c["rope_theta"] is not None:
        q, k = (fluid.layers.rotary_embedding(t, pos, base=c["rope_theta"])
                for t in (q, k))
    ctx = fluid.layers.fused_attention(q, k, v, causal=True)
    return _linear(fluid.layers.reshape(ctx, shape=[0, -1, d]), d, c)


def feed_forward(x, c):
    """(out, aux) of one layer's FFN on x [B, T, D]: routed experts give
    aux = (balance_loss, z_loss, expert_load), the dense SwiGLU None."""
    if c["num_experts"]:
        out, balance, z, load = fluid.layers.moe_ffn(
            x, num_experts=c["num_experts"], d_expert=c["intermediate_size"],
            top_k=c["num_experts_per_tok"],
            norm_topk_prob=c["norm_topk_prob"],
            param_attr=fluid.ParamAttr(initializer=fluid.initializer.Normal(
                0.0, c["initializer_range"])))
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
    router_z_loss_coef x their mean z loss; expert_load [E] int32 sums the
    layers' assignment counts (None without experts)."""
    c = resolve(cfg)
    ids = fluid.layers.data("ids", [seq_len], dtype="int64")
    pos = fluid.layers.data("pos", [seq_len], dtype="int64")
    labels = fluid.layers.data("labels", [seq_len, 1], dtype="int64")
    h = fluid.layers.embedding(
        ids, size=[c["vocab_size"], c["hidden_size"]],
        param_attr=fluid.ParamAttr(initializer=fluid.initializer.Normal(
            0.0, c["initializer_range"])))
    aux = []
    for _ in range(c["num_hidden_layers"]):
        h = h + attention(_norm(h, c), pos, c)
        out, layer_aux = feed_forward(_norm(h, c), c)
        h = h + out
        if layer_aux is not None:
            aux.append(layer_aux)
    logits = _linear(_norm(h, c), c["vocab_size"], c)
    cost = fluid.layers.softmax_with_cross_entropy(
        logits=fluid.layers.reshape(logits, shape=[-1, c["vocab_size"]]),
        label=fluid.layers.reshape(labels, shape=[-1, 1]))
    loss = fluid.layers.mean(cost)
    load = None
    if aux:
        balance, z, load = (fluid.layers.sums(list(terms))
                            for terms in zip(*aux))
        loss = loss + balance * (c["router_aux_loss_coef"] / len(aux)) \
            + z * (c["router_z_loss_coef"] / len(aux))
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
