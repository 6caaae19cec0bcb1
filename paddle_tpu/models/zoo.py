"""Small-config build registry over every bundled model family.

One place that knows how to construct a representative (tiny) training
program per model in `paddle_tpu/models` — the shared work-list of
`tools/pplint.py --all-models` (the tier-1 lint sweep: every bundled
model analyzed under every applicable deployment context) and of the
tooling tests. Configs are deliberately minimal: the SHAPE of each
program (op vocabulary, sub-blocks, sequence plumbing) is what the
consumers exercise, not its capacity.

    for name in zoo.names():
        main, startup = zoo.build(name)
"""
import paddle_tpu as fluid


def _builders():
    L = fluid.layers

    def mnist():
        from . import recognize_digits
        recognize_digits.build(nn_type="conv")

    def sentiment():
        from .understand_sentiment import stacked_lstm_net
        data = L.data(name="words", shape=[1], dtype="int64", lod_level=1)
        stacked_lstm_net(data, dict_dim=100, class_dim=2, emb_dim=16,
                         hid_dim=16, stacked_num=3)

    def seq2seq():
        from .machine_translation import build_train
        build_train(dict_size=30, word_dim=8, hidden_dim=16,
                    decoder_size=16)

    def transformer():
        from . import transformer as tfm
        tfm.build_train(src_vocab_size=20, trg_vocab_size=20, max_length=8,
                        n_layer=1, n_head=2, d_key=8, d_value=8, d_model=16,
                        d_inner_hid=32)

    def srl():
        from . import label_semantic_roles
        label_semantic_roles.build_train(
            word_dict_len=50, label_dict_len=9, pred_dict_len=20,
            word_dim=8, mark_dim=4, hidden_dim=16, depth=2, lr=0.03,
            mix_hidden_lr=1.0)

    def ctr():
        from . import ctr as m
        m.build(sparse_feature_dim=1000, embedding_size=8)

    def word2vec():
        from . import word2vec as m
        m.build(dict_size=100, embed_size=8, hidden_size=16)

    def recommender():
        from . import recommender_system as m
        m.build_train(emb_dim=8, fc_dim=16)

    def language_model():
        from . import language_model as m
        m.build(vocab_size=120, emb_size=8, hidden_size=8, num_layers=2)

    def causal_lm():
        from . import causal_lm as m
        m.build_train(dict(vocab_size=40, hidden_size=16,
                           num_hidden_layers=1, num_attention_heads=2,
                           intermediate_size=8, num_experts=4,
                           num_experts_per_tok=2, qk_norm=True), seq_len=8)

    return {"causal_lm": causal_lm, "mnist": mnist, "sentiment": sentiment,
            "seq2seq": seq2seq, "transformer": transformer, "srl": srl, "ctr": ctr,
            "word2vec": word2vec, "recommender": recommender,
            "language_model": language_model}


def names():
    """Sorted model names in the zoo."""
    return sorted(_builders())


def build(name):
    """Construct model `name` at its zoo config -> (main, startup)
    Programs, built under fresh name/program guards."""
    builder = _builders().get(name)
    if builder is None:
        raise KeyError("no zoo model named %r (have: %s)"
                       % (name, ", ".join(names())))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        builder()
    return main, startup
