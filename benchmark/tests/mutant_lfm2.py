"""Runs the LFM2 cell with its model broken on purpose, to show that
`correct` can fail for what the cell measures.

    python benchmark/tests/mutant_lfm2.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. Every mutant's last line has to say `"correct":
false`; the configuration's .json has what the chip gave.

The last two are no mutants of the program: `reference_bf16_weights` and
`reference_fp8_weights` run the healthy program against the reference with
its weights rounded to bfloat16 (which has to stay correct: it is the
precision the configuration states) and to float8 e4m3, scaled a tensor,
the nearest precision below (which has to fail a tolerance).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _rule_with(op_type, change):
    """The registered lowering of `op_type` behind `change(ins, attrs) ->
    (ins, attrs)`."""
    from paddle_tpu.core import registry
    rule = registry.get(op_type)
    lower = rule.lower

    def changed(ctx, ins, attrs):
        return lower(ctx, *change(ins, dict(attrs)))
    rule.lower = changed


def bias_off(moe, causal_lm, layers):
    """The experts are chosen without the bias: top-4 of s, not of s + b."""
    _rule_with("moe_ffn", lambda ins, attrs: (
        {slot: v for slot, v in ins.items() if slot != "ExpertBias"}, attrs))


def bias_in_weights(moe, causal_lm, layers):
    """The bias carries weight: the chosen experts are weighed by s + b,
    renormalised, where the model weighs them by s."""
    import jax.numpy as jnp
    route = moe._route

    def biased(logits, top_k, norm_topk_prob, scoring, expert_bias, scale):
        probs, lse, _, expert = route(logits, top_k, norm_topk_prob, scoring,
                                      expert_bias, scale)
        gate = jnp.take_along_axis(probs + expert_bias, expert, axis=-1)
        gate = gate / (gate.sum(-1, keepdims=True) + moe.SIGMOID_NORM_EPS)
        return probs, lse, gate * scale, expert
    moe._route = biased


def softmax_for_sigmoid(moe, causal_lm, layers):
    """The router scores by a softmax over the 32 experts, not by a sigmoid
    an expert."""
    def softmax(ins, attrs):
        attrs.pop("scoring")
        return ins, attrs
    _rule_with("moe_ffn", softmax)


def no_renorm(moe, causal_lm, layers):
    """The four chosen scores are not divided by their sum."""
    _rule_with("moe_ffn", lambda ins, attrs: (
        ins, dict(attrs, norm_topk_prob=False)))


def gates_swapped(moe, causal_lm, layers):
    """The short convolution's two gates change places: C * u goes in, B
    multiplies what comes out."""
    def swapped(x, c):
        d = c["hidden_size"]
        b, gate, u = layers.split(causal_lm._linear(x, 3 * d, c, "w_in"), 3,
                                  dim=-1)
        mixed = layers.causal_conv1d(gate * u, c["conv_L_cache"],
                                     param_attr=causal_lm._matrix(c, "conv"))
        return causal_lm._linear(b * mixed, d, c, "w_out")
    causal_lm.short_conv = swapped


def taps_reversed(moe, causal_lm, layers):
    """The filter's taps are read in the other order: the tap meant for the
    current token weighs the one two back."""
    _rule_with("causal_conv1d", lambda ins, attrs: (
        dict(ins, Filter=[ins["Filter"][0][:, ::-1]]), attrs))


def conv_silu(moe, causal_lm, layers):
    """The convolution's output passes a SiLU (a gated delta net's
    convolution has one, this mixer's has none)."""
    _rule_with("causal_conv1d", lambda ins, attrs: (
        ins, dict(attrs, activation="silu")))


def dense_layer_routed(moe, causal_lm, layers):
    """The leading dense layer is given experts: its FFN of 7168 is read as
    four experts of 1792 under the layers' own routing, top-4 renormalised,
    with the router a dense layer does not have at zero: every token takes
    all four at a weight of 1/4, where the model adds them whole."""
    swiglu = causal_lm._swiglu

    def routed(x, width, c, role=""):
        out = swiglu(x, width, c, role)
        return out if role else layers.scale(
            out, scale=1.0 / c["num_experts_per_tok"])
    causal_lm._swiglu = routed


def untied_head(moe, causal_lm, layers):
    """The head reads another matrix than the embedding: its rows moved on
    by one word, which is to the reference what an untied head's own weights
    would be. (The head is the model's only `matmul` op.)"""
    import jax.numpy as jnp
    _rule_with("matmul", lambda ins, attrs: (
        dict(ins, Y=[jnp.roll(ins["Y"][0], 1, axis=0)]), attrs))


def qk_norm_off(moe, causal_lm, layers):
    """Queries and keys reach rotary and the scores without their norm a
    head (the two weights stay, unused)."""
    norm = causal_lm._norm

    def skipped(x, c, role=None):
        out = norm(x, c, role)
        return x if (role or c.get("role")) in ("q_norm", "k_norm") else out
    causal_lm._norm = skipped


def wrong_kv_head(moe, causal_lm, layers):
    """Query head h reads key/value head h % 8 where the model gives it
    h // 4. On both attention paths (flash at the cell's T, dense at the
    rehearsal's): the query heads are put where the core pairs them so, and
    the outputs put back."""
    import importlib
    import numpy as np
    from paddle_tpu.ops import pallas_kernels
    ring_attention = importlib.import_module(
        "paddle_tpu.parallel.ring_attention")

    def misread(attend):
        def broken(q, k, v, **kw):
            hq, hkv = q.shape[2], k.shape[2]
            heads = np.arange(hq)
            place = (heads % hkv) * (hq // hkv) + heads // hkv
            out = attend(q[:, :, np.argsort(place)], k, v, **kw)
            return out[:, :, place]
        return broken
    pallas_kernels.flash_attention = misread(pallas_kernels.flash_attention)
    ring_attention.attention_reference = misread(
        ring_attention.attention_reference)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "lfm2.py")):
            reference = mod.reference
            mod.reference = lambda cfg, traffic, params, batch: reference(
                cfg, traffic, [round_weights(p) for p in params], batch)
        return mod
    manifest.load_module = load_and_wrap


def reference_bf16_weights(moe, causal_lm, layers):
    """The reference with its weights rounded to bfloat16: stays correct."""
    import jax.numpy as jnp
    _reference_with(lambda p: p.astype(jnp.bfloat16).astype(jnp.float32))


def reference_fp8_weights(moe, causal_lm, layers):
    """The reference with its weights rounded to float8 e4m3, scaled a
    tensor to the format's range: has to fail a tolerance."""
    import jax.numpy as jnp

    def fp8(p):
        scale = jnp.abs(p).max() / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


MUTANTS = {f.__name__: f for f in (
    bias_off, bias_in_weights, softmax_for_sigmoid, no_renorm, gates_swapped,
    taps_reversed, conv_silu, dense_layer_routed, untied_head, qk_norm_off,
    wrong_kv_head, reference_bf16_weights, reference_fp8_weights)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_lfm2.py <%s> <arguments of benchmark/run.py>"
              % "|".join(MUTANTS), file=sys.stderr)
        return 1
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    from paddle_tpu.parallel import moe
    MUTANTS[argv[0]](moe, causal_lm, fluid.layers)
    print("bench: MUTANT %s: %s" % (argv[0], MUTANTS[argv[0]].__doc__),
          flush=True)
    from benchmark import run
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
