"""Runs the Laguna-S-2.1 cell with its model broken on purpose, to show that
`correct` can fail for what the cell measures.

    python benchmark/tests/mutant_laguna.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters, their shapes and their
order as they are, so the reference still reads the program's weights; then
the cell runs as benchmark/run.py runs it. Every mutant's last line has to
say `"correct": false`; the configuration's .json has what the chip gave.

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

SLIDING = "sliding_attention"
_ROPE = ("rope_type", "rotary_dim", "rope_inv_freq", "rope_theta",
         "rope_table_scale", "attention_scale")


def _layer_with(causal_lm, change):
    """causal_lm._layer behind `change(c, i, cl) -> {key: value}`, what the
    layer sees otherwise."""
    layer = causal_lm._layer

    def changed(c, i):
        cl = layer(c, i)
        return dict(cl, **change(c, i, cl))
    causal_lm._layer = changed


def _kind(c, i):
    return c["layer_types"][i]


def _rule_with(op_type, change):
    """The registered lowering of `op_type` behind `change(ins, attrs) ->
    (ins, attrs)`."""
    from paddle_tpu.core import registry
    rule = registry.get(op_type)
    lower = rule.lower

    def changed(ctx, ins, attrs):
        return lower(ctx, *change(dict(ins), dict(attrs)))
    rule.lower = changed


def _gate_with(fluid, change):
    """The multiply of the core's output by the gate a head
    (elementwise_mul at axis 0 in `attention`) behind `change(gate) ->
    gate`."""
    layers = fluid.layers
    multiply = layers.elementwise_mul

    def gated(x, y, axis=-1, **kw):
        if axis == 0 and len(x.shape) == 4 and len(y.shape) == 3:
            y = change(y)
        return multiply(x, y, axis=axis, **kw)
    layers.elementwise_mul = gated


def _cores_with(change):
    """Both attention cores (the Pallas flash kernel at the cell's T, the
    dense path at the rehearsal's) behind `change(attend) -> attend`."""
    import importlib
    from paddle_tpu.ops import pallas_kernels
    ring_attention = importlib.import_module(
        "paddle_tpu.parallel.ring_attention")
    pallas_kernels.flash_attention = change(pallas_kernels.flash_attention)
    ring_attention.attention_reference = change(
        ring_attention.attention_reference)


def heads_12_everywhere(fluid, causal_lm, moe):
    """A sliding layer runs at the full layers' head count: of its 18 held
    query heads the first 12 reach W_o, the others' context is zero (the
    weights stay [3072, 18 x 128])."""
    layers = fluid.layers
    attention, attend = causal_lm.attention, layers.fused_attention
    least = {}

    def narrowed(q, k, v, **kw):
        out = attend(q, k, v, **kw)
        h, full = int(q.shape[2]), least["heads"]
        if h == full:
            return out
        kept, rest = layers.split(out, [full, h - full], dim=2)
        return layers.concat([kept, layers.scale(rest, scale=0.0)], axis=2)

    def layer_attention(x, pos, c):
        least["heads"] = min(g["num_attention_heads"]
                             for g in c["geometry_layers"] if g)
        return attention(x, pos, c)
    causal_lm.attention, layers.fused_attention = layer_attention, narrowed


def window_off(fluid, causal_lm, moe):
    """The three sliding layers attend the whole causal prefix."""
    _layer_with(causal_lm, lambda c, i, cl: {"window": None})


def window_on_full(fluid, causal_lm, moe):
    """The full layers sit behind the window of 512 too."""
    _layer_with(causal_lm, lambda c, i, cl: {"window": c["sliding_window"]})


def window_511(fluid, causal_lm, moe):
    """Key i - 511 is not seen: the other convention of the window's
    edge."""
    _layer_with(causal_lm, lambda c, i, cl: {
        "window": cl["window"] - 1 if cl["window"] else None})


def rope_tables_swapped(fluid, causal_lm, moe):
    """A full layer turns by the sliding layers' parameters (the whole head
    at theta 1e4) and a sliding layer by the full layers' (half a head
    under YaRN)."""
    def swapped(c, i, cl):
        other = next(g for g in c["geometry_layers"]
                     if g and g["rope_type"] != cl["rope_type"])
        return {key: other[key] for key in _ROPE}
    _layer_with(causal_lm, swapped)


def yarn_off(fluid, causal_lm, moe):
    """The full layers turn by theta^(-2i/64) as it is: no YaRN ramp, no
    factor on cos and sin."""
    _layer_with(causal_lm, lambda c, i, cl: {
        "rope_inv_freq": None, "rope_table_scale": 1.0})


def attention_factor_1(fluid, causal_lm, moe):
    """YaRN's frequencies without attention_factor: cos and sin as they
    are."""
    _layer_with(causal_lm, lambda c, i, cl: {"rope_table_scale": 1.0})


def rotary_whole_head_on_full(fluid, causal_lm, moe):
    """The full layers turn all 128 channels of a head (partial_rotary_factor
    1), under YaRN over that width."""
    def whole(c, i, cl):
        if _kind(c, i) == SLIDING:
            return {}
        found = causal_lm._rope_kind(c, _kind(c, i), dict(
            c["rope_parameters"][_kind(c, i)], partial_rotary_factor=1))
        return {key: found[key] for key in _ROPE}
    _layer_with(causal_lm, whole)


def theta_shared(fluid, causal_lm, moe):
    """ONE theta a program: the sliding layers turn at the full layers'
    500000."""
    _layer_with(causal_lm, lambda c, i, cl: {
        "rope_theta": c["rope_parameters"]["full_attention"]["rope_theta"]})


def gate_dropped(fluid, causal_lm, moe):
    """The core's output enters W_o as it is (the gate reads 1; W_g stays a
    parameter with a gradient of 0)."""
    _gate_with(fluid, lambda gate: fluid.layers.scale(gate, scale=0.0,
                                                      bias=1.0))


def gate_after_wo(fluid, causal_lm, moe):
    """The gate multiplies what W_o gives: one scalar a TOKEN, the heads'
    mean gate, where each head's context has its own before W_o."""
    layers = fluid.layers

    def mean_gate(gate):
        mean = layers.reduce_mean(gate, dim=-1, keep_dim=True)
        return layers.expand(mean, expand_times=[1, 1, int(gate.shape[-1])])
    _gate_with(fluid, mean_gate)


def gate_elementwise(fluid, causal_lm, moe):
    """The H gates are laid over the H x 128 channels one a CHANNEL, in
    channel order (channel j reads gate j mod H: the layout of a gate a
    channel), not one a head."""
    layers = fluid.layers
    multiply = layers.elementwise_mul

    def by_channel(x, y, axis=-1, **kw):
        if axis == 0 and len(x.shape) == 4 and len(y.shape) == 3:
            h, hd = int(x.shape[2]), int(x.shape[3])
            tiled = layers.reshape(
                layers.expand(y, expand_times=[1, 1, hd]),
                shape=[0, -1, h, hd])
            return multiply(x, tiled, **kw)
        return multiply(x, y, axis=axis, **kw)
    layers.elementwise_mul = by_channel


def qk_norm_dropped(fluid, causal_lm, moe):
    """q and k reach the rotary as the projections give them (the norms'
    weights stay parameters with a gradient of 0)."""
    norm = causal_lm._norm

    def unnormed(x, c, role=None):
        out = norm(x, c, role)
        if (role or c.get("role")) in ("q_norm", "k_norm"):
            return x + fluid.layers.scale(out, scale=0.0)
        return out
    causal_lm._norm = unnormed


def _moe_ffn_with(fluid, **changed):
    ffn = fluid.layers.moe_ffn
    fluid.layers.moe_ffn = lambda *a, **kw: ffn(*a, **dict(kw, **changed))


def scaling_factor_1(fluid, causal_lm, moe):
    """moe_routed_scaling_factor 1: the routed experts at two fifths of
    their weight."""
    _moe_ffn_with(fluid, routed_scaling_factor=1)


def renorm_dropped(fluid, causal_lm, moe):
    """The chosen probabilities weigh the experts as they are, not divided
    by their sum."""
    _moe_ffn_with(fluid, norm_topk_prob=False)


def top_k_9(fluid, causal_lm, moe):
    """A token goes to 9 experts, not 10. `dropless` fails too."""
    routed = moe.routed_ffn
    moe.routed_ffn = lambda *a, top_k, **kw: routed(*a, top_k=top_k - 1,
                                                    **kw)


def router_sigmoid(fluid, causal_lm, moe):
    """The scores are sigmoids of the logits, not their softmax."""
    _moe_ffn_with(fluid, scoring="sigmoid")


def shared_expert_dropped(fluid, causal_lm, moe):
    """An expert layer adds its routed experts alone: the shared expert's
    part is zero."""
    swiglu = causal_lm._swiglu

    def dropped(x, width, c, role=""):
        out = swiglu(x, width, c, role)
        return fluid.layers.scale(out, scale=0.0) \
            if role == "shared_expert." else out
    causal_lm._swiglu = dropped


def shared_gate_dropped(fluid, causal_lm, moe):
    """The shared expert is added as it is: its sigmoid gate reads 1."""
    linear = causal_lm._linear

    def open_gate(x, size, c, role, bias=False):
        out = linear(x, size, c, role, bias)
        return fluid.layers.scale(out, scale=0.0, bias=30.0) \
            if role == "shared_expert.gate" else out
    causal_lm._linear = open_gate


def layer_0_routed(fluid, causal_lm, moe):
    """Layer 0's FFN is sparse: cut into blocks of columns of which a token
    passes the first top_k in top_k + 2 (10240 of the 12288), where
    mlp_only_layers says every token passes the whole dense FFN; the
    parameters stay as they are."""
    layers, linear = fluid.layers, causal_lm._linear

    def sparse_down(x, size, c, role, bias=False):
        if role == "w_down" and c.get("layer") == 0:
            width, k = int(x.shape[-1]), c["num_experts_per_tok"]
            kept = width * k // (k + 2)
            passed, rest = layers.split(x, [kept, width - kept], dim=-1)
            x = layers.concat([passed, layers.scale(rest, scale=0.0)],
                              axis=-1)
        return linear(x, size, c, role, bias)
    causal_lm._linear = sparse_down


def experts_9_to_16_held(fluid, causal_lm, moe):
    """The chip computes the assignments of the router's columns 8 .. 15
    (with the weights it holds), the wrong share of the 256: chip 1's."""
    def next_share(ins, attrs):
        attrs["first_expert"] = ins["WUp"][0].shape[0]
        return ins, attrs
    _rule_with("moe_ffn", next_share)


def kv_heads_2_to_3_held(fluid, causal_lm, moe):
    """The wrong share of the heads: a group of query heads reads the other
    held key/value head's keys and values (0-1 as if they were 2-3's: the
    pairing shifted by one), on both attention paths."""
    def misread(attend):
        def broken(q, k, v, **kw):
            return attend(q, k[:, :, ::-1], v[:, :, ::-1], **kw)
        return broken
    _cores_with(misread)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "laguna.py")):
            reference = mod.reference
            mod.reference = lambda cfg, traffic, params, batch: reference(
                cfg, traffic, [round_weights(p) for p in params], batch)
        return mod
    manifest.load_module = load_and_wrap


def reference_bf16_weights(fluid, causal_lm, moe):
    """The reference with its weights rounded to bfloat16: stays correct."""
    import jax.numpy as jnp
    _reference_with(lambda p: p.astype(jnp.bfloat16).astype(jnp.float32))


def reference_fp8_weights(fluid, causal_lm, moe):
    """The reference with its weights rounded to float8 e4m3, scaled a
    tensor to the format's range: has to fail a tolerance."""
    import jax.numpy as jnp

    def fp8(p):
        scale = jnp.abs(p).max() / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


MUTANTS = {f.__name__: f for f in (
    heads_12_everywhere, window_off, window_on_full, window_511,
    rope_tables_swapped, yarn_off, attention_factor_1,
    rotary_whole_head_on_full, theta_shared, gate_dropped, gate_after_wo,
    gate_elementwise, qk_norm_dropped, scaling_factor_1, renorm_dropped,
    top_k_9, router_sigmoid, shared_expert_dropped, shared_gate_dropped,
    layer_0_routed, experts_9_to_16_held, kv_heads_2_to_3_held,
    reference_bf16_weights, reference_fp8_weights)}
# those whose last line has to say `"correct": false`
HAVE_TO_FAIL = tuple(name for name in MUTANTS
                     if name != "reference_bf16_weights")


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_laguna.py <%s> <arguments of benchmark/run.py>"
              % "|".join(MUTANTS), file=sys.stderr)
        return 1
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    from paddle_tpu.parallel import moe
    MUTANTS[argv[0]](fluid, causal_lm, moe)
    print("bench: MUTANT %s: %s" % (argv[0], MUTANTS[argv[0]].__doc__),
          flush=True)
    from benchmark import run
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
