"""Runs the Qwen3-Next cell with its model broken on purpose, to show that
`correct` can fail for what the cell measures.

    python benchmark/tests/mutant_qwen3_next.py <mutant> <the arguments of benchmark/run.py>

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


def _ones_for_sigmoid(layers, build):
    """`build` with layers.sigmoid giving 1 everywhere while it runs (the
    gate's weights stay, with a zero gradient)."""
    def patched(*args, **kwargs):
        sigmoid = layers.sigmoid
        layers.sigmoid = lambda x: layers.scale(x, scale=0.0, bias=1.0)
        try:
            return build(*args, **kwargs)
        finally:
            layers.sigmoid = sigmoid
    return patched


def _rule_with(op_type, change):
    """The registered lowering of `op_type` behind `change(ins, attrs) ->
    (ins, attrs)`."""
    from paddle_tpu.core import registry
    rule = registry.get(op_type)
    lower = rule.lower

    def changed(ctx, ins, attrs):
        return lower(ctx, *change(ins, dict(attrs)))
    rule.lower = changed


def no_decay(moe, causal_lm, layers):
    """The state never decays: g = 0 in every gated delta net."""
    rule = layers.gated_delta_rule
    layers.gated_delta_rule = lambda q, k, v, g, beta, **kw: rule(
        q, k, v, layers.scale(g, scale=0.0), beta, **kw)


def beta_one(moe, causal_lm, layers):
    """Every token writes at full strength: beta = 1."""
    rule = layers.gated_delta_rule
    layers.gated_delta_rule = lambda q, k, v, g, beta, **kw: rule(
        q, k, v, g, layers.scale(beta, scale=0.0, bias=1.0), **kw)


def no_l2norm(moe, causal_lm, layers):
    """q and k enter the delta rule as the convolution left them."""
    from paddle_tpu.ops import gated_delta_kernels
    gated_delta_kernels._l2norm = lambda x: x


def no_carry(moe, causal_lm, layers):
    """No state leaves a chunk: every chunk of the delta rule starts from
    S = 0, as a pass over chunks that lost its carry would (the operand the
    leaving state is written from is zeroed, on the kernel and the scan
    path, forward and backward alike)."""
    from paddle_tpu.ops import gated_delta_kernels
    prepare = gated_delta_kernels._prepare

    def cut(*args, **kw):
        qe, kd, m, u, w, erow = prepare(*args, **kw)
        return qe, kd * 0, m, u, w, erow
    gated_delta_kernels._prepare = cut


def conv_off(moe, causal_lm, layers):
    """The convolution sees no earlier token: only its last tap is kept."""
    def last_tap(ins, attrs):
        w = ins["Filter"][0]
        return dict(ins, Filter=[w.at[:, :-1].set(0.0)]), attrs
    _rule_with("causal_conv1d", last_tap)


def rope_whole_head(moe, causal_lm, layers):
    """Rotary turns all 256 channels of a head, not the first 64."""
    rotary = layers.rotary_embedding
    layers.rotary_embedding = lambda *a, rotary_dim=None, **kw: rotary(*a,
                                                                       **kw)


def output_gate_off(moe, causal_lm, layers):
    """The full-attention layer's context reaches Wo ungated."""
    causal_lm.attention = _ones_for_sigmoid(layers, causal_lm.attention)


def norm_not_zero_centred(moe, causal_lm, layers):
    """The norms multiply by w and not by 1 + w."""
    def plain(ins, attrs):
        attrs.pop("zero_centered", None)
        return ins, attrs
    _rule_with("rms_norm", plain)


def shared_gate_off(moe, causal_lm, layers):
    """The shared expert is added whole, without sigmoid(x w_s)."""
    causal_lm.feed_forward = _ones_for_sigmoid(layers, causal_lm.feed_forward)


def wrong_key_head(moe, causal_lm, layers):
    """Key head j serves value heads j and j + Hk (a tiled repeat) and not
    2j and 2j + 1 (repeat_interleave)."""
    import numpy as np
    from paddle_tpu.ops import gated_delta_kernels
    rule = gated_delta_kernels.gated_delta_rule

    def tiled(q, k, v, g, beta, **kw):
        hk, hv = q.shape[2], v.shape[2]
        rep = hv // hk
        to = np.array([(h % hk) * rep + h // hk for h in range(hv)])
        back = np.argsort(to)
        out = rule(q, k, v[:, :, back], g[:, :, back], beta[:, :, back],
                   **kw)
        return out[:, :, to]
    gated_delta_kernels.gated_delta_rule = tiled


def top9(moe, causal_lm, layers):
    """A token's weakest expert is dropped: top-9 routing. `dropless` fails
    too: 9 x N assignments a layer were counted."""
    routed = moe.routed_ffn
    moe.routed_ffn = lambda *a, top_k, **kw: routed(*a, top_k=top_k - 1, **kw)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "qwen3_next.py")):
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
        scale = jnp.maximum(jnp.abs(p).max(), 1e-30) / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


MUTANTS = {f.__name__: f for f in (
    no_decay, beta_one, no_l2norm, no_carry, conv_off, rope_whole_head,
    output_gate_off, norm_not_zero_centred, shared_gate_off, wrong_key_head,
    top9, reference_bf16_weights, reference_fp8_weights)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_qwen3_next.py <%s> <arguments of "
              "benchmark/run.py>" % "|".join(MUTANTS), file=sys.stderr)
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
