"""Runs the SmallThinker cell with its model broken on purpose, to show that
`correct` can fail for what the cell measures.

    python benchmark/tests/mutant_smallthinker.py <mutant> <the arguments of benchmark/run.py>

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


def window_off(moe, causal_lm, layers):
    """The three windowed layers attend the whole causal prefix."""
    attend = layers.fused_attention
    layers.fused_attention = lambda *a, window=None, **kw: attend(*a, **kw)


def rope_on_global(moe, causal_lm, layers):
    """The full-attention layer rotates its queries and keys like the
    windowed ones (it has no rotary: NoPE)."""
    layer = causal_lm._layer

    def every_layer_rotates(c, i):
        return dict(layer(c, i), rope_theta=c["rope_theta"])
    causal_lm._layer = every_layer_rotates


def silu_for_relu(moe, causal_lm, layers):
    """The experts' gate goes through SiLU and not ReLU."""
    moe._gated_relu = moe._gated_silu


def router_after_attention(moe, causal_lm, layers):
    """The router reads the experts' own input, after attention, and not
    the attention's normed input."""
    ffn = layers.moe_ffn
    layers.moe_ffn = lambda *a, router_input=None, **kw: ffn(*a, **kw)


def wrong_kv_head(moe, causal_lm, layers):
    """The last query head of every group reads a key/value head that is
    not its own. This chip holds one key/value head, so the other is an
    absent one, all zeros: that query head's output is zero. On both
    attention paths (flash at the cell's T, dense at the rehearsal's)."""
    import importlib
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels
    # the module: the package exports a function of the same name
    ring_attention = importlib.import_module(
        "paddle_tpu.parallel.ring_attention")

    def misread(attend):
        def broken(q, k, v, **kw):
            group = q.shape[2] // k.shape[2]
            own = jnp.arange(q.shape[2]) % group != group - 1
            out = attend(q, k, v, **kw)
            return out * own[None, None, :, None].astype(out.dtype)
        return broken
    pallas_kernels.flash_attention = misread(pallas_kernels.flash_attention)
    ring_attention.attention_reference = misread(
        ring_attention.attention_reference)


def top5(moe, causal_lm, layers):
    """A token's weakest expert is dropped: top-5 routing. `dropless` fails
    too: 5 x N assignments a layer were counted."""
    routed = moe.routed_ffn
    moe.routed_ffn = lambda *a, top_k, **kw: routed(*a, top_k=top_k - 1, **kw)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "smallthinker.py")):
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
    window_off, rope_on_global, silu_for_relu, router_after_attention,
    wrong_kv_head, top5, reference_bf16_weights, reference_fp8_weights)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_smallthinker.py <%s> <arguments of "
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
