"""Runs the granite-4.0-h-micro cell with its model broken on purpose, to show
that `correct` can fail for what the cell measures.

    python benchmark/tests/mutant_granite_4_0_h_micro.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. `causal_lm.resolve` is left alone: the
reference reads the configuration through it too. Every mutant's last line
has to say `"correct": false`; the configuration's .json has what the chip
gave.

The last two are no mutants of the program: `reference_bf16_weights` and
`reference_fp8_weights` run the healthy program against the reference with
its weights rounded to bfloat16 (which has to stay correct: it is the
precision the configuration states) and to float8 e4m3, scaled a tensor, the
nearest precision below (which has to fail a tolerance). `gradients_kept` is
no mutant either: the healthy program and the healthy check, and the five
gradient fetches beside the reference's written to
`chiprun_out/granite_gradients_<loss>.npz`, for whoever asks which heads an
error sits in.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _block(fluid):
    return fluid.default_main_program().global_block()


def state_not_carried(fluid, causal_lm):
    """Every chunk of the scan (the table's 128 tokens; half the sequence
    where that is shorter) starts from an empty state: what the tokens
    before a chunk's edge wrote is lost there."""
    from paddle_tpu.ops import kernel_config, ssd_kernels
    scan = ssd_kernels.ssd_scan

    def chunk_by_chunk(x, delta, a, b, c, d, **kw):
        batch, t = x.shape[:2]
        chunk = min(kernel_config.DEFAULT_TILES["ssd"]["chunk"], t // 2)
        if t % chunk:
            raise ValueError("the mutant wants whole chunks of %d" % chunk)

        def cut(v):
            return v.reshape((batch * (t // chunk), chunk) + v.shape[2:])
        return scan(cut(x), cut(delta), a, cut(b), cut(c), d, **kw) \
            .reshape(x.shape)
    ssd_kernels.ssd_scan = chunk_by_chunk


def _scale_as(fluid, was, now):
    """layers.scale called with the factor `was` multiplies by `now`."""
    scale = fluid.layers.scale

    def other(x, scale_=1.0, **kw):
        factor = kw.pop("scale", scale_)
        return scale(x, scale=now if factor == was else factor, **kw)
    fluid.layers.scale = other


def a_not_negated(fluid, causal_lm):
    """A = exp(A_log), not its negative: every state grows."""
    _scale_as(fluid, -1.0, 1.0)


def embedding_multiplier_1(fluid, causal_lm):
    """The embedding's output enters the stack as it is, not 12 times."""
    _scale_as(fluid, 12.0, 1.0)


def residual_multiplier_1(fluid, causal_lm):
    """Each branch is added to the stream whole, not at 0.22."""
    _scale_as(fluid, 0.22, 1.0)


def logits_scaling_1(fluid, causal_lm):
    """The logits are not divided by 8."""
    _scale_as(fluid, 0.125, 1.0)


def delta_without_softplus(fluid, causal_lm):
    """Delta = dt + dt_bias as it comes, without the softplus."""
    fluid.layers.softplus = lambda x, **kw: x


def _scan_with(fluid, change):
    scan = fluid.layers.ssd_scan
    fluid.layers.ssd_scan = lambda *args, **kw: scan(*change(list(args)),
                                                     **kw)


def d_dropped(fluid, causal_lm):
    """The scan's output lacks its skip term D x."""
    def zero_d(args):
        args[5] = fluid.layers.scale(args[5], scale=0.0)
        return args
    _scan_with(fluid, zero_d)


def b_and_c_exchanged(fluid, causal_lm):
    """The state is written along C and read along B."""
    def swapped(args):
        args[3], args[4] = args[4], args[3]
        return args
    _scan_with(fluid, swapped)


def norm_before_gate(fluid, causal_lm):
    """RMSNorm(y) * SiLU(z), the order layers.rms_norm(gate=) has, where the
    mixer gates first and norms the product."""
    norm = fluid.layers.rms_norm

    def norm_first(x, param_attr=None, **kw):
        if not str(getattr(param_attr, "name", "")).endswith("gated_norm"):
            return norm(x, param_attr=param_attr, **kw)
        product = next(op for op in reversed(_block(fluid).ops)
                       if x.name in op.output("Out"))
        y, gate = (_block(fluid).var(product.input(slot)[0])
                   for slot in ("X", "Y"))
        return norm(y, param_attr=param_attr, **kw) * gate
    fluid.layers.rms_norm = norm_first


def conv_bias_dropped(fluid, causal_lm):
    """SiLU(conv(xBC)) without the convolution's bias (the parameter
    stays)."""
    add = fluid.layers.elementwise_add

    def dropped(x, y, **kw):
        if str(getattr(y, "name", "")).endswith("conv.bias"):
            y = fluid.layers.scale(y, scale=0.0)
        return add(x, y, **kw)
    fluid.layers.elementwise_add = dropped


def _layer_with(causal_lm, **changed):
    layer = causal_lm._layer
    causal_lm._layer = lambda c, i: dict(layer(c, i), **changed)


def rotary_on(fluid, causal_lm):
    """The attention layer turns q and k by rotary positions (theta 10000),
    where the model has no positional term."""
    _layer_with(causal_lm, rope_theta=10000.0)


def score_scale_rsqrt(fluid, causal_lm):
    """The scores are scaled by 64^-1/2 = 0.125, not by
    attention_multiplier = 1/64."""
    _layer_with(causal_lm, attention_scale=64 ** -0.5)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "granite_4_0_h_micro.py")):
            reference = mod.reference
            mod.reference = lambda cfg, traffic, params, batch: reference(
                cfg, traffic, [round_weights(p) for p in params], batch)
        return mod
    manifest.load_module = load_and_wrap


def reference_bf16_weights(fluid, causal_lm):
    """The reference with its weights rounded to bfloat16: stays correct."""
    import jax.numpy as jnp
    _reference_with(lambda p: p.astype(jnp.bfloat16).astype(jnp.float32))


def reference_fp8_weights(fluid, causal_lm):
    """The reference with its weights rounded to float8 e4m3, scaled a
    tensor to the format's range: has to fail a tolerance."""
    import jax.numpy as jnp

    def fp8(p):
        scale = jnp.maximum(jnp.abs(p).max(), 1e-30) / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


def gradients_kept(fluid, causal_lm):
    """No mutant: the healthy program and check; the five gradient fetches
    and the reference's are written to chiprun_out/ as they are compared."""
    import numpy as np
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "granite_4_0_h_micro.py")):
            check = mod.check

            def check_and_keep(cfg, first, want, scalars):
                kept = {}
                for name in mod.GRADIENTS:
                    kept["program." + name] = np.asarray(first[name])
                    kept["reference." + name] = np.asarray(want[name])
                os.makedirs("chiprun_out", exist_ok=True)
                np.savez(os.path.join(
                    "chiprun_out", "granite_gradients_%.6f.npz"
                    % float(np.ravel(first["loss"])[0])), **kept)
                return check(cfg, first, want, scalars)
            mod.check = check_and_keep
        return mod
    manifest.load_module = load_and_wrap


MUTANTS = {f.__name__: f for f in (
    state_not_carried, a_not_negated, delta_without_softplus, d_dropped,
    b_and_c_exchanged, norm_before_gate, conv_bias_dropped, rotary_on,
    score_scale_rsqrt, embedding_multiplier_1, residual_multiplier_1,
    logits_scaling_1, reference_bf16_weights, reference_fp8_weights,
    gradients_kept)}
# those whose last line has to say `"correct": false`
HAVE_TO_FAIL = tuple(name for name in MUTANTS if name not in (
    "reference_bf16_weights", "gradients_kept"))


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_granite_4_0_h_micro.py <%s> <arguments of "
              "benchmark/run.py>" % "|".join(MUTANTS), file=sys.stderr)
        return 1
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    MUTANTS[argv[0]](fluid, causal_lm)
    print("bench: MUTANT %s: %s" % (argv[0], MUTANTS[argv[0]].__doc__),
          flush=True)
    from benchmark import run
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
