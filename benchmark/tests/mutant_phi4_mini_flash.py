"""Runs the Phi-4-mini-flash cell with its model broken on purpose, to show
that `correct` can fail for what the cell measures.

    python benchmark/tests/mutant_phi4_mini_flash.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. Every mutant's last line has to say `"correct":
false`; the configuration's .json has what the chip gave.

`memory_readers_gradient_dropped` and `kv_readers_gradient_dropped` leave the
forward pass alone and drop, from the gradient that reaches what one layer
hands on, the part a LATER layer's reader adds. The last three are no
mutants of the program: `reference_bf16_weights` and `reference_fp8_weights`
run the healthy program against the reference with its weights rounded to
bfloat16 (which has to stay correct: it is the precision the configuration
states) and to float8 e4m3, scaled a tensor, the nearest precision below
(which has to fail a tolerance); `gradient_witness` runs the healthy program
and the healthy check, and beside them the reference a second time with
every matmul's inputs rounded to bfloat16 (the program's precision in a
second implementation that runs none of its kernels), and prints where each
gradient fetch's largest errors sit: the program's and the witness's
against float32, and the two against each other.
"""
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _block(fluid):
    return fluid.default_main_program().global_block()


def _core_with(fluid, change):
    """layers.fused_attention behind `change(window) -> window`."""
    core = fluid.layers.fused_attention
    fluid.layers.fused_attention = lambda q, k, v, window=None, **kw: core(
        q, k, v, window=change(window), **kw)


def window_off(fluid, causal_lm):
    """The windowed layer sees every key before it: no sliding window."""
    _core_with(fluid, lambda window: None)


def window_off_by_one(fluid, causal_lm):
    """The windowed layer sees key i - 512 too: a window of 513."""
    _core_with(fluid, lambda window: None if window is None else window + 1)


def lambda_init_at_cuts_index(fluid, causal_lm):
    """lambda_init from the layer's place in the stack that was built (1, 3,
    5), where it is a function of the PUBLISHED index (1, 17, 19)."""
    layer = causal_lm._layer

    def at_place(c, i):
        cl = layer(c, i)
        if cl["lambda_init"] is not None:
            cl["lambda_init"] = 0.8 - 0.6 * math.exp(-0.3 * i)
        return cl
    causal_lm._layer = at_place


def subln_off(fluid, causal_lm):
    """P_1 V - lambda P_2 V enters W_o without the norm over its 128
    channels (the weight stays, unused)."""
    norm = fluid.layers.rms_norm

    def skipped(x, param_attr=None, **kw):
        out = norm(x, param_attr=param_attr, **kw)
        return x if str(getattr(param_attr, "name", "")).endswith(".subln") \
            else out
    fluid.layers.rms_norm = skipped


def scale_off(fluid, causal_lm):
    """The normed output is not multiplied by 1 - lambda_init."""
    scale = fluid.layers.scale

    def unscaled(x, scale_=1.0, bias=0.0, **kw):
        factor = kw.pop("scale", scale_)
        if factor not in (0.0, 1.0, -1.0) and not bias:
            factor = 1.0                # the one call at 1 - lambda_init
        return scale(x, scale=factor, bias=bias, **kw)
    fluid.layers.scale = unscaled


def halves_as_chunks(fluid, causal_lm):
    """A query's two maps are the first and the second HALF of the
    projection's columns (q.chunk(2)), where they are the two members of
    each pair of heads ([.., pairs, 2, hd])."""
    reshape, transpose = fluid.layers.reshape, fluid.layers.transpose

    def chunked(x, shape, **kw):
        if len(shape) == 6 and shape[4] == 2:   # [0, -1, kv, group, 2, hd]
            return transpose(reshape(x, shape=[0, -1, 2] + list(shape[2:4])
                                     + [shape[5]], **kw),
                             perm=[0, 1, 3, 4, 2, 5])
        return reshape(x, shape=shape, **kw)
    fluid.layers.reshape = chunked


def memory_after_gate(fluid, causal_lm):
    """The memory handed on is y * SiLU(z), the scan's output AFTER the
    layer's own gate, where the memory units read it before."""
    mamba = causal_lm.mamba

    def gated(x, c):
        out = mamba(x, c)
        if c["layer"] == c["memory_layer"]:
            y = c["handed_on"]["memory"]
            product = next(op for op in reversed(_block(fluid).ops)
                           if op.type == "elementwise_mul"
                           and y.name in op.all_input_vars())
            c["handed_on"]["memory"] = _block(fluid).var(
                product.output("Out")[0])
        return out
    causal_lm.mamba = gated


def cross_reads_layer_1(fluid, causal_lm):
    """The cross attention reads the keys and values of the FIRST attention
    layer (published layer 1, under its window), not layer 17's."""
    attention = causal_lm.differential_attention
    causal_lm.differential_attention = lambda x, pos, c: attention(
        x, pos, dict(c, kv_layer=1))


def _scan_with(fluid, change):
    scan = fluid.layers.selective_scan
    fluid.layers.selective_scan = lambda *args, **kw: scan(*change(
        list(args)), **kw)


def d_dropped(fluid, causal_lm):
    """The scan's output lacks its skip term D x."""
    def zero_d(args):
        args[5] = fluid.layers.scale(args[5], scale=0.0)
        return args
    _scan_with(fluid, zero_d)


def _bias_dropped(fluid, suffix):
    add = fluid.layers.elementwise_add

    def dropped(x, y, **kw):
        if str(getattr(y, "name", "")).endswith(suffix):
            y = fluid.layers.scale(y, scale=0.0)
        return add(x, y, **kw)
    fluid.layers.elementwise_add = dropped


def conv_bias_dropped(fluid, causal_lm):
    """SiLU(conv(u)) without the convolution's bias (the parameter stays)."""
    _bias_dropped(fluid, "conv.bias")


def dt_bias_dropped(fluid, causal_lm):
    """Delta = softplus(r W_Delta) without b_Delta (the parameter stays)."""
    _bias_dropped(fluid, "dt_bias")


def _later_readers_gradient_dropped(fluid, causal_lm, what):
    """Of the uses whose gradients the lowering sums into what one layer
    hands on (`what`: "memory", or "kv" for the shared keys), the first to
    arrive adds zeros: gradient ops run from the loss back, so that is the
    LATER layer's reader."""
    from paddle_tpu.core import lowering
    names = set()
    for name in ("mamba", "differential_attention"):
        def recorded(*args, builder=getattr(causal_lm, name)):
            out = builder(*args)
            found = args[-1]["handed_on"].get(what)
            for var in found if isinstance(found, tuple) else (found,):
                if var is not None:
                    names.add(var.name + "@GRAD")
            return out
        setattr(causal_lm, name, recorded)
    accumulate = lowering.Env.accumulate

    def dropped(env, name, value):
        if name in names and env.read_opt(name) is None:
            value = value * 0
        accumulate(env, name, value)
    lowering.Env.accumulate = dropped


def memory_readers_gradient_dropped(fluid, causal_lm):
    """The gradient that reaches the memory is its own layer's gate's
    alone: what the gated memory unit adds is dropped. The forward pass is
    healthy."""
    _later_readers_gradient_dropped(fluid, causal_lm, "memory")


def kv_readers_gradient_dropped(fluid, causal_lm):
    """The gradient that reaches the shared keys and values is their own
    layer's core's alone: what the cross attention adds is dropped. The
    forward pass is healthy."""
    _later_readers_gradient_dropped(fluid, causal_lm, "kv")


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "phi4_mini_flash.py")):
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


def gradient_witness(fluid, causal_lm):
    """No mutant: the healthy program and check; beside the float32
    reference, the same reference with bfloat16 matmuls as a witness."""
    import json
    import jax
    import numpy as np
    from benchmark import manifest
    load = manifest.load_module
    names = ("memory_grad", "shared_k_grad")

    def report(pair, a, b, scale):
        error = np.abs(a - b)
        order = np.argsort(error, axis=None)[::-1][:8]
        places = [list(map(int, np.unravel_index(i, b.shape))) for i in order]
        return {"pair": pair, "largest": float(error.max() / scale),
                "p999": float(np.quantile(error, 0.999) / scale),
                "mean": float(error.mean() / np.abs(b).mean()),
                "places": places,
                "there": [[float(a[tuple(p)]), float(b[tuple(p)])]
                          for p in places[:3]]}

    def load_and_wrap(path):
        mod = load(path)
        if not path.endswith(os.path.join("configs", "phi4_mini_flash.py")):
            return mod
        reference, check = mod.reference, mod.check

        def both(cfg, traffic, params, batch):
            want = reference(cfg, traffic, params, batch)
            highest = jax.default_matmul_precision
            jax.default_matmul_precision = lambda _: highest("bfloat16")
            try:    # after the first (it reads its loss): one at a time
                ids = batch["ids"] + (want["loss"] * 0).astype(
                    batch["ids"].dtype)
                low = reference(cfg, traffic, params, dict(batch, ids=ids))
            finally:
                jax.default_matmul_precision = highest
            return dict(want, **{"witness." + n: low[n] for n in names})

        def check_and_say(cfg, first, want, scalars):
            found = {}
            for n in names:
                ref = np.asarray(want[n], np.float32)
                got = np.asarray(first[n], np.float32).reshape(ref.shape)
                low = np.asarray(want["witness." + n], np.float32)
                scale = np.abs(ref).max()
                big = np.unravel_index(np.abs(ref).argmax(), ref.shape)
                found[n] = {
                    "largest_value": [float(scale), list(map(int, big))],
                    "pairs": [report("program-float32", got, ref, scale),
                              report("witness-float32", low, ref, scale),
                              report("program-witness", got, low, scale)]}
            print("bench: WITNESS " + json.dumps(found), flush=True)
            return check(cfg, first, want, scalars)
        mod.reference, mod.check = both, check_and_say
        return mod
    manifest.load_module = load_and_wrap


MUTANTS = {f.__name__: f for f in (
    window_off, window_off_by_one, lambda_init_at_cuts_index, subln_off,
    scale_off, halves_as_chunks, memory_after_gate, cross_reads_layer_1,
    d_dropped, conv_bias_dropped, dt_bias_dropped,
    memory_readers_gradient_dropped, kv_readers_gradient_dropped,
    reference_bf16_weights, reference_fp8_weights, gradient_witness)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_phi4_mini_flash.py <%s> <arguments of "
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
