"""Runs the Nemotron-3-Super cell with its model broken on purpose, to show
that `correct` can fail for what the cell measures.

    python benchmark/tests/mutant_nemotron_3_super.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. `causal_lm.resolve` is left alone: the
reference reads the configuration through it too. Every mutant's last line
has to say `"correct": false`; the configuration's .json has what the chip
gave.

Three of them change a tensor's width and fill it out so that the shapes
stay: `router_reads_latent` gives the router u four times side by side
(1024 -> 4096), `latent_up_dropped` adds the experts' sum four times side
by side in place of its product with W_up (which stays in the graph at a
weight of 0, so that its gradient exists), and `second_branch_added` builds
the second branch of an `M` layer from the mixer's own matrices.

The last two are no mutants of the program: `reference_bf16_weights` and
`reference_fp8_weights` run the healthy program against the reference with
its weights rounded to bfloat16 (which has to stay correct: it is the
precision the configuration states) and to float8 e4m3, scaled a tensor, the
nearest precision below (which has to fail a tolerance). `gradients_kept`
is no mutant either: the healthy program and the healthy check, and the five
gradient fetches beside the reference's written to
`chiprun_out/nemotron_gradients_<loss>.npz`.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _block(fluid):
    return fluid.default_main_program().global_block()


def _rule_with(op_type, change):
    """The registered lowering of `op_type` behind `change(ins, attrs) ->
    (ins, attrs)`."""
    from paddle_tpu.core import registry
    rule = registry.get(op_type)
    lower = rule.lower

    def changed(ctx, ins, attrs):
        return lower(ctx, *change(dict(ins), dict(attrs)))
    rule.lower = changed


def _layer_with(causal_lm, **changed):
    layer = causal_lm._layer
    causal_lm._layer = lambda c, i: dict(layer(c, i), **changed)


def relu_not_squared(fluid, causal_lm, moe):
    """act = ReLU, not ReLU^2: in the routed experts and in the shared
    one."""
    import jax
    import jax.numpy as jnp
    moe._ungated_relu2 = lambda up: jax.nn.relu(
        up.astype(jnp.float32)).astype(up.dtype)
    fluid.layers.square = lambda x, **kw: x


def experts_gated_silu(fluid, causal_lm, moe):
    """A routed expert is SiLU(u W1) * (u W1) W2, the gated unit of the
    other models' experts with the one matrix there is on both sides."""
    import jax
    import jax.numpy as jnp

    def gated(up):
        up32 = up.astype(jnp.float32)
        return (jax.nn.silu(up32) * up32).astype(up.dtype)
    moe._ungated_relu2 = gated


def router_reads_latent(fluid, causal_lm, moe):
    """The router reads u, the experts' input (four times side by side, to
    the router's width), where the model routes from the hidden state."""
    import jax.numpy as jnp

    def latent(ins, attrs):
        x = ins["X"][0]
        times = ins["RouterX"][0].shape[-1] // x.shape[-1]
        ins["RouterX"] = [jnp.concatenate([x] * times, axis=-1)]
        return ins, attrs
    _rule_with("moe_ffn", latent)


def latent_up_dropped(fluid, causal_lm, moe):
    """The experts' sum enters the stream as it is (four times side by
    side), not through W_up."""
    linear, layers = causal_lm._linear, fluid.layers

    def dropped(x, size, c, role, bias=False):
        out = linear(x, size, c, role, bias)
        if role != "latent_up":
            return out
        return layers.scale(out, scale=0.0) + layers.concat(
            [x] * (size // int(x.shape[-1])), axis=2)
    causal_lm._linear = dropped


def scaling_factor_1(fluid, causal_lm, moe):
    """routed_scaling_factor 1: the routed experts at a fifth of their
    weight beside the shared expert."""
    def unscaled(ins, attrs):
        attrs.pop("scale")
        return ins, attrs
    _rule_with("moe_ffn", unscaled)


def bias_in_weights(fluid, causal_lm, moe):
    """The correction bias carries weight: the chosen experts are weighed
    by s + b, renormalised, where the model weighs them by s."""
    import jax.numpy as jnp
    route = moe._route

    def biased(logits, top_k, norm_topk_prob, scoring, expert_bias, scale,
               **kw):
        probs, lse, _, expert = route(logits, top_k, norm_topk_prob, scoring,
                                      expert_bias, scale, **kw)
        gate = jnp.take_along_axis(probs + expert_bias, expert, axis=-1)
        gate = gate / (gate.sum(-1, keepdims=True) + kw.get("norm_eps", 0.0))
        return probs, lse, gate * scale, expert
    moe._route = biased


def renorm_dropped(fluid, causal_lm, moe):
    """The chosen scores weigh the experts as they are, not divided by
    their sum (norm_topk_prob false): 20 times the weight."""
    def plain(ins, attrs):
        attrs["norm_topk_prob"] = False
        return ins, attrs
    _rule_with("moe_ffn", plain)


def top_k_21(fluid, causal_lm, moe):
    """A token goes to 21 experts, not 22."""
    def fewer(ins, attrs):
        attrs["top_k"] = attrs["top_k"] - 1
        return ins, attrs
    _rule_with("moe_ffn", fewer)


def shared_expert_dropped(fluid, causal_lm, moe):
    """An `E` layer adds its routed experts alone: the shared expert's part
    at a weight of 0."""
    mlp = causal_lm._relu2_mlp

    def dropped(x, width, c, role=""):
        out = mlp(x, width, c, role)
        return fluid.layers.scale(out, scale=0.0) \
            if role == "shared_expert." else out
    causal_lm._relu2_mlp = dropped


def second_branch_added(fluid, causal_lm, moe):
    """An `M` layer is given an FFN too, beside its mixer and reading the
    same normed state: relu(x W_in[:, :d_i])^2 W_out, from the mixer's own
    two matrices, so that the parameters stay as they are."""
    mixer, layers = causal_lm.mamba2, fluid.layers

    def with_ffn(x, c):
        out = mixer(x, c)
        w_in, w_out = (_block(fluid).var("layer_%d.%s" % (c["layer"], role))
                       for role in ("w_in", "w_out"))
        di = int(w_out.shape[0])
        hidden = layers.matmul(x, layers.crop(
            w_in, shape=[int(w_in.shape[0]), di]))
        return out + layers.matmul(layers.square(layers.relu(hidden)), w_out)
    causal_lm.mamba2 = with_ffn


def pattern_shifted(fluid, causal_lm, moe):
    """`E` and `M` exchanged: the first two layers run in the other order
    (the expert layer on the embedding, the mixer behind it), each with its
    own parameters. The forward graph is rewired before the backward pass
    is appended: layer 1's ops move before layer 0's and read what layer 0
    read, layer 0 reads what layer 1 gives, and what read layer 1 reads
    layer 0."""
    build = causal_lm.causal_lm

    def shifted(cfg, seq_len, **kw):
        result = build(cfg, seq_len, **kw)
        block = _block(fluid)
        ops = block.ops

        def start(i):           # the op that reads layer i's norm
            return next(k for k, op in enumerate(ops)
                        if op.type == "rms_norm"
                        and op.input("Scale")[0] == "layer_%d.norm" % i)

        s0, s1, s2 = start(0), start(1), start(2)
        x0, x1, x2 = (ops[s].input("X")[0] for s in (s0, s1, s2))

        def reading(run, was, now):
            for op in run:
                for names in op.inputs.values():
                    names[:] = [now if n == was else n for n in names]

        first, second, rest = ops[s1:s2], ops[s0:s1], ops[s2:]
        reading(first, x1, x0)      # the expert layer on the embedding
        reading(second, x0, x2)     # the mixer on what the experts gave
        reading(rest, x2, x1)       # the rest on what the mixer gave
        ops[s0:s2] = first + second
        return result
    causal_lm.causal_lm = shifted


def rotary_on(fluid, causal_lm, moe):
    """The attention layer turns q and k by rotary positions (theta 10000),
    where the model has no positional term."""
    _layer_with(causal_lm, rope_theta=10000.0)


def score_scale_1(fluid, causal_lm, moe):
    """The scores are not divided by sqrt(128)."""
    _layer_with(causal_lm, attention_scale=1.0)


def norm_before_gate(fluid, causal_lm, moe):
    """RMSNorm(y) * SiLU(z), the order layers.rms_norm(gate=) has, where the
    mixer gates first and norms the product (a group at a time where the
    mixer has groups: the product then reaches the norm through a
    reshape)."""
    norm, layers = fluid.layers.rms_norm, fluid.layers

    def made(name):
        return next(op for op in reversed(_block(fluid).ops)
                    if name in op.output("Out"))

    def norm_first(x, param_attr=None, **kw):
        if not str(getattr(param_attr, "name", "")).endswith("gated_norm"):
            return norm(x, param_attr=param_attr, **kw)
        product, grouped = made(x.name), None
        if product.type.startswith("reshape"):
            grouped = [0, -1] + [int(n) for n in x.shape[2:]]
            product = made(product.input("X")[0])
        y, gate = (_block(fluid).var(product.input(slot)[0])
                   for slot in ("X", "Y"))
        if grouped:
            y, gate = (layers.reshape(t, shape=grouped) for t in (y, gate))
        return norm(y, param_attr=param_attr, **kw) * gate
    fluid.layers.rms_norm = norm_first


def experts_9_to_16_held(fluid, causal_lm, moe):
    """The chip computes the assignments of the router's columns 8 .. 15
    (with the weights it holds), the wrong share of the 512: chip 1's."""
    def next_share(ins, attrs):
        attrs["first_expert"] = ins["WUp"][0].shape[0]
        return ins, attrs
    _rule_with("moe_ffn", next_share)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "nemotron_3_super.py")):
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
        scale = jnp.maximum(jnp.abs(p).max(), 1e-30) / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


def gradients_kept(fluid, causal_lm, moe):
    """No mutant: the healthy program and check; the five gradient fetches,
    the loads and the reference's margins are written to chiprun_out/ as
    they are compared, for whoever asks which rows an error sits in."""
    import numpy as np
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "nemotron_3_super.py")):
            check = mod.check

            def check_and_keep(cfg, first, want, scalars):
                kept = {}
                for name in list(mod.GRADIENTS) + ["expert_load"]:
                    kept["program." + name] = np.asarray(first[name])
                    kept["reference." + name] = np.asarray(want[name])
                for name in ("router_margin", "experts_margin"):
                    kept["reference." + name] = np.asarray(want[name])
                os.makedirs("chiprun_out", exist_ok=True)
                np.savez(os.path.join(
                    "chiprun_out", "nemotron_gradients_%.6f.npz"
                    % float(np.ravel(first["loss"])[0])), **kept)
                return check(cfg, first, want, scalars)
            mod.check = check_and_keep
        return mod
    manifest.load_module = load_and_wrap


MUTANTS = {f.__name__: f for f in (
    relu_not_squared, experts_gated_silu, router_reads_latent,
    latent_up_dropped, scaling_factor_1, bias_in_weights, renorm_dropped,
    top_k_21, shared_expert_dropped, second_branch_added, pattern_shifted,
    rotary_on, score_scale_1, norm_before_gate, experts_9_to_16_held,
    reference_bf16_weights, reference_fp8_weights, gradients_kept)}
# those whose last line has to say `"correct": false`
HAVE_TO_FAIL = tuple(name for name in MUTANTS if name not in (
    "reference_bf16_weights", "gradients_kept"))


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_nemotron_3_super.py <%s> <arguments of "
              "benchmark/run.py>" % "|".join(MUTANTS), file=sys.stderr)
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
