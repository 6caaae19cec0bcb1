"""Runs the looped-decoder cell with its model broken on purpose, to show
that `correct` can fail for what the cell measures.

    python benchmark/tests/mutant_ouro.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. Every mutant's last line has to say `"correct":
false`; the configuration's .json has what the chip gave. The two
`reference_*_weights` round the REFERENCE's weights instead: bfloat16, the
precision the configuration states, stays correct; float8 e4m3, the nearest
below, has to fail a tolerance.
"""
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _wrap_module(change):
    """`change(mod)` on the configuration module configs/ouro.py as it is
    loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_change(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "ouro.py")):
            change(mod)
        return mod
    manifest.load_module = load_and_change


def _build_with(**keys):
    """The program built from the configuration with `keys` changed; the
    reference reads the configuration as it is."""
    def change(mod):
        build = mod.build
        mod.build = lambda fluid, cfg, traffic: build(
            fluid, dict(cfg, **keys), traffic)
    _wrap_module(change)


def three_passes(causal_lm, layers):
    """The stack runs three times, not four: the fourth pass's logits are
    the third's again and no share of p is left for it."""
    def change(mod):
        build = mod.build

        def short(fluid, cfg, traffic):
            passes, t = cfg["total_ut_steps"], traffic["seq_len"]
            out = build(fluid, dict(cfg, total_ut_steps=passes - 1), traffic)
            last = fluid.layers.crop(out["logits"], shape=[-1, t, -1],
                                     offsets=[0, (passes - 2) * t, 0])
            nothing = fluid.layers.scale(fluid.layers.crop(
                out["exit_p"], shape=[-1, 1, -1]), scale=0.0)
            return dict(
                out, logits=fluid.layers.concat([out["logits"], last], axis=1),
                exit_p=fluid.layers.concat([out["exit_p"], nothing], axis=1))
        mod.build = short
    _wrap_module(change)


def pass_two_on_other_weights(causal_lm, layers):
    """The second pass multiplies by other weights than the first's: every
    matrix of a layer with its columns moved on by one. (A copy 2 % larger
    would pass: the norm on the way out of every branch takes a scale
    away.)"""
    import jax.numpy as jnp
    from paddle_tpu.core import registry
    rule = registry.get("mul")
    lower = rule.lower

    def perturbed(ctx, ins, attrs):
        trips = getattr(ctx, "_loop_iters", None)     # inside a loop op
        if trips:
            second = (trips[-1] == 1)
            ins = dict(ins, Y=[jnp.where(second, jnp.roll(y, 1, axis=-1), y)
                               for y in ins["Y"]])
        return lower(ctx, ins, attrs)
    rule.lower = perturbed


def no_norm_between_passes(causal_lm, layers):
    """The final norm is the head's alone: a pass starts from the state
    before it (the paper's composition, not the released model's)."""
    update = layers.StaticRNN.update_memory

    def before_the_norm(self, ex_mem, new_mem):
        block = self._step_block
        norm = next(op for op in reversed(block.ops)
                    if new_mem.name in op.outputs["Y"])
        return update(self, ex_mem, block.var(norm.inputs["X"][0]))
    layers.StaticRNN.update_memory = before_the_norm


def sandwich_off(causal_lm, layers):
    """The two norms on the way out of a layer's branches are dropped (their
    weights stay in the program, multiplied by zero)."""
    norm = causal_lm._norm

    def only_going_in(x, c, role=None):
        y = norm(x, c, role)
        return x + y * 0.0 if role in ("mixer_out_norm", "ffn_out_norm") \
            else y
    causal_lm._norm = only_going_in


def last_pass_gated(causal_lm, layers):
    """p_4 = lambda_4 prod_(j<4) (1 - lambda_j), like the passes before it,
    not the remainder: the shares no longer sum to 1."""
    exits = causal_lm.exit_distribution

    def gated(states, c):
        p, log_p = exits(list(states) + [states[-1]], c)
        return tuple(layers.crop(x, shape=[-1, len(states), -1])
                     for x in (p, log_p))
    causal_lm.exit_distribution = gated


def gate_off(causal_lm, layers):
    """Every pass has the same share of the loss, p uniform (the gate's
    weights stay, with a zero gradient)."""
    exits = causal_lm.exit_distribution

    def uniform(states, c):
        p, log_p = exits(states, c)
        share = 1.0 / len(states)
        return (layers.scale(p, scale=0.0, bias=share),
                layers.scale(log_p, scale=0.0, bias=math.log(share)))
    causal_lm.exit_distribution = uniform


def entropy_term_off(causal_lm, layers):
    """beta = 0: the loss is the expected cross-entropy alone."""
    _build_with(exit_entropy_coef=0.0)


def rotary_off(causal_lm, layers):
    """Queries and keys are not rotated: attention sees no position."""
    layers.rotary_embedding = lambda x, pos, **kw: x


def _reference_with(round_weights):
    def change(mod):
        reference = mod.reference
        mod.reference = lambda cfg, traffic, params, batch: reference(
            cfg, traffic, [round_weights(p) for p in params], batch)
    _wrap_module(change)


def reference_bf16_weights(causal_lm, layers):
    """The reference with its weights rounded to bfloat16: stays correct."""
    import jax.numpy as jnp
    _reference_with(lambda p: p.astype(jnp.bfloat16).astype(jnp.float32))


def reference_fp8_weights(causal_lm, layers):
    """The reference with its weights rounded to float8 e4m3, scaled a
    tensor to the format's range: has to fail a tolerance."""
    import jax.numpy as jnp

    def fp8(p):                 # the gate's bias is all zeros: no scale
        scale = jnp.maximum(jnp.abs(p).max(), 1e-30) / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


MUTANTS = {f.__name__: f for f in (
    three_passes, pass_two_on_other_weights, no_norm_between_passes,
    sandwich_off, last_pass_gated, gate_off, entropy_term_off, rotary_off,
    reference_bf16_weights, reference_fp8_weights)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_ouro.py <%s> <arguments of benchmark/run.py>"
              % "|".join(MUTANTS), file=sys.stderr)
        return 1
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    MUTANTS[argv[0]](causal_lm, fluid.layers)
    print("bench: MUTANT %s: %s" % (argv[0], MUTANTS[argv[0]].__doc__),
          flush=True)
    from benchmark import run
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
