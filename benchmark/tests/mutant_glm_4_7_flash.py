"""Runs the GLM-4.7-Flash cell with its model broken on purpose, to show that
`correct` can fail for what the cell measures.

    python benchmark/tests/mutant_glm_4_7_flash.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. Every mutant's last line has to say `"correct":
false`; the configuration's .json has what the chip gave.

`module_head_gradient_dropped` and `module_lookup_gradient_dropped` leave
the forward pass alone and drop one of the two uses of a shared parameter
from its gradient. The last two are no mutants of the program: `reference_bf16_weights` and
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


def _block(fluid):
    return fluid.default_main_program().global_block()


def _nth_call(owner, name, n, change):
    """`owner.name` behind `change(original, *args, **kw)` on its n-th call
    (from 0) of a build; every other call passes through."""
    original, calls = getattr(owner, name), []

    def counted(*args, **kw):
        calls.append(1)
        if len(calls) - 1 == n:
            return change(original, *args, **kw)
        return original(*args, **kw)
    setattr(owner, name, counted)


def module_embeds_inputs(fluid, causal_lm):
    """The module embeds t_i, the token at its own position, where it has
    to embed t_(i+1): the second lookup reads `ids`."""
    _nth_call(fluid.layers, "embedding", 1, lambda embed, tokens, **kw:
              embed(_block(fluid).var("ids"), **kw))


def second_labels_are_the_first(fluid, causal_lm):
    """The module's loss is taken against t_(i+1), the trunk's labels,
    where its targets are t_(i+2)."""
    _nth_call(fluid.layers, "softmax_with_cross_entropy", 1,
              lambda xent, logits, label, **kw: xent(
                  logits=logits, label=fluid.layers.reshape(
                      _block(fluid).var("labels"), shape=[-1, 1]), **kw))


def _norm_off(causal_lm, which):
    norm = causal_lm._norm

    def skipped(x, c, role=None):
        out = norm(x, c, role)
        return x if (role or c.get("role")) == which else out
    causal_lm._norm = skipped


def hnorm_off(fluid, causal_lm):
    """The trunk's state enters the module's projection without its norm
    N_h (the weight stays, unused)."""
    _norm_off(causal_lm, "hnorm")


def concat_swapped(fluid, causal_lm):
    """W_eh reads [N_h(state); N_e(embedding)], the report's order, where
    the weights were made for the embedding first."""
    concat = fluid.layers.concat

    def swapped(inputs, axis=0, **kw):
        if len(inputs) == 2 and axis == 2:      # the module's, alone
            inputs = inputs[::-1]
        return concat(inputs, axis=axis, **kw)
    fluid.layers.concat = swapped


def lambda_1(fluid, causal_lm):
    """L = L_main + L_mtp: the module's term at weight 1 for lambda."""
    build = causal_lm.causal_lm
    causal_lm.causal_lm = lambda cfg, *args, **kw: build(
        dict(cfg, mtp_loss_weight=1.0), *args, **kw)


def head_not_shared(fluid, causal_lm):
    """The module's logits come from a head that is not the trunk's: its
    state against the embedding, transposed (a tied head of its own)."""
    linear = causal_lm._linear
    seen = []

    def own(x, size, c, role):
        if role == "head":
            seen.append(1)
            if len(seen) == 2:
                return fluid.layers.matmul(
                    x, _block(fluid).var("embedding"), transpose_y=True)
        return linear(x, size, c, role)
    causal_lm._linear = own


def module_ffn_dense(fluid, causal_lm):
    """The module's FFN is a dense SwiGLU alone (its shared expert): what
    its routed experts add is dropped."""
    from paddle_tpu.core import lowering, registry
    rule = registry.get("moe_ffn")
    lower = rule.lower

    def dropped(ctx, ins, attrs):
        outs = lower(ctx, ins, attrs)
        if attrs.get(lowering.ROLE_ATTR):       # the module's layer alone
            outs = dict(outs, Out=[outs["Out"][0] * 0])
        return outs
    rule.lower = dropped


def _rule_with(op_type, change):
    """The registered lowering of `op_type` behind `change(ins, attrs) ->
    (ins, attrs)`."""
    from paddle_tpu.core import registry
    rule = registry.get(op_type)
    lower = rule.lower

    def changed(ctx, ins, attrs):
        return lower(ctx, *change(ins, dict(attrs)))
    rule.lower = changed


def scale_1(fluid, causal_lm):
    """routed_scaling_factor 1 for 1.8: the routed experts' output at 5/9
    of its weight beside the shared expert's."""
    def unscaled(ins, attrs):
        attrs.pop("scale")
        return ins, attrs
    _rule_with("moe_ffn", unscaled)


def kvb_columns_256_192(fluid, causal_lm):
    """A head's 448 columns of W_kvb are cut [v (256); k_nope (192)], where
    the checkpoint has them [k_nope (192); v (256)]."""
    columns, calls = causal_lm._head_columns, []

    def cut(w, heads, widths):
        calls.append(1)
        if len(calls) % 2:          # a layer's first call: W_qb
            return columns(w, heads, widths)
        return columns(w, heads, widths[::-1])[::-1]
    causal_lm._head_columns = cut


def rope_on_nope(fluid, causal_lm):
    """Rotary turns the first 64 channels of the part without position
    too, of q and k."""
    core = fluid.layers.fused_attention

    def turned(q, k, v, q_rope=None, k_rope=None, **kw):
        pos = _block(fluid).var("pos")
        q, k = (fluid.layers.rotary_embedding(
            t, pos, base=1000000.0, rotary_dim=int(q_rope.shape[-1]),
            layout="interleaved") for t in (q, k))
        return core(q, k, v, q_rope=q_rope, k_rope=k_rope, **kw)
    fluid.layers.fused_attention = turned


def _first_use_dropped(gradient):
    """Of the uses whose gradients the lowering sums into `gradient`, the
    first to arrive, the module's (gradient ops run from the loss back, and
    the module lies behind the trunk), adds zeros."""
    from paddle_tpu.core import lowering
    accumulate = lowering.Env.accumulate

    def dropped(env, name, value):
        if name == gradient and env.read_opt(name) is None:
            value = value * 0
        accumulate(env, name, value)
    lowering.Env.accumulate = dropped


def module_head_gradient_dropped(fluid, causal_lm):
    """The head's gradient is its matmul in the trunk's pass alone: what
    the module's pass of the ONE head parameter adds is dropped. The forward
    pass is healthy."""
    _first_use_dropped("head@GRAD")


def module_lookup_gradient_dropped(fluid, causal_lm):
    """The embedding's gradient is the scatter-add of the inputs' lookup
    alone: the rows the module's lookup of the next tokens adds to the ONE
    table are dropped. The forward pass is healthy."""
    _first_use_dropped("embedding@GRAD")


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "glm_4_7_flash.py")):
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


MUTANTS = {f.__name__: f for f in (
    module_embeds_inputs, second_labels_are_the_first, hnorm_off,
    concat_swapped, lambda_1, head_not_shared, module_ffn_dense, scale_1,
    kvb_columns_256_192, rope_on_nope, module_head_gradient_dropped,
    module_lookup_gradient_dropped, reference_bf16_weights,
    reference_fp8_weights)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_glm_4_7_flash.py <%s> <arguments of "
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
