"""Runs the Ling-3.0-flash cell with its model broken on purpose, to show
that `correct` can fail for what the cell measures.

    python benchmark/tests/mutant_ling_3_0.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. Every mutant's last line has to say `"correct":
false`; the configuration's .json has what the chip gave. One cannot leave
the parameters as they are: `latent_every_4th` builds latent attention on
other layers than the configuration says, whose parameters are others, and
the reference refuses to read them (the run ends in its error, no line).

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


def _delta_rule_with(change):
    """ops/kda_kernels.kda_delta_rule behind `change(q, k, v, g, beta) ->
    the same five` (the op's rule looks the function up when it lowers)."""
    from paddle_tpu.ops import kda_kernels
    rule = kda_kernels.kda_delta_rule
    kda_kernels.kda_delta_rule = lambda q, k, v, g, beta, **kw: rule(
        *change(q, k, v, g, beta), **kw)


def _sigmoid_is_one(layers, build, which):
    """`build` with its `which`-th call of layers.sigmoid (from 1; None:
    every call) giving 1 while it runs; the gate's weights stay, with a
    zero gradient."""
    def patched(*args, **kwargs):
        sigmoid, calls = layers.sigmoid, [0]

        def counted(x):
            calls[0] += 1
            return layers.scale(x, scale=0.0, bias=1.0) \
                if which in (None, calls[0]) else sigmoid(x)
        layers.sigmoid = counted
        try:
            return build(*args, **kwargs)
        finally:
            layers.sigmoid = sigmoid
    return patched


def scalar_decay(moe, causal_lm, layers):
    """A head's mean log decay on every one of its channels: Qwen3-Next's
    rule, a decay a head, under Ling's name."""
    import jax.numpy as jnp
    _delta_rule_with(lambda q, k, v, g, beta: (
        q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta))


def gate_unbounded(moe, causal_lm, layers):
    """The decay's gate without its bound: -softplus(z) where the layer has
    kda_lower_bound x sigmoid(z) (z recovered from the bounded gate). A
    decay past -5.9 a token overflows the chunked form's exponentials:
    `finite` may fail before `reference` does."""
    import jax
    import jax.numpy as jnp

    def unbounded(q, k, v, g, beta):
        s = jnp.clip(g / -5.0, 1e-7, 1.0 - 1e-7)
        return q, k, v, -jax.nn.softplus(jnp.log(s) - jnp.log1p(-s)), beta
    _delta_rule_with(unbounded)


def decay_bf16(moe, causal_lm, layers):
    """The log decay and its running sums inside a chunk in bfloat16, where
    the module states float32: g is rounded, summed token by token with a
    bfloat16 accumulator, and the rule is given the differences of those
    sums (its own float32 running sums are then the rounded ones)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kernel_config
    chunk = kernel_config.DEFAULT_TILES["kda"]["chunk"]

    def rounded(q, k, v, g, beta):
        b, t = g.shape[:2]
        n = -(-t // chunk)
        pad = jnp.pad(g, [(0, 0), (0, n * chunk - t), (0, 0), (0, 0)])
        steps = jnp.moveaxis(pad.reshape((b, n, chunk) + g.shape[2:]), 2,
                             0).astype(jnp.bfloat16)
        _, sums = jax.lax.scan(
            lambda c, x: ((c + x).astype(jnp.bfloat16),) * 2,
            jnp.zeros(steps.shape[1:], jnp.bfloat16), steps)
        sums = sums.astype(jnp.float32)
        back = jnp.concatenate([sums[:1], sums[1:] - sums[:-1]])
        back = jnp.moveaxis(back, 0, 2).reshape(pad.shape)[:, :t]
        return q, k, v, back, beta
    _delta_rule_with(rounded)


def beta_off(moe, causal_lm, layers):
    """Every token writes at full strength: beta = 1."""
    import jax.numpy as jnp
    _delta_rule_with(lambda q, k, v, g, beta: (q, k, v, g,
                                               jnp.ones_like(beta)))


def conv_silu_off(moe, causal_lm, layers):
    """The three convolutions' SiLU is dropped (linear_silu false)."""
    def linear(ins, attrs):
        attrs.pop("activation", None)
        return ins, attrs
    _rule_with("causal_conv1d", linear)


def kda_gate_off(moe, causal_lm, layers):
    """A KDA layer's normed output reaches W_o ungated: the third sigmoid
    of the mixer (after the decay's and beta's) gives 1."""
    causal_lm.kda = _sigmoid_is_one(layers, causal_lm.kda, 3)


def head_norm_after_gate(moe, causal_lm, layers):
    """N_head(o * gate) where the layer has N_head(o) * gate. The norm's
    weight is made where it always was (by a norm whose result nothing
    reads) and asked for again, by name, behind the gate."""
    kda, linear = causal_lm.kda, causal_lm._linear

    def patched(x, c):
        norm, kept = layers.rms_norm, {}

        def later(o, **kw):
            kept.update(kw)
            norm(o, **kw)
            return o

        def wo(t, size, cl, role, *more):
            if role == "wo":
                heads = cl["num_attention_heads"]
                t = layers.reshape(norm(layers.reshape(
                    t, shape=[0, -1, heads, cl["head_dim"]]), **kept),
                    shape=[0, -1, heads * cl["head_dim"]])
            return linear(t, size, cl, role, *more)
        layers.rms_norm, causal_lm._linear = later, wo
        try:
            return kda(x, c)
        finally:
            layers.rms_norm, causal_lm._linear = norm, linear
    causal_lm.kda = patched


def no_group_limit(moe, causal_lm, layers):
    """The router takes its top 8 over all 512 experts."""
    route = moe._route
    moe._route = lambda *a, groups=None, **kw: route(*a, **kw)


def group_by_top1(moe, causal_lm, layers):
    """A group's score is its one largest entry, not the sum of its two."""
    import jax
    import jax.numpy as jnp

    def limited(choice, groups):
        n_group, topk_group = groups
        n, e = choice.shape
        by_group = choice.reshape(n, n_group, e // n_group)
        _, best = jax.lax.top_k(by_group.max(-1), topk_group)
        kept = (best[:, :, None] == jnp.arange(n_group)).any(1)
        return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(n, e)
    moe._group_limited = limited


def mla_gate_off(moe, causal_lm, layers):
    """The latent layer's context reaches W_o without its gate a head."""
    causal_lm.latent_attention = _sigmoid_is_one(
        layers, causal_lm.latent_attention, None)


def latent_every_4th(moe, causal_lm, layers):
    """Latent attention on every fourth published layer (described_as's
    "3 KDA : 1 MLA") and not every sixth: other layers, other parameters,
    and the reference refuses to read them."""
    hybrid = causal_lm._bailing_hybrid
    causal_lm._bailing_hybrid = lambda c, published: hybrid(
        dict.__setitem__(c, "layer_group_size", 4) or c, published)


def rotary_off(moe, causal_lm, layers):
    """No rotary positions on the latent layer's q_rope and k_r."""
    layers.rotary_embedding = lambda t, pos, **kw: t


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "ling_3_0.py")):
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
    scalar_decay, gate_unbounded, decay_bf16, beta_off, conv_silu_off,
    kda_gate_off, head_norm_after_gate, no_group_limit, group_by_top1,
    mla_gate_off, latent_every_4th, rotary_off, reference_bf16_weights,
    reference_fp8_weights)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_ling_3_0.py <%s> <arguments of "
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
