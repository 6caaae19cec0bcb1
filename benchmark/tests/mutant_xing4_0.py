"""Runs the Xing4.0 cell with its model broken on purpose, to show that
`correct` can fail for what the cell measures.

    python benchmark/tests/mutant_xing4_0.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. Every mutant's last line has to say `"correct":
false`; the configuration's .json has what the chip gave. Those of the
hyper-connections act on the kernels' path (ops/mhc_kernels.py), which a
TPU takes and the CPU under PADDLE_TPU_PALLAS=mhc.

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


def _table_with(causal_lm, change):
    table = causal_lm.yarn_table

    def changed(scaling, theta, rotary_dim, head_dim):
        return change(*table(scaling, theta, rotary_dim, head_dim),
                      head_dim=head_dim)
    causal_lm.yarn_table = changed


def yarn_off(fluid, causal_lm, moe, mhc):
    """Rotary turns by the plain table theta^(-2i/64), not YaRN's."""
    _table_with(causal_lm, lambda f, ts, s, head_dim: (None, ts, s))


def mscale_off(fluid, causal_lm, moe, mhc):
    """The scores' scale is 192^(-1/2), without YaRN's m^2."""
    _table_with(causal_lm, lambda f, ts, s, head_dim: (f, ts,
                                                       head_dim ** -0.5))


def _core_with(fluid, change):
    """layers.fused_attention behind `change(q, k, v, q_rope, k_rope) ->
    the same five`, where the builder calls it."""
    core = fluid.layers.fused_attention

    def changed(q, k, v, q_rope=None, k_rope=None, **kw):
        q, k, v, q_rope, k_rope = change(q, k, v, q_rope, k_rope)
        return core(q, k, v, q_rope=q_rope, k_rope=k_rope, **kw)
    fluid.layers.fused_attention = changed


def rope_on_nope(fluid, causal_lm, moe, mhc):
    """Rotary turns the first 64 channels of the part without position
    too, of q and k (by the plain table and half-split pairs: the op as the
    other configurations run it on a part of a head)."""
    def turned(q, k, v, q_rope, k_rope):
        pos = fluid.default_main_program().global_block().var("pos")
        q, k = (fluid.layers.rotary_embedding(
            t, pos, base=10000.0, rotary_dim=int(q_rope.shape[-1]))
            for t in (q, k))
        return q, k, v, q_rope, k_rope
    _core_with(fluid, turned)


def rope_half_split(fluid, causal_lm, moe, mhc):
    """Rotary pairs channel i with i + 32, not 2i with 2i + 1."""
    rotary = fluid.layers.rotary_embedding
    fluid.layers.rotary_embedding = lambda x, pos, **kw: rotary(
        x, pos, **dict(kw, layout="half"))


def k_rope_per_head(fluid, causal_lm, moe, mhc):
    """Head h reads a rotary key of its own, the shared one with its
    channels moved on by h (head 0's is the shared one): q_rope . roll(
    k_rope, h) = roll(q_rope, -h) . k_rope, so the queries are moved."""
    import jax.numpy as jnp

    def moved(ins, attrs):
        q_rope = ins["QRope"][0]
        return dict(ins, QRope=[jnp.stack(
            [jnp.roll(q_rope[:, :, h], -h, -1)
             for h in range(q_rope.shape[2])], 2)]), attrs
    _rule_with("fused_attention", moved)


def _norm_off(causal_lm, which):
    norm = causal_lm._norm

    def skipped(x, c, role=None):
        out = norm(x, c, role)
        return x if (role or c.get("role")) == which else out
    causal_lm._norm = skipped


def kv_norm_off(fluid, causal_lm, moe, mhc):
    """The compressed kv reaches its up-projection without its norm (the
    weight stays, unused)."""
    _norm_off(causal_lm, "kv_a_norm")


def q_norm_off(fluid, causal_lm, moe, mhc):
    """The compressed q reaches its up-projection without its norm."""
    _norm_off(causal_lm, "q_a_norm")


def v_from_k_nope(fluid, causal_lm, moe, mhc):
    """A head's value is its key's part without position (the other half of
    kv's 256 columns)."""
    _rule_with("fused_attention", lambda ins, attrs: (
        dict(ins, V=[ins["K"][0]]), attrs))


def sinkhorn_off(fluid, causal_lm, moe, mhc):
    """H_res = exp(clip(Ht_res)), no Sinkhorn step."""
    _rule_with("mhc_pre", lambda ins, attrs: (
        ins, dict(attrs, sinkhorn_iters=0)))


def sinkhorn_rows_only(fluid, causal_lm, moe, mhc):
    """Every Sinkhorn step divides the rows by their sums and leaves the
    columns alone."""
    import jax.numpy as jnp
    normalise, plain = mhc._normalise, mhc.sinkhorn

    def rows_only(m, eps, rows):
        if rows:
            return normalise(m, eps, rows)
        return m, [jnp.ones_like(m[0])] * int(len(m) ** 0.5)

    def plain_rows_only(a, iters, eps):
        m = jnp.exp(a)
        for _ in range(iters):
            m = m / (m.sum(1) + eps)[:, None]
        return m
    mhc._normalise, mhc.sinkhorn = rows_only, plain_rows_only


def _coef_with(change):
    """mhc_post behind `change(coef [.., 128]) -> coef`."""
    _rule_with("mhc_post", lambda ins, attrs: (
        dict(ins, Coef=[change(ins["Coef"][0], attrs["streams"])]), attrs))


def h_res_transposed(fluid, causal_lm, moe, mhc):
    """The streams are mixed by H_res transposed: X'[i] = sum_j H_res[j, i]
    X[j]."""
    def transposed(coef, n):
        res = coef[..., 2 * n:2 * n + n * n].reshape(coef.shape[:-1] + (n, n))
        return coef.at[..., 2 * n:2 * n + n * n].set(
            res.swapaxes(-1, -2).reshape(coef.shape[:-1] + (n * n,)))
    _coef_with(transposed)


def h_post_unscaled(fluid, causal_lm, moe, mhc):
    """H_post = sigmoid, without the factor 2."""
    _coef_with(lambda coef, n: coef.at[..., n:2 * n].multiply(0.5))


def h_pre_softmax(fluid, causal_lm, moe, mhc):
    """H_pre = softmax of Ht_pre over the four streams (the unconstrained
    hyper-connections' normalisation), not a sigmoid a stream."""
    import jax.numpy as jnp
    from jax import lax

    def softmax(ht):
        lane = lax.broadcasted_iota(jnp.int32, ht.shape, 1)
        e = jnp.where(lane < 4, jnp.exp(ht - jnp.max(
            jnp.where(lane < 4, ht, -1e30), -1, keepdims=True)), 0.0)
        return e / jnp.sum(e, -1, keepdims=True)
    mhc._h_pre = softmax


def coeffs_static(fluid, causal_lm, moe, mhc):
    """alpha = 0: the coefficients are their biases, the same for every
    token."""
    _rule_with("mhc_pre", lambda ins, attrs: (
        dict(ins, Alpha=[ins["Alpha"][0] * 0.0]), attrs))


def readout_mean(fluid, causal_lm, moe, mhc):
    """The streams are read out by their mean, not their sum."""
    from paddle_tpu.core import registry
    rule = registry.get("mhc_reduce")
    lower = rule.lower

    def mean(ctx, ins, attrs):
        out = lower(ctx, ins, attrs)
        return {"Out": [out["Out"][0] / attrs["streams"]]}
    rule.lower = mean


def shared_gated(fluid, causal_lm, moe, mhc):
    """The shared expert passes a sigmoid gate (Qwen3-Next's form; here of
    the token's mean channel, there being no weight for it) where the
    model adds it as it is."""
    swiglu, layers = causal_lm._swiglu, fluid.layers

    def gated(x, width, c, role=""):
        out = swiglu(x, width, c, role)
        if role != "shared_expert.":
            return out
        return out * layers.sigmoid(layers.reduce_mean(x, dim=-1,
                                                       keep_dim=True))
    causal_lm._swiglu = gated


def scale_1(fluid, causal_lm, moe, mhc):
    """routed_scaling_factor 1: the routed experts' output at half its
    weight beside the shared expert's."""
    def unscaled(ins, attrs):
        attrs.pop("scale")
        return ins, attrs
    _rule_with("moe_ffn", unscaled)


def bias_in_weights(fluid, causal_lm, moe, mhc):
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


def softmax_for_sigmoid(fluid, causal_lm, moe, mhc):
    """The router scores by a softmax over the 64 experts, not by a sigmoid
    an expert."""
    def softmax(ins, attrs):
        attrs.pop("scoring")
        return ins, attrs
    _rule_with("moe_ffn", softmax)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "xing4_0.py")):
            reference = mod.reference
            mod.reference = lambda cfg, traffic, params, batch: reference(
                cfg, traffic, [round_weights(p) for p in params], batch)
        return mod
    manifest.load_module = load_and_wrap


def reference_bf16_weights(fluid, causal_lm, moe, mhc):
    """The reference with its weights rounded to bfloat16: stays correct."""
    import jax.numpy as jnp
    _reference_with(lambda p: p.astype(jnp.bfloat16).astype(jnp.float32))


def reference_fp8_weights(fluid, causal_lm, moe, mhc):
    """The reference with its weights rounded to float8 e4m3, scaled a
    tensor to the format's range: has to fail a tolerance."""
    import jax.numpy as jnp

    def fp8(p):
        scale = jnp.maximum(jnp.abs(p).max(), 1e-30) / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


MUTANTS = {f.__name__: f for f in (
    yarn_off, mscale_off, rope_on_nope, rope_half_split, k_rope_per_head,
    kv_norm_off, q_norm_off, v_from_k_nope, sinkhorn_off, sinkhorn_rows_only,
    h_res_transposed, h_post_unscaled, h_pre_softmax, coeffs_static,
    readout_mean, shared_gated, scale_1, bias_in_weights,
    softmax_for_sigmoid, reference_bf16_weights, reference_fp8_weights)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_xing4_0.py <%s> <arguments of benchmark/run.py>"
              % "|".join(MUTANTS), file=sys.stderr)
        return 1
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    from paddle_tpu.ops import mhc_kernels
    from paddle_tpu.parallel import moe
    MUTANTS[argv[0]](fluid, causal_lm, moe, mhc_kernels)
    print("bench: MUTANT %s: %s" % (argv[0], MUTANTS[argv[0]].__doc__),
          flush=True)
    from benchmark import run
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
