"""Runs one causal_lm cell with its model broken on purpose, to show that
`correct` can fail for what the cell measures.

    python benchmark/tests/mutant_olmoe.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters and their order as they
are, so the reference still reads the program's weights; then the cell runs
as benchmark/run.py runs it. Every mutant's last line has to say `"correct":
false`; the configuration's .json has what the chip gave.
"""
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def fewer_experts(moe, causal_lm, layers):
    """A token's weakest expert is dropped: top-(k-1) routing. `dropless`
    fails too: the experts computed (k-1) * N assignments."""
    routed = moe.routed_ffn
    moe.routed_ffn = lambda *a, top_k, **kw: routed(*a, top_k=top_k - 1, **kw)


def renormalised(moe, causal_lm, layers):
    """The top-k router weights are renormalised to sum 1."""
    routed = moe.routed_ffn
    moe.routed_ffn = lambda *a, norm_topk_prob=False, **kw: routed(
        *a, norm_topk_prob=True, **kw)


def no_qk_norm(moe, causal_lm, layers):
    """Queries and keys skip their RMS norm (its weights stay in the
    program, multiplied by zero)."""
    attention, norm = causal_lm.attention, causal_lm._norm

    def broken(x, pos, c):
        causal_lm._norm = lambda t, c: t + norm(t, c) * 0.0
        try:
            return attention(x, pos, c)
        finally:
            causal_lm._norm = norm
    causal_lm.attention = broken


def rotary_off(moe, causal_lm, layers):
    """Queries and keys are not rotated: attention sees no position."""
    layers.rotary_embedding = lambda x, pos, **kw: x


def relu_for_silu(moe, causal_lm, layers):
    """The experts' gate goes through ReLU and not SiLU."""
    import jax
    import jax.numpy as jnp
    moe._gated_silu = lambda gate, up: (
        jax.nn.relu(gate.astype(jnp.float32))
        * up.astype(jnp.float32)).astype(gate.dtype)


MUTANTS = {f.__name__: f for f in (fewer_experts, renormalised, no_qk_norm,
                                   rotary_off, relu_for_silu)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_olmoe.py <%s> <arguments of benchmark/run.py>"
              % "|".join(MUTANTS), file=sys.stderr)
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
