"""Runs one cell with its attention broken on purpose, to show that `correct`
can fail for the computation the cell measures.

    python benchmark/tests/mutant.py <mutant> <the arguments of benchmark/run.py>

The mutant wraps the two functions every attention core of the program goes
through, the Pallas flash kernel (`ops.pallas_kernels.flash_attention`, at
and above the crossover) and the dense path (`parallel.ring_attention.
attention_reference`, below it), in this process alone, then runs the cell
as benchmark/run.py does. Every mutant's last line has to say `"correct":
false` with the verdict `reference` false; PERF.md has what the chip gave.
"""
import functools
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def zeroed(attention, q, k, v, **kw):
    """Every attention core gives zeros."""
    return attention(q, k, v, **kw) * 0


def no_causal(attention, q, k, v, **kw):
    """The decoder's self-attention sees the positions after it."""
    return attention(q, k, v, **dict(kw, causal=False))


def unscaled(attention, q, k, v, **kw):
    """The scores are not divided by sqrt(d_key)."""
    return attention(q, k, v, **dict(kw, scale=1.0))


MUTANTS = {f.__name__: f for f in (zeroed, no_causal, unscaled)}


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant.py <%s> <arguments of benchmark/run.py>"
              % "|".join(MUTANTS), file=sys.stderr)
        return 1
    for module, name in (
            ("paddle_tpu.ops.pallas_kernels", "flash_attention"),
            ("paddle_tpu.parallel.ring_attention", "attention_reference")):
        module = importlib.import_module(module)
        setattr(module, name, functools.partial(
            MUTANTS[argv[0]], getattr(module, name)))
    print("bench: MUTANT %s: %s" % (argv[0], MUTANTS[argv[0]].__doc__),
          flush=True)
    from benchmark import run
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
