"""Host seconds of shape inference while the programs were built: every
`append_op(infer_shape=True)` runs the op's lowering rule under
`jax.eval_shape` (twice where a dim is -1) or the op's own `infer`
(`ptpu_infer_shape_seconds_total{op, how}`, summed over both labels). It
lies inside `program_build_s`; by op type it is the last table of
`paddle_tpu.profiler.profile_report()`."""
from benchmark.registry_reads import family_sum


def read(record):
    return family_sum("ptpu_infer_shape_seconds_total")
