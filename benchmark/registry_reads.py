"""One read of the program's registry for the metric readers whose number
is a sum over a counter's or a gauge's samples (the build phases, shape
inference and the imports, PR 51). None where the program has no such
family, as a commit from before it has not, and the harness then leaves the
metric out of the line; a number, 0.0 included, wherever it has."""


def family_sum(family, **where):
    """The sum of `family`'s samples whose labels hold every `label=value`
    of `where` (a tuple of values: any of them), or None without the
    family."""
    from paddle_tpu.observability.registry import REGISTRY
    found = REGISTRY.snapshot().get(family)
    if found is None:
        return None
    return float(sum(
        value for labels, value in found["samples"]
        if all(labels.get(k) in (v if isinstance(v, tuple) else (v,))
               for k, v in where.items())))
