"""Forward ops the program ran a second time in the backward pass, as a
share of the forward ops it lowered: 100 x `replayed` over `forward` of the
program's counter `ptpu_remat_ops_total` (paddle_tpu/core/lowering.py: a
program that recomputes counts every forward op once, and once more each op
it replays). None where the program has no such counter or recomputes
nothing."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_remat_ops_total")
    by_kind = {}
    for labels, value in family["samples"] if family else ():
        kind = labels.get("kind")
        by_kind[kind] = by_kind.get(kind, 0.0) + value
    if not by_kind.get("forward"):
        return None
    return 100.0 * by_kind.get("replayed", 0.0) / by_kind["forward"]
