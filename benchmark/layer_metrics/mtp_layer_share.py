"""Decoder layers the program built inside a multi-token-prediction module,
as a share of all the decoder layers it built: 100 x the counts under
`module="mtp"` over all counts of the program's counter
`ptpu_causal_lm_layers_total` (paddle_tpu/models/causal_lm.py: one count a
layer built, by the module it belongs to). GLM-4.7-Flash's cut builds five
trunk layers and the module's one, 16.7 %, where the published depth has 1
of 48, 2.1 %: what the cut overstates the module by. None where the program
has no such counter, where the counter has no `module` label (a program from
before the module) or where no layer was built under a module: a change
that drops the module shows as the metric falling silent."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    by_module = {}
    for labels, value in family["samples"] if family else ():
        module = labels.get("module")
        by_module[module] = by_module.get(module, 0.0) + value
    if not by_module.get("mtp"):
        return None
    return 100.0 * by_module["mtp"] / sum(by_module.values())
