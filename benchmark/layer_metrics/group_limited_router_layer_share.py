"""Layers of routed experts the program built whose router chooses groups
before experts (DeepSeek-V3's group-limited choice: the top k inside a
token's `kept_groups` best of `groups` runs of neighbouring experts), as a
share of all the layers of routed experts it built: 100 x the counts that
carry a `groups` label over all counts with `ffn="experts"` of the program's
counter `ptpu_causal_lm_layers_total` (paddle_tpu/models/causal_lm.py: one
count a layer built). Ling-3.0-flash's cut builds six of six, 100 %. None
where the program has no such counter or built no such layer: a limit that
is dropped shows as the metric falling silent."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    limited = routed = 0.0
    for labels, value in family["samples"] if family else ():
        if labels.get("ffn") != "experts":
            continue
        routed += value
        if labels.get("groups"):
            limited += value
    if not limited:
        return None
    return 100.0 * limited / routed
