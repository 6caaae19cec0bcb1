"""Decoder layers the program built whose mixer is a delta rule with a decay
a key channel (Kimi Delta Attention), as a share of all the decoder layers
it built: 100 x the counts under `mixer="kda"` over all counts of the
program's counter `ptpu_causal_lm_layers_total`
(paddle_tpu/models/causal_lm.py: one count a layer built, by mixer).
Ling-3.0-flash's cut builds six of seven, 85.7 %; the published depth has 35
of 42, 83.3 %. None where the program has no such counter or built no such
layer (a program from before the mixer): a change that builds another mixer
under the model's name shows as the metric falling silent."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    by_mixer = {}
    for labels, value in family["samples"] if family else ():
        mixer = labels.get("mixer")
        by_mixer[mixer] = by_mixer.get(mixer, 0.0) + value
    if not by_mixer.get("kda"):
        return None
    return 100.0 * by_mixer["kda"] / sum(by_mixer.values())
