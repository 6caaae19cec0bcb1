"""Decoder layers the program built whose attention sits behind a sliding
window, as a share of all the decoder layers it built: 100 x the counts
whose label `window` is above 0 over all counts of the program's counter
`ptpu_causal_lm_layers_total` (paddle_tpu/models/causal_lm.py: one count a
layer built; `window` is the layer's own, 0 where it attends the whole
causal prefix, and is written for a config with a geometry by layer alone).
Laguna-S-2.1's cut builds three of five, 60 % (36 of 48, 75 %, in the whole
model); a window dropped, or put on a full layer, shows. None where the
program has no such counter, where the counter has no `window` label (a
program from before the geometry by layer) or where no layer was built with
the label."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    windowed = total = 0.0
    labelled = False
    for labels, value in family["samples"] if family else ():
        total += value
        width = labels.get("window")
        if width is not None:
            labelled = True
            if width.isdigit() and int(width) > 0:
                windowed += value
    if not labelled:
        return None
    return 100.0 * windowed / total
