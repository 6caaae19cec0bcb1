"""Decoder layers the program built whose attention runs under the
block-diffusion mask, as a share of all the decoder layers it built: 100 x
the counts whose label `mask` is `block_diffusion` over all counts of the
program's counter `ptpu_causal_lm_layers_total` (paddle_tpu/models/
causal_lm.py: one count a layer built; `mask` and `block_length` are written
for a config with `objective: block_diffusion` alone). SDAR-30B-A3B's cut
builds four of four, 100 %; a layer that fell back to the causal mask shows.
None where the program has no such counter or the counter has no `mask`
label (a program from before the objective, or one that built next-token
models alone)."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    masked = total = 0.0
    labelled = False
    for labels, value in family["samples"] if family else ():
        total += value
        if "mask" in labels:
            labelled = True
            if labels["mask"] == "block_diffusion":
                masked += value
    if not labelled:
        return None
    return 100.0 * masked / total
