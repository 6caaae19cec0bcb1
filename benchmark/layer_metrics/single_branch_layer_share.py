"""Decoder layers the program built that are ONE branch, h + f(N(h)) with f
a mixer or an FFN (nemotron_h's `hybrid_override_pattern`), as a share of
all the decoder layers it built: 100 x the counts under `branches="1"` over
all counts of the program's counter `ptpu_causal_lm_layers_total`
(paddle_tpu/models/causal_lm.py: one count a layer built). Nemotron-3-Super's
cut builds eleven of eleven, 100 %; a layer given a second branch (the
multi-token-prediction module's layer has two) shows as less. None where
the program has no such counter, where the counter has no `branches` label
(a program from before the one-branch layer) or where no such layer was
built."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    one = total = 0.0
    for labels, value in family["samples"] if family else ():
        total += value
        if labels.get("branches") == "1":
            one += value
    if not one:
        return None
    return 100.0 * one / total
