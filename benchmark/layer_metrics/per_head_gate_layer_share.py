"""Decoder layers the program built whose attention output is multiplied by
a sigmoid gate a HEAD (laguna's `gating: per-head`: one scalar a head from a
projection of its own, before W_o), as a share of all the decoder layers it
built: 100 x the counts under `gate="per_head"` over all counts of the
program's counter `ptpu_causal_lm_layers_total`
(paddle_tpu/models/causal_lm.py: one count a layer built). Laguna-S-2.1's
cut builds five of five, 100 %; a gate dropped, or built a channel from a
twice-wide W_q (`gate="true"`, Qwen3-Next's), shows as less. None where the
program has no such counter or where no layer was built with such a gate (a
program from before the form never writes the value)."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    gated = total = 0.0
    for labels, value in family["samples"] if family else ():
        total += value
        if labels.get("gate") == "per_head":
            gated += value
    if not gated:
        return None
    return 100.0 * gated / total
