"""Decoder layers the program built whose mixer reads ANOTHER layer's state
(a gated memory unit on one layer's scan output, a cross attention on one
layer's keys and values), as a share of all the decoder layers it built:
100 x the counts under `reads="shared"` over all counts of the program's
counter `ptpu_causal_lm_layers_total` (paddle_tpu/models/causal_lm.py: one
count a layer built, by whose state its mixer reads). Phi-4-mini-flash's cut
builds two such layers of six, 33.3 %, where the published depth has 14 of
32, 43.8 %. None where the program has no such counter, where the counter
has no `reads` label (a program from before the cross-decoder) or where no
layer reads another's state: a change that makes every layer compute its own
shows as the metric falling silent."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    by_reads = {}
    for labels, value in family["samples"] if family else ():
        reads = labels.get("reads")
        by_reads[reads] = by_reads.get(reads, 0.0) + value
    if not by_reads.get("shared"):
        return None
    return 100.0 * by_reads["shared"] / sum(by_reads.values())
