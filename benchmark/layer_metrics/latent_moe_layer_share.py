"""Decoder layers the program built whose routed experts work in a latent
space (nemotron_h's LatentMoE: u = x W_dn, ungated experts on u, their sum
through W_up; the router and the shared expert read x), as a share of all
the decoder layers it built: 100 x the counts whose label `latent` is above
0 over all counts of the program's counter `ptpu_causal_lm_layers_total`
(paddle_tpu/models/causal_lm.py: one count a layer built; `latent` is the
experts' input width, 0 where they read the hidden state). Nemotron-3-Super's
cut builds five of eleven, 45.5 %, which is also the published depth's 40 of
88. None where the program has no such counter, where the counter has no
`latent` label (a program from before the form) or where no layer was built
with one: a change that builds the experts on the hidden state under the
model's name shows as the metric falling silent."""


def read(record):
    from paddle_tpu.observability.registry import REGISTRY
    family = REGISTRY.snapshot().get("ptpu_causal_lm_layers_total")
    latent = total = 0.0
    for labels, value in family["samples"] if family else ():
        total += value
        width = labels.get("latent", "0")
        if width.isdigit() and int(width) > 0:
            latent += value
    if not latent:
        return None
    return 100.0 * latent / total
