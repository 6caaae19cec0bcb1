"""Rows a data token puts through a layer's FFN (what lies behind the
attention's core: W_o, the residual, the router and the experts), a layer:
the `ffn` rows of the program's counter `ptpu_causal_lm_rows_total`
(paddle_tpu/models/causal_lm.py: the rows of ONE sequence each part of each
layer is built over under objective block_diffusion, by part and copy), the
batch's times as many, over the layers counted under `mask =
block_diffusion` x the configuration module's `samples_per_step`. SDAR's
cut: (3 x 8192 + 4096) / (4 x 4096) = 1.75 (two copies a token through
layers 0-2, the noised rows alone through the last layer); 1.98 in the whole
model's 48 layers; 1 in every next-token model. It moves if the last layer's
cut is lost (2.0) or another is found. None where the program has no such
counter (a program from before the objective, or a next-token model)."""
from benchmark.registry_reads import family_sum


def read(record):
    cell = record["cell"]
    rows = family_sum("ptpu_causal_lm_rows_total", part="ffn")
    layers = family_sum("ptpu_causal_lm_layers_total",
                        mask="block_diffusion")
    if not rows or not layers:
        return None
    samples = cell.config_module.samples_per_step(cell.config, cell.traffic)
    return rows * cell.traffic["batch"] / (layers * samples)
