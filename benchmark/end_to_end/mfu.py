"""Model FLOP/s utilisation, in percent: the operations one sample needs
(the configuration's own ops_per_sample) times samples a second a chip, over
the published bf16 peak of the device kind (benchmark/peaks.json)."""
from benchmark.readers import samples_per_s_per_chip


def read(record):
    rate = samples_per_s_per_chip(record)
    if rate is None or record["peak"] is None:
        return None
    return 100.0 * record["ops_per_sample"] * rate \
        / record["peak"]["bf16_flops_per_s"]
