"""100 x the device's self time under a fluid op's scope or in a named
Pallas kernel over all of it, from the compiled step's own map
(benchmark/op_ms.py): whether a table by fluid op is whole."""
from benchmark.op_ms import named_share


def read(record):
    return named_share(record)
