"""Peak device memory in GiB after the window, the largest over the cell's
devices: the arrays alive at once plus the scratch space the runtime
reserved for the programs' temporaries (cell._memory_peak).

A layer's metric with no bound, not an end-to-end one: memory is what a step
spends against recomputation ("Memory: peak use, and operations recomputed
to save memory" stands under Layers in the choosing-metrics guide's sheet
for training), so it is read beside `recomputed_forward_share` and
`xla_op_ms_per_step`, and a change that spends it says what it bought. What
a user feels of memory is whether the step fits, and that needs no bound: a
step that does not fit compiles to no executable, the cell prints no
result, and the run fails."""


def read(record):
    peak = record["memory_peak_bytes"]
    return None if peak is None else peak / 2.0 ** 30
