"""Process start to the first timed step: imports, program build, startup
program, batch, reference check, first step (trace, lower, compile or cache
load); less the accelerator runtime's own start (the first jax.devices()),
which no PR can change and which is the unsteady part: 7-13 s on the v5e,
drifting by seconds between calls on one machine. In that it departs from
ISSUE 22's "process start to the first timed step"; the start is printed as
`backend` on every run's `bench: set-up` line."""


def read(record):
    return record["setup_s"]
