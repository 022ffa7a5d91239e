"""Device (PjRt): host time a step in PjRt's buffer-allocation events
(Allocate*, nested ones counted once) inside the program's launch spans.
The CPU client emits none and reads 0.0."""

from .. import program_trace


def read(run):
    return program_trace.launch_alloc_ms_per_step(program_trace.of_run(run))
