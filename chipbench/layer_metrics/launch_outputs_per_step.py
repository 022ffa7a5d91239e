"""Device (PjRt): buffers a step's launches hand back, the sum of n_out
over mx.graph.launch, mx.tape.vjp, mx.trainer.launch and mx.bulk.flush.
A forward launch's residuals are PjRt's to allocate too and are not in
it."""

from .. import program_trace


def read(run):
    return program_trace.launch_outputs_per_step(program_trace.of_run(run))
