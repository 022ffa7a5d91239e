"""Graph capture (gluon/block.py _CachedGraph._execute): host time a step
inside mx.graph.launch, the jitted call down to PjRt (under record() it
is jax.vjp's forward, residuals and all)."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.graph.launch')
