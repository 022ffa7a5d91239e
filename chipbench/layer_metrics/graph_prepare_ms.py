"""Graph capture (gluon/block.py _CachedGraph): self time a step of
mx.graph.call, what is left of the compiled call after the engine's flush
and the launch: flattening the arguments, _params(), the key, the lock."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.graph.call', self_time=True)
