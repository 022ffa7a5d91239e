"""Graph capture (gluon/block.py _CachedGraph._await_backward): host time
a step inside mx.graph.await, the wait of a recorded call for the last
backward launched from its graph before it enqueues its forward (PR 32:
one set of residuals on the device). It lies inside mx.graph.flush, so
it is the part of graph_flush_ms that is not the eager engine's; where
the device bounds the step it is the host's wait for the device. A
program whose launches carry no ``ahead`` has no such span either (older
than PR 39) and reads nothing, not a false 0."""

from .. import program_trace
from .launch_queue_depth import marked


def of_analysis(got):
    if not marked(got):
        return None
    return program_trace.span_ms_per_step(got, 'mx.graph.await')


def read(run):
    return of_analysis(program_trace.of_run(run))
