"""Eager engine (_bulk.py): host time a step inside the two sync points of
a step, the flush at the top of a compiled call (mx.graph.flush) and of
backward() (mx.tape.flush), the segment's launch (mx.bulk.flush) included."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.graph.flush', 'mx.tape.flush')
