"""Autograd (_tape.py): host time a step inside mx.tape.vjp, the launches
of the backward programs (a hybridized block's, a bulk segment's)."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.tape.vjp')
