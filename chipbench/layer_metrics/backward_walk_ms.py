"""Autograd (_tape.py): self time a step of mx.tape.backward, what is
left of backward() after the engine's flush and the backward programs'
launches: the toposort, the cotangent sums, the writes into .grad."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.tape.backward', self_time=True)
