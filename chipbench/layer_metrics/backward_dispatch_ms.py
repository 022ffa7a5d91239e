"""Autograd (_tape.py): host time a step inside the span round loss.backward()."""

from . import span_ms_per_step


def read(run):
    return span_ms_per_step(run, 'backward')
