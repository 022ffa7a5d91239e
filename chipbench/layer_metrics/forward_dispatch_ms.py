"""Graph capture (gluon/block.py _CachedGraph): host time a step inside the span round net(...) under autograd.record(). Includes the eager engine's flush, which the compiled call makes a sync point."""

from . import span_ms_per_step


def read(run):
    return span_ms_per_step(run, 'forward')
