"""Eager engine (_bulk.py): host time a step inside the span round the eager loss ops (loss_fn(...), masking, .mean())."""

from . import span_ms_per_step


def read(run):
    return span_ms_per_step(run, 'loss')
