"""Update (gluon/trainer.py): host time a step inside the span round trainer.step()."""

from . import span_ms_per_step


def read(run):
    return span_ms_per_step(run, 'update')
