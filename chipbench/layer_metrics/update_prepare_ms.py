"""Update (gluon/trainer.py): self time a step of mx.trainer.step, what
is left of Trainer.step after placing, the hyperparameters and the
launch: _update's loop over the parameters, praws/graws/sraws, the cache
key, the rebinding loop."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.trainer.step', self_time=True)
