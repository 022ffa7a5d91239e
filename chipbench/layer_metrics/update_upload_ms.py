"""Update (gluon/trainer.py _fused_update): host time a step inside
mx.trainer.hyper: the update counts, the lr/wd vectors and the upload
of the step counts."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.trainer.hyper')
