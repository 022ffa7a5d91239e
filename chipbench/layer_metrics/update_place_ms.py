"""Update (gluon/trainer.py _mesh_place): host time a step inside
mx.trainer.place, committing the fused update's operands to the mesh.
The span exists only under a mesh."""

from .. import program_trace


def read(run):
    return program_trace.span_ms_per_step(
        program_trace.of_run(run), 'mx.trainer.place')
