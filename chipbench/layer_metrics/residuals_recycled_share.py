"""Graph capture (gluon/block.py ``_VjpPrograms``): the share of the
residual buffers the window's recorded forwards wrote into the last
backward's spent ones, donated, rather than into buffers PjRt allocated:
the sum of ``recycled`` over the sum of ``residuals`` of the
mx.graph.launch spans. A program whose launches carry no ``residuals``
(older than the attribute), or whose forwards hand back none (every
entry under ``remat``), reads nothing."""

from .. import program_trace


def of_analysis(got):
    attrs = got['spans'].get('mx.graph.launch', {}).get('attrs', {})
    if not attrs.get('residuals'):
        return None
    return 100.0 * attrs.get('recycled', 0) / attrs['residuals']


def read(run):
    return of_analysis(program_trace.of_run(run))
