"""Device (PjRt's queue): the mean ``ahead`` of the window's launch spans
(mx.graph.launch, mx.tape.vjp, mx.trainer.launch, mx.bulk.flush): how
many of the program's earlier launches had not finished on the device
when a launch's call returned (``_bulk.LaunchRecord``, at most 8). Near
0 the device runs out of queued work at the launches, and idles while
the host enqueues; at 1 or more it always had work in hand. A program
whose launches carry no ``ahead`` (older than PR 39) reads nothing."""

from .. import program_trace


def marked(got):
    """The launch spans of an analysis (``program_trace.analyse``) that
    carry ``ahead``; none in a program from before the attribute."""
    return [sp for name, sp in got['spans'].items()
            if name in program_trace.LAUNCHES and 'ahead' in sp['attrs']]


def of_analysis(got):
    spans = marked(got)
    if not spans:
        return None
    return sum(sp['attrs']['ahead'] for sp in spans) / \
        sum(sp['count'] for sp in spans)


def read(run):
    return of_analysis(program_trace.of_run(run))
