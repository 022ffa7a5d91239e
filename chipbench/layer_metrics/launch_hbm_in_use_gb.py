"""Device (PjRt): the most memory any launch of the window left in use,
GB (1e9 B): the largest ``in_use`` of the launch spans (mx.graph.launch,
mx.tape.vjp, mx.trainer.launch, mx.bulk.flush), ``bytes_in_use`` of the
fullest device once the launch's outputs were allocated. On a v5e it
reads memory_peak_bytes itself in the BERT pre-train and both sparse
cells (PR 39): PjRt takes what a program needs when it is enqueued, so
what the peak holds beyond this reading was allocated outside every
launch. 0.0 where no launch carries it (the CPU client keeps no
statistics), nothing where no launch carries ``ahead`` either (a
program from before PR 39)."""

from .. import program_trace, trace_reduce


def of_host(host):
    """From ``trace_reduce.load``'s host events, clipped to the window as
    ``program_trace.analyse`` clips them."""
    windows = sorted((s, e) for name, s, e, *_ in host
                     if name == program_trace.WINDOW)
    if not windows:
        raise ValueError(f'no {program_trace.WINDOW} span in the trace')
    lo, hi = windows[0]
    launches = [attrs for name, s, e, _, attrs in host
                if name in program_trace.LAUNCHES
                and min(e, hi) > max(s, lo)]
    if not any('ahead' in attrs for attrs in launches):
        return None
    return max((attrs['in_use'] for attrs in launches if 'in_use' in attrs),
               default=0) / 1e9


def read(run):
    return of_host(trace_reduce.load_dir(run['trace_dir'])['host'])
