"""The program's own spans in a profiler trace, beside PjRt's and the
device's.

``mxnet_tpu`` opens a span at each boundary of the train path
(``mx.graph.call``, ``mx.tape.backward``, ``mx.trainer.step`` and their
children; docs/observability.md, "Tracing a training loop"). While the
profiler runs they are events of ``/host:CPU`` with their counts as the
events' stats, on the clock of the device's ``XLA Ops``. This file turns
the one ``.xplane.pb`` of a ``--trace 1`` run into, inside
``chipbench.window`` and with the step count from ``chipbench.update`` as
``trace_reduce`` takes them:

* every ``mx.*`` span's count, time, self time (its duration less what
  its children on the same thread line cover) and the sums of its
  attributes;
* PjRt's buffer-allocation events clipped to the launch span they fall
  in;
* each chipbench phase's time that no ``mx.*`` span covers;
* where there is a device plane, the device's idle seconds by the
  innermost span the host was in when each gap began.

The program's ``jax.named_scope``s (``mx.attention``, ``mx.layer_norm``,
``mx.optimizer_step``) reach a v5e profile as a stat of each operation's
event *metadata* (its ``op_name``), which ``jax.profiler.ProfileData``
does not yield (PERF.md section 3). ``trace_reduce.load`` reads them by
the schema TensorFlow ships and keeps each operation's scope; the
print-out sums them (:func:`device_seconds_by_scope`), and a per-layer
reader takes ``scope_s`` of ``trace_reduce.reduce``.

The file is parsed once, by ``trace_reduce.load_dir``, for both. The
per-layer readers under ``layer_metrics/`` take :func:`of_run`, which
finds the trace directory in the ``run`` the runner hands them;

    python3 chipbench/program_trace.py <trace_dir>

prints the tables PERF.md section 5 is written from. A program without
the spans (an older commit) reads as no span at all and nothing raises.
"""

import bisect
import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from chipbench import trace_reduce
from chipbench.trace_reduce import (ALLOCATION, BENCH, PROGRAM, clipped,
                                    innermost, merged, minus, profile_under,
                                    total)

WINDOW = BENCH + 'window'
STEP = BENCH + 'update'         # one a step
PHASES = tuple(BENCH + p for p in trace_reduce.PHASES)
# the spans round a jitted call down to PjRt; their ``n_out`` is the
# number of buffers the call hands back
LAUNCHES = ('mx.graph.launch', 'mx.tape.vjp', 'mx.trainer.launch',
            'mx.bulk.flush')
ONE_BUFFER = 'AllocateRawBuffer'     # one of PjRt's events a buffer


def of_loaded(trace):
    """``trace_reduce.load``'s data as :func:`analyse` takes it:
    ``{'host': [(name, start, end, line, attrs)], 'devices': {n: [(start,
    end)]}}``, in nanoseconds: the host events it kept, and of a device
    its ``XLA Ops``."""
    return {'host': trace['host'],
            'devices': {n: [(s, e) for _, s, e, *_ in dev['ops']]
                        for n, dev in trace['devices'].items()}}


def _nest(spans):
    """``[(span, children)]`` for spans of one thread line, a child being
    a span directly inside another."""
    out, stack = [], []
    for sp in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and sp[1] >= stack[-1][0][2]:
            stack.pop()
        node = (sp, [])
        if stack:
            stack[-1][1].append(sp)
        stack.append(node)
        out.append(node)
    return out


def _holds(intervals, t):
    """Whether one of the merged ``intervals`` holds ``t``."""
    i = bisect.bisect_right(intervals, (t, float('inf'))) - 1
    return i >= 0 and t < intervals[i][1]


def analyse(trace):
    """Plain trace data -> what the readers and the print-out take."""
    host = trace['host']
    windows = sorted((s, e) for name, s, e, *_ in host if name == WINDOW)
    if not windows:
        raise ValueError(f'no {WINDOW} span in the trace')
    lo, hi = windows[0]
    inside = [(name, max(s, lo), min(e, hi), line, attrs)
              for name, s, e, line, attrs in host
              if min(e, hi) > max(s, lo)]
    steps = sum(1 for sp in inside if sp[0] == STEP)

    # the program's spans: time, self time, attributes
    by_line = {}
    for sp in inside:
        if sp[0].startswith(PROGRAM):
            by_line.setdefault(sp[3], []).append(sp)
    spans = {}
    for line_spans in by_line.values():
        for (name, s, e, _, attrs), children in _nest(line_spans):
            covered = total(merged((c[1], c[2]) for c in children))
            got = spans.setdefault(name, {'count': 0, 'total_s': 0.0,
                                          'self_s': 0.0, 'attrs': {}})
            got['count'] += 1
            got['total_s'] += (e - s) * 1e-9
            got['self_s'] += (e - s - covered) * 1e-9
            for k, v in attrs.items():
                got['attrs'][k] = got['attrs'].get(k, 0) + v

    # PjRt's allocations, clipped to the launch span they fall in
    allocs = [sp for sp in inside if ALLOCATION.match(sp[0])]
    alloc_busy = merged((s, e) for _, s, e, *_ in allocs)
    alloc = {}
    for name in LAUNCHES:
        own = merged((s, e) for n, s, e, *_ in inside if n == name)
        if not own:
            continue
        outside = minus(alloc_busy, own)
        alloc[name] = {
            's': (total(alloc_busy) - total(outside)) * 1e-9,
            'buffers': sum(1 for n, s, *_ in allocs
                           if n == ONE_BUFFER and _holds(own, s))}

    # each phase of the benchmark: what no span of the program covers
    program_busy = merged((s, e) for n, s, e, *_ in inside
                          if n.startswith(PROGRAM))
    uncovered = {}
    for phase in PHASES:
        own = merged((s, e) for n, s, e, *_ in inside if n == phase)
        if own:
            uncovered[phase[len(BENCH):]] = {
                's': total(own) * 1e-9,
                'uncovered_s': total(minus(own, program_busy)) * 1e-9}

    return {
        'window_ns': (lo, hi), 'steps': steps, 'spans': spans,
        'alloc': alloc, 'phases': uncovered,
        'idle_by_span_s': idle_by_program_span(trace, inside, lo, hi)}


def idle_by_program_span(trace, inside, lo, hi):
    """The device's idle seconds of the window by the innermost ``mx.*``
    span (else the ``chipbench.`` phase, else ``between``) the host was
    in when each gap began; mean over the devices. The host is the
    thread line that holds the window span."""
    if not trace['devices']:
        return {}
    main = next(sp[3] for sp in inside if sp[0] == WINDOW)
    at = innermost([sp for sp in inside if sp[3] == main
                    and (sp[0].startswith(PROGRAM) or sp[0] in PHASES)])

    def doing(t):
        name = at(t)
        return name[len(BENCH):] if name in PHASES else name

    return trace_reduce.idle_by(
        doing, [merged(clipped(ops, lo, hi))
                for ops in trace['devices'].values()], lo, hi)


def device_seconds_by_scope(trace, lo, hi):
    """Device seconds of the operations of ``trace`` (as
    ``trace_reduce.load`` gives it) inside [lo, hi] (nanoseconds) by
    their ``mx.`` scope, ``other`` where an operation has none; mean over
    the devices; None where the scopes could not be read (no schema
    installed). An executable read from a compilation cache that an
    older tree filled carries that tree's names: every operation then
    reads ``other``."""
    if not trace['scopes_read']:
        return None
    per_device = []
    for dev in trace['devices'].values():
        seconds = {}
        for _, s, e, _, scope in dev['ops']:
            for cs, ce in clipped([(s, e)], lo, hi):
                key = scope or 'other'
                seconds[key] = seconds.get(key, 0.0) + (ce - cs) * 1e-9
        per_device.append(seconds)
    return {k: sum(d.get(k, 0.0) for d in per_device) / len(per_device)
            for d in per_device for k in d}


@functools.lru_cache(maxsize=1)
def _analysed(path, _mtime_ns):
    return analyse(of_loaded(trace_reduce.load_once(path)))


def of_dir(trace_dir):
    """The analysis of the profile under ``trace_dir``, made once."""
    path = profile_under(trace_dir)
    return _analysed(path, os.stat(path).st_mtime_ns)


def of_run(run):
    """The analysis of the profile the runner has just written (it says
    where in ``run['trace_dir']``), made once for all the readers of a
    run."""
    return of_dir(run['trace_dir'])


# ----------------------------------------------------- what the readers take
def span_seconds(got, *names, self_time=False):
    """Seconds of the window inside the spans ``names``; 0.0 for a span
    that never ran."""
    key = 'self_s' if self_time else 'total_s'
    return sum(got['spans'][n][key] for n in names if n in got['spans'])


def _a_step(got, amount):
    """``amount`` of the window, a step; None where the program has no
    span at all (a commit from before the spans: 0.0 would be a false
    reading) or the window no step."""
    if not got['steps'] or not got['spans']:
        return None
    return amount / got['steps']


def span_ms_per_step(got, *names, self_time=False):
    """Host milliseconds a step inside the spans ``names``; 0.0 where the
    program has spans and none of these ran."""
    return _a_step(got, span_seconds(got, *names, self_time=self_time) * 1e3)


def launch_alloc_ms_per_step(got):
    return _a_step(got, sum(a['s'] for a in got['alloc'].values()) * 1e3)


def launch_outputs_per_step(got):
    return _a_step(got, sum(
        got['spans'][n]['attrs'].get('n_out', 0) for n in LAUNCHES
        if n in got['spans']))


# ------------------------------------------------------------ the print-out
def report(got):
    """The tables PERF.md section 5 is written from, as lines."""
    n = got['steps'] or 1
    lo, hi = got['window_ns']
    out = [f'window {(hi - lo) * 1e-9:.3f} s, {got["steps"]} steps; '
           'host ms a step', '',
           f'{"span":<22}{"a step":>8}{"total":>9}{"self":>9}  attributes '
           'a step']
    for name, sp in sorted(got['spans'].items()):
        attrs = ' '.join(f'{k}={v / n:g}' for k, v in sorted(
            sp['attrs'].items()))
        out.append(f'{name:<22}{sp["count"] / n:>8.2f}'
                   f'{sp["total_s"] / n * 1e3:>9.3f}'
                   f'{sp["self_s"] / n * 1e3:>9.3f}  {attrs}')
    out += ['', "PjRt's allocations inside each launch span, a step:"]
    for name, a in sorted(got['alloc'].items()):
        out.append(f'{name:<22}{a["s"] / n * 1e3:>9.3f} ms'
                   f'{a["buffers"] / n:>9.1f} buffers')
    out += ['', 'each phase of the benchmark and the part of it no mx.* '
            'span covers, ms a step:']
    for name, p in got['phases'].items():
        out.append(f'{name:<22}{p["s"] / n * 1e3:>9.3f}'
                   f'{p["uncovered_s"] / n * 1e3:>9.3f}')
    out += ['', 'device idle seconds of the window by the innermost span the '
            'host was in when the gap began:']
    for name, sec in sorted(got['idle_by_span_s'].items(),
                            key=lambda kv: -kv[1]):
        if sec > 0:
            out.append(f'{name:<26}{sec:>9.4f}')
    return out


def main(argv):
    if len(argv) != 1:
        print('usage: program_trace.py <trace_dir>', file=sys.stderr)
        return 2
    got = of_dir(argv[0])
    print('\n'.join(report(got)))
    by_scope = device_seconds_by_scope(trace_reduce.load_dir(argv[0]),
                                       *got['window_ns'])
    if by_scope is None:
        print('\nno xplane_pb2 installed: device seconds by mx. scope not '
              'read')
        return 0
    print('\ndevice seconds of the window by mx. scope:')
    for name, sec in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        print(f'{name:<26}{sec:>9.4f}')
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
