"""From a profiler trace (``.xplane.pb``) to numbers.

What a v5e trace holds (looked at by hand, PERF.md section 3): one plane
``/device:TPU:<n>`` for each chip, with a line ``XLA Modules`` (one event
for each execution of a compiled program, named ``<module>(<id>)``), a
line ``XLA Ops`` (one event for each operation inside it, named by its
whole HLO line) and a line ``Async XLA Ops`` (copies and collectives that
run beside the ops, from their start to their done); and the plane
``/host:CPU`` whose thread lines carry the benchmark's own spans
(``chipbench.<phase>``). All planes share one clock, in nanoseconds.

Everything is taken inside the span ``chipbench.window``. Times are per
device; a figure for the cell is the worst device's, but ``busy_s`` is
the mean over the devices (the contract's definition).
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
HOST_PLANE = '/host:CPU'
MODULE_LINE = 'XLA Modules'
OP_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'    # DMA that runs beside the ops
COLLECTIVE = re.compile(
    r'all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute'
    r'|collective-broadcast')
PHASES = ('feed', 'forward', 'loss', 'backward', 'update', 'wait')
TOP = 10


# ------------------------------------------------------------- intervals
def merged(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clipped(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def minus(a, b):
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle stretches of [lo, hi] between merged busy intervals."""
    return minus([(lo, hi)], busy)


_LAYOUT = re.compile(r'\{[^{}]*\}')
_RESULT = re.compile(r'^(\([^()]*\)|\S+)\s+([\w\-]+)\(')


def op_name(event_name):
    """An op's event is named by its whole HLO line. Keep its name, its
    opcode and the type of its result without layouts:
    ``%fusion.12 = f32[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 fusion f32[8,128]``."""
    head, _, rest = event_name.partition(' = ')
    head = head.lstrip('%')
    m = _RESULT.match(_LAYOUT.sub('', _LAYOUT.sub('', rest)))
    if not m:
        return head
    return f'{head} {m.group(2)} {m.group(1)[:48]}'


def module_name(event_name):
    """``jit_fused(1234)`` -> ``jit_fused``."""
    return re.sub(r'\(\d+\)$', '', event_name)


# -------------------------------------------------------------- the trace
def load(path):
    """An .xplane.pb as plain data: {'devices': {n: {'modules': [(name,
    start, end)], 'ops': [...]}}, 'spans': {name: [(start, end)]}}."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {'modules': [], 'ops': [], 'async': []})
            for line in plane.lines:
                key = {MODULE_LINE: 'modules', OP_LINE: 'ops',
                       ASYNC_LINE: 'async'}.get(line.name)
                if key is None:
                    continue
                short = str if key == 'modules' else op_name
                for ev in line.events:
                    dev[key].append((short(ev.name), ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return {'devices': devices, 'spans': spans}


def reduce(trace, prefix='chipbench.'):
    """Plain trace data -> the figures the per-layer readers take."""
    spans = {k[len(prefix):]: sorted(v) for k, v in trace['spans'].items()
             if k.startswith(prefix)}
    if not spans.get('window'):
        raise ValueError(f'no {prefix}window span in the trace')
    lo, hi = spans['window'][0]
    steps = len(clipped(spans.get('update', []), lo, hi))
    # the phases follow one another on one thread: they do not overlap
    phase_at = sorted((s, e, name) for name in PHASES
                      for s, e in spans.get(name, []))
    starts = [s for s, _, _ in phase_at]

    def host_doing(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < phase_at[i][1]:
            return phase_at[i][2]
        return 'between'

    per_device, op_seconds, gap_list = [], {}, []
    for n, dev in sorted(trace['devices'].items()):
        ops = [(name, *c) for name, s, e in dev['ops']
               for c in clipped([(s, e)], lo, hi)]
        busy = merged((s, e) for _, s, e in ops)
        coll = merged(c for name, s, e in ops + list(dev.get('async', []))
                      if COLLECTIVE.search(name)
                      for c in clipped([(s, e)], lo, hi))
        compute = merged((s, e) for name, s, e in ops
                         if not COLLECTIVE.search(name))
        programs = {}
        for name, s, e in dev['modules']:
            for cs, ce in clipped([(s, e)], lo, hi):
                programs[module_name(name)] = \
                    programs.get(module_name(name), 0) + ce - cs
        for name, s, e in ops:
            op_seconds[name] = op_seconds.get(name, 0) + (e - s) * 1e-9
        idle = gaps(busy, lo, hi)
        gap_list += [(e - s, host_doing(s)) for s, e in idle]
        per_device.append({
            'device': n,
            'busy_s': total(busy) * 1e-9,
            'collective_s': total(coll) * 1e-9,
            'collective_exposed_s': total(minus(coll, compute)) * 1e-9,
            'program_s': {k: v * 1e-9 for k, v in programs.items()},
        })
    if not per_device:
        raise ValueError('no device plane in the trace')
    n_dev = len(per_device)
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    by_phase = {}
    for dur, name in gap_list:
        by_phase[name] = by_phase.get(name, 0) + dur * 1e-9 / n_dev
    top_gaps = sorted(by_phase.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        'window_s': (hi - lo) * 1e-9,
        'steps': steps,
        'busy_s': sum(d['busy_s'] for d in per_device) / n_dev,
        'devices': per_device,
        'host_span_s': {k: total(clipped(v, lo, hi)) * 1e-9
                        for k, v in spans.items() if k != 'window'},
        'idle_by_phase_s': by_phase,
        'longest_gaps': [[dur * 1e-9, name] for dur, name in
                         sorted(gap_list, reverse=True)[:5]],
        'breakdown': {
            'device_ops': [[k, v / n_dev] for k, v in top_ops],
            # idle seconds of the window by what the host was doing
            # when each gap began, mean over the devices
            'idle_gaps': [[name, sec] for name, sec in top_gaps],
        },
    }


def reduce_dir(trace_dir, prefix='chipbench.'):
    """Reduce the one trace the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    if len(paths) != 1:
        raise FileNotFoundError(
            f'want one .xplane.pb under {trace_dir}, found {len(paths)}')
    return reduce(load(paths[0]), prefix)
