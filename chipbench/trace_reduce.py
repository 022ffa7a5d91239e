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

The seam for a reader of one kernel's or one scope's device time: every
``XLA Ops`` event carries its ``kernel`` (a ``custom-call`` is named by
its instruction, ``mx_adam_step.153`` -> ``mx_adam_step``: that is how a
Pallas kernel reads) and its ``scope`` (the program's ``jax.named_scope``
it was traced under, ``mx.attention``). The scope is a string stat of the
event's *metadata* (``tf_op``), which ``jax.profiler.ProfileData`` does
not yield: it is read by the ``xplane_pb2`` schema TensorFlow ships,
where that is installed. :func:`reduce` gives ``kernel_s`` and
``scope_s`` a device and ``scoped``, whether any operation of the window
carried a scope at all: an executable read from a compilation cache that
an older tree filled carries that tree's names, and a reader has to tell
"no such work" from "the names are stale".

A run's one ``.xplane.pb`` is opened once by each of the two parsers
(:func:`load_dir` keeps the last file's data).
"""

import bisect
import functools
import glob
import importlib.util
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
BENCH = 'chipbench.'            # prefix of the benchmark's own host spans
PROGRAM = 'mx.'                 # prefix of the program's spans and scopes
# PjRt's own host events (PERF.md section 3): the allocation of a
# program's output buffers, one AllocateRawBuffer a buffer
ALLOCATION = re.compile(r'^Allocate')
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


def innermost(spans):
    """``at(t)``: the name of the innermost of ``spans`` (``(name, start,
    end, ...)`` of one thread line, properly nested) that holds ``t``,
    else None."""
    edges = []          # (time, name in force from then on), in order
    stack = []

    def close():
        _, end = stack.pop()
        edges.append((end, stack[-1][0] if stack else None))

    for name, s, e, *_ in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            close()
        stack.append((name, e))
        edges.append((s, name))
    while stack:
        close()
    times = [t for t, _ in edges]

    def at(t):
        i = bisect.bisect_right(times, t) - 1
        return edges[i][1] if i >= 0 else None
    return at


_LAYOUT = re.compile(r'\{[^{}]*\}')
_RESULT = re.compile(r'^(\([^()]*\)|\S+)\s+([\w\-]+)\(')
_SCOPE = re.compile(r'\bmx\.[a-z_]+')


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


def kernel_of(short_name):
    """The kernel of an operation by its :func:`op_name`: a
    ``custom-call`` is named by its instruction without the numeric
    suffix (``mx_adam_step.153 custom-call ...`` -> ``mx_adam_step``: a
    Pallas kernel's ``name``); any other operation has none."""
    head, _, rest = short_name.partition(' ')
    if rest.split(' ', 1)[0] != 'custom-call':
        return None
    return re.sub(r'\.\d+$', '', head)


def scope_of(op_name):
    """The ``mx.`` scope in an operation's ``op_name``, None where there
    is none. Forward and backward read alike: the tape takes ``jax.vjp``
    of the jitted forward, and the backward program it launches names
    its operations ``jit(pure_fn)/mx.attention/...`` as the forward's."""
    m = _SCOPE.search(op_name)
    return m.group(0) if m else None


def module_name(event_name):
    """``jit_fused(1234)`` -> ``jit_fused``."""
    return re.sub(r'\(\d+\)$', '', event_name)


# -------------------------------------------------------------- the trace
def schema():
    """The schema of an .xplane.pb (tsl/profiler/protobuf/xplane.proto)
    as TensorFlow ships it, loaded by its file: importing the package
    would bring TensorFlow's runtime in and take ten seconds. None where
    it is not installed."""
    found = importlib.util.find_spec('tensorflow')
    if found is None or not found.origin:
        return None
    path = os.path.join(os.path.dirname(found.origin), 'tsl', 'profiler',
                        'protobuf', 'xplane_pb2.py')
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location('xplane_pb2', path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def op_scopes(path, xplane_pb2):
    """``{device plane's name: {index of its XLA Ops line: [(the event's
    name, its scope or None), ...]}}``, the events in the file's order,
    which is the order ``ProfileData`` yields them in. The scope is a
    string stat of the event's metadata."""
    space = xplane_pb2.XSpace()
    with open(path, 'rb') as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        named = {}
        for key, metadata in plane.event_metadata.items():
            found = (scope_of(stat.str_value) for stat in metadata.stats)
            named[key] = (metadata.name, next(filter(None, found), None))
        out[plane.name] = {
            i: [named.get(ev.metadata_id, (None, None)) for ev in line.events]
            for i, line in enumerate(plane.lines) if line.name == OP_LINE}
    return out


def load(path):
    """An .xplane.pb as plain data, in nanoseconds::

        {'devices': {n: {'modules': [(name, start, end)],
                         'ops': [(name, start, end, kernel, scope)],
                         'async': [(name, start, end)]}},
         'spans': {name: [(start, end)]},              every host event
         'host': [(name, start, end, line, attrs)],
         'scopes_read': bool}

    ``host`` keeps the program's spans (with their whole-number
    attributes), the benchmark's and PjRt's allocations, each with the
    thread line it lies on: what ``program_trace`` nests. ``scopes_read``
    says whether the operations' scopes could be read (a schema is
    installed and yields the events as ``ProfileData`` does); where not,
    every ``scope`` is None."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    xplane_pb2 = schema()
    scopes = op_scopes(path, xplane_pb2) if xplane_pb2 else {}
    devices, spans, host = {}, {}, []
    short = {}                       # an op's event name -> (name, kernel)
    aligned = xplane_pb2 is not None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {'modules': [], 'ops': [], 'async': []})
            for i, line in enumerate(plane.lines):
                key = {MODULE_LINE: 'modules', OP_LINE: 'ops',
                       ASYNC_LINE: 'async'}.get(line.name)
                if key is None:
                    continue
                tagged = iter(scopes.get(plane.name, {}).get(i, ()))
                for ev in line.events:
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if key == 'modules':
                        dev[key].append((ev.name, *span))
                        continue
                    if ev.name not in short:
                        name = op_name(ev.name)
                        short[ev.name] = (name, kernel_of(name))
                    name, kernel = short[ev.name]
                    if key == 'async':
                        dev[key].append((name, *span))
                        continue
                    whose, scope = next(tagged, (None, None))
                    # both parsers yield a line's events in the file's
                    # order; where they ever do not, no scope is read
                    aligned = aligned and whose == ev.name
                    dev[key].append((name, *span, kernel, scope))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    spans.setdefault(name, []).append(span)
                    if name.startswith(PROGRAM):
                        attrs = {k: v for k, v in ev.stats
                                 if isinstance(v, int)}
                    elif name.startswith(BENCH) or ALLOCATION.match(name):
                        attrs = {}
                    else:
                        continue
                    host.append((name, *span, line.name, attrs))
    if not aligned:
        for dev in devices.values():
            dev['ops'] = [(*op[:4], None) for op in dev['ops']]
    return {'devices': devices, 'spans': spans, 'host': host,
            'scopes_read': aligned}


def idle_by(at, busy_of, lo, hi):
    """The idle seconds of [lo, hi] by what ``at`` says the host was
    doing when each gap began (``between`` where it says nothing), mean
    over the devices. ``busy_of``: a device's merged busy intervals."""
    out = {}
    for busy in busy_of:
        for s, e in gaps(busy, lo, hi):
            name = at(s) or 'between'
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9 / len(busy_of)
    return out


def by_key(ops, index):
    """Seconds of ``ops`` (clipped already) by their entry ``index`` (3:
    kernel, 4: scope), the operations that have none left out."""
    out = {}
    for op in ops:
        if op[index] is not None:
            out[op[index]] = out.get(op[index], 0.0) + (op[2] - op[1]) * 1e-9
    return out


def reduce(trace, prefix=BENCH):
    """Plain trace data -> the figures the per-layer readers take."""
    spans = {k[len(prefix):]: sorted(v) for k, v in trace['spans'].items()
             if k.startswith(prefix)}
    if not spans.get('window'):
        raise ValueError(f'no {prefix}window span in the trace')
    lo, hi = spans['window'][0]
    steps = len(clipped(spans.get('update', []), lo, hi))
    # what the host was doing at a time: the benchmark's phase (they
    # follow one another on one thread), and the innermost of the
    # program's spans inside it on that thread, where there is one
    phases = [(name, s, e) for name in PHASES for s, e in spans.get(name, [])]
    phase_at = innermost(phases)
    main = next((line for name, s, _, line, _ in trace.get('host', ())
                 if name == prefix + 'window' and s == lo), None)
    span_at = innermost(phases + [
        sp for sp in trace.get('host', ())
        if sp[3] == main and sp[0].startswith(PROGRAM)])

    per_device, op_seconds, busy_of = [], {}, []
    for n, dev in sorted(trace['devices'].items()):
        ops = [(name, *c, kernel, scope)
               for name, s, e, kernel, scope in dev['ops']
               for c in clipped([(s, e)], lo, hi)]
        busy = merged((s, e) for _, s, e, *_ in ops)
        coll = merged(c for name, s, e, *_ in ops + list(dev.get('async', []))
                      if COLLECTIVE.search(name)
                      for c in clipped([(s, e)], lo, hi))
        compute = merged((s, e) for name, s, e, *_ in ops
                         if not COLLECTIVE.search(name))
        programs = {}
        for name, s, e in dev['modules']:
            for cs, ce in clipped([(s, e)], lo, hi):
                programs[module_name(name)] = \
                    programs.get(module_name(name), 0) + ce - cs
        for name, s, e, *_ in ops:
            op_seconds[name] = op_seconds.get(name, 0) + (e - s) * 1e-9
        busy_of.append(busy)
        per_device.append({
            'device': n,
            'busy_s': total(busy) * 1e-9,
            'collective_s': total(coll) * 1e-9,
            'collective_exposed_s': total(minus(coll, compute)) * 1e-9,
            'program_s': {k: v * 1e-9 for k, v in programs.items()},
            'kernel_s': by_key(ops, 3),
            'scope_s': by_key(ops, 4),
        })
    if not per_device:
        raise ValueError('no device plane in the trace')
    n_dev = len(per_device)
    top_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    by_span = idle_by(span_at, busy_of, lo, hi)
    top_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(((e - s) * 1e-9, span_at(s) or 'between')
                     for busy in busy_of for s, e in gaps(busy, lo, hi))
    return {
        'window_s': (hi - lo) * 1e-9,
        'steps': steps,
        'busy_s': sum(d['busy_s'] for d in per_device) / n_dev,
        'devices': per_device,
        # whether any operation of the window carried a scope
        'scoped': any(d['scope_s'] for d in per_device),
        'host_span_s': {k: total(clipped(v, lo, hi)) * 1e-9
                        for k, v in spans.items() if k != 'window'},
        'idle_by_phase_s': idle_by(phase_at, busy_of, lo, hi),
        'idle_by_span_s': by_span,
        'longest_gaps': [list(gap) for gap in longest[:-6:-1]],
        'breakdown': {
            'device_ops': [[k, v / n_dev] for k, v in top_ops],
            # idle seconds of the window by the innermost span of the
            # program the host was in when each gap began, the
            # benchmark's phase where none held it; mean over the devices
            'idle_gaps': [[name, sec] for name, sec in top_gaps],
        },
    }


def profile_under(trace_dir):
    """The one .xplane.pb the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    if len(paths) != 1:
        raise FileNotFoundError(
            f'want one .xplane.pb under {trace_dir}, found {len(paths)}')
    return paths[0]


@functools.lru_cache(maxsize=1)
def _loaded(path, _mtime_ns):
    return load(path)


def load_once(path):
    """:func:`load`, the last file's data kept: a run's trace is parsed
    once for every reader of it."""
    return _loaded(path, os.stat(path).st_mtime_ns)


def load_dir(trace_dir):
    return load_once(profile_under(trace_dir))


def reduce_dir(trace_dir, prefix=BENCH):
    """Reduce the one trace the profiler wrote under ``trace_dir``."""
    return reduce(load_dir(trace_dir), prefix)
