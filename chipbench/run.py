"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``; ``check``
(each number compared beside its limit) comes last. Without a TPU, or
with another number of chips than the cell states, it refuses: exit code
1, the reason on stderr, no result. There is no CPU option.

Nothing here names a cell, a family or a metric. ``BENCHMARK.json`` names
the cell and the metrics; the cell's file (``workloads/<cell>.json``)
names its configuration (``configs/<config>.json``), that names its
family (``families/<family>.py``; ``families/__init__.py`` says what a
family owns), and every metric is read by the file of its name under
``end_to_end/`` or ``layer_metrics/``.
"""

import time

T_PROCESS = time.perf_counter()     # set-up counts from here

import argparse
import collections
import gc
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(HERE, '.trace')          # git-ignored
TRACE_SECONDS = 3.0        # of the window, in a --trace 1 run
TRACE_MIN_STEPS = 8
IN_FLIGHT = 2              # steps dispatched and not yet waited for
COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'
CACHE_HIT_EVENT = '/jax/compilation_cache/cache_hits'
SPAN = 'chipbench.'        # prefix of the benchmark's host spans


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name):
    """(cell, config): the cell's file and the configuration it names."""
    cell = load_json(HERE, 'workloads', name + '.json')
    return cell, load_json(HERE, 'configs', cell['config'] + '.json')


def load_peaks(device_kind):
    peaks = load_json(HERE, 'peaks.json')
    if device_kind not in peaks:
        raise KeyError(f'no peaks for device kind {device_kind!r} in '
                       'chipbench/peaks.json: add them with their source')
    return peaks[device_kind]


def reader(group, name):
    """The ``read(run)`` of one metric, found by the metric's name."""
    return importlib.import_module(f'chipbench.{group}.{name}').read


class CompileLog:
    """Counts and times XLA compiles through jax.monitoring: every jit of
    the process, the program's own and JAX's helpers alike."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == CACHE_HIT_EVENT:
            self.cache_hits += 1


def span(name):
    """One of the benchmark's own host spans round its calls into each
    layer: a ``jax.profiler.TraceAnnotation``, so a traced run has it on
    the profiler's clock beside the device's operations."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN + name)


def one_step(job, batch):
    """One training step as a user of Gluon writes it. Returns the loss,
    dispatched and not waited for."""
    from mxnet_tpu import autograd
    with span('feed'):
        dev = job.upload(batch)
    with autograd.record():
        with span('forward'):
            out = job.forward(dev)
        with span('loss'):
            loss = job.loss(out, dev)
    with span('backward'):
        loss.backward()
    with span('update'):
        job.trainer.step(1)          # the loss is already a mean
    return loss._data


def first_steps(job, steps):
    """Drive the compiled step through its first ``steps`` steps (the
    warm-up; step 1 compiles) and take the readings ``correct`` rests on.
    """
    import jax
    import numpy as np
    from chipbench import check
    losses = []
    for i in range(steps):
        loss = jax.block_until_ready(one_step(job, job.pool[i]))
        losses.append(float(np.asarray(loss)))
        if i == 0:
            raws, scale = job.first_gradient_raws()
            grad_norms = check.norms_of(raws, scale=scale,
                                        parts=job.leaf_parts())
            del raws
    now = job.param_raws()
    change = check.norms_of(now, minus=job.initial_raws(now),
                            parts=job.leaf_parts())
    return {'losses': losses, 'grad_norms': grad_norms,
            'change_norms': change}


def window(job, seconds, first_batch, trace_dir=None):
    """The measured loop. Never blocks on the step it has just
    dispatched: before step i it waits for the loss of step i - 2,
    stamps the clock and checks that loss. With ``trace_dir`` the
    profiler runs over the first seconds of it, and the time it takes to
    stop is not the window's."""
    import jax
    import numpy as np
    # what each batch of the pool is worth: real tokens, required FLOPs,
    # and those of the step's named parts that a kernel's roofline needs
    worth = [(job.tokens(b), job.step_flops(b), job.part_flops(b))
             for b in job.pool]
    pending = collections.deque()
    stamps, tokens, flops = [], 0, 0
    part_flops = collections.Counter()
    attempted = failed = 0
    paused = 0.0
    traced = None

    def settle():
        nonlocal failed
        loss = pending.popleft()
        with span('wait'):
            jax.block_until_ready(loss)
        stamps.append(time.perf_counter())
        if not np.isfinite(np.asarray(loss)):
            failed += 1

    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        whole = span('window')
        whole.__enter__()
    t0 = time.perf_counter()
    while True:
        if len(pending) == IN_FLIGHT:
            settle()
        elapsed = time.perf_counter() - t0 - paused
        if trace_dir and traced is None and attempted >= TRACE_MIN_STEPS \
                and elapsed >= TRACE_SECONDS:
            while pending:
                settle()
            whole.__exit__(None, None, None)
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            traced = {'steps': attempted, 'seconds': t_stop - t0,
                      'part_flops': dict(part_flops)}
            paused += time.perf_counter() - t_stop
            continue
        if elapsed >= seconds:
            break
        k = (first_batch + attempted) % len(job.pool)
        try:
            pending.append(one_step(job, job.pool[k]))
            tokens += worth[k][0]
            flops += worth[k][1]
            part_flops.update(worth[k][2])
        except Exception as e:           # a step that raises has failed;
            failed += 1                  # the run goes on and reports it
            print(f'step {attempted} raised {type(e).__name__}: {e}',
                  file=sys.stderr)
        attempted += 1
    while pending:
        settle()
    # and the last update has landed
    jax.block_until_ready(next(iter(job.param_raws().values())))
    t_end = time.perf_counter()
    if trace_dir and traced is None:
        whole.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced = {'steps': attempted, 'seconds': t_end - t0,
                  'part_flops': dict(part_flops)}
    return {'seconds': t_end - t0 - paused, 'stamps': stamps,
            'tokens': tokens, 'flops': flops, 'attempted': attempted,
            'failed': failed, 'traced': traced}


def counters(job, compiles):
    from mxnet_tpu import _bulk
    return {'compile_events': compiles.count,
            'net_compiles': job.net.compile_count,
            'bulk': _bulk.stats(),
            'fused_fallback': bool(job.trainer._fused_fallback_taken)}


def memory(devices):
    """Peak and limit of the fullest device, after the window."""
    stats = [d.memory_stats() or {} for d in devices]
    fullest = max(stats, key=lambda s: s.get('peak_bytes_in_use', 0))
    return {'peak_bytes': fullest.get('peak_bytes_in_use'),
            'limit_bytes': fullest.get('bytes_limit')}


def run_cell(cell, cfg, metrics, seed, seconds, trace, ctx, peaks):
    """Everything of a run after the look for a chip. ``metrics`` is
    ``{'end_to_end': [...], 'per_layer': [...]}``: the entries of
    BENCHMARK.json that this cell reports. Returns the result object."""
    import jax
    from chipbench import check, families, trace_reduce

    marks = {'start': time.perf_counter() - T_PROCESS}
    compiles = CompileLog()
    family = families.load(cfg['family'])
    job = family.Job(cfg, cell, seed, ctx)
    marks['built'] = time.perf_counter() - T_PROCESS

    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    with job.scope():
        got = first_steps(job, check.STEPS)
        before = counters(job, compiles)
        t_setup = time.perf_counter() - T_PROCESS
        win = window(job, seconds, check.STEPS, trace_dir)
    after = counters(job, compiles)
    mem = memory(jax.local_devices())
    run = {
        'cell': cell, 'config': cfg, 'peaks': peaks, 'chips': cell['chips'],
        'seconds_to_window': t_setup, 'window': win, 'memory': mem,
        'counters': {'before': before, 'after': after},
        'update_bytes': job.update_bytes(),
        'update_program': family.UPDATE_PROGRAM,
        'compile': {'count': compiles.count, 'seconds': compiles.seconds,
                    'cache_hits': compiles.cache_hits},
        # the readers find the profile there; it is parsed once
        'trace_dir': trace_dir,
        'trace': trace_reduce.reduce_dir(trace_dir, SPAN) if trace else None,
    }

    # the reference has the chip to itself: the peak is read, the
    # program's state goes first
    pool = job.pool[:check.STEPS]
    job.free()
    gc.collect()
    t_ref = time.perf_counter()
    numbers, where = check.compare(got, job.follow_reference(pool))
    table, ok = check.verdict(numbers, cell['limits'])
    ok = ok and win['failed'] == 0 and win['attempted'] > 0 \
        and not after['fused_fallback']

    group = 'layer_metrics' if trace else 'end_to_end'
    out, nothing_to_read = {}, []
    for m in metrics['per_layer' if trace else 'end_to_end']:
        value = reader(group, m['name'])(run)
        if value is None:
            nothing_to_read.append(m['name'])
        else:
            out[m['name']] = {'value': value, 'unit': m['unit']}
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': jax.device_count(),
              'memory_peak_bytes': mem['peak_bytes']}
    result = {'correct': ok, 'attempted': win['attempted'],
              'failed': win['failed'], 'metrics': out, 'device': device}
    if trace:
        device['busy_s'] = run['trace']['busy_s']
        device['window_s'] = run['trace']['window_s']
        result['breakdown'] = run['trace']['breakdown']
    # for whoever reads a run by hand; the driver ignores it
    where['left_out'] = len(where['left_out'])
    result['notes'] = {
        **notes_on(win, run['trace']), 'nothing_to_read': nothing_to_read,
        'compile': run['compile'],
        'reference_s': time.perf_counter() - t_ref, 'worst_leaf': where,
        'first_losses': got['losses'],
        'marks_to_window': {**marks, 'build_parts': job.timing}}
    result['check'] = table
    return result


def notes_on(win, trace):
    """How the window's steps were spread, and the traced part of it."""
    import numpy as np
    gaps_ms = np.diff(win['stamps']) * 1e3
    return {
        'steps_completed': len(win['stamps']), 'window_s': win['seconds'],
        'step_ms_p10_p50_p90_p99_max': [
            float(np.percentile(gaps_ms, q)) for q in (10, 50, 90, 99, 100)]
        if len(gaps_ms) else None,
        # scoped false: no operation of the window carried a scope of
        # the program, so a reader keyed on one finds nothing to read
        'traced': win['traced'] and {
            **win['traced'], 'longest_gaps': trace['longest_gaps'],
            'host_span_s': trace['host_span_s'],
            'scoped': trace['scoped'],
            'device0_scope_s': trace['devices'][0]['scope_s'],
            'device0_kernel_s': trace['devices'][0]['kernel_s']}}


def place_compile_cache():
    """JAX's persistent compilation cache where the program puts it
    (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache), for
    every program however small, so that only a cell's first run in a
    checkout compiles."""
    import jax
    from mxnet_tpu import _compile_cache
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    return _compile_cache.place()


def entries_for(bench, cell_name):
    """The metrics of BENCHMARK.json that this cell reports."""
    def mine(m):
        return 'workloads' not in m or cell_name in m['workloads']
    return {k: [m for m in bench[k] if mine(m)]
            for k in ('end_to_end', 'per_layer')}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, 'BENCHMARK.json')
    entry = next((w for w in bench['workloads']
                  if w['name'] == args.workload), None)
    if entry is None:
        print(f'no cell {args.workload!r} in BENCHMARK.json',
              file=sys.stderr)
        return 2
    cell, cfg = load_cell(args.workload)
    if cell['config'] != entry['config'] or cell['chips'] != entry['chips']:
        print(f'{args.workload}: its file and BENCHMARK.json disagree on '
              'config or chips', file=sys.stderr)
        return 2

    import jax
    devs = jax.devices()
    device = {'platform': devs[0].platform, 'kind': devs[0].device_kind,
              'count': len(devs)}
    if device['platform'] != 'tpu' or device['count'] != cell['chips']:
        print(json.dumps({
            'correct': False, 'device': device,
            'error': f'need {cell["chips"]} TPU chip(s); there is no CPU '
                     'fallback'}), file=sys.stderr)
        return 1
    peaks = load_peaks(device['kind'])

    import mxnet_tpu as mx
    place_compile_cache()
    result = run_cell(cell, cfg, entries_for(bench, args.workload),
                      args.seed, args.seconds, bool(args.trace),
                      mx.tpu(0), peaks)
    for name, e in result['check'].items():
        print(f'check {name}: {e["value"]:.6g} (limit {e["limit"]:g})',
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
