"""``mx.profiler`` — tracing/profiling.

Reference: ``python/mxnet/profiler.py`` over ``src/profiler/`` (chrome-trace
JSON, aggregate stats). TPU design: delegate to ``jax.profiler`` — traces
are written in the TensorBoard/XPlane format (viewable in Perfetto just like
the reference's chrome traces), and ``dumps()`` reports per-op aggregate
stats from a lightweight host-side recorder.
"""

import contextlib
import time

import jax

from .telemetry import trace as _trace
from .telemetry.metrics import Histogram as _Histogram

_config = {'profile_all': False, 'filename': '/tmp/mxnet_tpu_profile',
           'running': False, 'ops': False, 'memory': False}
# scoped host timings, aggregated at record time: name -> [count,
# total_s] — bounded by the number of distinct scope names (the old
# per-event list grew by one tuple per scope() forever)
_records = {}
# name -> [count, total_s, min_s, max_s, out_bytes, hist]; ``hist`` is
# a telemetry Histogram (fixed log-scale buckets, bounded memory)
# feeding the percentile columns
_op_stats = {}
_mem_stats = {'peak_live_bytes': 0}
_analysis_reports = {}   # graph name -> mx.analysis.AnalysisReport
_cost_reports = {}       # graph name -> mx.analysis.CostReport
_serving = {}            # server name -> stats-snapshot provider (mx.serve)
_checkpoint = {}         # trainer name -> stats-snapshot provider (mx.train)


def percentiles(samples, qs=(50, 95, 99)):
    """Nearest-rank percentiles of a latency sample set, as
    ``{q: value}``. Shared between the per-op table and the Serving
    section (``mx.serve`` metrics use the same estimator so the two
    surfaces agree).

    Accepts any iterable (lists, generators, numpy arrays — whose
    truthiness is ambiguous and used to raise here). Empty input
    yields all-zero percentiles; a single sample reports itself for
    every ``q``."""
    s = sorted(float(x) for x in samples)
    if not s:
        return {q: 0.0 for q in qs}
    if len(s) == 1:
        return {q: s[0] for q in qs}
    return {q: s[min(len(s) - 1, int(round(q / 100.0 * (len(s) - 1))))]
            for q in qs}


def set_config(profile_all=False, profile_symbolic=True,
               profile_imperative=True, profile_memory=True, profile_api=True,
               filename='/tmp/mxnet_tpu_profile', aggregate_stats=False,
               **kwargs):
    """Reference profiler.py set_config → MXSetProcessProfilerConfig.

    ``profile_imperative``/``profile_all`` arm per-op aggregate stats:
    every imperative dispatch is timed to completion (a sync per op —
    the reference recommends NaiveEngine for accurate per-op numbers,
    and this is the same trade) and tallied into the ``dumps()`` table.
    ``profile_memory`` additionally tracks live device bytes per op
    (≙ storage_profiler.h).
    """
    _config.update(profile_all=profile_all, filename=filename,
                   ops=bool(profile_all or profile_imperative),
                   memory=bool(profile_memory))


def set_state(state='stop', profile_process='worker'):
    if state == 'run':
        start()
    else:
        stop()


def start(profile_process='worker'):
    if not _config['running']:
        jax.profiler.start_trace(_config['filename'])
        _config['running'] = True


def stop(profile_process='worker'):
    if _config['running']:
        jax.profiler.stop_trace()
        _config['running'] = False


def pause(profile_process='worker'):
    stop()


def resume(profile_process='worker'):
    start()


def dump(finished=True, profile_process='worker'):
    stop()


def _is_profiling_ops():
    return _config['running'] and _config['ops']


import threading as _threading

_stats_lock = _threading.Lock()


def record_op(name, dt, out_bytes):
    """Called by the dispatch layer (ops/registry.py) when op profiling
    is armed — the aggregate_stats.cc tally. Locked: DataLoader worker
    threads dispatch ops concurrently."""
    with _stats_lock:
        s = _op_stats.get(name)
        if s is None:
            s = [0, 0.0, dt, dt, 0, _Histogram()]
            _op_stats[name] = s
        s[0] += 1
        s[1] += dt
        s[2] = min(s[2], dt)
        s[3] = max(s[3], dt)
        s[4] += out_bytes
        s[5].observe(dt)
        if _config['memory']:
            # O(1) allocator peak where the backend exposes it (TPU
            # does); a per-op live_arrays() walk would be O(live
            # buffers) per call. Under the stats lock so a concurrent
            # dumps(reset=True) cannot interleave with the update.
            try:
                stats = jax.devices()[0].memory_stats()
                peak = int((stats or {}).get('peak_bytes_in_use', 0))
                if peak > _mem_stats['peak_live_bytes']:
                    _mem_stats['peak_live_bytes'] = peak
            except Exception:
                pass


def attach_serving(name, provider):
    """Register a serving-stats snapshot provider (``mx.serve`` servers
    call this at construction) so ``dumps()`` shows a Serving section
    next to the op table. ``provider`` is a zero-arg callable returning
    the stats dict; it stays registered across ``dumps(reset=True)`` —
    the server owns its counters' lifetime, not the profiler."""
    with _stats_lock:
        _serving[name] = provider


def detach_serving(name):
    """Drop a serving provider (called from ``Server.close()``)."""
    with _stats_lock:
        _serving.pop(name, None)


def attach_checkpoint(name, provider):
    """Register a checkpoint-stats snapshot provider
    (``mx.train.ElasticTrainer`` calls this at construction) so
    ``dumps()`` shows a Checkpoint section — most importantly the
    per-step blocking time of the async snapshot path, the number the
    CheckFreq-style pipeline exists to keep small."""
    with _stats_lock:
        _checkpoint[name] = provider


def detach_checkpoint(name):
    """Drop a checkpoint provider (called from ``ElasticTrainer.close()``)."""
    with _stats_lock:
        _checkpoint.pop(name, None)


def attach_analysis(name, report):
    """Attach a graph-sanitizer report (``mx.analysis``) so ``dumps()``
    shows static findings next to the runtime numbers —
    ``hybridize(check=True)`` calls this after its first-compile lint.
    Latest report per graph name wins."""
    with _stats_lock:
        _analysis_reports[name] = report


def attach_cost(name, cost):
    """Attach an analytical roofline cost report
    (``mx.analysis.CostReport``) so ``dumps()`` shows predicted
    FLOPs/bytes/peak-HBM next to the measured numbers —
    ``hybridize(check=True)`` computes one per compiled graph unless
    ``MXNET_ANALYSIS_COSTS=0``. Latest report per graph name wins."""
    with _stats_lock:
        _cost_reports[name] = cost


def dumps(reset=False):
    """Aggregate statistics table (reference ``mx.profiler.dumps()`` over
    ``src/profiler/aggregate_stats.cc``): per-op count / total / avg /
    p50 / p95 / p99 latency + output bytes, then scoped host timings,
    then the memory summary, then the serving section (``mx.serve``),
    then any attached graph-analysis summaries."""
    lines = ['Profile Statistics:']
    if _op_stats:
        lines.append('Operator summary (imperative dispatch, synced '
                     'per call):')
        lines.append(f'{"Name":<32}{"Count":>8}{"Total(ms)":>12}'
                     f'{"Avg(ms)":>10}{"p50(ms)":>10}{"p95(ms)":>10}'
                     f'{"p99(ms)":>10}{"Out(MB)":>10}')
        for name, (c, t, _lo, _hi, nb, hist) in sorted(
                _op_stats.items(), key=lambda kv: -kv[1][1]):
            pct = hist.percentiles()
            lines.append(f'{name:<32}{c:>8}{t * 1e3:>12.3f}'
                         f'{t / c * 1e3:>10.3f}{pct[50] * 1e3:>10.3f}'
                         f'{pct[95] * 1e3:>10.3f}{pct[99] * 1e3:>10.3f}'
                         f'{nb / 1e6:>10.2f}')
    if _records:
        lines.append('Scoped host timings:')
        lines.append(f'{"Name":<40}{"Count":>8}{"Total(ms)":>12}')
        for name, (c, t) in sorted(_records.items(),
                                   key=lambda kv: -kv[1][1]):
            lines.append(f'{name:<40}{c:>8}{t * 1e3:>12.3f}')
    if _config['memory'] and _mem_stats['peak_live_bytes']:
        lines.append(f'Peak live device memory: '
                     f'{_mem_stats["peak_live_bytes"] / 1e6:.2f} MB')
    if _serving:
        lines.append('Serving (mx.serve):')
        for name, provider in sorted(_serving.items()):
            try:
                snap = provider()
            except Exception:    # a closed/broken server must not kill dumps
                continue
            lines.append(
                f'  {name}: requests={snap.get("requests", 0)} '
                f'completed={snap.get("completed", 0)} '
                f'shed={snap.get("shed", 0)} '
                f'expired={snap.get("expired", 0)} '
                f'batches={snap.get("batches", 0)} '
                f'occupancy={snap.get("occupancy_avg", 0.0):.2f}')
            lat = snap.get('latency_ms', {})
            qt = snap.get('queue_ms', {})
            if lat or qt:
                lines.append(
                    f'    latency_ms p50/p95/p99: '
                    f'{lat.get(50, 0.0):.3f}/{lat.get(95, 0.0):.3f}/'
                    f'{lat.get(99, 0.0):.3f}   queue_ms p50/p95/p99: '
                    f'{qt.get(50, 0.0):.3f}/{qt.get(95, 0.0):.3f}/'
                    f'{qt.get(99, 0.0):.3f}')
    if _checkpoint:
        lines.append('Checkpoint (mx.train):')
        for name, provider in sorted(_checkpoint.items()):
            try:
                snap = provider()
            except Exception:   # a closed trainer must not kill dumps
                continue
            lines.append(
                f'  {name}: saves={snap.get("saves", 0)} '
                f'async={snap.get("async_saves", 0)} '
                f'coalesced={snap.get("coalesced", 0)} '
                f'errors={snap.get("errors", 0)} '
                f'last_step={snap.get("last_step", -1)}')
            lines.append(
                f'    blocked_ms avg/max: '
                f'{snap.get("blocked_ms_avg", 0.0):.3f}/'
                f'{snap.get("blocked_ms_max", 0.0):.3f}   '
                f'serialize_ms avg/max: '
                f'{snap.get("serialize_ms_avg", 0.0):.3f}/'
                f'{snap.get("serialize_ms_max", 0.0):.3f}')
    if _analysis_reports:
        lines.append('Graph analysis (mx.analysis):')
        for name, report in sorted(_analysis_reports.items()):
            lines.append(f'  {report.summary()}')
            for f in report.findings:
                lines.append(f'    [{f.severity}] {f.rule}: {f.message}')
    if _cost_reports:
        lines.append('Cost (mx.analysis.costs, static roofline):')
        for name, cost in sorted(_cost_reports.items()):
            lines.append(f'  {cost.summary()}')
    try:
        from .analysis import race as _race
    except ImportError:         # partial install / early interpreter exit
        _race = None
    if _race is not None and _race.enabled():
        lines.append('Concurrency (mx.analysis.race):')
        lines.append(f'  {_race.summary_line()}')
        for f in _race.report().findings:
            loc = f' @ {f.location}' if f.location else ''
            lines.append(f'    [{f.severity}] {f.rule}: {f.message}{loc}')
    if reset:
        # under the stats lock: DataLoader worker threads may be mid-
        # record_op while the main thread resets between epochs
        with _stats_lock:
            _records.clear()
            _op_stats.clear()
            _mem_stats['peak_live_bytes'] = 0
            _analysis_reports.clear()
            _cost_reports.clear()
    return '\n'.join(lines)


def memory_summary(device=None):
    """Device memory snapshot (reference storage_profiler.h GPU memory
    profiler): allocator stats where the backend exposes them, plus the
    live-buffer aggregate."""
    dev = device or jax.devices()[0]
    out = {'device': str(dev)}
    try:
        stats = dev.memory_stats()
        if stats:
            out.update({k: int(v) for k, v in stats.items()
                        if isinstance(v, (int, float))})
    except Exception:
        pass
    try:
        live = [a for a in jax.live_arrays()]
        out['live_buffers'] = len(live)
        out['live_bytes'] = sum(int(a.nbytes) for a in live)
    except Exception:
        pass
    out['peak_live_bytes'] = _mem_stats['peak_live_bytes']
    return out


def _record(name, dt):
    with _stats_lock:
        r = _records.get(name)
        if r is None:
            _records[name] = [1, dt]
        else:
            r[0] += 1
            r[1] += dt


@contextlib.contextmanager
def scope(name='<unk>:'):
    """Reference profiler.scope — also an event of a running
    ``jax.profiler`` trace, beside the device's operations, through the
    span primitive ``mx.telemetry`` uses; the tally takes that span's
    one duration."""
    with _trace.profiler_span(name) as s:
        yield
    _record(name, s.seconds)


class Task:
    def __init__(self, name, domain=None):
        self.name = name
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is not None:
            _record(self.name, time.perf_counter() - self._t0)


Frame = Task
Event = Task


class Counter:
    def __init__(self, name, domain=None, value=0):
        self.name = name
        self.value = value

    def set_value(self, value):
        self.value = value

    def increment(self, delta=1):
        self.value += delta

    def decrement(self, delta=1):
        self.value -= delta


class Marker:
    def __init__(self, name, domain=None):
        self.name = name

    def mark(self, scope='process'):
        _record(self.name, 0.0)


def server_annotation(*a, **kw):
    """TensorBoard server-side annotations — jax.profiler owns the server."""
