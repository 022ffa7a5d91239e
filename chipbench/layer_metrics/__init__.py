"""One reader for each per-layer metric of BENCHMARK.json, found by the
metric's name: ``read(run) -> number or None``. A reader that finds
nothing to read returns None and the metric is left out of the line.
``run['trace']`` is ``trace_reduce.reduce``'s result (a --trace 1 run),
``run['counters']`` the program's counters before and after the window.
"""


def span_ms_per_step(run, phase):
    """Host time inside the benchmark's ``phase`` span, a step."""
    t = run['trace']
    if not t['steps'] or phase not in t['host_span_s']:
        return None
    return t['host_span_s'][phase] / t['steps'] * 1e3


def worst_device(run, value):
    """The largest ``value(device)`` over the traced devices that have
    one."""
    got = [v for v in map(value, run['trace']['devices']) if v is not None]
    return max(got) if got else None
