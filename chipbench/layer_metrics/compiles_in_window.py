"""Graph capture: XLA compiles between the first timed dispatch and the
window's end (jax.monitoring events of the whole process, plus the
net's own compile_count). Expected 0."""


def read(run):
    a, b = run['counters']['after'], run['counters']['before']
    return (a['compile_events'] - b['compile_events']) + \
        (a['net_compiles'] - b['net_compiles'])
