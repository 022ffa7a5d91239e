"""Eager engine (_bulk.py): segments flushed a step. Under a mesh eager
ops are not bulked and this reads 0: every eager op is its own launch."""


def read(run):
    a, b = run['counters']['after'], run['counters']['before']
    steps = run['window']['attempted']
    if not steps:
        return None
    return (a['bulk']['flushes'] - b['bulk']['flushes']) / steps
