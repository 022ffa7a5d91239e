"""Sharding: device time a step in all-gather / all-reduce /
reduce-scatter / all-to-all operations. Worst device."""

from . import worst_device


def read(run):
    steps = run['trace']['steps']
    got = worst_device(run, lambda d: d['collective_s'] or None)
    return got / steps * 1e3 if got and steps else None
