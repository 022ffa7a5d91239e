"""Sharding: the part of the collectives' device time during which no
other operation runs on that device. Worst device."""

from . import worst_device


def read(run):
    steps = run['trace']['steps']
    if worst_device(run, lambda d: d['collective_s'] or None) is None:
        return None
    got = worst_device(run, lambda d: d['collective_exposed_s'])
    return got / steps * 1e3 if steps else None
