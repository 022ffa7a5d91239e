"""Eager engine (_bulk.py): eager ops a step that the engine was offered
and did not take, each a launch of its own (_bulk.stats()['unbulked']):
every eager op under a mesh or with bulking off, none on one chip."""


def read(run):
    a, b = run['counters']['after'], run['counters']['before']
    steps = run['window']['attempted']
    if not steps or 'unbulked' not in a['bulk']:
        return None
    return (a['bulk']['unbulked'] - b['bulk']['unbulked']) / steps
