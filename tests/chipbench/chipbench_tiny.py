"""What the tests of chipbench/ share: the cells they run over, each
cell's family and its sizes for the CPU, and the hand-built trace.

The cells are those of BENCHMARK.json (``CELLS``) and, for every test
that is generic over families, the cell of the toy family under ``toy/``
as well (``ALL_CELLS``). ``toy/`` is laid out as ``chipbench/`` is
(``configs/``, ``workloads/``, ``families/``, ``reference/``, ``flops/``)
and is found through the same names: nothing here or in a test names a
family, or a key of a configuration.
"""

import json
import os

import chipbench.families
import chipbench.flops
import chipbench.reference
from chipbench import families, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, 'toy')

# the toy family's modules are found as chipbench's own are
for _package in (chipbench.families, chipbench.reference, chipbench.flops):
    _there = os.path.join(TOY, _package.__name__.rsplit('.', 1)[-1])
    if _there not in _package.__path__:
        _package.__path__.append(_there)


def load_bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


CELLS = [w['name'] for w in load_bench()['workloads']]
TOY_CELLS = sorted(f[:-len('.json')]
                   for f in os.listdir(os.path.join(TOY, 'workloads')))
ALL_CELLS = CELLS + TOY_CELLS
PEAKS = {'flops_per_s': 1e12, 'hbm_bytes_per_s': 1e11}     # not a device's


def load_cell(name):
    """(cell, config) as their files have them: ``run.load_cell`` for a
    cell of the benchmark, the files under ``toy/`` for the toy's."""
    if name not in TOY_CELLS:
        return run.load_cell(name)
    cell = run.load_json(TOY, 'workloads', name + '.json')
    return cell, run.load_json(TOY, 'configs', cell['config'] + '.json')


def family_of(name):
    """The family of the cell ``name``, found as ``run.run_cell`` finds
    it: by the name in its configuration's file."""
    return families.load(load_cell(name)[1]['family'])


def tiny(name):
    """(cell, config) of a cell at a size a test can hold: the sizes are
    its family's to choose."""
    return family_of(name).tiny(*load_cell(name))


def tiny_job(name, seed, ctx):
    """A job of the cell ``name`` at its tiny size."""
    cell, cfg = tiny(name)
    return family_of(name).Job(cfg, cell, seed, ctx)


def small_trace():
    """trace_small.json as ``trace_reduce.load`` would give it: an
    operation's kernel is read off its name, as ``load`` reads it."""
    with open(os.path.join(HERE, 'trace_small.json')) as f:
        raw = json.load(f)
    devices = {}
    for n, dev in raw['devices'].items():
        devices[int(n)] = {
            'modules': [tuple(e) for e in dev['modules']],
            'async': [tuple(e) for e in dev['async']],
            'ops': [(name, s, e, trace_reduce.kernel_of(name), scope)
                    for name, s, e, scope in dev['ops']]}
    return {'devices': devices,
            'spans': {k: [tuple(e) for e in v]
                      for k, v in raw['spans'].items()},
            'host': [(name, s, e, line, {})
                     for name, s, e, line in raw['host']],
            'scopes_read': True}
