"""The harness end to end on the CPU at tiny sizes: every cell of
BENCHMARK.json yields every metric it names, the command refuses without
a TPU, and every name resolves to a file. Device numbers read here are
thrown away: nothing from a CPU run is a measurement."""

import importlib
import json
import os
import re

import pytest

import mxnet_tpu as mx
from chipbench import run, trace_reduce

from chipbench_tiny import (CELLS, PEAKS, ROOT, load_bench, small_trace,
                            tiny)

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
BENCH = load_bench()


@pytest.fixture
def hand_trace(monkeypatch):
    """A CPU trace has no device plane; the reduction is given the
    hand-built one, the profiler still runs round the window."""
    monkeypatch.setattr(trace_reduce, 'reduce_dir',
                        lambda d, prefix: trace_reduce.reduce(small_trace()))


@pytest.mark.parametrize('name', CELLS)
def test_cell_yields_its_end_to_end_metrics(name):
    cell, cfg = tiny(name)
    want = run.entries_for(BENCH, name)
    r = run.run_cell(cell, cfg, want, 2 ** 31 + 11, 0.5, False,
                     mx.cpu(0), PEAKS)
    assert r['correct'], r['check']
    assert r['attempted'] > 0 and r['failed'] == 0
    assert set(r['metrics']) == {m['name'] for m in want['end_to_end']}
    assert all(v['value'] > 0 for v in r['metrics'].values())
    assert list(r)[-1] == 'check'
    assert set(r['device']) >= {'platform', 'kind', 'count',
                                'memory_peak_bytes'}
    assert r['device']['platform'] == 'cpu'        # and says so
    json.dumps(r)


@pytest.mark.parametrize('name', CELLS)
def test_cell_yields_its_per_layer_metrics(name, hand_trace):
    cell, cfg = tiny(name)
    want = run.entries_for(BENCH, name)
    r = run.run_cell(cell, cfg, want, 5, 0.5, True, mx.cpu(0), PEAKS)
    # no memory_stats() on the CPU: that reader finds nothing and is
    # left out, every other metric of the cell is there
    names = {m['name'] for m in want['per_layer']} - {'peak_hbm_share'}
    assert set(r['metrics']) == names
    assert r['device']['busy_s'] > 0 and r['device']['window_s'] > 0
    assert len(r['breakdown']['device_ops']) <= 10
    assert len(r['breakdown']['idle_gaps']) <= 10
    assert r['metrics']['compiles_in_window']['value'] == 0


def test_without_a_tpu_the_command_refuses(capsys):
    rc = run.main(['--workload', CELLS[0], '--seed', '1', '--seconds', '1',
                   '--trace', '0'])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ''                                # no result
    said = json.loads(err.strip().splitlines()[-1])
    assert said['correct'] is False
    assert said['device']['platform'] == 'cpu'


def test_an_unknown_cell_is_an_error(capsys):
    assert run.main(['--workload', 'no.such_cell', '--seed', '1',
                     '--seconds', '1']) != 0
    assert capsys.readouterr().out == ''


def test_an_unknown_device_kind_has_no_peaks():
    assert run.load_peaks('TPU v5 lite')['flops_per_s'] == 197e12
    with pytest.raises(KeyError, match='no peaks'):
        run.load_peaks('cpu')


def test_every_peak_names_its_source():
    for kind, p in run.load_json(run.HERE, 'peaks.json').items():
        assert p['source'], kind


def _named():
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        for e in BENCH[group]:
            yield group, e


@pytest.mark.parametrize('group, entry', list(_named()),
                         ids=lambda v: v['name'] if isinstance(v, dict)
                         else v)
def test_names_and_units_are_well_formed(group, entry):
    assert NAME.match(entry['name'])
    if 'unit' in entry:
        assert UNIT.match(entry['unit'])
        assert entry['better'] in ('lower', 'higher')
    for key in ('config', 'traffic'):
        if key in entry:
            assert NAME.match(entry[key])
    for key in entry.get('reduced', []):
        assert NAME.match(key)


@pytest.mark.parametrize('name', CELLS)
def test_cell_resolves_to_its_files(name):
    entry = next(w for w in BENCH['workloads'] if w['name'] == name)
    cell, cfg = run.load_cell(name)
    assert cell['config'] == entry['config']
    assert cell['chips'] == entry['chips']
    assert name == f'{entry["config"]}.{entry["traffic"]}'
    conf = next(c for c in BENCH['configs'] if c['name'] == entry['config'])
    assert os.path.samefile(os.path.join(ROOT, conf['file']), os.path.join(
        run.HERE, 'configs', cell['config'] + '.json'))
    for key in conf['reduced']:
        assert key in cfg['changed']
    for kind in ('families', 'reference', 'flops'):
        importlib.import_module(f'chipbench.{kind}.{cfg["family"]}')
    assert set(cell['limits']) == {'loss_gap', 'grad_gap', 'change_gap'}
    assert len(entry['why']) <= 200 and entry['why'] == cell['why']


@pytest.mark.parametrize('group, metric', [
    (g, m['name']) for g in ('end_to_end', 'per_layer')
    for m in BENCH[g]])
def test_metric_resolves_to_its_reader(group, metric):
    folder = 'layer_metrics' if group == 'per_layer' else group
    assert os.path.isfile(os.path.join(run.HERE, folder, metric + '.py'))
    assert callable(run.reader(folder, metric))


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m['name'] for m in BENCH['end_to_end']}
    for m in BENCH['per_layer']:
        assert m['moves'] in e2e
        for w in m.get('workloads', []):
            assert w in CELLS


def test_run_py_names_no_cell_family_or_metric():
    with open(os.path.join(run.HERE, 'run.py')) as f:
        text = f.read()
    names = [e['name'] for _, e in _named()] + ['bert']
    assert [n for n in names if n in text] == []
