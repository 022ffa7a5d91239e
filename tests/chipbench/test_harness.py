"""The harness end to end on the CPU at tiny sizes: every cell of
BENCHMARK.json, and the toy family's, yields every metric it names, the
command refuses without a TPU, every name resolves to a file, and a
family that lacks a piece of its contract is told which. Device numbers
read here are thrown away: nothing from a CPU run is a measurement."""

import importlib
import json
import os
import re

import pytest

import mxnet_tpu as mx
from chipbench import families, run, trace_reduce

from chipbench_tiny import (ALL_CELLS, CELLS, PEAKS, ROOT, family_of,
                            load_bench, load_cell, small_trace, tiny)

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
BENCH = load_bench()


@pytest.fixture
def hand_device(monkeypatch):
    """A CPU trace has no device plane and the CPU no memory_stats(): the
    reduction is given the hand-built trace (its operations carry kernels
    and scopes) and the memory reading a hand-built one; the profiler
    still runs round the window and the readers of the program's spans
    read what it wrote."""
    monkeypatch.setattr(trace_reduce, 'reduce_dir',
                        lambda d, prefix: trace_reduce.reduce(small_trace()))
    monkeypatch.setattr(run, 'memory', lambda devices: {
        'peak_bytes': 3 * 2 ** 30, 'limit_bytes': 16 * 2 ** 30})


@pytest.mark.parametrize('name', ALL_CELLS)
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


@pytest.mark.parametrize('name', ALL_CELLS)
def test_cell_yields_its_per_layer_metrics(name, hand_device):
    cell, cfg = tiny(name)
    want = run.entries_for(BENCH, name)
    r = run.run_cell(cell, cfg, want, 5, 0.5, True, mx.cpu(0), PEAKS)
    # every metric of the cell, none excused by name
    assert set(r['metrics']) == {m['name'] for m in want['per_layer']}
    assert r['notes']['nothing_to_read'] == []
    assert all(v['value'] >= 0 for v in r['metrics'].values())
    shares = [m['name'] for m in want['per_layer'] if m['unit'] == '%']
    assert all(r['metrics'][n]['value'] > 0 for n in shares)
    assert r['device']['busy_s'] > 0 and r['device']['window_s'] > 0
    assert len(r['breakdown']['device_ops']) <= 10
    assert len(r['breakdown']['idle_gaps']) <= 10
    assert r['metrics']['compiles_in_window']['value'] == 0


def _traced(trace):
    """What a reader of the device seam takes of a run."""
    return {'trace': trace_reduce.reduce(trace), 'chips': 1, 'peaks': PEAKS,
            'window': {'traced': {'part_flops': {'attention': 1e6}}}}


def _without_scopes(trace, keep=lambda op: False):
    for dev in trace['devices'].values():
        dev['ops'] = [op if keep(op) else (*op[:4], None)
                      for op in dev['ops']]
    return trace


def test_attention_is_read_by_scope_and_by_kernel_where_names_are_stale():
    device_ms = run.reader('layer_metrics', 'attention_device_ms')
    roofline = run.reader('layer_metrics', 'attention_roofline')
    # by scope: device 0's 5000 ns under mx.attention over two steps, and
    # 1e6 FLOP at 1e12 FLOP/s are 1000 ns of them
    scoped = _traced(small_trace())
    assert device_ms(scoped) == pytest.approx(2.5e-3)
    assert roofline(scoped) == pytest.approx(20.0)
    # no operation carries a scope: the flash kernels by name, which is
    # not all of attention, so no roofline
    stale = _traced(_without_scopes(small_trace()))
    assert device_ms(stale) == pytest.approx(1.5e-3)
    assert roofline(stale) is None
    # scopes are there and attention's is not: nothing, never 0.0
    other = _traced(_without_scopes(
        small_trace(), keep=lambda op: op[4] != 'mx.attention'))
    assert other['trace']['scoped']
    assert device_ms(other) is None and roofline(other) is None
    # neither a scope nor a kernel of that name
    bare = small_trace()
    for dev in bare['devices'].values():
        dev['ops'] = [(*op[:3], None, None) for op in dev['ops']]
    assert device_ms(_traced(bare)) is None


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
    # the families' names as the configurations' files give them
    named = {load_cell(c)[1]['family'] for c in ALL_CELLS}
    assert len(named) > 1
    names = [e['name'] for _, e in _named()] + sorted(named)
    assert [n for n in names if n in text] == []


@pytest.mark.parametrize('name', sorted(
    {load_cell(c)[1]['family'] for c in ALL_CELLS}))
@pytest.mark.parametrize('piece', families.MODULE + tuple(
    f'Job.{k}' for k in families.JOB))
def test_a_family_without_a_piece_of_the_contract_is_told_which(
        name, piece, monkeypatch):
    family = families.load(name)
    owner, _, attr = piece.rpartition('.')
    monkeypatch.delattr(family.Job if owner else family, attr)
    with pytest.raises(NotImplementedError, match=(
            rf'{name}\W+ lacks {re.escape(piece)}: .*families/__init__')):
        families.load(name)
