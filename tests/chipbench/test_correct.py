"""``correct`` has to come out false when it should. Each case drives a
whole run (all but the look for a chip) at a tiny size on the CPU, under
the cell's own limits, for every cell of BENCHMARK.json and the toy
family's (chipbench_tiny.py); the family is found from the cell:

* the control: the reference in bfloat16, put in the program's place;
* the timed path broken underneath: a step that leaves the parameters
  unchanged; half of the batch left out and the mean taken over the
  rest; for a cell on a mesh, the rows of one chip only, which is what a
  chip's gradient is when the exchange between chips is left out.

The readings of the same control and faults on the chip, at the cells'
own sizes, are in PERF.md section 2 (chipbench/calibrate.py makes them).
"""

import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from chipbench import check, run

from chipbench_tiny import (ALL_CELLS, PEAKS, load_bench, load_cell, tiny,
                            tiny_job)

BENCH = load_bench()
MESH_CELLS = [c for c in ALL_CELLS if load_cell(c)[0].get('mesh')]


def run_tiny(name, seed=9):
    cell, cfg = tiny(name)
    return run.run_cell(cell, cfg, run.entries_for(BENCH, name), seed, 0.3,
                        False, mx.cpu(0), PEAKS)


def keep_rows(monkeypatch, share):
    """The loss over the first ``share`` of the rows, the mean taken over
    them: the rest of the batch never reaches a gradient."""
    real = gluon.loss.SoftmaxCrossEntropyLoss.forward

    def forward(self, pred, label, sample_weight=None):
        loss = real(self, pred, label, sample_weight)
        n = loss.shape[0]
        keep = mx.np.arange(n) < int(n * share)
        return loss * keep.astype('float32') / share

    monkeypatch.setattr(gluon.loss.SoftmaxCrossEntropyLoss, 'forward',
                        forward)


@pytest.mark.parametrize('name', ALL_CELLS)
def test_sound_run_is_correct(name):
    r = run_tiny(name)
    assert r['correct'], r['check']


@pytest.mark.parametrize('name', ALL_CELLS)
def test_state_left_unchanged_is_not_correct(name, monkeypatch):
    real = gluon.Trainer.step

    def step(self, batch_size, **kw):
        self.set_learning_rate(0.0)
        return real(self, batch_size, **kw)

    monkeypatch.setattr(gluon.Trainer, 'step', step)
    r = run_tiny(name)
    assert not r['correct']
    assert r['check']['change_gap']['value'] == pytest.approx(1.0)


@pytest.mark.parametrize('name', ALL_CELLS)
def test_half_of_the_batch_left_out_is_not_correct(name, monkeypatch):
    keep_rows(monkeypatch, 0.5)
    r = run_tiny(name)
    assert not r['correct'], r['check']


@pytest.mark.parametrize('name', MESH_CELLS)
def test_exchange_between_chips_left_out_is_not_correct(name, monkeypatch):
    chips = load_cell(name)[0]['chips']
    keep_rows(monkeypatch, 1.0 / chips)
    r = run_tiny(name)
    assert not r['correct'], r['check']


@pytest.mark.parametrize('name', ALL_CELLS)
def test_bfloat16_control_is_not_correct(name):
    cell, _ = tiny(name)
    job = tiny_job(name, 4, mx.cpu(0))
    pool = job.pool[:check.STEPS]
    want = job.follow_reference(pool)
    control = job.follow_reference(pool, dtype='bfloat16')
    numbers, _ = check.compare(control, want)
    _, ok = check.verdict(numbers, cell['limits'])
    assert not ok, numbers
    # and the reference against itself is exact
    assert check.compare(want, want)[0] == {
        'loss_gap': 0.0, 'grad_gap': 0.0, 'change_gap': 0.0}


@pytest.mark.parametrize('name', ALL_CELLS)
def test_every_fault_keeps_a_row_of_the_cells_batch(name):
    for cell in (load_cell(name)[0], tiny(name)[0]):
        kept = check.fault_rows(cell)
        assert set(kept) == {'half_batch'} | (
            {'no_exchange'} if cell['chips'] > 1 else set())
        assert all(1 <= rows < cell['batch'] for rows in kept.values())


@pytest.mark.parametrize('batch, chips, fault', [
    (1, 1, 'half_batch'), (2, 4, 'no_exchange')])
def test_a_batch_that_leaves_a_fault_no_row_is_refused(batch, chips, fault):
    """calibrate.py compares against the rows a fault keeps; none is a
    NaN and no reading."""
    with pytest.raises(ValueError, match=f'the fault {fault} no row'):
        check.fault_rows({'batch': batch, 'chips': chips})


def test_a_number_that_is_not_finite_is_not_correct():
    limits = {'loss_gap': 1.0, 'grad_gap': 1.0, 'change_gap': 1.0}
    _, ok = check.verdict({'loss_gap': float('nan'), 'grad_gap': 0.0,
                           'change_gap': 0.0}, limits)
    assert not ok
    got = {'losses': [1.0], 'grad_norms': {'a': float('nan'), 'b': 1.0},
           'change_norms': {'a': 1.0, 'b': 1.0}}
    want = {'losses': [1.0], 'grad_norms': {'a': 1.0, 'b': 1.0},
            'change_norms': {'a': 1.0, 'b': 1.0}}
    numbers, where = check.compare(got, want)
    assert where['grad_gap'] == 'a' and not check.verdict(numbers, limits)[1]


def test_a_dead_leaf_is_left_out_of_the_change():
    want = {'losses': [1.0],
            'grad_norms': {'a': 1.0, 'b': 1.0, 'dead': 1e-9},
            'change_norms': {'a': 1.0, 'b': 1.0, 'dead': 1e-3}}
    got = {'losses': [1.0], 'grad_norms': dict(want['grad_norms']),
           'change_norms': {'a': 1.0, 'b': 1.0, 'dead': 0.5}}
    numbers, where = check.compare(got, want)
    assert where['left_out'] == ['dead']
    assert numbers['change_gap'] == 0.0
