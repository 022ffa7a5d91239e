"""The record of launches behind the ``ahead`` attribute of the train
path's launch spans (``mxnet_tpu._bulk.LaunchRecord``, ISSUE 39): what
counts as still queued on the device, what counts as finished, that it
keeps nothing alive, and that a step nobody listens to records
nothing."""

import gc

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _bulk, autograd, gluon, telemetry
from mxnet_tpu.telemetry import trace as _trace


class Output:
    """A launch's output as the record sees it: whether the device has
    made it, whether it was deleted. ``is_ready()`` on a deleted array
    kills the process on the CPU client, so here it fails the test."""

    def __init__(self, ready=False, deleted=False):
        self.ready = ready
        self.deleted = deleted

    def is_ready(self):
        assert not self.deleted, 'is_ready() asked of a deleted array'
        return self.ready

    def is_deleted(self):
        return self.deleted


@pytest.fixture
def record():
    return _bulk.LaunchRecord()


def test_a_launch_whose_output_is_not_ready_counts(record):
    first, second = Output(), Output()
    assert record.note(first) == 0          # nothing was queued
    assert record.note(second) == 1
    assert record.note(None) == 2           # and nothing more to watch


def test_a_launch_whose_output_is_ready_does_not(record):
    first, second = Output(), Output()
    record.note(first)
    record.note(second)
    first.ready = True
    assert record.note(None) == 1
    second.ready = True
    assert record.note(None) == 0
    assert len(record) == 0                 # the finished are dropped


def test_a_collected_output_counts_as_finished_and_is_not_kept(record):
    out = Output()
    record.note(out)
    del out
    gc.collect()
    assert record.note(None) == 0


def test_a_deleted_output_counts_as_finished_unasked(record):
    record.note(Output(deleted=True))
    assert record.note(None) == 0


def test_a_donated_array_counts_as_finished():
    """The real thing on the CPU client: a buffer donated to a jitted
    call reads ``is_deleted()``; asked ``is_ready()`` the process would
    die."""
    record = _bulk.LaunchRecord()
    x = jnp.ones((256, 256))
    record.note(x)
    y = jax.jit(lambda a: a * 2, donate_argnums=0)(x)
    assert x.is_deleted()
    assert record.note(y) == 0
    y.block_until_ready()
    assert record.note(None) == 0


def test_at_most_eight_launches_are_watched(record):
    outs = [Output() for _ in range(12)]
    got = [record.note(o) for o in outs]
    assert got == [0, 1, 2, 3, 4, 5, 6, 7, 8, 8, 8, 8]
    assert len(record) == _bulk.LaunchRecord.WATCHED == 8
    # the oldest went first: the newest eight are the ones left
    for o in outs[4:]:
        o.ready = True
    assert record.note(None) == 0


def test_a_launchs_attributes_on_the_cpu():
    """``ahead`` always; ``in_use`` only where the client keeps memory
    statistics, which the CPU's does not."""
    attrs = _bulk.launch_attrs(jnp.ones(3) + 1)
    assert set(attrs) == {'ahead'}
    assert jax.devices()[0].memory_stats() is None
    # nothing to watch: no array, or a value being traced
    before = len(_bulk._launches)
    assert set(_bulk.launch_attrs(None)) == {'ahead'}
    jax.jit(lambda a: _bulk.launch_attrs(a)['ahead'] + a)(jnp.ones(2))
    assert len(_bulk._launches) <= before


def test_in_use_is_the_fullest_device():
    class Device:
        def __init__(self, used):
            self.used = used

        def memory_stats(self):
            return None if self.used is None else {'bytes_in_use': self.used}

    assert _bulk.bytes_in_use([Device(5), Device(9), Device(7)]) == 9
    assert _bulk.bytes_in_use([Device(None), Device(3)]) == 3
    assert _bulk.bytes_in_use([Device(None)]) is None
    assert _bulk.bytes_in_use(jax.devices()[:1]) is None        # the CPU


@pytest.fixture
def _listening():
    telemetry.configure(enabled=True, sample=1.0)
    telemetry.clear()
    yield
    telemetry.configure(enabled=_trace._env_enabled(),
                        buffer=_trace._env_buffer(),
                        sample=_trace._env_sample())
    telemetry.clear()


def test_a_step_nobody_listens_to_records_no_launch(monkeypatch,
                                                    _listening):
    net = gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 0.01})
    x = mx.np.ones((2, 3))
    calls = []
    monkeypatch.setattr(_bulk, 'launch_attrs',
                        lambda out: calls.append(out) or {})

    def step():
        with autograd.record():
            loss = (net(x) ** 2).mean()
        loss.backward()
        trainer.step(1)
        return loss

    with _bulk.force(True):
        for _ in range(3):
            step().asnumpy()
        assert calls == []
        with telemetry.span('train.step', step=0):
            step()
    # the forward, the loss's segment, the two vjps, the update
    assert len(calls) == 5
