"""The recorded forward writes its residuals into the last backward's
spent ones (gluon/block.py ``_VjpPrograms``, ``_Vjp.spent``): a backward
without ``retain_graph`` hands the call's residual buffers back to its
entry, and the entry's next recorded forward takes them donated. What a
call may not recycle (``retain_graph``, a second forward before the
backward, a forward never differentiated, ``remat``) allocates, and
every route gives the same numbers."""

import contextlib
import gc
import weakref

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.telemetry import trace as _trace

from test_cached_vjp import (CASES, _grads, _loss, _step, _twin, _x,
                             per_step_vjp)


def _programs(net):
    (entry,) = [e for e in net._cached_graph._compiled.values()
                if e.vjp is not None and e.vjp.treedef is not None]
    return entry.vjp


def _ready(make, shape, **hybridize):
    net = make()
    net.initialize()
    net(_x(shape))
    net.hybridize(**hybridize)
    return net


@pytest.fixture
def recorder():
    telemetry.configure(enabled=True, sample=1.0)
    telemetry.clear()
    yield telemetry
    telemetry.configure(enabled=_trace._env_enabled(),
                        buffer=_trace._env_buffer(),
                        sample=_trace._env_sample())
    telemetry.clear()


def _launch(recorder, net, x, **backward):
    recorder.clear()
    with recorder.span('train.step'):
        _step(net, x, **backward)
    (attrs,) = [e['attrs'] for e in recorder.events()
                if e['name'] == 'mx.graph.launch']
    return attrs


@pytest.fixture
def backend_compiles():
    """How many programs the backend compiles from here on."""
    seen = []

    def listen(name, *_, **__):
        if 'backend_compile' in name:
            seen.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listen)


# ------------------------------------------------------ the spares engage
@pytest.mark.parametrize('case', ['dense', 'batchnorm_donated_aux',
                                  'null_leaf', 'two_outputs_both'])
def test_from_the_second_step_the_forward_writes_over_the_last_residuals(
        recorder, case):
    make, shape, hybridize, reads = CASES[case]
    net = _ready(make, shape, **hybridize)
    first = _launch(recorder, net, _x(shape))
    programs = _programs(net)
    assert programs.recycles and programs.n_res > 0
    # the entry's first call finds no spares: it allocates them
    assert first['recycled'] == 0
    assert first['residuals'] == programs.n_res
    for step in range(1, 4):
        spent = programs.spares
        assert len(spent) == programs.n_res
        got = _launch(recorder, net, _x(shape, seed=step))
        assert got['recycled'] == got['residuals'] == programs.n_res
        assert all(r.is_deleted() for r in spent)     # donated
    assert net.vjp_trace_count == 1


def test_the_launch_of_an_unrecorded_call_carries_no_residuals(recorder):
    net = _ready(*CASES['dense'][:2])
    recorder.clear()
    with recorder.span('infer'):
        net(_x((5, 6)))
    (attrs,) = [e['attrs'] for e in recorder.events()
                if e['name'] == 'mx.graph.launch']
    assert 'residuals' not in attrs and 'recycled' not in attrs


# ------------------------------------------------------ the same numbers
def _train(net, trainer, xs, **backward):
    losses, grads = [], []
    for x in xs:
        with autograd.record():
            loss = _loss(net(x), None)
        loss.backward(**backward)
        trainer.step(x.shape[0])
        losses.append(loss.asnumpy())
        grads.append(_grads(net))
    return losses, grads


def test_three_trainer_steps_are_bitwise_those_that_cannot_recycle():
    """Recycling, ``retain_graph=True`` on every step (the spares are
    never handed back) and eager autograd: the same losses and gradients
    to the bit."""
    shape = (5, 6)
    recycling, retained = _twin(CASES['dense'][0], shape)
    eager = CASES['dense'][0]()
    eager.initialize()
    eager(_x(shape))
    for p, q in zip(recycling.collect_params().values(),
                    eager.collect_params().values()):
        q.set_data(p.data().copy())
    xs = [_x(shape, seed=s) for s in range(3)]
    runs = []
    for net, backward in ((recycling, {}), (retained, {'retain_graph': True}),
                          (eager, {})):
        trainer = gluon.Trainer(net.collect_params(), 'adam',
                                {'learning_rate': 1e-2})
        runs.append(_train(net, trainer, xs, **backward))
    assert _programs(recycling).recycled == _programs(recycling).n_res
    assert _programs(retained).recycled == 0
    for losses, grads in runs[1:]:
        for got, want in zip(runs[0][0], losses):
            assert np.array_equal(got, want)
        for got, want in zip(runs[0][1], grads):
            assert got.keys() == want.keys()
            for k in want:
                assert np.array_equal(got[k], want[k]), k


def test_retain_graph_twice_recycles_nothing_and_gives_equal_gradients(
        recorder):
    net = _ready(*CASES['dense'][:2])
    x = _x((5, 6))
    programs = None
    for step in range(3):
        with autograd.record():
            loss = _loss(net(x), None)
        programs = programs or _programs(net)
        loss.backward(retain_graph=True)
        first = _grads(net)
        loss.backward(retain_graph=True)
        assert programs.spares is None
        assert programs.recycled == 0
        for k, g in _grads(net).items():
            assert np.array_equal(g, first[k]), k
    # a backward that lets the graph go hands its residuals back
    _step(net, x)
    assert len(programs.spares) == programs.n_res
    assert _launch(recorder, net, x)['recycled'] == programs.n_res


def test_two_forwards_before_one_backward_both_differentiate():
    shape = (5, 6)
    new, old = _twin(CASES['dense'][0], shape)
    _step(new, _x(shape))               # spares for the first forward
    programs = _programs(new)
    for net, route in ((new, None), (old, per_step_vjp)):
        with route() if route else contextlib.nullcontext():
            with autograd.record():
                a = _loss(net(_x(shape, seed=1)), None)
                took = programs.recycled if net is new else None
                b = _loss(net(_x(shape, seed=2)), None)
                both = a + 2 * b
            both.backward()
        if net is new:
            # the first took the spares, the second found none
            assert took == programs.n_res and programs.recycled == 0
            # each handed its residuals back; one set is kept
            assert len(programs.spares) == programs.n_res
    for k, g in _grads(old).items():
        np.testing.assert_allclose(_grads(new)[k], g, rtol=1e-6, atol=1e-6,
                                   err_msg=k)


def test_a_forward_never_differentiated_leaks_no_spares():
    net = _ready(*CASES['dense'][:2])
    x = _x((5, 6))
    _step(net, x)
    programs = _programs(net)
    with autograd.record():
        out = net(x)                    # takes the spares, no backward
    assert programs.spares is None
    node = out._ag.node
    kept = [weakref.ref(r) for r in node.vjp_fn.residuals]
    del out, node
    gc.collect()
    assert programs.spares is None
    assert all(ref() is None for ref in kept)
    # the next call allocates and the loop goes on recycling
    _step(net, x)
    _step(net, x)
    assert programs.recycled == programs.n_res


def test_a_dropped_net_takes_its_spares_with_it_under_the_bulking_engine():
    """A loss in bulked eager ops is a segment whose plan the engine
    caches for good; the plan keeps its ops' functions, and none of them
    may hold the NDArray it was called on (through its tape node that
    would hold the graph, its weights and the entry's spares)."""
    net = _ready(*CASES['dense'][:2])
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-2})
    with mx.engine.bulk(1000):
        _train(net, trainer, [_x((5, 6), seed=s) for s in range(3)])
    programs = _programs(net)
    assert programs.recycled == programs.n_res
    kept = [weakref.ref(programs)] + [weakref.ref(r) for r in programs.spares]
    del net, trainer, programs
    gc.collect()
    assert all(ref() is None for ref in kept)


@pytest.mark.parametrize('case', ['remat', 'remat_batchnorm'])
def test_under_remat_nothing_is_recycled_or_donated(recorder, case):
    make, shape, hybridize, _ = CASES[case]
    net = _ready(make, shape, **hybridize)
    for step in range(3):
        x = _x(shape, seed=step)
        attrs = _launch(recorder, net, x)
        programs = _programs(net)
        assert programs.n_res == 0 and not programs.recycles
        assert attrs['residuals'] == attrs['recycled'] == 0
        assert programs.spares is None
        assert not x._data.is_deleted()
    assert net.vjp_trace_count == 1


# -------------------------------------------------------------- under a mesh
def test_under_a_mesh_the_spares_alias_and_no_entry_compiles_twice(
        recorder, backend_compiles):
    shape = (8, 6)
    new, old = _twin(CASES['dense'][0], shape)
    with mx.sharding.mesh(dp=4, devices=jax.devices()[:4]):
        for step in range(4):
            before = len(backend_compiles)
            attrs = _launch(recorder, new, _x(shape, seed=step))
            programs = _programs(new)
            if step:
                assert attrs['recycled'] == programs.n_res > 0
                assert all(r.is_deleted() for r in spent)
                # the steady step compiles nothing
                assert len(backend_compiles) == before
            spent = programs.spares
            assert all(len(r.sharding.device_set) == 4 for r in spent)
            with per_step_vjp():
                _step(old, _x(shape, seed=step))
            for k, g in _grads(old).items():
                np.testing.assert_allclose(_grads(new)[k], g, rtol=1e-6,
                                           atol=1e-6, err_msg=k)
        assert new.vjp_trace_count == 1 and new.compile_count == 1


def test_weights_moved_to_another_device_leave_the_spent_set_behind():
    """The spares lie where the last call ran: after ``reset_ctx`` the
    next call allocates on the weights' new device, and the loop then
    recycles there."""
    shape = (5, 6)
    net = _ready(*CASES['dense'][:2])
    _step(net, _x(shape))
    _step(net, _x(shape))
    programs = _programs(net)
    net.reset_ctx(mx.cpu(1))
    x = mx.np.array(np.random.default_rng(3).normal(0, 1, shape)
                    .astype('float32'), ctx=mx.cpu(1))
    _step(net, x)
    assert programs.recycled == 0
    _step(net, x)
    assert programs.recycled == programs.n_res
    assert all(r.devices() == {jax.devices()[1]} for r in programs.spares)
    eager = CASES['dense'][0]()
    eager.initialize(ctx=mx.cpu(1))
    eager(x)
    for p, q in zip(net.collect_params().values(),
                    eager.collect_params().values()):
        q.set_data(p.data().copy())
    _step(eager, x)
    for k, g in _grads(eager).items():
        np.testing.assert_allclose(_grads(net)[k], g, rtol=1e-6, atol=1e-6,
                                   err_msg=k)
