"""Trainer (reference tests/python/unittest/test_gluon_trainer.py)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import assert_almost_equal


def _make_net():
    net = nn.Dense(1, in_units=2)
    net.initialize()
    return net


def test_trainer_basic_step():
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    w0 = net.weight.data().asnumpy().copy()
    x = mx.np.ones((4, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(4)
    assert not np.allclose(net.weight.data().asnumpy(), w0)


def test_trainer_learning_rate():
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    assert trainer.learning_rate == pytest.approx(0.1)
    trainer.set_learning_rate(0.2)
    assert trainer.learning_rate == pytest.approx(0.2)


def test_linear_regression_convergence():
    np.random.seed(3)
    true_w = np.array([[2.0], [-3.4]], dtype='float32')
    true_b = 4.2
    X = np.random.randn(256, 2).astype('float32')
    Y = (X @ true_w).ravel() + true_b
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    loss_fn = gluon.loss.L2Loss()
    data, label = mx.np.array(X), mx.np.array(Y)
    for _ in range(150):
        with autograd.record():
            l = loss_fn(net(data), label).mean()
        l.backward()
        trainer.step(1)
    assert float(l.asnumpy()) < 1e-3
    assert_almost_equal(net.weight.data().asnumpy().ravel(),
                        true_w.ravel(), rtol=0.05, atol=0.02)
    assert abs(float(net.bias.data().asnumpy()) - true_b) < 0.05


def test_trainer_states_roundtrip(tmp_path):
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'adam')
    x = mx.np.ones((2, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(2)
    f = str(tmp_path / 'trainer.states')
    trainer.save_states(f)
    trainer2 = gluon.Trainer(net.collect_params(), 'adam')
    trainer2.load_states(f)
    assert trainer2._optimizer.num_update == trainer._optimizer.num_update


def test_trainer_states_roundtrip_bit_identical_next_update(tmp_path):
    """load_states must restore EVERYTHING the next update depends on —
    adam slots, the global update counter, per-param counts, and the
    lr-scheduler's mutable state — so the restored trainer's next step
    is bit-identical to the original's (the elastic-resume contract;
    a lost num_update would silently reset adam bias correction and the
    lr schedule)."""
    def build():
        net = nn.Dense(2, in_units=3)
        net.initialize()
        sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5,
                                                base_lr=0.1)
        trainer = gluon.Trainer(net.collect_params(), 'adam',
                                {'learning_rate': 0.1,
                                 'lr_scheduler': sched})
        return net, trainer

    def step(net, trainer, s):
        x = mx.np.array(np.full((2, 3), 0.5 + s, dtype='float32'))
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(2)

    net1, tr1 = build()
    for s in range(4):                       # crosses a scheduler factor
        step(net1, tr1, s)
    f = str(tmp_path / 'tr.states')
    tr1.save_states(f)
    w_ckpt = {k: v.data().asnumpy().copy()
              for k, v in net1.collect_params().items()}

    net2, tr2 = build()
    for k, p in net2.collect_params().items():
        p.set_data(mx.np.array(w_ckpt[k]))
    tr2.load_states(f)
    assert tr2._optimizer.num_update == tr1._optimizer.num_update
    sch1 = tr1._optimizer.lr_scheduler
    sch2 = tr2._optimizer.lr_scheduler
    assert sch2.count == sch1.count
    assert sch2.base_lr == pytest.approx(sch1.base_lr)

    step(net1, tr1, 4)
    step(net2, tr2, 4)
    for k in w_ckpt:
        a = net1.collect_params()[k].data().asnumpy()
        b = net2.collect_params()[k].data().asnumpy()
        assert a.tobytes() == b.tobytes(), k


def test_trainer_load_states_accepts_legacy_tuple(tmp_path):
    """Pre-elastic state files pickled (states, num_update) — they must
    still load."""
    import pickle
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'adam')
    x = mx.np.ones((2, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.step(2)
    sd = trainer.state_dict()
    f = str(tmp_path / 'legacy.states')
    with open(f, 'wb') as fh:
        pickle.dump((sd['states'], sd['num_update']), fh)
    trainer2 = gluon.Trainer(net.collect_params(), 'adam')
    trainer2.load_states(f)
    assert trainer2._optimizer.num_update == trainer._optimizer.num_update


def test_trainer_with_kvstore_types():
    for kv in ('local', 'device', 'dist_sync'):
        net = _make_net()
        trainer = gluon.Trainer(net.collect_params(), 'sgd',
                                {'learning_rate': 0.01}, kvstore=kv)
        x = mx.np.ones((2, 2))
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(2)


def test_trainer_update_on_kvstore():
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1}, kvstore='local',
                            update_on_kvstore=True)
    x = mx.np.ones((2, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    w0 = net.weight.data().asnumpy().copy()
    trainer.step(2)
    assert not np.allclose(net.weight.data().asnumpy(), w0)


def test_trainer_allreduce_and_update_split():
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    x = mx.np.ones((2, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    trainer.allreduce_grads()
    trainer.update(2)


def test_bf16_cast_net_keeps_dtype_across_steps():
    """A bf16-cast net must still be bf16 after trainer.step — round-2
    regression: momentum math promoted weights to f32 after step 1,
    breaking the cached graph's dtype signature."""
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    net(mx.np.ones((1, 3)))
    net.cast('bfloat16')
    trainer = mx.gluon.Trainer(net.collect_params(), 'sgd',
                               {'learning_rate': 0.1, 'momentum': 0.9})
    x = mx.np.ones((2, 3), dtype='bfloat16')
    from mxnet_tpu import autograd
    for _ in range(3):
        with autograd.record():
            loss = (net(x).astype('float32') ** 2).sum()
        loss.backward()
        trainer.step(2)
    assert str(net.weight.data().dtype) == 'bfloat16'
    # per-param (non-fused) path too
    net2 = mx.gluon.nn.Dense(4, in_units=3)
    net2.initialize()
    net2(mx.np.ones((1, 3)))
    net2.cast('bfloat16')
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
    state = opt.create_state(0, net2.weight.data())
    g = mx.np.ones(net2.weight.shape, dtype='bfloat16')
    opt.update(0, net2.weight.data(), g, state)
    assert str(net2.weight.data().dtype) == 'bfloat16'


# ------------------------------------------------- the donating update
def _steps(net, trainer, n, x=None, batch=4):
    x = mx.np.ones((batch, 2)) if x is None else x
    for _ in range(n):
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(batch)


def _state_leaves(trainer):
    out = []
    for s in trainer._states.values():
        for e in (s if isinstance(s, (list, tuple)) else [s]):
            if e is not None:
                out.append(e)
    return out


@pytest.mark.parametrize('name,kwargs,slots', [
    ('sgd', {'learning_rate': 0.1}, 0),
    ('sgd', {'learning_rate': 0.1, 'momentum': 0.9}, 1),
    ('adam', {'learning_rate': 0.01}, 2),
])
def test_step_donates_the_weights_and_slots_it_replaces(name, kwargs,
                                                        slots):
    """After step() the arrays a parameter and its slots held before it
    are deleted (their buffers are the new values'), and the values are
    the parameter-by-parameter update's."""
    net, ref = _make_net(), _make_net()
    for p, q in zip(net.collect_params().values(),
                    ref.collect_params().values()):
        q.set_data(p.data())
    trainer = gluon.Trainer(net.collect_params(), name, dict(kwargs))
    opt = mx.optimizer.create(name, **kwargs)
    ref_params = list(ref.collect_params().values())
    ref_states = [opt.create_state_multi_precision(i, p.data())
                  for i, p in enumerate(ref_params)]
    x = mx.np.array(np.random.randn(4, 2).astype('float32'))
    for step in range(3):
        for n_, o_ in ((net, None), (ref, opt)):
            with autograd.record():
                loss = (n_(x) ** 2).sum()
            loss.backward()
        held = [p.data()._data for p in net.collect_params().values()] \
            + [e._data for e in _state_leaves(trainer)]
        trainer.step(4)
        opt.rescale_grad = 1.0 / 4
        for i, p in enumerate(ref_params):
            opt.update_multi_precision(i, p.data(), p.grad(),
                                       ref_states[i])
        assert len(held) == (2 if step == 0 else 2 * (1 + slots))
        assert all(a.is_deleted() for a in held), step
    assert len(_state_leaves(trainer)) == 2 * slots
    assert not trainer._fused_fallback_taken
    for p, q in zip(net.collect_params().values(), ref_params):
        # a few float32 ulp: XLA fuses the jitted update's arithmetic
        assert_almost_equal(p.data().asnumpy(), q.data().asnumpy(),
                            rtol=1e-5, atol=1e-6)


def _mlp():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation='relu'),
            nn.Dense(8, in_units=16))
    net.initialize()
    net.hybridize()
    return net


@pytest.mark.parametrize('dp', [None, 4])
def test_compiled_update_aliases_every_donated_operand(dp):
    """The claim machine-checked: every operand the fused update donates
    is in the compiled program's input_output_alias table, on one device
    and under a mesh once the layouts have settled."""
    import contextlib
    import warnings
    scope = mx.sharding.mesh(dp=dp) if dp else contextlib.nullcontext()
    with scope, warnings.catch_warnings():
        warnings.simplefilter('error')      # 'donated buffers not usable'
        net = _mlp()
        trainer = gluon.Trainer(net.collect_params(), 'adam',
                                {'learning_rate': 0.01})
        _steps(net, trainer, 3, x=mx.np.ones((8, 8)), batch=8)
        audit = trainer.audit_donation()
        if dp:
            shardings = {len(p.data()._data.sharding.device_set)
                         for p in net.collect_params().values()}
            assert shardings == {dp}
    n_params = len(net.collect_params())
    assert audit == {'donated_args': 3 * n_params,
                     'aliased_args': 3 * n_params}
    # the audit stepped nothing and deleted nothing
    assert trainer.optimizer.num_update == 3
    for p in net.collect_params().values():
        assert np.isfinite(p.data().asnumpy()).all()


class _PrevWeightSGD(mx.optimizer.Optimizer):
    """A state that is the weight's own array, as DCASGD's is."""

    def create_state(self, index, weight):
        return mx.nd.NDArray(weight._data)

    def step(self, w, g, state, lr, wd, t):
        new_w = w - lr * (self._prep(g) + 0.5 * (w - state._data))
        return new_w, new_w


def test_a_buffer_met_twice_is_not_donated():
    """Two parameters on one raw array, and a state that is the weight
    itself: XLA refuses a buffer that is donated and used again in one
    call, so the update passes those undonated and still steps."""
    from mxnet_tpu import telemetry
    net = _make_net()
    twin = _make_net()
    twin.weight.data()._rebind(net.weight.data()._data)
    params = list(net.collect_params().values()) \
        + list(twin.collect_params().values())
    trainer = gluon.Trainer(params, 'adam', {'learning_rate': 0.01})
    x = mx.np.ones((4, 2))
    telemetry.configure(enabled=True, sample=1.0)
    with telemetry.span('train.step'):
        with autograd.record():
            loss = (net(x) ** 2).sum() + (twin(x) ** 2).sum()
        loss.backward()
        shared = net.weight.data()._data
        trainer.step(4)
    launch = [e for e in telemetry.events()
              if e['name'] == 'mx.trainer.launch'][-1]['attrs']
    # four weights and eight slots; the shared weight's two occurrences
    # are passed as they are
    assert launch['n_out'] == 12 and launch['donated'] == 10
    assert not shared.is_deleted()
    assert_almost_equal(net.weight.data().asnumpy(),
                        twin.weight.data().asnumpy())
    assert net.weight.data()._data is not twin.weight.data()._data

    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), _PrevWeightSGD(
        learning_rate=0.1))
    w0 = net.weight.data().asnumpy()
    _steps(net, trainer, 2)
    assert not trainer._fused_fallback_taken
    assert not np.allclose(net.weight.data().asnumpy(), w0)


def test_backward_through_a_graph_whose_weights_were_stepped():
    """retain_graph, step, backward: the graph's weights were updated in
    place, and the error says so instead of 'Array has been deleted'."""
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    x = mx.np.ones((4, 2))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward(retain_graph=True)
    trainer.step(4)
    with pytest.raises(mx.MXNetError, match='updated in place'):
        loss.backward()


@pytest.mark.parametrize('hybridize', [False, True])
def test_two_backwards_before_one_step_accumulate(hybridize):
    """Nothing is donated until the update: gradient accumulation over a
    retained graph works as before."""
    net = _make_net()
    if hybridize:
        net.hybridize()
    for p in net.collect_params().values():
        p.grad_req = 'add'
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    x = mx.np.array(np.random.randn(4, 2).astype('float32'))
    _steps(net, trainer, 1, x=x)     # past the first step's set-up
    for p in net.collect_params().values():
        p.zero_grad()
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward(retain_graph=True)
    once = net.weight.grad().asnumpy().copy()
    loss.backward()
    assert_almost_equal(net.weight.grad().asnumpy(), 2 * once)
    w0 = net.weight.data().asnumpy()
    trainer.step(4)
    assert_almost_equal(net.weight.data().asnumpy(),
                        w0 - 0.1 * 2 * once / 4, rtol=1e-5, atol=1e-6)


def test_copyto_a_weight_gives_it_a_buffer_of_its_own():
    """x.copyto(w.data()) copies (the reference's does): on the same
    device device_put would hand the weight x's own buffer, which the
    step then deletes under its owner, and two weights filled from one
    x would share a buffer and lose donation."""
    net, other = _make_net(), _make_net()
    x = mx.np.array([[0.5, -0.25]])
    x.copyto(net.weight.data())
    x.copyto(other.weight.data())
    where = [a._data.unsafe_buffer_pointer()
             for a in (x, net.weight.data(), other.weight.data())]
    assert len(set(where)) == 3
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    _steps(net, trainer, 2)
    assert_almost_equal(x.asnumpy(), np.array([[0.5, -0.25]], 'float32'))
    assert_almost_equal(other.weight.data().asnumpy(), x.asnumpy())


def test_set_data_leaves_the_callers_array_alone():
    """set_data copies (the reference's does): a step neither deletes
    nor rebinds the caller's array, and one array given to two
    parameters makes two buffers."""
    net, other = _make_net(), _make_net()
    x = mx.np.array([[0.5, -0.25]])
    net.weight.set_data(x)
    other.weight.set_data(x)
    assert net.weight.data() is not x
    assert net.weight.data()._data is not other.weight.data()._data
    trainer = gluon.Trainer(
        list(net.collect_params().values())
        + list(other.collect_params().values()), 'sgd',
        {'learning_rate': 0.1})
    for _ in range(2):
        with autograd.record():
            loss = (net(mx.np.ones((4, 2))) ** 2).sum() \
                + (other(mx.np.ones((4, 2))) ** 2).sum()
        loss.backward()
        trainer.step(4)
    assert_almost_equal(x.asnumpy(), np.array([[0.5, -0.25]], 'float32'))
    assert not np.allclose(net.weight.data().asnumpy(), x.asnumpy())


@pytest.mark.parametrize('handle', ['detach', 'copy', 'asnumpy'])
def test_a_held_handle_to_a_weight_after_a_step(handle):
    """copy() and asnumpy() are the caller's own and keep the value;
    detach() shares the weight's buffer, which the step wrote over: a
    clear error on use, never a silently stale value."""
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    _steps(net, trainer, 1)
    before = net.weight.data().asnumpy().copy()
    held = getattr(net.weight.data(), handle)()
    _steps(net, trainer, 1)
    if handle == 'detach':
        with pytest.raises(mx.MXNetError, match='buffer was donated'):
            held.asnumpy()
    else:
        assert_almost_equal(np.asarray(held), before)


@pytest.mark.parametrize('pending', ['weight', 'slot', 'forward'])
def test_a_lazy_read_pending_in_the_bulking_engine_sees_the_old_value(
        pending):
    """The bulking engine (on by default on the TPU, forced here) keeps
    a pending segment's concrete inputs by their raw arrays and launches
    with them only at its flush. The donating update is a write sync
    point: a read of a weight or slot that is still lazy when step()
    runs yields the pre-step value (the reference's read-before-write
    order), not JAX's 'Array has been deleted' at a later flush."""
    from mxnet_tpu import engine
    net = _make_net()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1, 'momentum': 0.9})
    _steps(net, trainer, 1)
    w0 = net.weight.data().asnumpy().copy()
    m0 = trainer._states[0].asnumpy().copy()
    x = mx.np.ones((4, 2))
    with engine.bulk(100):
        if pending == 'forward':
            # an eager forward with no backward() before update(): the
            # gradients are those of the step before
            lazy = net(x)
            want = x.asnumpy() @ w0.T + net.bias.data().asnumpy()
            trainer.update(4)
        else:
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            if pending == 'weight':
                lazy, want = (net.weight.data() ** 2).sum(), (w0 ** 2).sum()
            else:
                lazy, want = trainer._states[0] * 2, m0 * 2
            assert lazy._lazy is not None and lazy._lazy.value is None
            trainer.step(4)
        got = lazy.asnumpy()
    assert_almost_equal(got, want, rtol=1e-5, atol=1e-6)
    assert not np.allclose(net.weight.data().asnumpy(), w0)


@pytest.mark.parametrize('async_save', [False, True])
def test_elastic_snapshot_of_a_sharded_param_outlives_the_step(
        tmp_path, async_save):
    """ElasticTrainer keeps a parameter that is sharded over several
    devices on the device for the checkpoint writer: a copy, since the
    next step donates the parameter's own buffers."""
    from mxnet_tpu.parallel.checkpoint import SharedCheckpointManager
    from mxnet_tpu.train import ElasticTrainer
    with mx.sharding.mesh(dp=4):
        net = _mlp()
        params = dict(net.collect_params())
        trainer = gluon.Trainer(params, 'adam', {'learning_rate': 0.01})
        x = mx.np.ones((8, 8))
        _steps(net, trainer, 2, x=x, batch=8)
        name, p = next((n, p) for n, p in params.items()
                       if len(p.data()._data.sharding.device_set) > 1)
        et = ElasticTrainer(params, trainer,
                            SharedCheckpointManager(str(tmp_path)),
                            name=f'donate{int(async_save)}',
                            async_save=async_save)
        saved = {n: q.data().asnumpy().copy() for n, q in params.items()}
        tree = et.snapshot(2)
        live = p.data()._data
        assert tree['params'][name] is not live
        _steps(net, trainer, 1, x=x, batch=8)
        assert live.is_deleted()
        et._manager.save(2, tree) if not async_save else (
            et._daemon.submit(2, tree), et.flush(timeout=60))
        assert not np.allclose(p.data().asnumpy(), saved[name])
        assert et.restore() == 2
        for n, q in params.items():
            np.testing.assert_array_equal(saved[n], q.data().asnumpy())
        assert trainer.optimizer.num_update == 2
        et.close()


# ------------------------------------- one device in one process: no kvstore
def _odd_net(ctx=None, shape=(7, 13)):
    net = nn.Dense(shape[0], in_units=shape[1])
    net.initialize(ctx=ctx)
    return net


def _n_live(shape):
    import gc
    import jax
    gc.collect()
    return sum(1 for a in jax.live_arrays()
               if a.shape == shape and not a.is_deleted())


@pytest.mark.parametrize('kv', ['device', 'local', 'nccl'])
def test_one_device_trainer_makes_no_kvstore(kv):
    """The reference's rule (``_create_kvstore``): one context under a
    store name without ``dist`` gets no kvstore, and the weights are
    those of ``kvstore=None``."""
    net, ref = _odd_net(), _odd_net()
    for p, q in zip(net.collect_params().values(),
                    ref.collect_params().values()):
        q.set_data(p.data())
    kwargs = {} if kv == 'device' else {'kvstore': kv}   # the default
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1}, **kwargs)
    none = gluon.Trainer(ref.collect_params(), 'sgd',
                         {'learning_rate': 0.1}, kvstore=None)
    x = mx.np.array(np.random.randn(4, 13).astype('float32'))
    for n_, t_ in ((net, trainer), (ref, none)):
        _steps(n_, t_, 3, x=x)
    assert trainer._kv_initialized
    assert trainer._kvstore is None
    assert trainer._update_on_kvstore is False
    for p, q in zip(net.collect_params().values(),
                    ref.collect_params().values()):
        np.testing.assert_array_equal(p.data().asnumpy(),
                                      q.data().asnumpy())


def test_one_device_trainer_holds_no_second_copy_of_a_weight():
    """The first step() leaves as many arrays of the weight's shape alive
    as it found (the weight and its gradient): no store's copy beside
    them. The shape is one nothing else in the process holds."""
    shape = (11, 17)
    net = _odd_net(shape=shape)
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    x = mx.np.ones((4, shape[1]))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    del loss
    before = _n_live(shape)
    trainer.step(4)
    assert before == 2
    assert _n_live(shape) == before


@pytest.mark.parametrize('case', ['update_on_kvstore', 'instance',
                                  'dist_sync', 'two_contexts'])
def test_a_kvstore_is_kept_where_one_is_asked_for(case):
    """``update_on_kvstore=True``, a ``KVStoreBase`` instance, a ``dist*``
    name or several contexts get the store they got before."""
    from mxnet_tpu.kvstore import KVStoreLocal, KVStoreTPUSync
    kwargs = {
        'update_on_kvstore': dict(kvstore='local', update_on_kvstore=True),
        'instance': dict(kvstore=mx.kvstore.create('local')),
        'dist_sync': dict(kvstore='dist_sync'),
        'two_contexts': {},
    }[case]
    ctxs = [mx.cpu(0), mx.cpu(1)] if case == 'two_contexts' else [mx.cpu(0)]
    net = _odd_net(ctx=ctxs)
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1}, **kwargs)
    w0 = net.weight.data(ctxs[0]).asnumpy().copy()
    xs = [mx.np.ones((2, 13), ctx=c) * (i + 1) for i, c in enumerate(ctxs)]
    with autograd.record():
        losses = [net(x).sum() for x in xs]
    for l in losses:
        l.backward()
    trainer.step(2 * len(ctxs))
    want = KVStoreTPUSync if case == 'dist_sync' else KVStoreLocal
    assert isinstance(trainer._kvstore, want), type(trainer._kvstore)
    if case == 'instance':
        assert trainer._kvstore is kwargs['kvstore']
    assert trainer._update_on_kvstore is (case == 'update_on_kvstore')
    got = [d.asnumpy() for d in net.weight.list_data()]
    assert not np.allclose(got[0], w0)
    # every replica holds the same new weight: the store's pushpull
    # handed each the merged gradient
    for g in got[1:]:
        np.testing.assert_array_equal(got[0], g)
