"""A compiled graph's vjp is built once an entry (ISSUE 32): a recorded
call of a hybridized block in train mode launches two jitted programs,
the recorded forward and its backward (gluon/block.py ``_VjpPrograms``),
where every step used to run ``jax.vjp`` through the jitted forward. The
gradients are those of the per-step route, the counter says when a vjp
was traced, and what is an argument of the forward is not handed back by
it."""

import contextlib

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import block as _block, nn
from mxnet_tpu.telemetry import trace as _trace


@contextlib.contextmanager
def per_step_vjp():
    """Entries built inside have no programs of their own, so that
    ``apply_op`` takes ``jax.vjp`` of the jitted forward on every call:
    the route before ISSUE 32, to compare with."""
    kept = _block._VjpPrograms
    _block._VjpPrograms = lambda *args, **kwargs: None
    try:
        yield
    finally:
        _block._VjpPrograms = kept


class TwoHeads(nn.HybridBlock):
    def __init__(self):
        super().__init__()
        self.body = nn.Dense(12, activation='tanh', in_units=6)
        self.a = nn.Dense(3, in_units=12)
        self.b = nn.Dense(5, in_units=12)

    def forward(self, x):
        h = self.body(x)
        return self.a(h), self.b(h)


class ReadsAFrozenLeaf(nn.HybridBlock):
    """A leaf the forward only reads (``grad_req='null'``, handed back as
    it came) beside BatchNorm's statistics, which it writes."""

    def __init__(self):
        super().__init__()
        self.dense = nn.Dense(8, in_units=6)
        self.norm = nn.BatchNorm(in_channels=8)
        self.offset = gluon.Parameter('offset', shape=(8,), init='ones',
                                      grad_req='null')

    def forward(self, x):
        return self.norm(self.dense(x)) * self.offset.data()


def _dense():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation='relu', in_units=6), nn.Dense(4))
    return net


def _batchnorm():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, 3, padding=1, in_channels=2), nn.BatchNorm(),
            nn.Activation('relu'), nn.Flatten(), nn.Dense(3))
    return net


CASES = {
    # name: (net, input shape, hybridize arguments, which outputs the
    # loss reads)
    'dense': (_dense, (5, 6), {}, None),
    'batchnorm_donated_aux': (_batchnorm, (4, 2, 6, 6), {}, None),
    'batchnorm_no_static_alloc': (_batchnorm, (4, 2, 6, 6),
                                  {'static_alloc': False}, None),
    'remat': (_dense, (5, 6), {'remat': True}, None),
    'remat_batchnorm': (_batchnorm, (4, 2, 6, 6), {'remat': True}, None),
    'null_leaf': (ReadsAFrozenLeaf, (5, 6), {}, None),
    'two_outputs_one_cotangent': (TwoHeads, (5, 6), {}, 0),
    'two_outputs_both': (TwoHeads, (5, 6), {}, None),
}


def _x(shape, seed=0):
    return mx.np.array(np.random.default_rng(seed).normal(0, 1, shape)
                       .astype('float32'))


def _twin(make, shape, **hybridize):
    """Two nets with the same weights: the first takes the built
    programs, the second the per-step ``jax.vjp``."""
    nets = []
    for _ in range(2):
        net = make()
        net.initialize()
        net(_x(shape))
        nets.append(net)
    new, old = nets
    for p, q in zip(new.collect_params().values(),
                    old.collect_params().values()):
        q.set_data(p.data().copy())
    new.hybridize(**hybridize)
    old.hybridize(**hybridize)
    return new, old


def _loss(out, reads):
    if isinstance(out, tuple):
        out = [out[reads]] if reads is not None else list(out)
        return sum((o ** 2).sum() for o in out)
    return (out ** 2).sum()


def _step(net, x, reads=None, **backward):
    with autograd.record():
        loss = _loss(net(x), reads)
    loss.backward(**backward)
    return loss


def _grads(net):
    return {k: p.grad().asnumpy() for k, p in net.collect_params().items()
            if p.grad_req != 'null'}


def _state(net):
    return {k: p.data().asnumpy() for k, p in net.collect_params().items()
            if p.grad_req == 'null'}


def _same(got, want, exact=False):
    assert got.keys() == want.keys()
    for k in want:
        if exact:
            assert np.array_equal(got[k], want[k]), k
        else:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6,
                                       atol=1e-6, err_msg=k)


# ----------------------------------------------------------- the gradients
@pytest.mark.parametrize('case', sorted(CASES))
def test_gradients_equal_the_per_step_vjp(case):
    make, shape, hybridize, reads = CASES[case]
    new, old = _twin(make, shape, **hybridize)
    for step in range(3):       # the tracing call, then two launches
        x = _x(shape, seed=step)
        got = _step(new, x, reads)
        with per_step_vjp():
            want = _step(old, x, reads)
        np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-6)
        _same(_grads(new), _grads(old))
        # BatchNorm's running statistics, written by the same program
        _same(_state(new), _state(old))
    assert new.vjp_trace_count == 1 and old.vjp_trace_count == 0
    assert any(np.any(g) for g in _grads(new).values())


def test_the_dense_gradients_are_bitwise_those_of_eager_autograd():
    """No second net: the block un-hybridized differentiates op by op."""
    net = _dense()
    net.initialize()
    x = _x((5, 6))
    _step(net, x)
    want = _grads(net)
    net.hybridize()
    for _ in range(2):
        _step(net, x)
        _same(_grads(net), want, exact=True)


def test_an_output_without_cotangent_gives_zero_gradients_to_its_head():
    new, _ = _twin(TwoHeads, (5, 6))
    for _ in range(2):
        _step(new, _x((5, 6)), reads=0)
        grads = _grads(new)
        assert not grads['b.weight'].any() and not grads['b.bias'].any()
        assert grads['a.weight'].any() and grads['body.weight'].any()


def test_grad_req_add_accumulates_over_two_backwards():
    new, old = _twin(_dense, (5, 6))
    for net in (new, old):
        net.collect_params().setattr('grad_req', 'add')
    x = _x((5, 6))
    _step(new, x)
    once = _grads(new)
    _step(new, x)
    with per_step_vjp():
        _step(old, x)
        _step(old, x)
    _same(_grads(new), _grads(old))
    _same(_grads(new), {k: 2 * g for k, g in once.items()})
    assert new.vjp_trace_count == 1


def test_retain_graph_calls_the_backward_program_twice():
    new, old = _twin(_dense, (5, 6))
    x = _x((5, 6))
    _step(new, x)                       # builds the programs
    with autograd.record():
        loss = _loss(new(x), None)
    loss.backward(retain_graph=True)
    first = _grads(new)
    loss.backward()                     # nothing was donated into it
    _same(_grads(new), first, exact=True)
    with per_step_vjp():
        _step(old, x)
    _same(first, _grads(old))


def test_create_graph_replays_fn_through_the_tape():
    """Second order: d/dx of |dL/dx|^2 of the hybridized block equals
    that of the same block run eagerly."""
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation='tanh', in_units=6), nn.Dense(1))
    net.initialize()

    def second_order():
        x = _x((5, 6))
        x.attach_grad()
        with autograd.record():
            y = net(x).sum()
            (dx,) = autograd.grad(y, [x], create_graph=True)
            z = (dx ** 2).sum()
        z.backward()
        return dx.asnumpy(), x.grad.asnumpy()

    want = second_order()
    net.hybridize()
    for _ in range(2):
        got = second_order()
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)
        assert np.any(got[1])


def test_predict_record_mode_defers_the_vjp_as_before():
    net = _batchnorm()
    net.initialize()
    x = _x((4, 2, 6, 6))
    with autograd.record(train_mode=False):
        loss = _loss(net(x), None)
    loss.backward()
    want = _grads(net)
    net.hybridize()
    for _ in range(2):
        with autograd.record(train_mode=False):
            loss = _loss(net(x), None)
        loss.backward()
        _same(_grads(net), want)
    # an entry of predict mode has no programs to build
    assert net.vjp_trace_count == 0


def test_a_float_input_gets_its_gradient_and_an_integer_one_none():
    class Embeds(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.table = nn.Embedding(10, 4)
            self.out = nn.Dense(2, in_units=4)

        def forward(self, ids, scale):
            return self.out(self.table(ids).mean(axis=1) * scale)

    net = Embeds()
    net.initialize()
    ids = mx.np.array(np.arange(6).reshape(2, 3).astype('int32'))
    scale = _x((2, 1))
    scale.attach_grad()

    def grads():
        with autograd.record():
            loss = (net(ids, scale) ** 2).sum()
        loss.backward()
        return dict(_grads(net), scale=scale.grad.asnumpy())

    want = grads()
    net.hybridize()
    for _ in range(2):
        _same(grads(), want)
    assert want['scale'].any()


# ------------------------------------------------------------- the counter
def test_vjp_trace_count_rises_once_an_entry():
    net = _dense()
    net.initialize()
    net(_x((5, 6)))
    net.hybridize()
    assert net.vjp_trace_count == 0
    net(_x((5, 6)))                     # not recorded: no vjp
    assert net.vjp_trace_count == 0 and net.compile_count == 1
    _step(net, _x((5, 6)))
    assert net.vjp_trace_count == 1
    for step in range(5):
        _step(net, _x((5, 6), seed=step))
    assert net.vjp_trace_count == 1
    _step(net, _x((7, 6)))              # a new input shape, a new entry
    assert net.vjp_trace_count == 2
    for step in range(2):
        _step(net, _x((7, 6), seed=step))
        _step(net, _x((5, 6), seed=step))
    assert net.vjp_trace_count == 2
    assert net.compile_count == 3       # predict, and train at two shapes


def test_vjp_trace_count_sums_the_children():
    outer = nn.Sequential()             # a plain Block round two graphs
    outer.add(_dense(), nn.Dense(2, in_units=4))
    outer.initialize()
    outer(_x((5, 6)))
    outer.hybridize()
    for _ in range(3):
        _step(outer, _x((5, 6)))
    assert outer.vjp_trace_count == 2
    assert outer[0].vjp_trace_count == 1


def test_a_child_inside_a_parents_trace_builds_nothing():
    net = _dense()
    net.initialize()
    net(_x((5, 6)))
    net.hybridize()
    for _ in range(2):
        _step(net, _x((5, 6)))
    # recording is off inside the parent's trace: the children inline
    assert [c.vjp_trace_count for c in net._children.values()] == [0, 0]
    assert net.vjp_trace_count == 1


@pytest.fixture
def recorder():
    telemetry.configure(enabled=True, sample=1.0)
    telemetry.clear()
    yield telemetry
    telemetry.configure(enabled=_trace._env_enabled(),
                        buffer=_trace._env_buffer(),
                        sample=_trace._env_sample())
    telemetry.clear()


def _spans(recorder, net, x):
    recorder.clear()
    with recorder.span('train.step'):
        _step(net, x)
    return {e['name']: e['attrs'] for e in recorder.events()
            if e['name'] in ('mx.graph.launch', 'mx.tape.vjp')}


def test_traced_is_one_on_the_call_that_builds_and_zero_after(recorder):
    net = _dense()
    net.initialize()
    x = _x((5, 6))
    net(x)
    net.hybridize()
    first = _spans(recorder, net, x)
    assert first['mx.graph.launch']['traced'] == 1
    assert first['mx.tape.vjp']['traced'] == 1
    for _ in range(3):
        later = _spans(recorder, net, x)
        assert later['mx.graph.launch']['traced'] == 0
        assert later['mx.tape.vjp']['traced'] == 0
    again = _spans(recorder, net, _x((9, 6)))
    assert again['mx.graph.launch']['traced'] == 1


def test_traced_is_one_where_predict_record_defers_the_vjp(recorder):
    net = _dense()
    net.initialize()
    x = _x((5, 6))
    net(x)
    net.hybridize()
    for _ in range(2):
        recorder.clear()
        with recorder.span('train.step'):
            with autograd.record(train_mode=False):
                loss = _loss(net(x), None)
            loss.backward()
        spans = {e['name']: e.get('attrs') for e in recorder.events()}
        assert spans['mx.tape.vjp']['traced'] == 1     # every backward
    assert net.vjp_trace_count == 0


# ------------------------------------- what the recorded forward hands back
def _programs(net):
    (entry,) = [e for e in net._cached_graph._compiled.values()
                if e.vjp is not None and e.vjp.treedef is not None]
    return entry.vjp


@pytest.mark.parametrize('remat', [False, True])
def test_no_argument_comes_back_as_an_output(recorder, remat):
    net = _dense()
    net.initialize()
    x = _x((5, 6))
    net(x)
    net.hybridize(remat=remat)
    _step(net, x)
    programs = _programs(net)
    main, aux = net._cached_graph._params()
    n_args = 1 + 1 + len(main) + len(aux)       # key, x, the parameters
    # every matrix is a residual, and goes to the backward as an argument
    matrices = [2 + i for i, p in enumerate(main) if len(p.shape) == 2]
    assert set(matrices) <= set(programs.forwarded)
    assert all(0 <= i < n_args for i in programs.forwarded)
    n_residuals = len({at for kind, at in programs.sources
                       if kind == 'residual'})
    assert programs.n_out == 1 + n_residuals
    if remat:
        # under jax.checkpoint every residual is an argument
        assert n_residuals == 0
    # the launch span counts what the program handed back
    assert _spans(recorder, net, x)['mx.graph.launch']['n_out'] == \
        programs.n_out
    # and none of it is a parameter's or the input's twin
    key = jax.random.PRNGKey(0)
    raws = tuple(p.data()._data for p in main)
    outs, _, vjp = programs(key, (x._data,), raws, ())
    assert len(outs) + len(vjp.residuals) == programs.n_out
    for r in vjp.residuals:
        for twin in raws + (x._data,):
            assert not (r.shape == twin.shape
                        and np.array_equal(np.asarray(r), np.asarray(twin)))
    # the arguments handed on are the step's own arrays
    args = (key, x._data) + raws
    assert all(f is args[i] for f, i in zip(vjp.forwarded,
                                            programs.forwarded))


def test_a_constant_of_the_trace_is_no_buffer():
    class Scaled(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.dense = nn.Dense(8, in_units=6)

        def forward(self, x):
            return mx.np.tanh(self.dense(x) * 0.125)

    net = Scaled()
    net.initialize()
    x = _x((5, 6))
    _step(net, x)
    want = _grads(net)
    net.hybridize()
    for _ in range(2):
        _step(net, x)
        _same(_grads(net), want)
    programs = _programs(net)
    constants = [float(at) for kind, at in programs.sources
                 if kind == 'constant']
    assert 0.125 in constants
    key = jax.random.PRNGKey(0)
    main, _ = net._cached_graph._params()
    _, _, vjp = programs(key, (x._data,),
                         tuple(p.data()._data for p in main), ())
    assert all(r.ndim > 0 for r in vjp.residuals)


def test_a_donated_aux_leaf_is_not_forwarded_and_a_held_one_is_itself():
    net = ReadsAFrozenLeaf()
    net.initialize()
    net.hybridize(static_alloc=True)
    x = _x((5, 6))
    for step in range(3):
        offset = net.offset.data()._data
        mean = net.norm.running_mean.data()._data
        _step(net, x)
        assert mean.is_deleted() == (step > 0)     # written over in place
        assert net.offset.data()._data is offset   # no copy came back
    programs = _programs(net)
    main, aux = net._cached_graph._params()
    # arguments that outlive the call: key, x, the weights, the held leaf
    assert len(programs._arguments(0, (x,), [p for p in main],
                                   ([], [net.offset]))) == 3 + len(main)
    held = [at for at in programs.aux_forwarded if at is not None]
    assert held == [2 + len(main)]


def test_a_recorded_call_waits_for_the_backward_before_it(monkeypatch):
    """The forward's residuals are allocated when it is enqueued: it is
    not enqueued beside a set whose backward has not run."""
    net = _dense()
    net.initialize()
    x = _x((5, 6))
    net(x)
    net.hybridize()
    graph = net._cached_graph
    _step(net, x)
    launched = graph._backward
    assert launched is net.collect_params()['1.bias'].grad()._data
    waited = []
    ready = jax.block_until_ready
    monkeypatch.setattr(jax, 'block_until_ready',
                        lambda a: waited.append(a) or ready(a))
    net(x)                              # not recorded: waits for nothing
    assert waited == [] and graph._backward is launched
    with autograd.record():
        out = net(x)
        assert waited == [launched] and graph._backward is None
        net(x)                          # two forwards, one backward later
        assert len(waited) == 1
        loss = out.sum()
    loss.backward()
    assert graph._backward is not None and len(waited) == 1


# ------------------------------------------------------------ with Trainer
def test_backward_after_step_on_a_retained_graph_still_raises():
    net = _dense()
    net.initialize()
    x = _x((5, 6))
    net(x)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    _step(net, x)
    trainer.step(1)
    with autograd.record():
        loss = _loss(net(x), None)
    loss.backward(retain_graph=True)
    trainer.step(1)
    with pytest.raises(MXNetError, match='updated in place'):
        loss.backward()


def test_training_through_the_built_programs_follows_the_per_step_route():
    new, old = _twin(_batchnorm, (4, 2, 6, 6))
    trainers = [gluon.Trainer(net.collect_params(), 'adam',
                              {'learning_rate': 1e-2}) for net in (new, old)]
    losses = []
    for step in range(6):
        x = _x((4, 2, 6, 6), seed=step)
        got = _step(new, x)
        trainers[0].step(4)
        with per_step_vjp():
            want = _step(old, x)
        trainers[1].step(4)
        np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-4)
        losses.append(float(got.asnumpy()))
    for p, q in zip(new.collect_params().values(),
                    old.collect_params().values()):
        np.testing.assert_allclose(p.data().asnumpy(), q.data().asnumpy(),
                                   rtol=1e-4, atol=1e-5)
    assert new.vjp_trace_count == 1


# -------------------------------------------------------------- under a mesh
def test_under_a_mesh_the_gradients_keep_the_parameters_layout():
    shape = (8, 6)
    new, old = _twin(_dense, shape)
    single = _dense()
    single.initialize()
    single(_x(shape))
    for p, q in zip(new.collect_params().values(),
                    single.collect_params().values()):
        q.set_data(p.data().copy())
    _step(single, _x(shape))
    with mx.sharding.mesh(dp=4, devices=jax.devices()[:4]):
        for step in range(3):
            _step(new, _x(shape))
            with per_step_vjp():
                _step(old, _x(shape))
            for k, p in new.collect_params().items():
                grad, data = p.grad()._data, p.data()._data
                assert len(grad.sharding.device_set) == 4, k
                assert grad.sharding.is_equivalent_to(data.sharding,
                                                      grad.ndim), k
            _same(_grads(new), _grads(old))
        _step(new, _x((12, 6)))         # another shape under the same mesh
        assert new.vjp_trace_count == 2
    _same(_grads(old), _grads(single))
    # outside the mesh the same shapes are entries of their own
    assert old.vjp_trace_count == 0
