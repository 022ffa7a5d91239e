"""The bulking engine under ``mx.sharding.mesh`` (ISSUE 36): eager ops are
recorded into a segment under a mesh as off it, the flush lifts the
segment's boundary onto the mesh once, a plan belongs to the context it was
traced under, and entering or leaving a context flushes. Bulking is forced
on, as it is on the chip (the CPU's default is off); the mesh is four of the
CPU's forced host devices."""

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _bulk, autograd, gluon, sharding, telemetry
from mxnet_tpu.ops import registry
from mxnet_tpu.sharding.context import ShardingContext
from mxnet_tpu.telemetry import trace as _trace

DEVICES = 4


def _mesh():
    return sharding.mesh(dp=DEVICES, devices=jax.devices()[:DEVICES])


def _n_devices(nd):
    return len(nd._data.sharding.device_set)


@pytest.fixture(autouse=True)
def _fresh_engine():
    _bulk.reset()
    telemetry.configure(enabled=True, sample=1.0)
    telemetry.clear()
    yield
    telemetry.configure(enabled=_trace._env_enabled(),
                        buffer=_trace._env_buffer(),
                        sample=_trace._env_sample())
    telemetry.clear()
    _bulk.reset()


# ------------------------------------------------------- (a) a training step
def _l2(out, batch):
    return ((out - batch['target']) ** 2).mean()


_CE = gluon.loss.SoftmaxCrossEntropyLoss()


def _masked_ce(out, batch):
    """The shape of the pre-training loss (chipbench/families/bert.py): a
    reshape, a weighted cross-entropy summed over a count, plus a mean."""
    rows = _CE(out.reshape(-1, 4), batch['labels'], batch['weight'])
    return rows.sum() / batch['count'] + (out ** 2).mean()


def _batches(rs, steps):
    for _ in range(steps):
        yield {
            'x': mx.nd.array(rs.rand(16, 24).astype('f')),
            'target': mx.nd.array(rs.rand(16, 16).astype('f')),
            'labels': mx.nd.array(rs.randint(0, 4, (64,)).astype('f')),
            'weight': mx.nd.array(rs.randint(0, 2, (64, 1)).astype('f')),
            'count': mx.nd.array(np.float32(32.0)),
        }


def _train(loss_fn, bulk, steps=3):
    """Hybridized net, eager loss over single-device labels, Adam, under a
    mesh: a step's loss, gradients, engine counters and spans."""
    mx.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation='relu'), gluon.nn.Dense(16))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 0.05})
    seen = []
    with _bulk.force(bulk), _mesh():
        # the first call runs the layers eagerly to find their shapes
        net(mx.nd.zeros((16, 24)))
        for batch in _batches(np.random.RandomState(3), steps):
            before = _bulk.stats()
            telemetry.clear()
            with telemetry.span('train.step'):
                with autograd.record():
                    loss = loss_fn(net(batch['x']), batch)
                loss.backward()
                grads = {n: p.grad().asnumpy()
                         for n, p in net.collect_params().items()}
                trainer.step(16)
            after = _bulk.stats()
            seen.append({
                'loss': float(loss.asnumpy()), 'grads': grads,
                'stats': {k: after[k] - before[k] for k in after},
                'spans': telemetry.events()})
    return seen


@pytest.mark.parametrize('loss_fn', [_l2, _masked_ce],
                         ids=['l2', 'masked_ce'])
def test_a_mesh_step_is_one_segment_and_equals_the_unbulked_run(loss_fn):
    eager = _train(loss_fn, bulk=False)
    bulked = _train(loss_fn, bulk=True)
    assert all(s['stats']['unbulked'] > 0 for s in eager)
    for step in bulked[1:]:
        assert step['stats']['unbulked'] == 0
        assert step['stats']['flushes'] == 1
        assert step['stats']['compiles'] == 0
        backward, = [e for e in step['spans']
                     if e['name'] == 'mx.tape.backward']
        assert backward['attrs']['n_nodes'] == 2    # the graph, the segment
        vjps = [e for e in step['spans'] if e['name'] == 'mx.tape.vjp']
        assert len(vjps) == 2
        assert all(e['attrs']['traced'] == 0 for e in vjps)
    for want, got in zip(eager, bulked):
        np.testing.assert_allclose(got['loss'], want['loss'], rtol=1e-5)
        for name, g in want['grads'].items():
            np.testing.assert_allclose(got['grads'][name], g, rtol=1e-5,
                                       atol=1e-6, err_msg=name)


# ------------------------------------------ (b) a plan belongs to a context
class Probe:
    """An op that notes the mesh context it is traced under."""

    def __init__(self):
        self.seen = []
        self.op = registry.Op('_test_context_probe', self._fn)

    def _fn(self, x):
        self.seen.append(sharding.current())
        return x * 3.0

    def __call__(self, x):
        return registry.invoke(self.op, (x,), {})


def _sequence(probe, x):
    """The same ops, keys and avals wherever it runs: (3x + 1).sum()."""
    with autograd.record():
        y = (probe(x) + 1.0).sum()
    y.backward()
    return float(y.asnumpy()), x.grad.asnumpy()


@pytest.mark.parametrize('mesh_first', [False, True],
                         ids=['off_then_under', 'under_then_off'])
def test_one_op_sequence_off_a_mesh_and_under_it_is_two_plans(mesh_first):
    probe = Probe()
    x = mx.nd.array(np.arange(8, dtype='f'))
    x.attach_grad()

    def run(under):
        del probe.seen[:]
        before = _bulk.stats()['compiles']
        if under:
            with _mesh() as ctx:
                got = _sequence(probe, x)
        else:
            ctx, got = None, _sequence(probe, x)
        assert got[0] == 3.0 * 28 + 8 and np.all(got[1] == 3.0)
        return _bulk.stats()['compiles'] - before, ctx

    with _bulk.force(True):
        for under in (mesh_first, not mesh_first):
            compiles, ctx = run(under)
            assert compiles == 1
            # abstract evaluation, the plan's trace and its vjp's trace
            assert len(probe.seen) == 3
            assert all(c is ctx for c in probe.seen)
        for under in (mesh_first, not mesh_first):
            compiles, _ = run(under)
            assert compiles == 0        # both plans are kept,
            assert probe.seen == []     # and neither is traced again


@pytest.mark.parametrize('recorded_under', [True, False],
                         ids=['backward_after_the_mesh', 'backward_in_a_mesh'])
def test_a_vjp_is_traced_under_the_context_of_its_segment(recorded_under):
    """``backward()`` may come after the mesh was left (or inside one for a
    segment from before it): the segment's vjp is traced then, under the
    context the ops were recorded in."""
    probe = Probe()
    x = mx.nd.array(np.arange(8, dtype='f'))
    x.attach_grad()
    with _bulk.force(True):
        if recorded_under:
            with _mesh() as ctx:
                with autograd.record():
                    y = probe(x).sum()
            assert y._lazy.value is not None    # leaving flushed it
            del probe.seen[:]
            y.backward()
        else:
            ctx = None
            with autograd.record():
                y = probe(x).sum()
            with _mesh():
                assert y._lazy.value is not None    # entering flushed it
                del probe.seen[:]
                y.backward()
    assert probe.seen == [ctx]
    assert np.all(x.grad.asnumpy() == 3.0)


# ---------------------------------------- (c) a context change is a flush
@pytest.mark.parametrize('enter', ['mesh', 'use'])
def test_a_pending_segment_is_flushed_where_a_context_changes(enter):
    ctx = ShardingContext(mx.parallel.make_mesh(
        devices=jax.devices()[:DEVICES], dp=DEVICES))
    x = mx.nd.array(np.arange(6, dtype='f'))
    with _bulk.force(True):
        flushes = _bulk.stats()['flushes']
        a = x * 2.0
        assert a._lazy.value is None
        with (_mesh() if enter == 'mesh' else sharding.use(ctx)):
            assert a._lazy.value is not None
            assert _bulk.stats()['flushes'] == flushes + 1
            b = a + 1.0
            assert b._lazy.value is None
        assert b._lazy.value is not None
        assert _bulk.stats()['flushes'] == flushes + 2
        c = b - 1.0                 # a fresh segment, off the mesh again
        assert c._lazy.value is None
    np.testing.assert_array_equal(a.asnumpy(), 2.0 * np.arange(6))
    np.testing.assert_array_equal(b.asnumpy(), 2.0 * np.arange(6) + 1)
    np.testing.assert_array_equal(c.asnumpy(), 2.0 * np.arange(6))


# ------------------------------- (d) nothing on the mesh, nothing to lift
def test_a_single_device_boundary_under_a_mesh_is_not_lifted(monkeypatch):
    puts = []
    put = ShardingContext.put
    monkeypatch.setattr(ShardingContext, 'put',
                        lambda self, raw, spec: puts.append(spec)
                        or put(self, raw, spec))
    x = mx.nd.array(np.arange(8, dtype='f'))
    x.attach_grad()
    with _bulk.force(True), _mesh():
        with autograd.record():
            y = (x * x).sum()
        assert y._lazy.value is None
        y.backward()
        node = y._ag.node
        assert node.in_vals[0] is x._data       # the boundary as it was
        assert _n_devices(y) == 1
        assert _n_devices(x.grad) == 1
    assert puts == []
    np.testing.assert_array_equal(x.grad.asnumpy(), 2.0 * np.arange(8))


def test_a_mixed_boundary_is_lifted_once_for_the_segment(monkeypatch):
    """The counterpart: one single-device array read by three ops of a
    segment whose other input is on the mesh is placed once."""
    puts = []
    put = ShardingContext.put
    monkeypatch.setattr(ShardingContext, 'put',
                        lambda self, raw, spec: puts.append(raw.shape)
                        or put(self, raw, spec))
    label = mx.nd.array(np.ones((8, 2), 'f'))
    with _bulk.force(True), _mesh() as ctx:
        on_mesh = mx.nd.NDArray(ctx.put(np.ones((8, 2), 'f'),
                                        ctx.batch_spec((8, 2))))
        del puts[:]
        out = (on_mesh + label) * label - label
        assert _n_devices(out) == DEVICES
    assert puts == [(8, 2)]
    np.testing.assert_array_equal(out.asnumpy(), np.ones((8, 2)))


# --------------------- (e) what the engine turns away is lifted and counted
@pytest.mark.parametrize('bulk', [True, False])
def test_an_op_the_engine_turns_away_under_a_mesh_is_lifted(bulk):
    """A dynamic-shape op raises under the engine's abstract evaluation
    and runs eagerly; its operands are still reconciled op by op."""
    op = registry.Op('_test_turned_away', lambda a, b: a + b,
                     dynamic_shape=True)
    label = mx.nd.array(np.ones((8, 2), 'f'))
    with _bulk.force(bulk), _mesh() as ctx:
        on_mesh = mx.nd.NDArray(ctx.put(np.ones((8, 2), 'f'),
                                        ctx.batch_spec((8, 2))))
        before = _bulk.stats()
        out = registry.invoke(op, (on_mesh, label), {})
        assert out._lazy is None
        after = _bulk.stats()
        assert after['unbulked'] == before['unbulked'] + 1
        assert after['flushes'] == before['flushes']
        assert _n_devices(out) == DEVICES
    np.testing.assert_array_equal(out.asnumpy(), 2.0 * np.ones((8, 2)))


def test_a_head_gradient_on_one_device_meets_a_boundary_on_the_mesh():
    """The tape hands the segment's vjp a cotangent committed to one
    device beside a boundary on the mesh: reconciled as at the flush."""
    head = mx.nd.array(np.full((8, 2), 0.5, 'f'))
    head._data = jax.device_put(head._data, jax.devices()[0])
    with _bulk.force(True), _mesh() as ctx:
        x = mx.nd.NDArray(ctx.put(np.ones((8, 2), 'f'),
                                  ctx.batch_spec((8, 2))))
        x.attach_grad()
        with autograd.record():
            y = x * 4.0
        y.backward(head)
        assert _n_devices(x.grad) == DEVICES
    np.testing.assert_array_equal(x.grad.asnumpy(), np.full((8, 2), 2.0))
