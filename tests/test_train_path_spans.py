"""Spans, counters and scopes inside the train path (ISSUE 27): what a
hybridized BERT step leaves in the flight recorder, that their number does
not depend on the model, what ``_bulk.stats()`` counts, and that the
lowered programs carry their ``mx.`` scopes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _bulk, autograd, gluon, telemetry
from mxnet_tpu.gluon.model_zoo.bert import get_bert_model
from mxnet_tpu.telemetry import trace as _trace

# a span and the spans directly inside it, for one hybridized step with
# the bulking engine on (as on the chip)
TREE = {
    'train.step': {'mx.graph.call', 'mx.tape.backward', 'mx.trainer.step'},
    'mx.graph.call': {'mx.graph.flush', 'mx.graph.launch'},
    'mx.graph.flush': {'mx.graph.await'},
    'mx.tape.backward': {'mx.tape.flush', 'mx.tape.vjp'},
    'mx.tape.flush': {'mx.bulk.flush'},
    'mx.trainer.step': {'mx.trainer.hyper', 'mx.trainer.launch'},
}
ATTRS = {
    'mx.graph.call': {'n_in', 'n_params', 'compiled'},
    'mx.graph.launch': {'n_out', 'traced', 'ahead', 'residuals', 'recycled'},
    'mx.tape.backward': {'n_nodes', 'n_vars'},
    'mx.tape.vjp': {'n_out', 'traced', 'ahead'},
    'mx.bulk.flush': {'n_ops', 'n_out', 'compiled', 'ahead'},
    'mx.trainer.step': {'n_params'},
    'mx.trainer.hyper': {'uploaded'},
    'mx.trainer.launch': {'n_in', 'n_out', 'donated', 'ahead'},
}
# the spans round a jitted call down to PjRt
LAUNCHES = ('mx.graph.launch', 'mx.tape.vjp', 'mx.bulk.flush',
            'mx.trainer.launch')


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.configure(enabled=True, sample=1.0)
    telemetry.clear()
    yield
    telemetry.configure(enabled=_trace._env_enabled(),
                        buffer=_trace._env_buffer(),
                        sample=_trace._env_sample())
    telemetry.clear()


class Loop:
    """BERT-tiny with a 2-class head, hybridized, with its Trainer: the
    loop of examples/bert_finetune.py at a size a test can hold."""

    def __init__(self, layers):
        bert = get_bert_model(
            'bert_12_768_12', vocab_size=200, token_type_vocab_size=2,
            units=64, hidden_size=128, num_layers=layers, num_heads=2,
            max_length=32, dropout=0.0, use_decoder=False,
            use_classifier=False)

        class Classifier(gluon.nn.HybridBlock):
            def __init__(self):
                super().__init__()
                self.bert = bert
                self.head = gluon.nn.Dense(2)

            def forward(self, tokens, types, valid_length):
                _, pooled = self.bert(tokens, types, valid_length)
                return self.head(pooled)

        rng = np.random.RandomState(0)
        self.batch = (
            mx.np.array(rng.randint(0, 200, (4, 16)).astype('int32')),
            mx.np.array(np.zeros((4, 16), 'int32')),
            mx.np.array(np.full((4,), 12, 'int32')),
            mx.np.array(rng.randint(0, 2, (4,)).astype('float32')))
        self.net = Classifier()
        self.net.initialize()
        self.net(*self.batch[:3])
        self.net.hybridize(static_alloc=True)
        self.trainer = gluon.Trainer(self.net.collect_params(), 'adam',
                                     {'learning_rate': 1e-4})
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.n_params = len(self.net.collect_params())

    def step(self):
        tokens, types, valid_length, labels = self.batch
        with autograd.record():
            out = self.net(tokens, types, valid_length)
            loss = self.loss_fn(out, labels).mean()
        loss.backward()
        self.trainer.step(1)
        return loss

    def traced_step(self):
        """The events and the counters' growth of one steady step under
        a caller's train.step span."""
        for _ in range(2):                  # compile, then settle
            self.step().asnumpy()
        telemetry.clear()
        before = _bulk.stats()
        with telemetry.span('train.step', step=0):
            self.step()
        after = _bulk.stats()
        return telemetry.events(), {k: after[k] - before[k] for k in after}

    def handed_back(self):
        """Buffers the recorded forward of the one train entry hands
        back: the output and the residuals that are not its arguments."""
        (programs,) = [e.vjp for e in
                       self.net._cached_graph._compiled.values() if e.vjp]
        assert self.net.vjp_trace_count == 1
        return programs.n_out


@pytest.fixture(scope='module')
def bulked_steps():
    """One traced step of a 2-layer and of a 4-layer net, bulking on."""
    telemetry.configure(enabled=True, sample=1.0)
    out = {}
    with _bulk.force(True):
        for layers in (2, 4):
            loop = Loop(layers)
            out[layers] = loop.traced_step() + (loop.n_params,
                                                loop.handed_back())
    return out


def test_a_step_is_one_connected_tree(bulked_steps):
    events = bulked_steps[2][0]
    tids = telemetry.trace_ids(events)
    assert len(tids) == 1
    roots = telemetry.trace_tree(events, tids[0])
    assert len(roots) == 1 and roots[0]['rec']['name'] == 'train.step'
    assert roots[0]['rec']['attrs'] == {'step': 0}


def test_the_tree_holds_every_span_each_child_inside_its_parent(
        bulked_steps):
    events = bulked_steps[2][0]
    by_id = {e['span']: e for e in events}
    children = {}
    for e in events:
        if e['parent'] is not None:
            parent = by_id[e['parent']]
            children.setdefault(parent['name'], set()).add(e['name'])
            assert parent['t0'] <= e['t0'] <= e['t1'] <= parent['t1'], \
                (parent['name'], e['name'])
    assert children == TREE


def test_the_spans_carry_their_counts(bulked_steps):
    events, _, n_params, handed_back = bulked_steps[2]
    for e in events:
        if e['name'] in ATTRS:
            # in_use is not among them: the CPU client keeps no memory
            # statistics
            assert set(e['attrs']) == ATTRS[e['name']], e['name']
    one = {e['name']: {k: v for k, v in e['attrs'].items() if k != 'ahead'}
           for e in events if e['name'] != 'mx.tape.vjp' and 'attrs' in e}
    assert one['mx.graph.call'] == {'n_in': 3, 'n_params': n_params,
                                    'compiled': 0}
    # the recorded forward's output and residuals; the programs were
    # built two steps ago and this call launched them, writing every
    # residual over the last step's spent ones
    assert one['mx.graph.launch'] == {'n_out': handed_back, 'traced': 0,
                                      'residuals': handed_back - 1,
                                      'recycled': handed_back - 1}
    assert handed_back > 1
    assert one['mx.trainer.step'] == {'n_params': n_params}
    assert one['mx.trainer.hyper'] == {'uploaded': 1}
    # w, g and Adam's two slots in; w and the slots out, each written
    # over the distinct buffer it replaces
    assert one['mx.trainer.launch'] == {'n_in': 4 * n_params + 3,
                                        'n_out': 3 * n_params,
                                        'donated': 3 * n_params}
    assert one['mx.tape.backward']['n_vars'] == n_params
    # the two compiled nodes of the tape: the loss segment, the net
    vjps = sorted(e['attrs']['n_out'] for e in events
                  if e['name'] == 'mx.tape.vjp')
    # a gradient a parameter; the three integer inputs get none
    assert vjps[-1] == n_params and len(vjps) == 2
    assert [e['attrs']['traced'] for e in events
            if e['name'] == 'mx.tape.vjp'] == [0, 0]
    assert one['mx.tape.backward']['n_nodes'] == 2


def test_the_number_of_spans_does_not_depend_on_depth(bulked_steps):
    names = {layers: sorted(e['name'] for e in bulked_steps[layers][0])
             for layers in (2, 4)}
    assert names[2] == names[4]
    assert len(names[2]) == 13          # train.step and twelve of its own
    assert bulked_steps[4][2] > bulked_steps[2][2]     # more parameters


def test_bulk_stats_grow_with_the_flushes(bulked_steps):
    events, grew = bulked_steps[2][:2]
    flushes = [e for e in events if e['name'] == 'mx.bulk.flush']
    assert grew['flushes'] == len(flushes) == 1
    assert flushes[0]['attrs']['n_ops'] >= flushes[0]['attrs']['n_out'] > 0
    assert grew['unbulked'] == 0 and grew['compiles'] == 0


def test_every_launch_says_how_much_work_was_queued_ahead_of_it(
        bulked_steps):
    events = bulked_steps[2][0]
    launches = [e for e in events if e['name'] in LAUNCHES]
    assert {e['name'] for e in launches} == set(LAUNCHES)
    # the earlier launches still running on the device, of the eight
    # watched
    for e in launches:
        assert 0 <= e['attrs']['ahead'] <= _bulk.LaunchRecord.WATCHED


def test_the_wait_for_the_last_backward_is_its_own_span(bulked_steps):
    events = bulked_steps[2][0]
    by_id = {e['span']: e for e in events}
    wait, = [e for e in events if e['name'] == 'mx.graph.await']
    assert by_id[wait['parent']]['name'] == 'mx.graph.flush'
    assert 'attrs' not in wait


def test_a_call_with_no_backward_to_wait_for_opens_no_wait():
    loop = Loop(2)
    with telemetry.span('train.step', step=0):
        loop.step()
    names = [e['name'] for e in telemetry.events()]
    assert 'mx.graph.flush' in names and 'mx.graph.await' not in names


def test_with_bulking_off_every_eager_op_is_counted_unbulked():
    with _bulk.force(False):
        events, grew = Loop(2).traced_step()
    assert grew['flushes'] == 0
    assert grew['unbulked'] > 0
    names = {e['name'] for e in events}
    assert 'mx.bulk.flush' not in names and 'mx.tape.flush' in names
    # eager nodes of the tape get no span of their own: one vjp, the net's
    assert sum(e['name'] == 'mx.tape.vjp' for e in events) == 1
    backward, = [e for e in events if e['name'] == 'mx.tape.backward']
    assert backward['attrs']['n_nodes'] == 1 + grew['unbulked']


def test_ops_traced_into_a_graph_are_not_counted_unbulked():
    loop = Loop(2)
    before = _bulk.stats()['unbulked']
    with _bulk.force(False):
        loop.net(*loop.batch[:3])       # traces and compiles the forward
        assert _bulk.stats()['unbulked'] == before
        (loop.batch[3] + 1).asnumpy()
        assert _bulk.stats()['unbulked'] == before + 1


def test_a_step_outside_any_context_leaves_the_recorder_empty():
    loop = Loop(2)
    loop.step().asnumpy()
    assert telemetry.events() == []


def test_a_step_with_telemetry_off_still_trains():
    telemetry.configure(enabled=False)
    loop = Loop(2)
    with telemetry.span('train.step', step=0):
        first = float(loop.step().asnumpy())
    assert np.isfinite(first) and telemetry.events() == []


def test_under_a_mesh_the_update_places_its_operands():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation='relu'), gluon.nn.Dense(16))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 0.05})
    x, y = mx.nd.rand(16, 64), mx.nd.rand(16, 16)
    with mx.sharding.mesh(dp=4, devices=jax.devices()[:4]):
        for i in range(3):
            if i == 2:
                telemetry.clear()
                before = _bulk.stats()['unbulked']
            with telemetry.span('train.step', step=i):
                with autograd.record():
                    loss = ((net(x) - y) ** 2).mean()
                loss.backward()
                trainer.step(16)
        unbulked = _bulk.stats()['unbulked'] - before
    events = telemetry.events()
    by_id = {e['span']: e for e in events}
    place, = [e for e in events if e['name'] == 'mx.trainer.place']
    assert by_id[place['parent']]['name'] == 'mx.trainer.step'
    # subtract, square, mean: one launch each on the CPU's default, where
    # the bulking engine is off. Where it is on (the chip; forced here in
    # tests/test_bulk_mesh.py) the three are one segment under a mesh too
    # and this reads 0.
    assert unbulked >= 3


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


Q = jnp.ones((2, 16, 64))
MASK = jnp.ones((2, 1, 16, 16), bool)
G = jnp.ones((64,))
W = jnp.ones((8, 128))


def _attention(mask):
    from mxnet_tpu.ops.contrib import multi_head_attention
    return lambda q: multi_head_attention(q, q, q, 2, mask=mask).sum()


def _layer_norm(x):
    from mxnet_tpu.ops.pallas.fused_norms import fused_layer_norm
    return fused_layer_norm(x, G, G).sum()


def _rms_norm(x):
    from mxnet_tpu.ops.pallas.fused_norms import fused_rms_norm
    return fused_rms_norm(x, G).sum()


def _adam(w):
    from mxnet_tpu.ops.optimizer_ops import fused_adam_step
    return fused_adam_step(w, w, w, w)


def _sgd_mom(w):
    from mxnet_tpu.ops.optimizer_ops import fused_sgd_mom_step
    return fused_sgd_mom_step(w, w, w, momentum=0.9)


@pytest.mark.parametrize('fn, arg, scope', [
    (_attention(None), Q, 'jvp(mx.attention)'),
    (_attention(MASK), Q, 'jvp(mx.attention)'),
    (_layer_norm, Q, 'jvp(mx.layer_norm)'),
    (_rms_norm, Q, 'jvp(mx.layer_norm)'),
], ids=['attention', 'attention_masked', 'layer_norm', 'rms_norm'])
def test_forward_and_backward_carry_the_scope(fn, arg, scope):
    text = _lowered(jax.value_and_grad(fn), arg)
    assert f'/{scope}/' in text                       # the forward's ops
    assert f'/transpose({scope})/' in text            # and the backward's


@pytest.mark.parametrize('fn', [_adam, _sgd_mom], ids=['adam', 'sgd_mom'])
def test_the_optimizer_step_carries_its_scope(fn):
    assert '/mx.optimizer_step/' in _lowered(fn, W)
