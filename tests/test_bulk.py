"""Bulked eager execution (mxnet_tpu/_bulk.py).

Reference contract: engine.h:310 StartBulk/StopBulk + engine.py bulk()
context — consecutive imperative ops fuse into one engine push. Here the
fused unit is a cached XLA program; these tests pin laziness, sync points,
cache reuse, autograd equivalence, and the eager-fallback guards.
"""
import gc

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _bulk, autograd, engine, gluon


def test_lazy_until_sync_point():
    with engine.bulk(100):
        a = mx.np.ones((3, 3))
        b = a * 2 + 1
        assert b._lazy is not None and b._lazy.value is None
        # shape/dtype/ndim come from the abstract value, no flush
        assert b.shape == (3, 3)
        assert b.dtype == onp.float32
        assert b.ndim == 2
        assert b._lazy.value is None
        got = b.asnumpy()           # sync point
    onp.testing.assert_allclose(got, onp.full((3, 3), 3.0))


def test_chain_parity_and_cache_reuse():
    def run():
        with engine.bulk(100):
            a = mx.np.arange(12).reshape(3, 4).astype('float32')
            b = mx.np.tanh(a) @ mx.np.ones((4, 2))
            c = (b * b).sum()
            return float(c)

    v1 = run()
    compiles = _bulk.stats()['compiles']
    v2 = run()                      # identical segment: trie + plan hit
    assert _bulk.stats()['compiles'] == compiles
    assert v1 == v2
    expect = ((onp.tanh(onp.arange(12).reshape(3, 4)) @
               onp.ones((4, 2))) ** 2).sum()
    assert abs(v1 - expect) < 1e-4


def test_autograd_matches_eager():
    def grads(bulked):
        x = mx.np.array([[1., 2.], [3., 4.]])
        x.attach_grad()
        ctx = engine.bulk(1000) if bulked else engine.naive_engine()
        with ctx:
            with autograd.record():
                y = ((x * x).sum() + (3 * x).sum())
            y.backward()
        return x.grad.asnumpy()

    onp.testing.assert_allclose(grads(True), grads(False), rtol=1e-6)


def test_pause_blocks_gradient_inside_segment():
    x = mx.np.array([2.0])
    x.attach_grad()
    with engine.bulk(100):
        with autograd.record():
            y = x * 3
            with autograd.pause():
                z = y * 10          # recorded w/o grad: must block flow
            w = (y + z).sum()
        w.backward()
    # d w/dx = 3 (through y) + 0 (z path stopped) — eager tape semantics
    onp.testing.assert_allclose(x.grad.asnumpy(), [3.0])


def test_out_kwarg_stays_in_segment():
    with engine.bulk(100):
        a = mx.np.ones((4,))
        out = mx.np.zeros((4,))
        mx.np.add(a, a, out=out)
        assert out._lazy is not None and out._lazy.value is None
        onp.testing.assert_allclose(out.asnumpy(), onp.full((4,), 2.0))


def test_cross_segment_chaining():
    with engine.bulk(100):
        a = mx.np.ones((2, 2)) * 4
        _ = a.asnumpy()             # flush mid-stream
        b = a + 1                   # new segment consumes flushed value
        onp.testing.assert_allclose(b.asnumpy(), onp.full((2, 2), 5.0))


def test_size_cap_flushes():
    with engine.bulk(2):
        a = mx.np.ones((2,))
        b = a + 1
        c = b + 1                   # second entry: cap reached, flush
        assert c._lazy is None or c._lazy.value is not None
        onp.testing.assert_allclose(c.asnumpy(), onp.full((2,), 3.0))


def test_varying_scalar_marks_unstable_not_compile_storm():
    compiles0 = _bulk.stats()['compiles']
    for i in range(40):
        with engine.bulk(100):
            a = mx.np.ones((2,))
            b = a * float(i)        # scalar baked into the op: varies
            assert abs(float(b.asnumpy()[0]) - float(i)) < 1e-6
    # after _MAX_SIBLINGS distinct constants the position goes eager
    # (with periodic re-admission); compiles stay bounded instead of
    # one per iteration
    assert _bulk.stats()['compiles'] - compiles0 <= _bulk._MAX_SIBLINGS + 6


def test_training_loop_parity_with_trainer():
    def train(bulked):
        mx.np.random.seed(7)
        net = gluon.nn.Dense(1, in_units=3)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), 'sgd',
                                {'learning_rate': 0.1, 'momentum': 0.9})
        xs = onp.random.default_rng(0).standard_normal((8, 3)).astype('f')
        ys = (xs @ onp.array([[1.], [2.], [3.]], 'f')).astype('f')
        ctx = engine.bulk(4096) if bulked else engine.naive_engine()
        with ctx:
            for _ in range(5):
                x, y = mx.np.array(xs), mx.np.array(ys)
                with autograd.record():
                    loss = ((net(x) - y) ** 2).mean()
                loss.backward()
                trainer.step(1)
        return {k: v.data().asnumpy()
                for k, v in net.collect_params().items()}

    got, want = train(True), train(False)
    for k in want:
        onp.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=2e-5)


def test_second_iteration_no_retrace():
    net = gluon.nn.Dense(4, in_units=4)
    net.initialize()

    def step(i):
        with engine.bulk(4096):
            x = mx.np.ones((2, 4)) * (1.0 + 0.0)   # stable constants
            with autograd.record():
                y = (net(x) ** 2).sum()
            y.backward()
            return float(y.asnumpy())

    step(0)
    s = _bulk.stats()
    step(1)
    s2 = _bulk.stats()
    assert s2['compiles'] == s['compiles'], 'iteration 2 recompiled'
    assert s2['misses'] == s['misses'], 'iteration 2 missed the trie'


def test_pending_forward_does_not_ride_into_hybridized_loop():
    """examples/bert_finetune.py's shape: an eager forward nobody reads
    resolves the shapes, then hybridize() and the loop. The compiled graph
    is a sync point, so the pending forward is its own segment and the
    loop's loss segment is the same one from the first step on."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation='relu'), gluon.nn.Dense(2))
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.np.array(onp.ones((4, 3), 'f'))
    y = mx.np.array(onp.array([0, 1, 0, 1], 'f'))
    seen = []
    with engine.bulk(4096):
        net(x[:1])                          # result unread: stays pending
        net.hybridize(static_alloc=True)
        trainer = gluon.Trainer(net.collect_params(), 'adam')
        for _ in range(3):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(4)
            seen.append(_bulk.stats())
    assert seen[1]['compiles'] == seen[0]['compiles'], 'step 2 recompiled'
    assert seen[2]['misses'] == seen[0]['misses'], 'step 2 missed the trie'


def test_dead_intermediates_not_materialized():
    _bulk.reset()       # pristine trie (earlier tests mark positions)
    with engine.bulk(100):
        a = mx.np.ones((4, 4))
        b = a * 2           # kept
        tmp = a * 3         # dropped before flush
        del tmp
        gc.collect()
        seg = _bulk._st.segment
        n_live = sum(1 for e in seg.entries for w in e.out_refs
                     if w() is not None)
        assert n_live == 1
        onp.testing.assert_allclose(b.asnumpy(), onp.full((4, 4), 2.0))


def test_nondifferentiable_op_detached():
    x = mx.np.array([1.5, 2.5])
    x.attach_grad()
    with engine.bulk(100):
        with autograd.record():
            y = mx.np.round(x) * x      # round contributes no gradient
            s = y.sum()
        s.backward()
    onp.testing.assert_allclose(x.grad.asnumpy(), onp.round([1.5, 2.5]))


def test_higher_order_through_segment():
    x = mx.np.array([2.0])
    x.attach_grad()
    with engine.bulk(100):
        with autograd.record():
            y = (x ** 3).sum()
            gx, = autograd.grad(y, [x], create_graph=True)
            gy = gx.sum()
        gy.backward()
    onp.testing.assert_allclose(x.grad.asnumpy(), [12.0])  # d2/dx2 x^3=6x


def test_stochastic_op_bulks_with_fresh_keys():
    with engine.bulk(100):
        a = mx.np.random.uniform(size=(64,))
        b = mx.np.random.uniform(size=(64,))
        va, vb = a.asnumpy(), b.asnumpy()
    assert not onp.allclose(va, vb)     # distinct keys per call


def test_naive_engine_bypasses_bulk():
    with engine.naive_engine():
        a = mx.np.ones((2,)) + 1
        assert a._lazy is None
    onp.testing.assert_allclose(a.asnumpy(), [2.0, 2.0])


def test_set_bulk_size_toggles():
    prev_size = _bulk._size
    try:
        engine.set_bulk_size(16)
        a = mx.np.ones((2,)) * 5
        assert a._lazy is not None          # bulking on
        engine.set_bulk_size(0)
        b = mx.np.ones((2,)) * 5
        assert b._lazy is None              # bulking off
        onp.testing.assert_allclose(a.asnumpy(), [5.0, 5.0])
    finally:
        _bulk._enabled = None               # restore env default
        _bulk._size = prev_size


def test_bulk_stats_surface():
    s = engine.bulk_stats()
    assert {'hits', 'misses', 'flushes', 'compiles'} <= set(s)


def test_detach_blocks_gradient_inside_segment():
    """A detached alias of an in-segment value must not leak gradient
    (eager: the detached NDArray has no lineage)."""
    def run(bulked):
        x = mx.np.array([1.0, 2.0, 3.0])
        w = mx.np.array([1.0, 1.0, 1.0])
        x.attach_grad()
        w.attach_grad()
        ctx = engine.bulk(100) if bulked else engine.naive_engine()
        with ctx:
            with autograd.record():
                y = x * 2
                z = y.detach() * w        # w tracked; y edge detached
                loss = (y + z).sum()
            loss.backward()
        return x.grad.asnumpy(), w.grad.asnumpy()

    (gx_b, gw_b), (gx_e, gw_e) = run(True), run(False)
    onp.testing.assert_allclose(gx_b, gx_e)   # [2,2,2], not [4,4,4]
    onp.testing.assert_allclose(gw_b, gw_e)


def test_detached_boundary_alias_keeps_tracked_gradient():
    """First-seen-untracked aliasing of a boundary raw must not discard
    the tracked alias's lineage."""
    def run(bulked):
        x = mx.np.array([1.0, 2.0, 3.0])
        x.attach_grad()
        ctx = engine.bulk(100) if bulked else engine.naive_engine()
        with ctx:
            with autograd.record():
                a = x.detach() + 0.0      # untracked use enters first
                b = x * 3.0               # tracked use, same raw
                loss = (a + b).sum()
            loss.backward()
        return x.grad.asnumpy()

    onp.testing.assert_allclose(run(True), run(False))  # [3,3,3]


def test_scalar_type_distinguishes_cache_keys():
    """2 vs 2.0 hash equal in Python but compile differently — the
    segment key must not collide them."""
    with engine.bulk(100):
        x = mx.np.array(onp.array([1, 2, 3], 'int32'))
        a = (x ** 2).asnumpy()
        b = (x ** 2.0).asnumpy()
    assert a.dtype == onp.asarray(onp.array([1], 'int32') ** 2).dtype \
        or str(a.dtype).startswith('int')
    assert str(b.dtype).startswith('float'), \
        f'float-power result reused the int-power plan: {b.dtype}'


def test_aliased_lineages_get_distinct_boundary_slots():
    """x and x.detach()+attach_grad() share one raw buffer but carry
    DISTINCT lineage (the TBPTT idiom). Bulked gradients must match
    eager — r3 regression: boundary inputs deduped by id(raw) collapsed
    both edges into the first-seen AGInfo, giving (8, 0) not (3, 5)."""
    def run(bulked):
        x = mx.np.array([2.0, 3.0])
        x.attach_grad()
        y = x.detach()
        y.attach_grad()
        ctx = engine.bulk(100) if bulked else engine.naive_engine()
        with ctx:
            with autograd.record():
                z = (x * 3 + y * 5).sum()
            z.backward()
        return x.grad.asnumpy(), y.grad.asnumpy()

    (gx_b, gy_b), (gx_e, gy_e) = run(True), run(False)
    onp.testing.assert_allclose(gx_b, gx_e)   # 3
    onp.testing.assert_allclose(gy_b, gy_e)   # 5


def test_aliased_lineages_pending_value():
    """Same aliasing but through a segment-produced value: attach_grad
    on the detached alias is a sync point (grad buffer needs the dtype),
    after which both aliases enter the next segment as boundary inputs
    with distinct lineage."""
    def run(bulked):
        a = mx.np.array([2.0, 3.0])
        a.attach_grad()
        ctx = engine.bulk(100) if bulked else engine.naive_engine()
        with ctx:
            with autograd.record():
                x = a * 1.0
                y = x.detach()
                y.attach_grad()
                z = (x * 3 + y * 5).sum()
            z.backward()
        return a.grad.asnumpy(), y.grad.asnumpy()

    (ga_b, gy_b), (ga_e, gy_e) = run(True), run(False)
    onp.testing.assert_allclose(ga_b, ga_e)   # 3 (through x)
    onp.testing.assert_allclose(gy_b, gy_e)   # 5


def test_marked_pending_alias_dispatches_eagerly():
    """mark_variables on a still-pending detached alias (no _data touch,
    no flush) diverges from the segment's recorded lineage: the segment
    must settle and dispatch that op eagerly rather than misroute the
    cotangent to the recorded producer."""
    from mxnet_tpu import _tape

    def run(bulked):
        a = mx.np.array([2.0, 3.0])
        a.attach_grad()
        ctx = engine.bulk(100) if bulked else engine.naive_engine()
        with ctx:
            with autograd.record():
                x = a * 1.0
                y = x.detach()
                _tape.mark_variables([y], [mx.np.zeros((2,))])
                z = (x * 3 + y * 5).sum()
            z.backward()
        return a.grad.asnumpy(), y.grad.asnumpy()

    (ga_b, gy_b), (ga_e, gy_e) = run(True), run(False)
    onp.testing.assert_allclose(ga_b, ga_e)   # 3 (through x)
    onp.testing.assert_allclose(gy_b, gy_e)   # 5


def test_hashable_slice_recurses():
    """A slice carrying an unhashable member must raise _Unkeyable (so
    dispatch falls back to eager) instead of TypeError at the trie
    lookup; np-integer members tokenize under the scalar rules."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import registry

    with pytest.raises(registry._Unkeyable):
        registry._hashable(slice(jnp.ones((2,)), None, None))
    t_np = registry._hashable(slice(onp.int32(2), None, None))
    t_py = registry._hashable(slice(2, None, None))
    assert t_np != t_py
    assert t_py == ('__slice__', ('i', 2), None, None)


# ----------------------------------------------- foreign-thread settles
def test_foreign_thread_settle_interleaving():
    """Regression pin for the try_record settle window (_bulk.py): the
    recording thread's segment is flushed BY ANOTHER THREAD between two
    of its records. The flushed re-check under the segment lock must
    restart recording into a fresh segment instead of appending to the
    dead one (which would orphan the outputs). Event-sequenced — the
    interleaving is the same every run."""
    import threading

    out = {}
    e_recorded = threading.Event()
    e_settled = threading.Event()
    errs = []

    def recorder():
        try:
            with engine.bulk(64):
                a = mx.np.ones((4,))
                out['y'] = a + 1            # lazy in segment S1
                seg1 = out['y']._lazy.seg
                e_recorded.set()
                assert e_settled.wait(10)   # main flushed S1 meanwhile
                # S1 is now foreign-flushed: this record must land in a
                # fresh segment, not the dead S1
                b = mx.np.ones((4,)) * 3
                out['w'] = b + 1
                assert out['w']._lazy is not None
                assert out['w']._lazy.seg is not seg1
                assert seg1.flushed
        except Exception as e:              # surfaced below
            errs.append(e)
            e_recorded.set()

    t = threading.Thread(target=recorder)
    t.start()
    assert e_recorded.wait(10)
    assert not errs
    # foreign settle: main thread flushes the recorder's live segment
    onp.testing.assert_allclose(out['y'].asnumpy(), 2.0)
    e_settled.set()
    t.join(10)
    assert not errs
    onp.testing.assert_allclose(out['w'].asnumpy(), 4.0)


def test_foreign_settle_stress():
    """Thread B keeps settling A's freshest lazy output while A records
    — every settled value must be correct and A's own sync at the end
    must agree. (The deterministic single-interleaving version is
    test_foreign_thread_settle_interleaving; this sweeps the window.)"""
    import threading

    rounds = 30
    latest = {'nd': None, 'round': -1}
    stop = threading.Event()
    errs = []

    def settler():
        try:
            while not stop.is_set():
                nd, rnd = latest['nd'], latest['round']
                if nd is not None:
                    got = nd.asnumpy()      # foreign settle mid-record
                    onp.testing.assert_allclose(got, float(rnd + 2))
        except Exception as e:
            errs.append(e)

    t = threading.Thread(target=settler)
    t.start()
    try:
        finals = []
        with engine.bulk(8):
            for i in range(rounds):
                a = mx.np.ones((4,)) * (i + 1)
                y = a + 1
                latest['nd'], latest['round'] = y, i
                finals.append((i, y))
        for i, y in finals:
            onp.testing.assert_allclose(y.asnumpy(), float(i + 2))
    finally:
        stop.set()
        t.join(10)
    assert not errs, errs
