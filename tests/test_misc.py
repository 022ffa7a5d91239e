"""Engine semantics, exception handling, profiler, recordio, runtime
features, initializers, context (reference test_engine.py,
test_exc_handling.py, test_profiler.py, misc)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def test_context():
    assert mx.cpu(0) == mx.cpu(0)
    assert mx.cpu(0) != mx.cpu(1)
    assert str(mx.tpu(0)) == 'tpu(0)'
    with mx.cpu(1):
        assert mx.current_context() == mx.cpu(1)
    assert mx.current_context() != mx.cpu(1)
    d = {mx.cpu(0): 1}
    assert d[mx.cpu(0)] == 1


def test_tpu_context_needs_a_real_chip():
    """No CPU stand-in for mx.tpu(), and no wrap of a chip index that
    does not exist onto one that does."""
    import jax
    assert jax.default_backend() == 'cpu'
    with pytest.raises(RuntimeError, match='no accelerator'):
        mx.tpu(0).to_jax()
    with pytest.raises(RuntimeError, match='no accelerator'):
        mx.gpu(3).to_jax()

    class _Chip:
        platform = 'tpu'

    chips = [_Chip(), _Chip()]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, 'local_devices', lambda *a, **k: chips)
        assert mx.tpu(1).to_jax() is chips[1]
        for bad in (2, 5, -1):
            with pytest.raises(ValueError, match='out of range'):
                mx.tpu(bad).to_jax()


@pytest.mark.parametrize('env_dir', [None, 'from_env'])
def test_compile_cache_placed_from_outside(env_dir, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets nothing. Unset:
    the one fixed path under the checkout."""
    import os
    import jax
    from mxnet_tpu import _compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert _compile_cache.CHECKOUT_CACHE == os.path.join(repo, '.jax_cache')
    before = jax.config.jax_compilation_cache_dir
    updates = []
    real_update = jax.config.update

    def spy(name, val):
        updates.append((name, val))
        real_update(name, val)

    monkeypatch.setattr(jax.config, 'update', spy)
    try:
        if env_dir is None:
            monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
            assert _compile_cache.place() == _compile_cache.CHECKOUT_CACHE
            assert updates == [('jax_compilation_cache_dir',
                                _compile_cache.CHECKOUT_CACHE)]
        else:
            monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR',
                               str(tmp_path / env_dir))
            assert _compile_cache.place() == before
            assert updates == []
    finally:
        real_update('jax_compilation_cache_dir', before)


def test_naive_engine_switch():
    with mx.engine.naive_engine():
        x = mx.np.ones((2, 2)) * 3
        assert x.asnumpy().sum() == 12
    with mx.engine.bulk(16):
        y = mx.np.ones((2,)) + 1
    assert y.asnumpy().tolist() == [2, 2]


def test_async_exception_at_sync_point():
    """Reference test_exc_handling.py: errors surface at sync points."""
    bad = mx.np.array([1.0]) / mx.np.array([0.0])
    # inf, not an exception (matches numpy semantics)
    assert np.isinf(bad.asnumpy()).all()
    with pytest.raises(Exception):
        mx.np.ones((2, 2)).reshape((5, 5))


def test_profiler_api(tmp_path):
    prof = mx.profiler
    prof.set_config(profile_all=True, filename=str(tmp_path / 'prof'))
    with prof.scope('test_region'):
        mx.np.ones((10, 10)).sum().wait_to_read()
    out = prof.dumps()
    assert 'test_region' in out


def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats.is_enabled('XLA')
    assert not feats.is_enabled('CUDA')
    assert len(mx.runtime.feature_list()) > 5


def test_recordio_roundtrip(tmp_path):
    from mxnet_tpu import recordio
    path = str(tmp_path / 'test.rec')
    w = recordio.MXRecordIO(path, 'w')
    for i in range(5):
        w.write(f'record{i}'.encode())
    w.close()
    r = recordio.MXRecordIO(path, 'r')
    items = []
    while True:
        buf = r.read()
        if buf is None:
            break
        items.append(buf)
    assert items == [f'record{i}'.encode() for i in range(5)]


def test_recordio_pack_unpack():
    from mxnet_tpu import recordio
    header = recordio.IRHeader(0, 5.0, 7, 0)
    s = recordio.pack(header, b'imagedata')
    h2, data = recordio.unpack(s)
    assert h2.label == 5.0
    assert h2.id == 7
    assert data == b'imagedata'
    # vector label
    header = recordio.IRHeader(0, np.array([1.0, 2.0], dtype='float32'), 1, 0)
    s = recordio.pack(header, b'x')
    h3, d3 = recordio.unpack(s)
    assert_almost_equal(h3.label, [1.0, 2.0])


def test_initializers():
    from mxnet_tpu import initializer
    for name, init in [('xavier', initializer.Xavier()),
                       ('normal', initializer.Normal(1.0)),
                       ('uniform', initializer.Uniform(2.0)),
                       ('orthogonal', initializer.Orthogonal()),
                       ('msraprelu', initializer.MSRAPrelu())]:
        arr = mx.np.zeros((8, 8))
        init('weight', arr)
        assert abs(arr.asnumpy()).sum() > 0, name
    arr = mx.np.zeros((4,))
    initializer.One()('weight', arr)
    assert_almost_equal(arr, np.ones(4))
    c = mx.np.zeros((2,))
    initializer.Constant(3.5)('weight', c)
    assert_almost_equal(c, [3.5, 3.5])
    # registry
    assert isinstance(initializer.create('xavier'), initializer.Xavier)


def test_lr_schedulers():
    from mxnet_tpu import lr_scheduler
    s = lr_scheduler.FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert s(1) == 1.0
    assert s(11) == 0.5
    m = lr_scheduler.MultiFactorScheduler(step=[5, 10], factor=0.1,
                                          base_lr=1.0)
    assert m(1) == 1.0
    assert m(6) == pytest.approx(0.1)
    p = lr_scheduler.PolyScheduler(max_update=100, base_lr=1.0)
    assert p(0) == 1.0
    assert p(100) < 0.01
    c = lr_scheduler.CosineScheduler(max_update=100, base_lr=1.0,
                                     warmup_steps=10)
    assert c(5) < 1.0  # warming up
    assert c(100) < 0.01


def test_amp_policy():
    mx.amp.init()
    assert mx.amp.is_enabled()
    assert mx.amp.compute_dtype() == 'bfloat16'
    net = mx.gluon.nn.Dense(2, in_units=2)
    net.initialize()
    mx.amp.convert_hybrid_block(net)
    assert str(net.weight.data().dtype) == 'bfloat16'


def test_image_ops():
    img = mx.np.array(np.random.randint(0, 255, (10, 12, 3)).astype('uint8'))
    from mxnet_tpu import image
    r = image.imresize(img, 6, 5)
    assert r.shape == (5, 6, 3)
    c, _ = image.center_crop(img, (4, 4))
    assert c.shape == (4, 4, 3)
    s = image.resize_short(img, 6)
    assert min(s.shape[:2]) == 6


def test_visualization_print_summary(capsys):
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(4, in_units=3))
    net.initialize()
    mx.visualization.print_summary(net, (1, 3))
    assert 'Total params' in capsys.readouterr().out


def test_attention_ops():
    """interleaved matmul attention parity (reference
    src/operator/contrib/transformer.cc:650-826)."""
    np.random.seed(0)
    S, B, H, D = 4, 2, 2, 3
    qkv = np.random.randn(S, B, H * 3 * D).astype('float32')
    scores = mx.nd.interleaved_matmul_selfatt_qk(mx.np.array(qkv), heads=H)
    assert scores.shape == (B * H, S, S)
    # manual reference
    x = qkv.reshape(S, B, H, 3, D)
    q, k = x[:, :, :, 0], x[:, :, :, 1]
    want = np.einsum('sbhd,tbhd->bhst', q * (D ** -0.5), k).reshape(
        B * H, S, S)
    assert_almost_equal(scores, want, rtol=1e-4)
    att = mx.nd.softmax(scores, axis=-1)
    out = mx.nd.interleaved_matmul_selfatt_valatt(mx.np.array(qkv), att,
                                                  heads=H)
    assert out.shape == (S, B, H * D)
    # fused MHA
    q2 = mx.np.array(np.random.randn(B, S, H * D).astype('float32'))
    o = mx.nd.multi_head_attention(q2, q2, q2, num_heads=H)
    assert o.shape == (B, S, H * D)


def test_box_ops():
    boxes = mx.np.array([[0., 0., 2., 2.], [1., 1., 3., 3.]])
    iou = mx.nd.box_iou(boxes, boxes)
    assert_almost_equal(np.diag(iou.asnumpy()), [1.0, 1.0])
    assert iou.asnumpy()[0, 1] == pytest.approx(1.0 / 7.0, rel=1e-4)


def test_estimator_fit():
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon import data as gdata, loss as gloss, nn
    X = np.random.randn(32, 4).astype('float32')
    y = (X.sum(1) > 0).astype('int32')
    loader = gdata.DataLoader(gdata.ArrayDataset(X, y), batch_size=8)
    net = nn.Dense(2)
    net.initialize()
    est = Estimator(net, gloss.SoftmaxCrossEntropyLoss())
    est.fit(loader, epochs=1)


# ------------------------------------------------------------------- amp

def test_amp_dynamic_loss_scaler():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import amp, autograd, gluon

    amp.init(target_dtype='float16')
    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1})
    amp.init_trainer(trainer)
    scaler = trainer._amp_loss_scaler
    s0 = scaler.loss_scale
    assert s0 > 1.0

    x = mx.np.array(np.random.uniform(-1, 1, (2, 3)).astype('f'))
    with autograd.record():
        out = net(x)
        with amp.scale_loss((out ** 2).mean(), trainer) as scaled:
            pass
        loss = scaled
    loss.backward()
    ok = amp.unscale(trainer)
    assert ok                                     # finite grads → applied
    g = net.weight.grad().asnumpy()
    assert np.isfinite(g).all() and np.abs(g).max() < 10  # unscaled back

    # force an overflow: non-finite grad → zeroed, scale halves
    net.weight.grad()._rebind(
        mx.np.array(np.full((4, 3), np.inf, 'f'))._data)
    ok = amp.unscale(trainer)
    assert not ok
    assert scaler.loss_scale == s0 / 2
    assert (net.weight.grad().asnumpy() == 0).all()
    amp._state['enabled'] = False


def test_amp_overflow_skips_trainer_update():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import amp, autograd, gluon

    amp.init(target_dtype='float16')
    net = gluon.nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1, 'momentum': 0.9,
                             'wd': 0.1})
    amp.init_trainer(trainer)
    w_before = net.weight.data().asnumpy().copy()
    x = mx.np.ones((1, 2))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    net.weight.grad()._rebind(
        mx.np.array(np.full((2, 2), np.inf, 'f'))._data)
    ok = amp.unscale(trainer)
    assert not ok
    trainer.step(1)
    # overflow step applies NO update: wd/momentum untouched
    np.testing.assert_array_equal(net.weight.data().asnumpy(), w_before)
    amp._state['enabled'] = False


def test_early_stopping_auto_mode_and_estimator_polls_all_handlers():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        EarlyStoppingHandler)

    # auto mode resolves accuracy-like monitors to 'max'
    acc = mx.metric.Accuracy()
    h = EarlyStoppingHandler(acc, patience=0)
    assert h.mode == 'max'

    # a custom handler's stop flag halts fit()
    net = gluon.nn.Dense(2)
    net.initialize()
    metrics = [mx.metric.Accuracy()]
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    train_metrics=metrics)
    assert len(metrics) == 1               # caller's list untouched

    class StopNow(EarlyStoppingHandler):
        def epoch_end(self, estimator, *a, **k):
            self.stop_training = True

    data = [(mx.np.ones((4, 3)), mx.np.zeros((4,)))]
    stopper = StopNow(acc)
    est.fit(data, epochs=50, event_handlers=[stopper])
    assert stopper.stop_training
    assert est.current_epoch if hasattr(est, 'current_epoch') else True


def test_multinomial_batched_and_categorical():
    import numpy as np
    import mxnet_tpu as mx
    probs = mx.np.array(np.tile(np.array([0.1, 0.2, 0.7], 'f'), (4, 1)))
    out = mx.npx.sample_multinomial(probs, shape=5)
    assert out.shape == (4, 5)
    assert (out.asnumpy() >= 0).all() and (out.asnumpy() < 3).all()
    # scalar draw per row
    single = mx.npx.sample_multinomial(probs)
    assert single.shape == (4,)
    # get_prob returns log-probs of the samples
    s, lp = mx.npx.sample_multinomial(probs, shape=2, get_prob=True)
    assert s.shape == (4, 2) and lp.shape == (4, 2)
    assert (lp.asnumpy() <= 0).all()
    # categorical with num_samples on batched logits
    logits = mx.np.array(np.random.randn(8, 5).astype('f'))
    c = mx.npx.categorical(logits, num_samples=3)
    assert c.shape == (8, 3)
