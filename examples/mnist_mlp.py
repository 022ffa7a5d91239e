"""MNIST-style MLP training (parity with reference example/gluon/mnist).

Uses real MNIST when available under MXNET_HOME/datasets/mnist, else a
synthetic separable dataset (zero-egress CI), so the script always runs
end-to-end: DataLoader -> hybridized net -> autograd -> Trainer -> metric.

Run: python examples/mnist_mlp.py [--epochs 3] [--cpu]
"""

import argparse
import os
import sys
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--epochs', type=int, default=3)
    parser.add_argument('--batch-size', type=int, default=128)
    parser.add_argument('--lr', type=float, default=0.01)
    parser.add_argument('--cpu', action='store_true',
                        help='force CPU')
    parser.add_argument('--no-hybridize', action='store_true')
    args = parser.parse_args()

    if args.cpu:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import _cpu_guard
        _cpu_guard.force_cpu()

    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    ctx = mx.current_context()
    print(f'context: {ctx}')

    try:
        train_ds = gluon.data.vision.MNIST(train=True)
        X = train_ds._data.asnumpy().reshape(-1, 784).astype('float32') / 255
        Y = np.asarray(train_ds._label)
        print('using real MNIST')
    except Exception:
        print('MNIST files not found; using synthetic dataset')
        rng = np.random.default_rng(0)
        centers = rng.standard_normal((10, 784)).astype('float32') * 2
        Y = rng.integers(0, 10, 8192)
        X = centers[Y] + rng.standard_normal((8192, 784)).astype(
            'float32') * 0.7
        Y = Y.astype('int32')

    loader = gluon.data.DataLoader(
        gluon.data.ArrayDataset(X, Y), batch_size=args.batch_size,
        shuffle=True, last_batch='discard')

    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation='relu'),
            nn.Dense(64, activation='relu'),
            nn.Dense(10))
    net.initialize(init='xavier', ctx=ctx)
    if not args.no_hybridize:
        net.hybridize(static_alloc=True)

    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        metric.reset()
        tic = time.time()
        n = 0
        for data, label in loader:
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label).mean()
            loss.backward()
            trainer.step(1)
            metric.update([label], [out])
            n += data.shape[0]
        name, acc = metric.get()
        print(f'epoch {epoch}: {name}={acc:.4f} '
              f'({n / (time.time() - tic):.0f} samples/s)')

    assert acc > 0.9, f'training failed to converge: acc={acc}'
    net.export('/tmp/mnist_mlp')
    print('exported; final accuracy %.4f' % acc)


if __name__ == '__main__':
    main()
