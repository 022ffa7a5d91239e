"""Multi-process dist-kvstore worker script.

Reference: ``tests/nightly/dist_sync_kvstore.py`` — a plain worker script
asserting synchronous kvstore semantics, launched as a local multi-process
cluster by ``tools/launch.py -n N --launcher local`` (the reference's CI
pattern from ``tests/nightly/test_distributed_training-gpu.sh:27-34``,
scheduler+servers+workers collapsed here to N equal SPMD processes).

Run directly:
    JAX_PLATFORMS=cpu python tools/launch.py -n 2 --launcher local \
        python tests/nightly/dist_sync_kvstore.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import _cpu_guard  # noqa: E402
_cpu_guard.force_cpu()

import numpy as onp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import kvstore, parallel  # noqa: E402


def main():
    parallel.init_distributed()
    kv = kvstore.create('dist_tpu_sync')
    rank, size = kv.rank, kv.num_workers
    assert size == int(os.environ.get('MX_NPROC', '1')), \
        (size, os.environ.get('MX_NPROC'))

    # --- synchronous pushpull: out == sum over workers (reference
    # dist_sync_kvstore.py check_default_keys)
    kv.init(3, mx.np.zeros((4, 2)))
    val = mx.np.array(onp.full((4, 2), rank + 1.0, 'f'))
    out = mx.np.zeros((4, 2))
    kv.pushpull(3, val, out=out)
    expect = sum(r + 1.0 for r in range(size))
    onp.testing.assert_allclose(out.asnumpy(), onp.full((4, 2), expect),
                                rtol=1e-6)

    # --- broadcast: rank 0's value is authoritative (KVStoreDist::Init)
    mine = mx.np.array(onp.full((3,), 100.0 + rank, 'f'))
    got = mx.np.zeros((3,))
    kv.broadcast('w0', mine, out=got)
    onp.testing.assert_allclose(got.asnumpy(), onp.full((3,), 100.0),
                                rtol=1e-6)

    # --- barrier then compressed pushpull (2-bit, error feedback kept
    # worker-local; each worker contributes ±threshold after quantization)
    kv.barrier()
    kv.set_gradient_compression({'type': '2bit', 'threshold': 0.5})
    g = mx.np.array(onp.array([0.6, -0.7, 0.1, 0.0], 'f'))
    cout = mx.np.zeros((4,))
    kv.pushpull(7, g, out=cout)
    onp.testing.assert_allclose(
        cout.asnumpy(), [0.5 * size, -0.5 * size, 0.0, 0.0], atol=1e-6)

    # --- optimizer-on-store: the reference's update_on_kvstore runs the
    # optimizer on the PS (kvstore_dist_server.h ApplyUpdates); here the
    # updater applies to every host's replica of the store after the
    # global allreduce, so all ranks converge identically.
    kv2 = kvstore.create('dist_tpu_sync')
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
    kv2.init(0, mx.np.array(onp.full((2,), 10.0, 'f')))
    grad = mx.np.array(onp.full((2,), 1.0, 'f'))
    wout = mx.np.zeros((2,))
    kv2.pushpull(0, grad, out=wout)
    # merged grad = size * 1.0; w <- 10 - 0.5 * size
    onp.testing.assert_allclose(wout.asnumpy(),
                                onp.full((2,), 10.0 - 0.5 * size),
                                rtol=1e-6)

    # --- fused (bucketed) pushpull: many keys, one collective per fusion
    # buffer (reference PushPullDefault + P3 slicing, here XLA psum)
    fkeys = list(range(20, 27))
    fvals = [mx.np.array(onp.full((5, 3), (rank + 1.0) * (k - 19), 'f'))
             for k in fkeys]
    fouts = [mx.np.zeros((5, 3)) for _ in fkeys]
    kv.set_gradient_compression({'type': 'none'})
    kv.fused_pushpull(fkeys, fvals, outs=[[o] for o in fouts],
                      priorities=[-i for i in range(len(fkeys))])
    for k, o in zip(fkeys, fouts):
        want = sum((r + 1.0) * (k - 19) for r in range(size))
        onp.testing.assert_allclose(o.asnumpy(), onp.full((5, 3), want),
                                    rtol=1e-6)

    # --- fused + 2-bit compression: words cross the wire, decode+sum on
    # device; each worker contributes +-threshold after quantization
    kvc = kvstore.create('dist_tpu_sync')
    kvc.set_gradient_compression({'type': '2bit', 'threshold': 0.5})
    cg = [mx.np.array(onp.array([0.6, -0.7, 0.1, 0.0], 'f')),
          mx.np.array(onp.array([[0.9, -0.1], [0.0, 0.55]], 'f'))]
    couts = [mx.np.zeros((4,)), mx.np.zeros((2, 2))]
    kvc.fused_pushpull([70, 71], cg, outs=couts)
    onp.testing.assert_allclose(
        couts[0].asnumpy(), [0.5 * size, -0.5 * size, 0.0, 0.0], atol=1e-6)
    onp.testing.assert_allclose(
        couts[1].asnumpy(),
        [[0.5 * size, 0.0], [0.0, 0.5 * size]], atol=1e-6)

    # --- ZeRO-1 sharded optimizer-on-store: updater runs once per key
    # globally (on its owner), weights all_gather back; every rank must
    # see identical post-update weights
    kvz = kvstore.create('dist_tpu_sync')
    kvz.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
    zkeys = [0, 1, 2]
    for k in zkeys:
        kvz.init(k, mx.np.array(onp.full((3,), 10.0 * (k + 1), 'f')))
    zgrads = [mx.np.array(onp.full((3,), 1.0 * (k + 1), 'f'))
              for k in zkeys]
    zouts = [mx.np.zeros((3,)) for _ in zkeys]
    kvz.fused_pushpull(zkeys, zgrads, outs=zouts)
    for k, o in zip(zkeys, zouts):
        # merged grad = size*(k+1); w <- 10(k+1) - 0.5*size*(k+1)
        want = 10.0 * (k + 1) - 0.5 * size * (k + 1)
        onp.testing.assert_allclose(o.asnumpy(), onp.full((3,), want),
                                    rtol=1e-6)

    # --- row_sparse_pull across processes: store holds the full (dense)
    # table, each rank pulls its own row ids (reference PullRowSparse)
    kv.init('emb', mx.np.array(
        onp.arange(8, dtype='float32').reshape(4, 2)))
    rows = mx.np.array(onp.array([rank, 3]))
    pulled = kv.row_sparse_pull('emb', row_ids=rows)
    got = pulled.asnumpy()
    onp.testing.assert_allclose(got[rank], [2.0 * rank, 2.0 * rank + 1])
    onp.testing.assert_allclose(got[3], [6.0, 7.0])

    print(f'worker {rank}/{size}: all dist kvstore assertions passed',
          flush=True)


if __name__ == '__main__':
    main()
