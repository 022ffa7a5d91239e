"""Input rules for every registered op, and the kvstore fault soak.

Two things live here, under the name the reference gave its per-op
benchmark (``benchmark/opperf/opperf.py``); nothing in this file times
an op (the benchmark is ``chipbench/run.py``, ``PERF.md`` §1):

- ``_RULES`` / ``rule`` / ``_register_rules``: an example input per op
  family (≙ the reference's ``benchmark/opperf/rules/``), the input table
  of ``tests/test_op_sweep.py`` and ``tests/test_op_coverage_meta.py``.
- ``kvstore_soak`` (``--kvstore-soak N``): N push/pull rounds on an
  in-process ``dist_async`` store under a fixed fault spec
  (``--fault-spec``, default a deterministic periodic connection reset),
  verifying exactly-once delivery against the server's apply counters and
  printing one JSON line with retry/injection/apply counts. Exit status
  is non-zero when verification fails.

    python benchmark/opperf.py --cpu --kvstore-soak 50
    python benchmark/opperf.py --cpu --kvstore-soak 200 \
        --fault-spec 'reset_every:push:5;drop:push:0.2:seed=3'
"""

import argparse
import json
import os
import sys

_RULES = {}


def rule(*names, **gen):
    for n in names:
        _RULES[n] = gen


def _register_rules(np_, large=(1024, 1024), nn_scale=8):
    """Input-shape rules per op family (≙ benchmark/opperf/rules/).

    ``large``/``nn_scale`` shrink the inputs for the correctness sweep in
    tests/test_op_sweep.py."""
    u = lambda *s: np_.random.uniform(0.5, 1.5, s).astype('float32')  # noqa: E731
    LARGE = large
    sc = nn_scale

    for n in ['exp', 'log', 'sqrt', 'sin', 'cos', 'tanh', 'abs', 'square',
              'relu', 'sigmoid', 'erf', 'gelu', 'softplus', 'silu', 'sign',
              'floor', 'ceil', 'rint', 'negative', 'reciprocal', 'cbrt',
              'log1p', 'expm1',
              # round-2 additions
              'softsign', 'quadratic', 'div_sqrt_dim', 'round_ste',
              'sign_ste', 'gradient_multiplier', 'square_sum',
              'amp_cast']:
        rule(n, args=lambda u=u: (u(*LARGE),))
    for n in ['add', 'subtract', 'multiply', 'true_divide', 'power',
              'maximum', 'minimum', 'hypot', 'arctan2', 'logaddexp']:
        rule(n, args=lambda u=u: (u(*LARGE), u(*LARGE)))
    for n in ['sum', 'mean', 'max', 'min', 'prod', 'var', 'std']:
        rule(n, args=lambda u=u: (u(*LARGE),))
    rule('dot', args=lambda u=u: (u(*LARGE), u(*LARGE)))
    rule('matmul', args=lambda u=u, sc=sc: (u(4 * sc, 32 * sc, 32 * sc),
                                            u(4 * sc, 32 * sc, 32 * sc)))
    rule('batch_dot', args=lambda u=u, sc=sc: (u(4 * sc, 32 * sc, 32 * sc),
                                               u(4 * sc, 32 * sc, 32 * sc)))
    rule('einsum', args=lambda u=u, sc=sc: ('bij,bjk->bik',
                                            u(4 * sc, 32 * sc, 32 * sc),
                                            u(4 * sc, 32 * sc, 32 * sc)))
    rule('transpose', args=lambda u=u: (u(*LARGE),))
    rule('reshape', args=lambda u=u: (u(*LARGE),),
         kwargs_fn=lambda LARGE=LARGE: {'newshape':
                                        (LARGE[0] // 2, LARGE[1] * 2)})
    rule('concat', args=lambda u=u, sc=sc: ([u(64 * sc, 64 * sc),
                                             u(64 * sc, 64 * sc)],),
         kwargs={'axis': 0})
    rule('softmax', 'log_softmax',
         args=lambda u=u, sc=sc: (u(16 * sc, 128 * sc),))
    rule('topk', args=lambda u=u, sc=sc: (u(16 * sc, 128 * sc),),
         kwargs={'k': 8}, no_grad=True)
    rule('sort', 'argsort', args=lambda u=u, sc=sc: (u(16 * sc, 128 * sc),),
         no_grad=True)
    rule('fully_connected',
         args=lambda u=u, sc=sc: (u(8 * sc, 128 * sc), u(128 * sc, 128 * sc),
                                  u(128 * sc)),
         kwargs_fn=lambda sc=sc: {'num_hidden': 128 * sc})
    rule('convolution',
         args=lambda u=u, sc=sc: (u(4 * sc, 8 * sc, 7 * sc, 7 * sc),
                                  u(8 * sc, 8 * sc, 3, 3), u(8 * sc)),
         kwargs_fn=lambda sc=sc: {'kernel': (3, 3), 'pad': (1, 1),
                                  'num_filter': 8 * sc})
    rule('pooling', args=lambda u=u, sc=sc: (u(4 * sc, 8 * sc, 7 * sc,
                                               7 * sc),),
         kwargs={'kernel': (2, 2), 'stride': (2, 2), 'pool_type': 'max'})
    rule('batch_norm_inference',
         args=lambda u=u, sc=sc: (u(4 * sc, 8 * sc, 7 * sc, 7 * sc),
                                  u(8 * sc), u(8 * sc), u(8 * sc),
                                  u(8 * sc) * 0 + 1))
    rule('layer_norm', args=lambda u=u, sc=sc: (u(8 * sc, 128 * sc),
                                                u(128 * sc), u(128 * sc)))
    rule('rms_norm', args=lambda u=u, sc=sc: (u(8 * sc, 128 * sc),
                                              u(128 * sc)))
    rule('embedding', args=lambda np_=np_, u=u, sc=sc: (
        np_.random.randint(0, 100, (8 * sc, 16 * sc)).astype('float32'),
        u(100, 64 * sc)))
    rule('multi_head_attention',
         args=lambda u=u, sc=sc: (u(2 * sc, 64 * sc, 64 * sc),
                                  u(2 * sc, 64 * sc, 64 * sc),
                                  u(2 * sc, 64 * sc, 64 * sc)),
         kwargs={'num_heads': 8})
    rule('flash_attention',
         args=lambda u=u, sc=sc: (u(2, 2 * sc, 64 * sc, 64),
                                  u(2, 2 * sc, 64 * sc, 64),
                                  u(2, 2 * sc, 64 * sc, 64)))
    rule('take', args=lambda np_=np_, u=u, sc=sc: (
        u(100, 64 * sc), np_.random.randint(0, 100, (64 * sc,))
        .astype('float32')))
    rule('where', args=lambda np_=np_, u=u: (
        (np_.random.uniform(size=LARGE) > .5), u(*LARGE), u(*LARGE)))
    rule('cumsum', args=lambda u=u: (u(*LARGE),))
    rule('clip', args=lambda u=u: (u(*LARGE),),
         kwargs={'a_min': 0.7, 'a_max': 1.3})
    rule('sgd_update', args=lambda u=u: (u(*LARGE), u(*LARGE)),
         no_grad=True)
    rule('adam_update',
         args=lambda u=u: (u(*LARGE), u(*LARGE), u(*LARGE), u(*LARGE)),
         no_grad=True)

    # ------------------------------------------------- manipulation family
    rule('stack', args=lambda u=u: ([u(*LARGE), u(*LARGE)],),
         kwargs={'axis': 0})
    rule('tile', args=lambda u=u: (u(*LARGE),), kwargs={'reps': (2, 1)})
    rule('repeat', args=lambda u=u: (u(*LARGE),),
         kwargs={'repeats': 2, 'axis': 0})
    rule('flip', args=lambda u=u: (u(*LARGE),), kwargs={'axis': 0})
    rule('roll', args=lambda u=u: (u(*LARGE),),
         kwargs={'shift': 3, 'axis': 0})
    rule('squeeze', args=lambda u=u, LARGE=LARGE: (
        u(1, *LARGE),), kwargs={'axis': 0})
    rule('expand_dims', args=lambda u=u: (u(*LARGE),), kwargs={'axis': 0})
    rule('swapaxes', args=lambda u=u: (u(*LARGE),),
         kwargs={'axis1': 0, 'axis2': 1})
    rule('pad', args=lambda u=u: (u(*LARGE),),
         kwargs={'pad_width': ((1, 1), (2, 2))})
    rule('tril', 'triu', args=lambda u=u: (u(*LARGE),))
    rule('diff', args=lambda u=u: (u(*LARGE),))
    rule('cumprod', args=lambda u=u: (u(*LARGE),))
    rule('broadcast_to', args=lambda u=u, LARGE=LARGE: (u(1, LARGE[1]),),
         kwargs_fn=lambda LARGE=LARGE: {'shape': LARGE})
    rule('split', args=lambda u=u: (u(*LARGE), 2), kwargs={'axis': 0})
    rule('take_along_axis', args=lambda np_=np_, u=u, LARGE=LARGE: (
        u(*LARGE),
        np_.random.randint(0, LARGE[0], LARGE).astype('int64')),
        kwargs={'axis': 0})
    rule('gather_nd', args=lambda np_=np_, u=u, LARGE=LARGE: (
        u(*LARGE),
        np_.random.randint(0, LARGE[0], (1, 8)).astype('float32')))
    rule('one_hot', args=lambda np_=np_, LARGE=LARGE: (
        np_.random.randint(0, 10, (LARGE[0],)).astype('float32'),),
        kwargs={'depth': 10}, no_grad=True)
    rule('unique', args=lambda np_=np_: (
        np_.random.randint(0, 50, (256,)).astype('float32'),),
        no_grad=True)
    rule('searchsorted', args=lambda np_=np_: (
        np_.sort(np_.random.uniform(size=64)).astype('float32'),
        np_.random.uniform(size=32).astype('float32')), no_grad=True)

    # ------------------------------------------------------ linalg family
    def _spd(n):
        a = np_.random.uniform(0.1, 1.0, (n, n)).astype('float32')
        return a @ a.T + n * np_.eye(n, dtype='float32')

    rule('linalg_cholesky', args=lambda _spd=_spd: (_spd(24),))
    rule('linalg_inv', args=lambda _spd=_spd: (_spd(24),))
    rule('linalg_det', args=lambda _spd=_spd: (_spd(8),))  # det(24I)~1e33 overflows f32 grads
    rule('linalg_slogdet', args=lambda _spd=_spd: (_spd(24),))

    rule('linalg_qr', args=lambda u=u: (u(24, 16),))
    rule('linalg_svd', args=lambda u=u: (u(24, 16),), no_grad=True)
    rule('linalg_eigh', args=lambda _spd=_spd: (_spd(24),))
    rule('linalg_solve', args=lambda _spd=_spd, u=u: (_spd(24), u(24, 4)))
    rule('linalg_norm', args=lambda u=u: (u(*LARGE),))
    rule('linalg_trsm', args=lambda _spd=_spd, u=u: (_spd(16), u(16, 8)))
    rule('linalg_gemm2', args=lambda u=u: (u(32, 32), u(32, 32)))
    rule('kron', args=lambda u=u: (u(8, 8), u(4, 4)))
    rule('tensordot', args=lambda u=u: (u(8, 16), u(16, 8)),
         kwargs={'axes': 1})
    rule('outer', args=lambda u=u: (u(32), u(32)))
    rule('trace', args=lambda u=u: (u(*LARGE),))
    rule('diagonal', args=lambda u=u: (u(*LARGE),))

    # ------------------------------------------------------- more reduce
    rule('median', args=lambda u=u: (u(*LARGE),), no_grad=True)
    rule('percentile', args=lambda u=u: (u(*LARGE), 75.0), no_grad=True)
    rule('nansum', 'nanmean', args=lambda u=u: (u(*LARGE),))
    rule('amax', 'amin', 'ptp', args=lambda u=u: (u(*LARGE),))
    rule('argmax', 'argmin', args=lambda u=u: (u(*LARGE),), no_grad=True)
    rule('count_nonzero', args=lambda u=u: (u(*LARGE),), no_grad=True)

    # --------------------------------------------------------- nn extras
    rule('leaky_relu', args=lambda u=u: (u(*LARGE),))
    rule('hard_sigmoid', 'hard_swish', args=lambda u=u: (u(*LARGE),))
    rule('l2_normalization', args=lambda u=u, sc=sc: (u(4 * sc, 16 * sc),))
    rule('group_norm', args=lambda u=u, sc=sc: (
        u(2, 8, 4 * sc, 4 * sc), u(8), u(8)), kwargs={'num_groups': 2})
    rule('instance_norm', args=lambda u=u, sc=sc: (
        u(2, 8, 4 * sc, 4 * sc), u(8), u(8)))
    rule('lrn', args=lambda u=u, sc=sc: (u(2, 8, 4 * sc, 4 * sc),))
    rule('moments', args=lambda u=u: (u(*LARGE),))
    rule('masked_softmax', args=lambda np_=np_, u=u, LARGE=LARGE: (
        u(*LARGE), (np_.random.uniform(size=LARGE) > 0.3)))
    rule('im2col', args=lambda u=u, sc=sc: (u(2, 4, 4 * sc, 4 * sc),),
         kwargs={'kernel': (3, 3), 'pad': (1, 1)})
    rule('depth_to_space', args=lambda u=u, sc=sc: (
        u(2, 16, 2 * sc, 2 * sc),), kwargs={'block_size': 2})
    rule('space_to_depth', args=lambda u=u, sc=sc: (
        u(2, 4, 4 * sc, 4 * sc),), kwargs={'block_size': 2})
    rule('rnn', args=lambda np_=np_, u=u: (
        u(8, 4, 16),
        np_.random.uniform(-0.1, 0.1,
                           (4 * 32 * 16 + 4 * 32 * 32 + 2 * 4 * 32,))
        .astype('float32'), np_.zeros((1, 4, 32), 'float32'),
        np_.zeros((1, 4, 32), 'float32')),
        kwargs={'mode': 'lstm', 'state_size': 32, 'num_layers': 1})
    rule('ctc_loss', args=lambda np_=np_, u=u: (
        u(16, 4, 12), np_.random.randint(1, 11, (4, 5)).astype('float32')))
    rule('interleaved_matmul_selfatt_qk',
         args=lambda u=u, sc=sc: (u(8 * sc, 2, 8 * 3 * 8),),
         kwargs={'heads': 8})


def kvstore_soak(rounds, fault_spec, size=1024, keys=2, port=None):
    """N rounds of push/pull per key on an in-process ``dist_async``
    store with a fault plan armed; returns the result record. The
    invariant proved: after N pushes of ones — across every injected
    reset/drop and the retries they trigger — each key holds exactly N
    and the server applied exactly ``rounds * keys`` pushes (the
    exactly-once seq-dedup contract, docs/fault-tolerance.md)."""
    import time
    if port is None:
        port = 49821
    os.environ.setdefault('MX_COORDINATOR', f'127.0.0.1:{port}')
    os.environ.setdefault('MXNET_KVSTORE_ASYNC_PORT', str(port + 30))
    os.environ.setdefault('MXNET_KVSTORE_HEARTBEAT_S', '3600')
    os.environ.setdefault('MXNET_KVSTORE_RPC_BACKOFF_S', '0.005')
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore
    from mxnet_tpu.kvstore import faults

    faults.clear()
    if fault_spec:
        faults.configure(fault_spec)
    kv = kvstore.create('dist_async')
    names = [f'soak{k}' for k in range(keys)]
    for n in names:
        kv.init(n, mx.np.zeros((size,)))
    one = mx.np.ones((size,))
    t0 = time.perf_counter()
    for _ in range(rounds):
        for n in names:
            kv.push(n, one)
            kv.pull(n)
    elapsed = time.perf_counter() - t0
    ok = all(np.allclose(kv.pull(n).asnumpy(), float(rounds))
             for n in names)
    counters = kv.server_health()[0]['counters']
    ok = ok and counters['push_applied'] == rounds * keys
    result = {
        'mode': 'kvstore-soak', 'rounds': rounds, 'keys': keys,
        'fault_spec': fault_spec, 'elapsed_s': round(elapsed, 3),
        'transport': kv.transport_stats(),
        'faults': faults.injected(),
        'server_counters': counters,
        'verified_exactly_once': ok,
    }
    faults.clear()
    kv.close()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--cpu', action='store_true')
    ap.add_argument('--kvstore-soak', type=int, required=True,
                    metavar='N',
                    help='run N dist_async push/pull rounds under '
                         '--fault-spec')
    ap.add_argument('--fault-spec',
                    default='reset_every:push:7;delay:push:1ms',
                    help='MXNET_KVSTORE_FAULT_SPEC grammar for the '
                         'soak (empty string = fault-free)')
    args = ap.parse_args()

    # repo root on sys.path regardless of device: `python
    # benchmark/opperf.py` puts only benchmark/ there
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if args.cpu:
        import _cpu_guard
        _cpu_guard.force_cpu()

    res = kvstore_soak(args.kvstore_soak, args.fault_spec)
    print(json.dumps(res), flush=True)
    sys.exit(0 if res['verified_exactly_once'] else 1)


if __name__ == '__main__':
    main()
