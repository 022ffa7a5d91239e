#!/usr/bin/env python
"""Measure KVStore push/pull bandwidth (reference analog:
``tools/bandwidth/measure.py`` — allreduce bandwidth of model-sized
gradients through the KVStore).

The TPU path being measured is the jitted XLA allreduce that replaced the
reference's ps-lite/NCCL transports. Reports per-iteration time and the
algorithmic bandwidth 2·S·(n-1)/n / t (the standard allreduce cost model)
over the aggregate gradient bytes of the chosen model.

Usage:
    python tools/bandwidth/measure.py --network resnet50_v1 --num-batches 10
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import kvstore  # noqa: E402
from mxnet_tpu.ndarray.ndarray import NDArray as _ND  # noqa: E402


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description='KVStore bandwidth bench')
    parser.add_argument('--network', type=str, default='resnet50_v1',
                        help='model whose gradient sizes to simulate, or '
                             '"uniform" for --size-mb equal chunks')
    parser.add_argument('--kv-store', type=str, default='device')
    parser.add_argument('--num-batches', type=int, default=10)
    parser.add_argument('--warmup', type=int, default=2)
    parser.add_argument('--size-mb', type=float, default=100.0,
                        help='total MB when --network uniform')
    parser.add_argument('--num-keys', type=int, default=50,
                        help='key count when --network uniform')
    parser.add_argument('--disp-batches', type=int, default=1)
    parser.add_argument('--per-key', action='store_true',
                        help='issue one pushpull per key (round-1 path) '
                             'instead of one fused_pushpull call')
    parser.add_argument('--replicas', type=int, default=0,
                        help='device-replica copies per key to reduce; '
                             '0 = one per local device (min 2, so the '
                             'measurement always moves real bytes)')
    parser.add_argument('--device-only', action='store_true',
                        help='measure the pure device-side reduce as one '
                             'on-device loop (no per-iter host dispatch): '
                             'the roofline-relative number.')
    return parser.parse_args(argv)


def grad_shapes(args):
    if args.network == 'uniform':
        per = int(args.size_mb * 1e6 / 4 / args.num_keys)
        return [(per,)] * args.num_keys
    from mxnet_tpu.gluon.model_zoo import vision
    net = getattr(vision, args.network)()
    net.initialize()
    net(mx.np.ones((1, 3, 224, 224)))
    return [p.data().shape for p in net.collect_params().values()]


def device_only_bench(args, total_bytes, n_rep):
    """K chained replica-reduce rounds inside ONE executable
    (lax.fori_loop): measures what the fused reduce costs on device with
    host dispatch out of the picture. Each round's replicas are rolls of
    the evolving buffer — real memory traffic XLA cannot simplify away."""
    import time
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = total_bytes // 4
    k_inner = 25

    def round_(i, buf):
        fi = (i + 1).astype(jnp.float32)
        reps = [jnp.roll(buf, 4096 * (r + 1)) * (1.0 + 1e-6 * fi * (r + 1))
                for r in range(n_rep)]
        s = reps[0]
        for r in reps[1:]:
            s = s + r
        return s / n_rep  # keep magnitudes bounded

    fn = jax.jit(lambda b: lax.fori_loop(0, k_inner, round_, b))
    buf = jnp.ones((S,), jnp.float32) * 0.5
    float(fn(buf)[::8192].sum())  # compile + warm
    t0 = time.perf_counter()
    out = fn(buf)
    s = float(out[::8192].sum())
    dt = time.perf_counter() - t0
    per_round = dt / k_inner
    moved = total_bytes * (n_rep + 1)
    import json
    print(f'{k_inner} on-device rounds: {dt * 1e3:.1f} ms total, '
          f'{per_round * 1e3:.2f} ms/round (checksum {s:.3f})',
          file=sys.stderr)
    print(json.dumps({'metric': 'kvstore_reduce_device_bandwidth',
                      'value': round(moved / per_round / 1e9, 3),
                      'unit': 'GB/s',
                      'mean_ms': round(per_round * 1e3, 3),
                      'total_mb': round(total_bytes / 1e6, 1),
                      'replicas': n_rep}))
    return 0


def main(argv=None):
    args = parse_args(argv)
    shapes = grad_shapes(args)
    total_bytes = sum(4 * int(np.prod(s)) for s in shapes)
    import jax
    n_dev = jax.local_device_count()
    print(f'{len(shapes)} keys, {total_bytes / 1e6:.1f} MB total, '
          f'{n_dev} devices, kvstore={args.kv_store}', file=sys.stderr)

    # replica copies per key: the reduce across them is the real work the
    # kvstore does on a host (CommDevice::Reduce); with a single device
    # and one replica a pushpull is just a handle rebind, which would
    # measure nothing but Python dispatch
    n_rep = args.replicas or max(n_dev, 2)

    if args.device_only:
        return device_only_bench(args, total_bytes, n_rep)

    kv = kvstore.create(args.kv_store)
    rng = np.random.RandomState(0)
    grads = [[mx.np.array(rng.uniform(-1, 1, s).astype('float32'))
              for _ in range(n_rep)] for s in shapes]
    for i, g in enumerate(grads):
        kv.init(i, g[0])
    fused = hasattr(kv, 'fused_pushpull') and not args.per_key
    print(f'{n_rep} replicas/key, path={"fused" if fused else "per-key"}',
          file=sys.stderr)

    keys = list(range(len(grads)))
    prios = [-i for i in keys]

    import jax
    # all replica perturbations in ONE dispatch (per-op dispatch would
    # swamp the measurement), scaled back by the fan-in so chained
    # values stay finite
    n_total = n_rep * max(kv.num_workers, 1)
    perturb = jax.jit(lambda raws: [
        [r * ((1.0 + 1e-4 * (k + 1)) / n_total) for k in range(n_rep)]
        for r in raws])

    def run_iters(n, outs):
        """n chained pushpull rounds. Each round's gradients derive from
        the previous round's outputs, so the whole chain is one
        dependency graph and ONE readback at the end waits for all of
        it; no host sync is paid per round."""
        for _ in range(n):
            cur = [[_ND(g) for g in gs]
                   for gs in perturb([o._data for o in outs])]
            if fused:
                kv.fused_pushpull(keys, cur, outs=[[o] for o in outs],
                                  priorities=prios)
            else:
                for i, gs in enumerate(cur):
                    kv.pushpull(i, gs, out=outs[i], priority=-i)
        # dependent readback forces the chain to completion
        acc = sum(o._data.reshape(-1)[::8192].sum() for o in outs)
        return float(acc)

    outs = [mx.np.ones(s) * 1e-3 for s in shapes]
    run_iters(args.warmup, outs)                      # compile + warm
    t0 = time.perf_counter()
    run_iters(args.num_batches, outs)
    dt = time.perf_counter() - t0
    mean_t = dt / args.num_batches
    print(f'{args.num_batches} chained iters: {dt * 1e3:.1f} ms total, '
          f'{mean_t * 1e3:.2f} ms/iter', file=sys.stderr)
    # bytes actually moved per iteration: the replica reduce reads
    # n_rep x S and writes S; the cross-device allreduce costs the
    # standard 2(n-1)/n on top
    moved = total_bytes * (n_rep + 1)
    if n_dev > 1:
        moved += 2 * total_bytes * (n_dev - 1) / n_dev
    algbw = moved / mean_t
    import json
    print(json.dumps({'metric': 'kvstore_pushpull_bandwidth',
                      'value': round(algbw / 1e9, 3), 'unit': 'GB/s',
                      'mean_ms': round(mean_t * 1e3, 3),
                      'total_mb': round(total_bytes / 1e6, 1),
                      'replicas': n_rep,
                      'fused': fused}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
