"""Does the system still start on the chip?  BERT-base takes Trainer steps.

    python chip_smoke.py             # one chip: eager ops, then the train loop
    python chip_smoke.py --chips 4   # four chips: the same step under
                                     # mx.sharding.mesh(dp=4) against one chip

The loop is the one of ``examples/bert_finetune.py`` at the published
width: ``bert_12_768_12`` (12 layers, 768 wide, 12 heads, FFN 3072, vocab
30522), batch 32 x sequence 128, float32 parameters, no attention mask,
through ``initialize(ctx=mx.tpu())`` -> ``hybridize(static_alloc=True)`` ->
``autograd.record()`` -> ``backward()`` -> ``Trainer('adam').step()``.
Weights and data are random, made from ``--seed``.

One process, which holds the chip. Without a TPU it refuses: there is no
CPU fallback. Every line of stdout is one JSON object; the last is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Any failed
check or exception ends the run with a non-zero exit code.
"""

import argparse
import dataclasses
import glob
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
IR_DIR = os.path.join(HERE, '.chip_smoke_ir')      # git-ignored
COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'
CACHE_HIT_EVENT = '/jax/compilation_cache/cache_hits'
KERNELS = ('mx_flash_attention', 'mx_flash_attention_bwd',
           'mx_fused_layer_norm', 'mx_adam_step')


@dataclasses.dataclass
class Config:
    """bert_12_768_12 as published; a rehearsal shrinks it, the chip run
    does not."""
    layers: int = 12
    units: int = 768
    hidden: int = 3072
    heads: int = 12
    vocab: int = 30522
    batch: int = 32
    seq: int = 128
    steps: int = 4          # timed steps, after one warm-up step
    lr: float = 2e-5        # the published fine-tuning rate
    seed: int = 0


def emit(**obj):
    print(json.dumps(obj), flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


class CompileLog:
    """Counts and times XLA compiles through jax.monitoring: every jit of
    the process, the repo's own and JAX's helpers alike."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == CACHE_HIT_EVENT:
            self.cache_hits += 1


def build(cfg, ctx):
    """The classifier of examples/bert_finetune.py and one fixed batch."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.bert import get_bert_model

    mx.random.seed(cfg.seed)
    np.random.seed(cfg.seed)
    bert = get_bert_model(
        'bert_12_768_12', vocab_size=cfg.vocab, num_layers=cfg.layers,
        units=cfg.units, hidden_size=cfg.hidden, num_heads=cfg.heads,
        dropout=0.1, use_decoder=False, use_classifier=False)

    class Classifier(gluon.nn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.bert = bert
            self.head = gluon.nn.Dense(2)

        def forward(self, tokens, segments):
            _, pooled = self.bert(tokens, segments)
            return self.head(pooled)

    net = Classifier()
    net.initialize(mx.initializer.Normal(0.02), ctx=ctx)

    rng = np.random.default_rng(cfg.seed)
    toks = rng.integers(8, cfg.vocab, (cfg.batch, cfg.seq)).astype('int32')
    labels = (rng.uniform(size=cfg.batch) > 0.5).astype('float32')
    toks[labels == 1, rng.integers(1, cfg.seq, cfg.batch)[labels == 1]] = 7
    x = mx.np.array(toks, ctx=ctx)
    s = mx.np.array(np.zeros_like(toks), ctx=ctx)
    y = mx.np.array(labels, ctx=ctx)

    net(x[:1], s[:1])                 # deferred shapes resolve eagerly
    net.hybridize(static_alloc=True)
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': cfg.lr})
    return net, trainer, gluon.loss.SoftmaxCrossEntropyLoss(), (x, s, y)


def one_step(net, trainer, loss_fn, batch):
    """One training step; returns the loss (a device array, ready), the
    logits, and the host seconds until loss and updated parameters were
    ready."""
    import jax
    from mxnet_tpu import autograd
    x, s, y = batch
    t0 = time.perf_counter()
    with autograd.record():
        out = net(x, s)
        loss = loss_fn(out, y).mean()
    loss.backward()
    trainer.step(1)                   # the loss is already a mean
    raw = jax.block_until_ready(loss._data)
    jax.block_until_ready(net.head.weight.data()._data)
    return raw, out._data, time.perf_counter() - t0


def kernel_sites(text):
    """Per kernel name, how many tpu_custom_call sites a lowered program
    (its MLIR text) runs from @main: a site in a private function counts
    once for every call of that function (the flash kernels' calls are
    jitted on their own, so a model's layers share one function)."""
    own, calls, name = {}, {}, None
    for line in text.splitlines():
        start = re.match(r'\s*func\.func \w+ @"?([\w.$-]+)', line)
        if start:
            name = start.group(1)
            own[name], calls[name] = dict.fromkeys(KERNELS, 0), []
        elif name is not None:
            calls[name] += re.findall(r'(?<![\w.])call @"?([\w.$-]+)', line)
            if '@tpu_custom_call' in line:
                for k in KERNELS:
                    if re.search(rf'\b{k}\b', line):
                        own[name][k] += 1

    def total(fn, k):
        return own[fn][k] + sum(total(c, k) for c in calls[fn] if c in own)
    return {k: total('main', k) for k in KERNELS} if 'main' in own else {}


def dumped_kernels():
    """Per kernel name, how many tpu_custom_call sites each program that
    JAX lowered in this process holds (read from the IR dump)."""
    found = {k: {} for k in KERNELS}
    for path in sorted(glob.glob(os.path.join(IR_DIR, '*.mlir'))):
        with open(path) as f:
            text = f.read()
        if '@tpu_custom_call' not in text:
            continue
        prog = re.sub(r'^jax_ir\d+_|_compile\.mlir$', '',
                      os.path.basename(path))
        for k, n in kernel_sites(text).items():
            if n:
                found[k][prog] = found[k].get(prog, 0) + n
    return found


def eager_phase(ctx):
    """A handful of imperative mx.np ops on the chip: on an accelerator
    backend they go through the bulking engine, which no CPU test runs."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import _bulk
    check(_bulk.active(), 'eager bulking is on by default on the chip')
    a_np = np.arange(12, dtype='float32').reshape(3, 4)
    a = mx.np.array(a_np, ctx=ctx)
    b = mx.np.ones((4, 2), ctx=ctx)
    c = mx.np.matmul(a + 1.0, b)
    got = c.asnumpy()
    want = (a_np + 1.0) @ np.ones((4, 2), 'float32')
    check(np.array_equal(got, want), f'eager matmul: {got} != {want}')
    dev = next(iter(c._data.devices()))
    check(dev.platform == 'tpu', f'eager result lives on {dev}')
    emit(phase='eager', ops=['array', 'ones', 'add', 'matmul', 'asnumpy'],
         bulked=True, device=str(dev))


def train_phase(cfg, ctx, compiles):
    """Warm-up + timed steps. On a ``ctx`` that is no TPU (a rehearsal,
    .claude/skills/verify/SKILL.md) the checks that read the device are
    left out; main() gives it none."""
    import numpy as np
    import jax
    from mxnet_tpu.ops.pallas import fused_optimizer

    t_build = time.perf_counter()
    net, trainer, loss_fn, batch = build(cfg, ctx)
    params = net.collect_params()
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    build_s = time.perf_counter() - t_build

    c0, s0 = compiles.count, compiles.seconds
    loss0, _, warm_s = one_step(net, trainer, loss_fn, batch)
    warm_compiles = compiles.count - c0
    compile_s = compiles.seconds - s0
    net_compiles = net.compile_count
    emit(phase='train', event='warmup', seconds=warm_s,
         compile_seconds=compile_s, compiles=warm_compiles,
         cache_hits=compiles.cache_hits, build_seconds=build_s,
         params=n_params, layers=cfg.layers, batch=cfg.batch, seq=cfg.seq)

    c1 = compiles.count
    losses, times = [float(loss0)], []
    for _ in range(cfg.steps):
        raw, _, dt = one_step(net, trainer, loss_fn, batch)
        losses.append(float(raw))
        times.append(dt)
    steady_compiles = compiles.count - c1

    check(all(np.isfinite(losses)), f'losses not finite: {losses}')
    check(losses[-1] < losses[0], f'loss did not fall: {losses}')
    check(steady_compiles == 0 and net.compile_count == net_compiles,
          f'{steady_compiles} XLA compile(s) after warm-up '
          f'(block compile_count {net_compiles} -> {net.compile_count})')
    check(not trainer._fused_fallback_taken,
          'Trainer fell back to per-parameter updates')

    if ctx.device_type == 'tpu':
        for name, p in params.items():
            for d in p.data()._data.devices():
                check(d.platform == 'tpu', f'{name} lives on {d}')
        for d in raw.devices():
            check(d.platform == 'tpu', f'loss lives on {d}')
        kernels = dumped_kernels()
        # by the update's own gate, asked here as the Trainer's trace
        # asks it: on the chip, outside a mesh
        n_adam = sum(fused_optimizer.use_pallas(p.data()._data)
                     for p in params.values())
        want = {'mx_flash_attention': cfg.layers,
                'mx_flash_attention_bwd': cfg.layers,
                'mx_fused_layer_norm': 2 * cfg.layers + 1,
                'mx_adam_step': n_adam}
        for k, n in want.items():
            most = max(kernels[k].values(), default=0)
            check(most >= n, f'{k}: want {n} tpu_custom_call sites in one '
                  f'program of the step, found {kernels[k]}')
        check(kernels['mx_adam_step'].get('jit_fused', 0) == n_adam,
              f'adam kernel sites in the fused update: '
              f'{kernels["mx_adam_step"]}, want {n_adam}')
        stats = jax.local_devices()[0].memory_stats() or {}
        emit(phase='train', event='kernels', tpu_custom_call=kernels,
             adam_params_on_kernel=n_adam, adam_params=len(params))
    else:
        stats = {}

    emit(phase='train', event='steps', losses=losses, step_seconds=times,
         compiles_after_warmup=steady_compiles,
         peak_bytes_in_use=stats.get('peak_bytes_in_use'),
         bytes_limit=stats.get('bytes_limit'))
    return losses


def mesh_phase(cfg, ctx, compiles):
    """Four chips: the step under mx.sharding.mesh(dp=4), and the same
    step (same seed, same batch) on one chip as what it is compared with.
    """
    import contextlib
    import numpy as np
    import jax
    import mxnet_tpu as mx

    check(jax.device_count() == 4, f'{jax.device_count()} device(s)')

    def run(scope):
        shutil.rmtree(IR_DIR, ignore_errors=True)   # this run's programs
        net, trainer, loss_fn, batch = build(cfg, ctx)
        losses, times = [], []
        s0 = compiles.seconds
        with scope:
            for _ in range(1 + cfg.steps):
                raw, out, dt = one_step(net, trainer, loss_fn, batch)
                losses.append(float(raw))
                times.append(dt)
        return dict(trainer=trainer, out=out, losses=losses, times=times,
                    kernels=dumped_kernels(),
                    compile_seconds=compiles.seconds - s0)

    one = run(contextlib.nullcontext())
    emit(phase='mesh', event='one_chip', losses=one['losses'],
         step_seconds=one['times'][1:], warmup_seconds=one['times'][0],
         compile_seconds=one['compile_seconds'],
         tpu_custom_call=one['kernels'])
    del one['trainer'], one['out']

    four = run(mx.sharding.mesh(dp=4))
    trainer = four['trainer']

    def spread(raw):
        return len({d.id for d in raw.devices()}), \
            not raw.sharding.is_fully_replicated

    # FSDP: matrix parameters are split over the four chips; ZeRO-1: so
    # are the optimizer slots, those of replicated vectors too
    params_split, slots, slots_split = 0, 0, 0
    for i, p in enumerate(trainer._params):
        n_dev, split = spread(p.data()._data)
        check(n_dev == 4, f'{p.name} is on {n_dev} device(s)')
        params_split += split
        st = trainer._states.get(i)
        for leaf in (st if isinstance(st, (list, tuple)) else [st]):
            raw = getattr(leaf, '_data', None)
            if raw is None or raw.shape != p.shape:
                continue
            n_dev, split = spread(raw)
            check(n_dev == 4, f'slot of {p.name} is on {n_dev} device(s)')
            slots += 1
            slots_split += split
    check(params_split > 0, 'no parameter is split over the mesh (FSDP)')
    check(slots_split > params_split,
          f'ZeRO-1: {slots_split} of {slots} slots split, '
          f'{params_split} params split')
    # the batch: the step's logits come back 8 rows a chip
    out = four['out']
    n_dev, split = spread(out)
    rows = sorted(sh.data.shape[0] for sh in out.addressable_shards)
    check(n_dev == 4 and split and rows == [cfg.batch // 4] * 4,
          f'logits {out.shape}: {n_dev} device(s), rows per shard {rows}')

    tol = 1e-3
    l1, l4 = one['losses'], four['losses']
    check(all(np.isfinite(l4)), f'mesh losses not finite: {l4}')
    check(abs(l4[0] - l1[0]) <= tol * max(1.0, abs(l1[0])),
          f'first-step loss: mesh {l4[0]} vs one chip {l1[0]}, tol {tol}')
    check(l4[-1] < l4[0], f'mesh loss did not fall: {l4}')
    check(not trainer._fused_fallback_taken,
          'Trainer fell back to per-parameter updates')
    # GSPMD cannot partition a pallas_call: under the mesh every kernel
    # gate takes its XLA branch (ops/pallas/flash_attention._under_mesh),
    # on one chip none does
    if ctx.device_type == 'tpu':
        check(all(one['kernels'].values()),
              f'one chip, kernels missing: {one["kernels"]}')
    check(not any(four['kernels'].values()),
          f'pallas_call under the mesh: {four["kernels"]}')
    emit(phase='mesh', event='four_chips', losses=l4,
         tpu_custom_call=four['kernels'],
         step_seconds=four['times'][1:], warmup_seconds=four['times'][0],
         compile_seconds=four['compile_seconds'],
         first_loss_abs_diff=abs(l4[0] - l1[0]), tolerance=tol,
         params=len(trainer._params), params_split=params_split,
         slots=slots, slots_split=slots_split, rows_per_chip=rows,
         peak_bytes_in_use=[
             (d.memory_stats() or {}).get('peak_bytes_in_use')
             for d in jax.local_devices()])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--chips', type=int, default=1, choices=(1, 4))
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {'platform': devs[0].platform, 'kind': devs[0].device_kind,
              'count': len(devs)}
    if device['platform'] != 'tpu' or device['count'] != args.chips:
        emit(ok=False, device=device,
             error=f'need {args.chips} TPU chip(s); there is no CPU '
                   'fallback')
        return 1

    shutil.rmtree(IR_DIR, ignore_errors=True)
    jax.config.update('jax_dump_ir_to', IR_DIR)
    compiles = CompileLog()

    import mxnet_tpu as mx
    from mxnet_tpu import _compile_cache
    import jaxlib
    cache_dir = _compile_cache.place()
    emit(phase='start', jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=_libtpu_version(), device=device, cache_dir=cache_dir,
         cache_dir_from_env=bool(
             os.environ.get('JAX_COMPILATION_CACHE_DIR')))

    cfg = Config(seed=args.seed)
    t0 = time.perf_counter()
    ctx = mx.tpu(0)
    if args.chips == 4:
        mesh_phase(cfg, ctx, compiles)
    else:
        eager_phase(ctx)
        train_phase(cfg, ctx, compiles)
    emit(phase='done', seconds=time.perf_counter() - t0,
         compiles=compiles.count, compile_seconds=compiles.seconds,
         cache_hits=compiles.cache_hits)
    emit(ok=True, device=device)
    return 0


def _libtpu_version():
    from importlib import metadata
    try:
        return metadata.version('libtpu')
    except metadata.PackageNotFoundError:       # a label, not a check
        return None


if __name__ == '__main__':
    sys.exit(main())
