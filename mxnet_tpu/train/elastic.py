"""Elastic, preemption-tolerant training supervision (``mx.train``).

Three legs, composing machinery the framework already has:

1. **Async crash-consistent checkpoints** — :class:`ElasticTrainer`
   snapshots device state to host ON-step (the cheap phase) and hands
   serialization to a background :class:`_CheckpointDaemon` thread
   running :class:`~mxnet_tpu.parallel.checkpoint.SharedCheckpointManager`
   saves OFF-step (CheckFreq, FAST '21: pipelined checkpointing at
   bounded stall). The manager's commit protocol (staging dir → atomic
   rename → fsynced manifest) makes a kill at any point leave
   ``latest_step()`` on the previous complete checkpoint. Knobs:
   ``MXNET_CKPT_ASYNC=1`` (default off — synchronous saves),
   ``MXNET_CKPT_EVERY_S`` (minimum seconds between accepted saves).

2. **Bit-exact resume** — the checkpoint carries, besides parameters:
   the full ``Trainer`` state (optimizer slots, update counters,
   lr-scheduler), every RNG stream (``mx.random.get_state()``) and the
   data-iterator position (``DataLoader.resumable()`` state). A run
   killed at step k and resumed trains on *exactly* the same batch /
   dropout / schedule sequence as one that never died.

3. **Worker-loss recovery** — :class:`ElasticGroup` drives the
   ``dist_async`` elastic membership protocol (``elastic_join`` /
   ``elastic_barrier`` / ``elastic_commit`` on server 0): surviving
   workers detect a silently dead peer within
   ``MXNET_KVSTORE_DEADLINE_S`` (heartbeat table + ejection inside the
   barrier wait), re-form at the last committed step, rescale gradient
   aggregation to the live count, and re-admit a restarted worker from
   the latest checkpoint. Below ``MXNET_ELASTIC_MIN_WORKERS`` live
   workers the group checkpoint-and-halts (:class:`ElasticHalted`).

Concurrency: the daemon's ``_cv`` is level ``train.ckpt`` in the
declared hierarchy (docs/threading.md) and is tracked under
``MXNET_RACE_CHECK=1``; the orbax serialize runs OUTSIDE it, so a slow
save never blocks the step loop handing off the next snapshot.
"""

import os
import pickle
import threading
import time

import numpy as _np

from .. import _rng
from .. import profiler as _profiler
from ..telemetry import metrics as _tmetrics
from ..telemetry import trace as _trace
from ..telemetry.metrics import Reservoir


class ElasticHalted(RuntimeError):
    """The live worker count fell below ``MXNET_ELASTIC_MIN_WORKERS``:
    the caller should checkpoint and exit cleanly (the run resumes when
    capacity returns)."""


def _env_flag(name, default='0'):
    return os.environ.get(name, default).strip().lower() in (
        '1', 'true', 'yes', 'on')


class _CheckpointDaemon(threading.Thread):
    """Background serializer: a single-slot mailbox of the newest
    pending snapshot (latest wins — an overwritten pending snapshot is
    counted ``coalesced``, matching CheckFreq's bounded-lag contract:
    at most one checkpoint behind, never a growing queue)."""

    def __init__(self, manager, stats, stats_lock, name='ckpt-daemon',
                 observe=None):
        super().__init__(daemon=True, name=name)
        self._manager = manager
        self._stats = stats
        self._stats_lock = stats_lock
        self._observe = observe     # serialize-time sink (histogram)
        self._cv = threading.Condition()
        self._pending = None        # (step, tree) | None
        self._busy = False
        self._stopping = False
        self._race = None
        from ..analysis import race as _race
        if _race.enabled():
            self._cv = _race.tracked_condition(self._cv, 'train.ckpt')
            self._race = _race.shared_state(
                'train._CheckpointDaemon._pending', guard=self._cv)

    def submit(self, step, tree):
        with self._cv:
            if self._race is not None:
                self._race.write()
            if self._pending is not None:
                with self._stats_lock:
                    self._stats['coalesced'] += 1
            self._pending = (step, tree)
            self._cv.notify_all()

    def flush(self, timeout=None):
        """Block until the mailbox is empty AND no save is in flight.
        Returns False on timeout."""
        with self._cv:
            return self._cv.wait_for(
                lambda: self._pending is None and not self._busy,
                timeout=timeout)

    def close(self, timeout=30.0):
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        self.join(timeout=timeout)

    def run(self):
        while True:
            with self._cv:
                while self._pending is None and not self._stopping:
                    # timeout slices, not an untimed wait: close() can
                    # race the notify, and the lint's blocking rule
                    # wants bounded waits under train.ckpt
                    self._cv.wait(timeout=0.5)
                if self._pending is None:
                    return            # stopping and drained
                if self._race is not None:
                    self._race.write()
                step, tree = self._pending
                self._pending = None
                self._busy = True
            t0 = time.perf_counter()
            err = None
            try:
                # OUTSIDE the cv: the whole point — serialization
                # overlaps the training step that is already running
                self._manager.save(step, tree)
            except BaseException as e:      # must keep draining
                err = e
            dt_ms = (time.perf_counter() - t0) * 1e3
            with self._stats_lock:
                if err is None:
                    self._stats['saves'] += 1
                    self._stats['async_saves'] += 1
                    self._stats['last_step'] = step
                else:
                    self._stats['errors'] += 1
                    self._stats['last_error'] = repr(err)
                self._stats['serialize_ms'].add(dt_ms)
            if self._observe is not None:
                self._observe(dt_ms)
            with self._cv:
                self._busy = False
                self._cv.notify_all()


class ElasticTrainer:
    """Checkpoint/resume supervisor for a single training process.

    Wraps a parameter dict + ``gluon.Trainer`` + a
    :class:`~mxnet_tpu.parallel.checkpoint.SharedCheckpointManager` and
    owns WHAT goes into a checkpoint (see module docstring leg 2) and
    WHEN it is written (sync, or async off the step loop).

    ``params`` is a ``{name: Parameter}`` dict (e.g.
    ``dict(net.collect_params())``); ``data_iter`` is optional and must
    expose ``state_dict()`` / ``load_state_dict()`` (the
    ``DataLoader.resumable()`` iterator does).
    """

    def __init__(self, params, trainer, manager, data_iter=None,
                 name='elastic0', async_save=None, every_s=None,
                 clock=time.monotonic):
        self._params = dict(params)
        self._trainer = trainer
        self._manager = manager
        self._data_iter = data_iter
        self._name = name
        self._clock = clock
        self._async = _env_flag('MXNET_CKPT_ASYNC') \
            if async_save is None else bool(async_save)
        if every_s is None:
            try:
                every_s = float(os.environ.get('MXNET_CKPT_EVERY_S', '0'))
            except ValueError:
                every_s = 0.0
        self._every_s = float(every_s)
        self._last_accept = None      # clock time of last accepted save
        self._stats_lock = threading.Lock()
        # bounded reservoirs, not unbounded lists: a long-running
        # trainer accumulated one float per save forever; the reservoir
        # keeps exact count/sum/min/max plus a uniform sample
        self._stats = {'saves': 0, 'async_saves': 0, 'coalesced': 0,
                       'throttled': 0, 'errors': 0, 'last_step': -1,
                       'last_error': None,
                       'blocked_ms': Reservoir(512),
                       'serialize_ms': Reservoir(512)}
        self._h_blocked = _tmetrics.histogram('mx_ckpt_blocked_ms',
                                              trainer=name)
        self._h_serialize = _tmetrics.histogram('mx_ckpt_serialize_ms',
                                                trainer=name)
        self._collector_key = _tmetrics.register_collector(
            f'elastic:{name}', self._collect)
        self._daemon = None
        if self._async:
            self._daemon = _CheckpointDaemon(
                manager, self._stats, self._stats_lock,
                name=f'ckpt-{name}', observe=self._h_serialize.observe)
            self._daemon.start()
        self._closed = False
        _profiler.attach_checkpoint(name, self.stats)

    def _collect(self):
        """Registry collector: checkpoint counters as Prometheus
        samples (the ``stats()`` dict stays the local view)."""
        with self._stats_lock:
            counters = {k: self._stats[k] for k in
                        ('saves', 'async_saves', 'coalesced',
                         'throttled', 'errors')}
        labels = {'trainer': self._name}
        for k, v in counters.items():
            yield ('counter', f'mx_ckpt_{k}_total', labels, v)

    # ---------------------------------------------------------- snapshot
    @staticmethod
    def _snap_param(p):
        """One parameter's snapshot leaf: host-local params copy to
        numpy (the original contract), but a param sharded over >1
        device stays a DEVICE array — gathering a pod-sharded FSDP
        param to host on-step would serialize the whole model through
        one host; orbax writes each shard from where it lives instead.
        It is a copy (shard by shard, the layout kept): the Trainer's
        fused update donates the parameter's own buffers to the next
        step, so the array the parameter holds now is deleted while
        the daemon still serializes. The optimizer slots reach the
        ``meta`` blob through ``Trainer.state_dict()``, which copies
        them to the host on the step."""
        nd = p.data()
        raw = getattr(nd, '_data', None)
        sh = getattr(raw, 'sharding', None)
        if sh is not None and len(getattr(sh, 'device_set', ())) > 1:
            import jax.numpy as jnp
            return jnp.copy(raw)
        return nd.asnumpy()

    def snapshot(self, step):
        """Build the checkpoint tree: device→host parameter copies
        (sharded params stay device-resident — see :meth:`_snap_param`)
        plus a pickled ``meta`` blob (trainer counters + optimizer
        slots, RNG streams, iterator position, the step). This is the
        ON-step cost of an async save."""
        tree = {'params': {n: self._snap_param(p)
                           for n, p in self._params.items()}}
        meta = {
            'step': int(step),
            'trainer': self._trainer.state_dict()
            if self._trainer is not None else None,
            'rng': _rng.get_state(),
            'data_iter': self._data_iter.state_dict()
            if self._data_iter is not None else None,
        }
        tree['meta'] = _np.frombuffer(pickle.dumps(meta), dtype=_np.uint8)
        return tree

    # -------------------------------------------------------------- save
    def save(self, step, block=False):
        """Checkpoint ``step``. Returns True if a save was accepted.

        Async mode: builds the host snapshot (bounded on-step cost,
        recorded as ``blocked_ms``) and mails it to the daemon; the
        serialize overlaps the next training steps. Sync mode: the full
        save runs inline. ``MXNET_CKPT_EVERY_S`` throttles accepted
        saves; ``block=True`` bypasses the throttle and, in async mode,
        waits for THIS snapshot to be durable before returning."""
        if self._every_s > 0 and not block \
                and self._last_accept is not None \
                and self._clock() - self._last_accept < self._every_s:
            with self._stats_lock:
                self._stats['throttled'] += 1
            return False
        # the step loop's checkpoint-blocked time as a span: inside a
        # caller's train-step trace it shows exactly where checkpoint
        # cost lands; standalone it roots a small ckpt trace
        with _trace.span('ckpt.save', trainer=self._name,
                         step=int(step), sync=self._daemon is None):
            return self._save(step, block)

    def _save(self, step, block):
        t0 = time.perf_counter()
        tree = self.snapshot(step)
        if self._daemon is not None:
            self._daemon.submit(int(step), tree)
            blocked_ms = (time.perf_counter() - t0) * 1e3
            if block:
                self._daemon.flush()
        else:
            err = None
            try:
                self._manager.save(int(step), tree)
            except BaseException as e:
                err = e
            blocked_ms = (time.perf_counter() - t0) * 1e3
            with self._stats_lock:
                if err is None:
                    self._stats['saves'] += 1
                    self._stats['last_step'] = int(step)
                else:
                    self._stats['errors'] += 1
                    self._stats['last_error'] = repr(err)
                self._stats['serialize_ms'].add(blocked_ms)
            self._h_serialize.observe(blocked_ms)
            if err is not None:
                raise err
        with self._stats_lock:
            self._stats['blocked_ms'].add(blocked_ms)
        self._h_blocked.observe(blocked_ms)
        self._last_accept = self._clock()
        return True

    def flush(self, timeout=None):
        """Drain any in-flight async save (no-op in sync mode).
        Returns False on timeout."""
        if self._daemon is not None:
            return self._daemon.flush(timeout=timeout)
        return True

    # ----------------------------------------------------------- restore
    def _restore_template(self, step):
        """Restore template carrying the LIVE params' sharded
        placements, shapes/dtypes from the checkpoint's METADATA — so a
        checkpoint written on one mesh restores (resharding on load)
        onto whatever mesh the live params are compiled under now: the
        re-shard-on-restore leg of pod re-formation. ``None`` when no
        live param is sharded (the original host-numpy restore path) or
        the metadata is unreadable."""
        shardings = {}
        for n, p in self._params.items():
            try:
                raw = p.data()._data
            except Exception:
                continue
            sh = getattr(raw, 'sharding', None)
            if sh is not None and len(getattr(sh, 'device_set', ())) > 1:
                shardings[n] = sh
        if not shardings:
            return None
        meta = getattr(self._manager, 'step_metadata', lambda s: None)(step)
        if not isinstance(meta, dict) or 'params' not in meta \
                or 'meta' not in meta:
            return None
        import jax
        tparams = {}
        for n, m in meta['params'].items():
            shape, dtype = tuple(m.shape), _np.dtype(m.dtype)
            if n in shardings:
                tparams[n] = jax.ShapeDtypeStruct(
                    shape, dtype, sharding=shardings[n])
            else:
                tparams[n] = _np.zeros(shape, dtype)
        mb = meta['meta']
        return {'params': tparams,
                'meta': _np.zeros(tuple(mb.shape), _np.dtype(mb.dtype))}

    def restore(self, step=None):
        """Restore the latest (or given) committed checkpoint into the
        live objects — parameters, trainer, RNG streams, iterator
        position. Returns the restored step, or -1 when no checkpoint
        exists (cold start: the caller trains from its own init)."""
        if step is None:
            step = self._manager.latest_step()
        if step is None:
            return -1
        tree = self._manager.restore(int(step),
                                     template=self._restore_template(
                                         int(step)))
        from ..ndarray.ndarray import array
        params = tree['params']
        for n, p in self._params.items():
            if n not in params:
                raise KeyError(
                    f'checkpoint step {step} has no parameter {n!r}')
            val = _np.asarray(params[n])
            p.set_data(array(val.astype(p.dtype, copy=False)))
        meta = pickle.loads(_np.asarray(tree['meta'],
                                        dtype=_np.uint8).tobytes())
        if self._trainer is not None and meta.get('trainer') is not None:
            self._trainer.load_state_dict(meta['trainer'])
        if meta.get('rng') is not None:
            _rng.set_state(meta['rng'])
        if self._data_iter is not None \
                and meta.get('data_iter') is not None:
            self._data_iter.load_state_dict(meta['data_iter'])
        with self._stats_lock:
            self._stats['last_step'] = int(meta['step'])
        return int(meta['step'])

    # ------------------------------------------------------------- stats
    def stats(self):
        """Snapshot for tests and the profiler's Checkpoint section."""
        with self._stats_lock:
            s = dict(self._stats)
            blocked = s.pop('blocked_ms')
            ser = s.pop('serialize_ms')
            # reservoir running aggregates are EXACT over the whole
            # run (only the sample set is bounded)
            s['blocked_ms_avg'] = blocked.mean
            s['blocked_ms_max'] = blocked.max if len(blocked) else 0.0
            s['serialize_ms_avg'] = ser.mean
            s['serialize_ms_max'] = ser.max if len(ser) else 0.0
        return s

    def close(self, timeout=30.0):
        if self._closed:
            return
        self._closed = True
        _tmetrics.unregister_collector(self._collector_key)
        _profiler.detach_checkpoint(self._name)
        if self._daemon is not None:
            self._daemon.close(timeout=timeout)
            self._daemon = None

    def __del__(self):                  # pragma: no cover - GC timing
        try:
            self.close(timeout=1.0)
        except Exception:
            pass


class ElasticGroup:
    """Membership/step-protocol driver over a ``dist_async`` store.

    One instance per worker. The per-step protocol the chaos tests (and
    a real elastic loop) follow::

        group = ElasticGroup(store)           # elastic_join
        step = max(group.resume_step, restored + 1)
        while training:
            pre = group.pre_step(step)        # fixes count for scaling
            ... pull weights, compute grad ...
            store.push(key, -lr * grad / pre['count'])
            post = group.post_step(step)
            if post['changed']:               # membership changed
                step = group.committed + 1    #   mid-step: roll back
                if group.is_leader(post):
                    ... put() checkpointed weights back ...
                continue
            if group.is_leader(post):
                ... save checkpoint, group.commit(step) ...
            step += 1

    A worker that dies silently is ejected inside the barrier wait
    within ``MXNET_KVSTORE_DEADLINE_S``; the release then reports
    ``changed=True`` and the shrunken ``count``. A restarted worker
    re-joins and is scheduled in from the first not-yet-released step
    (it sits out any step already in flight — its gradient would be
    scaled for a world it was not part of).
    """

    def __init__(self, store, min_workers=None):
        if min_workers is None:
            try:
                min_workers = int(os.environ.get(
                    'MXNET_ELASTIC_MIN_WORKERS', '1'))
            except ValueError:
                min_workers = 1
        self._min = max(1, int(min_workers))
        self._store = store
        self._rank = store.rank
        info = store.elastic_join()
        self._gen = info['gen']
        self._committed = int(info['committed'])
        self._resume = int(info['resume'])

    @property
    def rank(self):
        return self._rank

    @property
    def resume_step(self):
        """First step this member participates in (join reply)."""
        return self._resume

    @property
    def committed(self):
        """Last step known checkpoint-committed (join reply / barriers)."""
        return self._committed

    def is_leader(self, verdict):
        """Leader = lowest live rank of the given barrier verdict; the
        leader saves the group checkpoint and performs rollback puts."""
        return self._rank == min(verdict['live'])

    def _barrier(self, phase, step):
        v = self._store.elastic_barrier(phase, step)
        self._gen = v['gen']
        self._committed = int(v['committed'])
        if len(v['live']) < self._min:
            raise ElasticHalted(
                f'{len(v["live"])} live worker(s) < '
                f'MXNET_ELASTIC_MIN_WORKERS={self._min} at '
                f'({phase}, {step}): checkpoint and halt')
        return v

    def barrier(self, phase, step):
        """Named rendezvous of the live members outside the pre/post
        step protocol — mesh re-formation drains ('reform') and rejoins
        ('rejoin') on these. Same ejection/halt semantics as the step
        barriers."""
        return self._barrier(str(phase), int(step))

    def pre_step(self, step):
        """Entry barrier: fixes the gradient-scaling ``count``."""
        return self._barrier('pre', step)

    def post_step(self, step):
        """Exit barrier: ``changed=True`` means the membership moved
        mid-step — roll back to ``committed`` and redo."""
        return self._barrier('post', step)

    def commit(self, step):
        """Record the checkpoint for ``step`` as durable (leader calls
        after the save)."""
        self._committed = self._store.elastic_commit(step)
        return self._committed

    def leave(self):
        """Clean exit (planned scale-down): no ejection wait for peers."""
        self._store.elastic_leave()


class MeshElasticTrainer:
    """One emulated host of a pod-scale elastic FSDP run.

    Composes the pod layers end to end: a ``dist_async`` store (this
    host's kvstore rank + mesh membership), a
    :class:`~mxnet_tpu.sharding.MeshGroup` (which host owns which
    devices), an :class:`ElasticGroup` (the per-step membership
    protocol) and an :class:`ElasticTrainer` (crash-consistent sharded
    checkpoints). Under single-process GSPMD emulation the LEADER
    (lowest live rank) executes the global sharded program over the
    union of the live hosts' devices; followers run only the protocol
    — heartbeats, barriers — and take over (rebuild + restore from the
    committed checkpoint) when leadership migrates onto them.

    ``build(ctx)`` is the model factory, called under the formation's
    sharding context whenever this host (re)becomes leader; it returns
    ``{'params': {name: Parameter}, 'trainer': gluon.Trainer | None,
    'step': fn(step)}`` with parameters already placed on ``ctx``'s
    mesh (run a warm-up forward inside). After a host death the mesh
    re-forms through the span tree ``mesh.reform`` → detect / drain /
    restore / rejoin: the leader ejects the dead ranks via
    ``mesh_epoch`` (bumping the generation, so stale-generation pushes
    of the dead host reject typed), every survivor drains its async
    checkpoint daemon, rebuilds on the shrunk mesh, the leader restores
    the last committed step (resharding onto the smaller mesh), and
    training resumes at ``committed + 1`` — bit-exact w.r.t. a run that
    never faulted at the reduced world size, because the restored state
    and programs are identical. A second death during re-formation just
    re-enters the loop (membership strictly shrinks, each barrier is
    deadline-bounded — convergence or :class:`ElasticHalted`, never a
    hang).
    """

    def __init__(self, store, group, build, ckpt_dir, tp=None,
                 min_workers=None, name='mesh'):
        self._store = store
        self._rank = store.rank
        self._build = build
        self._dir = ckpt_dir
        self._tp = tp
        self._name = name
        self._formed = group
        self._ctx = None
        self._state = None       # leader-only: build(ctx) result
        self._et = None          # leader-only: ElasticTrainer
        from ..parallel.checkpoint import SharedCheckpointManager
        self._manager = SharedCheckpointManager(ckpt_dir)
        self._h_reform = _tmetrics.histogram('mx_mesh_reform_duration_ms',
                                             host=str(self._rank))
        self._reform_s = float(os.environ.get('MXNET_MESH_REFORM_S',
                                              '300'))
        store.mesh_join(meta={
            'devices': len(group.devices_for(self._rank))})
        self._elastic = ElasticGroup(store, min_workers=min_workers)

    # ------------------------------------------------------------- state
    @property
    def group(self):
        """The current formation (live hosts + generation mirror)."""
        return self._formed

    @property
    def committed(self):
        return self._elastic.committed

    def _form(self, live):
        """Formation for ``live`` ranks, generation mirrored from the
        kvstore's authoritative membership table."""
        from ..sharding.context import MeshGroup
        gen = self._store.mesh_table()['gen']
        return MeshGroup(self._formed.n_procs, self._formed._devices,
                         generation=gen, live=live)

    def _context(self):
        if self._ctx is None:
            self._ctx = self._formed.context(tp=self._tp)
        return self._ctx

    def _restore_state(self):
        """(Re)build the model under the current formation's context
        and restore the last committed checkpoint onto it — the
        re-shard-on-restore path when the mesh shrank. Leader-only."""
        from ..sharding.context import use as _use
        if self._et is not None:
            self._et.close()
            self._et = None
        ctx = self._context()
        with _use(ctx):
            st = self._build(ctx)
        self._state = st
        # per-formation name: collectors/histograms key on it, and two
        # formations of one run must not collide in the registry
        self._et = ElasticTrainer(
            st['params'], st.get('trainer'), self._manager,
            name=f'{self._name}-r{self._rank}-g{self._formed.generation}')
        return self._et.restore()

    # ------------------------------------------------------------ reform
    def _reform(self, verdict, step):
        """Leader-driven mesh re-formation after a membership change.
        Loops until a formation survives both its barriers unchanged
        (a second death during re-formation re-enters with the smaller
        verdict). Returns the step training resumes at."""
        t0 = time.perf_counter()
        with _trace.span('mesh.reform', rank=self._rank, step=int(step)):
            while True:
                # convergence budget: cascading deaths strictly shrink
                # membership, but a flapping store could loop forever —
                # bound one re-formation to MXNET_MESH_REFORM_S wall
                # seconds, then halt typed rather than livelock
                if time.perf_counter() - t0 > self._reform_s:
                    raise ElasticHalted(
                        'mesh re-formation did not converge within '
                        f'MXNET_MESH_REFORM_S={self._reform_s:g}s')
                live = sorted(verdict['live'])
                with _trace.child_span('mesh.reform.detect',
                                       live=list(live)):
                    dead = [r for r in self._formed.live
                            if r not in live]
                    if self._elastic.is_leader(verdict):
                        # bump the generation fence: every in-flight
                        # push of an ejected host now rejects typed
                        self._store.mesh_epoch(eject=dead)
                with _trace.child_span('mesh.reform.drain'):
                    if self._et is not None:
                        self._et.flush()
                    v = self._elastic.barrier('reform', step)
                    if sorted(v['live']) != live:
                        verdict = v      # double death mid-reformation
                        continue
                with _trace.child_span('mesh.reform.restore'):
                    # followers learn the new generation off the
                    # heartbeat piggyback; the leader already adopted
                    # it in mesh_epoch
                    self._store.set_mesh_gen(
                        self._store.mesh_table()['gen'])
                    self._formed = self._form(live)
                    self._ctx = None
                    self._state = None
                    if self._elastic.is_leader(v):
                        self._restore_state()
                v2 = self._elastic.barrier('rejoin', step)
                if sorted(v2['live']) != live:
                    verdict = v2
                    continue
                break
        self._h_reform.observe((time.perf_counter() - t0) * 1e3)
        return self._elastic.committed + 1

    # --------------------------------------------------------------- run
    def run(self, num_steps):
        """Drive steps ``resume .. num_steps-1`` through the elastic
        protocol, re-forming the mesh on every membership change.
        Raises :class:`ElasticHalted` when the live host count falls
        below ``MXNET_ELASTIC_MIN_WORKERS``. Returns the first
        not-yet-run step (``num_steps`` on normal completion)."""
        from ..sharding.context import use as _use
        # staggered mesh_joins left peers on different cached
        # generations — adopt the authoritative one before stepping
        self._store.set_mesh_gen(self._store.mesh_table()['gen'])
        step = max(self._elastic.resume_step,
                   self._elastic.committed + 1)
        num_steps = int(num_steps)
        while step < num_steps:
            pre = self._elastic.pre_step(step)
            if sorted(pre['live']) != list(self._formed.live):
                step = self._reform(pre, step)
                continue
            if self._elastic.is_leader(pre):
                if self._state is None:
                    self._restore_state()
                with _use(self._context()):
                    self._state['step'](step)
            post = self._elastic.post_step(step)
            if post['changed'] \
                    or sorted(post['live']) != list(self._formed.live):
                step = self._reform(post, step)
                continue
            if self._elastic.is_leader(post):
                self._et.save(step, block=True)
                self._elastic.commit(step)
            step += 1
        return step

    def close(self):
        if self._et is not None:
            self._et.close()
            self._et = None
