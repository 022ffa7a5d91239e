"""Mesh-scoped sharding context: ``with mx.sharding.mesh(dp=4, tp=2):``.

Inside the context every ``HybridBlock.hybridize()`` compile routes
through ``jax.jit`` with ``in_shardings`` derived from the partition-rule
registry (rules.py), parameters are placed sharded on the mesh, the
Trainer partitions optimizer slots along the data axis (ZeRO-1), and
``DecodeServer`` shards its KV page pool — all with zero model-code
changes (gluon/block.py reads the ambient context at compile time).

The context is thread-local and reentrant (a stack); its
``fingerprint()`` is part of the ``_CachedGraph`` compile-cache key, so
entering a *different* mesh retraces by design (a new device assignment
is a new XLA program — the recompile-hazard rule documents this as a
non-hazard), while re-entering the *same* mesh shape hits the warm
cache.

Env overrides (docs/env_vars.md):

* ``MXNET_SHARDING_DP`` / ``MXNET_SHARDING_TP`` — override the axis
  sizes passed to :func:`mesh` (deploy-time reshape without code edits);
* ``MXNET_SHARDING_DISABLE=1`` — make :func:`mesh` a no-op (escape
  hatch: single-device semantics for bisection);
* ``MXNET_SHARDING_STRICT=1`` — error instead of replicating when a
  rule's mesh axis does not divide the dim (rules.resolve_spec).
"""

import os
import threading
from contextlib import contextmanager

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import rules as _rules
from .. import _bulk

__all__ = ['ShardingContext', 'MeshGroup', 'mesh', 'current',
           'constrain', 'batch_spec', 'use']

_STACK = threading.local()


def _stack():
    if not hasattr(_STACK, 'items'):
        _STACK.items = []
    return _STACK.items


def current():
    """The innermost active :class:`ShardingContext`, or None."""
    items = _stack()
    return items[-1] if items else None


class ShardingContext:
    """One mesh + rule table + the derived placement helpers."""

    def __init__(self, mesh, rules=None, mode=None, arch=None,
                 data_axis='dp'):
        self.mesh = mesh
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.axis_sizes = sizes
        if mode is None:
            mode = 'tp' if sizes.get('tp', 1) > 1 else 'fsdp'
        self.mode = mode
        self.arch = arch          # None -> inferred per block
        self._rules = rules       # explicit table beats the registry
        self.data_axis = data_axis if sizes.get(data_axis, 1) > 1 else None
        self.n_devices = int(mesh.devices.size)

    # ------------------------------------------------------------- identity
    def fingerprint(self):
        """Hashable identity for compile-cache keys: mesh shape + axis
        names + device ids + mode (+ rule-table identity). Two contexts
        over the same devices/axes/rules share compiled executables."""
        dev_ids = tuple(int(d.id) for d in self.mesh.devices.flat)
        return (tuple(self.mesh.axis_names),
                tuple(self.mesh.devices.shape), dev_ids, self.mode,
                self.arch, id(self._rules) if self._rules else None)

    # ------------------------------------------------------------ rule match
    def rules_for_block(self, block=None, arch=None):
        if self._rules is not None:
            return self._rules
        arch = arch or self.arch
        if arch is None and block is not None:
            arch = _rules.infer_arch(block)
        arch = arch or 'generic'
        try:
            return _rules.rules_for(arch, self.mode)
        except KeyError:
            if arch != 'generic' and self.mode == 'fsdp':
                return _rules.rules_for('generic', 'fsdp')
            raise

    def spec_for(self, name, shape, rules):
        """Resolved PartitionSpec for one named parameter (rule match +
        divisibility fallback against this mesh)."""
        spec = _rules.match_spec(name, shape, rules)
        return _rules.resolve_spec(spec, shape, self.mesh, name=name)

    def sharding_for(self, name, shape, rules):
        return NamedSharding(self.mesh, self.spec_for(name, shape, rules))

    # ------------------------------------------------------------ placement
    def batch_spec(self, shape):
        """Activation spec: leading (batch) dim on the data axis when it
        divides, otherwise replicated — the rule-tagged graph boundary
        the hybridize cache constrains activations at."""
        if self.data_axis is None or not shape:
            return P()
        extent = self.axis_sizes.get(self.data_axis, 1)
        if shape[0] % extent:
            return P()
        return P(self.data_axis)

    def put(self, raw, spec):
        return jax.device_put(raw, NamedSharding(self.mesh, spec))

    def lift(self, raws):
        """Reconcile the device sets of one program's operands: where
        any of ``raws`` lies on more than one device, every
        single-device array among them is placed on the mesh at its
        batch spec; where none does, the same list comes back."""
        for r in raws:
            sh = getattr(r, 'sharding', None)
            if sh is not None and len(sh.device_set) > 1:
                break
        else:
            return raws
        out = []
        for r in raws:
            sh = getattr(r, 'sharding', None)
            if sh is not None and len(sh.device_set) == 1 \
                    and getattr(r, 'ndim', None) is not None:
                r = self.put(r, self.batch_spec(r.shape))
            out.append(r)
        return out

    def zero1_spec(self, param_spec, shape):
        """Optimizer-slot spec: the parameter's layout plus the data
        axis on the first still-replicated divisible dim — optimizer
        state partitioned along 'dp' (ZeRO-1; the GSPMD expression of
        the kvstore/tpu.py ``_zero1_update`` owner plan, where each
        data-parallel rank updates only its slice)."""
        if self.data_axis is None:
            return param_spec
        extent = self.axis_sizes.get(self.data_axis, 1)
        entries = list(tuple(param_spec)) + [None] * (len(shape)
                                                      - len(param_spec))
        used = set()
        for e in entries:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a is not None:
                    used.add(a)
        if self.data_axis in used:
            return param_spec
        sizes = self.axis_sizes
        for d, e in enumerate(entries):
            have = 1
            for a in ((e if isinstance(e, tuple) else (e,)) or ()):
                if a is not None:
                    have *= sizes.get(a, 1)
            if shape[d] % (have * extent) == 0 and shape[d] >= extent:
                if e is None:
                    entries[d] = self.data_axis
                elif isinstance(e, tuple):
                    entries[d] = e + (self.data_axis,)
                else:
                    entries[d] = (e, self.data_axis)
                while entries and entries[-1] is None:
                    entries.pop()
                return P(*entries)
        return param_spec

    def __repr__(self):
        ax = ', '.join(f'{k}={v}' for k, v in self.axis_sizes.items())
        return f'<ShardingContext {ax} mode={self.mode}>'


class MeshGroup:
    """Mesh topology separated from process topology (the pod layer).

    A :class:`ShardingContext` describes a *device* mesh; a
    :class:`MeshGroup` describes which *host* (process) owns which
    slice of it — the ``jax.distributed`` view, emulated over
    ``n_procs`` local "hosts" on the CPU backend
    (``--xla_force_host_platform_device_count``) so pod-scale
    membership logic is tier-1 testable. Each host owns a contiguous
    block of ``len(devices) / n_procs`` devices; the group tracks the
    LIVE host set plus a re-formation ``generation``.

    The group is immutable: :meth:`eject` returns a NEW group with the
    dead hosts removed and the generation bumped — the shape handed to
    :meth:`context`, which builds a :class:`ShardingContext` over only
    the live hosts' devices (the re-formed, smaller mesh). The
    authoritative generation for stale-push rejection lives on the
    kvstore (``mesh_epoch`` verb); this one mirrors it for display and
    registration records.

    ``n_procs`` defaults to ``MXNET_MESH_PROCS`` (docs/env_vars.md).
    """

    def __init__(self, n_procs=None, devices=None, generation=0,
                 live=None):
        if n_procs is None:
            try:
                n_procs = int(os.environ.get('MXNET_MESH_PROCS', '1'))
            except ValueError:
                n_procs = 1
        n_procs = int(n_procs)
        devices = list(devices) if devices is not None \
            else list(jax.devices())
        if n_procs < 1:
            raise ValueError(f'n_procs must be >= 1, got {n_procs}')
        if len(devices) % n_procs:
            raise ValueError(
                f'{len(devices)} devices do not split evenly over '
                f'{n_procs} emulated hosts')
        self.n_procs = n_procs
        self._devices = devices
        per = len(devices) // n_procs
        self.devices_per_proc = per
        self._owned = {r: tuple(devices[r * per:(r + 1) * per])
                       for r in range(n_procs)}
        self.generation = int(generation)
        live = sorted(set(range(n_procs)) if live is None else
                      {int(r) for r in live})
        for r in live:
            if not 0 <= r < n_procs:
                raise ValueError(f'live rank {r} outside 0..{n_procs - 1}')
        if not live:
            raise ValueError('a MeshGroup needs at least one live host')
        self._live = tuple(live)

    # ---------------------------------------------------------- topology
    @property
    def live(self):
        """Live host ranks, ascending."""
        return self._live

    @property
    def leader(self):
        """Lowest live rank — the host that executes the global program
        and drives re-formation (leadership migrates on its death)."""
        return self._live[0]

    def devices_for(self, rank):
        """The contiguous device block host ``rank`` owns (dead or
        alive — ownership is topology, liveness is membership)."""
        return self._owned[int(rank)]

    def live_devices(self):
        """Union of the live hosts' devices, rank order — the device
        set the re-formed mesh is built over."""
        return [d for r in self._live for d in self._owned[r]]

    # -------------------------------------------------------- membership
    def eject(self, *ranks):
        """New group without ``ranks``, generation bumped — host loss
        (or planned scale-down) as a value, never in-place mutation."""
        gone = {int(r) for r in ranks}
        live = [r for r in self._live if r not in gone]
        if not live:
            raise ValueError(
                f'ejecting {sorted(gone)} would leave no live host')
        return MeshGroup(self.n_procs, self._devices,
                         generation=self.generation + 1, live=live)

    # ----------------------------------------------------------- context
    def context(self, tp=None, rules=None, mode=None, arch=None):
        """A :class:`ShardingContext` over the LIVE hosts' devices:
        ``dp`` = live devices / ``tp`` (default tp=1 — pure FSDP).
        Enter it with :func:`use`; deliberately not a contextmanager so
        drivers and servers can hold and re-enter one formation."""
        devs = self.live_devices()
        tp = int(tp) if tp else 1
        if tp > 1 and len(devs) % tp:
            raise ValueError(
                f'tp={tp} does not divide {len(devs)} live devices')
        dp = len(devs) // tp
        sizes = {}
        if dp > 1:
            sizes['dp'] = dp
        if tp > 1:
            sizes['tp'] = tp
        if not sizes:
            sizes = {'dp': len(devs)}
        from ..parallel.mesh import make_mesh
        return ShardingContext(make_mesh(devices=devs, **sizes),
                               rules=rules, mode=mode, arch=arch)

    def describe(self):
        """Registration-record form (serving: the router stores this
        per replica; training: the mesh_join meta)."""
        return {'n_procs': self.n_procs,
                'devices_per_proc': self.devices_per_proc,
                'n_devices': len(self._devices),
                'live': list(self._live),
                'generation': self.generation}

    def __repr__(self):
        return (f'<MeshGroup {len(self._live)}/{self.n_procs} hosts x '
                f'{self.devices_per_proc} dev gen={self.generation}>')


def constrain(x, spec=None):
    """``with_sharding_constraint`` under the active mesh; identity when
    no context is active (so library/model code may call it
    unconditionally). ``x`` may be an NDArray or a raw array; ``spec``
    defaults to the context's batch spec for the value's shape."""
    ctx = current()
    if ctx is None:
        return x
    from ..ndarray.ndarray import NDArray
    raw = x._data if isinstance(x, NDArray) else x
    if spec is None:
        spec = ctx.batch_spec(raw.shape)
    else:
        spec = _rules.resolve_spec(spec, raw.shape, ctx.mesh)
    out = jax.lax.with_sharding_constraint(
        raw, NamedSharding(ctx.mesh, spec))
    return NDArray(out) if isinstance(x, NDArray) else out


def batch_spec(shape):
    """The active context's batch spec for ``shape`` (P() when none)."""
    ctx = current()
    return ctx.batch_spec(tuple(shape)) if ctx is not None else P()


def lift_raws(raws):
    """Device reconciliation of what runs eagerly under the active mesh
    (:meth:`ShardingContext.lift`; the same list back where no context
    is active).

    Inside a mesh context one program may see arrays committed to the
    full mesh (sharded graph outputs) next to host-fresh single-device
    arrays (labels, loss masks) — jax rejects mixed committed device
    sets. Lifting the single-device ones onto the mesh at their batch
    spec lets eager loss/metric math compose with sharded forwards with
    zero model-code changes. Two callers: the bulking engine lifts a
    segment's boundary once, at flush (``_bulk._Segment._launch``, from
    the context the segment was recorded under), so labels and weights
    are placed once a step and not once for every op that reads them;
    ``ops.registry.apply_op`` lifts the operands of an op the engine
    turned away."""
    ctx = current()
    return raws if ctx is None else ctx.lift(raws)


@contextmanager
def entered(ctx):
    """``ctx`` (None: no mesh) is the calling thread's context inside;
    nothing is flushed. The bulking engine traces a segment's plan under
    the context the segment was recorded in, whichever is active when
    the trace happens."""
    _stack().append(ctx)
    try:
        yield ctx
    finally:
        _stack().pop()


@contextmanager
def _changed_to(ctx):
    """Enter ``ctx`` as a context change: the calling thread's pending
    bulk segment runs before the change and what was recorded inside
    runs before the way out, so no segment is recorded under one
    context and launched under another."""
    _bulk.flush_current()
    with entered(ctx):
        try:
            yield ctx
        finally:
            _bulk.flush_current()


def _env_axis(name, value):
    env = os.environ.get(name, '')
    if env:
        return int(env)
    return value


@contextmanager
def mesh(dp=None, tp=None, devices=None, rules=None, mode=None,
         arch=None, **axes):
    """Scoped sharding over a device mesh built from axis sizes::

        with mx.sharding.mesh(dp=4, tp=2):
            net.hybridize()
            out = net(x)            # pjit-sharded, zero model changes

    ``dp``/``tp`` (and any extra named axes) size the mesh;
    ``MXNET_SHARDING_DP``/``MXNET_SHARDING_TP`` override them from the
    environment, and ``MXNET_SHARDING_DISABLE=1`` turns the whole
    context into a no-op. ``rules`` pins an explicit rule table;
    otherwise the registry table for ``arch`` (inferred per block when
    omitted) and the mode ('tp' when tp>1 else 'fsdp') applies.

    Entering and leaving are sync points of the bulking engine: the
    calling thread's pending segment of eager ops runs first, so that
    none is recorded under one context and launched under another.
    """
    if os.environ.get('MXNET_SHARDING_DISABLE', '') == '1':
        yield None
        return
    from ..parallel.mesh import make_mesh
    dp = _env_axis('MXNET_SHARDING_DP', dp)
    tp = _env_axis('MXNET_SHARDING_TP', tp)
    sizes = {}
    if dp and dp > 1:
        sizes['dp'] = dp
    if tp and tp > 1:
        sizes['tp'] = tp
    for k, v in axes.items():
        if v and v > 1:
            sizes[k] = v
    if not sizes:
        sizes = {'dp': len(devices or jax.devices())}
    ctx = ShardingContext(make_mesh(devices=devices, **sizes),
                          rules=rules, mode=mode, arch=arch)
    with _changed_to(ctx):
        yield ctx


@contextmanager
def use(ctx):
    """Re-enter an existing :class:`ShardingContext` (e.g. one captured
    by a server at construction); a sync point of the bulking engine
    on the way in and out, as :func:`mesh` is."""
    if ctx is None:
        yield None
        return
    with _changed_to(ctx):
        yield ctx
