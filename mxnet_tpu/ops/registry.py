"""Op registry + imperative dispatch.

Replaces three reference components at once (SURVEY §3.1 call stack):

* the NNVM op registry (``NNVM_REGISTER_OP``, e.g.
  src/operator/nn/fully_connected.cc:251) → :class:`Op` records in a dict;
* the PackedFunc FFI layer (src/api/operator/**, src/runtime/registry.cc) →
  plain Python calls, since frontend and "kernels" share the process;
* ``Imperative::Invoke`` → ``InvokeOp`` → ``Engine::PushAsync``
  (src/imperative/imperative.cc:98,49) → :func:`apply_op`, which dispatches
  to a pure jax function. JAX's async dispatch plays the role of the
  ThreadedEngine: the call returns as soon as the work is enqueued on the
  TPU stream, and ``wait_to_read``/``asnumpy`` are the sync points.

Shape/dtype inference (the reference's FInferShape/FInferType attributes) is
implicit: jax's abstract evaluation computes output avals during dispatch.
"""

import functools

import jax
import numpy as _np

from .. import _bulk
from .. import _deferred_compute as _dc
from .. import _rng, _tape
from .. import profiler as _prof

_OPS = {}

# bound lazily on first dispatch: ops loads before mx.sharding does
_sharding_current = None
_lift_raws = None


def _bind_sharding():
    global _sharding_current, _lift_raws
    from ..sharding.context import current, lift_raws
    _sharding_current = current
    _lift_raws = lift_raws


def _on_mesh():
    return _sharding_current is not None and _sharding_current() is not None


class Op:
    """One registered operator.

    Attributes mirror the reference's op attrs (include/mxnet/op_attr_types.h):
    ``fn`` ≙ FCompute (but pure, over jax arrays), ``differentiable=False`` ≙
    MakeZeroGradNodes, ``stochastic`` ≙ FResourceRequest[kRandom] — the
    dispatch layer injects a PRNG key kwarg drawn from the context RNG
    resource (see mxnet_tpu/_rng.py).
    """

    __slots__ = ('name', 'fn', 'differentiable', 'stochastic', 'namespaces',
                 'aliases', 'wrap', 'n_out', 'static_argnums',
                 'static_argnames', 'dynamic_shape', 'vjp_lock',
                 'host_transfer', 'f32_only', 'cost', 'fused_kernel')

    def __init__(self, name, fn, differentiable=True, stochastic=False,
                 namespaces=('np', 'nd'), aliases=(), wrap=None, n_out=1,
                 static_argnums=(), static_argnames=(), dynamic_shape=False,
                 host_transfer=None, f32_only=False, cost=None,
                 fused_kernel=False):
        self.name = name
        self.fn = fn
        # held while a DEFERRED jax.vjp re-traces fn at backward() time
        # (predict-record mode): _CachedOp's re-trace swaps shared
        # Parameter payloads and must serialize with the graph lock
        # exactly like the tracing of an entry's programs does
        # (docs/threading.md)
        self.vjp_lock = None
        self.differentiable = differentiable
        self.stochastic = stochastic
        self.namespaces = namespaces
        self.aliases = aliases
        self.wrap = wrap
        # output arity for symbolic construction (≙ FNumOutputs in the
        # reference op registry): int, or callable(args, kwargs) -> int
        self.n_out = n_out
        # NDArray args baked as concrete constants instead of traced
        # (their values may steer data-dependent output shapes, and no
        # gradient flows to them — reference MakeZeroGradNodes on that
        # input). E.g. boolean_mask's mask.
        self.static_argnums = frozenset(static_argnums)
        self.static_argnames = frozenset(static_argnames)
        # op's output shape depends on input VALUES (reference
        # FInferShape returning unknown → dynamic-shape CachedOp):
        # raises DynamicShapeError under abstract tracing so callers
        # (e.g. _CachedGraph) can fall back to eager precisely
        self.dynamic_shape = dynamic_shape
        # mx.analysis metadata (docs/static-analysis.md). host_transfer:
        # the op forces a device->host sync per call (dynamic-shape ops
        # always do — the output shape is read from device values).
        # f32_only: the op intentionally computes in f32 under AMP
        # (loss-scale bookkeeping, norm accumulations), so the
        # dtype-promotion rule must not flag its internal upcasts.
        self.host_transfer = bool(dynamic_shape if host_transfer is None
                                  else host_transfer)
        self.f32_only = bool(f32_only)
        # analysis.costs metadata. cost: callable(eqn) -> flops | None,
        # consulted for equations attributed to this op (source-info
        # frames, walker.eqn_op); returning None falls through to the
        # per-primitive closed forms. The override exists for equations
        # the primitive table cannot cost from shapes alone — today
        # pallas_call, whose kernel body the walker does not recurse.
        # fused_kernel: the op dispatches to a hand-fused kernel
        # (ops/pallas), so the bandwidth-bound-chain lint must not
        # re-propose it as a fusion target.
        self.cost = cost
        self.fused_kernel = bool(fused_kernel)


class DynamicShapeError(TypeError):
    """A dynamic-output-shape op was reached with abstract (traced)
    inputs. Raised instead of an opaque jax tracer error so the caller
    can distinguish "this graph needs eager execution" (reference
    CachedOp is_dynamic) from a genuine tracing bug in user code."""


def register(name=None, differentiable=True, stochastic=False,
             namespaces=('np', 'nd'), aliases=(), wrap=None, n_out=1,
             static_argnums=(), static_argnames=(), dynamic_shape=False,
             host_transfer=None, f32_only=False, cost=None,
             fused_kernel=False):
    """Decorator registering a raw-array function as an operator.

    The decorated ``fn`` takes jax arrays (plus static kwargs) and returns a
    jax array or tuple of them. A generic NDArray-level wrapper is generated
    by the frontend (ndarray/register.py) unless ``wrap`` supplies a custom
    one.
    """

    def deco(fn):
        opname = name or fn.__name__
        op = Op(opname, fn, differentiable=differentiable,
                stochastic=stochastic, namespaces=namespaces,
                aliases=aliases, wrap=wrap, n_out=n_out,
                static_argnums=static_argnums,
                static_argnames=static_argnames,
                dynamic_shape=dynamic_shape,
                host_transfer=host_transfer, f32_only=f32_only,
                cost=cost, fused_kernel=fused_kernel)
        _OPS[opname] = op
        for a in aliases:
            _OPS[a] = op
        return fn

    return deco


def get_op(name):
    return _OPS[name]


def list_ops():
    return dict(_OPS)


class _Unkeyable(TypeError):
    pass


def _hashable(x):
    """Best-effort hashable token for a static op argument; raises
    _Unkeyable for values (device arrays, numpy buffers) that must not be
    baked into a bulk-segment cache key. Tokens carry the value's TYPE
    and, for floats, its repr: 2 vs 2.0 vs True and 0.0 vs -0.0 compare
    equal in Python but compile to different programs."""
    if x is None or isinstance(x, (str, bytes)):
        return x
    if isinstance(x, bool):
        return ('b', x)
    if isinstance(x, int):
        return ('i', x)
    if isinstance(x, float):
        return ('f', repr(x))
    if isinstance(x, complex):
        return ('c', repr(x))
    if isinstance(x, (tuple, list)):
        return tuple(_hashable(e) for e in x)
    if isinstance(x, slice):
        # recurse: a slice member can itself be unhashable (device array)
        # — must raise _Unkeyable here so dispatch falls back to eager,
        # not TypeError later at the trie dict lookup — and np-integer
        # members must tokenize consistently with the scalar rules
        return ('__slice__', _hashable(x.start), _hashable(x.stop),
                _hashable(x.step))
    if isinstance(x, _np.dtype):
        return ('__dtype__', str(x))
    if isinstance(x, _np.generic):
        # keep the numpy dtype in the token: np.int32(2)/np.float32(2.0)
        # compare equal as .item()s but compile differently
        return ('np', str(x.dtype), repr(x.item()))
    if isinstance(x, type):
        return ('__type__', x.__name__)
    raise _Unkeyable(repr(type(x)))


def apply_op(op, arrays, fn, n_out=None, name=None, _from_invoke=False,
             bulk_key=None, lift=True, record=None):
    """Imperative dispatch of a pure function over NDArray inputs.

    ``arrays``: NDArray inputs participating in autograd. ``fn``: closure over
    their raw arrays (constants already baked in). Returns raw output(s);
    the caller wraps them. If autograd is recording and any input is tracked,
    a TapeNode is attached to the outputs (reference: Imperative::RecordOp).
    ``record``, where given, is ``fn`` with its vjp already built:
    ``record(*raws) -> (outs, vjp_fn)`` takes the place of ``jax.vjp(fn,
    *raws)`` (a compiled graph's two programs, gluon/block.py).

    Under deferred-compute capture, direct apply_op calls (closure-based
    dispatchers like fused RNN) record an *opaque* node: the captured graph
    stays executable, but tojson() refuses it with a clear error.
    """
    from ..ndarray.ndarray import NDArray, _wrap_out, _wrap_lazy

    recording = _tape.is_recording() and _tape._needs_grad(arrays)
    profiling = _prof._is_profiling_ops()

    # ---- bulked (lazy) dispatch: record into the segment instead of
    # executing; the flush runs the whole segment as one XLA program.
    # Under a mesh context too: a segment's boundary may mix arrays
    # committed to the mesh with single-device ones, and the flush
    # reconciles them once for the segment (_bulk._Segment._launch), as
    # the eager path below does for each op the engine turns away.
    offered = (bulk_key is not None and arrays and not profiling
               and not _dc.is_deferred_compute())
    if offered:
        grad_active = recording and op.differentiable
        rec = _bulk.try_record(op, arrays, fn, bulk_key, grad_active)
        if rec is not None:
            refs, multi, ags = rec
            wrapped = [_wrap_lazy(r, arrays) for r in refs]
            for w, ag in zip(wrapped, ags):
                if ag is not None:
                    w._ag = ag
            _bulk.cap_check()
            return tuple(wrapped) if multi else wrapped[0]

    raws = [a._data for a in arrays]
    if offered:
        _bulk.note_unbulked(raws)       # a launch of its own
    if lift and _on_mesh():
        # mesh context active: reconcile committed device sets (sharded
        # graph outputs vs host-fresh labels) before dispatch. The
        # _CachedGraph dispatch opts out (lift=False): its pjit entry
        # declares explicit per-param in_shardings and places args
        # itself.
        raws = _lift_raws(raws)
    vjp_fn = None
    if profiling:
        import time as _time
        _t0 = _time.perf_counter()
    if recording and op.differentiable and _tape.is_training():
        outs, vjp_fn = (jax.vjp(fn, *raws) if record is None
                        else record(*raws))
    else:
        outs = fn(*raws)
    if profiling:
        # per-op latency needs completion, not dispatch: sync each op
        # (the reference's NaiveEngine-profiling trade, SURVEY §5)
        try:
            jax.block_until_ready(outs)
        except Exception:
            pass
        _nb = sum(int(getattr(o, 'nbytes', 0)) for o in
                  (outs if isinstance(outs, (tuple, list)) else [outs]))
        _prof.record_op(name or op.name,
                        _time.perf_counter() - _t0, _nb)
    multi = isinstance(outs, (tuple, list))
    out_list = list(outs) if multi else [outs]

    wrapped = [_wrap_out(o, arrays) for o in out_list]
    if recording and op.differentiable:
        node = _tape.TapeNode(
            fn, raws, [getattr(a, '_ag', None) for a in arrays],
            len(out_list), name or op.name, vjp_fn=vjp_fn,
            out_avals=[jax.typeof(o) for o in out_list], multi=multi,
            vjp_lock=op.vjp_lock)
        for i, w in enumerate(wrapped):
            w._ag = _tape.AGInfo(node=node, index=i)
    if not _from_invoke and _dc.is_deferred_compute():
        _dc.record_opaque(op, fn, arrays,
                          tuple(wrapped) if multi else wrapped[0])
    return tuple(wrapped) if multi else wrapped[0]


def invoke(op_name, args, kwargs):
    """Generic call path used by generated frontend functions.

    Splits NDArray args from constants, builds the pure closure, dispatches.
    Handles ``out=`` keyword by writing into the given array (reference op
    signature convention).
    """
    from ..ndarray.ndarray import NDArray

    op = _OPS[op_name] if isinstance(op_name, str) else op_name
    out = kwargs.pop('out', None)
    if op.stochastic and kwargs.get('training', True):
        # training=False (e.g. eval-mode dropout) never consumes the
        # key: drawing one anyway would burn an RNG fold per call and
        # leave a dead random_fold_in chain in every eval graph (the
        # mx.analysis dead-code rule flagged exactly this in the zoo)
        kwargs.setdefault('key', _rng.next_key())

    # split tracked NDArrays (incl. inside list/tuple args, e.g. concat).
    # ``fn`` fills their slots of ``consts``, which hold None: a bulk
    # segment's cached plan keeps ``fn``, and an NDArray held there would
    # keep its tape node, the graph that recorded it and all they hold
    arr_slots = []   # (pos, sub_index or None)
    arrays = []
    consts = list(args)
    for i, a in enumerate(args):
        if isinstance(a, NDArray):
            if i in op.static_argnums:
                # bake concrete; no grad, no tracing. Under abstract
                # tracing the value is a tracer — baking it would leak
                # it into a "constant"; raise DynamicShapeError so
                # _CachedGraph falls back to eager (today only
                # boolean_mask hits this, which also sets
                # dynamic_shape=True; this assert makes the invariant
                # explicit rather than incidental)
                import jax.core as _jc
                if not _jc.is_concrete(a._data):
                    raise DynamicShapeError(
                        f'op {op.name!r}: static NDArray argument '
                        f'{i} must be concrete, got a traced value')
                consts[i] = a._data
            else:
                arr_slots.append((i, None))
                arrays.append(a)
                consts[i] = None
        elif isinstance(a, (list, tuple)):
            consts[i] = list(a)
            for j, e in enumerate(a):
                if isinstance(e, NDArray):
                    arr_slots.append((i, j))
                    arrays.append(e)
                    consts[i][j] = None
    kw_arr = {k: v for k, v in kwargs.items() if isinstance(v, NDArray)
              and k not in op.static_argnames}
    kw_static = {k: (v._data if isinstance(v, NDArray) else v)
                 for k, v in kwargs.items() if k not in kw_arr}
    # lift raw device arrays (e.g. the injected PRNG key) into traced
    # inputs: they are data, not attributes — baking them would poison
    # the bulk-segment cache and they carry no gradient anyway.
    # NOT under deferred compute: the capture path must keep seeing the
    # stochastic 'key' in kwargs so it can skip it and re-draw at replay
    # (a lifted key would be frozen into the exported graph).
    if not _dc.is_deferred_compute():
        for k in list(kw_static):
            v = kw_static[k]
            if isinstance(v, jax.Array) and k not in op.static_argnames:
                kw_arr[k] = NDArray(v)
                del kw_static[k]
    kw_keys = list(kw_arr)
    arrays = arrays + [kw_arr[k] for k in kw_keys]

    # bulk-segment cache key over everything that is baked into ``fn``
    # (reference analog: the op attr dict that keys CachedOp buckets)
    try:
        arrpos = {(i, j) for i, j in arr_slots}
        key_parts = []
        for i, c in enumerate(consts):
            if (i, None) in arrpos:
                key_parts.append('@')
            elif isinstance(c, list):
                key_parts.append(tuple(
                    '@' if (i, j) in arrpos else _hashable(e)
                    for j, e in enumerate(c)))
            else:
                key_parts.append(_hashable(c))
        bulk_key = (tuple(key_parts),
                    tuple(sorted((k, _hashable(v))
                                 for k, v in kw_static.items())),
                    tuple(kw_keys))
    except _Unkeyable:
        bulk_key = None

    fn_raw = op.fn
    npos = len(arr_slots)

    def fn(*raws):
        a = [list(x) if isinstance(x, list) else x for x in consts]
        for (i, j), r in zip(arr_slots, raws[:npos]):
            if j is None:
                a[i] = r
            else:
                a[i][j] = r
        kw = dict(kw_static)
        for k, r in zip(kw_keys, raws[npos:]):
            kw[k] = r
        dyn = op.dynamic_shape(a, kw) if callable(op.dynamic_shape) \
            else op.dynamic_shape
        # abstract tracers only: vjp/JVP tracers carry concrete primals
        # and evaluate dynamic-shape ops fine
        if dyn and any(isinstance(x, jax.core.Tracer)
                       and not jax.core.is_concrete(x)
                       for x in (*a, *kw.values()) if x is not None):
            raise DynamicShapeError(
                f'op {op.name!r} has a data-dependent output shape and '
                'cannot run under abstract tracing (reference '
                'dynamic-shape CachedOp); execute it eagerly')
        return fn_raw(*a, **kw)

    if out is not None:
        # out= writes drop autograd linkage on rebind anyway (reference
        # kWriteTo into an existing array) — skip the tape/vjp work
        prev_rec = _tape.set_recording(False)
        try:
            res = apply_op(op, arrays, fn, name=op.name, _from_invoke=True,
                           bulk_key=bulk_key)
        finally:
            _tape.set_recording(prev_rec)
    else:
        res = apply_op(op, arrays, fn, name=op.name, _from_invoke=True,
                       bulk_key=bulk_key)
    if out is not None:
        if isinstance(res, tuple):
            raise ValueError('out= not supported for multi-output op')
        if res._lazy is not None and res._lazy.value is None:
            out._adopt_lazy(res)     # keep the write inside the segment
        else:
            out._rebind(res._data)
        if _dc.is_deferred_compute():
            _dc.record(op, args, kw_static, kw_keys, arrays, res, out)
        return out
    if _dc.is_deferred_compute():
        _dc.record(op, args, kw_static, kw_keys, arrays, res, None)
    return res


def make_frontend(op_name):
    """Generate the user-facing function for an op (≙ codegen in
    reference python/mxnet/ndarray/register.py:265)."""
    op = _OPS[op_name]
    if op.wrap is not None:
        return op.wrap

    @functools.wraps(op.fn)
    def frontend(*args, **kwargs):
        return invoke(op, args, kwargs)

    frontend.__name__ = op_name
    return frontend
