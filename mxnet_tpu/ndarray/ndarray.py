"""NDArray: the user-visible tensor.

TPU-native re-design of the reference NDArray (include/mxnet/ndarray.h:82,
python/mxnet/ndarray/ndarray.py:249). The reference NDArray is a mutable
value-semantic handle over a shared ``Chunk`` (storage + engine variable,
ndarray.h:851-1122); every mutation is an engine push and ``WaitToRead`` is
the sync point.

Here the payload is an immutable ``jax.Array``; mutation is *rebinding*: the
NDArray holds ``_data`` and in-place ops (``+=``, ``x[...] = v``) replace it
with a new functional value (``.at[].set``). This is exactly the versioned-
handle scheme the reference implements manually with ``Chunk`` + engine
``Var`` versions — XLA's async dispatch supplies the dependency ordering the
ThreadedEngine supplied there, and ``wait_to_read`` maps to
``block_until_ready`` (reference ndarray.py:2378).

Autograd metadata (``_ag``) mirrors the reference's per-array
``autograd_entry_`` (include/mxnet/imperative.h:83).
"""

import numpy as _np

import jax
import jax.numpy as jnp

from .. import _tape
from ..base import MXNetError
from ..context import Context, current_context

__all__ = ['NDArray', 'array', 'concatenate_dtypes', '_wrap_out',
           '_wrap_lazy']

_INT_TYPES = (int, _np.integer)


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


def _is_static_key(key):
    """True for basic-indexing keys (ints/slices/None/Ellipsis/int lists)
    that can be baked into a registered op call and serialized."""
    if isinstance(key, tuple):
        return all(_is_static_key(k) for k in key)
    if key is None or key is Ellipsis or isinstance(key, _INT_TYPES):
        return True
    if isinstance(key, slice):
        return all(b is None or isinstance(b, _INT_TYPES)
                   for b in (key.start, key.stop, key.step))
    if isinstance(key, list):
        return all(isinstance(k, _INT_TYPES) for k in key)
    return False


class NDArray:
    """N-dimensional array on a Context, dispatching to XLA.

    Holds a raw ``jax.Array`` (or a jax tracer during graph capture — the
    deferred-compute mode of the reference, imperative.h:244-250, falls out
    for free: the same imperative code runs under ``jax.jit`` tracing).
    """

    # ensure NDArray op overloads win over numpy scalars on the left
    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None):
        self._lazy = None
        self._raw = data
        self._ctx = ctx
        self._ag = None

    @property
    def _data(self):
        """The raw payload. Materializes a pending bulked value — reading
        ``_data`` is a sync point for the bulking engine (_bulk.py), just
        as reading a reference NDArray waits on its engine var."""
        ref = self._lazy
        if ref is not None:
            if ref.value is None:
                from .. import _bulk
                _bulk.materialize(ref)
            self._raw = ref.value
            self._lazy = None
        return self._raw

    @_data.setter
    def _data(self, raw):
        self._lazy = None
        self._raw = raw

    def _adopt_lazy(self, other):
        """Rebind to another NDArray's (possibly pending) payload without
        forcing a flush — the lazy analog of ``_rebind(other._data)``."""
        self._lazy = other._lazy
        self._raw = other._raw
        if self._ag is not None and not self._ag.variable:
            self._ag = None

    # ------------------------------------------------------------------ basic
    @property
    def shape(self):
        ref = self._lazy
        if ref is not None and ref.value is None:
            return tuple(ref.aval.shape)
        return tuple(self._data.shape)

    @property
    def dtype(self):
        ref = self._lazy
        if ref is not None and ref.value is None:
            return _np.dtype(ref.aval.dtype)
        return _np.dtype(self._data.dtype)

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        if self._ctx is not None:
            return self._ctx
        return current_context()

    ctx = context
    device = context

    @property
    def stype(self):
        """Storage type. Dense only for now; row_sparse/csr arrive with the
        sparse module (reference ndarray.h:61-66)."""
        return 'default'

    def _rebind(self, raw):
        """Replace the payload (a 'write' in reference engine terms) —
        bumps the logical version. Node-produced autograd linkage goes
        stale and is dropped; a *variable* marking (attach_grad) persists
        across writes, matching the reference where the engine Var and the
        grad buffer belong to the array, not to one value of it."""
        self._data = raw
        if self._ag is not None and not self._ag.variable:
            self._ag = None

    # ------------------------------------------------------------- sync points
    def wait_to_read(self):
        """Block until the value is computed (reference ndarray.py:2378;
        engine WaitForVar). Re-raises deferred device errors, matching the
        reference's exception-at-sync-point contract (threaded_engine.h:365)."""
        if not _is_tracer(self._data):
            self._data.block_until_ready()

    def wait_to_write(self):
        self.wait_to_read()

    def asnumpy(self):
        """Copy to a host numpy array — THE sync point (ndarray.py:2574)."""
        raw = self._data
        if getattr(raw, 'is_deleted', bool)():
            raise MXNetError(
                'this array\'s buffer was donated to a compiled call and '
                'written over, e.g. by Trainer.step, which updates '
                'weights and optimizer states in place (a detach(), a '
                'same-shape view or the raw array of Parameter.data() '
                'shares the weight\'s buffer), or by '
                'hybridize(donate_inputs=True): read the parameter '
                'again, or keep copy() / asnumpy() of a value the call '
                'should not touch')
        return _np.asarray(jax.device_get(raw))

    def item(self):
        return self.asnumpy().item()

    def asscalar(self):
        if self.size != 1:
            raise ValueError('The current array is not a scalar')
        return self.item()

    def tolist(self):
        return self.asnumpy().tolist()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __array_function__(self, func, types, args, kwargs):
        """NumPy dispatch protocol (reference
        python/mxnet/numpy_dispatch_protocol.py): ``numpy.mean(mx_arr)``
        routes to the mx.np op when one is registered, else falls back to
        official numpy on host copies (reference numpy/fallback.py)."""
        from .. import numpy as mxnp

        mxfn = getattr(mxnp, func.__name__, None)
        if mxfn is not None and callable(mxfn):
            try:
                return mxfn(*args, **kwargs)
            except TypeError:
                pass                      # signature mismatch → fallback
        conv = lambda x: x.asnumpy() if isinstance(x, NDArray) else x  # noqa: E731
        args = [conv(a) for a in args]
        kwargs = {k: conv(v) for k, v in kwargs.items()}
        return func(*args, **kwargs)

    def __array_ufunc__(self, ufunc, method, *args, **kwargs):
        """Route numpy ufuncs (np.add(a, mx_arr), np.exp(mx_arr), ...)
        through the op registry; non-__call__ methods (reduce, outer)
        fall back to host numpy."""
        from .. import numpy as mxnp

        if method == '__call__' and not kwargs.get('out'):
            mxfn = getattr(mxnp, ufunc.__name__, None)
            if mxfn is not None and callable(mxfn):
                try:
                    return mxfn(*args, **kwargs)
                except TypeError:
                    pass
        out_nd = None
        out_spec = kwargs.get('out')
        if out_spec is not None:
            outs = out_spec if isinstance(out_spec, tuple) else (out_spec,)
            if len(outs) == 1 and isinstance(outs[0], NDArray):
                out_nd = outs[0]
                kwargs = {k: v for k, v in kwargs.items() if k != 'out'}
        conv = lambda x: x.asnumpy() if isinstance(x, NDArray) else x  # noqa: E731
        args = [conv(a) for a in args]
        kwargs = {k: conv(v) for k, v in kwargs.items()}
        res = getattr(ufunc, method)(*args, **kwargs)
        if out_nd is not None:
            # mutate the caller's NDArray like numpy's out= contract
            out_nd._rebind(jnp.asarray(res, dtype=out_nd.dtype))
            return out_nd
        return res

    def __dlpack__(self, **kwargs):
        return self._data.__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    # ------------------------------------------------------------ conversions
    def astype(self, dtype, copy=True):
        from ..ops.registry import get_op, invoke
        if _np.dtype(dtype) == self.dtype and not copy:
            return self
        return invoke(get_op('cast'), (self,), {'dtype': _np.dtype(dtype)})

    def copy(self):
        return self.copyto(self.context)

    def copyto(self, other):
        """Copy to a Context (new array) or into another NDArray
        (reference ndarray.py copyto: casts to the destination's dtype,
        shapes must match)."""
        if isinstance(other, Context):
            dev = other.to_jax()
            # may_alias=False: on the array's own device device_put
            # hands back the same buffer under a new name, and a buffer
            # that Trainer.step donates takes every holder with it
            raw = self._data if _is_tracer(self._data) else \
                jax.device_put(self._data, dev, may_alias=False)
            return NDArray(raw, ctx=other)
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise ValueError(
                    f'copyto shape mismatch: {self.shape} vs destination '
                    f'{other.shape}')
            raw = self._data.astype(other.dtype) \
                if other.dtype != self.dtype else self._data
            # may_alias=False as above: the destination, which may be
            # a weight, gets a buffer of its own
            other._rebind(jax.device_put(raw, other.context.to_jax(),
                                         may_alias=False))
            return other
        raise TypeError(f'copyto does not support type {type(other)}')

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context
    to_device = as_in_context

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    # --------------------------------------------------------------- autograd
    def attach_grad(self, grad_req='write', stype=None):
        """Allocate a gradient buffer and mark self as an autograd variable
        (reference autograd.py:218 mark_variables / Parameter flow)."""
        grad = NDArray(jnp.zeros(self.shape, dtype=self._data.dtype),
                       ctx=self._ctx)
        _tape.mark_variables([self], [grad], [grad_req])

    @property
    def grad(self):
        info = self._ag
        if info is not None and info.variable:
            return info.grad
        return None

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        """Reference ndarray.backward → MXAutogradBackwardEx
        (src/c_api/c_api_ndarray.cc:342)."""
        _tape.backward([self], [out_grad] if out_grad is not None else None,
                       retain_graph=retain_graph, train_mode=train_mode)

    def detach(self):
        # share the (possibly pending) payload without forcing a flush:
        # detaching is a lineage operation, not a sync point
        out = NDArray(None, ctx=self._ctx)
        out._lazy = self._lazy
        out._raw = self._raw
        return out

    # --------------------------------------------------------------- indexing
    def _raw_key(self, key):
        def conv(k):
            if isinstance(k, NDArray):
                return k._data
            return k
        if isinstance(key, tuple):
            return tuple(conv(k) for k in key)
        return conv(key)

    def __getitem__(self, key):
        from ..ops.registry import get_op, apply_op, invoke
        if _is_static_key(key):
            # registered-op path: records under deferred compute / export
            return invoke(get_op('_npi_getitem'), (self,), {'key': key})
        rkey = self._raw_key(key)
        op = get_op('_slice_like_internal')
        return apply_op(op, [self], lambda x: x[rkey], name='getitem')

    def __setitem__(self, key, value):
        from ..ops.registry import get_op, invoke
        if _is_static_key(key):
            invoke(get_op('_npi_setitem'), (self, value),
                   {'key': key, 'out': self})
            return
        from .. import _deferred_compute as _dc
        if _dc.is_deferred_compute():
            raise NotImplementedError(
                'in-place assignment with array/boolean indices cannot be '
                'recorded for export; use static indices or np.where '
                'instead (reference deferred compute has the same limit)')
        rkey = self._raw_key(key)
        raw_v = value._data if isinstance(value, NDArray) else jnp.asarray(
            value, dtype=self._data.dtype)
        if rkey is Ellipsis or (isinstance(rkey, slice) and rkey == slice(None)):
            new = jnp.broadcast_to(jnp.asarray(raw_v, dtype=self._data.dtype),
                                   self.shape)
        else:
            new = self._data.at[rkey].set(raw_v)
        self._rebind(new)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError('len() of unsized object')
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asnumpy())
        raise ValueError('The truth value of an array with more than one '
                         'element is ambiguous.')

    def __int__(self):
        return int(self.asnumpy())

    def __float__(self):
        return float(self.asnumpy())

    def __index__(self):
        if self.ndim == 0 and _np.issubdtype(self.dtype, _np.integer):
            return int(self.asnumpy())
        raise TypeError('only integer scalar arrays can be converted to an index')

    def __hash__(self):
        return id(self)

    def __repr__(self):
        if _is_tracer(self._data):
            return f'NDArray(traced, shape={self.shape}, dtype={self.dtype})'
        return f'{self.asnumpy()!r} <NDArray {self.shape} @{self.context}>'

    # ------------------------------------------------------------- arithmetic
    def _binop(self, other, opname, reverse=False):
        from ..ops.registry import get_op, invoke
        if isinstance(other, NDArray) or _np.isscalar(other) or isinstance(
                other, (_np.ndarray, list, tuple)):
            if isinstance(other, (_np.ndarray, list, tuple)):
                other = array(other, ctx=self._ctx)
            a, b = (other, self) if reverse else (self, other)
            return invoke(get_op(opname), (a, b), {})
        return NotImplemented

    def __add__(self, o): return self._binop(o, 'add')
    def __radd__(self, o): return self._binop(o, 'add', True)
    def __sub__(self, o): return self._binop(o, 'subtract')
    def __rsub__(self, o): return self._binop(o, 'subtract', True)
    def __mul__(self, o): return self._binop(o, 'multiply')
    def __rmul__(self, o): return self._binop(o, 'multiply', True)
    def __truediv__(self, o): return self._binop(o, 'true_divide')
    def __rtruediv__(self, o): return self._binop(o, 'true_divide', True)
    def __floordiv__(self, o): return self._binop(o, 'floor_divide')
    def __rfloordiv__(self, o): return self._binop(o, 'floor_divide', True)
    def __mod__(self, o): return self._binop(o, 'mod')
    def __rmod__(self, o): return self._binop(o, 'mod', True)
    def __pow__(self, o): return self._binop(o, 'power')
    def __rpow__(self, o): return self._binop(o, 'power', True)
    def __matmul__(self, o): return self._binop(o, 'matmul')
    def __rmatmul__(self, o): return self._binop(o, 'matmul', True)

    def __eq__(self, o): return self._binop(o, 'equal')
    def __ne__(self, o): return self._binop(o, 'not_equal')
    def __lt__(self, o): return self._binop(o, 'less')
    def __le__(self, o): return self._binop(o, 'less_equal')
    def __gt__(self, o): return self._binop(o, 'greater')
    def __ge__(self, o): return self._binop(o, 'greater_equal')

    def __and__(self, o): return self._binop(o, 'bitwise_and')
    def __or__(self, o): return self._binop(o, 'bitwise_or')
    def __xor__(self, o): return self._binop(o, 'bitwise_xor')

    def __neg__(self):
        from ..ops.registry import get_op, invoke
        return invoke(get_op('negative'), (self,), {})

    def __abs__(self):
        from ..ops.registry import get_op, invoke
        return invoke(get_op('abs'), (self,), {})

    def __invert__(self):
        from ..ops.registry import get_op, invoke
        return invoke(get_op('logical_not'), (self,), {})

    def _inplace(self, other, opname):
        res = self._binop(other, opname)
        if res is NotImplemented:
            raise TypeError(
                f'unsupported operand type for in-place {opname}: '
                f'{type(other).__name__}')
        self._rebind(res._data)
        return self

    def __iadd__(self, o): return self._inplace(o, 'add')
    def __isub__(self, o): return self._inplace(o, 'subtract')
    def __imul__(self, o): return self._inplace(o, 'multiply')
    def __itruediv__(self, o): return self._inplace(o, 'true_divide')

    # ------------------------------------------------------ shape-manipulation
    def _op(self, name, *args, **kwargs):
        from ..ops.registry import get_op, invoke
        return invoke(get_op(name), (self,) + args, kwargs)

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self._op('reshape', newshape=shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return self._op('transpose', axes=axes or None)

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return self.reshape((-1,))

    def squeeze(self, axis=None):
        return self._op('squeeze', axis=axis)

    def expand_dims(self, axis):
        return self._op('expand_dims', axis=axis)

    def broadcast_to(self, shape):
        return self._op('broadcast_to', shape=shape)

    def broadcast_like(self, other):
        return self._op('broadcast_to', shape=other.shape)

    def swapaxes(self, a1, a2):
        return self._op('swapaxes', axis1=a1, axis2=a2)

    def split(self, *a, **kw):
        return self._op('split', *a, **kw)

    def take(self, indices, axis=None, mode='clip'):
        return self._op('take', indices, axis=axis, mode=mode)

    def repeat(self, repeats, axis=None):
        return self._op('repeat', repeats=repeats, axis=axis)

    def tile(self, reps):
        return self._op('tile', reps=reps)

    def clip(self, a_min=None, a_max=None):
        return self._op('clip', a_min=a_min, a_max=a_max)

    def round(self, decimals=0):
        return self._op('round', decimals=decimals)

    def pad(self, *a, **kw):
        return self._op('pad', *a, **kw)

    # ---------------------------------------------------------------- reduces
    def sum(self, axis=None, dtype=None, keepdims=False):
        return self._op('sum', axis=axis, dtype=dtype, keepdims=keepdims)

    def mean(self, axis=None, dtype=None, keepdims=False):
        return self._op('mean', axis=axis, dtype=dtype, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._op('prod', axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        return self._op('max', axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return self._op('min', axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return self._op('argmax', axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return self._op('argmin', axis=axis, keepdims=keepdims)

    def std(self, axis=None, ddof=0, keepdims=False):
        return self._op('std', axis=axis, ddof=ddof, keepdims=keepdims)

    def var(self, axis=None, ddof=0, keepdims=False):
        return self._op('var', axis=axis, ddof=ddof, keepdims=keepdims)

    def cumsum(self, axis=None, dtype=None):
        return self._op('cumsum', axis=axis, dtype=dtype)

    def dot(self, other):
        return self._op('dot', other)

    def norm(self, ord=None, axis=None, keepdims=False):
        return self._op('norm', ord=ord, axis=axis, keepdims=keepdims)

    def abs(self):
        return self.__abs__()

    def sqrt(self):
        return self._op('sqrt')

    def exp(self):
        return self._op('exp')

    def log(self):
        return self._op('log')

    def sign(self):
        return self._op('sign')

    def all(self, axis=None, keepdims=False):
        return self._op('all', axis=axis, keepdims=keepdims)

    def any(self, axis=None, keepdims=False):
        return self._op('any', axis=axis, keepdims=keepdims)

    def tostype(self, stype):
        if stype != 'default':
            raise NotImplementedError('sparse storage arrives with the '
                                      'sparse module')
        return self

    def zeros_like(self):
        return self._op('zeros_like')

    def ones_like(self):
        return self._op('ones_like')


def _wrap_out(raw, input_arrays):
    """Wrap a raw op output; context propagates from the first NDArray input
    (reference imperative_utils.h:169 SetShapeType ctx rules)."""
    ctx = None
    for a in input_arrays:
        if isinstance(a, NDArray) and a._ctx is not None:
            ctx = a._ctx
            break
    return NDArray(raw, ctx=ctx)


def _wrap_lazy(ref, input_arrays):
    """Wrap a pending bulk-segment output (same ctx rules as _wrap_out)."""
    ctx = None
    for a in input_arrays:
        if isinstance(a, NDArray) and a._ctx is not None:
            ctx = a._ctx
            break
    nd = NDArray(None, ctx=ctx)
    nd._lazy = ref
    return nd


def array(source_array, ctx=None, dtype=None, device=None):
    """Create an NDArray from any array-like (reference ndarray.py:array)."""
    ctx = ctx or device
    if isinstance(source_array, NDArray):
        raw = source_array._data
        if dtype is not None:
            raw = raw.astype(dtype)
        if ctx is not None:
            if not isinstance(ctx, Context):
                ctx = Context(ctx)
            if not _is_tracer(raw):
                raw = jax.device_put(raw, ctx.to_jax())
        return NDArray(raw, ctx=ctx or source_array._ctx)
    if dtype is None:
        if isinstance(source_array, _np.ndarray):
            dtype = source_array.dtype
            if dtype == _np.float64:
                dtype = _np.float32
            if dtype == _np.int64:
                dtype = _np.int32
        else:
            arr = _np.asarray(source_array)
            dtype = (_np.float32 if arr.dtype.kind == 'f'
                     else _np.int32 if arr.dtype.kind == 'i' else arr.dtype)
    host = _np.asarray(source_array, dtype=dtype)
    if ctx is not None and not isinstance(ctx, Context):
        ctx = Context(ctx)
    dev = (ctx or current_context()).to_jax()
    return NDArray(jax.device_put(host, dev), ctx=ctx)


def concatenate_dtypes(arrays):
    return jnp.result_type(*[a._data for a in arrays])
