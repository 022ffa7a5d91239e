"""Gluon Parameter & Constant.

Reference: ``python/mxnet/gluon/parameter.py`` (Parameter:47, deferred init
``_finish_deferred_init``:336, per-ctx data/grad replicas ``data``:567
``grad``:604, Constant:708). Semantics preserved: shape may contain unknown
dims (0/-1) resolved at first forward; ``initialize`` places replicas on one
or more Contexts; ``attach_grad`` allocates grad buffers and marks the data
arrays as autograd variables.
"""

import numpy as _np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray.ndarray import NDArray, array
from .. import initializer


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape was known (reference
    parameter.py:DeferredInitializationError)."""


class Parameter:
    """A trainable parameter (reference gluon/parameter.py:47)."""

    def __init__(self, name='weight', grad_req='write', shape=None,
                 dtype='float32', lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype='default', grad_stype='default'):
        self._name = name
        self._grad_req = grad_req if differentiable else 'null'
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self._allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self._data = None   # dict Context -> NDArray
        self._grad = None   # dict Context -> NDArray
        self._deferred_init = None
        self._structure_name = None  # set by Block registration
        # PartitionSpec matched by the mx.sharding rule registry when a
        # mesh context compiled this param's block; placement is sticky:
        # set_data() re-places new values (checkpoint restores) on the
        # same mesh layout instead of silently un-sharding the param
        self._sharding_spec = None
        self._sharding_mesh = None

    # ------------------------------------------------------------------ props
    @property
    def name(self):
        return self._structure_name or self._name

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(
            s1 in (0, -1, None) or s1 == s2
            for s1, s2 in zip(self._shape, new_shape))
        assert len(self._shape) == len(new_shape) and unknown_ok, (
            f'Expected shape {self._shape} is incompatible with given shape '
            f'{new_shape} for Parameter {self.name}')
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ('write', 'add', 'null')
        if not self._differentiable:
            req = 'null'
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == 'null':
            self._grad = None
            if self._data:
                for arr in self._data.values():
                    arr._ag = None
        elif self._data is not None:
            self._init_grad()

    @property
    def stype(self):
        return self._stype

    def _shape_known(self):
        return self._shape is not None and all(
            s not in (0, -1, None) and s > 0 for s in self._shape)

    # ------------------------------------------------------------------- init
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """Reference parameter.py:initialize. Deferred if shape unknown and
        allow_deferred_init."""
        if self._data is not None and not force_reinit:
            return
        default_init = default_init or initializer.Uniform()
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if not self._shape_known():
            if self._allow_deferred_init:
                self._deferred_init = (init, ctx, default_init)
                return
            raise ValueError(
                f'Cannot initialize Parameter {self.name} because it has '
                f'invalid shape: {self._shape}.')
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx, default_init):
        init = init or self.init or default_init
        if isinstance(init, str):
            init = initializer.create(init)
        host = _np.zeros(self._shape, dtype=self.dtype)
        proto = array(host, ctx=ctx[0], dtype=self.dtype)
        desc = initializer.InitDesc(self.name, {'__init__': ''})
        if isinstance(init, initializer.Initializer):
            init(desc, proto)
        else:
            init(proto)
        self._data = {c: (proto if c == ctx[0]
                          else proto.as_in_context(c)) for c in ctx}
        self._deferred_init = None
        if self._grad_req != 'null':
            self._init_grad()

    def _finish_deferred_init(self):
        """Reference parameter.py:336 — called once the shape is inferred."""
        if self._deferred_init is None:
            return
        if not self._shape_known():
            raise DeferredInitializationError(
                f'Parameter {self.name} has unknown shape {self._shape}')
        init, ctx, default_init = self._deferred_init
        self._finish_init(init, ctx, default_init)

    def _init_grad(self):
        from .. import _tape
        import jax.numpy as jnp
        self._grad = {}
        for c, arr in self._data.items():
            g = NDArray(jnp.zeros(arr.shape, dtype=arr._data.dtype), ctx=c)
            self._grad[c] = g
            _tape.mark_variables([arr], [g], [self._grad_req])

    # ------------------------------------------------------------------ access
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f'Parameter {self.name} has not been initialized yet '
                    'because initialization was deferred. Actual '
                    'initialization happens during the first forward pass.')
            raise RuntimeError(
                f'Parameter {self.name} has not been initialized. You '
                'should initialize parameters and create Trainer with '
                'Block.collect_params() instead of Block.params')

    def data(self, ctx=None):
        """Reference parameter.py:567."""
        self._check_initialized()
        if ctx is None:
            return next(iter(self._data.values()))
        if ctx not in self._data:
            raise RuntimeError(
                f'Parameter {self.name} was not initialized on context '
                f'{ctx}. It was only initialized on {list(self._data)}.')
        return self._data[ctx]

    def list_data(self):
        self._check_initialized()
        return list(self._data.values())

    @staticmethod
    def _surface_grad(g):
        """Row-sparse grads ride on the buffer as ``_rsp`` (written by
        the tape's sparse-embedding backward) — surface them so the
        dense table-shaped buffer is never materialized."""
        rsp = getattr(g, '_rsp', None)
        return rsp if rsp is not None else g

    def grad(self, ctx=None):
        """Reference parameter.py:604."""
        self._check_initialized()
        if self._grad is None:
            raise RuntimeError(
                f'Cannot get gradient array for Parameter {self.name} '
                'because grad_req="null"')
        if ctx is None:
            return self._surface_grad(next(iter(self._grad.values())))
        return self._surface_grad(self._grad[ctx])

    def list_grad(self):
        self._check_initialized()
        if self._grad is None:
            return []
        return [self._surface_grad(g) for g in self._grad.values()]

    def list_ctx(self):
        if self._data is None and self._deferred_init is not None:
            return self._deferred_init[1]
        self._check_initialized()
        return list(self._data)

    def set_data(self, data):
        """Set value on all contexts (reference parameter.py:set_data).

        The parameter takes a copy, as the reference's does: the
        caller's array stays the caller's. ``Trainer.step`` donates a
        parameter's buffer to the update, which would delete an adopted
        array under its owner and, given to two parameters, be one
        buffer donated twice."""
        self.shape = data.shape
        if self._data is None:
            if self._deferred_init is not None:
                self._finish_deferred_init()
            else:
                self._data = {data.context if isinstance(data, NDArray)
                              else current_context(): None}
        src = data if isinstance(data, NDArray) else array(data)
        for c in list(self._data):
            nd = src.as_in_context(c).astype(self.dtype, copy=False)
            self._data[c] = nd.copy() if nd is data else nd
        if self._sharding_spec is not None and \
                self._sharding_mesh is not None:
            # sticky sharded placement: a restored checkpoint value goes
            # back onto the mesh layout the compiled program expects
            import jax
            from jax.sharding import NamedSharding
            sh = NamedSharding(self._sharding_mesh, self._sharding_spec)
            for c, nd in list(self._data.items()):
                if getattr(nd._data, 'sharding', None) != sh:
                    nd._rebind(jax.device_put(nd._data, sh))
        if self._grad_req != 'null':
            self._init_grad()

    def zero_grad(self):
        if self._grad is None:
            return
        import jax.numpy as jnp
        for g in self._grad.values():
            g._rebind(jnp.zeros_like(g._data))
            g._rsp = None   # clear any surfaced row-sparse gradient

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            proto = next(iter(self._data.values()))
            self._data = {c: proto.as_in_context(c) for c in ctx}
            if self._grad_req != 'null':
                self._init_grad()
        elif self._deferred_init is not None:
            init, _, default_init = self._deferred_init
            self._deferred_init = (init, ctx, default_init)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        for c, arr in self._data.items():
            self._data[c] = arr.astype(dtype)
        if self._grad_req != 'null':
            self._init_grad()

    def var(self):
        raise NotImplementedError(
            'Symbol variables do not exist in the TPU design; use '
            'HybridBlock.export for graph capture')

    def __repr__(self):
        return (f'Parameter {self.name} (shape={self._shape}, '
                f'dtype={self.dtype})')


class Constant(Parameter):
    """Non-differentiable constant parameter (reference parameter.py:708)."""

    def __init__(self, value, name='const'):
        if not isinstance(value, NDArray):
            value = array(value)
        self._value = value
        super().__init__(name=name, grad_req='null', shape=value.shape,
                         dtype=value.dtype, differentiable=False,
                         init=None)

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._data = {c: self._value.as_in_context(c) for c in ctx}
