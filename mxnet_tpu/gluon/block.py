"""Gluon Block / HybridBlock.

Reference: ``python/mxnet/gluon/block.py`` (Block:201, __call__:705,
HybridBlock:859, hybridize:1217, graph capture _get_graph_v2:959 via
deferred-compute tracing, _build_cache:993 → CachedOp, export:1299,
SymbolBlock:1485).

TPU re-design of the capture pipeline (SURVEY §3.2): ``hybridize()`` makes
the next call trace ``forward`` with jax tracers flowing through the same
NDArray ops (the role of deferred compute, imperative.h:244-250) and
compiles an XLA executable with ``jax.jit`` (the role of CachedOp,
cached_op.cc:776). The compiled step:

* is cached per (input shapes/dtypes, train-mode) — ≙ CachedOpState keyed
  by shape/type inference results (cached_op.cc:168 SetForwardGraph);
* records as ONE node on the autograd tape (≙ RecordOp("_CachedOp"),
  cached_op.cc:836-844) whose VJP is the XLA-differentiated executable —
  so ``loss.backward()`` runs a compiled backward the way
  CachedOp::Backward (:1016) does;
* returns auxiliary-state updates (BN running stats) as extra outputs that
  are written back after the call — the functional analog of the
  reference's mutable aux states;
* static_alloc maps to XLA buffer donation; bulking/fusion are XLA's job.
"""

import os
import re
import threading
import warnings

import numpy as _np

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray, array
from .parameter import Constant, DeferredInitializationError, Parameter
from .. import _bulk, _rng, _tape
from ..telemetry import trace as _trace

_BLOCK_TRACE = threading.local()


def _trace_state():
    if not hasattr(_BLOCK_TRACE, 'aux_writes'):
        _BLOCK_TRACE.aux_writes = None
    return _BLOCK_TRACE


def is_tracing():
    """True while a HybridBlock forward is being traced for compilation."""
    return _trace_state().aux_writes is not None


def record_aux_update(param, value):
    """Layers call this to update an auxiliary state (e.g. BN running
    mean). Eagerly: rebind now (keeping a pending bulked value lazy).
    Tracing: collected as an extra output of the compiled graph. Accepts
    an NDArray or a raw array."""
    from ..ndarray.ndarray import NDArray as _ND
    st = _trace_state()
    if st.aux_writes is not None:
        raw = value._data if isinstance(value, _ND) else value
        st.aux_writes[id(param)] = (param, raw)
    elif isinstance(value, _ND):
        for c in list(param._data):
            param._data[c]._adopt_lazy(value)
    else:
        for c in list(param._data):
            param._data[c]._rebind(value)


class ParameterDict(dict):
    """Ordered name->Parameter mapping with batch helpers (the surviving
    surface of the reference's ParameterDict after the 2.0 API cleanup)."""

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        for param in self.values():
            param.initialize(init=init, ctx=ctx, force_reinit=force_reinit)

    def zero_grad(self):
        for param in self.values():
            param.zero_grad()

    def setattr(self, name, value):
        for param in self.values():
            setattr(param, name, value)

    def reset_ctx(self, ctx):
        for param in self.values():
            param.reset_ctx(ctx)

    def save(self, filename, strip_prefix=''):
        from ..model import save_ndarray_map
        data = {}
        for name, param in self.items():
            if name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            data[name] = param.data()
        save_ndarray_map(filename, data)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, cast_dtype=False, dtype_source='current'):
        from ..model import load_ndarray_map
        loaded = load_ndarray_map(filename)
        for name, param in self.items():
            if name in loaded:
                param.set_data(loaded[name])
            elif not allow_missing:
                raise KeyError(f'Parameter {name} missing in {filename}')


class _BlockScope:
    pass


class Block:
    """Base building block (reference gluon/block.py:201)."""

    def __init__(self, prefix=None, params=None):
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []
        self._shared = params
        self._ctx = None

    # ----------------------------------------------------------- registration
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get('_children')
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            existing = self.__dict__.get('_reg_params')
            if existing is not None:
                existing[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        return block

    @property
    def params(self):
        """Direct parameters of this block (no descendants)."""
        return ParameterDict(self._reg_params)

    def collect_params(self, select=None):
        """All parameters in this block's subtree, structurally named
        (reference block.py collect_params)."""
        out = ParameterDict()
        self._collect_params_with_prefix(out, '')
        if select is not None:
            pattern = re.compile(select)
            out = ParameterDict({k: v for k, v in out.items()
                                 if pattern.match(k)})
        return out

    def _collect_params_with_prefix(self, out, prefix):
        for name, param in self._reg_params.items():
            full = f'{prefix}{name}'
            param._structure_name = full
            out[full] = param
        for name, child in self._children.items():
            child._collect_params_with_prefix(out, f'{prefix}{name}.')

    # ------------------------------------------------------------------ hooks
    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------------ state
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        """Reference block.py initialize — collects + initializes."""
        self._ctx = ctx
        self.collect_params().initialize(init=init, ctx=ctx,
                                         force_reinit=force_reinit)

    def _initialized_once(self):
        params = self.collect_params()
        return all(p._data is not None or p._deferred_init is not None
                   for p in params.values()) and bool(params)

    def cast(self, dtype):
        for param in self.collect_params().values():
            param.cast(dtype)
        return self

    def reset_ctx(self, ctx):
        self.collect_params().reset_ctx(ctx)

    def zero_grad(self):
        self.collect_params().zero_grad()

    def share_parameters(self, shared):
        """Reference block.py share_parameters (gluon 2.0 weight sharing)."""
        own = self.collect_params()
        for name, param in shared.items():
            if name in own:
                self._set_param_by_path(name, param)
        return self

    def _set_param_by_path(self, path, param):
        parts = path.split('.')
        block = self
        for p in parts[:-1]:
            block = block._children[p]
        block._reg_params[parts[-1]] = param
        object.__setattr__(block, parts[-1], param)

    # ----------------------------------------------------------- save / load
    def save_parameters(self, filename, deduplicate=False):
        """Reference block.py:339 (NDArray-map format)."""
        self.collect_params().save(filename)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source='current'):
        """Reference block.py:375."""
        params = self.collect_params()
        if not self._initialized_once():
            self.initialize(ctx=ctx)
        params.load(filename, ctx=ctx, allow_missing=allow_missing,
                    ignore_extra=ignore_extra)

    def save(self, prefix):
        self.save_parameters(f'{prefix}-model.params.npz')

    def load(self, prefix):
        self.load_parameters(f'{prefix}-model.params.npz')

    # ------------------------------------------------------------------- call
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        from ..visualization import print_summary
        return print_summary(self, inputs[0].shape if inputs else
                             (1, 3, 224, 224))

    def __repr__(self):
        s = f'{type(self).__name__}('
        for name, child in self._children.items():
            s += f'\n  ({name}): {child!r}'.replace('\n', '\n  ')
        return s + ('\n)' if self._children else ')')

    def hybridize(self, active=True, **kwargs):
        """Plain Blocks recurse into children (reference block.py:693)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    @property
    def compile_count(self):
        """Total XLA executables built for this block's subtree since
        construction (monotonic; survives re-hybridize/clear). The
        serving layer (``mx.serve``) asserts this stays flat after
        bucket prewarm — the zero-recompiles-under-traffic guarantee."""
        return sum(child.compile_count for child in self._children.values())

    @property
    def vjp_trace_count(self):
        """Calls of this block's subtree on which ``jax.vjp``'s Python
        ran (monotonic): a hybridized block traces the vjp of a compiled
        entry once, on its first call under ``record()`` in train mode,
        and later recorded calls launch the two programs built then. It
        rises again only for a new input shape, train mode or mesh."""
        return sum(child.vjp_trace_count
                   for child in self._children.values())


# where a leaf of a built vjp_fn comes from (_VjpPrograms.sources): the
# recorded forward's residual outputs, its own arguments, or the trace
_RESIDUAL, _ARGUMENT, _CONSTANT = 'residual', 'argument', 'constant'


def _constant_of(leaf):
    """The value of a residual leaf that is a constant of the trace, None
    where it is computed."""
    import jax
    if not isinstance(leaf, jax.core.Tracer):
        return leaf
    return leaf.get_const()


class _Entry:
    """One compiled entry of a :class:`_CachedGraph`: ``forward`` is the
    jitted forward, ``vjp`` the :class:`_VjpPrograms` a call under
    ``record()`` in train mode takes (None on an entry of predict
    mode)."""

    __slots__ = ('forward', 'vjp')

    def __init__(self, forward, vjp):
        self.forward = forward
        self.vjp = vjp


class _VjpPrograms:
    """The vjp of one compiled entry as two jitted programs, so that a
    recorded call runs none of ``jax.vjp``'s Python after the first.

    ``forward`` is ``jax.vjp`` of the entry's forward under one
    ``jax.jit``: it returns the outputs, the new aux values and the
    residuals that are not its own arguments. A residual that is the
    tracer of an argument (every matrix; under ``remat`` every residual)
    would come back as a copy, so which leaves of the ``vjp_fn`` are
    arguments is found at trace time and kept here with its treedef, and
    ``backward`` is handed those arguments beside the returned
    residuals; a leaf that is a constant of the trace stays with the
    treedef. An aux leaf the forward hands back as it came in is taken
    from the arguments likewise. ``backward`` rebuilds the ``vjp_fn`` and
    calls it; nothing is donated into it (``retain_graph=True`` calls it
    twice).

    The forward writes its residuals over those of the call before it.
    When the tape is done with a call's vjp (a backward without
    ``retain_graph``), :meth:`_Vjp.spent` hands its residual buffers back
    here as ``spares``, and the next call takes them as a last argument,
    donated and otherwise unused: PjRt writes each residual into a spare
    of its aval instead of allocating one. A call that finds no spares
    (an entry's first, a second forward before a backward, one after
    ``retain_graph``) takes fresh buffers from a program that only
    allocates. The graph's wait for the last backward
    (``_CachedGraph._await_backward``) is what makes the spares free by
    then. An entry whose forward hands back no residuals of its own
    (every one under ``remat``) has nothing to recycle and takes none.
    """

    def __init__(self, graph, prog, jit_kwargs, place, pack,
                 main_shardings):
        import jax
        import jax.numpy as jnp

        self.graph = graph
        self.place = place
        self.pack = pack
        self.treedef = None         # of the vjp_fn, set by forward's trace
        self.sources = None         # per leaf of the vjp_fn: (kind, at)
        self.forwarded = None       # positions in _arguments() handed on
        self.aux_forwarded = None   # per aux leaf: its position, or None
        self.out_avals = None
        self.n_out = None           # buffers the forward hands back
        self.n_res = None           # of them the residuals
        self.recycles = False       # whether the forward takes spares
        # the residual buffers of a spent call, until the next call takes
        # them; and how many of them the last call wrote over
        self.spares = None
        self.recycled = 0
        # a donated aux buffer is gone after the call: as a residual it
        # is returned like any other
        self._donated = jit_kwargs.get('donate_argnums', ())
        self._jit_kwargs = jit_kwargs
        self._mesh = main_shardings is not None
        self.forward = None         # built on the first call (_launcher)
        self._fresh = None

        def recorded_forward(rng_key, in_raws, main_raws, *aux_parts):
            graph.vjp_traces += 1
            _tape.note_vjp_trace()
            outs, vjp_fn, aux_out = jax.vjp(
                lambda ins, mains: prog(rng_key, ins, mains, *aux_parts),
                in_raws, main_raws, has_aux=True)
            leaves, self.treedef = jax.tree.flatten(vjp_fn)
            position = {id(a): i for i, a in enumerate(self._arguments(
                rng_key, in_raws, main_raws, aux_parts))}
            slot, residuals, forwarded, sources = {}, [], [], []
            for leaf in leaves:
                at = position.get(id(leaf))
                const = None if at is not None else _constant_of(leaf)
                if const is not None:
                    # a literal of the trace (a scale, 0.5): the backward
                    # program closes over it, no buffer carries it
                    sources.append((_CONSTANT, const))
                    continue
                into = residuals if at is None else forwarded
                if id(leaf) not in slot:
                    slot[id(leaf)] = len(into)
                    into.append(leaf if at is None else at)
                sources.append((_RESIDUAL if at is None else _ARGUMENT,
                                slot[id(leaf)]))
            self.sources, self.forwarded = sources, tuple(forwarded)
            self.aux_forwarded = tuple(position.get(id(a)) for a in aux_out)
            aux_out = tuple(a for a, at in zip(aux_out, self.aux_forwarded)
                            if at is None)
            self.out_avals = [(o.shape, o.dtype) for o in outs]
            self.n_out = len(outs) + len(aux_out) + len(residuals)
            self.n_res = len(residuals)
            return outs, aux_out, tuple(residuals)

        def recorded_backward(residuals, forwarded, cots):
            _tape.note_vjp_trace()
            vjp_fn = jax.tree.unflatten(self.treedef, [
                at if kind is _CONSTANT else
                (residuals if kind is _RESIDUAL else forwarded)[at]
                for kind, at in self.sources])
            in_cots, main_cots = vjp_fn(tuple(
                jnp.zeros(*aval) if c is None else c
                for c, aval in zip(cots, self.out_avals)))
            if main_shardings is not None:
                # p.grad and the fused update see the parameter's layout
                main_cots = tuple(
                    jax.lax.with_sharding_constraint(c, sh)
                    for c, sh in zip(main_cots, main_shardings))
            # an integer input's cotangent is float0: the tape takes None
            return tuple(None if c.dtype == jax.dtypes.float0 else c
                         for c in in_cots + main_cots)

        # traced once an entry, by _launcher; inline, so that the program
        # launched holds its equations as its own
        self._traced = jax.jit(recorded_forward, inline=True)
        self.backward = jax.jit(recorded_backward)

    def _launcher(self, head, anchor):
        """Build ``forward`` and ``_fresh`` from the residuals that one
        trace of the forward at the arguments ``head`` finds.

        The forward is compiled before any spare is made, so that none
        stands on the device while it compiles. Without a mesh the spares
        are placed as the arguments are (on the device of ``anchor``, the
        first committed input or weight, committed as the residuals come
        back: a spare that is not would compile the forward again), and
        the jitted call finds that compile. Under a mesh the residuals'
        shardings are the compiler's to choose: each residual is held to
        its spare's (``shard_alike``), the spares' placement is left open
        in the compile, and they are made as it chose. Either way the
        forward compiles once for a placement."""
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_alike import shard_alike

        traced, mesh = self._traced, self._mesh
        residuals = jax.eval_shape(traced, *head)[2]
        self.recycles = bool(residuals) and not any(
            jax.dtypes.issubdtype(r.dtype, jax.dtypes.extended)
            for r in residuals)

        def recorded_forward(*args):
            *args, spares = args
            outs, aux_out, residuals = traced(*args)
            if mesh and spares:
                residuals = tuple(shard_alike(r, s)[0]
                                  for r, s in zip(residuals, spares))
            return outs, aux_out, residuals

        jit_kwargs = dict(self._jit_kwargs)
        if 'in_shardings' in jit_kwargs:
            jit_kwargs['in_shardings'] += (None,)
        if not self.recycles:
            self.forward = jax.jit(recorded_forward, **jit_kwargs)
            self._fresh = lambda anchor: ()
            return
        # the spares are otherwise unused: kept, so that they can be
        # donated
        jit_kwargs['keep_unused'] = True
        jit_kwargs['donate_argnums'] = self._donated + (len(head),)
        jitted = jax.jit(recorded_forward, **jit_kwargs)
        avals = [(r.shape, r.dtype) for r in residuals]
        on = None if mesh or anchor is None else anchor.sharding
        compiled = jitted.lower(*head, tuple(
            jax.ShapeDtypeStruct(s, d, sharding=on) for s, d in avals)
        ).compile()
        if mesh:
            self.forward = compiled
            self._fresh = jax.jit(
                lambda anchor: tuple(jnp.zeros(s, d) for s, d in avals),
                out_shardings=compiled.input_shardings[0][-1])
        else:
            self.forward = jitted
            # an allocation: lax.empty writes nothing into the buffers
            self._fresh = jax.jit(
                lambda anchor: tuple(jax.lax.empty(s, d) for s, d in avals),
                keep_unused=True)

    def _arguments(self, rng_key, in_raws, main_raws, aux_parts):
        """The forward's arguments that outlive the call, flat."""
        flat = [rng_key, *in_raws, *main_raws]
        for argnum, part in enumerate(aux_parts, 3):
            if argnum not in self._donated:
                flat.extend(part)
        return flat

    def __call__(self, rng_key, in_raws, main_raws, aux_raws):
        """``(outs, aux_out, vjp_fn)`` of one recorded call."""
        rng_key, in_raws = self.place(rng_key, in_raws)
        aux_parts = self.pack(aux_raws)
        head = (rng_key, in_raws, main_raws, *aux_parts)
        anchor = next((a for a in (*in_raws, *main_raws)
                       if getattr(a, 'committed', False)), None)
        if self.forward is None:
            self._launcher(head, anchor)
        # one swap: a hand-back that races it is freed, never shared
        spares, self.spares = self.spares, None
        if spares and not self._mesh and anchor is not None and \
                spares[0].sharding != anchor.sharding:
            spares = None       # the weights moved: the spent set stays
        self.recycled = len(spares) if spares is not None else 0
        outs, written, residuals = self.forward(
            *head, self._fresh(anchor) if spares is None else spares)
        args = self._arguments(rng_key, in_raws, main_raws, aux_parts)
        written = iter(written)
        aux_out = tuple(next(written) if at is None else args[at]
                        for at in self.aux_forwarded)
        return outs, aux_out, _Vjp(
            self, residuals, tuple(args[i] for i in self.forwarded))


class _Vjp:
    """The ``vjp_fn`` of one recorded call (a TapeNode's): the call's
    residuals and the launch of its entry's backward program. The tape
    calls ``indexed`` with the cotangents that arrived; zeros for the
    others are made inside the program."""

    __slots__ = ('programs', 'residuals', 'forwarded')

    def __init__(self, programs, residuals, forwarded):
        self.programs = programs
        self.residuals = residuals
        self.forwarded = forwarded

    def indexed(self, present):
        # aux outputs follow the outputs in the node and get no cotangent
        cots = self.programs.backward(
            self.residuals, self.forwarded,
            tuple(present.get(i)
                  for i in range(len(self.programs.out_avals))))
        # the outputs of a program are ready together: the last will do
        self.programs.graph._backward = cots[-1]
        return cots

    def __call__(self, cots):
        return self.indexed(dict(enumerate(cots)))

    def spent(self):
        """The tape is done with this vjp (a backward without
        ``retain_graph`` has run it): its residuals become its entry's
        spares, one store, replacing a set no call has taken."""
        residuals, self.residuals = self.residuals, None
        if self.programs.recycles:
            self.programs.spares = residuals


class _CachedGraph:
    """Compiled-executable cache for one HybridBlock (≙ CachedOp,
    src/imperative/cached_op.h:463)."""

    def __init__(self, block, static_alloc=False, static_shape=False,
                 backend=None, flags=None, remat=False, check=False,
                 donate_inputs=False):
        self.block = block
        self.static_alloc = static_alloc
        self.static_shape = static_shape
        self.backend = backend
        self.remat = remat or os.environ.get(
            'MXNET_BACKWARD_DO_MIRROR', '') == '1'
        # lint the traced graph after the first compile (mx.analysis)
        self.check = check
        self._checked = False
        # opt-in: donate input activations to XLA (caller promises not
        # to reuse the passed buffers); never the default — gluon
        # callers keep live NDArray handles to their inputs
        self.donate_inputs = donate_inputs
        # monotonic count of executables built (never reset by clear():
        # the serving layer's zero-recompiles-after-warmup guarantee is
        # checked against this, so re-hybridize churn must show up too)
        self.compiles = 0
        # calls on which jax.vjp's Python ran: once an entry, when its
        # recorded forward is traced (Block.vjp_trace_count)
        self.vjp_traces = 0
        # an output of the last backward program launched from this
        # graph, until the next recorded call has waited for it
        self._backward = None
        self._compiled = {}
        self._out_trees = {}       # per cache entry: output pytree structure
        self._param_order = None
        self._monitor_callbacks = []
        # serializes tracing + recorded calls; see __call__ (reference:
        # src/imperative/cached_op_threadsafe.cc thread-safe CachedOp)
        self._lock = threading.RLock()
        self._race = None
        from ..analysis import race as _race
        if _race.enabled():
            # declared level 'block.graph' (analysis/locks.py). Only
            # cache WRITES are annotated: the lock-free _ready probe on
            # the steady-state inference path is by design (re-checked
            # under the lock) and must not be reported.
            self._lock = _race.tracked(self._lock, 'block.graph')
            self._race = _race.shared_state('block._CachedGraph.cache',
                                            guard=self._lock)
        self._ready = set()        # keys whose first call fully completed
        # set when the graph has data-dependent shapes (boolean_mask,
        # np.unique, ...) that abstract jit tracing cannot express —
        # the block then runs eagerly, like the reference CachedOp with
        # config.is_dynamic (cached_op.h:455: "uses dynamic shape" →
        # op-by-op execution)
        self._dynamic = False

    def clear(self):
        with self._lock:
            if self._race is not None:
                self._race.write()
            self._compiled.clear()
            self._out_trees.clear()
            self._ready.clear()
            self._param_order = None

    def _params(self):
        if self._param_order is None:
            params = self.block.collect_params()
            main, aux = [], []
            for p in params.values():
                (aux if p.grad_req == 'null' else main).append(p)
            self._param_order = (main, aux)
        return self._param_order

    def _sharding_plan(self, ctx, in_nds):
        """Resolved shardings for one compile under an active
        ``mx.sharding`` context: ``(in_shardings kwarg, param specs,
        input specs)``. Params match the rule registry by structural
        name; inputs take the batch spec (leading dim on the data
        axis). Parameter buffers are placed on the mesh here, once —
        later calls dispatch on already-sharded arrays."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as _P

        main, aux = self._params()
        rules = ctx.rules_for_block(self.block)
        # names relative to THIS block, resolved fresh: a child-level
        # collect_params() call (infer_shape tracing a child's cached
        # graph, a user poking net.output) re-stamps _structure_name
        # with child-relative names, so the cached stamp cannot be
        # trusted for rule matching
        fresh = {id(p): k for k, p in self.block.collect_params().items()}
        specs = {}
        for p in list(main) + list(aux):
            name = fresh.get(id(p)) or p.name
            spec = ctx.spec_for(name, p.shape, rules)
            specs[id(p)] = spec
            sh = NamedSharding(ctx.mesh, spec)
            for c, nd in list(p._data.items()):
                if getattr(nd._data, 'sharding', None) != sh:
                    nd._rebind(jax.device_put(nd._data, sh))
            p._sharding_spec = spec
            p._sharding_mesh = ctx.mesh
        in_specs = tuple(ctx.batch_spec(x.shape) for x in in_nds)
        # rng key and graph inputs arrive as fresh single-device arrays
        # each call: leave their entry None (jax.jit: inherit from the
        # argument) and let the with_sharding_constraint injected in
        # pure_fn distribute them; a committed explicit sharding here
        # would make pjit reject the host-resident batch outright.
        in_shardings = (
            None,
            tuple(None for _ in in_specs),
            tuple(NamedSharding(ctx.mesh, specs[id(p)]) for p in main),
            tuple(NamedSharding(ctx.mesh, specs[id(p)]) for p in aux),
        )
        return in_shardings, specs, in_specs

    def _build(self, shapes_key, train_mode, n_in, treedef, donate=(),
               ctx=None, in_nds=()):
        import jax

        jit_kwargs = {}
        aux_specs = None
        in_specs = None
        if ctx is not None:
            in_shardings, specs, in_specs = self._sharding_plan(ctx,
                                                                in_nds)
            jit_kwargs['in_shardings'] = in_shardings
            _, aux = self._params()
            aux_specs = tuple(specs[id(p)] for p in aux)
        pure_fn = self._make_pure(shapes_key, train_mode, treedef,
                                  ctx=ctx, aux_specs=aux_specs)
        if donate:
            # static_alloc buffer reuse (≙ the reference's persistent
            # workspace): donate the mutable aux state (argnum 3, BN
            # running stats) on recorded-train entries so XLA updates
            # it in place (input_output_alias), and the inputs (argnum
            # 1) when the caller opted in via donate_inputs. __call__
            # computes the tuple; inference entries never donate aux —
            # lock-free threads share those buffers. The donation-audit
            # rule (mx.analysis) machine-checks the aliasing actually
            # happens.
            jit_kwargs['donate_argnums'] = tuple(donate)
        held = self._aux_handed_back(pure_fn, in_nds) if 3 in donate \
            else ()
        if self.remat:
            # recompute activations in backward instead of storing them
            # (reference backward mirroring, MXNET_BACKWARD_DO_MIRROR)
            pure_fn = jax.checkpoint(pure_fn)
        if held:
            # a grad_req='null' leaf the forward only reads (a router's
            # correction bias, a frozen embedding) has no new value to
            # land in its buffer: it goes in undonated, as a fifth
            # argument, and is put back in its place inside the program
            _, aux = self._params()
            written = [i for i in range(len(aux)) if i not in held]

            def prog(rng_key, in_raws, main_raws, written_raws, held_raws):
                at = dict(zip(written + list(held),
                              written_raws + held_raws))
                return pure_fn(rng_key, in_raws, main_raws,
                               tuple(at[i] for i in range(len(aux))))

            def pack(aux_raws):
                return (tuple(aux_raws[i] for i in written),
                        tuple(aux_raws[i] for i in held))

            if 'in_shardings' in jit_kwargs:
                sh = jit_kwargs['in_shardings']
                jit_kwargs['in_shardings'] = sh[:3] + pack(sh[3])
        else:
            prog = pure_fn

            def pack(aux_raws):
                return (aux_raws,)

        jitted = jax.jit(prog, **jit_kwargs)
        main_shardings = None
        if ctx is None:
            def place(rng_key, in_raws):
                return rng_key, in_raws
        else:
            # rng key / inputs arrive as committed single-device arrays
            # each call while the params are committed to the mesh — jax
            # rejects mixed device sets, so place them on the mesh at
            # dispatch. device_put is a traceable primitive, so a vjp
            # re-trace of ``forward`` (create_graph, predict-record)
            # stays valid.
            from jax.sharding import NamedSharding, PartitionSpec as _P
            key_sh = NamedSharding(ctx.mesh, _P())
            in_shs = tuple(NamedSharding(ctx.mesh, s) for s in in_specs)
            main_shardings = jit_kwargs['in_shardings'][2]

            def place(rng_key, in_raws):
                return jax.device_put(rng_key, key_sh), tuple(
                    jax.device_put(r, sh)
                    if getattr(r, 'ndim', None) is not None else r
                    for r, sh in zip(in_raws, in_shs))

        def forward(rng_key, in_raws, main_raws, aux_raws):
            rng_key, in_raws = place(rng_key, in_raws)
            return jitted(rng_key, in_raws, main_raws, *pack(aux_raws))

        return _Entry(forward, _VjpPrograms(
            self, prog, jit_kwargs, place, pack, main_shardings)
            if train_mode else None)

    def _aux_handed_back(self, pure_fn, in_nds):
        """Indices of the aux leaves that this entry's forward hands
        back as they came in (no ``record_aux_update`` on them), found
        by one abstract trace of the forward: only a net that has aux
        leaves and donates them pays it, once an entry."""
        import jax

        main, aux = self._params()
        struct = lambda raw: jax.ShapeDtypeStruct(raw.shape, raw.dtype)
        held = []

        def probe(rng_key, in_raws, main_raws, aux_raws):
            outs, aux_out = pure_fn(rng_key, in_raws, main_raws, aux_raws)
            held.extend(i for i, (new, old) in enumerate(
                zip(aux_out, aux_raws)) if new is old)
            return outs

        jax.eval_shape(
            probe, struct(_rng._global()),
            tuple(struct(x._data) for x in in_nds),
            tuple(struct(p.data()._data) for p in main),
            tuple(struct(p.data()._data) for p in aux))
        return tuple(held)

    def _make_pure(self, shapes_key, train_mode, treedef, ctx=None,
                   aux_specs=None):
        import jax

        main, aux = self._params()

        if ctx is not None:
            # rule-tagged activation boundaries: constrain graph inputs
            # and outputs to the batch spec (leading dim on the data
            # axis) and aux write-backs to their param spec, so GSPMD
            # propagation anchors at the graph edge and the donated aux
            # output provably aliases its (identically sharded) input.
            # Interior boundaries: mx.sharding.constrain() — a no-op
            # outside the context, so models stay mesh-agnostic.
            from jax.sharding import NamedSharding

            def _bound(raw, spec=None):
                if getattr(raw, 'ndim', None) is None:
                    return raw
                spec = spec if spec is not None else ctx.batch_spec(
                    raw.shape)
                return jax.lax.with_sharding_constraint(
                    raw, NamedSharding(ctx.mesh, spec))
        else:
            def _bound(raw, spec=None):
                return raw

        def pure_fn(rng_key, in_raws, main_raws, aux_raws):
            # swap traced values into the parameters
            saved = []
            st = _trace_state()
            prev_aux = st.aux_writes
            st.aux_writes = {}
            prov = _rng.push_trace_provider(rng_key)
            prev_rec = _tape.set_recording(False)
            prev_train = _tape.set_training(train_mode)
            try:
                for p, raw in list(zip(main, main_raws)) + \
                        list(zip(aux, aux_raws)):
                    saved.append((p, p._data))
                    p._data = {c: NDArray(raw, ctx=c) for c in p._data}
                args = jax.tree.unflatten(
                    treedef, [NDArray(_bound(r)) for r in in_raws])
                out = self.block.forward(*args)
                out_leaves, out_tree = jax.tree.flatten(
                    out, is_leaf=lambda x: isinstance(x, NDArray))
                out_raws = [_bound(o._data) if isinstance(o, NDArray)
                            else o for o in out_leaves]
                if aux_specs is not None:
                    aux_out = [_bound(st.aux_writes[id(p)][1], spec)
                               if id(p) in st.aux_writes else ar
                               for p, ar, spec in zip(aux, aux_raws,
                                                      aux_specs)]
                else:
                    aux_out = [st.aux_writes[id(p)][1]
                               if id(p) in st.aux_writes else ar
                               for p, ar in zip(aux, aux_raws)]
                self._out_trees[shapes_key] = out_tree
                return tuple(out_raws), tuple(aux_out)
            finally:
                for p, data in saved:
                    p._data = data
                _tape.set_recording(prev_rec)
                _tape.set_training(prev_train)
                _rng.pop_trace_provider()
                st.aux_writes = prev_aux

        return pure_fn

    def __call__(self, args):
        if self._dynamic:
            out = self.block.forward(*args)
            for cb in self._monitor_callbacks:
                cb(self.block, out)
            return out

        # spans of the train path (docs/observability.md): what is left
        # of mx.graph.call after flush and launch is this layer's own
        # host time
        with _trace.child_span('mx.graph.call') as span:
            built = self.compiles
            # a compiled graph is a launch of its own and a sync point of
            # the bulking engine, like backward(): eager ops still pending
            # (the shape-resolving forward before hybridize(), whose
            # result nobody reads) are dispatched first, in program
            # order. Left pending they ride into the first step's
            # segment, and the second step's segment is then a new one to
            # compile.
            with _trace.child_span('mx.graph.flush'):
                _bulk.flush_current()
                if _tape.is_recording():
                    self._await_backward()
            out = self._call_static(args, span)
            if span.live:
                span.set(compiled=self.compiles - built)
            return out

    def _await_backward(self):
        """A recorded forward's residuals are allocated when it is
        enqueued. Enqueued while the backward of the call before it has
        not run, they are a second set on the device beside that call's
        (and a third where the host is two steps ahead): a host that is
        faster than the device would fill the chip with them. So a
        recorded call first waits for the last backward launched from
        this graph; the update that follows a backward on the device
        covers the forward's dispatch. The wait is the span
        ``mx.graph.await``, inside ``mx.graph.flush``, opened only where
        there is a backward to wait for."""
        import jax

        launched, self._backward = self._backward, None
        if launched is not None:
            with _trace.child_span('mx.graph.await'):
                jax.block_until_ready(launched)

    def _call_static(self, args, span):
        import jax

        leaves, treedef = jax.tree.flatten(
            args, is_leaf=lambda x: isinstance(x, NDArray))
        in_nds = [x if isinstance(x, NDArray) else array(x) for x in leaves]
        main, aux = self._params()
        if span.live:
            span.set(n_in=len(in_nds), n_params=len(main) + len(aux))
        # the train flag alone decides the traced branch/behavior
        # (dropout, BN stats, detector training heads): record() turns
        # it on by default, autograd.train_mode() turns it on without
        # recording — eager and hybridized must agree in every scope
        train_mode = _tape.is_training()
        recording = _tape.is_recording()
        # Donation decision, per entry (and therefore part of the key):
        # aux state is donated only on recorded-train executables — those
        # run under the graph lock and immediately rebind the params to
        # the aliased outputs, so no other thread can keep a handle to
        # the donated buffer. donate_inputs is the caller's opt-in and
        # excluded while recording (input activations are backward
        # residuals).
        donate = ()
        if self.static_alloc and train_mode and recording and aux:
            donate += (3,)
        if self.donate_inputs and not recording:
            donate += (1,)
        donate = tuple(sorted(donate))
        # ambient mx.sharding context: its fingerprint joins the cache
        # key (a different mesh is a different XLA program — retracing
        # on mesh change is by design, the recompile-hazard rule
        # documents it as a non-hazard), and the entry compiles with
        # in_shardings derived from the partition-rule registry.
        from .. import sharding as _sharding
        ctx = _sharding.current()
        mesh_key = ctx.fingerprint() if ctx is not None else None
        # treedef is part of the key: same leaf shapes under different arg
        # nesting (or train/eval forwards with different output structures)
        # must not share a compiled entry or its output pytree
        key = (tuple((x.shape, str(x.dtype)) for x in in_nds), train_mode,
               donate, treedef, mesh_key)
        # Thread-safety contract (reference thread-safe CachedOp,
        # src/imperative/cached_op_threadsafe.cc:1-316; docs/threading.md):
        # compiled steady-state INFERENCE runs lock-free from N threads —
        # the executable is pure over its fetched inputs and jax dispatch
        # is thread-safe. The lock serializes (a) tracing, because
        # jax.jit traces lazily on first execution and pure_fn swaps
        # traced values into the SHARED Parameter payloads, and (b) any
        # autograd-recorded call: the first of an entry traces its
        # recorded forward and re-enters that swap, and every one
        # rebinds donated aux state. Parameter snapshots on the
        # lock-free path still acquire the lock briefly so they can
        # never observe a mid-trace swap.
        if key in self._ready and not recording:
            with self._lock:
                # re-check under the lock: a concurrent clear()
                # (re-hybridize/cast while serving) may have emptied the
                # cache since the unlocked _ready probe. out_tree is
                # snapshotted here too — _execute must not re-read the
                # dict after the lock drops.
                entry = self._compiled.get(key)
                out_tree = self._out_trees.get(key)
                main_nds = [p.data() for p in main]
                aux_raws = tuple(p.data()._data for p in aux)
            if entry is not None and out_tree is not None:
                try:
                    return self._execute(args, key, entry, in_nds,
                                         main_nds, aux_raws, out_tree)
                except RuntimeError as e:
                    if 'deleted' not in str(e).lower():
                        raise
                    # a recorded-train step donated the aux buffers this
                    # thread snapshotted between the lock release and
                    # dispatch, or another thread's Trainer.step donated
                    # a weight after _execute read it out of main_nds
                    # (NDArrays, which the step rebinds); fall through
                    # to the serialized path, which re-snapshots the
                    # rebound (post-donation) state under the lock and
                    # executes while holding it
        with self._lock:
            if self._race is not None:
                self._race.write()
            if key not in self._compiled:
                self._compiled[key] = self._build(key, train_mode,
                                                  len(in_nds), treedef,
                                                  donate=donate, ctx=ctx,
                                                  in_nds=in_nds)
                self.compiles += 1
            entry = self._compiled[key]
            main_nds = [p.data() for p in main]
            aux_raws = tuple(p.data()._data for p in aux)
            out = self._execute(args, key, entry, in_nds, main_nds,
                                aux_raws, None)
            self._ready.add(key)
            if self.check and not self._checked:
                self._checked = True
                self._run_check(args, train_mode)
            return out

    def _run_check(self, args, train_mode):
        """hybridize(check=True): lint the just-compiled graph once and
        route findings through ``warnings`` (mx.analysis). Errors —
        including strict-promoted warnings under MXNET_ANALYSIS_STRICT=1
        — raise MXNetError."""
        from .. import analysis, profiler

        name = type(self.block).__name__
        try:
            graph = analysis.trace_block(self.block, *args,
                                         train=train_mode, name=name)
            report = analysis.lint_graph(graph)
        except Exception as e:   # noqa: BLE001 - lint must never kill a step
            warnings.warn(f'{name}: hybridize(check=True) could not lint '
                          f'the graph: {type(e).__name__}: {e}',
                          stacklevel=4)
            return
        self.block._analysis_report = report
        profiler.attach_analysis(name, report)
        if os.environ.get('MXNET_ANALYSIS_COSTS', '1') != '0':
            try:
                cost = analysis.cost_of_graph(graph)
                self.block._cost_report = cost
                profiler.attach_cost(name, cost)
            except Exception as e:   # noqa: BLE001 - advisory only
                warnings.warn(f'{name}: cost model failed: '
                              f'{type(e).__name__}: {e}', stacklevel=4)
        if report.findings:
            warnings.warn(str(report), stacklevel=4)
        report.raise_if_errors()

    def _execute(self, args, key, entry, in_nds, main_nds, aux_raws,
                 out_tree):
        import jax
        from ..ops.registry import Op, apply_op, DynamicShapeError

        main, aux = self._params()
        rng_key = _rng.next_key()
        n_in = len(in_nds)
        n_aux = len(aux)
        recorded = False

        def fn(*raws):
            outs, aux_out = entry.forward(rng_key, raws[:n_in], raws[n_in:],
                                          aux_raws)
            return tuple(outs) + tuple(aux_out)

        def record(*raws):
            # fn with its vjp, from the entry's two built programs: what
            # a recorded call in train mode runs in place of jax.vjp(fn)
            nonlocal recorded
            outs, aux_out, vjp_fn = entry.vjp(rng_key, raws[:n_in],
                                              raws[n_in:], aux_raws)
            recorded = True
            return tuple(outs) + tuple(aux_out), vjp_fn

        op = Op('_CachedOp', fn, differentiable=True)
        # predict-record mode defers jax.vjp(fn) to backward() time
        # (_tape.py); that re-trace re-enters pure_fn's shared-Parameter
        # payload swap and must hold this graph's lock (ADVICE r4)
        op.vjp_lock = self._lock
        try:
            # the jitted call down to PjRt (under record() in train mode
            # the recorded forward, which hands back its residuals too)
            with _trace.child_span('mx.graph.launch') as launch:
                traces = _tape.vjp_traces()
                res = apply_op(op, in_nds + main_nds, fn,
                               name='_CachedOp', lift=False,
                               record=record if entry.vjp else None)
                if launch.live:
                    first = res[0] if isinstance(res, tuple) else res
                    launch.set(
                        n_out=entry.vjp.n_out if recorded else
                        len(res) if isinstance(res, tuple) else 1,
                        traced=int(_tape.vjp_traces() != traces),
                        **_bulk.launch_attrs(first._data),
                        # how many of its residuals the forward wrote
                        # over the last backward's spent ones
                        **({'residuals': entry.vjp.n_res,
                            'recycled': entry.vjp.recycled}
                           if recorded else {}))
        except DynamicShapeError:
            # a dynamic-output-shape op inside the graph (boolean_mask,
            # unique, ...): permanently switch this block to eager
            # op-by-op execution (reference dynamic-shape CachedOp).
            # Other tracing errors — e.g. Python control flow on traced
            # values — propagate unchanged so user bugs stay visible.
            # The failed entry is dropped so a later clear()+
            # re-hybridize can retry compilation.
            self._dynamic = True
            with self._lock:
                if self._race is not None:
                    self._race.write()
                self._compiled.pop(key, None)
                self._out_trees.pop(key, None)
                self._ready.discard(key)
            warnings.warn(
                f'{type(self.block).__name__}: graph has data-dependent '
                'shapes; hybridize falls back to eager execution '
                '(reference CachedOp is_dynamic)', stacklevel=2)
            return self(args)
        if not isinstance(res, tuple):
            res = (res,)
        out_vals = res[:len(res) - n_aux] if n_aux else res
        aux_vals = res[len(res) - n_aux:] if n_aux else ()
        if aux:
            # BN-stat style rebinding mutates shared Parameters: keep it
            # under the lock so a concurrent snapshot reads a coherent set
            with self._lock:
                for p, v in zip(aux, aux_vals):
                    for c in list(p._data):
                        p._data[c]._rebind(v._data)
                    # aux outputs never need grad linkage
                    v._ag = None
        if out_tree is None:
            # locked path: the tree was written during this call's trace
            # and the caller still holds the graph lock
            out_tree = self._out_trees[key]
        out = jax.tree.unflatten(out_tree, list(out_vals))
        for cb in self._monitor_callbacks:
            cb(self.block, out)
        return out


class HybridBlock(Block):
    """Reference gluon/block.py:859 — traceable/compilable Block."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self._active = False
        self._cached_graph = None
        self._first_forward_done = False

    def hybridize(self, active=True, backend=None, backend_opts=None,
                  static_alloc=True, static_shape=False, inline_limit=2,
                  forward_bulk_size=None, backward_bulk_size=None,
                  remat=False, check=False, donate_inputs=False, **kwargs):
        """Reference block.py:1217. backend= selected subgraph backends in
        the reference (optimize_for); the whole graph goes to XLA here.

        ``remat=True`` wraps the compiled forward in ``jax.checkpoint``:
        backward recomputes activations instead of keeping them — the
        reference's backward-mirroring memory trade
        (MXNET_BACKWARD_DO_MIRROR, src/nnvm/gradient.cc:58-77), but as a
        per-block switch.

        ``check=True`` lints the traced graph right after the first
        compile (``mx.analysis``: dtype promotion, captured constants,
        recompile hazards, host transfers, dead code) and reports
        findings through ``warnings``; error findings — or any finding
        under ``MXNET_ANALYSIS_STRICT=1`` — raise :class:`MXNetError`.

        ``donate_inputs=True`` donates input activation buffers to XLA
        on non-recorded entries (buffer reuse — the caller must not
        touch the passed arrays after the call). Mutable aux state (BN
        running stats) is donated automatically on recorded-train
        entries under ``static_alloc``; the ``donation-audit`` analysis
        rule verifies the aliasing actually happens."""
        self._active = active
        self._cached_graph = _CachedGraph(
            self, static_alloc=static_alloc, static_shape=static_shape,
            backend=backend, remat=remat, check=check,
            donate_inputs=donate_inputs) if active else None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def optimize_for(self, x, *args, backend=None, clear=True, **kwargs):
        """Reference block.py:1038 — partition for a backend. XLA compiles
        the whole graph; this hybridizes + warms the cache."""
        self.hybridize(True)
        return self(x, *args)

    def pure_function(self, *args, train=False):
        """Export this block's forward as a pure jax function — the
        TPU-idiomatic escape hatch for building fully-fused training
        programs (lax.scan over steps, pjit over meshes) where the
        per-step Python dispatch of the imperative path would dominate.

        Returns ``(fn, in_raws, main_raws, aux_raws)`` with
        ``fn(rng_key, in_raws, main_raws, aux_raws) ->
        (out_raws_tuple, new_aux_raws_tuple)`` pure and traceable.
        ``main_raws`` are the trainable parameters (grad_req != 'null'),
        ``aux_raws`` the rest (e.g. BatchNorm running stats — returned
        updated when ``train=True``). No reference analog: CachedOp has
        no user-facing pure form; this is new TPU-first surface."""
        import jax
        if not isinstance(self._cached_graph, _CachedGraph):
            self.hybridize(True)
        graph = self._cached_graph
        if not self._first_forward_done:
            self(*args)  # materialize deferred params
        leaves, treedef = jax.tree.flatten(
            args, is_leaf=lambda x: isinstance(x, NDArray))
        in_raws = tuple(x._data if isinstance(x, NDArray)
                        else array(x)._data for x in leaves)
        main, aux = graph._params()
        fn = graph._make_pure(None, train, treedef)
        main_raws = tuple(p.data()._data for p in main)
        aux_raws = tuple(p.data()._data for p in aux)
        return fn, in_raws, main_raws, aux_raws

    @property
    def compile_count(self):
        """See :attr:`Block.compile_count`; adds this block's own cache."""
        own = self._cached_graph.compiles if isinstance(
            self._cached_graph, _CachedGraph) else 0
        return own + sum(c.compile_count for c in self._children.values())

    @property
    def vjp_trace_count(self):
        """See :attr:`Block.vjp_trace_count`; adds this block's own."""
        own = self._cached_graph.vjp_traces if isinstance(
            self._cached_graph, _CachedGraph) else 0
        return own + sum(c.vjp_trace_count
                         for c in self._children.values())

    def prewarm(self, input_specs, dtype='float32'):
        """Compile executables for a declared set of input shapes before
        they ever see traffic (the serving layer's bucket warmup; no
        reference analog — CachedOp compiles lazily per shape).

        ``input_specs``: iterable of entries, each either a shape tuple
        for a single-input block, a ``(shape, dtype)`` pair, or a tuple
        of shape tuples for multi-input blocks. Runs one non-recorded
        forward per entry (discarding outputs) so the compile cache holds
        every declared bucket. Returns the number of new executables
        built (0 when everything was already warm)."""
        before = self.compile_count
        for spec in input_specs:
            d = dtype
            if (isinstance(spec, tuple) and len(spec) == 2
                    and isinstance(spec[0], tuple)
                    and isinstance(spec[1], str)):
                spec, d = spec
            if isinstance(spec, tuple) and spec \
                    and isinstance(spec[0], tuple):
                shapes = spec
            else:
                shapes = (tuple(spec),)
            args = [array(_np.zeros(s, dtype=_np.dtype(d))) for s in shapes]
            prev = _tape.set_recording(False)
            try:
                first = not self._first_forward_done
                self(*args)
                if first:
                    # the very first call runs the shape-inference
                    # forward without populating the compile cache —
                    # dispatch again so this bucket is genuinely warm
                    self(*args)
            finally:
                _tape.set_recording(prev)
        return self.compile_count - before

    def infer_shape(self, *args):
        """Reference block.py:1278 — resolve deferred parameter shapes from
        input shapes by abstract evaluation (no FLOPs)."""
        import jax
        leaves, treedef = jax.tree.flatten(
            args, is_leaf=lambda x: isinstance(x, NDArray))

        def run(*raw):
            nds = jax.tree.unflatten(treedef, [NDArray(r) for r in raw])
            prev = _tape.set_recording(False)
            try:
                self.forward(*nds)
            finally:
                _tape.set_recording(prev)
            return 0

        try:
            jax.eval_shape(run, *[x._data for x in leaves])
        except DeferredInitializationError:
            pass

    def register_op_hook(self, callback, monitor_all=False):
        """Reference cached_op.cc:1212 RegisterOpHook — here a whole-graph
        monitor (per-op hooks would defeat XLA fusion)."""
        if self._cached_graph is not None:
            self._cached_graph._monitor_callbacks.append(callback)

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        if all(isinstance(a, NDArray) for a in args) and args:
            self._last_in_specs = [(a.shape, a.dtype) for a in args]
        from .. import _deferred_compute as _dc
        if self._active and self._cached_graph is not None and \
                self._first_forward_done and not _dc.is_deferred_compute() \
                and not is_tracing():
            # is_tracing(): inside a parent's graph capture children inline
            # into the parent executable (reference: CachedOp inline_limit /
            # whole-graph capture) instead of nesting compiled calls
            if kwargs:
                raise ValueError(
                    'keyword arguments are not supported when a HybridBlock '
                    'is hybridized (reference block.py raises the same); '
                    'pass them positionally or call hybridize(False)')
            out = self._cached_graph(args)
        else:
            out = self.forward(*args, **kwargs)
            self._first_forward_done = True
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        if hasattr(self, 'hybrid_forward'):
            # legacy hybrid_forward(F, x, **params) protocol (v1 graph mode)
            from .. import ndarray as F
            pdata = {name: p.data() for name, p in self._reg_params.items()}
            return self.hybrid_forward(F, *args, **pdata)
        raise NotImplementedError(
            f'{type(self).__name__} must implement forward')

    def _trace_symbol(self, *args):
        """Capture the (inference-mode) forward graph as a Symbol via
        deferred compute (≙ _get_graph_v2, reference block.py:959).

        ``args``: example NDArrays (or shape tuples) for the data inputs.
        Parameters become symbol variables named by their structural names,
        so the params file keys match ``symbol.list_arguments()``.
        """
        import jax

        from .. import _deferred_compute as dc

        in_specs = []
        for a in args:
            if isinstance(a, NDArray):
                in_specs.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
            else:
                in_specs.append(jax.ShapeDtypeStruct(tuple(a), _np.float32))
        in_names = ['data'] if len(args) == 1 else \
            [f'data{i}' for i in range(len(args))]

        params = self.collect_params()
        p_items = list(params.items())
        p_specs = [jax.ShapeDtypeStruct(p.shape, _np.dtype(p.dtype))
                   for _, p in p_items]
        n_in = len(in_specs)
        captured = {}
        st = _trace_state()

        def run(*raws):
            saved = []
            prev_rec = _tape.set_recording(False)
            prev_train = _tape.set_training(False)
            prev_aux = st.aux_writes
            st.aux_writes = {}
            try:
                with dc.context():
                    nds = [NDArray(r) for r in raws[:n_in]]
                    dc.set_variable(nds, in_names)
                    for (name, p), r in zip(p_items, raws[n_in:]):
                        nd = NDArray(r)
                        saved.append((p, p._data))
                        p._data = {c: nd for c in p._data}
                        dc.set_variable(nd, name)
                    out = self.forward(*nds)
                    outs = out if isinstance(out, (list, tuple)) else [out]
                    captured['sym'] = dc.get_symbol(list(outs))
                return 0
            finally:
                for p, data in saved:
                    p._data = data
                _tape.set_recording(prev_rec)
                _tape.set_training(prev_train)
                st.aux_writes = prev_aux

        jax.eval_shape(run, *(in_specs + p_specs))
        return captured['sym']

    def export(self, path, epoch=0, remove_amp_cast=True, input_shapes=None):
        """Reference block.py:1299 — serialize graph + params for
        deployment.

        Emits ``{path}-symbol.json`` (the role of model-symbol.json; loads
        back via :meth:`SymbolBlock.imports`) and
        ``{path}-{epoch:04d}.params.npz``. Input shapes come from the first
        compiled-cache entry, or pass ``input_shapes=[(...), ...]``.
        """
        from ..model import save_ndarray_map
        params = self.collect_params()
        if input_shapes is None:
            specs = getattr(self, '_last_in_specs', None)
            if not specs:
                raise ValueError(
                    'export() needs input shapes: run a forward first, or '
                    'pass input_shapes=[...] (the reference has the same '
                    'run-before-export requirement, block.py:1299)')
            import jax
            args = [NDArray(jax.ShapeDtypeStruct(s, d)) for s, d in specs]
        else:
            args = list(input_shapes)
        param_path = f'{path}-{epoch:04d}.params.npz'
        sym = self._trace_symbol(*args)
        if not any(n.op == '_opaque' for n in sym._topo()):
            # hoisted constant buffers ride the params file beside weights
            data = dict({k: v.data() for k, v in params.items()},
                        **sym._aux)
            save_ndarray_map(param_path, data)
            sym.save(f'{path}-symbol.json')
            return f'{path}-symbol.json', param_path
        save_ndarray_map(param_path,
                         {k: v.data() for k, v in params.items()})
        # closure-dispatched layers (fused RNN etc.) can't serialize to
        # JSON — export the compiled graph as portable StableHLO instead
        return self._export_stablehlo(path, args), param_path

    def _export_stablehlo(self, path, args):
        """Portable serialized executable via jax.export (the deployment
        fallback for graphs containing closure-based ops)."""
        import jax
        from jax import export as jexport

        items = list(self.collect_params().items())
        st = _trace_state()

        def fn(in_raws, p_raws):
            saved = []
            prev_rec = _tape.set_recording(False)
            prev_train = _tape.set_training(False)
            prev_aux = st.aux_writes
            st.aux_writes = {}
            try:
                for (_, p), r in zip(items, p_raws):
                    saved.append((p, p._data))
                    p._data = {c: NDArray(r) for c in p._data}
                out = self.forward(*[NDArray(r) for r in in_raws])
                leaves, _ = jax.tree.flatten(
                    out, is_leaf=lambda x: isinstance(x, NDArray))
                return tuple(o._data if isinstance(o, NDArray) else o
                             for o in leaves)
            finally:
                for p, d in saved:
                    p._data = d
                _tape.set_recording(prev_rec)
                _tape.set_training(prev_train)
                st.aux_writes = prev_aux

        in_specs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in args)
        p_specs = tuple(jax.ShapeDtypeStruct(p.shape, _np.dtype(p.dtype))
                        for _, p in items)
        exp = jexport.export(jax.jit(fn))(in_specs, p_specs)
        out_path = f'{path}-symbol.stablehlo'
        with open(out_path, 'wb') as f:
            f.write(exp.serialize())
        return out_path


class SymbolBlock(HybridBlock):
    """Run a Symbol graph as a Block (reference block.py:1485).

    Every non-input variable of the symbol becomes a :class:`Parameter`
    (loaded from the params file or initialized), and ``forward`` replays
    the graph through the op registry — so autograd and re-hybridization
    both work on imported models.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__()
        from ..symbol.symbol import Group, Symbol
        if not isinstance(outputs, Symbol):
            outputs = Group(list(outputs))
        self._sym = outputs
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._input_names = [i if isinstance(i, str) else i.name
                             for i in inputs]
        shape_attrs = {n.name: (n.attrs.get('__shape__'),
                                n.attrs.get('__dtype__', 'float32'))
                       for n in self._sym._topo() if n.op == 'null'}
        self._sym_param_names = [n for n in self._sym.list_arguments()
                                 if n not in self._input_names]
        # hoisted constant buffers captured on an in-memory symbol load as
        # (non-trainable) parameters alongside any explicitly passed params
        params = dict(outputs._aux, **(params or {}))
        for name in self._sym_param_names:
            shape, dtype = shape_attrs.get(name, (None, 'float32'))
            p = Parameter(name, shape=shape, dtype=dtype,
                          allow_deferred_init=True)
            if name in params:
                v = params[name]
                if not isinstance(v, NDArray):
                    v = array(v)
                p.dtype = str(v.dtype)
                p.set_data(v)
            self._reg_params[name] = p

    @staticmethod
    def imports(symbol_file, input_names='data', param_file=None, ctx=None):
        """Load an exported model (reference block.py SymbolBlock.imports)."""
        from ..model import load_ndarray_map
        from ..symbol import load as sym_load
        sym = sym_load(symbol_file)
        params = load_ndarray_map(param_file) if param_file else {}
        if ctx is not None:
            params = {k: v.as_in_context(ctx) for k, v in params.items()}
        if isinstance(input_names, str):
            input_names = [input_names]
        return SymbolBlock(sym, list(input_names), params=params)

    def forward(self, *args):
        bindings = {}
        for name, a in zip(self._input_names, args):
            bindings[name] = a if isinstance(a, NDArray) else array(a)
        for name in self._sym_param_names:
            bindings[name] = self._reg_params[name].data()
        outs = self._sym._execute(bindings)
        return outs[0] if len(outs) == 1 else tuple(outs)
