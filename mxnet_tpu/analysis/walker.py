"""Jaxpr tracing + traversal for the graph sanitizer.

The sanitizer operates on the exact artifact ``hybridize`` compiles: the
pure function ``pure_fn(rng_key, inputs, params, aux)`` that
``_CachedGraph`` hands to ``jax.jit`` (gluon/block.py). Tracing it with
``jax.make_jaxpr`` yields the same jaxpr XLA would receive, with three
properties the rules depend on:

* parameters arrive as *arguments* (swapped into the Block during the
  trace), so anything that shows up in ``jaxpr.consts`` is a genuinely
  closure-captured buffer — the large-constant rule reads that directly;
* every traced input has a stable flat position, so findings can name
  the offending argument (``param:features.0.weight``, ``input[1]``);
* the donation audit can re-lower the identical function with the
  donation the block would request and compare XLA's recorded
  input-output aliasing against the claim.

``iter_eqns`` walks nested sub-jaxprs (pjit/scan/cond/remat bodies) so
rules see through ``jax.checkpoint`` and control-flow wrappers.
"""

import numpy as _np

import jax
from jax.extend import core as _core

from ..context import current_context

LOW_PRECISION_DTYPES = ('bfloat16', 'float16')


class ArgInfo:
    """One flat traced input of the linted graph."""

    __slots__ = ('index', 'label', 'kind', 'aval')

    def __init__(self, index, label, kind, aval):
        self.index = index        # position in jaxpr.invars
        self.label = label        # e.g. 'param:features.0.weight'
        self.kind = kind          # 'rng' | 'input' | 'param' | 'aux'
        self.aval = aval

    def __repr__(self):
        return f'<{self.kind} {self.label}: {self.aval}>'


class GraphView:
    """A traced graph plus the argument/const metadata rules consume."""

    def __init__(self, closed_jaxpr, args, out_kinds, name,
                 source='function', block=None, static_alloc=False,
                 donate_groups=(), lower_fn=None, notes=None,
                 suppressions=None, sharding=None):
        self.closed = closed_jaxpr
        self.jaxpr = closed_jaxpr.jaxpr
        self.consts = list(closed_jaxpr.consts)
        self.args = args                    # list[ArgInfo], == invars order
        self.out_kinds = out_kinds          # 'output' | 'aux' per outvar
        self.name = name
        self.source = source                # 'block' | 'function'
        self.block = block
        self.static_alloc = static_alloc
        # argnum-group names the block would donate ('aux', 'inputs')
        self.donate_groups = tuple(donate_groups)
        # lower_fn(donate_argnums) -> jax.stages.Lowered over the same
        # avals; None when the caller didn't supply a compilable form
        self.lower_fn = lower_fn
        self.notes = list(notes or [])
        # rule -> justification, collected from `_analysis_suppressions`
        # dicts on the block tree (docs/static-analysis.md "Suppressing
        # a finding"): a justified suppression downgrades that rule's
        # findings to info instead of dropping them — the report still
        # shows the pattern exists and why it is accepted.
        self.suppressions = dict(suppressions or {})
        # non-None when traced under an active mx.sharding context:
        # {'axes', 'mode', 'n_devices', 'data_axis', 'specs' (per arg
        # label), 'factors' (per arg label, = #shards of that buffer)}.
        # The cost model divides per-device traffic by these factors and
        # the recompile rule reads it to state the mesh-key non-hazard.
        self.sharding = sharding

    # ---------------------------------------------------------------- helpers
    @property
    def low_precision(self):
        """True when the graph computes in bf16/f16 (AMP or cast net):
        any non-rng input arrives in a low-precision dtype."""
        from .. import amp
        if amp.is_enabled():
            return True
        return any(str(a.aval.dtype) in LOW_PRECISION_DTYPES
                   for a in self.args if a.kind != 'rng')

    def args_of_kind(self, *kinds):
        return [a for a in self.args if a.kind in kinds]

    def arg_for_invar(self, var):
        try:
            return self.args[self.jaxpr.invars.index(var)]
        except ValueError:
            return None

    def flat_indices(self, kind):
        return [a.index for a in self.args if a.kind == kind]

    def stats(self):
        n_eqns = sum(1 for _ in iter_eqns(self.jaxpr))
        return {
            'eqns': n_eqns,
            'inputs': len(self.flat_indices('input')),
            'params': len(self.flat_indices('param')),
            'aux': len(self.flat_indices('aux')),
            'consts': len(self.consts),
            'const_bytes': sum(_const_nbytes(c) for c in self.consts),
        }


def _const_nbytes(c):
    nb = getattr(c, 'nbytes', None)
    if nb is not None:
        return int(nb)
    return int(_np.asarray(c).nbytes)


def source_location(eqn):
    """'file:line' of the deepest user frame that emitted this eqn."""
    try:
        from jax._src import source_info_util
        frames = list(source_info_util.user_frames(eqn.source_info))
        if frames:
            f = frames[0]
            return f'{f.file_name}:{f.start_line}'
    except Exception:
        pass
    return None


# --------------------------------------------------------------------- lookup
_OP_CODE_INDEX = None


def _op_code_index():
    """(co_filename, co_name) -> Op for every registered operator body,
    so eqn source-info frames can be attributed to the op that emitted
    them (the per-op metadata hook: Op.host_transfer / Op.f32_only)."""
    global _OP_CODE_INDEX
    if _OP_CODE_INDEX is None:
        from ..ops import registry
        idx = {}
        for name, op in registry.list_ops().items():
            code = getattr(op.fn, '__code__', None)
            if code is not None:
                idx[(code.co_filename, code.co_name)] = op
        _OP_CODE_INDEX = idx
    return _OP_CODE_INDEX


def eqn_op(eqn):
    """The registered Op whose body emitted this eqn, or None."""
    idx = _op_code_index()
    try:
        frames = eqn.source_info.traceback.frames
    except Exception:
        return None
    for f in frames:
        op = idx.get((f.file_name, f.function_name))
        if op is not None:
            return op
    return None


# ------------------------------------------------------------------ traversal
def _sub_jaxprs(eqn):
    """Sub-jaxprs carried in an eqn's params (pjit, scan, cond, remat,
    custom_jvp/vjp call bodies...)."""
    for v in eqn.params.values():
        if isinstance(v, _core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, _core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for e in v:
                if isinstance(e, _core.ClosedJaxpr):
                    yield e.jaxpr
                elif isinstance(e, _core.Jaxpr):
                    yield e


def iter_eqns(jaxpr, _depth=0):
    """Yield (eqn, depth) over this jaxpr and every nested sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn, _depth
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, _depth + 1)


def iter_jaxprs(jaxpr):
    """Yield every (sub)jaxpr, outermost first — for rules that need
    per-level def/use analysis."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            yield from iter_jaxprs(sub)


# -------------------------------------------------------------------- tracing
def _example_key():
    return jax.random.PRNGKey(0)


def collect_suppressions(block):
    """Gather ``_analysis_suppressions`` ({rule: justification}) from a
    block and all its children. A child's entry wins over the parent's
    only if the parent did not set one — outer blocks own the policy."""
    out = {}
    stack = [block]
    while stack:
        b = stack.pop()
        for rule, why in getattr(b, '_analysis_suppressions', {}).items():
            out.setdefault(rule, why)
        stack.extend(getattr(b, '_children', {}).values())
    return out


def trace_block(block, *example_args, train=False, name=None):
    """Trace a (Hybrid)Block's forward to a GraphView — the same capture
    ``hybridize`` performs, shapes taken from ``example_args`` (NDArrays,
    jax arrays, numpy arrays, or shape tuples)."""
    from ..gluon.block import HybridBlock, _CachedGraph
    from ..ndarray.ndarray import NDArray

    if not isinstance(block, HybridBlock):
        raise TypeError(
            f'analysis.lint needs a HybridBlock or callable, got '
            f'{type(block).__name__} (plain Blocks have no traceable '
            'graph — the reference has the same hybridize constraint)')

    args = []
    for a in example_args:
        if isinstance(a, NDArray):
            args.append(a)
        elif isinstance(a, (tuple, list)) and all(
                isinstance(d, int) for d in a):
            args.append(NDArray(jax.ShapeDtypeStruct(tuple(a),
                                                     _np.float32)))
        else:
            from ..ndarray.ndarray import array
            args.append(array(a))

    if not block._initialized_once():
        block.initialize(ctx=current_context())
    # resolve + materialize deferred-shape parameters without FLOPs, so
    # they trace as arguments below (never as closure constants)
    block.infer_shape(*args)

    graph = block._cached_graph
    static_alloc = graph.static_alloc if isinstance(graph, _CachedGraph) \
        else True
    donate_inputs = bool(getattr(graph, 'donate_inputs', False))
    temp = graph if isinstance(graph, _CachedGraph) else \
        _CachedGraph(block, static_alloc=static_alloc)
    main, aux = temp._params()

    notes = []

    def _initialized(p):
        try:
            p.data()
            return True
        except Exception:
            return False

    deferred = [p.name for p in list(main) + list(aux)
                if not _initialized(p)]
    if deferred:
        # a layer that forward() never calls keeps its deferred-shape
        # params uninitialized forever — infer_shape cannot see it.
        # Trace without them (on a scratch graph so the block's real
        # cache keeps the full order) and let the dead-code rule report.
        if temp is graph:
            temp = _CachedGraph(block, static_alloc=static_alloc)
        main = [p for p in main if _initialized(p)]
        aux = [p for p in aux if _initialized(p)]
        temp._param_order = (main, aux)
        notes.append('deferred-params:' + ','.join(deferred))

    treedef = jax.tree.structure(
        tuple(args), is_leaf=lambda x: isinstance(x, NDArray))

    # sharding-aware trace: under an active mx.sharding context lint the
    # program the context would actually compile — the same injected
    # with_sharding_constraint boundaries (_make_pure ctx arg) and
    # params/aux avals carrying their rule-resolved NamedShardings, so
    # lower_fn produces a genuinely sharded lowering for the donation
    # audit and the cost model can report per-device numbers.
    from .. import sharding as _shd
    ctx = _shd.current()
    sharding_meta = None
    aux_specs = None
    if ctx is not None:
        from jax.sharding import NamedSharding
        rules = ctx.rules_for_block(block)
        specs, factors = {}, {}

        def _note(label, spec, shape):
            specs[label] = tuple(spec)
            factors[label] = _shd.shard_factor(spec, shape, ctx.mesh)

        in_specs = []
        for i, a in enumerate(args):
            spec = ctx.batch_spec(a.shape)
            in_specs.append(spec)
            _note(f'input[{i}]', spec, a.shape)
        # block-relative names resolved fresh — a child-level
        # collect_params() (infer_shape above traces child cached
        # graphs) re-stamps _structure_name child-relative
        fresh = {id(p): k for k, p in block.collect_params().items()}
        main_specs, aux_param_specs = [], []
        for p in main:
            name = fresh.get(id(p)) or p.name
            spec = ctx.spec_for(name, p.data().shape, rules)
            main_specs.append(spec)
            _note(f'param:{name}', spec, p.data().shape)
        for p in aux:
            name = fresh.get(id(p)) or p.name
            spec = ctx.spec_for(name, p.data().shape, rules)
            aux_param_specs.append(spec)
            _note(f'aux:{name}', spec, p.data().shape)
        aux_specs = tuple(aux_param_specs)
        sharding_meta = {
            'axes': dict(ctx.axis_sizes),
            'mode': ctx.mode,
            'n_devices': ctx.n_devices,
            'data_axis': ctx.data_axis,
            'specs': specs,
            'factors': factors,
        }
        notes.append('traced under mx.sharding mesh '
                     + 'x'.join(f'{k}={v}'
                                for k, v in ctx.axis_sizes.items()))

        def _sds(shape, dtype, spec):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(ctx.mesh, spec))
    else:
        def _sds(shape, dtype, spec):
            return jax.ShapeDtypeStruct(shape, dtype)
        in_specs = [None] * len(args)
        main_specs = [None] * len(main)
        aux_param_specs = [None] * len(aux)

    pure_fn = temp._make_pure(('analysis',), train, treedef, ctx=ctx,
                              aux_specs=aux_specs)

    key = _example_key()
    in_sds = tuple(_sds(a.shape, a.dtype, s)
                   for a, s in zip(args, in_specs))
    main_sds = tuple(_sds(p.data().shape, p.data().dtype, s)
                     for p, s in zip(main, main_specs))
    aux_sds = tuple(_sds(p.data().shape, p.data().dtype, s)
                    for p, s in zip(aux, aux_param_specs))

    closed, out_shapes = jax.make_jaxpr(pure_fn, return_shape=True)(
        key, in_sds, main_sds, aux_sds)

    args_meta = _label_args(closed, key, in_sds, main_sds, aux_sds,
                            [p.name for p in main], [p.name for p in aux])
    out_kinds = _label_outs(out_shapes)

    donate_groups = []
    if static_alloc and train:
        # the runtime donates aux only on recorded-train executables;
        # inference entries run lock-free over shared buffers and must
        # not donate (gluon/block.py thread-safety contract)
        donate_groups.append('aux')
    if donate_inputs and not train:
        # runtime excludes input donation while recording (activations
        # are backward residuals); train=True lint models that entry
        donate_groups.append('inputs')

    def lower_fn(donate_argnums=()):
        # keep_unused: HLO entry params must stay 1:1 with the flat
        # invars or the alias table's param indices would shift (jit
        # DCEs an unused rng arg otherwise)
        return jax.jit(pure_fn, donate_argnums=donate_argnums,
                       keep_unused=True).lower(
            key, in_sds, main_sds, aux_sds)

    if isinstance(graph, _CachedGraph) and graph._dynamic:
        notes.append('block fell back to eager op-by-op execution '
                     '(data-dependent shapes)')

    return GraphView(closed, args_meta, out_kinds,
                     name or type(block).__name__, source='block',
                     block=block, static_alloc=static_alloc,
                     donate_groups=donate_groups, lower_fn=lower_fn,
                     notes=notes,
                     suppressions=collect_suppressions(block),
                     sharding=sharding_meta)


def _label_args(closed, key, in_sds, main_sds, aux_sds, main_names,
                aux_names):
    """Flat ArgInfo list aligned with jaxpr.invars: the pytree flatten
    order of (key, inputs, params, aux)."""
    flat = []
    key_leaves = jax.tree.leaves(key)
    for _ in key_leaves:
        flat.append(('rng', 'rng'))
    for i, sds in enumerate(jax.tree.leaves(in_sds)):
        flat.append((f'input[{i}]', 'input'))
    for name, sds in zip(main_names, main_sds):
        flat.append((f'param:{name}', 'param'))
    for name, sds in zip(aux_names, aux_sds):
        flat.append((f'aux:{name}', 'aux'))
    invars = closed.jaxpr.invars
    if len(flat) != len(invars):
        # nested pytree inputs flatten to more leaves than len(in_sds);
        # recover by re-flattening the full example
        flat_all = jax.tree.leaves((key, in_sds, main_sds, aux_sds))
        n_key = len(key_leaves)
        n_main = len(main_names)
        n_aux = len(aux_names)
        n_in = len(flat_all) - n_key - n_main - n_aux
        flat = ([('rng', 'rng')] * n_key
                + [(f'input[{i}]', 'input') for i in range(n_in)]
                + [(f'param:{n}', 'param') for n in main_names]
                + [(f'aux:{n}', 'aux') for n in aux_names])
    return [ArgInfo(i, lbl, kind, v.aval)
            for i, ((lbl, kind), v) in enumerate(zip(flat, invars))]


def _label_outs(out_shapes):
    """pure_fn returns (outputs_tuple, aux_tuple): label each flat
    outvar so rules exempt the aux write-backs from output checks."""
    outs, auxs = out_shapes
    return (['output'] * len(jax.tree.leaves(outs))
            + ['aux'] * len(jax.tree.leaves(auxs)))


def trace_function(fn, *example_args, name=None):
    """Trace a raw step function (over NDArrays or jax/numpy arrays) to
    a GraphView. All leaves are 'input' args; there is no param/aux
    split, so the donation audit treats every input as donatable."""
    from ..ndarray.ndarray import NDArray

    import jax.numpy as _jnp

    leaves, treedef = jax.tree.flatten(
        example_args, is_leaf=lambda x: isinstance(x, NDArray))
    # leaves the fn sees as NDArrays: everything except raw jax
    # arrays/ShapeDtypeStructs (a caller passing those is working at
    # the jax level and gets tracers back). Python scalars and numpy
    # arrays are mx-style args — NDArray arithmetic must work on them.
    wrap_nd = [not isinstance(x, (jax.Array, jax.ShapeDtypeStruct))
               for x in leaves]
    sds = []
    for x in leaves:
        if isinstance(x, NDArray):
            sds.append(jax.ShapeDtypeStruct(x.shape, x.dtype))
        elif isinstance(x, jax.ShapeDtypeStruct):
            sds.append(x)
        else:
            # concrete jnp value, not an SDS: preserves weak_type for
            # Python scalars so the recompile-hazard rule sees exactly
            # what jit would cache on
            sds.append(_jnp.asarray(x))

    def wrapped(*raws):
        rebuilt = [NDArray(r) if nd else r for r, nd in zip(raws, wrap_nd)]
        out = fn(*jax.tree.unflatten(treedef, rebuilt))
        out_leaves, _ = jax.tree.flatten(
            out, is_leaf=lambda x: isinstance(x, NDArray))
        return tuple(o._data if isinstance(o, NDArray) else o
                     for o in out_leaves)

    closed, out_shapes = jax.make_jaxpr(wrapped, return_shape=True)(*sds)
    args_meta = [ArgInfo(i, f'input[{i}]', 'input', v.aval)
                 for i, v in enumerate(closed.jaxpr.invars)]
    out_kinds = ['output'] * len(jax.tree.leaves(out_shapes))

    def lower_fn(donate_argnums=()):
        return jax.jit(wrapped, donate_argnums=donate_argnums,
                       keep_unused=True).lower(*sds)

    return GraphView(closed, args_meta, out_kinds,
                     name or getattr(fn, '__name__', '<fn>'),
                     source='function', lower_fn=lower_fn)
