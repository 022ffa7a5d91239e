"""``gluon.Trainer`` — bridges Parameters ↔ KVStore ↔ Optimizer.

Reference: ``python/mxnet/gluon/trainer.py`` (_init_kvstore:188, step:334,
_allreduce_grads:385, _update:444, save_states:482). Semantics preserved:
``step(batch_size)`` = gradient aggregation (kvstore pushpull across device
replicas / hosts) + per-parameter optimizer update. On TPU the per-key
priority scheduling (priority=-i for comm/compute overlap) is a no-op —
XLA's async collectives already overlap — but the argument is accepted.
"""

import collections
import logging

import jax

from ..kvstore import create as _create_kvstore
from ..kvstore.base import KVStoreBase
from .. import optimizer as opt
from .parameter import Parameter
from ..ndarray.ndarray import NDArray
from ..telemetry import trace as _trace


class _FusedUnsupported(Exception):
    """Optimizer could not be traced into the fused update executable."""


# what an optimizer raises when it has no traceable ``step``: the base
# class's stub, or host control flow on a traced lr/wd/t. Anything else
# (a kernel the compiler refuses, a sharding error) is a fault and
# propagates
_UNTRACEABLE = (NotImplementedError, jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError)


_FUSED_SENTINEL = object()


def _zero_hypers(n):
    """Stand-ins for the fused update's lr, wd and step-count vectors,
    for tracing and lowering it without a step."""
    import jax.numpy as jnp
    return (jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32),
            jnp.zeros(n, jnp.int32))


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore
                 ='device', compression_params=None, update_on_kvstore=None):
        if isinstance(params, (dict,)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError('params must be a dict/list of Parameters')
        self._params = []
        # keyed by id(param): structural names are re-derived by
        # collect_params() calls and can change under the trainer
        self._param2idx = {}
        for i, param in enumerate(params):
            if not isinstance(param, Parameter):
                raise ValueError(f'invalid parameter {param}')
            self._param2idx[id(param)] = i
            self._params.append(param)
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_params = {
            'kvstore': kvstore, 'update_on_kvstore': update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = []
        self._reset_kvstore()

    # ----------------------------------------------------------------- setup
    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx() if param._data is not None or \
                param._deferred_init is not None else None
            if ctx is None:
                continue
            assert contexts is None or contexts == ctx, (
                f'All Parameters must be initialized on the same set of '
                f'contexts, but Parameter {param.name} is on {ctx} while '
                f'previous ones are on {contexts}.')
            contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                'optimizer_params must be None if optimizer is an instance'
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._states = {}
        self._fused_cache = {}
        self._fused_fallback_taken = False

    def _reset_kvstore(self):
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._params_to_init = list(self._params)

    def _init_kvstore(self):
        """Reference trainer.py:188 — decides kvstore type +
        update_on_kvstore. Here: multi-worker → dist_tpu_sync allreduce
        (never server-side updates: there are no servers).

        As in the reference's ``_create_kvstore``, one context under a
        store name without ``dist`` in it gets no kvstore: there is
        nothing to aggregate, and a local store would keep a copy of
        every weight that nothing reads. ``compression_params`` then
        compress nothing, because nothing is exchanged. A ``KVStoreBase``
        instance, a ``dist*`` name, several contexts or
        ``update_on_kvstore=True`` get their store."""
        config = self._kvstore_params
        kv = config['kvstore']
        one_device = (isinstance(kv, str) and 'dist' not in kv
                      and len(self._contexts) == 1
                      and config['update_on_kvstore'] is not True)
        if kv is None or kv == '' or not self._contexts or one_device:
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            self._kvstore = kv if isinstance(kv, KVStoreBase) else \
                _create_kvstore(kv)
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            self._update_on_kvstore = bool(config['update_on_kvstore']) \
                if config['update_on_kvstore'] is not None else False
            if self._update_on_kvstore:
                if any(p._grad_stype == 'row_sparse' for p in self._params):
                    import warnings
                    warnings.warn(
                        'update_on_kvstore=True densifies row_sparse '
                        'gradients: lazy row-wise update semantics '
                        '(no wd/momentum on untouched rows) are lost. '
                        'Use update_on_kvstore=False to keep the sparse '
                        'path.', UserWarning, stacklevel=3)
                self._kvstore.set_optimizer(self._optimizer)
        self._kv_initialized = True

    def _init_params(self):
        """Broadcast initial params across workers (reference
        trainer.py:_init_params)."""
        params_to_init = []
        for param in self._params_to_init:
            if param._deferred_init is not None and param._data is None:
                params_to_init.append(param)
            elif self._kvstore is not None and param._data is not None:
                idx = self._param2idx[id(param)]
                vals = param.list_data()
                self._kvstore.broadcast(idx, vals[0], vals)
        self._params_to_init = params_to_init

    # ------------------------------------------------------------ properties
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # ------------------------------------------------------------------ step
    def step(self, batch_size, ignore_stale_grad=False):
        """Reference trainer.py:334."""
        # what is left of this span after place, hyper and launch is the
        # update's own host time: the loops over the parameters
        with _trace.child_span('mx.trainer.step') as span:
            if span.live:
                span.set(n_params=len(self._params))
            rescale_grad = self._scale / batch_size
            self._check_and_rescale_grad(rescale_grad)
            if not self._kv_initialized:
                self._init_kvstore()
            if self._params_to_init:
                self._init_params()
            self._allreduce_grads()
            self._update(ignore_stale_grad)

    def _check_and_rescale_grad(self, scale):
        if self._update_on_kvstore and self._kv_initialized and \
                self._kvstore is not None:
            if self._optimizer.rescale_grad != scale:
                raise UserWarning(
                    'Possible change in the `batch_size` from previous '
                    '`step` detected.')
        self._optimizer.rescale_grad = scale

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        self._allreduce_grads()

    def _allreduce_grads(self):
        """Reference trainer.py:385 — pushpull with priority −i.

        All dense params go through ONE ``fused_pushpull`` call: the
        kvstore coalesces them into fusion buffers and issues a handful
        of async collectives in priority order (the comm/compute overlap
        the reference's per-key priority machinery bought), instead of
        hundreds of per-key dispatches."""
        if self._kvstore is None:
            return
        entries = []
        for i, param in enumerate(self._params):
            if param.grad_req == 'null':
                continue
            if param._grad_stype == 'row_sparse':
                # keep row-sparse grads out of the dense allreduce: the
                # kvstore merge would densify the O(table) gradient —
                # exactly what the sparse path exists to avoid. The
                # local lazy update handles them (reference: sparse
                # params take the push/row_sparse_pull route).
                if getattr(self._kvstore, 'num_workers', 1) > 1 and \
                        not getattr(self, '_warned_sparse_dist', False):
                    import warnings
                    warnings.warn(
                        'row_sparse gradients are applied rank-locally '
                        'under a distributed kvstore (no sparse '
                        'allreduce); replicate embeddings or use '
                        'dist_async for server-side sparse updates.',
                        UserWarning)
                    self._warned_sparse_dist = True
                continue
            grads = param.list_grad()
            if grads:
                entries.append((i, param, grads))
        if not entries:
            return
        if hasattr(self._kvstore, 'fused_pushpull'):
            self._kvstore.fused_pushpull(
                [i for i, _, _ in entries],
                [g for _, _, g in entries],
                outs=[p.list_data() for _, p, _ in entries]
                if self._update_on_kvstore else None,
                priorities=[-i for i, _, _ in entries])
            return
        for i, param, grads in entries:
            if self._update_on_kvstore:
                # server-side update: fresh weights land in the param
                # arrays directly (reference trainer.py:385 out=data)
                self._kvstore.pushpull(i, grads, out=param.list_data(),
                                       priority=-i)
            else:
                self._kvstore.pushpull(i, grads, priority=-i)

    def _update(self, ignore_stale_grad=False):
        """Reference trainer.py:444 — run optimizer per device replica.

        All parameter updates execute as ONE jitted call (the role of the
        reference's fused multi-tensor kernels, optimizer_op.cc
        multi_sgd/preloaded_multi_*): per-param eager dispatch of hundreds
        of tiny update ops would dominate step time on TPU. Falls back to
        the per-param loop if fused tracing fails for a custom optimizer.

        The jitted call donates the weights and slots it replaces
        (:meth:`_fused_program`): after it the arrays they held are
        deleted, and a handle that shared one (``detach()``, a view, a
        retained autograd graph) raises on use. The per-param loop, the
        row-sparse path and ``update_on_kvstore`` rebind fresh arrays
        and delete nothing.
        """
        if self._update_on_kvstore:
            return  # server-side update already applied by pushpull
        if getattr(self, '_amp_skip_update', False):
            # amp.unscale detected a gradient overflow: skip this update
            # entirely (no wd/momentum mutation on zeroed grads)
            self._amp_skip_update = False
            return
        live, sparse_live = self._live_params()
        if sparse_live:
            from ..ndarray import sparse as _sp
            opt = self._optimizer
            wants_rows = getattr(opt, 'lazy_update', False) or \
                opt._sparse_rowwise
            for i, param in sparse_live:
                # row_sparse grads (Embedding(sparse_grad=True)) take the
                # per-param sparse path: the optimizer updates only the
                # rows present in the gradient (reference sgd lazy_update
                # / sparse.adagrad_update). The dense tape grad is
                # compressed here — the nnz discovery is the cast_storage
                # step the reference runs inside the sparse backward
                # kernel. A non-lazy optimizer would densify right back,
                # so only compress when the row-wise path will be taken.
                datas = param.list_data()
                g = param.list_grad()[0]
                if wants_rows and not isinstance(g, _sp.BaseSparseNDArray):
                    g = _sp.row_sparse_array(g)
                self._optimizer.update_multi_precision(
                    i, datas[0], g, self._states[i])
                for d in datas[1:]:
                    d._rebind(datas[0]._data)
        if not live:
            return
        try:
            self._fused_update(live)
        except _FusedUnsupported as e:
            if not self._fused_fallback_taken:
                self._fused_fallback_taken = True
                logging.getLogger(__name__).warning(
                    '%r does not trace into one fused update (%s); '
                    'updating parameter by parameter', self._optimizer, e)
            for i, param in live:
                datas = param.list_data()
                grads = param.list_grad()
                self._optimizer.update_multi_precision(
                    i, datas[0], grads[0], self._states[i])
                for d in datas[1:]:
                    d._rebind(datas[0]._data)
                self._restore_placement(param)

    def _live_params(self):
        """``(dense, row_sparse)``: the ``(index, parameter)`` pairs an
        update takes, by the storage of their gradients, each with its
        optimizer state created."""
        live = []
        sparse_live = []
        for i, param in enumerate(self._params):
            if param.grad_req == 'null' or param._data is None:
                continue
            if i not in self._states:
                self._states[i] = self._zero1_place(
                    param, self._optimizer.create_state_multi_precision(
                        i, param.data()))
            if param._grad_stype == 'row_sparse':
                sparse_live.append((i, param))
            else:
                live.append((i, param))
        return live, sparse_live

    # ------------------------------------------------------- sharded slots
    def _zero1_place(self, param, state):
        """Place freshly created optimizer slots on the active
        ``mx.sharding`` mesh: the parameter's own layout plus the data
        axis on the first still-replicated divisible dim (ZeRO-1 — the
        GSPMD expression of kvstore/tpu.py ``_zero1_update``'s owner
        plan, where each data-parallel rank holds and updates only its
        slice of the slots). No-op outside a sharding context."""
        from .. import sharding as _sharding
        ctx = _sharding.current()
        if ctx is None:
            return state
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        pspec = getattr(param, '_sharding_spec', None)
        if pspec is None or getattr(param, '_sharding_mesh', None) \
                != ctx.mesh:
            # param never compiled under this mesh: treat as replicated
            pspec = P()

        def place(nd):
            if not isinstance(nd, NDArray) or nd.shape is None:
                return nd
            spec = ctx.zero1_spec(pspec, nd.shape) \
                if nd.shape == param.shape else P()
            nd._rebind(jax.device_put(
                nd._data, NamedSharding(ctx.mesh, spec)))
            return nd

        if isinstance(state, NDArray):
            return place(state)
        if isinstance(state, (list, tuple)):
            return type(state)(place(e) for e in state)
        return state

    def _mesh_place(self, live, ctx):
        """Commit every fused-update operand to the active mesh.

        The operands can arrive on mixed committed device sets: the
        first-ever forward runs eagerly for shape inference and leaves
        params/grads on one device while ``_zero1_place`` already
        committed the fresh slots to the mesh — and conversely a
        trainer warmed outside the context carries single-device slots
        next to mesh-sharded params. jax rejects mixed committed sets
        in one jitted call, so lift stragglers to the param's recorded
        layout (replicated when the graph has not compiled under this
        mesh yet) and rebind in place; the next sharded compile
        re-places params per the rules regardless."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def on_mesh(raw):
            sh = getattr(raw, 'sharding', None)
            return sh is not None and \
                len(sh.device_set) == ctx.n_devices

        for i, p in live:
            sp = getattr(p, '_sharding_spec', None)
            if sp is None or getattr(p, '_sharding_mesh', None) \
                    != ctx.mesh:
                sp = P()
            sh = NamedSharding(ctx.mesh, sp)
            for nd in (p.list_data()[0], p.list_grad()[0]):
                if not on_mesh(nd._data):
                    nd._rebind(jax.device_put(nd._data, sh))
            st = self._states.get(i)
            leaves = [st] if isinstance(st, NDArray) else \
                [e for e in (st or ()) if isinstance(e, NDArray)]
            for e in leaves:
                if not on_mesh(e._data) and e.shape is not None:
                    spec = ctx.zero1_spec(sp, e.shape) \
                        if e.shape == p.shape else P()
                    e._rebind(jax.device_put(
                        e._data, NamedSharding(ctx.mesh, spec)))

    def _restore_placement(self, param):
        """Eager-update fallback: put the rebound weight back on its
        recorded mesh layout (the fused path constrains this inside the
        jitted update instead)."""
        from .. import sharding as _sharding
        ctx = _sharding.current()
        sp = getattr(param, '_sharding_spec', None)
        if ctx is None or sp is None or \
                getattr(param, '_sharding_mesh', None) != ctx.mesh:
            return
        import jax
        from jax.sharding import NamedSharding
        sh = NamedSharding(ctx.mesh, sp)
        for nd in param.list_data():
            if nd._data.sharding != sh:
                nd._rebind(jax.device_put(nd._data, sh))

    # -------------------------------------------------------- fused update
    def _fused_program(self, live):
        """The jitted update for ``live`` and its operands, as the next
        launch takes them: ``(fn, donated, kept, graws)``.

        ``fn(donated, kept, graws, lrs, wds, ts)`` donates its first
        argument, ``(weights, slots)``: XLA aliases each new weight and
        slot onto the buffer it replaces, and the arrays the parameters
        and states held before the step are deleted by it. ``kept`` is
        ``None``, or the same two lists holding the leaves that are
        passed undonated, each ``None`` in the one where it is set in
        the other. Those are what the code can see would not alias, or
        must not be donated:

        * a buffer that appears twice among the operands (two
          Parameters on one array, ``create_state`` handing back the
          weight, a view of it): XLA refuses a buffer that is donated
          and used again in one call, so no occurrence is donated;
        * a leaf whose output has another shape, dtype or layout than
          it has (a weight whose recorded mesh layout differs from the
          one it arrives in): it would cost a copy and a warning.

        Gradients, hyperparameters and step counts are never donated.
        This is the one donating call. The parameter-by-parameter
        fallback, ``update_on_kvstore`` and the row-sparse path rebind
        fresh arrays and delete nothing. ``PipelineTrainer`` and the
        contrib ``Estimator`` step a ``gluon.Trainer`` of their own and
        so come through here; both read the parameters anew each step
        and hold no array across one."""
        import jax
        from .. import _bulk, _tape

        opt = self._optimizer
        # a write sync point, like backward() and a hybridized call: a
        # pending bulk segment holds the raw arrays of its concrete
        # inputs and launches with them only at its flush, so an eager
        # read of a weight or slot that is still pending has to run
        # before the update deletes what it reads. It then yields the
        # pre-step value, the reference's read-before-write order.
        # Nothing is pending after backward(), and this costs nothing
        _bulk.flush_current()

        def flat_state(s):
            if s is None:
                return []
            if isinstance(s, NDArray):
                return [s._data]
            return [e._data for e in s if isinstance(e, NDArray)]

        from .. import sharding as _sharding
        _ctx = _sharding.current()
        if _ctx is not None:
            with _trace.child_span('mx.trainer.place'):
                self._mesh_place(live, _ctx)

        praws = [p.list_data()[0]._data for _, p in live]
        graws = [p.list_grad()[0]._data for _, p in live]
        sraws = [flat_state(self._states[i]) for i, _ in live]

        # placements join the key under a mesh: the step after the first
        # sharded compile re-places params per the rules, and the fused
        # fn's baked w_shard/s_shard constraints must be rebuilt for the
        # new layouts
        place_key = tuple(str(getattr(r, 'sharding', None))
                          for r in praws) if _ctx is not None else None
        key = (id(opt), opt.rescale_grad, opt.clip_gradient,
               tuple((r.shape, str(r.dtype)) for r in praws),
               _ctx.fingerprint() if _ctx is not None else None,
               place_key)
        entry = self._fused_cache.get(key)
        if entry is None:
            state_templates = [self._states[i] for i, _ in live]
            # under a mesh context, pin the updated weights and slots to
            # the layouts the compiled forward / ZeRO-1 plan expect:
            # GSPMD would otherwise let a replicated param inherit its
            # gradient's data-parallel sharding and break the pjit
            # entry's declared in_shardings on the next step. A weight
            # with no recorded layout keeps the one it came in with, so
            # that its donated buffer can be written in place
            w_shard = [None] * len(live)
            s_shard = [None] * len(live)
            if _ctx is not None:
                from jax.sharding import NamedSharding
                for j, (i, p) in enumerate(live):
                    sp = getattr(p, '_sharding_spec', None)
                    if sp is not None and \
                            getattr(p, '_sharding_mesh', None) == _ctx.mesh:
                        w_shard[j] = NamedSharding(_ctx.mesh, sp)
                    else:
                        w_shard[j] = praws[j].sharding
                    s_shard[j] = [e.sharding for e in sraws[j]] or None

            # traced inside the mesh context when there is one: the
            # fused optimizer ops then take their XLA path, which GSPMD
            # can partition (ops/pallas/fused_optimizer.py use_pallas)
            def fused(donated_, kept_, graws_, lrs_, wds_, ts_):
                praws_, sraws_ = donated_
                if kept_ is not None:
                    praws_ = [k if d is None else d
                              for d, k in zip(praws_, kept_[0])]
                    sraws_ = [[k if d is None else d
                               for d, k in zip(ds, ks)]
                              for ds, ks in zip(sraws_, kept_[1])]
                prev = _tape.set_recording(False)
                try:
                    new_ws, new_ss = [], []
                    for j, (w, g) in enumerate(zip(praws_, graws_)):
                        tmpl = state_templates[j]
                        if tmpl is None:
                            st = None
                        elif isinstance(tmpl, NDArray):
                            st = NDArray(sraws_[j][0])
                        else:
                            it = iter(sraws_[j])
                            st = type(tmpl)(
                                NDArray(next(it)) if isinstance(e, NDArray)
                                else e for e in tmpl)
                        nw, ns = opt.step(w, g, st, lrs_[j], wds_[j],
                                          ts_[j])
                        # keep the stored weight dtype stable across
                        # steps (bf16-cast nets: math promotes to f32,
                        # the parameter itself must stay bf16)
                        if nw.dtype != w.dtype:
                            nw = nw.astype(w.dtype)
                        if w_shard[j] is not None:
                            nw = jax.lax.with_sharding_constraint(
                                nw, w_shard[j])
                        new_ws.append(nw)
                        if ns is None:
                            ns_list = []
                        elif isinstance(ns, tuple):
                            ns_list = list(ns)
                        else:
                            ns_list = [ns]
                        if s_shard[j]:
                            ns_list = [
                                jax.lax.with_sharding_constraint(e, sh)
                                if sh is not None and hasattr(e, 'shape')
                                else e
                                for e, sh in zip(ns_list, s_shard[j])]
                        new_ss.append(ns_list)
                    return new_ws, new_ss
                finally:
                    _tape.set_recording(prev)

            n = len(live)
            try:
                fn = jax.jit(fused, donate_argnums=(0,))
                # trace-check BEFORE advancing update counts so a failed
                # optimizer falls back without double-counting
                out_ws, out_ss = jax.eval_shape(
                    fn, (praws, sraws), None, graws, *_zero_hypers(n))
            except _UNTRACEABLE as e:
                self._fused_cache[key] = _FUSED_SENTINEL
                raise _FusedUnsupported(
                    f'{type(e).__name__}: {e}'.splitlines()[0])

            def aliases(raw, out, pinned):
                return (raw.shape, raw.dtype) == (out.shape, out.dtype) \
                    and (pinned is None or
                         raw.sharding.is_equivalent_to(pinned, raw.ndim))

            # (j, -1) a weight, (j, k) a slot: leaves no output can
            # take the place of
            fixed = frozenset(
                [(j, -1) for j in range(n)
                 if not aliases(praws[j], out_ws[j], w_shard[j])] +
                [(j, k) for j in range(n) for k, e in enumerate(sraws[j])
                 if k >= len(out_ss[j]) or
                 not aliases(e, out_ss[j][k], None)])
            entry = self._fused_cache[key] = (fn, fixed)
        elif entry is _FUSED_SENTINEL:
            raise _FusedUnsupported('previously failed')
        fn, fixed = entry

        ids = [id(e) for raw, slots in zip(praws, sraws)
               for e in (raw, *slots)]
        read = set(map(id, graws))
        if not fixed and len(read.union(ids)) == len(read) + len(ids):
            return fn, (praws, sraws), None, graws
        times = collections.Counter(ids)

        def split(j, k, e):
            keep = (j, k) in fixed or times[id(e)] > 1 or id(e) in read
            return (None, e) if keep else (e, None)

        w = [split(j, -1, raw) for j, raw in enumerate(praws)]
        s = [[split(j, k, e) for k, e in enumerate(slots)]
             for j, slots in enumerate(sraws)]
        donated = ([d for d, _ in w], [[d for d, _ in l] for l in s])
        kept = ([k for _, k in w], [[k for _, k in l] for l in s])
        return fn, donated, kept, graws

    def audit_donation(self):
        """Compile the fused update as the next ``step`` would launch it
        and read ``input_output_alias`` from its HLO (the parser of
        ``mx.analysis``' donation-audit rule): ``{'donated_args': the
        operand buffers passed as donated, 'aliased_args': those of
        them an output is written over}``. Equal when the donation is
        real. Steps nothing and deletes nothing."""
        from ..analysis.rules.donation import parse_input_output_aliases
        live, _ = self._live_params()
        fn, donated, kept, graws = self._fused_program(live)
        hlo = fn.lower(donated, kept, graws,
                       *_zero_hypers(len(live))).compile().as_text()
        aliased = parse_input_output_aliases(hlo)
        # the donated leaves are the program's first parameters
        n_donated = len(jax.tree.leaves(donated))
        return {'donated_args': n_donated,
                'aliased_args': sum(1 for i in aliased if i < n_donated)}

    def _fused_update(self, live):
        import numpy as _onp
        import jax.numpy as jnp
        from .. import _bulk

        opt = self._optimizer
        fn, donated, kept, graws = self._fused_program(live)

        with _trace.child_span('mx.trainer.hyper') as hyper:
            for i, _ in live:
                opt._update_count(i)
            # constant hyperparameter vectors are cached device-side; the
            # update counts change every step and are uploaded (one small
            # transfer, and no program beyond the fused update to compile)
            lr_vals = tuple(opt._get_lr(i) for i, _ in live)
            wd_vals = tuple(opt._get_wd(i) for i, _ in live)
            cached = getattr(self, '_hyper_cache', None)
            fresh = cached is None or cached[0] != (lr_vals, wd_vals)
            if fresh:
                lrs = jnp.asarray(_onp.asarray(lr_vals, _onp.float32))
                wds = jnp.asarray(_onp.asarray(wd_vals, _onp.float32))
                self._hyper_cache = ((lr_vals, wd_vals), lrs, wds)
            else:
                lrs, wds = cached[1], cached[2]
            ts = jnp.asarray(_onp.asarray(
                [opt._index_update_count[i] for i, _ in live], _onp.int32))
            if hyper.live:
                hyper.set(uploaded=3 if fresh else 1)
        with _trace.child_span('mx.trainer.launch') as launch:
            new_ws, new_ss = fn(donated, kept, graws, lrs, wds, ts)
            if launch.live:
                n_state = sum(map(len, donated[1]))
                n_kept = 0 if kept is None else sum(
                    e is not None for l in (kept[0], *kept[1]) for e in l)
                launch.set(n_in=2 * len(live) + n_state + 3,
                           n_out=len(live) + n_state,
                           donated=len(live) + n_state - n_kept,
                           **_bulk.launch_attrs(new_ws[0]))
        for (i, param), nw, ns in zip(live, new_ws, new_ss):
            datas = param.list_data()
            datas[0]._rebind(nw)
            for d in datas[1:]:
                d._rebind(nw)
            st = self._states[i]
            if st is None:
                continue
            if isinstance(st, NDArray):
                st._rebind(ns[0])
            else:
                k = 0
                for e in st:
                    if isinstance(e, NDArray):
                        e._rebind(ns[k])
                        k += 1

    def update(self, batch_size, ignore_stale_grad=False):
        """Manual update path (reference trainer.py:update)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not self._update_on_kvstore, \
            'update() cannot be called when update_on_kvstore is set'
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    # ------------------------------------------------------------ save / load
    def state_dict(self):
        """Full trainer state as host data (picklable, checkpointable).

        Beyond the optimizer slot states this captures everything the
        update *schedule* depends on: the global update counter, the
        per-index update counts (adam's bias-correction ``t``, per-param
        lr/wd schedules) and the lr-scheduler's mutable attributes —
        omitting any of them makes a restored trainer's next step drift
        from the uninterrupted run.
        """
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        sd = {
            'states': {i: _state_to_host(s)
                       for i, s in self._states.items()},
            'num_update': int(self._optimizer.num_update),
            'index_update_count': {
                int(i): int(c) for i, c in
                self._optimizer._index_update_count.items()},
        }
        sch = getattr(self._optimizer, 'lr_scheduler', None)
        if sch is not None:
            import copy
            sd['lr_scheduler'] = copy.deepcopy(sch.__dict__)
        return sd

    def load_state_dict(self, sd):
        """Restore state captured by :meth:`state_dict` — the next
        ``step`` is bit-identical to the uninterrupted trainer's."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._states = {int(i): _state_from_host(s)
                        for i, s in sd['states'].items()}
        self._optimizer.num_update = int(sd['num_update'])
        self._optimizer._index_update_count = {
            int(i): int(c)
            for i, c in sd.get('index_update_count', {}).items()}
        sch = getattr(self._optimizer, 'lr_scheduler', None)
        if sch is not None and 'lr_scheduler' in sd:
            sch.__dict__.update(sd['lr_scheduler'])
        # drop device-side caches keyed on the old counters/hypers
        self._hyper_cache = None

    def save_states(self, fname):
        """Reference trainer.py:482 (pickled updater states)."""
        import pickle
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            # optimizer state lives in the kvstore updater in this mode
            # (reference trainer.py:482 warns it's rank-local)
            self._kvstore.save_optimizer_states(fname, dump_optimizer=False)
            return
        with open(fname, 'wb') as f:
            pickle.dump({'version': 2, **self.state_dict()}, f)

    def load_states(self, fname):
        """Reference trainer.py:511."""
        import pickle
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            return
        with open(fname, 'rb') as f:
            payload = pickle.load(f)
        if isinstance(payload, dict):
            self.load_state_dict(payload)
            return
        # legacy format: (states, num_update) tuple — no schedule state
        states, num_update = payload
        self._states = {i: _state_from_host(s) for i, s in states.items()}
        self._optimizer.num_update = num_update
        self._hyper_cache = None


def _state_to_host(state):
    import numpy as _np
    if state is None:
        return None
    if isinstance(state, NDArray):
        return state.asnumpy()
    if isinstance(state, (list, tuple)):
        return tuple(_state_to_host(s) for s in state)
    return state


def _state_from_host(state):
    import numpy as _np
    from ..ndarray.ndarray import array
    if state is None:
        return None
    if isinstance(state, _np.ndarray):
        return array(state)
    if isinstance(state, tuple):
        return tuple(_state_from_host(s) for s in state)
    return state
