"""Imperative autograd tape.

TPU-native re-design of the reference's ``Imperative`` runtime
(include/mxnet/imperative.h:51, src/imperative/imperative.cc): thread-local
``is_recording``/``is_train`` flags (imperative.h:309-323), per-array autograd
info (``AGInfo``, imperative.h:54-92), ``RecordOp`` building a graph on the
fly, and ``Backward`` (imperative.cc:377) constructing + executing the
backward graph.

Design differences from the reference:

* Nodes hold *pure functions over jax arrays* instead of nnvm ops. The
  backward rule for every node is obtained from ``jax.vjp`` — the MXGradient
  pass (src/nnvm/gradient.cc:699) collapses into XLA's autodiff.
* When both recording and training, the VJP is computed at record time
  (the forward runs once and keeps residuals) — this mirrors the
  reference keeping forward activations alive for backward. A single
  eager op gets it from ``jax.vjp``; a compiled graph's call brings the
  ``vjp_fn`` of its two built programs (gluon/block.py ``_VjpPrograms``),
  so no ``jax.vjp`` runs in Python on such a step. In predict-record mode
  we defer and re-linearize ``node.fn`` at ``backward()`` time.
* Gradient aggregation (the reference's elemwise_sum/_grad_add nodes and
  kAddTo request) is plain accumulation into a cotangent map.
"""

import threading

import jax
import jax.numpy as jnp

from .base import MXNetError
from .telemetry import trace as _trace

_state = threading.local()


def _st():
    if not hasattr(_state, 'recording'):
        _state.recording = False
        _state.training = False
        _state.vjp_traces = 0
    return _state


def note_vjp_trace():
    """Called wherever a compiled program's vjp is traced in Python (a
    compiled graph building one of its two programs, a deferred
    ``jax.vjp``): the ``traced`` attribute of ``mx.graph.launch`` and
    ``mx.tape.vjp`` is whether this moved during the span."""
    _st().vjp_traces += 1


def vjp_traces():
    return _st().vjp_traces


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(flag):
    prev = _st().recording
    _state.recording = flag
    return prev


def set_training(flag):
    prev = _st().training
    _state.training = flag
    return prev


class AGInfo:
    """Autograd metadata attached to an NDArray (reference imperative.h:54).

    Either a *variable* (leaf marked by ``mark_variables``: carries the grad
    buffer and grad_req) or an *output* of a recorded TapeNode.
    """

    __slots__ = ('node', 'index', 'variable', 'grad', 'grad_req',
                 '__weakref__')

    def __init__(self, node=None, index=0, variable=False, grad=None,
                 grad_req='write'):
        self.node = node
        self.index = index
        self.variable = variable
        self.grad = grad
        self.grad_req = grad_req


class RowSparseCot:
    """Row-sparse cotangent: the backward of a sparse-grad embedding
    lookup carries (values, row indices) instead of scattering into a
    dense table-shaped array (reference: Embedding's FGradient emits a
    row_sparse grad, src/operator/tensor/indexing_op.cc). Indices may
    repeat (one entry per token occurrence); the consumer merges.
    """

    __slots__ = ('values', 'indices', 'shape')

    def __init__(self, values, indices, shape):
        self.values = values        # (nnz,) + shape[1:]
        self.indices = indices      # (nnz,) int32
        self.shape = shape          # full dense shape

    @property
    def dtype(self):
        return self.values.dtype

    def astype(self, dtype):
        if dtype == self.values.dtype:
            return self
        return RowSparseCot(self.values.astype(dtype), self.indices,
                            self.shape)

    def dense(self):
        z = jnp.zeros(self.shape, self.values.dtype)
        return z.at[self.indices].add(self.values)

    def __add__(self, other):
        if isinstance(other, RowSparseCot):
            return RowSparseCot(
                jnp.concatenate([self.values, other.values]),
                jnp.concatenate([self.indices, other.indices]),
                self.shape)
        if other is None:
            return self
        return self.dense() + other     # mixed with a dense cotangent

    def __radd__(self, other):
        if other is None or (isinstance(other, (int, float))
                             and other == 0):
            return self
        return other + self.dense()


class TapeNode:
    """One recorded op: pure fn, captured input values, parent links."""

    __slots__ = ('fn', 'in_vals', 'parents', 'n_out', 'name', 'vjp_fn',
                 'out_avals', 'multi', 'vjp_lock')

    def __init__(self, fn, in_vals, parents, n_out, name, vjp_fn=None,
                 out_avals=None, multi=None, vjp_lock=None):
        self.fn = fn
        self.in_vals = in_vals      # raw jax arrays at record time
        self.parents = parents      # list of AGInfo or None per input
        self.n_out = n_out
        self.name = name
        self.vjp_fn = vjp_fn        # set when recorded in train mode
        # lock to hold while a deferred jax.vjp re-traces fn (a
        # _CachedOp re-trace swaps shared Parameter payloads and must
        # serialize with the graph lock — ADVICE r4)
        self.vjp_lock = vjp_lock
        self.out_avals = out_avals
        # whether fn returns a tuple (vjp cotangent must match structure)
        self.multi = n_out > 1 if multi is None else multi


def record_node(fn, nd_inputs, raw_outputs, name='op'):
    """Attach a TapeNode to raw_outputs given recorded nd_inputs.

    ``fn`` must be pure over the raw input arrays: fn(*raws) == raw_outputs.
    Returns the node; caller attaches AGInfo(node, i) to each output NDArray.
    """
    parents = [getattr(x, '_ag', None) for x in nd_inputs]
    raws = [x._data for x in nd_inputs]
    node = TapeNode(fn, raws, parents, len(raw_outputs), name,
                    out_avals=[jax.typeof(o) for o in raw_outputs])
    return node


def _needs_grad(nd_inputs):
    return any(getattr(x, '_ag', None) is not None for x in nd_inputs)


def mark_variables(variables, gradients, grad_reqs='write'):
    """Reference: Imperative::MarkVariables (imperative.h:237)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, grad, req in zip(variables, gradients, grad_reqs):
        var._ag = AGInfo(variable=True, grad=grad, grad_req=req)


_ONES_CACHE = {}


def _ones_cached(shape, dtype):
    """Head cotangent seed; immutable, so cached per (shape, dtype) — a
    fresh device allocation per backward() is pure dispatch latency."""
    key = (tuple(shape), str(dtype))
    got = _ONES_CACHE.get(key)
    if got is None:
        if len(_ONES_CACHE) > 256:
            _ONES_CACHE.clear()
        got = _ONES_CACHE[key] = jnp.ones(shape, dtype=dtype)
    return got


def _toposort(head_infos):
    """Reverse-topological order of TapeNodes reachable from heads."""
    order, seen, stack = [], set(), []
    for info in head_infos:
        if info is not None and info.node is not None:
            stack.append(info.node)
    visiting = {}
    while stack:
        node = stack[-1]
        if id(node) in seen:
            stack.pop()
            continue
        if visiting.get(id(node)):
            seen.add(id(node))
            order.append(node)
            stack.pop()
            continue
        visiting[id(node)] = True
        for p in node.parents:
            if p is not None and p.node is not None and id(p.node) not in seen:
                stack.append(p.node)
    return order[::-1]  # heads-first


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             variables=None, create_graph=False):
    """Reference: Imperative::Backward (src/imperative/imperative.cc:377).

    heads: list of NDArrays; head_grads: matching list (None → ones).
    Accumulates into the ``.grad`` buffers of marked variables — or, when
    ``variables`` is given (the ``autograd.grad`` path, c_api
    MXAutogradBackwardEx with variable handles), returns their cotangents
    instead of writing buffers.

    ``create_graph=True`` replays each node's VJP *through the tape* (the
    backward ops are recorded like forward ops), so the returned gradients
    are differentiable — higher-order autograd, the role of the
    reference's create_graph handling in MXGradient.
    """
    with _trace.child_span('mx.tape.backward') as span:
        return _backward(heads, head_grads, retain_graph, train_mode,
                         variables, create_graph, span)


def _check_not_updated_in_place(node):
    """``backward()`` through a graph whose weights ``Trainer.step`` has
    updated since it was recorded: the update donates the buffers the
    graph holds in ``in_vals``, and what the vjp kept of them may be a
    transposed copy that still reads, at the old value. The reference,
    which updates in place, would differentiate at the new weights;
    neither a deleted-array error from inside JAX nor a silent gradient
    at the old ones will do."""
    for v in node.in_vals:
        if getattr(v, 'is_deleted', bool)():
            raise MXNetError(
                f'backward() through a retained graph (node '
                f'{node.name!r}) whose weights were updated in place '
                'since it was recorded: Trainer.step() writes each new '
                'weight over the buffer the graph still holds. Call '
                'backward() as often as needed before step() '
                '(grad_req=\'add\' accumulates), or record the forward '
                'again after it.')


def _node_vjp(node, present, indexed):
    """The input cotangents of one node from the cotangents ``present``
    of its outputs."""
    _check_not_updated_in_place(node)
    if indexed is not None:
        # a bulk segment's node, a compiled graph's recorded call: zero
        # cotangents are synthesized inside the jitted vjp (symbolic
        # zeros) instead of N host ops
        return indexed({
            i: (c.dense() if isinstance(c, RowSparseCot) else c)
            for i, c in present.items()})
    out_cots = [
        present.get(i) if present.get(i) is not None
        else jnp.zeros(node.out_avals[i].shape,
                       dtype=node.out_avals[i].dtype)
        for i in range(node.n_out)]
    if node.vjp_fn is not None:
        vjp_fn = node.vjp_fn
    elif node.vjp_lock is not None:
        # predict-record deferral: the re-trace re-enters
        # _CachedOp's pure_fn Parameter-payload swap, which
        # must not race lock-free inference snapshots
        note_vjp_trace()
        with node.vjp_lock:
            _, vjp_fn = jax.vjp(node.fn, *node.in_vals)
    else:
        _, vjp_fn = jax.vjp(node.fn, *node.in_vals)
    return vjp_fn(tuple(out_cots) if node.multi else out_cots[0])


def _backward(heads, head_grads, retain_graph, train_mode, variables,
              create_graph, span):
    from .ndarray.ndarray import NDArray  # local import to avoid cycle
    from . import _bulk
    with _trace.child_span('mx.tape.flush'):
        _bulk.flush_current()   # segment tape nodes must be complete

    head_infos = []
    for h in heads:
        info = getattr(h, '_ag', None)
        if info is None:
            raise ValueError(
                'cannot differentiate a head that was not computed while '
                'autograd recording was on')
        head_infos.append(info)

    if head_grads is None:
        head_grads = [None] * len(heads)

    if create_graph:
        return _backward_recorded(heads, head_infos, head_grads,
                                  variables, train_mode)

    # cotangent accumulation per (node, out_index)
    cots = {}
    var_grads = {}  # id(AGInfo) -> (info, cotangent)

    def _push(info, cot):
        if info is None or cot is None:
            return
        if info.variable:
            key = id(info)
            if key in var_grads:
                var_grads[key] = (info, var_grads[key][1] + cot)
            else:
                var_grads[key] = (info, cot)
        elif info.node is not None:
            key = (id(info.node), info.index)
            cots[key] = cot if key not in cots else cots[key] + cot

    for h, info, hg in zip(heads, head_infos, head_grads):
        if hg is None:
            g = _ones_cached(h.shape, h._data.dtype)
        else:
            g = hg._data if isinstance(hg, NDArray) else jnp.asarray(hg)
        _push(info, g)

    order = _toposort(head_infos)
    node_index = {id(n): n for n in order}
    if span.live:
        span.set(n_nodes=len(order))

    prev_train = set_training(train_mode)
    try:
        for node in order:
            present = {}
            for i in range(node.n_out):
                c = cots.pop((id(node), i), None)
                if c is not None:
                    present[i] = c
            if not present:
                continue
            indexed = getattr(node.vjp_fn, 'indexed', None)
            if indexed is not None or node.vjp_lock is not None:
                # the node is a compiled program (a bulk segment's or a
                # recorded graph call's indexed vjp; a graph's call in
                # predict-record mode, which brings a lock and defers):
                # its vjp launches the backward programs. Single eager
                # ops get no span of their own.
                with _trace.child_span('mx.tape.vjp') as launch:
                    traces = vjp_traces()
                    in_cots = _node_vjp(node, present, indexed)
                    if launch.live:
                        launch.set(
                            n_out=sum(c is not None for c in in_cots),
                            traced=int(vjp_traces() != traces),
                            **_bulk.launch_attrs(next(
                                (c for c in in_cots if c is not None),
                                None)))
            else:
                in_cots = _node_vjp(node, present, indexed)
            for parent, cot in zip(node.parents, in_cots):
                _push(parent, cot)
            if not retain_graph:
                # a recorded graph call's residuals go back to its entry,
                # for the next recorded forward to write over
                spent = getattr(node.vjp_fn, 'spent', None)
                if spent is not None:
                    spent()
                node.vjp_fn = None
    finally:
        set_training(prev_train)
    if span.live:
        span.set(n_vars=len(var_grads))

    if variables is not None:
        out = []
        for v in variables:
            info = getattr(v, '_ag', None)
            if info is None or not info.variable:
                raise ValueError('grad() variables must be marked '
                                 '(attach_grad/mark_variables)')
            got = var_grads.get(id(info))
            if got is None:
                out.append(NDArray(jnp.zeros(v.shape, v._data.dtype)))
            elif isinstance(got[1], RowSparseCot):
                from .ndarray import sparse as _sp
                rsp = _sp.RowSparseNDArray(
                    NDArray(got[1].values),
                    NDArray(got[1].indices.astype(jnp.int64)),
                    got[1].shape)
                rsp._may_have_duplicates = True
                out.append(rsp)
            else:
                out.append(NDArray(got[1]))
        return out

    # write into variable grad buffers honoring grad_req
    for info, cot in var_grads.values():
        if info.grad is None or info.grad_req == 'null':
            continue
        if cot.dtype == jax.dtypes.float0:
            continue      # integer-dtype variable: no gradient (float0)
        if isinstance(cot, RowSparseCot):
            if info.grad_req == 'add':
                # accumulation mode may mix sparse and dense
                # contributions across backward() calls — densify so
                # neither is lost (the no-densify fast path is the
                # default grad_req='write')
                cot = cot.dense()
            else:
                # keep the gradient row-sparse end-to-end: the dense
                # buffer is never materialized; Parameter.grad()/
                # list_grad surface the attached RowSparseNDArray
                # (10M-row embeddings never touch O(table) grad memory)
                from .ndarray import sparse as _sp
                rsp = _sp.RowSparseNDArray(
                    NDArray(cot.values.astype(info.grad._data.dtype)),
                    NDArray(cot.indices.astype(jnp.int64)), cot.shape)
                rsp._may_have_duplicates = True
                info.grad._rsp = rsp
                continue
        info.grad._rsp = None
        have = info.grad._data
        if cot.dtype != have.dtype:     # else astype is Python for nothing
            cot = cot.astype(have.dtype)
        info.grad._data = have + cot if info.grad_req == 'add' else cot
    del node_index
    return None


def _backward_recorded(heads, head_infos, head_grads, variables,
                       train_mode):
    """Backward pass executed as *recorded* ops: every VJP application is
    re-dispatched through the op registry with recording on, so the
    cotangent chain itself lives on the tape (higher-order autograd)."""
    from .ndarray.ndarray import NDArray
    from .ops.registry import Op, apply_op

    cots = {}       # (node id, out idx) -> NDArray cotangent
    var_grads = {}  # id(AGInfo) -> (info, NDArray cotangent)

    def _push(info, cot_nd):
        if info is None or cot_nd is None:
            return
        if info.variable:
            key = id(info)
            if key in var_grads:
                var_grads[key] = (info, var_grads[key][1] + cot_nd)
            else:
                var_grads[key] = (info, cot_nd)
        elif info.node is not None:
            key = (id(info.node), info.index)
            cots[key] = cot_nd if key not in cots else cots[key] + cot_nd

    for h, info, hg in zip(heads, head_infos, head_grads):
        if hg is None:
            g = NDArray(jnp.ones(h.shape, dtype=h._data.dtype))
        elif isinstance(hg, NDArray):
            g = hg
        else:
            g = NDArray(jnp.asarray(hg))
        _push(info, g)

    order = _toposort(head_infos)
    prev_train = set_training(train_mode)
    prev_rec = set_recording(True)
    try:
        for node in order:
            out_cots, any_cot = [], False
            for i in range(node.n_out):
                c = cots.pop((id(node), i), None)
                if c is None:
                    aval = node.out_avals[i]
                    c = NDArray(jnp.zeros(aval.shape, dtype=aval.dtype))
                else:
                    any_cot = True
                out_cots.append(c)
            if not any_cot:
                continue

            n_out, multi, fwd_fn = node.n_out, node.multi, node.fn

            def bwd_fn(*raws, _n=n_out, _multi=multi, _f=fwd_fn):
                cot_raws, in_raws = raws[:_n], raws[_n:]
                _, vjp = jax.vjp(_f, *in_raws)
                return vjp(tuple(cot_raws) if _multi else cot_raws[0])

            # original inputs re-wrapped with their recorded lineage so
            # third-and-higher orders chain through them too
            in_nds = []
            for raw, parent in zip(node.in_vals, node.parents):
                nd = NDArray(raw)
                if parent is not None:
                    nd._ag = parent
                in_nds.append(nd)
            op = Op(f'_backward_{node.name}', bwd_fn)
            arrays = list(out_cots) + in_nds
            raws = [a._data for a in arrays]
            _check_not_updated_in_place(node)
            res = apply_op(op, arrays,
                           lambda *r, _b=bwd_fn: _b(*r), name=op.name)
            in_cots = res if isinstance(res, tuple) else (res,)
            for parent, cot in zip(node.parents, in_cots):
                _push(parent, cot)
    finally:
        set_recording(prev_rec)
        set_training(prev_train)

    if variables is not None:
        out = []
        for v in variables:
            info = getattr(v, '_ag', None)
            if info is None or not info.variable:
                raise ValueError('grad() variables must be marked '
                                 '(attach_grad/mark_variables)')
            got = var_grads.get(id(info))
            out.append(got[1] if got is not None
                       else NDArray(jnp.zeros(v.shape, v._data.dtype)))
        return out
    for info, cot_nd in var_grads.values():
        if info.grad is None or info.grad_req == 'null':
            continue
        # recorded (create_graph) backward is dense-only: drop any
        # surfaced row-sparse grad so it cannot shadow this write
        info.grad._rsp = None
        if info.grad_req == 'add':
            info.grad._data = info.grad._data + cot_nd._data.astype(
                info.grad._data.dtype)
        else:
            info.grad._data = cot_nd._data.astype(info.grad._data.dtype)
    return None
