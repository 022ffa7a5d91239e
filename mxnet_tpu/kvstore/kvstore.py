"""In-process KVStore types: ``local`` and ``device``.

Reference: src/kvstore/kvstore_local.h:70 + comm.h (CommCPU :104 /
CommDevice :452 — the GPU reduce trees). On TPU a single process owns all
local chips; "reduce across device copies" is one stacked jnp.sum that XLA
executes with on-chip ICI transfers, so CommDevice/CommDeviceTree collapse
into one fused reduction. The updater/optimizer hooks
(set_updater/set_optimizer, include/mxnet/kvstore.h:297) are preserved.
"""

import jax.numpy as jnp

from ..ndarray.ndarray import NDArray
from .base import KVStoreBase, register


def _group(keys, values):
    """Group possibly-flat (key, value) lists by key
    (reference kvstore_local.h GroupKVPairs)."""
    if not isinstance(keys, (list, tuple)):
        return [(keys, values if isinstance(values, (list, tuple))
                 else [values])]
    if len(keys) == len(values) and not any(
            isinstance(v, (list, tuple)) for v in values):
        merged = {}
        order = []
        for k, v in zip(keys, values):
            if k not in merged:
                merged[k] = []
                order.append(k)
            merged[k].append(v)
        return [(k, merged[k]) for k in order]
    return [(k, v if isinstance(v, (list, tuple)) else [v])
            for k, v in zip(keys, values)]


def _reduce(values):
    """Sum a list of NDArray replicas (CommDevice::Reduce, comm.h:452)."""
    if len(values) == 1:
        return values[0]._data
    return jnp.sum(jnp.stack([v._data for v in values]), axis=0)


@register
class KVStoreLocal(KVStoreBase):
    """Reference kvstore_local.h:70 — single-process aggregation."""

    NAME = 'local'

    def __init__(self):
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._states = {}

    # ------------------------------------------------------- classic surface
    def init(self, key, value):
        from ..ndarray import sparse as _sp
        for k, vals in _group(key, value):
            v = vals[0]
            if isinstance(v, _sp.BaseSparseNDArray):
                self._store[k] = v.copy()   # keep sparse storage
            else:
                # the store's own copy, in the caller's layout
                # (reference: init copies in, pull copies out): the
                # caller's array may be a weight, whose buffer
                # Trainer.step donates to the update
                self._store[k] = NDArray(jnp.copy(v._data), ctx=v._ctx)

    def push(self, key, value, priority=0):
        for k, vals in _group(key, value):
            merged = _reduce(vals)
            if self._updater is not None and k in self._store:
                self._updater(k, NDArray(merged), self._store[k])
            elif k in self._store:
                self._store[k]._rebind(self._store[k]._data + merged)
            else:
                self._store[k] = NDArray(merged)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        for k, outs in _group(key, out):
            src = self._store[k]
            for o in outs:
                o._rebind(jnp.copy(src._data))

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull (reference PushPullDefault kvstore_dist.h:578).

        Without an updater this is a pure allreduce: out ← sum(value).
        """
        for k, vals in _group(key, value):
            merged = _reduce(vals)
            if self._updater is not None:
                if k not in self._store:
                    raise ValueError(
                        f'pushpull with an updater requires key {k!r} to be '
                        'initialized first (init/broadcast), matching the '
                        'reference KVStore contract')
                self._updater(k, NDArray(merged), self._store[k])
                result = self._store[k]._data
            else:
                result = merged
            if out is not None:
                outs = [o for kk, os in _group(key, out) if kk == k
                        for o in os]
                for o in outs:
                    o._rebind(result)
            else:
                for v in vals:
                    v._rebind(result)

    def broadcast(self, key, value, out, priority=0):
        """``init`` then ``pull``. In one process the value is already
        what every out has to hold, so an out that is the value itself
        keeps its array: a weight stays on the buffer its recorded
        graphs and the caller's handles know."""
        self.init(key, value)
        sources = {id(vals[0]) for _, vals in _group(key, value)}
        for k, outs in _group(key, out):
            rest = [o for o in outs if id(o) not in sources]
            if rest:
                self.pull(k, out=rest, priority=priority)

    # ---------------------------------------------------------- fused path
    def fused_pushpull(self, keys, values, outs=None, priorities=None):
        """Multi-key pushpull in as few device programs as possible.

        ``values[i]`` is the replica list for ``keys[i]``. All keys'
        replica reductions run in ONE jitted executable (the role the
        reference's per-key ``CommDevice::Reduce`` + engine bulking
        played); the distributed subclass adds bucketed cross-process
        collectives on top. ``priorities`` is accepted for API parity;
        ordering only matters in the distributed subclass, where it
        sequences bucket dispatch (reference Trainer's ``priority=-i``).
        """
        vals_lists = [v if isinstance(v, (list, tuple)) else [v]
                      for v in values]
        merged = self._merge_local(keys, vals_lists)
        self._apply_merged(keys, merged, vals_lists, outs)

    def _merge_local(self, keys, vals_lists):
        from . import fusion
        raws = [[v._data for v in vs] for vs in vals_lists]
        if any(len(r) > 1 for r in raws):
            return fusion._fused_replica_sum(raws)
        return [r[0] for r in raws]

    def _apply_merged(self, keys, merged, vals_lists, outs):
        for i, k in enumerate(keys):
            if self._updater is not None:
                if k not in self._store:
                    raise ValueError(
                        f'pushpull with an updater requires key {k!r} to '
                        'be initialized first (init/broadcast)')
                self._updater(k, NDArray(merged[i]), self._store[k])
                result = self._store[k]._data
            else:
                result = merged[i]
            targets = outs[i] if outs is not None else vals_lists[i]
            if not isinstance(targets, (list, tuple)):
                targets = [targets]
            for t in targets:
                t._rebind(result)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows (reference kvstore.py
        row_sparse_pull → PullRowSparse, include/mxnet/kvstore.h:221).

        With a RowSparseNDArray stored value, returns/updates the retained
        rows; dense stored values gather the requested rows into the dense
        output (the useful TPU form: gather over a sharded embedding axis,
        SURVEY §5 last row)."""
        from ..ndarray import sparse as _sp
        if isinstance(key, (list, tuple)):
            rids = row_ids if isinstance(row_ids, (list, tuple)) else \
                [row_ids] * len(key)
            outs = out if isinstance(out, (list, tuple)) else \
                [None] * len(key)
            return [self.row_sparse_pull(k, out=o, priority=priority,
                                         row_ids=r)
                    for k, o, r in zip(key, outs, rids)]
        value = self._store[key]
        if row_ids is None:
            self.pull(key, out=out, priority=priority)
            return out
        if isinstance(value, _sp.RowSparseNDArray):
            res = _sp.retain(value, row_ids)
            if out is not None:
                outs = out if isinstance(out, (list, tuple)) else [out]
                for o in outs:
                    o.data = res.data
                    o.indices = res.indices
                    o._invalidate()
                return out
            return res
        import jax.numpy as jnp
        rows = row_ids._data.astype(jnp.int32) if hasattr(row_ids, '_data') \
            else jnp.asarray(row_ids, jnp.int32)
        gathered = value._data.at[rows].get()
        if out is not None:
            outs = out if isinstance(out, (list, tuple)) else [out]
            for o in outs:
                if isinstance(o, _sp.RowSparseNDArray):
                    # actual row slices — never densify the pull
                    o.data = NDArray(gathered)
                    o.indices = NDArray(rows.astype(jnp.int64))
                    o._invalidate()
                else:
                    o._rebind(o._data.at[rows].set(gathered))
            return out
        # no out given: return the row slices themselves (O(nnz), not
        # O(table) — a 10M-row embedding pull must not densify)
        return _sp.RowSparseNDArray(NDArray(gathered),
                                    NDArray(rows.astype(jnp.int64)),
                                    value.shape)

    # ------------------------------------------------------ optimizer hooks
    def set_updater(self, updater):
        self._updater = updater

    _set_updater = set_updater

    def set_optimizer(self, optimizer):
        from ..optimizer import get_updater
        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def set_gradient_compression(self, compression_params):
        """Enable 2-bit compression (reference SetGradientCompression,
        include/mxnet/kvstore.h + gradient_compression.h:37). On the
        local store this only validates/records params — like the
        reference, compression is applied on the distributed hop
        (KVStoreTPUSync), not on in-process reduction."""
        from .gradient_compression import GradientCompression
        gc = GradientCompression()
        gc.set_params(compression_params)
        if gc.active and type(self) in (KVStoreLocal, KVStoreDevice):
            # the reference raises for kvstore types without compression
            # support (kvstore.cc); we accept for API parity but make
            # the no-op visible
            import warnings
            warnings.warn(
                f'gradient compression is a no-op on the {self.NAME!r} '
                'kvstore: it applies only on the distributed hop '
                '(dist_tpu_sync)', UserWarning, stacklevel=2)
        self._gc = gc

    @property
    def gradient_compression(self):
        gc = getattr(self, '_gc', None)
        if gc is None:
            from .gradient_compression import GradientCompression
            gc = self._gc = GradientCompression()
        return gc

    # ------------------------------------------------------------- topology
    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    def barrier(self):
        pass

    @property
    def type(self):
        return self.NAME

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, 'updater is not initialized'
        with open(fname, 'wb') as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, 'updater is not initialized'
        with open(fname, 'rb') as f:
            self._updater.set_states(f.read())

    @staticmethod
    def is_capable(capability):
        return capability.lower() in ('optimizer', 'init')


@register
class KVStoreDevice(KVStoreLocal):
    """Reference 'device' type: aggregation on-accelerator (CommDevice).
    Identical here — the reduce already runs on TPU."""

    NAME = 'device'


KVStore = KVStoreLocal  # classic class name (python/mxnet/kvstore/kvstore.py)
