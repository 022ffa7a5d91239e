"""Bulked (lazy) eager execution — the imperative engine's fast path.

TPU-native re-design of the reference engine's operation bulking
(include/mxnet/engine.h:310 ``StartBulk``/``StopBulk``,
src/imperative/imperative_utils.h:636 ``RunGraph`` bulk segments): the
reference fuses up to ``MXNET_ENGINE_BULK_SIZE`` consecutive engine pushes
into one scheduled unit to amortize per-op dispatch. Here the per-op cost
being amortized is an XLA executable launch, so bulking goes further:
consecutive imperative ops are *recorded* into a segment and compiled into
ONE cached XLA program, flushed at sync points.

How it works
------------
* ``registry.apply_op`` offers each invoke()-dispatched op to
  :func:`try_record`. If bulking is active, the op is appended to the
  thread-local :class:`_Segment` and the caller receives **lazy** NDArrays
  (``NDArray._lazy`` holds a :class:`LazyRef` with the abstract value;
  ``NDArray._data`` materializes on touch).
* The segment keeps a **trie** keyed by (op name, static-argument key,
  grad-activity, input wiring): a training loop's second iteration walks the
  same trie path and reuses the recorded output avals — no re-abstract-eval,
  no retracing, no per-op device dispatch.
* A **flush** (sync point: ``_data`` touch, ``backward()``, a hybridized
  block's compiled call, segment-size cap, explicit ``engine.bulk`` exit)
  compiles — once per (trie node, live output set) — a jitted replay of
  the whole segment and executes it as one device program. Subsequent
  identical segments are a dict hit + one call.
* Autograd: per-op tape nodes are *not* created inside a segment. Instead
  the flush populates ONE :class:`_tape.TapeNode` covering the segment,
  whose vjp re-linearizes the jitted replay (rematerialized backward — the
  standard TPU trade of FLOPs for memory/launches). Ops that would not have
  been recorded eagerly (recording off, non-differentiable, no tracked
  input) get ``lax.stop_gradient`` in the replay, reproducing the eager
  tape's gradient-blocking exactly.
* Under ``mx.sharding.mesh`` a segment is recorded as it is off one, and
  its boundary — which may mix arrays committed to the mesh (a sharded
  graph's outputs) with single-device ones (labels, loss weights) — is
  reconciled once, at the flush, by the context the segment was opened
  under (``ShardingContext.lift``: where any boundary array lies on more
  than one device the single-device ones are placed on the mesh at their
  batch spec; where none does nothing is placed). The lifted boundary is
  what the tape node and the segment's vjp keep, and a cotangent that
  reaches that vjp on one device is lifted the same way: each of the two
  programs sees one device set. Labels are placed once a step, not once
  for every op that reads them.

Reference: engine.h:310-317 (bulk API), imperative_utils.h:636 (bulked
graph execution), docs faq env_var MXNET_ENGINE_BULK_SIZE.

Correctness guards:
* ops with unhashable static arguments (device arrays baked as constants,
  numpy buffers) fall back to eager dispatch (registry builds no bulk key);
* a trie position whose children keep multiplying (a Python-scalar constant
  that changes every iteration, e.g. a hand-rolled schedule) is marked
  unstable and ops at it run eagerly — one compile cannot be reused, so
  caching would turn into a compile-per-step storm;
* dynamic-output-shape ops raise under abstract evaluation and fall back;
* a plan traced under one mesh context is never replayed under another
  (an op reads the context while it is traced: the Pallas gates take
  XLA under a mesh, and ``jax.jit`` keeps one trace an aval signature
  whatever the sharding): the trie a segment walks is its context's own
  (a root a mesh fingerprint, one more off a mesh), entering or leaving
  ``mx.sharding.mesh``/``use`` flushes the calling thread's pending
  segment as ``engine.bulk``'s exit does, and a plan's programs enter
  the segment's context whenever they are traced (``_under``: a
  ``backward()`` after the mesh was left, another thread's settle);
* what the engine turns away under a mesh is lifted op by op by the
  eager path and counted ``unbulked``, as before;
* deferred-compute capture, per-op profiling, ``naive_engine`` and jit
  tracing all bypass bulking (checked by the registry / via tracer inputs).
"""

import collections
import functools
import os
import threading
import weakref

import jax
from jax import lax

from . import _tape
from .telemetry import trace as _trace
from .analysis import race as _race
from .analysis.race import guarded_by as _guarded_by

_MAX_SIBLINGS = 16     # distinct static-arg keys per (position, op) before
                       # the position is treated as unstable
_RETRY = 13            # re-admit every Nth attempt while unstable, so a
                       # later loop with STABLE constants can recover
_MAX_TOTAL = 64        # hard cap on keys per (position, op): bounds the
                       # worst-case compile count from a varying constant


class LazyRef:
    """A pending value: output ``key`` of a segment, materialized at flush."""

    __slots__ = ('seg', 'key', 'aval', 'value', '__weakref__')

    def __init__(self, seg, key, aval):
        self.seg = seg
        self.key = key          # (entry_idx, out_idx)
        self.aval = aval        # jax.ShapeDtypeStruct
        self.value = None


class _Entry:
    __slots__ = ('fn', 'in_refs', 'n_out', 'multi', 'stopgrad', 'out_refs')

    def __init__(self, fn, in_refs, n_out, multi, stopgrad):
        self.fn = fn
        self.in_refs = in_refs      # tuple of (0, boundary_idx) | (1, ei, oi)
        self.n_out = n_out
        self.multi = multi
        self.stopgrad = stopgrad
        self.out_refs = []          # weakrefs to LazyRefs


class _TrieNode:
    __slots__ = ('children', 'out_avals', 'multi', 'plans', 'op_counts',
                 'attempts')

    def __init__(self):
        self.children = {}
        self.out_avals = None       # this entry's output avals
        self.multi = False
        self.plans = {}             # out_keys -> _Plan (flush-here plans)
        self.op_counts = {}         # op name -> distinct keys seen here
        self.attempts = {}          # op name -> turned-away attempts


class _Plan:
    __slots__ = ('jfwd', 'fwd_raw', 'replay', 'out_keys', 'vjp_cache',
                 'ctx')

    def __init__(self, jfwd, fwd_raw, replay, out_keys, ctx):
        self.jfwd = jfwd
        self.fwd_raw = fwd_raw      # unjitted: boundary -> output tuple
        self.replay = replay        # unjitted full-env replay, for re-vjp
        self.out_keys = out_keys
        self.vjp_cache = {}         # nonzero-cot index tuple -> jitted vjp
        self.ctx = ctx              # mesh context of the trie it hangs in


class _SegVjp:
    """Segment-level vjp: recompute-based, jitted, cached per cotangent
    sparsity pattern. ``indexed`` lets the tape skip materializing zero
    cotangents for the (typically many) outputs that received none."""

    __slots__ = ('plan', 'boundary')

    def __init__(self, plan, boundary):
        self.plan = plan
        self.boundary = boundary

    def indexed(self, present):
        idxs = tuple(sorted(present))
        jf = self.plan.vjp_cache.get(idxs)
        if jf is None:
            replay = self.plan.replay
            sel = tuple(self.plan.out_keys[i] for i in idxs)

            def vjp_apply(boundary, cts):
                _tape.note_vjp_trace()      # the span's ``traced``

                def f(*b):
                    env = replay(*b)
                    return tuple(env[ei][oi] for ei, oi in sel)
                _, vjp = jax.vjp(f, *boundary)
                return vjp(cts)

            jf = jax.jit(_under(self.plan.ctx, vjp_apply))
            self.plan.vjp_cache[idxs] = jf
        cts = [present[i] for i in idxs]
        ctx = self.plan.ctx
        if ctx is not None:
            # a head gradient committed to one device beside a boundary
            # on the mesh: one device set, as at the flush
            n = len(self.boundary)
            cts = ctx.lift([*self.boundary, *cts])[n:]
        return jf(self.boundary, tuple(cts))

    def __call__(self, cots):
        # full-cotangent fallback (create_graph and other tape paths that
        # pre-build dense cotangent lists)
        if not isinstance(cots, tuple):
            cots = (cots,)
        return self.indexed(dict(enumerate(cots)))


class _Segment:
    def __init__(self, state):
        self.state = state
        self.lock = threading.RLock()
        self._race = None
        if _race.enabled():
            # declared level 'bulk.segment' (analysis/locks.py); every
            # entries/trie mutation must hold self.lock — the Eraser
            # lockset checker verifies it across foreign-thread settles
            self.lock = _race.tracked(self.lock, 'bulk.segment')
            self._race = _race.shared_state('bulk._Segment',
                                            guard=self.lock)
        self.ctx = _mesh_context()  # recorded and launched under it
        self.boundary = []          # raw jax arrays
        self.boundary_ids = {}      # (id(raw), id(ag)) -> index
        self.boundary_ags = []      # AGInfo|None per boundary input
        self.entries = []
        self.trie_pos = state.root(self.ctx)
        self.agrefs = []            # ((ei, oi), weakref(AGInfo))
        self.ag_by_key = {}         # (ei, oi) -> weakref(AGInfo) we created
        self.tape_node = None
        self.flushed = False

    # ------------------------------------------------------------- recording
    @_guarded_by('lock')
    def add(self, op, arrays, fn, bulk_key, grad_active):
        """Append one op. Returns list of LazyRefs, or None (caller goes
        eager; segment left consistent)."""
        if self._race is not None:
            self._race.write()
        # Pass 1 — validate before mutating anything: an in-segment lazy
        # value whose NDArray carries an _ag DIFFERENT from the AGInfo this
        # segment attached to that output (detach()+attach_grad alias, a
        # variable rebound via _adopt_lazy) has lineage the segment graph
        # cannot express — the cotangent would be misrouted to the recorded
        # producer. Settle the segment and let the op dispatch eagerly.
        for nd in arrays:
            ref = nd._lazy
            if ref is not None and ref.seg is self and ref.value is None:
                ag = getattr(nd, '_ag', None)
                if ag is not None:
                    w = self.ag_by_key.get(ref.key)
                    if w is None or w() is not ag:
                        self.flush()
                        return None

        in_refs = []
        in_avals = []
        descr = []
        for nd in arrays:
            ref = nd._lazy
            ag = getattr(nd, '_ag', None)
            # Per-EDGE gradient connectivity: in eager dispatch the
            # cotangent for an input only propagates if THAT NDArray
            # carries lineage (_ag) — a detach()ed alias of a segment
            # value or of a tracked boundary array must block gradient
            # on its edge even though the underlying value is shared.
            blocked = grad_active and ag is None
            if ref is not None and ref.seg is self and ref.value is None:
                ei, oi = ref.key
                in_refs.append((1, ei, oi, blocked))
                in_avals.append(ref.aval)
                descr.append((1, ei, oi, blocked))
            else:
                raw = nd._raw if ref is None else ref.value
                # key by (buffer, lineage): two NDArrays sharing one raw
                # buffer but carrying distinct AGInfos (x and
                # x.detach()+attach_grad — the TBPTT idiom) must occupy
                # distinct boundary slots, or their gradients collapse
                # into whichever lineage was recorded first. The raw is
                # simply passed twice as replay args; jax.vjp then yields
                # a separate cotangent per slot, matching the eager
                # tape's per-edge parent links.
                bkey = (id(raw), id(ag))
                bidx = self.boundary_ids.get(bkey)
                if bidx is None:
                    bidx = len(self.boundary)
                    self.boundary.append(raw)
                    self.boundary_ids[bkey] = bidx
                    self.boundary_ags.append(ag)
                in_refs.append((0, bidx, 0, blocked))
                in_avals.append(
                    jax.ShapeDtypeStruct(raw.shape, raw.dtype))
                descr.append((0, bidx, blocked, str(raw.dtype))
                             + tuple(raw.shape))

        key = (op.name, bulk_key, grad_active, tuple(descr))
        node = self.trie_pos
        child = node.children.get(key)
        if child is None:
            cnt = node.op_counts.get(op.name, 0)
            if cnt >= _MAX_SIBLINGS:
                # this op at this position keeps arriving with fresh
                # static arguments (e.g. a Python-scalar schedule):
                # caching would compile per iteration, so go eager —
                # but re-admit every _RETRY-th attempt (a later loop
                # with stable constants then recovers the fast path)
                # up to a hard key cap that bounds total compiles.
                a = node.attempts.get(op.name, 0) + 1
                node.attempts[op.name] = a
                if cnt >= _MAX_TOTAL or a % _RETRY:
                    return None
            node.op_counts[op.name] = cnt + 1
            try:
                out = jax.eval_shape(fn, *in_avals)
            except Exception:
                return None         # dynamic shape / trace-hostile op
            child = _TrieNode()
            child.multi = isinstance(out, (tuple, list))
            outs = list(out) if child.multi else [out]
            child.out_avals = [jax.ShapeDtypeStruct(o.shape, o.dtype)
                               for o in outs]
            node.children[key] = child
            self.state.misses += 1
        else:
            self.state.hits += 1

        ei = len(self.entries)
        entry = _Entry(fn, tuple(in_refs), len(child.out_avals),
                       child.multi, not grad_active)
        self.entries.append(entry)
        self.trie_pos = child

        refs = []
        for oi, aval in enumerate(child.out_avals):
            ref = LazyRef(self, (ei, oi), aval)
            entry.out_refs.append(weakref.ref(ref))
            refs.append(ref)
        ags = self._make_ags(refs) if grad_active else [None] * len(refs)
        return refs, child.multi, ags

    def _make_ags(self, refs):
        """Create provisional AGInfos for just-recorded outputs. Called
        under the segment lock (from add), so a concurrent flush cannot
        snapshot agrefs between recording and attachment."""
        if self.tape_node is None:
            self.tape_node = _tape.TapeNode(None, [], [], 0,
                                            'bulk_segment', multi=True)
        ags = []
        for ref in refs:
            ag = _tape.AGInfo(node=self.tape_node, index=0)
            w = weakref.ref(ag)
            self.agrefs.append((ref.key, w))
            self.ag_by_key[ref.key] = w
            ags.append(ag)
        return ags

    # --------------------------------------------------------------- flushing
    def flush(self):
        with self.lock:
            if self.flushed:
                return
            if self._race is not None:
                self._race.write()
            self.flushed = True
            if not self.entries:
                _race.handoff_release(self)
                return
            self.state.flushes += 1
            # plan lookup, the segment's jitted call, publishing the refs
            with _trace.child_span('mx.bulk.flush') as span:
                self._launch(span)
            # release recording state (tape node keeps what it needs)
            self.entries = []
            self.agrefs = []
            self.ag_by_key = {}
            # happens-before edge: values are published; the recording
            # thread's next access to them is a handoff, not a race
            _race.handoff_release(self)

    def _launch(self, span):
        """Run the recorded entries as one program and publish the
        values (under the segment lock, from flush)."""
        live_keys = []
        live_refs = []
        for ei, e in enumerate(self.entries):
            for oi, w in enumerate(e.out_refs):
                ref = w()
                if ref is not None:
                    live_keys.append((ei, oi))
                    live_refs.append(ref)
        out_keys = tuple(live_keys)

        plan = self.trie_pos.plans.get(out_keys)
        if span.live:
            span.set(n_ops=len(self.entries), n_out=len(out_keys),
                     compiled=int(plan is None))
        if plan is None:
            replay = _build_replay(self.entries)

            def fwd(*boundary):
                env = replay(*boundary)
                return tuple(env[ei][oi] for ei, oi in out_keys)

            fwd = _under(self.ctx, fwd)
            plan = _Plan(jax.jit(fwd), fwd, replay, out_keys, self.ctx)
            self.trie_pos.plans[out_keys] = plan
            self.state.compiles += 1

        # under a mesh the boundary may mix arrays committed to the mesh
        # (a sharded graph's outputs) with single-device ones (labels):
        # reconciled here, once for the segment, as the eager path does
        # for each op (sharding/context.py ``lift``). What the tape node
        # and the segment's vjp keep is the lifted boundary.
        boundary = self.boundary if self.ctx is None \
            else self.ctx.lift(self.boundary)
        outs = plan.jfwd(*boundary)
        if span.live:
            span.set(**launch_attrs(outs[0] if outs else None))

        for i, ref in enumerate(live_refs):
            ref.value = outs[i]
            ref.seg = None

        if self.tape_node is not None:
            pos = {k: i for i, k in enumerate(out_keys)}
            node = self.tape_node
            node.fn = plan.fwd_raw
            node.in_vals = list(boundary)
            node.parents = list(self.boundary_ags)
            node.n_out = len(out_keys)
            node.out_avals = [r.aval for r in live_refs]
            node.vjp_fn = _SegVjp(plan, tuple(boundary))
            for key, agw in self.agrefs:
                ag = agw()
                if ag is not None and key in pos:
                    ag.index = pos[key]


def _build_replay(entries):
    entries = tuple(entries)

    def replay(*boundary):
        env = []
        for e in entries:
            ins = []
            for r in e.in_refs:
                v = boundary[r[1]] if r[0] == 0 else env[r[1]][r[2]]
                if r[3]:                   # detached/untracked edge
                    v = lax.stop_gradient(v)
                ins.append(v)
            outs = e.fn(*ins)
            outs = list(outs) if isinstance(outs, (tuple, list)) \
                else [outs]
            if e.stopgrad:
                outs = [lax.stop_gradient(o) for o in outs]
            env.append(outs)
        return env

    return replay


def _under(ctx, fn):
    """``fn`` as a plan keeps it: run, which is to say traced, under the
    mesh context its ops were recorded in. An op reads that context
    while it is traced (the Pallas gates take XLA under a mesh), and
    not every trace happens at the flush: ``backward()`` may come after
    the mesh was left, another thread may settle the segment."""
    @functools.wraps(fn)
    def under(*args):
        from .sharding.context import entered
        with entered(ctx):
            return fn(*args)

    return under


# ------------------------------------------------------------------- state
class _State(threading.local):
    def __init__(self):
        self.segment = None
        self.trie = _TrieNode()     # the root off a mesh
        self.mesh_tries = {}        # mesh fingerprint -> root under it
        self.size_override = None   # set by force(size=...) for this thread
        self.force_depth = 0
        self.disabled_depth = 0
        self.hits = 0
        self.misses = 0
        self.flushes = 0
        self.compiles = 0
        self.unbulked = 0           # eager ops the engine did not take

    def root(self, ctx):
        """The trie of the context a segment opens under: a plan traced
        under one mesh context (or none) is never replayed under
        another, whatever its ops and avals."""
        if ctx is None:
            return self.trie
        key = ctx.fingerprint()
        root = self.mesh_tries.get(key)
        if root is None:
            root = self.mesh_tries[key] = _TrieNode()
        return root


_st = _State()
_env_default = None
# Process-wide defaults (engine.set_bulk_size documents itself as the
# process default, matching the reference's MXNET_ENGINE_BULK_SIZE): the
# enabled switch and segment-size cap are module globals read by every
# thread; the force/disable depths and size_override remain thread-local
# scope overrides.
_enabled = None                 # None = resolve from env/backend
_size = int(os.environ.get('MXNET_ENGINE_BULK_SIZE', 4096))


def _mesh_context():
    """The calling thread's ``mx.sharding`` context, None off a mesh
    (``mx.sharding`` loads after the ops do, so not at import)."""
    from .sharding.context import current
    return current()


def _default_enabled():
    """Default: on for accelerator backends (where per-op launch overhead
    dominates), off for CPU (tests / debugging keep strict per-op eager)."""
    global _env_default
    if _env_default is None:
        env = os.environ.get('MXNET_ENGINE_BULK', 'auto')
        if env == '0':
            _env_default = False
        elif env == '1':
            _env_default = True
        else:
            _env_default = jax.default_backend() != 'cpu'
    return _env_default


def active():
    if _st.disabled_depth:
        return False
    if _st.force_depth:
        return True
    if _enabled is not None:
        return _enabled
    return _default_enabled()


def set_enabled(flag):
    """Explicit process-wide on/off switch (flushes the calling thread's
    pending segment; other threads' segments flush at their own sync
    points)."""
    global _enabled
    flush_current()
    _enabled = flag


def set_size(n):
    """Process-wide default segment-size cap."""
    global _size
    _size = n


def current_size():
    return _st.size_override if _st.size_override is not None else _size


def stats():
    return {'hits': _st.hits, 'misses': _st.misses,
            'flushes': _st.flushes, 'compiles': _st.compiles,
            'unbulked': _st.unbulked}


def note_unbulked(raws):
    """An op that was the engine's to take went eager, a launch of its
    own (bulking off, no bulk key, a position that keeps changing).
    Inside a ``jit`` trace the op launches nothing and is not counted."""
    for r in raws:
        if isinstance(r, jax.core.Tracer):
            return
    _st.unbulked += 1


# ---------------------------------------------------------------- the queue
class LaunchRecord:
    """What the device still had queued when a launch was enqueued: one
    output of each of the last ``WATCHED`` launches of the train path (a
    compiled call's forward, a tape node's vjp, a segment's flush, the
    fused update), of every thread, since they share the device.

    An output is held by weak reference, so nothing is kept alive and no
    donated buffer is pinned. One that was collected or deleted (donated
    to a later launch) counts as finished, and ``is_ready()`` is never
    asked of it: on a deleted array it kills the process on the CPU
    client (jax 0.9). Such an output was, as a rule, the operand of a
    later launch, which is watched itself: ``ahead`` 0 stays exact."""

    WATCHED = 8

    def __init__(self):
        self._watched = collections.deque(maxlen=self.WATCHED)
        self._lock = threading.Lock()

    def __len__(self):
        return len(self._watched)

    @staticmethod
    def _pending(ref):
        out = ref()
        return out is not None and not out.is_deleted() \
            and not out.is_ready()

    def note(self, out):
        """A launch that made ``out`` (None: nothing to watch) has just
        been enqueued. Returns ``ahead``: how many of the earlier
        launches watched had not finished on the device. 0 means the
        device had run out of work, and idled for whatever part of this
        launch outlasted what was queued."""
        with self._lock:
            pending = [r for r in self._watched if self._pending(r)]
            self._watched.clear()
            self._watched.extend(pending)
            if out is not None:
                self._watched.append(weakref.ref(out))
            return len(pending)


_launches = LaunchRecord()


def launch_attrs(out):
    """A launch span's attributes once its call has returned, for the
    launch that made the array ``out`` (one of its outputs; None or a
    tracer: nothing to watch): ``ahead`` (:class:`LaunchRecord`) and,
    where the client keeps memory statistics (the CPU's does not),
    ``in_use``: bytes in use on the fullest of the devices that hold
    ``out``, this launch's outputs allocated. Computed only while a span
    listens (``if span.live``)."""
    if not isinstance(out, jax.Array) or isinstance(out, jax.core.Tracer):
        out = None
    attrs = {'ahead': _launches.note(out)}
    used = None if out is None else bytes_in_use(out.sharding.device_set)
    if used is not None:
        attrs['in_use'] = used
    return attrs


def bytes_in_use(devices):
    """``bytes_in_use`` of the fullest of ``devices`` by their
    ``memory_stats()``; None where none keeps statistics."""
    used = [(d.memory_stats() or {}).get('bytes_in_use') for d in devices]
    return max((u for u in used if u is not None), default=None)


def reset():
    """Drop the segment trie and all cached plans (flushes first)."""
    flush_current()
    _st.trie = _TrieNode()
    _st.mesh_tries = {}


class force:
    """Context manager: force bulking on (engine.bulk) or off
    (naive_engine / profiling scopes)."""

    def __init__(self, on, size=None):
        self.on = on
        self.size = size
        self.prev_override = None

    def __enter__(self):
        if self.on:
            _st.force_depth += 1
            if self.size:
                self.prev_override = _st.size_override
                _st.size_override = self.size
        else:
            flush_current()
            _st.disabled_depth += 1
        return self

    def __exit__(self, *exc):
        if self.on:
            _st.force_depth -= 1
            if self.size:
                _st.size_override = self.prev_override
            flush_current()
        else:
            _st.disabled_depth -= 1
        return False


def _current():
    seg = _st.segment
    if seg is not None and seg.flushed:
        _st.segment = None
        seg = None
    return seg


def flush_current():
    seg = _current()
    if seg is not None:
        seg.flush()
        _st.segment = None


def materialize(ref):
    if ref.value is None and ref.seg is not None:
        seg = ref.seg
        seg.flush()
        _race.handoff_acquire(seg)


# ------------------------------------------------------------ dispatch hook
def try_record(op, arrays, fn, bulk_key, grad_active):
    """Offer an op to the bulking engine. Returns ``(refs, multi, ags)``
    — the output LazyRefs (caller wraps them, assigns the provisional
    AGInfos, then calls cap_check) — or None (caller dispatches
    eagerly). AGInfo creation happens inside the segment lock so a
    concurrent flush can never miss them."""
    if not active():
        return None
    for nd in arrays:
        ref = nd._lazy
        if ref is None:
            raw = nd._raw
            if raw is None or isinstance(raw, jax.core.Tracer):
                return None
        elif ref.value is None and ref.seg is not None \
                and ref.seg is not _st.segment:
            # lazy value from a foreign (e.g. other-thread) segment:
            # settle it before taking our own lock (avoids lock nesting)
            fseg = ref.seg
            fseg.flush()
            _race.handoff_acquire(fseg)
    while True:
        seg = _current()
        if seg is None:
            seg = _Segment(_st)
            _st.segment = seg
        with seg.lock:
            if seg.flushed:
                # another thread flushed this segment between _current()
                # and the lock; recording into it would orphan the
                # outputs — start a fresh segment
                _st.segment = None
                continue
            return seg.add(op, arrays, fn, bulk_key, grad_active)


def cap_check():
    """Flush if the current segment hit the bulk-size cap. Called by the
    dispatcher after outputs (and their AGInfos) are fully wired."""
    seg = _current()
    if seg is not None and len(seg.entries) >= current_size():
        seg.flush()
        _st.segment = None
