"""Context-scoped PRNG resource.

The reference gives every op a per-device random resource through
``ResourceManager`` (include/mxnet/resource.h:43-51) so user code never
touches generator state. JAX instead wants explicit keys. This module hides
the keys: stochastic ops call :func:`next_key`, which

* in eager mode splits a process-global key (seeded by ``mx.random.seed``),
* under graph capture (hybridize / CachedOp tracing) splits a *traced* key
  supplied by the trace context, so the compiled executable takes the key as
  an input and stays pure.
"""

import threading

import jax
import numpy as _np
from jax._src import core as _core

_state = threading.local()


def _global():
    if getattr(_state, 'key', None) is None:
        _state.key = jax.random.PRNGKey(_np.random.randint(0, 2**31 - 1))
    return _state.key


def seed(seed_state, ctx=None):  # noqa: ARG001 - ctx kept for API parity
    """Seed the global generator (reference: python/mxnet/random.py:seed)."""
    _state.key = jax.random.PRNGKey(int(seed_state))


class _TraceKeyProvider:
    """Splits subkeys off a traced base key during graph capture."""

    def __init__(self, base_key):
        self.base_key = base_key
        self.count = 0

    def next_key(self):
        self.count += 1
        return jax.random.fold_in(self.base_key, self.count)


def _providers():
    # THREAD-LOCAL: graph capture happens on whichever thread traces the
    # block; a process-global stack would hand another thread's eager
    # next_key() a traced provider (leaked tracers) whenever two threads
    # share one hybridized block (multi-threaded inference).
    ps = getattr(_state, 'providers', None)
    if ps is None:
        ps = _state.providers = []
    return ps


def push_trace_provider(base_key):
    prov = _TraceKeyProvider(base_key)
    _providers().append(prov)
    return prov


def pop_trace_provider():
    return _providers().pop()


def next_key():
    """Next PRNG subkey — traced provider if capturing, else eager global.

    The eager split runs under ``ensure_compile_time_eval``: inside an
    outer trace (eval_shape / jit replaying a symbol) omnistaging would
    otherwise stage the split and store a *tracer* into the global state,
    poisoning every later eager op (leaked-tracer errors)."""
    ps = _providers()
    if ps:
        return ps[-1].next_key()
    if _core.trace_state_clean():
        # normal eager path: async split, no device sync
        key = _global()
        key, sub = jax.random.split(key)
        _state.key = key
        return sub
    # inside an outer trace: escape it so the stored key stays concrete —
    # ensure_compile_time_eval *blocks*, so it must not run per eager call
    with jax.ensure_compile_time_eval():
        key = _global()
        key, sub = jax.random.split(key)
        _state.key = key
    return sub


def current_numpy_rng():
    """Host-side numpy Generator for initializers/data augmentation."""
    if not hasattr(_state, 'np_rng'):
        _state.np_rng = _np.random.default_rng()
    return _state.np_rng


def get_state():
    """Snapshot every RNG stream a training step consumes, as plain
    host data (picklable, checkpointable).

    Covers the eager PRNG key (dropout & friends via :func:`next_key`),
    the host-side numpy Generator (initializers / data augmentation),
    and numpy's legacy global stream (data-pipeline shuffles). Restoring
    the snapshot with :func:`set_state` makes a resumed run draw the
    exact same sequences as the uninterrupted one.
    """
    return {
        'key': _np.asarray(_global()).copy(),
        'np_rng': current_numpy_rng().bit_generator.state,
        'np_global': _np.random.get_state(),
    }


def set_state(state):
    """Restore a snapshot taken by :func:`get_state` (this thread)."""
    import jax.numpy as jnp
    _state.key = jnp.asarray(state['key'])
    current_numpy_rng().bit_generator.state = state['np_rng']
    _np.random.set_state(state['np_global'])
