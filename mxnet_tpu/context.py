"""Device contexts: ``mx.cpu()``, ``mx.tpu()``.

TPU-native analog of the reference's ``python/mxnet/context.py`` and the C++
``Context`` enum (include/mxnet/base.h:92-116). A Context names a *logical*
device; it resolves lazily to a concrete ``jax.Device``. ``mx.gpu()`` is kept
as an alias that resolves to an accelerator if one exists (so reference
example code runs unchanged), but the first-class accelerator is TPU.

Unlike the reference there is no kCPUPinned/kCPUShared: XLA manages staging
buffers, and DataLoader workers exchange host numpy arrays.
"""

import threading

_DEVICE_KINDS = ('cpu', 'tpu', 'gpu')


class Context:
    """A logical device. ``Context('tpu', 0)`` maps to ``jax.devices()[0]``.

    Mirrors reference Context semantics: hashable, comparable, usable in a
    ``with`` block to set the thread-local default context
    (context.py:`_current` stack in the reference).
    """

    _thread = threading.local()

    devtype2str = {1: 'cpu', 2: 'gpu', 3: 'cpu_pinned', 5: 'cpu_shared', 6: 'tpu'}
    devstr2type = {v: k for k, v in devtype2str.items()}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError(f'unknown device type {device_type!r}')
            self.device_type = device_type
            self.device_id = device_id
        self._jax_device = None

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    def to_jax(self):
        """Resolve to a concrete ``jax.Device`` (lazily, cached).

        Always a process-LOCAL device: under multi-host SPMD
        (jax.distributed), jax.devices() lists every process's devices
        and indexing it would hand a remote (non-addressable) device to
        eager ops — each host's Context must map to its own chips (the
        reference's per-worker ctx in dist training behaves the same)."""
        if self._jax_device is None:
            import jax
            kind = self.device_type
            if kind in ('cpu', 'cpu_pinned', 'cpu_shared'):
                # backend='cpu' queries the CPU client explicitly — the
                # default-backend list has no CPU devices on TPU hosts
                devs = jax.local_devices(backend='cpu') \
                    if _has_platform('cpu') else jax.local_devices()
                index = self.device_id % len(devs)
            else:
                # tpu (or gpu alias): any non-cpu accelerator backend.
                # No CPU stand-in and no wrap-around: a chip that is not
                # there is an error, not chip 0
                devs = [d for d in jax.local_devices()
                        if d.platform != 'cpu']
                if not devs:
                    raise RuntimeError(
                        f'{self!r}: no accelerator device; '
                        f'jax backend is {jax.default_backend()!r}')
                if not 0 <= self.device_id < len(devs):
                    raise ValueError(
                        f'{self!r}: device_id out of range, '
                        f'{len(devs)} accelerator device(s) present')
                index = self.device_id
            self._jax_device = devs[index]
        return self._jax_device

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __repr__(self):
        return f'{self.device_type}({self.device_id})'

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(self._thread, 'stack'):
            self._thread.stack = []
        self._thread.stack.append(self)
        return self

    def __exit__(self, *exc):
        self._thread.stack.pop()

    def empty_cache(self):
        """Reference frees the memory-pool here (storage.h ReleaseAll).

        XLA owns device memory; we clear jax's live-buffer caches where
        possible. Currently a no-op placeholder.
        """

    @classmethod
    def default_ctx(cls):
        stack = getattr(cls._thread, 'stack', None)
        if stack:
            return stack[-1]
        return _default_context()


def _has_platform(name):
    import jax
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


_DEFAULT = None


def _default_context():
    """Default context = the best device available: tpu if present else cpu."""
    global _DEFAULT
    if _DEFAULT is None:
        import jax
        plat = jax.default_backend()
        _DEFAULT = Context('cpu' if plat == 'cpu' else 'tpu', 0)
    return _DEFAULT


def cpu(device_id=0):
    """Return a CPU context."""
    return Context('cpu', device_id)


def cpu_pinned(device_id=0):
    """Alias of cpu() — XLA stages host transfers itself."""
    return Context('cpu_pinned', device_id)


def tpu(device_id=0):
    """Return a TPU context — the headline API of this framework."""
    return Context('tpu', device_id)


def gpu(device_id=0):
    """Compatibility alias: resolves to the accelerator backend (TPU here).

    Kept so reference example code (`mx.gpu(0)`) runs unchanged on TPU.
    """
    return Context('gpu', device_id)


def num_gpus():
    """Number of accelerator devices visible (reference context.py:num_gpus)."""
    import jax
    return len([d for d in jax.devices() if d.platform != 'cpu'])


def num_tpus():
    import jax
    return len([d for d in jax.devices() if d.platform != 'cpu'])


def current_context():
    """The thread-local default context (reference context.py:current_context)."""
    return Context.default_ctx()
