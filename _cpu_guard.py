"""Force CPU-only jax in this process, with ``n_devices`` virtual devices.

Import BEFORE any jax backend initializes. Used by tests and by
``__graft_entry__.dryrun_multichip`` for a virtual CPU mesh. The TPU
backend's factory is removed before backend init, so a CPU-only process
never loads the TPU library (one process at a time may hold it).
"""

import os


def force_cpu(n_devices=None):
    import jax
    # pallas registers TPU lowerings at import; it must load while the
    # 'tpu' platform is still known, or later imports crash
    import jax.experimental.pallas  # noqa: F401
    import jax.experimental.pallas.tpu  # noqa: F401
    from jax._src import xla_bridge as _xb
    if n_devices is not None and 'host_platform_device_count' not in \
            os.environ.get('XLA_FLAGS', ''):
        os.environ['XLA_FLAGS'] = (
            os.environ.get('XLA_FLAGS', '') +
            f' --xla_force_host_platform_device_count={n_devices}').strip()
    _xb._backend_factories.pop('tpu', None)
    os.environ['JAX_PLATFORMS'] = ''
    jax.config.update('jax_platforms', 'cpu')
