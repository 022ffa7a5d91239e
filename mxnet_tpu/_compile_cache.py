"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key, so it must not move between
runs: it is either what the environment says
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself — nothing is set
in code then) or one fixed path under the checkout, ``.jax_cache``
(git-ignored). Entry points that compile large programs call
:func:`place` once before their first jit (``chip_smoke.py``).
"""

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')


def place():
    """Return the cache directory in effect, setting the checkout's own
    only when the environment names none."""
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', CHECKOUT_CACHE)
    return jax.config.jax_compilation_cache_dir
