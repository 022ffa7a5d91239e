"""Operator library.

TPU-native replacement for the reference's 201 kLoC ``src/operator/`` tree
(584 NNVM_REGISTER_OP sites — SURVEY §2.1). Roughly 90% of those ops are
thin wrappers over jax.numpy / jax.lax, which XLA fuses and tiles onto the
MXU; the remainder (fused attention, specialized reductions) get Pallas
kernels under :mod:`mxnet_tpu.ops.pallas` (flash attention, fused norms).

Importing this package registers all ops into the global registry; the
frontend namespaces (mx.nd, mx.np, mx.npx) are then code-generated from the
registry, mirroring ``_init_op_module`` (reference python/mxnet/base.py:600).
"""

from . import registry
from .registry import apply_op, get_op, list_ops, register

from . import creation      # noqa: F401
from . import elemwise      # noqa: F401
from . import reduce        # noqa: F401
from . import manipulation  # noqa: F401
from . import linalg        # noqa: F401
from . import random_ops    # noqa: F401
from . import nn            # noqa: F401
from . import contrib       # noqa: F401
from . import experts       # noqa: F401
from . import ssm           # noqa: F401
from . import kda           # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import fft_ops       # noqa: F401
from . import quantization_ops  # noqa: F401
from . import legacy_ops    # noqa: F401
from . import numpy_extras  # noqa: F401
