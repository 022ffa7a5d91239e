"""``mx.serve`` — dynamic-batching inference serving runtime.

The training stack's whole design (hybridize → one XLA executable,
static shapes, bucketed retracing) is exactly what a serving system
needs, so this package is thin: a model registry that lints and
pre-warms (:class:`ModelRunner`), a coalescing request batcher over
bucketed shapes (:class:`DynamicBatcher`), a continuous-batching decode
loop for generate workloads over a paged KV cache
(:class:`DecodeServer` + :class:`PageAllocator`), typed admission
control (:class:`ServerOverloaded` & friends) and serving metrics that
surface in ``mx.profiler.dumps()``'s Serving section and
:func:`stats`.

A replicated tier rides on top: :class:`Replica` hosts a DecodeServer
behind the kvstore RPC transport and :class:`Router` spreads traffic
over N of them with heartbeat ejection/re-admission, exactly-once
failover via the ``(client, seq)`` dedup window, least-loaded routing,
hedged retries and zero-downtime hot-swap (docs/deployment.md).

Environment knobs: ``MXNET_SERVE_BUCKETS``, ``MXNET_SERVE_MAX_WAIT_US``,
``MXNET_SERVE_QUEUE_DEPTH``, ``MXNET_SERVE_DEADLINE_MS``,
``MXNET_SERVE_FAULT_SPEC``, ``MXNET_SERVE_PAGE_SIZE``,
``MXNET_SERVE_PAGES``, ``MXNET_SERVE_PREFILL_CHUNK``,
``MXNET_SERVE_PREFIX_CACHE``,
``MXNET_SERVE_DRAIN_S``, ``MXNET_SERVE_HEDGE_MS`` (docs/env_vars.md;
the design docs are docs/serving.md and docs/deployment.md).
"""

from .errors import ServeError, ServerOverloaded, DeadlineExceeded, \
    ServerClosed, PagesExhausted, NoHealthyReplicas
from .buckets import parse_buckets, pick_bucket, pow2_bucket, \
    default_buckets, chunk_spans
from .runner import ModelRunner
from .batcher import DynamicBatcher
from .decode import DecodeServer
from .pages import PageAllocator, chain_key
from .replica import Replica
from .router import Router
from .metrics import ServingMetrics, registry as _registry
from . import faults
from . import pages

__all__ = ['ModelRunner', 'DynamicBatcher', 'DecodeServer',
           'PageAllocator', 'Replica', 'Router', 'ServingMetrics',
           'ServeError', 'ServerOverloaded', 'PagesExhausted',
           'DeadlineExceeded', 'ServerClosed', 'NoHealthyReplicas',
           'parse_buckets', 'pick_bucket', 'pow2_bucket',
           'default_buckets', 'chunk_spans', 'chain_key', 'faults',
           'pages', 'stats']


def stats():
    """Snapshot of every live server's metrics: name -> stats dict
    (the same payload the profiler's Serving section renders)."""
    return {name: m.snapshot() for name, m in _registry().items()}
