"""``mx.telemetry`` — distributed tracing, unified metrics and the
flight recorder.

Zero-dependency observability for the whole stack (serving tier,
dist_async training, elastic checkpoints):

* **Spans + context propagation** (:mod:`.trace`): ``with
  telemetry.span('train.step', step=i): ...`` — spans nest via
  thread-local context, cross process boundaries as one optional
  ``tc`` field on every RPC envelope (injected by ``RpcClient``,
  adopted by ``RpcServer``), and land in a bounded per-process ring
  buffer (the flight recorder). One user request through the router =
  one connected trace: routing → retry/failover attempts → replica
  admission → queue wait → prefill chunks → per-step decode. The
  recorder's clock is the wall clock; every span is also a
  ``jax.profiler.TraceAnnotation`` for its body, so a running
  ``jax.profiler`` trace holds it on the clock of the device's
  operations. The Gluon train path opens ``mx.graph.*``,
  ``mx.tape.*``, ``mx.bulk.flush`` and ``mx.trainer.*`` child spans
  (docs/observability.md, "Tracing a training loop").
* **Metrics registry** (:mod:`.metrics`): Counter / Gauge / Histogram
  with fixed mergeable log-scale buckets; the serving/RPC/training
  ``stats()`` surfaces register into it, the router aggregates
  fleet-wide over the RPC ``metrics`` verb, and
  :func:`render_prometheus` emits the text exposition format.
* **Export** (:mod:`.export`): Chrome-trace/Perfetto JSON with
  cross-process clock normalization off RPC ping timestamps, plus the
  span-tree formatter behind ``tools/trace_dump.py``.

Env knobs: ``MXNET_TELEMETRY`` (default on; ``0`` disables tracing,
both sinks — the disabled path is a near-no-op, and so is a
``child_span`` with no caller's context and no profile being taken),
``MXNET_TELEMETRY_BUFFER`` (ring
capacity, default 4096 events), ``MXNET_TELEMETRY_SAMPLE`` (root-span
sampling fraction, default 1.0). See docs/observability.md.
"""

from . import trace
from . import metrics
from . import export

from .trace import (span, child_span, attach, emit, current_tc, enabled,
                    configure, events, clear, snapshot_buffer,
                    note_clock, clock_offsets, proc_name, walltime)
from .metrics import (Counter, Gauge, Histogram, Reservoir,
                      MetricsRegistry, default_registry, counter, gauge,
                      histogram, register_collector,
                      unregister_collector, merge_snapshots,
                      render_prometheus)
from .export import (merge_buffers, trace_ids, trace_tree, format_tree,
                     chrome_doc, export_chrome_trace, dump_json)

__all__ = [
    'trace', 'metrics', 'export',
    # spans / flight recorder
    'span', 'child_span', 'attach', 'emit', 'current_tc', 'enabled',
    'configure', 'events', 'clear', 'snapshot_buffer', 'note_clock',
    'clock_offsets', 'proc_name', 'walltime',
    # metrics
    'Counter', 'Gauge', 'Histogram', 'Reservoir', 'MetricsRegistry',
    'default_registry', 'counter', 'gauge', 'histogram',
    'register_collector', 'unregister_collector', 'merge_snapshots',
    'render_prometheus',
    # export
    'merge_buffers', 'trace_ids', 'trace_tree', 'format_tree',
    'chrome_doc', 'export_chrome_trace', 'dump_json',
]
