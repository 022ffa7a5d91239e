"""host-transfer: host round-trips inside the compiled step.

The whole point of the fused step is that the TPU runs ahead of the
host (async dispatch ≙ the reference ThreadedEngine). A callback
primitive inside the jaxpr stalls the device on the host every
iteration — the static equivalent of the `asnumpy()`-in-the-training-
loop bug the profiler can only show after the fact, and what JAX's
transfer-guard work catches dynamically (PAPERS.md).

Flagged:

* ``pure_callback`` / ``io_callback`` / ``debug_print`` and
  ``debug_callback`` (from ``jax.debug.print`` / ``jax.debug.callback``)
  — error for pure/io (semantic host dependence), warning for the debug
  pair (usually leftover instrumentation);
* ``infeed`` / ``outfeed`` — warning (legitimate but rare, and never
  something a model-zoo forward should contain);
* ``device_put`` eqns with an explicit device/memory-kind target —
  warning (cross-memory traffic pinned inside the step). Plain
  ``device_put`` of captured numpy constants is the large-constant
  rule's business and is not double-reported here.

Block-level: a graph that *fell back to eager* because of a
dynamic-output-shape op (``boolean_mask``/``unique``...; Op metadata
``host_transfer=True`` in ops/registry.py) executes op-by-op with a
host sync per dynamic op — reported as a warning with the op names.
"""

from . import register_rule
from ..walker import iter_eqns, eqn_op, source_location

CALLBACK_SEVERITY = {
    'pure_callback': 'error',
    'io_callback': 'error',
    'callback': 'error',
    'debug_callback': 'warning',
    'debug_print': 'warning',
    'infeed': 'warning',
    'outfeed': 'warning',
}


def _device_put_explicit(eqn):
    """True when device_put moves data across *memory kinds* (e.g.
    pinned_host <-> device HBM). Plain const uploads also carry a
    concrete device in ``devices`` (capturing an already-placed array
    records its sharding), so a device target alone is not a finding —
    only memory-kind transfers are pinned traffic the user asked for."""
    devices = eqn.params.get('devices', ())
    srcs = eqn.params.get('srcs', ())
    for d in list(devices) + list(srcs):
        if d is None:
            continue
        if isinstance(d, str):          # bare memory-kind string
            return True
        if type(d).__name__ == 'TransferToMemoryKind':
            return True
        mk = getattr(d, 'memory_kind', None)
        if mk is not None and mk not in ('device', 'default'):
            return True
    return False


@register_rule('host-transfer')
def run(graph, report, config):
    for eqn, depth in iter_eqns(graph.jaxpr):
        name = eqn.primitive.name
        sev = CALLBACK_SEVERITY.get(name)
        if sev is not None:
            op = eqn_op(eqn)
            via = f' (op {op.name!r})' if op is not None else ''
            report.add(
                'host-transfer', sev,
                f'{name} inside the compiled step{via} — the device '
                'stalls on the host every iteration; move it out of '
                'the step or behind a sync point',
                location=source_location(eqn), primitive=name,
                depth=depth)
        elif name == 'device_put' and _device_put_explicit(eqn):
            report.add(
                'host-transfer', 'warning',
                'device_put with an explicit placement inside the step '
                '— pinned cross-memory traffic per iteration',
                location=source_location(eqn), primitive=name,
                depth=depth)
    # block-level: dynamic-shape eager fallback = host sync per op
    if graph.block is not None:
        from ..walker import GraphView  # noqa: F401 (doc cross-ref)
        graph_notes = [n for n in graph.notes if 'eager' in n]
        if graph_notes:
            from ...ops import registry
            dyn_ops = sorted(n for n, op in registry.list_ops().items()
                             if getattr(op, 'host_transfer', False))
            report.add(
                'host-transfer', 'warning',
                f'{graph.name} executes eagerly op-by-op '
                f'({graph_notes[0]}); dynamic-shape ops '
                f'(e.g. {", ".join(dyn_ops[:4])}...) force a host '
                'round-trip per call — consider masked/padded '
                'formulations to stay compiled',
                fallback=True)
