"""Roofline-driven performance lints over the analysis.costs pass.

Four rules, all fed by the same cached :func:`costs.cost_of_graph`
report — they turn aggregate observations (a low train MFU, int8
slower than bf16, a small share of the HBM roofline) into findings that
point at equations:

==========================  ==================================================
rule                        catches
==========================  ==================================================
unfused-dequant             an int8 dequantize living as its own equation
                            chain next to a matmul instead of a fused
                            epilogue/prologue — the exact pattern behind
                            int8 losing to bf16 (0.63x on an earlier
                            development device; docs/quantization.md
                            round-trip note)
bandwidth-bound-chain       a data-dependent run of elementwise/reduce
                            equations whose arithmetic intensity sits below
                            machine balance and which no ops/pallas fused
                            kernel covers — the machine-generated Pallas
                            target list (ROADMAP item 5)
small-collective            a psum/reduce-scatter whose payload is under the
                            kvstore fusion-buffer bucket threshold — an
                            unbucketed gradient push (ROADMAP item 2).
                            Collectives over a *named mesh axis* (the
                            mx.sharding TP/FSDP psums) are in-step GSPMD
                            collectives, not kvstore pushes: always info
                            with ``mesh_axes`` data, never the bucketing
                            warning
padding-waste               worst-case FLOPs the serve pad-to-bucket policy
                            wastes above ``MXNET_ANALYSIS_PAD_WASTE_FRAC``,
                            per MXNET_SERVE_BUCKETS bucket
==========================  ==================================================

Suppression: a block may declare ``_analysis_suppressions = {rule:
justification}``; the walker collects these into
``GraphView.suppressions`` and a suppressed rule downgrades its findings
to info with the justification attached (never silently dropped). The
dead-man's-switch tests pass ``ignore_suppressions=True`` to prove the
detector still fires underneath the suppression.
"""

from jax.extend import core as _core

from . import register_rule
from ..costs import (CHEAP_PRIMS, COLLECTIVE_PRIMS, MOVEMENT_PRIMS,
                     REDUCE_PRIMS, cost_of_graph, prim_flops)
from ..walker import eqn_op, iter_jaxprs, source_location

_INT_DTYPES = ('int8', 'uint8', 'int32')
_CALL_PRIMS = ('pjit', 'closed_call', 'core_call', 'custom_jvp_call',
               'custom_vjp_call', 'remat', 'remat2', 'checkpoint')


def _suppressed(graph, config, rule):
    """Justification string when the graph suppresses ``rule``
    (and the caller didn't ask to ignore suppressions), else None."""
    if config.get('ignore_suppressions'):
        return None
    return graph.suppressions.get(rule)


def _emit(graph, report, config, rule, severity, message, **kw):
    why = _suppressed(graph, config, rule)
    if why is not None:
        kw.setdefault('data', {})
        report.add(rule, 'info',
                   f'{message} [suppressed: {why}]',
                   suppressed=True, justification=why,
                   **{k: v for k, v in kw.items() if k != 'data'},
                   **kw.get('data', {}))
    else:
        report.add(rule, severity, message,
                   **{k: v for k, v in kw.items() if k != 'data'},
                   **kw.get('data', {}))


# --------------------------------------------------------- unfused-dequant
_CHASE_PRIMS = CHEAP_PRIMS | MOVEMENT_PRIMS | REDUCE_PRIMS
_MATMULS = ('dot_general', 'conv_general_dilated')


def _find_dequant(start_var, defs, max_steps=48):
    """Walk a matmul operand backward through cheap/movement equations
    looking for an int->float ``convert_element_type`` (the dequantize).
    Returns (dequant_eqn, crossed_requant) or (None, False).

    Only int8 sources, or int32 sources produced by a matmul (the int8
    accumulator), count — int32 iota/counter upcasts are not dequants.
    """
    frontier = [start_var]
    seen = set()
    crossed_requant = False
    steps = 0
    while frontier and steps < max_steps:
        v = frontier.pop()
        if not isinstance(v, _core.Var) or id(v) in seen:
            continue
        seen.add(id(v))
        eqn = defs.get(id(v))
        if eqn is None:
            continue
        steps += 1
        name = eqn.primitive.name
        if name == 'convert_element_type':
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            src_dt, dst_dt = str(src.dtype), str(dst.dtype)
            dst_float = dst_dt.startswith('float') or dst_dt == 'bfloat16'
            if src_dt in _INT_DTYPES and dst_float:
                src_def = defs.get(id(eqn.invars[0])) \
                    if isinstance(eqn.invars[0], _core.Var) else None
                if src_dt in ('int8', 'uint8') or (
                        src_def is not None
                        and src_def.primitive.name in _MATMULS):
                    return eqn, crossed_requant
                continue
            if dst_dt in ('int8', 'uint8'):
                crossed_requant = True      # f32 -> int8: a requantize
                frontier.extend(eqn.invars)
                continue
            frontier.extend(eqn.invars)     # float<->float cast: chase on
            continue
        if name in _CHASE_PRIMS:
            frontier.extend(eqn.invars)
        elif name in _CALL_PRIMS and _cheap_body(eqn):
            # round/clip from quantize_v2 and relu trace as pjit /
            # custom_jvp_call wrappers — transparent when the body is
            # pure elementwise
            frontier.extend(eqn.invars)
    return None, False


def _cheap_body(eqn):
    """True when every equation in the call's sub-jaxpr(s) is cheap
    elementwise/movement — the wrapper is chase-transparent."""
    from ..walker import _sub_jaxprs
    subs = list(_sub_jaxprs(eqn))
    if not subs:
        return False
    for sub in subs:
        for e in sub.eqns:
            if e.primitive.name in _CHASE_PRIMS:
                continue
            if e.primitive.name in _CALL_PRIMS and _cheap_body(e):
                continue
            return False
    return True


def _fused_epilogue(deq, defs):
    """True when the found dequantize equation is attributed to a
    ``fused_kernel=True`` op AND, if its source is an int32 matmul
    accumulator, that matmul shares the attribution — i.e. the scale
    multiply already lives in the producing op's epilogue (one kernel
    on TPU, one fused jaxpr region off-TPU)."""
    dop = eqn_op(deq)
    if dop is None or not getattr(dop, 'fused_kernel', False):
        return False
    src = deq.invars[0]
    src_def = defs.get(id(src)) if isinstance(src, _core.Var) else None
    if src_def is not None and src_def.primitive.name in _MATMULS:
        return eqn_op(src_def) is dop
    return True


@register_rule('unfused-dequant')
def unfused_dequant(graph, report, config):
    for jaxpr in iter_jaxprs(graph.jaxpr):
        defs = {id(v): eqn for eqn in jaxpr.eqns for v in eqn.outvars}
        for eqn in jaxpr.eqns:
            if eqn.primitive.name not in _MATMULS:
                continue
            for operand in eqn.invars[:2]:
                if not isinstance(operand, _core.Var):
                    continue
                deq, crossed = _find_dequant(operand, defs)
                if deq is None:
                    continue
                if _fused_epilogue(deq, defs):
                    # scale-in-epilogue: the dequantize is part of a
                    # registered fused-kernel op's body (int32 accum ->
                    # scale -> cast inside quantized_dense & co) — the
                    # fused form this rule exists to demand. Inline
                    # unattributed dequants still fire (the planted-
                    # finding dead-man's-switch in tests/test_perf_lint
                    # proves it).
                    continue
                dt = str(operand.aval.dtype)
                if crossed or dt in ('int8', 'uint8'):
                    msg = ('int8 dequantize -> float compute -> '
                           'requantize round trip between int8 matmuls '
                           '— three full HBM passes that a fused '
                           'requantize epilogue on the first matmul '
                           'would eliminate (the pattern behind int8 '
                           'trailing bf16)')
                    pattern = 'dequant-requant-round-trip'
                else:
                    msg = (f'int8 dequantize feeds a {dt} '
                           f'{eqn.primitive.name} as a separate '
                           'equation — the scale multiply belongs in '
                           'the matmul epilogue (fused dequant), not '
                           'as its own HBM round trip')
                    pattern = 'dequant-before-matmul'
                _emit(graph, report, config, 'unfused-dequant',
                      'warning', msg,
                      location=source_location(deq) or
                      source_location(eqn),
                      data={'pattern': pattern,
                            'matmul': eqn.primitive.name,
                            'operand_dtype': dt,
                            'dequant_bytes': int(
                                deq.outvars[0].aval.size
                                * deq.outvars[0].aval.dtype.itemsize)})
                break       # one finding per matmul is enough


# --------------------------------------------------- bandwidth-bound-chain
_FUSABLE = CHEAP_PRIMS | REDUCE_PRIMS | frozenset(
    ('convert_element_type', 'broadcast_in_dim', 'reshape', 'transpose',
     'squeeze', 'expand_dims'))


def _chain_stats(run, balance, min_eqns, min_bytes):
    """(flops, moved, intensity) when ``run`` qualifies as a
    bandwidth-bound chain on the roofline thresholds — attribution to a
    fused kernel is judged separately (``_chain_fused``) so coverage
    accounting can see both sides. None otherwise."""
    compute = [e for e in run if e.primitive.name in CHEAP_PRIMS
               or e.primitive.name in REDUCE_PRIMS]
    if len(compute) < min_eqns:
        return None
    flops = 0
    moved = 0
    for e in run:
        f, _ = prim_flops(e)
        flops += f
        moved += sum(int(v.aval.size * v.aval.dtype.itemsize)
                     for v in (*e.invars, *e.outvars)
                     if isinstance(v, _core.Var))
    if moved < min_bytes:
        return None
    intensity = flops / moved if moved else 0.0
    if intensity >= balance:
        return None
    return flops, moved, intensity


def _chain_fused(run):
    """True when any equation of the run is attributed to an op that
    dispatches to a hand-fused kernel on TPU — the run traces here as
    that op's XLA fallback chain, not a fusion target."""
    for e in run:
        op = eqn_op(e)
        if op is not None and getattr(op, 'fused_kernel', False):
            return True
    return False


def chain_coverage(graph, config=None):
    """Fraction of bandwidth-bound-chain bytes covered by registered
    fused kernels: chains are found exactly as the
    ``bandwidth-bound-chain`` rule finds them, but chains attributed to
    a ``fused_kernel=True`` op count as covered instead of exempt.
    Returns (covered_bytes / total_chain_bytes, total_chain_bytes) —
    (1.0, 0) for a graph with no qualifying chains. A kernel regression
    (a fused op silently falling back to an unattributed chain) shows
    up as a coverage drop, not just throughput drift."""
    config = config or {}
    cost = cost_of_graph(graph)
    balance = cost.machine_balance
    min_eqns = int(config.get('bw_chain_min_eqns', 4) or 4)
    min_bytes = int(config.get('bw_chain_min_bytes', 1 << 20) or 1 << 20)
    covered = total = 0

    def tally(run):
        nonlocal covered, total
        stats = _chain_stats(run, balance, min_eqns, min_bytes)
        if stats is None:
            return
        _, moved, _ = stats
        total += moved
        if _chain_fused(run):
            covered += moved

    for jaxpr in iter_jaxprs(graph.jaxpr):
        run = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _FUSABLE:
                run.append(eqn)
                continue
            tally(run)
            run = []
        tally(run)
    return (covered / total if total else 1.0), total


def _flush_chain(run, graph, report, config, jaxpr_depth, balance,
                 min_eqns, min_bytes):
    stats = _chain_stats(run, balance, min_eqns, min_bytes)
    if stats is None:
        return
    if _chain_fused(run):
        return
    flops, moved, intensity = stats
    run_ids = {id(v) for e in run for v in e.outvars}
    boundary = 0
    for e in run:
        boundary += sum(int(v.aval.size * v.aval.dtype.itemsize)
                        for v in e.invars
                        if isinstance(v, _core.Var)
                        and id(v) not in run_ids)
    ops_named = sorted({op.name for op in map(eqn_op, run)
                        if op is not None})
    via = f' (ops: {", ".join(ops_named)})' if ops_named else ''
    _emit(graph, report, config, 'bandwidth-bound-chain', 'info',
          f'{len(run)} chained elementwise/reduce equation(s) at '
          f'intensity {intensity:.2f} flop/B — far below machine '
          f'balance {balance:.0f}; a fused (Pallas) kernel would cut '
          f'~{(moved - boundary) / 1e6:.2f} MB of HBM round trips per '
          f'step{via}',
          location=source_location(run[0]),
          data={'eqns': len(run), 'flops': int(flops),
                'bytes_moved': int(moved),
                'intensity': round(intensity, 3),
                'primitives': sorted({e.primitive.name for e in run}),
                'depth': jaxpr_depth,
                'fusable_savings_bytes': int(max(0, moved - boundary))})


@register_rule('bandwidth-bound-chain')
def bandwidth_bound_chain(graph, report, config):
    cost = cost_of_graph(graph)
    balance = cost.machine_balance
    min_eqns = int(config.get('bw_chain_min_eqns', 4) or 4)
    min_bytes = int(config.get('bw_chain_min_bytes', 1 << 20) or 1 << 20)
    for depth, jaxpr in enumerate(iter_jaxprs(graph.jaxpr)):
        # consecutive fusable equations in program order — the same
        # adjacency XLA's fusion pass works over. Param reshapes and
        # broadcasts interleave with the compute (BN: reshape(mean),
        # sub, reshape(gamma), mul, ...), so dataflow connectivity is
        # not required within a run; a matmul/collective/control-flow
        # equation ends it.
        run = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _FUSABLE:
                run.append(eqn)
                continue
            _flush_chain(run, graph, report, config, depth, balance,
                         min_eqns, min_bytes)
            run = []
        _flush_chain(run, graph, report, config, depth, balance,
                     min_eqns, min_bytes)


# -------------------------------------------------------- small-collective
def _mesh_axes(eqn):
    """Named mesh axes a collective reduces over, e.g. ('dp',) for a
    psum bound to an ``mx.sharding`` mesh axis — empty for positional
    axes (vmap ints) and for axis-free collectives."""
    axes = eqn.params.get('axes', None)
    if axes is None:
        axes = eqn.params.get('axis_name', ())
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


@register_rule('small-collective')
def small_collective(graph, report, config):
    from ...kvstore.fusion import fusion_buffer_bytes
    threshold = int(config.get('small_collective_bytes',
                               fusion_buffer_bytes()))
    scalar_floor = 4096     # scalar/loss psums are unavoidable: info
    from ..walker import iter_eqns
    # axis names that belong to a real device mesh: the sharding
    # context's axes plus any shard_map mesh in the graph. A pmap
    # axis_name is NOT one — its psum is the kvstore-style replica
    # all-reduce the bucketing remedy exists for.
    known = set((getattr(graph, 'sharding', None) or {}).get('axes', {}))
    for eqn, _ in iter_eqns(graph.jaxpr):
        names = getattr(eqn.params.get('mesh', None), 'axis_names', None)
        if names:
            known.update(a for a in names if isinstance(a, str))
    for eqn, depth in iter_eqns(graph.jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        payload = sum(int(v.aval.size * v.aval.dtype.itemsize)
                      for v in eqn.invars if isinstance(v, _core.Var))
        if payload >= threshold:
            continue
        mesh_axes = tuple(a for a in _mesh_axes(eqn) if a in known)
        if mesh_axes:
            # a psum over a named mesh axis is GSPMD-scheduled inside
            # the step (mx.sharding TP/FSDP cross-shard reduction), not
            # an unbucketed kvstore gradient push — XLA fuses and
            # overlaps these; the fusion-buffer remedy does not apply
            _emit(graph, report, config, 'small-collective', 'info',
                  f'{eqn.primitive.name} over mesh axis '
                  f'{"/".join(mesh_axes)} ({payload / 1e6:.3f} MB) — '
                  'an in-step GSPMD collective on the sharding mesh, '
                  'not an unbucketed gradient push; no fusion-buffer '
                  'action needed',
                  location=source_location(eqn),
                  data={'primitive': eqn.primitive.name,
                        'payload_bytes': int(payload),
                        'mesh_axes': list(mesh_axes),
                        'in_step_collective': True, 'depth': depth})
            continue
        sev = 'warning' if payload >= scalar_floor else 'info'
        _emit(graph, report, config, 'small-collective', sev,
              f'{eqn.primitive.name} over {payload / 1e6:.3f} MB — '
              f'under the {threshold / 1e6:.0f} MB kvstore '
              'fusion-buffer bucket; latency-bound on the interconnect '
              'instead of bandwidth-bound (coalesce into a fusion '
              'buffer, MXNET_KVSTORE_FUSION_BUFFER_MB)',
              location=source_location(eqn),
              data={'primitive': eqn.primitive.name,
                    'payload_bytes': int(payload),
                    'threshold_bytes': int(threshold), 'depth': depth})


# ---------------------------------------------------------- padding-waste
@register_rule('padding-waste')
def padding_waste(graph, report, config):
    import os
    from ...serve.buckets import bucket_waste_fracs, default_buckets
    frac_limit = float(config.get(
        'pad_waste_frac',
        os.environ.get('MXNET_ANALYSIS_PAD_WASTE_FRAC', '0.5')))
    buckets = config.get('serve_buckets')
    buckets = tuple(buckets) if buckets else default_buckets()
    cost = cost_of_graph(graph)
    for bucket, frac in bucket_waste_fracs(buckets).items():
        if frac <= frac_limit:
            continue
        _emit(graph, report, config, 'padding-waste', 'warning',
              f'serve bucket {bucket} wastes up to {frac:.0%} of its '
              f'FLOPs on pad rows (~{frac * cost.flops / 1e9:.2f} '
              f'GFLOP/step for this graph) — add an intermediate '
              f'bucket to MXNET_SERVE_BUCKETS (current: '
              f'{",".join(map(str, buckets))})',
              data={'bucket': int(bucket),
                    'worst_waste_frac': round(frac, 4),
                    'wasted_flops': int(frac * cost.flops),
                    'buckets': list(buckets),
                    'threshold_frac': frac_limit})
