"""Static roofline cost model over traced jaxprs (``mx.analysis.costs``).

A measured aggregate (an MFU, a share of the HBM roofline) cannot point
at the equations responsible. This pass computes, statically over the
exact jaxpr ``hybridize`` compiles:

* per-equation **FLOPs** and **bytes in/out** from closed-form
  per-primitive cost functions (dot_general ``2·B·M·N·K``, conv
  ``2·|out|·K_spatial·C_in/groups``, elementwise 1 flop/element,
  reductions 1 flop/input element; data movement 0), with a
  conservative shape-based default for unmodeled primitives and a
  per-op override hook (``Op.cost`` in ops/registry.py);
* per-graph totals, **arithmetic intensity**, and a roofline
  classification against a device-spec table
  (analysis/device_specs.py — default: ``bench-r05``, read on an
  earlier development device: 95 TFLOP/s, 62.5 GB/s);
* a donation-aware **liveness walk** predicting peak HBM bytes.

FLOP-counting conventions (documented so fixtures stay comparable):
2 flops per MAC (the MFU convention: ResNet-50's forward is 7.72 GFLOP
an image, tests/test_cost_model.py); transcendentals count 1
flop/element like any
other elementwise op; ``scan`` bodies count once per iteration;
``while`` bodies count ``while_trips`` iterations (default 1, recorded
as an assumption); ``cond`` takes the most expensive branch.

Control flow is costed through ``walker._sub_jaxprs`` recursion — the
llama decode loop's per-token cost is ``length ×`` the body, not 1 ×
(tests/test_cost_model.py pins this).
"""

import math

from jax.extend import core as _core

from .device_specs import get_device_spec, machine_balance
from .walker import eqn_op

__all__ = ['CostReport', 'analyze', 'cost_of_graph', 'peak_hbm_bytes',
           'COLLECTIVE_PRIMS', 'CHEAP_PRIMS', 'REDUCE_PRIMS', 'MATMUL_PRIMS']


# ------------------------------------------------------------- conventions
MATMUL_PRIMS = ('dot_general', 'conv_general_dilated')

# elementwise compute: 1 flop per output element (includes
# transcendentals — see module docstring for the convention)
CHEAP_PRIMS = frozenset("""
add sub mul div rem neg sign abs max min pow integer_pow exp exp2 log
log1p expm1 tanh sin cos tan asin acos atan atan2 sinh cosh asinh acosh
atanh erf erfc erf_inv logistic rsqrt sqrt cbrt square reciprocal floor
ceil round clamp nextafter select_n eq ne lt le gt ge and or xor not
shift_left shift_right_logical shift_right_arithmetic is_finite sort
population_count clz real imag conj complex add_any stop_gradient
""".split())

REDUCE_PRIMS = frozenset("""
reduce_sum reduce_max reduce_min reduce_prod reduce_and reduce_or
reduce_xor argmax argmin reduce_precision cumsum cumprod cummax cummin
cumlogsumexp logsumexp
""".split())

# pure data movement / layout: 0 flops, bytes still counted
MOVEMENT_PRIMS = frozenset("""
reshape broadcast_in_dim transpose squeeze expand_dims convert_element_type
bitcast_convert_type slice dynamic_slice dynamic_update_slice concatenate
pad rev gather copy device_put iota eye tril triu split empty
real_to_complex sharding_constraint optimization_barrier
""".split())

COLLECTIVE_PRIMS = frozenset("""
psum psum_invariant psum_scatter all_gather all_to_all ppermute pbroadcast
reduce_scatter allreduce pmax pmin
""".split())

# control-flow / call primitives handled by recursion
_RECURSE_X1 = frozenset(('pjit', 'closed_call', 'core_call', 'xla_call',
                         'remat', 'checkpoint', 'remat2', 'custom_jvp_call',
                         'custom_vjp_call', 'custom_jvp_call_jaxpr',
                         'custom_vjp_call_jaxpr', 'shard_map',
                         'custom_lin', 'name'))


def _aval_bytes(aval):
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except Exception:
        return 0


def _var_bytes(v):
    return _aval_bytes(v.aval)


def _prod(xs):
    return int(math.prod(xs)) if xs else 1


# ----------------------------------------------------- per-primitive flops
def _dot_general_flops(eqn):
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, _rb) = eqn.params['dimension_numbers']
    k = _prod([lhs.shape[d] for d in lc])
    b = _prod([lhs.shape[d] for d in lb])
    m = _prod([lhs.shape[d] for d in range(lhs.ndim)
               if d not in lc and d not in lb])
    n = _prod([rhs.shape[d] for d in range(rhs.ndim)
               if d not in rc and d not in eqn.params[
                   'dimension_numbers'][1][1]])
    return 2 * b * m * n * k


def _conv_flops(eqn):
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params['dimension_numbers']
    rhs_spec = dn.rhs_spec  # (out_c, in_c_per_group, *spatial)
    spatial = _prod([rhs.shape[d] for d in rhs_spec[2:]])
    cin_per_group = rhs.shape[rhs_spec[1]]
    return 2 * _prod(out.shape) * spatial * cin_per_group


def _reduce_window_flops(eqn):
    out = eqn.outvars[0].aval
    win = _prod(eqn.params.get('window_dimensions', ()))
    return _prod(out.shape) * max(win, 1)


def _default_flops(eqn):
    """Conservative default for unmodeled primitives: one flop per
    output element (never silently zero-cost)."""
    return sum(_prod(v.aval.shape) for v in eqn.outvars)


def prim_flops(eqn):
    """Closed-form FLOPs for one equation (no sub-jaxpr recursion —
    callers handle control flow). Returns (flops, modeled)."""
    name = eqn.primitive.name
    if name == 'dot_general':
        return _dot_general_flops(eqn), True
    if name == 'conv_general_dilated':
        return _conv_flops(eqn), True
    if name == 'reduce_window_sum' or name.startswith('reduce_window'):
        return _reduce_window_flops(eqn), True
    if name in CHEAP_PRIMS:
        return sum(_prod(v.aval.shape) for v in eqn.outvars), True
    if name in REDUCE_PRIMS:
        return sum(_prod(v.aval.shape) for v in eqn.invars
                   if isinstance(v, _core.Var)), True
    if name in MOVEMENT_PRIMS:
        return 0, True
    if name.startswith('scatter'):
        # scatter-add & friends: one combine per update element
        upd = eqn.invars[-1].aval if eqn.invars else None
        return (_prod(upd.shape) if upd is not None else 0), True
    if name in COLLECTIVE_PRIMS:
        # combine cost is bandwidth-dominated; count 1 flop/element
        return sum(_prod(v.aval.shape) for v in eqn.outvars), True
    if name in ('threefry2x32', 'random_bits', 'random_seed',
                'random_wrap', 'random_fold_in', 'random_unwrap'):
        return sum(_prod(v.aval.shape) for v in eqn.outvars), True
    return _default_flops(eqn), False


# --------------------------------------------------------------- the report
class CostReport:
    """Aggregated analytical cost of one traced graph."""

    def __init__(self, graph_name, device):
        self.graph_name = graph_name
        self.device = device
        self.flops = 0
        self.bytes_in = 0
        self.bytes_out = 0
        self.bytes_moved = 0        # Σ per-eqn (in+out): as-if-unfused
        self.hbm_bytes_min = 0      # boundary buffers once: fused bound
        self.peak_hbm_bytes = 0
        self.eqns = 0
        self.by_primitive = {}      # name -> {count, flops, bytes}
        self.collectives = []       # [{primitive, bytes, location}]
        self.unmodeled = {}         # primitive -> eqn count
        self.assumptions = []
        self.machine_balance = machine_balance(device)
        # set by cost_of_graph when the graph was traced under an
        # mx.sharding mesh: per-device flops/bytes/peak (see
        # _per_device_costs for the scaling model and its assumption)
        self.per_device = None

    # ------------------------------------------------------------ derived
    @property
    def intensity(self):
        """Arithmetic intensity under the perfectly-fused traffic bound
        (boundary buffers touched once) — the optimistic roofline."""
        return self.flops / self.hbm_bytes_min if self.hbm_bytes_min else 0.0

    @property
    def naive_intensity(self):
        """Intensity as-if-unfused (every eqn round-trips HBM)."""
        return self.flops / self.bytes_moved if self.bytes_moved else 0.0

    @property
    def classification(self):
        return ('compute-bound' if self.intensity >= self.machine_balance
                else 'bandwidth-bound')

    @property
    def mfu_bound(self):
        """Roofline-implied ceiling on MFU: below machine balance the
        MXU cannot be fed faster than HBM delivers operands."""
        if not self.machine_balance:
            return 1.0
        return min(1.0, self.intensity / self.machine_balance)

    def predicted_step_seconds(self):
        """max(compute time, HBM time) under the fused traffic bound."""
        t_flops = self.flops / float(self.device['peak_flops'])
        t_hbm = self.hbm_bytes_min / float(self.device['hbm_bytes_s'])
        return max(t_flops, t_hbm)

    # ---------------------------------------------------------- recording
    def _record(self, eqn, flops, b_in, b_out, repeats, modeled):
        name = eqn.primitive.name
        self.flops += flops * repeats
        self.bytes_in += b_in * repeats
        self.bytes_out += b_out * repeats
        self.bytes_moved += (b_in + b_out) * repeats
        self.eqns += 1
        s = self.by_primitive.setdefault(
            name, {'count': 0, 'flops': 0, 'bytes': 0})
        s['count'] += repeats
        s['flops'] += flops * repeats
        s['bytes'] += (b_in + b_out) * repeats
        if not modeled:
            self.unmodeled[name] = self.unmodeled.get(name, 0) + 1
        if name in COLLECTIVE_PRIMS:
            self.collectives.append(
                {'primitive': name, 'bytes': b_in, 'repeats': repeats})

    # ------------------------------------------------------------- output
    def as_dict(self):
        return {
            'graph': self.graph_name,
            'device': self.device.get('name', '<custom>'),
            'flops': int(self.flops),
            'bytes_in': int(self.bytes_in),
            'bytes_out': int(self.bytes_out),
            'bytes_moved': int(self.bytes_moved),
            'hbm_bytes_min': int(self.hbm_bytes_min),
            'peak_hbm_bytes': int(self.peak_hbm_bytes),
            'eqns': int(self.eqns),
            'intensity_flop_per_byte': round(self.intensity, 3),
            'naive_intensity_flop_per_byte': round(self.naive_intensity, 3),
            'machine_balance_flop_per_byte': round(self.machine_balance, 1),
            'classification': self.classification,
            'predicted_mfu_bound': round(self.mfu_bound, 4),
            'by_primitive': {k: dict(v)
                             for k, v in sorted(self.by_primitive.items())},
            'collectives': list(self.collectives),
            'unmodeled_primitives': dict(self.unmodeled),
            'assumptions': list(self.assumptions),
            'per_device': dict(self.per_device) if self.per_device else None,
        }

    def summary(self):
        return (f'{self.graph_name}: {self.flops / 1e9:.2f} GFLOP, '
                f'{self.hbm_bytes_min / 1e6:.1f} MB boundary / '
                f'{self.bytes_moved / 1e6:.1f} MB unfused, '
                f'intensity {self.intensity:.1f} flop/B vs balance '
                f'{self.machine_balance:.0f} ({self.classification}, '
                f'mfu bound {self.mfu_bound:.3f}), peak HBM '
                f'{self.peak_hbm_bytes / 1e6:.1f} MB')

    def __str__(self):
        lines = [f'CostReport[{self.graph_name}] on '
                 f'{self.device.get("name", "<custom>")}',
                 f'  {self.summary()}']
        top = sorted(self.by_primitive.items(),
                     key=lambda kv: -kv[1]['flops'])[:12]
        if top:
            lines.append(f'  {"primitive":<28}{"count":>8}{"GFLOP":>12}'
                         f'{"MB moved":>12}')
            for name, s in top:
                lines.append(f'  {name:<28}{s["count"]:>8}'
                             f'{s["flops"] / 1e9:>12.3f}'
                             f'{s["bytes"] / 1e6:>12.2f}')
        if self.unmodeled:
            lines.append(f'  unmodeled primitives (defaulted): '
                         f'{sorted(self.unmodeled)}')
        if self.per_device:
            pd = self.per_device
            lines.append(
                f'  per-device ({pd["n_devices"]}x): '
                f'{pd["flops"] / 1e9:.2f} GFLOP, '
                f'{pd["hbm_bytes_min"] / 1e6:.1f} MB boundary, '
                f'peak HBM {pd["peak_hbm_bytes"] / 1e6:.1f} MB')
        for a in self.assumptions:
            lines.append(f'  assumption: {a}')
        return '\n'.join(lines)

    def __repr__(self):
        return f'<CostReport {self.summary()}>'


# --------------------------------------------------------------- the walker
def _sub_closed(v):
    if isinstance(v, _core.ClosedJaxpr):
        return v.jaxpr
    if isinstance(v, _core.Jaxpr):
        return v
    return None


def _eqn_repeats(eqn, config):
    """(repeat multiplier, sub-jaxprs to recurse) for a control-flow
    eqn; (1, []) for plain equations."""
    name = eqn.primitive.name
    p = eqn.params
    if name == 'scan':
        body = _sub_closed(p.get('jaxpr'))
        length = int(p.get('length') or 1)
        return length, [body] if body is not None else []
    if name == 'while':
        trips = int(config.get('while_trips', 1) or 1)
        subs = [_sub_closed(p.get('body_jaxpr'))]
        cond = _sub_closed(p.get('cond_jaxpr'))
        if cond is not None:
            subs.append(cond)
        return trips, [s for s in subs if s is not None]
    if name == 'cond':
        return 1, []        # handled specially (max branch)
    if name == 'pallas_call':
        return 1, []        # hand-written kernel: use Op.cost / default
    if name in _RECURSE_X1 or any(
            _sub_closed(v) is not None
            for v in p.values() if not isinstance(v, (tuple, list))):
        subs = []
        for v in p.values():
            s = _sub_closed(v)
            if s is not None:
                subs.append(s)
            elif isinstance(v, (tuple, list)):
                subs.extend(s for s in map(_sub_closed, v) if s is not None)
        return 1, subs
    # tuples of jaxprs (e.g. custom transforms)
    subs = []
    for v in p.values():
        if isinstance(v, (tuple, list)):
            subs.extend(s for s in map(_sub_closed, v) if s is not None)
    return 1, subs


def _walk(jaxpr, report, config, repeats):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        b_in = sum(_var_bytes(v) for v in eqn.invars)
        b_out = sum(_var_bytes(v) for v in eqn.outvars)
        if name == 'cond':
            # charge the most expensive branch (conservative peak)
            branches = [_sub_closed(b)
                        for b in eqn.params.get('branches', ())]
            best, best_flops = None, -1
            for br in branches:
                if br is None:
                    continue
                probe = CostReport(report.graph_name, report.device)
                _walk(br, probe, config, 1)
                if probe.flops > best_flops:
                    best, best_flops = br, probe.flops
            report._record(eqn, 0, b_in, b_out, repeats, True)
            if best is not None:
                report.assumptions.append(
                    'cond: charged the most expensive branch')
                _walk(best, report, config, repeats)
            continue
        mult, subs = _eqn_repeats(eqn, config)
        if name == 'while' and mult != 1:
            report.assumptions.append(
                f'while: assumed {mult} trip(s) (config while_trips)')
        if name == 'scan' and subs:
            # the eqn boundary itself moves consts+carries+xs once;
            # body eqns repeat per iteration
            report._record(eqn, 0, b_in, b_out, repeats, True)
            for s in subs:
                _walk(s, report, config, repeats * mult)
            continue
        if subs:
            report._record(eqn, 0, b_in, b_out, repeats, True)
            for s in subs:
                _walk(s, report, config, repeats * mult)
            continue
        flops, modeled = prim_flops(eqn)
        op = eqn_op(eqn)
        if op is not None and getattr(op, 'cost', None) is not None:
            custom = op.cost(eqn)
            if custom is not None:
                flops, modeled = int(custom), True
        report._record(eqn, flops, b_in, b_out, repeats, modeled)


# ------------------------------------------------------------ peak-HBM walk
def _internal_peak(jaxpr, config):
    """Transient bytes a sub-jaxpr needs beyond its own inputs/outputs
    (both owned by the outer scope): max live intermediate footprint."""
    probe_report = peak_hbm_bytes_jaxpr(jaxpr, donated_idx=(),
                                        const_bytes=0, config=config)
    boundary = (sum(_var_bytes(v) for v in jaxpr.invars)
                + sum(_var_bytes(v) for v in jaxpr.outvars
                      if isinstance(v, _core.Var)))
    return max(0, probe_report - boundary)


def peak_hbm_bytes_jaxpr(jaxpr, donated_idx, const_bytes, config):
    """Liveness walk in program order. Non-donated invars are pinned for
    the whole program (the caller holds them); donated invars and
    equation outputs die after their last use. Equations carrying
    sub-jaxprs contribute their internal transient peak while live."""
    eqns = jaxpr.eqns
    n = len(eqns)
    last_use = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if isinstance(v, _core.Var):
                last_use[id(v)] = i
    for v in jaxpr.outvars:
        if isinstance(v, _core.Var):
            last_use[id(v)] = n          # escapes: lives to the end

    pinned = const_bytes
    transient = 0
    free_at = [[] for _ in range(n + 1)]
    for i, v in enumerate(jaxpr.invars):
        if i in donated_idx:
            transient += _var_bytes(v)
            free_at[min(last_use.get(id(v), 0) + 1, n)].append(
                _var_bytes(v))
        else:
            pinned += _var_bytes(v)
    peak = pinned + transient
    for i, eqn in enumerate(eqns):
        alloc = sum(_var_bytes(v) for v in eqn.outvars)
        sub_extra = 0
        _, subs = _eqn_repeats(eqn, config)
        if eqn.primitive.name == 'cond':
            subs = [s for s in map(_sub_closed,
                                   eqn.params.get('branches', ()))
                    if s is not None]
        for s in subs:
            sub_extra = max(sub_extra, _internal_peak(s, config))
        transient += alloc
        peak = max(peak, pinned + transient + sub_extra)
        for v in eqn.outvars:
            if id(v) not in last_use:        # dead output: freed at once
                transient -= _var_bytes(v)
        for b in free_at[i + 1]:
            transient -= b
        for v in eqn.invars:
            if isinstance(v, _core.Var) and last_use.get(id(v)) == i \
                    and id(v) not in {id(x) for x in jaxpr.invars} \
                    and id(v) not in {id(x) for x in jaxpr.outvars}:
                transient -= _var_bytes(v)
    return peak


def peak_hbm_bytes(graph, config=None):
    """Donation-aware predicted peak HBM bytes for a GraphView: reuses
    the PR 2 donation semantics — aux buffers donate on recorded-train
    entries, inputs only on the caller's opt-in (gluon/block.py)."""
    config = config or {}
    donated = set()
    kinds = set(graph.donate_groups)
    for a in graph.args:
        if (a.kind == 'aux' and 'aux' in kinds) or \
                (a.kind == 'input' and 'inputs' in kinds):
            donated.add(a.index)
    const_bytes = sum(int(getattr(c, 'nbytes', 0) or 0)
                      for c in graph.consts)
    return peak_hbm_bytes_jaxpr(graph.jaxpr, donated, const_bytes, config)


def _per_device_costs(graph, report):
    """Per-device cost dict for a graph traced under an mx.sharding
    mesh (GraphView.sharding metadata from the walker).

    Model: FLOPs divide evenly over the mesh (SPMD — every device runs
    the same program over its shard). Boundary bytes divide per-argument
    by that argument's shard factor (a replicated bias counts full on
    every device, a 'dp'-sharded batch counts 1/dp); closure constants
    are always replicated. Interior traffic and peak HBM are scaled by
    the resulting boundary ratio — recorded as an assumption, since
    GSPMD may materialize different interiors (halo exchanges,
    re-sharding) than the single-device jaxpr suggests.
    """
    meta = graph.sharding
    n = int(meta.get('n_devices', 1) or 1)
    factors = meta.get('factors', {})
    out_axis = meta.get('data_axis')
    extent = meta.get('axes', {}).get(out_axis, 1) if out_axis else 1

    boundary = sum(int(getattr(c, 'nbytes', 0) or 0)
                   for c in graph.consts)
    for a in graph.args:
        f = max(1, int(factors.get(a.label, 1)))
        boundary += _var_bytes(graph.jaxpr.invars[a.index]) / f
    for v, kind in zip(graph.jaxpr.outvars, graph.out_kinds):
        if not isinstance(v, _core.Var):
            continue
        shape = tuple(v.aval.shape)
        # outputs leave at the batch spec; aux write-backs at the param
        # spec — approximate the latter by the mean param factor
        if kind == 'aux':
            pf = [f for lbl, f in factors.items()
                  if lbl.startswith(('param:', 'aux:'))]
            f = max(1, int(sum(pf) / len(pf))) if pf else 1
        else:
            f = extent if (shape and extent > 1
                           and shape[0] % extent == 0) else 1
        boundary += _var_bytes(v) / f

    ratio = (boundary / report.hbm_bytes_min
             if report.hbm_bytes_min else 1.0 / n)
    flops = report.flops / n
    hbm_min = boundary
    peak = report.peak_hbm_bytes * ratio
    t_flops = flops / float(report.device['peak_flops'])
    t_hbm = hbm_min / float(report.device['hbm_bytes_s'])
    report.assumptions.append(
        f'per-device: FLOPs/{n}; boundary bytes divided per-arg by '
        f'shard factor; interior traffic and peak HBM scaled by the '
        f'boundary ratio {ratio:.3f} (GSPMD may materialize different '
        'interiors: halo exchange, re-sharding)')
    return {
        'n_devices': n,
        'mode': meta.get('mode'),
        'axes': dict(meta.get('axes', {})),
        'flops': int(flops),
        'hbm_bytes_min': int(hbm_min),
        'bytes_moved': int(report.bytes_moved * ratio),
        'peak_hbm_bytes': int(peak),
        'intensity_flop_per_byte': round(flops / hbm_min, 3)
        if hbm_min else 0.0,
        'predicted_step_seconds': max(t_flops, t_hbm),
    }


# ------------------------------------------------------------- entry points
def cost_of_graph(graph, device_spec=None, **config):
    """Analytical CostReport for an already-traced GraphView. Cached on
    the graph — rules and surfaces share one pass."""
    cached = getattr(graph, '_cost_report', None)
    if cached is not None and not config and device_spec is None:
        return cached
    device = get_device_spec(device_spec)
    report = CostReport(graph.name, device)
    _walk(graph.jaxpr, report, config, 1)
    # perfectly-fused traffic bound: every boundary buffer once
    report.hbm_bytes_min = (
        sum(int(getattr(c, 'nbytes', 0) or 0) for c in graph.consts)
        + sum(_var_bytes(v) for v in graph.jaxpr.invars)
        + sum(_var_bytes(v) for v in graph.jaxpr.outvars
              if isinstance(v, _core.Var)))
    report.peak_hbm_bytes = peak_hbm_bytes(graph, config)
    if getattr(graph, 'sharding', None):
        report.per_device = _per_device_costs(graph, report)
    if not config and device_spec is None:
        graph._cost_report = report
    return report


def analyze(fn_or_block, *example_args, train=False, device_spec=None,
            name=None, **config):
    """Trace + cost a HybridBlock or step function — the
    ``mx.analysis.cost_report()`` entry point (analysis/__init__.py)."""
    from .walker import trace_block, trace_function
    from ..gluon.block import Block

    if isinstance(fn_or_block, Block):
        graph = trace_block(fn_or_block, *example_args, train=train,
                            name=name)
    elif callable(fn_or_block):
        graph = trace_function(fn_or_block, *example_args, name=name)
    else:
        raise TypeError(
            f'cost_report() takes a HybridBlock or a callable, got '
            f'{type(fn_or_block).__name__}')
    return cost_of_graph(graph, device_spec=device_spec, **config)
