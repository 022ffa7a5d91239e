"""dead-code: unused params/inputs, degenerate outputs, dead equations.

Three independent checks; unused-argument and output checks read the
outermost jaxpr (that is where the graph's arguments live), while dead
equations are counted through nested sub-jaxprs — dead compute inside a
scan/while/cond body repeats every iteration:

* **unused arguments** — a param/input invar no eqn reads and no output
  returns. For params this usually means a layer was constructed but
  never called (weights still allocated, synced, and checkpointed);
  warning. Unused *aux* state is info (eval-mode graphs legitimately
  ignore update paths).
* **degenerate outputs** — an output that is literally an input
  (pass-through: wasted device->host traffic per step) or a jaxpr
  Literal (a constant the caller could hold instead); info. Aux
  write-back outputs are exempt — inference graphs return running
  stats unchanged by design.
* **dead equations** — equations DCE would delete because nothing they
  produce reaches an output. XLA will drop them too, but they still
  cost trace+lower time every cache entry, and dead compute in a
  forward usually indicates a forgotten head or a mis-wired residual;
  warning with the primitive census when more than ``dead_eqn_info``
  (default 0) equations die.
"""

from jax.extend import core as _core

from . import register_rule
from ..walker import iter_eqns


def _dce(jaxpr):
    try:
        from jax.interpreters import partial_eval as pe
        new_jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        return new_jaxpr
    except Exception:
        return None


@register_rule('dead-code')
def run(graph, report, config):
    jaxpr = graph.jaxpr

    # params the tracer had to skip: their deferred init never resolved
    # because no forward path touches their layer (walker.trace_block)
    for note in graph.notes:
        if note.startswith('deferred-params:'):
            for pname in note.split(':', 1)[1].split(','):
                report.add(
                    'dead-code', 'warning',
                    f'parameter {pname} never left deferred '
                    'initialization — its layer is constructed but no '
                    'forward path calls it (forgotten layer?)',
                    arg=f'param:{pname}', kind='param', deferred=True)

    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            used.add(id(v))
    for v in jaxpr.outvars:
        used.add(id(v))

    for arg in graph.args:
        if arg.kind == 'rng':
            continue
        var = jaxpr.invars[arg.index]
        if id(var) not in used:
            sev = 'info' if arg.kind == 'aux' else 'warning'
            what = {'param': 'parameter', 'aux': 'aux state',
                    'input': 'input'}[arg.kind]
            report.add(
                'dead-code', sev,
                f'unused {what} {arg.label} — it is traced, '
                'transferred, and kept alive but contributes to no '
                'output' + (' (forgotten layer?)'
                            if arg.kind == 'param' else ''),
                arg=arg.label, kind=arg.kind)

    invar_ids = {id(v): i for i, v in enumerate(jaxpr.invars)}
    n_outputs = graph.out_kinds.count('output')
    for pos, (var, kind) in enumerate(zip(jaxpr.outvars,
                                          graph.out_kinds)):
        if kind != 'output':
            continue        # aux write-backs pass through by design
        if isinstance(var, _core.Literal):
            report.add(
                'dead-code', 'info',
                f'output[{pos}] is a compile-time constant — the '
                'caller could hold the value instead of fetching it '
                'every step', output=pos)
        elif id(var) in invar_ids:
            arg = graph.args[invar_ids[id(var)]]
            report.add(
                'dead-code', 'info',
                f'output[{pos}] is a pass-through of {arg.label} — '
                'returned unmodified every step', output=pos,
                arg=arg.label)

    live = _dce(jaxpr)
    if live is not None:
        # count nested equations too: dce_jaxpr prunes inside
        # scan/while/cond/pjit bodies, and dead compute hiding in a
        # decode loop repeats every iteration — the outermost eqn list
        # alone would miss it entirely
        n_total = sum(1 for _ in iter_eqns(jaxpr))
        n_live = sum(1 for _ in iter_eqns(live))
        n_dead = n_total - n_live
        if n_dead > int(config.get('dead_eqn_info', 0) or 0):
            census = {}
            live_count = {}
            for eqn, _d in iter_eqns(live):
                live_count[eqn.primitive.name] = \
                    live_count.get(eqn.primitive.name, 0) + 1
            for eqn, _d in iter_eqns(jaxpr):
                census[eqn.primitive.name] = \
                    census.get(eqn.primitive.name, 0) + 1
            dead = {k: v - live_count.get(k, 0) for k, v in census.items()
                    if v - live_count.get(k, 0) > 0}
            report.add(
                'dead-code', 'warning',
                f'{n_dead} equation(s) compute values that reach no '
                f'output (dead compute: {dead}) — a forgotten head or '
                'mis-wired branch; XLA drops them but tracing pays for '
                'them per cache entry',
                n_dead=n_dead, dead_prims=dead)
