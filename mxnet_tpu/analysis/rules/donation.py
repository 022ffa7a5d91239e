"""donation-audit: does static_alloc's claimed donation actually alias?

``hybridize(static_alloc=True)`` donates the mutable aux-state argnum
(BN running stats) on recorded-train executables, and
``hybridize(donate_inputs=True)`` additionally donates the input
activations (gluon/block.py ``_CachedGraph._build``). A donation is
only worth anything if XLA accepts it — i.e. the compiled executable
records an entry in ``input_output_alias`` mapping the donated
parameter onto an output buffer. Shape/dtype/layout mismatches make
XLA silently decline, which is exactly the inert-claim failure mode
this rule machine-checks (VERDICT r5 weak #2).

The audit lowers the *same* pure function the block compiles, with the
*same* donation the block would request, and parses the aliasing table
out of the compiled HLO:

* claimed donation that did NOT alias  -> warning (the claim is inert);
* donated + aliased                    -> recorded in ``report.stats``;
* donatable-but-undonated buffer (an input/aux whose shape+dtype
  matches an output, donation not requested) -> info.

Requires compilation, so it only runs when the caller passes
``compile_rules=True`` (mx.analysis.lint(..., donation=True), the CLI
``--donation`` flag, or the dedicated unit tests).
"""

import re
import warnings

from . import register_rule

_ALIAS_ENTRY = re.compile(r'\{\s*(\d*)\s*\}:\s*\((\d+)')

GROUP_ARGNUM = {'inputs': 1, 'aux': 3}      # pure_fn(rng, ins, mains, aux)


def parse_input_output_aliases(hlo_text):
    """-> dict flat_param_index -> flat_output_index, from the
    ``input_output_alias={ {out}: (param, {}, may-alias), ... }``
    annotation of the compiled HLO module header (brace-counted — the
    entries nest braces)."""
    aliases = {}
    start = hlo_text.find('input_output_alias={')
    if start < 0:
        return aliases
    i = hlo_text.index('{', start)
    depth, j = 0, i
    for j in range(i, len(hlo_text)):
        if hlo_text[j] == '{':
            depth += 1
        elif hlo_text[j] == '}':
            depth -= 1
            if depth == 0:
                break
    body = hlo_text[i + 1:j]
    for out_idx, param_idx in _ALIAS_ENTRY.findall(body):
        aliases[int(param_idx)] = int(out_idx) if out_idx else 0
    return aliases


@register_rule('donation-audit', needs_compile=True)
def run(graph, report, config):
    if graph.lower_fn is None:
        return
    if graph.source == 'block' and not graph.static_alloc:
        report.add(
            'donation-audit', 'info',
            f'{graph.name} was hybridized with static_alloc=False — no '
            'donation is claimed, none audited', claimed=False)
        return

    if graph.source == 'block' and not graph.donate_groups:
        report.add(
            'donation-audit', 'info',
            f'{graph.name}: inference-mode entries donate nothing by '
            'design (lock-free threads share param/aux buffers); lint '
            'with train=True to audit the recorded-train donation',
            claimed=False)
        return

    if graph.source == 'block':
        donate_argnums = tuple(sorted(GROUP_ARGNUM[g]
                                      for g in graph.donate_groups))
        donated_kinds = set(g.rstrip('s') for g in graph.donate_groups)
        donated = [a for a in graph.args
                   if a.kind in donated_kinds]
    else:
        donate_argnums = tuple(config.get('donate_argnums', ()) or ())
        donated = [a for a in graph.args if a.index in donate_argnums]

    compile_warnings = []
    try:
        with warnings.catch_warnings(record=True) as ws:
            warnings.simplefilter('always')
            compiled = graph.lower_fn(donate_argnums).compile()
        compile_warnings = [str(w.message) for w in ws
                            if 'donat' in str(w.message).lower()]
        hlo = compiled.as_text()
    except Exception as exc:   # pragma: no cover - backend-specific
        report.add(
            'donation-audit', 'info',
            f'could not compile {graph.name} for the donation audit: '
            f'{type(exc).__name__}: {exc}', compile_failed=True)
        return

    aliases = parse_input_output_aliases(hlo)
    report.stats['donated_args'] = len(donated)
    report.stats['aliased_args'] = sum(1 for a in donated
                                       if a.index in aliases)

    if not donated:
        report.add(
            'donation-audit', 'info',
            f'{graph.name}: static_alloc claims donation but the graph '
            'has no donatable buffers in its donated groups '
            f'({", ".join(graph.donate_groups) or "none"}) — nothing '
            'to alias (e.g. no mutable aux state)', claimed=True,
            donated=0)

    for a in donated:
        if a.index in aliases:
            report.add(
                'donation-audit', 'info',
                f'donated {a.label} aliases output '
                f'[{aliases[a.index]}] in the compiled executable — '
                'the buffer is reused in place', arg=a.label,
                aliased=True, output=aliases[a.index])
        else:
            declined = ('; XLA reported: ' + compile_warnings[0]
                        if compile_warnings else '')
            report.add(
                'donation-audit', 'warning',
                f'donation of {a.label} did NOT alias any output — the '
                f'static_alloc claim is inert for this buffer'
                f'{declined} (no output matches its shape/dtype, or '
                'the backend declined)', arg=a.label, aliased=False)

    # donatable-but-undonated: inputs/aux with an output twin
    out_sigs = {}
    for var, kind in zip(graph.jaxpr.outvars, graph.out_kinds):
        aval = getattr(var, 'aval', None)
        if aval is not None and getattr(aval, 'shape', None) is not None:
            out_sigs.setdefault(
                (tuple(aval.shape), str(aval.dtype)), kind)
    donated_idx = {a.index for a in donated}
    for a in graph.args_of_kind('input', 'aux'):
        if a.index in donated_idx:
            continue
        sig = (tuple(a.aval.shape), str(a.aval.dtype))
        if sig in out_sigs and a.aval.ndim > 0:
            how = ('hybridize(donate_inputs=True)' if a.kind == 'input'
                   else 'static_alloc=True (recorded-train entries)')
            report.add(
                'donation-audit', 'info',
                f'{a.label} matches an output buffer '
                f'({sig[1]}{list(sig[0])}) and could be donated via '
                f'{how} if the caller does not reuse it', arg=a.label,
                donatable=True)
