"""``mx.analysis`` — static graph sanitizer over traced jaxprs.

The reference stack catches graph-level mistakes at runtime (NaiveEngine
re-runs, the thread-safety suites); on TPU the expensive failure modes —
silent bf16→f32 upcasts, constants baked into the HLO, per-step
recompilation, host syncs inside the step, inert buffer donation — are
statically visible in the traced jaxpr before any device time is spent.
This package closes that gap in the spirit of XLA's HLO verifier and
JAX's transfer guards (PAPERS.md), over the exact artifact ``hybridize``
compiles.

Three surfaces:

* ``mx.analysis.lint(fn_or_block, *example_args)`` — returns an
  :class:`AnalysisReport`;
* ``HybridBlock.hybridize(..., check=True)`` — lints the graph right
  after the first compile and routes findings through ``warnings``
  (gluon/block.py);
* ``tools/graph_lint.py`` — CLI over the model zoo, nonzero exit on
  errors (the CI tier).

``MXNET_ANALYSIS_STRICT=1`` promotes warnings to errors everywhere
(docs/static-analysis.md has the full rule table).
"""

from .report import AnalysisReport, Finding, strict_enabled
from .walker import GraphView, trace_block, trace_function, iter_eqns
from . import rules
from .rules import all_rules, run_rules
from .rules.perf import chain_coverage
from . import costs
from .costs import CostReport, cost_of_graph
from .device_specs import DEVICE_SPECS, get_device_spec
from . import locks
from . import race

__all__ = ['lint', 'cost_report', 'AnalysisReport', 'Finding',
           'GraphView', 'CostReport', 'cost_of_graph', 'costs',
           'DEVICE_SPECS', 'get_device_spec', 'all_rules', 'rules',
           'strict_enabled', 'locks', 'race', 'chain_coverage']


def lint(fn_or_block, *example_args, train=False, rules=None,
         donation=False, donate_argnums=None, strict=None, name=None,
         **config):
    """Statically analyze a HybridBlock or step function.

    Parameters
    ----------
    fn_or_block : HybridBlock or callable
        A block (traced exactly as ``hybridize`` would trace it) or a
        raw function over NDArrays / jax arrays.
    *example_args
        Example inputs — NDArrays, numpy/jax arrays, or shape tuples
        (blocks only) — fixing the traced shapes/dtypes.
    train : bool
        Trace the train-mode graph (dropout active, BN batch stats +
        aux write-backs) instead of inference. Blocks only.
    rules : list[str], optional
        Subset of rule names to run (default: all registered rules).
    donation : bool
        Also run the compile-backed donation audit (lowers + compiles
        the graph — not free; off by default).
    donate_argnums : tuple[int], optional
        For raw functions: flat argnums to audit as donated.
    strict : bool, optional
        Promote warnings to errors for this report (default: the
        ``MXNET_ANALYSIS_STRICT`` env var).
    config
        Rule knobs, e.g. ``const_bytes=<threshold>`` for the
        large-constant rule.

    Returns
    -------
    AnalysisReport
    """
    from ..gluon.block import Block

    if isinstance(fn_or_block, Block):
        graph = trace_block(fn_or_block, *example_args, train=train,
                            name=name)
    elif callable(fn_or_block):
        graph = trace_function(fn_or_block, *example_args, name=name)
    else:
        raise TypeError(
            f'lint() takes a HybridBlock or a callable, got '
            f'{type(fn_or_block).__name__}')

    report = AnalysisReport(graph_name=graph.name, strict=strict)
    report.stats.update(graph.stats())
    if donate_argnums is not None:
        config['donate_argnums'] = tuple(donate_argnums)
    run_rules(graph, report, rules=rules, compile_rules=donation,
              **config)
    return report


def cost_report(fn_or_block, *example_args, train=False,
                device_spec=None, name=None, **config):
    """Analytical roofline cost of a HybridBlock or step function: total
    FLOPs, bytes moved, arithmetic intensity vs machine balance, and
    predicted peak HBM (donation-aware liveness). Same tracing contract
    as :func:`lint`; returns a :class:`CostReport`.

    ``device_spec`` picks the roofline device: a name from
    :data:`DEVICE_SPECS`, a JSON path, or a dict (default: the
    ``bench-r05`` entry, read on an earlier development device,
    overridable via ``MXNET_ANALYSIS_DEVICE_SPEC``). ``while_trips=N`` sets the assumed
    trip count for ``lax.while_loop`` equations (static analysis cannot
    know it; the assumption is recorded on the report).
    """
    return costs.analyze(fn_or_block, *example_args, train=train,
                         device_spec=device_spec, name=name, **config)


def lint_graph(graph, strict=None, rules=None, donation=False, **config):
    """Lint an already-traced :class:`GraphView` (the hybridize hook's
    entry point — the trace is reused, not redone)."""
    report = AnalysisReport(graph_name=graph.name, strict=strict)
    report.stats.update(graph.stats())
    run_rules(graph, report, rules=rules, compile_rules=donation,
              **config)
    return report
