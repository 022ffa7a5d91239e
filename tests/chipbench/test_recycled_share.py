"""The reader of ``residuals_recycled_share`` (``recycled`` over
``residuals`` of mx.graph.launch) on hand-built analyses and on CPU runs
of the cells left off its list. A program from before the attributes,
or whose forwards hand back no residuals, reads nothing, never a false
0."""

import pytest

from chipbench import program_trace
from chipbench.layer_metrics import residuals_recycled_share

from chipbench_tiny import CELLS, PEAKS, load_bench


def analysis(**launch_attrs):
    """Two steps of a program_trace.analyse result whose recorded
    forwards' launch span carries ``launch_attrs``."""
    def span(count, total_ms, **attrs):
        return {'count': count, 'total_s': total_ms * 1e-3,
                'self_s': total_ms * 1e-3, 'attrs': attrs}

    return {'steps': 2, 'spans': {
        'mx.graph.call': span(2, 40.0, n_in=6),
        'mx.graph.launch': span(2, 30.0, n_out=446, ahead=0,
                                **launch_attrs),
        'mx.tape.vjp': span(4, 16.0, n_out=312, ahead=6),
    }}


@pytest.mark.parametrize('recycled, share', [(222, 50.0), (444, 100.0),
                                             (0, 0.0)])
def test_the_recycled_share_is_recycled_over_residuals_of_the_launches(
        recycled, share):
    got = analysis(residuals=444, recycled=recycled)
    assert residuals_recycled_share.of_analysis(got) == pytest.approx(share)


def test_no_residuals_read_nothing_never_a_false_share():
    # a program older than the attributes
    assert residuals_recycled_share.of_analysis(analysis()) is None
    # forwards that hand back no residuals of their own (remat)
    got = analysis(residuals=0, recycled=0)
    assert residuals_recycled_share.of_analysis(got) is None
    del got['spans']['mx.graph.launch']
    assert residuals_recycled_share.of_analysis(got) is None


_NOT_READ = [name for name in CELLS if name not in next(
    m for m in load_bench()['per_layer']
    if m['name'] == 'residuals_recycled_share')['workloads']]


@pytest.mark.parametrize('name', _NOT_READ)
def test_a_cell_whose_forwards_hand_back_no_residuals_reads_nothing(
        name, monkeypatch):
    """In a CPU run of a cell left off the metric's list (every forward
    under ``remat``) the reader finds no residual to share out."""
    import mxnet_tpu as mx
    from chipbench import run, trace_reduce

    from chipbench_tiny import small_trace, tiny
    monkeypatch.setattr(trace_reduce, 'reduce_dir',
                        lambda d, prefix: trace_reduce.reduce(small_trace()))
    cell, cfg = tiny(name)
    r = run.run_cell(cell, cfg, run.entries_for(load_bench(), name), 7, 0.3,
                     True, mx.cpu(0), PEAKS)
    assert r['correct'], r['check']
    got = program_trace.of_dir(run.TRACE_DIR)
    assert got['spans']['mx.graph.launch']['attrs']['residuals'] == 0
    assert residuals_recycled_share.of_analysis(got) is None
