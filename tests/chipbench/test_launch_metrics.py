"""The readers of ISSUE 39's span and attributes on hand-built analyses
and host events: ``graph_await_ms`` (mx.graph.await),
``launch_queue_depth`` (the mean ``ahead`` of the launch spans) and
``launch_hbm_in_use_gb`` (the largest ``in_use`` inside the window). A
program from before them reads nothing, never a false 0."""

import pytest

from chipbench import program_trace
from chipbench.layer_metrics import (graph_await_ms, launch_hbm_in_use_gb,
                                     launch_queue_depth)


def span(count, total_ms=0.0, **attrs):
    return {'count': count, 'total_s': total_ms * 1e-3,
            'self_s': total_ms * 1e-3, 'attrs': attrs}


def analysis(ahead=True):
    """Two steps of a program_trace.analyse result; the launch spans
    carry ``ahead`` unless the program is older than it."""
    def launch(count, total_ms, n_out, a):
        return span(count, total_ms, n_out=n_out,
                    **({'ahead': a} if ahead else {}))

    spans = {
        'mx.graph.call': span(2, 40.0, n_in=6),
        'mx.graph.flush': span(2, 20.4),
        'mx.graph.launch': launch(2, 30.0, 446, 0),
        'mx.tape.flush': span(2, 1.0),
        'mx.tape.vjp': launch(4, 16.0, 312, 6),
        'mx.bulk.flush': launch(2, 0.6, 2, 2),
        'mx.trainer.launch': launch(2, 4.0, 600, 4),
    }
    if ahead:
        spans['mx.graph.await'] = span(2, 19.0)
    return {'steps': 2, 'spans': spans}


def test_the_queue_depth_is_the_mean_ahead_of_the_launches():
    # (0 + 6 + 2 + 4) over 2 + 4 + 2 + 2 launches
    assert launch_queue_depth.of_analysis(analysis()) == pytest.approx(1.2)


def test_only_the_launch_spans_are_counted():
    got = analysis()
    got['spans']['mx.graph.call']['attrs']['ahead'] = 100
    assert launch_queue_depth.of_analysis(got) == pytest.approx(1.2)


def test_the_wait_is_a_part_of_the_flush():
    got = analysis()
    assert graph_await_ms.of_analysis(got) == pytest.approx(9.5)
    assert graph_await_ms.of_analysis(got) <= program_trace.span_ms_per_step(
        got, 'mx.graph.flush', 'mx.tape.flush')
    # a program that marks its launches and never waited: a true 0
    del got['spans']['mx.graph.await']
    assert graph_await_ms.of_analysis(got) == 0.0


def test_an_older_program_reads_nothing():
    got = analysis(ahead=False)
    assert launch_queue_depth.of_analysis(got) is None
    assert graph_await_ms.of_analysis(got) is None


WINDOW = program_trace.WINDOW


def host(in_use=True, ahead=True):
    """Host events as trace_reduce.load gives them, in ns: the window
    [1000, 9000) on the main line, launches inside, across its edges and
    outside it."""
    def launch(name, s, e, used):
        attrs = {'n_out': 3}
        if ahead:
            attrs['ahead'] = 1
        if in_use:
            attrs['in_use'] = used
        return (name, s, e, 'python3', attrs)

    return [
        (WINDOW, 1000, 9000, 'python3', {}),
        launch('mx.graph.launch', 200, 900, 15_000_000_000),    # before
        launch('mx.graph.launch', 900, 1200, 7_000_000_000),    # across
        launch('mx.tape.vjp', 2000, 2600, 8_500_000_000),
        launch('mx.bulk.flush', 3000, 3100, 6_000_000_000),
        launch('mx.trainer.launch', 8900, 9500, 8_250_000_000),  # across
        launch('mx.trainer.launch', 9500, 9900, 16_000_000_000),  # after
        # not a launch: its attribute is none of this reader's
        ('mx.graph.call', 1500, 2500, 'python3', {'in_use': 2 * 10 ** 10}),
        ('AllocateRawBuffer', 2100, 2200, 'main/7', {}),
    ]


def test_in_use_is_the_largest_of_the_windows_launches():
    assert launch_hbm_in_use_gb.of_host(host()) == pytest.approx(8.5)


def test_without_the_attribute_in_use_reads_zero():
    """The CPU client keeps no memory statistics: the launches carry
    ``ahead`` and no ``in_use``."""
    assert launch_hbm_in_use_gb.of_host(host(in_use=False)) == 0.0


def test_an_older_programs_launches_read_nothing():
    assert launch_hbm_in_use_gb.of_host(host(ahead=False)) is None


def test_no_window_is_an_error():
    events = [e for e in host() if e[0] != WINDOW]
    with pytest.raises(ValueError, match='chipbench.window'):
        launch_hbm_in_use_gb.of_host(events)
