"""chipbench/trace_reduce.py on a hand-built trace (trace_small.json, in
nanoseconds): busy union and idle share, device time by program,
collective time and its exposed part, gaps by what the host was doing."""

import pytest

from chipbench import trace_reduce as tr

from chipbench_tiny import small_trace


@pytest.fixture(scope='module')
def reduced():
    return tr.reduce(small_trace())


@pytest.mark.parametrize('given, want', [
    ([], []),
    ([(0, 1), (1, 2)], [(0, 2)]),                      # touching
    ([(5, 9), (0, 3), (2, 4)], [(0, 4), (5, 9)]),      # unsorted, overlap
    ([(0, 10), (2, 3)], [(0, 10)]),                    # nested
    ([(3, 3), (4, 2)], []),                            # empty and reversed
])
def test_merged(given, want):
    assert tr.merged(given) == want


@pytest.mark.parametrize('a, b, want', [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [(-5, 1), (3, 9)], [(1, 3)]),
])
def test_minus(a, b, want):
    assert tr.minus(a, b) == want


def test_module_name_drops_the_run_id():
    assert tr.module_name('jit_fused(1234)') == 'jit_fused'
    assert tr.module_name('jit_f(x)(7)') == 'jit_f(x)'


def test_window_and_steps(reduced):
    assert reduced['window_s'] == pytest.approx(10000e-9)
    # the update span before the window is not a step of it
    assert reduced['steps'] == 2


@pytest.mark.parametrize('device, busy_ns', [(0, 8100), (1, 4000)])
def test_busy_union_by_device(reduced, device, busy_ns):
    # device 0: the op that began before the window counts from the
    # window's start (100), then two stretches of 4000 with overlaps
    # counted once
    assert reduced['devices'][device]['busy_s'] == pytest.approx(
        busy_ns * 1e-9)


def test_busy_s_is_the_mean_over_devices(reduced):
    assert reduced['busy_s'] == pytest.approx(6050e-9)


@pytest.mark.parametrize('program, ns', [
    ('jit_pure_fn', 4000), ('jit_bwd', 3000), ('jit_fused', 1000)])
def test_device_time_by_program(reduced, program, ns):
    assert reduced['devices'][0]['program_s'][program] == pytest.approx(
        ns * 1e-9)


def test_collective_time_and_its_exposed_part(reduced):
    d0, d1 = reduced['devices']
    # a step: all-gather 600 of which fusions cover 100 + 200, and an
    # all-reduce of 200 that nothing covers
    assert d0['collective_s'] == pytest.approx(2 * 800e-9)
    assert d0['collective_exposed_s'] == pytest.approx(2 * 500e-9)
    # device 1: an async all-gather (2500, 3500), the fusion ends at 3000
    assert d1['collective_s'] == pytest.approx(1000e-9)
    assert d1['collective_exposed_s'] == pytest.approx(500e-9)
    # and what runs on the async line does not make the device busy
    assert d1['busy_s'] == pytest.approx(4000e-9)


def test_gaps_go_to_what_the_host_was_doing(reduced):
    by = reduced['idle_by_phase_s']
    # device 0: (100, 500) began under forward, (4500, 5500) and
    # (9500, 10000) under wait. device 1: (0, 1000) under feed,
    # (3000, 6000) and (8000, 10000) under wait. Means over 2 devices.
    assert by['forward'] == pytest.approx(400e-9 / 2)
    assert by['feed'] == pytest.approx(1000e-9 / 2)
    assert by['wait'] == pytest.approx((1000 + 500 + 3000 + 2000) * 1e-9 / 2)
    assert sum(by.values()) == pytest.approx(
        reduced['window_s'] - reduced['busy_s'])
    most = reduced["breakdown"]["idle_gaps"][0]
    assert most[0] == "wait" and most[1] == pytest.approx(by["wait"])


def test_breakdown_names_the_heaviest_ops(reduced):
    ops = dict(reduced['breakdown']['device_ops'])
    assert len(reduced['breakdown']['device_ops']) <= 10
    assert next(iter(ops)) == 'fusion.1'          # (2000 + 4000) / 2
    assert ops['fusion.1'] == pytest.approx(3000e-9)
    assert ops['all-gather.1'] == pytest.approx(600e-9)


def test_host_spans_inside_the_window(reduced):
    assert reduced['host_span_s']['forward'] == pytest.approx(1000e-9)
    assert reduced['host_span_s']['update'] == pytest.approx(1000e-9)
    assert 'window' not in reduced['host_span_s']


def test_a_trace_without_a_device_is_refused():
    trace = small_trace()
    trace['devices'] = {}
    with pytest.raises(ValueError, match='no device plane'):
        tr.reduce(trace)


def test_a_trace_without_the_window_span_is_refused():
    trace = small_trace()
    del trace['spans']['chipbench.window']
    with pytest.raises(ValueError, match='window'):
        tr.reduce(trace)


@pytest.mark.parametrize('line, want', [
    ('%fusion.12 = f32[8,128]{1,0:T(8,128)S(1)} fusion(f32[8]{0} %p), '
     'kind=kLoop', 'fusion.12 fusion f32[8,128]'),
    ('%k.3 = (f32[4,2]{1,0:T(8,128)}, s32[]) custom-call(f32[4]{0} %a)',
     'k.3 custom-call (f32[4,2], s32[])'),
    ('all-gather.1', 'all-gather.1'),
])
def test_op_name_keeps_name_opcode_and_result(line, want):
    assert tr.op_name(line) == want
