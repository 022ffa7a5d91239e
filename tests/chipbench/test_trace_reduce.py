"""chipbench/trace_reduce.py on a hand-built trace (trace_small.json, in
nanoseconds): busy union and idle share, device time by program, by
kernel and by scope, collective time and its exposed part, gaps by what
the host was doing; and every figure PR 28's reduce gave, unchanged."""

import copy
import json
import os

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


def test_gaps_go_to_the_innermost_program_span(reduced):
    by = reduced['idle_by_span_s']
    # device 0's (100, 500) began inside mx.graph.launch inside
    # mx.graph.call inside forward; no span of the program held any
    # other gap, the one on the line 'worker' (4400, 4600) is not the
    # host's, and their phases keep them
    assert by == pytest.approx({
        'mx.graph.launch': 400e-9 / 2, 'feed': 1000e-9 / 2,
        'wait': (1000 + 500 + 3000 + 2000) * 1e-9 / 2})
    assert sum(by.values()) == pytest.approx(
        sum(reduced['idle_by_phase_s'].values()))
    assert dict(reduced['breakdown']['idle_gaps']) == pytest.approx(by)
    assert [name for _, name in reduced['longest_gaps']] == \
        ['wait', 'wait', 'wait', 'feed', 'wait']


def test_a_trace_without_the_programs_spans_keeps_the_phases():
    trace = small_trace()
    trace['host'] = [sp for sp in trace['host']
                     if not sp[0].startswith(tr.PROGRAM)]
    got = tr.reduce(trace)
    assert got['idle_by_span_s'] == pytest.approx(got['idle_by_phase_s'])
    del trace['host']                   # as PR 28's loader gave it
    assert tr.reduce(trace)['idle_by_span_s'] == pytest.approx(
        got['idle_by_phase_s'])


def test_device_time_by_scope_and_by_kernel(reduced):
    d0, d1 = reduced['devices']
    assert reduced['scoped'] is True
    # device 0, a step: fusion.1 (1000) and the backward's flash kernel
    # (1500) under mx.attention, fusion.2 (700) under mx.layer_norm, the
    # Adam kernel (300) under mx.optimizer_step; the op that began before
    # the window carries no scope, the collectives none
    assert d0['scope_s'] == pytest.approx({
        'mx.attention': 2 * 2500e-9, 'mx.layer_norm': 2 * 700e-9,
        'mx.optimizer_step': 2 * 300e-9})
    assert d0['kernel_s'] == pytest.approx({
        'mx_flash_attention_bwd': 2 * 1500e-9, 'mx_adam_step': 2 * 300e-9})
    assert d1['scope_s'] == pytest.approx({'mx.attention': 4000e-9})
    assert d1['kernel_s'] == {}


def test_an_operation_cut_by_the_window_counts_its_part_inside():
    trace = small_trace()
    ops = trace['devices'][0]['ops']
    trace['devices'][0]['ops'] = [
        (name, s, e, kernel, 'mx.layer_norm' if name == 'fusion.0' else scope)
        for name, s, e, kernel, scope in ops]
    got = tr.reduce(trace)['devices'][0]['scope_s']
    assert got['mx.layer_norm'] == pytest.approx((100 + 2 * 700) * 1e-9)


def test_names_that_are_stale_read_as_not_scoped():
    """An executable from a compile cache that an older tree filled: no
    operation carries a scope, and a reader can tell that from a window
    in which the scoped work did not run."""
    trace = small_trace()
    for dev in trace['devices'].values():
        dev['ops'] = [(*op[:4], None) for op in dev['ops']]
    got = tr.reduce(trace)
    assert got['scoped'] is False
    assert all(d['scope_s'] == {} for d in got['devices'])
    assert got['devices'][0]['kernel_s']['mx_adam_step'] == pytest.approx(
        600e-9)


@pytest.mark.parametrize('short, kernel', [
    ('mx_adam_step.153 custom-call (f32[8], f32[8])', 'mx_adam_step'),
    ('mx_flash_attention_fwd custom-call f32[8,128]',
     'mx_flash_attention_fwd'),
    ('fusion.12 fusion f32[8,128]', None),
    ('custom-call.3 fusion f32[8]', None),     # a name is no opcode
    ('all-gather.1', None),
])
def test_kernel_of_an_operation(short, kernel):
    assert tr.kernel_of(short) == kernel


def _same(old, new, path=''):
    """Everything ``old`` holds, ``new`` holds with the same value."""
    if isinstance(old, dict):
        for k, v in old.items():
            assert k in new, f'{path}/{k} is gone'
            _same(v, new[k], f'{path}/{k}')
    elif isinstance(old, list):
        assert len(old) == len(new), path
        for i, (a, b) in enumerate(zip(old, new)):
            _same(a, b, f'{path}[{i}]')
    elif isinstance(old, float):
        assert new == pytest.approx(old, rel=1e-12), path
    else:
        assert new == old, path


def test_every_figure_of_the_reduce_before_the_seam_is_unchanged(reduced):
    """trace_small.reduced_pr28.json is what PR 28's reduce gave for the
    same trace. What the seam changed on purpose: idle_gaps and the
    names in longest_gaps go by the program's span where one held the
    gap; their seconds, and idle_by_phase_s, are as they were."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'trace_small.reduced_pr28.json')) as f:
        old = json.load(f)['reduced']
    new = copy.deepcopy(reduced)
    assert dict(old['breakdown'].pop('idle_gaps')) == pytest.approx(
        new['idle_by_phase_s'])
    assert [sec for sec, _ in old.pop('longest_gaps')] == pytest.approx(
        [sec for sec, _ in new['longest_gaps']])
    _same(old, new)


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
