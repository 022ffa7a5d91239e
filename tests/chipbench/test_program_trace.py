"""chipbench/program_trace.py on a hand-built trace (every number below
can be read off program_trace_small.json), and on a real CPU profile of
the program's own spans."""

import copy
import json
import os

import pytest

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr

NS = 1e-9


def small():
    """program_trace_small.json as ``program_trace.of_loaded`` gives a
    loaded profile."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'program_trace_small.json')) as f:
        raw = json.load(f)
    return {'host': [tuple(e) for e in raw['host']],
            'devices': {int(k): [tuple(e) for e in v]
                        for k, v in raw['devices'].items()}}


@pytest.fixture(scope='module')
def got():
    return pt.analyse(small())


def test_window_and_steps(got):
    assert got['window_ns'] == (0, 10000)
    assert got['steps'] == 2        # the update before the window is not one


@pytest.mark.parametrize('name, count, total, self_time', [
    ('mx.graph.call', 3, 860, 380),         # 2 x 400 + the worker's 60
    ('mx.graph.flush', 2, 80, 80),
    ('mx.graph.launch', 2, 400, 400),
    ('mx.tape.backward', 2, 1120, 300),     # 560 - (70 + 40 + 300)
    ('mx.tape.flush', 2, 140, 40),          # 70 - the bulk flush's 50
    ('mx.bulk.flush', 2, 100, 100),
    ('mx.tape.vjp', 4, 680, 680),
    ('mx.trainer.step', 2, 960, 460),       # 480 - (50 + 200)
    ('mx.trainer.hyper', 2, 100, 100),
    ('mx.trainer.launch', 2, 400, 400),
])
def test_span_time_and_self_time(got, name, count, total, self_time):
    sp = got['spans'][name]
    assert sp['count'] == count
    assert sp['total_s'] == pytest.approx(total * NS)
    assert sp['self_s'] == pytest.approx(self_time * NS)


def test_a_span_on_another_line_is_nobodys_child(got):
    # the worker's mx.graph.call lies inside the main thread's in time;
    # as its child it would take 60 ns off that span's self time
    assert got['spans']['mx.graph.call']['self_s'] == \
        pytest.approx((2 * 160 + 60) * NS)


def test_attributes_are_summed(got):
    assert got['spans']['mx.tape.vjp']['attrs'] == {'n_out': 30}
    assert got['spans']['mx.trainer.launch']['attrs'] == \
        {'n_in': 86, 'n_out': 60}
    assert pt.launch_outputs_per_step(got) == (4 + 30 + 60 + 8) / 2


def test_allocations_are_clipped_to_their_launch_span(got):
    # nested events once; the buffer that straddles the launch's end up
    # to that end; the one inside no launch not at all
    assert got['alloc']['mx.graph.launch']['s'] == pytest.approx(90 * NS)
    assert got['alloc']['mx.graph.launch']['buffers'] == 2
    assert got['alloc']['mx.trainer.launch']['s'] == pytest.approx(20 * NS)
    assert got['alloc']['mx.trainer.launch']['buffers'] == 1
    assert got['alloc']['mx.tape.vjp'] == {'s': 0.0, 'buffers': 0}
    assert pt.launch_alloc_ms_per_step(got) == pytest.approx(110e-6 / 2)


def test_the_part_of_a_phase_no_span_covers(got):
    phases = got['phases']
    assert phases['forward']['uncovered_s'] == pytest.approx(200 * NS)
    assert phases['loss']['uncovered_s'] == pytest.approx(
        phases['loss']['s']) == pytest.approx(600 * NS)
    assert phases['backward']['uncovered_s'] == pytest.approx(80 * NS)
    assert phases['update']['s'] == pytest.approx(1000 * NS)
    assert phases['update']['uncovered_s'] == pytest.approx(40 * NS)


def test_idle_goes_to_the_innermost_span(got):
    # device 0's four gaps, halved by the mean over two devices
    assert got['idle_by_span_s'] == pytest.approx({
        'between': 200 * NS, 'backward': 100 * NS,
        'mx.trainer.launch': 425 * NS, 'mx.graph.launch': 80 * NS})


def test_per_step_readings(got):
    assert pt.span_ms_per_step(got, 'mx.graph.flush', 'mx.tape.flush') == \
        pytest.approx(220e-6 / 2)
    assert pt.span_ms_per_step(got, 'mx.trainer.step', self_time=True) == \
        pytest.approx(460e-6 / 2)
    assert pt.span_ms_per_step(got, 'mx.trainer.place') == 0.0


def test_a_trace_without_the_programs_spans():
    """An older commit's profile: every span reads 0.0 seconds, nothing
    raises, and the readers report nothing rather than a false 0 ms."""
    trace = small()
    trace['host'] = [e for e in trace['host']
                     if not e[0].startswith(pt.PROGRAM)]
    got = pt.analyse(trace)
    assert got['steps'] == 2 and got['spans'] == {} and got['alloc'] == {}
    assert pt.span_seconds(got, 'mx.graph.launch') == 0.0
    assert pt.span_seconds(got, 'mx.graph.call', self_time=True) == 0.0
    assert got['phases']['update']['uncovered_s'] == pytest.approx(
        1000 * NS)
    assert set(got['idle_by_span_s']) == {'between', 'backward', 'update',
                                          'forward'}
    assert pt.span_ms_per_step(got, 'mx.graph.launch') is None
    assert pt.launch_alloc_ms_per_step(got) is None
    assert pt.launch_outputs_per_step(got) is None
    assert '\n'.join(pt.report(got))


def test_a_trace_without_a_device_plane(got):
    trace = small()
    trace['devices'] = {}
    host_only = pt.analyse(trace)
    assert host_only['idle_by_span_s'] == {}
    assert host_only['spans'] == got['spans']


def test_no_window_is_an_error():
    trace = copy.deepcopy(small())
    trace['host'] = [e for e in trace['host'] if e[0] != pt.WINDOW]
    with pytest.raises(ValueError, match='chipbench.window'):
        pt.analyse(trace)


@pytest.mark.parametrize('text, scope', [
    ('jit(pure_fn)/jit(main)/jvp(mx.attention)/dot_general', 'mx.attention'),
    ('jit(pure_fn)/mx.layer_norm/mul:', 'mx.layer_norm'),   # a v5e tf_op
    ('jit(f)/transpose(jvp(mx.attention))/mul', 'mx.attention'),
    ('jit(fused)/mx.optimizer_step/sqrt', 'mx.optimizer_step'),
    ('jit(pure_fn)/jit(main)/dot_general', None),
])
def test_scope_of_an_operation(text, scope):
    assert tr.scope_of(text) == scope


def test_device_seconds_by_scope_from_the_events_metadata(tmp_path):
    """A device plane as a v5e profile holds it (my chip run, PR 27): the
    op_name is a string stat (``tf_op``) of the operation's event
    metadata."""
    space = tr.schema().XSpace()

    def plane(dev, ops):
        pl = space.planes.add(id=dev, name=f'/device:TPU:{dev}')
        for key, op_name, opcode in (
                (1, 'jit(f)/jvp(mx.attention)/dot_general', 'custom-call'),
                (2, 'jit(f)/mx.layer_norm/mul:', 'fusion'),
                (3, 'jit(f)/jit(main)/add', 'fusion')):
            meta = pl.event_metadata[key]
            meta.id = key
            meta.name = f'%k.{key} = f32[8]{{0}} {opcode}()'
            meta.stats.add(metadata_id=9, str_value='file.py:7')
            meta.stats.add(metadata_id=26, str_value=op_name)
        steps = pl.lines.add(name='Steps', timestamp_ns=5)
        steps.events.add(metadata_id=1, offset_ps=0, duration_ps=10 ** 7)
        xla_ops = pl.lines.add(name='XLA Ops', timestamp_ns=5)
        for key, start, dur in ops:
            xla_ops.events.add(metadata_id=key, offset_ps=start * 1000,
                               duration_ps=dur * 1000)

    plane(0, [(1, 1000, 300), (2, 1300, 350), (3, 2000, 500),
              (1, 9900, 400)])
    plane(1, [(3, 0, 10000)])
    space.planes.add(name='/host:CPU')
    path = tmp_path / 'x.xplane.pb'
    path.write_bytes(space.SerializeToString())
    loaded = tr.load(str(path))
    assert loaded['scopes_read']
    # every operation with its kernel (a custom-call's name) and scope
    assert loaded['devices'][0]['ops'] == [
        ('k.1 custom-call f32[8]', 1005, 1305, 'k', 'mx.attention'),
        ('k.2 fusion f32[8]', 1305, 1655, None, 'mx.layer_norm'),
        ('k.3 fusion f32[8]', 2005, 2505, None, None),
        ('k.1 custom-call f32[8]', 9905, 10305, 'k', 'mx.attention')]
    # the line starts at 5 ns; the last attention op is cut by the window
    got = pt.device_seconds_by_scope(loaded, 0, 10005)
    assert got == pytest.approx({
        'mx.attention': (300 + 100) / 2 * NS,
        'mx.layer_norm': 350 / 2 * NS,
        'other': (500 + 10000) / 2 * NS})


def test_the_schema_reads_what_the_profiler_writes(tmp_path):
    """The shipped schema against the real thing: what the profiler
    writes here, read back by both ProfileData and the schema."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation('chipbench.window'):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = pt.profile_under(str(tmp_path))
    space = tr.schema().XSpace()
    with open(path, 'rb') as f:
        space.ParseFromString(f.read())
    data = jax.profiler.ProfileData.from_file(path)
    assert [p.name for p in space.planes] == [p.name for p in data.planes]
    # no device plane on the CPU: nothing to sum, nothing raised
    assert pt.device_seconds_by_scope(tr.load(path), 0, 2 ** 62) == {}


def test_without_a_schema_the_print_out_says_so(tmp_path, monkeypatch,
                                                capsys):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(pt.WINDOW):
            pass
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(tr, 'schema', lambda: None)
    loaded = tr.load(pt.profile_under(str(tmp_path)))
    assert not loaded['scopes_read']
    assert pt.device_seconds_by_scope(loaded, 0, 1) is None
    assert pt.main([str(tmp_path)]) == 0
    assert 'no xplane_pb2' in capsys.readouterr().out


def test_a_runs_profile_is_parsed_once(tmp_path, monkeypatch):
    """The reduction and every reader of the program's spans share one
    parse of the run's .xplane.pb, by each of the two parsers."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(pt.WINDOW):
            pass
    finally:
        jax.profiler.stop_trace()
    parses = {'ProfileData': 0, 'schema': 0}
    real_from_file = jax.profiler.ProfileData.from_file
    real_op_scopes = tr.op_scopes

    def from_file(path):
        parses['ProfileData'] += 1
        return real_from_file(path)

    def op_scopes(path, schema):
        parses['schema'] += 1
        return real_op_scopes(path, schema)

    monkeypatch.setattr(jax.profiler.ProfileData, 'from_file', from_file)
    monkeypatch.setattr(tr, 'op_scopes', op_scopes)
    run = {'trace_dir': str(tmp_path)}
    with pytest.raises(ValueError, match='no device plane'):
        tr.reduce_dir(run['trace_dir'])          # what run.py calls first
    for _ in range(3):                           # then reader after reader
        pt.of_run(run)
    assert pt.main([str(tmp_path)]) == 0         # and the print-out
    assert parses == {'ProfileData': 1, 'schema': 1}


def test_the_print_out_names_every_span(got):
    text = '\n'.join(pt.report(got))
    for name in got['spans']:
        assert name in text
    assert 'between' in text


def test_a_real_cpu_profile_of_the_programs_spans(tmp_path):
    """The loader on what the profiler writes here: spans opened through
    mx.telemetry inside a chipbench.window annotation come back with
    their attributes, and the CLI prints them."""
    import jax
    from mxnet_tpu import telemetry

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(pt.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation(pt.STEP):
                    with telemetry.child_span('mx.trainer.step',
                                              n_params=7):
                        with telemetry.child_span('mx.trainer.launch',
                                                  n_in=4) as launch:
                            launch.set(n_out=5)
    finally:
        jax.profiler.stop_trace()
    got = pt.of_run({'trace_dir': str(tmp_path)})
    assert got is pt.of_dir(str(tmp_path))      # analysed once
    assert got['steps'] == 3
    assert got['spans']['mx.trainer.step']['count'] == 3
    assert got['spans']['mx.trainer.step']['attrs'] == {'n_params': 21}
    assert got['spans']['mx.trainer.launch']['attrs'] == \
        {'n_in': 12, 'n_out': 15}
    step = got['spans']['mx.trainer.step']
    assert 0 <= step['self_s'] <= step['total_s']
    assert pt.launch_outputs_per_step(got) == 5
    assert pt.launch_alloc_ms_per_step(got) == 0.0   # the CPU client: none
    assert got['idle_by_span_s'] == {}
    assert pt.main([str(tmp_path)]) == 0
