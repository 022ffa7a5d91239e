"""One span primitive, two sinks (mxnet_tpu/telemetry/trace.py): a
telemetry span is also an event of a running ``jax.profiler`` trace, on
the ``/host:CPU`` plane with its attributes; ``child_span`` outside any
context reaches the profiler alone; ``MXNET_TELEMETRY=0`` silences both;
``profiler.scope`` goes through the same primitive."""

import glob
import os

import jax
import pytest

from mxnet_tpu import profiler, telemetry
from mxnet_tpu.telemetry import trace as _trace

LONG = 'x' * 200


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.configure(enabled=True, sample=1.0)
    telemetry.clear()
    yield
    telemetry.configure(enabled=_trace._env_enabled(),
                        buffer=_trace._env_buffer(),
                        sample=_trace._env_sample())
    telemetry.clear()


def profiled(tmp_path, body):
    """Run ``body()`` inside a jax.profiler trace; the host plane's
    events as {name: [stats dict, ...]}."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    data = jax.profiler.ProfileData.from_file(path)
    host, = [p for p in data.planes if p.name == '/host:CPU']
    found = {}
    for line in host.lines:
        for ev in line.events:
            found.setdefault(ev.name, []).append(
                {**dict(ev.stats), '_start': ev.start_ns,
                 '_end': ev.start_ns + ev.duration_ns})
    return found


def _by_name(name):
    return [e for e in telemetry.events() if e['name'] == name]


def test_a_span_is_in_the_profile_with_its_attributes(tmp_path):
    def body():
        with telemetry.span('train.step', step=7, phase='warm'):
            with telemetry.span('inner', n_out=3):
                pass
    found = profiled(tmp_path, body)
    step, = found['train.step']
    inner, = found['inner']
    assert step['step'] == 7 and step['phase'] == 'warm'
    assert inner['n_out'] == 3
    # on one clock, the child inside its parent
    assert step['_start'] <= inner['_start'] <= inner['_end'] <= step['_end']
    # and both are in the flight recorder as before
    assert _by_name('inner')[0]['parent'] == _by_name('train.step')[0]['span']


def test_set_reaches_both_sinks(tmp_path):
    def body():
        with telemetry.span('launch', n_in=2) as s:
            s.set(n_out=5)
    found = profiled(tmp_path, body)
    assert found['launch'][0]['n_in'] == 2
    assert found['launch'][0]['n_out'] == 5
    assert _by_name('launch')[0]['attrs'] == {'n_in': 2, 'n_out': 5}


def test_only_ints_and_short_strings_reach_the_profile(tmp_path):
    def body():
        with telemetry.span('attrs', n=1, s='short', long=LONG, f=0.5,
                            obj=object()):
            pass
    found = profiled(tmp_path, body)
    stats = {k for k in found['attrs'][0] if not k.startswith('_')}
    assert stats == {'n', 's'}
    # the flight recorder keeps them all
    assert set(_by_name('attrs')[0]['attrs']) == {'n', 's', 'long', 'f',
                                                 'obj'}


def test_child_span_outside_a_context_is_in_the_profile_alone(tmp_path):
    def body():
        with telemetry.child_span('mx.library.hot', n_out=4) as s:
            s.set(compiled=0)
            assert telemetry.current_tc() is None      # roots no trace
    found = profiled(tmp_path, body)
    hot, = found['mx.library.hot']
    assert hot['n_out'] == 4 and hot['compiled'] == 0
    assert telemetry.events() == []


def test_child_span_inside_a_context_is_in_both(tmp_path):
    def body():
        with telemetry.span('caller'):
            with telemetry.child_span('mx.library.hot'):
                pass
    found = profiled(tmp_path, body)
    assert len(found['mx.library.hot']) == 1
    assert _by_name('mx.library.hot')[0]['parent'] == \
        _by_name('caller')[0]['span']


def test_disabled_telemetry_silences_both_sinks(tmp_path):
    telemetry.configure(enabled=False)

    def body():
        with telemetry.span('never', x=1):
            with telemetry.child_span('never.child'):
                pass
        with telemetry.child_span('never.alone') as s:
            s.set(n=1)
    found = profiled(tmp_path, body)
    assert not {'never', 'never.child', 'never.alone'} & set(found)
    assert telemetry.events() == []


def test_outside_a_profile_a_span_still_records():
    # no trace running: the annotation is inactive, the ring is not
    with telemetry.span('quiet', n=1) as s:
        s.set(m=2)
    assert _by_name('quiet')[0]['attrs'] == {'n': 1, 'm': 2}
    # nobody listens to a child_span here: it allocates nothing
    alone = telemetry.child_span('quiet.alone', n=1)
    assert alone is telemetry.child_span('quiet.again')
    with alone as s:
        s.set(m=2)
    assert _by_name('quiet.alone') == []


def test_an_exception_leaves_the_span_and_propagates(tmp_path):
    def body():
        with pytest.raises(KeyError):
            with telemetry.child_span('mx.raises'):
                raise KeyError('k')
        with telemetry.child_span('mx.after'):
            pass
    found = profiled(tmp_path, body)
    raised, = found['mx.raises']
    after, = found['mx.after']
    assert raised['_end'] <= after['_start']        # it was closed


def test_profiler_scope_is_in_the_profile_and_the_tally(tmp_path):
    profiler.dumps(reset=True)

    def body():
        with profiler.scope('fc1:'):
            pass
        with profiler.scope('fc1:'):
            pass
    found = profiled(tmp_path, body)
    assert len(found['fc1:']) == 2
    assert profiler._records['fc1:'][0] == 2
    assert profiler._records['fc1:'][1] >= 0.0
    assert 'fc1:' in profiler.dumps(reset=True)
    assert telemetry.events() == []         # a scope is no telemetry span


def test_profiler_scope_does_not_hang_on_the_telemetry_switch(tmp_path):
    # a user who writes a scope asked for it by name
    telemetry.configure(enabled=False)
    profiler.dumps(reset=True)

    def body():
        with profiler.scope('still:'):
            pass
    found = profiled(tmp_path, body)
    assert len(found['still:']) == 1
    assert profiler._records['still:'][0] == 1
    profiler.dumps(reset=True)
