"""mx.serve dynamic batcher: coalescing, admission control, zero
recompiles after warmup (ISSUE 10).

Deterministic scenarios drive ``DynamicBatcher.run_once`` directly with
a fake clock (no scheduler thread, no sleeps); the threaded tests use
the real scheduler and are re-run under ``MXNET_RACE_CHECK=1`` in a
child pytest (the test_race_ci.py pattern) so the serve locks'
hierarchy declarations are exercised dynamically on every CI run.
"""

import os
import subprocess
import sys
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, profiler, serve
from mxnet_tpu.serve import (DeadlineExceeded, DynamicBatcher, ModelRunner,
                             ServeError, ServerClosed, ServerOverloaded)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    net = gluon.nn.HybridSequential(gluon.nn.Dense(8, in_units=4))
    net.initialize()
    return net


def _runner(buckets=(1, 2, 4, 8)):
    return ModelRunner(_mlp(), (4,), buckets=buckets)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------- buckets
def test_bucket_helpers():
    assert serve.parse_buckets('8,1,4,2,4') == (1, 2, 4, 8)
    with pytest.raises(ValueError):
        serve.parse_buckets('1,x')
    with pytest.raises(ValueError):
        serve.parse_buckets('0,2')
    assert serve.pick_bucket(3, (1, 2, 4, 8)) == 4
    assert serve.pick_bucket(8, (1, 2, 4, 8)) == 8
    assert serve.pick_bucket(9, (1, 2, 4, 8)) is None
    assert serve.pow2_bucket(5, lo=4) == 8
    assert serve.pow2_bucket(1, lo=4) == 4
    assert serve.pow2_bucket(100, lo=4, hi=64) == 64


def test_bucket_env_knob(monkeypatch):
    monkeypatch.setenv('MXNET_SERVE_BUCKETS', '2,16')
    assert serve.default_buckets() == (2, 16)
    monkeypatch.delenv('MXNET_SERVE_BUCKETS')
    assert serve.default_buckets() == (1, 2, 4, 8)


# ----------------------------------------------------------------- runner
def test_runner_prewarms_every_bucket_and_stays_flat():
    r = _runner((1, 2, 4))
    # >= one executable per bucket (the first shape-inference forward
    # may additionally compile child-level executables — harmless, the
    # steady state only ever dispatches the parent's cached graph)
    assert r.warmup_compiles >= 3
    base = r.compile_count
    for n in (1, 2, 3, 4, 1, 3):           # mixed sizes, all post-warmup
        rows, n_pad = r.run_batch([onp.ones(4)] * n)
        assert len(rows) == n
        assert n_pad == r.bucket_for(n) - n
    assert r.compile_count == base         # zero recompiles
    assert r.lint_report is not None


def test_runner_rejects_lint_errors(monkeypatch):
    class _Bad:
        errors = [type('F', (), {'message': 'planted finding'})()]

    monkeypatch.setattr('mxnet_tpu.serve.runner._analysis.lint',
                        lambda *a, **k: _Bad())
    with pytest.raises(ServeError, match='rejected at registration'):
        ModelRunner(_mlp(), (4,), buckets=(1,))


def test_runner_oversize_batch_refused():
    r = _runner((1, 2))
    with pytest.raises(ServeError, match='largest bucket'):
        r.run_batch([onp.ones(4)] * 3)


# ----------------------------------------------------- deterministic batch
def test_deterministic_coalescing_fake_clock():
    clock = _FakeClock()
    b = DynamicBatcher(_runner((1, 2, 4)), max_wait_us=1000, clock=clock,
                       start=False)
    futs = [b.submit(onp.ones(4) * i) for i in range(3)]
    # batching window still open: nothing may dispatch
    assert b.run_once(block=False) == 0
    assert not any(f.done() for f in futs)
    clock.advance(0.002)                   # window expires
    assert b.run_once(block=False) == 3    # ONE coalesced batch
    for i, f in enumerate(futs):
        onp.testing.assert_allclose(
            f.result(1).asnumpy(),
            b.runner.run_batch([onp.ones(4) * i])[0][0].asnumpy(),
            rtol=1e-6)
    s = b.stats()
    assert s['batches'] == 1 and s['completed'] == 3
    assert s['padded_rows'] == 1           # 3 rows padded into bucket 4
    assert s['occupancy_avg'] == 3.0
    b.close()


def test_full_batch_cuts_before_window():
    clock = _FakeClock()
    b = DynamicBatcher(_runner((1, 2, 4)), max_batch=4,
                       max_wait_us=10_000_000, clock=clock, start=False)
    for i in range(4):
        b.submit(onp.ones(4))
    # max_batch reached: the (huge) window must not delay the cut
    assert b.run_once(block=False) == 4
    b.close()


def test_shed_at_capacity():
    clock = _FakeClock()
    b = DynamicBatcher(_runner((1, 2)), queue_depth=2, clock=clock,
                       start=False)
    b.submit(onp.ones(4))
    b.submit(onp.ones(4))
    with pytest.raises(ServerOverloaded):
        b.submit(onp.ones(4))
    assert b.stats()['shed'] == 1
    b.close()


def test_deadline_expires_before_dispatch():
    clock = _FakeClock()
    b = DynamicBatcher(_runner((1, 2)), max_wait_us=0, clock=clock,
                       start=False)
    f = b.submit(onp.ones(4), deadline_ms=50)
    clock.advance(0.06)                    # expired while queued
    dispatched = []
    orig = b.runner.run_batch
    b.runner.run_batch = lambda rows: dispatched.append(len(rows)) \
        or orig(rows)
    assert b.run_once(block=False) == 1
    with pytest.raises(DeadlineExceeded):
        f.result(1)
    assert dispatched == []                # aborted BEFORE device dispatch
    assert b.stats()['expired'] == 1
    b.close()


def test_fault_stall_expires_queued_deadline():
    """kvstore/faults.py-style injection: a dispatch stall (virtual —
    the injected sleep advances the fake clock) makes the next queued
    request's deadline expire deterministically."""
    clock = _FakeClock()
    serve.faults.configure('stall:dispatch:200ms', sleep=clock.advance)
    try:
        b = DynamicBatcher(_runner((1, 2)), max_batch=1, max_wait_us=0,
                           clock=clock, start=False)
        fa = b.submit(onp.ones(4))
        fb = b.submit(onp.ones(4), deadline_ms=100)
        assert b.run_once(block=False) == 1    # A dispatches, stalls 200ms
        assert fa.result(1) is not None
        assert b.run_once(block=False) == 1    # B is now past deadline
        with pytest.raises(DeadlineExceeded):
            fb.result(1)
        assert serve.faults.injected() == {'stall': 1, 'error': 0,
                                           'crash': 0, 'partition': 0,
                                           'kill_host': 0, 'total': 1}
    finally:
        serve.faults.clear()
        b.close()


def test_fault_error_fails_batch_not_server():
    clock = _FakeClock()
    serve.faults.configure('error:dispatch')
    try:
        b = DynamicBatcher(_runner((1, 2)), max_wait_us=0, clock=clock,
                           start=False)
        f1 = b.submit(onp.ones(4))
        b.run_once(block=False)
        with pytest.raises(RuntimeError, match='fault-injected'):
            f1.result(1)
        serve.faults.clear()
        f2 = b.submit(onp.ones(4))             # server still serves
        b.run_once(block=False)
        assert f2.result(1) is not None
        assert b.stats()['failed'] == 1
    finally:
        serve.faults.clear()
        b.close()


def test_bad_fault_spec():
    with pytest.raises(serve.faults.FaultSpecError):
        serve.faults.configure('explode:dispatch:1')
    with pytest.raises(serve.faults.FaultSpecError):
        serve.faults.configure('stall:dispatch:xx')


# ------------------------------------------------- zero-recompile stream
def test_mixed_stream_zero_recompiles():
    """Acceptance: >= 100 mixed-size requests over >= 3 bucket sizes
    complete with ZERO new compiles after warmup (compile counter
    asserted, not eyeballed)."""
    clock = _FakeClock()
    r = _runner((1, 2, 4, 8))
    b = DynamicBatcher(r, max_wait_us=1000, clock=clock, start=False)
    base = r.compile_count
    sizes = []
    orig = r.run_batch
    r.run_batch = lambda rows: sizes.append(len(rows)) or orig(rows)
    futs = []
    for group in [1, 3, 8, 2, 6] * 6:          # 120 requests
        futs.extend(b.submit(onp.ones(4) * i) for i in range(group))
        clock.advance(0.002)
        while b.run_once(block=False):
            pass
    for f in futs:
        assert f.result(1) is not None
    assert len(futs) == 120
    assert r.compile_count == base             # THE guarantee
    s = b.stats()
    assert s['recompiles'] == 0 and s['completed'] == 120
    buckets_hit = {r.bucket_for(n) for n in sizes}
    assert len(buckets_hit) >= 3, buckets_hit
    b.close()


# ------------------------------------------------------------- threaded
def test_threaded_occupancy_and_drain():
    """Real scheduler thread + concurrent clients: the batcher must
    coalesce (occupancy > 1), complete everything, and drain clean.
    Re-run under MXNET_RACE_CHECK=1 by the child-pytest test below."""
    from mxnet_tpu.analysis import race

    b = DynamicBatcher(_runner((1, 2, 4, 8)), max_wait_us=50_000,
                       queue_depth=256)
    n_threads, per = 8, 6
    barrier = threading.Barrier(n_threads)
    futs, flock = [], threading.Lock()
    errs = []

    def client():
        try:
            barrier.wait(10)
            mine = [b.submit(onp.ones(4) * k) for k in range(per)]
            with flock:
                futs.extend(mine)
        except Exception as e:              # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errs
    for f in futs:
        assert f.result(30) is not None
    s = b.stats()
    assert s['completed'] == n_threads * per
    assert s['occupancy_avg'] > 1.0, s      # acceptance: coalescing real
    assert s['recompiles'] == 0
    b.close(drain=True)
    assert b.closed
    with pytest.raises(ServerClosed):
        b.submit(onp.ones(4))
    if race.enabled():
        race.assert_clean()


def test_close_without_drain_rejects_queued():
    clock = _FakeClock()
    b = DynamicBatcher(_runner((1, 2)), clock=clock, start=False)
    f = b.submit(onp.ones(4))
    b.close(drain=False)
    with pytest.raises(ServerClosed):
        f.result(1)
    with pytest.raises(ServerClosed):
        b.submit(onp.ones(4))


def test_close_with_drain_flushes_queue():
    clock = _FakeClock()
    b = DynamicBatcher(_runner((1, 2)), clock=clock, start=False)
    futs = [b.submit(onp.ones(4)) for _ in range(3)]
    b.close(drain=True)
    for f in futs:
        assert f.result(1) is not None


# ------------------------------------------------------- metrics surface
def test_profiler_serving_section_and_stats():
    clock = _FakeClock()
    b = DynamicBatcher(_runner((1, 2)), max_wait_us=0, clock=clock,
                       start=False, name='unit-batcher')
    b.submit(onp.ones(4))
    clock.advance(0.001)
    b.run_once(block=False)
    table = profiler.dumps()
    assert 'Serving (mx.serve)' in table
    assert 'unit-batcher' in table
    assert 'latency_ms p50/p95/p99' in table
    st = serve.stats()
    assert 'unit-batcher' in st
    snap = st['unit-batcher']
    assert snap['completed'] == 1
    assert set(snap['latency_ms']) == {50, 95, 99}
    assert snap['latency_ms'][50] <= snap['latency_ms'][99]
    b.close()
    # a closed server unregisters from both surfaces
    assert 'unit-batcher' not in serve.stats()
    assert 'unit-batcher' not in profiler.dumps()


# ----------------------------------------------------- tier-1 subprocesses
def test_threaded_serve_clean_under_race_check():
    """Soak rerun (test_race_ci.py pattern): the threaded serve tests
    must pass — and assert_clean() — with the dynamic race checker
    instrumenting the serve.queue/serve.slots locks."""
    if os.environ.get('MXNET_RACE_CHECK') == '1':
        pytest.skip('already running under the race checker')
    env = dict(os.environ)
    env['MXNET_RACE_CHECK'] = '1'
    env['JAX_PLATFORMS'] = 'cpu'  # conftest leaves it '' in-proc; '' defeats setdefault
    r = subprocess.run(
        [sys.executable, '-m', 'pytest', '-q', '-x',
         '-p', 'no:cacheprovider',
         os.path.join(REPO, 'tests', 'test_serve.py'),
         os.path.join(REPO, 'tests', 'test_serve_decode.py'),
         '-k', 'threaded'],
        capture_output=True, text=True, timeout=480, cwd=REPO, env=env)
    assert r.returncode == 0, (
        f'threaded serve tests fail under MXNET_RACE_CHECK=1:\n'
        f'{r.stdout[-6000:]}\n{r.stderr[-2000:]}')
