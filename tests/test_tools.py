"""Tools suite (reference tools/: im2rec, launch, parse_log, diagnose)."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, 'tools')

sys.path.insert(0, TOOLS)


def _make_image_tree(root, n_per_class=3, classes=('cat', 'dog')):
    from PIL import Image
    rng = np.random.RandomState(0)
    for cls in classes:
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = rng.randint(0, 255, (48, 64, 3), dtype=np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f'{cls}_{i}.jpg'))


def test_im2rec_roundtrip(tmp_path):
    import im2rec
    from mxnet_tpu import recordio

    img_root = tmp_path / 'images'
    _make_image_tree(str(img_root))
    prefix = str(tmp_path / 'data')
    assert im2rec.main([prefix, str(img_root), '--list', '--recursive']) == 0
    assert os.path.exists(prefix + '.lst')
    assert im2rec.main([prefix, str(img_root), '--resize', '32',
                        '--num-thread', '2']) == 0
    assert os.path.exists(prefix + '.rec')
    assert os.path.exists(prefix + '.idx')

    rec = recordio.MXIndexedRecordIO(prefix + '.idx', prefix + '.rec', 'r')
    assert len(rec.keys) == 6
    labels = set()
    for k in rec.keys:
        header, img = recordio.unpack_img(rec.read_idx(k))
        labels.add(float(header.label))
        assert img.shape[0] >= 32 and img.shape[1] >= 32
    rec.close()
    assert labels == {0.0, 1.0}


def test_im2rec_pass_through(tmp_path):
    import im2rec
    from mxnet_tpu import recordio

    img_root = tmp_path / 'images'
    _make_image_tree(str(img_root), n_per_class=2, classes=('a',))
    prefix = str(tmp_path / 'raw')
    im2rec.main([prefix, str(img_root), '--list', '--recursive'])
    im2rec.main([prefix, str(img_root), '--pass-through'])
    rec = recordio.MXIndexedRecordIO(prefix + '.idx', prefix + '.rec', 'r')
    header, blob = recordio.unpack(rec.read_idx(rec.keys[0]))
    assert blob[:2] == b'\xff\xd8'  # JPEG magic: raw bytes, not re-encoded
    rec.close()


def test_parse_log(tmp_path):
    import parse_log

    log = '\n'.join([
        'INFO Epoch[0] Batch [20]\tSpeed: 1000.00 samples/sec\taccuracy=0.50',
        'INFO Epoch[0] Batch [40]\tSpeed: 3000.00 samples/sec\taccuracy=0.60',
        'INFO Epoch[0] Validation-accuracy=0.700000',
        'INFO Epoch[1] Batch [20]\tSpeed: 2000.00 samples/sec\taccuracy=0.80',
    ])
    epochs = parse_log.parse(log.splitlines())
    assert epochs[0]['speed'] == [1000.0, 3000.0]
    assert epochs[0]['train']['accuracy'] == pytest.approx(0.6)
    assert epochs[0]['val']['accuracy'] == pytest.approx(0.7)
    csv = parse_log.render(epochs, 'csv')
    assert csv.splitlines()[1].startswith('0,2000.00')
    md = parse_log.render(epochs, 'markdown')
    assert md.count('\n') >= 3


def test_launch_local_env_plumbing(tmp_path):
    out = tmp_path / 'ranks'
    out.mkdir()
    script = tmp_path / 'worker.py'
    script.write_text(
        'import os\n'
        'rank = os.environ["MX_PROC_ID"]\n'
        'open(os.path.join(%r, rank), "w").write(\n'
        '    os.environ["MX_NPROC"] + " " + os.environ["MX_COORDINATOR"]\n'
        '    + " " + os.environ["DMLC_WORKER_ID"])\n' % str(out))
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, 'launch.py'), '-n', '3',
         '--launcher', 'local', '--env', 'FOO=bar', '--',
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    ranks = sorted(os.listdir(out))
    assert ranks == ['0', '1', '2']
    body = (out / '1').read_text().split()
    assert body[0] == '3' and body[2] == '1'


def test_diagnose_runs():
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    r = subprocess.run([sys.executable, os.path.join(TOOLS, 'diagnose.py')],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    assert 'Python Info' in r.stdout
    assert 'mxnet_tpu    : 2.0.0' in r.stdout


def test_flakiness_checker_spec_parsing():
    """Reference tools/flakiness_checker.py CLI spec forms."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'flakiness_checker', 'tools/flakiness_checker.py')
    fc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fc)
    p, name = fc.parse_test_spec('test_tools.py::test_diagnose_runs')
    assert p.endswith('test_tools.py') and name == 'test_diagnose_runs'
    p2, name2 = fc.parse_test_spec('test_diagnose_runs')
    assert p2.endswith('test_tools.py') and name2 == 'test_diagnose_runs'
    p3, name3 = fc.parse_test_spec('test_tools.py')
    assert p3.endswith('test_tools.py') and name3 is None


def test_flakiness_checker_race_mode(monkeypatch):
    """--race injects MXNET_RACE_CHECK=1 into every trial's env (and
    plain trials leave it unset) without touching the parent env."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'flakiness_checker', 'tools/flakiness_checker.py')
    fc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fc)
    seen = []

    class _Res:
        returncode = 0
        stdout = b''

    def fake_run(cmd, env=None, capture_output=None):
        seen.append(env)
        return _Res()

    monkeypatch.setattr(fc.subprocess, 'run', fake_run)
    monkeypatch.delenv('MXNET_RACE_CHECK', raising=False)
    fails = fc.run_trials('tests/test_tools.py', None, 2, seed=0,
                          verbosity=0, race=True)
    assert fails == 0 and len(seen) == 2
    assert all(e.get('MXNET_RACE_CHECK') == '1' for e in seen)
    seen.clear()
    fc.run_trials('tests/test_tools.py', None, 1, seed=0, verbosity=0)
    assert 'MXNET_RACE_CHECK' not in seen[0]
    assert 'MXNET_RACE_CHECK' not in os.environ


# ------------------------------------------------ perf_lint (roofline CI)
def test_perf_lint_cli_gates_representative_models():
    """The roofline CI gate: tools/perf_lint.py over resnet50 / bert /
    llama-decode must exit 0 — zero error-severity findings and every
    analytical cost total inside the checked-in fixture tolerance
    (tests/fixtures/costs). A nonzero exit here is a graph-shape perf
    regression even if the numerics still pass."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'perf_lint.py')],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'}, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert 'clean vs fixtures' in proc.stdout, proc.stdout


# ------------------------------------------------ trace_dump (telemetry)
def test_trace_dump_smoke_cli():
    """tools/trace_dump.py --smoke generates a demo trace, renders the
    span tree and self-checks connectivity — all WITHOUT importing jax
    (the tool loads mx.telemetry standalone by file path)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'trace_dump.py'),
         '--smoke'],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert 'SMOKE OK' in proc.stdout
    assert 'smoke.request' in proc.stdout


def test_trace_dump_reads_dump_json_and_converts(tmp_path):
    from mxnet_tpu import telemetry

    telemetry.configure(enabled=True, sample=1.0)
    telemetry.clear()
    with telemetry.span('cli.root', who='test_tools'):
        with telemetry.span('cli.leg'):
            pass
    dump = str(tmp_path / 'run.trace.json')
    telemetry.dump_json(dump)
    telemetry.clear()

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'trace_dump.py'),
         dump],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert 'cli.root' in proc.stdout and 'cli.leg' in proc.stdout

    out = str(tmp_path / 'chrome.json')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'trace_dump.py'),
         dump, '--chrome', out],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    with open(out) as f:
        doc = json.load(f)
    names = {e['name'] for e in doc['traceEvents']
             if e.get('ph') == 'X'}
    assert names == {'cli.root', 'cli.leg'}
