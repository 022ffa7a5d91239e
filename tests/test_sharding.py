"""mx.sharding: partition-rule registry + mesh-scoped sharded hybridize.

The PR's acceptance criteria live here, all on the tier-1 8-fake-device
CPU mesh (conftest forces ``--xla_force_host_platform_device_count=8``):

* the rule registry contract — first match wins, scalars replicate, an
  uncovered param errors naming the nearest rule, user tables register;
* an UNMODIFIED model trains and infers FSDP- and TP-sharded inside
  ``with mx.sharding.mesh(...)``: FSDP forward bit-exact vs single
  device (no contraction splits), TP forward and an adam train step
  allclose, ZeRO-1 optimizer slots partitioned on the data axis;
* zero recompiles after warmup; a mesh *change* retraces by design and
  the recompile-hazard rule documents it as a non-hazard;
* the serve path: llama decode under a dp x tp mesh is token-identical
  to single-device ``generate()`` and the pool donation audit verifies
  aliasing on the genuinely sharded program;
* the analysis pass reports per-device costs and recognizes mesh-axis
  psums as in-step GSPMD collectives (not kvstore pushes).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import analysis, autograd, gluon, nd, parallel, sharding
from mxnet_tpu.gluon import nn
from mxnet_tpu.sharding import (UnmatchedParamError, match_spec,
                                register_rules, resolve_spec, rules_for,
                                shard_factor)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason='needs the 8-device CPU mesh')


def _axes_of(spec):
    """Mesh axes a PartitionSpec actually uses (entries may be tuples)."""
    out = set()
    for e in spec:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a is not None:
                out.add(a)
    return out


# ------------------------------------------------------------- registry
def test_first_match_wins():
    rules = [(r'.*\.weight', P('tp', None)), (r'.*', P('dp'))]
    assert match_spec('encoder.0.weight', (8, 8), rules) == P('tp', None)
    assert match_spec('encoder.0.bias', (8,), rules) == P('dp')


def test_scalars_replicate_unconditionally():
    rules = [(r'.*', P('dp'))]
    assert match_spec('temperature', (), rules) == P()


def test_unmatched_errors_naming_nearest_rule():
    rules = [(r'encoder\..*\.weight', P('tp', None))]
    with pytest.raises(UnmatchedParamError) as ei:
        match_spec('decoder.0.weight', (8, 8), rules)
    assert 'encoder' in str(ei.value)       # nearest rule named
    assert 'decoder.0.weight' in str(ei.value)
    # legacy contract: replicate instead of raising
    assert match_spec('decoder.0.weight', (8, 8), rules,
                      on_unmatched='replicate') == P()


def test_register_custom_arch_table():
    register_rules('sharding_test_arch', 'tp',
                   [(r'.*proj.*', P(None, 'tp')), (r'.*', P())])
    got = rules_for('sharding_test_arch', 'tp')
    assert got[0][1] == P(None, 'tp')
    assert 'sharding_test_arch' in sharding.list_archs()


def test_resolve_spec_drops_nondividing_axis(monkeypatch):
    mesh = parallel.make_mesh(dp=8)
    # 7 % 8 != 0: the axis is dropped (dim replicates)
    assert resolve_spec(P('dp'), (7, 4), mesh) == P()
    # a mesh without the named axis also drops it
    assert resolve_spec(P('tp'), (8, 4), mesh) == P()
    monkeypatch.setenv('MXNET_SHARDING_STRICT', '1')
    with pytest.raises(ValueError):
        resolve_spec(P('dp'), (7, 4), mesh, name='w')


def test_shard_factor():
    mesh = parallel.make_mesh(dp=4, tp=2)
    assert shard_factor(P('dp'), (16, 8), mesh) == 4
    assert shard_factor(P('dp', 'tp'), (16, 8), mesh) == 8
    assert shard_factor(P(), (16, 8), mesh) == 1
    assert shard_factor(P('dp'), (7, 8), mesh) == 1   # non-dividing


# -------------------------------------------------- zero-model-change TP/FSDP
def _mlp(seed=7):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation='relu'), nn.Dense(16))
    net.initialize()
    net.hybridize()
    return net


def test_fsdp_forward_bit_exact():
    """FSDP shards parameters but splits no contraction dim, so the
    sharded forward must be BIT-EXACT vs single device."""
    net = _mlp()
    x = nd.rand(16, 64)
    ref = net(x).asnumpy()
    with sharding.mesh(dp=8):
        got = net(x).asnumpy()
        # params were actually placed sharded on the mesh
        w = net[0].weight.data()._data
        assert len(w.sharding.device_set) == 8
    assert np.array_equal(ref, got)


def test_tp_forward_allclose():
    """TP splits contractions over 'tp' — psum reassociation allows
    float drift, but only epsilon-level."""
    net = _mlp(seed=11)
    x = nd.rand(8, 64)
    ref = net(x).asnumpy()
    tp_rules = [(lambda name, shape: len(shape) <= 1, P()),
                (r'.*0\.weight', P('tp', None)),
                (r'.*1\.weight', P(None, 'tp')),
                (r'.*', P())]
    with sharding.mesh(tp=8, rules=tp_rules):
        got = net(x).asnumpy()
    assert np.allclose(ref, got, rtol=1e-5, atol=1e-5)


def _train_steps(net, steps, xs, ys, mesh_axes=None):
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 0.05})
    import contextlib
    scope = sharding.mesh(**mesh_axes) if mesh_axes \
        else contextlib.nullcontext()
    with scope:
        for x, y in zip(xs, ys):
            with autograd.record():
                out = net(x)
                loss = ((out - y) ** 2).mean()
            loss.backward()
            trainer.step(x.shape[0])
    return trainer


def test_fsdp_train_step_allclose_and_zero1_slots():
    """An unmodified model + Trainer runs a sharded train step inside
    the mesh context; weights track the single-device run and the adam
    slots of a REPLICATED param are partitioned on the data axis
    (ZeRO-1)."""
    xs = [nd.rand(16, 64) for _ in range(2)]
    ys = [nd.rand(16, 16) for _ in range(2)]

    ref_net = _mlp(seed=3)
    _train_steps(ref_net, 2, xs, ys)
    ref = {k: v.data().asnumpy()
           for k, v in ref_net.collect_params().items()}

    net = _mlp(seed=3)
    trainer = _train_steps(net, 2, xs, ys, mesh_axes={'dp': 8})

    got = {k: v.data().asnumpy()
           for k, v in net.collect_params().items()}
    for k in ref:
        assert np.allclose(ref[k], got[k], rtol=1e-5, atol=1e-5), k

    # ZeRO-1: a bias is replicated by the fsdp rules (1-d), but its
    # optimizer slots must be sharded over 'dp'
    zero1_seen = False
    for i, param in enumerate(trainer._params):
        if param.shape and len(param.shape) == 1 and i in trainer._states:
            st = trainer._states[i]
            leaves = st if isinstance(st, (list, tuple)) else [st]
            for leaf in leaves:
                raw = getattr(leaf, '_data', None)
                if raw is not None and raw.shape == param.shape and \
                        'dp' in _axes_of(raw.sharding.spec):
                    zero1_seen = True
    assert zero1_seen, 'no dp-sharded optimizer slot found (ZeRO-1)'


def test_zero_recompiles_after_warmup_and_mesh_change_retraces():
    net = _mlp(seed=5)
    x = nd.rand(16, 64)
    with sharding.mesh(dp=8):
        net(x)
        net(x)                      # populates + warms the cache
        warm = net.compile_count
        for _ in range(3):
            net(x)
        assert net.compile_count == warm        # zero recompiles
    # a DIFFERENT mesh is a new cache entry: retrace by design
    with sharding.mesh(dp=4, devices=jax.devices()[:4]):
        net(x)
        net(x)
        assert net.compile_count > warm


def test_recompile_rule_documents_mesh_nonhazard():
    """Planted case for the recompile-hazard rule: a sharded graph gets
    the documented mesh-change non-hazard as INFO, never a warning."""
    net = _mlp(seed=9)
    x = nd.rand(16, 64)
    with sharding.mesh(dp=8):
        rep = analysis.lint(net, x)
    assert rep.stats.get('mesh_keyed') is True
    mesh_findings = [f for f in rep.findings
                     if f.rule == 'recompile-hazard'
                     and f.data.get('non_hazard') == 'mesh-change-retrace']
    assert len(mesh_findings) == 1
    assert mesh_findings[0].severity == 'info'
    # unsharded trace: no mesh finding, stat present and False
    rep2 = analysis.lint(net, x)
    assert rep2.stats.get('mesh_keyed') is False
    assert not [f for f in rep2.findings
                if f.data.get('non_hazard') == 'mesh-change-retrace']


def test_mesh_env_overrides(monkeypatch):
    monkeypatch.setenv('MXNET_SHARDING_DP', '4')
    with sharding.mesh(dp=8) as ctx:
        assert ctx.axis_sizes == {'dp': 4}
    monkeypatch.setenv('MXNET_SHARDING_DISABLE', '1')
    with sharding.mesh(dp=8) as ctx:
        assert ctx is None
        assert sharding.current() is None


@pytest.mark.parametrize('bulk', [False, True])
def test_eager_loss_composes_with_sharded_forward(bulk):
    """Eager loss/metric math mixes sharded graph outputs with fresh
    host arrays — the dispatch layer lifts the single-device operands
    onto the mesh (ops.registry -> sharding.lift_raws). With the bulking
    engine on, as it is on an accelerator, the ops are recorded into a
    segment under the mesh as off it, and the flush lifts the segment's
    boundary once (tests/test_bulk_mesh.py)."""
    from mxnet_tpu import _bulk
    net = _mlp(seed=13)
    x = nd.rand(16, 64)
    with _bulk.force(bulk), sharding.mesh(dp=8):
        out = net(x)
        label = nd.rand(16, 16)         # fresh single-device array
        diff = out - label
        assert (diff._lazy is not None) == bulk
        val = float((diff ** 2).mean().asnumpy())
    assert np.isfinite(val)


# --------------------------------------------------------- shard_params
def test_shard_params_wrapper_agrees_with_registry():
    mesh = parallel.make_mesh(tp=8)
    rules = [(r'.*\.weight', P('tp', None)), (r'.*', P())]
    params = {'a.weight': nd.rand(16, 8), 'a.bias': nd.rand(16)}
    placed = parallel.shard_params(params, mesh, rules=rules)
    assert placed['a.weight'].sharding.spec[0] == 'tp'
    assert _axes_of(placed['a.bias'].sharding.spec) == set()
    # registry contract on demand: unmatched raises
    with pytest.raises(UnmatchedParamError):
        parallel.shard_params({'x': nd.rand(4, 4)}, mesh,
                              rules=[(r'nomatch', P())],
                              on_unmatched='error')


# ------------------------------------------------------- sharded serving
@pytest.fixture(scope='module')
def llama_net():
    from mxnet_tpu.gluon.model_zoo.llama import llama_tiny
    net = llama_tiny()
    net.initialize()
    net(mx.np.zeros((1, 2)))
    return net


def test_sharded_decode_token_parity_and_donation(llama_net):
    """DecodeServer under a dp x tp mesh: pool pages sharded on 'dp',
    KV heads on 'tp', tokens identical to single-device generate(),
    zero recompiles after warmup, and the donation audit proves every
    page buffer aliases an output on the SHARDED program."""
    from mxnet_tpu.serve import DecodeServer
    prompt = [3, 1, 4, 1, 5]
    want = llama_net.generate(mx.np.array([prompt]), max_new_tokens=6)
    want = [int(t) for t in want.asnumpy()[0, len(prompt):]]

    with sharding.mesh(dp=2, tp=2):
        # 66 pages: divisible by dp=2 so the page dim actually shards
        ds = DecodeServer(llama_net, slots=2, max_length=32,
                          page_size=4, num_pages=66, prefill_chunk=8,
                          start=False)
        k0 = ds._pool[0][0]
        assert k0.sharding.spec[0] == 'dp'      # pages on the data axis
        assert 'tp' in _axes_of(k0.sharding.spec)   # kv heads on tp
        f = ds.submit(prompt, max_new_tokens=6)
        for _ in range(12):
            if f.done():
                break
            ds.step_once()
        assert f.result(1) == want
        assert ds.stats()['recompiles'] == 0
        rep = ds.audit_donation()
        assert rep.stats['aliased_args'] == rep.stats['donated_args']
        ds.close()


# ------------------------------------------------------ analysis surface
def test_per_device_costs():
    net = _mlp(seed=17)
    x = nd.rand(16, 64)
    with sharding.mesh(dp=8):
        g = analysis.trace_block(net, x, train=True)
        rep = analysis.cost_of_graph(g)
    pd = rep.per_device
    assert pd is not None and pd['n_devices'] == 8
    assert pd['flops'] == int(rep.flops / 8)
    assert pd['hbm_bytes_min'] < rep.hbm_bytes_min
    assert pd['peak_hbm_bytes'] < rep.peak_hbm_bytes
    assert any('per-device' in a for a in rep.assumptions)
    assert rep.as_dict()['per_device']['mode'] == 'fsdp'
    # no context -> no per-device section
    g2 = analysis.trace_block(net, x, train=True)
    assert analysis.cost_of_graph(g2).per_device is None


def test_small_collective_recognizes_mesh_axis_psum():
    """A psum bound to a named mesh axis is an in-step GSPMD collective
    — info with mesh_axes data, never the kvstore bucketing warning."""
    from jax.experimental.shard_map import shard_map
    mesh = parallel.make_mesh(dp=8)

    def fn(x):
        f = shard_map(lambda a: jax.lax.psum(a, 'dp'), mesh=mesh,
                      in_specs=P('dp'), out_specs=P())
        return f(x)

    g = analysis.trace_function(
        fn, jax.ShapeDtypeStruct((8, 4), jnp.float32))
    rep = analysis.AnalysisReport(g.name)
    analysis.run_rules(g, rep, rules=['small-collective'])
    found = [f for f in rep.findings if f.rule == 'small-collective']
    assert found, 'mesh-axis psum not reported at all'
    for f in found:
        assert f.severity == 'info'
        assert f.data.get('mesh_axes') == ['dp']
        assert f.data.get('in_step_collective') is True
