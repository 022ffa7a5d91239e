"""mx.analysis.costs: analytical FLOP counts vs hand-derived closed
forms (dense, conv, adam, rms_norm), control-flow multipliers
(scan x length, while_trips, cond max-branch), the Op.cost /
fused_kernel hooks for Pallas kernels, donation-aware peak-HBM
liveness against an independent reference walk, device-spec
resolution, and the checked-in resnet50 fixture and the ResNet-50 train
step vs the analytical count (docs/static-analysis.md)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.analysis import costs
from mxnet_tpu.analysis.device_specs import machine_balance

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_DIR = os.path.join(REPO, 'tests', 'fixtures', 'costs')


def cost_of(fn, *args, **config):
    g = analysis.trace_function(fn, *args, name='t')
    return analysis.cost_of_graph(g, **config)


def rel_err(got, want):
    return abs(got - want) / abs(want)


# ------------------------------------------------ closed-form FLOP counts
def test_dense_matmul_exact():
    # dot_general: 2*M*N*K multiply-accumulates
    B, K, N = 8, 32, 16
    c = cost_of(lambda x, w: x @ w, jnp.ones((B, K)), jnp.ones((K, N)))
    assert c.flops == 2 * B * K * N
    assert not c.unmodeled


def test_conv2d_exact():
    # conv_general_dilated: 2 * |out| * KH*KW*Cin/groups
    from jax import lax
    N, Ci, H, W, Co, kh, kw = 2, 4, 16, 16, 8, 3, 3

    def conv(x, w):
        return lax.conv_general_dilated(x, w, (1, 1), 'SAME')

    c = cost_of(conv, jnp.ones((N, Ci, H, W)), jnp.ones((Co, Ci, kh, kw)))
    assert c.flops == 2 * N * Co * H * W * kh * kw * Ci


def test_adam_update_closed_form():
    # per element: rescale + two EMA updates (2 mul + add each), g*g,
    # sqrt, +eps, lr*mean, div, final sub -> 15 elementwise primitives
    # at 1 flop/element under the documented conventions
    from mxnet_tpu.ops import optimizer_ops
    n = 1024
    a = [jnp.ones((n,)), jnp.ones((n,)), jnp.zeros((n,)), jnp.zeros((n,))]
    c = cost_of(lambda *xs: optimizer_ops.adam_update(*xs), *a)
    assert rel_err(c.flops, 15 * n) < 0.01, c.by_primitive


def test_rms_norm_xla_closed_form():
    # XLA lowering (the CPU path: fused_norms only takes Pallas on TPU
    # with d%128==0): square (n) + reduce (n) + normalize mul (n) +
    # gamma mul (n) + per-row mean-div/eps-add/rsqrt (3r) = 4n + 3r
    from mxnet_tpu.ops import nn as opsnn
    rows, d = 8, 96
    n = rows * d
    c = cost_of(lambda x, g: opsnn.rms_norm(x, g),
                jnp.ones((rows, d)), jnp.ones((d,)))
    assert rel_err(c.flops, 4 * n + 3 * rows) < 0.01, c.by_primitive


# ResNet-50's forward pass at 224x224: 7.72 GFLOP an image, at 2 FLOP a
# multiply-accumulate
RESNET50_FWD_FLOPS = 7.72e9


def test_resnet50_fixture_matches_analytical():
    # the checked-in perf_lint fixture (regenerated only on INTENDED
    # graph changes) must stay within 10% of the analytical count
    with open(os.path.join(FIXTURE_DIR, 'resnet50.json')) as f:
        fixture = json.load(f)
    assert rel_err(fixture['flops'], RESNET50_FWD_FLOPS) < 0.10


def test_resnet50_train_step_cost_matches_analytical():
    # cost_report over the ResNet-50 train step itself (forward, backward
    # and an SGD update): within 10% of 3 x the forward count an image,
    # the denominator of a training MFU
    from mxnet_tpu.gluon.model_zoo import vision

    B = 2
    with mx.cpu():
        net = vision.resnet50_v1()
        net.initialize()
        net(mx.np.ones((1, 3, 224, 224)))
        x0 = mx.np.ones((B, 3, 224, 224))
        pure, in_raws, params, aux = net.pure_function(x0, train=True)
    labels = jnp.arange(B, dtype=jnp.int32) % 1000
    key = jax.random.PRNGKey(0)

    def train_step(x, ps, aux_s):
        def loss_of(ps_):
            outs, new_aux = pure(key, (x,), ps_, aux_s)
            logp = jax.nn.log_softmax(outs[0])
            return -logp[jnp.arange(B), labels].mean(), new_aux

        (loss, new_aux), grads = jax.value_and_grad(
            loss_of, has_aux=True)(ps)
        new_ps = jax.tree.map(lambda w, g: w - 0.05 * g, ps, grads)
        return loss, new_ps, new_aux

    c = analysis.cost_report(train_step, in_raws[0], params, tuple(aux),
                             name='resnet50-train-step')
    assert rel_err(c.flops, 3 * RESNET50_FWD_FLOPS * B) < 0.10, c.flops
    assert c.peak_hbm_bytes > 0
    assert 0 < c.mfu_bound <= 1.0


# ------------------------------------------------- Pallas Op.cost hooks
def _stub_eqn(prim_name, in_shapes, out_shapes, dtype=jnp.float32):
    mk = lambda s: types.SimpleNamespace(aval=jax.core.ShapedArray(s, dtype))
    return types.SimpleNamespace(
        primitive=types.SimpleNamespace(name=prim_name),
        invars=[mk(s) for s in in_shapes],
        outvars=[mk(s) for s in out_shapes], params={})


def test_norm_pallas_cost_hook():
    from mxnet_tpu.ops.registry import get_op
    op = get_op('rms_norm')
    assert op.fused_kernel
    eqn = _stub_eqn('pallas_call', [(4, 128), (128,)], [(4, 128)])
    assert op.cost(eqn) == 5 * 4 * 128
    # non-pallas eqns fall through to the primitive table
    assert op.cost(_stub_eqn('mul', [(4, 128)], [(4, 128)])) is None
    assert get_op('layer_norm').fused_kernel


def test_flash_attention_pallas_cost_hook():
    from mxnet_tpu.ops.registry import get_op
    op = get_op('flash_attention')
    assert op.fused_kernel
    b, h, t, s, d = 2, 4, 16, 32, 64
    eqn = _stub_eqn('pallas_call',
                    [(b, h, t, d), (b, h, s, d), (b, h, s, d)],
                    [(b, h, t, d)])
    assert op.cost(eqn) == 4 * b * h * t * s * d
    assert get_op('multi_head_attention').fused_kernel
    # a value head of its own width: QK^T over d, PV over dv
    dv = 32
    eqn = _stub_eqn('pallas_call',
                    [(b * h, t, d), (b * h, s, d), (b * h, s, dv)],
                    [(b * h, t, dv), (b * h, 1, t)])
    assert op.cost(eqn) == 2 * b * h * t * s * (d + dv)
    # the heads packed along the last axis, as the kernels take them
    eqn = _stub_eqn('pallas_call',
                    [(b, t, h * d), (b, s, h * d), (b, s, h * dv)],
                    [(b, t, h * dv), (b, h // 2, 2, t)])
    assert op.cost(eqn) == 2 * b * h * t * s * (d + dv)


def test_flash_attention_backward_kernel_has_its_own_price():
    """mx_flash_attention_bwd takes (q, k, v, do, lse, delta): the scores
    again, dP and dV over v's width, dK and dQ over the scores' width —
    five products a tile where the forward has two."""
    from mxnet_tpu.ops.registry import get_op
    bh, t, s, d, dv = 8, 16, 32, 64, 32
    eqn = _stub_eqn('pallas_call',
                    [(bh, t, d), (bh, s, d), (bh, s, dv), (bh, t, dv),
                     (bh, 1, t), (bh, 1, t)],
                    [(bh, t, d), (bh, s, d), (bh, s, dv)])
    for name in ('flash_attention', 'multi_head_attention'):
        assert get_op(name).cost(eqn) == \
            2 * bh * t * s * (3 * d + 2 * dv)
    # at one width: five products to the forward's two
    same = _stub_eqn('pallas_call',
                     [(bh, t, d), (bh, s, d), (bh, s, d), (bh, t, d),
                      (bh, 1, t), (bh, 1, t)], [(bh, t, d)] * 3)
    fwd = _stub_eqn('pallas_call', [(bh, t, d), (bh, s, d), (bh, s, d)],
                    [(bh, t, d)])
    cost = get_op('flash_attention').cost
    assert 2 * cost(same) == 5 * cost(fwd)


# ------------------------------------------------ control-flow multipliers
def _scan_fn(length):
    def f(x):
        def body(c, _):
            return c @ c + 1.0, ()
        y, _ = jax.lax.scan(body, x, None, length=length)
        return y
    return f


def test_scan_body_costs_scale_with_length():
    x = jnp.ones((16, 16))
    c8 = cost_of(_scan_fn(8), x)
    c16 = cost_of(_scan_fn(16), x)
    body = 2 * 16 ** 3 + 16 * 16     # matmul + add per trip
    assert c8.flops == 8 * body
    assert c16.flops == 16 * body


def test_while_trips_assumption():
    def f(x):
        return jax.lax.while_loop(lambda c: c[0, 0] < 100.0,
                                  lambda c: c * 2.0, x)

    x = jnp.ones((32, 32))
    c1 = cost_of(f, x)                      # default: 1 trip
    c5 = cost_of(f, x, while_trips=5)
    assert c5.flops == 5 * c1.flops > 0
    assert any('while_trips' in a for a in c5.assumptions)


def test_cond_charges_max_branch():
    def f(p, x):
        return jax.lax.cond(p, lambda v: (v @ v) @ v, lambda v: v + 1.0, x)

    n = 16
    c = cost_of(f, jnp.asarray(True), jnp.ones((n, n)))
    assert c.flops == 2 * (2 * n ** 3)      # two chained matmuls
    assert any('cond' in a for a in c.assumptions)


# --------------------------------------------------- peak-HBM liveness
def _var_bytes(v):
    return v.aval.size * v.aval.dtype.itemsize


def _reference_peak(jaxpr, donated, const_bytes):
    """Independent flat liveness walk (top-level eqns only): pinned =
    non-donated invars + consts; transients alloc at def, free after
    last use."""
    last_use = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jax.extend.core.Literal):
                last_use[id(v)] = i
    for v in jaxpr.outvars:
        if not isinstance(v, jax.extend.core.Literal):
            last_use[id(v)] = len(jaxpr.eqns)
    pinned = const_bytes + sum(
        _var_bytes(v) for i, v in enumerate(jaxpr.invars)
        if i not in donated)
    live = {id(v): _var_bytes(v) for i, v in enumerate(jaxpr.invars)
            if i in donated}
    cur = peak = sum(live.values())
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if id(v) not in live:
                live[id(v)] = _var_bytes(v)
                cur += live[id(v)]
        peak = max(peak, cur)
        for v in list(live):
            if last_use.get(v, -1) <= i:
                cur -= live.pop(v)
    return pinned + peak


def test_resnet18_train_peak_hbm_vs_reference_walk():
    from mxnet_tpu.gluon.model_zoo.vision import get_model
    net = get_model('resnet18_v1', classes=10)
    net.initialize()
    g = analysis.trace_block(net, (1, 3, 224, 224), train=True,
                             name='r18')
    assert 'aux' in g.donate_groups       # static_alloc donates aux
    c = analysis.cost_of_graph(g)
    jx = g.closed.jaxpr
    donated = {i for i, a in enumerate(g.args) if a.kind == 'aux'}
    const_bytes = sum(_var_bytes(v) for v in jx.constvars)
    ref = _reference_peak(jx, donated, const_bytes)
    assert rel_err(c.peak_hbm_bytes, ref) < 0.10, (c.peak_hbm_bytes, ref)
    # params dominate at batch 1: peak must cover the pinned weights
    assert c.peak_hbm_bytes >= c.hbm_bytes_min > 0


def test_peak_hbm_donation_lowers_peak():
    def f(x, w):
        return x @ w + 1.0

    g = analysis.trace_function(f, jnp.ones((256, 256)),
                                jnp.ones((256, 256)), name='d')
    base = costs.peak_hbm_bytes(g)
    jx = g.closed.jaxpr
    donated = costs.peak_hbm_bytes_jaxpr(
        jx, donated_idx={0}, const_bytes=0, config={})
    assert donated < base                 # donated input frees after use


# ------------------------------------------------ device specs / surface
def test_device_spec_resolution(monkeypatch, tmp_path):
    default = analysis.get_device_spec()
    assert default['name'] == 'bench-r05'
    v5e = analysis.get_device_spec('v5e-spec')
    assert v5e['peak_flops'] > default['peak_flops']
    custom = {'name': 'x', 'peak_flops': 1e12, 'hbm_bytes_s': 1e11,
              'hbm_bytes': 8e9}
    assert analysis.get_device_spec(custom)['name'] == 'x'
    p = tmp_path / 'spec.json'
    p.write_text(json.dumps(custom))
    assert analysis.get_device_spec(str(p))['name'] == 'x'
    monkeypatch.setenv('MXNET_ANALYSIS_DEVICE_SPEC', 'v4-spec')
    assert analysis.get_device_spec()['name'] == 'v4-spec'
    with pytest.raises((KeyError, ValueError, OSError)):
        analysis.get_device_spec('no-such-device')


def test_roofline_classification_tracks_balance():
    # a bare elementwise op is far under machine balance; a big matmul
    # on the same device is compute-bound
    bw = cost_of(lambda x: x + 1.0, jnp.ones((256, 256)))
    assert bw.classification == 'bandwidth-bound'
    assert bw.intensity < machine_balance(bw.device)
    # 2n^3 flops over 3n^2*4 boundary bytes -> intensity n/6; the
    # bench-r05 balance is 1524 flop/B, so n=16384 clears it
    n = 16384
    mm = cost_of(lambda x, w: x @ w, jnp.ones((n, n)), jnp.ones((n, n)))
    assert mm.classification == 'compute-bound'
    assert mm.mfu_bound == 1.0


def test_cost_report_surface_and_caching():
    def f(x, w):
        return jnp.tanh(x @ w)

    c = analysis.cost_report(f, jnp.ones((8, 16)), jnp.ones((16, 4)))
    d = c.as_dict()
    for key in ('flops', 'bytes_moved', 'hbm_bytes_min',
                'peak_hbm_bytes', 'intensity_flop_per_byte',
                'classification', 'predicted_mfu_bound', 'eqns'):
        assert key in d, key
    assert 'flop' in str(c).lower()
    json.dumps(d)                          # must be JSON-clean
    g = analysis.trace_function(f, jnp.ones((8, 16)), jnp.ones((16, 4)),
                                name='cache')
    c1 = analysis.cost_of_graph(g)
    assert analysis.cost_of_graph(g) is c1          # cached on the graph
    c2 = analysis.cost_of_graph(g, device_spec='v5e-spec')
    assert c2 is not c1                    # overrides bypass the cache
