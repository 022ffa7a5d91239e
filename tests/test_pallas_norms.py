"""Pallas fused LayerNorm/RMSNorm kernels (ops/pallas/fused_norms.py)
against the plain XLA lowering and autograd.

Reference counterpart: src/operator/nn/layer_norm.cc fused kernel tests in
tests/python/unittest/test_operator.py (test_layer_norm). The kernel runs
in interpreter mode on CPU (same discipline as flash attention tests).
"""

import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.ops.pallas import fused_norms as fn
from mxnet_tpu.test_utils import assert_almost_equal


def _np_layernorm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mean) / onp.sqrt(var + eps) * g + b


@pytest.mark.parametrize('shape', [(4, 256), (2, 3, 128), (5, 384)])
def test_fused_layer_norm_kernel_matches_numpy(shape):
    rng = onp.random.default_rng(0)
    x = rng.standard_normal(shape).astype('float32')
    g = rng.standard_normal(shape[-1]).astype('float32')
    b = rng.standard_normal(shape[-1]).astype('float32')
    out = fn._fused_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b),
                         1e-5, False, True)   # force the (interpret) kernel
    assert_almost_equal(onp.asarray(out), _np_layernorm(x, g, b, 1e-5),
                        rtol=1e-5, atol=1e-5)


def test_fused_rms_norm_kernel_matches_numpy():
    rng = onp.random.default_rng(1)
    x = rng.standard_normal((6, 256)).astype('float32')
    g = rng.standard_normal(256).astype('float32')
    out = fn._fused_norm(jnp.asarray(x), jnp.asarray(g), None,
                         1e-6, True, True)
    ref = x / onp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * g
    assert_almost_equal(onp.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_fused_block_rows_vmem_budget():
    assert fn._block_rows(1024, 128) >= 8
    # Mosaic takes a block of 8k rows or the whole array, nothing else
    assert fn._block_rows(7, 128) == 7        # odd row counts: one block
    assert fn._block_rows(4, 10 ** 6) == 4
    bn = fn._block_rows(10 ** 6, 768)         # ragged last block
    assert bn % 8 == 0 and 4 * 768 * bn <= fn._VMEM_BUDGET


def test_layer_norm_op_gradient_matches_composite():
    """The custom recompute-backward equals the differentiated composite."""
    rng = onp.random.default_rng(2)
    x_np = rng.standard_normal((4, 128)).astype('float32')
    g_np = rng.standard_normal(128).astype('float32')
    b_np = rng.standard_normal(128).astype('float32')

    def run(fn_ln):
        x = mx.np.array(x_np)
        g = mx.np.array(g_np)
        b = mx.np.array(b_np)
        for a in (x, g, b):
            a.attach_grad()
        with autograd.record():
            out = fn_ln(x, g, b)
            loss = (out * out).sum()
        loss.backward()
        return x.grad.asnumpy(), g.grad.asnumpy(), b.grad.asnumpy()

    dx1, dg1, db1 = run(lambda x, g, b: mx.npx.layer_norm(x, g, b))

    def composite(x, g, b):
        mean = x.mean(axis=1, keepdims=True)
        var = ((x - mean) ** 2).mean(axis=1, keepdims=True)
        return (x - mean) / mx.np.sqrt(var + 1e-5) * g + b

    dx2, dg2, db2 = run(composite)
    assert_almost_equal(dx1, dx2, rtol=1e-4, atol=1e-4)
    assert_almost_equal(dg1, dg2, rtol=1e-4, atol=1e-4)
    assert_almost_equal(db1, db2, rtol=1e-4, atol=1e-4)


def test_rms_norm_op_gradient():
    rng = onp.random.default_rng(3)
    x = mx.np.array(rng.standard_normal((3, 256)).astype('float32'))
    g = mx.np.array(rng.standard_normal(256).astype('float32'))
    x.attach_grad()
    g.attach_grad()
    with autograd.record():
        loss = mx.npx.rms_norm(x, g).sum()
    loss.backward()
    assert onp.isfinite(x.grad.asnumpy()).all()
    # dgamma for sum-loss = sum of normalized rows
    xf = x.asnumpy()
    xhat = xf / onp.sqrt((xf * xf).mean(-1, keepdims=True) + 1e-6)
    assert_almost_equal(g.grad.asnumpy(), xhat.sum(0), rtol=1e-4,
                        atol=1e-4)


def test_layer_norm_other_axis_still_works():
    rng = onp.random.default_rng(4)
    x = mx.np.array(rng.standard_normal((4, 8, 6)).astype('float32'))
    g = mx.np.array(onp.ones(8, 'f'))
    b = mx.np.array(onp.zeros(8, 'f'))
    out = mx.npx.layer_norm(x, g, b, axis=1)
    ref = _np_layernorm(onp.moveaxis(x.asnumpy(), 1, -1),
                        onp.ones(8, 'f'), onp.zeros(8, 'f'), 1e-5)
    assert_almost_equal(out.asnumpy(), onp.moveaxis(ref, -1, 1),
                        rtol=1e-5, atol=1e-5)


def test_mixed_dtype_promotion_matches_composite():
    """bf16 x with fp32 norm weights promotes to fp32 on every axis —
    the fused path must not silently narrow to the input dtype."""
    rng = onp.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.bfloat16)
    g = jnp.asarray(rng.standard_normal(256), jnp.float32)
    b = jnp.asarray(rng.standard_normal(256), jnp.float32)
    out_kernel = fn._fused_norm(x, g, b, 1e-5, False, True)
    out_xla = fn._fused_norm(x, g, b, 1e-5, False, False)
    assert out_kernel.dtype == jnp.float32
    assert out_xla.dtype == jnp.float32
    out_rms = fn._fused_norm(x, g, None, 1e-6, True, True)
    assert out_rms.dtype == jnp.float32
