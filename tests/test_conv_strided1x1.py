"""Strided-1x1 convolution rewrite: slice-then-conv parity.

ops/nn.py rewrites a 1x1 stride-s pad-0 conv as a stride-grid slice plus a
stride-1 conv, so the VJP stays at the low resolution instead of XLA's
full-resolution lhs-dilated expansion (the ResNet-50 downsample
data-gradients were 4x oversized). Reference parity target:
src/operator/nn/convolution.cc strided conv semantics.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import nn as N
from jax import lax


def _ref_conv(x, w, stride, pad):
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ('NCHW', 'OIHW', 'NCHW'))
    return lax.conv_general_dilated(
        x, w, stride, [(p, p) for p in pad], dimension_numbers=dn)


@pytest.mark.parametrize('shape,stride', [
    ((4, 16, 9, 9), (2, 2)),       # odd spatial
    ((2, 8, 10, 11), (3, 2)),      # mixed stride, mixed parity
    ((2, 64, 56, 56), (2, 2)),     # the ResNet downsample shape family
])
def test_forward_and_grad_parity(shape, stride):
    kx = jax.random.PRNGKey(0)
    x = jax.random.normal(kx, shape, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(kx, 1),
                          (shape[1] * 2, shape[1], 1, 1), jnp.float32)

    got = N.convolution(x, w, stride=stride, pad=(0, 0), no_bias=True)
    ref = _ref_conv(x, w, stride, (0, 0))
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-6, atol=1e-6)

    g_got = jax.grad(lambda a: N.convolution(
        a, w, stride=stride, pad=(0, 0), no_bias=True).sum())(x)
    g_ref = jax.grad(lambda a: _ref_conv(a, w, stride, (0, 0)).sum())(x)
    onp.testing.assert_allclose(onp.asarray(g_got), onp.asarray(g_ref),
                                rtol=1e-6, atol=1e-6)

    gw_got = jax.grad(lambda ww: (N.convolution(
        x, ww, stride=stride, pad=(0, 0), no_bias=True) ** 2).sum())(w)
    gw_ref = jax.grad(lambda ww: (_ref_conv(x, ww, stride, (0, 0)) ** 2
                                  ).sum())(w)
    onp.testing.assert_allclose(onp.asarray(gw_got), onp.asarray(gw_ref),
                                rtol=1e-5, atol=1e-5)


def test_padded_strided_1x1_not_rewritten():
    """pad>0 must take the plain conv path (slice would drop positions)."""
    kx = jax.random.PRNGKey(2)
    x = jax.random.normal(kx, (2, 4, 8, 8), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(kx, 1), (4, 4, 1, 1),
                          jnp.float32)
    got = N.convolution(x, w, stride=(2, 2), pad=(1, 1), no_bias=True)
    ref = _ref_conv(x, w, (2, 2), (1, 1))
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-6, atol=1e-6)


def test_grouped_strided_1x1():
    kx = jax.random.PRNGKey(3)
    x = jax.random.normal(kx, (2, 8, 8, 8), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(kx, 1), (8, 4, 1, 1),
                          jnp.float32)
    got = N.convolution(x, w, stride=(2, 2), pad=(0, 0), num_group=2,
                        no_bias=True)
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ('NCHW', 'OIHW', 'NCHW'))
    ref = lax.conv_general_dilated(x, w, (2, 2), [(0, 0), (0, 0)],
                                   dimension_numbers=dn,
                                   feature_group_count=2)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(ref),
                                rtol=1e-6, atol=1e-6)
