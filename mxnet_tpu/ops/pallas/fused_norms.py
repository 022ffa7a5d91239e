"""Fused row-normalization Pallas TPU kernels: LayerNorm and RMSNorm.

Functional parity target: the reference's fused norm kernels
(``src/operator/nn/layer_norm.cc`` — hand-fused CUDA computing mean/var and
the normalized output in one pass) and the RMSNorm used by Llama-family
models.

TPU re-design: one kernel program per block of rows; the block lives in
VMEM, statistics are computed in fp32 on the VPU, and the row is read from
HBM exactly once (XLA's default lowering reads it twice: once for the
statistics reduction, once for normalization). Feature dim sits on the
lane axis. Backward is plain XLA math via custom_vjp (recompute beats
storing per-row statistics, mirroring flash_attention.py's choice).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _on_tpu, _under_mesh

_VMEM_BUDGET = 2 * 1024 * 1024   # bytes of fp32 workspace per block


def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps, rms):
    x = x_ref[...].astype(jnp.float32)            # (bn, D)
    if rms:
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(ms + eps)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps)
    y = y * g_ref[...].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _block_rows(n, d):
    """Row block whose fp32 image fits the VMEM budget: the whole array
    when it fits, else a power of two (>= 8, Mosaic's sublane rule) with
    a ragged last block — rows are independent, so its out-of-range rows
    compute garbage that is never written back."""
    cap = max(8, _VMEM_BUDGET // (4 * d))
    cap = 1 << (cap.bit_length() - 1)
    return n if n <= cap else cap


def _ln_pallas(x2, gamma, beta, eps, rms, interpret, out_dtype):
    n, d = x2.shape
    bn = _block_rows(n, d)
    base = functools.partial(_ln_kernel, eps=eps, rms=rms)
    in_specs = [pl.BlockSpec((bn, d), lambda i: (i, 0)),
                pl.BlockSpec((d,), lambda i: (0,))]
    args = [x2, gamma]
    if beta is not None:
        kernel = base
        in_specs.append(pl.BlockSpec((d,), lambda i: (0,)))
        args.append(beta)
    else:
        def kernel(x_ref, g_ref, o_ref):
            base(x_ref, g_ref, None, o_ref)

    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        interpret=interpret,
        name='mx_fused_rms_norm' if rms else 'mx_fused_layer_norm',
    )(*args)


def _out_dtype(x, gamma, beta):
    """Match the composite lowering's promotion (`out * gamma + beta`):
    mixed-precision models keeping norm weights in fp32 get fp32 out."""
    if beta is None:
        return jnp.result_type(x.dtype, gamma.dtype)
    return jnp.result_type(x.dtype, gamma.dtype, beta.dtype)


def _ln_xla(x, gamma, beta, eps, rms):
    xf = x.astype(jnp.float32)
    if rms:
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mean
        y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps)
    y = y * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(_out_dtype(x, gamma, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_norm(x, gamma, beta, eps, rms, use_pallas):
    if use_pallas:
        d = x.shape[-1]
        x2 = x.reshape((-1, d))
        return _ln_pallas(x2, gamma, beta, eps, rms,
                          interpret=not _on_tpu(),
                          out_dtype=_out_dtype(x, gamma, beta)
                          ).reshape(x.shape)
    return _ln_xla(x, gamma, beta, eps, rms)


def _fused_norm_fwd(x, gamma, beta, eps, rms, use_pallas):
    return _fused_norm(x, gamma, beta, eps, rms, use_pallas), \
        (x, gamma, beta)


def _fused_norm_bwd(eps, rms, use_pallas, res, g):
    """Recompute-statistics backward in fp32 XLA (reference
    layer_norm.cc backward computes the same three reductions)."""
    x, gamma, beta = res
    f32 = jnp.float32
    xf, gf = x.astype(f32), g.astype(f32)
    gm = gamma.astype(f32)
    red = tuple(range(x.ndim - 1))
    if rms:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(ms + eps)
        xhat = xf * rstd
        dgamma = jnp.sum(gf * xhat, axis=red)
        dy = gf * gm
        # d/dx of x * rsqrt(mean(x^2)+eps)
        dx = rstd * (dy - xhat * jnp.mean(dy * xhat, -1, keepdims=True))
        dbeta = None if beta is None else jnp.sum(gf, axis=red)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = xc * rstd
        dgamma = jnp.sum(gf * xhat, axis=red)
        dbeta = None if beta is None else jnp.sum(gf, axis=red)
        dy = gf * gm
        dx = rstd * (dy - jnp.mean(dy, -1, keepdims=True)
                     - xhat * jnp.mean(dy * xhat, -1, keepdims=True))
    out = (dx.astype(x.dtype), dgamma.astype(gamma.dtype))
    if beta is None:
        return out + (None,)
    return out + (dbeta.astype(beta.dtype),)


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


# round the call, so that the Pallas kernel and the XLA path both carry
# it and a profile's device operations can be put down to the norm
SCOPE = 'mx.layer_norm'


def _use_pallas(d):
    return _on_tpu() and not _under_mesh() and d > 0 and d % 128 == 0


def fused_layer_norm(x, gamma, beta, eps=1e-5):
    """Single-HBM-pass LayerNorm over the last axis. Pallas on TPU when
    the feature dim tiles (multiple of 128 lanes) and no mesh context is
    active; XLA elsewhere — numerics identical (fp32 statistics)."""
    with jax.named_scope(SCOPE):
        return _fused_norm(x, gamma, beta, float(eps), False,
                           _use_pallas(x.shape[-1]))


def fused_rms_norm(x, gamma, eps=1e-6):
    """Single-pass RMSNorm (Llama-family); same dispatch rule."""
    with jax.named_scope(SCOPE):
        return _fused_norm(x, gamma, None, float(eps), True,
                           _use_pallas(x.shape[-1]))
