"""Int8 matmul with a fused dequantize epilogue — Pallas TPU kernel.

Why int8 inference read 0.63x bf16 on an earlier development device
(95 TFLOP/s, 62.5 GB/s): the int32 accumulator left the matmul,
round-tripped HBM as f32 for the scale multiply and bias add, then
round-tripped again for the downcast. This kernel keeps the epilogue
where the accumulator already lives — VMEM: int8 x int8 -> int32 on the MXU (the int8 path the MXU natively runs at
2x bf16 throughput), then per-output-channel scale, bias, and the bf16
downcast applied to the register-resident accumulator before the single
HBM write. One read of x, one read of w, one write of out — the
epilogue is free.

Layout follows the quantized Dense weight: x (M, K) int8, w (N, K) int8
(Dense stores (out, in)), scale (N,) f32 per-channel, optional bias (N,)
f32. Grid (M/bm, N/bn, K/bk) with K innermost; a (bm, bn) int32 VMEM
scratch carries the partial accumulator across K blocks.

Off-TPU the registered op (ops/quantization_ops.py: ``quantized_dense``)
runs the same math as one XLA region inside the op body — same
attribution, same fused-epilogue shape, allclose numerics.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _choose_block, _on_tpu, _under_mesh

# MXU-native int8 tile is (32, 128); fp32 epilogue tiles are (8, 128)
_SUBLANE, _LANES = 32, 128


def _kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * s_ref[...]
        if b_ref is not None:
            out = out + b_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


def int8_matmul(x, w, scale, bias, out_dtype, interpret=False,
                block_m=256, block_n=256, block_k=512):
    """x: (..., K) int8; w: (N, K) int8; scale: (N,) f32; bias: (N,) f32
    or None. Returns (..., N) in ``out_dtype`` with the dequant epilogue
    fused into the matmul."""
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    n = w.shape[0]
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    bm = _choose_block(m, block_m)
    bn = _choose_block(n, block_n)
    bk = _choose_block(kdim, block_k)
    n_k = kdim // bk

    kernel = functools.partial(_kernel, n_k=n_k)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
                pl.BlockSpec((bn,), lambda i, j, k: (j,))]
    args = [x2, w, scale.astype(jnp.float32)]
    if bias is not None:
        in_specs.append(pl.BlockSpec((bn,), lambda i, j, k: (j,)))
        args.append(bias.astype(jnp.float32))
    else:
        kernel = functools.partial(
            lambda x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k:
            _kernel(x_ref, w_ref, s_ref, None, o_ref, acc_ref, n_k=n_k),
            n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(*args)
    return out.reshape(lead + (n,))


def use_pallas(x, w):
    """TPU, no mesh context, and MXU-tileable int8 operands; anything
    else takes the XLA fallback region in ops/quantization_ops.py."""
    kdim = x.shape[-1]
    m = 1
    for d in x.shape[:-1]:
        m *= d
    return (_on_tpu() and not _under_mesh()
            and x.dtype == jnp.int8 and w.dtype == jnp.int8
            and m % _SUBLANE == 0 and w.shape[0] % _LANES == 0
            and kdim % _LANES == 0)
