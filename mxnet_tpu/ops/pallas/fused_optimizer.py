"""Fused optimizer-update Pallas TPU kernels: Adam and SGD-momentum.

The optimizer step is the textbook bandwidth-bound chain: ~15 elementwise
equations over (param, grad, slot...) that XLA *does* fuse, but whose
roofline the auditor still flags (``bandwidth-bound-chain``) because the
chain reads and writes every operand through HBM once per fusion boundary
the surrounding program imposes (donation copies, sharding constraints,
multi-output fusions split by the scheduler). One pallas_call pins the
whole update — read param/grad/slots once, write param'/slots' once — and
aliases param and slot buffers in place (``input_output_aliases``), which
is the kernel-level form of the donation ``Trainer._fused_program`` makes
of the same operands: the compiled update then writes each new weight and
slot over the buffer it replaces (``Trainer.audit_donation()``).

Step-varying hyperparameters (lr, wd, the bias-correction denominators
that depend on ``t``) arrive as a tiny fp32 vector operand rather than
compile-time constants, so LR schedules never recompile the kernel —
the same trick as the reference's ``preloaded_multi_sgd`` family
(src/operator/contrib/preloaded_multi_sgd-inl.h: rates live in device
memory, not kernel attributes).

Math is kept operation-for-operation identical to the XLA fallbacks in
``optimizer/__init__.py`` (Adam.step / SGD.step), so interpret-mode runs
are bit-exact against the eager path — the parity contract tier-1 tests
pin (tests/test_pallas_kernels.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _on_tpu, _under_mesh

_VMEM_BUDGET = 2 * 1024 * 1024   # fp32 workspace bytes per block
_LANES = 128


def _block_rows(n, arrays):
    """Row block for an (n, 128) fp32 view with `arrays` operands live.

    Mosaic takes a block whose row count is a multiple of 8 or the whole
    array. So: the whole array when it fits the VMEM budget (any n, also
    6 or 12 rows), else the largest power of two inside the budget, with
    a ragged last block (the grid is ``cdiv(n, bn)``; out-of-range rows
    of the last block are not written back)."""
    cap = max(8, _VMEM_BUDGET // (4 * _LANES * arrays))
    cap = 1 << (cap.bit_length() - 1)
    return n if n <= cap else cap


def _tileable(*arrs):
    """Shape-decided branch: fp32 operands whose size fills whole 128-lane
    rows take the kernel; everything else takes the XLA update."""
    size = arrs[0].size
    return (size > 0 and size % _LANES == 0
            and all(a.dtype == jnp.float32 for a in arrs))


def use_pallas(*arrs):
    """The Trainer traces its fused update inside the mesh context, so a
    sharded update (ZeRO-1 owned tiles, FSDP shards) takes the XLA path,
    still one fused HLO region, which GSPMD can partition."""
    return _on_tpu() and not _under_mesh() and _tileable(*arrs)


def _prep_grad(g, w, wd, rescale_grad, clip_gradient):
    # mirrors Optimizer._prep + `+ wd * w` (optimizer/__init__.py)
    g = g * rescale_grad
    if clip_gradient is not None:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return g + wd * w


# ------------------------------------------------------------------- adam

def _adam_kernel(h_ref, w_ref, g_ref, m_ref, v_ref,
                 ow_ref, om_ref, ov_ref, *,
                 beta1, beta2, epsilon, rescale_grad, clip_gradient,
                 correct_bias):
    lr, wd, bc1, bc2 = h_ref[0], h_ref[1], h_ref[2], h_ref[3]
    w = w_ref[...]
    g = _prep_grad(g_ref[...], w, wd, rescale_grad, clip_gradient)
    m = beta1 * m_ref[...] + (1 - beta1) * g
    v = beta2 * v_ref[...] + (1 - beta2) * g * g
    if correct_bias:
        mhat = m / bc1
        vhat = v / bc2
    else:
        mhat, vhat = m, v
    ow_ref[...] = w - lr * mhat / (jnp.sqrt(vhat) + epsilon)
    om_ref[...] = m
    ov_ref[...] = v


def adam_step(w, g, m, v, lr, wd, t, *, beta1, beta2, epsilon,
              rescale_grad=1.0, clip_gradient=None, correct_bias=True,
              interpret=False):
    """One fused Adam update: (w, g, m, v) -> (w', m', v').

    ``lr``/``wd``/``t`` may be traced (the Trainer's fused closure passes
    them as device scalars); everything else is compile-time.
    """
    shape = w.shape
    if correct_bias:
        bc1 = 1.0 - beta1 ** t
        bc2 = 1.0 - beta2 ** t
    else:
        bc1 = bc2 = 1.0
    hyper = jnp.stack([jnp.asarray(x, jnp.float32)
                       for x in (lr, wd, bc1, bc2)])

    r = w.size // _LANES
    w2, g2, m2, v2 = (a.reshape(r, _LANES) for a in (w, g, m, v))
    bn = _block_rows(r, arrays=7)
    kernel = functools.partial(
        _adam_kernel, beta1=beta1, beta2=beta2, epsilon=epsilon,
        rescale_grad=rescale_grad, clip_gradient=clip_gradient,
        correct_bias=correct_bias)
    tile = pl.BlockSpec((bn, _LANES), lambda i: (i, 0))
    ow, om, ov = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, bn),),
        in_specs=[pl.BlockSpec((4,), lambda i: (0,)), tile, tile, tile,
                  tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((r, _LANES), jnp.float32)] * 3,
        # in-place update: param/slot HBM buffers are reused for the
        # outputs (operand indices count the hyper vector)
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name='mx_adam_step',
    )(hyper, w2, g2, m2, v2)
    return ow.reshape(shape), om.reshape(shape), ov.reshape(shape)


# ----------------------------------------------------------- sgd momentum

def _sgd_mom_kernel(h_ref, w_ref, g_ref, mom_ref, ow_ref, omom_ref, *,
                    momentum, rescale_grad, clip_gradient):
    lr, wd = h_ref[0], h_ref[1]
    w = w_ref[...]
    g = _prep_grad(g_ref[...], w, wd, rescale_grad, clip_gradient)
    new_mom = momentum * mom_ref[...] - lr * g
    ow_ref[...] = w + new_mom
    omom_ref[...] = new_mom


def sgd_mom_step(w, g, mom, lr, wd, *, momentum, rescale_grad=1.0,
                 clip_gradient=None, interpret=False):
    """One fused SGD-with-momentum update: (w, g, mom) -> (w', mom')."""
    shape = w.shape
    hyper = jnp.stack([jnp.asarray(x, jnp.float32) for x in (lr, wd)])
    r = w.size // _LANES
    w2, g2, m2 = (a.reshape(r, _LANES) for a in (w, g, mom))
    bn = _block_rows(r, arrays=5)
    kernel = functools.partial(
        _sgd_mom_kernel, momentum=momentum, rescale_grad=rescale_grad,
        clip_gradient=clip_gradient)
    tile = pl.BlockSpec((bn, _LANES), lambda i: (i, 0))
    ow, omom = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, bn),),
        in_specs=[pl.BlockSpec((2,), lambda i: (0,)), tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((r, _LANES), jnp.float32)] * 2,
        input_output_aliases={1: 0, 3: 1},
        interpret=interpret,
        name='mx_sgd_mom_step',
    )(hyper, w2, g2, m2)
    return ow.reshape(shape), omom.reshape(shape)
