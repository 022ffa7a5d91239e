"""Fused optimizer-update Pallas TPU kernels: Adam and SGD-momentum.

The optimizer step is the textbook bandwidth-bound chain: ~15 elementwise
equations over (param, grad, slot...). One pallas_call pins the whole
update: read param/grad/slots once, write param'/slots' once, 28 bytes a
float32 parameter under Adam, with the param and slot buffers aliased in
place (``input_output_aliases``), the kernel-level form of the donation
``Trainer._fused_program`` makes of the same operands.

What is measured (TPU v5e; PERF.md §6, PR 35). Until PR 35 the kernels
took every leaf as ``(size // 128, 128)`` rows. Under the TPU's (8, 128)
tiling that view of a leaf of two or more axes is no bitcast: the
compiled update held four ``reshape``s in and three out round every
kernel, each a read and a write of the whole leaf, so it moved 84 bytes a
parameter where the kernel moves 28 and ran at 31-38 % of the HBM
roofline (the whole state of the sparse decoder's cell, 576 M
parameters: 63.1 ms; BERT-base: 9.9 ms). The kernels now take each leaf
as it lies (``_rows_view``): the compiled update is the kernels and
nothing of a leaf's size beside them, 24.15 ms and 4.75 ms, 81.5 and
79.3 % of 819 GB/s, the same to 0.1 % at blocks of 256 KiB, 512 KiB and
1 MiB an operand. XLA's own fusion of the same equations, in the leaf's
own layout, reads 24.09 and 4.69 ms beside them, and in the cells'
traced runs 23.18 against the kernels' 23.31 ms (sparse decoder) and
4.76 against 4.89 ms (BERT-base pre-training). It needs no Mosaic
compile, declares no VMEM and partitions under a mesh, so the
like-for-like reading chose it: ``use_pallas`` is closed and every leaf
takes XLA's branch of ``ops/optimizer_ops.py``. The kernels stay, with
their parity tests and their compiles for the described chip, until a
``simplicity`` issue takes them out (ROADMAP C4).

Step-varying hyperparameters (lr, wd, the bias-correction denominators
that depend on ``t``) arrive as a tiny fp32 vector operand rather than
compile-time constants, so LR schedules never recompile the kernel, the
same trick as the reference's ``preloaded_multi_sgd`` family
(src/operator/contrib/preloaded_multi_sgd-inl.h: rates live in device
memory, not kernel attributes).

Math is kept operation-for-operation identical to the XLA branch in
``ops/optimizer_ops.py``, so interpret-mode runs are bit-exact against it
in the slots, the parity contract tier-1 tests pin
(tests/test_pallas_kernels.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# What a kernel declares, and no more than it needs (docs/kernels.md; a
# Mosaic call that declared 96 MiB spoiled the step round it, PERF.md §6
# PR 34). A block is at most _BLOCK_BYTES an operand: on the chip 256 KiB,
# 512 KiB and 1 MiB read the same to 0.1 %, and Mosaic's compile grows
# with the block (PERF.md §6 PR 35).
_VMEM_LIMIT = 32 * 2 ** 20
_BLOCK_BYTES = 2 ** 19


def _rows_view(shape):
    """The (rows, cols) the kernels update a leaf of this shape as, or
    None where they do not take it. The view has to be a bitcast under the
    TPU's (8, 128) tiling of the two minor axes: a leaf of two or more
    axes keeps its last axis, a multiple of 128 lanes, and its leading
    axes collapse into rows, which moves nothing when the second-minor
    axis fills whole sublane tiles (or nothing lies above it). A 1-D leaf
    (a bias, a norm's gain) of whole 128-lane rows is viewed as those
    rows; the compiler makes that a bitcast too."""
    if not shape or 0 in shape:
        return None
    if len(shape) == 1:
        return (shape[0] // _LANES, _LANES) if shape[0] % _LANES == 0 \
            else None
    rows, cols = math.prod(shape[:-1]), shape[-1]
    if cols % _LANES or (rows != shape[-2] and shape[-2] % _SUBLANES):
        return None
    return rows, cols


def _block_rows(rows, cols, arrays):
    """(block_rows, lanes) for a (rows, cols) fp32 view with `arrays`
    operands live (7 for Adam, 5 for SGD-momentum), double-buffered in
    at most half of _VMEM_LIMIT; the body's temporaries have the rest.

    ``lanes`` is the whole last axis where eight rows of it fit a block,
    else its largest divisor of whole 128-lane tiles that does. Mosaic
    takes a block whose row count is a multiple of 8 or the whole array.
    So: all rows when they fit (any count, also 2 or 6), else the largest
    multiple of 8 inside the budget, with a ragged last block (the grid
    is ``cdiv(rows, block_rows)``; out-of-range rows of the last block
    are not written back)."""
    budget = min(_BLOCK_BYTES, _VMEM_LIMIT // (4 * arrays)) // 4
    tiles = cols // _LANES
    fit = max(1, budget // (_SUBLANES * _LANES))
    lanes = _LANES * max(d for d in range(1, min(tiles, fit) + 1)
                         if tiles % d == 0)
    cap = budget // lanes // _SUBLANES * _SUBLANES
    return (rows if rows <= cap else cap), lanes


def _tileable(*arrs):
    """Which leaves the kernels take, by shape alone: fp32 operands of
    one shape that `_rows_view` takes as they lie."""
    shape = arrs[0].shape
    return (_rows_view(shape) is not None
            and all(a.shape == shape and a.dtype == jnp.float32
                    for a in arrs))


def use_pallas(*arrs):
    """Which leaves take the kernel on the registered ops' path: none.
    Measured like for like on the v5e (the module's docstring), XLA's
    fusion of the update is as fast as the kernel or faster over the
    leaves of both families' one-chip cells; under a mesh GSPMD
    partitions it, and could not partition a ``pallas_call``."""
    return False


def _update_call(kernel, name, hyper, operands, aliases, interpret):
    """One pallas_call over the operands' common (rows, cols) view, a
    grid of row blocks by lane blocks; ``aliases`` maps an operand's
    index to the output written over it."""
    shape = operands[0].shape
    rows, cols = _rows_view(shape)
    bn, lanes = _block_rows(rows, cols, len(operands) + len(aliases))
    tile = pl.BlockSpec((bn, lanes), lambda i, j: (i, j))
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, bn), cols // lanes),
        in_specs=[pl.BlockSpec(hyper.shape, lambda i, j: (0,))]
        + [tile] * len(operands),
        out_specs=[tile] * len(aliases),
        out_shape=[jax.ShapeDtypeStruct((rows, cols), jnp.float32)]
        * len(aliases),
        # in-place update: param/slot HBM buffers are reused for the
        # outputs (operand indices count the hyper vector)
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel'),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(hyper, *(a.reshape(rows, cols) for a in operands))
    return tuple(o.reshape(shape) for o in outs)


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
    if correct_bias:
        bc1 = 1.0 - beta1 ** t
        bc2 = 1.0 - beta2 ** t
    else:
        bc1 = bc2 = 1.0
    hyper = jnp.stack([jnp.asarray(x, jnp.float32)
                       for x in (lr, wd, bc1, bc2)])

    kernel = functools.partial(
        _adam_kernel, beta1=beta1, beta2=beta2, epsilon=epsilon,
        rescale_grad=rescale_grad, clip_gradient=clip_gradient,
        correct_bias=correct_bias)
    return _update_call(kernel, 'mx_adam_step', hyper, (w, g, m, v),
                        {1: 0, 3: 1, 4: 2}, interpret)


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
    hyper = jnp.stack([jnp.asarray(x, jnp.float32) for x in (lr, wd)])
    kernel = functools.partial(
        _sgd_mom_kernel, momentum=momentum, rescale_grad=rescale_grad,
        clip_gradient=clip_gradient)
    return _update_call(kernel, 'mx_sgd_mom_step', hyper, (w, g, mom),
                        {1: 0, 3: 1}, interpret)
