"""Optimizer update kernels.

Reference: ``src/operator/optimizer_op.cc`` / ``optimizer_op-inl.h`` (SGD,
momentum, NAG, Adam, RMSProp, FTRL, SignSGD/Signum, LAMB phases, the fused
multi-tensor ``multi_*``/``preloaded_multi_*`` variants, ``multi_sum_sq``,
``reset_arrays``) and ``src/operator/contrib/adamw.cc``.

TPU design notes: the reference fuses multi-tensor updates into one CUDA
kernel launch to amortize launch overhead; under XLA a Python loop over the
tensor list inside one jitted update produces a single fused HLO module, so
the ``multi_*`` ops here are loops — same wire format, same fusion effect.
Mixed-precision (``mp_*``) variants keep an fp32 master copy alongside
bf16/fp16 weights, exactly like the reference's ``MultiPrecision`` path.

All kernels are pure: they *return* the updated tensors (weight, state...)
instead of mutating in place; the NDArray frontend rebinds. Gate order and
semantics (rescale_grad, clip_gradient, wd applied to raw weight) follow the
reference's optimizer_op-inl.h structs.
"""

import jax
import jax.numpy as jnp

from .registry import register


def _rescale_clip(grad, rescale_grad, clip_gradient):
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return g


def _prep(grad, weight, rescale_grad, clip_gradient, wd):
    return _rescale_clip(grad, rescale_grad, clip_gradient) + wd * weight


# ------------------------------------------------------------ fused updates
# (docs/kernels.md) The ops the in-repo Optimizer.step actually calls:
# one Adam or SGD-momentum step of one leaf, in the leaf's own layout,
# which XLA fuses into one pass over the operands (28 bytes a float32
# parameter under Adam). Behind ``use_pallas`` each has a Pallas kernel
# of the same equations (ops/pallas/fused_optimizer.py); since PR 35 the
# gate is closed, because XLA's fusion measured as fast on the chip
# (PERF.md §6), and every platform runs the math below. Registered
# ``fused_kernel=True`` so the bandwidth-bound-chain lint treats the
# update as already fused, and with a closed-form ``cost=`` so the
# roofline model can price the opaque pallas_call where a caller takes
# the kernel.

def _elementwise_pallas_cost(flops_per_elem):
    def cost(eqn):
        if eqn.primitive.name != 'pallas_call':
            return None
        return flops_per_elem * eqn.outvars[0].aval.size
    return cost


# flops/element: prep(3: rescale+clip+wd) + moments(7) + bias(2) +
# denom/update(6) — the closed form BENCH rows divide achieved time by
_ADAM_FLOPS_PER_ELEM = 18
_SGD_MOM_FLOPS_PER_ELEM = 7
# round both sides of the gate: a profile's device operations are put
# down to the optimizer step whichever side ran it
_STEP_SCOPE = 'mx.optimizer_step'


@register('fused_adam_step', n_out=3, fused_kernel=True,
          cost=_elementwise_pallas_cost(_ADAM_FLOPS_PER_ELEM))
def fused_adam_step(weight, grad, mean, var, lr=0.001, wd=0.0, t=1,
                    beta1=0.9, beta2=0.999, epsilon=1e-8,
                    rescale_grad=1.0, clip_gradient=None,
                    correct_bias=True):
    """One Adam step, (w, g, m, v) -> (w', m', v'). ``lr``/``wd``/``t``
    may be traced scalars (LR schedules never recompile)."""
    from .pallas import fused_optimizer as _fo
    with jax.named_scope(_STEP_SCOPE):
        if _fo.use_pallas(weight, grad, mean, var):
            return _fo.adam_step(
                weight, grad, mean, var, lr, wd, t, beta1=beta1,
                beta2=beta2, epsilon=epsilon, rescale_grad=rescale_grad,
                clip_gradient=clip_gradient, correct_bias=correct_bias)
        g = grad * rescale_grad
        if clip_gradient is not None:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        g = g + wd * weight
        m = beta1 * mean + (1 - beta1) * g
        v = beta2 * var + (1 - beta2) * g * g
        if correct_bias:
            mhat = m / (1 - beta1 ** t)
            vhat = v / (1 - beta2 ** t)
        else:
            mhat, vhat = m, v
        return weight - lr * mhat / (jnp.sqrt(vhat) + epsilon), m, v


@register('fused_sgd_mom_step', n_out=2, fused_kernel=True,
          cost=_elementwise_pallas_cost(_SGD_MOM_FLOPS_PER_ELEM))
def fused_sgd_mom_step(weight, grad, mom, lr=0.01, wd=0.0, momentum=0.0,
                       rescale_grad=1.0, clip_gradient=None):
    """One SGD-momentum step, (w, g, mom) -> (w', mom')."""
    from .pallas import fused_optimizer as _fo
    with jax.named_scope(_STEP_SCOPE):
        if _fo.use_pallas(weight, grad, mom):
            return _fo.sgd_mom_step(
                weight, grad, mom, lr, wd, momentum=momentum,
                rescale_grad=rescale_grad, clip_gradient=clip_gradient)
        g = grad * rescale_grad
        if clip_gradient is not None:
            g = jnp.clip(g, -clip_gradient, clip_gradient)
        g = g + wd * weight
        new_mom = momentum * mom - lr * g
        return weight + new_mom, new_mom


# ------------------------------------------------------------------ sgd family

@register('sgd_update')
def sgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
               clip_gradient=-1.0, lazy_update=True):
    g = _prep(grad, weight, rescale_grad, clip_gradient, wd)
    return weight - lr * g


@register('sgd_mom_update', n_out=2)
def sgd_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True):
    g = _prep(grad, weight, rescale_grad, clip_gradient, wd)
    mom = momentum * mom - lr * g
    return weight + mom, mom


@register('mp_sgd_update', n_out=2)
def mp_sgd_update(weight, grad, weight32, lr=0.01, wd=0.0, rescale_grad=1.0,
                  clip_gradient=-1.0):
    g = _prep(grad.astype(jnp.float32), weight32, rescale_grad,
              clip_gradient, wd)
    w32 = weight32 - lr * g
    return w32.astype(weight.dtype), w32


@register('mp_sgd_mom_update', n_out=3)
def mp_sgd_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                      wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    g = _prep(grad.astype(jnp.float32), weight32, rescale_grad,
              clip_gradient, wd)
    mom = momentum * mom - lr * g
    w32 = weight32 + mom
    return w32.astype(weight.dtype), mom, w32


@register('nag_mom_update', n_out=2)
def nag_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0):
    g = _prep(grad, weight, rescale_grad, clip_gradient, wd)
    mom = momentum * mom + g
    return weight - lr * (g + momentum * mom), mom


@register('signsgd_update')
def signsgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0):
    g = _prep(grad, weight, rescale_grad, clip_gradient, wd)
    return weight - lr * jnp.sign(g)


@register('signum_update', n_out=2)
def signum_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                  rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    mom = momentum * mom - (1 - momentum) * (g + wd * weight)
    w = (1 - lr * wd_lh) * weight + lr * jnp.sign(mom)
    return w, mom


# ----------------------------------------------------------------- adam family

@register('adam_update', n_out=3)
def adam_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                lazy_update=True):
    g = _prep(grad, weight, rescale_grad, clip_gradient, wd)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * g * g
    w = weight - lr * mean / (jnp.sqrt(var) + epsilon)
    return w, mean, var


@register('adamw_update', n_out=3)
def adamw_update(weight, grad, mean, var, rescale_grad=1.0, lr=0.001,
                 beta1=0.9, beta2=0.999, epsilon=1e-8, wd=0.0, eta=1.0,
                 clip_gradient=-1.0):
    """Decoupled weight decay (reference src/operator/contrib/adamw.cc:
    wd multiplies the weight directly, not the gradient)."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * g * g
    w = weight - eta * (lr * mean / (jnp.sqrt(var) + epsilon) + wd * weight)
    return w, mean, var


@register('ftrl_update', n_out=3)
def ftrl_update(weight, grad, z, n, lr=0.1, lamda1=0.01, beta=1.0, wd=0.0,
                rescale_grad=1.0, clip_gradient=-1.0):
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    new_n = n + g * g
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    z = z + g - sigma * weight
    w = jnp.where(
        jnp.abs(z) > lamda1,
        -(z - jnp.sign(z) * lamda1) /
        ((beta + jnp.sqrt(new_n)) / lr + wd),
        0.0)
    return w, z, new_n


@register('rmsprop_update', n_out=2)
def rmsprop_update(weight, grad, n, lr=0.001, gamma1=0.9, epsilon=1e-8,
                   wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                   clip_weights=-1.0):
    g = _prep(grad, weight, rescale_grad, clip_gradient, wd)
    n = gamma1 * n + (1 - gamma1) * g * g
    w = weight - lr * g / jnp.sqrt(n + epsilon)
    if clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, n


@register('rmspropalex_update', n_out=4)
def rmspropalex_update(weight, grad, n, g_acc, delta, lr=0.001, gamma1=0.95,
                       gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                       clip_gradient=-1.0, clip_weights=-1.0):
    g = _prep(grad, weight, rescale_grad, clip_gradient, wd)
    n = gamma1 * n + (1 - gamma1) * g * g
    g_acc = gamma1 * g_acc + (1 - gamma1) * g
    delta = gamma2 * delta - lr * g / jnp.sqrt(n - g_acc * g_acc + epsilon)
    w = weight + delta
    if clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, n, g_acc, delta


@register('lamb_update_phase1', n_out=3)
def lamb_update_phase1(weight, grad, mean, var, beta1=0.9, beta2=0.999,
                       epsilon=1e-6, t=1, bias_correction=True, wd=0.0,
                       rescale_grad=1.0, clip_gradient=-1.0):
    """Reference optimizer_op.cc lamb_update_phase1 — returns the raw
    update direction plus the advanced (mean, var) moments; phase2 applies
    the trust ratio."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * g * g
    if bias_correction:
        mhat = mean / (1 - beta1 ** t)
        vhat = var / (1 - beta2 ** t)
    else:
        mhat, vhat = mean, var
    return mhat / (jnp.sqrt(vhat) + epsilon) + wd * weight, mean, var


@register('lamb_update_phase2')
def lamb_update_phase2(weight, g, r1, r2, lr=0.001, lower_bound=-1.0,
                       upper_bound=-1.0):
    if lower_bound > 0:
        r1 = jnp.maximum(r1, lower_bound)
    if upper_bound > 0:
        r1 = jnp.minimum(r1, upper_bound)
    ratio = jnp.where(jnp.logical_and(r1 > 0, r2 > 0), r1 / r2, 1.0)
    return weight - lr * ratio * g


# ------------------------------------------------------------ multi-tensor ops

def _as_triples(arrays, n):
    """Split the flat variadic array list into n per-weight groups."""
    k = len(arrays) // n
    return [arrays[i * k:(i + 1) * k] for i in range(n)]


@register('multi_sgd_update', n_out=lambda a, kw: kw.get(
    'num_weights') or (len(a[0]) if a and isinstance(a[0], (list, tuple))
                       else len(a)) // 2)
def multi_sgd_update(*arrays, lrs=None, wds=None, rescale_grad=1.0,
                     clip_gradient=-1.0, num_weights=None):
    """Fused multi-tensor SGD (reference optimizer_op.cc multi_sgd_update:
    arrays = [w0, g0, w1, g1, ...]). One jit → one fused HLO module."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    n = num_weights if num_weights is not None else len(arrays) // 2
    outs = []
    for i in range(n):
        w, g = arrays[2 * i], arrays[2 * i + 1]
        outs.append(sgd_update(w, g, lr=lrs[i], wd=wds[i],
                               rescale_grad=rescale_grad,
                               clip_gradient=clip_gradient))
    return tuple(outs)


@register('multi_sgd_mom_update', n_out=lambda a, kw: 2 * (
    kw.get('num_weights') or len(a) // 3))
def multi_sgd_mom_update(*arrays, lrs=None, wds=None, momentum=0.0,
                         rescale_grad=1.0, clip_gradient=-1.0,
                         num_weights=None):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    n = num_weights if num_weights is not None else len(arrays) // 3
    outs = []
    for i in range(n):
        w, g, m = arrays[3 * i], arrays[3 * i + 1], arrays[3 * i + 2]
        w2, m2 = sgd_mom_update(w, g, m, lr=lrs[i], momentum=momentum,
                                wd=wds[i], rescale_grad=rescale_grad,
                                clip_gradient=clip_gradient)
        outs.extend([w2, m2])
    return tuple(outs)


@register('multi_sum_sq', differentiable=False)
def multi_sum_sq(*arrays, num_arrays=None):
    """Reference: src/operator/contrib/multi_sum_sq.cc — per-tensor sum of
    squares in one fused pass (used by LAMB/LARS trust-ratio)."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return jnp.stack([jnp.sum((a.astype(jnp.float32)) ** 2)
                      for a in arrays])


@register('reset_arrays', differentiable=False,
          n_out=lambda a, kw: kw.get('num_arrays') or len(a))
def reset_arrays(*arrays, num_arrays=None):
    """Reference: src/operator/contrib/reset_arrays.cc — zero a list of
    tensors in one engine op (grad clearing)."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return tuple(jnp.zeros_like(a) for a in arrays)


# ------------------------------------------ round-2 op-ledger additions
# (VERDICT r1 item 5: the fused multi-tensor family + mp/master-weight
# variants the reference registers in optimizer_op.cc and
# src/operator/contrib/{preloaded_multi_sgd,multi_lamb,multi_lans,
# adamw,multi_lars}-inl.h. One XLA program per call — the reason these
# exist in the reference (one engine op for N tensors) is the reason
# they are single jit dispatches here.)

@register('ftml_update', n_out=4)
def ftml_update(weight, grad, d, v, z, lr=0.0025, beta1=0.6, beta2=0.999,
                epsilon=1e-8, t=1, wd=0.0, rescale_grad=1.0,
                clip_grad=-1.0):
    """Reference optimizer_op.cc FTMLUpdate (Follow The Moving Leader)."""
    g = _rescale_clip(grad, rescale_grad, clip_grad) + wd * weight
    v_new = beta2 * v + (1 - beta2) * g * g
    d_new = (1 - beta1 ** t) / lr * (
        jnp.sqrt(v_new / (1 - beta2 ** t)) + epsilon)
    sigma = d_new - beta1 * d
    z_new = beta1 * z + (1 - beta1) * g - sigma * weight
    w = -z_new / d_new
    return w, d_new, v_new, z_new


@register('mp_nag_mom_update', n_out=3)
def mp_nag_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                      wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """Master-weight NAG (reference optimizer_op.cc MPNAGMomUpdate)."""
    g = _prep(grad.astype(jnp.float32), weight32, rescale_grad,
              clip_gradient, wd)
    mom_new = momentum * mom + g
    w32 = weight32 - lr * (g + momentum * mom_new)
    return w32.astype(weight.dtype), mom_new, w32


@register('mp_adamw_update', n_out=4)
def mp_adamw_update(weight, grad, mean, var, weight32, rescale_grad=1.0,
                    lr=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                    wd=0.0, eta=1.0, clip_gradient=-1.0):
    """Master-weight AdamW (reference contrib/adamw.cc mp path)."""
    g = _rescale_clip(grad.astype(jnp.float32), rescale_grad,
                      clip_gradient)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * g * g
    w32 = weight32 - eta * (lr * mean / (jnp.sqrt(var) + epsilon)
                            + wd * weight32)
    return w32.astype(weight.dtype), mean, var, w32


@register('mp_lamb_update_phase1', n_out=3)
def mp_lamb_update_phase1(weight, grad, mean, var, weight32, beta1=0.9,
                          beta2=0.999, epsilon=1e-6, t=1,
                          bias_correction=True, wd=0.0, rescale_grad=1.0,
                          clip_gradient=-1.0):
    g = _rescale_clip(grad.astype(jnp.float32), rescale_grad,
                      clip_gradient)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * g * g
    if bias_correction:
        mhat = mean / (1 - beta1 ** t)
        vhat = var / (1 - beta2 ** t)
    else:
        mhat, vhat = mean, var
    return mhat / (jnp.sqrt(vhat) + epsilon) + wd * weight32, mean, var


@register('mp_lamb_update_phase2', n_out=2)
def mp_lamb_update_phase2(weight, g, r1, r2, weight32, lr=0.001,
                          lower_bound=-1.0, upper_bound=-1.0):
    if lower_bound > 0:
        r1 = jnp.maximum(r1, lower_bound)
    if upper_bound > 0:
        r1 = jnp.minimum(r1, upper_bound)
    ratio = jnp.where(jnp.logical_and(r1 > 0, r2 > 0), r1 / r2, 1.0)
    w32 = weight32 - lr * ratio * g
    return w32.astype(weight.dtype), w32


def _interleaved(arrays, stride):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    n = len(arrays) // stride
    return arrays, n


@register('multi_mp_sgd_update', n_out=lambda a, kw: 2 * (
    kw.get('num_weights') or len(a) // 3))
def multi_mp_sgd_update(*arrays, lrs=None, wds=None, rescale_grad=1.0,
                        clip_gradient=-1.0, num_weights=None):
    """(w, g, w32) triples (reference optimizer_op.cc MultiMPSGDUpdate)."""
    arrays, n = _interleaved(arrays, 3)
    outs = []
    for i in range(n):
        w, g, w32 = arrays[3 * i], arrays[3 * i + 1], arrays[3 * i + 2]
        gp = _prep(g.astype(jnp.float32), w32, rescale_grad,
                   clip_gradient, wds[i])
        nw32 = w32 - lrs[i] * gp
        outs.extend([nw32.astype(w.dtype), nw32])
    return tuple(outs)


@register('multi_mp_sgd_mom_update', n_out=lambda a, kw: 3 * (
    kw.get('num_weights') or len(a) // 4))
def multi_mp_sgd_mom_update(*arrays, lrs=None, wds=None, momentum=0.0,
                            rescale_grad=1.0, clip_gradient=-1.0,
                            num_weights=None):
    """(w, g, mom, w32) quadruples (reference MultiMPSGDMomUpdate)."""
    arrays, n = _interleaved(arrays, 4)
    outs = []
    for i in range(n):
        w, g, m, w32 = arrays[4 * i:4 * i + 4]
        gp = _prep(g.astype(jnp.float32), w32, rescale_grad,
                   clip_gradient, wds[i])
        nm = momentum * m - lrs[i] * gp
        nw32 = w32 + nm
        outs.extend([nw32.astype(w.dtype), nm, nw32])
    return tuple(outs)


# preloaded_* variants: lrs/wds arrive as DEVICE TENSORS appended to the
# array list instead of host attrs (reference
# contrib/preloaded_multi_sgd-inl.h — saves the host->device scalar
# copies per step; here it additionally keeps the jit signature static
# when schedules change lr every step)
@register('preloaded_multi_sgd_update', n_out=lambda a, kw: (
    kw.get('num_weights') or (len(a) - 2) // 2))
def preloaded_multi_sgd_update(*arrays, rescale_grad=1.0,
                               clip_gradient=-1.0, num_weights=None):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    n = num_weights if num_weights is not None else (len(arrays) - 2) // 2
    lrs, wds = arrays[-2], arrays[-1]
    outs = []
    for i in range(n):
        w, g = arrays[2 * i], arrays[2 * i + 1]
        gp = _prep(g, w, rescale_grad, clip_gradient, wds[i])
        outs.append(w - lrs[i] * gp)
    return tuple(outs)


@register('preloaded_multi_sgd_mom_update', n_out=lambda a, kw: 2 * (
    kw.get('num_weights') or (len(a) - 2) // 3))
def preloaded_multi_sgd_mom_update(*arrays, momentum=0.0, rescale_grad=1.0,
                                   clip_gradient=-1.0, num_weights=None):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    n = num_weights if num_weights is not None else (len(arrays) - 2) // 3
    lrs, wds = arrays[-2], arrays[-1]
    outs = []
    for i in range(n):
        w, g, m = arrays[3 * i:3 * i + 3]
        gp = _prep(g, w, rescale_grad, clip_gradient, wds[i])
        nm = momentum * m - lrs[i] * gp
        outs.extend([w + nm, nm])
    return tuple(outs)


@register('preloaded_multi_mp_sgd_update', n_out=lambda a, kw: 2 * (
    kw.get('num_weights') or (len(a) - 2) // 3))
def preloaded_multi_mp_sgd_update(*arrays, rescale_grad=1.0,
                                  clip_gradient=-1.0, num_weights=None):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    n = num_weights if num_weights is not None else (len(arrays) - 2) // 3
    lrs, wds = arrays[-2], arrays[-1]
    outs = []
    for i in range(n):
        w, g, w32 = arrays[3 * i:3 * i + 3]
        gp = _prep(g.astype(jnp.float32), w32, rescale_grad,
                   clip_gradient, wds[i])
        nw32 = w32 - lrs[i] * gp
        outs.extend([nw32.astype(w.dtype), nw32])
    return tuple(outs)


@register('preloaded_multi_mp_sgd_mom_update', n_out=lambda a, kw: 3 * (
    kw.get('num_weights') or (len(a) - 2) // 4))
def preloaded_multi_mp_sgd_mom_update(*arrays, momentum=0.0,
                                      rescale_grad=1.0,
                                      clip_gradient=-1.0,
                                      num_weights=None):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    n = num_weights if num_weights is not None else (len(arrays) - 2) // 4
    lrs, wds = arrays[-2], arrays[-1]
    outs = []
    for i in range(n):
        w, g, m, w32 = arrays[4 * i:4 * i + 4]
        gp = _prep(g.astype(jnp.float32), w32, rescale_grad,
                   clip_gradient, wds[i])
        nm = momentum * m - lrs[i] * gp
        nw32 = w32 + nm
        outs.extend([nw32.astype(w.dtype), nm, nw32])
    return tuple(outs)


def _lamb_full(w32, g, mean, var, beta1, beta2, epsilon, t,
               bias_correction, wd, lower_bound, upper_bound, lr):
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * g * g
    if bias_correction:
        mhat = mean / (1 - beta1 ** t)
        vhat = var / (1 - beta2 ** t)
    else:
        mhat, vhat = mean, var
    upd = mhat / (jnp.sqrt(vhat) + epsilon) + wd * w32
    r1 = jnp.sqrt(jnp.sum(w32 * w32))
    if lower_bound > 0:
        r1 = jnp.maximum(r1, lower_bound)
    if upper_bound > 0:
        r1 = jnp.minimum(r1, upper_bound)
    r2 = jnp.sqrt(jnp.sum(upd * upd))
    ratio = jnp.where(jnp.logical_and(r1 > 0, r2 > 0), r1 / r2, 1.0)
    return w32 - lr * ratio * upd, mean, var


@register('multi_lamb_update', n_out=lambda a, kw: 3 * (
    kw.get('num_tensors') or len(a) // 4))
def multi_lamb_update(*arrays, learning_rates=None, wds=None, beta1=0.9,
                      beta2=0.999, epsilon=1e-6, step_count=None,
                      bias_correction=True, rescale_grad=1.0,
                      lower_bound=-1.0, upper_bound=-1.0,
                      clip_gradient=-1.0, num_tensors=None):
    """(w, g, mean, var) quadruples (reference contrib/multi_lamb.cc)."""
    arrays, n = _interleaved(arrays, 4)
    outs = []
    for i in range(n):
        w, g, mean, var = arrays[4 * i:4 * i + 4]
        gp = _rescale_clip(g, rescale_grad, clip_gradient)
        nw, nmean, nvar = _lamb_full(
            w, gp, mean, var, beta1, beta2, epsilon, step_count[i],
            bias_correction, wds[i], lower_bound, upper_bound,
            learning_rates[i])
        # the reference mutates the moment inputs in place; functional
        # form returns them (w, mean, var) per tensor
        outs.extend([nw, nmean, nvar])
    return tuple(outs)


@register('multi_mp_lamb_update', n_out=lambda a, kw: 4 * (
    kw.get('num_tensors') or len(a) // 5))
def multi_mp_lamb_update(*arrays, learning_rates=None, wds=None,
                         beta1=0.9, beta2=0.999, epsilon=1e-6,
                         step_count=None, bias_correction=True,
                         rescale_grad=1.0, lower_bound=-1.0,
                         upper_bound=-1.0, clip_gradient=-1.0,
                         num_tensors=None):
    """(w, g, mean, var, w32) — master-weight variant."""
    arrays, n = _interleaved(arrays, 5)
    outs = []
    for i in range(n):
        w, g, mean, var, w32 = arrays[5 * i:5 * i + 5]
        gp = _rescale_clip(g.astype(jnp.float32), rescale_grad,
                           clip_gradient)
        nw32, nmean, nvar = _lamb_full(
            w32, gp, mean, var, beta1, beta2, epsilon, step_count[i],
            bias_correction, wds[i], lower_bound, upper_bound,
            learning_rates[i])
        outs.extend([nw32.astype(w.dtype), nmean, nvar, nw32])
    return tuple(outs)


def _lans_full(w32, g, mean, var, beta1, beta2, epsilon, t, wd, lr):
    # LANS (Zheng et al.): gradient pre-normalized per tensor; update is
    # the sum of an Adam-style term and a momentum-free term, each
    # trust-ratio scaled
    g = g / jnp.maximum(jnp.sqrt(jnp.sum(g * g)), 1e-12)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * g * g
    mhat = mean / (1 - beta1 ** t)
    vhat = var / (1 - beta2 ** t)
    denom = jnp.sqrt(vhat) + epsilon
    upd_m = mhat / denom + wd * w32
    upd_g = g / denom + wd * w32
    wnorm = jnp.sqrt(jnp.sum(w32 * w32))

    def ratio(upd):
        un = jnp.sqrt(jnp.sum(upd * upd))
        return jnp.where(jnp.logical_and(wnorm > 0, un > 0),
                         wnorm / un, 1.0)

    new_w = w32 - lr * (beta1 * ratio(upd_m) * upd_m
                        + (1 - beta1) * ratio(upd_g) * upd_g)
    return new_w, mean, var


@register('multi_lans_update', n_out=lambda a, kw: 3 * (
    kw.get('num_tensors') or len(a) // 4))
def multi_lans_update(*arrays, learning_rates=None, wds=None, beta1=0.9,
                      beta2=0.999, epsilon=1e-6, step_count=None,
                      rescale_grad=1.0, clip_gradient=-1.0,
                      num_tensors=None):
    """(w, g, mean, var) quadruples (reference contrib/multi_lans.cc)."""
    arrays, n = _interleaved(arrays, 4)
    outs = []
    for i in range(n):
        w, g, mean, var = arrays[4 * i:4 * i + 4]
        gp = _rescale_clip(g, rescale_grad, clip_gradient)
        nw, nmean, nvar = _lans_full(
            w, gp, mean, var, beta1, beta2, epsilon, step_count[i],
            wds[i], learning_rates[i])
        outs.extend([nw, nmean, nvar])
    return tuple(outs)


@register('multi_mp_lans_update', n_out=lambda a, kw: 4 * (
    kw.get('num_tensors') or len(a) // 5))
def multi_mp_lans_update(*arrays, learning_rates=None, wds=None,
                         beta1=0.9, beta2=0.999, epsilon=1e-6,
                         step_count=None, rescale_grad=1.0,
                         clip_gradient=-1.0, num_tensors=None):
    arrays, n = _interleaved(arrays, 5)
    outs = []
    for i in range(n):
        w, g, mean, var, w32 = arrays[5 * i:5 * i + 5]
        gp = _rescale_clip(g.astype(jnp.float32), rescale_grad,
                           clip_gradient)
        nw32, nmean, nvar = _lans_full(
            w32, gp, mean, var, beta1, beta2, epsilon, step_count[i],
            wds[i], learning_rates[i])
        outs.extend([nw32.astype(w.dtype), nmean, nvar, nw32])
    return tuple(outs)


@register('multi_adamw_update', n_out=lambda a, kw: 3 * (
    kw.get('num_tensors') or len(a) // 4))
def multi_adamw_update(*arrays, learning_rates=None, wds=None, etas=None,
                       beta1=0.9, beta2=0.999, epsilon=1e-8,
                       rescale_grad=1.0, clip_gradient=-1.0,
                       num_tensors=None):
    """(w, g, mean, var) quadruples (reference contrib/adamw.cc multi)."""
    arrays, n = _interleaved(arrays, 4)
    outs = []
    for i in range(n):
        w, g, mean, var = arrays[4 * i:4 * i + 4]
        gp = _rescale_clip(g, rescale_grad, clip_gradient)
        mean = beta1 * mean + (1 - beta1) * gp
        var = beta2 * var + (1 - beta2) * gp * gp
        eta = etas[i] if etas is not None else 1.0
        outs.extend([w - eta * (learning_rates[i] * mean
                                / (jnp.sqrt(var) + epsilon)
                                + wds[i] * w), mean, var])
    return tuple(outs)


@register('multi_mp_adamw_update', n_out=lambda a, kw: 4 * (
    kw.get('num_tensors') or len(a) // 5))
def multi_mp_adamw_update(*arrays, learning_rates=None, wds=None,
                          etas=None, beta1=0.9, beta2=0.999,
                          epsilon=1e-8, rescale_grad=1.0,
                          clip_gradient=-1.0, num_tensors=None):
    arrays, n = _interleaved(arrays, 5)
    outs = []
    for i in range(n):
        w, g, mean, var, w32 = arrays[5 * i:5 * i + 5]
        gp = _rescale_clip(g.astype(jnp.float32), rescale_grad,
                           clip_gradient)
        mean = beta1 * mean + (1 - beta1) * gp
        var = beta2 * var + (1 - beta2) * gp * gp
        eta = etas[i] if etas is not None else 1.0
        nw32 = w32 - eta * (learning_rates[i] * mean
                            / (jnp.sqrt(var) + epsilon) + wds[i] * w32)
        outs.extend([nw32.astype(w.dtype), mean, var, nw32])
    return tuple(outs)


@register('multi_all_finite', differentiable=False)
def multi_all_finite(*arrays, num_arrays=None, init_output=True):
    """1 iff every element of every tensor is finite (reference
    contrib/all_finite.cc MultiAllFinite — the AMP overflow check).
    With ``init_output=False`` the reference ANDs into the existing
    output buffer; functionally the last positional array plays that
    role here (pass the previous flag as the final argument)."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    if not init_output:
        arrays, prev = arrays[:-1], arrays[-1]
        ok = prev.reshape(()).astype(jnp.bool_)
    else:
        ok = jnp.bool_(True)
    for a in arrays:
        ok = jnp.logical_and(ok, jnp.isfinite(
            a.astype(jnp.float32)).all())
    return ok.astype(jnp.float32).reshape(1)


@register('multi_lars', differentiable=False)
def multi_lars(lrs, weights_sum_sq, grads_sum_sq, wds, eta=0.001,
               eps=1e-8, rescale_grad=1.0):
    """Per-tensor LARS local learning rates from squared norms
    (reference contrib/multi_lars.cc — pairs with multi_sum_sq)."""
    wn = jnp.sqrt(weights_sum_sq)
    gn = jnp.sqrt(grads_sum_sq) * rescale_grad
    trust = eta * wn / (gn + wds * wn + eps)
    return lrs * jnp.where(jnp.logical_and(wn > 0, gn > 0), trust, 1.0)


@register('sparse_adagrad_update', n_out=2)
def sparse_adagrad_update(weight, grad, history, lr=0.01, epsilon=1e-7,
                          wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """Dense-input form of the reference's row-sparse AdaGrad kernel
    (src/operator/optimizer_op.cc _sparse_adagrad_update). The true
    row-sparse path (update only rows present in the gradient) is the
    optimizer's lazy route — optimizer/__init__.py _update_one_lazy —
    which this op complements for API parity."""
    g = _rescale_clip(grad, rescale_grad, clip_gradient)
    if wd > 0:
        g = g + wd * weight
    h = history + g * g
    return weight - lr * g / (jnp.sqrt(h) + epsilon), h
