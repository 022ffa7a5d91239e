"""Neural-network ops.

Reference: ``src/operator/nn/`` (31 kLoC — activation, batch_norm,
layer/group/instance norm, convolution, deconvolution, fully_connected,
pooling, softmax family, dropout, embedding, upsampling, moments, lrn) and
the fused cudnn paths. TPU design: every op is a composition of XLA HLOs —
convs and FC land on the MXU via ``lax.conv_general_dilated`` / dot_general;
norms and activations are VPU elementwise that XLA fuses into neighbors, so
the cudnn-style monolithic kernels are unnecessary.

Layout: APIs default to the reference's NCHW for compatibility, but every op
takes ``layout=`` and the Gluon layers can run NHWC end-to-end (TPU's
preferred layout; XLA re-lays-out NCHW convs automatically but NHWC avoids
the transposes).
"""

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register


# --------------------------------------------------------------------- linear
@register('fully_connected', aliases=('FullyConnected',))
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    """Reference: src/operator/nn/fully_connected.cc:251.

    weight: (num_hidden, input_dim) as in the reference; one MXU matmul.
    """
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    # contract over the weight's second axis where it lies: ``weight.T``
    # would be a value of its own, and the backward pass would be handed
    # a transposed copy of every weight as a residual of the forward
    out = jnp.einsum('...i,oi->...o', data, weight)
    if bias is not None and not no_bias:
        out = out + bias
    return out


@register('embedding', aliases=('Embedding',))
def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    """Reference: src/operator/tensor/indexing_op.cc Embedding — an XLA
    gather along the vocab axis."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# --------------------------------------------------------------- convolutions
def _conv_dn(ndim, layout):
    if layout is None:
        layout = {1: 'NCW', 2: 'NCHW', 3: 'NCDHW'}[ndim]
    spatial = layout[2:] if layout.startswith('NC') else layout[1:-1]
    if layout.startswith('NC'):
        rhs = 'OI' + spatial
    else:
        rhs = 'OI' + spatial  # weights always OIHW (reference layout)
    return lax.conv_dimension_numbers((1,) * (ndim + 2), (1,) * (ndim + 2),
                                      (layout, rhs, layout)), layout


def _tuplize(v, n):
    if v is None:
        return (0,) * n if isinstance(v, int) else None
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register('convolution', aliases=('Convolution',))
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None):
    """Reference: src/operator/nn/convolution.cc. Grouped + dilated conv in
    one ``lax.conv_general_dilated`` → single MXU op."""
    ndim = data.ndim - 2
    stride = _tuplize(stride, ndim) or (1,) * ndim
    dilate = _tuplize(dilate, ndim) or (1,) * ndim
    pad = _tuplize(pad, ndim) or (0,) * ndim
    dn, layout = _conv_dn(ndim, layout)
    if (weight.shape[2:] == (1,) * ndim and any(s > 1 for s in stride)
            and all(p == 0 for p in pad) and layout.startswith('NC')):
        # A strided 1x1 conv only ever reads the stride-grid positions,
        # so slice first and convolve stride-1.  Forward is identical;
        # the payoff is the VJP: XLA expands the data-gradient of a
        # strided conv into an lhs-dilated conv at FULL resolution
        # (4x the needed FLOPs for stride 2 — 26.3G vs 6.6G per
        # ResNet-50 downsample, ~7% of the whole train step), while the
        # slice's gradient is a cheap scatter and the stride-1 conv's
        # gradient stays at the low resolution.
        idx = (slice(None), slice(None)) + tuple(
            slice(None, None, s) for s in stride)
        data = data[idx]
        stride = (1,) * ndim
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if bias is not None and not no_bias:
        c_axis = layout.index('C')
        bshape = [1] * out.ndim
        bshape[c_axis] = -1
        out = out + bias.reshape(bshape)
    return out


@register('deconvolution', aliases=('Deconvolution',))
def deconvolution(data, weight, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, num_filter=None,
                  num_group=1, no_bias=False, layout=None,
                  target_shape=None):
    """Reference: src/operator/nn/deconvolution.cc (transposed conv)."""
    ndim = data.ndim - 2
    stride = _tuplize(stride, ndim) or (1,) * ndim
    dilate = _tuplize(dilate, ndim) or (1,) * ndim
    pad = _tuplize(pad, ndim) or (0,) * ndim
    adj = _tuplize(adj, ndim) or (0,) * ndim
    dn, layout = _conv_dn(ndim, layout)
    kshape = weight.shape[2:]
    padding = []
    for i in range(ndim):
        k = (kshape[i] - 1) * dilate[i]
        padding.append((k - pad[i], k - pad[i] + adj[i]))
    # transposed conv = lhs-dilated conv with flipped, IO-swapped kernel
    w = jnp.flip(weight, axis=tuple(range(2, 2 + ndim)))
    if num_group > 1:
        # (G*I, O/G, ...) semantics: reshape to keep grouping
        gi, og = weight.shape[0], weight.shape[1]
        w = w.reshape(num_group, gi // num_group, og, *kshape)
        w = jnp.swapaxes(w, 1, 2).reshape(num_group * og, gi // num_group,
                                          *kshape)
    else:
        w = jnp.swapaxes(w, 0, 1)
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * ndim, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        c_axis = layout.index('C')
        bshape = [1] * out.ndim
        bshape[c_axis] = -1
        out = out + bias.reshape(bshape)
    return out


# -------------------------------------------------------------------- pooling
@register('pooling', aliases=('Pooling',))
def pooling(data, kernel=None, pool_type='max', global_pool=False,
            stride=None, pad=None, pooling_convention='valid',
            count_include_pad=True, layout=None):
    """Reference: src/operator/nn/pooling.cc — lax.reduce_window."""
    ndim = data.ndim - 2
    layout = layout or {1: 'NCW', 2: 'NCHW', 3: 'NCDHW'}[ndim]
    sp_axes = [layout.index(c) for c in layout if c not in 'NC']
    if global_pool:
        if pool_type == 'max':
            return jnp.max(data, axis=tuple(sp_axes), keepdims=True)
        return jnp.mean(data, axis=tuple(sp_axes), keepdims=True)
    kernel = _tuplize(kernel, ndim)
    stride = _tuplize(stride, ndim) or (1,) * ndim
    pad = _tuplize(pad, ndim) or (0,) * ndim

    window = [1] * data.ndim
    strides = [1] * data.ndim
    paddings = [(0, 0)] * data.ndim
    for i, ax in enumerate(sp_axes):
        window[ax] = kernel[i]
        strides[ax] = stride[i]
        lo = pad[i]
        hi = pad[i]
        if pooling_convention == 'full':
            size = data.shape[ax] + 2 * pad[i] - kernel[i]
            rem = size % stride[i]
            if rem:
                hi += stride[i] - rem
        paddings[ax] = (lo, hi)

    if pool_type == 'max':
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides,
                                 paddings)
    if pool_type in ('avg', 'sum'):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides,
                                   paddings)
        if pool_type == 'sum':
            return summed
        if count_include_pad:
            denom = _np.prod(kernel)
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides,
                                   paddings)
        return summed / counts
    if pool_type == 'lp':
        p = 2.0
        summed = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window,
                                   strides, paddings)
        return summed ** (1.0 / p)
    raise ValueError(f'unknown pool_type {pool_type}')


@register('adaptive_avg_pooling', aliases=('contrib_AdaptiveAvgPooling2D',))
def adaptive_avg_pooling(data, output_size=1):
    """Reference: src/operator/contrib/adaptive_avg_pooling.cc (NCHW)."""
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    oh, ow = output_size
    n, c, h, w = data.shape
    x = data.reshape(n, c, oh, h // oh, ow, w // ow)
    return x.mean(axis=(3, 5))


# ---------------------------------------------------------------- activations
@register('activation', aliases=('Activation',))
def activation(data, act_type='relu'):
    """Reference: src/operator/nn/activation.cc."""
    if act_type == 'relu':
        return jax.nn.relu(data)
    if act_type == 'sigmoid':
        return jax.nn.sigmoid(data)
    if act_type == 'tanh':
        return jnp.tanh(data)
    if act_type == 'softrelu':
        return jax.nn.softplus(data)
    if act_type == 'softsign':
        return jax.nn.soft_sign(data)
    if act_type == 'log_sigmoid':
        return jax.nn.log_sigmoid(data)
    if act_type == 'mish':
        return data * jnp.tanh(jax.nn.softplus(data))
    raise ValueError(f'unknown act_type {act_type}')


@register('relu')
def relu(x):
    return jax.nn.relu(x)


@register('sigmoid')
def sigmoid(x):
    return jax.nn.sigmoid(x)


@register('softplus')
def softplus(x):
    return jax.nn.softplus(x)


@register('silu', aliases=('swish',))
def silu(x):
    return jax.nn.silu(x)


@register('gelu')
def gelu(x, approximate=True):
    return jax.nn.gelu(x, approximate=approximate)


@register('hard_sigmoid')
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


@register('hard_swish')
def hard_swish(x):
    return x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


@register('leaky_relu', aliases=('LeakyReLU',))
def leaky_relu(data, gamma=None, act_type='leaky', slope=0.25,
               lower_bound=0.125, upper_bound=0.334, key=None):
    """Reference: src/operator/leaky_relu.cc (leaky/prelu/elu/selu/gelu/rrelu)."""
    if act_type == 'leaky':
        return jnp.where(data >= 0, data, slope * data)
    if act_type == 'prelu':
        g = gamma
        if g.ndim < data.ndim:
            shape = [1] * data.ndim
            shape[1] = -1
            g = g.reshape(shape)
        return jnp.where(data >= 0, data, g * data)
    if act_type == 'elu':
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == 'selu':
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == 'gelu':
        return jax.nn.gelu(data, approximate=False)
    if act_type == 'rrelu':
        return jnp.where(data >= 0, data,
                         (lower_bound + upper_bound) / 2.0 * data)
    raise ValueError(f'unknown act_type {act_type}')


# ------------------------------------------------------------------- softmaxes
@register('softmax', aliases=('Softmax',))
def softmax(data, axis=-1, length=None, temperature=None, use_length=False,
            dtype=None):
    """Reference: src/operator/nn/softmax.cc (with optional length masking)."""
    x = data if temperature in (None, 1.0) else data / temperature
    if use_length and length is not None:
        steps = jnp.arange(x.shape[axis])
        bshape = [1] * x.ndim
        bshape[axis] = -1
        mask = steps.reshape(bshape) < jnp.expand_dims(length, axis)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    out = jax.nn.softmax(x, axis=axis)
    return out.astype(dtype) if dtype else out


@register('log_softmax')
def log_softmax(data, axis=-1, temperature=None, dtype=None):
    x = data if temperature in (None, 1.0) else data / temperature
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(dtype) if dtype else out


@register('masked_softmax')
def masked_softmax(data, mask=None, axis=-1, temperature=1.0,
                   normalize=True):
    if mask is None:
        return jax.nn.softmax(data / temperature, axis=axis)
    neg = jnp.finfo(data.dtype).min
    x = jnp.where(mask.astype(bool), data / temperature, neg)
    out = jax.nn.softmax(x, axis=axis)
    return jnp.where(mask.astype(bool), out, 0.0)


@register('masked_log_softmax')
def masked_log_softmax(data, mask=None, axis=-1, temperature=1.0):
    if mask is None:
        return jax.nn.log_softmax(data / temperature, axis=axis)
    neg = jnp.finfo(data.dtype).min
    x = jnp.where(mask.astype(bool), data / temperature, neg)
    return jax.nn.log_softmax(x, axis=axis)


@register('softmax_cross_entropy')
def softmax_cross_entropy(data, label):
    """Reference: src/operator/loss_binary_op.cc softmax_cross_entropy."""
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1],
                        dtype=data.dtype)
    return -jnp.sum(oh * logp)


# ------------------------------------------------------------- normalizations
@register('batch_norm_inference', aliases=('BatchNormInference',))
def batch_norm_inference(x, gamma, beta, moving_mean, moving_var, eps=1e-5,
                         axis=1, fix_gamma=False, use_global_stats=True,
                         scale_shift=True):
    shape = [1] * x.ndim
    shape[axis] = -1
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = lax.rsqrt(moving_var.reshape(shape) + eps)
    return (x - moving_mean.reshape(shape)) * inv * g.reshape(shape) + \
        beta.reshape(shape)


@register('batch_norm_train')
def batch_norm_train(x, gamma, beta, eps=1e-5, axis=1, fix_gamma=False):
    """Training-mode BN: returns (out, batch_mean, batch_var). The layer
    updates running stats from the extra outputs (the reference mutates aux
    states inside the op — src/operator/nn/batch_norm.cc)."""
    red = tuple(i for i in range(x.ndim) if i != axis)
    mean = jnp.mean(x, axis=red)
    var = jnp.var(x, axis=red)
    shape = [1] * x.ndim
    shape[axis] = -1
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = lax.rsqrt(var.reshape(shape) + eps)
    out = (x - mean.reshape(shape)) * inv * g.reshape(shape) + \
        beta.reshape(shape)
    return out, mean, var


def _norm_pallas_cost(eqn):
    """Analytical cost for the fused Pallas norm kernels (mx.analysis.costs).

    The single-pass kernel reads each element once and does O(1) arithmetic
    per element (center/square, rsqrt-scale, affine) — price it at 5 flops
    per output element. Non-pallas equations return None so the generic
    primitive table handles the XLA fallback lowering.
    """
    if eqn.primitive.name != 'pallas_call':
        return None
    out = max((v.aval for v in eqn.outvars), key=lambda a: a.size)
    return 5 * out.size


@register('layer_norm', aliases=('LayerNorm',), f32_only=True,
          fused_kernel=True, cost=_norm_pallas_cost)
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    """Reference: src/operator/nn/layer_norm.cc (hand-fused CUDA kernel).
    Last-axis norms take the Pallas single-HBM-pass kernel on TPU
    (ops/pallas/fused_norms.py, fp32 statistics, custom recompute
    backward); other axes and non-tiling widths use the XLA lowering."""
    if axis in (-1, data.ndim - 1) and gamma.ndim == 1:
        from .pallas.fused_norms import fused_layer_norm
        return fused_layer_norm(data, gamma, beta, eps)
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = -1
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register('group_norm', aliases=('GroupNorm',))
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    """Reference: src/operator/nn/group_norm.cc (NCHW)."""
    n, c = data.shape[0], data.shape[1]
    spatial = data.shape[2:]
    x = data.reshape(n, num_groups, c // num_groups, *spatial)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = [1, c] + [1] * len(spatial)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register('instance_norm', aliases=('InstanceNorm',))
def instance_norm(data, gamma, beta, eps=1e-5):
    """Reference: src/operator/instance_norm.cc (NC...)."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1, -1] + [1] * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register('l2_normalization', aliases=('L2Normalization',))
def l2_normalization(data, eps=1e-10, mode='instance'):
    """Reference: src/operator/l2_normalization.cc."""
    if mode == 'instance':
        red = tuple(range(1, data.ndim))
        keep = True
    elif mode == 'channel':
        red = (1,)
        keep = True
    elif mode == 'spatial':
        red = tuple(range(2, data.ndim))
        keep = True
    else:
        raise ValueError(mode)
    norm = jnp.sqrt(jnp.sum(data * data, axis=red, keepdims=keep) + eps)
    return data / norm


@register('lrn', aliases=('LRN',))
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Reference: src/operator/nn/lrn.cc (cross-channel, NCHW)."""
    sq = data * data
    half = nsize // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2)
    sqp = jnp.pad(sq, pad)
    window = [1, nsize] + [1] * (data.ndim - 2)
    ssum = lax.reduce_window(sqp, 0.0, lax.add, window, [1] * data.ndim,
                             [(0, 0)] * data.ndim)
    return data / (knorm + alpha / nsize * ssum) ** beta


@register('moments', n_out=2)
def moments(data, axes=None, keepdims=False):
    """Reference: src/operator/nn/moments.cc."""
    mean = jnp.mean(data, axis=axes, keepdims=keepdims)
    var = jnp.var(data, axis=axes, keepdims=keepdims)
    return mean, var


@register('rms_norm', f32_only=True, fused_kernel=True,
          cost=_norm_pallas_cost)
def rms_norm(data, gamma, axis=-1, eps=1e-6):
    """New (no reference analog): RMSNorm for the LLM stack. Last-axis
    case takes the Pallas single-pass kernel (ops/pallas/fused_norms.py)."""
    if axis in (-1, data.ndim - 1) and gamma.ndim == 1:
        from .pallas.fused_norms import fused_rms_norm
        return fused_rms_norm(data, gamma, eps)
    ms = jnp.mean(jnp.square(data), axis=axis, keepdims=True)
    out = data * lax.rsqrt(ms + eps)
    shape = [1] * data.ndim
    shape[axis] = -1
    return out * gamma.reshape(shape)


# -------------------------------------------------------------------- dropout
@register('dropout', aliases=('Dropout',), stochastic=True)
def dropout(data, p=0.5, mode='training', axes=(), key=None, training=True):
    """Reference: src/operator/nn/dropout.cc. The PRNG key is injected by
    dispatch (resource model); under hybridize it becomes a traced input."""
    if not training or p <= 0:
        return data
    shape = list(data.shape)
    for ax in axes:
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


# -------------------------------------------------------- resize / upsampling
@register('upsampling', aliases=('UpSampling',))
def upsampling(data, scale=2, sample_type='nearest'):
    """Reference: src/operator/nn/upsampling.cc (NCHW nearest)."""
    n, c, h, w = data.shape
    if sample_type == 'nearest':
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    return jax.image.resize(data, (n, c, h * scale, w * scale), 'bilinear')


@register('interp_resize', aliases=('contrib_BilinearResize2D',))
def interp_resize(data, height=None, width=None, scale_height=None,
                  scale_width=None, mode='bilinear', align_corners=False):
    n, c, h, w = data.shape
    oh = height or int(h * scale_height)
    ow = width or int(w * scale_width)
    method = 'linear' if mode in ('bilinear', 'linear') else mode
    return jax.image.resize(data, (n, c, oh, ow), method)


# ---------------------------------------------------------------- misc neural
@register('topk_accuracy_helper', differentiable=False)
def topk_accuracy_helper(pred, label, k=1):
    idx = lax.top_k(pred, k)[1]
    return jnp.any(idx == label[..., None].astype(idx.dtype), axis=-1)


@register('ctc_loss', aliases=('CTCLoss',))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             blank_label='first'):
    """Reference: src/operator/nn/ctc_loss.cc (wraps warp-ctc / cudnn).

    Forward-algorithm CTC in log space via ``lax.scan`` over time — XLA
    compiles the scan into a single fused loop on TPU.
    data: (seq_len, batch, alphabet); label: (batch, label_len), 0-padded
    (blank_label='first': blank id 0, labels shifted by +1 as in reference).
    """
    T, B, A = data.shape
    L = label.shape[1]
    blank = 0 if blank_label == 'first' else A - 1
    labels = label.astype(jnp.int32)
    if blank_label == 'first':
        pass  # labels already 1-based with 0 = padding
    logp = jax.nn.log_softmax(data, axis=-1)

    # expanded label sequence with interleaved blanks: length 2L+1
    S = 2 * L + 1
    positions = jnp.arange(S)
    lab_idx = jnp.where(positions % 2 == 1, positions // 2, 0)
    ext = jnp.where((positions % 2 == 1)[None, :],
                    jnp.take_along_axis(labels, lab_idx[None, :].repeat(B, 0),
                                        axis=1), blank)
    if label_lengths is None:
        label_lengths = jnp.sum(labels != 0, axis=1)
    if data_lengths is None:
        data_lengths = jnp.full((B,), T)
    seq_s = 2 * label_lengths + 1

    NEG = -1e30
    alpha0 = jnp.full((B, S), NEG)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    first_lab = ext[:, 1]
    alpha0 = alpha0.at[:, 1].set(
        jnp.take_along_axis(logp[0], first_lab[:, None], axis=1)[:, 0])

    same_as_prev2 = jnp.concatenate(
        [jnp.zeros((B, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)
    is_blank = ext == blank

    def step(alpha, lp_t):
        shift1 = jnp.concatenate([jnp.full((B, 1), NEG), alpha[:, :-1]],
                                 axis=1)
        shift2 = jnp.concatenate([jnp.full((B, 2), NEG), alpha[:, :-2]],
                                 axis=1)
        allow2 = ~(is_blank | same_as_prev2)
        m = jnp.maximum(alpha, shift1)
        m = jnp.where(allow2, jnp.maximum(m, shift2), m)
        # mask INSIDE the exp argument: where disallowed, shift2 may exceed
        # m and exp(shift2-m) would be inf — where(False, inf, 0) has a
        # 0·inf = NaN gradient (the classic masked-softmax trap)
        acc = jnp.exp(alpha - m) + jnp.exp(shift1 - m) + \
            jnp.exp(jnp.where(allow2, shift2 - m, NEG))
        new = m + jnp.log(jnp.maximum(acc, 1e-37))
        emit = jnp.take_along_axis(lp_t, ext, axis=1)
        return new + emit, new + emit

    _, alphas = lax.scan(step, alpha0, logp[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T,B,S)

    t_idx = (data_lengths - 1).astype(jnp.int32)
    final = alphas[t_idx, jnp.arange(B)]  # (B, S)
    last = jnp.take_along_axis(final, (seq_s - 1)[:, None], axis=1)[:, 0]
    last2 = jnp.take_along_axis(final, (seq_s - 2)[:, None], axis=1)[:, 0]
    m = jnp.maximum(last, last2)
    ll = m + jnp.log(jnp.exp(last - m) + jnp.exp(last2 - m))
    return -ll


# ------------------------------------------------------------------ fused rnn

def _rnn_gates(mode):
    return {'rnn_relu': 1, 'rnn_tanh': 1, 'lstm': 4, 'gru': 3}[mode]


def _rnn_unpack(parameters, mode, input_size, state_size, num_layers, dirs):
    """Unpack the cuDNN-canonical flat parameter vector.

    Layout matches the reference's fused RNN op (src/operator/rnn-inl.h
    GetRnnParamSize / cuDNN canonical order): all weights first — per layer,
    per direction: i2h (G*H, I_l) then h2h (G*H, H) — then all biases in the
    same order (b_i2h, b_h2h each G*H). Gate order: LSTM [i, f, g, o],
    GRU [r, z, n] (cuDNN order, as the reference's kernels use).
    """
    G, H = _rnn_gates(mode), state_size
    ws, bs, off = [], [], 0
    for layer in range(num_layers):
        il = input_size if layer == 0 else state_size * dirs
        for _ in range(dirs):
            wi = parameters[off:off + G * H * il].reshape(G * H, il)
            off += G * H * il
            wh = parameters[off:off + G * H * H].reshape(G * H, H)
            off += G * H * H
            ws.append((wi, wh))
    for _ in range(num_layers * dirs):
        bi = parameters[off:off + G * H]
        off += G * H
        bh = parameters[off:off + G * H]
        off += G * H
        bs.append((bi, bh))
    return ws, bs


def _rnn_layer_scan(mode, x, h0, c0, wi, wh, bi, bh, reverse):
    """One direction of one layer. x: (T, B, I). Returns (T, B, H), hT, cT.

    The input projection for the whole sequence is one big MXU matmul
    (T*B, I)·(I, G*H); the scan carries only the (B, H) recurrence.
    """
    H = h0.shape[-1]

    if mode in ('rnn_relu', 'rnn_tanh'):
        xg = jnp.einsum('tbi,gi->tbg', x, wi) + bi + bh  # (T, B, G*H)
        act = jax.nn.relu if mode == 'rnn_relu' else jnp.tanh

        def step(h, xg_t):
            h = act(xg_t + h @ wh.T)
            return h, h

        hT, ys = lax.scan(step, h0, xg, reverse=reverse)
        return ys, hT, None

    if mode == 'lstm':
        xg = jnp.einsum('tbi,gi->tbg', x, wi) + bi + bh  # (T, B, G*H)

        def step(carry, xg_t):
            h, c = carry
            g = xg_t + h @ wh.T
            i, f, gg, o = jnp.split(g, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(gg)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        (hT, cT), ys = lax.scan(step, (h0, c0), xg, reverse=reverse)
        return ys, hT, cT

    # gru — cuDNN formulation: n = tanh(x_n + b_n + r * (h @ Whn + bhn));
    # the h2h part of the n gate is gated by r *before* adding the input
    # part, so recompute it inside the scan from the raw recurrence.
    wir, wiz, win = jnp.split(wi, 3, axis=0)
    whr, whz, whn = jnp.split(wh, 3, axis=0)
    bir, biz, bin_ = jnp.split(bi, 3)
    bhr, bhz, bhn = jnp.split(bh, 3)
    xr = jnp.einsum('tbi,gi->tbg', x, wir) + bir
    xz = jnp.einsum('tbi,gi->tbg', x, wiz) + biz
    xn = jnp.einsum('tbi,gi->tbg', x, win) + bin_
    xg = jnp.concatenate([xr, xz, xn], axis=-1)

    def step(h, xg_t):
        xr_t, xz_t, xn_t = jnp.split(xg_t, 3, axis=-1)
        r = jax.nn.sigmoid(xr_t + h @ whr.T + bhr)
        z = jax.nn.sigmoid(xz_t + h @ whz.T + bhz)
        n = jnp.tanh(xn_t + r * (h @ whn.T + bhn))
        h = (1 - z) * n + z * h
        return h, h

    hT, ys = lax.scan(step, h0, xg, reverse=reverse)
    return ys, hT, None


def _rnn_n_out(args, kw):
    mode = kw.get('mode', 'lstm')
    if not kw.get('state_outputs', False):
        return 1
    return 3 if mode == 'lstm' else 2


@register('rnn', aliases=('RNN',), n_out=_rnn_n_out)
def rnn(data, parameters, state, state_cell=None, mode='lstm',
        state_size=None, num_layers=1, bidirectional=False, p=0.0,
        state_outputs=False, key=None):
    """Fused multi-layer (bi)directional RNN/LSTM/GRU.

    Reference: src/operator/rnn.cc (`_npx_rnn`, cuDNN fused kernels +
    native rnn-inl.h). TPU design: per layer, the input projection is one
    batched MXU matmul over the whole sequence; only the (B, H) recurrence
    lives in a ``lax.scan``, which XLA compiles to a single fused loop.

    data: (T, B, I); state: (L*dirs, B, H); state_cell (lstm): same.
    Returns output (T, B, H*dirs) [+ hy (+ cy) if state_outputs].
    Inter-layer dropout ``p`` applies between layers in training graphs when
    a PRNG ``key`` is supplied (the op is registered non-stochastic so eager
    inference stays deterministic; Gluon passes the key when training).
    """
    dirs = 2 if bidirectional else 1
    T, B, I = data.shape
    H = state_size if state_size is not None else state.shape[-1]
    ws, bs = _rnn_unpack(parameters, mode, I, H, num_layers, dirs)

    x = data
    hys, cys = [], []
    for layer in range(num_layers):
        outs = []
        for d in range(dirs):
            idx = layer * dirs + d
            wi, wh = ws[idx]
            bi, bh = bs[idx]
            h0 = state[idx]
            c0 = state_cell[idx] if state_cell is not None else None
            ys, hT, cT = _rnn_layer_scan(mode, x, h0, c0, wi, wh, bi, bh,
                                         reverse=(d == 1))
            outs.append(ys)
            hys.append(hT)
            if cT is not None:
                cys.append(cT)
        x = outs[0] if dirs == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0.0 and key is not None and layer < num_layers - 1:
            sub = jax.random.fold_in(key, layer)
            keep = jax.random.bernoulli(sub, 1.0 - p, x.shape)
            x = jnp.where(keep, x / (1.0 - p), 0.0)

    if not state_outputs:
        return x
    hy = jnp.stack(hys)
    if mode == 'lstm':
        return x, hy, jnp.stack(cys)
    return x, hy


# ------------------------------------------------------------- im2col/col2im

def _im2col_raw(data, kernel, stride, dilate, pad):
    nd = len(kernel)
    stride = stride or (1,) * nd
    dilate = dilate or (1,) * nd
    pad = pad or (0,) * nd
    padding = [(p, p) for p in pad]
    patches = lax.conv_general_dilated_patches(
        data, filter_shape=kernel, window_strides=stride, padding=padding,
        rhs_dilation=dilate)
    # (N, C*prod(kernel), *out_spatial), channel-major — same row order as
    # the reference's im2col (src/operator/nn/im2col.h)
    n, ck = patches.shape[:2]
    return patches.reshape(n, ck, -1)


@register('im2col')
def im2col(data, kernel=None, stride=None, dilate=None, pad=None):
    """Reference: src/operator/nn/im2col.h (_npx_im2col). data: (N, C, *S)
    → (N, C*prod(kernel), prod(out_spatial))."""
    kernel = tuple(kernel)
    return _im2col_raw(data, kernel, stride and tuple(stride),
                       dilate and tuple(dilate), pad and tuple(pad))


@register('col2im')
def col2im(data, output_size=None, kernel=None, stride=None, dilate=None,
           pad=None):
    """Adjoint of im2col (reference src/operator/nn/im2col.h col2im):
    overlapping patches sum back into the image. Implemented as the linear
    transpose of ``im2col`` — XLA turns it into the same gather/scatter it
    uses for conv input gradients."""
    kernel = tuple(kernel)
    output_size = tuple(output_size)
    n = data.shape[0]
    c = data.shape[1] // int(_np.prod(kernel))
    img_shape = (n, c) + output_size
    zero = jnp.zeros(img_shape, data.dtype)
    _, vjp = jax.vjp(
        lambda x: _im2col_raw(x, kernel, stride and tuple(stride),
                              dilate and tuple(dilate), pad and tuple(pad)),
        zero)
    return vjp(data)[0]


@register('softmin')
def softmin(data, axis=-1, length=None, temperature=None, use_length=False,
            dtype=None):
    """Reference: src/operator/nn/softmax.cc softmin — softmax of -x,
    sharing softmax's length-masking path (same SoftmaxParam)."""
    return softmax(-data, axis=axis, length=length, temperature=temperature,
                   use_length=use_length, dtype=dtype)
