"""INT8 post-training quantization (PTQ).

Reference: ``src/operator/quantization/`` — ``quantize_v2.cc`` /
``dequantize.cc`` / ``requantize.cc`` kernels, histogram calibration with
naive/entropy(KL) modes (``calibrate.cc``), and the ``QuantizeGraph`` pass
that rewrites the graph around quantizable nodes
(``quantize_graph_pass.cc:580``). The reference lowers to MKLDNN/cuDNN int8
kernels; the TPU design lowers to XLA int8 ``dot_general``/conv with
``preferred_element_type=int32`` — the MXU's native int8 path — and keeps
layer outputs in float (the reference's ``enable_float_output`` variant), so
only layer *inputs* need calibrated ranges and there is no int8 graph
plumbing between layers.

Scheme: symmetric, per-tensor. scale = max(|min|,|max|) / 127; zero-point 0.
"""

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from .gluon.block import HybridBlock
from .gluon.parameter import Parameter
from .ndarray.ndarray import NDArray
from .ops.quantization_ops import (quantize_v2, dequantize, requantize,
                                   range_to_scale)

__all__ = ['quantize_v2', 'dequantize', 'requantize', 'quantize_net',
           'calib_table', 'QuantizedDense', 'QuantizedConv2D']


# ------------------------------------------------------------ calibration
class _HistogramCollector:
    """Per-layer input min/max + histogram (reference calibrate.cc's
    LayerOutputMinMaxCollector / HistogramCollector)."""

    def __init__(self, num_bins=2048):
        self.num_bins = num_bins
        self.min = None
        self.max = None
        self.hist = None
        self.edges = None

    def collect(self, arr):
        a = _np.asarray(arr, dtype=_np.float32).ravel()
        lo, hi = float(a.min()), float(a.max())
        self.min = lo if self.min is None else min(self.min, lo)
        self.max = hi if self.max is None else max(self.max, hi)
        amax = max(abs(self.min), abs(self.max)) or 1.0
        hist, edges = _np.histogram(a, bins=self.num_bins,
                                    range=(-amax, amax))
        if self.hist is None or len(self.hist) != len(hist) or \
                self.edges[-1] != edges[-1]:
            # range grew: rebuild by re-binning the old histogram midpoints
            if self.hist is not None:
                mids = (self.edges[:-1] + self.edges[1:]) / 2
                old, _ = _np.histogram(mids, bins=self.num_bins,
                                       range=(-amax, amax),
                                       weights=self.hist)
                hist = hist + old.astype(hist.dtype)
            self.edges = edges
        else:
            hist = hist + self.hist
        self.hist = hist

    # threshold selection -------------------------------------------------
    def naive(self):
        return self.min, self.max

    def percentile(self, p=99.99):
        total = self.hist.sum()
        target = total * (p / 100.0)
        c = _np.cumsum(self.hist)
        # symmetric: walk outward from the center until p% mass is covered
        center = self.num_bins // 2
        for w in range(1, center + 1):
            covered = c[min(center + w, self.num_bins - 1)] - \
                (c[center - w - 1] if center - w - 1 >= 0 else 0)
            if covered >= target:
                # covered mass extends through the UPPER edge of bin
                # center+w, i.e. edges[center+w+1]
                t = float(self.edges[min(center + w + 1, self.num_bins)])
                return -t, t
        return self.min, self.max

    def entropy(self, num_quantized_bins=255):
        """KL-divergence threshold search (reference calibrate.cc — the
        TensorRT algorithm: pick the clip threshold whose quantized
        distribution diverges least from the clipped reference)."""
        hist = self.hist.astype(_np.float64)
        total = hist.sum()
        if total == 0:
            return self.min, self.max
        p_full = hist / total
        edges = self.edges
        center = self.num_bins // 2
        eps = 1e-10
        best_t, best_kl = max(abs(self.min), abs(self.max)), _np.inf
        # KL is measured against the FULL distribution, with the window's
        # reconstruction saturating clipped mass onto the edge bins — so
        # clipping genuinely costs divergence (a window whose 2w bins
        # quantize losslessly does not get a free KL=0).
        for w in range(center, num_quantized_bins // 2 - 1,
                       -max(center // 64, 1)):
            lo_i, hi_i = center - w, center + w
            window = hist[lo_i:hi_i]
            if window.sum() == 0:
                continue
            factor = len(window) / num_quantized_bins
            recon = _np.zeros_like(window)
            for i in range(num_quantized_bins):
                s = int(i * factor)
                e = max(int((i + 1) * factor), s + 1)
                chunk = window[s:e]
                nz = (chunk > 0).sum()
                if nz:
                    recon[s:e] = _np.where(chunk > 0, chunk.sum() / nz, 0)
            q = _np.zeros_like(hist)
            q[lo_i:hi_i] = recon
            q[lo_i] += hist[:lo_i].sum()     # saturation
            q[hi_i - 1] += hist[hi_i:].sum()
            q = q / q.sum()
            mask = p_full > 0
            kl = float(_np.sum(p_full[mask] * _np.log(
                p_full[mask] / _np.maximum(q[mask], eps))))
            if kl < best_kl:
                best_kl = kl
                best_t = float(edges[hi_i] if hi_i < len(edges) else
                               edges[-1])
        return -best_t, best_t


def calib_table(collectors, mode='entropy'):
    """collectors: {layer_name: _HistogramCollector} → {name: (min, max)}.
    Layers never exercised by the calibration data are omitted.
    Reference: SetCalibTableToQuantizedGraph (quantize_graph_pass.cc)."""
    if mode not in ('naive', 'percentile', 'entropy'):
        raise ValueError(f'unknown calib_mode {mode!r}; expected '
                         "'naive', 'percentile' or 'entropy'")
    table = {}
    for name, c in collectors.items():
        if c.hist is None:
            continue
        if mode == 'naive':
            table[name] = c.naive()
        elif mode == 'percentile':
            table[name] = c.percentile()
        else:
            table[name] = c.entropy()
    return table


# ------------------------------------------------------- quantized layers
class _QuantizedLayer(HybridBlock):
    """Shared int8 state: quantized weight + scales + input calib range.

    The dequantize lives in the matmul epilogue (ops/quantization_ops.py
    ``quantized_dense`` / ``quantized_conv2d``): int32 accumulator →
    per-channel scale → bias → activation-dtype downcast inside one
    fused kernel/region, so the historical ``unfused-dequant``
    suppression this class carried is gone — the lint passes by
    construction (docs/kernels.md)."""

    def __init__(self, float_layer, in_min, in_max,
                 activation_dtype='bfloat16', **kwargs):
        super().__init__(**kwargs)
        # inter-layer activations leave in this dtype: bf16 halves the
        # HBM bytes between layers vs f32 — on a bandwidth-bound device
        # an f32-activation int8 net is SLOWER than the bf16 float net
        # (shown on an earlier development device at 62.5 GB/s; not
        # measured on the v5e); the int32->float
        # rescale still happens in f32 before the downcast
        self._act_dtype = jnp.dtype(activation_dtype)
        w = float_layer.weight.data()._data.astype(jnp.float32)
        # per-output-channel symmetric scales (axis 0 is out-channels
        # for both Dense (O, I) and Conv OIHW): finer than the old
        # per-tensor scale, and free now that the scale multiply rides
        # the matmul epilogue as a (O,) vector instead of a scalar
        red = tuple(range(1, w.ndim))
        amax = jnp.max(jnp.abs(w), axis=red) if red else jnp.abs(w)
        self._w_scale = jnp.where(amax > 0, amax / 127.0,
                                  1.0).astype(jnp.float32)      # (O,)
        cshape = (-1,) + (1,) * (w.ndim - 1)
        qw = jnp.clip(jnp.round(w / self._w_scale.reshape(cshape)),
                      -127, 127).astype(jnp.int8)
        qw = _np.asarray(qw, dtype=_np.int8)
        self.qweight = Parameter('qweight', shape=qw.shape, dtype='int8',
                                 grad_req='null')
        self.qweight.initialize(init='zeros')
        self.qweight.set_data(NDArray(jnp.asarray(qw)))
        self._has_bias = getattr(float_layer, 'bias', None) is not None and \
            getattr(float_layer, '_use_bias', True)
        if self._has_bias:
            self.bias = Parameter('bias', shape=float_layer.bias.shape,
                                  grad_req='null')
            self.bias.initialize(init='zeros')
            self.bias.set_data(float_layer.bias.data())
        self._x_scale = float(range_to_scale(in_min, in_max))
        self.collected_range = (in_min, in_max)

    def _quantize_input(self, x):
        xr = x._data if isinstance(x, NDArray) else x
        q, _, _ = quantize_v2(xr.astype(jnp.float32), *self.collected_range)
        return q


class QuantizedDense(_QuantizedLayer):
    """int8 FullyConnected (reference quantized_fully_connected.cc):
    int8 × int8 → int32 on the MXU, one float rescale out."""

    def __init__(self, float_layer, in_min, in_max, **kwargs):
        super().__init__(float_layer, in_min, in_max, **kwargs)
        self._flatten = float_layer._flatten
        self.act = float_layer.act

    def forward(self, x):
        from .ops.quantization_ops import quantized_dense
        q = self._quantize_input(x)
        if self._flatten and q.ndim > 2:
            q = q.reshape(q.shape[0], -1)
        qw = self.qweight.data()._data
        out = quantized_dense(
            q, qw, self._x_scale * self._w_scale,
            self.bias.data()._data if self._has_bias else None,
            out_dtype=self._act_dtype)
        out = NDArray(out)
        if self.act is not None:
            out = self.act(out)
        return out


class QuantizedConv2D(_QuantizedLayer):
    """int8 Convolution (reference quantized_conv.cc)."""

    def __init__(self, float_layer, in_min, in_max, **kwargs):
        super().__init__(float_layer, in_min, in_max, **kwargs)
        self._stride = float_layer._strides
        self._pad = float_layer._padding
        self._dilate = float_layer._dilation
        self._groups = float_layer._groups
        self._layout = float_layer._layout or 'NCHW'
        self.act = float_layer.act

    def forward(self, x):
        from .ops.quantization_ops import quantized_conv2d
        q = self._quantize_input(x)
        qw = self.qweight.data()._data
        stride = self._stride if isinstance(self._stride, tuple) else \
            (self._stride,) * 2
        pad = self._pad if isinstance(self._pad, tuple) else (self._pad,) * 2
        dil = self._dilate if isinstance(self._dilate, tuple) else \
            (self._dilate,) * 2
        out = quantized_conv2d(
            q, qw, self._x_scale * self._w_scale,
            self.bias.data()._data if self._has_bias else None,
            out_dtype=self._act_dtype, strides=stride, padding=pad,
            dilation=dil, groups=self._groups, layout=self._layout)
        out = NDArray(out)
        if self.act is not None:
            out = self.act(out)
        return out


# --------------------------------------------------------- graph rewrite
def _quantizable(block):
    from .gluon.nn.basic_layers import Dense
    from .gluon.nn.conv_layers import Conv2D
    if isinstance(block, Dense):
        return QuantizedDense
    if isinstance(block, Conv2D):
        return QuantizedConv2D
    return None


def _walk(block, prefix=''):
    for name, child in list(block._children.items()):
        path = f'{prefix}{name}'
        yield block, name, path, child
        yield from _walk(child, path + '.')


def quantize_net(net, calib_data=None, calib_mode='entropy',
                 quantized_dtype='int8', exclude_layers=None,
                 num_calib_batches=None, logger=None,
                 activation_dtype='bfloat16'):
    """Quantize a trained network for int8 inference.

    The reference flow (quantize_graph_pass.cc + calibrate.cc): insert
    quantize/dequantize around quantizable nodes, run calibration batches,
    set the calib table. Here: run ``calib_data`` through the float net with
    input-collecting hooks, derive per-layer ranges by ``calib_mode``
    ('naive' | 'percentile' | 'entropy'), then swap each quantizable child
    (Dense/Conv2D) for its int8 twin. Children are swapped in place; if the
    net ITSELF is a quantizable layer its int8 twin is the return value —
    always use the returned block. Hybridization is cleared (compiled caches
    would keep serving the float graph); re-hybridize afterwards.
    """
    assert quantized_dtype == 'int8', 'TPU MXU int8 path only'
    if calib_data is None:
        raise ValueError('calib_data is required for post-training '
                         'quantization')
    exclude_layers = set(exclude_layers or ())

    # Compiled caches bypass child hooks and would keep executing the float
    # graph after the swap — calibrate and rewrite in eager mode. The caller
    # re-hybridizes the quantized net afterwards.
    if isinstance(net, HybridBlock) or hasattr(net, 'hybridize'):
        net.hybridize(False)

    root_cls = _quantizable(net)
    targets = [(parent, name, path, child)
               for parent, name, path, child in _walk(net)
               if _quantizable(child) and path not in exclude_layers]
    if root_cls is not None and '.' not in exclude_layers:
        targets.append((None, None, '.', net))  # the net IS the layer
    if not targets:
        return net

    collectors = {path: _HistogramCollector()
                  for _, _, path, _ in targets}
    handles = []

    def make_hook(path):
        def hook(block, inputs):
            x = inputs[0]
            collectors[path].collect(
                x.asnumpy() if isinstance(x, NDArray) else x)
        return hook

    try:
        for _, _, path, child in targets:
            hook = make_hook(path)
            child._forward_pre_hooks.append(hook)
            handles.append((child, hook))
        n = 0
        for batch in calib_data:
            x = batch[0] if isinstance(batch, (tuple, list)) else batch
            net(x if isinstance(x, NDArray) else NDArray(jnp.asarray(x)))
            n += 1
            if num_calib_batches is not None and n >= num_calib_batches:
                break
    finally:
        for child, hook in handles:
            child._forward_pre_hooks.remove(hook)

    table = calib_table(collectors, calib_mode)
    result = net
    for parent, name, path, child in targets:
        if path not in table:
            # layer never saw calibration data (e.g. a disabled branch):
            # leave it in float
            if logger:
                logger.warning('layer %s not exercised by calib_data; '
                               'kept in float', path)
            continue
        lo, hi = table[path]
        qlayer = _quantizable(child)(child, lo, hi,
                                     activation_dtype=activation_dtype)
        if parent is None:
            result = qlayer  # root swap happens via the return value
            continue
        parent._children[name] = qlayer
        # attribute access must resolve to the new child too
        for attr, value in list(parent.__dict__.items()):
            if value is child:
                parent.__dict__[attr] = qlayer
    if logger:
        for path, (lo, hi) in table.items():
            logger.info('calibrated %s: [%.5f, %.5f]', path, lo, hi)
    return result
